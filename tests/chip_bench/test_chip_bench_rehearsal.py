"""The chip benchmark's control flow, rehearsed on the CPU at toy sizes, and
the rules ``BENCHMARK.json`` is held to.

Every cell of ``BENCHMARK.json`` runs through ``run.py``'s ``main`` with
``--rehearse`` (thumbnail ResNet-18, a d64 decoder, the virtual CPU mesh of
``tests/conftest.py``), untraced and traced; the last line is held to the
contract's keys; and a configuration, a traffic mix, a cell and a reader
dropped as new files into a copy are found with no edit to a file that was
there.  Nothing here is a speed: a rehearsal's result says so and carries no
value.

The rules are checks of a *spec* and run twice: on the repository's
``BENCHMARK.json`` and on the same with a later PR's cell, configuration and
per-layer metrics appended (``with_later_entries``).  None of them reads the
order of a list or compares one with "all cells", so a PR that adds a cell
adds no edit here.  What the four cells of PR 24 print is held name for name
by ``PARENT`` below, which names those four cells and no rule; the fifth
cell, ``opt6b7_fused_adam``, is held to having come the way a later PR's
will: by entries of its own and no edit to a list.
"""
import copy
import json
import pathlib
import re
import shutil

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def with_later_entries(spec):
    """``spec`` as a later PR leaves it: one more configuration, one more
    cell, a per-layer metric with a reader of its own, and a per-layer and
    an end-to-end metric that the harness already reads
    (``<metric>.<anything>`` is read as ``<metric>``), each appended and
    listing the later cell alone."""
    spec = copy.deepcopy(spec)
    spec["configs"].append({
        "name": "later_lm", "source": "a later PR", "reduced": [],
        "file": "benchmark/chip/configs/later_lm.json", "why": "test"})
    spec["workloads"].append({
        "name": "later_cell", "config": "later_lm", "traffic": "later_mix",
        "chips": 1, "why": "test"})
    for name, unit, source in (
            ("later_steps", "count", "program_counter"),
            ("dispatch_span_ms_p50.later", "ms", "program_span")):
        spec["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "train step", "moves": "samples_per_s_per_chip",
            "workloads": ["later_cell"]})
    spec["end_to_end"].append({
        "name": "peak_hbm_gb.later", "unit": "GB", "better": "lower",
        "bound": 0.01, "source": "host_clock", "workloads": ["later_cell"]})
    return spec


SPECS = {"repo": SPEC, "with_a_later_cell": with_later_entries(SPEC)}


@pytest.fixture(params=sorted(SPECS))
def spec(request):
    return SPECS[request.param]


def _cells(spec):
    return [w["name"] for w in spec["workloads"]]


def _reported_in(metric, cell):
    return cell in metric.get("workloads", [cell])


# ---------------------------------------------------------------------------
# the rules, of any spec
# ---------------------------------------------------------------------------

def test_spec_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark/chip", "tests/chip_bench"]
    assert 1 <= spec["run_seconds"] <= 51
    metrics = spec["end_to_end"] + spec["per_layer"]
    for group in (spec["configs"], spec["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    assert len(json.dumps(spec, indent=1)) <= 64 * 1024


def test_spec_configs_and_cells(spec):
    configs = {c["name"] for c in spec["configs"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/chip/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    # every configuration is some cell's
    assert configs == {w["config"] for w in spec["workloads"]}


def test_spec_metrics(spec):
    cells = set(_cells(spec))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        # every listed name is a cell, and a list is never empty
        assert "workloads" not in m or (
            m["workloads"] and set(m["workloads"]) <= cells), m["name"]
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 0 < len(m["layer"]) <= 200 and "\n" not in m["layer"]
        # a per-layer metric is reported only where the metric it moves is;
        # one without a list has to be reported wherever that one is
        moved = e2e[m["moves"]]
        reported = {c for c in cells if _reported_in(m, c)}
        assert reported <= {c for c in cells if _reported_in(moved, c)}, \
            m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_spec_every_cell_reports(spec):
    for cell in _cells(spec):
        reported = [m["name"] for m in spec["end_to_end"]
                    if _reported_in(m, cell)]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(_reported_in(m, cell) for m in spec["per_layer"]), cell


# What section 3 of PERF.md says of each per-layer metric: its layer, where
# the number comes from, the end-to-end metric it should move.  ``EVERY
# CELL`` marks the metrics a cell reports because it is a cell (they carry
# no list); the others are read where a reader finds its evidence and list
# those cells, because the driver refuses a traced line that lacks a metric
# without a list.
EVERY_CELL = object()
THROUGHPUT, SETUP = "samples_per_s_per_chip", "setup_s"
SECTION_3 = {
    "setup_init_s": ("model code", "host_clock", SETUP, EVERY_CELL),
    "setup_compile_s": ("compile layer", "program_counter", SETUP,
                        EVERY_CELL),
    "setup_cache_hit_pct": ("compile layer", "program_counter", SETUP,
                            EVERY_CELL),
    "window_compiles": ("compile layer", "program_counter", THROUGHPUT,
                        EVERY_CELL),
    "step_ms_p50": ("train step", "host_clock", THROUGHPUT, EVERY_CELL),
    "step_ms_p90": ("train step", "host_clock", THROUGHPUT, EVERY_CELL),
    "step_host_ms_p50": ("train step", "host_clock", THROUGHPUT, EVERY_CELL),
    "window_lost_pct": ("train step", "host_clock", THROUGHPUT, EVERY_CELL),
    "median_block_samples_per_s": ("train step", "host_clock", THROUGHPUT,
                                   EVERY_CELL),
    "device_programs_per_step": ("train step", "device_trace", THROUGHPUT,
                                 EVERY_CELL),
    "fwd_device_ms_per_step": ("train step", "device_trace", THROUGHPUT,
                               EVERY_CELL),
    "bwd_device_ms_per_step": ("train step", "device_trace", THROUGHPUT,
                               EVERY_CELL),
    "update_device_ms_per_step": ("train step", "device_trace", THROUGHPUT,
                                  EVERY_CELL),
    "device_idle_pct": ("device", "device_trace", THROUGHPUT, EVERY_CELL),
    "mfu_pct": ("device", "host_clock", THROUGHPUT, EVERY_CELL),
    "device_unattributed_pct": ("device", "device_trace", THROUGHPUT,
                                EVERY_CELL),
    "place_span_ms_p50": ("train step", "program_span", THROUGHPUT, None),
    "dispatch_span_ms_p50": ("train step", "program_span", THROUGHPUT, None),
    "fwd_host_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "bwd_host_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "update_host_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "fwd_span_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "bwd_span_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "kvstore_span_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "update_span_ms_p50": ("gluon loop", "program_span", THROUGHPUT, None),
    "engine_flushes_per_step": ("gluon loop", "program_counter", THROUGHPUT,
                                None),
    "flash_fwd_ms_per_step": ("pallas kernel", "device_trace", THROUGHPUT,
                              None),
    "flash_fwd_roofline": ("pallas kernel", "device_trace", THROUGHPUT, None),
    "flash_bwd_ms_per_step": ("pallas kernel", "device_trace", THROUGHPUT,
                              None),
    "flash_bwd_roofline": ("pallas kernel", "device_trace", THROUGHPUT, None),
    "allreduce_ms_per_step": ("collective", "device_trace", THROUGHPUT, None),
    "allreduce_exposed_ms_per_step": ("collective", "device_trace",
                                      THROUGHPUT, None),
}


def test_spec_declares_what_section_3_says(spec):
    """Layer, source and moved metric of every metric PERF.md's section 3
    has; the general ones carry no list.  A later PR's metric is held to the
    rules above and, where its name is ``<metric>.<anything>``, to what is
    said of ``<metric>``."""
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert set(SECTION_3) <= set(by_name)
    for name, m in by_name.items():
        said = SECTION_3.get(name.partition(".")[0])
        if said is None:
            continue
        layer, source, moves, where = said
        assert (m["layer"], m["source"], m["moves"]) == (layer, source,
                                                         moves), name
        if "." in name:
            assert "workloads" in m, name
        elif where is EVERY_CELL:
            assert "workloads" not in m, name


# ---------------------------------------------------------------------------
# every cell, rehearsed
# ---------------------------------------------------------------------------

_REHEARSED = {}


def _rehearse(chip_run, capsys, cell, trace):
    """One rehearsal of a cell, traced or not, shared by the tests below.
    The traced stretch is short and inside the window, so that some steps
    are untraced however slow the machine is: a span metric is named by one
    span that began in an untraced step."""
    if (cell, trace) not in _REHEARSED:
        traced_for = chip_run.TRACE_SECONDS
        chip_run.TRACE_SECONDS = 0.3
        try:
            rc = chip_run.main([
                "--workload", cell, "--seed", "3", "--seconds",
                "8" if trace else "2", "--trace", str(trace), "--rehearse"])
        finally:
            chip_run.TRACE_SECONDS = traced_for
        _REHEARSED[cell, trace] = (rc,) + _result(capsys)
    return _REHEARSED[cell, trace]


def _result(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-2].startswith("[facts] ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("[facts] "):])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(chip_run, bench_catalog, capsys, cell, trace):
    rc, result, facts = _rehearse(chip_run, capsys, cell, trace)
    assert rc == 0
    assert set(result) == RESULT_KEYS | {"rehearsal"}
    assert result["rehearsal"] is True
    assert result["correct"] is True, facts["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    # the names the cell would report on the chip, and no device number
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"]
                for m in bench_catalog.metrics(group, cell)}
    assert result["metrics"], "the cell reports nothing"
    for name, m in result["metrics"].items():
        assert m == {"value": None, "unit": declared[name]}
    if not trace:
        assert set(result["metrics"]) == set(declared)
        # the end-to-end rate is the whole window's: every step's samples
        # over the time to when the last of the work was done
        window = facts["window"]
        assert window["last_loss_to_done_s"] >= 0.0
        assert facts["end_to_end"]["samples_per_s_per_chip"] * window[
            "seconds"] == pytest.approx(facts["steps"] * facts[
                "samples_per_step"] / bench_catalog.cell(cell)["chips"])
    assert facts["compile_window"]["programs"] == 0


# What the cells of PR 24 (the parent of the PR that took the lists of cells
# out of the general metrics) print, name for name: ``chip`` from the
# driver's runs of that commit (PERF_LEDGER.jsonl, PR 24), ``rehearsal``
# from this file's rehearsal run on an archive of it.  A metric added to one
# of these cells since is listed under ``SINCE`` with the PR that added it.
_ALL = ["setup_init_s", "setup_compile_s", "setup_cache_hit_pct",
        "step_ms_p50", "step_ms_p90", "step_host_ms_p50", "window_lost_pct",
        "window_compiles", "fwd_device_ms_per_step",
        "bwd_device_ms_per_step", "update_device_ms_per_step",
        "device_unattributed_pct"]
_ON_CHIP = ["device_programs_per_step", "device_idle_pct", "mfu_pct"]
_FUSED = ["place_span_ms_p50", "dispatch_span_ms_p50"]
_GLUON = ["fwd_host_ms_p50", "bwd_host_ms_p50", "update_host_ms_p50",
          "fwd_span_ms_p50", "bwd_span_ms_p50", "kvstore_span_ms_p50",
          "update_span_ms_p50", "engine_flushes_per_step"]
_E2E = ["samples_per_s_per_chip", "setup_s"]
# read from ten step samples or more, which two seconds of a ResNet on this
# machine's CPU may or may not give: in a rehearsal's line or not by the clock
_TEN_STEPS = {"step_ms_p50", "step_ms_p90", "step_host_ms_p50",
              "window_lost_pct", "median_block_samples_per_s"}
PARENT = {
    "resnet50_gluon_b128": {
        "end_to_end": _E2E, "rehearsal": _ALL + _GLUON,
        "chip": _ALL + _ON_CHIP + _GLUON},
    "resnet50_fused_b256": {
        "end_to_end": _E2E + ["peak_hbm_gb"], "rehearsal": _ALL + _FUSED,
        "chip": _ALL + _ON_CHIP + _FUSED},
    "opt6b7_fused_s2048": {
        "end_to_end": _E2E + ["peak_hbm_gb"],
        "rehearsal": _ALL + _FUSED + ["flash_bwd_ms_per_step"],
        "chip": _ALL + _ON_CHIP + _FUSED + [
            "flash_fwd_ms_per_step", "flash_fwd_roofline",
            "flash_bwd_ms_per_step"]},
    "resnet50_fused_dp4_b1024": {
        "end_to_end": _E2E + ["peak_hbm_gb"], "rehearsal": _ALL + _FUSED,
        "chip": _ALL + _ON_CHIP + _FUSED + [
            "allreduce_ms_per_step", "allreduce_exposed_ms_per_step"]},
}
SINCE = {
    ("opt6b7_fused_s2048", "chip"): ["flash_bwd_roofline"],      # PR 25
}
# PR 25: the median block's rate, once the end-to-end rate, beside it
SINCE_IN_EVERY_TRACED_LINE = ["median_block_samples_per_s"]


def _since(cell, kind):
    return set(SINCE.get((cell, kind), ())) | set(
        () if kind == "end_to_end" else SINCE_IN_EVERY_TRACED_LINE)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(PARENT))
def test_rehearsal_prints_the_parents_names(chip_run, capsys, cell, trace):
    _, result, facts = _rehearse(chip_run, capsys, cell, trace)
    kind = "rehearsal" if trace else "end_to_end"
    want = set(PARENT[cell][kind]) | _since(cell, kind)
    assert set(result["metrics"]) - _TEN_STEPS == want - _TEN_STEPS, \
        facts["metrics_left_out"]


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_cell_declares_the_names_the_chip_printed(bench_catalog, cell):
    """On the chip every reader finds its evidence, so a cell prints what
    is declared for it: the parent's lines, name for name."""
    for group, kind in (("end_to_end", "end_to_end"), ("per_layer", "chip")):
        want = set(PARENT[cell][kind]) | _since(cell, kind)
        got = {m["name"] for m in bench_catalog.metrics(group, cell)}
        assert got == want, (group, got ^ want)


def test_the_fifth_cell_came_by_entries_of_its_own(chip_run, capsys):
    """``opt6b7_fused_adam`` was added as a later PR adds a cell: no list
    of another metric names it; what it reports of the metrics that list
    their cells it reports under ``<metric>.<its name>``, which the harness
    reads as ``<metric>``, and they are the OPT cell's, name for name."""
    cell, like = "opt6b7_fused_adam", "opt6b7_fused_s2048"
    mine = {}
    for group in ("end_to_end", "per_layer"):
        for m in SPEC[group]:
            if cell in m.get("workloads", ()):
                assert m["workloads"] == [cell], m["name"]
                base, dot, suffix = m["name"].partition(".")
                assert (dot, suffix) == (".", cell), m["name"]
                mine[base] = group
    assert mine == {m["name"]: group
                    for group in ("end_to_end", "per_layer")
                    for m in SPEC[group] if like in m.get("workloads", ())}
    _, result, _ = _rehearse(chip_run, capsys, cell, 1)
    assert {"place_span_ms_p50." + cell, "dispatch_span_ms_p50." + cell,
            "flash_bwd_ms_per_step." + cell} <= set(result["metrics"])
    _, result, _ = _rehearse(chip_run, capsys, cell, 0)
    assert "peak_hbm_gb." + cell in result["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_resolve_by_name(bench_catalog, cell):
    entry = bench_catalog.cell(cell)
    sizes, module = bench_catalog.config(entry["config"])
    for fn in ("build", "initializer", "sample_shape", "samples_per_row",
               "check_rows", "reference", "reference_loss",
               "train_flops_per_sample"):
        assert callable(getattr(module, fn)), fn
    assert set(module.TOLERANCE) >= {"block_f32", "step_loss",
                                     "step_loss_rehearsal"}
    # the chip's tolerance is the tight one
    assert module.TOLERANCE["step_loss"] < module.TOLERANCE[
        "step_loss_rehearsal"]
    declared = next(c for c in SPEC["configs"]
                    if c["name"] == entry["config"])
    assert sorted(declared["reduced"]) == sorted(sizes["reduced"])
    traffic = bench_catalog.traffic(entry["traffic"])
    assert callable(bench_catalog.driver(traffic["driver"]).setup)
    readers = bench_catalog.readers()
    assert readers and all(callable(r.read) for r in readers)


def test_new_files_are_found_without_an_edit(chip_run, tmp_path, capsys):
    """A later PR's configuration, traffic mix, cell and metrics: new files
    and appended BENCHMARK.json entries only (``with_later_entries``, the
    spec the rules above are also run on)."""
    shutil.copytree(ROOT / "benchmark" / "chip",
                    tmp_path / "benchmark" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmark" / "chip"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    sizes = json.loads((bench / "configs" / "opt_6b7_l2.json").read_text())
    sizes["rehearsal"]["num_attention_heads"] = 4
    (bench / "configs" / "later_lm.json").write_text(json.dumps(sizes))
    (bench / "traffic" / "later_mix.json").write_text(json.dumps(
        {"driver": "fused", "batch_per_chip": 4, "seq_len": 256,
         "dtype": "bfloat16", "rehearsal": {"seq_len": 128}}))
    (bench / "layer_metrics" / "later_reader.py").write_text(
        "def read(run):\n    return {'later_steps': len(run.stamps)}\n")
    (tmp_path / "BENCHMARK.json").write_text(
        json.dumps(SPECS["with_a_later_cell"]))

    rc = chip_run.main(["--workload", "later_cell", "--seed", "5",
                        "--seconds", "1", "--trace", "1", "--rehearse"],
                       root=tmp_path)
    result, facts = _result(capsys)
    assert rc == 0 and result["correct"] is True, facts["checks"]
    # its own reader's metric, one that an existing reader gives under the
    # later cell's own entry, and the general ones it gets for being a cell
    assert {"later_steps", "dispatch_span_ms_p50.later",
            "fwd_device_ms_per_step", "device_unattributed_pct",
            "window_compiles"} <= set(result["metrics"])
    # what lists other cells stays theirs
    assert "dispatch_span_ms_p50" not in result["metrics"]
    # and untraced: guarded on memory by an entry of its own
    rc = chip_run.main(["--workload", "later_cell", "--seed", "5",
                        "--seconds", "1", "--trace", "0", "--rehearse"],
                       root=tmp_path)
    result, _ = _result(capsys)
    assert rc == 0 and set(result["metrics"]) == {
        "samples_per_s_per_chip", "setup_s", "peak_hbm_gb.later"}
    assert {p: p.read_bytes() for p in before} == before


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        chip_run, tmp_path, capsys):
    """The timed path broken underneath: the same cell in a copy whose
    configuration steps at learning rate 0, so every step returns the
    weights it was given.  The rest of the run is driven as it is, and
    ``correct`` comes out false, by the check that is there for it."""
    shutil.copytree(ROOT / "benchmark" / "chip",
                    tmp_path / "benchmark" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "benchmark" / "chip" / "configs" / "resnet50_v1.json"
    sizes = json.loads(path.read_text())
    sizes["rehearsal"]["optimizer"]["learning_rate"] = 0.0
    path.write_text(json.dumps(sizes))
    rc = chip_run.main(["--workload", "resnet50_fused_b256", "--seed", "7",
                        "--seconds", "1", "--trace", "0", "--rehearse"],
                       root=tmp_path)
    result, facts = _result(capsys)
    assert rc == 0 and result["correct"] is False
    assert [k for k, ok in facts["checks"].items() if not ok] == ["loss_fell"]


def test_first_steps_that_move_nothing_are_not_correct(chip_run, capsys,
                                                       monkeypatch):
    """The same fault where the first steps are followed by the reference
    (``opt6b7_fused_adam``): the driver's job with its trainer's rate set
    to 0 after it is built, the configuration and so the reference as they
    are.  The parameters' change reads a gap of 1 and the later steps'
    losses stay where the reference's fall."""
    real = chip_run.catalog.Catalog.driver

    def driver(self, name):
        module = real(self, name)

        class Still:
            @staticmethod
            def setup(run, net, x, y):
                job = module.setup(run, net, x, y)
                job.trainer.set_learning_rate(0.0)
                return job
        return Still

    monkeypatch.setattr(chip_run.catalog.Catalog, "driver", driver)
    rc = chip_run.main(["--workload", "opt6b7_fused_adam", "--seed", "7",
                        "--seconds", "1", "--trace", "0", "--rehearse"])
    result, facts = _result(capsys)
    assert rc == 0 and result["correct"] is False
    assert facts["first_steps"]["param_change_norm"]["gap"] == pytest.approx(
        1.0)
    # (the timed job's losses, under dropout, may still fall by chance)
    assert {"param_change_norm_agrees_with_reference",
            "steps_loss_agrees_with_reference"} <= {
                k for k, ok in facts["checks"].items() if not ok}


def test_unknown_cell_prints_no_result(chip_run, capsys):
    with pytest.raises(SystemExit) as e:
        chip_run.main(["--workload", "no_such_cell", "--rehearse"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_without_a_tpu_there_is_no_result(chip_run, capsys):
    """Not a rehearsal and no TPU: non-zero exit, nothing on stdout."""
    with pytest.raises(SystemExit) as e:
        chip_run.main(["--workload", CELLS[0], "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
