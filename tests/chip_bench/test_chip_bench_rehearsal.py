"""The chip benchmark's control flow, rehearsed on the CPU at toy sizes.

Every cell of ``BENCHMARK.json`` runs through ``run.py``'s ``main`` with
``--rehearse`` (thumbnail ResNet-18, a d64 decoder, the virtual CPU mesh of
``tests/conftest.py``), untraced and traced; the last line is held to the
contract's keys; every name and unit is held to the allowed characters;
and a configuration, a traffic mix, a cell and a reader dropped as new files
into a copy are found with no edit to a file that was there.  Nothing here
is a speed: a rehearsal's result says so and carries no value.
"""
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


def _result(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-2].startswith("[facts] ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("[facts] "):])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(chip_run, bench_catalog, capsys, cell, trace):
    rc = chip_run.main(["--workload", cell, "--seed", "3", "--seconds", "2",
                        "--trace", str(trace), "--rehearse"])
    result, facts = _result(capsys)
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
    assert facts["compile_window"]["programs"] == 0


def test_names_units_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark/chip", "tests/chip_bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = lambda group: [e["name"] for e in SPEC[group]]      # noqa: E731
    for group in ("configs", "workloads"):
        assert len(set(names(group))) == len(names(group))
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/chip/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["config"] in names("configs")
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # a per-layer metric is reported only where the metric it moves is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS)), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in CELLS:
        reported = [m["name"] for m in SPEC["end_to_end"]
                    if cell in m.get("workloads", CELLS)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in SPEC["per_layer"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_resolve_by_name(bench_catalog, cell):
    entry = bench_catalog.cell(cell)
    sizes, module = bench_catalog.config(entry["config"])
    for fn in ("build", "initializer", "sample_shape", "samples_per_row",
               "check_rows", "reference", "reference_loss",
               "train_flops_per_sample"):
        assert callable(getattr(module, fn)), fn
    assert set(module.TOLERANCE) == {"block_f32", "step_loss",
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
    """A later PR's configuration, traffic mix, cell and reader: new files
    and BENCHMARK.json entries only."""
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
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "later_lm", "source": "a later PR", "reduced": [],
        "file": "benchmark/chip/configs/later_lm.json", "why": "test"})
    spec["workloads"].append({
        "name": "later_cell", "config": "later_lm", "traffic": "later_mix",
        "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "later_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "samples_per_s_per_chip", "workloads": ["later_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    rc = chip_run.main(["--workload", "later_cell", "--seed", "5",
                        "--seconds", "1", "--trace", "1", "--rehearse"],
                       root=tmp_path)
    result, facts = _result(capsys)
    assert rc == 0 and result["correct"] is True, facts["checks"]
    assert "later_steps" in result["metrics"]
    assert {p: p.read_bytes() for p in before} == before


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
