"""``layer_metrics/moe_step.py``: the routed layer's counts out of the
compiled step, on a made-up run worked out by hand (the median, the drift,
the worst layer's load, the roofline and its join by step id), a program
without the accessor, the three routed cells' CPU rehearsals, and the four
entries of ``BENCHMARK.json``.
"""
import json
import pathlib
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
US = 1000
ROUTED = ["lfm2moe_fused_s8192", "mellum2_fused_s8192",
          "kimivl_mla_fused_1row"]
FOUR = ["moe_step_held_assignment_pct", "moe_step_held_drift_pct",
        "moe_step_load_max_over_mean", "moe_experts_rows_roofline"]


@pytest.fixture(scope="module")
def reader(bench_catalog):
    return next(r for r in bench_catalog.readers()
                if r.__name__.endswith("moe_step"))


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


class _Program:
    def __init__(self, ops):
        self.phase, self.ops, self.error = None, ops, None


EXPERTS = "jit(dp_train_step)/xray:forward/jvp(net_moe0/moe_experts)/" \
    "dot_general"
OTHER = "jit(dp_train_step)/xray:forward/jvp(net_moe0/moe_dispatch)/gather"
# Six traced waits end at 0, 50, 200, 300, 400, 500 us: the traced window is
# [200, 500] and holds three steps, each with one op of 20 us under the
# experts' scope and one of 30 us under another.
PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            _ev("jit_dp_train_step(7)", t, 90) for t in (200, 300, 400)]},
        {"name": "XLA Ops", "events": [
            ev for t in (200, 300, 400)
            for ev in (_ev("%fusion.7", t, 20), _ev("%gather.8", t + 30, 30))
        ]}]},
    {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        _ev("bench:wait", e - 1, 1) for e in (0, 50, 200, 300, 400, 500)]}]},
]
REGISTRY = {"dp_train_step": _Program({"fusion.7": EXPERTS,
                                       "gather.8": OTHER})}
STEPS, FIRST_ID = 26, 4             # three warm-up steps came before
SIZES = {"hidden_size": 128, "moe_intermediate_size": 64}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e15}


def _held(i):
    """Step i of the window: layer 0 drifts, layer 1 stands still."""
    return np.array([[10 + i, 10], [20, 20]], np.int32)


def _run(chip_run, bench_catalog, records=None, accessor=True, traced=True):
    """A run of 26 one-second steps (ids 4..29), stamps 100..125; the
    profiler started after stamp 10 and stopped at stamp 17, so the traced
    waits closed stamps 10..15 and the device ran steps 13, 14, 15 inside
    the traced window.  Before the window: another trainer's three steps,
    whose ids collide with nothing the window asks for, and a stale record
    under an id of the window's."""
    spans = [("step", 10.0 + i, 10.5 + i, None, i + 1) for i in range(3)]
    spans += [("step", 99.2 + i, 99.4 + i, None, FIRST_ID + i)
              for i in range(STEPS)]
    spans += [("dispatch", 99.3 + i, 99.35 + i, "step", FIRST_ID + i)
              for i in range(STEPS)]
    made = np.array([100, 100], np.int32)
    if records is None:
        records = [(i + 1, {"moe_held_rows": _held(99), "moe_assignments":
                            made}) for i in range(3)]
        records.append((FIRST_ID + 1, {"moe_held_rows": _held(99),
                                       "moe_assignments": made}))
        records += [(FIRST_ID + i, {"moe_held_rows": _held(i),
                                    "moe_assignments": made})
                    for i in range(STEPS)]
    telemetry = types.SimpleNamespace(
        programs=lambda: REGISTRY,
        spans=lambda since=None: [s for s in spans
                                  if since is None or s[1] >= since])
    if accessor:
        telemetry.step_counters = lambda since_step=None: [
            r for r in records if since_step is None or r[0] >= since_step]
    recorded = {"planes": PLANES, "ops": {}} if traced else None
    return chip_run.Run(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded) if traced else None,
        stamps=[100.0 + i for i in range(STEPS)], window_span=(99.0, 126.0),
        traced_stamps=(10, 17) if traced else (None, None), rehearse=False,
        sizes=SIZES, traffic={"dtype": "bfloat16"}, peaks=PEAKS,
        catalog=bench_catalog)


def test_median_drift_and_load_by_hand(chip_run, bench_catalog, reader):
    run = _run(chip_run, bench_catalog)
    got = reader.read(run)
    # untraced: steps 1..7 and 19..25 (the first fills the pipeline; the
    # traced stretch and its margins, 8..18, are left out): 14 steps, the
    # median between step 7 and step 19.  Held rows of step i: 60 + i of 200
    assert got["moe_step_held_assignment_pct"] == pytest.approx(
        (33.5 + 39.5) / 2)
    # last tenth (steps 24, 25) over first tenth (steps 0, 1) of 26
    assert got["moe_step_held_drift_pct"] == pytest.approx(
        100 * (84.5 / 60.5 - 1))
    # layer 0, the worst: (10 + i) over its mean (20 + i) / 2; layer 1 is 1
    assert got["moe_step_load_max_over_mean"] == pytest.approx(
        (34 / 27 + 58 / 39) / 2)
    facts = run.facts["moe_step"]
    assert facts["steps_counted"] == 14 and facts["records"] == STEPS
    assert facts["assignments_per_step"] == 200
    assert facts["layer_rows_first_median_last"] == [[21, 33, 45],
                                                     [40, 40, 40]]
    # blocks of one step: rows beside seconds, the stale records nowhere
    assert facts["block_steps"] == 1
    assert facts["blocks_rows_and_seconds"][:2] == [[61.0, 1.0], [62.0, 1.0]]
    assert len(facts["blocks_rows_and_seconds"]) == STEPS - 1
    assert facts["read_s"] >= 0


def test_the_roofline_joins_the_traced_steps_by_id(chip_run, bench_catalog,
                                                   reader):
    run = _run(chip_run, bench_catalog)
    got = reader.read(run)
    # the device ran steps 13, 14, 15 in the traced window: layer 0 got 33,
    # 34, 35 rows and layer 1 40 each, 222 in all; a row needs 3 * 3 * 2 *
    # 128 * 64 FLOPs, and the scope took 3 x 20 us
    least = 18 * 128 * 64 * 222 / 1e12
    assert got["moe_experts_rows_roofline"] == pytest.approx(
        100 * least / 60e-6)
    assert got["moe_experts_rows_roofline"] < 100
    facts = run.facts["moe_step"]
    assert facts["traced_steps"] == 3
    assert facts["traced_rows_per_step"] == pytest.approx(74.0)
    assert facts["experts_ms_per_step"] == pytest.approx(0.020)
    assert facts["least_ms_per_step"] == pytest.approx(1e3 * least / 3)
    assert reader.traced(run, STEPS) == [13, 14, 15]
    assert reader.untraced(run, STEPS) == list(range(1, 8)) + list(
        range(19, 26))


def test_a_traced_step_without_its_record_leaves_the_roofline_out(
        chip_run, bench_catalog, reader):
    made = np.array([100, 100], np.int32)
    records = [(FIRST_ID + i, {"moe_held_rows": _held(i),
                               "moe_assignments": made})
               for i in range(STEPS) if i != 14]
    got = reader.read(_run(chip_run, bench_catalog, records=records))
    assert set(got) == set(FOUR[:3])


def test_an_untraced_run_reads_the_three_counts(chip_run, bench_catalog,
                                                reader):
    run = _run(chip_run, bench_catalog, traced=False)
    got = reader.read(run)
    assert set(got) == set(FOUR[:3])
    # every step but the first: the median between steps 12 and 13 ... 14
    assert got["moe_step_held_assignment_pct"] == pytest.approx(
        (60 + 13) / 2.0)
    assert run.facts["moe_step"]["steps_counted"] == STEPS - 1


@pytest.mark.parametrize("case", ["no_accessor", "nothing_emitted",
                                  "other_names_only"])
def test_a_program_with_nothing_to_read_reports_nothing(
        chip_run, bench_catalog, reader, case):
    records = {"no_accessor": None, "nothing_emitted": [],
               "other_names_only": [(FIRST_ID, {"loss_rows": np.ones(1)})]}
    run = _run(chip_run, bench_catalog, records=records[case],
               accessor=case != "no_accessor")
    assert reader.read(run) == {}
    assert "moe_step" not in run.facts


@pytest.mark.parametrize("cell", ROUTED)
def test_routed_cells_rehearsal_names_the_four(chip_run, bench_catalog,
                                               capsys, monkeypatch, cell):
    monkeypatch.setattr(chip_run, "TRACE_SECONDS", 0.3)
    rc = chip_run.main(["--workload", cell, "--seed", "2147483733",
                        "--seconds", "4", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    facts = json.loads(lines[-2][len("[facts] "):])
    assert rc == 0 and result["correct"] is True, facts["checks"]
    assert set(FOUR) <= set(result["metrics"])
    assert all(result["metrics"][n]["value"] is None for n in FOUR)
    counted = facts["moe_step"]
    sizes, _ = bench_catalog.config(bench_catalog.cell(cell)["config"], True)
    assert len(counted["layer_rows_first_median_last"]) == (
        sizes["num_hidden_layers"] - sizes["num_dense_layers"])
    assert 0 < counted["moe_step_held_assignment_pct"] <= 100
    assert counted["records"] == facts["steps"]


def test_the_four_entries_are_appended_and_list_the_routed_cells():
    last = SPEC["per_layer"][-4:]
    assert [m["name"] for m in last] == FOUR
    for m in last:
        assert m["layer"] == "expert layer"
        assert m["moves"] == "samples_per_s_per_chip"
        assert m["workloads"] == ROUTED
    assert [(m["unit"], m["source"], m["better"]) for m in last] == [
        ("%", "program_counter", "lower"), ("%", "program_counter", "lower"),
        ("ratio", "program_counter", "lower"),
        ("%", "device_trace", "higher")]
