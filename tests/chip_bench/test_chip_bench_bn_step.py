"""``layer_metrics/bn_step.py``: BatchNorm's second passes out of the
compiled step, on a made-up run counted by hand, a program with nothing to
read (the parent of the PR that brought the guard), a fused ResNet cell's CPU
rehearsal, and the entry of ``BENCHMARK.json``.
"""
import json
import pathlib
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "bn_second_pass_per_step"
CELLS = ["resnet50_fused_b256", "resnet50_fused_dp4_b1024"]
STEPS, FIRST_ID, LAYERS = 10, 4, 5


@pytest.fixture(scope="module")
def reader(bench_catalog):
    return next(r for r in bench_catalog.readers()
                if r.__name__.endswith("bn_step"))


def _taken(*layers):
    taken = np.zeros(LAYERS, np.int32)
    taken[list(layers)] = 1
    return {"bn_second_pass": taken}


def _run(chip_run, bench_catalog, records, accessor=True):
    """Ten steps (ids 4..13) in a window that opens at 99.0, after three
    warm-up steps of the same trainer and three of an earlier trainer of the
    process, which numbered its own from 1 too."""
    spans = [("step", 10.0 + i, 10.5 + i, None, i + 1) for i in range(6)]
    spans += [("step", 50.0 + i, 50.5 + i, None, i + 1) for i in range(3)]
    spans += [("step", 99.2 + i, 99.4 + i, None, FIRST_ID + i)
              for i in range(STEPS)]
    spans += [("dispatch", 99.3 + i, 99.35 + i, "step", FIRST_ID + i)
              for i in range(STEPS)]
    telemetry = types.SimpleNamespace(
        programs=lambda: {},
        spans=lambda since=None: [s for s in spans
                                  if since is None or s[1] >= since])
    if accessor:
        telemetry.step_counters = lambda since_step=None: [
            r for r in records if since_step is None or r[0] >= since_step]
    return chip_run.Run(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=None,
        reduced=None, stamps=[100.0 + i for i in range(STEPS)],
        window_span=(99.0, 110.0), traced_stamps=(None, None),
        rehearse=False, catalog=bench_catalog)


def test_second_passes_of_the_window_by_hand(chip_run, bench_catalog, reader):
    # the earlier trainer: six steps, layers 0 and 1 far off in each; its
    # ids 4, 5, 6 are ids of the window too
    records = [(i + 1, _taken(0, 1)) for i in range(6)]
    # this trainer's warm-up: layer 2 far off in its first step
    records += [(1, _taken(2)), (2, _taken()), (3, _taken())]
    # the window: its first step takes two second passes, its sixth one
    records += [(FIRST_ID + i, _taken(*{0: (0, 3), 5: (4,)}.get(i, ())))
                for i in range(STEPS)]
    run = _run(chip_run, bench_catalog, records)
    assert reader.read(run) == {METRIC: pytest.approx(3 / STEPS)}
    facts = run.facts["bn_step"]
    assert facts["layers"] == LAYERS and facts["steps_read"] == STEPS
    assert facts["second_passes"] == 3
    # what came under an id below the window's first: both trainers' steps
    # 1..3, in the order they ran
    assert facts["second_passes_before_the_window"] == [2, 2, 2, 1, 0, 0]
    assert facts["read_s"] >= 0


def test_a_quiet_window_reads_zero_and_a_lost_record_is_left_out(
        chip_run, bench_catalog, reader):
    records = [(FIRST_ID + i, _taken()) for i in range(STEPS) if i != 7]
    run = _run(chip_run, bench_catalog, records)
    got = reader.read(run)
    assert got == {METRIC: 0.0} and got[METRIC] is not None
    assert run.facts["bn_step"]["steps_read"] == STEPS - 1


@pytest.mark.parametrize("case", ["no_accessor", "nothing_emitted",
                                  "other_names_only"])
def test_a_program_with_nothing_to_read_reports_nothing(
        chip_run, bench_catalog, reader, case):
    records = {"no_accessor": None, "nothing_emitted": [],
               "other_names_only": [(FIRST_ID, {"moe_held_rows":
                                                np.ones((2, 2))})]}
    run = _run(chip_run, bench_catalog, records[case],
               accessor=case != "no_accessor")
    assert reader.read(run) == {}
    assert "bn_step" not in run.facts


def test_a_fused_resnet_cells_rehearsal_names_the_metric(
        chip_run, bench_catalog, capsys, monkeypatch):
    monkeypatch.setattr(chip_run, "TRACE_SECONDS", 0.3)
    rc = chip_run.main(["--workload", CELLS[0], "--seed", "2147483733",
                        "--seconds", "3", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    facts = json.loads(lines[-2][len("[facts] "):])
    assert rc == 0 and result["correct"] is True, facts["checks"]
    assert result["metrics"][METRIC] == {"value": None, "unit": "count"}
    counted = facts["bn_step"]
    # the rehearsal's ResNet-18: the stem has none under `thumbnail`, two a
    # basic block, one a projection shortcut
    assert counted["layers"] == 19
    assert counted["second_passes"] == 0 and counted["steps_read"] >= 1
    # the warm-up's three steps (after those of whatever trainers the
    # process ran before)
    assert counted["second_passes_before_the_window"][-3:] == [0, 0, 0]


def test_the_entry_is_appended_and_lists_the_fused_resnet_cells():
    entry, = [m for m in SPEC["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "samples_per_s_per_chip", "workloads": CELLS}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert all(cells[c]["config"] == "resnet50_v1" for c in CELLS)
