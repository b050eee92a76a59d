"""graftxray tests (ISSUE 18): scope-map parsing from optimized HLO,
conservation-exact phase attribution over synthetic profiler traces,
the ONE shared parser core behind both ``attribute`` and the offline
``--ingest-xla`` CLI, and scope maps resolved lazily from the program
registry.  The triggered-capture harness these once covered went in PR
23, the cost ledger with the step compiler that fed it in PR 43; the
registry's lazy side and the scopes of the train paths are in
``test_program_tracing.py``."""
import json

import pytest

import incubator_mxnet_tpu as mx  # noqa: F401
from incubator_mxnet_tpu.telemetry import aggregate, xray


@pytest.fixture
def fresh_xray():
    """A clean registry for one test."""
    xray.reset()
    yield xray
    xray.reset()


# ---------------------------------------------------------------------------
# scope maps from optimized HLO
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_dp_train_step, entry_computation_layout={(f32[1,5]{1,0})->f32[1,5]{1,0}}

%fused_computation (p0: f32[1,5]) -> f32[1,5] {
  %p0 = f32[1,5]{1,0} parameter(0)
  ROOT %m = f32[1,5]{1,0} multiply(%p0, %p0)
}

ENTRY %main.42 (param_0: f32[1,5]) -> f32[1,5] {
  %param_0 = f32[1,5]{1,0} parameter(0)
  %fusion.1 = f32[1,5]{1,0} fusion(%param_0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(dp_train_step)/jit(main)/xray:forward/mul" source_file="net.py" source_line=7}
  %loop_add = f32[1,5]{1,0} add(%fusion.1, %fusion.1), metadata={op_name="jit(dp_train_step)/jit(main)/xray:update[0]/xray:inner/add"}
  %copy.9 = f32[1,5]{1,0} copy(%loop_add), metadata={op_name="jit(dp_train_step)/jit(main)/convert"}
  ROOT %sub.3 = f32[1,5]{1,0} subtract(%copy.9, %fusion.1), metadata={op_name="jit(dp_train_step)/jit(main)/xray:backward/sub"}
}
"""


def test_scope_map_from_hlo_parses_fusions_root_and_skips_scopeless():
    m = xray.scope_map_from_hlo(_HLO)
    assert m == {
        "fusion.1": "forward",
        # nested scopes resolve to the OUTERMOST xray token
        "loop_add": "update[0]",
        "sub.3": "backward",
    }
    # scope-less ops (copy.9, param_0, the fused-computation body) are
    # left out — they pool into "unattributed" at attribution time
    assert "copy.9" not in m and "param_0" not in m and "m" not in m


def test_phase_of_first_token_wins_and_hyphen_spelling_is_excluded():
    assert xray.phase_of(
        "jit(f)/xray:update[3]/xray:inner/add") == "update[3]"
    # the optimizer's fused-formula scope is DELIBERATELY spelled with
    # a hyphen ("xray-apply-sgd") so bucket-grained update[k] phases
    # stay the unit of attribution — it must NOT parse as a phase
    assert xray.phase_of("jit(f)/xray-apply-sgd/mul") is None
    assert xray.phase_of("") is None
    assert xray.phase_of(None) is None


def test_norm_module_strips_jit_prefix_and_uniquifier():
    assert xray._norm_module("jit_dp_train_step.5") == "dp_train_step"
    assert xray._norm_module("jit_trainer_bucket_update") == "trainer_bucket_update"
    assert xray._norm_module("dp_train_step") == "dp_train_step"
    assert xray._norm_module(None) == ""


# ---------------------------------------------------------------------------
# attribution: the conservation-exact partition
# ---------------------------------------------------------------------------

def _dev_ev(name, ts_us, dur_us, op=None, module="jit_dp_train_step.3",
            step=None, pid=7):
    args = {}
    if op is not None:
        args["hlo_op"] = op
    if module is not None:
        args["hlo_module"] = module
    if step is not None:
        args["step"] = step
    return {"ph": "X", "name": name, "pid": pid, "tid": 1,
            "ts": ts_us, "dur": dur_us, "args": args}


def _meta(pid=7, name="/device:TPU:0 Compute"):
    return {"ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": name}}


def test_attribute_exact_conservation_with_fractional_us():
    """Fractional-µs durations (the TPU trace reality) must still sum
    EXACTLY: durations accumulate as integer nanoseconds, so the phase
    partition + unattributed == program span is integer equality, not
    a float tolerance."""
    scope_maps = {"dp_train_step": {"fusion.1": "forward",
                                "loop_add": "update[0]",
                                "sub.3": "backward"}}
    events = [
        _meta(),
        _dev_ev("fusion.1", 100.0, 10.3, op="fusion.1", step=0),
        _dev_ev("sub.3", 111.0, 20.7, op="sub.3", step=0),
        _dev_ev("loop_add", 132.5, 5.1, op="loop_add", step=1),
        # scope-less op of a REGISTERED module -> unattributed
        _dev_ev("copy.9", 138.0, 0.7, op="copy.9", step=1),
        # op of an UNREGISTERED module -> unattributed
        _dev_ev("other", 139.0, 3.3, op="whatever",
                module="jit_warmup.1", step=1),
        # host event on a non-device pid: excluded entirely
        {"ph": "X", "name": "python", "pid": 1, "tid": 2,
         "ts": 100.0, "dur": 500.0, "args": {}},
    ]
    rep = xray.attribute(events, scope_maps=scope_maps)
    assert rep["device_events"] == 5
    assert set(rep["phases"]) == {"forward", "backward", "update[0]"}
    assert rep["phases"]["forward"]["device_s"] == pytest.approx(10.3e-6)
    assert rep["phases"]["backward"]["device_s"] == pytest.approx(20.7e-6)
    assert rep["phases"]["update[0]"]["device_s"] == pytest.approx(5.1e-6)
    assert rep["unattributed_s"] == pytest.approx((0.7 + 3.3) * 1e-6)
    # the conservation contract is EXACT (integer ns), not approx
    assert rep["conservation_ok"]
    assert rep["program_device_s"] == (10300 + 20700 + 5100 + 700
                                       + 3300) * 1e-9
    # shares partition to 1 over phases + unattributed
    total_share = sum(p["share"] for p in rep["phases"].values())
    assert total_share == pytest.approx(1.0 - (4000 / 40100))
    # true device-side window in the trace timebase
    assert rep["span"]["t0"] == pytest.approx(100.0e-6)
    assert rep["span"]["t1"] == pytest.approx((139.0 + 3.3) * 1e-6)
    # modules roll up by normalized name
    assert set(rep["modules"]) == {"dp_train_step", "warmup"}
    # the shared ledger produced one row per step stamp
    steps = [r["step"] for r in rep["ledger"]["steps"]]
    assert steps == [0, 1]
    for row in rep["ledger"]["steps"]:
        assert row["busy_s"] + row["idle_s"] == pytest.approx(
            row["wall_s"])
    # top op is the backward sub
    assert rep["top_ops"][0]["op"] == "sub.3"
    assert rep["top_ops"][0]["phase"] == "backward"


def test_attribute_empty_and_scopeless_traces():
    rep = xray.attribute([], scope_maps={})
    assert rep["device_events"] == 0
    assert rep["phases"] == {}
    assert rep["conservation_ok"]    # 0 + 0 == 0
    assert rep["span"] is None
    # a trace with device ops but NO registered scope maps: everything
    # pools into unattributed, conservation still exact
    events = [_meta(), _dev_ev("x", 10.0, 2.5, op="x", step=0)]
    rep = xray.attribute(events, scope_maps={})
    assert rep["phases"] == {}
    assert rep["unattributed_s"] == pytest.approx(2.5e-6)
    assert rep["conservation_ok"]


def test_parse_trace_offline_twin(tmp_path):
    doc = {"traceEvents": [_meta(),
                           _dev_ev("f", 5.0, 4.0, op="fusion.1", step=0)]}
    p = tmp_path / "t.trace.json"
    p.write_text(json.dumps(doc))
    rep = xray.parse_trace(str(p),
                           scope_maps={"dp_train_step": {"fusion.1": "fwd"}})
    assert rep["phases"]["fwd"]["device_s"] == pytest.approx(4.0e-6)
    assert rep["conservation_ok"]


# ---------------------------------------------------------------------------
# parser unification: ONE shared core behind aggregate.ingest_xla and
# xray.attribute
# ---------------------------------------------------------------------------

def test_parser_core_is_shared_not_cloned():
    """The offline CLI's parser internals must BE the xray core (same
    function objects), not a drifting copy — the dedup the refactor
    promised."""
    assert aggregate._merge_intervals is xray.merge_intervals
    assert aggregate._DEVICE_PID_HINTS is xray.DEVICE_PID_HINTS


def test_ingest_xla_and_attribute_agree_on_step_rows(tmp_path):
    """Both paths run the same events through step_spans/step_rows: the
    per-step device ledger rows must be identical."""
    events = [_meta(),
              _dev_ev("a", 10.0, 3.0, op="a", step=0),
              _dev_ev("b", 14.0, 2.0, op="b", step=0),
              _dev_ev("c", 17.0, 4.5, op="c", step=1),
              _dev_ev("d", 30.0, 1.5, op="d")]      # unstamped pool
    p = tmp_path / "steps.trace.json"
    p.write_text(json.dumps({"traceEvents": events}))
    offline = aggregate.ingest_xla(str(p))
    online = xray.attribute(events, scope_maps={})
    assert offline["steps"] == online["ledger"]["steps"]
    assert offline["total"] == online["ledger"]["total"]


# ---------------------------------------------------------------------------
# scope maps from the registry
# ---------------------------------------------------------------------------

class _FakeJitted(object):
    """Weakref-able stand-in for a jitted function whose executable's
    text is ``hlo``: ``lower(...).compile().as_text()``."""

    def __init__(self, hlo):
        self._hlo = hlo

    def lower(self, *avals):
        return self

    def compile(self):
        return self

    def as_text(self):
        return self._hlo

    def cost_analysis(self):
        return {"flops": 1.0}

    def memory_analysis(self):
        return None


def test_scope_maps_resolve_lazily_from_live_executables(fresh_xray):
    fn = _FakeJitted(_HLO)
    xray.register_program("dp_train_step", fn, ())
    maps = xray._scope_maps()
    assert maps["dp_train_step"]["fusion.1"] == "forward"
    # a collected program drops out instead of erroring
    xray.register_program("gone", _FakeJitted(_HLO), ())
    import gc
    gc.collect()
    assert "gone" not in xray._scope_maps()
    assert xray.programs()["gone"].error == "the program is gone"
    del fn


# ---------------------------------------------------------------------------
# profiler-trace ingestion (telemetry --ingest-xla)
# ---------------------------------------------------------------------------

def test_ingest_xla_unions_overlapping_device_spans(tmp_path):
    """Synthetic chrome trace: overlapping device spans must UNION per
    step (never sum), busy + idle == wall per row, unstamped device
    spans pool separately, host spans are ignored."""
    us = 1e6
    events = [
        {"ph": "M", "name": "process_name", "pid": "d0",
         "args": {"name": "TPU:0 device stream"}},
        # step 1: two overlapping spans 0-10ms and 5-15ms -> 15ms busy
        {"ph": "X", "name": "op", "pid": "d0", "tid": 1,
         "ts": 0.000 * us, "dur": 0.010 * us, "args": {"step": 1}},
        {"ph": "X", "name": "op", "pid": "d0", "tid": 1,
         "ts": 0.005 * us, "dur": 0.010 * us, "args": {"step": 1}},
        # step 2: one span 20-25ms; window = prev end (15ms) -> 25ms
        {"ph": "X", "name": "op", "pid": "d0", "tid": 1,
         "ts": 0.020 * us, "dur": 0.005 * us, "args": {"step": 2}},
        # our own sync-mode flush span (host pid, device_time arg)
        {"ph": "X", "name": "bulk_segment_flush", "pid": 77, "tid": 2,
         "ts": 0.030 * us, "dur": 0.002 * us,
         "args": {"device_time": True}},
        # host span: ignored
        {"ph": "X", "name": "host", "pid": 77, "tid": 2,
         "ts": 0.000 * us, "dur": 0.050 * us, "args": {}},
    ]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"traceEvents": events}))
    report = aggregate.ingest_xla(str(path))
    assert report["problems"] == []
    assert report["device_events"] == 4
    rows = {r["step"]: r for r in report["steps"]}
    assert rows[1]["busy_s"] == pytest.approx(0.015)
    assert rows[1]["wall_s"] == pytest.approx(0.015)
    assert rows[2]["busy_s"] == pytest.approx(0.005)
    assert rows[2]["wall_s"] == pytest.approx(0.010)   # 15ms -> 25ms
    for r in report["steps"]:
        assert r["busy_s"] + r["idle_s"] == pytest.approx(r["wall_s"])
    assert rows[None]["spans"] == 1                    # the flush span


def test_ingest_xla_total_is_span_union_not_row_sum(tmp_path):
    """Unstamped spans pool into a None row whose window OVERLAPS the
    stamped rows' chained windows: the total must be the union over all
    device spans, not the sum of row walls (which would double the wall
    and halve the headline busy_fraction)."""
    us = 1e6
    path = tmp_path / "u.json"
    path.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0.000 * us, "dur": 0.010 * us, "args": {"step": 1}},
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0.020 * us, "dur": 0.005 * us, "args": {"step": 2}},
        # unstamped span covering the WHOLE capture
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0.000 * us, "dur": 0.030 * us, "args": {}}]}))
    report = aggregate.ingest_xla(str(path))
    assert report["total"]["wall_s"] == pytest.approx(0.030)
    assert report["total"]["busy_s"] == pytest.approx(0.030)
    assert report["total"]["busy_fraction"] == pytest.approx(1.0)


def test_ingest_xla_flags_non_monotonic_step_ids(tmp_path):
    """A restarted step counter (or merged captures) puts a low step id
    LATE in time: id-order window chaining clamps its successors' wall
    to 0 — the report must say so in problems[], not zero silently."""
    us = 1e6
    path = tmp_path / "nm.json"
    path.write_text(json.dumps({"traceEvents": [
        # step 5 runs first in time, step 1 (restarted counter) after —
        # id order chains step 5's window start past its own spans
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0.000 * us, "dur": 0.010 * us, "args": {"step": 5}},
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0.100 * us, "dur": 0.010 * us, "args": {"step": 1}}]}))
    report = aggregate.ingest_xla(str(path))
    rows = {r["step"]: r for r in report["steps"]}
    assert rows[5]["wall_s"] == 0.0                 # the clamped row
    assert any("not time-monotonic" in p for p in report["problems"])


def test_ingest_xla_cli(tmp_path, capsys):
    from incubator_mxnet_tpu.telemetry.__main__ import main as tmain
    us = 1e6
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0, "dur": 0.004 * us, "args": {"step": 1}}]}))
    rc = tmain(["--ingest-xla", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "device-ledger ingestion" in out
    assert "1" in out
    # external traces stamp steps as strings: "2" must pool with 2 and
    # a non-numeric stamp must sort, not TypeError against ints
    path3 = tmp_path / "m.json"
    path3.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 0, "dur": 1000, "args": {"step": 2}},
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 2000, "dur": 1000, "args": {"step": "2"}},
        {"ph": "X", "name": "op", "pid": "/device:TPU:0", "tid": 1,
         "ts": 4000, "dur": 1000, "args": {"step": "warmup"}}]}))
    report = aggregate.ingest_xla(str(path3))
    assert [r["step"] for r in report["steps"]] == [2, "warmup"]
    assert report["steps"][0]["spans"] == 2
    # empty trace: rc 1 + a problem line
    path2 = tmp_path / "e.json"
    path2.write_text(json.dumps({"traceEvents": []}))
    assert tmain(["--ingest-xla", str(path2)]) == 1
