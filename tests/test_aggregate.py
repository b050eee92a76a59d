"""``telemetry/aggregate.py``: step-id threading through the flight
recorder, the cross-rank aggregator + straggler table, metadata/flow trace
validation, the rank-suffixed dump path, and the 2-proc dist harness with
a deliberately delayed rank.  (The file was ``test_lens.py`` until PR 43,
after a module PR 28 deleted.)

``--analyze`` over two ranks' artifacts must produce a schema-valid
merged chrome trace with per-rank tracks, cross-rank flow links per
reduce, and a straggler table naming the delayed rank.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.telemetry import aggregate, blackbox
from incubator_mxnet_tpu.telemetry import tracing as ttracing
from incubator_mxnet_tpu.telemetry.__main__ import main as telemetry_main


def _build_params(n, shape=(8, 8), prefix="lp", seed=0):
    rs = np.random.RandomState(seed)
    ps = []
    for k in range(n):
        p = gluon.Parameter("%s%d" % (prefix, k), shape=shape)
        p.initialize(ctx=mx.cpu())
        p.data()._write(rs.randn(*shape).astype(np.float32))
        ps.append(p)
    return ps


def _train_steps(ps, trainer, n):
    for _ in range(n):
        with autograd.record():
            loss = None
            for p in ps:
                y = (p.data() * p.data()).sum()
                loss = y if loss is None else loss + y
        loss.backward()
        trainer.step(1)
    ps[-1].data().asnumpy()


# ---------------------------------------------------------------------------
# step-id threading (flushes + collectives + journals share the key)
# ---------------------------------------------------------------------------

def test_step_id_threaded_through_ring_events():
    blackbox.set_enabled(True)
    blackbox._ring.clear()
    blackbox._tls.steps_closed = 0      # this thread's ids start over
    assert ttracing.current_step() == 1
    try:
        ps = _build_params(4, prefix="lt")
        t = gluon.Trainer(ps, "sgd", {"learning_rate": 0.01},
                          kvstore=mx.kv.create("local"))
        _train_steps(ps, t, 3)
        evs = blackbox.events()
        steps = [e["data"] for e in evs if e["kind"] == "step"]
        assert [s["step"] for s in steps] == [1, 2, 3]
        assert not any("lens" in s for s in steps)
        # collectives carry the step they ran under plus a lockstep seq
        colls = [e["data"] for e in evs if e["kind"] == "collective"]
        assert colls
        assert all("seq" in c for c in colls)
        seqs = [c["seq"] for c in colls]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert {c["step"] for c in colls} <= {1, 2, 3}
        # every span of a step carries the id its journal closed with
        by_step = {}
        for name, _t0, _t1, _parent, step in blackbox.spans():
            by_step.setdefault(step, set()).add(name)
        assert all({"bwd", "kvstore", "update"} <= by_step[k]
                   for k in (1, 2, 3)), by_step
        assert ttracing.current_step() == 4
    finally:
        blackbox.set_enabled(None)


def test_step_id_advances_with_the_recorder_off():
    """Chrome spans still carry step ids under GRAFT_BLACKBOX=0: the
    journal's place is taken by a boundary that only counts."""
    blackbox.set_enabled(False)
    try:
        ps = _build_params(2, prefix="lo")
        t = gluon.Trainer(ps, "sgd", {"learning_rate": 0.01},
                          kvstore=mx.kv.create("local"))
        before = ttracing.current_step()
        _train_steps(ps, t, 2)
        assert ttracing.current_step() == before + 2
    finally:
        blackbox.set_enabled(None)


# ---------------------------------------------------------------------------
# chrome-trace metadata + flow-step validation (satellite)
# ---------------------------------------------------------------------------

def test_process_metadata_events_label_tracks():
    evs = ttracing.process_metadata_events(rank=3, role="blackbox", pid=3)
    names = {e["name"]: e for e in evs}
    assert names["process_name"]["args"]["name"] == "rank 3 (blackbox)"
    assert names["process_sort_index"]["args"]["sort_index"] == 3
    assert names["thread_name"]["pid"] == 3


def test_validator_accepts_metadata_and_multi_hop_flows():
    trace = {"traceEvents": (
        ttracing.process_metadata_events(rank=0)
        + [{"name": "c", "cat": "x", "ph": "X", "ts": 1.0, "dur": 2.0,
            "pid": 0, "tid": 0},
           {"name": "l", "cat": "f", "ph": "s", "id": "a", "ts": 1.0,
            "pid": 0, "tid": 0},
           {"name": "l", "cat": "f", "ph": "t", "id": "a", "ts": 2.0,
            "pid": 1, "tid": 0},
           {"name": "l", "cat": "f", "ph": "f", "bp": "e", "id": "a",
            "ts": 3.0, "pid": 2, "tid": 0}])}
    assert ttracing.validate_chrome_trace(trace) == []
    # a hop without a start is still a problem
    bad = {"traceEvents": [
        {"name": "l", "cat": "f", "ph": "t", "id": "zz", "ts": 1.0,
         "pid": 0, "tid": 0}]}
    assert any("without a start" in p
               for p in ttracing.validate_chrome_trace(bad))
    # M events must carry args
    assert any("(M)" in p for p in ttracing.validate_chrome_trace(
        {"traceEvents": [{"name": "process_name", "ph": "M", "pid": 0}]}))


def test_profiler_dump_carries_metadata_and_wall_anchor(tmp_path):
    from incubator_mxnet_tpu import profiler
    path = str(tmp_path / "trace.json")
    profiler.set_config(filename=path, profile_all=True)
    profiler.set_state("run")
    (mx.nd.ones((4, 4)) + 1).asnumpy()
    profiler.set_state("stop")
    profiler.dump()
    with open(path) as f:
        doc = json.load(f)
    assert ttracing.validate_chrome_trace(doc) == []
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               for e in doc["traceEvents"])
    anchor = doc["otherData"]["wall_anchor"]
    assert abs(anchor["wall_s"] - time.time()) < 60.0
    assert doc["otherData"]["rank"] == blackbox._rank[0]


# ---------------------------------------------------------------------------
# multi-rank dump path (satellite)
# ---------------------------------------------------------------------------

def test_blackbox_dump_path_rank_suffix(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_BLACKBOX_PATH", str(tmp_path / "bb.json"))
    try:
        blackbox.set_rank(0)
        assert blackbox.default_path() == str(tmp_path / "bb.json")
        blackbox.set_rank(2)
        assert blackbox.default_path() == str(tmp_path / "bb.rank2.json")
        # a path already naming this rank (old per-worker guidance) is
        # kept verbatim; a {rank} placeholder substitutes exactly
        monkeypatch.setenv("GRAFT_BLACKBOX_PATH",
                           str(tmp_path / "bb_rank2.json"))
        assert blackbox.default_path() == str(tmp_path / "bb_rank2.json")
        monkeypatch.setenv("GRAFT_BLACKBOX_PATH",
                           str(tmp_path / "bb.{rank}.json"))
        assert blackbox.default_path() == str(tmp_path / "bb.2.json")
        blackbox.set_clock_offset(0.125)
        doc = blackbox.snapshot()
        assert doc["rank"] == 2 and doc["clock_offset_s"] == 0.125
    finally:
        blackbox.set_rank(0)
        blackbox._clock_offset[0] = None


# ---------------------------------------------------------------------------
# cross-rank aggregation + straggler table
# ---------------------------------------------------------------------------

def test_aggregate_selftest_passes():
    assert aggregate.selftest() == []


def test_aggregate_blames_delayed_rank(tmp_path):
    delay = 0.2
    paths = []
    for rank in (0, 1):
        p = tmp_path / ("rank%d.json" % rank)
        p.write_text(json.dumps(aggregate._synthetic_dump(rank, delay)))
        paths.append(str(p))
    merged_path = str(tmp_path / "merged.json")
    report, trace = aggregate.analyze(paths, merged_out=merged_path)
    assert report["problems"] == []
    assert ttracing.validate_chrome_trace(trace) == []
    s = report["straggler_summary"]
    assert s["worst_rank"] == 1
    assert s["max_enter_spread_s"] == pytest.approx(delay, abs=0.02)
    assert s["blame"]["1"] == s["collectives_matched"] > 0
    # per-rank process tracks + >=1 flow link per reduced bucket
    pids = {e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert pids == {0, 1}
    labels = {r["label"] for r in report["stragglers"]}
    assert len(labels) == 2
    assert report["cross_rank_flow_links"] >= len(labels)
    flow_ids = {e["id"] for e in trace["traceEvents"]
                if e.get("ph") in ("s", "t", "f")}
    assert any(str(f).startswith("xr/") for f in flow_ids)
    with open(merged_path) as f:
        assert ttracing.validate_chrome_trace(json.load(f)) == []


def test_worst_rank_is_who_cost_the_most_seconds_not_the_most_rows(tmp_path):
    """Eight pulls at a store's start tie to a tenth of a millisecond and
    rank 0 loses each tie; rank 1 then enters three reduces 0.2 s late.
    Rank 0 has the rows, rank 1 made everyone wait."""
    delay, base = 0.2, 1700000000.0
    paths = []
    for rank in (0, 1):
        doc = aggregate._synthetic_dump(rank, delay,
                                        buckets=("reduce_many",))
        for e in doc["events"]:             # the reduces follow the pulls
            if e["kind"] == "collective":
                e["data"]["seq"] += 8
        ties = []
        for seq in range(1, 9):
            exit_ = base + 0.01 * seq + (1e-4 if rank == 0 else 0.0)
            ties.append({"ts": exit_, "kind": "collective", "data": {
                "path": "pull", "seq": seq, "step": 1, "n_keys": 1,
                "nbytes": 256, "rank": rank, "latency_ms": 0.0}})
        doc["events"] = ties + doc["events"]
        doc["events_total"] = len(doc["events"])
        p = tmp_path / ("rank%d.json" % rank)
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    report, _trace = aggregate.analyze(paths)
    assert report["problems"] == []
    s = report["straggler_summary"]
    assert s["blame"] == {"0": 8, "1": 3}
    assert s["wait_caused_s"]["1"] == pytest.approx(3 * delay, abs=0.05)
    assert s["wait_caused_s"]["0"] < 0.01
    assert s["worst_rank"] == 1


def test_async_collectives_never_corrupt_clock_or_exit_blame(tmp_path):
    """Overlapped (reduce_many_async) events are stamped at host-local
    wait-return time: they must not serve as clock anchors (a healthy
    40ms host lag before wait() would fabricate a 40ms offset) nor as
    exit-spread evidence."""
    base = 1700000000.0
    lag = 0.04                    # rank 0 reaches wait() 40ms late
    docs = {}
    for rank in (0, 1):
        events = []
        for step in range(1, 4):
            t = base + step * 0.5
            # async reduce: both ranks ISSUE together (enter == t), but
            # rank 0's host returns from wait() `lag` later
            exit_ = t + 0.1 + (lag if rank == 0 else 0.0)
            events.append({"ts": exit_, "kind": "collective", "data": {
                "path": "reduce_many_async", "seq": step, "step": step,
                "bucket": "bucket[float32:8p:2048B]",
                "latency_ms": (exit_ - t) * 1e3}})
            events.append({"ts": t + 0.3, "kind": "dist_heartbeat",
                           "data": {"workers": 2, "step": step}})
        docs[rank] = dict(aggregate._synthetic_dump(rank, 0.0),
                          events=events, events_total=len(events))
        (tmp_path / ("a%d.json" % rank)).write_text(json.dumps(docs[rank]))
    report, _trace = aggregate.analyze([str(tmp_path / "a0.json"),
                                        str(tmp_path / "a1.json")])
    assert report["problems"] == []
    # clocks really are synced: the async wait lag must not leak in
    assert abs(report["clock_offsets_s"]["1"]) < 1e-6, report
    rows = report["stragglers"]
    assert rows
    for r in rows:
        assert r["last_to_exit"] is None and r["exit_spread_s"] is None
        assert r["enter_spread_s"] == pytest.approx(0.0, abs=1e-6)


def test_aggregate_mixed_trace_and_dump(tmp_path):
    """A real profiler trace of this process merges with a synthetic
    peer dump: collective chrome spans carry seq/step so the join works
    across artifact kinds."""
    from incubator_mxnet_tpu import profiler
    blackbox.set_enabled(True)
    blackbox._ring.clear()
    tracefile = str(tmp_path / "r0_trace.json")
    try:
        seq0 = next(blackbox._collective_seq)
        profiler.set_config(filename=tracefile, profile_all=True)
        profiler.set_state("run")
        kv = mx.kv.create("local")
        kv.init("w", mx.nd.ones((8,)))
        kv.push("w", mx.nd.ones((8,)))
        out = mx.nd.zeros((8,))
        kv.pull("w", out=out)
        out.asnumpy()
        profiler.set_state("stop")
        profiler.dump()
    finally:
        blackbox.set_enabled(None)
    with open(tracefile) as f:
        doc = json.load(f)
    colls = [e for e in doc["traceEvents"]
             if e.get("cat") == "collective" and e.get("ph") == "X"]
    assert colls and all("seq" in e["args"] for e in colls)
    # a synthetic rank-1 dump whose collectives reuse the same seqs
    wall = aggregate._wall_fn(doc["otherData"]["wall_anchor"])
    events = []
    for e in colls:
        events.append({"ts": wall(e["ts"] + e.get("dur", 0.0)) + 0.05,
                       "kind": "collective",
                       "data": {"path": e["args"]["path"],
                                "seq": e["args"]["seq"], "rank": 1,
                                "nbytes": e["args"].get("nbytes"),
                                "latency_ms": 1.0}})
    peer = dict(aggregate._synthetic_dump(1, 0.0), events=events,
                events_total=len(events))
    p1 = tmp_path / "rank1.json"
    p1.write_text(json.dumps(peer))
    report, trace = aggregate.analyze([tracefile, str(p1)])
    assert report["problems"] == []
    assert report["cross_rank_flow_links"] >= 1
    assert seq0 >= 0
    # a rank's trace AND dump together are legitimate ('mixed freely'):
    # they merge onto ONE track — no phantom rank, no self-match
    own = dict(aggregate._synthetic_dump(0, 0.0), events=[
        {"ts": wall(e["ts"] + e.get("dur", 0.0)), "kind": "collective",
         "data": {"path": e["args"]["path"], "seq": e["args"]["seq"],
                  "rank": 0, "latency_ms": e.get("dur", 0.0) / 1e3}}
        for e in colls])
    p0 = tmp_path / "rank0_dump.json"
    p0.write_text(json.dumps(own))
    report, trace = aggregate.analyze([tracefile, str(p0), str(p1)])
    assert report["problems"] == []
    assert sorted(report["ranks"]) == ["0", "1"]
    assert len(report["ranks"]["0"]["sources"]) == 2
    pids = {e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert pids == {0, 1}
    for row in report["stragglers"]:
        assert sorted(row["ranks"]) == [0, 1]   # never rank 0 vs itself


def test_cli_analyze_and_steps(tmp_path, capsys):
    for rank in (0, 1):
        (tmp_path / ("r%d.json" % rank)).write_text(
            json.dumps(aggregate._synthetic_dump(rank, 0.1)))
    merged = str(tmp_path / "merged.json")
    rc = telemetry_main(["--analyze", str(tmp_path / "r0.json"),
                         str(tmp_path / "r1.json"), "--merged", merged,
                         "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    report = json.loads(out)
    assert report["straggler_summary"]["worst_rank"] == 1
    assert os.path.exists(merged)
    rc = telemetry_main(["--analyze", str(tmp_path / "r0.json"),
                         str(tmp_path / "r1.json")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "straggler table" in out and "worst rank: 1" in out


# ---------------------------------------------------------------------------
# the 2-proc dist harness: a deliberately delayed rank must be named
# ---------------------------------------------------------------------------

_PRELUDE = textwrap.dedent("""
    import os, sys, traceback
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
""")


def _skipwrap(body):
    return _PRELUDE + "try:\n" \
        + textwrap.indent(textwrap.dedent(body), "    ") \
        + textwrap.dedent("""
            except Exception:
                if "Multiprocess computations aren't implemented" \\
                        in traceback.format_exc():
                    print("SKIP-MULTIPROC", flush=True)
                    os._exit(0)
                raise
        """)


_LENS_WORKER = """
    import time
    from incubator_mxnet_tpu import autograd, gluon
    from incubator_mxnet_tpu.telemetry import blackbox

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw
    rs = np.random.RandomState(0)
    ps = []
    for k in range(8):
        p = gluon.Parameter("p%%d" %% k, shape=(8, 8))
        p.initialize(ctx=mx.cpu())
        p.data()._write(rs.randn(8, 8).astype(np.float32))
        ps.append(p)
    t = gluon.Trainer(ps, "sgd", {"learning_rate": 0.01}, kvstore=kv)
    t._bucket_bytes_override = 1024
    t._overlap_override = False      # serial reduces: enter times carry
    #                                  the full straggler signal
    for step in range(4):
        if rank == 1:
            time.sleep(0.2)          # rank 1 is the deliberate straggler
        with autograd.record():
            loss = None
            for p in ps:
                y = (p.data() * p.data()).sum()
                loss = y if loss is None else loss + y
        loss.backward()
        t.step(1)
    ps[-1].data().asnumpy()

    out = blackbox.dump(path=r"%(dir)s/lens_bb.rank%%d.json" %% rank,
                        reason="manual")
    assert out, "dump failed"
    print("WORKER %%d LENS OK" %% rank, flush=True)
"""


def _launch_two(tmp_path, source, timeout=300, port_base=9900):
    worker = tmp_path / "worker.py"
    worker.write_text(source)
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(repo) + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    port = port_base + os.getpid() % 500
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "-p", str(port), sys.executable, str(worker)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        pytest.fail("2-process lens run deadlocked (%ds timeout)"
                    % timeout)
    out = stdout + stderr
    if "SKIP-MULTIPROC" in out:
        pytest.skip("backend lacks multiprocess CPU collectives")
    assert proc.returncode == 0, out[-3000:]
    return out


def test_two_process_straggler_analysis(tmp_path):
    """ISSUE-8 acceptance: train on the real 2-proc dist_sync wire with
    rank 1 deliberately delayed, dump both flight recorders, and the
    aggregator must name rank 1 in a schema-valid merged trace with
    cross-rank flow links per reduce.  The store's initialisation adds
    eight ``pull`` rows that tie within a fraction of a millisecond: they
    are in the table and carry no blame that counts."""
    src = _skipwrap(_LENS_WORKER % {"dir": str(tmp_path)})
    out = _launch_two(tmp_path, src, timeout=300)
    assert "WORKER 0 LENS OK" in out and "WORKER 1 LENS OK" in out, \
        out[-3000:]
    p0 = tmp_path / "lens_bb.rank0.json"
    p1 = tmp_path / "lens_bb.rank1.json"
    assert p0.exists() and p1.exists()
    merged = str(tmp_path / "merged.json")
    report, trace = aggregate.analyze([str(p0), str(p1)],
                                      merged_out=merged)
    assert report["problems"] == []
    assert ttracing.validate_chrome_trace(trace) == []
    s = report["straggler_summary"]
    assert s["worst_rank"] == 1, report["straggler_summary"]
    assert s["max_enter_spread_s"] > 0.05
    # every step's reduce got a matched row + flow link.  The worker
    # reduces serially, so a step's buckets go out as ONE ``reduce_many``
    # with no bucket label (``bucket[...]`` names an overlapped bucket's
    # ``reduce_many_async``)
    reduce_rows = [r for r in report["stragglers"]
                   if r["label"] == "reduce_many"]
    assert sorted(r["step"] for r in reduce_rows) == [1, 2, 3, 4], \
        report["stragglers"]
    # (the first step's spread is the two ranks' compile times as well)
    steady = [r for r in reduce_rows if r["step"] > 1]
    assert all(r["last_to_enter"] == 1 for r in steady)
    assert min(r["enter_spread_s"] for r in steady) > 0.05
    assert report["cross_rank_flow_links"] >= len(reduce_rows)
    pids = {e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert pids == {0, 1}
