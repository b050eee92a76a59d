"""grafttsan — happens-before race detector (analysis/tsan.py), the
lockstep divergence auditor (analysis/lockstep.py + the dist heartbeat
piggyback + telemetry/aggregate.py cross-check), and the GL2xx static
concurrency lint (analysis/concurrency.py).

Contract per the EH2xx half: one deliberately-injected race per rule
must yield EXACTLY that diagnostic with both racing stacks, the
sanctioned patterns (same-thread writes, wait-then-write, explicit sync
edges) must stay silent, and a real overlapped/duplex training loop
under GRAFT_TSAN=1 must produce zero reports (the clean-run parity the
tier-1 acceptance rides).
"""
import json
import textwrap
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, engine, gluon, nd, overlap
from incubator_mxnet_tpu.analysis import concurrency, lockstep, tsan
from incubator_mxnet_tpu.telemetry import aggregate, blackbox


@pytest.fixture
def tsan_on():
    tsan.set_enabled(True)
    tsan.clear()
    try:
        yield tsan
    finally:
        tsan.set_enabled(None)
        tsan.clear()


@pytest.fixture
def lockstep_clean():
    lockstep.reset()
    try:
        yield lockstep
    finally:
        lockstep.reset()


def _codes():
    return [r.code for r in tsan.reports()]


def _in_thread(fn, name="racer"):
    box = []

    def run():
        try:
            fn()
        except BaseException as exc:       # surfaced by the caller
            box.append(exc)
    t = threading.Thread(target=run, name=name)
    t.start()
    t.join()
    if box:
        raise box[0]


# ---------------------------------------------------------------------------
# EH201 — write to an in-flight handle value
# ---------------------------------------------------------------------------

def test_eh201_cross_thread_write_to_inflight_handle(tsan_on):
    kv = mx.kv.create("local")
    arr = nd.array(np.ones((4,), np.float32))
    handle = kv.reduce_many_async([arr], label="bucket[f32:1p]")
    try:
        _in_thread(lambda: arr._write(jnp.zeros((4,), jnp.float32)))
    finally:
        handle.abandon()
    assert _codes() == ["EH201"]
    rep = tsan.reports()[0]
    assert "bucket[f32:1p]" in rep.message
    assert rep.stack and rep.other_stack, "a racing stack went missing"
    assert rep.thread == "racer" and rep.other_thread == "MainThread"


def test_eh201_same_thread_and_post_wait_writes_are_clean(tsan_on):
    kv = mx.kv.create("local")
    arr = nd.array(np.ones((4,), np.float32))
    handle = kv.reduce_many_async([arr])
    arr._write(jnp.zeros((4,), jnp.float32))   # issuing thread: program
    handle.wait()                              # order, the version rails
    _in_thread(lambda: arr._write(jnp.ones((4,), jnp.float32)))
    assert _codes() == []                      # settled handle: free


def test_eh201_window_covers_the_blocking_wait(tsan_on):
    """wait() flips ``done`` before the blocking section, but the wire
    owns the bytes until the block returns — a third thread writing
    while another thread is still INSIDE wait() is a race; the waiting
    thread's own post-acquire writes are not."""
    from incubator_mxnet_tpu.kvstore import _AsyncHandle
    arr = nd.array(np.ones((4,), np.float32))
    entered, release = threading.Event(), threading.Event()

    class _Blocking(_AsyncHandle):
        __slots__ = ()

        def _materialize(self):
            entered.set()
            release.wait(5)

    handle = _Blocking([arr], label="blocking")
    waiter = threading.Thread(target=handle.wait, name="waiter")
    waiter.start()
    assert entered.wait(5)
    _in_thread(lambda: arr._write(jnp.zeros((4,), jnp.float32)))
    release.set()
    waiter.join()
    assert _codes() == ["EH201"]
    # after the wait completed the registry is settled: free to write
    _in_thread(lambda: arr._write(jnp.ones((4,), jnp.float32)))
    assert _codes() == ["EH201"]


def test_eh201_suppressed_by_explicit_sync_edge(tsan_on):
    """The vector-clock machinery, not a thread-id shortcut: a release/
    acquire pair between issuer and writer orders the accesses and the
    report must NOT fire."""
    kv = mx.kv.create("local")
    arr = nd.array(np.ones((4,), np.float32))
    handle = kv.reduce_many_async([arr])
    tsan.sync_release("chan")

    def writer():
        tsan.sync_acquire("chan")
        arr._write(jnp.zeros((4,), jnp.float32))
    _in_thread(writer)
    handle.abandon()
    assert _codes() == []


# ---------------------------------------------------------------------------
# EH202 — concurrent scheduler regions, through the real scheduler
# ---------------------------------------------------------------------------

class _BlockingHost(object):
    """BucketScheduler host whose _sched_eligible parks inside arm()
    until released — the window in which a second thread's entry is the
    injected race."""
    _sched_autograd_hooks = False

    def __init__(self):
        self.inside = threading.Event()
        self.release = threading.Event()

    def _sched_entries(self, b):
        return []

    def _sched_eligible(self, b):
        self.inside.set()
        self.release.wait(5)
        return False

    def _sched_kv(self):
        return None

    def _sched_flat(self, b):
        return None

    def _sched_pass_id(self):
        return 0

    def _sched_label(self, b):
        return "b"


def test_eh202_hook_races_consumer(tsan_on):
    host = _BlockingHost()
    sched = overlap.BucketScheduler(host)
    plan = ([overlap.Bucket((0,), None, np.dtype("f4"), 4)], [])

    t = threading.Thread(target=lambda: sched.arm(plan), name="armer")
    t.start()
    host.inside.wait(5)
    sched.disarm()              # concurrent entry while arm() is inside
    host.release.set()
    t.join()
    assert "EH202" in _codes()
    rep = next(r for r in tsan.reports() if r.code == "EH202")
    assert "disarm" in rep.message and "arm" in rep.message
    assert rep.stack and rep.other_stack


def test_eh202_single_threaded_reentry_is_clean(tsan_on):
    """arm() -> disarm() nests regions on ONE thread — the sanctioned
    shape must stay silent."""
    host = _BlockingHost()
    host.release.set()          # don't park
    sched = overlap.BucketScheduler(host)
    plan = ([overlap.Bucket((0,), None, np.dtype("f4"), 4)], [])
    sched.arm(plan)
    sched.take(plan)
    sched.disarm()
    assert _codes() == []


# ---------------------------------------------------------------------------
# EH203 — foreign-thread resolve of an open segment
# ---------------------------------------------------------------------------

def test_eh203_foreign_thread_resolves_open_segment(tsan_on):
    a = nd.array(np.ones((4, 4), np.float32))
    with engine.bulk(8):
        b = a * a
        _in_thread(b.asnumpy, name="reader")
    assert _codes() == ["EH203"]
    rep = tsan.reports()[0]
    assert "offband" in rep.message
    assert rep.stack and rep.other_stack
    # the remembered side is the segment-open site (this test function)
    assert any("bulk" in line or "test_eh203" in line
               for line in rep.other_stack)


def test_eh203_same_thread_and_offband_are_clean(tsan_on):
    a = nd.array(np.ones((4, 4), np.float32))
    with engine.bulk(8):
        b = a * a
        b.asnumpy()             # owner-thread read: ordinary flush
        with engine.offband():
            c = a + a           # off-band dispatch alongside the scope
            _in_thread(c.asnumpy, name="reader")   # concrete: no segment
    assert _codes() == []


# ---------------------------------------------------------------------------
# EH204 — tracked shared arrays
# ---------------------------------------------------------------------------

def test_eh204_unsynchronized_tracked_write(tsan_on):
    arr = tsan.track(nd.array(np.zeros((2,), np.float32)), label="cell")
    arr._write(jnp.ones((2,), jnp.float32))
    _in_thread(lambda: arr._write(jnp.zeros((2,), jnp.float32)))
    tsan.untrack(arr)
    assert _codes() == ["EH204"]
    rep = tsan.reports()[0]
    assert "cell" in rep.message
    assert rep.stack and rep.other_stack


def test_eh204_sync_edge_orders_the_accesses(tsan_on):
    arr = tsan.track(nd.array(np.zeros((2,), np.float32)))
    arr._write(jnp.ones((2,), jnp.float32))
    tsan.sync_release("handoff")

    def consumer():
        tsan.sync_acquire("handoff")
        arr._read()
        arr._write(jnp.zeros((2,), jnp.float32))
    _in_thread(consumer)
    tsan.untrack(arr)
    assert _codes() == []


def test_abort_raises_at_the_race(tsan_on, monkeypatch):
    monkeypatch.setenv("GRAFT_TSAN_ABORT", "1")
    arr = tsan.track(nd.array(np.zeros((2,), np.float32)))
    arr._write(jnp.ones((2,), jnp.float32))
    with pytest.raises(tsan.TsanError) as ei:
        _in_thread(lambda: arr._write(jnp.zeros((2,), jnp.float32)))
    assert ei.value.code == "EH204"
    tsan.untrack(arr)


def test_reports_land_in_blackbox_ring(tsan_on):
    prev = blackbox._enabled_override
    blackbox.set_enabled(True)
    try:
        arr = tsan.track(nd.array(np.zeros((2,), np.float32)))
        arr._write(jnp.ones((2,), jnp.float32))
        _in_thread(lambda: arr._write(jnp.zeros((2,), jnp.float32)))
        tsan.untrack(arr)
        evs = [e for e in blackbox.events() if e["kind"] == "tsan_report"]
        assert evs and evs[-1]["data"]["code"] == "EH204"
        assert evs[-1]["data"]["stack_tail"], "dump-side stack missing"
    finally:
        blackbox.set_enabled(prev)


def test_tsan_selftest_smoke():
    assert tsan.selftest() == []


# ---------------------------------------------------------------------------
# clean-run parity: the real overlapped/duplex machinery under GRAFT_TSAN
# ---------------------------------------------------------------------------

def _mini_params(prefix, specs, rs):
    params = []
    for k, shape in enumerate(specs):
        p = gluon.Parameter("%s%d" % (prefix, k), shape=shape)
        p.initialize(ctx=mx.cpu())
        p.data()._write(jnp.asarray(rs.randn(*shape).astype(np.float32)))
        params.append(p)
    return params


def test_clean_run_parity_overlapped_and_duplex(tsan_on):
    """tier-1's concurrency surface in miniature — bulked segments,
    grad-ready hooks issuing async reduces mid-backward, the duplex
    store-update path with first-touch pulls, and a worker-threaded
    DataLoader — must produce ZERO EH2xx reports."""
    rs = np.random.RandomState(3)
    specs = [(5,), (3, 4), (7,), (2, 3)]

    # overlapped local-update path (BucketScheduler + reduce_many_async)
    pa = _mini_params("cl", specs, rs)
    consts = [nd.array(rs.randn(*s).astype(np.float32)) for s in specs]
    ta = gluon.Trainer(pa, "sgd", {"learning_rate": 0.05},
                       kvstore=mx.kv.create("dist_sync"))
    ta._bucket_bytes_override = 48
    ta._overlap_override = True
    for _ in range(4):
        with engine.bulk(32):
            with autograd.record():
                loss = None
                for p, c in zip(pa, consts):
                    y = (p.data() * p.data() * c).sum()
                    loss = y if loss is None else loss + y
            loss.backward()
        ta.step(2)
    assert ta._scheduler.issued_total > 0, "overlap never engaged"

    # duplex store-update path (apply_reduced + PullScheduler)
    pb = _mini_params("cd", specs, rs)
    tb = gluon.Trainer(pb, "sgd", {"learning_rate": 0.05},
                       kvstore=mx.kv.create("local"),
                       update_on_kvstore=True)
    tb._bucket_bytes_override = 48
    for _ in range(3):
        with autograd.record():
            loss = None
            for p, c in zip(pb, consts):
                y = (p.data() * p.data() * c).sum()
                loss = y if loss is None else loss + y
        loss.backward()
        tb.step(2)
    tb._pull_scheduler.finish()

    # worker-threaded data pipeline
    ds = gluon.data.ArrayDataset(
        rs.rand(16, 4).astype(np.float32),
        rs.rand(16, 1).astype(np.float32))
    dl = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
    for _x, _y in dl:
        _x.asnumpy()
    dl.close()

    assert tsan.reports() == [], tsan.reports()


# ---------------------------------------------------------------------------
# lockstep auditor (unit)
# ---------------------------------------------------------------------------

def test_lockstep_fold_is_deterministic(lockstep_clean):
    stream = [(1, "reduce_many", 2, 4096, None),
              (2, "pull", 3, 1024, ["0", "1", "2"]),
              (3, "reduce_many_async", 1, 2048, ["bucket[f32]"])]
    for seq, path, nk, nb, keys in stream:
        lockstep.fold(seq, path, n_keys=nk, nbytes=nb, keys=keys)
    _seq_a, hash_a = lockstep.state()
    lockstep.reset()
    for seq, path, nk, nb, keys in stream:
        lockstep.fold(seq, path, n_keys=nk, nbytes=nb, keys=keys)
    _seq_b, hash_b = lockstep.state()
    assert hash_a == hash_b, "same stream, different hash"
    lockstep.reset()
    for seq, path, nk, nb, keys in [stream[0], stream[2], stream[1]]:
        lockstep.fold(seq, path, n_keys=nk, nbytes=nb, keys=keys)
    _seq_c, hash_c = lockstep.state()
    assert hash_c != hash_a, "order divergence must change the hash"


def test_lockstep_excludes_ps_paths(lockstep_clean):
    lockstep.fold(1, "ps_push", n_keys=4, nbytes=1024)
    assert lockstep.state() == (0, 0)


def test_lockstep_observe_names_rank_and_first_position(lockstep_clean):
    prev = blackbox._enabled_override
    blackbox.set_enabled(True)
    try:
        # fold 3 agrees; fold 5 diverges on rank 1
        assert lockstep.observe({0: (3, 111), 1: (3, 111)},
                                my_rank=0) is None
        rep = lockstep.observe({0: (5, 222), 1: (5, 999)}, my_rank=0)
        assert rep is not None
        assert rep["first_divergent_fold"] == 5
        assert rep["divergent_ranks"] == [1]
        assert lockstep.divergence() is rep
        evs = [e for e in blackbox.events()
               if e["kind"] == "lockstep_divergence"]
        assert evs and evs[-1]["data"]["first_divergent_fold"] == 5
        # latched: a later mismatch does not re-report
        assert lockstep.observe({0: (6, 1), 1: (6, 2)}, my_rank=0) is None
    finally:
        blackbox.set_enabled(prev)


def test_lockstep_observe_catches_skipped_collective(lockstep_clean):
    """A rank that SKIPS one collective misaligns its fold counts with
    everyone else's forever after — the exact-position match may never
    recur.  The self-table lookback still catches it: the peer's hash
    at fold F is checked against the LOCAL rolling at fold F."""
    for i in range(1, 6):
        lockstep.fold(i, "reduce_many", n_keys=1, nbytes=64 * i)
    rows = lockstep.table()
    my_roll_at_4 = rows[3]["rolling"]
    # a healthy laggard (same stream, one behind) must NOT report
    assert lockstep.observe({0: (5, rows[4]["rolling"]),
                             1: (4, my_roll_at_4)}, my_rank=0) is None
    # rank 1 skipped one bucket: at fold 4 its stream covered DIFFERENT
    # collectives, so its hash differs from our rolling at fold 4
    rep = lockstep.observe({0: (5, rows[4]["rolling"]),
                            1: (4, my_roll_at_4 ^ 0x5a5a)}, my_rank=0)
    assert rep is not None
    assert rep["divergent_ranks"] == [1]
    assert rep["first_divergent_fold"] == 4


def test_lockstep_order_guard(lockstep_clean):
    assert lockstep.note_order("ps_push_async", 0)
    assert lockstep.note_order("ps_push_async", 1)
    assert not lockstep.note_order("ps_push_async", 3)   # 2 skipped
    snap = lockstep.snapshot()
    assert snap["order_violations"] == [
        {"path": "ps_push_async", "expected": 2, "got": 3}]


def test_lockstep_table_rides_blackbox_dumps(lockstep_clean):
    prev = blackbox._enabled_override
    blackbox.set_enabled(True)
    try:
        lockstep.fold(7, "reduce_many", n_keys=1, nbytes=64)
        doc = blackbox.snapshot()
        assert doc["lockstep"]["folds"] == 1
        assert doc["lockstep"]["last_wire_seq"] == 7
        row = doc["lockstep"]["table"][-1]
        assert row["path"] == "reduce_many"
        assert row["fold"] == 1 and row["seq"] == 7
        assert blackbox.validate_dump(doc) == []
    finally:
        blackbox.set_enabled(prev)


def test_lockstep_fold_ignores_wire_seq_skew(lockstep_clean):
    """Two ranks with identical audited streams must hash identically
    even when rank-asymmetric ps_* brackets skewed their wire seq
    counters (the dist_async background client) — the hash mixes the
    fold index, never the wire seq."""
    for seq, path in [(1, "pull"), (5, "reduce_many")]:
        lockstep.fold(seq, path, n_keys=1, nbytes=64)
    reference = lockstep.state()
    lockstep.reset()
    for seq, path in [(3, "pull"), (9, "reduce_many")]:     # skewed
        lockstep.fold(seq, path, n_keys=1, nbytes=64)
    assert lockstep.state() == reference


def test_collective_brackets_feed_the_fold(lockstep_clean):
    kv = mx.kv.create("local")
    kv.init("lk", nd.ones((4,)))
    before = lockstep.state()
    kv.push("lk", nd.ones((4,)))
    out = nd.zeros((4,))
    kv.pull("lk", out=out)
    seq, rolling = lockstep.state()
    assert seq > before[0] and rolling != before[1]
    rows = lockstep.table()
    assert [r["path"] for r in rows[-2:]] == ["push", "pull"]


# ---------------------------------------------------------------------------
# offline cross-check (telemetry/aggregate.py)
# ---------------------------------------------------------------------------

def _divergent_dumps():
    """Two synthetic rank dumps: rank 1 swaps the two buckets of step 2
    (seqs 3/4 carry each other's label/nbytes) — the order-divergence
    injection."""
    d0 = aggregate._synthetic_dump(0, 0.0)
    d1 = aggregate._synthetic_dump(1, 0.0)
    swapped = 0
    for e in d1["events"]:
        if e["kind"] == "collective" and e["data"]["seq"] in (3, 4):
            e["data"]["bucket"] = (
                "bucket[float32:3p:3072B]" if e["data"]["seq"] == 3
                else "bucket[float32:4p:4096B]")
            e["data"]["nbytes"] = 3072 if e["data"]["seq"] == 3 else 4096
            swapped += 1
    assert swapped == 2
    return d0, d1


def test_aggregate_lockstep_check_names_divergent_collective():
    d0, d1 = _divergent_dumps()
    arts = [aggregate.parse_artifact(d0, source="r0"),
            aggregate.parse_artifact(d1, source="r1")]
    report = aggregate.lockstep_check(arts)
    assert report["first_divergent_seq"] == 3
    assert report["divergent_ranks"] == [1] or \
        report["divergent_ranks"] == [0, 1]
    assert report["mismatches"][0]["seq"] == 3
    # identical streams stay clean
    clean = [aggregate.parse_artifact(aggregate._synthetic_dump(r, 0.0),
                                      source="r%d" % r) for r in (0, 1)]
    rep2 = aggregate.lockstep_check(clean)
    assert rep2["first_divergent_seq"] is None
    assert rep2["seqs_checked"] > 0


def test_aggregate_lockstep_check_catches_holes():
    d0 = aggregate._synthetic_dump(0, 0.0)
    d1 = aggregate._synthetic_dump(1, 0.0)
    d1["events"] = [e for e in d1["events"]
                    if not (e["kind"] == "collective"
                            and e["data"]["seq"] == 3)]
    arts = [aggregate.parse_artifact(d0, source="r0"),
            aggregate.parse_artifact(d1, source="r1")]
    report = aggregate.lockstep_check(arts)
    assert {"seq": 3, "missing_rank": 1} in report["holes"]
    assert report["first_divergent_seq"] == 3
    assert 1 in report["divergent_ranks"]


def test_aggregate_lockstep_declines_async_wire_sets():
    """ps_* brackets skew the shared seq counter rank-dependently, so
    seq matching over a dist_async artifact set would blame healthy
    ranks — the offline check must decline with a note instead."""
    d0, d1 = _divergent_dumps()
    d0["events"].append({"ts": 1700000099.0, "kind": "collective",
                         "data": {"path": "ps_push_async", "seq": 99,
                                  "n_keys": 1, "nbytes": 64, "rank": 0,
                                  "latency_ms": 1.0}})
    arts = [aggregate.parse_artifact(d0, source="r0"),
            aggregate.parse_artifact(d1, source="r1")]
    report = aggregate.lockstep_check(arts)
    assert report["seqs_checked"] == 0
    assert report["first_divergent_seq"] is None
    assert "async wire" in report["note"]


def test_analyze_report_carries_lockstep_section(tmp_path):
    paths = []
    for r, doc in zip((0, 1), _divergent_dumps()):
        p = tmp_path / ("r%d.json" % r)
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    report, _trace = aggregate.analyze(paths)
    assert report["lockstep"]["first_divergent_seq"] == 3
    assert report["problems"] == []     # divergence is a finding, not a
    #                                     malformed-artifact problem


# ---------------------------------------------------------------------------
# GL2xx static lint
# ---------------------------------------------------------------------------

_GL_FIXTURE = textwrap.dedent("""
    import threading
    _a_lock = threading.Lock()
    _b_lock = threading.Lock()
    _hits = 0

    def forward():
        with _a_lock:
            with _b_lock:
                pass

    def backward():
        with _b_lock:
            with _a_lock:
                pass

    def worker():
        global _hits
        _hits += 1

    threading.Thread(target=worker, daemon=True).start()

    class PartialHost:
        def _sched_entries(self, b):
            return []
        def _sched_kv(self):
            return None

    class LeakyOwner:
        def __init__(self):
            threading.Thread(target=worker, daemon=True).start()

    class CleanOwner:
        def __init__(self):
            threading.Thread(target=worker, daemon=True).start()
        def close(self):
            pass
""")


def _by_code(diags):
    out = {}
    for d in diags:
        out.setdefault(d.code, []).append(d)
    return out


def test_gl2xx_fixture_rules_fire():
    by = _by_code([d for d in concurrency.lint_source(
        _GL_FIXTURE, filename="fix.py") if not d.suppressed])
    assert set(by) == {"GL201", "GL202", "GL203", "GL204"}
    assert "PartialHost" in by["GL203"][0].message
    assert "_sched_eligible" in by["GL203"][0].message
    assert "LeakyOwner" in by["GL204"][0].message
    assert not any("CleanOwner" in d.message for d in by["GL204"])
    assert "_hits" in by["GL202"][0].message


def test_gl2xx_guarded_global_is_clean():
    src = _GL_FIXTURE.replace(
        "    global _hits\n    _hits += 1",
        "    global _hits\n    with _a_lock:\n        _hits += 1")
    assert "with _a_lock" in src
    by = _by_code(concurrency.lint_source(src, filename="fix.py"))
    assert "GL202" not in by


def test_gl2xx_suppression_syntax():
    src = _GL_FIXTURE.replace(
        "    _hits += 1",
        "    # graftlint: disable=GL202 advisory counter\n"
        "    _hits += 1")
    assert "disable=GL202" in src
    g202 = [d for d in concurrency.lint_source(src, filename="fix.py")
            if d.code == "GL202"]
    assert g202 and all(d.suppressed for d in g202)
    assert g202[0].justification == "advisory counter"


_GL_INTERPROC_FIXTURE = textwrap.dedent("""
    import threading
    _a_lock = threading.Lock()
    _b_lock = threading.Lock()

    def grab_b():
        with _b_lock:
            pass

    def forward():
        with _a_lock:
            grab_b()            # a -> b, one call level deep

    def backward():
        with _b_lock:
            with _a_lock:       # b -> a, lexical
                pass

    class Pipe:
        def __init__(self):
            self._x_lock = threading.Lock()
            self._y_lock = threading.Lock()

        def _grab_y(self):
            with self._y_lock:
                pass

        def fwd(self):
            with self._x_lock:
                self._grab_y()  # x -> y via a self-method call

        def bwd(self):
            with self._y_lock:
                with self._x_lock:
                    pass
""")


def test_gl201_interprocedural_one_level():
    """PR 12: a call made while holding lock A contributes A -> every
    lock the callee's own body acquires — both for bare same-module
    functions and self-method calls — so cross-function inversions form
    GL201 cycles."""
    by = _by_code([d for d in concurrency.lint_source(
        _GL_INTERPROC_FIXTURE, filename="ip.py") if not d.suppressed])
    assert "GL201" in by
    msgs = " | ".join(d.message for d in by["GL201"])
    assert "_a_lock" in msgs and "_b_lock" in msgs
    assert "_x_lock" in msgs and "_y_lock" in msgs
    # drop the lexical halves: the interprocedural edges alone are
    # acyclic, so no GL201 — one level propagates, nothing fabricates
    clean = _GL_INTERPROC_FIXTURE.replace(
        "def backward():\n"
        "    with _b_lock:\n"
        "        with _a_lock:       # b -> a, lexical\n"
        "            pass\n", "").replace(
        "    def bwd(self):\n"
        "        with self._y_lock:\n"
        "            with self._x_lock:\n"
        "                pass\n", "")
    assert "backward" not in clean and "bwd" not in clean
    by2 = _by_code(concurrency.lint_source(clean, filename="ip.py"))
    assert "GL201" not in by2


def test_gl201_nested_def_does_not_collide_with_top_level():
    """A local closure's lock summary must NOT merge with a same-named
    top-level function: the fabricated edge would report a deadlock
    cycle that does not exist in the call graph."""
    src = textwrap.dedent("""
        import threading
        _a_lock = threading.Lock()
        _b_lock = threading.Lock()

        def helper():
            pass                    # top-level helper: NO locks

        def runner():
            def helper():           # unrelated local closure
                with _a_lock:
                    pass
            helper()

        def forward():
            with _b_lock:
                helper()            # resolves to the TOP-LEVEL helper

        def backward():
            with _a_lock:
                with _b_lock:
                    pass
    """)
    by = _by_code(concurrency.lint_source(src, filename="nest.py"))
    assert "GL201" not in by


def test_gl201_interprocedural_stays_one_level():
    """Deeper call chains are documented out of scope: holding A and
    calling f, where only f's CALLEE takes B, must not edge A -> B."""
    src = textwrap.dedent("""
        import threading
        _a_lock = threading.Lock()
        _b_lock = threading.Lock()

        def leaf():
            with _b_lock:
                pass

        def middle():
            leaf()              # no locks of its own

        def forward():
            with _a_lock:
                middle()        # two levels to _b_lock: out of scope

        def backward():
            with _b_lock:
                with _a_lock:
                    pass
    """)
    by = _by_code(concurrency.lint_source(src, filename="deep.py"))
    assert "GL201" not in by


def test_gl2xx_repo_is_clean():
    active = [d for d in concurrency.lint_package() if not d.suppressed]
    assert active == [], "\n".join(repr(d) for d in active)


def test_sched_protocol_constant_matches_hosts():
    """The lint's protocol list must track the real hosts — a drift here
    means GL203 checks a stale surface."""
    from incubator_mxnet_tpu.gluon.trainer import Trainer
    from incubator_mxnet_tpu.module.module import Module
    for cls in (Trainer, Module):
        for name in concurrency.SCHED_PROTOCOL:
            assert hasattr(cls, name), (cls, name)


# ---------------------------------------------------------------------------
# graftduplex: the dist_async background push (ROADMAP satellite)
# ---------------------------------------------------------------------------

def test_dist_async_duplex_push_read_your_writes(lockstep_clean):
    kv = mx.kv.create("dist_async")
    try:
        assert kv._duplex_push_enabled()
        kv.init("dw", nd.ones((4,)) * 10.0)
        kv.push("dw", nd.ones((4,)) * 2.0)      # queued on the client
        out = nd.zeros((4,))
        kv.pull("dw", out=out)                  # sync pull drains first
        np.testing.assert_allclose(out.asnumpy(), 12.0)
        assert kv._push_futs == [], "drain left futures behind"
        assert lockstep.snapshot()["order_violations"] == []
    finally:
        kv.close()


def test_dist_async_duplex_push_groups_and_order(lockstep_clean,
                                                 monkeypatch):
    monkeypatch.setenv("GRAFT_BUCKET_BYTES", "64")  # tiny groups
    prev = blackbox._enabled_override
    blackbox.set_enabled(True)
    kv = mx.kv.create("dist_async")
    try:
        keys = list(range(6))
        vals = [nd.ones((8,)) * (i + 1) for i in keys]   # 32B each
        kv.init(keys, [nd.zeros((8,)) for _ in keys])
        kv.push_many(keys, vals)
        kv.barrier()                            # drains the queue
        outs = [nd.zeros((8,)) for _ in keys]
        kv.pull_many(keys, outs)
        for i, o in enumerate(outs):
            np.testing.assert_allclose(o.asnumpy(), i + 1.0)
        asyncs = [e for e in blackbox.events()
                  if e["kind"] == "collective"
                  and e["data"]["path"] == "ps_push_async"]
        assert len(asyncs) >= 3, "push groups did not split (%d)" \
            % len(asyncs)
        assert lockstep.snapshot()["order_violations"] == []
    finally:
        blackbox.set_enabled(prev)
        kv.close()


def test_dist_async_duplex_push_kill_switch(monkeypatch):
    monkeypatch.setenv("GRAFT_DUPLEX_PUSH", "0")
    kv = mx.kv.create("dist_async")
    try:
        kv.init("kw", nd.zeros((4,)))
        kv.push("kw", nd.ones((4,)))
        assert kv._push_futs == []              # synchronous path
        out = nd.zeros((4,))
        kv.pull("kw", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)
    finally:
        kv.close()


def test_dist_async_push_failure_is_pruned():
    """A failed push RPC surfaces ONCE (at the next push) and is pruned
    — it must not re-raise its stale exception on every later call."""
    from concurrent.futures import Future
    kv = mx.kv.create("dist_async")
    try:
        kv.init("pf", nd.zeros((2,)))
        poisoned = Future()
        poisoned.set_exception(RuntimeError("server boom"))
        kv._push_futs.append(poisoned)
        with pytest.raises(RuntimeError, match="server boom"):
            kv.push("pf", nd.ones((2,)))    # reap surfaces the failure
        kv.push("pf", nd.ones((2,)))        # ...exactly once
        out = nd.zeros((2,))
        kv.pull("pf", out=out)
        # both real pushes landed (the raising call had already
        # submitted its RPC before the reap fired)
        np.testing.assert_allclose(out.asnumpy(), 2.0)
    finally:
        kv.close()


def test_dist_async_close_shuts_background_client():
    kv = mx.kv.create("dist_async")
    kv.init("cw", nd.zeros((2,)))
    kv.push("cw", nd.ones((2,)))
    pool = kv._pull_executor()
    kv.close()
    assert kv._pull_pool is None and kv._ps is None
    with pytest.raises(RuntimeError):
        pool.submit(lambda: None)               # executor really shut


# ---------------------------------------------------------------------------
# 2-proc forced-divergence harness (SKIP-MULTIPROC pattern)
# ---------------------------------------------------------------------------

_DIVERGENCE_WORKER = textwrap.dedent("""
    import os, sys, traceback
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["GRAFT_WATCHDOG_TIMEOUT"] = "120"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.analysis import lockstep
    try:
        kv = mx.kv.create("dist_sync")
        rank, nw = kv.rank, kv.num_workers
        assert nw == 2, nw
        # two same-shape "buckets": the wire pairs fine either way, but
        # rank 1 issues them in SWAPPED order — the injected lockstep
        # divergence (a rank-order bug; a skipped collective would hang
        # the XLA wire itself, which is exactly what this auditor exists
        # to catch BEFORE it happens)
        a = nd.ones((16,)) * (rank + 1)
        b = nd.ones((16,)) * (rank + 3)
        labels = ("bucket[A]", "bucket[B]")
        order = (0, 1) if rank == 0 else (1, 0)
        vals, labs = (a, b), labels
        for step in range(2):
            for j in order:
                kv.reduce_many_async([vals[j]], label=labs[j]).wait()
            kv.heartbeat()      # ships (seq, rolling hash); observe()
        div = lockstep.divergence()
        assert div is not None, "divergence not detected"
        assert div["first_divergent_fold"] <= 2, div
        peers = div["divergent_ranks"]
        assert (1 - rank) in peers or rank in peers, div
        from incubator_mxnet_tpu.telemetry import blackbox
        evs = [e for e in blackbox.events()
               if e["kind"] == "lockstep_divergence"]
        assert evs, "no flight-recorder divergence event"
        print("WORKER %d DIVERGENCE seq=%d peers=%s OK"
              % (rank, div["first_divergent_fold"], peers), flush=True)
    except Exception:
        tb = traceback.format_exc()
        if "Multiprocess computations aren't implemented" in tb:
            print("SKIP-MULTIPROC", flush=True)
            os._exit(0)
        raise
""")


def test_two_process_forced_divergence(tmp_path):
    """Rank 1 issues its buckets in swapped order; the heartbeat-borne
    rolling hash must name the divergence (first bad seq <= 2) on both
    ranks BEFORE any watchdog trip."""
    from test_dist_multiprocess import _launch_two
    out = _launch_two(tmp_path, _DIVERGENCE_WORKER, timeout=240,
                      port_base=9700, require_rc0=False)
    if "SKIP-MULTIPROC" in out:
        pytest.skip("backend lacks multiprocess CPU collectives")
    assert "WORKER 0 DIVERGENCE" in out and "WORKER 1 DIVERGENCE" in out, \
        out[-3000:]
    assert "WATCHDOG TRIP" not in out, out[-3000:]


# ---------------------------------------------------------------------------
# lockstep online bisection
# ---------------------------------------------------------------------------

def test_lockstep_pins_skipped_collective_online():
    """A rank that SKIPS one mid-stream collective is not just named —
    the lagged-prefix points bracket the divergence to adjacent folds
    and the report pins the exact collective from the local table."""
    from incubator_mxnet_tpu.analysis import lockstep as ls
    ls.reset()
    ls.set_enabled(True)
    try:
        def digest(i):
            return ls._crc("reduce_many|1|%d|%d"
                           % (4096 + i, ls.keys_digest(["k%d" % i])))

        for i in range(1, 11):
            ls.fold(i, "reduce_many", n_keys=1, nbytes=4096 + i,
                    keys=["k%d" % i])
        # simulate the peer's stream: identical minus collective #5
        rolling, foldn, points = 0, 0, []
        for i in [1, 2, 3, 4, 6, 7, 8, 9, 10]:
            foldn += 1
            rolling = (rolling * 1000003 + digest(i) + foldn) & 0x7fffffff
            points.append((foldn, rolling))
        report = None
        for k, head in enumerate(points):
            lagp = points[k - 2] if k >= 2 else (0, 0)
            report = ls.observe({1: (head[0], head[1],
                                     lagp[0], lagp[1])}, my_rank=0)
            if report:
                break
        assert report is not None
        assert report["pinned"] is True
        assert report["first_divergent_fold"] == 5
        assert report["last_matching_fold"] == 4
        assert report["divergent_ranks"] == [1]
        c = report["divergent_collective"]
        assert c["path"] == "reduce_many" and c["nbytes"] == 4096 + 5
        # latched: later heartbeats do not re-report
        assert ls.observe({1: points[-1] + (0, 0)}, my_rank=0) is None
        assert ls.divergence()["pinned"] is True
    finally:
        ls.reset()
        ls.set_enabled(None)


def test_lockstep_state_lagged_pairs():
    from incubator_mxnet_tpu.analysis import lockstep as ls
    ls.reset()
    ls.set_enabled(True)
    try:
        # shorter than the lag: lag half ships (0, 0)
        ls.fold(1, "reduce_many", n_keys=1, nbytes=1, keys=["a"])
        f, h, lf, lh = ls.state_lagged()
        assert (f, lf, lh) == (1, 0, 0) and h != 0
        for i in range(2, 12):
            ls.fold(i, "reduce_many", n_keys=1, nbytes=i, keys=["a"])
        f, h, lf, lh = ls.state_lagged()
        assert f == 11 and lf == 11 - ls.lag()
        rows = {r["fold"]: r["rolling"] for r in ls.table()}
        assert lh == rows[lf]
        # a healthy laggard (peer = our own lagged prefix) never reports
        assert ls.observe({1: (lf, lh)}, my_rank=0) is None
    finally:
        ls.reset()
        ls.set_enabled(None)
