"""graftduplex: the full-duplex step must be BIT-IDENTICAL to the serial
bucketed path.

PR 7 hid the reduce (push) side of the wire under backward; this suite
covers the rest of the duplex contract (PR 9):

* the update_on_kvstore path — previously 100% serial — bucketed
  (``Trainer._duplex_plan`` + ``KVStore.apply_reduced``), its reduces
  overlapped mid-backward and its weight pulls issued per bucket as
  ``PullHandle``s waited at FIRST USE in the next forward
  (``overlap.PullScheduler`` first-touch hooks) — bytes-equality on
  weights AND store-side optimizer states across the optimizer matrix;
* the pull-side safety rails: stale (user-overwritten) weight →
  abandon-and-fallback, ``GRAFT_OVERLAP_PULL=0`` kill switch, the
  watchdog naming a stuck in-flight pull bucket;
* tape-order bucket packing (``GRAFT_BUCKET_ORDER=tape``, the default):
  buckets close EARLIER in backward than index packing on an
  interleaved-use model (issue fire-counts asserted), revertible via
  ``GRAFT_BUCKET_ORDER=index``;
* Module riding the same schedulers: bucketed+overlapped reduce on the
  local-update path (executor grad-ready hooks), first-touch pull
  overlap on update_on_kvstore — both bytes-equal to the per-key wire;
* an 8-virtual-device mesh backward through the overlap machinery
  (multi-ctx grad-ready hooks + committed-device-safe context sums);
* the prefetch-to-device DataLoader satellite (the consumer's wait
  shrinks) and the pull-overlap telemetry.
"""
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, module as mod
from incubator_mxnet_tpu.telemetry import blackbox, watchdog
import jax.numpy as jnp


SPECS = [(7,), (3, 5), (11,), (2, 2, 2), (13,), (4,)]


def _make_params(prefix, specs=SPECS, dtype="float32", grad_reqs=None,
                 ctx=None):
    params = []
    for k, shape in enumerate(specs):
        req = grad_reqs[k] if grad_reqs else "write"
        p = gluon.Parameter("%s%d" % (prefix, k), shape=shape, dtype=dtype,
                            grad_req=req)
        p.initialize(ctx=ctx if ctx is not None else mx.cpu())
        params.append(p)
    return params


def _seed(params, weights):
    from incubator_mxnet_tpu import engine
    for p, w in zip(params, weights):
        for d in p.list_data():
            # colocate: jnp.asarray lands on the default device, but a
            # multi-ctx replica must stay committed to ITS device
            d._write(engine.colocate(jnp.asarray(w).astype(d.dtype),
                                     d._read()))


def _backward_loss(params, consts):
    with autograd.record():
        loss = None
        for p, c in zip(params, consts):
            if p.grad_req == "null":
                continue
            y = (p.data() * p.data() * c).sum()
            loss = y if loss is None else loss + y
    loss.backward()


def _build_duplex_trainer(params, optimizer, opt_kw, overlap, pull,
                          bucket_bytes=48):
    t = gluon.Trainer(params, optimizer, dict(opt_kw),
                      kvstore=mx.kv.create("dist_sync"),
                      update_on_kvstore=True)
    t._bucket_bytes_override = bucket_bytes
    t._overlap_override = overlap
    t._overlap_pull_override = pull
    return t


def _store_states(trainer):
    return trainer._kvstore_obj._updater.states


def _assert_store_parity(params_a, params_b, ta, tb):
    for a, b in zip(params_a, params_b):
        wa, wb = a.data().asnumpy(), b.data().asnumpy()
        assert wa.dtype == wb.dtype
        assert wa.tobytes() == wb.tobytes(), \
            "weight %s diverged (max |d|=%g)" % (
                a.name, float(np.max(np.abs(
                    wa.astype(np.float64) - wb.astype(np.float64)))))
    sa, sb = _store_states(ta), _store_states(tb)
    assert set(sa) == set(sb)

    def leaves(s):
        if s is None:
            return []
        if isinstance(s, (tuple, list)):
            out = []
            for x in s:
                out.extend(leaves(x))
            return out
        return [s]
    for i in sa:
        for x, y in zip(leaves(sa[i]), leaves(sb[i])):
            assert x.asnumpy().tobytes() == y.asnumpy().tobytes(), \
                "store state %s diverged" % (i,)


def _duplex_parity_run(optimizer, opt_kw, specs=SPECS, dtype="float32",
                       grad_reqs=None, bucket_bytes=48, steps=5,
                       batch_size=2):
    """serial (bucketed, overlap+pull off) vs full-duplex (both on) on
    the update_on_kvstore wire — plus a per-key reference (bucket plan
    disabled) so all three spellings of the step are bytes-equal."""
    rs = np.random.RandomState(7)
    weights = [rs.randn(*s).astype(np.float32) for s in specs]
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in specs]

    runs = {}
    for name, (bb, ov, pl) in {
            "perkey": (0, False, False),
            "serial": (bucket_bytes, False, False),
            "duplex": (bucket_bytes, True, True)}.items():
        ps = _make_params(name[0], specs, dtype, grad_reqs)
        _seed(ps, weights)
        t = _build_duplex_trainer(ps, optimizer, opt_kw, ov, pl, bb)
        for _ in range(steps):
            _backward_loss(ps, consts)
            t.step(batch_size)
        runs[name] = (ps, t)
    pd, td = runs["duplex"]
    assert td._duplex_plan() is not None, \
        "duplex trainer unexpectedly fell off the bucketed path"
    assert td._scheduler.issued_total > 0, "reduce overlap never engaged"
    assert td._scheduler.taken_total > 0
    assert td._pull_scheduler.issued_total > 0, "pull overlap never engaged"
    assert td._pull_scheduler.touched_total > 0, \
        "no pull was waited at first touch"
    for other in ("perkey", "serial"):
        po, to = runs[other]
        _assert_store_parity(po, pd, to, td)
    return runs


def test_duplex_sgd_parity_with_null_holes():
    _duplex_parity_run("sgd", {"learning_rate": 0.1, "wd": 0.01},
                       grad_reqs=["write", "null", "write", "write",
                                  "null", "write"])


def test_duplex_sgd_momentum_parity():
    _duplex_parity_run("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                               "wd": 0.01})


def test_duplex_adam_parity():
    _duplex_parity_run("adam", {"learning_rate": 0.01}, steps=5)


def test_duplex_mp_bf16_parity():
    _duplex_parity_run("sgd", {"learning_rate": 0.05, "momentum": 0.9,
                               "wd": 0.001, "multi_precision": True},
                       dtype="bfloat16", bucket_bytes=24, steps=6)


def test_duplex_pulls_in_flight_until_first_touch():
    """The core pull-side semantic: after step() returns, the bucket
    pulls are OPEN flight-recorder brackets; the next forward's first
    weight read waits them (touched_total moves), and nothing stays in
    flight once every weight was touched."""
    rs = np.random.RandomState(3)
    params = _make_params("pif")
    _seed(params, [rs.randn(*s).astype(np.float32) for s in SPECS])
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    t = _build_duplex_trainer(params, "sgd", {"learning_rate": 0.1},
                              True, True)
    _backward_loss(params, consts)
    t.step(2)
    assert t._pull_scheduler.inflight_groups > 0, \
        "no pulls in flight after step"
    if blackbox.enabled():
        sites = [e for e in blackbox.inflight_entries()
                 if e["detail"].get("path") == "pull_many_async"]
        assert sites, "in-flight pull carries no recorder bracket"
        assert all("pull[" in str(e["detail"].get("bucket"))
                   for e in sites)
    touched_before = t._pull_scheduler.touched_total
    params[0].data().asnumpy()      # first touch: waits that bucket
    assert t._pull_scheduler.touched_total == touched_before + 1
    for p in params:                # touch the rest
        p.data().asnumpy()
    assert t._pull_scheduler.inflight_groups == 0
    assert not [e for e in blackbox.inflight_entries()
                if e["detail"].get("path") == "pull_many_async"]


def test_view_read_first_touches_base_pull():
    """A view read slices the BASE's buffer, so it must count as the
    base's first use: the pending pull lands before the slice (the
    dist_async path defers its weight writes to wait time — a view read
    that bypassed the hook would return pre-pull bytes)."""
    from incubator_mxnet_tpu.overlap import PullScheduler
    kv = mx.kv.create("local")
    kv.init([0], [mx.nd.array(np.arange(8, dtype=np.float32))])
    out = mx.nd.array(np.zeros(8, np.float32))
    view = out[2:5]
    view.asnumpy()              # materialize the view pre-pull
    sched = PullScheduler()
    sched.issue(kv, [0], [[out]], label="pull[view]")
    assert sched.inflight_groups == 1
    got = view.asnumpy()        # read through the VIEW only
    assert sched.touched_total == 1, "view read did not first-touch"
    assert sched.inflight_groups == 0
    assert np.array_equal(got, np.arange(2, 5, dtype=np.float32))


def test_graft_overlap_pull_env_kill_switch(monkeypatch):
    monkeypatch.setenv("GRAFT_OVERLAP_PULL", "0")
    rs = np.random.RandomState(2)
    params = _make_params("env")
    _seed(params, [rs.randn(*s).astype(np.float32) for s in SPECS])
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    t = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                      kvstore=mx.kv.create("dist_sync"),
                      update_on_kvstore=True)
    t._bucket_bytes_override = 48
    for _ in range(3):
        _backward_loss(params, consts)
        t.step(2)
    assert t._pull_scheduler.issued_total == 0
    # the reduce side keeps overlapping — the switches are independent
    assert t._scheduler.issued_total > 0


def test_stale_weight_mutation_abandons_and_falls_back():
    """Overwriting a weight while its pull is in flight must keep the
    USER's bytes (the serial pull-then-write ordering) and downgrade the
    next round to the serial pull — while a parallel serial trainer fed
    the same mutations stays bit-identical."""
    rs = np.random.RandomState(9)
    weights = [rs.randn(*s).astype(np.float32) for s in SPECS]
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    pa = _make_params("sta")
    pb = _make_params("stb")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = _build_duplex_trainer(pa, "sgd", {"learning_rate": 0.1},
                               False, False)
    tb = _build_duplex_trainer(pb, "sgd", {"learning_rate": 0.1},
                               True, True)

    def mutated_step(params, trainer):
        _backward_loss(params, consts)
        trainer.step(2)
        # overwrite WITHOUT reading: serial semantics = pull landed
        # first, then this write wins
        params[0].data()._write(jnp.full(SPECS[0], 0.25, jnp.float32))

    for _ in range(3):
        mutated_step(pa, ta)
        mutated_step(pb, tb)
    # the final mutation happened with its pull still in flight: the
    # settle here must DETECT it (stale > 0), not silently apply
    stale_seen = tb._pull_scheduler.finish()
    assert stale_seen > 0, "stale overwrite was not detected"
    # the overwritten weight holds the user's bytes on both sides
    assert np.allclose(pb[0].data().asnumpy(), 0.25)
    _assert_store_parity(pa, pb, ta, tb)


def test_stale_round_runs_serial_next_pull():
    """After a stale detection the NEXT round's pulls are serial
    (abandon-and-fallback), then async resumes."""
    rs = np.random.RandomState(4)
    params = _make_params("fbk")
    _seed(params, [rs.randn(*s).astype(np.float32) for s in SPECS])
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    t = _build_duplex_trainer(params, "sgd", {"learning_rate": 0.1},
                              True, True)
    _backward_loss(params, consts)
    t.step(2)
    issued_before = t._pull_scheduler.issued_total
    assert issued_before > 0
    # overwrite while in flight -> stale
    params[0].data()._write(jnp.zeros(SPECS[0], jnp.float32))
    _backward_loss(params, consts)
    t.step(2)       # finish() sees the stale out; this round pulls serial
    assert t._pull_scheduler.issued_total == issued_before, \
        "stale round still issued async pulls"
    _backward_loss(params, consts)
    t.step(2)       # clean round: async resumes
    assert t._pull_scheduler.issued_total > issued_before


def test_first_touch_read_modify_write_sees_pulled_bytes():
    """`w *= 0.5` between steps READS first: the first-touch hook must
    deliver the pulled value before the mutation computes — byte-equal
    to the serial trainer doing the same mutation."""
    rs = np.random.RandomState(11)
    weights = [rs.randn(*s).astype(np.float32) for s in SPECS]
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    pa = _make_params("rma")
    pb = _make_params("rmb")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = _build_duplex_trainer(pa, "sgd", {"learning_rate": 0.1},
                               False, False)
    tb = _build_duplex_trainer(pb, "sgd", {"learning_rate": 0.1},
                               True, True)
    for _ in range(3):
        for params, trainer in ((pa, ta), (pb, tb)):
            _backward_loss(params, consts)
            trainer.step(2)
            w = params[2].data()
            w._write(w._read() * 0.5)       # RMW: read fires the hook
    tb._pull_scheduler.finish()
    _assert_store_parity(pa, pb, ta, tb)


# ---------------------------------------------------------------------------
# watchdog: a stuck in-flight pull bucket is named
# ---------------------------------------------------------------------------

def test_watchdog_names_stalled_inflight_pull():
    prev = blackbox._enabled_override
    blackbox.set_enabled(True)
    try:
        kv = mx.kv.create("dist_sync")
        kv.init([0], [mx.nd.array(np.ones(16, np.float32))])
        outs = [[mx.nd.array(np.zeros(16, np.float32))]]
        h = kv.pull_many_async([0], outs, label="pull[float32:1p:64B]")
        wd = watchdog.Watchdog(timeout=0.05)
        trips = []
        wd.trip = lambda entry, age: trips.append(entry)
        time.sleep(0.12)
        # deliberately left in flight (the next forward has not touched
        # the weights yet) = healthy overlap: NO trip...
        wd.poll()
        assert not trips, "watchdog tripped on a healthy in-flight pull"
        # ...but the dump names it while in flight
        doc = blackbox.snapshot(reason="test")
        stuck = [e for e in doc["in_flight"]
                 if e["detail"].get("path") == "pull_many_async"
                 and e["detail"].get("bucket") == "pull[float32:1p:64B]"]
        assert stuck, doc["in_flight"]
        # once a consumer starts WAITING, a stall is a genuine hang
        h._begin_wait()
        time.sleep(0.12)
        wd.poll()
        assert trips, "watchdog did not trip on the stalled pull wait"
        assert trips[0]["site"] == "collective"
        assert trips[0]["detail"]["bucket"] == "pull[float32:1p:64B]"
        h.wait()
        assert not [e for e in blackbox.inflight_entries()
                    if e["detail"].get("bucket") == "pull[float32:1p:64B]"]
    finally:
        blackbox.set_enabled(prev)


def test_pull_handle_wait_idempotent_and_abandon():
    kv = mx.kv.create("local")
    kv.init([0], [mx.nd.array(np.arange(4, dtype=np.float32))])
    outs = [[mx.nd.array(np.zeros(4, np.float32))]]
    h = kv.pull_many_async([0], outs, label="pull[x]")
    assert h.wait() is h.values and h.done
    h.wait()                    # idempotent
    assert np.allclose(outs[0][0].asnumpy(), np.arange(4))
    h2 = kv.pull_many_async([0], outs, label="pull[y]")
    h2.abandon()
    assert h2.done
    assert not [e for e in blackbox.inflight_entries()
                if e["detail"].get("bucket") in ("pull[x]", "pull[y]")]


# ---------------------------------------------------------------------------
# tape-order bucket packing
# ---------------------------------------------------------------------------

TAPE_SPECS = [(4,)] * 6                 # equal sizes: 3 params per 48B bucket
TAPE_USE_ORDER = [0, 3, 1, 4, 2, 5]     # forward use order != index order


def _tape_order_run(overlap_trainer_order):
    """Train 2 steps with the given GRAFT_BUCKET_ORDER; return
    (plan bucket index tuples, issue_log of the last armed backward)."""
    rs = np.random.RandomState(5)
    params = _make_params("tp" + overlap_trainer_order, TAPE_SPECS)
    _seed(params, [rs.randn(*s).astype(np.float32) for s in TAPE_SPECS])
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32))
              for s in TAPE_SPECS]
    t = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                      kvstore=mx.kv.create("dist_sync"))
    t._bucket_bytes_override = 48
    t._overlap_override = True

    def step():
        with autograd.record():
            loss = None
            for k in TAPE_USE_ORDER:
                y = (params[k].data() * params[k].data() * consts[k]).sum()
                loss = y if loss is None else loss + y
        loss.backward()
        t.step(2)

    step()          # arms (tape stamps exist from this first backward)
    step()          # overlapped: issue_log fills
    # read the log of the LAST pass before the next backward resets it
    plan = t._fused_plan()
    buckets = tuple(tuple(b.indices) for b in plan[0])
    log = list(t._scheduler.issue_log)
    assert log, "no buckets were issued mid-backward"
    return buckets, log


def test_tape_order_closes_first_bucket_earlier(monkeypatch):
    monkeypatch.setenv("GRAFT_BUCKET_ORDER", "tape")
    tape_buckets, tape_log = _tape_order_run("t")
    monkeypatch.setenv("GRAFT_BUCKET_ORDER", "index")
    index_buckets, index_log = _tape_order_run("i")
    # index mode is the PR 4 packing, revertible
    assert index_buckets == ((0, 1, 2), (3, 4, 5))
    # tape mode groups by reverse use order: first bucket = last-used
    assert tape_buckets == ((5, 2, 4), (1, 3, 0))
    # the tentpole claim, in fire-counts: the first bucket ISSUES after
    # fewer grad deliveries under tape packing than under index packing
    first_issue_tape = min(n for _idx, n in tape_log)
    first_issue_index = min(n for _idx, n in index_log)
    assert first_issue_tape == 3, tape_log
    assert first_issue_index == 5, index_log
    assert first_issue_tape < first_issue_index


def test_tape_order_parity_vs_serial():
    """Tape-packed overlapped steps stay bytes-equal to the serial
    trainer (whose plan is index-packed — partitioning must not matter)."""
    rs = np.random.RandomState(8)
    weights = [rs.randn(*s).astype(np.float32) for s in TAPE_SPECS]
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32))
              for s in TAPE_SPECS]
    pa = _make_params("tps", TAPE_SPECS)
    pb = _make_params("tpo", TAPE_SPECS)
    _seed(pa, weights)
    _seed(pb, weights)
    ta = gluon.Trainer(pa, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore=mx.kv.create("dist_sync"))
    tb = gluon.Trainer(pb, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore=mx.kv.create("dist_sync"))
    ta._bucket_bytes_override = tb._bucket_bytes_override = 48
    ta._overlap_override = False
    tb._overlap_override = True

    def step(params, trainer):
        with autograd.record():
            loss = None
            for k in TAPE_USE_ORDER:
                y = (params[k].data() * params[k].data() * consts[k]).sum()
                loss = y if loss is None else loss + y
        loss.backward()
        trainer.step(2)

    for _ in range(4):
        step(pa, ta)
        step(pb, tb)
    assert tb._scheduler.issued_total > 0
    for a, b in zip(pa, pb):
        assert a.data().asnumpy().tobytes() == b.data().asnumpy().tobytes()
    sa, sb = ta._updaters[0].states, tb._updaters[0].states
    for i in sa:
        assert sa[i].asnumpy().tobytes() == sb[i].asnumpy().tobytes()


# ---------------------------------------------------------------------------
# 8-virtual-device mesh: multi-ctx grad-ready hooks + device-safe sums
# ---------------------------------------------------------------------------

def test_multi_device_mesh_overlap_parity():
    import jax
    n_dev = min(8, len(jax.devices()))
    if n_dev < 2:
        pytest.skip("needs multiple host devices")
    ctxs = [mx.cpu(i) for i in range(n_dev)]
    specs = [(5,), (3, 4), (9,), (2, 3)]
    rs = np.random.RandomState(6)
    weights = [rs.randn(*s).astype(np.float32) for s in specs]
    base = [rs.randn(*s).astype(np.float32) for s in specs]

    def build(prefix, overlap):
        ps = _make_params(prefix, specs, ctx=ctxs)
        _seed(ps, weights)
        t = gluon.Trainer(ps, "sgd",
                          {"learning_rate": 0.05, "momentum": 0.9},
                          kvstore=mx.kv.create("dist_sync"))
        t._bucket_bytes_override = 48
        t._overlap_override = overlap
        consts = [[mx.nd.array(c * (j + 1), ctx=ctx)
                   for j, ctx in enumerate(ctxs)] for c in base]
        return ps, t, consts

    def step(ps, t, consts):
        # ONE recorded scope, one backward over all contexts' losses:
        # grads for every (param, ctx) finalize inside a single pass
        with autograd.record():
            losses = []
            for j, ctx in enumerate(ctxs):
                loss = None
                for p, cs in zip(ps, consts):
                    d = p.data(ctx)
                    y = (d * d * cs[j]).sum()
                    loss = y if loss is None else loss + y
                losses.append(loss)
        autograd.backward(losses)
        t.step(2)

    pa, ta, ca = build("mds", False)
    pb, tb, cb = build("mdo", True)
    for _ in range(4):
        step(pa, ta, ca)
        step(pb, tb, cb)
    assert tb._scheduler.issued_total > 0, \
        "multi-ctx hooks never issued a bucket"
    assert tb._scheduler.taken_total > 0
    for a, b in zip(pa, pb):
        for da, db in zip(a.list_data(), b.list_data()):
            assert da.asnumpy().tobytes() == db.asnumpy().tobytes(), \
                "replica of %s diverged" % a.name
    for ua, ub in zip(ta._updaters, tb._updaters):
        assert set(ua.states) == set(ub.states)
        for i in ua.states:
            assert ua.states[i].asnumpy().tobytes() \
                == ub.states[i].asnumpy().tobytes()


# ---------------------------------------------------------------------------
# Module: the executor grad arrays ride the same schedulers
# ---------------------------------------------------------------------------

def _build_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


_MODULE_INIT = None


def _module_init():
    global _MODULE_INIT
    if _MODULE_INIT is None:
        rs = np.random.RandomState(1)
        _MODULE_INIT = {
            "fc1_weight": rs.randn(8, 10).astype(np.float32) * 0.1,
            "fc1_bias": np.zeros(8, np.float32),
            "fc2_weight": rs.randn(4, 8).astype(np.float32) * 0.1,
            "fc2_bias": np.zeros(4, np.float32)}
    return _MODULE_INIT


def _build_module(kvstore, bucket_bytes, overlap, pull):
    m = mod.Module(_build_sym(), context=mx.cpu())
    m.bind(data_shapes=[("data", (6, 10))],
           label_shapes=[("softmax_label", (6,))])
    m.init_params(arg_params={k: mx.nd.array(v)
                              for k, v in _module_init().items()},
                  aux_params={})
    m.init_optimizer(kvstore=kvstore, optimizer="sgd",
                     optimizer_params=(("learning_rate", 0.1),
                                       ("momentum", 0.9)))
    m._bucket_bytes_override = bucket_bytes
    m._overlap_override = overlap
    m._overlap_pull_override = pull
    return m


def _module_batch():
    rs = np.random.RandomState(0)
    x = rs.rand(6, 10).astype(np.float32)
    y = rs.randint(0, 4, (6,)).astype(np.float32)
    return mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])


def _train_module(m, batch, n=4):
    for _ in range(n):
        m.forward(batch, is_train=True)
        m.backward()
        m.update()


def _assert_module_parity(ma, mb):
    pa, aa = ma.get_params()
    pb, ab = mb.get_params()
    assert set(pa) == set(pb)
    for k in pa:
        assert pa[k].asnumpy().tobytes() == pb[k].asnumpy().tobytes(), \
            "param %s diverged" % k


def test_module_bucketed_overlap_parity(monkeypatch):
    """Local-update Module (MXNET_UPDATE_ON_KVSTORE=0): the executor's
    grad arrays fire grad-ready hooks, buckets reduce mid-backward, and
    the result is bytes-equal to the per-key push/pull wire."""
    monkeypatch.setenv("MXNET_UPDATE_ON_KVSTORE", "0")
    batch = _module_batch()
    ma = _build_module(mx.kv.create("dist_sync"), 0, False, False)
    mb = _build_module(mx.kv.create("dist_sync"), 64, True, False)
    assert not ma._update_on_kvstore and not mb._update_on_kvstore
    _train_module(ma, batch)
    _train_module(mb, batch)
    assert mb._scheduler.issued_total > 0, "module overlap never engaged"
    assert mb._scheduler.taken_total > 0
    _assert_module_parity(ma, mb)


def test_module_update_on_kvstore_pull_overlap_parity():
    """Store-update Module: weight pulls ride PullScheduler first-touch
    hooks; bytes-equal to the synchronous pull."""
    batch = _module_batch()
    ma = _build_module(mx.kv.create("dist_sync"), 0, False, False)
    mb = _build_module(mx.kv.create("dist_sync"), 64, False, True)
    assert ma._update_on_kvstore and mb._update_on_kvstore
    _train_module(ma, batch)
    _train_module(mb, batch)
    assert mb._pull_scheduler.issued_total > 0, "pull overlap never engaged"
    assert mb._pull_scheduler.touched_total > 0, \
        "module forward never first-touched a pulled weight"
    _assert_module_parity(ma, mb)


def test_module_grad_add_req_not_scheduled(monkeypatch):
    """grad_req='add' executors accumulate — their buckets must not arm
    (the executor also never fires hooks for add-req grads)."""
    monkeypatch.setenv("MXNET_UPDATE_ON_KVSTORE", "0")
    batch = _module_batch()
    m = mod.Module(_build_sym(), context=mx.cpu())
    m.bind(data_shapes=[("data", (6, 10))],
           label_shapes=[("softmax_label", (6,))], grad_req="add")
    m.init_params(arg_params={k: mx.nd.array(v)
                              for k, v in _module_init().items()},
                  aux_params={})
    m.init_optimizer(kvstore=mx.kv.create("dist_sync"), optimizer="sgd")
    m._bucket_bytes_override = 64
    m._overlap_override = True
    _train_module(m, batch, n=3)
    assert m._scheduler.issued_total == 0


# ---------------------------------------------------------------------------
# satellite: prefetch-to-device double buffering shrinks data_wait
# ---------------------------------------------------------------------------

class _SlowDataset(gluon.data.Dataset):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        time.sleep(0.004)
        return mx.nd.array(np.full((4,), i, np.float32))


def _loader_data_wait(dl):
    """Seconds the consumer spent blocked in the loader's ``next``."""
    order, waited = [], 0.0
    it = iter(dl)
    while True:
        t0 = time.perf_counter()
        try:
            b = next(it)
        except StopIteration:
            break
        waited += time.perf_counter() - t0
        order.append(float(b.asnumpy()[0, 0]))
        time.sleep(0.02)        # the consumer's "compute"
    return waited, order


def test_prefetch_to_device_shrinks_data_wait():
    ds = _SlowDataset(24)
    sync = gluon.data.DataLoader(ds, batch_size=4, num_workers=0,
                                 prefetch_device=False)
    pre = gluon.data.DataLoader(ds, batch_size=4, num_workers=0,
                                prefetch_device=True)
    try:
        w_sync, order_sync = _loader_data_wait(sync)
        w_pre, order_pre = _loader_data_wait(pre)
    finally:
        pre.close()
    assert order_sync == order_pre, "prefetch reordered batches"
    assert w_pre < 0.5 * w_sync, \
        "prefetch did not shrink data_wait (%.3fs vs %.3fs)" % (
            w_pre, w_sync)


def test_prefetch_env_kill_switch(monkeypatch):
    monkeypatch.setenv("GRAFT_PREFETCH_DEVICE", "0")
    ds = _SlowDataset(8)
    dl = gluon.data.DataLoader(ds, batch_size=4, num_workers=0)
    batches = [b.asnumpy() for b in dl]
    assert len(batches) == 2
    assert dl._pool is None, \
        "kill switch still spun up the lookahead thread"


# ---------------------------------------------------------------------------
# telemetry: the pull-overlap gauge/counters populate
# ---------------------------------------------------------------------------

def test_pull_overlap_metrics_emitted():
    from incubator_mxnet_tpu import telemetry
    rs = np.random.RandomState(12)
    params = _make_params("met")
    _seed(params, [rs.randn(*s).astype(np.float32) for s in SPECS])
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in SPECS]
    t = _build_duplex_trainer(params, "sgd", {"learning_rate": 0.1},
                              True, True)
    for _ in range(4):
        _backward_loss(params, consts)
        t.step(2)
    t._pull_scheduler.finish()
    _backward_loss(params, consts)
    t.step(2)       # publishes the settled round
    snap = telemetry.compact_snapshot()
    assert snap.get(
        'graft_trainer_pull_buckets_total{mode="overlapped"}', 0) > 0
    assert "graft_trainer_pull_overlap_ratio" in snap
    assert 0.0 <= snap["graft_trainer_pull_overlap_ratio"] <= 1.0
    assert snap.get("graft_trainer_pull_exposed_seconds_count", 0) >= 1
