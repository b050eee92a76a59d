"""graftzero: block-scaled quantized bucket allreduce (error feedback)
+ ZeRO-1 sharded optimizer update.

The wire contract (docs/observability.md "quantization contract"): a
quantized reduce keeps the collective stream's SHAPE — one reduce per
bucket, same issue order — and bounds the per-element error by
``max|block|/254`` (int8) / ``max|block|/2`` (2bit) of the
error-compensated payload, with the dropped residual carried in the
Updater store (``__quant_ef__/...`` string keys) so it is re-injected
next round instead of accumulating.  ``GRAFT_QUANT_REDUCE=0`` is the
bit-identical escape hatch, even over a legacy
``set_gradient_compression("2bit")`` routing.

The ZeRO-1 contract: ``GRAFT_SHARD_OPTIMIZER=1`` makes each context (or
dist rank) run the fused update — and lazily create optimizer state —
only for its contiguous shard of the bucket plan, then broadcast; the
parity target is BYTE equality with the unsharded step's context-0
replica, and per-shard state bytes land on the
``graft_trainer_state_shard_bytes`` gauge (~1/N).
"""
import os
import pickle

import numpy as np
import pytest

import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, engine, gluon
from incubator_mxnet_tpu.analysis import lockstep, tsan
from incubator_mxnet_tpu.parallel import quant
from incubator_mxnet_tpu.telemetry import metrics as tmetrics


SPECS = [(7,), (3, 5), (11,), (2, 2, 2), (13,), (4,)]

_ENV = ("GRAFT_QUANT_REDUCE", "GRAFT_QUANT_BLOCK", "GRAFT_SHARD_OPTIMIZER")


@pytest.fixture(autouse=True)
def _clean_env():
    saved = {k: os.environ.pop(k, None) for k in _ENV}
    try:
        yield
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None)
            if v is not None:
                os.environ[k] = v


def _make_params(prefix, specs=SPECS, ctx=None):
    params = []
    for k, shape in enumerate(specs):
        p = gluon.Parameter("%s%d" % (prefix, k), shape=shape)
        p.initialize(ctx=ctx if ctx is not None else mx.cpu())
        params.append(p)
    return params


def _seed(params, weights):
    for p, w in zip(params, weights):
        for d in p.list_data():
            d._write(engine.colocate(jnp.asarray(w).astype(d.dtype),
                                     d._read()))


def _backward_loss(params, consts):
    with autograd.record():
        loss = None
        for p, c in zip(params, consts):
            y = (p.data() * p.data() * c).sum()
            loss = y if loss is None else loss + y
    loss.backward()


def _build_trainer(params, optimizer="sgd", opt_kw=None, overlap=False,
                   bucket_bytes=48):
    t = gluon.Trainer(params, optimizer,
                      dict(opt_kw or {"learning_rate": 0.05}),
                      kvstore=mx.kv.create("dist_sync"))
    t._bucket_bytes_override = bucket_bytes
    t._overlap_override = overlap
    return t


def _fixtures(seed=7, specs=SPECS):
    rs = np.random.RandomState(seed)
    weights = [rs.randn(*s).astype(np.float32) for s in specs]
    consts = [mx.nd.array(rs.randn(*s).astype(np.float32)) for s in specs]
    return weights, consts


def _residual_keys(trainer):
    return sorted(k for k in trainer._updaters[0].states
                  if quant.is_residual_key(k))


def _assert_bit_identical(pa, pb, ta, tb):
    for a, b in zip(pa, pb):
        assert a.data().asnumpy().tobytes() == b.data().asnumpy().tobytes(), \
            "weight %s diverged" % a.name
    sa, sb = ta._updaters[0].states, tb._updaters[0].states
    assert set(sa) == set(sb)
    for k in sa:
        for x, y in zip(_leaves(sa[k]), _leaves(sb[k])):
            assert np.asarray(_np(x)).tobytes() == \
                np.asarray(_np(y)).tobytes(), "state %r diverged" % (k,)


def _leaves(state):
    if isinstance(state, (tuple, list)):
        out = []
        for s in state:
            out.extend(_leaves(s))
        return out
    return [] if state is None else [state]


def _np(leaf):
    return leaf.asnumpy() if hasattr(leaf, "asnumpy") else np.asarray(leaf)


# ---------------------------------------------------------------------------
# kernels: round-trip bounds, wire bytes, shard maps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 15, 255, 256, 257, 1000])
@pytest.mark.parametrize("block", [64, 256])
def test_int8_roundtrip_bound(n, block):
    rs = np.random.RandomState(n + block)
    x = jnp.asarray((rs.randn(n) * 10).astype(np.float32))
    codes, scales = quant.encode(x, "int8", block)
    y = np.asarray(quant.decode(codes, scales, n, "int8", block))
    err = np.abs(y - np.asarray(x))
    for b in range(quant.n_blocks(n, block)):
        blk = np.asarray(x)[b * block:(b + 1) * block]
        bound = np.abs(blk).max() / 254.0 + 1e-7
        assert err[b * block:(b + 1) * block].max() <= bound, \
            "int8 block %d error above max|block|/254" % b


@pytest.mark.parametrize("n", [16, 255, 512, 1000])
def test_2bit_roundtrip_bound(n):
    block = 256
    rs = np.random.RandomState(n)
    x = jnp.asarray((rs.randn(n) * 3).astype(np.float32))
    codes, scales = quant.encode(x, "2bit", block)
    y = np.asarray(quant.decode(codes, scales, n, "2bit", block))
    err = np.abs(y - np.asarray(x))
    for b in range(quant.n_blocks(n, block)):
        blk = np.asarray(x)[b * block:(b + 1) * block]
        bound = np.abs(blk).max() / 2.0 + 1e-6
        assert err[b * block:(b + 1) * block].max() <= bound, \
            "2bit block %d error above max|block|/2" % b


def test_wire_nbytes_ratios():
    n = 1 << 16
    f32 = 4 * n
    assert f32 / quant.wire_nbytes(n, "int8", 256) >= 3.5
    assert f32 / quant.wire_nbytes(n, "2bit", 256) >= 12.0
    # ragged tail still bills whole blocks (codes are padded on the wire)
    assert quant.wire_nbytes(257, "int8", 256) == 2 * 256 + 8


def test_resolve_mode_and_block():
    assert quant.resolve_mode() is None
    os.environ["GRAFT_QUANT_REDUCE"] = "int8"
    assert quant.resolve_mode() == "int8"
    os.environ["GRAFT_QUANT_REDUCE"] = "0"
    # the escape hatch beats the legacy compression override
    assert quant.resolve_mode(override="2bit") is None
    del os.environ["GRAFT_QUANT_REDUCE"]
    assert quant.resolve_mode(override="2bit") == "2bit"
    os.environ["GRAFT_QUANT_BLOCK"] = "100"
    assert quant.resolve_block() == 112          # rounded up to 16 lanes
    os.environ["GRAFT_QUANT_BLOCK"] = "4"
    assert quant.resolve_block() == 16           # floor


def test_shard_owners_contiguous_and_complete():
    owners = quant.shard_owners(10, 4)
    assert len(owners) == 10
    assert list(owners) == sorted(owners), "shards must be contiguous runs"
    assert set(owners) <= set(range(4))
    # fewer buckets than shards: one bucket each for the first few
    assert quant.shard_owners(2, 8) == (0, 4)
    assert quant.shard_owners(0, 8) == ()
    # every rank derives the identical map (it is pure arithmetic)
    assert quant.shard_owners(10, 4) == owners


def test_residual_key_namespace():
    key = quant.residual_key((3, 1, 2), "float32")
    assert key == "__quant_ef__/float32:3,1,2"
    assert quant.is_residual_key(key)
    assert not quant.is_residual_key(7)
    assert not quant.is_residual_key("momentum")


def test_error_feedback_telescopes_exactly():
    """EF convergence in EXACT arithmetic: with dyadic-rational payloads
    every quantity (scale, code*scale, residual subtraction) is exactly
    representable in f32, so the telescoping identity

        sum_k decode(encode(g_k + r_{k-1})) == sum_k g_k - r_K

    holds to the BIT — the quantizer drops no mass, it only delays it.
    2bit mode: scale = max|block| (a power of two here), decoded values
    in {0, +/-scale}."""
    block = 16
    g = jnp.asarray(np.array([1.0, -0.5, 0.25, 2.0] * 4, np.float32))
    res = jnp.zeros_like(g)
    sum_dec = np.zeros(g.shape, np.float64)
    sum_g = np.zeros(g.shape, np.float64)
    for _ in range(8):
        acc = g + res
        codes, scales = quant.encode(acc, "2bit", block)
        dec = quant.decode(codes, scales, g.shape[0], "2bit", block)
        res = acc - dec
        sum_dec += np.asarray(dec, np.float64)
        sum_g += np.asarray(g, np.float64)
    np.testing.assert_array_equal(sum_dec + np.asarray(res, np.float64),
                                  sum_g)


def test_error_feedback_mean_converges():
    """The practical corollary: the running mean of the decoded payloads
    converges to the true (constant) gradient at 1/K — the residual is
    bounded, so its amortized share vanishes."""
    block = 64
    rs = np.random.RandomState(5)
    g = jnp.asarray((rs.randn(200) * 7).astype(np.float32))

    def mean_err(k_rounds):
        res = jnp.zeros_like(g)
        total = np.zeros(g.shape, np.float64)
        for _ in range(k_rounds):
            acc = g + res
            codes, scales = quant.encode(acc, "int8", block)
            dec = quant.decode(codes, scales, g.shape[0], "int8", block)
            res = acc - dec
            total += np.asarray(dec, np.float64)
        return np.abs(total / k_rounds - np.asarray(g, np.float64)).max()

    assert mean_err(32) < mean_err(2) / 8.0


# ---------------------------------------------------------------------------
# the trainer wire: serial + overlapped, escape hatch, legacy routing
# ---------------------------------------------------------------------------

def _quant_parity_run(mode, steps=4, lr=0.05, overlap=False):
    weights, consts = _fixtures()
    pa, pb = _make_params("f"), _make_params("q")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = _build_trainer(pa, opt_kw={"learning_rate": lr})
    tb = _build_trainer(pb, opt_kw={"learning_rate": lr}, overlap=overlap)
    for _ in range(steps):
        _backward_loss(pa, consts)
        ta.step(2)
        os.environ["GRAFT_QUANT_REDUCE"] = mode
        _backward_loss(pb, consts)
        tb.step(2)
        del os.environ["GRAFT_QUANT_REDUCE"]
    maxdiff = max(
        float(np.abs(a.data().asnumpy().astype(np.float64)
                     - b.data().asnumpy().astype(np.float64)).max())
        for a, b in zip(pa, pb))
    return pa, pb, ta, tb, maxdiff


def test_int8_serial_parity_within_tolerance():
    pa, pb, ta, tb, maxdiff = _quant_parity_run("int8")
    # loose end-to-end ceiling over the documented per-step per-element
    # bound (lr/batch * max|block|/254, amplified by the grad dynamics)
    assert 0 < maxdiff < 1e-2, maxdiff
    keys = _residual_keys(tb)
    assert keys and all(quant.is_residual_key(k) for k in keys)
    assert _residual_keys(ta) == []


def test_2bit_serial_parity_within_tolerance():
    _, _, _, tb, maxdiff = _quant_parity_run("2bit", lr=0.01)
    assert 0 < maxdiff < 0.5, maxdiff
    assert _residual_keys(tb)


def test_overlapped_quant_bit_identical_to_serial_quant():
    """Overlap moves the ISSUE time of the quantized reduce, never its
    content: serial-quant and overlapped-quant are byte-equal, residuals
    included."""
    weights, consts = _fixtures()
    pa, pb = _make_params("qs"), _make_params("qo")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = _build_trainer(pa)
    tb = _build_trainer(pb, overlap=True)
    os.environ["GRAFT_QUANT_REDUCE"] = "int8"
    for _ in range(5):
        _backward_loss(pa, consts)
        ta.step(2)
        _backward_loss(pb, consts)
        tb.step(2)
    assert tb._scheduler.issued_total > 0, "overlap never engaged"
    _assert_bit_identical(pa, pb, ta, tb)


def test_quant_off_env_is_bit_identical():
    weights, consts = _fixtures()
    pa, pb = _make_params("n"), _make_params("z")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = _build_trainer(pa)
    tb = _build_trainer(pb)
    for _ in range(4):
        _backward_loss(pa, consts)
        ta.step(2)
        os.environ["GRAFT_QUANT_REDUCE"] = "0"
        _backward_loss(pb, consts)
        tb.step(2)
        del os.environ["GRAFT_QUANT_REDUCE"]
    _assert_bit_identical(pa, pb, ta, tb)


def test_legacy_2bit_compression_deprecates_and_routes():
    """set_gradient_compression("2bit") must warn, route the store onto
    the graftzero wire (no serial per-key fallback), and stay overridden
    by the GRAFT_QUANT_REDUCE=0 escape hatch."""
    kv = mx.kv.create("dist_sync")
    with pytest.warns(DeprecationWarning):
        kv.set_gradient_compression({"type": "2bit"})
    assert kv._quant_override == "2bit"
    assert quant.resolve_mode(kv._quant_override) == "2bit"

    weights, consts = _fixtures()
    pa, pb = _make_params("lc"), _make_params("ln")
    _seed(pa, weights)
    _seed(pb, weights)
    ta = gluon.Trainer(pa, "sgd", {"learning_rate": 0.05}, kvstore=kv)
    ta._bucket_bytes_override = 48
    ta._overlap_override = False
    tb = _build_trainer(pb)
    # compression no longer excludes the fused plan
    for _ in range(3):
        _backward_loss(pa, consts)
        ta.step(2)
        _backward_loss(pb, consts)
        tb.step(2)
    assert ta._fused_plan() is not None and ta._fused_plan()[0], \
        "legacy compression store fell off the bucketed path"
    assert _residual_keys(ta), "legacy 2bit routing never quantized"
    # escape hatch beats the legacy routing, bit for bit
    pc = _make_params("le")
    _seed(pc, weights)
    kv2 = mx.kv.create("dist_sync")
    with pytest.warns(DeprecationWarning):
        kv2.set_gradient_compression({"type": "2bit"})
    tc = gluon.Trainer(pc, "sgd", {"learning_rate": 0.05}, kvstore=kv2)
    tc._bucket_bytes_override = 48
    tc._overlap_override = False
    os.environ["GRAFT_QUANT_REDUCE"] = "0"
    for _ in range(3):
        _backward_loss(pc, consts)
        tc.step(2)
    for b, c in zip(pb, pc):
        assert b.data().asnumpy().tobytes() == c.data().asnumpy().tobytes()


# ---------------------------------------------------------------------------
# wire-bytes telemetry + lockstep signature
# ---------------------------------------------------------------------------

def test_reduce_quantized_counts_codes_plus_scales():
    kv = mx.kv.create("dist_sync")
    n = 1000
    x = jnp.asarray(np.random.RandomState(0).randn(n).astype(np.float32))
    codes, scales = quant.encode(x, "int8", 256)
    from incubator_mxnet_tpu.ndarray import NDArray
    pair = (NDArray(codes), NDArray(scales))
    snap0 = tmetrics.compact_snapshot()
    kv.reduce_quantized([pair], [n], "int8", 256, label="t")
    snap1 = tmetrics.compact_snapshot()
    d_raw = snap1.get("graft_kvstore_push_bytes_total", 0) \
        - snap0.get("graft_kvstore_push_bytes_total", 0)
    d_wire = snap1.get("graft_kvstore_wire_bytes_total", 0) \
        - snap0.get("graft_kvstore_wire_bytes_total", 0)
    assert d_raw == 4 * n
    assert d_wire == quant.wire_nbytes(n, "int8", 256)
    assert d_raw / d_wire >= 3.5


def test_quant_signature_folds_into_lockstep():
    kv = mx.kv.create("dist_sync")
    wire, sig = kv._quant_signature([1000], "int8", 256)
    assert sig == "q:int8:b256:nb4"
    assert wire == quant.wire_nbytes(1000, "int8", 256)
    lockstep.reset()
    try:
        lockstep.fold(1, "reduce_quant", n_keys=1, nbytes=wire, keys=[sig])
        _, h_a = lockstep.state()
        lockstep.reset()
        _, sig_b = kv._quant_signature([1000], "int8", 128)
        wire_b = quant.wire_nbytes(1000, "int8", 128)
        lockstep.fold(1, "reduce_quant", n_keys=1, nbytes=wire_b,
                      keys=[sig_b])
        _, h_b = lockstep.state()
        assert h_a != h_b, \
            "a mismatched GRAFT_QUANT_BLOCK must diverge the digest"
        lockstep.reset()
        lockstep.fold(1, "reduce_quant", n_keys=1, nbytes=wire, keys=[sig])
        _, h_c = lockstep.state()
        assert h_c == h_a, "identical quant config must agree"
    finally:
        lockstep.reset()


def test_tsan_clean_overlapped_quant_loop():
    """The overlapped quantized loop — grad-ready hooks issuing
    reduce_quantized_async mid-backward, EF residual read/write in the
    Updater store — must be EH2xx-silent."""
    tsan.set_enabled(True)
    tsan.clear()
    try:
        weights, consts = _fixtures()
        ps = _make_params("ts")
        _seed(ps, weights)
        t = _build_trainer(ps, overlap=True)
        os.environ["GRAFT_QUANT_REDUCE"] = "int8"
        for _ in range(4):
            with engine.bulk(32):
                _backward_loss(ps, consts)
            t.step(2)
        assert t._scheduler.issued_total > 0, "overlap never engaged"
        assert tsan.reports() == [], tsan.reports()
    finally:
        tsan.set_enabled(None)
        tsan.clear()


# ---------------------------------------------------------------------------
# ZeRO-1 sharded update (8-ctx mesh from conftest)
# ---------------------------------------------------------------------------

def _mesh_fixtures(seed=6, specs=SPECS):
    ctxs = [mx.cpu(i) for i in range(8)]
    rs = np.random.RandomState(seed)
    weights = [rs.randn(*s).astype(np.float32) for s in specs]
    base = [rs.randn(*s).astype(np.float32) for s in specs]
    consts = [[mx.nd.array(c * (j + 1), ctx=ctx)
               for j, ctx in enumerate(ctxs)] for c in base]
    return ctxs, weights, consts


def _mesh_step(ctxs, ps, t, consts):
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
    t.step(len(ctxs))


def _mesh_build(prefix, ctxs, weights, optimizer="sgd", opt_kw=None):
    ps = _make_params(prefix, ctx=ctxs)
    _seed(ps, weights)
    t = gluon.Trainer(ps, optimizer,
                      dict(opt_kw or {"learning_rate": 0.05,
                                      "momentum": 0.9}),
                      kvstore=mx.kv.create("dist_sync"))
    t._bucket_bytes_override = 48
    return ps, t


def test_zero_sgd_momentum_byte_parity_and_gauge():
    ctxs, weights, consts = _mesh_fixtures()
    pa, ta = _mesh_build("u", ctxs, weights)
    for _ in range(4):
        _mesh_step(ctxs, pa, ta, consts)
    unsharded_bytes = ta._updaters[0].states_nbytes()
    pb, tb = _mesh_build("z", ctxs, weights)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    for _ in range(4):
        _mesh_step(ctxs, pb, tb, consts)
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
    for a, b in zip(pa, pb):
        ra = a.list_data()[0].asnumpy()
        rb = b.list_data()[0].asnumpy()
        assert ra.tobytes() == rb.tobytes(), \
            "sharded %s diverged from the unsharded ctx-0 replica " \
            "(max |d|=%g)" % (a.name, np.abs(ra - rb).max())
    shard_bytes = max(u.states_nbytes() for u in tb._updaters)
    assert 0 < shard_bytes < unsharded_bytes / 2, \
        "per-shard state %d not ~1/N of %d" % (shard_bytes, unsharded_bytes)
    gauge = float(tmetrics.compact_snapshot().get(
        "graft_trainer_state_shard_bytes", 0.0))
    assert gauge == float(shard_bytes)
    assert float(tmetrics.compact_snapshot().get(
        "graft_trainer_state_shards", 0.0)) == 8.0


def test_zero_adam_single_step_byte_parity():
    """Adam is byte-exact for ONE step (after that the unsharded
    multi-ctx baseline's own replicas diverge — the shared per-index
    update count gives each context its own bias correction; ctx-0 is
    the defined parity target)."""
    ctxs, weights, consts = _mesh_fixtures()
    pa, ta = _mesh_build("ua", ctxs, weights, "adam",
                         {"learning_rate": 0.01})
    _mesh_step(ctxs, pa, ta, consts)
    pb, tb = _mesh_build("za", ctxs, weights, "adam",
                         {"learning_rate": 0.01})
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    _mesh_step(ctxs, pb, tb, consts)
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
    for a, b in zip(pa, pb):
        assert a.list_data()[0].asnumpy().tobytes() == \
            b.list_data()[0].asnumpy().tobytes()


def test_zero_quant_compose_broadcast_consistent():
    """ZeRO + int8: the quantized reduce-scatter feeds the sharded
    update; every context replica must hold the SAME bytes after the
    broadcast, within quant tolerance of the unsharded trajectory."""
    ctxs, weights, consts = _mesh_fixtures()
    pa, ta = _mesh_build("uq", ctxs, weights)
    for _ in range(3):
        _mesh_step(ctxs, pa, ta, consts)
    pb, tb = _mesh_build("zq", ctxs, weights)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    os.environ["GRAFT_QUANT_REDUCE"] = "int8"
    for _ in range(3):
        _mesh_step(ctxs, pb, tb, consts)
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
    del os.environ["GRAFT_QUANT_REDUCE"]
    for p in pb:
        ref = p.list_data()[0].asnumpy()
        for d in p.list_data()[1:]:
            assert d.asnumpy().tobytes() == ref.tobytes(), \
                "broadcast left %s replicas inconsistent" % p.name
    maxdiff = max(
        float(np.abs(a.list_data()[0].asnumpy().astype(np.float64)
                     - b.list_data()[0].asnumpy().astype(np.float64)).max())
        for a, b in zip(pa, pb))
    assert maxdiff < 1.0, maxdiff


def test_save_load_states_refuse_sharded():
    ctxs, weights, consts = _mesh_fixtures()
    ps, t = _mesh_build("sv", ctxs, weights)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    _mesh_step(ctxs, ps, t, consts)
    with pytest.raises(ValueError, match="checkpointer"):
        t.save_states("/tmp/never_written.states")
    with pytest.raises(ValueError, match="checkpointer"):
        t.load_states(b"anything")
    del os.environ["GRAFT_SHARD_OPTIMIZER"]


# ---------------------------------------------------------------------------
# armor: sharded checkpoint round trip + typed ownership error
# ---------------------------------------------------------------------------

def test_armor_sharded_snapshot_roundtrip_with_residuals():
    from incubator_mxnet_tpu.armor.checkpoint import (restore_trainer,
                                                      snapshot_trainer)
    ctxs, weights, consts = _mesh_fixtures()
    pa, ta = _mesh_build("ck", ctxs, weights)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    os.environ["GRAFT_QUANT_REDUCE"] = "int8"
    for _ in range(2):
        _mesh_step(ctxs, pa, ta, consts)
    snap = snapshot_trainer(ta, step=2)
    assert snap["shard"] == {"axis": "ctx", "n": 8, "rank": 0}
    assert snap["optimizer"] is None
    assert len(snap["optimizer_shards"]) == 8
    res_seen = 0
    for blob in snap["optimizer_shards"]:
        states, _opt = pickle.loads(blob)
        for k, v in states.items():
            if quant.is_residual_key(k):
                res_seen += 1
                assert isinstance(v, np.ndarray), \
                    "EF residual persisted as %r, not numpy" % type(v)
    assert res_seen, "no EF residuals captured in the shard blobs"

    pb, tb = _mesh_build("ck", ctxs, weights)
    _mesh_step(ctxs, pb, tb, consts)        # materialize store + plan
    restore_trainer(tb, snap)
    for a, b in zip(pa, pb):
        for da, db in zip(a.list_data(), b.list_data()):
            assert da.asnumpy().tobytes() == db.asnumpy().tobytes()
    # the restored run must continue in LOCKSTEP with the original
    _mesh_step(ctxs, pa, ta, consts)
    _mesh_step(ctxs, pb, tb, consts)
    for a, b in zip(pa, pb):
        assert a.list_data()[0].asnumpy().tobytes() == \
            b.list_data()[0].asnumpy().tobytes()
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
    del os.environ["GRAFT_QUANT_REDUCE"]


def test_armor_shard_ownership_error_both_directions():
    from incubator_mxnet_tpu.armor import ShardOwnershipError
    from incubator_mxnet_tpu.armor.checkpoint import (restore_trainer,
                                                      snapshot_trainer)
    ctxs, weights, consts = _mesh_fixtures()
    # sharded snapshot -> unsharded trainer
    pa, ta = _mesh_build("so", ctxs, weights)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    _mesh_step(ctxs, pa, ta, consts)
    sharded_snap = snapshot_trainer(ta, step=1)
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
    pb, tb = _mesh_build("so", ctxs, weights)
    _mesh_step(ctxs, pb, tb, consts)
    with pytest.raises(ShardOwnershipError) as exc:
        restore_trainer(tb, sharded_snap)
    assert exc.value.saved == {"axis": "ctx", "n": 8, "rank": 0}
    assert exc.value.current is None
    # unsharded snapshot -> sharded trainer
    unsharded_snap = snapshot_trainer(tb, step=1)
    os.environ["GRAFT_SHARD_OPTIMIZER"] = "1"
    with pytest.raises(ShardOwnershipError) as exc:
        restore_trainer(ta, unsharded_snap)
    assert exc.value.saved is None
    assert exc.value.current == {"axis": "ctx", "n": 8, "rank": 0}
    del os.environ["GRAFT_SHARD_OPTIMIZER"]
