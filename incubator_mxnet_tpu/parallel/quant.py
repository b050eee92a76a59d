"""graftzero wire: block-scaled quantized bucket allreduce.

EQuARX-style (arXiv:2506.17615) block-scaled quantization for the
bucketed gradient wire (graftfuse/graftlap/graftduplex): each
bucket's flat gradient is cut into blocks of ``GRAFT_QUANT_BLOCK``
elements (default 256), every block gets one f32 scale, and the values
ride as narrow integer codes:

* ``int8`` — codes in [-127, 127], scale = max|block| / 127.  Wire is
  ~n + n/block·4 bytes vs 4n dense f32 (≥3.5x at the default block).
* ``2bit`` — codes in {-1, 0, +1} (packed 16 per uint32 word, the
  gradient_compression.h wire format), scale = max|block|, threshold at
  scale/2.  Wire is ~n/4 + n/block·4 bytes.

The payload of one bucket is (codes, scales) — one packed code buffer
plus one scale vector — and it crosses the wire as ONE collective
program: on the multi-worker mesh an all-to-all ships every worker its
contiguous shard of blocks (codes AND scales), the shard is dequantized
per source and summed in f32, the shard SUM is re-quantized with fresh
scales, and the replicated output all-gathers only the narrow codes +
scales (the EQuARX reduce-scatter + all-gather — no f32 collective
anywhere).  Single-worker stores reduce nothing; the payload round-trips
encode→decode locally so the algebra (and the byte accounting) is
identical everywhere.

Quantization error is recycled through ERROR FEEDBACK: the residual
``acc - dequant(quant(acc))`` of every bucket is kept in the Updater
state store (string-keyed beside the per-param optimizer state), so
``save_states``/``load_states`` and graftarmor checkpoint/resume carry
it for free and quantized-SGD converges to the float fixed point (the
classic EF-SGD telescoping argument; see the selftest).

Tolerance contract (documented in docs/observability.md): for one
encode→decode round trip the per-element error is bounded by
``max|block| / 254`` for int8 (half a code step) and ``max|block| / 2``
for 2bit; error feedback keeps the ACCUMULATED error of a training
trajectory bounded by one step's quantization error instead of growing
with step count.

``GRAFT_SHARD_OPTIMIZER=1`` (ZeRO-1) helpers live here too: the
contiguous bucket→owner assignment used by the Trainer's sharded fused
update.
"""
from __future__ import annotations

import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["resolve_mode", "resolve_block", "zero_enabled", "MODES",
           "wire_nbytes", "n_blocks", "encode", "decode",
           "reduce_payload_sum", "shard_owners", "BucketQuantizer",
           "QuantReduceHandle", "selftest"]

MODES = ("int8", "2bit")
_LANES = 16              # 2-bit codes per uint32 word
_DEFAULT_BLOCK = 256


def resolve_mode(override=None):
    """The active quant mode: ``GRAFT_QUANT_REDUCE`` ∈ {int8, 2bit}
    enables; ``0``/``off``/unset disables.  ``override`` is the
    deprecated ``set_gradient_compression("2bit")`` routing — the env
    var always wins, so ``GRAFT_QUANT_REDUCE=0`` stays the bit-identical
    escape hatch even with compression params set."""
    raw = os.environ.get("GRAFT_QUANT_REDUCE", "").strip().lower()
    if raw in MODES:
        return raw
    if raw in ("0", "off", "false", "no"):
        return None
    return override if override in MODES else None


def resolve_block():
    """GRAFT_QUANT_BLOCK elements per scale block (default 256), rounded
    up to a multiple of 16 so 2-bit word packing never straddles a
    block boundary."""
    try:
        b = int(os.environ.get("GRAFT_QUANT_BLOCK", str(_DEFAULT_BLOCK)))
    except ValueError:
        b = _DEFAULT_BLOCK
    b = max(b, _LANES)
    return ((b + _LANES - 1) // _LANES) * _LANES


def zero_enabled():
    """GRAFT_SHARD_OPTIMIZER (default off): ZeRO-1 sharded fused update —
    each rank/ctx owns a contiguous shard of buckets and holds optimizer
    state only for it."""
    return os.environ.get("GRAFT_SHARD_OPTIMIZER", "").strip().lower() \
        in ("1", "on", "true", "yes")


def n_blocks(n, block):
    return -(-int(n) // int(block))


def wire_nbytes(n, mode, block):
    """Bytes of one n-element payload on the wire: packed codes + f32
    scales.  This is what the kvstore byte counters report for a
    quantized reduce (satellite: wire bytes count quantized bytes, not
    the dequantized size)."""
    nb = n_blocks(n, block)
    if mode == "int8":
        return nb * block + 4 * nb
    if mode == "2bit":
        return nb * (block // _LANES) * 4 + 4 * nb
    raise ValueError("unknown quant mode %r" % (mode,))


# -- kernels (jitted, static block so shapes are compile-time) -------------

@partial(jax.jit, static_argnums=(1,))
def _encode_int8(flat, block):
    n = flat.shape[0]
    nb = n_blocks(n, block)
    x = jnp.pad(flat.astype(jnp.float32),
                (0, nb * block - n)).reshape(nb, block)
    scales = jnp.max(jnp.abs(x), axis=1) / jnp.float32(127.0)
    safe = jnp.where(scales > 0, scales, jnp.float32(1.0))
    codes = jnp.clip(jnp.round(x / safe[:, None]), -127, 127) \
        .astype(jnp.int8).reshape(-1)
    return codes, scales


@partial(jax.jit, static_argnums=(2, 3))
def _decode_int8(codes, scales, n, block):
    nb = n_blocks(n, block)
    vals = codes.astype(jnp.float32).reshape(nb, block) * scales[:, None]
    return vals.reshape(-1)[:n]


def _pack_2bit(codes, nb, bw):
    # codes: (nb, block) uint32 in {0,1,2}; disjoint bit fields — the
    # sum IS the bitwise-or of the shifted lanes
    shifts = (jnp.arange(_LANES, dtype=jnp.uint32) * 2)[None, :]
    return jnp.sum(codes.reshape(nb * bw, _LANES) << shifts, axis=1,
                   dtype=jnp.uint32).reshape(nb, bw)


def _unpack_2bit(words):
    shifts = (jnp.arange(_LANES, dtype=jnp.uint32) * 2)
    return (words[..., None] >> shifts) & jnp.uint32(3)


@partial(jax.jit, static_argnums=(1,))
def _encode_2bit(flat, block):
    n = flat.shape[0]
    nb = n_blocks(n, block)
    bw = block // _LANES
    x = jnp.pad(flat.astype(jnp.float32),
                (0, nb * block - n)).reshape(nb, block)
    scales = jnp.max(jnp.abs(x), axis=1)
    half = scales[:, None] / 2
    codes = jnp.where(x > half, jnp.uint32(1),
                      jnp.where(x < -half, jnp.uint32(2), jnp.uint32(0)))
    return _pack_2bit(codes, nb, bw).reshape(-1), scales


@partial(jax.jit, static_argnums=(2, 3))
def _decode_2bit(words, scales, n, block):
    nb = n_blocks(n, block)
    bw = block // _LANES
    c = _unpack_2bit(words.reshape(nb, bw))            # (nb, bw, 16)
    sign = jnp.where(c == 1, 1.0, jnp.where(c == 2, -1.0, 0.0))
    vals = sign.reshape(nb, block) * scales[:, None]
    return vals.reshape(-1)[:n]


def encode(flat, mode, block):
    """flat f32-like 1-D → (codes, scales).  codes is int8[nb·block]
    (int8) or packed uint32[nb·block/16] (2bit); scales is f32[nb]."""
    if mode == "int8":
        return _encode_int8(flat.ravel(), int(block))
    if mode == "2bit":
        return _encode_2bit(flat.ravel(), int(block))
    raise ValueError("unknown quant mode %r" % (mode,))


def decode(codes, scales, n, mode, block):
    """(codes, scales) → f32[n] dequantized values."""
    if mode == "int8":
        return _decode_int8(codes, scales, int(n), int(block))
    if mode == "2bit":
        return _decode_2bit(codes, scales, int(n), int(block))
    raise ValueError("unknown quant mode %r" % (mode,))


# -- the multi-worker payload collective -----------------------------------

_reduce_jit_cache = {}


def _payload_reduce_jitted(mesh, W, kb, block, mode):
    """Jit: this worker's (W·kb)-block payload sharded over 'worker' →
    replicated re-quantized SUM payload.  Per shard-map block: all_to_all
    ships each destination its kb-block slice of codes AND scales from
    every worker (the quantized reduce-scatter), the block dequantizes
    ONLY its shard per source, sums in f32, re-quantizes the shard sum
    with fresh scales, and the replicated out_sharding makes GSPMD
    all-gather the narrow codes + scales — no f32 collective anywhere
    (same lowering discipline as compression._rs_jitted)."""
    key = (mesh, W, kb, block, mode)
    fn = _reduce_jit_cache.get(key)
    if fn is None:
        from jax import lax, shard_map
        bw = block // _LANES

        def body(codes_blk, scales_blk):
            s = scales_blk[0].reshape(W, kb)
            srecv = lax.all_to_all(s, "worker", split_axis=0,
                                   concat_axis=0, tiled=False)
            if mode == "int8":
                c = codes_blk[0].reshape(W, kb, block)
                crecv = lax.all_to_all(c, "worker", split_axis=0,
                                       concat_axis=0, tiled=False)
                tot = jnp.sum(crecv.astype(jnp.float32)
                              * srecv[..., None], axis=0)   # (kb, block)
                ns = jnp.max(jnp.abs(tot), axis=1) / jnp.float32(127.0)
                safe = jnp.where(ns > 0, ns, jnp.float32(1.0))
                nc = jnp.clip(jnp.round(tot / safe[:, None]), -127, 127) \
                    .astype(jnp.int8).reshape(1, kb * block)
            else:
                w = codes_blk[0].reshape(W, kb, bw)
                wrecv = lax.all_to_all(w, "worker", split_axis=0,
                                       concat_axis=0, tiled=False)
                c = _unpack_2bit(wrecv)                 # (W, kb, bw, 16)
                sign = jnp.where(c == 1, 1.0,
                                 jnp.where(c == 2, -1.0, 0.0))
                vals = sign.reshape(W, kb, block) * srecv[..., None]
                tot = vals.sum(axis=0)                  # (kb, block)
                ns = jnp.max(jnp.abs(tot), axis=1)
                half = ns[:, None] / 2
                qc = jnp.where(tot > half, jnp.uint32(1),
                               jnp.where(tot < -half, jnp.uint32(2),
                                         jnp.uint32(0)))
                nc = _pack_2bit(qc, kb, bw).reshape(1, kb * bw)
            return nc, ns.reshape(1, kb)

        def run(codes_g, scales_g):
            return shard_map(body, mesh=mesh,
                             in_specs=(P("worker", None),
                                       P("worker", None)),
                             out_specs=(P("worker", None),
                                        P("worker", None)),
                             check_vma=False)(codes_g, scales_g)

        fn = jax.jit(run, out_shardings=(NamedSharding(mesh, P()),
                                         NamedSharding(mesh, P())))
        _reduce_jit_cache[key] = fn
    return fn


def reduce_payload_sum(codes, scales, n, mode, block, mesh):
    """Scale-correct quantized all-reduce of one bucket payload: this
    process's (codes, scales) in, the replicated RE-QUANTIZED payload of
    the cross-worker sum out (dequantize with :func:`decode`).  One
    compiled program per (mesh, shape, mode) — the bucket's single
    collective."""
    from .compression import _assemble_worker_global
    W = mesh.shape["worker"]
    nb = n_blocks(n, block)
    kb = -(-nb // W)
    bw = block // _LANES
    per_block = block if mode == "int8" else bw
    codes = jnp.pad(codes.reshape(nb, per_block),
                    ((0, kb * W - nb), (0, 0))).reshape(-1)
    scales = jnp.pad(scales, (0, kb * W - nb))
    fn = _payload_reduce_jitted(mesh, W, kb, block, mode)
    cg = _assemble_worker_global(codes, mesh)
    sg = _assemble_worker_global(scales, mesh)
    oc, os_ = fn(cg, sg)
    oc = jnp.asarray(oc.addressable_data(0))[:nb * per_block]
    os_ = jnp.asarray(os_.addressable_data(0))[:nb]
    return oc, os_


# -- ZeRO-1 shard assignment -----------------------------------------------

def shard_owners(n_buckets, n_shards):
    """Contiguous bucket→owner assignment: bucket k belongs to shard
    ``k * n_shards // n_buckets`` — shards are contiguous runs of the
    plan order and every rank derives the identical map (lockstep)."""
    n_buckets, n_shards = int(n_buckets), max(1, int(n_shards))
    return tuple(min(k * n_shards // max(n_buckets, 1), n_shards - 1)
                 for k in range(n_buckets))


# -- error-feedback bucket quantizer ---------------------------------------

_RES_PREFIX = "__quant_ef__"


def residual_key(indices, dtype):
    """The Updater-store key one bucket's error-feedback residual lives
    under — string-namespaced beside the int per-param optimizer state,
    so ``get_states``/``set_states`` (and armor snapshots) carry it."""
    return "%s/%s:%s" % (_RES_PREFIX, np.dtype(dtype).name,
                         ",".join(str(i) for i in indices))


def is_residual_key(key):
    return isinstance(key, str) and key.startswith(_RES_PREFIX)


class QuantReduceHandle(object):
    """Wraps the in-flight payload reduce of one bucket: ``wait()``
    settles the wire handle, dequantizes the reduced payload INTO the
    bucket's flat buffer and returns ``[flat]`` — drop-in for the
    :class:`~..kvstore.ReduceHandle` the overlap scheduler and the
    Trainer's wait loop already speak."""

    __slots__ = ("_inner", "_flat", "_n", "_mode", "_block", "_decoded")

    def __init__(self, inner, flat, n, mode, block):
        self._inner = inner
        self._flat = flat
        self._n = int(n)
        self._mode = mode
        self._block = int(block)
        self._decoded = False

    @property
    def issued_at(self):
        return self._inner.issued_at

    @property
    def label(self):
        return self._inner.label

    @property
    def done(self):
        return self._inner.done

    @property
    def blocked_s(self):
        return self._inner.blocked_s

    @property
    def inflight_s(self):
        return self._inner.inflight_s

    def wait(self):
        vals = self._inner.wait()
        if not self._decoded:
            self._decoded = True
            codes, scales = vals[0]._read(), vals[1]._read()
            out = decode(codes, scales, self._n, self._mode, self._block)
            self._flat._write(out.astype(self._flat.dtype))
        return [self._flat]

    def abandon(self):
        self._inner.abandon()


class BucketQuantizer(object):
    """Quantized replacement for one step's bucket reduces.

    ``store_fn`` returns the Updater whose ``states`` dict owns the
    error-feedback residuals (the Trainer's ``_updaters[0]`` on the
    fused path, the store-side updater on the duplex path) — keeping
    them there is what makes ``save_states`` / armor checkpoints carry
    them without any extra plumbing."""

    def __init__(self, mode, block, store_fn):
        self.mode = mode
        self.block = int(block)
        self._store_fn = store_fn

    # -- residual store ----------------------------------------------------
    def _residual(self, key, like):
        states = self._store_fn().states
        r = states.get(key)
        if r is None:
            return jnp.zeros_like(like)
        if not isinstance(r, jnp.ndarray):
            # set_states round trip parks residuals as host numpy;
            # rehydrate lazily like sync_state_context does for state
            r = jnp.asarray(np.asarray(r), dtype=like.dtype)
        return r

    def _set_residual(self, key, val):
        self._store_fn().states[key] = val

    # -- the quantize→wire→dequantize round --------------------------------
    def _encode_bucket(self, b, flat):
        """Error-feedback encode of one bucket flat: quantize
        residual+grad, store the NEW residual (local quantization
        error), return the payload."""
        g = flat._read().astype(jnp.float32)
        key = residual_key(b.indices, b.dtype)
        acc = g + self._residual(key, g)
        codes, scales = encode(acc, self.mode, self.block)
        self._set_residual(
            key, acc - decode(codes, scales, g.shape[0],
                              self.mode, self.block))
        return codes, scales

    def reduce_serial(self, kv, buckets, flats):
        """Serial-path replacement for ``kv.reduce_many`` over whole
        buckets: one quantized payload per bucket, ONE wire call for the
        batch, dequantized in place into each flat."""
        from ..ndarray import NDArray
        payloads, metas = [], []
        for b in buckets:
            flat = flats[id(b)]
            codes, scales = self._encode_bucket(b, flat)
            payloads.append((NDArray(codes, ctx=flat._ctx),
                             NDArray(scales, ctx=flat._ctx)))
            metas.append(int(np.prod(flat.shape)))
        kv.reduce_quantized(payloads, metas, self.mode, self.block)
        for b, (codes_nd, scales_nd), n in zip(buckets, payloads, metas):
            flat = flats[id(b)]
            out = decode(codes_nd._read(), scales_nd._read(), n,
                         self.mode, self.block)
            flat._write(out.astype(flat.dtype))
        return flats

    def reduce_async(self, kv, b, flat, label=None):
        """Overlapped-path replacement for ``kv.reduce_many_async`` of
        one bucket: encode now (mid-backward, inside the scheduler's
        offband section), put the payload on the wire, hand back a
        handle whose ``wait()`` dequantizes into ``flat``."""
        from ..ndarray import NDArray
        codes, scales = self._encode_bucket(b, flat)
        n = int(np.prod(flat.shape))
        inner = kv.reduce_quantized_async(
            [(NDArray(codes, ctx=flat._ctx),
              NDArray(scales, ctx=flat._ctx))],
            [n], self.mode, self.block, label=label)
        return QuantReduceHandle(inner, flat, n, self.mode, self.block)


# -- selftest ---------------------------------------------------------------

def _oracle_int8(x, block):
    nb = n_blocks(x.size, block)
    xp = np.pad(x.astype(np.float64), (0, nb * block - x.size)) \
        .reshape(nb, block)
    s = np.abs(xp).max(axis=1) / 127.0
    safe = np.where(s > 0, s, 1.0)
    c = np.clip(np.round(xp / safe[:, None]), -127, 127)
    return (c * s[:, None]).reshape(-1)[:x.size]


def selftest(verbose=True):
    """Exercised by ``python -m incubator_mxnet_tpu.parallel.quant
    --selftest`` (tools/run_lint.sh tier): kernel round trips vs a
    float64 numpy oracle, the documented error bounds, error-feedback
    convergence, the shard-owner map, and (with ≥2 host devices) the
    virtual-mesh payload collective."""
    rs = np.random.RandomState(0)
    block = 64

    # 1. int8 round trip matches the numpy oracle bit-for-bit in f32
    for n in (1, 63, 64, 65, 1000):
        x = rs.randn(n).astype(np.float32)
        codes, scales = encode(jnp.asarray(x), "int8", block)
        got = np.asarray(decode(codes, scales, n, "int8", block))
        want = _oracle_int8(x, block).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        # documented bound: half a code step per element
        bound = np.abs(np.pad(x, (0, n_blocks(n, block) * block - n))
                       .reshape(-1, block)).max(axis=1) / 254.0 + 1e-7
        err = np.abs(got - x).reshape(-1)
        per_blk = np.pad(err, (0, n_blocks(n, block) * block - n)) \
            .reshape(-1, block).max(axis=1)
        assert (per_blk <= bound + 1e-6).all(), (n, per_blk, bound)

    # 2. 2bit round trip: codes land exactly on {-s, 0, +s}
    x = rs.randn(515).astype(np.float32)
    codes, scales = encode(jnp.asarray(x), "2bit", block)
    got = np.asarray(decode(codes, scales, 515, "2bit", block))
    s_per = np.repeat(np.asarray(scales), block)[:515]
    ok = (np.isclose(got, 0) | np.isclose(got, s_per)
          | np.isclose(got, -s_per))
    assert ok.all()

    # 3. wire bytes: int8 beats dense f32 by ≥ 3.5x at the default block
    n = 1 << 20
    assert 4.0 * n / wire_nbytes(n, "int8", resolve_block()) >= 3.5
    assert 4.0 * n / wire_nbytes(n, "2bit", resolve_block()) >= 3.5

    # 4. error feedback drives quantized-SGD to the float fixed point:
    # constant gradient g, lr 0.25 — after T steps the float path moved
    # T·lr·g exactly; the EF path's cumulative dequantized updates
    # telescope to sum(g) - residual_T, so the gap stays bounded by ONE
    # step's quantization error instead of growing with T.
    g = (rs.randn(256) * np.float32(0.7)).astype(np.float32)
    lr = np.float32(0.25)
    res = jnp.zeros(256, jnp.float32)
    w_q = np.zeros(256, np.float32)
    w_f = np.zeros(256, np.float32)
    gaps = []
    for _ in range(40):
        acc = jnp.asarray(g) + res
        codes, scales = encode(acc, "int8", block)
        deq = decode(codes, scales, 256, "int8", block)
        res = acc - deq
        w_q = w_q - lr * np.asarray(deq)
        w_f = w_f - lr * g
        gaps.append(np.abs(w_q - w_f).max())
    one_step = lr * (np.abs(g).reshape(-1, block).max(axis=1) / 254.0
                     + 1e-6).max() * 2
    assert gaps[-1] <= one_step, (gaps[-1], one_step)
    assert gaps[-1] <= max(gaps[:5]) + 1e-6      # bounded, not growing

    # 5. contiguous shard owners
    assert shard_owners(8, 4) == (0, 0, 1, 1, 2, 2, 3, 3)
    assert shard_owners(3, 8) == (0, 2, 5)
    assert shard_owners(5, 1) == (0, 0, 0, 0, 0)

    # 6. virtual-mesh payload collective reproduces the dequantized sum
    # (per-worker payloads laid onto the mesh directly — the single
    # process plays every rank, like the compression virtual-mesh test)
    devs = jax.devices()
    if len(devs) >= 2:
        from jax.sharding import Mesh
        W = min(4, len(devs))
        mesh = Mesh(np.array(devs[:W]), ("worker",))
        n = 300
        nb = n_blocks(n, block)
        kb = -(-nb // W)
        xs = rs.randn(W, n).astype(np.float32)
        pays = [encode(jnp.asarray(x), "int8", block) for x in xs]
        codes_g = jax.device_put(
            jnp.stack([jnp.pad(c.reshape(nb, block),
                               ((0, kb * W - nb), (0, 0))).reshape(-1)
                       for c, _ in pays]),
            NamedSharding(mesh, P("worker")))
        scales_g = jax.device_put(
            jnp.stack([jnp.pad(s, (0, kb * W - nb)) for _, s in pays]),
            NamedSharding(mesh, P("worker")))
        fn = _payload_reduce_jitted(mesh, W, kb, block, "int8")
        oc, os_ = fn(codes_g, scales_g)
        oc = jnp.asarray(oc).reshape(-1)[:nb * block]
        os_ = jnp.asarray(os_).reshape(-1)[:nb]
        got = np.asarray(decode(oc, os_, n, "int8", block))
        want = np.sum([np.asarray(decode(c, s, n, "int8", block))
                       for c, s in pays], axis=0)
        # re-quantization of the shard sum: one more half-step of error
        scale_bound = np.abs(np.pad(want, (0, nb * block - n))) \
            .reshape(nb, block).max(axis=1) / 254.0 + 1e-6
        err = np.abs(got - want)
        per_blk = np.pad(err, (0, nb * block - n)) \
            .reshape(nb, block).max(axis=1)
        assert (per_blk <= scale_bound + 1e-6).all(), \
            (per_blk.max(), scale_bound.max())
    elif verbose:
        print("quant selftest: <2 devices, mesh leg skipped")

    if verbose:
        print("quant selftest: OK")
    return True


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(prog="incubator_mxnet_tpu.parallel.quant")
    p.add_argument("--selftest", action="store_true",
                   help="run the quant/shard kernel selftest")
    args = p.parse_args(argv)
    if args.selftest:
        selftest()
        return 0
    p.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
