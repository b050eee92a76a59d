"""Ring attention: sequence/context parallelism over the mesh "sp" axis.

The reference's long-sequence story is bucketing (SURVEY §5.7); this module
provides the modern capability the TPU build must add: sequences sharded
across devices, attention computed exactly by rotating K/V shards around
the ring with ``ppermute`` over ICI while each device accumulates its Q
shard's online softmax (Ring Attention; the blockwise-parallel formulation).

Communication pattern: P-1 ppermute steps, each overlapped by XLA with the
local (Sq/P × Sk/P) attention block — compute time per block ≫ ICI hop for
realistic shapes, so the ring pipelines cleanly.

Training: ``ring_attention`` carries a custom vjp. The backward makes one
more trip around the ring — each device recomputes its probability tiles
from the saved softmax stats (flash-style rematerialization, O(Sq·Sk/P)
per step, never the full matrix) while the dK/dV accumulators travel with
their K/V blocks and arrive home complete after P hops.

Works on any mesh (tested on the 8-device virtual CPU mesh).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_INF = -1e30
_HI = lax.Precision.HIGHEST


def _block_attn(q, k, v, scale, mask=None):
    """One (local) attention block: returns (unnormalized acc, m, l)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_HI,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = s.max(axis=-1)                                   # (b, h, q)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype), precision=_HI)
    return acc, m, l


def _causal_mask(my, src, sq, sk):
    q_pos = my * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_pos = src * sk + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return (q_pos >= k_pos)[None, None]


def _ring_body(q, k, v, axis_name, causal, scale):
    """Per-device forward: local Q shard attends to all K/V shards as they
    rotate around the ring.  Returns (out, m, l) — the softmax stats are
    the backward's residuals."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]

    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    m = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        # K/V chunk currently held arrived from device (my - i) mod n
        src = (my - i) % n
        mask = _causal_mask(my, src, sq, sk) if causal else None
        blk_acc, blk_m, blk_l = _block_attn(q, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(blk_m - m_new)
        acc = acc * alpha[..., None] + blk_acc * beta[..., None]
        l = l * alpha + blk_l * beta
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, m_new, l, k_nxt, v_nxt

    acc, m, l, _, _ = lax.fori_loop(
        0, n, step, (acc, m, l, k, v),
        unroll=True if isinstance(n, int) else False)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype), m, l


def _ring_bwd_body(q, k, v, out, m, l, g, axis_name, causal, scale):
    """Per-device backward: one more trip around the ring.  dQ accumulates
    locally; dK/dV accumulators travel *with* their K/V blocks and return
    home complete after n hops."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    sq, sk = q.shape[2], k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)   # (b, h, sq)
    # keep (m, l) separate — folding into lse loses log(l) to absorption
    # for rows whose every key is masked (m = -1e30 sentinel)
    l_inv = 1.0 / jnp.maximum(l, 1e-20)

    def step(i, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my - i) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32),
                       precision=_HI,
                       preferred_element_type=jnp.float32) * scale
        mask = _causal_mask(my, src, sq, sk) if causal else None
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - m[..., None]) * l_inv[..., None]
        dv_add = jnp.einsum("bhqk,bhqd->bhkd", p, gf, precision=_HI)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_cur.astype(jnp.float32),
                        precision=_HI)
        ds = p * (dp - delta[..., None]) * scale
        if mask is not None:
            # masked logits are forward constants (`where` routes the grad
            # around them): no dQ/dK through them — matters for rows with
            # no visible keys, where p is uniform rather than 0
            ds = jnp.where(mask, ds, 0.0)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_cur.astype(jnp.float32), precision=_HI)
        dk_add = jnp.einsum("bhqk,bhqd->bhkd", ds, qf, precision=_HI)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur + dk_add, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur + dv_add, axis_name, perm)
        return dq, k_nxt, v_nxt, dk_nxt, dv_nxt

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, _, _, dk, dv = lax.fori_loop(
        0, n, step, (dq0, k, v, dk0, dv0),
        unroll=True if isinstance(n, int) else False)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None):
    """Exact attention over sequence shards; reverse-mode differentiable.

    q/k/v: (B, H, S, D) GLOBAL arrays (sharded or shardable on S over
    ``axis``). Returns the (B, H, S, D) output with the same sharding.
    """
    out, _, _ = _ring_fwd_stats(q, k, v, mesh, axis, causal, scale)
    return out


def _ring_fwd_stats(q, k, v, mesh, axis, causal, scale):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, None, axis, None)
    stat_spec = P(None, None, axis)
    fn = shard_map(
        functools.partial(_ring_body, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, stat_spec, stat_spec),
        check_vma=False)
    return fn(q, k, v)


def _ring_attention_fwd(q, k, v, mesh, axis, causal, scale):
    out, m, l = _ring_fwd_stats(q, k, v, mesh, axis, causal, scale)
    return out, (q, k, v, out, m, l)


def _ring_attention_bwd(mesh, axis, causal, scale, res, g):
    q, k, v, out, m, l = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, None, axis, None)
    stat_spec = P(None, None, axis)
    fn = shard_map(
        functools.partial(_ring_bwd_body, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, stat_spec, stat_spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False)
    return fn(q, k, v, out, m, l, g)


ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)


def ring_attention_sharded(q, k, v, mesh, axis="sp", causal=False,
                           scale=None):
    """Same, but accepts/returns NDArrays (framework surface)."""
    from ..ndarray import NDArray
    qv = q._read() if isinstance(q, NDArray) else q
    kv = k._read() if isinstance(k, NDArray) else k
    vv = v._read() if isinstance(v, NDArray) else v
    sharding = NamedSharding(mesh, P(None, None, axis, None))
    qv = jax.device_put(qv, sharding)
    kv = jax.device_put(kv, sharding)
    vv = jax.device_put(vv, sharding)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh, axis, causal,
                                                 scale))(qv, kv, vv)
    return NDArray(out) if isinstance(q, NDArray) else out
