"""Attention kernels: Pallas flash attention + reference path.

The reference framework predates transformer attention entirely (SURVEY
§2.4: sequence handling = bucketing + fused RNN). These kernels are the
*new capability* SURVEY §7 phase 11 mandates: long-context attention that
maps onto the MXU with O(seq) memory.

* ``flash_attention`` — tiled online-softmax attention as a Pallas TPU
  kernel (one (block_q × d) Q tile resident in VMEM; K/V streamed in
  block_k tiles; running max/sum rescaling). Grid = (batch*heads,
  seq_q/block_q); the K loop is a fori_loop inside the kernel so the MXU
  sees back-to-back (block_q×d)·(d×block_k) matmuls.
* Off the TPU (the CPU test mesh), and for lengths that are not multiples
  of 128, the same math runs as jnp — the kernel is numerics-identical by
  construction and tested against it.  The platform is the one the call is
  lowered for, not the process default; each path runs under its own
  ``named_scope`` and every trace is counted by path
  (``graft_flash_attention_traces_total``).
* Registered as op ``_contrib_FlashAttention`` so both eager NDArray code
  and Symbol graphs can call it (one registry, two modes).
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import metrics as _metrics
from .registry import register

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# reference (jnp) attention — also the CPU path and the vjp recompute
# ---------------------------------------------------------------------------

def _attention_reference(q, k, v, causal=False, scale=None):
    """(B, H, Sq, D), (B, H, Sk, D) → (B, H, Sq, D)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(probs.dtype)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernel
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, sq, sk, causal,
                  scale, block_q):
    from jax.experimental import pallas as pl
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, d)
    bq, d = q.shape
    num_kb = sk // block_k
    q_blk = pl.program_id(1)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        # full f32 MXU passes — the default matmul precision on TPU is bf16,
        # which is not acceptable for softmax logits
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            precision=lax.Precision.HIGHEST)   # (bq, bk)
        if causal:
            # query row r may see keys up to r + (sk - sq): the diagonal is
            # anchored at the *end* of the key axis, matching the jnp path's
            # tril(k=sk-sq) — essential for KV-cache decode where Sq != Sk
            q_pos = q_blk * block_q + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos + (sk - sq) >= k_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def _flash_forward_pallas(q, k, v, causal, scale, block_q=128, block_k=128):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    # MXU lanes want D in multiples of 128; typical head dims (64, 96) get
    # zero-padded — padded Q columns contribute nothing to QKᵀ and padded V
    # columns produce output columns we slice off
    Dp = -(-D // 128) * 128
    if Dp != D:
        pad = [(0, 0)] * 3 + [(0, Dp - D)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    qf = q.reshape(B * H, Sq, Dp)
    kf = k.reshape(B * H, Sk, Dp)
    vf = v.reshape(B * H, Sk, Dp)
    grid = (B * H, Sq // block_q)
    kernel = functools.partial(_flash_kernel, block_k=block_k, sq=Sq, sk=Sk,
                               causal=causal, scale=scale, block_q=block_q)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, Dp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Sq * Sk * Dp,
            bytes_accessed=(qf.size + kf.size + vf.size) * 4,
            transcendentals=B * H * Sq * Sk),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, Dp)[..., :D]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, scale=None):
    """softmax(QKᵀ·scale)·V with O(seq) memory.

    Pallas kernel where the call runs on a TPU and both lengths are
    multiples of 128; the numerics-identical jnp path otherwise.  Backward
    recomputes attention (flash-style rematerialization) instead of storing
    the (Sq×Sk) probability matrix.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def pallas(q, k, v):
        with jax.named_scope("flash_attention_pallas"):
            return _flash_forward_pallas(q, k, v, causal, scale)

    def reference(q, k, v):
        with jax.named_scope("flash_attention_reference"):
            return _attention_reference(q, k, v, causal, scale)

    if q.shape[2] % 128 or k.shape[2] % 128:
        # O(S²) memory on any platform: worth a counter of its own
        _metrics.flash_attention_trace("reference_unaligned")
        return reference(q, k, v)
    if isinstance(q, jax.core.Tracer):
        # a tracer has no device: the program it is staged into picks the
        # branch when it is lowered for the platform its operands live on
        _metrics.flash_attention_trace("lowering_platform")
        return lax.platform_dependent(q, k, v, tpu=pallas, default=reference)
    if all(d.platform == "tpu" for d in q.devices()):
        _metrics.flash_attention_trace("pallas")
        return pallas(q, k, v)
    _metrics.flash_attention_trace("reference_off_tpu")
    return reference(q, k, v)


def _kv_block_size(sk):
    """Largest power-of-two K-chunk ≤1024 dividing sk (else no chunking)."""
    for b in (1024, 512, 256, 128, 64):
        if sk % b == 0:
            return b
    return sk


def _flash_fwd(q, k, v, causal, scale):
    out = flash_attention(q, k, v, causal, scale)
    return out, (q, k, v, out)


def _flash_bwd(causal, scale, res, g):
    """Flash-style backward: two chunked passes over the key axis, never
    materializing the (Sq × Sk) score matrix — backward memory matches the
    forward's O(Sq · block) profile.

    Pass 1 recovers the softmax log-normalizer with an online max/sum scan;
    pass 2 rebuilds each probability tile from (logits − lse) and
    accumulates dQ (carried) and per-tile dK/dV (scan outputs).

    All of it is staged under one ``named_scope``, so that a trace's
    reduction finds the operations of both scans by their ``op_name``
    (they are ``fusion`` ops inside two ``while`` loops, named like every
    other matmul).
    """
    with jax.named_scope("flash_attention_bwd"):
        return _flash_bwd_scans(causal, scale, res, g)


def _flash_bwd_scans(causal, scale, res, g):
    q, k, v = res[0], res[1], res[2]
    out = res[3]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    dtype_in = q.dtype
    Sq, Sk = q.shape[2], k.shape[2]
    block = _kv_block_size(Sk)
    nb = Sk // block
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    kb = k.astype(jnp.float32).reshape(*k.shape[:2], nb, block, k.shape[-1])
    vb = v.astype(jnp.float32).reshape(*v.shape[:2], nb, block, v.shape[-1])
    kb = jnp.moveaxis(kb, 2, 0)                       # (nb, B, H, blk, D)
    vb = jnp.moveaxis(vb, 2, 0)
    q_pos = jnp.arange(Sq)[:, None] + (Sk - Sq)       # diag anchored at end

    hi = jax.lax.Precision.HIGHEST  # bf16 MXU passes would desync p from out

    def scores(k_blk, i):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk, precision=hi,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = i * block + jnp.arange(block)[None, :]
            mask = q_pos >= k_pos
            return jnp.where(mask, s, _NEG_INF), mask
        return s, None

    def stat_step(carry, xs):
        m_prev, l_prev = carry
        k_blk, i = xs
        s, _ = scores(k_blk, i)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        l_new = l_prev * jnp.exp(m_prev - m_new) + \
            jnp.exp(s - m_new[..., None]).sum(axis=-1)
        return (m_new, l_new), None

    m0 = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    (m, l), _ = lax.scan(stat_step, (m0, l0), (kb, jnp.arange(nb)))
    # keep (m, l) separate: folding into m + log(l) loses log(l) to float
    # absorption when m is the -1e30 sentinel (rows with no visible keys)
    l_inv = 1.0 / jnp.maximum(l, 1e-20)
    delta = (gf * out.astype(jnp.float32)).sum(-1)    # (B, H, Sq)

    def grad_step(dq_acc, xs):
        k_blk, v_blk, i = xs
        s, mask = scores(k_blk, i)
        p = jnp.exp(s - m[..., None]) * l_inv[..., None]
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf, precision=hi)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_blk, precision=hi)
        ds = p * (dp - delta[..., None]) * scale
        if mask is not None:
            # masked logits are constants in the forward (`where` routes the
            # gradient around them), so they carry no dQ/dK — matters for
            # rows with no visible keys, where p is uniform, not 0
            ds = jnp.where(mask, ds, 0.0)
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk,
                                     precision=hi)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf, precision=hi)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk, dv) = lax.scan(grad_step, dq0, (kb, vb, jnp.arange(nb)))
    dk = jnp.moveaxis(dk, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv, 0, 2).reshape(v.shape)
    return (dq.astype(dtype_in), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@register("_contrib_FlashAttention", num_inputs=3,
          aliases=("flash_attention", "_contrib_DotProductAttention"))
def _flash_attention_op(q, k, v, causal=False, scale=None):
    """Registered op wrapper — (B, H, S, D) inputs."""
    return flash_attention(q, k, v, causal, scale)


# graftlint: disable=GL302 -- `eager` is a host are-we-staging bool from dispatch_on_mesh, not a traced value; branching on it is the point
@register("_contrib_RingAttention", num_inputs=3, no_jit=True,
          aliases=("ring_attention",))
def _ring_attention_op(q, k, v, seq_axis="sp", causal=False, scale=None):
    """Exact attention over sequence shards (B, H, S, D): S is sharded on
    the mesh axis ``seq_axis`` and K/V blocks rotate over ICI
    (parallel/ring_attention.py).  The mesh comes from the enclosing
    ``parallel.use_mesh`` scope — the op itself stays array-in/array-out
    like every registry op.  The modern capability mandated over the
    reference's bucketing story (SURVEY §5.7)."""
    from ..parallel.mesh import current_mesh
    mesh = current_mesh(required=True)
    if seq_axis not in mesh.axis_names:
        raise ValueError("mesh %s has no axis %r for ring attention"
                         % (mesh.axis_names, seq_axis))
    from ..parallel.ring_attention import ring_attention
    from ..parallel.mesh import dispatch_on_mesh, gather_home
    from jax.sharding import PartitionSpec
    spec = PartitionSpec(None, None, seq_axis, None)
    out, eager = dispatch_on_mesh(
        lambda a, b, c: ring_attention(a, b, c, mesh, seq_axis, causal,
                                       scale),
        mesh, (spec, spec, spec), q, k, v)
    # staging (inside e.g. the DataParallelTrainer step over a dp×sp
    # mesh): output STAYS sequence-sharded; eager: gather home so
    # downstream single-device ops see a plain array
    return gather_home(out, mesh) if eager else out
