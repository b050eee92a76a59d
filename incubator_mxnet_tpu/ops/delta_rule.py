"""The gated delta rule (Yang et al., Gated Delta Networks,
arXiv:2412.06464; the recurrence of ``Qwen3NextGatedDeltaNet``): a linear
recurrence over the sequence whose state is a matrix a head and whose decay
is a matrix update,

    S'  = exp(g_t) S_{t-1}                 one scalar decay a token and head
    u_t = beta_t (v_t - S'^T k_t)          what the state lacks of v_t at k_t
    S_t = S' + k_t u_t^T,   S_0 = 0        (key_dim, value_dim), float32
    o_t = S_t^T q_t

over H heads.  Unrolled, the states of a row of 4096 tokens and 32 heads of
128 x 128 are 8.6 GB: no form here writes them.  The form is the chunked
one (the WY representation, as ``torch_chunk_gated_delta_rule`` and the
``fla`` kernels have it), whose products are matmuls:

* chunks of ``CHUNK`` = 64 steps.  With ``c`` the running sum of g inside a
  chunk and ``D[i, j] = exp(c_i - c_j)`` for j <= i, a chunk that starts
  from the state S has ``(I + L) U = V_b - (K_b * e^c) S`` where ``L`` is
  the strictly lower part of ``(K_b K^T) * D`` and ``K_b, V_b`` the rows
  times beta: ``U = T V_b - T (K_b * e^c) S`` with ``T = (I + L)^-1`` in
  float32 by block forward substitution (``_unit_lower_inverse``: it stays
  exact where keys repeat; a product of powers of L over all 64 rows does
  not).  Then
  ``O = (Q * e^c) S + ((Q K^T) * D) U`` and the state after the chunk is
  ``e^(c_last) S + (K * e^(c_last - c))^T U``.  Every exponent is <= 0.
* ``_intra`` makes, for every chunk at once and as batched matmuls, the five
  operands that do not depend on the state (``T V_b``, ``T (K_b * e^c)``,
  ``Q * e^c``, ``(Q K^T) * D``, ``K * e^(c_last - c)``); the walk over a
  row's chunks (``_step`` a chunk: ``_inter``) carries S and makes the three
  state products.  State, decays and the inverse are float32; the products of
  q, k, v take the operands in their own dtype with float32 accumulation;
  the products with the state are float32 at the ambient matmul precision
  (a bf16 pass on a TPU inside a bf16 step, full precision under
  ``jax.default_matmul_precision("highest")``).
* ``gated_delta_rule`` is one ``custom_vjp``: the forward keeps q, k, v, g,
  beta and the states at the chunks' borders (S / 64 x H x key_dim x
  value_dim float32, 134 MB a layer at the cell's shape) and nothing of a
  chunk's inside; the backward makes ``_intra`` again, walks the chunks from
  the last (``_inter_bwd``: each chunk's own adjoint from its border, the
  state's cotangent carried) and takes ``_intra``'s ``vjp``.
* The walk has two executors.  On a TPU two Pallas (Mosaic) kernels,
  ``delta_rule_pallas`` and ``delta_rule_bwd``: grid (rows, blocks of 16
  heads, chunks), the chunks last and sequential, the block's states
  (forward) or their cotangents (backward) in VMEM scratch from chunk to
  chunk; a grid step reads its chunk of the five operands straight from
  ``_intra``'s (B, H, N, C, D) arrays through the index maps, the backward
  from the last chunk; only the borders' states pass through HBM, once.
  The backward makes u again from the border (one product) and writes out
  ``_step``'s adjoint, eight more.  Elsewhere (the CPU test mesh) and for
  shapes the kernels do not take (keys and values no whole tiles of 128
  lanes) a ``lax.scan`` over the chunks with the chunk's ``jax.vjp`` in the
  backward: the same ``_step``, and what the interpret-mode tests hold the
  kernels to.  ``_intra``, its ``vjp`` and what the rule keeps are the same
  under both.  The platform is the one the call is lowered for
  (``lax.platform_dependent``), as in ``ops/ssm``; a kernel's body does not
  inherit the ambient matmul precision, so it is read where the call is
  traced and handed in.
* Every trace is counted by form (``graft_delta_rule_traces_total{form}``:
  ``kernel``, ``lowering_platform`` or ``chunked`` the forward, the same
  with ``_bwd`` after it the backward) with the chunk and the state's bytes
  as gauges.  Registered as ``_contrib_GatedDeltaRule``.

k and q may have fewer heads than v (a divisor): value head h then reads
key head ``h // (H_v / H_k)``, repeated inside the rule so that what is
kept for the backward is not.  A length that is no multiple of the chunk is
padded with rows of ``beta = 0, g = 0``, which change nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import metrics as _metrics
from .registry import register

CHUNK = 64                      # time steps between two saved states


def _by_chunk(t, chunk):
    """(B, H, S, ...) -> (B, H, S / chunk, chunk, ...)."""
    return t.reshape(t.shape[:2] + (-1, chunk) + t.shape[3:])


_BASE = 8                       # the diagonal blocks inverted by powers


def _dot32(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """``(I + L)^-1`` for the strictly lower triangular ``lower`` (..., C, C)
    in float32, C a power of two times ``_BASE``: block forward substitution
    written as whole (C, C) products, which the MXU takes, where the
    compiler's own triangular solve inverts row by row (2.7 ms a layer's
    2048 systems on the v5e: PERF.md section 6, PR 48).  The ``_BASE``-wide
    diagonal blocks by ``(I + N)(I + N^2)(I + N^4)`` with ``N = -L`` there
    (N^8 = 0, and over 8 rows the powers' entries stay small where over 64
    they cancel catastrophically); then blocks of s join to blocks of 2 s:
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``, with the
    known inverses block-diagonal and ``C`` picked by a mask.  Full float32
    products whatever the ambient matmul precision.  Its backward rule is
    the inverse's own, ``dL = -T^T G T^T`` from the result T: two products,
    where differentiating the ten above would make twenty."""
    size = lower.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    dot = _dot32
    eye = jnp.eye(size, dtype=lower.dtype)
    power = jnp.where(row // _BASE == col // _BASE, -lower, 0.0)
    inverse = eye + power
    for _ in range(_BASE.bit_length() - 2):         # N^2, N^4
        power = dot(power, power)
        inverse = dot(inverse, eye + power)
    width = _BASE
    while width < size:
        below = (row // (2 * width) == col // (2 * width)) & (
            row // width == col // width + 1)
        inverse = inverse - dot(dot(inverse, jnp.where(below, lower, 0.0)),
                                inverse)
        width *= 2
    return inverse


def _unit_lower_inverse_fwd(lower):
    inverse = _unit_lower_inverse(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    turned = jnp.swapaxes(inverse, -1, -2)
    return (-_dot32(_dot32(turned, g), turned),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _intra(q, k, v, g, beta, chunk):
    """What a chunk's step needs beside the state, for every chunk at once:
    ``(w, kc, qg, a, kd, last)`` with w = T V_b (.., C, Dv), kc = T (K_b *
    e^c) (.., C, Dk), qg = Q * e^c, a = (Q K^T) * D (.., C, C), kd = K *
    e^(c_last - c) and last = e^(c_last) (..,), all float32 and shaped
    (B, H, chunks, ...).  ``q``, ``k`` (B, Hk, S, Dk), ``v`` (B, H, S, Dv),
    ``g``, ``beta`` (B, H, S)."""
    f32 = jnp.float32
    heads = v.shape[1]
    if k.shape[1] != heads:
        q, k = (jnp.repeat(t, heads // k.shape[1], axis=1) for t in (q, k))
    q, k, v, g, beta = (_by_chunk(t, chunk) for t in (q, k, v, g, beta))
    c = jnp.cumsum(g.astype(f32), axis=-1)                  # (B, H, N, C)
    beta = beta.astype(f32)[..., None]
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # exp of the masked difference, not the mask of the exp: above the
    # diagonal the difference is positive and may overflow
    decay = jnp.exp(jnp.where(row >= col, c[..., :, None] - c[..., None, :],
                              -jnp.inf))
    k_b = k.astype(f32) * beta
    grow = jnp.exp(c)[..., None]
    lower = jnp.where(row > col, jnp.einsum(
        "bhnik,bhnjk->bhnij", k_b.astype(k.dtype), k,
        preferred_element_type=f32) * decay, 0.0)
    # the solve's two right-hand sides, in full float32 as the inverse is
    inverse = _unit_lower_inverse(lower)
    w = _dot32(inverse, v.astype(f32) * beta)
    kc = _dot32(inverse, k_b * grow)
    a = jnp.einsum("bhnik,bhnjk->bhnij", q, k,
                   preferred_element_type=f32) * decay
    kd = k.astype(f32) * jnp.exp(c[..., -1:] - c)[..., None]
    return w, kc, q.astype(f32) * grow, a, kd, jnp.exp(c[..., -1])


def _step(state, w, kc, qg, a, kd, last):
    """One chunk from the state (B, H, Dk, Dv) at its border -> (the chunk's
    outputs (B, H, C, Dv), the state after it)."""
    u = w - jnp.einsum("bhck,bhkv->bhcv", kc, state)
    out = (jnp.einsum("bhck,bhkv->bhcv", qg, state)
           + jnp.einsum("bhij,bhjv->bhiv", a, u))
    after = (last[..., None, None] * state
             + jnp.einsum("bhck,bhcv->bhkv", kd, u))
    return out, after


def _chunks_first(parts):
    """Each (B, H, N, ...) with the chunks' axis first, as ``scan`` reads."""
    return tuple(jnp.moveaxis(t, 2, 0) for t in parts)


def _inter(parts):
    """The scan over a row's chunks: (o (B, H, N, C, Dv) float32, the states
    at the chunks' starts (N, B, H, Dk, Dv) float32)."""
    w, kc = parts[0], parts[1]

    def step(state, xs):
        out, after = _step(state, *xs)
        return after, (out, state)

    zero = jnp.zeros(w.shape[:2] + (kc.shape[-1], w.shape[-1]), jnp.float32)
    _, (out, borders) = lax.scan(step, zero, _chunks_first(parts))
    return jnp.moveaxis(out, 0, 2), borders


def _inter_bwd(parts, borders, g_out):
    """The cotangents of ``parts`` from the output's, a chunk at a time from
    the last: the chunk's own ``vjp`` from its saved border, the state's
    cotangent carried."""
    def step(d_state, xs):
        border, g_chunk, *of_chunk = xs
        _, vjp = jax.vjp(_step, border, *of_chunk)
        d_state, *d_parts = vjp((g_chunk, d_state))
        return d_state, tuple(d_parts)

    xs = (borders, jnp.moveaxis(g_out, 2, 0)) + _chunks_first(parts)
    _, d_parts = lax.scan(step, jnp.zeros_like(borders[0]), xs, reverse=True)
    return tuple(jnp.moveaxis(t, 0, 2) for t in d_parts)


# ---------------------------------------------------------------------------
# Pallas kernels: the same walk with the state in VMEM
# ---------------------------------------------------------------------------
# Grid (rows, blocks of heads, chunks), the chunks last and sequential.  A grid
# step holds one chunk of a block of heads: its operands as (heads, C, D)
# tiles cut from ``_intra``'s (B, H, N, C, D) arrays by the index maps, the
# state (forward) or its cotangent (backward) as (heads, Dk, Dv) of scratch
# that stays from chunk to chunk.  ``last`` comes and its cotangent goes as a
# row of Dv lanes a head and chunk.

_LANES = 128
_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _ambient_precision():
    """What ``jnp`` gives a float32 product where the kernel is traced,
    which a kernel's body does not inherit: ``DEFAULT`` (on a TPU one bf16
    pass) unless ``jax.default_matmul_precision`` asks for more, and then
    ``HIGHEST``, the one other precision Mosaic has."""
    name = jax.config.jax_default_matmul_precision
    low = name in (None, "default", "bfloat16", "BF16_BF16_F32")
    return lax.Precision.DEFAULT if low else lax.Precision.HIGHEST


def _dot(a, b, dims, precision):
    """A product of tiles with float32 accumulation: full float32 at
    ``HIGHEST``; at ``DEFAULT`` one bf16 pass, which is what XLA makes of a
    float32 product there and Mosaic by itself does not."""
    dtype = jnp.bfloat16 if precision == lax.Precision.DEFAULT else jnp.float32
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _walk_kernel(w_ref, kc_ref, qg_ref, a_ref, kd_ref, last_ref, out_ref,
                 border_ref, state_ref, *, precision):
    """``_step`` for a block of heads, the state in ``state_ref``."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    dot = functools.partial(_dot, precision=precision)
    border_ref[...] = state_ref[...]
    for h in range(state_ref.shape[0]):
        state = state_ref[h]
        u = w_ref[h] - dot(kc_ref[h], state, _NN)
        out = dot(qg_ref[h], state, _NN) + dot(a_ref[h], u, _NN)
        out_ref[h] = out.astype(out_ref.dtype)
        state_ref[h] = last_ref[h] * state + dot(kd_ref[h], u, _TN)


def _walk_bwd_kernel(w_ref, kc_ref, qg_ref, a_ref, kd_ref, last_ref,
                     border_ref, g_ref, dw_ref, dkc_ref, dqg_ref, da_ref,
                     dkd_ref, dlast_ref, carry_ref, *, precision):
    """``_step``'s adjoint for a block of heads, the chunks from the last:
    u again from the chunk's border, the six cotangents, and the state's
    cotangent in ``carry_ref``."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    dot = functools.partial(_dot, precision=precision)
    for h in range(carry_ref.shape[0]):
        state, d_after, g = border_ref[h], carry_ref[h], g_ref[h]
        kc, qg = kc_ref[h], qg_ref[h]
        u = w_ref[h] - dot(kc, state, _NN)
        du = dot(a_ref[h], g, _TN) + dot(kd_ref[h], d_after, _NN)
        dw_ref[h] = du
        dkc_ref[h] = -dot(du, state, _NT)
        dqg_ref[h] = dot(g, state, _NT)
        da_ref[h] = dot(g, u, _NT)
        dkd_ref[h] = dot(u, d_after, _NT)
        dlast_ref[h] = (d_after * state).sum(axis=0, keepdims=True)
        carry_ref[h] = (last_ref[h] * d_after + dot(qg, g, _TN)
                        - dot(kc, du, _TN))


def _head_block(heads):
    return next(b for b in (16, 8, 4, 2, 1) if heads % b == 0)


def _kernels_take(parts):
    """Whether the kernels take the call: keys and values whole tiles of
    128 lanes, the chunk whole (16, 128) tiles of bf16 rows."""
    w, kc = parts[0], parts[1]
    return (w.shape[-1] % _LANES == 0 and kc.shape[-1] % _LANES == 0
            and w.shape[-2] % 16 == 0)


def _layout(parts, backward, precision):
    """(grid, scratch, specs, operands) of a walk over ``parts``, the chunks
    from the last if ``backward``: the block specs of w, kc, qg, a, kd,
    ``last`` a row of lanes and the borders, in that order, which are also
    those of their cotangents; the kernel's first six operands.  kc, qg, a
    and kd are operands of products and nothing else: where those take one
    bf16 pass they are handed over in bf16, as the compiler hands them to
    the ``lax.scan`` (the cast rides in the fusion that makes them, and
    they cross HBM at half the bytes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    w, kc, last = parts[0], parts[1], parts[5]
    batch, heads, chunks, chunk, dv = w.shape
    dk, hb = kc.shape[-1], _head_block(heads)

    def at(i):
        return chunks - 1 - i if backward else i

    def tile(rows, lanes):
        return pl.BlockSpec((None, hb, None, rows, lanes),
                            lambda b, h, i: (b, h, at(i), 0, 0))

    value, key = tile(chunk, dv), tile(chunk, dk)
    border = pl.BlockSpec((None, None, hb, dk, dv),
                          lambda b, h, i: (at(i), b, h, 0, 0))
    rows = jnp.broadcast_to(last[..., None, None], last.shape + (1, dv))
    factors = parts[1:5]
    if precision == lax.Precision.DEFAULT:
        factors = tuple(t.astype(jnp.bfloat16) for t in factors)
    return ((batch, heads // hb, chunks),
            [pltpu.VMEM((hb, dk, dv), jnp.float32)],
            [value, key, key, tile(chunk, chunk), key, tile(1, dv), border],
            (w, *factors, rows))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          operands, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )(*operands)


def _inter_pallas(*parts, dtype=jnp.float32, interpret=False):
    """``_inter`` as a kernel, the outputs in ``dtype``."""
    precision = _ambient_precision()
    grid, scratch, specs, ins = _layout(parts, False, precision)
    w, kc = parts[0], parts[1]
    batch, heads, chunks = w.shape[:3]
    states = (chunks, batch, heads, kc.shape[-1], w.shape[-1])
    return _call(
        functools.partial(_walk_kernel, precision=precision),
        "delta_rule_pallas", grid, specs[:6],
        [specs[0], specs[6]],
        [jax.ShapeDtypeStruct(w.shape, dtype),
         jax.ShapeDtypeStruct(states, jnp.float32)], scratch, ins, interpret)


def _inter_bwd_pallas(*operands, interpret=False):
    """``_inter_bwd`` as a kernel: the outputs' cotangent in the dtype it
    comes in; ``last``'s leaves as a row of partial sums by lane."""
    *parts, borders, g_out = operands
    precision = _ambient_precision()
    grid, scratch, specs, ins = _layout(parts, True, precision)
    *d_parts, d_last = _call(
        functools.partial(_walk_bwd_kernel, precision=precision),
        "delta_rule_bwd", grid, specs + [specs[0]],
        specs[:6], [jax.ShapeDtypeStruct(t.shape, jnp.float32) for t in ins],
        scratch, ins + (borders, g_out), interpret)
    return (*d_parts, d_last.sum((-1, -2)))


# ---------------------------------------------------------------------------
# which form: by shape, then by where the call runs
# ---------------------------------------------------------------------------

def _choose(operands, phase, pallas, fallback):
    """``fallback(*operands)`` or ``pallas(*operands)``, the trace counted
    under the form taken with ``phase`` ("" or "_bwd") after it."""
    if _kernels_take(operands):
        if any(isinstance(t, jax.core.Tracer) for t in operands):
            # a tracer has no device: the program it is staged into picks
            # the branch when it is lowered for the platform its operands
            # live on
            _metrics.delta_rule_trace("lowering_platform" + phase)
            return lax.platform_dependent(*operands, tpu=pallas,
                                          default=fallback)
        if all(d.platform == "tpu" for d in operands[0].devices()):
            _metrics.delta_rule_trace("kernel" + phase)
            return pallas(*operands)
    _metrics.delta_rule_trace("chunked" + phase)
    return fallback(*operands)


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _rule_fwd(q, k, v, g, beta)[0]


def _rule_fwd(q, k, v, g, beta):
    _metrics.delta_rule_shape(
        CHUNK, v.shape[0] * v.shape[1] * q.shape[-1] * v.shape[-1] * 4)

    def scan(*parts):
        out, borders = _inter(parts)
        return out.astype(v.dtype), borders

    out, borders = _choose(
        _intra(q, k, v, g, beta, CHUNK), "",
        functools.partial(_inter_pallas, dtype=v.dtype), scan)
    return out.reshape(v.shape), (q, k, v, g, beta, borders)


def _rule_bwd(res, g_out):
    *operands, borders = res
    # the barrier ties the second ``_intra`` to the cotangent that asks for
    # it: without it the compiler merges it with the forward's and keeps
    # every chunk's inside from one pass to the other (1.8 GB in the cell)
    operands, g_out = lax.optimization_barrier((operands, g_out))
    parts, vjp = jax.vjp(lambda *t: _intra(*t, CHUNK), *operands)
    return vjp(_choose(
        (*parts, borders, _by_chunk(g_out, CHUNK)), "_bwd", _inter_bwd_pallas,
        lambda *t: _inter_bwd(t[:6], t[6], t[7].astype(jnp.float32))))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta):
    """o (B, H, S, Dv) of the recurrence in the module docstring: ``q``, ``k``
    (B, Hk, S, Dk) with Hk a divisor of H, ``v`` (B, H, S, Dv), ``g`` the
    decay's logarithm (<= 0) and ``beta`` (B, H, S).  q and k come as the
    recurrence takes them (normalised and scaled by the caller).  The state
    starts at zero; it, the decays and every sum are float32; o is in v's
    dtype."""
    if q.shape != k.shape or q.shape[0] != v.shape[0] \
            or q.shape[2] != v.shape[2] or v.shape[1] % k.shape[1] \
            or g.shape != v.shape[:3] or beta.shape != v.shape[:3]:
        raise ValueError(
            "gated_delta_rule takes q, k (B, Hk, S, Dk), v (B, H, S, Dv) "
            "with Hk a divisor of H and g, beta (B, H, S); got %s %s %s %s %s"
            % (q.shape, k.shape, v.shape, g.shape, beta.shape))
    length = v.shape[2]
    pad = -length % CHUNK
    if pad:
        q, k, v = (jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)])
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, [(0, 0), (0, 0), (0, pad)]) for t in (g, beta))
    out = _rule(q, k, v, g, beta)
    return out[:, :, :length] if pad else out


@register("_contrib_GatedDeltaRule", num_inputs=5,
          input_names=("query", "key", "value", "g", "beta"),
          aliases=("gated_delta_rule",))
def _gated_delta_rule_op(query, key, value, g, beta):
    """Registered op wrapper: ``ops.delta_rule.gated_delta_rule``."""
    return gated_delta_rule(query, key, value, g, beta)
