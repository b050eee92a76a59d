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
  ``Q * e^c``, ``(Q K^T) * D``, ``K * e^(c_last - c)``); ``_inter`` is a
  ``lax.scan`` over the chunks of a row that carries S and makes the three
  state products.  State, decays and the inverse are float32; the products of
  q, k, v take the operands in their own dtype with float32 accumulation;
  the products with the state are float32 at the ambient matmul precision
  (a bf16 pass on a TPU inside a bf16 step, full precision under
  ``jax.default_matmul_precision("highest")``).
* ``gated_delta_rule`` is one ``custom_vjp``: the forward keeps q, k, v, g,
  beta and the states at the chunks' borders (S / 64 x H x key_dim x
  value_dim float32, 134 MB a layer at the cell's shape) and nothing of a
  chunk's inside; the backward makes ``_intra`` again, runs the scan's
  adjoint a chunk at a time from the last (each chunk's own ``vjp`` from
  its border, the state's cotangent carried) and takes ``_intra``'s ``vjp``.
* Off the TPU and on it the same ``jnp`` form: there is no Pallas kernel
  here yet (ROADMAP R4).  Every trace is counted
  (``graft_delta_rule_traces_total{form}``: ``chunked`` the forward,
  ``chunked_bwd`` the backward) with the chunk and the state's bytes as
  gauges.  Registered as ``_contrib_GatedDeltaRule``.

k and q may have fewer heads than v (a divisor): value head h then reads
key head ``h // (H_v / H_k)``, repeated inside the rule so that what is
kept for the backward is not.  A length that is no multiple of the chunk is
padded with rows of ``beta = 0, g = 0``, which change nothing.
"""
from __future__ import annotations

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


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _rule_fwd(q, k, v, g, beta)[0]


def _rule_fwd(q, k, v, g, beta):
    _metrics.delta_rule_trace("chunked")
    _metrics.delta_rule_shape(
        CHUNK, v.shape[0] * v.shape[1] * q.shape[-1] * v.shape[-1] * 4)
    out, borders = _inter(_intra(q, k, v, g, beta, CHUNK))
    return out.reshape(v.shape).astype(v.dtype), (q, k, v, g, beta, borders)


def _rule_bwd(res, g_out):
    *operands, borders = res
    _metrics.delta_rule_trace("chunked_bwd")
    # the barrier ties the second ``_intra`` to the cotangent that asks for
    # it: without it the compiler merges it with the forward's and keeps
    # every chunk's inside from one pass to the other (1.8 GB in the cell)
    operands, g_out = lax.optimization_barrier((operands, g_out))
    parts, vjp = jax.vjp(lambda *t: _intra(*t, CHUNK), *operands)
    g_out = _by_chunk(g_out.astype(jnp.float32), CHUNK)
    return vjp(_inter_bwd(parts, borders, g_out))


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
