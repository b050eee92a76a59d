"""Attention kernels: Pallas flash attention + reference path.

The reference framework predates transformer attention entirely (SURVEY
§2.4: sequence handling = bucketing + fused RNN). These kernels are the
*new capability* SURVEY §7 phase 11 mandates: long-context attention that
maps onto the MXU with O(seq) memory.

* ``flash_attention`` — tiled online-softmax attention as Pallas TPU
  kernels in both directions, one ``custom_vjp``.  Forward: grid
  (batch*heads, Sq/block_q, Sk/block_k), K/V streamed by the grid's last
  axis, the running max / sum / output in VMEM scratch, so VMEM use does
  not grow with Sk.  Backward: a dK/dV kernel gridded over key blocks and a
  dQ kernel gridded over query blocks, both recomputing the scores from
  q, k and the row statistics (max ``m``, sum ``l``) the forward emits.
* What the MXU multiplies is what the caller's dtype says.  bf16 / f16
  operands: one MXU pass, f32 accumulation — a product of two bf16 numbers
  is exact in f32, so QKᵀ is the six-pass product of the upcast operands
  to the order of the sums; ``scale`` multiplies the f32 scores after the
  dot; the probabilities and dS are rounded to the operands' dtype for
  P·V, dV, dK, dQ, as in every bf16 Dense layer.  f32 operands: every
  product at ``Precision.HIGHEST``.
* A causal problem visits only the blocks the mask leaves (the diagonal
  anchored at the end of the key axis, so Sq != Sk keeps its meaning): a
  block wholly above the diagonal is neither fetched nor multiplied, and
  the iota mask is applied only to blocks the diagonal crosses.
* ``window=W`` (with ``causal``) is sliding-window attention: a query sees
  its own key and the W - 1 before it.  The same three kernel bodies, with
  the window's lower edge beside the diagonal in ``_visit`` / ``_crosses`` /
  ``_visible``; the grid's last axis then runs over the band's blocks alone
  (``_Band``: 3 steps a query block at S 8192, blocks of 512 and W 1024,
  where the causal grid has 16), and the calls are named
  ``flash_window_pallas`` / ``flash_window_bwd_dkv`` / ``flash_window_bwd_dq``.
  Without a window the program is the one it was.
* Two head widths: q and k share one (``Dqk``), v, the output and dO
  another (``Dv``), as latent attention has them (keys of 192 over values
  of 128).  Each is padded to the lanes on its own (``_fold``), so the
  q / k blocks are ``Dqk`` wide and the v / out / dO blocks, the forward's
  accumulator and the backward's ``delta`` ``Dv`` wide: P·V, dV and the
  traffic of out and dO are not paid at the key's width.  Equal widths are
  the program they were.
* Off the TPU (the CPU test mesh), and for lengths that are not multiples
  of 128, the same math runs as jnp: a dense forward that returns the same
  statistics and a chunked scan backward (f32, HIGHEST) that consumes
  them — the kernels are tested against both.  The platform is the one the
  call is lowered for, not the process default; each path runs under its
  own ``named_scope`` and every trace, forward and backward, is counted by
  path and window (``graft_flash_attention_traces_total``); a causal call
  also gauges the blocks its grid visits (``graft_flash_blocks_visited``).
* Registered as op ``_contrib_FlashAttention`` so both eager NDArray code
  and Symbol graphs can call it (one registry, two modes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import metrics as _metrics
from .registry import register

_NEG_INF = -1e30
_LANES = 128


# ---------------------------------------------------------------------------
# reference (jnp) attention — also the CPU path and the tests' oracle
# ---------------------------------------------------------------------------

def _attention_reference_stats(q, k, v, causal=False, scale=None,
                               window=None):
    """q (B, H, Sq, Dqk), k (B, H, Sk, Dqk), v (B, H, Sk, Dv) → out
    (B, H, Sq, Dv) and the softmax's row statistics (B, H, Sq) in f32: the
    maximum ``m`` of the masked, scaled scores and the sum ``l`` of
    ``exp(score - m)``."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            # the query's own key and the window - 1 before it
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        logits = jnp.where(mask, logits, _NEG_INF)
    m = logits.max(axis=-1)
    e = jnp.exp(logits - m[..., None])
    l = e.sum(axis=-1)
    probs = e / l[..., None]
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(probs.dtype)
                     ).astype(q.dtype)
    return out, m, l


def _attention_reference(q, k, v, causal=False, scale=None, window=None):
    """(B, H, Sq, Dqk), (B, H, Sk, Dqk), (B, H, Sk, Dv) → (B, H, Sq, Dv)."""
    return _attention_reference_stats(q, k, v, causal, scale, window)[0]


# ---------------------------------------------------------------------------
# Pallas flash kernels
# ---------------------------------------------------------------------------
# Blocks are (block_q × block_k) tiles of the score matrix; query row r sees
# keys up to r + off, off = Sk - Sq: the diagonal is anchored at the *end* of
# the key axis, matching the jnp path's tril(k=sk-sq) — essential for
# KV-cache decode where Sq != Sk.  With a window W it sees the W keys that
# end there, its own included: r + off - W < key <= r + off.

_NN = (((1,), (0,)), ((), ()))      # a · b
_NT = (((1,), (1,)), ((), ()))      # a · bᵀ


def _dot(a, b, dims):
    """MXU product in the operands' dtype with f32 accumulation: one pass
    for bf16 / f16 (their products are exact in f32), HIGHEST for f32."""
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _block(s):
    """Largest of 512, 256, 128 dividing the (128-aligned) length: 128-wide
    tiles starve a v5e MXU between grid steps."""
    return next(b for b in (512, 256, 128) if s % b == 0)


def _visit(qi, ki, bq, bk, off, window=None):
    """Whether block (qi, ki) holds anything the mask leaves: a key at or
    below the diagonal of some query of the block and, with a window, within
    ``window`` of one.  A row that sees no key at all (Sq > Sk) is uniform
    over *every* key in the reference, so a query block that holds one
    visits every key block."""
    seen = (qi + 1) * bq - 1 + off >= ki * bk
    if window is not None:
        seen &= (ki + 1) * bk - 1 > qi * bq + off - window
    if off < 0:
        seen |= qi * bq + off < 0
    return seen


def _crosses(qi, ki, bq, bk, off, window=None):
    """Whether block (qi, ki) holds a masked score at all: the diagonal
    crosses it, or the window's lower edge does."""
    crossed = (ki + 1) * bk - 1 > qi * bq + off
    if window is not None:
        crossed |= (qi + 1) * bq - 1 + off - ki * bk >= window
    return crossed


def _visible(qi, ki, bq, bk, off, shape, q_axis, window=None):
    """The mask of block (qi, ki) as a tile of ``shape`` whose ``q_axis``
    runs over the queries."""
    rows = qi * bq + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = ki * bk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return rows + off >= cols
    ahead = rows + off - cols
    return (ahead >= 0) & (ahead < window)


def _lanes(x, n):
    """A (rows, 128) tile whose lanes are equal, widened to n lanes."""
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


class _Band:
    """The blocks a window leaves, as a grid's last axis (``off >= 0``).

    A grid whose second axis runs over query blocks (``q_major``: forward,
    dQ) or key blocks (dK/dV) takes, for block ``i`` of that axis, only the
    blocks ``first(i) .. last(i)`` of the other: ``steps`` grid steps, the
    most any ``i`` needs, of which step ``j`` is block ``first(i) + j``.  At
    S 8192 in blocks of 512 under a window of 1024 that is 3 steps a query
    block where the causal triangle's grid has 16, so neither a product nor
    a fetch nor an empty grid step is spent outside the band."""

    def __init__(self, bq, bk, nq, nk, off, window, q_major):
        if q_major:     # keys r + off - window + 1 .. r + off of query r
            self.major, self.minor, self.count = bq, bk, nk
            self.lo, self.hi = off - window + 1, off
        else:           # queries c - off .. c - off + window - 1 of key c
            self.major, self.minor, self.count = bk, bq, nq
            self.lo, self.hi = -off, window - 1 - off
        edges = [self._edges(i, max, min, lambda a, b: a // b)
                 for i in range(nq if q_major else nk)]
        self.steps = max(1, max(last - first + 1 for first, last in edges))

    def _edges(self, i, mx, mn, div):
        first = div(mx(i * self.major + self.lo, 0), self.minor)
        last = mn(div(mx((i + 1) * self.major - 1 + self.hi, 0), self.minor),
                  self.count - 1)
        return first, last

    # plain lax on non-negative operands: an index map is lowered once a
    # block spec, and jnp's floor division is a jitted function of its own
    def first(self, i):
        return self._edges(i, lax.max, lax.min, lax.div)[0]

    def index(self, i, j):
        """The block that grid step ``j`` fetches: its own inside the band,
        the band's last behind it, which is then resident already."""
        first, last = self._edges(i, lax.max, lax.min, lax.div)
        return lax.min(first + j, last)


def _grid_ids(band):
    """(block of the grid's second axis, grid step of the last, the block of
    the other axis that this step is, whether a banded grid's step is a
    block at all: None where the grid is whole)."""
    from jax.experimental import pallas as pl
    i, j = pl.program_id(1), pl.program_id(2)
    if band is None:
        return i, j, j, None
    other = band.first(i) + j
    return i, j, other, other < band.count


def _per_block(causal, qi, ki, bq, bk, off, step, window=None, inside=None):
    """Run ``step(masked)`` for a block: not at all where the mask leaves
    nothing of it, with the mask where the diagonal or the window's edge
    crosses it, without it in between.  ``inside``: whether a banded grid's
    step is a block at all."""
    from jax.experimental import pallas as pl
    if not causal:
        step(False)
        return
    visit = _visit(qi, ki, bq, bk, off, window)
    if inside is not None:
        visit &= inside
    crosses = _crosses(qi, ki, bq, bk, off, window)
    pl.when(visit & crosses)(lambda: step(True))
    pl.when(visit & jnp.logical_not(crosses))(lambda: step(False))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc, m_run, l_run, *, causal, scale, off, window=None,
                      band=None):
    from jax.experimental import pallas as pl
    qi, j, ki, inside = _grid_ids(band)
    bq, bk, d = q_ref.shape[0], k_ref.shape[0], v_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_run[...] = jnp.full_like(m_run, _NEG_INF)
        l_run[...] = jnp.zeros_like(l_run)

    def step(masked):
        v = v_ref[...]
        s = _dot(q_ref[...], k_ref[...], _NT) * scale          # (bq, bk)
        if masked:
            s = jnp.where(_visible(qi, ki, bq, bk, off, s.shape, 0, window),
                          s, _NEG_INF)
        m_prev = m_run[...]                                    # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        l_run[...] = alpha * l_run[...] + p.sum(axis=1, keepdims=True)
        m_run[...] = m_new
        acc[...] = acc[...] * _lanes(alpha, d) + _dot(p.astype(v.dtype), v,
                                                      _NN)

    _per_block(causal, qi, ki, bq, bk, off, step, window, inside)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # every row has l >= 1: its maximum contributes exp(0)
        o_ref[...] = (acc[...] / _lanes(l_run[...], d)).astype(o_ref.dtype)
        # the statistics leave lane-dense, (1, bq) a block: a (bq, 1) column
        # would be padded to 128 lanes in HBM
        m_ref[...] = m_run[...].T[:1]
        l_ref[...] = l_run[...].T[:1]


def _probs_and_dscores(s, dp, m, linv, delta, visible, scale, off):
    """p and dS of a tile, whichever way it lies, from its raw scores, the
    forward's statistics and dP = dO·Vᵀ; ``visible`` is the mask of a block
    the diagonal or the window's edge crosses, else None."""
    s = s * scale
    if visible is not None:
        s = jnp.where(visible, s, _NEG_INF)
    p = jnp.exp(s - m) * linv
    ds = p * (dp - delta) * scale
    if visible is not None and off < 0:
        # a row that sees no key has uniform p, not 0, and its masked
        # scores are constants: they carry no dQ / dK
        ds = jnp.where(visible, ds, 0.0)
    return p, ds


def _flash_dkv_kernel(q_ref, k_ref, v_ref, g_ref, m_ref, linv_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale, off,
                      window=None, band=None):
    """dK, dV of one key block, the query blocks from the diagonal down (to
    the window's end) on the grid's last axis.  Scores are held transposed,
    (bk, bq), so that the row statistics broadcast as the (1, bq) rows they
    are stored as."""
    from jax.experimental import pallas as pl
    ki, j, qi, inside = _grid_ids(band)
    (bq, _), bk = q_ref.shape, k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q, k, g = q_ref[...], k_ref[...], g_ref[...]
        visible = (_visible(qi, ki, bq, bk, off, (bk, bq), 1, window)
                   if masked else None)
        pt, dst = _probs_and_dscores(                          # (bk, bq)
            _dot(k, q, _NT), _dot(v_ref[...], g, _NT), m_ref[...],
            linv_ref[...], delta_ref[...], visible, scale, off)
        dv_acc[...] += _dot(pt.astype(g.dtype), g, _NN)
        dk_acc[...] += _dot(dst.astype(q.dtype), q, _NN)

    _per_block(causal, qi, ki, bq, bk, off, step, window, inside)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, g_ref, m_ref, linv_ref, delta_ref,
                     dq_ref, dq_acc, *, causal, scale, off, window=None,
                     band=None):
    """dQ of one query block, the key blocks (from the window's start) up to
    the diagonal on the grid's last axis."""
    from jax.experimental import pallas as pl
    qi, j, ki, inside = _grid_ids(band)
    (bq, _), bk = q_ref.shape, k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked):
        k = k_ref[...]
        m, linv, delta = (jnp.expand_dims(r[0], -1)            # (bq, 1)
                          for r in (m_ref, linv_ref, delta_ref))
        visible = (_visible(qi, ki, bq, bk, off, (bq, bk), 0, window)
                   if masked else None)
        _, ds = _probs_and_dscores(                            # (bq, bk)
            _dot(q_ref[...], k, _NT), _dot(g_ref[...], v_ref[...], _NT),
            m, linv, delta, visible, scale, off)
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)

    _per_block(causal, qi, ki, bq, bk, off, step, window, inside)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _fold(x):
    """(B, H, S, D) → (B*H, S, Dp).  MXU lanes want D in multiples of 128;
    typical head dims (64, 96, 192) get zero-padded — padded Q/K columns
    contribute nothing to QKᵀ and padded V (or dO) columns produce output
    (or gradient) columns that ``_unfold`` slices off.  Each operand is
    padded from its own width: q and k share one (and dq, dk with them), v,
    the output and dO share the other (and dv), so a key of 192 over a value
    of 128 runs at 256 and 128 lanes, not 256 and 256."""
    b, h, s, d = x.shape
    dp = -(-d // _LANES) * _LANES
    if dp != d:
        x = jnp.pad(x, [(0, 0)] * 3 + [(0, dp - d)])
    return x.reshape(b * h, s, dp)


def _unfold(x, like):
    b, h, _, d = like.shape
    return x.reshape(b, h, *x.shape[1:])[..., :d]


def _pallas_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
                 flops, operands, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    outs = jax.tree.leaves(out_shape)
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=sum(x.size * x.dtype.itemsize
                               for x in (*operands, *outs)),
            transcendentals=grid[0] * operands[0].shape[1]
            * operands[1].shape[1]),
    )(*operands)


def _specs(bq, bk, off, causal, q_major, band=None):
    """``(q_like, kv_like, stat_spec)`` of a (batch*heads, ·, ·) grid whose
    second axis runs over query blocks (``q_major``: forward, dQ) or key
    blocks (dK/dV): the block spec of an operand that lies along the queries
    (q, out, dO, dq) or along the keys (k, v, dk, dv) at the lane width it
    is called with, and that of a row statistic.  The index of a block the
    causal mask skips is clamped to the nearest one it visits, so a skipped
    grid step fetches nothing; a ``band``'s steps are its own blocks
    (``_Band.index``)."""
    from jax.experimental import pallas as pl

    clamp = causal and off >= 0

    # plain lax on non-negative operands: an index map is lowered once a
    # block spec, and jnp's floor division is a jitted function of its own
    def q_of(i, j):
        if q_major:
            return i
        if band is not None:
            return band.index(i, j)
        return lax.max(j, lax.div(i * bk - off, bq)) if clamp else j

    def k_of(i, j):
        if not q_major:
            return i
        if band is not None:
            return band.index(i, j)
        return lax.min(j, lax.div((i + 1) * bq - 1 + off, bk)) if clamp else j

    def q_at(b, i, j):
        return b, q_of(i, j), 0

    def kv_at(b, i, j):
        return b, k_of(i, j), 0

    def stat_at(b, i, j):
        return b, 0, q_of(i, j)

    return (lambda lanes: pl.BlockSpec((None, bq, lanes), q_at),
            lambda lanes: pl.BlockSpec((None, bk, lanes), kv_at),
            pl.BlockSpec((None, 1, bq), stat_at))


def _geometry(q, k, block_q, block_k, window, q_major):
    """(bq, bk, off, band) of a call: the band where a window makes the
    grid's last axis its own (``off >= 0``; with more queries than keys the
    rows that see nothing visit every key block, and the grid stays whole)."""
    Sq, Sk = q.shape[2], k.shape[2]
    bq, bk = block_q or _block(Sq), block_k or _block(Sk)
    off = Sk - Sq
    band = None
    if window is not None and off >= 0:
        band = _Band(bq, bk, Sq // bq, Sk // bk, off, window, q_major)
    return bq, bk, off, band


def blocks_visited(sq, sk, window=None, block_q=None, block_k=None):
    """``(visited, causal)``: the (query block, key block) pairs of one
    (batch, head) slice in which a causal call's grid runs a product, under
    ``window`` and without it (``_visit``, evaluated on the host)."""
    bq, bk = block_q or _block(sq), block_k or _block(sk)
    pairs = [(qi, ki) for qi in range(sq // bq) for ki in range(sk // bk)]
    return tuple(sum(bool(_visit(qi, ki, bq, bk, sk - sq, w))
                     for qi, ki in pairs) for w in (window, None))


def _flash_forward_pallas(q, k, v, causal, scale, block_q=None, block_k=None,
                          interpret=False, window=None):
    """out (B, H, Sq, Dv) and the row statistics m, l (B, H, Sq) in f32."""
    from jax.experimental.pallas import tpu as pltpu
    Sq, Sk = q.shape[2], k.shape[2]
    bq, bk, off, band = _geometry(q, k, block_q, block_k, window, True)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    (BH, _, Dp), Dv = qf.shape, vf.shape[2]
    q_like, kv_like, stat_spec = _specs(bq, bk, off, causal, True, band)
    stat = jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)
    out, m, l = _pallas_call(
        functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                          off=off, **_windowed(window, band)),
        "flash_attention_pallas" if window is None else "flash_window_pallas",
        (BH, Sq // bq, band.steps if band else Sk // bk),
        [q_like(Dp), kv_like(Dp), kv_like(Dv)],
        [q_like(Dv), stat_spec, stat_spec],
        [jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype), stat, stat],
        [pltpu.VMEM((bq, Dv), jnp.float32),
         pltpu.VMEM((bq, _LANES), jnp.float32),
         pltpu.VMEM((bq, _LANES), jnp.float32)],
        2 * BH * Sq * Sk * (Dp + Dv), (qf, kf, vf), interpret)
    return (_unfold(out, v), m.reshape(q.shape[:3]), l.reshape(q.shape[:3]))


def _windowed(window, band):
    """The kernels' static arguments beside ``off``: none without a window,
    so that a call without one is the program it was."""
    return {} if window is None else {"window": window, "band": band}


def _flash_backward_pallas(q, k, v, out, m, l, g, causal, scale,
                           block_q=None, block_k=None, interpret=False,
                           window=None):
    from jax.experimental.pallas import tpu as pltpu
    Sq, Sk = q.shape[2], k.shape[2]
    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    (BH, _, Dp), Dv = qf.shape, vf.shape[2]
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    stats = [x.reshape(BH, 1, Sq) for x in (m, 1.0 / l, delta)]
    operands = (qf, kf, vf, gf, *stats)
    # one product over the score tile at the key's width, at the value's
    qk_flops, v_flops = (2 * BH * Sq * Sk * lanes for lanes in (Dp, Dv))
    names = (("flash_attention_bwd_dkv", "flash_attention_bwd_dq")
             if window is None else
             ("flash_window_bwd_dkv", "flash_window_bwd_dq"))

    bq, bk, off, band = _geometry(q, k, block_q, block_k, window, False)
    q_like, kv_like, stat_spec = _specs(bq, bk, off, causal, False, band)
    dk, dv = _pallas_call(
        functools.partial(_flash_dkv_kernel, causal=causal, scale=scale,
                          off=off, **_windowed(window, band)),
        names[0], (BH, Sk // bk, band.steps if band else Sq // bq),
        [q_like(Dp), kv_like(Dp), kv_like(Dv), q_like(Dv)] + [stat_spec] * 3,
        [kv_like(Dp), kv_like(Dv)],
        [jax.ShapeDtypeStruct(kf.shape, k.dtype),
         jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        [pltpu.VMEM((bk, Dp), jnp.float32), pltpu.VMEM((bk, Dv), jnp.float32)],
        2 * qk_flops + 2 * v_flops, operands, interpret)      # s, dK; dP, dV

    bq, bk, off, band = _geometry(q, k, block_q, block_k, window, True)
    q_like, kv_like, stat_spec = _specs(bq, bk, off, causal, True, band)
    dq = _pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, scale=scale,
                          off=off, **_windowed(window, band)),
        names[1], (BH, Sq // bq, band.steps if band else Sk // bk),
        [q_like(Dp), kv_like(Dp), kv_like(Dv), q_like(Dv)] + [stat_spec] * 3,
        q_like(Dp), jax.ShapeDtypeStruct(qf.shape, q.dtype),
        [pltpu.VMEM((bq, Dp), jnp.float32)],
        2 * qk_flops + v_flops, operands, interpret)            # s, dQ; dP
    return _unfold(dq, q), _unfold(dk, k), _unfold(dv, v)


# ---------------------------------------------------------------------------
# which path: by alignment, then by where the call runs
# ---------------------------------------------------------------------------

def _choose(paths, operands, pallas, fallback, window=None):
    """Call ``pallas`` where the lengths are multiples of 128 and the call
    runs on a TPU, else ``fallback``; count the trace under the label of
    ``paths`` (unaligned, staged, on a TPU, off it) that names the way."""
    unaligned, staged, on_tpu, off_tpu = paths
    q, k = operands[0], operands[1]
    if q.shape[2] % 128 or k.shape[2] % 128:
        # O(S²) memory on any platform: worth a counter of its own
        _metrics.flash_attention_trace(unaligned, window)
        return fallback(*operands)
    if any(isinstance(x, jax.core.Tracer) for x in operands):
        # a tracer has no device: the program it is staged into picks the
        # branch when it is lowered for the platform its operands live on
        _metrics.flash_attention_trace(staged, window)
        return lax.platform_dependent(*operands, tpu=pallas, default=fallback)
    if all(d.platform == "tpu" for d in q.devices()):
        _metrics.flash_attention_trace(on_tpu, window)
        return pallas(*operands)
    _metrics.flash_attention_trace(off_tpu, window)
    return fallback(*operands)


_FWD_PATHS = ("reference_unaligned", "lowering_platform", "pallas",
              "reference_off_tpu")
# none may begin with "reference": the benchmark refuses those on the chip
_BWD_PATHS = ("bwd_scan_unaligned", "bwd_lowering_platform", "bwd_pallas",
              "bwd_scan_off_tpu")


def _flash_forward(q, k, v, causal, scale, window=None):
    """out, m, l by whichever path the call takes."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    Sq, Sk = q.shape[2], k.shape[2]
    _metrics.flash_head_dim(q.shape[-1], v.shape[-1])
    if causal and not (Sq % 128 or Sk % 128):
        visited, triangle = blocks_visited(Sq, Sk, window)
        slices = q.shape[0] * q.shape[1]
        _metrics.flash_blocks(slices * visited, slices * triangle, window)

    def pallas(q, k, v):
        with jax.named_scope("flash_attention_pallas"):
            return _flash_forward_pallas(q, k, v, causal, scale,
                                         window=window)

    def reference(q, k, v):
        with jax.named_scope("flash_attention_reference"):
            return _attention_reference_stats(q, k, v, causal, scale, window)

    return _choose(_FWD_PATHS, (q, k, v), pallas, reference, window)


def _window_of(window, causal, sk):
    """``window`` as the kernels take it: None where it is absent or holds
    every key of the call (the causal program, then), else the width."""
    if window is None:
        return None
    if not causal:
        raise ValueError("a window counts back from the query's own key: "
                         "it needs causal=True")
    window = int(window)
    if window < 1:
        raise ValueError("window must be at least 1 (the query's own key), "
                         "got %d" % window)
    return None if window >= sk else window


def flash_attention(q, k, v, causal=False, scale=None, window=None):
    """softmax(QKᵀ·scale + mask)·V with O(seq) memory: q (B, H, Sq, Dqk),
    k (B, H, Sk, Dqk), v (B, H, Sk, Dv) → (B, H, Sq, Dv).  ``Dv`` may differ
    from ``Dqk`` (latent attention: keys of 192 over values of 128;
    differential attention: keys of 64 over values of 128, under a window
    too, the chip benchmark's ``phi4flash_sambay_fused_1row``); the
    default ``scale`` is ``1 / sqrt(Dqk)``.

    ``causal``: query r sees the keys up to its own (the diagonal anchored
    at the end of the key axis, so Sq != Sk keeps its meaning).  ``window``
    (with ``causal``): it sees only the last ``window`` of those, its own
    key included — ``window=1024`` is the query's key and the 1023 before
    it (sliding-window attention as Mistral, Gemma and Mellum number it); a
    window that holds every key is the causal call.

    Pallas kernels, forward and backward, where the call runs on a TPU and
    both lengths are multiples of 128; the jnp path otherwise.  The
    backward recomputes the probabilities from q, k and the row statistics
    the forward saved (flash-style rematerialization) instead of storing
    the (Sq×Sk) probability matrix.  The kernels run a product only in the
    blocks the mask leaves something of, and a windowed call's grid has no
    step for the others: its kernels are named ``flash_window_pallas``,
    ``flash_window_bwd_dkv`` and ``flash_window_bwd_dq`` in a trace, the
    others' ``flash_attention_*``.
    """
    return _flash(q, k, v, causal, scale,
                  _window_of(window, causal, k.shape[2]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, window):
    return _flash_forward(q, k, v, causal, scale, window)[0]


def _flash_fwd(q, k, v, causal, scale, window):
    out, m, l = _flash_forward(q, k, v, causal, scale, window)
    return out, (q, k, v, out, m, l)


def _flash_bwd(causal, scale, window, res, g):
    """Flash-style backward from the saved statistics, never materializing
    the (Sq × Sk) score matrix.  All of it is staged under one
    ``named_scope``, so that a trace's reduction finds its operations (the
    two Mosaic calls and the ``jnp`` around them, or the scan's fusions) by
    their ``op_name``."""
    if scale is None:
        scale = 1.0 / math.sqrt(res[0].shape[-1])
    with jax.named_scope("flash_attention_bwd"):
        return _choose(
            _BWD_PATHS, (*res, g),
            functools.partial(_flash_backward_pallas, causal=causal,
                              scale=scale, window=window),
            functools.partial(_flash_backward_scan, causal=causal,
                              scale=scale, window=window), window)


def _kv_block_size(sk):
    """Largest power-of-two K-chunk ≤1024 dividing sk (else no chunking)."""
    for b in (1024, 512, 256, 128, 64):
        if sk % b == 0:
            return b
    return sk


def _flash_backward_scan(q, k, v, out, m, l, g, causal, scale, window=None):
    """The backward as jnp: one chunked pass over the key axis that
    rebuilds each probability tile from the saved (m, l) and accumulates
    dQ (carried) and per-tile dK/dV (scan outputs) — backward memory
    matches the forward's O(Sq · block) profile.  In f32 at HIGHEST
    whatever the operands' dtype: the CPU path, and the kernels' oracle."""
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
    hi = jax.lax.Precision.HIGHEST
    # (m, l) stay separate: folding into m + log(l) loses log(l) to float
    # absorption when m is the -1e30 sentinel (rows with no visible keys)
    l_inv = 1.0 / l
    delta = (gf * out.astype(jnp.float32)).sum(-1)    # (B, H, Sq)

    def grad_step(dq_acc, xs):
        k_blk, v_blk, i = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk, precision=hi)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_blk, precision=hi)
        cols = i * block + jnp.arange(block)[None, :]
        mask = q_pos >= cols if causal else None
        if window is not None:
            mask &= q_pos - cols < window
        p, ds = _probs_and_dscores(s, dp, m[..., None], l_inv[..., None],
                                   delta[..., None], mask, scale, Sk - Sq)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf, precision=hi)
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk,
                                     precision=hi)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf, precision=hi)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk, dv) = lax.scan(grad_step, dq0, (kb, vb, jnp.arange(nb)))
    dk = jnp.moveaxis(dk, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv, 0, 2).reshape(v.shape)
    return (dq.astype(dtype_in), dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


@register("_contrib_FlashAttention", num_inputs=3,
          aliases=("flash_attention", "_contrib_DotProductAttention"))
def _flash_attention_op(q, k, v, causal=False, scale=None, window=None):
    """Registered op wrapper — (B, H, S, D) inputs, v's D its own (the
    output's).  ``window`` (with
    ``causal``): each query sees its own key and the ``window - 1`` before
    it; None: every key up to its own (``ops.attention.flash_attention``)."""
    return flash_attention(q, k, v, causal, scale, window)


# graftlint: disable=GL302 -- `eager` is a host are-we-staging bool from dispatch_on_mesh, not a traced value; branching on it is the point
@register("_contrib_RingAttention", num_inputs=3, no_jit=True,
          aliases=("ring_attention",))
def _ring_attention_op(q, k, v, seq_axis="sp", causal=False, scale=None,
                       window=None):
    """Exact attention over sequence shards (B, H, S, D): S is sharded on
    the mesh axis ``seq_axis`` and K/V blocks rotate over ICI
    (parallel/ring_attention.py).  The mesh comes from the enclosing
    ``parallel.use_mesh`` scope — the op itself stays array-in/array-out
    like every registry op.  The modern capability mandated over the
    reference's bucketing story (SURVEY §5.7).  No ``window``: the ring
    rotates every K/V block past every query shard."""
    if window is not None:
        raise ValueError(
            "ring attention has no window: parallel/ring_attention.py "
            "rotates every K/V shard past every query shard; run windowed "
            "layers through _contrib_FlashAttention (seq_axis=None)")
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
