"""The selective scan of a state-space layer (Mamba, Gu & Dao,
arXiv:2312.00752): a diagonal linear recurrence over the sequence whose
decay depends on the token.

    h_t[c, n] = exp(delta_t[c] * A[c, n]) * h_{t-1}[c, n]
                + delta_t[c] * x_t[c] * B_t[n],            h_0 = 0
    y_t[c]    = sum_n h_t[c, n] * C_t[n] + D[c] * x_t[c]

over C channels of N states each.  A differs by channel *and* state, so the
chunked matrix form of scalar-decay scans does not apply: there is no
matmul in it, the work is the VPU's and the EUP's.  Unrolled over the
sequence ``h`` is L x C x N float32 (1.34 GB a layer at L 4096, C 5120,
N 16): no form here writes it to HBM.

* ``selective_scan`` is one ``custom_vjp``.  State, ``exp``, products and
  sums are float32 whatever the operands' dtype (Mamba's own kernel does
  so).  The forward saves its operands and the state at the chunk borders
  only (L / T x C x N float32); the backward goes a chunk at a time from the
  last: it recomputes the chunk's states from its border and runs the
  adjoint recurrence ``lam_t = g_t C_t^T + exp(delta_{t+1} A) * lam_{t+1}``
  backwards.
* On a TPU two Pallas (Mosaic) kernels, ``selective_scan_pallas`` and
  ``selective_scan_bwd``: grid (batch, channel blocks, time chunks), the
  time axis last and sequential, the block's (N, channels) state in VMEM
  scratch carried from chunk to chunk, a ``fori_loop`` over the chunk's
  steps eight at a time.  The state lies states-on-sublanes,
  channels-on-lanes, so every vector op is full; ``B_t`` and ``C_t`` (one
  number a state a step) are spread over the lanes once a chunk by a
  one-hot product on the MXU, and the backward's dB and dC come back the
  same way.
* Elsewhere (the CPU test mesh) and for shapes the kernels do not take, the
  same mathematics as ``jnp``: ``lax.scan`` over the chunks, inside a chunk
  ``lax.associative_scan`` over its steps (T x C x N at a time), the
  backward a chunk's ``jax.vjp`` from its border.  It is also what the
  interpret-mode tests hold the kernels to.  The platform is the one the
  call is lowered for (``lax.platform_dependent``), as in ``ops/attention``.
* Every trace is counted by path (``graft_ssm_scan_traces_total``), with the
  chunk and the state's size as gauges.  Registered as
  ``_contrib_SelectiveScan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import metrics as _metrics
from .registry import register

CHUNK = 128                     # time steps between two saved states
_LANES = 128
_UNROLL = 8                     # steps a loop iteration: one f32 tile of rows
_NN = (((1,), (0,)), ((), ()))


# ---------------------------------------------------------------------------
# jnp form: the CPU path and the kernels' oracle
# ---------------------------------------------------------------------------

def _chunk_jnp(h0, x, delta, At, B, C):
    """One chunk from the state ``h0`` (batch, N, C) at its border: x, delta
    (batch, T, C), ``At`` (N, C) the decay's rates, B, C (batch, T, N), all
    float32 -> (y without the skip (batch, T, C), the state after the
    chunk).  Channels last, as the kernels lay the state: a TPU pads a last
    axis of 16 states to 128 lanes."""
    decay = jnp.exp(delta[:, :, None, :] * At)              # (b, T, N, C)
    drive = (delta * x)[:, :, None, :] * B[..., None]

    def combine(first, then):
        return first[0] * then[0], then[0] * first[1] + then[1]

    through, driven = lax.associative_scan(combine, (decay, drive), axis=1)
    h = through * h0[:, None] + driven
    return jnp.einsum("btnc,btn->btc", h, C), h[:, -1]


def _chunks(t, chunk):
    """(batch, L, ...) -> (L / chunk, batch, chunk, ...) in float32."""
    t = t.astype(jnp.float32)
    t = t.reshape(t.shape[0], -1, chunk, *t.shape[2:])
    return jnp.moveaxis(t, 1, 0)


def _forward_jnp(x, delta, A, B, C, D, chunk):
    """y (batch, L, C) in x's dtype and the states at the chunks' starts,
    (batch, L / chunk, N, C) float32."""
    At = A.astype(jnp.float32).T

    def step(h, xs):
        y, after = _chunk_jnp(h, *xs[:2], At, *xs[2:])
        return after, (y, h)

    h0 = jnp.zeros((x.shape[0],) + At.shape, jnp.float32)
    _, (y, borders) = lax.scan(
        step, h0, tuple(_chunks(t, chunk) for t in (x, delta, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    y = y + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype), jnp.moveaxis(borders, 0, 1)


def _backward_jnp(x, delta, A, B, C, D, borders, g, chunk):
    """The six gradients, a chunk at a time from the last: the chunk's own
    ``vjp`` from its saved border, the state's adjoint carried."""
    At, gf = A.astype(jnp.float32).T, g.astype(jnp.float32)
    xf = x.astype(jnp.float32)

    def step(carry, xs):
        dh, dAt = carry
        xc, dc, Bc, Cc, gc, h0 = xs
        _, vjp = jax.vjp(_chunk_jnp, h0, xc, dc, At, Bc, Cc)
        dh, dx, dd, dAt_c, dB, dC = vjp((gc, dh))
        return (dh, dAt + dAt_c), (dx, dd, dB, dC)

    zero = jnp.zeros((x.shape[0],) + At.shape, jnp.float32)
    xs = tuple(_chunks(t, chunk) for t in (x, delta, B, C, g)) + (
        jnp.moveaxis(borders, 1, 0),)
    (_, dAt), (dx, dd, dB, dC) = lax.scan(
        step, (zero, jnp.zeros_like(At)), xs, reverse=True)

    def whole(t, like):
        return jnp.moveaxis(t, 0, 1).reshape(like.shape)

    dx = whole(dx, x) + D.astype(jnp.float32) * gf
    return (dx.astype(x.dtype), whole(dd, delta).astype(delta.dtype),
            dAt.T.astype(A.dtype), whole(dB, B).astype(B.dtype),
            whole(dC, C).astype(C.dtype),
            (gf * xf).sum((0, 1)).astype(D.dtype))


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
# A block is (T time steps) x (cb channels).  x, delta, g and the gradients of
# the first two are (T, cb) tiles, rows by time; the state is (N, cb), states
# by sublane; B and C arrive zero-padded to 128 lanes, (T, 128), and are
# spread to (T * N, 128) tiles, row t * N + n holding B_t[n] in every lane.

def _pieces(z):
    """``z`` as bf16 pieces that add up to it: one for bf16, three for
    float32 (8 + 8 + 8 bits of mantissa), so that a product with a one-hot
    matrix is exact in single bf16 passes of the MXU."""
    if z.dtype == jnp.bfloat16:
        return [z]
    z = z.astype(jnp.float32)
    pieces = []
    for _ in range(3):
        piece = z.astype(jnp.bfloat16)
        pieces.append(piece)
        z = z - piece.astype(jnp.float32)
    return pieces


def _onehot_dot(onehot, z):
    """``onehot @ z`` in float32, ``onehot`` a matrix of 0 / 1.  Single bf16
    passes whatever ``jax.default_matmul_precision`` says around the call
    (Mosaic refuses bf16 operands at float32 precision): the pieces make the
    product exact as it is."""
    left = onehot.astype(jnp.bfloat16)
    return sum(lax.dot_general(left, p, _NN, precision=lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)
               for p in _pieces(z))


def _own_state(rows, n_states):
    """Mask (rows, 128): row t * N + n keeps lane n."""
    row = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.bitwise_and(row, n_states - 1) == lane


def _spread(m, n_states):
    """(T, 128) whose first N lanes hold a step's N numbers -> (T * N, 128),
    row t * N + n holding m[t, n] in every lane."""
    steps = m.shape[0]
    rows = steps * n_states
    row = lax.broadcasted_iota(jnp.int32, (rows, steps), 0)
    step = lax.broadcasted_iota(jnp.int32, (rows, steps), 1) * n_states
    copied = _onehot_dot((row >= step) & (row < step + n_states), m)
    kept = jnp.where(_own_state(rows, n_states), copied, 0.0)
    return jnp.broadcast_to(kept.sum(axis=1, keepdims=True), kept.shape)


def _gather(p, steps, n_states):
    """The inverse: (T * N, 128) partial sums by lane -> (T, 128) whose lane
    n of row t holds the sum over the lanes of row t * N + n."""
    rows = steps * n_states
    total = jnp.broadcast_to(p.sum(axis=1, keepdims=True), p.shape)
    kept = jnp.where(_own_state(rows, n_states), total, 0.0)
    row = lax.broadcasted_iota(jnp.int32, (steps, rows), 1)
    step = lax.broadcasted_iota(jnp.int32, (steps, rows), 0) * n_states
    return _onehot_dot((row >= step) & (row < step + n_states), kept)


def _fold(t):
    """(N, cb) -> (N, 128): the sum of its groups of 128 lanes."""
    return sum(t[:, i:i + _LANES] for i in range(0, t.shape[1], _LANES))


def _wide(t, lanes):
    """(N, 128) whose lanes are equal -> (N, lanes)."""
    return t if lanes == _LANES else jnp.tile(t, (1, lanes // _LANES))


def _advance(h, d, x, At, b):
    """One step of the state (N, cb): d, x (1, cb) the step's delta and
    input, ``At`` (N, cb) the decay's rates, b (N, 128) the step's B spread
    over the lanes."""
    return jnp.exp(d * At) * h + (d * x) * _wide(b, h.shape[1])


def _put_row(tile, i, row):
    """``tile`` (8, cb) with its row ``i`` set to ``row`` (1, cb)."""
    at = lax.broadcasted_iota(jnp.int32, tile.shape, 0) == i
    return jnp.where(at, row, tile)


def _scan_fwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, skip_ref,
                     y_ref, border_ref, h_ref, x32, d32, y32, bs, cs):
    from jax.experimental import pallas as pl
    steps, cb = x32.shape
    n = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    border_ref[...] = h_ref[...]
    x32[...] = x_ref[...].astype(jnp.float32)
    d32[...] = d_ref[...].astype(jnp.float32)
    bs[...] = _spread(b_ref[...], n)
    cs[...] = _spread(c_ref[...], n)
    At = a_ref[...]

    def eight(i, h):
        base = pl.multiple_of(i * _UNROLL, _UNROLL)
        xs, ds = x32[pl.ds(base, _UNROLL), :], d32[pl.ds(base, _UNROLL), :]
        ys = jnp.zeros((_UNROLL, cb), jnp.float32)
        for j in range(_UNROLL):
            at = pl.multiple_of((base + j) * n, n)
            h = _advance(h, ds[j:j + 1], xs[j:j + 1], At, bs[pl.ds(at, n), :])
            ys = _put_row(ys, j, (h * _wide(cs[pl.ds(at, n), :], cb)).sum(
                axis=0, keepdims=True))
        y32[pl.ds(base, _UNROLL), :] = ys
        return h

    h_ref[...] = lax.fori_loop(0, steps // _UNROLL, eight, h_ref[...])
    y_ref[...] = (y32[...] + skip_ref[...] * x32[...]).astype(y_ref.dtype)


def _scan_bwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, skip_ref, g_ref,
                     border_ref, dx_ref, dd_ref, da_ref, db_ref, dc_ref,
                     dskip_ref, lam_ref, hs, x32, d32, g32, dx32, dd32, bs,
                     cs, pb, pc):
    """One chunk, the chunks taken from the last: the states of the chunk
    again from its border into ``hs`` (row block t + 1 holds h_t, block 0 the
    border), then the adjoint from the chunk's last step to its first."""
    from jax.experimental import pallas as pl
    steps, cb = x32.shape
    n = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    x32[...] = x_ref[...].astype(jnp.float32)
    d32[...] = d_ref[...].astype(jnp.float32)
    g32[...] = g_ref[...].astype(jnp.float32)
    bs[...] = _spread(b_ref[...], n)
    cs[...] = _spread(c_ref[...], n)
    At = a_ref[...]
    hs[pl.ds(0, n), :] = border_ref[...]

    def again(i, h):
        base = pl.multiple_of(i * _UNROLL, _UNROLL)
        xs, ds = x32[pl.ds(base, _UNROLL), :], d32[pl.ds(base, _UNROLL), :]
        for j in range(_UNROLL):
            at = pl.multiple_of((base + j) * n, n)
            h = _advance(h, ds[j:j + 1], xs[j:j + 1], At, bs[pl.ds(at, n), :])
            hs[pl.ds(at + n, n), :] = h
        return h

    lax.fori_loop(0, steps // _UNROLL, again, border_ref[...])

    def eight(k, carry):
        lam, dA = carry
        i = steps // _UNROLL - 1 - k
        base = pl.multiple_of(i * _UNROLL, _UNROLL)
        rows = pl.ds(base, _UNROLL)
        xs, ds, gs = x32[rows, :], d32[rows, :], g32[rows, :]
        dxs = jnp.zeros((_UNROLL, cb), jnp.float32)
        dds = jnp.zeros((_UNROLL, cb), jnp.float32)
        for j in reversed(range(_UNROLL)):
            at = pl.multiple_of((base + j) * n, n)
            d, xr, gr = ds[j:j + 1], xs[j:j + 1], gs[j:j + 1]
            before, h = hs[pl.ds(at, n), :], hs[pl.ds(at + n, n), :]
            decay = jnp.exp(d * At)
            lam = gr * _wide(cs[pl.ds(at, n), :], cb) + lam
            pc[pl.ds(at, n), :] = _fold(gr * h)
            pb[pl.ds(at, n), :] = _fold(lam * (d * xr))
            via_b = (lam * _wide(bs[pl.ds(at, n), :], cb)).sum(
                axis=0, keepdims=True)
            through = lam * before * decay
            dxs = _put_row(dxs, j, d * via_b)
            dds = _put_row(dds, j, (through * At).sum(axis=0, keepdims=True)
                           + xr * via_b)
            dA = dA + through * d
            lam = decay * lam
        dx32[rows, :] = dxs
        dd32[rows, :] = dds
        return lam, dA

    lam, dA = lax.fori_loop(0, steps // _UNROLL, eight,
                            (lam_ref[...], jnp.zeros_like(At)))
    lam_ref[...] = lam
    da_ref[...] += dA
    dskip_ref[...] += (g32[...] * x32[...]).sum(axis=0, keepdims=True)
    dx_ref[...] = (dx32[...] + skip_ref[...] * g32[...]).astype(dx_ref.dtype)
    dd_ref[...] = dd32[...].astype(dd_ref.dtype)
    db_ref[...] = _gather(pb[...], steps, n)
    dc_ref[...] = _gather(pc[...], steps, n)


def _channel_block(channels):
    return next(b for b in (512, 256, 128) if channels % b == 0)


def _padded(t):
    """(batch, L, N) -> (batch, L, 128), zeros beyond the N lanes."""
    return jnp.pad(t, [(0, 0), (0, 0), (0, _LANES - t.shape[-1])])


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


def _forward_pallas(x, delta, A, B, C, D, chunk, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, length, channels = x.shape
    n = A.shape[1]
    cb, nt = _channel_block(channels), length // chunk
    f32 = jnp.float32
    tile = pl.BlockSpec((None, chunk, cb), lambda b, c, t: (b, t, c))
    states = pl.BlockSpec((None, chunk, _LANES), lambda b, c, t: (b, t, 0))
    per_channel = pl.BlockSpec((n, cb), lambda b, c, t: (0, c))
    skip = pl.BlockSpec((1, cb), lambda b, c, t: (0, c))
    return _call(
        _scan_fwd_kernel, "selective_scan_pallas",
        (batch, channels // cb, nt),
        [tile, tile, per_channel, states, states, skip],
        [tile, pl.BlockSpec((None, None, n, cb),
                            lambda b, c, t: (b, t, 0, c))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((batch, nt, n, channels), f32)],
        [pltpu.VMEM((n, cb), f32)] + [pltpu.VMEM((chunk, cb), f32)] * 3
        + [pltpu.VMEM((chunk * n, _LANES), f32)] * 2,
        (x, delta, A.astype(f32).T, _padded(B), _padded(C),
         D.astype(f32)[None]), interpret)


def _backward_pallas(x, delta, A, B, C, D, borders, g, chunk,
                     interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, length, channels = x.shape
    n = A.shape[1]
    cb, nt = _channel_block(channels), length // chunk
    f32 = jnp.float32
    last = nt - 1                       # grid step t is chunk last - t
    tile = pl.BlockSpec((None, chunk, cb), lambda b, c, t: (b, last - t, c))
    states = pl.BlockSpec((None, chunk, _LANES),
                          lambda b, c, t: (b, last - t, 0))
    per_channel = pl.BlockSpec((n, cb), lambda b, c, t: (0, c))
    skip = pl.BlockSpec((1, cb), lambda b, c, t: (0, c))
    border = pl.BlockSpec((None, None, n, cb),
                          lambda b, c, t: (b, last - t, 0, c))
    partial = pl.BlockSpec((None, None, chunk, _LANES),
                           lambda b, c, t: (b, c, last - t, 0))
    by_state = jax.ShapeDtypeStruct((batch, channels // cb, length, _LANES),
                                    f32)
    dx, dd, dA, dB, dC, dD = _call(
        _scan_bwd_kernel, "selective_scan_bwd", (batch, channels // cb, nt),
        [tile, tile, per_channel, states, states, skip, tile, border],
        [tile, tile,
         pl.BlockSpec((None, n, cb), lambda b, c, t: (b, 0, c)),
         partial, partial,
         pl.BlockSpec((None, 1, cb), lambda b, c, t: (b, 0, c))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(delta.shape, delta.dtype),
         jax.ShapeDtypeStruct((batch, n, channels), f32), by_state, by_state,
         jax.ShapeDtypeStruct((batch, 1, channels), f32)],
        [pltpu.VMEM((n, cb), f32), pltpu.VMEM(((chunk + 1) * n, cb), f32)]
        + [pltpu.VMEM((chunk, cb), f32)] * 5
        + [pltpu.VMEM((chunk * n, _LANES), f32)] * 4,
        (x, delta, A.astype(f32).T, _padded(B), _padded(C),
         D.astype(f32)[None], g, borders), interpret)
    return (dx, dd, dA.sum(0).T.astype(A.dtype),
            dB.sum(1)[..., :n].astype(B.dtype),
            dC.sum(1)[..., :n].astype(C.dtype),
            dD.sum((0, 1)).astype(D.dtype))


# ---------------------------------------------------------------------------
# which form: by shape, then by where the call runs
# ---------------------------------------------------------------------------

def _kernels_take(x, A, chunk):
    """Whether the kernels take the call: whole (8, 128) tiles of float32
    and whole (16, 128) ones of bf16 rows, states a power of two of
    sublanes."""
    n = A.shape[1]
    return (x.shape[2] % _LANES == 0 and chunk % 16 == 0
            and n % 8 == 0 and n & (n - 1) == 0 and n <= _LANES)


def _choose(operands, chunk, pallas, fallback):
    x, A = operands[0], operands[2]
    _metrics.ssm_scan_shape(chunk, A.shape[0] * A.shape[1])
    if not _kernels_take(x, A, chunk):
        _metrics.ssm_scan_trace("jnp")
        return fallback(*operands)
    if any(isinstance(t, jax.core.Tracer) for t in operands):
        # a tracer has no device: the program it is staged into picks the
        # branch when it is lowered for the platform its operands live on
        _metrics.ssm_scan_trace("lowering_platform")
        return lax.platform_dependent(*operands, tpu=pallas, default=fallback)
    if all(d.platform == "tpu" for d in x.devices()):
        _metrics.ssm_scan_trace("pallas")
        return pallas(*operands)
    _metrics.ssm_scan_trace("jnp")
    return fallback(*operands)


def _forward(x, delta, A, B, C, D, chunk):
    return _choose((x, delta, A, B, C, D), chunk,
                   functools.partial(_forward_pallas, chunk=chunk),
                   functools.partial(_forward_jnp, chunk=chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, delta, A, B, C, D, chunk):
    return _forward(x, delta, A, B, C, D, chunk)[0]


def _scan_fwd(x, delta, A, B, C, D, chunk):
    y, borders = _forward(x, delta, A, B, C, D, chunk)
    return y, (x, delta, A, B, C, D, borders)


def _scan_bwd(chunk, res, g):
    return _choose((*res, g), chunk,
                   functools.partial(_backward_pallas, chunk=chunk),
                   functools.partial(_backward_jnp, chunk=chunk))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, A, B, C, D):
    """y (batch, L, C) of the recurrence in the module docstring: x, delta
    (batch, L, C), A (C, N), B, C (batch, L, N), D (C,).  The state starts
    at zero; it, ``exp`` and every sum are float32; y is in x's dtype.

    The forward keeps a state every ``CHUNK`` steps for the backward (one,
    for a shorter sequence).  A length that is no multiple of the chunk is
    padded with steps that leave the state alone (``delta`` 0)."""
    length = x.shape[1]
    chunk = CHUNK if length >= CHUNK else -(-length // 16) * 16
    pad = -length % chunk
    if pad:
        x, delta, B, C = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                          for t in (x, delta, B, C))
    y = _scan(x, delta, A, B, C, D, chunk)
    return y[:, :length] if pad else y


@register("_contrib_SelectiveScan", num_inputs=6,
          input_names=("data", "delta", "A", "B", "C", "D"),
          aliases=("selective_scan",))
def _selective_scan_op(data, delta, A, B, C, D):
    """Registered op wrapper: ``ops.ssm.selective_scan``."""
    return selective_scan(data, delta, A, B, C, D)
