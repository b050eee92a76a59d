"""Shape-manipulation, indexing, and ordering operators.

TPU-native equivalents of src/operator/tensor/matrix_op.cc, indexing_op.cc,
ordering_op.cc, init_op.cc, control_flow_op.cc (reference, SURVEY §2.2).
All shape arithmetic happens in Python at trace time (shapes are static under
XLA), so these lower to pure lax reshapes/slices/gathers.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import metrics as _metrics
from .registry import register


def infer_reshape(src_shape, target, reverse=False):
    """MXNet Reshape special codes (ref: matrix_op-inl.h ReshapeParam docs):

    0 = copy this dim; -1 = infer; -2 = copy all remaining dims;
    -3 = merge next two dims; -4 = split next dim by the following two values.
    """
    src = list(src_shape)
    tgt = list(target)
    if reverse:
        src = src[::-1]
        tgt = [t for t in tgt[::-1]]
        # -4's two split factors travel with it; reversing swaps them
        out = infer_reshape(src, tgt, reverse=False)
        return tuple(out[::-1])
    out = []
    i = 0  # index into src
    j = 0
    while j < len(tgt):
        t = tgt[j]
        if t == 0:
            out.append(src[i]); i += 1
        elif t == -1:
            out.append(-1); i += 1  # placeholder; src cursor advance is heuristic
        elif t == -2:
            out.extend(src[i:]); i = len(src)
        elif t == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif t == -4:
            a, b = tgt[j + 1], tgt[j + 2]
            d = src[i]
            if a == -1:
                a = d // b
            if b == -1:
                b = d // a
            out.extend([a, b]); i += 1; j += 2
        else:
            out.append(t)
            # advance src cursor heuristically (only matters for 0/-1 codes)
            if i < len(src):
                i += 1
        j += 1
    known = 1
    for d in out:
        if d != -1:
            known *= d
    total = int(np.prod(src_shape)) if src_shape else 1
    return tuple(d if d != -1 else total // max(known, 1) for d in out)


@register("Reshape", num_inputs=1, aliases=("reshape",))
def _reshape(data, shape=(), reverse=False):
    """ref: src/operator/tensor/matrix_op.cc Reshape"""
    return jnp.reshape(data, infer_reshape(data.shape, shape, reverse))


@register("Flatten", num_inputs=1, aliases=("flatten",))
def _flatten(data):
    """ref: matrix_op.cc Flatten — collapse all but first axis."""
    return jnp.reshape(data, (data.shape[0], -1))


@register("transpose", num_inputs=1)
def _transpose(data, axes=()):
    """ref: matrix_op.cc transpose"""
    return jnp.transpose(data, axes if axes else None)


@register("expand_dims", num_inputs=1)
def _expand_dims(data, axis=0):
    return jnp.expand_dims(data, axis)


@register("squeeze", num_inputs=1)
def _squeeze(data, axis=None):
    return jnp.squeeze(data, axis)


@register("swapaxes", num_inputs=1, aliases=("SwapAxis",))
def _swapaxes(data, dim1=0, dim2=0):
    return jnp.swapaxes(data, dim1, dim2)


@register("slice", num_inputs=1, aliases=("crop",))
def _slice(data, begin=(), end=(), step=()):
    """ref: matrix_op.cc slice (begin/end may contain None)."""
    step = step or (None,) * len(begin)
    idx = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register("_bulk_view_extract", num_inputs=1)
def _bulk_view_extract(data, offset=0, shape=()):
    """Contiguous row-major view extraction (engine deferred views): the
    program-node form of NDArray._read over a (base, offset, shape) view,
    recorded inside a bulk segment so view creation no longer flushes."""
    flat = jnp.reshape(data, (-1,))
    size = 1
    for s in shape:
        size *= s
    return jnp.reshape(lax.slice(flat, (offset,), (offset + size,)), shape)


@register("_bulk_view_write", num_inputs=2)
def _bulk_view_write(base, value, offset=0):
    """Write-through to a deferred view: rebind the base's buffer with the
    view's span replaced (the program-node form of NDArray._write's
    scatter into the base)."""
    flat = jnp.reshape(base, (-1,))
    flat = lax.dynamic_update_slice(
        flat, jnp.reshape(value, (-1,)).astype(base.dtype), (offset,))
    return jnp.reshape(flat, base.shape)


@register("slice_axis", num_inputs=1)
def _slice_axis(data, axis=0, begin=0, end=None):
    """ref: matrix_op.cc slice_axis"""
    axis = axis % data.ndim
    n = data.shape[axis]
    b = begin if begin >= 0 else begin + n
    e = n if end is None else (end if end >= 0 else end + n)
    return lax.slice_in_dim(data, b, e, axis=axis)


@register("slice_like", num_inputs=2, nograd_inputs=(1,))
def _slice_like(data, shape_like, axes=()):
    """ref: matrix_op.cc slice_like"""
    axes = axes or tuple(range(shape_like.ndim))
    out = data
    for a in axes:
        out = lax.slice_in_dim(out, 0, shape_like.shape[a], axis=a)
    return out


@register("Concat", num_inputs=None, aliases=("concat",))
def _concat(*args, dim=1, num_args=None):
    """ref: src/operator/nn/concat.cc"""
    return jnp.concatenate(args, axis=dim)


@register("stack", num_inputs=None)
def _stack(*args, axis=0, num_args=None):
    return jnp.stack(args, axis=axis)


@register("SliceChannel", num_inputs=1, num_outputs=1, aliases=("split",),
          fnum_outputs=lambda p: int(p.get("num_outputs", 1)))
def _split(data, num_outputs=1, axis=1, squeeze_axis=False):
    """ref: src/operator/slice_channel.cc — returns a list of outputs.

    num_outputs is dynamic metadata; the front-end special-cases the output
    count (see ndarray/register.py analogue).
    """
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register("repeat", num_inputs=1)
def _repeat(data, repeats=1, axis=None):
    return jnp.repeat(data, repeats, axis=axis)


@register("tile", num_inputs=1)
def _tile(data, reps=()):
    return jnp.tile(data, reps)


@register("reverse", num_inputs=1, aliases=("flip",))
def _reverse(data, axis=()):
    ax = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.flip(data, ax)


@register("Pad", num_inputs=1, aliases=("pad",))
def _pad(data, mode="constant", pad_width=(), constant_value=0.0):
    """ref: src/operator/pad.cc (pad_width in mxnet flat before/after pairs)."""
    pw = [(int(pad_width[2 * i]), int(pad_width[2 * i + 1]))
          for i in range(len(pad_width) // 2)]
    mode_map = {"constant": "constant", "edge": "edge", "reflect": "reflect"}
    if mode == "constant":
        return jnp.pad(data, pw, mode="constant", constant_values=constant_value)
    return jnp.pad(data, pw, mode=mode_map[mode])


@register("space_to_depth", num_inputs=1)
def _space_to_depth(data, block_size=1):
    """ref: matrix_op.cc space_to_depth (NCHW)."""
    n, c, h, w = data.shape
    b = block_size
    x = data.reshape(n, c, h // b, b, w // b, b)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(n, c * b * b, h // b, w // b)


@register("depth_to_space", num_inputs=1)
def _depth_to_space(data, block_size=1):
    n, c, h, w = data.shape
    b = block_size
    x = data.reshape(n, b, b, c // (b * b), h, w)
    x = x.transpose(0, 3, 4, 1, 5, 2)
    return x.reshape(n, c // (b * b), h * b, w * b)

# ---------------------------------------------------------------------------
# indexing (reference: src/operator/tensor/indexing_op.cc)
# ---------------------------------------------------------------------------


@register("take", num_inputs=2, nograd_inputs=(1,))
def _take(a, indices, axis=0, mode="clip"):
    """ref: indexing_op.cc Take"""
    idx = indices.astype(jnp.int32)
    n = a.shape[axis]
    if mode == "clip":
        idx = jnp.clip(idx, 0, n - 1)
    elif mode == "wrap":
        idx = jnp.mod(idx, n)
    return jnp.take(a, idx, axis=axis)


@register("batch_take", num_inputs=2, nograd_inputs=(1,), aliases=("pick",))
def _pick(data, index, axis=1, keepdims=False):
    """ref: indexing_op.cc pick/batch_take"""
    idx = index.astype(jnp.int32)
    out = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


# -- Embedding: the lookup, and a backward rule of its own --------------------
#
# The table's gradient is the transpose of the lookup, XLA's ``scatter-add``
# of the cotangent's rows.  On a TPU the compiler sorts the ids and adds the
# rows one after another, and what a row costs goes with how it tiles a row
# of that width, not with the work: at most 0.40 us a row at every width that
# is a power of two (512 to 4096, six tables) and 0.62 at every other width
# under 2560 elements, but 2.4 at 2560, 1.25 at 3584 and 10.7 at 5120 over a
# table of 25088 rows, in bf16 and float32 alike (PERF.md section 6, PR 39).
# A quarter of the row at a time, 512 to 1024 elements wide, read 0.02-0.10.
# So a row of 2560 elements or more whose width is no power of two is handed
# over in four column blocks, where the three more passes over the table that
# the blocks' concatenation costs are worth it.

_WIDE = 2560            # elements: no narrower row read over 0.62 us
_PARTS = 4
_WORST_ROW_S = 2.6e-6   # the scatter's worst row inside a train step
_HBM_BYTES_S = 8e11     # what a pass over the table moves on a v5e
_MEASURED = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))  # 2-byte rows


# A branch of ``platform_dependent`` puts ``branch_0_fun`` on the name stack;
# the scope is set inside each form, so that a reader of the ops' ``op_name``
# paths finds it innermost whichever way the form was reached.
_SCOPE = "embed_grad"


def _table_grad_scatter(idx, g, rows):
    """The transpose of the clipped take as JAX writes it: one
    ``scatter-add`` of whole rows."""
    table = jax.ShapeDtypeStruct((rows,) + g.shape[idx.ndim:], g.dtype)
    take = lambda w: jnp.take(w, idx, axis=0, mode="clip")
    with jax.named_scope(_SCOPE):
        return jax.linear_transpose(take, table)(g)[0]


def _table_grad_column_parts(idx, g, rows):
    """The same sum, ``_PARTS`` column blocks of the table one after another:
    each its own ``scatter-add`` of the same ids."""
    with jax.named_scope(_SCOPE):
        ids = jnp.clip(idx.reshape(-1), 0, rows - 1)
        flat = g.reshape(ids.shape[0], -1)
        width = flat.shape[1] // _PARTS
        blocks = [jnp.zeros((rows, width), g.dtype).at[ids].add(
                      flat[:, lo:lo + width])
                  for lo in range(0, flat.shape[1], width)]
        return jnp.concatenate(blocks, axis=1).reshape(
            (rows,) + g.shape[idx.ndim:])


def embedding_grad_form(n, rows, dim, dtype, platform):
    """``"column_parts"`` or ``"scatter"``: how the gradient of a ``rows`` x
    ``dim`` table of ``dtype`` is summed from ``n`` looked-up rows on
    ``platform``.  Two modelled costs decide a wide row: the blocks' three
    more passes over the table, and half of what ``n`` rows cost where the
    whole-row scatter tiles badly, since nothing says whether it will."""
    dtype = jnp.dtype(dtype)
    trusted = dim < _WIDE or dim & (dim - 1) == 0 or dim % _PARTS != 0
    if platform != "tpu" or trusted or dtype not in _MEASURED:
        # a float32 table's scatter reads the same at 2560 columns; its
        # blocks were not timed (PERF.md section 7)
        return "scatter"
    passes = 3 * rows * dim * dtype.itemsize / _HBM_BYTES_S
    return "column_parts" if passes <= n * _WORST_ROW_S / 2 else "scatter"


def _table_grad(idx, g, rows):
    """The gradient of the table that ``idx`` looked rows up in, from the
    rows' cotangent ``g``: the form chosen by the operands' devices, or for
    tracers by the platform the enclosing program is lowered for."""
    traced = any(isinstance(t, jax.core.Tracer) for t in (idx, g))
    platform = "tpu" if traced else next(iter(g.devices())).platform
    dim = g.size // max(idx.size, 1)
    form = embedding_grad_form(idx.size, rows, dim, g.dtype, platform)
    scatter = functools.partial(_table_grad_scatter, rows=rows)
    if form == "scatter":
        _metrics.embedding_grad_trace("scatter")
        return scatter(idx, g)
    parts = functools.partial(_table_grad_column_parts, rows=rows)
    if traced:
        _metrics.embedding_grad_trace("column_parts_on_tpu")
        return lax.platform_dependent(idx, g, tpu=parts, default=scatter)
    _metrics.embedding_grad_trace("column_parts")
    return parts(idx, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lookup(rows, weight, idx):
    # clip, not fill: jnp.take's NaN-fill default turns one rounded-up
    # index (e.g. a bf16-cast token id at the vocab edge) into a NaN row
    # that poisons the whole step; the reference clamps too
    return jnp.take(weight, idx, axis=0, mode="clip")


def _lookup_fwd(rows, weight, idx):
    return _lookup(rows, weight, idx), idx


def _lookup_bwd(rows, idx, g):
    return _table_grad(idx, g, rows), np.zeros(idx.shape, jax.dtypes.float0)


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


@register("Embedding", num_inputs=2, nograd_inputs=(0,),
          input_names=("data", "weight"),
          finfer_params=lambda ds, p: {"weight": (p.get("input_dim", 0),
                                                  p.get("output_dim", 0))})
def _embedding(data, weight, input_dim=0, output_dim=0, dtype="float32", sparse_grad=False):
    """ref: indexing_op.cc Embedding — gather rows of weight.

    On TPU this is a gather from HBM; the rowsparse-gradient variant of the
    reference maps to the sparse module's row-sparse grad path.
    """
    return _lookup(weight.shape[0], weight, data.astype(jnp.int32))


@register("one_hot", num_inputs=1, differentiable=False)
def _one_hot(indices, depth=0, on_value=1.0, off_value=0.0, dtype="float32"):
    """ref: indexing_op.cc one_hot"""
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=jnp.dtype(dtype))
    return oh * on_value + (1.0 - oh) * off_value


@register("gather_nd", num_inputs=2, nograd_inputs=(1,))
def _gather_nd(data, indices):
    """ref: indexing_op.cc gather_nd — indices shape (M, ...)."""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register("scatter_nd", num_inputs=2, nograd_inputs=(1,))
def _scatter_nd(data, indices, shape=()):
    """ref: indexing_op.cc scatter_nd"""
    out = jnp.zeros(shape, dtype=data.dtype)
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return out.at[idx].add(data)


@register("where", num_inputs=3)
def _where(condition, x, y):
    """ref: src/operator/tensor/control_flow_op.cc where"""
    return jnp.where(condition != 0, x, y)

# ---------------------------------------------------------------------------
# ordering (reference: src/operator/tensor/ordering_op.cc)
# ---------------------------------------------------------------------------


@register("topk", num_inputs=1, differentiable=False,
          fnum_outputs=lambda p: 2 if p.get("ret_typ") == "both" else 1)
def _topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """ref: ordering_op.cc topk"""
    x = jnp.moveaxis(data, axis, -1)
    if is_ascend:
        vals, idxs = lax.top_k(-x, k)
        vals = -vals
    else:
        vals, idxs = lax.top_k(x, k)
    vals = jnp.moveaxis(vals, -1, axis)
    idxs = jnp.moveaxis(idxs, -1, axis)
    if ret_typ == "value":
        return vals
    if ret_typ == "indices":
        return idxs.astype(jnp.dtype(dtype))
    if ret_typ == "mask":
        oh = jax.nn.one_hot(jnp.moveaxis(idxs, axis, -1), data.shape[axis], dtype=data.dtype)
        return jnp.moveaxis(oh.sum(-2), -1, axis)
    # 'both'
    return vals, idxs.astype(jnp.dtype(dtype))


@register("sort", num_inputs=1, differentiable=False)
def _sort(data, axis=-1, is_ascend=True):
    s = jnp.sort(data, axis=axis)
    return s if is_ascend else jnp.flip(s, axis=axis)


@register("argsort", num_inputs=1, differentiable=False)
def _argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    s = jnp.argsort(data, axis=axis)
    if not is_ascend:
        s = jnp.flip(s, axis=axis)
    return s.astype(jnp.dtype(dtype))


@register("shuffle", num_inputs=1, differentiable=False, needs_rng=True, aliases=("_shuffle",))
def _shuffle(data, rng=None):
    """ref: src/operator/random/shuffle_op.cc — permute along first axis."""
    perm = jax.random.permutation(rng, data.shape[0])
    return jnp.take(data, perm, axis=0)

# ---------------------------------------------------------------------------
# casts & identity
# ---------------------------------------------------------------------------


@register("Cast", num_inputs=1, aliases=("cast",))
def _cast(data, dtype="float32"):
    return data.astype(jnp.dtype(dtype))


@register("_copy", num_inputs=1, aliases=("identity",))
def _copy(data):
    return jnp.asarray(data)


@register("BlockGrad", num_inputs=1, differentiable=False, aliases=("stop_gradient",))
def _blockgrad(data):
    """ref: elemwise_unary_op_basic.cc BlockGrad"""
    return lax.stop_gradient(data)


@register("make_loss", num_inputs=1, aliases=("MakeLoss",))
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """ref: src/operator/make_loss.cc — identity fwd, grad_scale bwd."""
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jnp.full_like(g, grad_scale),)

    f.defvjp(fwd, bwd)
    return f(data)


@register("_grad_add", num_inputs=2)
def _grad_add(lhs, rhs):
    return lhs + rhs

# ---------------------------------------------------------------------------
# sequence ops (reference: src/operator/sequence_{last,mask,reverse}.cc)
# ---------------------------------------------------------------------------


def _seq_len_or_full(data, sequence_length, use_sequence_length, time_axis=0):
    if use_sequence_length and sequence_length is not None:
        return sequence_length.astype(jnp.int32)
    return jnp.full((data.shape[1 - time_axis if time_axis == 0 else 0],),
                    data.shape[time_axis], dtype=jnp.int32)


@register("SequenceLast", num_inputs=None)
def _sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    """ref: sequence_last.cc — (T,N,...) pick last valid step per sequence."""
    x = jnp.moveaxis(data, axis, 0)
    T, N = x.shape[0], x.shape[1]
    if use_sequence_length and sequence_length is not None:
        idx = jnp.clip(sequence_length.astype(jnp.int32) - 1, 0, T - 1)
    else:
        idx = jnp.full((N,), T - 1, dtype=jnp.int32)
    return x[idx, jnp.arange(N)]


@register("SequenceMask", num_inputs=None)
def _sequence_mask(data, sequence_length=None, use_sequence_length=False,
                   value=0.0, axis=0):
    """ref: sequence_mask.cc — zero (or `value`) out steps beyond seq_len."""
    if not use_sequence_length or sequence_length is None:
        return data
    x = jnp.moveaxis(data, axis, 0)
    T, N = x.shape[0], x.shape[1]
    mask = jnp.arange(T)[:, None] < sequence_length.astype(jnp.int32)[None, :]
    mask = mask.reshape((T, N) + (1,) * (x.ndim - 2))
    out = jnp.where(mask, x, jnp.asarray(value, x.dtype))
    return jnp.moveaxis(out, 0, axis)


@register("SequenceReverse", num_inputs=None)
def _sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    """ref: sequence_reverse.cc — reverse each sequence up to its length."""
    x = jnp.moveaxis(data, axis, 0)
    T = x.shape[0]
    if not use_sequence_length or sequence_length is None:
        out = jnp.flip(x, axis=0)
    else:
        L = sequence_length.astype(jnp.int32)  # (N,)
        t = jnp.arange(T)[:, None]
        src = jnp.where(t < L[None, :], L[None, :] - 1 - t, t)  # (T,N)
        out = jnp.take_along_axis(x, src.reshape((T, x.shape[1]) + (1,) * (x.ndim - 2)), axis=0)
    return jnp.moveaxis(out, 0, axis)


@register("hard_sigmoid", num_inputs=1)
def _hard_sigmoid(data, alpha=0.2, beta=0.5):
    """ref: src/operator/mshadow_op.h hard_sigmoid — clip(a·x + b, 0, 1)."""
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("square_sum", num_inputs=1)
def _square_sum(data, axis=None, keepdims=False, exclude=False):
    """Fused sum(x²) (ref: src/operator/tensor/square_sum.cc — the
    row-sparse fast path lives on the NDArray surface; this is the dense
    registered op so Symbol graphs can reach it)."""
    ax = None if axis is None else (axis if isinstance(axis, (tuple, list))
                                    else (axis,))
    if ax is not None and exclude:
        ax = tuple(i for i in range(data.ndim) if i not in
                   tuple(a % data.ndim for a in ax))
    return jnp.sum(data * data, axis=ax, keepdims=keepdims)


@register("_cast_storage_dense", num_inputs=1, aliases=("cast_storage",))
def _cast_storage_op(data, stype="default"):
    """Registered twin of sparse.cast_storage (ref:
    src/operator/tensor/cast_storage.cc).  Inside a compiled graph every
    tensor is dense; 'row_sparse'/'csr' requests are honored at the
    NDArray surface (ndarray/sparse.py cast_storage), so here the values
    pass through unchanged — the graph stays correct, the storage
    optimization applies in eager mode."""
    return data


@register("_sparse_retain_dense", num_inputs=2, nograd_inputs=(1,),
          aliases=("sparse_retain",))
def _sparse_retain_op(data, indices):
    """Zero all rows except ``indices`` (ref:
    src/operator/tensor/sparse_retain.cc).  Dense semantics of the same
    contract; the rsp fast path is ndarray/sparse.py retain."""
    idx = indices.astype(jnp.int32)
    mask = jnp.zeros((data.shape[0],), jnp.bool_).at[idx].set(True)
    return jnp.where(mask.reshape((-1,) + (1,) * (data.ndim - 1)),
                     data, jnp.zeros_like(data))
