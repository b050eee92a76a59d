"""The cotangent of a grouped product's weights over several row buffers.

``parallel/moe.py`` sends a long row of tokens through the routed layer in p
equal parts, a buffer of assignments each.  The cotangent of an expert's
weights is then the sum over the parts of ``lhs_j[rows of group g].T @
rhs_j[rows of group g]``.  JAX's Pallas ``megablox.tgmm`` takes one buffer:
called once a part it writes the whole float32 ``(groups, k, n)`` result p
times and something has to read the p results again to add them (with
``existing_out=`` the kernel itself does, the same bytes).  ``tgmm_parts`` is
that kernel with 2p row operands: the row tiles of all parts are visited
group-major, a group's of every part one after another, so a group's
``(tk, tn)`` block is accumulated over all parts in the one float32
accumulator in VMEM and written to HBM once.

Grid ``(tiles_n, tiles_k, steps)`` as megablox's, the last axis dynamic.
The steps are the row tiles a group touches in each part, as megablox's
``make_group_metadata(..., visit_empty_groups=True)`` counts them (a group
without rows still gets a step, which zeroes and stores its block), laid
out group after group from the parts' group borders (``_steps``).  A step
knows its group (``group_ids``), its part (``part_ids``) and, for every part,
which row tile that part's operands show (``tile_at``): its own tile at its
own steps and its last own tile at the others', so that a block is fetched
once a visit and not again while another part works.  The body is
megablox's: both operand tiles masked to the rows of the step's group in
float32, one product into the accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["tgmm_parts"]


def _steps(group_sizes, rows, tm):
    """The schedule of the parts' row tiles, group-major: ``(offsets,
    group_ids, part_ids, tile_at), steps``.  ``offsets`` (p x (groups + 1),
    flat) are the parts' group borders; ``group_ids`` and ``part_ids`` (S,)
    say whose tile a step works, of which group; ``tile_at`` (p x S, flat)
    is the row tile each part's operands show at each step; ``steps`` how
    many of the S = p x (tiles + groups - 1) slots are real, the rest lie
    behind them and hold valid indices.

    A part visits the tiles its group's rows touch (a tile on a border is
    visited by both groups, as megablox's), a group without rows one tile,
    where nothing is multiplied: its step is there to zero and store."""
    parts, tiles = len(group_sizes), rows // tm
    sizes = jnp.stack(group_sizes)                           # (p, groups)
    groups = sizes.shape[1]
    ends = jnp.cumsum(sizes, axis=1)
    first = (ends - sizes) // tm
    visited = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 1)
    # the last tile a part shows in a group, and in the group before
    last = jnp.minimum(first + visited - 1, tiles - 1)
    before = jnp.pad(last, ((0, 0), (1, 0)))[:, :-1]
    # cells (group, part) in the order they are worked, the steps of each
    cell_steps = visited.T.reshape(-1)
    cell_ends = jnp.cumsum(cell_steps)
    cell_starts = cell_ends - cell_steps
    step = jnp.arange(parts * (tiles + groups - 1))[:, None]
    # (S, cells): the cell a step lies in; no cell for the slots behind
    # the steps, which so read group 0, part 0, tile 0.  A table is read
    # through it by a masked sum: the TPU's gather of a few dozen words
    # keeps a megabyte of temporaries a call alive
    inside = (step >= cell_starts) & (step < cell_ends)

    def at_step(by_cell):
        return jnp.where(inside, by_cell, 0).sum(axis=-1)

    cells = jnp.arange(parts * groups)
    group, part = at_step(cells // parts), at_step(cells % parts)
    tile = jnp.minimum(
        at_step(first.T.reshape(-1) - cell_starts + step), tiles - 1)
    # a part shows its own tile at its own steps; at the others' the last
    # tile it had, which before its turn in a group is the group before's
    # (tile 0 before group 0: the one it wants first)
    own = jnp.arange(parts)[:, None]
    shown = jnp.where(part > own,
                      at_step(jnp.repeat(last, parts, axis=1)[:, None]),
                      at_step(jnp.repeat(before, parts, axis=1)[:, None]))
    tile_at = jnp.where(part == own, tile, shown)
    offsets = jnp.pad(ends, ((0, 0), (1, 0)))
    return ((offsets.reshape(-1), group, part, tile_at.reshape(-1)),
            cell_ends[-1])


@functools.partial(jax.jit,
                   static_argnames=["out_dtype", "tiling", "vmem_bytes",
                                    "interpret"])
def tgmm_parts(lhs, rhs, group_sizes, out_dtype, tiling, vmem_bytes=None,
               interpret=False):
    """``sum_j lhs[j][rows of group g].T @ rhs[j][rows of group g]`` for every
    group g: (groups, k, n) in ``out_dtype``, accumulated in float32 over
    all parts and rounded once.

    lhs, rhs : p arrays (m, k) and p arrays (m, n) of one 16-bit float type,
        a part's rows sorted by group; rows behind a part's groups are never
        read into the sum (they may hold anything, a NaN among it).
    group_sizes : p arrays (groups,) int32.
    tiling : (tm, tk, tn), ``tm`` dividing m; ``tk`` and ``tn`` may leave a
        ragged last tile, which is computed and dropped as megablox's.
    vmem_bytes : the VMEM the kernel may hold (None: the compiler's
        default); its operands' blocks are 2p, each held twice.
    A group that no part has rows in gets zeros.
    """
    parts = len(lhs)
    (rows, k), n = lhs[0].shape, rhs[0].shape[1]
    groups = group_sizes[0].shape[0]
    tm, tk, tn = tiling
    tiles_k, tiles_n = pl.cdiv(k, tk), pl.cdiv(n, tn)
    metadata, steps = _steps(group_sizes, rows, tm)
    slots = metadata[1].shape[0]

    def kernel(offsets, group_ids, part_ids, tile_at, *refs):
        lhs_refs, rhs_refs = refs[:parts], refs[parts:2 * parts]
        out, acc = refs[2 * parts:]
        step, last = pl.program_id(2), pl.num_programs(2) - 1
        group, part = group_ids[step], part_ids[step]

        @pl.when((step == 0) | (group_ids[jnp.maximum(step - 1, 0)] != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        start = offsets[part * (groups + 1) + group]
        end = offsets[part * (groups + 1) + group + 1]

        def in_group(width, first_row):
            row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + first_row
            return (row >= start) & (row < end)

        for mine in range(parts):
            @pl.when((part == mine) & (end > start))
            def _product(mine=mine):
                first_row = tile_at[mine * slots + step] * tm
                a, b = lhs_refs[mine][...], rhs_refs[mine][...]
                a = jnp.where(in_group(tk, first_row), a.astype(jnp.float32),
                              0.0).swapaxes(0, 1).astype(a.dtype)
                b = jnp.where(in_group(tn, first_row), b.astype(jnp.float32),
                              0.0).astype(b.dtype)
                acc[...] += lax.dot(a, b, preferred_element_type=jnp.float32)

        @pl.when((step == last)
                 | (group_ids[jnp.minimum(step + 1, last)] != group))
        def _store():
            out[...] = acc[...].astype(out_dtype)

    def rows_of(part, column):
        def index(n_i, k_i, step, offsets, group_ids, part_ids, tile_at):
            return tile_at[part * slots + step], (k_i, n_i)[column]
        return index

    def out_index(n_i, k_i, step, offsets, group_ids, part_ids, tile_at):
        return group_ids[step], k_i, n_i

    itemsize = lhs[0].dtype.itemsize
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=(
                [pl.BlockSpec((tm, tk), rows_of(j, 0)) for j in range(parts)]
                + [pl.BlockSpec((tm, tn), rows_of(j, 1))
                   for j in range(parts)]),
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(tiles_n, tiles_k, steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * parts * rows * k * n, transcendentals=0,
            bytes_accessed=(
                parts * rows * itemsize * (k * tiles_n + n * tiles_k)
                + groups * k * n * jnp.dtype(out_dtype).itemsize)))
    return call(*metadata, *lhs, *rhs)
