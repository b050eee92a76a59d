"""Mixture-of-Experts with expert parallelism.

A new capability mandated by SURVEY §2.4 (the reference, a 2018
framework, has no EP row to port — "EP via sharded gather/scatter —
these are *new capabilities*"): a switch-style MoE feed-forward block
whose stacked expert weights shard over the mesh "ep" axis.

Three dispatch modes behind one module interface:

* ``dispatch="dense"`` (default) — einsums over the expert dimension,
  ``combine[n,e] · (x[n,d] @ W[e,d,h])``, with ``e`` sharded.  GSPMD
  partitions the contraction and inserts the psum merging expert outputs
  over ICI.  Simple and exact for any top_k, but compute ∝ num_experts.
* ``dispatch="capacity"`` — the classic Switch formulation: top-1
  routing with per-expert capacity slots; token activations travel to
  their expert's device via explicit ``lax.all_to_all`` and back, so
  compute is independent of num_experts and overflow tokens are dropped
  (``last_drop_fraction`` reports the rate on eager calls).
* ``dispatch="grouped"`` — dropless top-k for a layer that is *told which
  experts it holds* (``experts_held=(first, count)``, default all): the
  router scores all ``num_experts``, every (token, expert) assignment that
  lands on a held expert is kept, whatever the imbalance, and computed as
  one grouped matrix product over the held experts' stacked weights
  (``grouped_dot``: the assignments sorted by expert, the rows of absent
  experts behind them in no group; JAX's Pallas grouped matmul on the TPU,
  ``jax.lax.ragged_dot`` off it).  The buffer has a row for every
  assignment (tokens × top_k), so its shapes are static and nothing can
  overflow; the rows behind the groups are written by no kernel and read
  by nothing: the two gathers that move rows between the tokens' order and
  the buffer's send every assignment that lies in no group to a row of
  zeros (``_to_buffer``, ``_to_tokens``), so the buffer is never masked
  whole.  The assignments are numbered choice x N + token, so the buffer
  seen by choice, (k, N, d), is the same bytes for any top_k.  What absent
  experts would have added is left out: on the mesh of a deployment their
  chips add it through the exchange; on one chip the layer computes its
  share (the chip benchmark's cell ``lfm2moe_fused_s8192``: experts 0–7 of
  32, top-4).  Compute ∝ the assignments held, independent of num_experts.
  **The tokens in parts** (PR 41): a row of tokens whose buffer would be
  larger than ``_PART_BYTES`` (96 MiB) goes through the layer in the fewest
  equal parts that bring it under, each part the whole dropless layer over
  its tokens with a buffer of its own (N / p x top_k rows), the outputs
  concatenated and ``group_sizes`` summed; a token's output does not depend
  on which tokens share its buffer, so this is the same result, and since a
  part has a row for each of its assignments nothing can overflow (PR 31's
  bounded buffer cut the *rows* and ran a tail for the overflow).  Why: the
  TPU's compiler never gives an array of 128 MiB or more its second memory
  space (``S(1)`` in a layout), and XLA's gather over a buffer of 134 MB
  moved a row in 34.5 ns where over Kimi-VL's 100.7 MB it took 6.3-9.1
  (PERF.md, S16 (a)).  The parts come from the shapes and the rows' dtype
  alone (``_parts``: Kimi-VL's row of 4096 one, LFM2's and Trinity-Mini's
  two, Mellum2's four), no argument and no switch.  **The parts' weight
  gradient** (PR 46): the parts go through each of the layer's three
  products side by side under one rule (``parted_dot``), so that an expert
  weight's gradient is one product over all parts' rows
  (``parallel/tgmm_parts.py``: megablox's ``tgmm`` over 2p row buffers, a
  group's row tiles of every part one after another), accumulated in one
  float32 accumulator in VMEM and written once.  Until then each part's
  ``tgmm`` wrote a whole float32 (held, d, h) result, autodiff added the p
  results and Adam's update read all p: a third to a half of what the parts
  had won in the gathers (PERF.md section 6, PR 46).  One part keeps
  ``grouped_dot`` and megablox's kernel: Kimi-VL's step is the program it
  was.  The experts' weights reach the parts in float32 and the gradient
  leaves the kernel in float32, rounded once where the cast is transposed,
  as one part's is; off the TPU the rule adds the parts' ``ragged_dot``
  transposes in float32.
  **The products' tiles** (PR 42; ``_tiling``, from the shapes and the
  dtypes' sizes alone): rows in tiles of 256; the dimension a ``gmm``
  contracts whole up to 2048 (``_whole``); a weight dimension that a kernel
  writes in the largest multiple of 128 up to 1024 that divides it, and
  never under 512 where the dimension is that wide (``_tile``): the
  kernels' grids put the column tile outermost and fetch the rows again
  for each.  Kimi-VL's 1408 = 11 x 128 ran in eleven tiles of 128; it now
  runs whole where the kernel's blocks fit the 16 MiB of VMEM by
  ``_block_bytes`` (all six of its narrow products do, in bf16), else in
  two tiles of 768, the second ragged (megablox computes the 128 columns
  past the end and drops them).  A column tile changes no sum's order:
  the results are the parent's bit for bit.

Routing, shared by ``dense`` and ``grouped`` (``_route``): ``router`` says
how scores are made of the gate's logits (``"softmax"``, or ``"sigmoid"``
as DeepSeek-V3 and LFM2 score), ``selection_bias`` adds a buffer that is
not trained to the scores *for the choice only* (auxiliary-loss-free load
balancing), ``norm_topk`` divides the chosen experts' scores by their sum
(over all chosen, held or not), ``scaling`` multiplies them.  ``gated``
experts are W2 (silu(W1 x) ⊙ W3 x); otherwise relu(x W1) W2.
``shared_hidden_size`` adds a shared expert (DeepSeekMoE, arXiv:2401.06066):
one ``GatedMLP`` of that width that every token passes, unweighted, added to
the routed sum under the scope ``moe_shared``.  It is no part of the share:
outside ``experts_held``, not sharded over ``ep`` (every chip computes it
alike), so where shares are added up it counts once.

What has run on the chip: ``grouped`` alone, in ``lfm2moe_fused_s8192``
and, at top-8 of 64 by softmax with experts 0-7 held, in
``mellum2_fused_s8192``, and at top-6 of 64 by sigmoid with ``scaling``
2.446 beside a shared expert in ``kimivl_mla_fused_1row``, and at top-8 of
128 in ``trinitymini_gated_fused_1row`` (PERF.md).  The weights' widths
there and their tiles: 1792 in two of 896 (LFM2), 896 whole and 2304 in
three of 768 (Mellum2), 1024 whole (Trinity-Mini), every 2048 in two of
1024, and since PR 42 Kimi-VL's 1408 whole for eleven of 128.
``dense`` and ``capacity`` are held by the CPU tests
(``tests/test_model_parallel.py``, ``tests/test_moe_capacity.py``); no
benchmark cell runs them, and ``capacity`` needs a mesh with an ``ep`` axis.
On eager calls the layer counts itself: ``graft_moe_dispatch_traces_total
{path}`` every routed forward traced (beside it the gauges
``graft_moe_buffer_parts`` and ``graft_moe_buffer_part_bytes`` of the last
``grouped`` call traced, and ``graft_moe_product_tile{product, dim}`` with
``graft_moe_ragged_tile_traces_total{product}`` of the Pallas kernels
traced, and ``graft_moe_weight_grad_traces_total{form, parts}``, how the
experts' weight gradient is formed), and for ``grouped``
``graft_moe_assignments_total{held}`` and the gauge
``graft_moe_expert_load_max_over_mean`` (``last_expert_load`` has the
counts).  Inside a compiled step the same counts go out with the step's
results (``telemetry.step_counter``: ``moe_held_rows``, ``moe_assignments``,
and ``moe_dropped_rows`` of ``capacity``), a row a layer in call order,
read back by step id with ``telemetry.step_counters()``.
"""
from __future__ import annotations

import functools
import math
import operator

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from ..telemetry import metrics as _metrics
from ..telemetry.tracing import step_counter

__all__ = ["ExpertParallelMoE"]


def _switch_body(x, gw, w1, w2, *, axis, num_experts, cap):
    """Per-device capacity-based Switch dispatch (tokens sharded over the
    ep axis, experts sharded over the ep axis).

    The classic Switch-Transformer formulation: each token picks its top-1
    expert; the first ``cap`` tokens per expert get a capacity slot, the
    rest are DROPPED (output 0 for the FFN branch); dispatched token
    activations travel to the expert's device via ``lax.all_to_all`` and
    the expert outputs ride the reverse all-to-all home.  Compute is
    O(tokens·d·h) — independent of num_experts — where the dense masked
    path pays num_experts×.
    """
    nloc = x.shape[0]
    logits = x @ gw                                      # (N_l, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                  # (N_l,)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=x.dtype)
    # position of each token in its expert's queue (arrival order)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot - onehot,
                  axis=-1).astype(jnp.int32)
    keep = (pos < cap).astype(x.dtype)                   # capacity gate
    disp = onehot * keep[:, None]                        # (N_l, E)
    slot = jax.nn.one_hot(pos, cap, dtype=x.dtype)       # (N_l, C)
    dispatch = jnp.einsum("ne,nc->nec", disp, slot)      # (N_l, E, C)
    ein = jnp.einsum("nec,nd->ecd", dispatch, x)         # (E, C, d)
    # ship each expert's slot block to the device that owns the expert
    ein = lax.all_to_all(ein, axis, split_axis=0, concat_axis=1,
                         tiled=True)                     # (E/P, P·C, d)
    h = jax.nn.relu(jnp.einsum("gcd,gdh->gch", ein, w1))
    y = jnp.einsum("gch,ghd->gcd", h, w2)                # (E/P, P·C, d)
    y = lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                       tiled=True)                       # (E, C, d)
    # Switch combine: scale by the selected expert's softmax probability so
    # the gating logits stay differentiable (a bare one-hot combine would
    # starve the router of gradient).
    sel_prob = jnp.sum(probs * onehot, axis=-1, keepdims=True)
    out = jnp.einsum("nec,ecd->nd", dispatch, y) * sel_prob
    dropped = 1.0 - jnp.sum(keep) / nloc
    # Switch aux load-balance loss: E · Σ_e f_e·P_e (f = dispatch fraction,
    # P = mean router prob); minimised by uniform routing.
    aux = num_experts * jnp.sum(jnp.mean(onehot, axis=0)
                                * jnp.mean(probs, axis=0))
    return out, dropped.reshape(1), aux.reshape(1)


def switch_moe_apply(x, gw, w1, w2, mesh, ep_axis="ep",
                     capacity_factor=1.25):
    """Capacity-dispatch MoE over ``mesh[ep_axis]``: returns
    ``(out, drop_frac_per_device, aux_loss_per_device)``.  Tokens are
    sharded over the ep axis for dispatch (N must divide by the axis
    size); expert weights arrive sharded on their leading expert dim."""
    num_experts = w1.shape[0]
    ep = mesh.shape[ep_axis]
    if x.shape[0] % ep:
        raise ValueError("token count %d not divisible by ep=%d"
                         % (x.shape[0], ep))
    if num_experts % ep:
        raise ValueError("num_experts %d not divisible by ep=%d"
                         % (num_experts, ep))
    nloc = x.shape[0] // ep
    cap = max(1, int(math.ceil(capacity_factor * nloc / num_experts)))
    fn = shard_map(
        functools.partial(_switch_body, axis=ep_axis,
                          num_experts=num_experts, cap=cap),
        mesh=mesh,
        in_specs=(P(ep_axis), P(), P(ep_axis), P(ep_axis)),
        out_specs=(P(ep_axis), P(ep_axis), P(ep_axis)),
        check_vma=False)
    return fn(x, gw, w1, w2)


_NORM_EPS = 1e-6       # beside the chosen scores' sum, as LFM2 and DeepSeek


def _route(x, gw, bias, *, top_k, router, norm_topk, scaling):
    """``(scores, chosen, weights)`` of the tokens ``x`` (N, d): the scores
    (N, E) in float32, the ``top_k`` experts chosen a token (N, k) by score
    plus ``bias`` (a buffer, not differentiated), and the weights their
    outputs are summed with (N, k): the scores at the chosen experts,
    without the bias, over their sum where ``norm_topk``, times
    ``scaling``.  The gradient reaches the gate through the weights."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x, gw, preferred_element_type=jnp.float32)
        scores = (jax.nn.sigmoid(logits) if router == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        choose = scores if bias is None else scores + lax.stop_gradient(
            bias.astype(jnp.float32))
        _, chosen = lax.top_k(lax.stop_gradient(choose), top_k)
        # the scores at the chosen experts, by a mask and not a gather: the
        # gather's transpose is a scatter of N·k scalars
        picked = jax.nn.one_hot(chosen, scores.shape[-1], dtype=scores.dtype)
        weights = (scores[:, None, :] * picked).sum(-1)
        if norm_topk:
            weights = weights / (weights.sum(-1, keepdims=True) + _NORM_EPS)
        return scores, chosen, weights * scaling


# ---------------------------------------------------------------------------
# the grouped matrix product: rows sorted by group, one weight matrix a group
# ---------------------------------------------------------------------------
# On a TPU, for bf16 / f16 rows, JAX's own Pallas grouped matmul (megablox):
# it visits the row tiles that lie in a group and no others, so its time
# follows the assignments held and not the buffer, and its calls keep the
# ``op_name`` path they were staged under (XLA's expansion of
# ``lax.ragged_dot`` on the TPU multiplies every row of the buffer and names
# its calls ``ragged-dot-none``, scope and phase lost).  Off the TPU, and
# for float32 rows (the eager check at ``highest`` precision, which
# ``lax.ragged_dot`` honours), ``lax.ragged_dot``.

def _row_tile(rows):
    """256 rows a tile where they divide the buffer, else 128; None where
    neither does (the kernel wants whole row tiles).  A group of a
    thousand rows wastes least in tiles of 256: on the v5e at the
    benchmark's shapes (PERF.md, PR 26) 256 read 0.49-0.65 ms a product
    where 512 read 0.59-0.63 and 128 0.50-0.98."""
    return next((t for t in (256, 128) if rows % t == 0), None)


def _dividing(n):
    """The largest multiple of 128 up to 1024 that divides ``n``, else 128."""
    return next((t for t in range(1024, 0, -128) if n % t == 0), 128)


def _tile(n, fits=None):
    """The tile of a weight dimension that a grouped product *writes* (the
    columns of ``gmm``'s result, both dimensions of ``tgmm``'s): the largest
    multiple of 128 up to 1024 that divides ``n``.  Where that is under 512
    for an ``n`` of 512 or more: all of ``n`` where it is whole lanes and
    ``fits(n)`` says the kernel's blocks still fit the VMEM, else the
    multiple of 128 in 512 .. 1024 whose last, ragged tile overhangs ``n``
    least (the larger of two that overhang alike; megablox computes the
    overhang and drops it).  Why no tile under 512: megablox's grids put
    the column tile outermost, so every operand indexed by the row tile is
    fetched again for each column tile.  Kimi-VL's 1408 = 11 x 128, which
    no multiple of 128 in between divides, ran in eleven tiles of 128 until
    PR 42 and six of a layer's nine products read their rows eleven times,
    bound by the HBM at twice their MXU time: 0.91 ms a ``gmm`` at the
    cell's shapes and 1.09-1.25 a ``tgmm``, for 0.49 and 0.67-0.72 in two
    tiles of 768 (1536 columns computed, the rows read twice) and 0.45 and
    0.58-0.64 whole (v5e, PERF.md section 6, PR 42).  1792 takes 896
    (LFM2), 896 itself and 2304 768 (Mellum2), 1024 itself (Trinity-Mini),
    every 2048 1024: the tiles they always had."""
    tile = _dividing(n)
    if n < 512 or tile >= 512:
        return tile
    if fits is not None and n % 128 == 0 and fits(n):
        return n
    return min(range(1024, 511, -128), key=lambda t: -n % t)


def _whole(k):
    """The tile of the dimension a ``gmm`` *contracts*: all of it where it
    is at most 2048 and whole lanes (Kimi-VL's 1408 among them), so that a
    row tile's product is finished in one grid step; else a tile that
    divides it (``_dividing``: Mellum2's 2304 in three of 768), so that
    the kernel masks no ragged tile inside the accumulation."""
    return k if k <= 2048 and k % 128 == 0 else _dividing(k)


# What a Mosaic kernel may hold in VMEM on the v5e (the compiler's scoped
# limit), and what one of megablox's holds by this file's reckoning: its
# operands' and its result's blocks twice, for the pipeline, the float32
# accumulator, and in ``tgmm`` the float32 copies of both operand blocks
# that the kernel masks.  Held against the TPU's compiler at Kimi-VL's
# shapes (PR 42): ``gmm`` at (256, 2048, 1408) in bf16 reckons 15.75 MiB and
# compiles and runs, at (256, 2048, 1536) 17.0 and is refused; ``tgmm`` at
# (256, 1024, 1408) 15.75 with a bf16 result and runs, 21.25 with a float32
# one, (256, 1024, 1152) float32 17.75 and (256, 1024, 1664) bf16 18.25,
# all three refused.
_VMEM_BYTES = 16 << 20


def _block_bytes(product, tiles, itemsize, out_itemsize, parts=1):
    """The bytes of VMEM that the kernel ``product`` holds at ``tiles``;
    ``tgmm`` over the row buffers of ``parts`` parts (``tgmm_parts``) holds
    every part's operand blocks twice and masks one part's at a time."""
    rows, tk, tn = tiles
    if product == "tgmm":
        return ((2 * itemsize * parts + 4) * rows * (tk + tn)
                + (2 * out_itemsize + 4) * tk * tn)
    return (2 * itemsize * (rows * tk + tk * tn)
            + (2 * out_itemsize + 4) * rows * tn)


def _tiling(product, rows, k, n, itemsize, out_itemsize):
    """The tiles (row, k, n), in megablox's order, of one of the three
    kernels of a grouped product over ``rhs`` (E, k, n): ``"gmm"``, rows
    (A, k) to (A, n), contracts k; ``"gmm_t"``, the rows' cotangent (A, n)
    to (A, k), contracts n; ``"tgmm"``, the weights' cotangent (E, k, n),
    contracts the rows a row tile at a time.  From the shapes and the
    operands' and the result's ``itemsize`` alone; the gauges
    ``graft_moe_product_tile`` keep what was staged last."""
    rows = _row_tile(rows)
    first, second = (n, k) if product == "gmm_t" else (k, n)

    def fits(tk, tn):
        return _block_bytes(product, (rows, tk, tn), itemsize,
                            out_itemsize) <= _VMEM_BYTES
    # ``tgmm`` writes both: each takes its tile beside the other's
    tk = (_tile(first, lambda t: fits(t, _tile(second)))
          if product == "tgmm" else _whole(first))
    tn = _tile(second, lambda t: fits(tk, t))
    _metrics.moe_product_tile(product, (first, second), (tk, tn))
    return rows, tk, tn


def _kernel_or_ragged(operands, kernel, ragged):
    """``kernel`` where the call runs on a TPU in a 16-bit float type over
    a multiple of 128 rows, else ``ragged``: by the operands' devices, or
    for tracers by the platform the enclosing program is lowered for."""
    lhs = operands[0]
    if lhs.dtype == jnp.float32 or _row_tile(lhs.shape[0]) is None:
        return ragged(*operands)
    if any(isinstance(x, jax.core.Tracer) for x in operands):
        return lax.platform_dependent(*operands, tpu=kernel, default=ragged)
    if all(d.platform == "tpu" for d in lhs.devices()):
        return kernel(*operands)
    return ragged(*operands)


def _ragged(lhs, rhs, group_sizes):
    return lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                          preferred_element_type=lhs.dtype)


def _megablox():
    """The module ``megablox/gmm.py`` (``gmm``, ``tgmm``); the package's
    attribute of that name is the function its ``__init__`` exports."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


# The kernel's branch of ``platform_dependent`` puts ``branch_0_fun`` on the
# name stack; the scope is set again inside it, so that a reader of the
# ops' ``op_name`` paths finds the kernels under the scope they belong to.
_SCOPE = "moe_experts"


def _gmm(lhs, rhs, group_sizes):
    megablox = _megablox()
    k, n = rhs.shape[1:]
    size = lhs.dtype.itemsize
    with jax.named_scope(_SCOPE):
        return megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes,
                            lhs.dtype,
                            _tiling("gmm", lhs.shape[0], k, n, size, size))


def _ragged_bwd(lhs, rhs, group_sizes, g):
    # in ``rhs``'s dtype, the rows widened to it where it is the wider: the
    # weights' cotangent leaves as it was accumulated (``grouped_dot``)
    def product(a, b):
        return lax.ragged_dot(a.astype(b.dtype), b, group_sizes,
                              preferred_element_type=b.dtype)
    _, pullback = jax.vjp(product, lhs, rhs)
    return pullback(g.astype(rhs.dtype))


def _gmm_t(lhs, rhs, group_sizes, g):
    """The rows' cotangent, ``g`` times the groups' ``rhs`` transposed."""
    k, n = rhs.shape[1:]
    size = lhs.dtype.itemsize
    return _megablox().gmm(g, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
                           _tiling("gmm_t", lhs.shape[0], k, n, size, size),
                           transpose_rhs=True)


def _gmm_bwd(lhs, rhs, group_sizes, g):
    k, n = rhs.shape[1:]
    _metrics.moe_weight_grad_trace("tgmm", 1)
    with jax.named_scope(_SCOPE):
        d_lhs = _gmm_t(lhs, rhs, group_sizes, g)
        d_rhs = _megablox().tgmm(
            lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
            _tiling("tgmm", lhs.shape[0], k, n, lhs.dtype.itemsize,
                    rhs.dtype.itemsize))
    return d_lhs, d_rhs


@jax.custom_vjp
def grouped_dot(lhs, rhs, group_sizes):
    """``lhs`` (A, k), its rows sorted by group, times ``rhs[g]`` (k, n) for
    the rows of group g: rows [0, sum(group_sizes)) lie in groups, in
    order; what is left in the rows behind them is unspecified (the kernel
    does not visit them), so a caller masks them.  f32 accumulation, the
    result in lhs's dtype.  ``rhs`` may be wider than the rows (float32
    beside bf16 rows): the products take it in the rows' dtype and its
    cotangent leaves in its own, so that a caller who multiplies one
    ``rhs`` with several ``lhs`` gets the sum of their cotangents as it was
    accumulated, rounded by whoever narrowed ``rhs``, once."""
    return _kernel_or_ragged((lhs, rhs, group_sizes), _gmm, _ragged)


def _grouped_dot_fwd(lhs, rhs, group_sizes):
    return grouped_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_dot_bwd(res, g):
    d_lhs, d_rhs = _kernel_or_ragged((*res, g), _gmm_bwd, _ragged_bwd)
    return d_lhs, d_rhs, None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


# The same product over the row buffers of several parts of the tokens
# (module docstring, "the tokens in parts"), with a rule that spans the
# parts: forward and the rows' cotangents are a part's own kernels, as
# ``grouped_dot``'s; the weights' cotangent is one product over all parts'
# rows, accumulated in float32 and written once (``tgmm_parts``), where
# ``grouped_dot`` a part would write a whole float32 result a part and leave
# the p results to be read again and added.

def _of_parts(operands):
    """``(*lhs, *g, *group_sizes, rhs)`` as three tuples and ``rhs``."""
    *parted, rhs = operands
    parts = len(parted) // 3
    return tuple(tuple(parted[i * parts:(i + 1) * parts])
                 for i in range(3)) + (rhs,)


def _ragged_parts_bwd(*operands):
    lhs, gs, group_sizes, rhs = _of_parts(operands)
    wide = rhs.astype(jnp.float32)
    d_lhs, d_rhs = zip(*(_ragged_bwd(rows, wide, sizes, g)
                         for rows, g, sizes in zip(lhs, gs, group_sizes)))
    return d_lhs, functools.reduce(operator.add, d_rhs).astype(rhs.dtype)


def _gmm_parts_bwd(*operands):
    from .tgmm_parts import tgmm_parts
    lhs, gs, group_sizes, rhs = _of_parts(operands)
    k, n = rhs.shape[1:]
    sizes = lhs[0].dtype.itemsize, rhs.dtype.itemsize
    _metrics.moe_weight_grad_trace("tgmm_parts", len(lhs))
    with jax.named_scope(_SCOPE):
        d_lhs = tuple(_gmm_t(rows, rhs, of_part, g)
                      for rows, g, of_part in zip(lhs, gs, group_sizes))
        # the tiles of one part's ``tgmm``; the VMEM that one gets and the
        # other parts' operand blocks on top
        tiles = _tiling("tgmm", lhs[0].shape[0], k, n, *sizes)
        d_rhs = tgmm_parts(
            lhs, gs, group_sizes, rhs.dtype, tiles,
            vmem_bytes=_VMEM_BYTES + _block_bytes("tgmm", tiles, *sizes,
                                                  parts=len(lhs))
            - _block_bytes("tgmm", tiles, *sizes))
    return d_lhs, d_rhs


@jax.custom_vjp
def parted_dot(lhs, rhs, group_sizes):
    """``grouped_dot(lhs[j], rhs, group_sizes[j])`` for the row buffers of p
    parts, a tuple in and a tuple out.  The cotangent of ``rhs`` is the
    float32 sum over every part's rows, rounded once to ``rhs``'s dtype."""
    return tuple(_kernel_or_ragged((rows, rhs, sizes), _gmm, _ragged)
                 for rows, sizes in zip(lhs, group_sizes))


def _parted_dot_fwd(lhs, rhs, group_sizes):
    return parted_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _parted_dot_bwd(res, gs):
    lhs, rhs, group_sizes = res
    d_lhs, d_rhs = _kernel_or_ragged((*lhs, *gs, *group_sizes, rhs),
                                     _gmm_parts_bwd, _ragged_parts_bwd)
    return d_lhs, d_rhs, (None,) * len(group_sizes)


parted_dot.defvjp(_parted_dot_fwd, _parted_dot_bwd)


def _experts(xs, w1, w3, w2, group_sizes, in_group):
    """Rows ``xs`` (A, d), sorted by expert, through the experts whose
    stacked weights are given; ``xs``, ``group_sizes`` and ``in_group`` are
    tuples, an entry for each part of the tokens, and so is the result: the
    parts go through each product side by side, so that a product's rule
    sees all of them (``parted_dot``).  ``in_group`` (A, 1) marks the rows
    that lie in a group; the hidden products' rows behind them are set to
    zero at once: they are whatever the kernel's buffer held, a NaN among
    it, and zero times that is no zero in the products of the backward pass.
    The last product's rows behind the groups stay as the kernel left them:
    the caller reads none of them (``_to_tokens``).  Products accumulate in
    float32 and leave in xs's dtype, as a Dense layer's do; the activation
    is taken in float32."""
    def products(rows, w):
        if len(rows) == 1:
            return (grouped_dot(rows[0], w, group_sizes[0]),)
        return parted_dot(tuple(rows), w, tuple(group_sizes))

    def dot(rows, w):
        return [jnp.where(held, y, jnp.zeros((), y.dtype)).astype(jnp.float32)
                for held, y in zip(in_group, products(rows, w))]
    h = dot(xs, w1)
    if w3 is None:
        h = [jax.nn.relu(a) for a in h]
    else:
        h = [jax.nn.silu(a) for a in h]
        h = [a * b for a, b in zip(h, dot(xs, w3))]
    return products([a.astype(rows.dtype) for a, rows in zip(h, xs)], w2)


# Rows move between the tokens' order and the buffer's by two gathers that
# are each other's transpose: ``order`` (A,) names the assignment, choice x N
# + token, that each row of the buffer holds, and ``slot`` (A,) the row that
# holds each assignment, or A for an assignment that lies in no group.  That
# one reads zeros, so what no kernel wrote behind the groups (unspecified, a
# NaN among it) is never read and needs no mask: a pass over the whole
# buffer less for every array that crosses.  A gather both ways, where the
# transpose of a plain gather is a scatter-add that the TPU's compiler sorts
# and serialises.
#
# Choice-major, because of which dimension a view of the assignments splits:
# (A, d) seen as (k, N, d) splits the leading dimension and is the same
# bytes for every k (N a multiple of the sublane tile), while (N, k, d)
# splits the sublanes, and where k is no whole tile of them (top-4, top-6)
# the TPU's compiler writes the view out as a relayout, three passes over
# the buffer a layer and one of them in float32.  The weighted sum over a
# token's choices has its own rule (``_to_tokens``) for the same reason: its
# transpose writes the rows' cotangent a choice slab at a time, so the
# token cotangent's broadcast over the choices is never an array.

def _zero_filled(rows, slot):
    """``rows[slot]``, zeros where ``slot`` is ``len(rows)``."""
    return rows.at[slot].get(mode="fill", fill_value=0)


def _slabs(rows, top_k):
    """The assignments' rows (A, d) as ``top_k`` slabs (N, d), a choice
    each."""
    return rows.reshape(top_k, -1, rows.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_buffer(x, order, slot, top_k):
    """Each token's row of ``x`` (N, d) at the buffer rows that hold its
    assignments, (A, d).  The cotangent of a token is the float32 sum over
    its ``top_k`` assignments' rows, those in no group counting zero."""
    return x[order % x.shape[0]]


def _to_buffer_fwd(x, order, slot, top_k):
    return _to_buffer(x, order, slot, top_k), slot


def _to_buffer_bwd(top_k, slot, g):
    g = _slabs(_zero_filled(g, slot), top_k)
    return g.astype(jnp.float32).sum(axis=0).astype(g.dtype), None, None


_to_buffer.defvjp(_to_buffer_fwd, _to_buffer_bwd)


@jax.custom_vjp
def _to_tokens(ys, weights, slot, order):
    """The buffer's rows ``ys`` (A, d) back in the order of the assignments,
    zeros for an assignment in no group, and summed over each token's
    choices with ``weights`` (k, N): (N, d) in float32, the products and
    the sums in float32, choice 0 first."""
    return _to_tokens_fwd(ys, weights, slot, order)[0]


def _filled(rows, slot, top_k):
    """``rows`` (A, d), gathered with ``slot`` clipped, by choice, (k, N, d),
    with the rows of assignments in no group set to zero: the fill of
    ``_zero_filled``, taken where the rows are read, so that what is kept
    between the passes is the gather's own result."""
    held = (slot < len(slot)).reshape(top_k, -1, 1)
    return jnp.where(held, _slabs(rows, top_k), jnp.zeros((), rows.dtype))


def _to_tokens_fwd(ys, weights, slot, order):
    rows = ys.at[slot].get(mode="clip")
    filled = _filled(rows, slot, len(weights)).astype(jnp.float32)
    out = (filled * weights[:, :, None]).sum(axis=0)
    return out, (rows, weights, slot, order)


def _to_tokens_bwd(res, g):
    rows, weights, slot, order = res
    # a slab a choice: written as one broadcast of ``g`` over the choices,
    # the compiler materialises that broadcast in float32
    d_rows = jnp.stack([(g * w[:, None]).astype(rows.dtype) for w in weights])
    filled = _filled(rows, slot, len(weights)).astype(jnp.float32)
    return (d_rows.reshape(rows.shape)[order], (g * filled).sum(axis=-1),
            None, None)


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


# The largest buffer of assignments a part of the tokens may have (module
# docstring, "the tokens in parts"): under the 128 MiB from which on the
# TPU's compiler gives an array no second memory space and XLA's gather
# moves a row in 34.5 ns for 8-12, and at or above Kimi-VL's 100,663,296 B,
# whose layer stays one part and its step the program it was.
_PART_BYTES = 96 << 20


def _parts(n, k, d, itemsize):
    """The fewest equal parts of ``n`` tokens, ``k`` choices each, whose
    buffer of (n / parts) x k rows of ``d`` elements has at most
    ``_PART_BYTES`` and still whole row tiles where the buffer of all
    tokens has them; where no split fits, the finest that keeps them."""
    tile = _row_tile(n * k) or 1
    whole = [p for p in range(1, n + 1)
             if n % p == 0 and (n // p * k) % tile == 0]
    return next((p for p in whole
                 if n // p * k * d * itemsize <= _PART_BYTES), whole[-1])


def grouped_moe_apply(x, chosen, weights, w1, w3, w2, first):
    """Dropless dispatch of ``x`` (N, d) to the experts ``first ..
    first + w1.shape[0] - 1``: ``chosen`` (N, k) and ``weights`` (N, k)
    from ``_route``.  Returns the weighted sum (N, d) over the held experts
    among each token's chosen ones, in x's dtype, and the assignments each
    held expert got, (count,) int32.

    The tokens go through in ``_parts`` equal parts, by their shapes alone:
    a token's output does not depend on which tokens share its buffer, so
    every part is the whole layer over its tokens, no row can overflow and
    nothing is bounded (``_grouped_parts``: the parts side by side, so that
    an expert weight's gradient is formed over all of them at once).  The
    experts' weights reach the parts in float32, so that their gradient
    leaves its kernel as it was accumulated and is rounded once, as one
    part's is."""
    n, k = chosen.shape
    row_bytes = x.shape[1] * x.dtype.itemsize
    parts = _parts(n, k, x.shape[1], x.dtype.itemsize)
    _metrics.moe_buffer_parts(parts, n // parts * k * row_bytes)
    if parts == 1:
        out, load = _grouped_part(x, chosen, weights, w1, w3, w2, first)
        return out.astype(x.dtype), load
    w1, w3, w2 = (w if w is None else w.astype(jnp.float32)
                  for w in (w1, w3, w2))
    outs, loads = _grouped_parts(
        *(jnp.split(a, parts) for a in (x, chosen, weights)), w1, w3, w2,
        first)
    # joined in float32 and narrowed once, as one part's sum is: with each
    # part narrowed before the join Trinity-Mini's whole step compiled to
    # 52 MB more of temporaries than with one part, so to 30 MB fewer
    return jnp.concatenate(outs).astype(x.dtype), sum(loads)


def _grouped_part(x, chosen, weights, w1, w3, w2, first):
    """``grouped_moe_apply`` over tokens that share one buffer, the
    weighted sum still in float32."""
    (out,), (load,) = _grouped_parts((x,), (chosen,), (weights,), w1, w3, w2,
                                     first)
    return out, load


def _dispatch(x, chosen, first, count):
    """The tokens ``x`` of one buffer at its rows: ``(xs, group_sizes,
    in_group, slot, order)``.

    The A = N·k assignments, numbered choice × N + token, are sorted by
    held expert, those of absent experts behind them; both ways the rows
    move by a gather over a permutation of A (the sort's, and its inverse),
    so neither direction of the gradient adds rows serially."""
    n, k = chosen.shape
    local = chosen.T.reshape(-1) - first                      # (A,)
    held = (local >= 0) & (local < count)
    # absent experts sort behind every held one, as group ``count``
    key = jnp.where(held, local, count).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
        jnp.int32)
    back = jnp.argsort(order)                     # the inverse permutation
    in_groups = group_sizes.sum()
    in_group = (jnp.arange(n * k) < in_groups)[:, None]
    slot = jnp.where(back < in_groups, back, n * k)
    return _to_buffer(x, order, slot, k), group_sizes, in_group, slot, order


def _grouped_parts(x, chosen, weights, w1, w3, w2, first):
    """The dropless layer over the parts of the tokens, a buffer a part
    (``x``, ``chosen`` and ``weights`` hold an entry a part): the weighted
    sums (N / p, d), still in float32, and the parts' ``group_sizes``.  The
    parts go through side by side: every part's dispatch, then the experts'
    products over all parts, then every part's combine."""
    with jax.named_scope("moe_dispatch"):
        xs, group_sizes, in_group, slot, order = zip(*(
            _dispatch(*of_part, first, w1.shape[0])
            for of_part in zip(x, chosen)))
    with jax.named_scope(_SCOPE):
        ys = _experts(xs, w1, w3, w2, group_sizes, in_group)
    with jax.named_scope("moe_combine"):
        # back to (choice, token) order, then the weighted sum over a
        # token's choices: the weights meet the rows where both lie in the
        # router's order, so no scalar is gathered
        outs = [_to_tokens(*of_part) for of_part in zip(
            ys, (w.T for w in weights), slot, order)]
    return outs, group_sizes


class ExpertParallelMoE(HybridBlock):
    """Top-k MoE feed-forward block, (N, d) → (N, d), experts stacked and
    sharded over the mesh axis ``ep_axis``.

    Parameters live stacked: gate (d, num_experts), expert weights
    (held, d, h) and (held, h, d), with ``gated`` a second (held, d, h);
    with ``selection_bias`` a buffer ``expert_bias`` (num_experts,) that no
    gradient reaches.  ``ep_axis`` is annotated on the expert weights;
    DataParallelTrainer places them accordingly.

    hidden_size : the experts' width h.
    num_experts : the router's outputs E.
    top_k : experts chosen a token.
    dispatch : ``"dense"``, ``"capacity"`` or ``"grouped"`` (module docstring).
    capacity_factor : slots an expert, ``capacity`` only.
    experts_held : ``(first, count)``, the experts whose weights this layer
        has (``grouped`` only; None: all).  The router still scores all E.
    router, selection_bias, norm_topk, scaling : the routing (module
        docstring).  ``selection_bias`` is True (a buffer of zeros) or the
        buffer's initializer.  ``norm_topk=None`` renormalises where top_k > 1: one
        renormalised expert would weigh 1.0 and starve the router of
        gradient.
    gated : SwiGLU experts.
    in_units : the tokens' width d; 0 (the default) leaves it to the first
        input.
    shared_hidden_size : None, or the width of a shared expert: a child
        ``GatedMLP`` (``shared_experts_``) that every token passes, added
        to the routed sum unweighted; replicated under an ``ep`` mesh and
        outside ``experts_held``.  Needs ``in_units``.  Counted by
        ``graft_moe_shared_traces_total``.
    shared_gate : a scalar gate on the shared expert (Qwen3-Next's
        ``shared_expert_gate``): a parameter ``shared_gate_weight``
        (units,) whose dot product with the token, through a sigmoid,
        scales the shared expert's output, ``sigmoid(w_s . x) *
        Shared(x)``, under the scope ``moe_shared``.  Needs
        ``shared_hidden_size``.  False (the default): no such parameter and
        the program the layer staged before.

    The defaults are the layer as it was (softmax, ReLU experts, dense);
    the chip benchmark's ``lfm2moe_fused_s8192`` runs ``grouped`` with
    ``experts_held=(0, 8)`` of 32, top-4, sigmoid scores, a selection bias,
    renormalised, gated; ``mellum2_fused_s8192`` top-8 of 64 by softmax;
    ``kimivl_mla_fused_1row`` top-6 of 64 by sigmoid, ``scaling`` 2.446,
    with ``shared_hidden_size`` 2816.
    """

    def __init__(self, hidden_size, num_experts, top_k=1, ep_axis="ep",
                 dispatch="dense", capacity_factor=1.25, experts_held=None,
                 router="softmax", selection_bias=False, norm_topk=None,
                 scaling=1.0, gated=False, in_units=0,
                 shared_hidden_size=None, shared_gate=False, prefix=None,
                 params=None, **kwargs):
        super().__init__(prefix=prefix, params=params, **kwargs)
        self._hidden = hidden_size
        self._num_experts = num_experts
        self._top_k = int(top_k)
        self._ep_axis = ep_axis
        if dispatch not in ("dense", "capacity", "grouped"):
            raise ValueError("dispatch must be 'dense', 'capacity' or "
                             "'grouped', got %r" % (dispatch,))
        if router not in ("softmax", "sigmoid"):
            raise ValueError("router must be 'softmax' or 'sigmoid', got %r"
                             % (router,))
        plain = (router == "softmax" and not selection_bias and not gated
                 and norm_topk is None and scaling == 1.0)
        if dispatch == "capacity" and (self._top_k != 1 or not plain):
            raise ValueError("capacity dispatch implements top-1 Switch "
                             "routing over ReLU experts; use "
                             "dispatch='grouped' or 'dense' for top_k > 1 "
                             "and the other routings")
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count > 0 and first + count <= num_experts):
            raise ValueError("experts_held %r is not a run of the %d experts"
                             % (experts_held, num_experts))
        if count != num_experts and dispatch != "grouped":
            raise ValueError("only dispatch='grouped' computes a share of "
                             "the experts (experts_held)")
        self.num_experts, self.experts_held = num_experts, (first, count)
        self._dispatch = dispatch
        self._capacity_factor = float(capacity_factor)
        self._routing = dict(
            top_k=self._top_k, router=router, scaling=float(scaling),
            norm_topk=self._top_k > 1 if norm_topk is None else norm_topk)
        self.last_drop_fraction = None  # updated on eager capacity calls
        self.last_expert_load = self.last_chosen = None   # eager grouped
        self._last_aux = None           # Switch load-balance loss, lazy
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(in_units, num_experts),
                allow_deferred_init=True)
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(count, in_units, hidden_size),
                allow_deferred_init=True)
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(count, hidden_size, in_units),
                allow_deferred_init=True)
            self.expert_w3 = self.params.get(
                "expert_w3", shape=(count, in_units, hidden_size),
                allow_deferred_init=True) if gated else None
            # True: zeros, as a checkpoint's loader expects to overwrite;
            # else the buffer's initializer
            self.expert_bias = self.params.get(
                "expert_bias", shape=(num_experts,), grad_req="null",
                init="zeros" if selection_bias is True else selection_bias
            ) if selection_bias else None
            self.shared_experts = self.shared_gate_weight = None
            if shared_gate and shared_hidden_size is None:
                raise ValueError("shared_gate gates the shared expert: it "
                                 "needs shared_hidden_size")
            if shared_hidden_size is not None:
                if not in_units:
                    raise ValueError("a shared expert is built with its "
                                     "widths: shared_hidden_size needs "
                                     "in_units")
                from ..gluon.nn.basic_layers import GatedMLP
                self.shared_experts = GatedMLP(in_units, shared_hidden_size,
                                               prefix="shared_experts_")
                if shared_gate:
                    self.shared_gate_weight = self.params.get(
                        "shared_gate_weight", shape=(in_units,))
        # shard the expert dimension over "ep": each device owns E/ep
        # experts' weights and their compute.  ``ep_axis=None``: the layer
        # runs on a mesh without that axis (one chip's share, held whole)
        for w in (self.expert_w1, self.expert_w2, self.expert_w3):
            if w is not None and ep_axis is not None:
                w.sharding = (ep_axis, None, None)

    def _pre_infer(self, x):
        """Layer-local deferred-shape fill from the live input."""
        d = int(x.shape[-1])
        if self.gate_weight.shape[0] == 0:
            count = self.expert_w1.shape[0]
            self.gate_weight.shape = (d, self._num_experts)
            self.expert_w1.shape = (count, d, self._hidden)
            self.expert_w2.shape = (count, self._hidden, d)
            if self.expert_w3 is not None:
                self.expert_w3.shape = (count, d, self._hidden)

    def hybrid_forward(self, F, x, gate_weight=None, expert_w1=None,
                       expert_w2=None, expert_w3=None, expert_bias=None,
                       shared_gate_weight=None):
        """x: (N, d) → (N, d).  Top-k routing with a score-weighted
        combine; the expert products carry the sharded E dimension."""
        xv, gw, w1, w2, w3, bias, ws = (
            v._read() if isinstance(v, NDArray) else v
            for v in (x, gate_weight, expert_w1, expert_w2, expert_w3,
                      expert_bias, shared_gate_weight))

        if self._dispatch == "capacity":
            out = self._capacity_forward(xv, gw, w1, w2)
        else:
            out = self._routed(xv, gw, w1, w2, w3, bias)
        if self.shared_experts is not None:
            _metrics.moe_shared_trace()
            with jax.named_scope("moe_shared"):
                shared = self.shared_experts(x)
                shared = (shared._read() if isinstance(shared, NDArray)
                          else shared)
                if ws is not None:
                    gate = jax.nn.sigmoid(jnp.dot(
                        xv, ws, preferred_element_type=jnp.float32))
                    shared = shared * gate[:, None].astype(shared.dtype)
            out = out + shared
        return NDArray(out) if isinstance(x, NDArray) else out

    def _routed(self, xv, gw, w1, w2, w3, bias):
        """The routed sum of ``dense`` and ``grouped``."""
        scores, chosen, weights = _route(xv, gw, bias, **self._routing)
        _metrics.moe_dispatch_trace(self._dispatch)
        if self._dispatch == "grouped":
            out, load = grouped_moe_apply(xv, chosen, weights, w1, w3, w2,
                                          self.experts_held[0])
            self._store_load(load, chosen)
        else:
            # combine[n, e]: the weight of expert e in token n's sum.  At
            # top-1 the raw selected score (Switch); with every expert
            # chosen the scores themselves
            combine = jnp.zeros_like(scores).at[
                jnp.arange(scores.shape[0])[:, None], chosen].add(weights)
            # per-expert FFN, expert dim sharded:
            # h[e] = act(x @ W1[e]) (⊙ x @ W3[e]); y[e] = h[e] @ W2[e]
            h = jnp.einsum("nd,edh->neh", xv, w1)
            h = (jax.nn.relu(h) if w3 is None
                 else jax.nn.silu(h) * jnp.einsum("nd,edh->neh", xv, w3))
            y = jnp.einsum("neh,ehd->ned", h, w2)
            out = jnp.einsum("ne,ned->nd", combine.astype(xv.dtype), y)
        self._store_aux(chosen, scores)
        return out

    @property
    def last_aux_loss(self):
        """Switch load-balance loss E·Σ f_e·P_e from the last eager call
        (materialised lazily — reading it may sync with the device)."""
        v = self._last_aux
        return None if v is None else float(v)

    @last_aux_loss.setter
    def last_aux_loss(self, v):
        self._last_aux = v

    def _store_aux(self, chosen, probs):
        """Stash the load-balance loss on eager calls without forcing a
        device->host sync on the forward path.  Dispatch fraction uses the
        first choice (GShard convention) so the stat stays meaningful even
        for soft routing, where every expert is chosen."""
        if isinstance(probs, jax.core.Tracer):
            return
        frac = jnp.mean(jax.nn.one_hot(chosen[:, 0], self._num_experts,
                                       dtype=probs.dtype), axis=0)
        self._last_aux = self._num_experts * jnp.sum(
            frac * jnp.mean(probs, axis=0))

    def _store_load(self, load, chosen):
        """On eager ``grouped`` calls: the experts chosen a token
        (``last_chosen``, (N, k)) and the assignments each held expert got
        (``last_expert_load``), both device arrays, and the counters
        ``graft_moe_assignments_total{held}`` and gauge
        ``graft_moe_expert_load_max_over_mean``, which read the load back.
        A traced step must not sync: its load and the assignments made go
        out with the step's results, ``moe_held_rows`` (count,) and
        ``moe_assignments``."""
        if step_counter("moe_held_rows", load, layer=self.name):
            step_counter("moe_assignments", chosen.size, layer=self.name)
            return
        self.last_chosen, self.last_expert_load = chosen, load
        _metrics.moe_assignments(np.asarray(load), int(chosen.size))

    def _capacity_forward(self, xv, gw, w1, w2):
        """Switch all-to-all dispatch over the scoped mesh's ep axis.
        Eager calls place operands on the mesh, run, and gather the output
        home (storing ``last_drop_fraction``); inside an enclosing jit the
        caller's shardings flow through and the rows dropped go out with a
        compiled step's results (``moe_dropped_rows``)."""
        from .mesh import current_mesh, dispatch_on_mesh, gather_home
        mesh = current_mesh(required=True)
        if self._ep_axis not in mesh.axis_names:
            raise ValueError("mesh %s has no axis %r for capacity dispatch"
                             % (mesh.axis_names, self._ep_axis))
        ep = self._ep_axis
        (out, drops, aux), eager = dispatch_on_mesh(
            lambda a, b, c, d: switch_moe_apply(a, b, c, d, mesh, ep,
                                                self._capacity_factor),
            mesh, (P(ep), P(), P(ep), P(ep)), xv, gw, w1, w2)
        # a device's share of the tokens times the share it dropped
        dropped = drops.sum() * (xv.shape[0] // mesh.shape[ep])
        if not step_counter("moe_dropped_rows", dropped, layer=self.name):
            # concrete eager call; under the eager tape's vjp trace drops
            # is a tracer and the stats stay at their last value
            self.last_drop_fraction = float(np.mean(jax.device_get(drops)))
            self.last_aux_loss = float(np.mean(jax.device_get(aux)))
        return gather_home(out, mesh) if eager else out
