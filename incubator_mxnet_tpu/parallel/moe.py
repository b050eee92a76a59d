"""Mixture-of-Experts with expert parallelism.

A new capability mandated by SURVEY §2.4 (the reference, a 2018
framework, has no EP row to port — "EP via sharded gather/scatter —
these are *new capabilities*"): a switch-style MoE feed-forward block
whose stacked expert weights shard over the mesh "ep" axis.

Two dispatch modes behind one module interface:

* ``dispatch="dense"`` (default) — einsums over the expert dimension,
  ``combine[n,e] · (x[n,d] @ W[e,d,h])``, with ``e`` sharded.  GSPMD
  partitions the contraction and inserts the psum merging expert outputs
  over ICI.  Simple and exact for any top_k, but compute ∝ num_experts.
* ``dispatch="capacity"`` — the classic Switch formulation: top-1
  routing with per-expert capacity slots; token activations travel to
  their expert's device via explicit ``lax.all_to_all`` and back, so
  compute is independent of num_experts and overflow tokens are dropped
  (``last_drop_fraction`` reports the rate on eager calls).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..gluon.block import HybridBlock
from ..ndarray import NDArray

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


class ExpertParallelMoE(HybridBlock):
    """Switch-style top-k MoE FFN (experts sharded over mesh axis "ep").

    Parameters live stacked: gate (d, E), expert weights (E, d, h) and
    (E, h, d).  Set ``ep_axis`` to the mesh axis name that shards the
    expert dimension (annotated on the parameters; DataParallelTrainer
    places them accordingly).
    """

    def __init__(self, hidden_size, num_experts, top_k=1, ep_axis="ep",
                 dispatch="dense", capacity_factor=1.25,
                 prefix=None, params=None, **kwargs):
        super().__init__(prefix=prefix, params=params, **kwargs)
        self._hidden = hidden_size
        self._num_experts = num_experts
        self._top_k = int(top_k)
        self._ep_axis = ep_axis
        if dispatch not in ("dense", "capacity"):
            raise ValueError("dispatch must be 'dense' or 'capacity', got %r"
                             % (dispatch,))
        if dispatch == "capacity" and self._top_k != 1:
            raise ValueError("capacity dispatch implements top-1 Switch "
                             "routing; use dispatch='dense' for top_k > 1")
        self._dispatch = dispatch
        self._capacity_factor = float(capacity_factor)
        self.last_drop_fraction = None  # updated on eager capacity calls
        self._last_aux = None           # Switch load-balance loss, lazy
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(0, num_experts),
                allow_deferred_init=True)
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, 0, hidden_size),
                allow_deferred_init=True)
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden_size, 0),
                allow_deferred_init=True)
        # shard the expert dimension over "ep": each device owns E/ep
        # experts' weights and their compute
        self.expert_w1.sharding = (ep_axis, None, None)
        self.expert_w2.sharding = (ep_axis, None, None)

    def _pre_infer(self, x):
        """Layer-local deferred-shape fill from the live input."""
        d = int(x.shape[-1])
        if self.gate_weight.shape[0] == 0:
            self.gate_weight.shape = (d, self._num_experts)
            self.expert_w1.shape = (self._num_experts, d, self._hidden)
            self.expert_w2.shape = (self._num_experts, self._hidden, d)

    def hybrid_forward(self, F, x, gate_weight=None, expert_w1=None,
                       expert_w2=None):
        """x: (N, d) → (N, d).  Top-k gating with probability-weighted
        combine; the expert einsums carry the sharded E dimension."""
        xv = x._read() if isinstance(x, NDArray) else x
        gw = gate_weight._read() if isinstance(gate_weight, NDArray) \
            else gate_weight
        w1 = expert_w1._read() if isinstance(expert_w1, NDArray) else expert_w1
        w2 = expert_w2._read() if isinstance(expert_w2, NDArray) else expert_w2

        if self._dispatch == "capacity":
            out = self._capacity_forward(xv, gw, w1, w2)
            return NDArray(out) if isinstance(x, NDArray) else out

        logits = xv @ gw                               # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        if self._top_k == 1:
            # Switch combine: raw selected probability (renormalising a
            # single expert would collapse to 1.0 and starve the router
            # of gradient)
            onehot = jax.nn.one_hot(jnp.argmax(probs, axis=-1),
                                    self._num_experts, dtype=xv.dtype)
            combine = probs * onehot
        elif self._top_k < self._num_experts:
            top_vals, _ = jax.lax.top_k(probs, self._top_k)
            thresh = top_vals[..., -1:]
            mask = probs >= thresh
            gated = jnp.where(mask, probs, 0.0)
            # renormalize over the selected experts (top-k combine)
            combine = gated / jnp.maximum(
                gated.sum(-1, keepdims=True), 1e-9)
        else:
            combine = probs
        self._store_aux(combine, probs)
        # per-expert FFN, expert dim sharded: h[e] = relu(x @ W1[e]) @ W2[e]
        h = jax.nn.relu(jnp.einsum("nd,edh->neh", xv, w1))
        y = jnp.einsum("neh,ehd->ned", h, w2)
        out = jnp.einsum("ne,ned->nd", combine, y)
        return NDArray(out) if isinstance(x, NDArray) else out

    @property
    def last_aux_loss(self):
        """Switch load-balance loss E·Σ f_e·P_e from the last eager call
        (materialised lazily — reading it may sync with the device)."""
        v = self._last_aux
        return None if v is None else float(v)

    @last_aux_loss.setter
    def last_aux_loss(self, v):
        self._last_aux = v

    def _store_aux(self, combine, probs):
        """Stash the load-balance loss on eager calls without forcing a
        device->host sync on the forward path.  Dispatch fraction uses the
        top-1 choice (GShard convention) so the stat stays meaningful even
        for soft routing, where every combine entry is nonzero."""
        if isinstance(probs, jax.core.Tracer):
            return
        top = jnp.argmax(probs, axis=-1)
        frac = jnp.mean(jax.nn.one_hot(top, self._num_experts,
                                       dtype=probs.dtype), axis=0)
        self._last_aux = self._num_experts * jnp.sum(
            frac * jnp.mean(probs, axis=0))

    def _capacity_forward(self, xv, gw, w1, w2):
        """Switch all-to-all dispatch over the scoped mesh's ep axis.
        Eager calls place operands on the mesh, run, and gather the output
        home (storing ``last_drop_fraction``); inside an enclosing jit the
        caller's shardings flow through and stats stay on device."""
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
        if eager:
            if not isinstance(drops, jax.core.Tracer):
                # concrete eager call; under the eager tape's vjp trace
                # drops is a tracer — stats stay at their last value
                self.last_drop_fraction = float(
                    np.mean(jax.device_get(drops)))
                self.last_aux_loss = float(np.mean(jax.device_get(aux)))
            return gather_home(out, mesh)
        return out
