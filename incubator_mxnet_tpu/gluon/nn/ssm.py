"""State-space layers on the Gluon surface: ``Mamba`` (Gu & Dao,
arXiv:2312.00752, the first version: a diagonal state of ``state`` numbers a
channel) over ``ops/ssm.py``'s selective scan, and the ``GatedMemoryUnit``
that reads a memory another layer produced (Ren et al., arXiv:2507.06607,
the SambaY decoder-hybrid-decoder).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer
from ... import random_state
from ..block import HybridBlock
from .basic_layers import Dense

__all__ = ["Mamba", "GatedMemoryUnit"]


@initializer.register
class MambaStateLog(initializer.Initializer):
    """``A_log[c, n] = log(n + 1)``: the decay ``A = -exp(A_log)`` of state
    n is -(n + 1) in every channel (Mamba's own, S4D-real)."""

    def _init_weight(self, _, arr):
        states = jnp.arange(1, arr.shape[1] + 1, dtype=jnp.float32)
        self._fill(arr, jnp.broadcast_to(jnp.log(states), arr.shape))


@initializer.register
class MambaStepBias(initializer.Initializer):
    """The bias of the step's projection: the inverse softplus of a step
    drawn log-uniformly in [low, high], so that ``softplus(bias)`` is that
    step (Mamba's own)."""

    def __init__(self, low=1e-3, high=1e-1):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def _init_weight(self, _, arr):
        u = jax.random.uniform(random_state.next_key(), arr.shape,
                               jnp.float32)
        step = jnp.exp(u * (math.log(self.high) - math.log(self.low))
                       + math.log(self.low))
        self._fill(arr, step + jnp.log(-jnp.expm1(-step)))


class Mamba(HybridBlock):
    """The Mamba-1 mixer on (B, S, units) -> (B, S, units)::

        [x ; z] = W_in u                       units -> 2 x inner, no bias
        x = silu(conv(x) + b_conv)             depthwise, causal, ``conv`` taps
        [d ; B_t ; C_t] = W_x x                inner -> dt_rank + 2 x state
        delta = softplus(W_dt d + b_dt)        dt_rank -> inner
        A = -exp(A_log)                        (inner, state)
        h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) B_t^T,   h_0 = 0
        y_t = h_t C_t + D x_t                  float32 (``ops/ssm.py``)
        out = W_out (y silu(z))                inner -> units, no bias

    ``inner = expand * units``; ``dt_rank`` defaults to ``ceil(units / 16)``.
    With ``return_memory`` the layer returns ``(out, y)``: ``y``, the scan's
    result with the skip and before the gate, is the memory a
    ``GatedMemoryUnit`` of a later layer reads.

    ``A_log``, ``D``, ``W_dt``, ``b_dt`` and the convolution carry Mamba's
    own initialisation (``log(n + 1)``; ones; uniform in +- dt_rank^-1/2;
    the inverse softplus of a step log-uniform in [1e-3, 1e-1]; taps and
    bias uniform in +- conv^-1/2); the projections take the model's.  The
    scan is staged under the scope ``ssm_scan`` and everything else under
    ``ssm_proj``; the chip benchmark's ``phi4flash_sambay_fused_1row`` runs
    it at 2560 -> 5120 channels of 16 states.
    """

    def __init__(self, units, state=16, conv=4, expand=2, dt_rank=None,
                 return_memory=False, **kwargs):
        super().__init__(**kwargs)
        self._inner = inner = int(expand) * units
        self._state, self._conv = int(state), int(conv)
        self._rank = rank = int(dt_rank or -(-units // 16))
        self._return_memory = bool(return_memory)

        def dense(out, inp, prefix, **kw):
            return Dense(out, flatten=False, in_units=inp, prefix=prefix, **kw)

        with self.name_scope():
            self.in_proj = dense(2 * inner, units, "in_", use_bias=False)
            # the taps and their bias as Mamba's own code leaves them: a
            # depthwise Conv1d's default, uniform in +- conv^-1/2.  At a
            # model's 0.02 the convolution would shrink its input 25-fold
            # and the scan's share of the layer's output with it
            taps = initializer.Uniform(self._conv ** -0.5)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(inner, self._conv), init=taps)
            self.conv_bias = self.params.get("conv_bias", shape=(inner,),
                                             init=taps)
            self.x_proj = dense(rank + 2 * self._state, inner, "x_",
                                use_bias=False)
            self.dt_proj = dense(
                inner, rank, "dt_",
                weight_initializer=initializer.Uniform(rank ** -0.5),
                bias_initializer=MambaStepBias())
            self.A_log = self.params.get("A_log", shape=(inner, self._state),
                                         init=MambaStateLog())
            self.D = self.params.get("D", shape=(inner,),
                                     init=initializer.One())
            self.out_proj = dense(units, inner, "out_", use_bias=False)

    def hybrid_forward(self, F, u, conv_weight, conv_bias, A_log, D):
        inner, rank, state = self._inner, self._rank, self._state

        def part(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        with jax.named_scope("ssm_proj"):
            xz = self.in_proj(u)
            x, z = part(xz, 0, inner), part(xz, inner, 2 * inner)
            x = F.Activation(
                F._contrib_CausalConv1D(x, conv_weight, kernel=self._conv)
                + conv_bias, act_type="silu")
            dbc = self.x_proj(x)
            delta = F.Activation(self.dt_proj(part(dbc, 0, rank)),
                                 act_type="softrelu")
            b = part(dbc, rank, rank + state)
            c = part(dbc, rank + state, rank + 2 * state)
            a = -F.exp(A_log)
        with jax.named_scope("ssm_scan"):
            y = F._contrib_SelectiveScan(x, delta, a, b, c, D)
        with jax.named_scope("ssm_proj"):
            out = self.out_proj(y * F.Activation(z, act_type="silu"))
        return (out, y) if self._return_memory else out


class GatedMemoryUnit(HybridBlock):
    """``W_out (m silu(W_in u))`` on (B, S, units) and a memory ``m``
    (B, S, inner) that another layer produced (a ``Mamba(return_memory=True)``
    hands on its scan's output): no convolution, no scan, no state of its
    own.  No bias.  Staged under the scope ``gmu``."""

    def __init__(self, units, inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = Dense(inner, flatten=False, use_bias=False,
                                 in_units=units, prefix="in_")
            self.out_proj = Dense(units, flatten=False, use_bias=False,
                                  in_units=inner, prefix="out_")

    def hybrid_forward(self, F, u, memory):
        with jax.named_scope("gmu"):
            gate = F.Activation(self.in_proj(u), act_type="silu")
            return self.out_proj(memory * gate)
