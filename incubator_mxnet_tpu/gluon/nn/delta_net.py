"""Linear-attention layers on the Gluon surface whose state is a matrix a
head: ``GatedDeltaNet`` (Yang et al., arXiv:2412.06464, as Qwen3-Next's
``Qwen3NextGatedDeltaNet`` stacks it) over ``ops/delta_rule.py``'s chunked
gated delta rule.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer
from ... import random_state
from ..block import HybridBlock
from .basic_layers import Dense

__all__ = ["GatedDeltaNet"]


@initializer.register
class DeltaDecayLog(initializer.Initializer):
    """``A_log = log A`` with A uniform in (0, ``high``), as the published
    layer draws it, kept at or above ``low`` so that no head starts at
    ``log 0``: a head's decay is ``exp(-A softplus(a + dt_bias))`` a
    token."""

    def __init__(self, low=1e-4, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def _init_weight(self, _, arr):
        a = jax.random.uniform(random_state.next_key(), arr.shape,
                               jnp.float32, 0.0, self.high)
        self._fill(arr, jnp.log(jnp.maximum(a, self.low)))


class GatedDeltaNet(HybridBlock):
    """The Gated DeltaNet mixer on (B, S, units) -> (B, S, units), no bias::

        [q | k | v | z] = W_qkvz u     Hk Dk, Hk Dk, Hv Dv, Hv Dv wide
        [b | a] = W_ba u               Hv each
        [q | k | v] = silu(conv([q | k | v]))   depthwise, causal, ``conv`` taps
        q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(Dk),  k_h likewise without
            the last factor; value head h reads key head h // (Hv / Hk)
        beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)   float32
        S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t u_t^T;  o_t = S_t^T q_t      (``ops/delta_rule.py``)
        o_h = gamma * o_h / sqrt(mean o_h^2 + epsilon) * silu(z_h)
        out = W_out [o_1 ... o_Hv]     Hv Dv -> units

    The columns of ``W_qkvz`` and ``W_ba`` lie as blocks (all of q, then k,
    v, z; b, then a) where the published checkpoint interleaves them by key
    head: a permutation of columns.  ``A_log`` is ``log U(0, 16)`` kept away
    from ``log 0`` and ``dt_bias`` one, as published; the taps are uniform
    in +- conv^-1/2, a depthwise ``Conv1d``'s own default; the head norm's
    gains start at one; the projections take the model's initializer.

    The projections are staged under the scope ``gdn_proj``, the convolution
    and its SiLU under ``short_conv``, the L2 norms, gates, decays and the
    rule under ``gdn_scan`` (on a TPU, at heads whole lane tiles wide, its
    walk over the chunks' states is the Mosaic kernels ``delta_rule_pallas``
    and ``delta_rule_bwd``, whose calls lie under the same scope), the gated
    head norm under ``gdn_norm``; the chip benchmark's
    ``qwen3next_gdn_fused_1row`` runs it at 2048 -> 16 key and 32 value
    heads of 128.
    """

    def __init__(self, units, num_k_heads, num_v_heads, head_k_dim,
                 head_v_dim, conv=4, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_v_heads % num_k_heads:
            raise ValueError("num_v_heads (%d) must be a multiple of "
                             "num_k_heads (%d)" % (num_v_heads, num_k_heads))
        self._hk, self._hv = int(num_k_heads), int(num_v_heads)
        self._dk, self._dv = int(head_k_dim), int(head_v_dim)
        self._conv, self._epsilon = int(conv), float(epsilon)
        key, value = self._hk * self._dk, self._hv * self._dv

        def dense(out, inp, prefix):
            return Dense(out, flatten=False, use_bias=False, in_units=inp,
                         prefix=prefix)

        with self.name_scope():
            self.in_qkvz = dense(2 * key + 2 * value, units, "in_qkvz_")
            self.in_ba = dense(2 * self._hv, units, "in_ba_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(2 * key + value, self._conv),
                init=initializer.Uniform(self._conv ** -0.5))
            self.A_log = self.params.get("A_log", shape=(self._hv,),
                                         init=DeltaDecayLog())
            self.dt_bias = self.params.get("dt_bias", shape=(self._hv,),
                                           init=initializer.One())
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(self._dv,), init=initializer.One())
            self.out_proj = dense(units, value, "out_")

    def hybrid_forward(self, F, u, conv_weight, A_log, dt_bias, norm_gamma):
        B, S = u.shape[0], u.shape[1]
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        key, value = hk * dk, hv * dv

        def part(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        def heads(t, n):                # (B, S, n * D) -> (B, n, S, D)
            return F.transpose(F.reshape(t, shape=(B, S, n, -1)),
                               axes=(0, 2, 1, 3))

        def unit(t):                    # a head's rows at length one
            t32 = F.cast(t, dtype="float32")
            return t32 * F.rsqrt(F.sum(t32 * t32, axis=-1, keepdims=True)
                                 + 1e-6)

        with jax.named_scope("gdn_proj"):
            qkvz, ba = self.in_qkvz(u), self.in_ba(u)
        with jax.named_scope("short_conv"):
            qkv = F.Activation(F._contrib_CausalConv1D(
                part(qkvz, 0, 2 * key + value), conv_weight,
                kernel=self._conv), act_type="silu")
        with jax.named_scope("gdn_scan"):
            q = heads(part(qkv, 0, key), hk)
            k = heads(part(qkv, key, 2 * key), hk)
            v = heads(part(qkv, 2 * key, 2 * key + value), hv)
            q = F.cast(unit(q) * (1.0 / math.sqrt(dk)), dtype=v.dtype)
            k = F.cast(unit(k), dtype=v.dtype)
            ba = F.transpose(F.cast(ba, dtype="float32"), axes=(0, 2, 1))
            beta = F.sigmoid(F.slice_axis(ba, axis=1, begin=0, end=hv))
            rate = F.reshape(F.exp(F.cast(A_log, dtype="float32")),
                             shape=(1, hv, 1))
            bias = F.reshape(F.cast(dt_bias, dtype="float32"),
                             shape=(1, hv, 1))
            g = -rate * F.Activation(
                F.slice_axis(ba, axis=1, begin=hv, end=2 * hv) + bias,
                act_type="softrelu")
            o = F._contrib_GatedDeltaRule(q, k, v, g, beta)
        with jax.named_scope("gdn_norm"):
            o = F.transpose(o, axes=(0, 2, 1, 3))           # (B, S, Hv, Dv)
            z = F.reshape(part(qkvz, 2 * key + value, 2 * key + 2 * value),
                          shape=(B, S, hv, dv))
            o = F.RMSNorm(o, norm_gamma, eps=self._epsilon) * F.Activation(
                z, act_type="silu")
        with jax.named_scope("gdn_proj"):
            return self.out_proj(F.reshape(o, shape=(B, S, value)))
