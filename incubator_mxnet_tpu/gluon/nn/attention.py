"""Attention layers on the Gluon surface.

The reference (2018-era) has no attention layer; SURVEY §2.4/§5.7 mandate
sequence/context parallelism as a first-class capability of the TPU
rebuild.  ``MultiHeadAttention`` is the user-facing block: plain flash
attention on one device, and with ``seq_axis="sp"`` the SAME layer runs
exact ring attention over the scoped mesh's sequence axis — long-context
training without leaving the Gluon API (the gap called out by the round-2
review: ring attention existed only as a raw jax function).
"""
from __future__ import annotations

import jax
import numpy as np

from ..block import HybridBlock
from ... import initializer
from ...telemetry import metrics as _metrics
from .basic_layers import Dense, RMSNorm

__all__ = ["MultiHeadAttention", "LatentAttention", "DifferentialAttention"]


class MultiHeadAttention(HybridBlock):
    """Multi-head self/cross attention (B, S, E) -> (B, S, E).

    Parameters
    ----------
    units : int
        Total embedding width E (split across heads).
    num_heads : int
        Head count H; head dim D = E // H unless ``head_dim`` says otherwise.
    causal : bool
        Autoregressive masking.
    seq_axis : str or None
        None — flash attention on the local device
        (ops/attention.py Pallas kernel / lax fallback).
        An axis name (e.g. ``"sp"``) — exact ring attention with the
        sequence sharded over that axis of the mesh in the enclosing
        ``parallel.use_mesh`` scope; K/V shards rotate over ICI
        (parallel/ring_attention.py).  Same math, same layer, chosen per
        deployment.
    use_bias : bool
        Bias on the q/k/v/out projections.
    fused_qkv : bool
        Project q/k/v with ONE (E, 3E) matmul instead of three (E, E)
        ones (self-attention only).  On the MXU a single wide matmul
        sustains far higher throughput than three narrow ones (measured
        ~197 vs ~80 TFLOP/s at E=4096 on v5e), and XLA does not fuse the
        three projections itself.
    num_kv_heads : int or None
        Grouped-query attention (Ainslie et al., arXiv:2305.13245): K and V
        have this many heads of the same head dim D, and each serves
        ``num_heads // num_kv_heads`` query heads.  The K/V heads are
        repeated before the kernel, so the kernel's work is that of
        ``num_heads`` full heads; what is saved is the K/V projections.
        None (the default): as many as ``num_heads``.
    qk_norm : bool
        RMSNorm over the head dim of q and of k, each with its own gains
        (``q_norm_gamma``, ``k_norm_gamma``), before the positions.
    rotary_base : float or None
        Rotary positions on q and k over the whole head (rotate-half), with
        this base.  None: no positions inside the layer (the caller adds
        learned ones, as the OPT cells do).
    qk_norm_epsilon : float
        The epsilon of ``qk_norm``'s RMSNorm.
    head_dim : int or None
        The width D of a head where it is not ``units // num_heads``: q is
        then ``num_heads * head_dim`` wide, k and v ``num_kv_heads *
        head_dim``, ``proj_out`` maps the ``num_heads * head_dim`` channels
        back to ``units`` and the scores are scaled by ``1 / sqrt(head_dim)``
        (Mellum2: 32 heads of 128 over a model width of 2304).
    window : int or None
        Sliding-window attention (needs ``causal``): a query sees its own
        key and the ``window - 1`` before it, as ``sliding_window`` counts
        in HF configs.  None: every key up to its own.  Not with
        ``seq_axis``: the ring has no window.
    rotary_scaling : mapping or None
        YaRN's parameters for the rotary positions (needs ``rotary_base``):
        ``factor``, ``original_max_position`` and optionally ``beta_fast``,
        ``beta_slow``, ``attention_factor``, as ``_contrib_RotaryEmbedding``
        takes them under ``scaling``.  None: the plain frequencies.
    gate : bool
        Gated attention (Arcee's AFMoE, Trinity-Mini): a fifth projection
        ``gate_`` of the layer's input, ``units -> num_heads * head_dim``
        without bias, whose sigmoid multiplies the heads' outputs, laid out
        (B, S, H * D), before ``proj_out``: ``out = W_o (sigmoid(W_g x) *
        [o_1 ... o_H])``.  Self-attention only.  Staged under the scope
        ``attn_gate`` (projection, sigmoid and product) and counted by
        ``graft_attention_gate_traces_total``.  False (the default): no such
        parameter and the program the layer staged before.
    rotary_dim : int or None
        A partial rotary (needs ``rotary_base``): the positions turn the
        first ``rotary_dim`` channels of a head, as a head of that width
        would be turned, and the rest pass as they are
        (``_contrib_RotaryEmbedding``'s ``dim``; Qwen3-Next turns 64 of
        256).  None (the default): the whole head.

    With everything after ``fused_qkv`` left at its default the layer stages
    the program it staged before those arguments existed (the chip
    benchmark's ``opt6b7_fused_s2048`` and ``opt6b7_fused_adam`` run it so);
    ``lfm2moe_fused_s8192`` runs 32 query heads over 8 K/V heads of 64 with
    ``qk_norm`` and base 1e6; ``mellum2_fused_s8192`` 32 over 4 of 128 at
    width 2304, three layers with ``window=1024`` to one with YaRN;
    ``trinitymini_gated_fused_1row`` 32 over 4 of 128 with ``gate`` and
    ``qk_norm``, four layers with ``window=2048`` and base 1e4 to one with
    no positions at all (``rotary_base=None``);
    ``qwen3next_gdn_fused_1row`` 16 over 2 of 256 with ``gate``,
    ``qk_norm`` and ``rotary_dim=64`` at base 1e7;
    ``kimivl_mla_fused_1row`` runs ``LatentAttention`` below, through the
    same kernel call.
    """

    def __init__(self, units, num_heads, causal=False, seq_axis=None,
                 use_bias=True, fused_qkv=False, weight_initializer=None,
                 num_kv_heads=None, qk_norm=False, rotary_base=None,
                 qk_norm_epsilon=1e-5, head_dim=None, window=None,
                 rotary_scaling=None, gate=False, rotary_dim=None, **kwargs):
        super().__init__(**kwargs)
        if rotary_dim is not None and rotary_base is None:
            raise ValueError("rotary_dim counts the channels rotary "
                             "positions turn: it needs rotary_base")
        if head_dim is None and units % num_heads:
            raise ValueError("units (%d) must be divisible by num_heads (%d)"
                             % (units, num_heads))
        if window is not None and (not causal or seq_axis is not None):
            raise ValueError("window counts back from the query's own key: "
                             "it needs causal=True and seq_axis=None")
        if rotary_scaling is not None and rotary_base is None:
            raise ValueError("rotary_scaling scales rotary positions: it "
                             "needs rotary_base")
        num_kv_heads = num_heads if num_kv_heads is None else num_kv_heads
        if num_heads % num_kv_heads:
            raise ValueError("num_heads (%d) must be a multiple of "
                             "num_kv_heads (%d)" % (num_heads, num_kv_heads))
        if fused_qkv and num_kv_heads != num_heads:
            raise ValueError("fused_qkv projects three equal widths; "
                             "grouped K/V heads need fused_qkv=False")
        self._units = units
        self._num_heads = num_heads
        self._num_kv_heads = num_kv_heads
        self._rotary_base = rotary_base
        self._rotary_scaling = (None if rotary_scaling is None
                                else dict(rotary_scaling))
        self._rotary_dim = rotary_dim
        self._window = window
        self._head_dim = head_dim = (units // num_heads if head_dim is None
                                     else int(head_dim))
        q_units, kv_units = num_heads * head_dim, num_kv_heads * head_dim
        self._causal = bool(causal)
        self._seq_axis = seq_axis
        self._fused_qkv = bool(fused_qkv)
        with self.name_scope():
            if self._fused_qkv:
                self.proj_qkv = Dense(3 * q_units, flatten=False,
                                      use_bias=use_bias,
                                      weight_initializer=weight_initializer,
                                      prefix="qkv_")
            else:
                self.proj_q = Dense(q_units, flatten=False, use_bias=use_bias,
                                    weight_initializer=weight_initializer,
                                    prefix="q_")
                self.proj_k = Dense(kv_units, flatten=False,
                                    use_bias=use_bias,
                                    weight_initializer=weight_initializer,
                                    prefix="k_")
                self.proj_v = Dense(kv_units, flatten=False,
                                    use_bias=use_bias,
                                    weight_initializer=weight_initializer,
                                    prefix="v_")
            self.proj_out = Dense(units, flatten=False, use_bias=use_bias,
                                  weight_initializer=weight_initializer,
                                  prefix="out_")
            self.q_norm = self.k_norm = None
            if qk_norm:
                self.q_norm = RMSNorm(epsilon=qk_norm_epsilon,
                                      in_channels=head_dim, prefix="q_norm_")
                self.k_norm = RMSNorm(epsilon=qk_norm_epsilon,
                                      in_channels=head_dim, prefix="k_norm_")
            self.proj_gate = None
            if gate:
                self.proj_gate = Dense(q_units, flatten=False,
                                       use_bias=False,
                                       weight_initializer=weight_initializer,
                                       prefix="gate_")

    def _split_heads(self, F, x, B, S, heads=None, norm=None,
                     positions=False):
        """(B, S, E) -> (B, H, S, D); for q and k (``positions``) with the
        head's RMSNorm before and the rotary positions after the move."""
        x = F.reshape(x, shape=(B, S, heads or self._num_heads, -1))
        if norm is not None:
            x = norm(x)
        x = F.transpose(x, axes=(0, 2, 1, 3))
        if positions and self._rotary_base is not None:
            scaled = ({} if self._rotary_scaling is None
                      else {"scaling": self._rotary_scaling})
            if self._rotary_dim is not None:
                scaled["dim"] = self._rotary_dim
            x = F._contrib_RotaryEmbedding(x, base=self._rotary_base,
                                           **scaled)
        return x

    def hybrid_forward(self, F, query, key=None, value=None):
        if self._fused_qkv and (key is not None or value is not None):
            raise ValueError("fused_qkv supports self-attention only "
                             "(pass just the query)")
        if self.proj_gate is not None and (key is not None
                                           or value is not None):
            raise ValueError("the gate is of the layer's own input: "
                             "self-attention only (pass just the query)")
        key = query if key is None else key
        value = key if value is None else value
        B, S = query.shape[0], query.shape[1]
        Sk = key.shape[1]
        if self._fused_qkv:
            qkv = self.proj_qkv(query)                   # (B, S, 3E)
            E = self._num_heads * self._head_dim
            q = self._split_heads(
                F, F.slice_axis(qkv, axis=-1, begin=0, end=E), B, S,
                norm=self.q_norm, positions=True)
            k = self._split_heads(
                F, F.slice_axis(qkv, axis=-1, begin=E, end=2 * E), B, Sk,
                norm=self.k_norm, positions=True)
            v = self._split_heads(
                F, F.slice_axis(qkv, axis=-1, begin=2 * E, end=3 * E), B, Sk)
        else:
            kv = self._num_kv_heads
            q = self._split_heads(F, self.proj_q(query), B, S,
                                  norm=self.q_norm, positions=True)
            k = self._split_heads(F, self.proj_k(key), B, Sk, kv,
                                  norm=self.k_norm, positions=True)
            v = self._split_heads(F, self.proj_v(value), B, Sk, kv)
            if kv != self._num_heads:
                # each K/V head serves a run of consecutive query heads
                k, v = (F.repeat(t, repeats=self._num_heads // kv, axis=1)
                        for t in (k, v))
        scale = 1.0 / float(np.sqrt(self._head_dim))
        if self._seq_axis is None:
            windowed = ({} if self._window is None
                        else {"window": self._window})
            # the layer's kind for a trace's reader, inside the Block's name
            with jax.named_scope("attn_full" if self._window is None
                                 else "attn_window"):
                out = F._contrib_FlashAttention(
                    q, k, v, causal=self._causal, scale=scale, **windowed)
        else:
            out = F._contrib_RingAttention(q, k, v, seq_axis=self._seq_axis,
                                           causal=self._causal, scale=scale)
        out = F.transpose(out, axes=(0, 2, 1, 3))
        out = F.reshape(out, shape=(B, S,
                                    self._num_heads * self._head_dim))
        if self.proj_gate is not None:
            _metrics.attention_gate_trace()
            with jax.named_scope("attn_gate"):
                out = out * F.Activation(self.proj_gate(query),
                                         act_type="sigmoid")
        return self.proj_out(out)


class LatentAttention(HybridBlock):
    """Multi-head latent self-attention (DeepSeek-V2, arXiv:2405.04434;
    without a query latent, as Moonlight and Kimi-VL run it), causal,
    (B, S, E) -> (B, S, E), no bias.

    Keys and values come from one narrow latent a token and the positions
    from a rotary part beside the head's own channels::

        q = W_q x                      H heads of nope_dim + rope_dim
        [c ; k_pe] = W_kva x           kv_latent_dim + rope_dim: ONE rotary key
        [k_nope ; v] = W_kvb RMSNorm(c)   H heads of nope_dim + v_head_dim
        q_h = [q_nope_h ; rope(q_pe_h)],  k_h = [k_nope_h ; rope(k_pe)]
        o_h = softmax(q_h k_h^T / sqrt(nope_dim + rope_dim) + causal) v_h
        out = W_o [o_1 ; ... ; o_H]

    so a head's keys are ``nope_dim + rope_dim`` wide and its values
    ``v_head_dim`` (Kimi-VL: 192 over 128), the rotary key is shared by all
    heads, and positions touch ``rope_dim`` channels of a head only.

    Parameters
    ----------
    units : int
        The model width E.
    num_heads : int
        Heads H.
    kv_latent_dim : int
        Width of the K/V latent ``c`` (HF ``kv_lora_rank``).
    nope_dim, rope_dim : int
        A head's channels without positions and its rotary channels (HF
        ``qk_nope_head_dim``, ``qk_rope_head_dim``).
    v_head_dim : int
        A head's value width, which is its output's.
    rotary_base : float
        Base of the rotary positions, rotate-half over the ``rope_dim``
        channels (HF lays the pairs out adjacent: the same scores, with the
        rotary columns of ``W_q`` and ``W_kva`` permuted).
    latent_norm_epsilon : float
        Epsilon of the RMSNorm on the latent.
    rotary_scaling : mapping or None
        As ``MultiHeadAttention``'s: handed to ``_contrib_RotaryEmbedding``
        under ``scaling``.

    A class beside ``MultiHeadAttention`` and not arguments of it: the two
    share no projection (one down-projection, a norm and an up-projection
    stand where k and v had a Dense each), and every other argument of that
    layer (``seq_axis``, ``fused_qkv``, ``num_kv_heads``, ``qk_norm``,
    ``window``, cross attention) would have to be refused here.  What they
    share is the kernel call, ``F._contrib_FlashAttention(q, k, v)``, which
    takes v at a width of its own: wrapped in the scope ``attn_latent``,
    everything before it in ``attn_latent_proj``.  The rotary key is
    repeated to H heads before the kernel, as grouped K/V heads are.  The
    chip benchmark's ``kimivl_mla_fused_1row`` runs it at 16 heads, a latent
    of 512 and 128 + 64 over 128.
    """

    def __init__(self, units, num_heads, kv_latent_dim, nope_dim, rope_dim,
                 v_head_dim, rotary_base=10000.0, latent_norm_epsilon=1e-6,
                 rotary_scaling=None, **kwargs):
        super().__init__(**kwargs)
        if rope_dim % 2:
            raise ValueError("rotary positions need an even rope_dim, got %d"
                             % rope_dim)
        self._num_heads = num_heads
        self._latent, self._nope, self._rope = kv_latent_dim, nope_dim, rope_dim
        self._v_dim = v_head_dim
        self._rotary = {"base": rotary_base}
        if rotary_scaling is not None:
            self._rotary["scaling"] = dict(rotary_scaling)

        def dense(out, inp, prefix):
            return Dense(out, flatten=False, use_bias=False, in_units=inp,
                         prefix=prefix)

        with self.name_scope():
            self.proj_q = dense(num_heads * (nope_dim + rope_dim), units, "q_")
            self.proj_kv_a = dense(kv_latent_dim + rope_dim, units, "kv_a_")
            self.kv_norm = RMSNorm(epsilon=latent_norm_epsilon,
                                   in_channels=kv_latent_dim,
                                   prefix="kv_norm_")
            self.proj_kv_b = dense(num_heads * (nope_dim + v_head_dim),
                                   kv_latent_dim, "kv_b_")
            self.proj_out = dense(units, num_heads * v_head_dim, "out_")

    def _heads(self, F, x, B, S):
        """(B, S, H * D) -> (B, H, S, D)."""
        return F.transpose(
            F.reshape(x, shape=(B, S, self._num_heads, -1)),
            axes=(0, 2, 1, 3))

    def hybrid_forward(self, F, x):
        B, S = x.shape[0], x.shape[1]
        H, nope, rope = self._num_heads, self._nope, self._rope

        def part(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        def turned(t):
            return F._contrib_RotaryEmbedding(t, **self._rotary)

        with jax.named_scope("attn_latent_proj"):
            q = self._heads(F, self.proj_q(x), B, S)
            q = F.concat(part(q, 0, nope), turned(part(q, nope, nope + rope)),
                         dim=-1)
            down = self.proj_kv_a(x)                    # (B, S, latent + rope)
            latent = self.kv_norm(part(down, 0, self._latent))
            kv = self._heads(F, self.proj_kv_b(latent), B, S)
            # one rotary key a token, the same for every head
            k_pe = turned(F.reshape(part(down, self._latent,
                                         self._latent + rope),
                                    shape=(B, 1, S, rope)))
            k = F.concat(part(kv, 0, nope), F.repeat(k_pe, repeats=H, axis=1),
                         dim=-1)
            v = part(kv, nope, nope + self._v_dim)
        with jax.named_scope("attn_latent"):
            out = F._contrib_FlashAttention(
                q, k, v, causal=True,
                scale=1.0 / float(np.sqrt(nope + rope)))
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(B, S, H * self._v_dim))
        return self.proj_out(out)


class DifferentialAttention(HybridBlock):
    """Differential attention (Ye et al., arXiv:2410.05258, laid out as the
    Diff Transformer's own code and Phi-4-mini-flash's attention have it),
    causal, (B, S, E) -> (B, S, E), with biases::

        [q ; k ; v] = W_qkv x + b        E + 2 * num_kv_heads * D,  D = E / H
        q -> (H/2, 2, D): q1_i, q2_i     consecutive heads pair up
        k -> (G/2, 2, D): k1_j, k2_j     v -> (G/2, 2 D): a pair's two value
                                         heads side by side
        o_i = softmax(q1_i k1_j^T / sqrt(D) + M) v_j
              - lam * softmax(q2_i k2_j^T / sqrt(D) + M) v_j,   j = i // (H/G)
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
        lam_init = 0.8 - 0.6 exp(-0.3 depth)
        out = W_o [(1 - lam_init) RMSNorm_2D(o_i)]_i + b_o

    ``H = num_heads`` query heads over ``G = num_kv_heads`` K/V heads of D,
    i.e. H / 2 differential heads 2 D wide; four learned vectors of D and one
    norm gain of 2 D a layer; ``depth`` is the layer's index in the published
    model, which sets ``lam_init``.  ``M`` is causal, or with ``window`` the
    query's own key and the ``window - 1`` before it.

    On the kernels it is ONE call, ``F._contrib_FlashAttention(q, k, v)`` at
    keys of D over values of 2 D: the H query heads in their order
    (head 2 i + s is q1 for s = 0, q2 for s = 1), the values repeated to H
    heads, and the keys laid out so that query head 2 i + s meets key head
    2 j + s (the (G/2, 2) key heads broadcast over a pair's query groups,
    which a plain ``repeat`` is not: it would hand q2 the key k1).  The
    output (H, 2 D) read as (H/2, 2, 2 D) gives the two softmaxes' results.

    ``cross=True``: the layer projects q only (``W_q``, bias) and attends
    onto the ``(k, v)`` another layer made, handed in as ``kv``: k
    (B, G, S, D) and v (B, G/2, S, 2 D) as ``return_kv=True`` makes a layer
    return them beside its output.  No rotary positions.

    A class beside ``MultiHeadAttention`` and not arguments of it (as
    ``LatentAttention`` is): the pairing, the two value heads side by side,
    the four vectors, the norm after the kernel and the handed-over k, v
    would each be an argument that every other combination of that layer's
    arguments has to refuse.  What they share is the kernel call, here under
    the scope ``attn_diff`` (with ``attn_window`` / ``attn_full`` /
    ``attn_cross`` inside it); the subtraction, the norm and the factor are
    staged under ``attn_diff_combine``.  The chip benchmark's
    ``phi4flash_sambay_fused_1row`` runs it at 40 heads over 20 of 64, with
    ``window=512``, without, and as a cross layer.
    """

    def __init__(self, units, num_heads, num_kv_heads, depth, window=None,
                 cross=False, return_kv=False, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads or num_heads % 2 or num_kv_heads % 2 \
                or num_heads % num_kv_heads:
            raise ValueError(
                "differential heads pair up: units (%d) a multiple of an "
                "even num_heads (%d), itself a multiple of an even "
                "num_kv_heads (%d)" % (units, num_heads, num_kv_heads))
        if cross and (window is not None or return_kv):
            raise ValueError("a cross layer attends over the whole row of "
                             "another layer's k, v and has none to return")
        self._units, self._heads, self._kv_heads = units, num_heads, num_kv_heads
        self._dim = dim = units // num_heads
        self._window, self._cross, self._return_kv = window, cross, return_kv
        self._epsilon = epsilon
        self._lambda_init = 0.8 - 0.6 * float(np.exp(-0.3 * depth))
        with self.name_scope():
            if cross:
                self.proj_q = Dense(units, flatten=False, in_units=units,
                                    prefix="q_")
            else:
                self.proj_qkv = Dense(units + 2 * num_kv_heads * dim,
                                      flatten=False, in_units=units,
                                      prefix="qkv_")
            self.proj_out = Dense(units, flatten=False, in_units=units,
                                  prefix="out_")
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                setattr(self, name, self.params.get(
                    name, shape=(dim,), init=initializer.Normal(0.1)))
            self.subln = self.params.get("subln_gamma", shape=(2 * dim,),
                                         init=initializer.One())

    def _heads_of(self, F, t, heads):
        """(B, S, heads * D') -> (B, heads, S, D')."""
        B, S = t.shape[0], t.shape[1]
        return F.transpose(F.reshape(t, shape=(B, S, heads, -1)),
                           axes=(0, 2, 1, 3))

    def hybrid_forward(self, F, x, kv=None, lambda_q1=None, lambda_k1=None,
                       lambda_q2=None, lambda_k2=None, subln=None):
        B, S = x.shape[0], x.shape[1]
        H, G, D = self._heads, self._kv_heads, self._dim
        if self._cross:
            if kv is None:
                raise ValueError("a cross layer takes the (k, v) of the "
                                 "layer that made them")
            _metrics.shared_kv_read()
            q = self._heads_of(F, self.proj_q(x), H)
            k, v = kv
        else:
            qkv = self.proj_qkv(x)
            q = self._heads_of(F, F.slice_axis(
                qkv, axis=-1, begin=0, end=H * D), H)
            k = self._heads_of(F, F.slice_axis(
                qkv, axis=-1, begin=H * D, end=(H + G) * D), G)
            v = self._heads_of(F, F.slice_axis(
                qkv, axis=-1, begin=(H + G) * D, end=(H + 2 * G) * D), G // 2)
        Sk = k.shape[2]
        # query heads 2 i + s, i = fold * j .. fold * j + fold - 1, meet key
        # head 2 j + s: the pair (k1_j, k2_j) once for each of them
        fold = H // G
        keys = F.reshape(
            F.repeat(F.reshape(k, shape=(B, G // 2, 1, 2, Sk, D)),
                     repeats=fold, axis=2), shape=(B, H, Sk, D))
        values = F.repeat(v, repeats=2 * fold, axis=1)
        windowed = {} if self._window is None else {"window": self._window}
        kind = ("attn_cross" if self._cross else
                "attn_full" if self._window is None else "attn_window")
        with jax.named_scope("attn_diff"), jax.named_scope(kind):
            out = F._contrib_FlashAttention(
                q, keys, values, causal=True, scale=1.0 / float(np.sqrt(D)),
                **windowed)
        with jax.named_scope("attn_diff_combine"):
            lam = (F.exp(F.sum(lambda_q1 * lambda_k1))
                   - F.exp(F.sum(lambda_q2 * lambda_k2)) + self._lambda_init)
            both = F.reshape(out, shape=(B, H // 2, 2, S, 2 * D))
            first, second = (F.reshape(
                F.slice_axis(both, axis=2, begin=s, end=s + 1),
                shape=(B, H // 2, S, 2 * D)) for s in (0, 1))
            o = F.RMSNorm(first - lam * second, subln, eps=self._epsilon)
            o = o * (1.0 - self._lambda_init)
        o = F.reshape(F.transpose(o, axes=(0, 2, 1, 3)), shape=(B, S, H * D))
        o = self.proj_out(o)
        return (o, (k, v)) if self._return_kv else o
