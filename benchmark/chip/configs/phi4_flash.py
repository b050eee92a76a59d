"""Phi-4-mini-flash-reasoning's decoder (Microsoft,
``microsoft/Phi-4-mini-flash-reasoning``; the SambaY decoder-hybrid-decoder
of Ren et al., arXiv:2507.06607, the layers as HF ``modeling_phi4flash.py``
has them) for the chip benchmark, as one chip's share of a deployment that
spreads the tied token table over eight chips by rows.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.LayerNorm``, ``Mamba``,
                 ``GatedMemoryUnit``, ``DifferentialAttention`` (window,
                 full, cross), ``GatedMLP``, a tied ``Embedding`` head.
                 Nothing is defined here but how they are stacked and what
                 one layer hands to a later one: the Mamba layer its scan's
                 output ``m``, the full-attention layer its ``(k, v)``.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published equations, sharing no code with the program:
                 the recurrence a loop over single steps with a (C, N)
                 float32 state, its adjoint written out step by step
                 (``_recurrence``); the convolution as shifted products; an
                 explicit (S, S) mask with the band written out; the two
                 softmaxes of a differential head computed apart and
                 subtracted, a few heads at a time.  With
                 ``cfg["scan_state_dtype"] = "bfloat16"`` the state and the
                 decay are held in bfloat16: the control of this
                 configuration's own (``control_state.py``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

Layer l: ``h = x + Mixer_l(LN(x))``, ``y = h + MLP(LN(h))``, LayerNorm with
gain and bias, ``MLP(x) = W_2 (silu(W_1 x) * W_3 x)``.  The mixer of a layer
is its entry in ``layer_types``: ``sliding_attention`` / ``full_attention``
differential attention, ``mamba`` (which hands on ``m``), ``gmu`` (``W_out
(m * silu(W_in u))``), ``cross_attention`` (differential attention that
projects q only onto the full layer's k, v).  One LayerNorm after the last
layer, then the token table transposed.  The equations of each mixer stand
at its function below; the sizes come from the JSON beside this file.
"""
import math

PREFIX = "phi4flash_"
HEADS_A_BLOCK = 4               # differential heads whose scores coexist
WINDOW, MAMBA, FULL, GMU, CROSS = KINDS = (
    "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention")


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def _inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def _checked(cfg):
    kinds, depth = cfg["layer_types"], cfg["published_layer_index"]
    if len(kinds) != cfg["num_hidden_layers"] or len(depth) != len(kinds) \
            or set(kinds) - set(KINDS):
        raise ValueError("layer_types and published_layer_index name each "
                         "of the num_hidden_layers layers, by %s"
                         % ", ".join(KINDS))
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("head_dim is hidden_size over num_attention_heads")
    for i, kind in enumerate(kinds):
        before = kinds[:i]
        if (kind == GMU and MAMBA not in before) or (
                kind == CROSS and FULL not in before):
            raise ValueError("layer %d (%s) reads what no earlier layer "
                             "hands on" % (i, kind))
    if (not cfg["tie_word_embeddings"] or cfg["mlp_bias"]
            or cfg["lm_head_bias"] or cfg["resid_pdrop"]
            or cfg["embd_pdrop"]):
        raise ValueError("a tied head without bias, no bias in the MLP and "
                         "no dropout, as Phi-4-mini-flash-reasoning has them")


def build(cfg):
    """The uninitialised Gluon model.  The prefix is fixed: parameter names
    are keys of the step's argument trees and so part of the compile-cache
    key (PERF.md, PR 21)."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn

    _checked(cfg)
    d, eps = cfg["hidden_size"], cfg["layer_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    last_of = {kind: max(i for i, k in enumerate(cfg["layer_types"])
                         if k == kind) for kind in set(cfg["layer_types"])}

    def mixer(kind, i, depth):
        if kind == MAMBA:
            return nn.Mamba(d, state=cfg["mamba_d_state"],
                            conv=cfg["mamba_d_conv"],
                            expand=cfg["mamba_expand"],
                            dt_rank=cfg["mamba_dt_rank"],
                            return_memory=i == last_of[MAMBA],
                            prefix="mixer_")
        if kind == GMU:
            return nn.GatedMemoryUnit(d, _inner(cfg), prefix="mixer_")
        return nn.DifferentialAttention(
            d, heads, kv_heads, depth,
            window=cfg["sliding_window"] if kind == WINDOW else None,
            cross=kind == CROSS,
            return_kv=kind == FULL and i == last_of[FULL],
            epsilon=cfg["layer_norm_eps"], prefix="mixer_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, kind, i, depth, **kwargs):
            super().__init__(**kwargs)
            self._kind = kind
            with self.name_scope():
                self.input_layernorm = nn.LayerNorm(
                    epsilon=eps, in_channels=d, prefix="input_layernorm_")
                self.mixer = mixer(kind, i, depth)
                self.post_attention_layernorm = nn.LayerNorm(
                    epsilon=eps, in_channels=d,
                    prefix="post_attention_layernorm_")
                self.mlp = nn.GatedMLP(d, cfg["intermediate_size"],
                                       prefix="mlp_")

        def hybrid_forward(self, F, x, memory, kv):
            u = self.input_layernorm(x)
            if self._kind == GMU:
                out = self.mixer(u, memory)
            elif self._kind == CROSS:
                out = self.mixer(u, kv)
            else:
                out = self.mixer(u)
                if isinstance(out, tuple):      # the layer that hands on
                    out, handed = out
                    if self._kind == MAMBA:
                        memory = handed
                    else:
                        kv = handed
            h = x + out
            return h + self.mlp(self.post_attention_layernorm(h)), memory, kv

    class Phi4FlashLM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(_vocab(cfg), d,
                                          prefix="embed_tokens_")
                self.layers = nn.HybridSequential(prefix="layers_")
                with self.layers.name_scope():
                    for i, (kind, depth) in enumerate(zip(
                            cfg["layer_types"],
                            cfg["published_layer_index"])):
                        self.layers.add(DecoderLayer(kind, i, depth,
                                                     prefix="%d_" % i))
                self.norm = nn.LayerNorm(epsilon=eps, in_channels=d,
                                         prefix="norm_")
                # tied: the head's weight IS the token table, (vocab, d)
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False,
                                     params=self.embed.params)

        def hybrid_forward(self, F, tokens):
            x, memory, kv = self.embed(tokens), None, None
            for layer in self.layers:
                x, memory, kv = layer(x, memory, kv)
            return self.head(self.norm(x))

    return Phi4FlashLM(prefix=PREFIX)


def initializer(cfg):
    import incubator_mxnet_tpu as mx
    return mx.init.Normal(cfg["init_std"])


def sample_shape(cfg, traffic):
    """Shape of one row of the batch; a row holds ``samples_per_row``
    samples (tokens)."""
    return (traffic["seq_len"],)


def samples_per_row(cfg, traffic):
    return traffic["seq_len"]


def check_rows(cfg, traffic):
    """Rows of the batch the Block's own forward is compared on, and the
    reference differentiates at a time."""
    return 1


# ---------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernels, no gluon
# ---------------------------------------------------------------------------

def _recomputed(fn):
    """``fn`` with nothing kept for its backward pass but its arguments: the
    backward pass runs ``fn`` again.  A memory policy, not mathematics.
    Written out as a differentiation rule instead of ``jax.checkpoint``
    because the control (``chipbench/first_steps.py::with_matmuls_as``)
    walks the gradient's jaxpr and does not know the remat primitive."""
    import jax

    @jax.custom_vjp
    def again(*args):
        return fn(*args)

    def forward(*args):
        return fn(*args), args

    def backward(args, g):
        # the barrier ties the second run to the gradient that asks for it:
        # without it the compiler merges it with the first and keeps all
        first, g = jax.lax.optimization_barrier((args[0], g))
        return jax.vjp(fn, first, *args[1:])[1](g)

    again.defvjp(forward, backward)
    return again


def _layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    import jax.numpy as jnp
    return jnp.logaddexp(x, 0.0)


def _gated_mlp(x, gate, up, down):
    """SwiGLU with (out, in) weights: down (silu(gate x) * up x)."""
    return (_silu(x @ gate.T) * (x @ up.T)) @ down.T


# -- the Mamba mixer ---------------------------------------------------------

def _causal_conv(x, weight, bias):
    """Depthwise, causal, (C, K) taps: out_t = sum_j w[:, j] x_{t - (K - 1)
    + j} + b (tap j meets the input K - 1 - j steps back), the steps before
    the first taken as zero.  K shifted products."""
    import jax.numpy as jnp
    taps = weight.shape[1]
    seq = x.shape[1]
    out = bias
    for j in range(taps):
        back = taps - 1 - j
        delayed = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        out = out + delayed * weight[:, j]
    return out


def _recurrence(held=None):
    """``y = scan(x, delta, A, B, C)`` without the skip: x, delta (b, S, C),
    A (C, N), B, C (b, S, N) ->  y_t[c] = sum_n h_t[c, n] C_t[n] with
    h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) B_t^T, h_0 = 0, one step at
    a time with a float32 state of C x N numbers a row, kept (b, N, C):
    channels last, because a TPU pads a last axis of 16 states to 128 lanes
    (the states of a row of 4096 are 1.34 GB so, 10.7 GB the other way).

    The loop is a ``lax.while_loop`` and its gradient is written out below
    (the adjoint recurrence, one step at a time from the last): the control
    (``first_steps.with_matmuls_as``) evaluates the gradient's jaxpr and
    takes a ``scan``'s body for a function to call once; a ``while`` it
    binds as it is.  ``tests/test_phi4flash_chip_bench.py`` holds this rule
    to ``jax.grad`` of the same recurrence as a ``lax.scan`` and to
    ``transformers``' ``MambaMixer.slow_forward`` under torch's autograd.

    ``held``: a dtype the state and the decay are rounded to at every step
    (the bfloat16 control); None keeps float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def keep(t):
        # not a pair of conversions: the TPU's compiler removes a float32 ->
        # bfloat16 -> float32 round trip as excess precision it may keep
        if held is None:
            return t
        kind = jnp.finfo(held)
        return lax.reduce_precision(t, kind.nexp, kind.nmant)

    def at(t, i):
        return lax.dynamic_index_in_dim(t, i, axis=1, keepdims=False)

    def states(x, delta, A, B):
        """h_t for every t: (b, S, N, C)."""
        batch, seq, _ = x.shape
        At = A.T

        def step(carry):
            i, h, out = carry
            d = at(delta, i)
            h = keep(keep(jnp.exp(d[:, None, :] * At)) * h
                     + (d * at(x, i))[:, None, :] * at(B, i)[:, :, None])
            return i + 1, h, lax.dynamic_update_index_in_dim(out, h, i, 1)

        h0 = jnp.zeros((batch,) + At.shape, jnp.float32)
        out = jnp.zeros((batch, seq) + At.shape, jnp.float32)
        return lax.while_loop(lambda c: c[0] < seq, step, (0, h0, out))[2]

    @jax.custom_vjp
    def scan(x, delta, A, B, C):
        return (states(x, delta, A, B) * C[..., None]).sum(2)

    def forward(x, delta, A, B, C):
        return scan(x, delta, A, B, C), (x, delta, A, B, C)

    def backward(res, g):
        x, delta, A, B, C = res
        seq = x.shape[1]
        hs, At = states(x, delta, A, B), A.T
        d_c = (g[:, :, None, :] * hs).sum(-1)

        def step(carry):
            i, lam, d_x, d_delta, d_at, d_b = carry
            t = seq - 1 - i
            d, xt, bt = at(delta, t), at(x, t), at(B, t)
            before = jnp.where(t > 0, at(hs, jnp.maximum(t - 1, 0)), 0.0)
            decay = keep(jnp.exp(d[:, None, :] * At))
            lam = at(g, t)[:, None, :] * at(C, t)[:, :, None] + lam
            via_b = (lam * bt[:, :, None]).sum(1)               # (b, C)
            through = lam * before * decay
            put = lax.dynamic_update_index_in_dim
            return (i + 1, decay * lam,
                    put(d_x, d * via_b, t, 1),
                    put(d_delta, (through * At).sum(1) + xt * via_b, t, 1),
                    d_at + (through * d[:, None, :]).sum(0),
                    put(d_b, (lam * (d * xt)[:, None, :]).sum(-1), t, 1))

        zero = jnp.zeros_like
        carry = (0, zero(hs[:, 0]), zero(x), zero(delta), zero(At), zero(B))
        _, _, d_x, d_delta, d_at, d_b = lax.while_loop(
            lambda c: c[0] < seq, step, carry)
        return d_x, d_delta, d_at.T, d_b, d_c

    scan.defvjp(forward, backward)
    return scan


def _mamba(cfg, p, u):
    """``[x ; z] = W_in u``; ``x = silu(conv(x) + b)``; ``[d ; B ; C] = W_x
    x``; ``delta = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; the
    recurrence; ``W_out ((y + D x) silu(z))``.  -> (out, memory): what the
    layer hands to the gated memory units is the scan's result with the skip
    ``D x``, before the gate."""
    import jax.numpy as jnp
    inner, state, rank = (_inner(cfg), cfg["mamba_d_state"],
                          cfg["mamba_dt_rank"])
    xz = u @ p["mixer_in_weight"].T
    x, z = xz[..., :inner], xz[..., inner:]
    x = _silu(_causal_conv(x, p["mixer_conv_weight"], p["mixer_conv_bias"]))
    dbc = x @ p["mixer_x_weight"].T
    delta = _softplus(dbc[..., :rank] @ p["mixer_dt_weight"].T
                      + p["mixer_dt_bias"])
    held = cfg.get("scan_state_dtype")
    scanned = _recurrence(held and jnp.dtype(held))(
        x, delta, -jnp.exp(p["mixer_A_log"]), dbc[..., rank:rank + state],
        dbc[..., rank + state:rank + 2 * state])
    memory = scanned + p["mixer_D"] * x
    return (memory * _silu(z)) @ p["mixer_out_weight"].T, memory


def _gmu(p, u, memory):
    """``W_out (m * silu(W_in u))``."""
    return (memory * _silu(u @ p["mixer_in_weight"].T)) @ p[
        "mixer_out_weight"].T


# -- differential attention --------------------------------------------------

def _lambda_init(depth):
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _lambda(p, depth):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``."""
    import jax.numpy as jnp
    return (jnp.exp((p["mixer_lambda_q1"] * p["mixer_lambda_k1"]).sum())
            - jnp.exp((p["mixer_lambda_q2"] * p["mixer_lambda_k2"]).sum())
            + _lambda_init(depth))


def _head_norm(o, gain, eps):
    """RMSNorm over a differential head's 2 D channels, one gain a layer."""
    import jax.numpy as jnp
    return o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * gain


def _project_q(cfg, p, u, kind):
    """q (b, S, H/2, 2, D): consecutive heads pair up.  A cross layer has a
    projection of its own for it, the others the first rows of ``W_qkv``.
    No rotary positions, here or on k: the recurrence carries the order."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if kind == CROSS:
        q = u @ p["mixer_q_weight"].T + p["mixer_q_bias"]
    else:
        q = u @ p["mixer_qkv_weight"][:d].T + p["mixer_qkv_bias"][:d]
    return q.reshape(u.shape[:2] + (heads // 2, 2, d // heads))


def _project_kv(cfg, p, u):
    """k (b, S, G/2, 2, D) and v (b, S, G/2, 2 D) of a layer with ``W_qkv``:
    consecutive K/V heads pair up, a pair's two value heads side by side."""
    d, kv, = cfg["hidden_size"], cfg["num_key_value_heads"]
    dim = d // cfg["num_attention_heads"]
    batch, seq, _ = u.shape
    both = (u @ p["mixer_qkv_weight"][d:].T + p["mixer_qkv_bias"][d:])
    k = both[..., :kv * dim].reshape(batch, seq, kv // 2, 2, dim)
    v = both[..., kv * dim:].reshape(batch, seq, kv // 2, 2 * dim)
    return k, v


def _attention(cfg, p, u, kind, depth, handed=None):
    """Differential attention (module docstring of
    ``gluon.nn.DifferentialAttention`` has the equations; this side computes
    the two softmaxes of a head apart, over an explicit mask, and subtracts).
    ``handed``: the (k, v) a cross layer attends onto, which projects none
    of its own.  -> (out, (k, v))."""
    import jax
    import jax.numpy as jnp
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dim = d // heads
    batch, seq, _ = u.shape
    q = _project_q(cfg, p, u, kind)
    k, v = handed if kind == CROSS else _project_kv(cfg, p, u)
    i = jnp.arange(seq)
    ahead = i[:, None] - i[None, :]                 # query - key
    seen = ahead >= 0
    if kind == WINDOW:
        seen &= ahead < cfg["sliding_window"]
    lam = _lambda(p, depth)
    per = heads // kv                   # differential heads a K/V pair serves
    scale = 1.0 / math.sqrt(dim)

    def softmax_of(qs, ks, vs):         # (b, S, n, D) twice, (b, S, n, 2 D)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qs, ks) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vs)

    @_recomputed
    def block(qb, kb, vb, lam):         # n differential heads
        first = softmax_of(qb[:, :, :, 0], kb[:, :, :, 0], vb)
        second = softmax_of(qb[:, :, :, 1], kb[:, :, :, 1], vb)
        return first - lam * second

    n = min(HEADS_A_BLOCK, heads // 2)
    pair = [i // per for i in range(heads // 2)]    # head i's K/V pair
    o = jnp.concatenate([
        block(q[:, :, h:h + n], k[:, :, jnp.array(pair[h:h + n])],
              v[:, :, jnp.array(pair[h:h + n])], lam)
        for h in range(0, heads // 2, n)], axis=2)  # (b, S, H/2, 2D)
    o = _head_norm(o, p["mixer_subln_gamma"], cfg["layer_norm_eps"])
    o = o * (1.0 - _lambda_init(depth))
    out = o.reshape(batch, seq, d) @ p["mixer_out_weight"].T + p[
        "mixer_out_bias"]
    return out, (k, v)


def reference(cfg, params, tokens):
    """Logits (B, S, rows of the vocabulary held) for int tokens (B, S).

    ``params`` maps the suffix of a parameter's name after the model prefix
    to a float32 array; a projection's weight is (out, in), y = x W^T.
    Call it under ``jax.default_matmul_precision("highest")``: on a TPU a
    float32 matmul otherwise runs in bf16 passes.  Each layer is recomputed
    in the backward pass (``_recomputed``)."""
    import jax.numpy as jnp
    eps = cfg["layer_norm_eps"]
    kinds, depths = cfg["layer_types"], cfg["published_layer_index"]

    def of_layer(i):
        lp = "layers_%d_" % i
        return {n[len(lp):]: v for n, v in params.items()
                if n.startswith(lp)}

    def norms(p, which):
        return p[which + "_gamma"], p[which + "_beta"]

    def run(i):
        kind, depth = kinds[i], depths[i]

        def layer(x, p, memory, kv):
            u = _layer_norm(x, *norms(p, "input_layernorm"), eps)
            if kind == MAMBA:
                out, memory = _mamba(cfg, p, u)
            elif kind == GMU:
                out = _gmu(p, u, memory)
            elif kind == CROSS:
                out, _ = _attention(cfg, p, u, kind, depth, kv)
            else:
                out, made = _attention(cfg, p, u, kind, depth)
                if kind == FULL:
                    kv = made
            h = x + out
            f = _layer_norm(h, *norms(p, "post_attention_layernorm"), eps)
            y = h + _gated_mlp(f, *(p["mlp_w%d_weight" % n]
                                    for n in (1, 3, 2)))
            return y, memory, kv
        return _recomputed(layer)

    x = params["embed_tokens_weight"][tokens]
    # what a layer is handed before any layer has made it: nothing a layer
    # reads (``_checked``), zeros of the right shape for the rule above
    batch, seq = tokens.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["hidden_size"] // heads
    memory = jnp.zeros((batch, seq, _inner(cfg)), jnp.float32)
    kv = (jnp.zeros((batch, seq, kv_heads // 2, 2, dim), jnp.float32),
          jnp.zeros((batch, seq, kv_heads // 2, 2 * dim), jnp.float32))
    for i in range(len(kinds)):
        x, memory, kv = run(i)(x, of_layer(i), memory, kv)
    gain, bias = params["norm_gamma"], params["norm_beta"]
    return _layer_norm(x, gain, bias, eps) @ params["embed_tokens_weight"].T


def reference_loss(logits, labels):
    """Mean softmax cross-entropy over every position (the job's loss)."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -picked.mean()


# The readings behind each limit (my chip runs, PR 38: the program on eleven
# seeds, ``control.py`` and ``control_state.py`` on three; PERF.md section 4
# has every number).  ``block_f32``: the program 3.5e-6 - 4.2e-6, the scan's
# state in bf16 9.9e-3 - 7.1e-2: the one limit that control fails, on every
# seed.  ``step_loss``: the program 1.4e-6 - 8.8e-5 on eleven seeds, the fp8
# control 3.1e-4 - 5.3e-4 on three: the limit lies between the two, 1.9 times
# over the one and 1.8 under the other (the accepted cells' 4e-4 would pass
# the control on a seed).  ``steps_loss``: 0.0065 - 0.134 against fp8's 1.28
# - 1.30: the number the lower precision fails, on all three seeds.  fp8's is
# read at the second step and steady; the program's 0.134 is one seed's third
# step (0.053 or less on the other ten: the batch is memorised after one
# step, and a loss of 0.003 moves by a tenth with bf16), so the limit lies
# nearer the control's reading.
# ``first_grad_norm``: 0.008 - 0.106, the lambda vectors of the window layer
# on most seeds (a sum of ten million products that all but cancel:
# at initialisation a head's two softmaxes are nearly equal); fp8 reads 0.105
# - 0.277 there, so no limit parts them and the number has no upper reading:
# it is held between the program's reading and 1, which a gradient left out
# reads, and the control passes it.  ``param_change_norm``: 0.147 - 0.151 on
# every seed and under fp8 alike, always the fused ``qkv_bias``: a softmax
# does not see a bias on its keys, so that third of the leaf has no gradient,
# and Adam scales what rounding puts there up to a full step in bf16 and to
# none in float32 (OPT's separate key bias is left out by ``NO_GRADIENT``; a
# fused leaf is not); no upper reading either: held between the reading and
# 1, which a state left unchanged reads, so a fault of the update on any
# other leaf (0.064 or less) passes up to about six times (PERF.md section 7
# asks a ``benchmark`` PR to compare by the rows whose reference gradient is
# not zero; 0.15 would do then).
TOLERANCE = {"block_f32": 1e-3, "step_loss": 1.7e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 0.8, "first_grad_norm": 0.3,
             "param_change_norm": 0.4,
             "steps_loss_rehearsal": 1e-2, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.2}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def mixer_params(cfg, kind):
    """Matmul parameters of one mixer of ``kind`` (biases, the convolution's
    taps, A, D, the lambda vectors and the norm's gain are no matmul)."""
    d, inner = cfg["hidden_size"], _inner(cfg)
    kv_width = d // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    if kind == MAMBA:
        rank, state = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
        return (d * 2 * inner + inner * (rank + 2 * state) + rank * inner
                + inner * d)
    if kind == GMU:
        return d * inner + inner * d
    if kind == CROSS:
        return 2 * d * d
    return d * (d + 2 * kv_width) + d * d


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: every layer's MLP
    3 d f and its mixer, and the head over the rows held.  The token table
    is a gather: it does not count."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * 3 * d * cfg["intermediate_size"]
            + sum(mixer_params(cfg, kind) for kind in cfg["layer_types"])
            + _vocab(cfg) * d)


def keys_seen(seq, window=None):
    """Sum over a row's queries of the keys each sees: min(t + 1, W)."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches, plus attention's own two products, Q K^T at the
    key's width D and P V at the value's 2 D for each of the H query heads,
    2 H (D + 2 D) a visited pair, times 3 for forward and backward, over
    the mean number of keys a query sees in a layer of its kind.  **The
    scan's own arithmetic is left out** (about 7 operations and one ``exp``
    a channel a state a token, three passes: 1.7 MFLOP a token): it is not
    the MXU's work, and its time is what the ``ssm_scan_*`` metrics report.
    What the flash backward recomputes, padded lanes and the blocks a kernel
    visits beyond the mask are overhead, not model work."""
    heads = cfg["num_attention_heads"]
    dim = cfg["hidden_size"] // heads
    seq = traffic["seq_len"]
    per_pair = 2 * heads * (dim + 2 * dim)
    keys = sum(keys_seen(seq, cfg["sliding_window"] if kind == WINDOW
                         else None) / seq
               for kind in cfg["layer_types"]
               if kind in (WINDOW, FULL, CROSS))
    return 6.0 * matmul_params(cfg) + 3.0 * per_pair * keys
