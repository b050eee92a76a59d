"""Qwen3-Next-80B-A3B's decoder (Qwen, ``Qwen/Qwen3-Next-80B-A3B-Instruct``;
``model_type: qwen3_next``; the published description is its ``config.json``
and the equations of HF ``modeling_qwen3_next.py``) for the chip benchmark, as
one chip's share of a 32-chip expert-parallel deployment.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.Embedding``, two ``RMSNorm`` a layer,
                 ``GatedDeltaNet`` (16 key and 32 value heads of 128 over
                 ``ops/delta_rule.py``) on the ``linear_attention`` layers,
                 ``MultiHeadAttention`` (16 query heads over 2 K/V heads of
                 256, ``qk_norm``, ``gate``, ``rotary_dim`` 64) on the
                 ``full_attention`` ones, and ``parallel.ExpertParallelMoE``
                 with ``dispatch="grouped"`` holding ``num_experts`` of the
                 router's ``num_experts_published`` experts beside a shared
                 expert under its scalar gate.  Nothing is defined here but
                 how they are stacked.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published equations, sharing no code with the program:
                 the delta rule one token at a time with a (key, value)
                 float32 state a head, its adjoint written out step by step
                 (``_recurrence``), a few heads at a time; the convolution
                 as shifted products; attention as an explicit (S, S) mask,
                 one K/V head's query heads at a time; rotary positions on
                 the first ``rotary`` channels from their formula; every
                 held expert computed for every token and masked; the shared
                 expert whole under its gate, added once; what experts
                 16-511 would add left out, as in the program.  With
                 ``cfg["scan_state_dtype"] = "bfloat16"`` the state and the
                 decay are held in bfloat16: the control of this
                 configuration's own (``control_delta_state.py``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

Layer l is a ``full_attention`` layer where (l + 1) mod
``full_attention_interval`` is 0, else ``linear_attention``::

    h = x + Mixer_l(RMSNorm_1(x)),   y = h + MoE(RMSNorm_2(h))

Gated DeltaNet on the normalised input u::

    [q | k | v | z] = W_qkvz u,  [b | a] = W_ba u
    [q | k | v] = silu(conv4([q | k | v]))        depthwise, causal, no bias
    q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(Dk),  k_h without the last
    beta = sigmoid(b),  alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S' = alpha_t S_{t-1};  u_t = beta_t (v_t - S'^T k_{t, h div 2});
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t;  S_0 = 0
    o_h = w_n * o_h / sqrt(mean o_h^2 + eps) * silu(z_h);  out = W_out o

Full attention: ``q = RMSNorm_q(W_q u)``, ``k = RMSNorm_k(W_k u)`` head by
head, rotate-half positions at ``rope_theta`` over the first
``partial_rotary_factor * head_dim`` channels of a head, causal softmax at
``1 / sqrt(head_dim)``, ``out = W_o (sigmoid(W_g u) * heads)``.

Routed layer: ``p = softmax(W_r m)`` in float32 over all experts, the top-k
chosen, their p over their sum, SwiGLU experts, plus
``sigmoid(w_s . m) * SwiGLU_shared(m)``.  One RMSNorm after the last layer,
then the head's own matrix.  No bias anywhere.  The sizes come from the JSON
beside this file, which lists under ``assumed`` every departure.
"""
import math
import weakref

PREFIX = "qwen3next_"
HEADS_A_BLOCK = 8               # value heads whose every state coexists
L2_EPS = 1e-6                   # beside a head's sum of squares, as published
# Selection scores closer than this are a tie in float32 (``_routed``): the
# scores are softmax probabilities over 512 experts, about 2e-3 each, and the
# two sides reach them by different arithmetic and part by about 1e-9.
# Around the tenth best of 512 such scores neighbours lie about 1e-5 apart,
# so of a row's 4096 x 4 choices a few have their tenth and eleventh within
# 1e-7: mellum2_moe.py's kind of score, at a limit scaled to its size.
ROUTING_TIE = 1e-7
_built = None                   # the last model built, weakly (``_routed``)

LINEAR, FULL = KINDS = ("linear_attention", "full_attention")


def _held(cfg):
    """(first, count) of the experts this share holds."""
    return cfg.get("first_expert", 0), cfg["num_experts"]


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def _rotary_dim(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def _checked(cfg):
    kinds, every = cfg["layer_types"], cfg["full_attention_interval"]
    if len(kinds) != cfg["num_hidden_layers"] or any(
            kind != (FULL if (i + 1) % every == 0 else LINEAR)
            for i, kind in enumerate(kinds)):
        raise ValueError("layer_types names each of the %d layers: "
                         "full_attention where (l + 1) mod %d is 0, else "
                         "linear_attention" % (cfg["num_hidden_layers"],
                                               every))
    if (cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"]
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1
            or not cfg["norm_topk_prob"] or cfg["use_sliding_window"]):
        raise ValueError("plain rotary positions, a head of its own, every "
                         "layer routed and renormalised, no window, as "
                         "Qwen3-Next has them")


def build(cfg):
    """The uninitialised Gluon model.  The prefix is fixed: parameter names
    are keys of the step's argument trees and so part of the compile-cache
    key (PERF.md, PR 21)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE

    _checked(cfg)
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]

    def mixer(kind):
        if kind == LINEAR:
            return nn.GatedDeltaNet(
                d, cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                conv=cfg["linear_conv_kernel_dim"], epsilon=eps,
                prefix="linear_attn_")
        # the plain frequencies as a scaling of factor 1: the table of
        # cosines and sines is then made on the host (trinity_afmoe.py)
        return nn.MultiHeadAttention(
            d, cfg["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], qk_norm=True, qk_norm_epsilon=eps,
            gate=True, rotary_base=float(cfg["rope_theta"]),
            rotary_dim=_rotary_dim(cfg), rotary_scaling={
                "factor": 1.0, "attention_factor": 1.0,
                "original_max_position": cfg["max_position_embeddings"]},
            prefix="self_attn_")

    def routed():
        # ep_axis None: the cell's mesh is one chip's, and the share is
        # held whole on it
        return ExpertParallelMoE(
            cfg["moe_intermediate_size"], cfg["num_experts_published"],
            top_k=cfg["num_experts_per_tok"], dispatch="grouped",
            experts_held=_held(cfg), router="softmax",
            norm_topk=cfg["norm_topk_prob"], gated=True, in_units=d,
            shared_hidden_size=cfg["shared_expert_intermediate_size"],
            shared_gate=True, ep_axis=None, prefix="mlp_")

    def norm(name):
        return nn.RMSNorm(epsilon=eps, in_channels=d, prefix=name + "_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, kind, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.input_layernorm = norm("input_layernorm")
                self.mixer = mixer(kind)
                self.post_attention_layernorm = norm(
                    "post_attention_layernorm")
                self.mlp = routed()

        def hybrid_forward(self, F, x):
            h = x + self.mixer(self.input_layernorm(x))
            f = self.post_attention_layernorm(h)
            # the routed layer takes (tokens, d)
            return h + F.reshape(self.mlp(F.reshape(f, shape=(-1, d))),
                                 shape=h.shape)

    class Qwen3NextLM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                # its own initializer: see the file's assumed.initializer
                self.embed = nn.Embedding(
                    _vocab(cfg), d, prefix="embed_tokens_",
                    weight_initializer=mx.init.Normal(
                        cfg["embedding_init_std"]))
                self.layers = nn.HybridSequential(prefix="layers_")
                with self.layers.name_scope():
                    for i, kind in enumerate(cfg["layer_types"]):
                        self.layers.add(DecoderLayer(kind, prefix="%d_" % i))
                self.norm = norm("norm")
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False, prefix="lm_head_")

        def hybrid_forward(self, F, tokens):
            return self.head(self.norm(self.layers(self.embed(tokens))))

    net = Qwen3NextLM(prefix=PREFIX)
    global _built
    _built = weakref.ref(net)
    return net


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
    backward pass runs ``fn`` again.  A memory policy, not mathematics, for
    a whole layer at a time.  Written out as a differentiation rule instead
    of ``jax.checkpoint`` because the control
    (``chipbench/first_steps.py::with_matmuls_as``) walks the gradient's
    jaxpr and does not know the remat primitive."""
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


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    import jax.numpy as jnp
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    import jax.numpy as jnp
    return jnp.logaddexp(x, 0.0)


def _side_by_side(x, *weights):
    """``x W^T`` for each of the (out, in) ``weights``, made as ONE product
    with the weights laid side by side: the same sums, and fewer matrix
    products for the compiler (a float32 product at full precision is about
    a megabyte of the chip's code, and this program has to fit the compile
    cache beside the step: PERF.md section 6, PR 32 and PR 40)."""
    import jax.numpy as jnp
    out = x @ jnp.concatenate(weights, axis=0).T
    edges = []
    for w in weights[:-1]:
        edges.append((edges[-1] if edges else 0) + w.shape[0])
    return jnp.split(out, edges, axis=-1)


def _gated_mlp(x, gate, up, down):
    """SwiGLU with (out, in) weights: down (silu(gate x) * up x)."""
    g, u = _side_by_side(x, gate, up)
    return (_silu(g) * u) @ down.T


# -- the Gated DeltaNet mixer ------------------------------------------------

def _causal_conv(x, weight):
    """Depthwise, causal, (C, K) taps, no bias: out_t = sum_j w[:, j]
    x_{t - (K - 1) + j}, the steps before the first taken as zero.  K
    shifted products."""
    import jax.numpy as jnp
    taps, seq = weight.shape[1], x.shape[1]
    out = 0.0
    for j in range(taps):
        back = taps - 1 - j
        out = out + jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq] \
            * weight[:, j]
    return out


def _recurrence(held=None):
    """``o = rule(q, k, v, g, beta)``: q, k (b, S, n, Dk), v (b, S, n, Dv),
    g, beta (b, S, n) -> o (b, S, n, Dv) of the delta rule in the module
    docstring with alpha = exp(g), one token at a time with a float32 state
    of Dk x Dv numbers a head.

    The loop is a ``lax.while_loop`` and its gradient is written out below
    (the adjoint recurrence, one token at a time from the last): the control
    (``first_steps.with_matmuls_as``) evaluates the gradient's jaxpr and
    takes a ``scan``'s body for a function to call once; a ``while`` it
    binds as it is.  ``tests/test_qwen3next_chip_bench.py`` holds this rule
    to ``jax.grad`` of the same recurrence as a ``lax.scan`` and to
    ``transformers``' ``torch_recurrent_gated_delta_rule``.

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

    def decayed(state, g, i):
        """S' = alpha_i S_{i-1}: (b, n, Dk, Dv)."""
        return keep(jnp.exp(at(g, i)))[..., None, None] * state

    def lacking(decayed_state, k, v, i):
        """v_i - S'^T k_i: (b, n, Dv)."""
        return at(v, i) - (decayed_state * at(k, i)[..., None]).sum(-2)

    def states(k, v, g, beta):
        """S_t for every t: (b, S, n, Dk, Dv)."""
        batch, seq, heads, dk = k.shape

        def step(carry):
            i, state, out = carry
            before = decayed(state, g, i)
            u = at(beta, i)[..., None] * lacking(before, k, v, i)
            state = keep(before + at(k, i)[..., None] * u[..., None, :])
            return i + 1, state, lax.dynamic_update_index_in_dim(
                out, state, i, 1)

        zero = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
        out = jnp.zeros((batch, seq) + zero.shape[1:], jnp.float32)
        return lax.while_loop(lambda c: c[0] < seq, step, (0, zero, out))[2]

    @jax.custom_vjp
    def rule(q, k, v, g, beta):
        return (states(k, v, g, beta) * q[..., None]).sum(-2)

    def forward(q, k, v, g, beta):
        return rule(q, k, v, g, beta), (q, k, v, g, beta)

    def backward(res, d_o):
        q, k, v, g, beta = res
        seq = q.shape[1]
        every = states(k, v, g, beta)
        d_q = (every * d_o[..., None, :]).sum(-1)

        def step(carry):
            i, d_state, d_k, d_v, d_g, d_beta = carry
            t = seq - 1 - i
            kt, bt = at(k, t), at(beta, t)[..., None]
            earlier = jnp.where(t > 0, at(every, jnp.maximum(t - 1, 0)), 0.0)
            before = decayed(earlier, g, t)
            lack = lacking(before, k, v, t)
            d_state = d_state + at(q, t)[..., None] * at(d_o, t)[..., None, :]
            d_u = (d_state * kt[..., None]).sum(-2)                 # (b,n,Dv)
            through = bt * d_u
            d_before = d_state - kt[..., None] * through[..., None, :]
            alpha = keep(jnp.exp(at(g, t)))
            put = lax.dynamic_update_index_in_dim
            return (i + 1, alpha[..., None, None] * d_before,
                    put(d_k, (d_state * (bt * lack)[..., None, :]).sum(-1)
                        - (before * through[..., None, :]).sum(-1), t, 1),
                    put(d_v, through, t, 1),
                    put(d_g, (d_before * earlier).sum((-1, -2)) * alpha,
                        t, 1),
                    put(d_beta, (d_u * lack).sum(-1), t, 1))

        zero = jnp.zeros_like
        carry = (0, zero(every[:, 0]), zero(k), zero(v), zero(g), zero(beta))
        _, _, d_k, d_v, d_g, d_beta = lax.while_loop(
            lambda c: c[0] < seq, step, carry)
        return d_q, d_k, d_v, d_g, d_beta

    rule.defvjp(forward, backward)
    return rule


def _delta_net(cfg, p, u):
    """The Gated DeltaNet mixer of the module docstring on the layer's
    normalised input ``u`` (b, S, d), ``HEADS_A_BLOCK`` value heads at a
    time: the states of a row of 4096 tokens are 268 MB a head."""
    import jax.numpy as jnp
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = hk * dk, hv * dv
    batch, seq, _ = u.shape
    qkvz, ba = _side_by_side(u, p["linear_attn_in_qkvz_weight"],
                             p["linear_attn_in_ba_weight"])
    qkv = _silu(_causal_conv(qkvz[..., :2 * key + value],
                             p["linear_attn_conv_weight"]))
    z = qkvz[..., 2 * key + value:].reshape(batch, seq, hv, dv)
    q = qkv[..., :key].reshape(batch, seq, hk, dk)
    k = qkv[..., key:2 * key].reshape(batch, seq, hk, dk)
    v = qkv[..., 2 * key:].reshape(batch, seq, hv, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / math.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    beta = _sigmoid(ba[..., :hv])
    g = -jnp.exp(p["linear_attn_A_log"]) * _softplus(
        ba[..., hv:] + p["linear_attn_dt_bias"])
    held = cfg.get("scan_state_dtype")
    rule = _recurrence(held and jnp.dtype(held))
    # value head h reads key head h div (hv / hk)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    n = min(HEADS_A_BLOCK, hv)
    o = jnp.concatenate([
        rule(*(t[:, :, h:h + n] for t in (q, k, v, g, beta)))
        for h in range(0, hv, n)], axis=2)
    o = _rms_norm(o, p["linear_attn_norm_gamma"], cfg["rms_norm_eps"]) \
        * _silu(z)
    return o.reshape(batch, seq, value) @ p["linear_attn_out_weight"].T


# -- full attention ----------------------------------------------------------

SPLIT = 128                     # positions a row of ``_table``'s fine part


def _table(theta, seq, dim):
    """(cos, sin) of ``position * theta^(-2i/dim)``, (S, dim/2) float32
    each.  Frequencies and angles are taken with numpy in float64; so that
    no table of S rows enters the compiled reference as a constant, position
    SPLIT * a + b is turned by angle A_a and then by angle B_b, each from a
    short float64 table."""
    import jax.numpy as jnp
    import numpy as np
    freq = float(theta) ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    rows = -(-seq // SPLIT)
    big = np.arange(rows, dtype=np.float64)[:, None, None] * SPLIT * freq
    small = np.arange(SPLIT, dtype=np.float64)[None, :, None] * freq
    cos_a, sin_a, cos_b, sin_b = (jnp.asarray(f(t), jnp.float32)
                                  for t in (big, small)
                                  for f in (np.cos, np.sin))
    cos = (cos_a * cos_b - sin_a * sin_b).reshape(rows * SPLIT, -1)[:seq]
    sin = (sin_a * cos_b + cos_a * sin_b).reshape(rows * SPLIT, -1)[:seq]
    return cos, sin


def _rotate(x, theta, rotary):
    """Rotary positions on the first ``rotary`` channels of (B, S, heads,
    D), rotate-half among them: channel i < rotary / 2 pairs with
    i + rotary / 2 and turns by position * theta^(-2i/rotary); the other
    D - rotary channels pass as they are."""
    import jax.numpy as jnp
    seq = x.shape[1]
    cos, sin = (jnp.concatenate([t, t], -1)[:, None]
                for t in _table(theta, seq, rotary))
    turn, keep = x[..., :rotary], x[..., rotary:]
    x1, x2 = turn[..., :rotary // 2], turn[..., rotary // 2:]
    return jnp.concatenate(
        [turn * cos + jnp.concatenate([-x2, x1], -1) * sin, keep], -1)


def _attention(cfg, p, a):
    """The gated grouped-query attention of the module docstring on the
    layer's normalised input ``a`` (B, S, d), the query heads of one K/V
    head at a time: eight heads' (S, S) scores are 537 MB at S 4096, and the
    layer is recomputed in the backward pass (``reference``)."""
    import jax
    import jax.numpy as jnp
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    batch, seq, _ = a.shape
    theta, rotary = float(cfg["rope_theta"]), _rotary_dim(cfg)
    q, k, v, gate = _side_by_side(a, *(
        p["self_attn_%s_weight" % n] for n in ("q", "k", "v", "gate")))

    def by_head(t, n):
        return t.reshape(batch, seq, n, dim)

    q = _rotate(_rms_norm(by_head(q, heads), p["self_attn_q_norm_gamma"],
                          eps), theta, rotary)
    k = _rotate(_rms_norm(by_head(k, kv), p["self_attn_k_norm_gamma"], eps),
                theta, rotary)
    v = by_head(v, kv)
    i = jnp.arange(seq)
    seen = i[None, :] <= i[:, None]

    def group(qb, kg, vg):              # (B, S, n, D), (B, S, D), (B, S, D)
        scores = jnp.einsum("bqhd,bkd->bhqk", qb, kg) / math.sqrt(dim)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", probs, vg)

    per = heads // kv                   # query heads a K/V head serves
    ctx = jnp.concatenate(
        [group(q[:, :, g * per:(g + 1) * per], k[:, :, g], v[:, :, g])
         for g in range(kv)], axis=2).reshape(batch, seq, heads * dim)
    return (_sigmoid(gate) * ctx) @ p["self_attn_out_weight"].T


# -- the routed layer --------------------------------------------------------

def _program_choices(cfg, tokens):
    """{layer: the experts the program chose there on its last eager call,
    (tokens, k) int32}, of the last model built if it is alive and was last
    called on as many tokens; else empty.  The record is read once: it
    enters the compiled reference as a constant (``lfm2_moe.py`` has the
    same; PERF.md section 7)."""
    global _built
    net, _built = _built and _built(), None
    found = {}
    if net is not None:
        for i in range(cfg["num_hidden_layers"]):
            chosen = getattr(net.layers[i].mlp, "last_chosen", None)
            if chosen is not None and chosen.shape[0] == tokens:
                found[i] = chosen
    return found


def _routed(cfg, p, x, theirs=None):
    """p = softmax(W_r x) in float32; the top-k are chosen; their weights
    are p at the chosen over their sum (``norm_topk_prob``); the sum runs
    over all the chosen, the output over the held experts among them; the
    shared expert is added once under its gate, sigmoid(w_s . x).  Every
    held expert is computed for every token and masked by its weight (zero
    where it was not chosen).

    Top-k is discontinuous, so the program's choice is verified, not
    replayed (``mellum2_moe.py::_routed`` has the reasons): given
    ``theirs``, where every expert the program chose for a token scores here
    no less than this side's k-th best less ``ROUTING_TIE`` and none is
    chosen twice, its choice is followed; scores and weights stay this
    side's."""
    import jax
    import jax.numpy as jnp
    first, count = _held(cfg)
    experts = cfg["num_experts_published"]
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.softmax(tokens @ p["mlp_gate_weight"], axis=-1)
    choose = jax.lax.stop_gradient(scores)
    best, chosen = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    if theirs is not None:
        fair = (jnp.take_along_axis(choose, theirs, axis=1)
                >= best[:, -1:] - ROUTING_TIE).all(-1)
        fair &= (jax.nn.one_hot(theirs, experts).sum(1) <= 1).all(-1)
        chosen = jnp.where(fair[:, None], theirs, chosen)
    picked = jax.nn.one_hot(chosen, experts).sum(1)         # (T, E) of 0/1
    weights = scores * picked
    weights = weights / weights.sum(-1, keepdims=True)

    def held(rows, w1, w3, w2, share):
        # stacked as the program keeps them: (expert, d, h) gate and up,
        # (expert, h, d) down; every held expert over every token in one
        # batched product, gate and up side by side, each masked by its
        # weight
        both = jnp.einsum("td,edh->eth", rows, jnp.concatenate([w1, w3], -1))
        hidden = _silu(both[..., :w1.shape[-1]]) * both[..., w1.shape[-1]:]
        y = jnp.einsum("eth,ehd->etd", hidden, w2)
        return (y * share.T[:, :, None]).sum(0)

    out = held(tokens, *(p["mlp_expert_w%d" % n] for n in (1, 3, 2)),
               weights[:, first:first + count])
    return (out + _shared(p, tokens)).reshape(x.shape)


def _shared(p, tokens):
    """sigmoid(w_s . x) * SwiGLU_shared(x) on (T, d): what every chip
    computes alike, so where shares are added up it counts once."""
    shared = _gated_mlp(tokens, *(p["mlp_shared_experts_w%d_weight" % n]
                                  for n in (1, 3, 2)))
    return _sigmoid(tokens @ p["mlp_shared_gate_weight"])[:, None] * shared


def reference(cfg, params, tokens):
    """Logits (B, S, rows of the vocabulary held) for int tokens (B, S).

    ``params`` maps the suffix of a parameter's name after the model prefix
    to a float32 array; a projection's weight is (out, in), y = x W^T.
    Call it under ``jax.default_matmul_precision("highest")``: on a TPU a
    float32 matmul otherwise runs in bf16 passes.  Each layer is
    recomputed in the backward pass (``_recomputed``)."""
    eps = cfg["rms_norm_eps"]
    theirs = _program_choices(cfg, tokens.size)

    def layer(i, kind):
        def run(x, p):
            a = _rms_norm(x, p["input_layernorm_gamma"], eps)
            h = x + (_delta_net(cfg, p, a) if kind == LINEAR
                     else _attention(cfg, p, a))
            m = _rms_norm(h, p["post_attention_layernorm_gamma"], eps)
            return h + _routed(cfg, p, m, theirs.get(i))
        return _recomputed(run)

    h = params["embed_tokens_weight"][tokens]
    for i, kind in enumerate(cfg["layer_types"]):
        lp = "layers_%d_" % i
        h = layer(i, kind)(h, {n[len(lp):]: v for n, v in params.items()
                               if n.startswith(lp)})
    return _rms_norm(h, params["norm_gamma"], eps) @ params[
        "lm_head_weight"].T


def reference_loss(logits, labels):
    """Mean softmax cross-entropy over every position (the job's loss)."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -picked.mean()


# All readings: my chip runs, PR 48 (PERF.md sections 4 and 6): the program on
# twenty-one seeds at the file's sizes, ``control.py``'s fp8 control and
# ``control_delta_state.py`` on three.  The model draws no random numbers, so
# every check is made on the model itself.
# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward (the chunked rule) against the reference
#   (one token at a time), both at full float32 matmul precision: 3.5e-6 to
#   2.5e-5 on twenty-one seeds.  With the rule's state and decay rounded to
#   bfloat16 at every token the reference itself reads 5.4e-2, 6.2e-2 and
#   6.7e-2 against itself: the one limit that control fails, on every seed,
#   54 times over; a part left out reads more still
#   (tests/test_qwen3next_chip_bench.py).
# ROUTING_TIE (above): how far under this side's k-th best score an expert
#   the program chose may score and still be followed.
# step_loss: |first step's loss - reference loss| over the reference loss:
#   3e-6 to 7.3e-5 on twenty-one seeds (a root mean square of 3.5e-5).  At
#   the first step the loss is ln(18992) + 0.4 at any precision, so this holds
#   the loss's own arithmetic: the fp8 control reads 2.4e-4, 5.1e-4 and
#   6.4e-4.  The limit lies between, 1.9 times the program's largest and 0.58
#   of the control's smallest (the accepted routed cells' 4e-4 would let the
#   control's first seed pass).
# steps_loss: the worst of three steps' losses, relative; the loss falls 10.25
#   -> 7.1 -> 3.9 (the row is memorised fast: every token's embedding moves
#   by 1.5 % of its scale a step), so the third step shows what two updates
#   were worth: the program 2.5e-4 to 1.98e-3 (always the third step), the
#   reference with fp8 matrix products in its place 4.3e-2, 4.4e-2, 4.7e-2.
#   The limit lies between, 4.0 times the program's largest and 0.19 of the
#   control's smallest: a number the lower precision fails, on every seed.
# first_grad_norm: worst leaf, the gap between the norm of the first gradient
#   as Adam got it and the reference's: the program 2.0e-3 to 1.01e-2 (a
#   DeltaNet layer's A_log or head norm, or the first router: 2935 of a row's
#   4096 tokens are routed otherwise in bf16 than in float32 in some layer,
#   257 on a held expert); the fp8 control 2.4e-2, 2.6e-2, 2.9e-2 (the same
#   leaves).  Between, 1.9 times the program's largest and 0.79 of the
#   control's smallest: the reading moves with the seed's routing, so the room
#   is kept above it, and the lower precision still fails it on every seed.
# param_change_norm: worst leaf, the same of the change after two steps: the
#   program 0.0241 to 0.0264 on twenty-one seeds, always an expert's w1, w2 or w3 (the tokens
#   bf16 routes otherwise reach or miss that expert); no precision moves it
#   (fp8 0.0035 to 0.0039 at a router).  Between the largest reading and the
#   1.0 of a step that returns its state, 3.8 times the reading, the more
#   room above.
# *_rehearsal: at d64 over 128 positions (this sandbox) the first loss reads
#   7e-6 and the three 2.7e-5, 1.5e-2 and 1.8e-2; the control flow is what
#   is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 1.4e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 8e-3, "first_grad_norm": 1.9e-2,
             "param_change_norm": 0.1,
             "steps_loss_rehearsal": 1e-2, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.3}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def held_share(cfg):
    """The share of a token's expert assignments that land on this chip's
    experts when the router is balanced: held over published."""
    return cfg["num_experts"] / cfg["num_experts_published"]


def mixer_params(cfg, kind):
    """Matmul parameters of one mixer of ``kind`` (the convolution's taps,
    A_log, dt_bias and the norms' gains are no matmul)."""
    d = cfg["hidden_size"]
    if kind == LINEAR:
        key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
        return (d * (2 * key + 2 * value)
                + d * 2 * cfg["linear_num_value_heads"] + value * d)
    dim = cfg["head_dim"]
    return (3 * d * cfg["num_attention_heads"] * dim
            + 2 * d * cfg["num_key_value_heads"] * dim)


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: every layer's mixer,
    its router d * E, its shared expert 3 d s with the gate's d, and the
    *expected* held share of its top-k experts, k * held/E * 3 d h; and the
    head over the rows held.  The token table is a gather: it does not
    count."""
    d = cfg["hidden_size"]
    routed = (3 * d * cfg["shared_expert_intermediate_size"] + d
              + d * cfg["num_experts_published"]
              + cfg["num_experts_per_tok"] * held_share(cfg)
              * 3 * d * cfg["moe_intermediate_size"])
    return (sum(mixer_params(cfg, kind) for kind in cfg["layer_types"])
            + cfg["num_hidden_layers"] * routed + _vocab(cfg) * d)


def delta_rule_flops(cfg):
    """The recurrence's own FLOPs of one token and layer, forward: three
    products of key_dim x value_dim a value head (S'^T k, k u^T, S^T q), two
    FLOPs a multiply-add; what a chunked form adds to that is overhead."""
    return (3 * 2 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
            * cfg["linear_num_value_heads"])


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches; attention's own two products (Q K^T and P V) over
    the mean (S + 1) / 2 keys a causal query sees, 2 * 2 * H * D a key,
    times 3 for forward and backward; and the delta rule's recurrence,
    ``delta_rule_flops`` a linear layer, times 3.  The experts count at the
    balanced router's expected load, whatever a run's router does; gates,
    norms, the convolution's taps, what the flash backward recomputes and
    what the chunked form of the rule adds are not model work."""
    kinds = list(cfg["layer_types"])
    per_key = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    keys = kinds.count(FULL) * (traffic["seq_len"] + 1) / 2.0
    return (6.0 * matmul_params(cfg) + 3.0 * per_key * keys
            + 3.0 * delta_rule_flops(cfg) * kinds.count(LINEAR))
