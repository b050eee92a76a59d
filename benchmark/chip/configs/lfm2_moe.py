"""LFM2-MoE decoder LM (Liquid AI, ``LiquidAI/LFM2-8B-A1B``; the published
description is its ``config.json`` and HF ``modeling_lfm2_moe.py``) for the
chip benchmark, as one chip's share of a 4-chip expert-parallel deployment.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.RMSNorm`` / ``ShortConv`` / ``GatedMLP`` /
                 ``MultiHeadAttention`` (grouped K/V, per-head QK RMSNorm,
                 rotary positions) and ``parallel.ExpertParallelMoE`` with
                 ``dispatch="grouped"`` holding ``num_experts`` of the
                 router's ``num_experts_published`` experts.  Nothing is
                 defined here but how they are stacked.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published description, sharing no code with the
                 program: no kernel, no sort; every held expert computed for
                 every token and masked; what the absent experts would add
                 is left out, as in the program (no code stands in for the
                 other chips).  One record of the program is read: which
                 experts its last eager forward chose, to be verified
                 against this side's scores where two of them tie
                 (``_routed``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

Layer l: ``h = x + Op_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; Op is
the gated short convolution or grouped-query attention by ``layer_types``;
FFN is a SwiGLU MLP below ``num_dense_layers`` and the routed layer above
(sigmoid scores, top-k of score + bias, weights renormalised over the
chosen, held or not).  One RMSNorm after the last layer, then the head,
tied to the embedding.  The sizes come from the JSON beside this file.
"""
import math
import weakref

PREFIX = "lfm2_"
NORM_EPS_TOPK = 1e-6            # beside the chosen scores' sum, as published
# Selection scores closer than this are a tie in float32 (``_routed``): the
# scores are sigmoids in (0, 1) of a 2048-term float32 product, the bias is
# of order 0.05, and the two sides reach them by different arithmetic.
# Readings with the limit's reasons at TOLERANCE below.
ROUTING_TIE = 1e-5
_built = None                   # the last model built, weakly (``_routed``)


def _held(cfg):
    """(first, count) of the experts this share holds."""
    return 0, cfg["num_experts"]


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def build(cfg):
    """The uninitialised Gluon model.  The prefix is fixed: parameter names
    are keys of the step's argument trees and so part of the compile-cache
    key (PERF.md, PR 21)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE

    d, eps = cfg["hidden_size"], cfg["norm_eps"]
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "conv", "full_attention"}:
        raise ValueError("layer_types must name conv or full_attention for "
                         "each of the %d layers" % cfg["num_hidden_layers"])
    if cfg["conv_bias"] or not cfg["tie_word_embeddings"]:
        raise ValueError("no bias and a tied head, as LFM2-8B-A1B has them")

    def operator(kind):
        if kind == "conv":
            return nn.ShortConv(d, kernel=cfg["conv_L_cache"], prefix="conv_")
        return nn.MultiHeadAttention(
            d, cfg["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=cfg["num_key_value_heads"], qk_norm=True,
            qk_norm_epsilon=eps, rotary_base=float(cfg["rope_theta"]),
            prefix="self_attn_")

    def feed_forward(routed):
        if not routed:
            return nn.GatedMLP(d, cfg["intermediate_size"],
                               prefix="feed_forward_")
        bias = cfg["use_expert_bias"] and mx.init.Normal(
            cfg["expert_bias_std"])
        # ep_axis None: the cell's mesh is one chip's, and the share is
        # held whole on it
        return ExpertParallelMoE(
            cfg["moe_intermediate_size"], cfg["num_experts_published"],
            top_k=cfg["num_experts_per_tok"], dispatch="grouped",
            experts_held=_held(cfg), router="sigmoid", selection_bias=bias,
            norm_topk=cfg["norm_topk_prob"],
            scaling=cfg["routed_scaling_factor"], gated=True, in_units=d,
            ep_axis=None, prefix="feed_forward_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, kind, routed, **kwargs):
            super().__init__(**kwargs)
            self._routed = routed
            with self.name_scope():
                self.operator_norm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                                prefix="operator_norm_")
                self.op = operator(kind)
                self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                           prefix="ffn_norm_")
                self.ffn = feed_forward(routed)

        def hybrid_forward(self, F, x):
            h = x + self.op(self.operator_norm(x))
            f = self.ffn_norm(h)
            if self._routed:            # the routed layer takes (tokens, d)
                f = F.reshape(self.ffn(F.reshape(f, shape=(-1, d))),
                              shape=h.shape)
            else:
                f = self.ffn(f)
            return h + f

    class LFM2MoELM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(_vocab(cfg), d,
                                          prefix="embed_tokens_")
                self.layers = nn.HybridSequential(prefix="layers_")
                with self.layers.name_scope():
                    for i, kind in enumerate(kinds):
                        self.layers.add(DecoderLayer(
                            kind, i >= cfg["num_dense_layers"],
                            prefix="%d_" % i))
                self.embedding_norm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                                 prefix="embedding_norm_")
                # tied: the head's weight IS the embedding table, (vocab, d)
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False,
                                     params=self.embed.params)

        def hybrid_forward(self, F, tokens):
            return self.head(self.embedding_norm(
                self.layers(self.embed(tokens))))

    net = LFM2MoELM(prefix=PREFIX)
    # The selection bias is a buffer (``grad_req`` null in the layer) and no
    # gradient reaches it: it enters the choice of experts only.  The
    # harness's first steps (chipbench/first_steps.py) walk every parameter
    # the reference is given and read each one's gradient out of the
    # optimizer's state, where a frozen parameter has none.  So here it is
    # handed to the optimizer like the others: its gradient is exactly
    # zero, and Adam without decay leaves a parameter with zero moments
    # where it is (tests/test_lfm2_chip_bench.py holds it to
    # that, bit for bit).
    for name, p in net.collect_params().items():
        if name.endswith("expert_bias"):
            p.grad_req = "write"
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
    """``fn`` with nothing kept for its backward pass but its arguments:
    the backward pass runs ``fn`` again.  A memory policy, not mathematics
    (at 8192 tokens one attention layer's scores are 8.6 GB and a routed
    layer's held experts 2.4 GB, beside two gradient trees).  Written out
    as a differentiation rule instead of ``jax.checkpoint`` because the
    control (``chipbench/first_steps.py::with_matmuls_as``) walks the
    gradient's jaxpr and does not know the remat primitive."""
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


def _rotate(x, theta):
    """Rotary positions on (B, S, H, D), rotate-half: channel i pairs with
    i + D/2 and turns by position * theta^(-2i/D)."""
    import jax.numpy as jnp
    seq, dim = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _short_conv(cfg, p, x):
    """(B, C, u) = split3(W_in x); v_t = sum_j w[:, j] (B u)_{t-K+1+j};
    W_out (C v)."""
    import jax.numpy as jnp
    taps = cfg["conv_L_cache"]
    b, c, u = jnp.split(x @ p["conv_in_weight"].T, 3, axis=-1)
    bu = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    seq = x.shape[1]
    v = sum(bu[:, j:j + seq] * p["conv_conv_weight"][:, j]
            for j in range(taps))
    return (c * v) @ p["conv_out_weight"].T


def _attention(cfg, p, x):
    """32 query heads over 8 K/V heads, per-head RMSNorm of q and k, rotary
    positions, causal softmax.  One query head at a time, recomputed in
    the backward pass (``_recomputed``): a head's (S, S) scores are 268 MB
    at S 8192."""
    import jax
    import jax.numpy as jnp
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    batch, seq, d = x.shape
    dim = d // heads

    def project(name, n):
        return (x @ p["self_attn_%s_weight" % name].T).reshape(
            batch, seq, n, dim)

    q = _rotate(_rms_norm(project("q", heads),
                          p["self_attn_q_norm_gamma"], eps), theta)
    k = _rotate(_rms_norm(project("k", kv),
                          p["self_attn_k_norm_gamma"], eps), theta)
    v = project("v", kv)
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    @_recomputed
    def head(qh, kg, vg):               # (B, S, D) each
        scores = jnp.einsum("bqd,bkd->bqk", qh, kg) / math.sqrt(dim)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vg)

    per = heads // kv                   # query heads a K/V head serves
    ctx = jnp.stack([head(q[:, :, h], k[:, :, h // per], v[:, :, h // per])
                     for h in range(heads)], axis=2)
    return ctx.reshape(batch, seq, d) @ p["self_attn_out_weight"].T


def _gated_mlp(x, w1, w3, w2):
    """W2 (silu(W1 x) * W3 x) with weights (out, in), as Dense keeps them."""
    return (_silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def _program_choices(cfg, tokens):
    """{routed layer: the experts the program chose there on its last eager
    call, (tokens, k) int32}, of the last model built if it is alive and
    was last called on as many tokens; else empty.  The record is read
    once: it enters the compiled reference as a constant, so a program
    that holds it is compiled anew in every process (54 s at the cell's
    size).  ``run.py::reference_check`` calls the Block, then the
    reference's forward over the same row, which reads it, and then the
    reference's loss, which does not and is read from the compile cache."""
    global _built
    net, _built = _built and _built(), None
    found = {}
    if net is not None:
        for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"]):
            chosen = getattr(net.layers[i].ffn, "last_chosen", None)
            if chosen is not None and chosen.shape[0] == tokens:
                found[i] = chosen
    return found


def _routed(cfg, p, x, theirs=None):
    """s = sigmoid(W_g x); the top-k of s + b are chosen; their weights are
    s at the chosen over (their sum + 1e-6), times the scaling factor; the
    sum runs over the held experts among the chosen.  Every held expert is
    computed for every token and masked by its weight (zero where it was
    not chosen).

    Top-k is discontinuous.  Where a token's k-th and (k+1)-th scores lie
    within float32's rounding of each other, either is the top-k, the two
    sides' arithmetic decides differently, and the token's logits then
    differ by a tenth of their range (one run in seven on the chip, PR 26).
    So the choice is verified, not replayed: given ``theirs``, the experts
    the program chose for these tokens (``_program_choices``: the forward
    check of ``run.py``), where every expert it chose for a token scores
    here no less than this side's k-th best less ``ROUTING_TIE``, its
    choice is a top-k of these scores and is the one followed; a choice
    that is not (a wrong bias, a missing sigmoid, a repeated expert) is
    not followed and shows as the error it is.  Scores and weights stay
    this side's."""
    import jax
    import jax.numpy as jnp
    first, count = _held(cfg)
    experts = cfg["num_experts_published"]
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(tokens @ p["feed_forward_gate_weight"])
    choose = scores
    if cfg["use_expert_bias"]:
        choose = scores + jax.lax.stop_gradient(p["feed_forward_expert_bias"])
    choose = jax.lax.stop_gradient(choose)
    best, chosen = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    if theirs is not None:
        fair = (jnp.take_along_axis(choose, theirs, axis=1)
                >= best[:, -1:] - ROUTING_TIE).all(-1)
        fair &= (jax.nn.one_hot(theirs, experts).sum(1) <= 1).all(-1)
        chosen = jnp.where(fair[:, None], theirs, chosen)
    picked = jax.nn.one_hot(chosen, experts).sum(1)         # (T, E) of 0/1
    weights = scores * picked
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + NORM_EPS_TOPK)
    weights = weights * cfg["routed_scaling_factor"]
    @_recomputed
    def expert(rows, w1, w3, w2):
        # stacked as the program keeps them: (expert, d, h) up and gate,
        # (expert, h, d) down
        return (_silu(rows @ w1) * (rows @ w3)) @ w2

    out = jnp.zeros_like(tokens)
    for e in range(count):
        y = expert(tokens, *(p["feed_forward_expert_w%d" % n][e]
                             for n in (1, 3, 2)))
        out = out + weights[:, first + e:first + e + 1] * y
    return out.reshape(x.shape)


def reference(cfg, params, tokens):
    """Logits (B, S, rows of the vocabulary held) for int tokens (B, S).

    ``params`` maps the suffix of a parameter's name after the model prefix
    to a float32 array; a projection's weight is (out, in), y = x W^T.
    Call it under ``jax.default_matmul_precision("highest")``: on a TPU a
    float32 matmul otherwise runs in bf16 passes.  Each layer is
    recomputed in the backward pass (``_recomputed``)."""
    eps = cfg["norm_eps"]
    theirs = _program_choices(cfg, tokens.size)

    def layer(i, kind):
        def run(h, p):
            a = _rms_norm(h, p["operator_norm_gamma"], eps)
            h = h + (_short_conv(cfg, p, a) if kind == "conv"
                     else _attention(cfg, p, a))
            f = _rms_norm(h, p["ffn_norm_gamma"], eps)
            if i < cfg["num_dense_layers"]:
                return h + _gated_mlp(
                    f, *(p["feed_forward_w%d_weight" % n] for n in (1, 3, 2)))
            return h + _routed(cfg, p, f, theirs.get(i))
        return _recomputed(run)

    table = params["embed_tokens_weight"]
    h = table[tokens]
    for i, kind in enumerate(cfg["layer_types"]):
        lp = "layers_%d_" % i
        h = layer(i, kind)(h, {n[len(lp):]: v for n, v in params.items()
                               if n.startswith(lp)})
    return _rms_norm(h, params["embedding_norm_gamma"], eps) @ table.T


def reference_loss(logits, labels):
    """Mean softmax cross-entropy over every position (the job's loss)."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -picked.mean()


# Top-k is discontinuous: a token whose fourth and fifth selection scores
# lie closer than the two sides' rounding is routed differently by them,
# and where one of the two experts is held its logits move by a tenth of
# their range.  In bf16 that is 13-14 % of the tokens, half of them on a held
# expert (facts["moe_routing"]); their losses move both ways and the means
# below hardly.  All readings: my chip runs, PR 26 (PERF.md section 4).
# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward against the reference, both at full
#   float32 matmul precision: 6.3e-7 to 7.9e-7 on 32 seeds.  A part left
#   out moves the logits by far more
#   (tests/test_lfm2_chip_bench.py), and so did a tie of two
#   float32 scores, on one seed of the first 25 (1618033988: 0.0797;
#   layer 3's token 7819 had its fourth and fifth scores 1.8e-7 apart,
#   three units in the last place, and the Block took the fifth).
# ROUTING_TIE (above): how far under this side's k-th best score an expert
#   the program chose may score and still be followed (``_routed``).  The
#   one reading of what the two sides' rounding moves is that 1.8e-7; of a
#   row's 32768 choices 12 have the k-th and next score under 1e-5 apart
#   and 118 under 1e-4 (seed 1618033988), against a typical distance of
#   0.03, so at 1e-5 a wrongly routed token passes as a tie four times in
#   ten thousand and a rounding fifty times the one seen is still
#   followed.  With it that seed reads 7.0e-7.
# step_loss: |first step's loss - reference loss| over the reference loss:
#   2.5e-6 to 8.7e-5.  At the first step the loss is ln(16384) whatever
#   the precision (the fp8 control reads 0 to 2.4e-4 here), so this limit
#   holds the loss's own arithmetic, at 4.6 times the largest reading.
# steps_loss: the worst of three steps' losses, relative.  The loss falls
#   10.1 -> 5.6 -> 1.9, so the third step shows what two updates were worth:
#   the program 2.6e-3 to 4.4e-3, the reference with fp8 matrix products in
#   its place 7.7e-2, 7.8e-2, 7.9e-2 (control.py, three seeds).  The limit
#   is 3.4 times the one and a fifth of the other: the number the lower
#   precision fails.
# first_grad_norm: worst leaf, the gap between the norm of the first
#   gradient as Adam got it and the reference's: the program 1.4e-3 to
#   3.1e-3 (a router's or an expert's weights), the fp8 control 5.1e-3 to
#   6.5e-3: too close to part them, so it is held at three times the
#   program's largest against a gradient that lost a part of the batch, an
#   expert or a factor.
# param_change_norm: worst leaf, the same of the change after two steps:
#   the program 1.28e-2 to 1.34e-2, always at a conv projection; no
#   precision moves it (fp8 1.2e-2 to 1.3e-2, bf16 moments under 1e-5).
#   Three times the program's, against a step that returns its state (1.0),
#   a wrong rate, a missing bias correction.
# *_rehearsal: at d64 over 256 positions (9 seeds, this sandbox) the first
#   loss reads up to 1.5e-5 and the three up to 3.9e-5, 2.5e-2 and 2.2e-2;
#   the control flow is what is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 4e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 1.5e-2, "first_grad_norm": 1e-2,
             "param_change_norm": 4e-2,
             "steps_loss_rehearsal": 1e-3, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.1}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def layer_counts(cfg):
    """(conv layers, attention layers, dense FFNs, routed FFNs)."""
    kinds = cfg["layer_types"]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def held_share(cfg):
    """The share of a token's expert assignments that land on this chip's
    experts when the router is balanced: held over published."""
    return cfg["num_experts"] / cfg["num_experts_published"]


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: the conv operator's
    two projections 4 d^2, attention's q, out (d^2 each) and k, v (d * kv
    width each), the dense MLP 3 d ffn, the routed layer's router d * E and
    the *expected* held share of its top-k experts, k * held/E * 3 d h, and
    the head over the rows held.  The token table is a gather and the
    depthwise taps are no matmul: neither counts."""
    d = cfg["hidden_size"]
    convs, attns, dense, routed = layer_counts(cfg)
    kv_width = d // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return (convs * 4 * d * d + attns * (2 * d * d + 2 * d * kv_width)
            + dense * 3 * d * cfg["intermediate_size"]
            + routed * (d * cfg["num_experts_published"]
                        + cfg["num_experts_per_tok"] * held_share(cfg)
                        * expert)
            + _vocab(cfg) * d)


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches, plus causal attention 6 * S * d for each
    attention layer (QK^T and PV over all query heads, halved for
    causality, times 3 for forward and backward).  The experts count at the
    balanced router's expected load, whatever a run's router does; what the
    flash backward recomputes and the zero lanes of a padded head are
    overhead, not model work."""
    _, attns, _, _ = layer_counts(cfg)
    return (6.0 * matmul_params(cfg)
            + 6.0 * traffic["seq_len"] * cfg["hidden_size"] * attns)
