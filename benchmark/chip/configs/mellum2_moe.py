"""Mellum 2 decoder LM (JetBrains, ``JetBrains/Mellum2-12B-A2.5B-Instruct``;
the published description is its ``config.json``, ``model_type: mellum``,
whose layers are HF ``transformers``' shared ones) for the chip benchmark, as
one chip's share of an 8-chip expert-parallel deployment.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.RMSNorm``, ``MultiHeadAttention`` (32
                 query heads over 4 K/V heads of 128 at a model width of
                 2304; ``window=`` on the sliding layers, YaRN positions on
                 the full ones) and ``parallel.ExpertParallelMoE`` with
                 ``dispatch="grouped"`` holding ``num_experts`` of the
                 router's ``num_experts_published`` experts.  Nothing is
                 defined here but how they are stacked.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published description, sharing no code with the
                 program: no kernel, no sort; attention as an explicit
                 (S, S) mask a layer type, a few heads at a time; YaRN from
                 its formulas; every held expert computed for every token
                 and masked; what the absent experts would add is left out,
                 as in the program (no code stands in for the other chips).
                 One record of the program is read: which experts its last
                 eager forward chose, to be verified against this side's
                 scores where two of them tie (``_routed``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

Layer l: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.  Attn is
grouped-query softmax attention with rotary positions over the whole head
(rotate-half): under a causal mask with YaRN positions where ``layer_types``
says ``full_attention``, under a causal window of ``sliding_window`` keys
(the query's own and the 1023 before it) with the plain frequencies where it
says ``sliding_attention``.  MoE: ``p = softmax(W_g x)`` over all experts in
float32, the top-k of ``p``, their weights ``p`` at the chosen over the sum
of those (held or not), SwiGLU experts.  No bias, no shared expert, no
selection bias, no auxiliary loss.  One RMSNorm after the last layer, then
the head's own matrix.  The sizes come from the JSON beside this file.
"""
import math
import weakref

PREFIX = "mellum2_"
# Selection scores closer than this are a tie in float32 (``_routed``): the
# scores are softmax probabilities of 64 experts, 0.016 on average, of a
# 2304-term float32 product, and the two sides reach them by different
# arithmetic.  Readings with the limit's reasons at TOLERANCE below.
ROUTING_TIE = 1e-6
HEADS_A_BLOCK = 2               # query heads whose (S, S) scores coexist
_built = None                   # the last model built, weakly (``_routed``)


def _held(cfg):
    """(first, count) of the experts this share holds."""
    return 0, cfg["num_experts"]


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def _rotary(cfg, kind):
    """``rope_parameters`` of a layer type as ``MultiHeadAttention`` takes
    them: (base, scaling).  A default entry is handed over as a scaling of
    factor 1, which is the plain frequencies with the table of cosines and
    sines made on the host: the device's own float32 cosine at 8192
    positions parts two compilations of one formula by 1e-2 (PERF.md,
    PR 30)."""
    rope = cfg["rope_parameters"][kind]
    length = rope.get("original_max_position_embeddings",
                      cfg["max_position_embeddings"])
    if rope["rope_type"] == "default":
        return float(rope["rope_theta"]), {
            "factor": 1.0, "original_max_position": length,
            "attention_factor": 1.0}
    if rope["rope_type"] != "yarn":
        raise ValueError("rope_type %r: default or yarn" % rope["rope_type"])
    return float(rope["rope_theta"]), {
        "factor": rope["factor"], "original_max_position": length,
        "beta_fast": rope["beta_fast"], "beta_slow": rope["beta_slow"],
        "attention_factor": rope["attention_factor"]}


def build(cfg):
    """The uninitialised Gluon model.  The prefix is fixed: parameter names
    are keys of the step's argument trees and so part of the compile-cache
    key (PERF.md, PR 21)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE

    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "sliding_attention", "full_attention"}:
        raise ValueError("layer_types must name sliding_attention or "
                         "full_attention for each of the %d layers"
                         % cfg["num_hidden_layers"])
    if set(cfg["mlp_layer_types"]) != {"sparse"} or len(
            cfg["mlp_layer_types"]) != len(kinds):
        raise ValueError("every layer's MLP is sparse, as Mellum2's are")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("no bias and a head of its own, as Mellum2 has them")

    def attention(kind):
        base, scaling = _rotary(cfg, kind)
        return nn.MultiHeadAttention(
            d, cfg["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=cfg["num_key_value_heads"], rotary_base=base,
            head_dim=cfg["head_dim"], rotary_scaling=scaling,
            window=(cfg["sliding_window"] if kind == "sliding_attention"
                    else None), prefix="self_attn_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, kind, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.input_layernorm = nn.RMSNorm(
                    epsilon=eps, in_channels=d, prefix="input_layernorm_")
                self.self_attn = attention(kind)
                self.post_attention_layernorm = nn.RMSNorm(
                    epsilon=eps, in_channels=d,
                    prefix="post_attention_layernorm_")
                # ep_axis None: the cell's mesh is one chip's, and the share
                # is held whole on it
                self.mlp = ExpertParallelMoE(
                    cfg["moe_intermediate_size"],
                    cfg["num_experts_published"],
                    top_k=cfg["num_experts_per_tok"], dispatch="grouped",
                    experts_held=_held(cfg), router="softmax",
                    norm_topk=cfg["norm_topk_prob"], gated=True, in_units=d,
                    ep_axis=None, prefix="mlp_")

        def hybrid_forward(self, F, x):
            h = x + self.self_attn(self.input_layernorm(x))
            f = self.post_attention_layernorm(h)
            # the routed layer takes (tokens, d)
            f = F.reshape(self.mlp(F.reshape(f, shape=(-1, d))),
                          shape=h.shape)
            return h + f

    class Mellum2LM(gluon.HybridBlock):
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
                    for i, kind in enumerate(kinds):
                        self.layers.add(DecoderLayer(kind, prefix="%d_" % i))
                self.norm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                       prefix="norm_")
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False, prefix="lm_head_")

        def hybrid_forward(self, F, tokens):
            return self.head(self.norm(self.layers(self.embed(tokens))))

    net = Mellum2LM(prefix=PREFIX)
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
    (at 8192 tokens four heads' scores are 1.07 GB and a layer's held
    experts 1.9 GB, beside two gradient trees).  Written out as a
    differentiation rule instead of ``jax.checkpoint`` because the control
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


SPLIT = 128                     # positions a row of ``_table``'s fine part


def _table(rope, seq, dim):
    """(cos, sin) of ``position * inv_freq_i`` for a ``rope_parameters``
    entry, (S, D/2) float32 each.  default: inv_freq_i = f_i =
    theta^(-2i/D).  yarn, with s = ``factor`` and L =
    ``original_max_position_embeddings``: c(r) = D ln(L / (2 pi r)) /
    (2 ln theta) is the pair that makes r turns over L positions; low =
    max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), D - 1);
    ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq_i =
    (f_i / s) ramp_i + f_i (1 - ramp_i); cosine and sine are multiplied by
    ``attention_factor``.

    The frequencies and the angles are taken with numpy in float64: a
    TPU's float32 cosine of an angle of thousands of radians is good to a
    hundredth of a radian (PERF.md, PR 30), and a router downstream turns
    such a difference into another choice of experts.  So that no table of
    S rows enters the compiled reference as a constant, position
    SPLIT * a + b is turned by angle A_a and then by angle B_b (rotation
    matrices multiply), each from a short float64 table."""
    import jax.numpy as jnp
    import numpy as np
    theta = float(rope["rope_theta"])
    pair = np.arange(dim // 2, dtype=np.float64)
    freq, factor = theta ** (-2.0 * pair / dim), 1.0
    if rope["rope_type"] == "yarn":
        s, length = rope["factor"], rope["original_max_position_embeddings"]

        def c(turns):
            return dim * math.log(length / (2 * math.pi * turns)) / (
                2 * math.log(theta))

        low = max(math.floor(c(rope["beta_fast"])), 0)
        high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
        ramp = np.clip((pair - low) / (high - low), 0.0, 1.0)
        freq = (freq / s) * ramp + freq * (1.0 - ramp)
        factor = rope["attention_factor"]
    rows = -(-seq // SPLIT)
    big = np.arange(rows, dtype=np.float64)[:, None, None] * SPLIT * freq
    small = np.arange(SPLIT, dtype=np.float64)[None, :, None] * freq
    cos_a, sin_a, cos_b, sin_b = (jnp.asarray(f(t), jnp.float32)
                                  for t in (big, small)
                                  for f in (np.cos, np.sin))
    cos = (cos_a * cos_b - sin_a * sin_b).reshape(rows * SPLIT, -1)[:seq]
    sin = (sin_a * cos_b + cos_a * sin_b).reshape(rows * SPLIT, -1)[:seq]
    return cos * factor, sin * factor


def _rotate(x, rope):
    """Rotary positions on (B, S, H, D), rotate-half: channel i pairs with
    i + D/2 and turns by position * inv_freq_i (``_table``)."""
    import jax.numpy as jnp
    seq, dim = x.shape[1], x.shape[-1]
    cos, sin = (jnp.concatenate([t, t], -1)[:, None]
                for t in _table(rope, seq, dim))
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mask(cfg, kind, seq):
    """(S, S) of bool: query i sees key j where j <= i and, on a sliding
    layer, i - sliding_window < j."""
    import jax.numpy as jnp
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        seen &= j > i - cfg["sliding_window"]
    return seen


def _attention(cfg, p, x, kind):
    """32 query heads over 4 K/V heads of ``head_dim``, each K/V head
    serving a run of consecutive query heads; rotary positions by layer
    type; softmax(q k^T / sqrt(D) + mask) v.  ``HEADS_A_BLOCK`` query heads
    at a time, recomputed in the backward pass (``_recomputed``): a head's
    (S, S) scores are 268 MB at S 8192."""
    import jax
    import jax.numpy as jnp
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, rope = cfg["head_dim"], cfg["rope_parameters"][kind]
    batch, seq, _ = x.shape

    def project(name, n):
        return (x @ p["self_attn_%s_weight" % name].T).reshape(
            batch, seq, n, dim)

    q = _rotate(project("q", heads), rope)
    k = _rotate(project("k", kv), rope)
    v = project("v", kv)
    seen = _mask(cfg, kind, seq)

    @_recomputed
    def block(qb, kg, vg):              # (B, S, n, D), (B, S, D), (B, S, D)
        scores = jnp.einsum("bqhd,bkd->bhqk", qb, kg) / math.sqrt(dim)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", probs, vg)

    per = heads // kv                   # query heads a K/V head serves
    n = min(HEADS_A_BLOCK, per)
    ctx = jnp.concatenate(
        [block(q[:, :, h:h + n], k[:, :, h // per], v[:, :, h // per])
         for h in range(0, heads, n)], axis=2)
    return ctx.reshape(batch, seq, heads * dim) @ p["self_attn_out_weight"].T


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
    """p = softmax(W_g x) over all experts; the top-k of p are chosen; their
    weights are p at the chosen over their sum (``norm_topk_prob``); the sum
    of outputs runs over the held experts among the chosen.  Every held
    expert is computed for every token and masked by its weight (zero where
    it was not chosen).

    Top-k is discontinuous.  Where a token's k-th and (k+1)-th probabilities
    lie within float32's rounding of each other, either is the top-k, the
    two sides' arithmetic decides differently, and the token's logits then
    differ by far more than rounding.  So the choice is verified, not
    replayed: given ``theirs``, the experts the program chose for these
    tokens (``_program_choices``: the forward check of ``run.py``), where
    every expert it chose for a token scores here no less than this side's
    k-th best less ``ROUTING_TIE``, its choice is a top-k of these scores
    and is the one followed; a choice that is not (a missing softmax, a
    repeated expert) is not followed and shows as the error it is.  Scores
    and weights stay this side's."""
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
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)

    @_recomputed
    def expert(rows, w1, w3, w2):
        # stacked as the program keeps them: (expert, d, h) up and gate,
        # (expert, h, d) down
        return (_silu(rows @ w1) * (rows @ w3)) @ w2

    out = jnp.zeros_like(tokens)
    for e in range(count):
        y = expert(tokens, *(p["mlp_expert_w%d" % n][e] for n in (1, 3, 2)))
        out = out + weights[:, first + e:first + e + 1] * y
    return out.reshape(x.shape)


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
        def run(h, p):
            a = _rms_norm(h, p["input_layernorm_gamma"], eps)
            h = h + _attention(cfg, p, a, kind)
            f = _rms_norm(h, p["post_attention_layernorm_gamma"], eps)
            return h + _routed(cfg, p, f, theirs.get(i))
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


# All readings: my chip runs, PR 30 (PERF.md sections 4 and 6): the program on
# seven seeds at the file's sizes, ``control.py``'s fp8 control on three.
# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward against the reference, both at full
#   float32 matmul precision: 5.1e-7 to 5.9e-7.  A part left out, another
#   window, no YaRN move the logits by 0.034 to 0.25 at the published widths
#   (CPU, rows of 2048), and so does a token routed otherwise: 0.013 to 0.023
#   on two seeds in nine while the rotary tables were the device's own
#   float32 cosines (``_table``).
# ROUTING_TIE (above): how far under this side's k-th best probability an
#   expert the program chose may score and still be followed (``_routed``).
#   The two sides' hidden states part by 2e-7 to 2e-6 of their range, a
#   probability of 0.016 by some 1e-8; neighbours in a token's ranking lie
#   5e-4 apart, so a wrongly routed token passes as a tie twice in a thousand.
# step_loss: |first step's loss - reference loss| over the reference loss:
#   2.9e-7 to 7.3e-6.  At the first step the loss is ln(12288) at any
#   precision (the fp8 control reads 3e-5 to 9e-5), so this holds the loss's
#   own arithmetic; lfm2_moe.py's limit.
# steps_loss: the worst of three steps' losses, relative: the program 6.4e-4
#   to 6.7e-4 (step 3), the fp8 control 6.7e-4 to 7.5e-4: the loss falls 9.88
#   -> 9.18 -> 8.47 and two updates tell the precisions apart no better.
#   Held at lfm2_moe.py's limit, 22 times the program's, against a step that
#   does not train.
# first_grad_norm: worst leaf, the gap between the norm of the first
#   gradient as Adam got it and the reference's: the program 3.3e-4 to
#   1.2e-3 (mean 7.5e-4; a router's weights mostly), the fp8 control 4.0e-3,
#   7.0e-3, 7.1e-3.  The limit lies between, 2.5 times the program's largest
#   and three quarters of the control's smallest: the number the lower
#   precision fails, on every seed of the control.
# param_change_norm: worst leaf, the same of the change after two steps: the
#   program 0.0619 to 0.0622 on every seed, always the token embedding (0.023
#   when its rows were drawn at 1.0, not 3.0); no precision moves it (fp8
#   7e-3 at another leaf).  A row's gradient is of epsilon's own size there
#   (the loss is a mean over 8192 tokens and RMSNorm divides by the row's
#   norm), and ``adam_update`` keeps epsilon beside the uncorrected root of
#   the second moment where Kingma and Ba's Adam, the reference's, has it
#   beside the corrected one (PERF.md section 7, ROADMAP D12): the same
#   steady gap as OPT's 0.7 % and LFM2's 1.3 %, larger.  Three times the
#   reading, against a step that returns its state (1.0) or a wrong rate.
# *_rehearsal: at d64 over 256 positions (this sandbox, one seed) the first loss reads
#   up to 1e-5 and the three up to 1.4e-5, 3.9e-3 and 1.1e-2; the control
#   flow is what is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 4e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 1.5e-2, "first_grad_norm": 3e-3,
             "param_change_norm": 0.2,
             "steps_loss_rehearsal": 1e-3, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.1}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def held_share(cfg):
    """The share of a token's expert assignments that land on this chip's
    experts when the router is balanced: held over published."""
    return cfg["num_experts"] / cfg["num_experts_published"]


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: a layer's q and out
    (d * H * D each) and k, v (d * G * D each), its router d * E and the
    *expected* held share of its top-k experts, k * held/E * 3 d h, and the
    head over the rows held.  The token table is a gather: it does not
    count."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    q_width = cfg["num_attention_heads"] * dim
    kv_width = cfg["num_key_value_heads"] * dim
    expert = 3 * d * cfg["moe_intermediate_size"]
    layer = (2 * d * q_width + 2 * d * kv_width
             + d * cfg["num_experts_published"]
             + cfg["num_experts_per_tok"] * held_share(cfg) * expert)
    return cfg["num_hidden_layers"] * layer + _vocab(cfg) * d


def mean_keys(cfg, kind, seq):
    """Keys a query sees on average over a row of ``seq`` tokens, as the
    layer type's mask leaves them: query t sees min(t + 1, window)."""
    window = cfg["sliding_window"] if kind == "sliding_attention" else seq
    window = min(window, seq)
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches, plus attention's own two products (Q K^T and P V)
    over the keys each layer's mask leaves a query, 2 * 2 * H * D a key,
    times 3 for forward and backward.  The experts count at the balanced
    router's expected load, whatever a run's router does; what the flash
    backward recomputes and the blocks a kernel visits beyond the mask are
    overhead, not model work."""
    per_key = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    keys = sum(mean_keys(cfg, kind, traffic["seq_len"])
               for kind in cfg["layer_types"])
    return 6.0 * matmul_params(cfg) + 3.0 * per_key * keys
