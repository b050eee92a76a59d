"""Trinity-Mini's decoder (Arcee AI, ``arcee-ai/Trinity-Mini``, 26B-A3B;
``model_type: afmoe``; the published description is its ``config.json`` and
the equations of HF ``modeling_afmoe.py`` with Arcee's account of AFMoE) for
the chip benchmark, as one chip's share of a 16-chip expert-parallel
deployment.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.Embedding`` and the constant ``sqrt(d)``
                 on its rows, four ``RMSNorm`` a layer,
                 ``MultiHeadAttention`` (32 query heads over 4 K/V heads of
                 128, ``qk_norm``, ``gate``; ``window=`` and rotary
                 positions on the sliding layers, neither on the full ones),
                 ``GatedMLP`` in the leading dense layer and
                 ``parallel.ExpertParallelMoE`` with ``dispatch="grouped"``
                 holding ``num_experts`` of the router's
                 ``num_experts_published`` experts beside a shared expert.
                 Nothing is defined here but how they are stacked.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published equations, sharing no code with the program:
                 no kernel, no sort; attention as an explicit (S, S) mask a
                 layer kind, one K/V head's query heads at a time; rotary
                 positions from their formula, the angles taken on the host;
                 every held expert computed for every token and masked; the
                 shared expert whole, added once; what experts 8-127 would
                 add left out, as in the program (no code stands in for the
                 other chips).  One record of the program is read: which
                 experts its last eager forward chose, to be verified
                 against this side's scores where two of them tie
                 (``_routed``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

``x_0 = E[tokens] * sqrt(d)``.  Layer l of kind ``layer_types[l]``, with four
RMSNorms of their own gains::

    a = RMSNorm_in(x)
    q = RMSNorm_q(W_q a), k = RMSNorm_k(W_k a)   head by head over D
    v = W_v a,  g = sigmoid(W_g a)               g as wide as q: H * D
    sliding_attention: rotate-half rotary positions on q and k, theta
        ``rope_theta``; query i sees keys j with i - sliding_window < j <= i
    full_attention: no positions at all; query i sees keys j <= i
    o_h = softmax(q_h k_{h // (H/G)}^T / sqrt(D) + mask) v_{h // (H/G)}
    h = x + RMSNorm_post_attn(W_o (g * [o_1 ... o_H]))
    y = h + RMSNorm_post_mlp(F(RMSNorm_pre_mlp(h)))

``F`` is a SwiGLU of ``intermediate_size`` below ``num_dense_layers`` and
above it ``Shared(m) + sum_k w_k E_{i_k}(m)``: ``s = sigmoid(W_r m)`` in
float32 over all experts, the top-k of ``s + b`` (``b`` a buffer), weights
``s`` at the chosen over their sum (+ 1e-20) times ``route_scale``; every
expert and the shared one a SwiGLU of ``moe_intermediate_size``.  One RMSNorm
after the last layer, then the head's own matrix.  No bias.  The sizes come
from the JSON beside this file, which lists under ``assumed`` every line
above that the config's keys do not pin.

Departures from the published description, each also in the file's
``assumed``: no auxiliary load-balancing loss (``load_balance_coeff``) and no
update of the selection bias; norm gains start at one; the sum of the chosen
scores is over all eight whether the expert is held or not, and only the held
experts' outputs are added; the program's router keeps 1e-6 beside that sum
where the published code has 1e-20 (2.5e-7 of a weight: the sum of eight
sigmoids is about 4), this side the published 1e-20; rotary cosines and sines
are float32 products of float64 host tables on both sides (a TPU's own cosine
of thousands of radians is good to a hundredth, PERF.md PR 30).
"""
import math
import weakref

PREFIX = "trinitymini_"
NORM_EPS_TOPK = 1e-20           # beside the chosen scores' sum, as published
# Selection scores closer than this are a tie in float32 (``_routed``).  The
# scores are sigmoids of a 2048-term float32 product (logits about 0.9 wide
# under Normal(0.02) router weights on a normalised token) plus a bias of
# order 0.05; the two sides reach them by different arithmetic and part by
# about 1e-7.  Around the eighth best of 128 such scores neighbours lie
# about 9e-3 apart (1 / (128 x the scores' density of 0.8 there)), so of a
# row's 4096 x 4 choices some 17 have their eighth and ninth within 1e-5,
# and a wrongly routed token passes as a tie about once in a thousand:
# lfm2_moe.py's and kimi_vl_moe.py's limit, for the same kind of score.
ROUTING_TIE = 1e-5
_built = None                   # the last model built, weakly (``_routed``)

KINDS = ("sliding_attention", "full_attention")


def _held(cfg):
    """(first, count) of the experts this share holds."""
    return 0, cfg["num_experts"]


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def _checked(cfg):
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types must name sliding_attention or "
                         "full_attention for each of the %d layers"
                         % cfg["num_hidden_layers"])
    if (cfg["score_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["rope_scaling"] is not None
            or cfg["tie_word_embeddings"]):
        raise ValueError("sigmoid scores in one group, plain rotary "
                         "positions and a head of its own, as Trinity-Mini "
                         "has them")


def _positions(cfg, kind):
    """The rotary base of a layer kind, or None where it has no positions:
    the sliding layers turn q and k, the full ones do not."""
    return float(cfg["rope_theta"]) if kind == "sliding_attention" else None


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

    def attention(kind):
        base = _positions(cfg, kind)
        # the plain frequencies as a scaling of factor 1: the table of
        # cosines and sines is then made on the host (module docstring)
        rotary = {} if base is None else {
            "rotary_base": base, "rotary_scaling": {
                "factor": 1.0, "attention_factor": 1.0,
                "original_max_position": cfg["max_position_embeddings"]}}
        return nn.MultiHeadAttention(
            d, cfg["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], qk_norm=True, qk_norm_epsilon=eps,
            gate=True, window=(cfg["sliding_window"]
                               if kind == "sliding_attention" else None),
            prefix="self_attn_", **rotary)

    def feed_forward(routed):
        if not routed:
            return nn.GatedMLP(d, cfg["intermediate_size"], prefix="mlp_")
        # ep_axis None: the cell's mesh is one chip's, and the share is
        # held whole on it
        shared = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
        return ExpertParallelMoE(
            cfg["moe_intermediate_size"], cfg["num_experts_published"],
            top_k=cfg["num_experts_per_tok"], dispatch="grouped",
            experts_held=_held(cfg), router="sigmoid",
            selection_bias=mx.init.Normal(cfg["expert_bias_std"]),
            norm_topk=cfg["route_norm"], scaling=cfg["route_scale"],
            gated=True, in_units=d, shared_hidden_size=shared or None,
            ep_axis=None, prefix="mlp_")

    def norm(name):
        return nn.RMSNorm(epsilon=eps, in_channels=d, prefix=name + "_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, kind, routed, **kwargs):
            super().__init__(**kwargs)
            self._routed = routed
            with self.name_scope():
                self.input_layernorm = norm("input_layernorm")
                self.self_attn = attention(kind)
                self.post_attention_layernorm = norm(
                    "post_attention_layernorm")
                self.pre_mlp_layernorm = norm("pre_mlp_layernorm")
                self.mlp = feed_forward(routed)
                self.post_mlp_layernorm = norm("post_mlp_layernorm")

        def hybrid_forward(self, F, x):
            h = x + self.post_attention_layernorm(
                self.self_attn(self.input_layernorm(x)))
            f = self.pre_mlp_layernorm(h)
            if self._routed:            # the routed layer takes (tokens, d)
                f = F.reshape(self.mlp(F.reshape(f, shape=(-1, d))),
                              shape=h.shape)
            else:
                f = self.mlp(f)
            return h + self.post_mlp_layernorm(f)

    class TrinityDecoderLM(gluon.HybridBlock):
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
                        self.layers.add(DecoderLayer(
                            kind, i >= cfg["num_dense_layers"],
                            prefix="%d_" % i))
                self.norm = norm("norm")
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False, prefix="lm_head_")

        def hybrid_forward(self, F, tokens):
            x = self.embed(tokens)
            if cfg["mup_enabled"]:
                x = x * math.sqrt(d)
            return self.head(self.norm(self.layers(x)))

    net = TrinityDecoderLM(prefix=PREFIX)
    # The selection bias is a buffer and no gradient reaches it; it is handed
    # to the optimizer all the same, as lfm2_moe.py hands its own:
    # chipbench/first_steps.py reads every parameter's gradient out of the
    # optimizer's state.  Its gradient is exactly zero and Adam without decay
    # leaves it where it is.
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
    """``fn`` with nothing kept for its backward pass but its arguments: the
    backward pass runs ``fn`` again.  A memory policy, not mathematics, for
    a whole layer at a time (at 4096 tokens a layer's scores are 2.1 GB and
    its held experts' hidden rows 0.3 GB, beside two gradient trees; inside
    a layer nothing is recomputed a second time: every product staged twice
    over is a megabyte more of a program that has to fit the compile
    cache).  Written out as a differentiation
    rule instead of ``jax.checkpoint`` because the control
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


def _head_norm(x, gain, eps):
    """QK-norm: the RMSNorm of one head's D channels of (B, S, heads, D),
    the same D gains for every head."""
    return _rms_norm(x, gain, eps)


def _branch_out(out, gain, eps):
    """What a branch (attention, or the MLP) hands the residual stream: its
    output under an RMSNorm of its own."""
    return _rms_norm(out, gain, eps)


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _gate(logits):
    """sigmoid(W_g a): one value a channel of every head, of the layer's
    normalised input."""
    import jax.numpy as jnp
    return 1.0 / (1.0 + jnp.exp(-logits))


def _side_by_side(x, *weights):
    """``x W^T`` for each of the (out, in) ``weights``, made as ONE product
    with the weights laid side by side: the same sums, and a third of the
    matrix products for the compiler (a float32 product at full precision is
    about a megabyte of the chip's code, and this program has to fit the
    compile cache beside the step: PERF.md section 6, PR 32 and PR 40)."""
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


def _rotate(x, theta):
    """Rotary positions on (B, S, heads, D), rotate-half over the whole
    head: channel i pairs with i + D/2 and turns by position *
    theta^(-2i/D).  ``theta`` None: no positions, ``x`` as it is."""
    import jax.numpy as jnp
    if theta is None:
        return x
    seq, dim = x.shape[1], x.shape[-1]
    cos, sin = (jnp.concatenate([t, t], -1)[:, None]
                for t in _table(theta, seq, dim))
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


def _attention(cfg, p, a, kind):
    """The gated grouped-query attention of the module docstring on the
    layer's normalised input ``a`` (B, S, d), the query heads of one K/V
    head at a time: a head's (S, S) scores are 67 MB at S 4096, and the
    layer is recomputed in the backward pass (``reference``)."""
    import jax
    import jax.numpy as jnp
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    batch, seq, _ = a.shape
    theta = _positions(cfg, kind)
    q, k, v, gate = _side_by_side(a, *(
        p["self_attn_%s_weight" % n] for n in ("q", "k", "v", "gate")))

    def by_head(t, n):
        return t.reshape(batch, seq, n, dim)

    q = _rotate(_head_norm(by_head(q, heads), p["self_attn_q_norm_gamma"],
                           eps), theta)
    k = _rotate(_head_norm(by_head(k, kv), p["self_attn_k_norm_gamma"], eps),
                theta)
    v = by_head(v, kv)
    seen = _mask(cfg, kind, seq)

    def group(qb, kg, vg):              # (B, S, n, D), (B, S, D), (B, S, D)
        scores = jnp.einsum("bqhd,bkd->bhqk", qb, kg) / math.sqrt(dim)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", probs, vg)

    per = heads // kv                   # query heads a K/V head serves
    ctx = jnp.concatenate(
        [group(q[:, :, g * per:(g + 1) * per], k[:, :, g], v[:, :, g])
         for g in range(kv)], axis=2).reshape(batch, seq, heads * dim)
    return (_gate(gate) * ctx) @ p["self_attn_out_weight"].T


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
        for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"]):
            chosen = getattr(net.layers[i].mlp, "last_chosen", None)
            if chosen is not None and chosen.shape[0] == tokens:
                found[i] = chosen
    return found


def _routed(cfg, p, x, theirs=None):
    """s = sigmoid(W_r x); the top-k of s + b are chosen; their weights are
    s at the chosen over (their sum + 1e-20) where ``route_norm``, times
    ``route_scale``; the sum runs over the held experts among the chosen;
    the shared expert is added once, unweighted (not where
    ``num_shared_experts`` is 0).  Every held expert is computed for every
    token and masked by its weight (zero where it was not chosen).

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
    scores = jax.nn.sigmoid(tokens @ p["mlp_gate_weight"])
    choose = jax.lax.stop_gradient(
        scores + jax.lax.stop_gradient(p["mlp_expert_bias"]))
    best, chosen = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    if theirs is not None:
        fair = (jnp.take_along_axis(choose, theirs, axis=1)
                >= best[:, -1:] - ROUTING_TIE).all(-1)
        fair &= (jax.nn.one_hot(theirs, experts).sum(1) <= 1).all(-1)
        chosen = jnp.where(fair[:, None], theirs, chosen)
    picked = jax.nn.one_hot(chosen, experts).sum(1)         # (T, E) of 0/1
    weights = scores * picked
    if cfg["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + NORM_EPS_TOPK)
    weights = weights * cfg["route_scale"]

    def held(rows, w1, w3, w2, share):
        # stacked as the program keeps them: (expert, d, h) gate and up,
        # (expert, h, d) down; every held expert over every token in one
        # batched product, gate and up side by side (eight products apart
        # compile to eight times the code, and this program has to fit the
        # compile cache: PERF.md section 6, PR 32), each masked by its weight
        both = jnp.einsum("td,edh->eth", rows, jnp.concatenate([w1, w3], -1))
        hidden = _silu(both[..., :w1.shape[-1]]) * both[..., w1.shape[-1]:]
        y = jnp.einsum("eth,ehd->etd", hidden, w2)
        return (y * share.T[:, :, None]).sum(0)

    out = held(tokens, *(p["mlp_expert_w%d" % n] for n in (1, 3, 2)),
               weights[:, first:first + count])
    if cfg["num_shared_experts"]:
        out = out + _gated_mlp(
            tokens, *(p["mlp_shared_experts_w%d_weight" % n]
                      for n in (1, 3, 2)))
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
        def run(x, p):
            a = _rms_norm(x, p["input_layernorm_gamma"], eps)
            h = x + _branch_out(_attention(cfg, p, a, kind),
                                p["post_attention_layernorm_gamma"], eps)
            m = _rms_norm(h, p["pre_mlp_layernorm_gamma"], eps)
            if i < cfg["num_dense_layers"]:
                f = _gated_mlp(m, *(p["mlp_w%d_weight" % n]
                                    for n in (1, 3, 2)))
            else:
                f = _routed(cfg, p, m, theirs.get(i))
            return h + _branch_out(f, p["post_mlp_layernorm_gamma"], eps)
        return _recomputed(run)

    h = params["embed_tokens_weight"][tokens]
    if cfg["mup_enabled"]:
        h = h * math.sqrt(cfg["hidden_size"])
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


# All readings: my chip runs, PR 40 (PERF.md sections 4 and 6): the program on
# the seeds named there at the file's sizes, ``control.py``'s two controls on
# three.  The model draws no random numbers, so every check is made on the
# model itself.
# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward against the reference, both at full
#   float32 matmul precision: 4.2e-7 to 5.6e-7 on sixteen seeds.  A departure of
#   the mathematics reads far more (tests/test_trinitymini_chip_bench.py at
#   the rehearsal's widths: no gate, no QK-norm, positions put on the full
#   layer or taken off the sliding ones, the branches' norms or the
#   embedding's multiplier left out, the shared expert, ``route_scale``, a
#   window one key short, a part zeroed: 0.18 to 1.13), and so does a token
#   routed otherwise (lfm2_moe.py: 0.08 through one tie).
# ROUTING_TIE (above): how far under this side's k-th best score an expert
#   the program chose may score and still be followed.
# step_loss: |first step's loss - reference loss| over the reference loss:
#   2.7e-7 to 2.2e-5.  At the first step the loss is ln(25024) + 0.4 at any
#   precision (the fp8 control reads 7.7e-6 to 7.7e-5), so this holds the
#   loss's own arithmetic; the accepted routed cells' limit, 18 times the
#   largest reading.
# steps_loss: the worst of three steps' losses, relative; the loss falls 10.5
#   -> 9.0 -> 7.5, so the third step shows what two updates were worth: the
#   program 6.8e-4 to 8.4e-4 (sixteen seeds, always the third step: bf16's
#   own steady gap), the reference with fp8 matrix products in its place
#   2.31e-3, 2.49e-3, 2.52e-3.  The limit lies between, 1.5 times the
#   program's largest (14 of its standard deviations over its mean) and 0.56
#   of the control's smallest: the number the lower precision fails, on every seed
#   of the control.
# first_grad_norm: worst leaf, the gap between the norm of the first
#   gradient as Adam got it and the reference's: the program 6.6e-4 to 3.6e-3
#   (an expert's weights on fourteen seeds in sixteen: 698 of a row's 4096 tokens
#   are routed otherwise in bf16 than in float32 in some layer, 102 on a held
#   expert); the fp8 control 5.2e-3, 5.5e-3, 7.3e-3.  The two lie within a
#   factor 1.4 of each other where the program's own readings spread by a
#   factor 5.5 over seeds, so no limit between them leaves a fresh seed room:
#   held at 2.8 times the program's largest, which the control passes,
#   against a gradient that lost a part of the batch, an expert or a factor.
# param_change_norm: worst leaf, the same of the change after two steps: the
#   program 0.0193 to 0.0270, always an expert's w1, w2 or w3 (the tokens
#   bf16 routes otherwise reach or miss that expert); no precision moves it
#   (fp8 0.0057 to 0.0058 at a q or k projection, bf16 moments 2e-7 to 3e-7).
#   Between the largest reading and the 1.0 of a step that returns its state,
#   3.7 times the reading, the more room above.
# *_rehearsal: at d64 over 128 positions (this sandbox, ten seeds) the first
#   loss reads up to 2.8e-5 and the three up to 1.0e-4, 3.7e-2 and 0.125 (a
#   router's weights on one seed in ten, under 9e-3 on the other nine: two
#   rows of 128 tokens, where one token routed otherwise is a large share);
#   the control flow is what is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 4e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 1.3e-3, "first_grad_norm": 1e-2,
             "param_change_norm": 0.1,
             "steps_loss_rehearsal": 1e-3, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.3}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def held_share(cfg):
    """The share of a token's expert assignments that land on this chip's
    experts when the router is balanced: held over published."""
    return cfg["num_experts"] / cfg["num_experts_published"]


def attention_params(cfg):
    """Matmul parameters of one attention layer: W_q, W_g (the gate) and W_o
    at d x H D each, W_k and W_v at d x G D (the two head norms' gains are no
    matmul)."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    return (3 * d * cfg["num_attention_heads"] * dim
            + 2 * d * cfg["num_key_value_heads"] * dim)


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: every layer's
    attention with its gate, the dense layers' MLP 3 d f, a routed layer's
    shared expert 3 d s, its router d * E and the *expected* held share of
    its top-k experts, k * held/E * 3 d h, and the head over the rows held.
    The token table is a gather: it does not count."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["num_dense_layers"], layers)
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (cfg["num_shared_experts"] * expert
              + d * cfg["num_experts_published"]
              + cfg["num_experts_per_tok"] * held_share(cfg) * expert)
    return (layers * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * routed + _vocab(cfg) * d)


def mean_keys(cfg, kind, seq):
    """Keys a query sees on average over a row of ``seq`` tokens, as the
    layer kind's mask leaves them: query t sees min(t + 1, window)."""
    window = cfg["sliding_window"] if kind == "sliding_attention" else seq
    window = min(window, seq)
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches, plus attention's own two products (Q K^T and P V)
    over the keys each layer's mask leaves a query, 2 * 2 * H * D a key,
    times 3 for forward and backward.  The experts count at the balanced
    router's expected load, whatever a run's router does; the gate's
    element-wise product, what the flash backward recomputes and the blocks
    a kernel visits beyond the mask are not model work."""
    per_key = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    keys = sum(mean_keys(cfg, kind, traffic["seq_len"])
               for kind in cfg["layer_types"])
    return 6.0 * matmul_params(cfg) + 3.0 * per_key * keys
