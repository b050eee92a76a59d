"""Kimi-VL-A3B's decoder (Moonshot AI, ``moonshotai/Kimi-VL-A3B-Instruct``;
the published description is the language model's part of its
``config.json``, a DeepSeek-V3-style decoder, the Moonlight 16B-A3B one, and
the equations of HF ``modeling_deepseek.py`` / ``modeling_kimi_vl.py``) for
the chip benchmark, text only, as one chip's share of an 8-chip
expert-parallel deployment.

* ``build``      the model through the repo's own layers (the system under
                 test): ``gluon.nn.RMSNorm``, ``LatentAttention`` (16 heads,
                 a K/V latent of 512, keys of 128 + 64 rotary channels over
                 values of 128, one rotary key shared by the heads),
                 ``GatedMLP`` in the leading dense layer and
                 ``parallel.ExpertParallelMoE`` with ``dispatch="grouped"``
                 holding ``n_routed_experts`` of the router's
                 ``n_routed_experts_published`` experts beside a shared
                 expert (``shared_hidden_size``).  Nothing is defined here
                 but how they are stacked.
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published equations, sharing no code with the program:
                 no kernel, no sort; attention as an explicit (S, S) causal
                 mask, a few heads at a time, a head's score written as the
                 sum of its two dot products (the head's own channels, and
                 its rotary channels against the ONE rotary key); every held
                 expert computed for every token and masked; the shared
                 expert added once; what experts 8-63 would add left out, as
                 in the program (no code stands in for the other chips).
                 One record of the program is read: which experts its last
                 eager forward chose, to be verified against this side's
                 scores where two of them tie (``_routed``).
* ``train_flops_per_sample``  model FLOPs of one token, forward and backward.

Layer l: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``.  Attn:
``q = W_q x`` (H heads of nope + rope channels), ``[c ; k_pe] = W_kva x``
(the latent and one rotary key), ``[k_nope ; v] = W_kvb RMSNorm(c)`` (H heads
of nope + v channels), rotary positions on ``q_pe`` and ``k_pe`` alone,
``o_h = softmax((q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope) +
causal) v_h``, ``W_o``.  FFN_l is a SwiGLU MLP below ``first_k_dense_replace``
and above it ``sum_k w_k E_{i_k}(x) + S(x)``: ``s = sigmoid(W_g x)`` in
float32, the top-k of ``s + b`` (``b`` a buffer), weights ``s`` at the chosen
over their sum, times ``routed_scaling_factor``; ``S`` one SwiGLU of
``n_shared_experts * moe_intermediate_size``, unweighted.  One RMSNorm after
the last layer, then the head's own matrix.  No bias.  The sizes come from
the JSON beside this file.
"""
import math
import weakref

PREFIX = "kimivl_"
NORM_EPS_TOPK = 1e-6            # beside the chosen scores' sum (HF: 1e-20)
# Selection scores closer than this are a tie in float32 (``_routed``): the
# scores are sigmoids in (0, 1) of a 2048-term float32 product plus a bias of
# order 0.05, reached by different arithmetic on the two sides (lfm2_moe.py's
# limit, for the same scores; readings at TOLERANCE below).
ROUTING_TIE = 1e-5
HEADS_A_BLOCK = 8               # heads whose (S, S) scores coexist
_built = None                   # the last model built, weakly (``_routed``)


def _held(cfg):
    """(first, count) of the experts this share holds."""
    return 0, cfg["n_routed_experts"]


def _vocab(cfg):
    return cfg[cfg["input"]["vocab_key"]]


def _shared_width(cfg):
    return cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def _checked(cfg):
    """The file says some numbers twice, under the published key and under
    the name ``layer_metrics/moe.py`` reads: they are one number."""
    twice = (("num_experts", "n_routed_experts"),
             ("num_experts_published", "n_routed_experts_published"),
             ("num_dense_layers", "first_k_dense_replace"))
    for alias, key in twice:
        if cfg[alias] != cfg[key]:
            raise ValueError("%s (%r) is %s (%r) under the readers' name"
                             % (alias, cfg[alias], key, cfg[key]))
    if (cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None
            or cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["moe_layer_freq"] != 1 or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid"):
        raise ValueError("no query latent, plain rotary positions, no bias, "
                         "a head of its own, every layer after the dense "
                         "ones routed by sigmoid scores in one group, as "
                         "Kimi-VL-A3B's decoder has them")


def _rotary_scaling(cfg):
    """The plain frequencies handed over as a scaling of factor 1, which
    makes the table of cosines and sines on the host: the device's own
    float32 cosine at thousands of positions parts two compilations of one
    formula by 1e-2 (PERF.md, PR 30)."""
    return {"factor": 1.0, "attention_factor": 1.0,
            "original_max_position": cfg["max_position_embeddings"]}


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

    def feed_forward(routed):
        if not routed:
            return nn.GatedMLP(d, cfg["intermediate_size"], prefix="mlp_")
        # ep_axis None: the cell's mesh is one chip's, and the share is
        # held whole on it
        return ExpertParallelMoE(
            cfg["moe_intermediate_size"], cfg["n_routed_experts_published"],
            top_k=cfg["num_experts_per_tok"], dispatch="grouped",
            experts_held=_held(cfg), router="sigmoid",
            selection_bias=mx.init.Normal(cfg["expert_bias_std"]),
            norm_topk=cfg["norm_topk_prob"],
            scaling=cfg["routed_scaling_factor"], gated=True, in_units=d,
            shared_hidden_size=_shared_width(cfg) or None, ep_axis=None,
            prefix="mlp_")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, routed, **kwargs):
            super().__init__(**kwargs)
            self._routed = routed
            with self.name_scope():
                self.input_layernorm = nn.RMSNorm(
                    epsilon=eps, in_channels=d, prefix="input_layernorm_")
                self.self_attn = nn.LatentAttention(
                    d, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                    cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"], rotary_base=float(cfg["rope_theta"]),
                    latent_norm_epsilon=cfg["latent_norm_eps"],
                    rotary_scaling=_rotary_scaling(cfg), prefix="self_attn_")
                self.post_attention_layernorm = nn.RMSNorm(
                    epsilon=eps, in_channels=d,
                    prefix="post_attention_layernorm_")
                self.mlp = feed_forward(routed)

        def hybrid_forward(self, F, x):
            h = x + self.self_attn(self.input_layernorm(x))
            f = self.post_attention_layernorm(h)
            if self._routed:            # the routed layer takes (tokens, d)
                f = F.reshape(self.mlp(F.reshape(f, shape=(-1, d))),
                              shape=h.shape)
            else:
                f = self.mlp(f)
            return h + f

    class KimiVLDecoderLM(gluon.HybridBlock):
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
                    for i in range(cfg["num_hidden_layers"]):
                        self.layers.add(DecoderLayer(
                            i >= cfg["first_k_dense_replace"],
                            prefix="%d_" % i))
                self.norm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                       prefix="norm_")
                self.head = nn.Dense(_vocab(cfg), flatten=False, in_units=d,
                                     use_bias=False, prefix="lm_head_")

        def hybrid_forward(self, F, tokens):
            return self.head(self.norm(self.layers(self.embed(tokens))))

    net = KimiVLDecoderLM(prefix=PREFIX)
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
    backward pass runs ``fn`` again.  A memory policy, not mathematics (at
    4096 tokens eight heads' scores are 537 MB and a layer's held experts
    0.9 GB, beside two gradient trees).  Written out as a differentiation
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


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _gated_mlp(x, gate, up, down):
    """SwiGLU with (out, in) weights: down (silu(gate x) * up x)."""
    return (_silu(x @ gate.T) * (x @ up.T)) @ down.T


SPLIT = 128                     # positions a row of ``_table``'s fine part


def _table(theta, seq, dim):
    """(cos, sin) of ``position * theta^(-2i/dim)``, (S, dim/2) float32
    each.  Frequencies and angles are taken with numpy in float64 (a TPU's
    float32 cosine of an angle of thousands of radians is good to a
    hundredth of a radian); so that no table of S rows enters the compiled
    reference as a constant, position SPLIT * a + b is turned by angle A_a
    and then by angle B_b, each from a short float64 table."""
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
    """Rotary positions on (B, S, heads, R), rotate-half over the R
    channels: channel i pairs with i + R/2 and turns by position *
    theta^(-2i/R) (the file's assumed.rotary_layout)."""
    import jax.numpy as jnp
    seq, dim = x.shape[1], x.shape[-1]
    cos, sin = (jnp.concatenate([t, t], -1)[:, None]
                for t in _table(theta, seq, dim))
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _latent_norm(cfg, latent, gain):
    """The RMSNorm on the K/V latent, with its own gain and epsilon."""
    return _rms_norm(latent, gain, cfg["latent_norm_eps"])


def _score_scale(cfg):
    """One over the root of a key's whole width, rotary channels included."""
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _shared_key(k_pe, heads):
    """The one rotary key (B, S, R) as each of ``heads`` heads sees it: the
    same, (B, S, heads, R)."""
    import jax.numpy as jnp
    return jnp.broadcast_to(k_pe[:, :, None], k_pe.shape[:2] + (heads,)
                            + k_pe.shape[2:])


def _attention(cfg, p, x):
    """Latent attention from the equations of the module docstring,
    ``HEADS_A_BLOCK`` heads at a time, recomputed in the backward pass
    (``_recomputed``): a head's (S, S) scores are 67 MB at S 4096."""
    import jax
    import jax.numpy as jnp
    heads, latent = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, theta = cfg["v_head_dim"], cfg["rope_theta"]
    batch, seq, _ = x.shape

    q = (x @ p["self_attn_q_weight"].T).reshape(batch, seq, heads,
                                                nope + rope)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], theta)
    down = x @ p["self_attn_kv_a_weight"].T             # (B, S, latent+rope)
    k_pe = _rotate(down[:, :, None, latent:], theta)[:, :, 0]    # (B, S, R)
    c = _latent_norm(cfg, down[..., :latent], p["self_attn_kv_norm_gamma"])
    kv = (c @ p["self_attn_kv_b_weight"].T).reshape(batch, seq, heads,
                                                    nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    i = jnp.arange(seq)
    seen = i[None, :] <= i[:, None]                     # key j <= query i
    scale = _score_scale(cfg)

    @_recomputed
    def block(qn, qp, kn, kp, vb):      # (B, S, n, .) each; kp (B, S, n, R)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
                  + jnp.einsum("bqhr,bkhr->bhqk", qp, kp)) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vb)

    n = min(HEADS_A_BLOCK, heads)
    shared = _shared_key(k_pe, heads)
    ctx = jnp.concatenate([
        block(*(t[:, :, h:h + n] for t in (q_nope, q_pe, k_nope, shared, v)))
        for h in range(0, heads, n)], axis=2)
    return ctx.reshape(batch, seq, heads * vdim) @ p["self_attn_out_weight"].T


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
        for i in range(cfg["first_k_dense_replace"],
                       cfg["num_hidden_layers"]):
            chosen = getattr(net.layers[i].mlp, "last_chosen", None)
            if chosen is not None and chosen.shape[0] == tokens:
                found[i] = chosen
    return found


def _routed(cfg, p, x, theirs=None):
    """s = sigmoid(W_g x); the top-k of s + b are chosen; their weights are
    s at the chosen over (their sum + 1e-6), times the scaling factor; the
    sum runs over the held experts among the chosen; the shared expert is
    added once, unweighted (not where ``n_shared_experts`` is 0).  Every
    held expert is computed for every token and masked by its weight (zero
    where it was not chosen).

    Top-k is discontinuous, so the program's choice is verified, not
    replayed (``lfm2_moe.py::_routed`` has the reasons): given ``theirs``,
    where every expert the program chose for a token scores here no less
    than this side's k-th best less ``ROUTING_TIE`` and none is chosen
    twice, its choice is followed; scores and weights stay this side's."""
    import jax
    import jax.numpy as jnp
    first, count = _held(cfg)
    experts = cfg["n_routed_experts_published"]
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
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + NORM_EPS_TOPK)
    weights = weights * cfg["routed_scaling_factor"]

    @_recomputed
    def experts(rows, w1, w3, w2, share):
        # stacked as the program keeps them: (expert, d, h) gate and up,
        # (expert, h, d) down; every held expert over every token in one
        # batched product (eight products apart compile to eight times the
        # code, and this program has to fit the compile cache: PERF.md
        # section 6, PR 32), each masked by its weight
        hidden = (_silu(jnp.einsum("td,edh->eth", rows, w1))
                  * jnp.einsum("td,edh->eth", rows, w3))
        y = jnp.einsum("eth,ehd->etd", hidden, w2)
        return (y * share.T[:, :, None]).sum(0)

    out = experts(tokens, *(p["mlp_expert_w%d" % n] for n in (1, 3, 2)),
                  weights[:, first:first + count])
    if cfg["n_shared_experts"]:
        out = out + _recomputed(_gated_mlp)(
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

    def layer(i):
        def run(h, p):
            a = _rms_norm(h, p["input_layernorm_gamma"], eps)
            h = h + _attention(cfg, p, a)
            f = _rms_norm(h, p["post_attention_layernorm_gamma"], eps)
            if i < cfg["first_k_dense_replace"]:
                return h + _gated_mlp(
                    f, *(p["mlp_w%d_weight" % n] for n in (1, 3, 2)))
            return h + _routed(cfg, p, f, theirs.get(i))
        return _recomputed(run)

    h = params["embed_tokens_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        lp = "layers_%d_" % i
        h = layer(i)(h, {n[len(lp):]: v for n, v in params.items()
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


# All readings: my chip runs, PR 32 (PERF.md sections 4 and 6): the program on
# the seeds named there at the file's sizes, ``control.py``'s two controls on
# three (taken while ``_routed`` still looped over the experts one product
# at a time: the same sums in another order).
# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward against the reference, both at full
#   float32 matmul precision: 4.2e-7 to 5.3e-7 on thirteen seeds.  A departure
#   of the mathematics reads far more (tests/test_kimivl_chip_bench.py at the
#   rehearsal's widths: the latent norm left out, the rotary key kept from
#   all heads but one, the scale at the root of 128, the shared expert
#   dropped, no scaling factor, raw scores: 2.6e-2 to 0.58), and so does a
#   token routed otherwise (lfm2_moe.py: 0.08 through one tie).
# ROUTING_TIE (above): how far under this side's k-th best score an expert
#   the program chose may score and still be followed: lfm2_moe.py's, for the
#   same sigmoid scores of a 2048-term product.
# step_loss: |first step's loss - reference loss| over the reference loss:
#   7e-7 to 3.4e-5.  At the first step the loss is ln(20480) + 0.4 at any
#   precision (the fp8 control reads 2.1e-5 to 1.2e-4), so this holds the
#   loss's own arithmetic; the accepted routed cells' limit, 12 times the
#   largest reading.
# steps_loss: the worst of three steps' losses, relative; the loss falls 10.3
#   -> 7.9 -> 5.4, so the third step shows what two updates were worth: the
#   program 1.47e-3 to 1.81e-3, the reference with fp8 matrix products in its
#   place 8.13e-3, 8.17e-3, 8.83e-3.  The limit lies between, 2.2 times the
#   program's largest and half the control's smallest: the number the lower
#   precision fails, on every seed of the control.
# first_grad_norm: worst leaf, the gap between the norm of the first
#   gradient as Adam got it and the reference's: the program 1.6e-3 to
#   6.4e-3, a router's weights on four seeds in five (470 of a row's 4096
#   tokens are routed otherwise in bf16 than in float32 in some layer, 106 on
#   a held expert); the fp8 control 4.5e-3, 9.4e-3, 1.4e-2: they overlap, so
#   it is held at 2.4 times the program's largest, against a gradient that
#   lost a part of the batch, an expert or a factor.
# param_change_norm: worst leaf, the same of the change after two steps: the
#   program 0.0480 to 0.0488 on every seed, always layers_4_self_attn_q_weight;
#   no precision moves it (fp8 0.010 to 0.016 at an expert's weights, bf16
#   moments 2e-7).  The steady gap of ``adam_update``'s epsilon beside the
#   uncorrected root where a gradient is of epsilon's own size (mellum2_moe.py
#   has the reasons; ROADMAP D12).  Three times the reading, against a step
#   that returns its state (1.0) or a wrong rate.
# *_rehearsal: at d64 over 256 positions (this sandbox, seeds 3, 11, 12) the
#   first loss reads up to 6e-6 and the three up to 7.1e-6, 1.0e-2 and 9.8e-3;
#   the control flow is what is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 4e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 4e-3, "first_grad_norm": 1.5e-2,
             "param_change_norm": 0.15,
             "steps_loss_rehearsal": 1e-3, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.1}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def held_share(cfg):
    """The share of a token's expert assignments that land on this chip's
    experts when the router is balanced: held over published."""
    return cfg["n_routed_experts"] / cfg["n_routed_experts_published"]


def attention_params(cfg):
    """Matmul parameters of one latent-attention layer: W_q, W_kva, W_kvb,
    W_o (the latent norm's gain is no matmul)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    latent, vdim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (d * heads * (nope + rope) + d * (latent + rope)
            + latent * heads * (nope + vdim) + heads * vdim * d)


def matmul_params(cfg):
    """Parameters a token's matmuls touch on this chip: every layer's
    attention, the dense layers' MLP 3 d f, a routed layer's shared expert
    3 d s, its router d * E and the *expected* held share of its top-k
    experts, k * held/E * 3 d h, and the head over the rows held.  The
    token table is a gather: it does not count."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (3 * d * _shared_width(cfg)
              + d * cfg["n_routed_experts_published"]
              + cfg["num_experts_per_tok"] * held_share(cfg) * expert)
    return (layers * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * routed + _vocab(cfg) * d)


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter it touches, plus attention's own two products over the causal
    mean of (S + 1) / 2 keys a query: Q K^T at the key's whole width and
    P V at the value's, 2 * H * (nope + rope) + 2 * H * v a key, times 3 for
    forward and backward, every layer.  The experts count at the balanced
    router's expected load; what the flash backward recomputes, the padded
    lanes of a key of 192 and the blocks a kernel visits beyond the mask are
    overhead, not model work."""
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    keys = (traffic["seq_len"] + 1) / 2.0
    return (6.0 * matmul_params(cfg)
            + 3.0 * per_key * keys * cfg["num_hidden_layers"])
