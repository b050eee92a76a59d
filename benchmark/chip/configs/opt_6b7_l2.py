"""OPT decoder LM (Zhang et al., arXiv:2205.01068) for the chip benchmark.

Three things that belong to this configuration and to nothing else:

* ``build``      the model through the repo's own ``gluon`` layers (the
                 system under test): the block ``bench_transformer.py``
                 builds, at OPT's own sizes, with a bias on every projection,
                 the output head tied to the token embedding and dropout on
                 both sub-layers' outputs;
* ``reference``  the same mathematics in plain float32 ``jax.numpy`` from
                 the published description, sharing no code with ``gluon``.
                 It draws no dropout mask: it is the model of the JSON's
                 ``first_loss_with``, which the harness holds to it;
* ``train_flops_per_sample``  model FLOPs of one token, forward and
                 backward (PaLM appendix B, copied from
                 ``bench_transformer.py::model_flops_per_step``).

The sizes come from the JSON beside this file, whose keys are those of
``facebook/opt-6.7b``'s ``config.json``.
"""
import math

PREFIX = "opt_"


def build(cfg):
    """The uninitialised Gluon model.  The prefix is fixed: parameter names
    are keys of the step's argument trees and so part of the compile-cache
    key (PERF.md, PR 21)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn

    d_model, n_heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_ffn, vocab = cfg["ffn_dim"], cfg["vocab_size"]
    n_layers = cfg["num_hidden_layers"]
    positions, dropout = cfg["max_position_embeddings"], cfg["dropout"]
    if (cfg["word_embed_proj_dim"] != d_model
            or not cfg["do_layer_norm_before"]):
        raise ValueError("only OPT's pre-LayerNorm variants without an "
                         "embedding projection are built here")
    if (cfg["activation_function"] != "relu" or cfg["attention_dropout"]
            or cfg["activation_dropout"] or cfg["layerdrop"]):
        raise ValueError("ReLU, and dropout on the sub-layers' outputs "
                         "only, as facebook/opt-6.7b has them")

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.ln1 = nn.LayerNorm(in_channels=d_model)
                self.attn = nn.MultiHeadAttention(d_model, n_heads,
                                                  causal=True, use_bias=True)
                self.ln2 = nn.LayerNorm(in_channels=d_model)
                self.fc1 = nn.Dense(d_ffn, flatten=False, in_units=d_model)
                self.fc2 = nn.Dense(d_model, flatten=False, in_units=d_ffn)
                # OPTDecoderLayer: dropout on each sub-layer's output,
                # before the residual add.  It holds no parameter, so with
                # or without it the names, and the weights a seed draws,
                # are the same
                self.drop = nn.Dropout(dropout) if dropout else None

        def hybrid_forward(self, F, x):
            a = self.attn(self.ln1(x))
            x = x + (self.drop(a) if self.drop else a)
            h = F.Activation(self.fc1(self.ln2(x)), act_type="relu")
            h = self.fc2(h)
            return x + (self.drop(h) if self.drop else h)

    class OPTLM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, d_model)
                self.pos_embed = self.params.get(
                    "pos_embed", shape=(positions, d_model))
                self.layers = nn.HybridSequential(prefix="layers_")
                with self.layers.name_scope():
                    for _ in range(n_layers):
                        self.layers.add(DecoderLayer())
                self.ln_f = nn.LayerNorm(in_channels=d_model)
                # tied: the head's weight IS the embedding table, (vocab, d)
                self.head = nn.Dense(vocab, flatten=False, in_units=d_model,
                                     use_bias=False,
                                     params=self.embed.params)

        def hybrid_forward(self, F, tokens, pos_embed):
            seq = tokens.shape[1]
            pos = F.slice_axis(pos_embed, axis=0, begin=0, end=seq)
            h = self.embed(tokens) + F.expand_dims(pos, axis=0)
            h = self.layers(h)
            return self.head(self.ln_f(h))

    del mx
    return OPTLM(prefix=PREFIX)


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
    """Rows of the batch the Block's own forward is compared on."""
    return 1


# ---------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernels, no gluon
# ---------------------------------------------------------------------------

def _layer_norm(x, gamma, beta, eps=1e-5):
    import jax.numpy as jnp
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def reference(cfg, params, tokens):
    """Logits (B, S, vocab) of the OPT decoder for int tokens (B, S).

    ``params`` maps the suffix of a parameter's name after the model prefix
    to a float32 array, in the layout of the published checkpoints: a
    projection's weight is (out, in), y = x W^T + b.  Call it under
    ``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
    otherwise runs in bf16 passes.
    """
    import jax
    import jax.numpy as jnp

    n_heads = cfg["num_attention_heads"]
    p = params

    def dense(x, name):
        y = x @ p[name + "weight"].T
        return y + p[name + "bias"] if name + "bias" in p else y

    seq = tokens.shape[1]
    h = p["embedding0_weight"][tokens] + p["pos_embed"][:seq][None]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    for i in range(cfg["num_hidden_layers"]):
        lp = "layers_decoderlayer%d_" % i
        a = _layer_norm(h, p[lp + "layernorm0_gamma"],
                        p[lp + "layernorm0_beta"])
        mp = lp + "multiheadattention0_"

        def heads(x):
            b, s, e = x.shape
            return x.reshape(b, s, n_heads, e // n_heads).transpose(0, 2, 1, 3)

        q, k, v = (heads(dense(a, mp + n)) for n in ("q_", "k_", "v_"))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(h.shape)
        h = h + dense(ctx, mp + "out_")
        f = _layer_norm(h, p[lp + "layernorm1_gamma"],
                        p[lp + "layernorm1_beta"])
        h = h + dense(jax.nn.relu(dense(f, lp + "dense0_")), lp + "dense1_")
    h = _layer_norm(h, p["layernorm0_gamma"], p["layernorm0_beta"])
    return h @ p["embedding0_weight"].T


def reference_loss(logits, labels):
    """Mean softmax cross-entropy over every position (the job's loss)."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -picked.mean()


# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward against the reference, both at full
#   float32 matmul precision (the flash kernel multiplies at HIGHEST by
#   itself).  The two differ in the order of sums only; measured on the chip
#   1.6e-6 to 2.0e-6 in 13 runs (my chip runs, PR 22).  Zeroing one LayerNorm's beta in one layer moves
#   the logits by 1.8e-2 of their range, a projection bias or a layer's FFN
#   by more (tests/chip_bench/test_chip_bench_reference.py), so 1e-3 fails
#   every one of them.  At XLA's default precision the same forward is off
#   by 8e-3 (my chip run, PR 22): a lower precision fails too.
# step_loss: |first step's loss - reference loss| over the reference loss.
#   The step computes in bf16 (8 bits of mantissa) and the loss is a mean
#   over thousands of positions of values near ln(vocab), so the roundings
#   average out: measured, signed, over the seeds, standard deviation
#   1.8e-5 and at most 4.7e-5 in 19 runs of the step without dropout (my
#   chip runs, PR 22).  1e-4 is five and a half of that deviation, 0.0012
#   nats at a loss of 11.6.
# step_loss_rehearsal: the CPU rehearsal's mean is over 256 positions, not
#   8192, so the roundings average out less; it checks the control flow.
# The three below are read where the optimizer has a plain twin
# (chipbench/first_steps.py; today opt_6b7_l2_adam), on the model without
# dropout; my chip runs, PR 25: the program on 21 seeds, control.py on 3.
# steps_loss: the largest of the three steps' |loss - reference's| over the
#   reference's.  The loss falls 11.6 -> 8.6 -> 5.0, so by the third step
#   whatever was wrong in the first two updates shows: the program reads
#   7e-5 to 1.97e-3, always at step 3; the reference with fp8 matrix
#   products in its place 2.69e-2, 2.77e-2, 3.16e-2.  This is the number
#   the lower precision fails; 6e-3 is 3 times the one and under a
#   quarter of the other.
# first_grad_norm: worst leaf, the gap between the norm of the first
#   gradient as Adam got it and the reference's.  The program reads 1.5e-3
#   to 4.9e-3, the fp8 control 1.20e-2 to 1.26e-2 (at the key bias, whose
#   gradient is rounding alone): 2.5 times, too close to part them, so it
#   is held against a gradient that lost a part of the batch or a factor,
#   at three times the program's largest.
# param_change_norm: worst leaf, the same of the parameters' change after
#   two steps.  6.99e-3 to 9.63e-3, mostly at the tied embedding;
#   no precision moves it (fp8 products 1.0e-2 to 1.7e-2, bf16 moments
#   4e-6 to 1.2e-5: an Adam step is the learning rate whatever the moments
#   round to).  Held at three times the program's largest against a step
#   that returns its state unchanged (1.0), a wrong rate or a missing bias
#   correction.
# *_rehearsal: at d64 over 256 positions bf16's rounding reads 8e-5, 4.2e-2
#   and 2.2e-2 (9 seeds, this sandbox); the control flow is what is checked.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 1e-4,
             "step_loss_rehearsal": 1e-2,
             "steps_loss": 6e-3, "first_grad_norm": 1.5e-2,
             "param_change_norm": 3e-2,
             "steps_loss_rehearsal": 1e-3, "first_grad_norm_rehearsal": 0.2,
             "param_change_norm_rehearsal": 0.1}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def matmul_params(cfg):
    """Parameters that sit in a matmul: per layer 4 d^2 (q, k, v, out) and
    2 d ffn, plus the vocabulary head.  The token and position tables are
    gathers and count nothing (PaLM appendix B, non-embedding); tied or
    not, the head is a matmul and counts."""
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    return (cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f)
            + cfg["vocab_size"] * d)


def train_flops_per_sample(cfg, traffic):
    """Model FLOPs of one token, forward and backward: 6 per matmul
    parameter, plus causal attention 6 * S * d a layer (QK^T and PV, halved
    for causality, times 3 for forward and backward).  What the flash
    backward recomputes is overhead, not model work, and is not counted."""
    dense = 6.0 * matmul_params(cfg)
    attn = (6.0 * traffic["seq_len"] * cfg["hidden_size"]
            * cfg["num_hidden_layers"])
    return dense + attn
