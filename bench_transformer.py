"""Benchmark: decoder-transformer LM training — tokens/sec and MFU on one chip.

The compute-bound counterpart to bench.py's (HBM-bound, see
docs/perf_analysis_r03.md) ResNet-50: a GPT-style decoder LM at
d_model 4096 where >90% of the FLOPs are large bf16 matmuls, so the
measured model-FLOPs utilisation (MFU) is a direct statement about how
well the framework's fused train step feeds the MXU.

Model: learned token+position embeddings -> N pre-norm decoder blocks
(causal MultiHeadAttention flash kernel + 4x FFN) -> vocab projection.
Whole train step (fwd + CE loss + bwd + SGD-momentum update, bf16 compute
with f32 master weights) is ONE jitted XLA program via
DataParallelTrainer.

MFU convention (PaLM appendix B): model FLOPs = 6 * N * tokens with N =
NON-embedding parameters (the input token/position tables are gathers —
0 matmul FLOPs — so counting them would inflate MFU ~7% at the default
config; the vocab-projection head IS a matmul and stays in N), plus the
causal attention term 6 * S * tokens * d_model (QK^T and PV, halved for
causality, x3 for fwd+bwd) — flash recompute in the backward is NOT
counted (it is overhead, not model work).  The JSON reports both
conventions: "mfu" (non-embedding, headline) and "mfu_all_params" (the
pre-round-5 number, for comparability).

Prints ONE JSON line:
  {"metric": "transformer_lm_train_tokens_per_sec", "value": N,
   "unit": "tokens/s", "mfu": ..., "tflops_per_sec": ..., ...}
"""
import json
import os
import sys
import time

import numpy as np

# peak dense bf16 TFLOP/s by device_kind (public spec sheets)
_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def model_flops_per_step(n_params, tokens, seq_len, d_model, n_layers=1):
    """PaLM-style model FLOPs for one train step (fwd+bwd)."""
    dense = 6.0 * n_params * tokens
    # per-LAYER causal attention matmuls: 0.5 * 12 * S * T * d
    attn = 6.0 * seq_len * tokens * d_model * n_layers
    return dense + attn


def build_lm(vocab, d_model, n_heads, d_ffn, n_layers, seq_len):
    """The benchmark's decoder LM, uninitialised (``chip_smoke.py`` builds
    the same model at a cut depth)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn

    class DecoderBlock(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.ln1 = nn.LayerNorm()
                # fused_qkv measured slightly SLOWER end-to-end here
                # (405.8 vs 383.1 ms/step at d=4096): XLA already
                # schedules the three projections well at this shape
                self.attn = nn.MultiHeadAttention(d_model, n_heads,
                                                  causal=True, use_bias=False)
                self.ln2 = nn.LayerNorm()
                self.fc1 = nn.Dense(d_ffn, flatten=False, in_units=d_model,
                                    use_bias=False)
                self.fc2 = nn.Dense(d_model, flatten=False, in_units=d_ffn,
                                    use_bias=False)

        def hybrid_forward(self, F, x):
            x = x + self.attn(self.ln1(x))
            h = F.Activation(self.fc1(self.ln2(x)), act_type="relu")
            return x + self.fc2(h)

    class TransformerLM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, d_model)
                self.pos_embed = self.params.get(
                    "pos_embed", shape=(seq_len, d_model),
                    init=mx.init.Normal(0.02))
                self.blocks = nn.HybridSequential(prefix="blocks_")
                with self.blocks.name_scope():
                    for _ in range(n_layers):
                        self.blocks.add(DecoderBlock())
                self.ln_f = nn.LayerNorm()
                self.head = nn.Dense(vocab, flatten=False, in_units=d_model,
                                     use_bias=False)

        def hybrid_forward(self, F, tokens, pos_embed):
            h = self.embed(tokens) + F.expand_dims(pos_embed, axis=0)
            h = self.blocks(h)
            return self.head(self.ln_f(h))

    return TransformerLM()


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench_transformer.py measures a TPU and found %s (%s): "
                 "no chip, no number" % (dev.platform, dev.device_kind))
    if dev.device_kind not in _PEAK_TFLOPS:
        sys.exit("bench_transformer.py: no peak FLOP/s on record for "
                 "device kind %r; add it to _PEAK_TFLOPS with its source"
                 % dev.device_kind)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_mesh, DataParallelTrainer

    # Default config: d_model 4096 puts every matmul on a shape the MXU
    # sustains (docs/perf_analysis_r04.md; d 2048 shapes cap lower).
    vocab = int(os.environ.get("BENCH_VOCAB", "16384"))
    d_model = int(os.environ.get("BENCH_DMODEL", "4096"))
    n_heads = int(os.environ.get("BENCH_HEADS", "32"))
    d_ffn = int(os.environ.get("BENCH_FFN", str(4 * d_model)))
    n_layers = int(os.environ.get("BENCH_LAYERS", "4"))
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    n_steps = int(os.environ.get("BENCH_STEPS", "15"))

    mx.random.seed(0)
    net = build_lm(vocab, d_model, n_heads, d_ffn, n_layers, seq_len)
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", optimizer_params={"learning_rate": 0.05,
                                           "momentum": 0.9},
        mesh=mesh, dtype="bfloat16")

    rs = np.random.RandomState(0)
    # int32 token ids: the trainer keeps wide-integer inputs exact (no
    # bf16 rounding of indices); labels stay f32 for the pick-based loss
    x = mx.nd.array(rs.randint(0, vocab, (batch, seq_len)), dtype=np.int32)
    y = mx.nd.array(rs.randint(0, vocab, (batch, seq_len)).astype(np.float32))

    for _ in range(3):
        loss = trainer.step(x, y)
    loss.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = trainer.step(x, y)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    assert np.isfinite(float(loss)), "transformer bench loss went non-finite"

    n_params = int(sum(int(np.prod(p.shape))
                       for p in net.collect_params().values()))
    # input embedding + position table are gathers, not matmuls: exclude
    # from the FLOP model (PaLM appendix B non-embedding convention)
    n_embed = vocab * d_model + seq_len * d_model
    n_matmul = n_params - n_embed
    tokens = batch * seq_len
    tok_s = n_steps * tokens / dt
    flops = model_flops_per_step(n_matmul, tokens, seq_len, d_model,
                                 n_layers)
    flops_all = model_flops_per_step(n_params, tokens, seq_len, d_model,
                                     n_layers)
    achieved_tflops = flops * n_steps / dt / 1e12
    peak = _PEAK_TFLOPS[dev.device_kind]
    mfu = achieved_tflops / peak
    mfu_all = (flops_all * n_steps / dt / 1e12) / peak

    print(json.dumps({
        "metric": "transformer_lm_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "mfu": round(mfu, 4),
        "mfu_all_params": round(mfu_all, 4),
        "tflops_per_sec": round(achieved_tflops, 2),
        "peak_tflops": peak,
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "n_params": n_params, "n_params_non_embedding": n_matmul,
        "d_model": d_model, "n_layers": n_layers, "n_heads": n_heads,
        "d_ffn": d_ffn, "seq_len": seq_len, "batch": batch,
        "step_ms": round(dt / n_steps * 1e3, 2),
    }))


if __name__ == "__main__":
    main()
