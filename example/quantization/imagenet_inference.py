"""Quantized vs bf16 model inference — the INT8 serving proof.

TPU counterpart of the reference's quantization example pair
(ref: example/quantization/imagenet_gen_qsym.py:1 — calibrated symbol
generation; example/quantization/imagenet_inference.py:1 — quantized vs
fp32 inference timing): builds the symbolic ResNet, folds BatchNorm into
the convs (contrib.quantization.fold_batchnorm — the role the
reference's fused MKLDNN subgraphs play), calibrates + quantizes the
folded graph, then times bf16 vs int8 through the steady-state chained
harness (K forwards per dispatch, the benchmark_score.py --mode steady
discipline) so the ratio measures the chip, not the transport.

Accuracy is reported as int8-vs-f32 top-1 agreement on held-out
synthetic batches (no ImageNet in this environment; the subsystem's
≤1%-drop accuracy bar is separately enforced on a trained model in
tests/test_quantization.py).

Prints JSON lines; the last line carries the int8/bf16 speedup.

Usage:
    python imagenet_inference.py                     # resnet-50, b 1+32
    python imagenet_inference.py --num-layers 18 --batch-size 32 \
        --calib-mode entropy
"""
import argparse
import importlib.util
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu import autograd, io  # noqa: E402
from incubator_mxnet_tpu.contrib import quantization as qz  # noqa: E402
from incubator_mxnet_tpu.ndarray import NDArray  # noqa: E402


def _load_example(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", relpath))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _load_resnet():
    return _load_example(os.path.join("image-classification", "symbols",
                                      "resnet.py"), "sym_resnet")


def _host_init(pred, data_shape, seed=0):
    """MSRA-scaled init from a seeded host RNG (activations stay O(1)
    through the stack, so calibration ranges are realistic)."""
    rs = np.random.RandomState(seed)
    shapes, _, aux_shapes = pred.infer_shape(data=data_shape)
    args, aux = {}, {}
    for n, s in zip(pred.list_arguments(), shapes):
        if n == "data":
            continue
        if "weight" in n:
            fan_in = int(np.prod(s[1:]))
            v = rs.randn(*s).astype(np.float32) * np.sqrt(2.0 / fan_in)
        elif "gamma" in n:
            v = np.ones(s, np.float32)
        else:                       # beta / bias
            v = np.zeros(s, np.float32)
        args[n] = mx.nd.array(v)
    for n, s in zip(pred.list_auxiliary_states(), aux_shapes):
        aux[n] = mx.nd.array(np.ones(s, np.float32) if "var" in n
                             else np.zeros(s, np.float32))
    return args, aux


def _eval_fn(sym, cast=None):
    """Pure jittable fn(param_vals, x) over a Symbol's eval_dict trace."""
    def fn(param_vals, x):
        merged = {k: NDArray(v) for k, v in param_vals.items()}
        merged["data"] = NDArray(x)
        with autograd._scope(recording=False, training=False):
            out = sym.eval_dict(merged)
        out = out[0] if isinstance(out, (list, tuple)) else out
        return out._read()
    return fn


_BENCH_SCORE = None


def steady_rate(fn, param_vals, x, chain=50, repeats=2):
    """Images/sec through benchmark_score's steady harness — ONE timing
    discipline for plain and quantized serving (its fn_params/x hooks
    exist for exactly this caller)."""
    global _BENCH_SCORE
    if _BENCH_SCORE is None:
        _BENCH_SCORE = _load_example(
            os.path.join("image-classification", "benchmark_score.py"),
            "bench_score_q")
    return _BENCH_SCORE.score_steady(None, x.shape[0], chain=chain,
                                     repeats=repeats,
                                     fn_params=(fn, param_vals), x=x)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--num-layers", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=0,
                   help="single batch (default: sweep 1 and 32)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--chain", type=int, default=50)
    p.add_argument("--calib-mode", default="naive",
                   choices=["none", "naive", "entropy"])
    p.add_argument("--num-calib-batches", type=int, default=4)
    p.add_argument("--calib-batch-size", type=int, default=8)
    args = p.parse_args()

    import jax.numpy as jnp

    resnet = _load_resnet()
    size = args.image_size
    net = resnet.get_symbol(num_classes=1000, num_layers=args.num_layers)
    pred = net.get_internals()["fc1_output"]
    data_shape = (args.calib_batch_size, 3, size, size)
    arg_params, aux_params = _host_init(pred, data_shape)

    rs = np.random.RandomState(1)
    calib = rs.uniform(-1, 1, (args.num_calib_batches
                               * args.calib_batch_size, 3, size, size)) \
        .astype(np.float32)

    fsym, fargs, faux = qz.fold_batchnorm(pred, arg_params, aux_params)
    assert not faux, "BN must fold away for the int8 serving graph"
    calib_mode = args.calib_mode
    qsym, qargs, _ = qz.quantize_model(
        fsym, fargs, {}, calib_mode=calib_mode,
        calib_data=io.NDArrayIter(data=calib,
                                  batch_size=args.calib_batch_size),
        num_calib_examples=len(calib))

    # held-out agreement (f32 folded graph is the reference output)
    xa = mx.nd.array(rs.uniform(-1, 1, (16, 3, size, size))
                     .astype(np.float32))
    ref = fsym.bind(mx.cpu(), {**fargs, "data": xa},
                    grad_req="null").forward(is_train=False)[0].asnumpy()
    got = qsym.bind(mx.cpu(), {**qargs, "data": xa},
                    grad_req="null").forward(is_train=False)[0].asnumpy()
    agree = float((ref.argmax(1) == got.argmax(1)).mean())
    # random-init logits cluster near zero, so agreement underestimates
    # real-model fidelity; relative logit error is scale-free evidence
    rel_err = float(np.abs(got - ref).mean() / (np.abs(ref).std() + 1e-9))

    bf16_fn = _eval_fn(fsym)
    bf16_params = {k: v._read().astype(jnp.bfloat16)
                   for k, v in fargs.items()}
    q_fn = _eval_fn(qsym)
    q_params = {k: v._read() for k, v in qargs.items()}

    batches = [args.batch_size] if args.batch_size else [1, 32]
    for b in batches:
        x = rs.uniform(-1, 1, (b, 3, size, size)).astype(np.float32)
        r_bf16 = steady_rate(bf16_fn, bf16_params,
                             jnp.asarray(x, jnp.bfloat16), args.chain)
        r_int8 = steady_rate(q_fn, q_params, jnp.asarray(x), args.chain)
        print(json.dumps({
            "metric": "quantized_inference_imgs_per_sec",
            "network": "resnet-%d" % args.num_layers, "batch_size": b,
            "bf16_imgs_per_sec": round(r_bf16, 2),
            "int8_imgs_per_sec": round(r_int8, 2),
            "int8_speedup_vs_bf16": round(r_int8 / r_bf16, 3),
            "top1_agreement_int8_vs_f32": round(agree, 4),
            "logit_rel_err_int8_vs_f32": round(rel_err, 4),
            "calib_mode": calib_mode, "chain": args.chain,
        }), flush=True)


if __name__ == "__main__":
    main()
