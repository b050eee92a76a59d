"""Inference benchmark across the model zoo — the TPU counterpart of the
reference's scoring sweep (ref: example/image-classification/
benchmark_score.py:1-66, numbers in docs/faq/perf.md:122-144).

Two measurement modes:

* ``--mode steady`` (default): device time.  The hybridized forward is
  functionalized (``gluon.block.functionalize``) and ``lax.scan``-chained
  K times inside ONE XLA program, each iteration's input perturbed by a
  scalar probe of the previous iteration's output — a data dependence
  XLA can neither hoist out of the loop (LICM needs loop-invariance) nor
  batch away, so the timed region is K back-to-back forwards with ONE
  dispatch.  A small-batch forward runs for a fraction of a millisecond,
  the same order as one host dispatch: chaining divides the host's share
  by K so that wall clock over K reads as device time per forward.
* ``--mode eager``: one dispatch per batch through the stock
  CachedOp path — measures the FRAMEWORK serving path including
  per-call overhead (the number a latency-sensitive user sees), kept
  for comparability with the round-4 table.

Sync discipline (both modes): the timed region ends on a host fetch of
a value data-dependent on all timed work.  bf16 by default: inference has
no master-weight concern and the MXU doubles bf16 throughput.

Usage:
    python benchmark_score.py                  # full sweep, JSON lines
    python benchmark_score.py --network resnet-50 --batch-size 32
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu.gluon.model_zoo import vision  # noqa: E402

# network name (reference spelling) -> (zoo factory, input size)
NETWORKS = {
    "alexnet": ("alexnet", 224),
    "vgg-16": ("vgg16", 224),
    "inception-v3": ("inception_v3", 299),
    "resnet-50": ("resnet50_v1", 224),
    "resnet-152": ("resnet152_v1", 224),
    "mobilenet-1.0": ("mobilenet1_0", 224),
    "densenet-121": ("densenet121", 224),
    "squeezenet-1.0": ("squeezenet1_0", 224),
}


def _build(network, batch_size, dtype):
    factory, size = NETWORKS[network]
    mx.random.seed(0)
    net = getattr(vision, factory)(classes=1000)
    net.initialize(mx.init.Xavier(magnitude=2.0))
    if dtype not in ("float32", "none", None):
        net.cast(dtype)
    net.hybridize()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.uniform(-1, 1, (batch_size, 3, size, size))
                    .astype(np.float32))
    if dtype not in ("float32", "none", None):
        x = x.astype(dtype)
    return net, x


def score_steady(network, batch_size, chain=100, repeats=2,
                 dtype="bfloat16", fn_params=None, x=None):
    """img/s by the TWO-POINT chained method: time a K-chain and a
    2K-chain program and divide the K extra forwards by the time
    DIFFERENCE — the per-dispatch transport floor appears in both
    measurements and cancels exactly, so even batch-1 points measure the
    chip (a single-chain rate still carries floor/(K·t) bias, which made
    resnet-152 read faster than resnet-50 at b1).  ``fn_params``/``x``
    override the model (used by the quantization bench to time an
    already-transformed forward through the identical harness)."""
    import jax
    import jax.numpy as jnp

    if fn_params is None:
        from incubator_mxnet_tpu.gluon.block import functionalize
        net, xin = _build(network, batch_size, dtype)
        fn, params = functionalize(net, xin)
        x = xin._read()
    else:
        fn, params = fn_params

    def make(length):
        @jax.jit
        def chained(params, x0):
            def body(carry, _):
                out = fn(params, carry)
                # a scalar probe of THIS output is written INTO the
                # carried input (dynamic_update_slice, element [0...],
                # sub-ULP value): the op chain stays strictly serial and
                # nothing hoists.  An additive scalar probe is NOT safe:
                # the model's FIRST layer is linear, so XLA distributes
                # fn1(x0+s) = fn1(x0) + s*fn1(1) and hoists the
                # loop-invariant fn1(x0) out of the scan (see
                # benchmark_op.bench_serial_shape's HLO-verified notes).
                p = out.reshape(-1)[0].astype(jnp.float32)
                nxt = jax.lax.dynamic_update_slice(
                    carry, (p * 1e-20).astype(x0.dtype).reshape(
                        (1,) * x0.ndim), (0,) * x0.ndim)
                return nxt, p
            _, probes = jax.lax.scan(body, x0, None, length=length)
            return probes.sum()
        return chained

    def best_time(fn_c):
        float(fn_c(params, x))               # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(fn_c(params, x))           # host fetch = true sync
            best = min(best, time.perf_counter() - t0)
        return best

    # adaptive: when the K-vs-2K difference is inside dispatch jitter
    # (small model × small batch), quadruple K until the chained compute
    # clearly dominates — otherwise b1 rows read noise, up to 1/eps
    while True:
        t1 = best_time(make(chain))
        t2 = best_time(make(2 * chain))
        if t2 - t1 > 0.33 * t1 or chain >= 6400:
            break
        chain *= 4
    return chain * batch_size / max(t2 - t1, 1e-9)


def score_eager(network, batch_size, num_batches=10, dtype="bfloat16"):
    """img/s, one dispatch per batch (includes per-call overhead)."""
    net, x = _build(network, batch_size, dtype)

    def sync(out):
        # in-order device stream: fetching one element of the last output
        # bounds every dispatch before it
        return float(out.reshape((-1,))[0:1].asnumpy()[0])

    for _ in range(5):                     # warm-up (includes compile)
        out = net(x)
    sync(out)

    t0 = time.perf_counter()
    for _ in range(num_batches):
        out = net(x)
    sync(out)                              # host fetch = true sync
    dt = time.perf_counter() - t0
    return num_batches * batch_size / dt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--network", default=None,
                   help="one of %s (default: all)" % ", ".join(NETWORKS))
    p.add_argument("--batch-size", type=int, default=0,
                   help="single batch size (default: sweep 1 and 32)")
    p.add_argument("--mode", default="steady", choices=["steady", "eager"])
    p.add_argument("--chain", type=int, default=100,
                   help="forwards per dispatch in steady mode")
    p.add_argument("--num-batches", type=int, default=10,
                   help="batches to time in eager mode")
    p.add_argument("--dtype", default="bfloat16")
    args = p.parse_args()

    networks = [args.network] if args.network else list(NETWORKS)
    batches = [args.batch_size] if args.batch_size else [1, 32]
    for network in networks:
        for b in batches:
            if args.mode == "steady":
                img_s = score_steady(network, b, args.chain,
                                     dtype=args.dtype)
            else:
                img_s = score_eager(network, b, args.num_batches,
                                    args.dtype)
            print(json.dumps({
                "metric": "inference_imgs_per_sec", "network": network,
                "batch_size": b, "value": round(img_s, 2), "unit": "img/s",
                "dtype": args.dtype, "mode": args.mode,
            }), flush=True)


if __name__ == "__main__":
    main()
