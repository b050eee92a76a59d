"""INT8 vs bf16/f32 op speed on the real chip — the TPU counterpart of the
reference's quantized-op benchmark (ref: benchmark/python/quantization/
benchmark_op.py:1-90).

Times the framework's own op kernels (the fcomputes the nd/symbol front
ends dispatch): ``Convolution``/``FullyConnected`` in bf16 and f32 vs
``_contrib_quantized_conv``/``_contrib_quantized_fully_connected`` whose
int8 operands lower to the MXU's s8×s8→s32 pipeline
(ops/quantization.py:189, preferred_element_type=int32).

Timing discipline: a single conv or matmul at these shapes runs for tens
of microseconds, less than one host dispatch, so each measurement jits
ONE program that scans the op N times with a data dependency between
iterations and fetches a scalar — wall clock around the host fetch, over
N, is device time per call.

Prints JSON lines; run with --fc for the FullyConnected sweep too.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")))

import jax                                       # noqa: E402
import jax.numpy as jnp                          # noqa: E402

from incubator_mxnet_tpu.ops.registry import get_op  # noqa: E402

REPEATS = int(os.environ.get("BENCH_REPEATS", "20"))

# reference sweep (benchmark_op.py:73-89): resnet-style conv shapes
CONV_CONFIGS = [
    # (data_shape, kernel, num_filter, pad, stride)
    ((32, 64, 56, 56), (1, 1), 256, (0, 0), (1, 1)),
    ((32, 256, 56, 56), (1, 1), 64, (0, 0), (1, 1)),
    ((32, 256, 56, 56), (1, 1), 128, (0, 0), (2, 2)),
    ((32, 128, 28, 28), (3, 3), 128, (1, 1), (1, 1)),
    ((32, 1024, 14, 14), (1, 1), 256, (0, 0), (1, 1)),
    ((32, 2048, 7, 7), (1, 1), 512, (0, 0), (1, 1)),
]

FC_CONFIGS = [
    # (batch, in_features, num_hidden)
    (32, 2048, 1000),
    (256, 2048, 1000),
    (256, 4096, 4096),
    # large enough to clear the per-iteration latency floor and expose
    # the MXU's double-rate int8 pipeline (the reference shapes above
    # all finish under it on this chip)
    (8192, 8192, 8192),
]


def _timed_scan(fn, *args, repeats=None):
    """Jit a scan of ``fn``; return ms/call.

    Each iteration rebinds the first operand through a SELECT on a
    runtime predicate of the previous output (always false, but not
    provably so) — a data dependency XLA can neither hoist nor
    distribute through the op.  Scalar add/mul perturbations are NOT
    enough: XLA rewrites ``(a+eps)@b`` as ``a@b + eps@b`` and hoists
    ``a@b`` (measured: 8192^3 matmuls "ran" at 2x the chip's dense
    ceiling); an optimization_barrier alone fared even worse.  The
    select costs one elementwise pass per iteration — small vs any op
    worth benchmarking here.  The final scalar fetch is the sync point.
    """
    if repeats is None:
        repeats = REPEATS   # read at call time so tests can shrink it

    @jax.jit
    def many(*a):
        def body(carry, _):
            out = fn(*carry)
            lead = out[0] if isinstance(out, tuple) else out
            probe = lead.reshape(-1)[0].astype(jnp.float32)
            first = jnp.where(probe > 1e30, carry[0] + carry[0].dtype.type(1),
                              carry[0])
            carry = (first,) + carry[1:]
            return carry, probe
        _, probes = jax.lax.scan(body, a, None, length=repeats)
        return probes.sum()

    try:
        float(many(*args))      # compile + warm
    except jax.errors.JaxRuntimeError:
        # XLA's CPU backend mis-lowers some s8 ops inside scan (LLVM
        # verifier failure); fall back to a per-call loop — a CPU run
        # only checks the mechanics, its timings are not device numbers.
        one = jax.jit(lambda *a: (
            (fn(*a)[0] if isinstance(fn(*a), tuple) else fn(*a))
            .reshape(-1)[0].astype(jnp.float32)))
        float(one(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            r = one(*args)
        float(r)
        return (time.perf_counter() - t0) / repeats * 1e3
    t0 = time.perf_counter()
    float(many(*args))          # host fetch = true sync
    return (time.perf_counter() - t0) / repeats * 1e3


def bench_conv(data_shape, kernel, num_filter, pad, stride):
    rs = np.random.RandomState(0)
    conv = get_op("Convolution").fcompute
    qconv = get_op("_contrib_quantized_conv").fcompute
    w_shape = (num_filter, data_shape[1]) + kernel
    x32 = jnp.asarray(rs.normal(0, 0.2, data_shape), jnp.float32)
    w32 = jnp.asarray(rs.normal(0, 1, w_shape), jnp.float32)

    results = {}
    for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        x, w = x32.astype(dt), w32.astype(dt)
        results[name] = _timed_scan(
            lambda a, b: conv(a, b, None, kernel=kernel, stride=stride,
                              pad=pad, num_filter=num_filter, no_bias=True),
            x, w)

    x8 = jnp.clip(jnp.rint(x32 / jnp.abs(x32).max() * 127), -127,
                  127).astype(jnp.int8)
    w8 = jnp.clip(jnp.rint(w32 / jnp.abs(w32).max() * 127), -127,
                  127).astype(jnp.int8)
    mn = jnp.float32(-1)
    mx_ = jnp.float32(1)
    results["int8"] = _timed_scan(
        lambda a, b: qconv(a, b, mn, mx_, mn, mx_, kernel=kernel,
                           stride=stride, pad=pad, num_filter=num_filter,
                           no_bias=True),
        x8, w8)
    return results


def bench_fc(batch, in_features, num_hidden):
    rs = np.random.RandomState(0)
    fc = get_op("FullyConnected").fcompute
    qfc = get_op("_contrib_quantized_fully_connected").fcompute
    x32 = jnp.asarray(rs.normal(0, 0.2, (batch, in_features)), jnp.float32)
    w32 = jnp.asarray(rs.normal(0, 1, (num_hidden, in_features)), jnp.float32)

    results = {}
    for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        x, w = x32.astype(dt), w32.astype(dt)
        results[name] = _timed_scan(
            lambda a, b: fc(a, b, num_hidden=num_hidden, no_bias=True),
            x, w)

    x8 = jnp.clip(jnp.rint(x32 * 127), -127, 127).astype(jnp.int8)
    w8 = jnp.clip(jnp.rint(w32 / jnp.abs(w32).max() * 127), -127,
                  127).astype(jnp.int8)
    mn, mx_ = jnp.float32(-1), jnp.float32(1)
    results["int8"] = _timed_scan(
        lambda a, b: qfc(a, b, mn, mx_, mn, mx_, num_hidden=num_hidden,
                         no_bias=True),
        x8, w8)
    return results


def bench_serial_shape(fn, x0, ops, L1=128, L2=512, repeats=3):
    """ms/op at ONE shape by the floor-cancelling serial chain.

    A ``fori_loop`` chains L applications of ``fn`` inside one program;
    each iteration writes a scalar probe of its output INTO the carried
    input via ``dynamic_update_slice`` (element [0..0], sub-ULP value).
    Construction notes — three cheaper dependences all get optimized
    away (verified in compiled HLO):
    * additive/multiplicative scalar perturbation: conv/fc are linear,
      so XLA rewrites ``fn(x0 + s) = fn(x0) + s·fn(1)`` and hoists the
      loop-invariant part (measured: >5 PFLOP/s readings);
    * select-on-predicate rebinding: the select sinks / the op hoists;
    * optimization_barrier: the barrier's unused output is DCE'd and
      the op with it (0 dot ops left in the compiled module).
    DUS on the CARRY is in-place (no per-iteration copy — DUS on the
    invariant x0 forces a full-tensor copy each iteration) and nothing
    distributes through a point update, so the op stays in the loop
    body.  Timing two chain lengths and dividing the extra ops by the
    time DIFFERENCE cancels the per-dispatch transport floor exactly —
    the round-4 sweep's unresolved rows (every dtype ≈ the 0.5 ms/iter
    scan floor) resolve under this method.
    """
    def make(L):
        @jax.jit
        def run(x0, *ops):
            def body(_i, xc):
                out = fn(xc, *ops)
                lead = out[0] if isinstance(out, tuple) else out
                probe = (lead.reshape(-1)[0].astype(jnp.float32)
                         * 1e-20).astype(x0.dtype)
                return jax.lax.dynamic_update_slice(
                    xc, probe.reshape((1,) * x0.ndim),
                    (0,) * x0.ndim)
            xf = jax.lax.fori_loop(0, L, body, x0)
            return xf.reshape(-1)[0].astype(jnp.float32)
        return run

    def best(L):
        prog = make(L)
        float(prog(x0, *ops))          # compile + warm
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(prog(x0, *ops))      # host fetch = true sync
            b = min(b, time.perf_counter() - t0)
        return b

    # adaptive: reference shapes run in tens of µs, so the K-vs-4K time
    # difference must be grown until it clears dispatch jitter (same
    # discipline as benchmark_score.score_steady)
    while True:
        t1, t2 = best(L1), best(L2)
        if t2 - t1 > 0.33 * t1 or L2 >= 32768:
            break
        L1 *= 4
        L2 *= 4
    return max(t2 - t1, 1e-9) / (L2 - L1) * 1e3


def bench_conv_serial(data_shape, kernel, num_filter, pad, stride,
                      L1=128, L2=512):
    """int8-vs-bf16 ratio at one reference conv shape (serial-chain)."""
    rs = np.random.RandomState(0)
    conv = get_op("Convolution").fcompute
    qconv = get_op("_contrib_quantized_conv").fcompute
    w_shape = (num_filter, data_shape[1]) + kernel
    x32 = jnp.asarray(rs.normal(0, 0.2, data_shape), jnp.float32)
    w32 = jnp.asarray(rs.normal(0, 1, w_shape), jnp.float32)

    results = {}
    for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        results[name] = bench_serial_shape(
            lambda a, b: conv(a, b, None, kernel=kernel, stride=stride,
                              pad=pad, num_filter=num_filter, no_bias=True),
            x32.astype(dt), (w32.astype(dt),), L1, L2)

    x8 = jnp.clip(jnp.rint(x32 / jnp.abs(x32).max() * 127), -127,
                  127).astype(jnp.int8)
    w8 = jnp.clip(jnp.rint(w32 / jnp.abs(w32).max() * 127), -127,
                  127).astype(jnp.int8)
    mn, mx_ = jnp.float32(-1), jnp.float32(1)
    results["int8"] = bench_serial_shape(
        lambda a, b: qconv(a, b, mn, mx_, mn, mx_, kernel=kernel,
                           stride=stride, pad=pad, num_filter=num_filter,
                           no_bias=True)[0].astype(jnp.int8),
        x8, (w8,), L1, L2)
    return results


def bench_fc_serial(batch, in_features, num_hidden, L1=128, L2=512):
    """int8-vs-bf16 ratio at one reference FC shape (serial-chain)."""
    rs = np.random.RandomState(0)
    fc = get_op("FullyConnected").fcompute
    qfc = get_op("_contrib_quantized_fully_connected").fcompute
    x32 = jnp.asarray(rs.normal(0, 0.2, (batch, in_features)), jnp.float32)
    w32 = jnp.asarray(rs.normal(0, 1, (num_hidden, in_features)),
                      jnp.float32)

    results = {}
    for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        results[name] = bench_serial_shape(
            lambda a, b: fc(a, b, num_hidden=num_hidden, no_bias=True),
            x32.astype(dt), (w32.astype(dt),), L1, L2)

    x8 = jnp.clip(jnp.rint(x32 * 127), -127, 127).astype(jnp.int8)
    w8 = jnp.clip(jnp.rint(w32 / jnp.abs(w32).max() * 127), -127,
                  127).astype(jnp.int8)
    mn, mx_ = jnp.float32(-1), jnp.float32(1)
    results["int8"] = bench_serial_shape(
        lambda a, b: qfc(a, b, mn, mx_, mn, mx_, num_hidden=num_hidden,
                         no_bias=True)[0].astype(jnp.int8),
        x8, (w8,), L1, L2)
    return results


def bench_serial_matmul(n=8192, repeats=30):
    """The conclusive int8-vs-bf16 probe: each iteration's matmul consumes
    the previous OUTPUT (renormalized), a dependency XLA cannot hoist or
    algebraically distribute away — unlike scalar-perturbation chains,
    which XLA rewrites as ``a@b + eps@b`` and hoists (measured 2x-fake
    throughput).  Same methodology for both dtypes, so the RATIO is
    solid even where absolute numbers carry the renorm pass."""
    key = jax.random.PRNGKey(0)
    results = {}
    for name, dt in (("bf16", jnp.bfloat16), ("int8", jnp.int8)):
        if dt == jnp.int8:
            a = (jax.random.normal(key, (n, n)) * 10).astype(jnp.int8)
            b = (jax.random.normal(key, (n, n)) * 10).astype(jnp.int8)

            def mm(x, y):
                return jax.lax.dot_general(
                    x, y, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)

            def norm(o):
                return (o >> 8).astype(jnp.int8)
        else:
            a = jax.random.normal(key, (n, n), dt)
            b = jax.random.normal(key, (n, n), dt)

            def mm(x, y):
                return x @ y

            def norm(o):
                return o * jnp.float32(1e-4).astype(o.dtype)

        @jax.jit
        def many(a, b):
            def body(carry, _):
                out = mm(carry, b)
                return norm(out), out.reshape(-1)[0].astype(jnp.float32)
            _, probes = jax.lax.scan(body, a, None, length=repeats)
            return probes.sum()

        float(many(a, b))
        t0 = time.perf_counter()
        float(many(a, b))
        dt_s = time.perf_counter() - t0
        results[name] = {
            "ms": dt_s / repeats * 1e3,
            "tops": 2 * n ** 3 * repeats / dt_s / 1e12,
        }
    return results


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--fc", action="store_true", help="include FC sweep")
    p.add_argument("--conv", action="store_true", help="include conv sweep")
    p.add_argument("--serial-probe", action="store_true",
                   help="serial-chain 8192^3 matmul: the conclusive "
                        "int8-vs-bf16 ratio")
    p.add_argument("--serial-sweep", action="store_true",
                   help="floor-cancelling serial chain at EVERY reference "
                        "conv/fc shape (VERDICT r4 task 7)")
    p.add_argument("--chain", type=int, default=128,
                   help="serial-sweep L1 (L2 = 4*L1)")
    args = p.parse_args()
    if args.serial_sweep:
        for cfg in CONV_CONFIGS:
            r = bench_conv_serial(*cfg, L1=args.chain, L2=4 * args.chain)
            print(json.dumps({
                "op": "conv_serial", "data_shape": cfg[0], "kernel": cfg[1],
                "num_filter": cfg[2], "stride": cfg[4],
                "f32_ms": round(r["f32"], 4), "bf16_ms": round(r["bf16"], 4),
                "int8_ms": round(r["int8"], 4),
                "int8_vs_f32": round(r["f32"] / r["int8"], 2),
                "int8_vs_bf16": round(r["bf16"] / r["int8"], 2),
            }), flush=True)
        for cfg in FC_CONFIGS[:-1]:     # 8192^3 has the dedicated probe
            r = bench_fc_serial(*cfg, L1=args.chain, L2=4 * args.chain)
            print(json.dumps({
                "op": "fc_serial", "batch": cfg[0], "in_features": cfg[1],
                "num_hidden": cfg[2],
                "f32_ms": round(r["f32"], 4), "bf16_ms": round(r["bf16"], 4),
                "int8_ms": round(r["int8"], 4),
                "int8_vs_f32": round(r["f32"] / r["int8"], 2),
                "int8_vs_bf16": round(r["bf16"] / r["int8"], 2),
            }), flush=True)
        return
    if args.serial_probe:
        r = bench_serial_matmul()
        print(json.dumps({
            "op": "serial_matmul_8192", "bf16_ms": round(r["bf16"]["ms"], 2),
            "int8_ms": round(r["int8"]["ms"], 2),
            "bf16_tflops": round(r["bf16"]["tops"], 1),
            "int8_tops": round(r["int8"]["tops"], 1),
            "int8_vs_bf16": round(r["bf16"]["ms"] / r["int8"]["ms"], 2),
        }), flush=True)
        return      # standalone measurement: no implicit sweeps after it
    do_conv = args.conv or not args.fc
    if do_conv:
        for cfg in CONV_CONFIGS:
            r = bench_conv(*cfg)
            print(json.dumps({
                "op": "conv", "data_shape": cfg[0], "kernel": cfg[1],
                "num_filter": cfg[2], "stride": cfg[4],
                "f32_ms": round(r["f32"], 3), "bf16_ms": round(r["bf16"], 3),
                "int8_ms": round(r["int8"], 3),
                "int8_vs_f32": round(r["f32"] / r["int8"], 2),
                "int8_vs_bf16": round(r["bf16"] / r["int8"], 2),
            }), flush=True)
    if args.fc:
        for cfg in FC_CONFIGS:
            r = bench_fc(*cfg)
            print(json.dumps({
                "op": "fc", "batch": cfg[0], "in": cfg[1], "hidden": cfg[2],
                "f32_ms": round(r["f32"], 3), "bf16_ms": round(r["bf16"], 3),
                "int8_ms": round(r["int8"], 3),
                "int8_vs_f32": round(r["f32"] / r["int8"], 2),
                "int8_vs_bf16": round(r["bf16"] / r["int8"], 2),
            }), flush=True)


if __name__ == "__main__":
    main()
