"""KVStore push/pull bandwidth measurement — TPU counterpart of the
reference's tool (ref: tools/bandwidth/measure.py:1-40).

Pushes ResNet-152-sized gradient buffers (or a custom size list) through
a kvstore and reports effective GB/s per push+pull round, for
local / device / dist_sync (dense and 2-bit compressed) / dist_async.

Single process measures the local store; run under ``tools/launch.py -n
N`` for the dist types — every worker pushes, rank 0 prints.  The timed
region ends on a host fetch of the pulled value.

Usage:
    python tools/bandwidth/measure.py --kv-store local
    python tools/launch.py -n 2 python tools/bandwidth/measure.py \
        --kv-store dist_sync [--gc-type 2bit]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))


def default_sizes():
    """ResNet-152-ish parameter sizing: a few big conv/fc buffers plus a
    tail of small ones (the shape mix that stresses batching)."""
    sizes = [2048 * 1000, 2048 * 512 * 9, 1024 * 256 * 9, 512 * 128 * 9]
    sizes += [256 * 64 * 9] * 8 + [65536] * 16 + [4096] * 32
    return sizes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--kv-store", default="local")
    p.add_argument("--num-batches", type=int, default=10)
    p.add_argument("--gc-type", default="none",
                   help="'2bit' enables the compressed wire")
    p.add_argument("--optimizer", default="none",
                   help="server-side optimizer name or 'none'")
    p.add_argument("--platform", default=None,
                   help="'cpu' forces the CPU backend (multi-process CPU "
                        "runs: every worker must pick it BEFORE jax init)")
    p.add_argument("--report", default="time", choices=["time", "bytes"],
                   help="'bytes': report wire bytes shipped per round "
                        "instead of loopback time.  On loopback transports "
                        "encode/decode compute swamps free local bytes, so "
                        "time CANNOT see the compression win; bytes mode "
                        "measures what the wire actually ships — the "
                        "quantity the compressed wire optimizes.  The "
                        "per-value byte model is wire_bytes_per_worker, "
                        "whose lowering (u32 all-to-all + s8 all-gather) "
                        "is pinned by an HLO assertion in "
                        "tests/test_compression.py")
    p.add_argument("--num-workers", type=int, default=0,
                   help="bytes mode: model W workers without launching "
                        "them (default: the live kv.num_workers)")
    args = p.parse_args()

    if args.platform == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
        import jax
        jax.config.update("jax_platforms", "cpu")
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd

    if args.report == "bytes":
        from incubator_mxnet_tpu.parallel.compression import (
            wire_bytes_per_worker)
        sizes = default_sizes()
        W = args.num_workers
        if W <= 0:
            kv = mx.kv.create(args.kv_store)
            W = kv.num_workers
        W = max(W, 2)      # a 1-worker "wire" ships nothing; model the
        #                    smallest real topology and report that W
        comp = dense = 0
        for n in sizes:
            c, d = wire_bytes_per_worker(n, W)
            comp += c
            dense += d
        shipped = comp if args.gc_type != "none" else dense
        print(json.dumps({
            "metric": "kvstore_wire_bytes_per_round",
            "kv_store": args.kv_store, "gc_type": args.gc_type,
            "num_workers": W,
            "payload_mb": round(4 * sum(sizes) / 1e6, 1),
            "value": shipped, "unit": "bytes/worker/round",
            "dense_bytes": dense, "compressed_bytes": comp,
            "compression_ratio": round(dense / comp, 2),
        }, ), flush=True)
        return

    kv = mx.kv.create(args.kv_store)
    if args.gc_type != "none":
        kv.set_gradient_compression({"type": args.gc_type, "threshold": 0.5})
    if args.optimizer != "none":
        import incubator_mxnet_tpu.optimizer as opt
        kv.set_optimizer(opt.create(args.optimizer, learning_rate=0.01))

    rs = np.random.RandomState(0)
    sizes = default_sizes()
    keys = list(range(len(sizes)))
    vals = [nd.array(rs.uniform(-1, 1, (s,)).astype(np.float32))
            for s in sizes]
    outs = [nd.zeros((s,)) for s in sizes]
    kv.init(keys, [nd.zeros((s,)) for s in sizes])

    # warm-up round (compiles the reduce programs)
    kv.push(keys, vals)
    kv.pull(keys, out=outs)
    float(outs[0].asnumpy()[0])

    total_bytes = 4 * sum(sizes)
    t0 = time.perf_counter()
    for _ in range(args.num_batches):
        kv.push(keys, vals)
        kv.pull(keys, out=outs)
    float(outs[0].asnumpy()[0])        # host fetch = true sync
    dt = time.perf_counter() - t0

    gbs = args.num_batches * total_bytes / dt / 1e9
    if kv.rank == 0:
        print(json.dumps({
            "metric": "kvstore_push_pull_bandwidth",
            "kv_store": args.kv_store, "gc_type": args.gc_type,
            "num_workers": kv.num_workers,
            "payload_mb": round(total_bytes / 1e6, 1),
            "rounds": args.num_batches,
            "value": round(gbs, 3), "unit": "GB/s",
            "ms_per_round": round(dt / args.num_batches * 1e3, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
