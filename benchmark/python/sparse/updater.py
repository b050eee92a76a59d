"""Row-sparse optimizer-update throughput — TPU counterpart of the
reference's updater benchmark (ref: benchmark/python/sparse/updater.py:1).

Times SGD updates on a large embedding-style weight when the gradient is
row-sparse (the lazy path touches only occupied rows — optimizer.py
_sparse_sgd, the analogue of SGDUpdateRspRspImpl) vs the same gradient
densified.  Prints JSON lines.

``--bulk N``: run each update stream inside ``mx.engine.bulk`` so N
consecutive updates flush as ONE XLA dispatch — the configuration that
matters for training loops (the reference bulks optimizer updates inside
train segments, threaded_engine.h:472-509).  Without it the lazy path
pays one host dispatch per op, which dwarfs its bandwidth win.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")))

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu.ndarray.sparse import RowSparseNDArray  # noqa: E402

CONFIGS = [
    # (rows, cols, occupied-row fraction)
    (100000, 128, 0.01),
    (100000, 128, 0.1),
    (1000000, 64, 0.001),
]


def measure(update, sync, repeat=10, bulk=0):
    """ms per update.  bulk mode: N updates recorded per segment, one
    flush per scope exit, sync OUTSIDE the scope (a sync inside would
    materialize and break the segment)."""
    if bulk:
        def run():
            with mx.engine.bulk(bulk + 1):
                for _ in range(bulk):
                    update()
            sync()
        run()                       # warm-up (compile the replay)
        t0 = time.perf_counter()
        run()
        return (time.perf_counter() - t0) / bulk
    # non-bulk: sync EVERY update (the round-4 methodology — per-dispatch
    # latency is part of what this mode measures)
    update(); sync()
    t0 = time.perf_counter()
    for _ in range(repeat):
        update()
        sync()
    return (time.perf_counter() - t0) / repeat


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--repeat", type=int, default=10)
    p.add_argument("--bulk", type=int, default=0,
                   help="defer N updates per XLA dispatch via engine.bulk")
    args = p.parse_args()
    rs = np.random.RandomState(0)
    for rows, cols, frac in CONFIGS:
        k = max(1, int(rows * frac))
        idx = np.sort(rs.choice(rows, size=k, replace=False))
        vals = rs.randn(k, cols).astype(np.float32)
        grad_rsp = RowSparseNDArray(mx.nd.array(vals),
                                    mx.nd.array(idx.astype(np.int64)),
                                    (rows, cols))
        grad_dense = mx.nd.array(grad_rsp.todense().asnumpy())

        opt = mx.optimizer.SGD(learning_rate=0.1, lazy_update=True)
        w_lazy = mx.nd.array(rs.randn(rows, cols).astype(np.float32))
        w_dense = mx.nd.array(w_lazy.asnumpy())

        t_lazy = measure(lambda: opt.update(0, w_lazy, grad_rsp, None),
                         w_lazy.wait_to_read, args.repeat, args.bulk)
        t_dense = measure(lambda: opt.update(1, w_dense, grad_dense, None),
                          w_dense.wait_to_read, args.repeat, args.bulk)
        print(json.dumps({
            "op": "sgd_update", "weight_shape": [rows, cols],
            "occupied_frac": frac, "bulk": args.bulk,
            "lazy_rsp_ms": round(t_lazy * 1e3, 3),
            "dense_ms": round(t_dense * 1e3, 3),
            "lazy_speedup": round(t_dense / t_lazy, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
