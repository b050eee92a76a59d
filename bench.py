"""Benchmark: ResNet-50 training throughput on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N,
   "platform": "tpu", "device_kind": ..., "device_count": N}

Baseline: the reference's published single-GPU ResNet-50 train number,
batch 32 — 90.74 img/s on M40 (docs/faq/perf.md:174; the K80 row is 45.52).
Same workload (ResNet-50, synthetic ImageNet shapes), run the TPU-native
way: ONE fused XLA train step (forward+loss+backward+SGD update) via
parallel.DataParallelTrainer, bf16 compute with f32 master weights
(mixed precision, reference mp_sgd semantics), batch 256.

The number is a device metric, so the script needs the device: without a
TPU it exits non-zero and prints nothing.  This process is the only one
that opens the chip (no probe child, no CPU fallback).  The timed region
ends on ``block_until_ready`` of the last step's loss — the donated
parameter chain makes it depend on every step.
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 90.74  # M40, ResNet-50 train batch 32 (docs/faq/perf.md:174)


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench.py measures a TPU and found %s (%s): no chip, no "
                 "number" % (dev.platform, dev.device_kind))
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel import make_mesh, DataParallelTrainer

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9},
        mesh=mesh, dtype=None if dtype in ("float32", "none") else dtype)

    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    rs = np.random.RandomState(0)
    extra = {}

    if os.environ.get("BENCH_DATA", "0") not in ("0", ""):
        # Feed training from a RecordIO file through the full data plane
        # (indexed reader → threaded raw decode → batch assembly →
        # PrefetchingIter): the reference's train_imagenet.py shape.
        #
        # Two quantities, kept apart on purpose: (a) the host pipeline's
        # standalone rate, (b) training over DISTINCT batches the pipeline
        # produced, placed on the device beforehand so that (b) is the
        # step alone and the smaller of (a) and (b) bounds a live run.
        from jax.sharding import NamedSharding, PartitionSpec as P
        n_batches = 4
        it = _make_rec_iter(mx, rs, batch, n_batches=n_batches)
        pipe0 = time.perf_counter()
        host_batches = []
        for _ in range(2 * n_batches):  # two epochs through the pipeline
            b = _next_cycled(it)
            host_batches.append((np.asarray(b.data[0]._read()),
                                 np.asarray(b.label[0]._read())))
        pipe_dt = time.perf_counter() - pipe0
        extra["host_pipeline_img_per_sec"] = round(
            len(host_batches) * batch / pipe_dt, 2)
        batch_sh = NamedSharding(mesh, P("dp"))
        batches = [(mx.nd.NDArray(jax.device_put(x, batch_sh)),
                    mx.nd.NDArray(jax.device_put(y, batch_sh)))
                   for x, y in host_batches[:n_batches]]
        metric = "resnet50_train_imgs_per_sec_per_chip_recordio"
    else:
        batches = [(mx.nd.array(rs.rand(batch, 3, 224, 224)
                                .astype(np.float32)),
                    mx.nd.array((rs.rand(batch) * 1000)
                                .astype(np.float32)))]
        metric = "resnet50_train_imgs_per_sec_per_chip"

    # warmup (compile); sync before the timed region starts
    for i in range(3):
        loss = trainer.step(*batches[i % len(batches)])
    loss.block_until_ready()

    t0 = time.perf_counter()
    for i in range(n_steps):
        loss = trainer.step(*batches[i % len(batches)])
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    assert np.isfinite(float(loss)), "bench loss went non-finite"

    img_s = n_steps * batch / dt
    print(json.dumps({
        "metric": metric,
        "value": round(img_s, 2),
        "unit": "img/s",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        **extra,
        "metrics": mx.telemetry.compact_snapshot(),
        "blackbox": mx.telemetry.blackbox.stats(),
    }))


def _make_rec_iter(mx, rs, batch, n_batches):
    """Write a raw-tensor .rec (if absent) and open the full pipeline over
    it: uint8 end-to-end on the host, cast to compute dtype on device."""
    from incubator_mxnet_tpu import recordio, io as mio
    n = batch * n_batches
    path = os.environ.get("BENCH_REC_PATH",
                          "/tmp/bench_imagenet_raw_%d" % n)
    if not os.path.exists(path + ".rec"):
        rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
        for i in range(n):
            img = (rs.rand(224, 224, 3) * 255).astype(np.uint8)
            header = recordio.IRHeader(0, float(i % 1000), i, 0)
            rec.write_idx(i, recordio.pack(header, img.tobytes()))
        rec.close()
    it = mio.ImageRecordIter(
        path_imgrec=path + ".rec", path_imgidx=path + ".idx",
        data_shape=(3, 224, 224), batch_size=batch, dtype="uint8",
        aug_list=[], preprocess_threads=2, prefetch_buffer=3,
        ctx=mx.cpu(0))
    return it


def _next_cycled(it):
    try:
        return it.next()
    except StopIteration:
        it.reset()
        return it.next()


if __name__ == "__main__":
    main()
