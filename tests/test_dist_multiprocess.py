"""Multi-process distributed KVStore + end-to-end training: REAL 2-worker runs.

Parity model: tests/nightly/dist_sync_kvstore.py + tests/nightly/dist_lenet.py
— N worker processes on one machine launched via tools/launch.py, asserting
(a) exact algebraic invariants of sync push/pull (value == sum over workers,
row-sparse union semantics) and (b) that a MODEL trains across processes via
every user-facing surface: Module.fit(kvstore="dist_sync"), Gluon Trainer,
and the fused DataParallelTrainer whose gradient psum runs INSIDE the jitted
step over the process-spanning mesh.  Workers rendezvous through the jax
coordination service (the ps-lite tracker's successor); the kvstore wire is
the in-graph all-reduce of parallel/dist.py:_global_sum.
"""
import os
import signal
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = textwrap.dedent("""
    import os, sys, traceback
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
""")


def _skipwrap(body):
    """Wrap a worker body so backends without multiprocess CPU collectives
    produce the ``SKIP-MULTIPROC`` sentinel (clean pytest.skip in
    ``_launch_two``) instead of a chronic red — same contract as the skew
    harness in test_blackbox.py."""
    return _PRELUDE + "try:\n" \
        + textwrap.indent(textwrap.dedent(body), "    ") \
        + textwrap.dedent("""
            except Exception:
                if "Multiprocess computations aren't implemented" \\
                        in traceback.format_exc():
                    print("SKIP-MULTIPROC", flush=True)
                    os._exit(0)
                raise
        """)


_KV_WORKER = _skipwrap("""
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw
    kv.init("w", nd.zeros((3, 2)))
    kv.push("w", nd.ones((3, 2)) * (rank + 1))     # 1 + 2 = 3
    out = nd.zeros((3, 2))
    kv.pull("w", out=out)
    assert np.allclose(out.asnumpy(), 3.0), out.asnumpy()

    kv.init(["a", "b"], [nd.zeros(2), nd.zeros(2)])
    kv.push(["a", "b"], [nd.ones(2) * (rank + 1),
                         nd.ones(2) * 10 * (rank + 1)])
    oa, ob = nd.zeros(2), nd.zeros(2)
    kv.pull(["a", "b"], out=[oa, ob])
    assert np.allclose(oa.asnumpy(), 3.0) and np.allclose(ob.asnumpy(), 30.0)

    # row-sparse push: workers hold DIFFERENT row sets; the reduce must
    # union row ids and sum overlaps (ref: comm.h ReduceRowSparse)
    kv.init("rs", nd.zeros((6, 3)))
    dense = np.zeros((6, 3), np.float32)
    for r in [rank, 2 + rank, 4]:
        dense[r] = rank + 1
    kv.push("rs", nd.array(dense).tostype("row_sparse"))
    ors = nd.zeros((6, 3))
    kv.pull("rs", out=ors)
    exp = np.zeros((6, 3), np.float32)
    exp[0], exp[1], exp[2], exp[3], exp[4] = 1, 2, 1, 2, 3
    assert np.allclose(ors.asnumpy(), exp), ors.asnumpy()

    # one distributed "train step": push local grads (summed across
    # workers), pull, apply — both workers land on identical params
    rng = np.random.RandomState(0)
    Xs = rng.randn(40, 6).astype(np.float32)[rank::2]
    grad = (Xs.T @ Xs / len(Xs)).astype(np.float32)[:3]   # (3, 6) shard grad
    kv.init("grad", nd.zeros((3, 6)))
    kv.push("grad", nd.array(grad))
    summed = nd.zeros((3, 6))
    kv.pull("grad", out=summed)
    w = 0.05 - 0.1 * summed.asnumpy() / nw
    from jax.experimental import multihost_utils
    both = multihost_utils.process_allgather(jax.numpy.asarray(w))
    assert np.allclose(both[0], both[1], atol=1e-6), "params diverged"

    # compressed push: the wire ships packed 2-bit words (1/16 bytes,
    # parallel/compression.py) and dequant+sum must match the residual
    # algebra exactly.  threshold 0.5; rank0 pushes 0.3 (below threshold,
    # q=0, residual 0.3), rank1 pushes 0.6 (q=0.5, residual 0.1) -> sum 0.5
    kvc = mx.kv.create("dist_sync")
    kvc.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kvc.init("cw", nd.zeros((5,)))
    kvc.push("cw", nd.ones((5,)) * (0.3 * (rank + 1)))
    oc = nd.zeros((5,))
    kvc.pull("cw", out=oc)
    assert np.allclose(oc.asnumpy(), 0.5), oc.asnumpy()
    # second identical push: rank0 acc 0.6 -> 0.5 ; rank1 acc 0.7 -> 0.5
    kvc.push("cw", nd.ones((5,)) * (0.3 * (rank + 1)))
    kvc.pull("cw", out=oc)
    assert np.allclose(oc.asnumpy(), 1.0), oc.asnumpy()
    print("WORKER %d COMPRESS OK" % rank, flush=True)

    # dist_async: true parameter-server semantics on the host service —
    # each push applies IMMEDIATELY server-side (parallel/ps.py); order
    # across ranks is free but the commutative SGD algebra pins the sum
    kva = mx.kv.create("dist_async")
    kva.init("aw", nd.ones((4,)) * 10.0)
    import incubator_mxnet_tpu.optimizer as opt
    kva.set_optimizer(opt.create("sgd", learning_rate=1.0))
    kva.push("aw", nd.ones((4,)) * (rank + 1))   # -1 and -2, any order
    kva.barrier()
    oa2 = nd.zeros((4,))
    kva.pull("aw", out=oa2)
    assert np.allclose(oa2.asnumpy(), 7.0), oa2.asnumpy()
    print("WORKER %d ASYNC OK" % rank, flush=True)

    kv.barrier()
    print("WORKER %d OK" % rank)
""")

# End-to-end model training across processes — the path that deadlocked in
# round 2 (collective-order mismatch).  Covers the reference's
# tests/nightly/dist_lenet.py semantics on all three training surfaces.
_TRAIN_WORKER = _skipwrap("""
    from incubator_mxnet_tpu import gluon, autograd
    from incubator_mxnet_tpu.parallel import dist
    from incubator_mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    from jax.experimental import multihost_utils

    def assert_synced(arr, tag):
        both = multihost_utils.process_allgather(jax.numpy.asarray(arr))
        assert np.allclose(both[0], both[1], atol=1e-5), tag + " diverged"

    rng = np.random.RandomState(42)
    X = rng.randn(64, 10).astype(np.float32)
    W = rng.randn(10, 1).astype(np.float32)
    y = (X @ W > 0).astype(np.float32).ravel()

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    Xs, ys = X[rank::nw], y[rank::nw]

    # --- surface 1: Module.fit(kvstore="dist_sync") ---------------------
    data = mx.io.NDArrayIter(Xs, ys, batch_size=8, shuffle=False,
                             label_name="softmax_label")
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(data, num_epoch=2, kvstore=kv,
            optimizer="sgd", optimizer_params={"learning_rate": 0.05},
            initializer=mx.init.Xavier(magnitude=2.0))
    assert_synced(mod.get_params()[0]["fc1_weight"].asnumpy(), "fit")
    print("WORKER %d FIT OK" % rank, flush=True)

    # --- surface 2: Gluon Trainer over the dist kvstore -----------------
    gnet = gluon.nn.Sequential()
    gnet.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(2))
    gnet.initialize(mx.init.Xavier(magnitude=2.0))
    kv2 = mx.kv.create("dist_sync")     # own store: int keys are per-store
    trainer = gluon.Trainer(gnet.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore=kv2)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for epoch in range(8):
        tot = 0.0
        for i in range(0, len(Xs), 8):
            xb, yb = nd.array(Xs[i:i+8]), nd.array(ys[i:i+8])
            with autograd.record():
                loss = loss_fn(gnet(xb), yb)
            loss.backward()
            trainer.step(8 * nw)
            tot += float(loss.asnumpy().mean())
        losses.append(tot)
    assert losses[-1] < losses[0], losses
    assert_synced(gnet[0].weight.data().asnumpy(), "trainer")
    print("WORKER %d TRAINER OK" % rank, flush=True)

    # --- surface 3: fused DataParallelTrainer, psum IN the jitted step --
    hnet = gluon.nn.HybridSequential()
    hnet.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(2))
    hnet.initialize(mx.init.Xavier(magnitude=2.0))
    tr = DataParallelTrainer(hnet, loss_fn, "sgd",
                             {"learning_rate": 0.05})
    yl = y.astype(np.int64)
    dlosses = []
    for ep in range(10):
        for i in range(0, 64, 16):
            lo = rank * 8
            loss = tr.step(X[i:i+16][lo:lo+8], yl[i:i+16][lo:lo+8])
            dlosses.append(float(jax.device_get(loss.addressable_data(0))))
    head, tail = np.mean(dlosses[:4]), np.mean(dlosses[-4:])
    assert tail < head, (head, tail, dlosses)
    tr.sync_params()
    assert_synced(hnet[0].weight.data().asnumpy(), "dpt")
    print("WORKER %d DPT OK" % rank, flush=True)
""")


def _launch_two(tmp_path, source, timeout=300, n=2, port_base=9300,
                require_rc0=True):
    worker = tmp_path / "worker.py"
    worker.write_text(source)
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(repo) + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    port = port_base + os.getpid() % 500  # avoid collisions between runs
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", str(n), "-p", str(port), sys.executable, str(worker)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        # a hang here IS the failure mode this test exists to catch;
        # kill the whole process group so the workers don't leak
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        pytest.fail("%d-process dist run deadlocked (%ds timeout)"
                    % (n, timeout))
    out = stdout + stderr
    if "SKIP-MULTIPROC" in out:
        pytest.skip("backend lacks multiprocess CPU collectives")
    if require_rc0:
        assert proc.returncode == 0, out[-3000:]
    return out


def test_two_process_dist_sync(tmp_path):
    out = _launch_two(tmp_path, _KV_WORKER, timeout=240)
    assert "WORKER 0 OK" in out and "WORKER 1 OK" in out, out[-2000:]
    assert "WORKER 0 COMPRESS OK" in out and "WORKER 1 COMPRESS OK" in out, \
        out[-2000:]
    assert "WORKER 0 ASYNC OK" in out and "WORKER 1 ASYNC OK" in out, \
        out[-2000:]


def test_two_process_end_to_end_training(tmp_path):
    """Round-2's known deadlock path: a model must actually TRAIN across
    processes on every surface (ref: tests/nightly/dist_lenet.py)."""
    out = _launch_two(tmp_path, _TRAIN_WORKER, timeout=420)
    for rank in (0, 1):
        for tag in ("FIT", "TRAINER", "DPT"):
            assert "WORKER %d %s OK" % (rank, tag) in out, out[-3000:]


_COMPRESS4_WORKER = _skipwrap("""
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 4, nw
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("cw", nd.zeros((37,)))     # odd length: exercises word padding
    # rank r pushes 0.3*(r+1): q = {0, 0.5, 0.5, 0.5}, residuals kept
    kv.push("cw", nd.ones((37,)) * (0.3 * (rank + 1)))
    out = nd.zeros((37,))
    kv.pull("cw", out=out)
    assert np.allclose(out.asnumpy(), 1.5), out.asnumpy()
    # second push: acc = residual + new = {0.6, 0.7, 1.3, 1.9}; the 2-bit
    # code takes ONE +-t step per push -> q = 0.5 everywhere -> sum 2.0
    kv.push("cw", nd.ones((37,)) * (0.3 * (rank + 1)))
    kv.pull("cw", out=out)
    assert np.allclose(out.asnumpy(), 2.0), out.asnumpy()
    print("WORKER %d COMPRESS4 OK" % rank, flush=True)
""")


def test_four_process_compressed_wire(tmp_path):
    """W=4 compressed reduce: the scale-correct wire (compressed
    reduce-scatter + int8 sum gather) must keep the exact residual
    algebra beyond the W=2 case the old allgather wire was tested at."""
    out = _launch_two(tmp_path, _COMPRESS4_WORKER, timeout=300, n=4,
                      port_base=9800)
    for rank in range(4):
        assert "WORKER %d COMPRESS4 OK" % rank in out, out[-3000:]


_DEAD_NODE_WORKER = _skipwrap("""
    import time
    kv = mx.kv.create("dist_async")
    rank, nw = kv.rank, kv.num_workers
    kv.init("w", nd.ones((4,)))
    kv.barrier()
    if rank == 1:
        # die without ceremony: heartbeats stop mid-job
        print("WORKER 1 DYING", flush=True)
        os._exit(0)
    # rank 0: watch the heartbeat table flip the dead worker
    deadline = time.time() + 30
    n = 0
    while time.time() < deadline:
        n = kv.num_dead_nodes(timeout_sec=2)
        if n == 1:
            break
        time.sleep(0.5)
    assert n == 1, n
    # the timeout path is SURFACED, not a silent return (graftwatch):
    # the gauge tracks the count and the flight recorder holds an event
    # naming the dead worker
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import blackbox
    assert telemetry.registry().gauge("graft_dist_dead_nodes").value() \
        == 1
    dead_evs = [e for e in blackbox.events() if e["kind"] == "dead_nodes"]
    assert dead_evs, blackbox.events()
    assert dead_evs[-1]["data"]["dead"] == [1], dead_evs[-1]
    print("WORKER 0 DEADNODE OK", flush=True)
    os._exit(0)   # skip jax.distributed teardown: rank 1 is gone
""")


def test_async_dead_node_detection(tmp_path):
    """Kill a worker mid-job: the parameter service's heartbeat table must
    surface num_dead_nodes == 1 (kvstore_dist.h:109-115) — through the
    graft_dist_dead_nodes gauge and a flight-recorder event, not just
    the return value (asserted inside the worker)."""
    # the launcher reports nonzero when a worker vanishes mid-job (the
    # coordination service flags the lost member) — that's the scenario
    # under test, so only the rank-0 marker matters
    out = _launch_two(tmp_path, _DEAD_NODE_WORKER, timeout=240,
                      port_base=9600, require_rc0=False)
    assert "WORKER 0 DEADNODE OK" in out, out[-3000:]
    assert "WORKER 1 DYING" in out, out[-3000:]


_CHAOS_WORKER_TMPL = _skipwrap("""
    import hashlib
    os.environ["GRAFT_RPC_BACKOFF_MS"] = "1"
    os.environ["GRAFT_FAULTS"] = "@FAULTS@"
    kva = mx.kv.create("dist_async")
    rank = kva.rank
    import incubator_mxnet_tpu.optimizer as opt
    kva.init("w", nd.ones((8,)) * 64.0)
    kva.set_optimizer(opt.create("sgd", learning_rate=1.0))
    # exact integer algebra: each push applies w -= grad server-side, so
    # ANY interleave/retry schedule that applies each push EXACTLY ONCE
    # lands on 64 - 5*(1+2) = 49 bit-for-bit.  A dropped-reply retry
    # that double-applied would land on != 49 and break the parity hash.
    for step in range(5):
        kva.push("w", nd.ones((8,)) * (rank + 1))
        kva.barrier()
    out = nd.zeros((8,))
    kva.pull("w", out=out)
    arr = np.asarray(out.asnumpy(), np.float32)
    assert np.allclose(arr, 49.0), arr
    from incubator_mxnet_tpu.telemetry import blackbox
    n_inj = len([e for e in blackbox.events()
                 if e["kind"] == "fault_injected"])
    print("CHAOS %d SHA %s INJ %d"
          % (rank, hashlib.sha256(arr.tobytes()).hexdigest(), n_inj),
          flush=True)
    kva.barrier()
""")


def _chaos_shas(out):
    shas, inj = {}, {}
    for line in out.splitlines():
        if line.startswith("CHAOS "):
            parts = line.split()
            shas[int(parts[1])] = parts[3]
            inj[int(parts[1])] = int(parts[5])
    return shas, inj


def test_two_process_chaos_parity(tmp_path):
    """graftarmor chaos gate: the same dist_async run under injected PS
    wire faults (dropped replies, mid-push disconnects on both ranks)
    must be BYTE-EQUAL to the un-faulted run — retries are idempotent
    (server-side dedup), reconnects are transparent."""
    clean = _launch_two(tmp_path,
                        _CHAOS_WORKER_TMPL.replace("@FAULTS@", ""),
                        timeout=240, port_base=10300)
    shas0, inj0 = _chaos_shas(clean)
    assert set(shas0) == {0, 1}, clean[-2000:]
    assert inj0 == {0: 0, 1: 0}, inj0

    spec = ("ps.recv:drop:n=2:cmd=push:rank=0;"
            "ps.send:disconnect:n=3:cmd=push:rank=1;"
            "ps.recv:drop:n=4:cmd=push:rank=1")
    chaos = _launch_two(tmp_path,
                        _CHAOS_WORKER_TMPL.replace("@FAULTS@", spec),
                        timeout=240, port_base=10300)
    shas1, inj1 = _chaos_shas(chaos)
    assert set(shas1) == {0, 1}, chaos[-2000:]
    assert inj1[0] >= 1 and inj1[1] >= 2, inj1   # the chaos really fired
    assert shas1 == shas0, (shas0, shas1)        # ...and changed nothing


_KILL_RESUME_WORKER = _skipwrap("""
    import time
    os.environ["GRAFT_RPC_BACKOFF_MS"] = "1"
    # rank 1 is killed mid-push (injected SIGKILL-style os._exit) — the
    # kill-rank-mid-step harness; rank 0 must see the dead rank AND its
    # own checkpoint/resume must replay the loss trajectory bit-exactly
    os.environ["GRAFT_FAULTS"] = "ps.send:kill:n=3:rank=1"
    kv = mx.kv.create("dist_async")
    rank = kv.rank
    kv.init("w", nd.ones((4,)))
    kv.barrier()
    if rank == 1:
        print("WORKER 1 PUSHING UNTIL KILLED", flush=True)
        for _ in range(10):
            kv.push("w", nd.ones((4,)))     # 3rd send never returns
        raise AssertionError("injected kill did not fire")

    from incubator_mxnet_tpu import gluon, autograd
    net = gluon.nn.Dense(4)
    net.initialize(ctx=mx.cpu())
    rng = np.random.RandomState(7)
    batches = [rng.randn(2, 6).astype(np.float32) for _ in range(7)]
    net(nd.array(batches[0]))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})

    def step(i):
        x = nd.array(batches[i])
        with autograd.record():
            loss = (net(x) * net(x)).sum()
        loss.backward()
        trainer.step(2)
        return float(loss.asnumpy())

    # beside this worker script, i.e. in the test's tmp_path, not in cwd
    ckdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "graft-ckpt-%d" % os.getpid())
    cp = trainer.checkpointer(ckdir, keep=3, emergency=False)
    first = []
    for i in range(6):
        first.append(step(i))
        if i == 2:
            cp.save(step=2)
    restored = cp.resume()
    assert restored == 2, restored
    replay = [step(i) for i in range(3, 6)]
    assert replay == first[3:], (replay, first[3:])   # bit-exact losses
    print("WORKER 0 RESUME OK", flush=True)

    deadline = time.time() + 30
    n = 0
    while time.time() < deadline:
        n = kv.num_dead_nodes(timeout_sec=2)
        if n == 1:
            break
        time.sleep(0.5)
    assert n == 1, n
    print("WORKER 0 KILLRESUME OK", flush=True)
    os._exit(0)   # skip jax.distributed teardown: rank 1 is gone
""")


def test_kill_rank_checkpoint_resume(tmp_path):
    """graftarmor fail-recover gate: rank 1 dies mid-push via the
    injected kill harness; rank 0's heartbeat table flips the dead rank
    and its checkpoint resume() replays the loss trajectory bit-exactly
    (params + momentum + RNG restored)."""
    out = _launch_two(tmp_path, _KILL_RESUME_WORKER, timeout=240,
                      port_base=10600, require_rc0=False)
    assert "WORKER 1 PUSHING UNTIL KILLED" in out, out[-3000:]
    assert "graftarmor: injected kill" in out, out[-3000:]
    assert "WORKER 0 RESUME OK" in out, out[-3000:]
    assert "WORKER 0 KILLRESUME OK" in out, out[-3000:]


def test_num_dead_nodes_surfaces_gauge_single_process():
    """Single-process contract of the same surfacing: the sync wire
    always answers 0, and the answer lands on the gauge (runnable
    without multi-host collectives)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry
    kv = mx.kv.create("dist_sync")
    assert kv.num_dead_nodes() == 0
    assert telemetry.registry().gauge("graft_dist_dead_nodes").value() == 0
