"""graftserve load generator — p50/p99 latency vs offered QPS, and
batched-vs-serial throughput (ISSUE 11, the ROADMAP serving scenario).

The model under load is a small MLP served two ways:

* **serial** — the pre-graftserve path: one ``Module.predict`` call per
  request (per-op executor replay at batch 1), the baseline every
  framework ships first;
* **batched** — the graftserve runtime: requests enqueue into the
  dynamic batcher, assemble under GRAFT_SERVE_MAX_BATCH /
  GRAFT_SERVE_MAX_WAIT_MS, and dispatch as ONE compiled call per padded
  shape bucket (default ``exact`` batch mode: every row IS the
  unbatched graph, so responses are asserted BIT-EQUAL to the serial
  ``Module.predict`` outputs before any throughput number is reported
  — the PR 4 oracle discipline).

Sections (all land in ONE BENCH JSON line):

* ``serve_serial_qps`` / ``serve_batched_qps`` /
  ``serve_batched_speedup`` — closed-loop: K client threads submitting
  back-to-back; the speedup bar is ≥ 3x (asserted);
* ``serve_qps_points`` — open-loop: a paced arrival stream at ≥ 3
  offered rates (fractions of the measured capacity), reporting
  p50/p99 end-to-end latency and the achieved rate at each point;
* mean SLO component split (queue_wait/batch_assembly/device_compute/
  host_io) over the run, the ``graft_serve_*`` metrics snapshot and the
  flight-recorder status.

``--smoke`` runs the same sections at small counts for the lint tier.
"""
import json
import sys
import threading
import time

import numpy as np

DIN, DHID, DOUT = 16, 32, 8


def _build_module(batch=1):
    """The bench model as a bound inference Module (symbol path — the
    serial baseline AND the serving source, so both serve the exact
    same weights)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import symbol as sym
    from incubator_mxnet_tpu.module import Module

    net = sym.FullyConnected(sym.var("data"), num_hidden=DHID, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=DOUT, name="fc2")
    net = sym.tanh(net, name="out")
    mod = Module(symbol=net, data_names=("data",), label_names=None,
                 context=mx.current_context())
    mod.bind(data_shapes=[("data", (batch, DIN))], label_shapes=None,
             for_training=False)
    mod.init_params(initializer=mx.initializer.Uniform(0.07))
    return mod


def _serial_qps(mod, xs, iters):
    """The per-request Module.predict loop (one forward per request)."""
    import incubator_mxnet_tpu as mx
    outs = []
    mod.predict(mx.nd.array(xs[0][None]))           # warm the executor
    t0 = time.perf_counter()
    for i in range(iters):
        outs.append(mod.predict(
            mx.nd.array(xs[i % len(xs)][None])).asnumpy()[0])
    dt = time.perf_counter() - t0
    return iters / dt, outs


def _closed_loop(srv, name, xs, n_clients, per_client):
    """K threads each submitting back-to-back; returns (qps, outputs in
    submit order per client)."""
    outs = [[None] * per_client for _ in range(n_clients)]

    def client(k):
        futs = []
        for i in range(per_client):
            futs.append(srv.submit(name, xs[(k * per_client + i) % len(xs)]))
        for i, f in enumerate(futs):
            outs[k][i] = f.get(timeout=120.0)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    return n_clients * per_client / dt, outs


def _open_loop(srv, name, xs, rate, n):
    """Paced arrivals at ``rate`` req/s; returns the latency/achieved
    stats for one offered-QPS point."""
    futs = []
    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + i / rate
        while True:
            now = time.perf_counter()
            if now >= target:
                break
            time.sleep(min(target - now, 1e-3))
        futs.append(srv.submit(name, xs[i % len(xs)]))
    for f in futs:
        f.get(timeout=120.0)
    dt = time.perf_counter() - t0
    walls = sorted(f.record["wall_s"] for f in futs)
    return {
        "offered_qps": round(rate, 1),
        "achieved_qps": round(n / dt, 1),
        "p50_ms": round(walls[len(walls) // 2] * 1e3, 3),
        "p99_ms": round(walls[min(int(len(walls) * 0.99),
                                  len(walls) - 1)] * 1e3, 3),
    }


def run(smoke=False):
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.serving import slo

    serial_iters = 40 if smoke else 200
    n_clients = 2 if smoke else 4
    per_client = 48 if smoke else 400
    open_n = 40 if smoke else 200

    rs = np.random.RandomState(0)
    xs = [rs.randn(DIN).astype(np.float32) for _ in range(64)]
    mod = _build_module()

    # -- serial baseline: the per-request Module.predict loop ------------
    serial_qps, serial_outs = _serial_qps(mod, xs, serial_iters)

    slo.reset()
    with serving.Server(max_batch=32, max_wait_ms=2) as srv:
        srv.load("bench", module=mod)
        srv.warmup("bench", xs[0])

        # -- parity gate: batched == the serial unbatched forward --------
        futs = [srv.submit("bench", x) for x in xs]
        served = [f.get(timeout=120.0) for f in futs]
        for i, (x, y) in enumerate(zip(xs, served)):
            ref = mod.predict(mx.nd.array(x[None])).asnumpy()[0]
            assert y.tobytes() == ref.tobytes(), \
                "serving output %d diverged from the unbatched " \
                "Module.predict forward" % i
        parity = True

        # -- closed-loop throughput --------------------------------------
        batched_qps, outs = _closed_loop(srv, "bench", xs, n_clients,
                                         per_client)
        # spot-check closed-loop rows against the serial oracle
        for j in range(min(len(xs), 16)):
            ref = mod.predict(mx.nd.array(xs[j][None])).asnumpy()[0]
            assert outs[0][j].tobytes() == ref.tobytes(), \
                "closed-loop output %d diverged from Module.predict" % j
        speedup = batched_qps / serial_qps

        # -- open-loop latency vs offered QPS ----------------------------
        cap = batched_qps
        rates = [max(cap * f, 20.0) for f in (0.2, 0.5, 0.9)]
        points = [_open_loop(srv, "bench", xs, rate, open_n)
                  for rate in rates]

        summary = slo.summary()
        stats = srv.stats()

    dev = jax.devices()[0]
    result = {
        "metric": "serving",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "model": "mlp_%d_%d_%d" % (DIN, DHID, DOUT),
        "serve_parity": parity,
        "serve_batch_mode": serving.serve_batch_mode(),
        "serve_serial_qps": round(serial_qps, 1),
        "serve_batched_qps": round(batched_qps, 1),
        "serve_batched_speedup": round(speedup, 2),
        "serve_qps_points": points,
        "serve_mean_batch_size": summary.get("mean_batch_size"),
        "serve_components_ms": summary.get("components_ms"),
        "serve_p50_ms": summary.get("p50_ms"),
        "serve_p99_ms": summary.get("p99_ms"),
        "serve_registry": stats["registry"],
        "metrics": {k: v for k, v in
                    mx.telemetry.compact_snapshot().items()
                    if k.startswith("graft_serve")},
        "blackbox": mx.telemetry.blackbox.stats(),
    }
    assert speedup >= 3.0, \
        "batched dispatch only %.2fx the serial Module.predict loop " \
        "(bar: 3x)" % speedup
    print(json.dumps(result))


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv[1:])
