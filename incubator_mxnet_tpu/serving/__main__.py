"""graftserve CLI.

    python -m incubator_mxnet_tpu.serving --selftest
        Lint smoke tier: a hybridized MLP serves threaded traffic
        through the dynamic batcher (bit-parity vs the eager forward
        asserted per request), the per-request SLO decomposition must
        conserve exactly, a mid-traffic hot-swap must flip atomically
        (every response entirely old- or new-version), and a tight
        residency budget must LRU-evict and transparently reload.
        Exit 1 on any regression.

    python -m incubator_mxnet_tpu.serving --demo [--json]
        Small human-readable demo: serve a few hundred requests and
        print the SLO summary + registry stats.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading

import numpy as np


def _build_net(seed=0, din=16, dh=32, dout=8, scale=1.0):
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon

    class MLP(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d1 = gluon.nn.Dense(dh, activation="relu")
                self.d2 = gluon.nn.Dense(dout)

        def hybrid_forward(self, F, x):
            return F.tanh(self.d2(self.d1(x)))

    net = MLP()
    net.initialize()    # default context: the selftest pins jax to the CPU
    net.hybridize()
    rs = np.random.RandomState(seed)
    net(mx.nd.array(rs.randn(1, din).astype(np.float32)))  # shapes
    for _name, p in net.collect_params().items():
        p.data()._write(jnp.asarray(
            (rs.randn(*p.shape) * 0.5 * scale).astype(np.float32)))
    return net


def selftest():
    import jax
    jax.config.update("jax_platforms", "cpu")  # lint tier: a CPU program
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.telemetry import blackbox

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)
            print("graftserve selftest FAIL: %s" % msg, file=sys.stderr)

    din = 16
    net = _build_net(din=din)
    rs = np.random.RandomState(7)
    example = rs.randn(din).astype(np.float32)

    with serving.Server(max_batch=8, max_wait_ms=2) as srv:
        srv.load("mlp", block=net, example=mx.nd.array(example[None]))
        srv.warmup("mlp", example)      # the per-request example shape

        # threaded traffic: batched responses must be bit-equal to the
        # eager (unbatched) forward.  Requests are single examples of
        # shape (din,); the batcher stacks them under the batch axis.
        xs = [rs.randn(din).astype(np.float32) for _ in range(24)]
        futs = [None] * len(xs)

        def client(lo, hi):
            for i in range(lo, hi):
                futs[i] = srv.submit("mlp", xs[i])

        threads = [threading.Thread(target=client, args=(k * 8, k * 8 + 8))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [f.get(timeout=30.0) for f in futs]
        for i, (x, y) in enumerate(zip(xs, outs)):
            ref = net(mx.nd.array(x[None])).asnumpy()[0]
            if y.tobytes() != ref.tobytes():
                check(False, "request %d: batched != unbatched forward" % i)
                break
        else:
            print("parity: %d threaded requests bit-equal to the eager "
                  "forward" % len(outs))
        check(not srv.registry.get("mlp").no_batch,
              "the parity probe demoted a signature on the reference MLP")

        # SLO conservation: the four components sum EXACTLY to wall
        for f in futs:
            r = f.record
            s = sum(r["components"][c] for c in serving.slo.COMPONENTS)
            check(s == r["wall_s"],
                  "decomposition not conserved: %r != %r" % (s, r["wall_s"]))
        print("conservation: queue_wait+batch_assembly+device_compute+"
              "host_io == wall for all %d requests" % len(futs))

        # hot-swap mid-traffic: every response entirely old or new
        _fn, pv = net.serving_fn(mx.nd.array(example[None]))
        new_params = {n: np.asarray(v) * 2.0 for n, v in pv.items()}
        ticket = srv.begin_swap("mlp", new_params)
        pre = srv.predict("mlp", xs[0])     # old version still serving
        v2 = ticket.commit()
        post = srv.predict("mlp", xs[0])
        check(v2 == 2, "swap did not bump the version (got %r)" % v2)
        check(pre.tobytes() == outs[0].tobytes(),
              "pre-commit response changed under an in-flight swap")
        check(post.tobytes() != outs[0].tobytes(),
              "post-commit response still serves old weights")

        # batches actually batched + journaled
        evts = [e["data"] for e in blackbox.events()
                if e["kind"] == "serve_batch"]
        check(len(evts) >= 1, "no serve_batch journal events")
        check(any(e.get("size", 0) > 1 for e in evts),
              "no batch assembled more than one request")

    # LRU eviction under a tight budget: two models fit, the third
    # evicts the least-recently-used; a request to the evicted model
    # transparently reloads it
    h = serving.ModelRegistry(memory_bytes=1)      # nothing fits next to
    nets = [_build_net(seed=s) for s in (1, 2)]    # each other
    ha = h.load_block("a", nets[0], mx.nd.array(example[None]))
    hb = h.load_block("b", nets[1], mx.nd.array(example[None]))
    check(not ha.resident and hb.resident,
          "budget=1: expected only the newest model resident "
          "(a=%s b=%s)" % (ha.resident, hb.resident))
    h.acquire("a")                                  # reload a, evict b
    check(ha.resident and not hb.resident,
          "acquire did not reload the evicted model / evict the LRU one")
    check(h.reloads_total >= 1 and h.evictions_total >= 2,
          "eviction/reload counters did not move: %r" % (h.stats(),))
    print("residency: LRU eviction + transparent reload under a tight "
          "budget OK (evictions=%d reloads=%d)"
          % (h.evictions_total, h.reloads_total))

    if failures:
        print("graftserve selftest: %d failure(s)" % len(failures),
              file=sys.stderr)
        return 1
    print("graftserve selftest OK (batched parity, SLO conservation, "
          "atomic hot-swap, LRU residency)")
    return 0


def demo(as_json=False):
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import serving

    net = _build_net()
    rs = np.random.RandomState(3)
    with serving.Server(max_batch=16, max_wait_ms=1) as srv:
        srv.load("mlp", block=net, example=mx.nd.array(
            rs.randn(1, 16).astype(np.float32)))
        futs = [srv.submit("mlp", rs.randn(1, 16).astype(np.float32))
                for _ in range(256)]
        for f in futs:
            f.get(timeout=30.0)
        stats = srv.stats()
    dev = jax.devices()[0]
    stats["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
    if as_json:
        print(json.dumps(stats, default=str))
    else:
        s = stats["slo"]
        print("graftserve demo on %s (%s): %d requests, %d batches "
              "(mean batch %.1f)" % (dev.platform, dev.device_kind,
                                     stats["requests"], stats["batches"],
                                     s.get("mean_batch_size", 0)))
        print("  latency p50 %.3fms p99 %.3fms | components (mean ms): %s"
              % (s.get("p50_ms", 0), s.get("p99_ms", 0),
                 s.get("components_ms")))
        print("  registry: %s" % stats["registry"])
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m incubator_mxnet_tpu.serving")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.demo:
        return demo(as_json=args.json)
    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
