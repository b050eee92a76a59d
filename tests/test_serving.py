"""graftserve tests (ISSUE 11): dynamic batching with bit-parity across
shape buckets and dtypes, max-wait timeout flush, LRU eviction under a
tight budget, mid-traffic hot-swap with no torn weights, watchdog-named
stalled batches, per-request SLO conservation, the parity-probe demotion
rail, the in-memory C-predict loader, and
GRAFT_TSAN coverage of the serving threads + KVStore._store."""
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, serving
from incubator_mxnet_tpu.analysis import tsan
from incubator_mxnet_tpu.telemetry import blackbox, watchdog

DIN, DHID, DOUT = 12, 16, 4


class _MLP(gluon.HybridBlock):
    def __init__(self, dh=DHID, dout=DOUT, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.d1 = gluon.nn.Dense(dh, activation="relu")
            self.d2 = gluon.nn.Dense(dout)

    def hybrid_forward(self, F, x):
        return F.tanh(self.d2(self.d1(x)))


class _BatchCoupled(gluon.HybridBlock):
    """Deliberately row-COUPLED forward (subtracts the batch mean): in
    ``fused`` batch mode its batched rows genuinely differ from the
    unbatched forward, so it deterministically triggers the parity
    probe; in ``exact`` mode every row is its own subgraph and parity
    holds structurally."""

    def hybrid_forward(self, F, x):
        return x - F.mean(x, axis=0, keepdims=True)


def _mlp(seed=0, din=DIN, scale=0.5):
    import jax.numpy as jnp
    net = _MLP()
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    rs = np.random.RandomState(seed)
    net(mx.nd.array(rs.randn(1, din).astype(np.float32)))
    for _name, p in net.collect_params().items():
        p.data()._write(jnp.asarray(
            (rs.randn(*p.shape) * scale).astype(np.float32)))
    return net


def _serve(net, n_req=12, seed=3, din=DIN, **srv_kw):
    """Serve n_req threaded single-example requests; returns (inputs,
    outputs, futures)."""
    rs = np.random.RandomState(seed)
    xs = [rs.randn(din).astype(np.float32) for _ in range(n_req)]
    kw = dict(max_batch=8, max_wait_ms=3)
    kw.update(srv_kw)
    with serving.Server(**kw) as srv:
        srv.load("m", block=net, example=mx.nd.array(xs[0][None]))
        futs = [None] * n_req

        def client(lo, hi):
            for i in range(lo, hi):
                futs[i] = srv.submit("m", xs[i])

        step = max(n_req // 3, 1)
        threads = [threading.Thread(target=client,
                                    args=(lo, min(lo + step, n_req)))
                   for lo in range(0, n_req, step)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [f.get(timeout=60.0) for f in futs]
    return xs, outs, futs


# ---------------------------------------------------------------------------
# batched-vs-unbatched bit parity
# ---------------------------------------------------------------------------

def test_batched_bit_parity_across_shape_buckets():
    """Requests of different example shapes (and hence different batch
    buckets) compile separate signatures and every response is
    bit-equal to the eager unbatched forward."""
    net = _mlp()
    coupled = _BatchCoupled()       # shape-agnostic forward
    coupled.initialize(ctx=mx.cpu())
    rs = np.random.RandomState(1)
    flat = [rs.randn(DIN).astype(np.float32) for _ in range(9)]
    wide = [rs.randn(5, 7).astype(np.float32) for _ in range(7)]
    with serving.Server(max_batch=8, max_wait_ms=3) as srv:
        srv.load("m", block=net, example=mx.nd.array(flat[0][None]))
        srv.load("c", block=coupled, example=mx.nd.array(wide[0][None]))
        futs = [("m", x, srv.submit("m", x)) for x in flat] + \
               [("c", x, srv.submit("c", x)) for x in wide]
        for name, x, f in futs:
            y = f.get(timeout=60.0)
            blk = net if name == "m" else coupled
            ref = blk(mx.nd.array(x[None])).asnumpy()[0]
            assert y.tobytes() == ref.tobytes()
            assert y.shape == ref.shape


def test_batched_bit_parity_float16():
    """A second dtype (f16) gets its own signatures and keeps parity
    (f64 needs jax x64 mode, unavailable on this CPU config)."""
    import jax.numpy as jnp
    net = _MLP()
    net.initialize(ctx=mx.cpu())
    net.cast("float16")
    rs = np.random.RandomState(2)
    net(mx.nd.array(rs.randn(1, DIN).astype(np.float16)))
    for _name, p in net.collect_params().items():
        p.data()._write(jnp.asarray(rs.randn(*p.shape).astype(np.float16)))
    xs = [rs.randn(DIN).astype(np.float16) for _ in range(6)]
    with serving.Server(max_batch=4, max_wait_ms=3) as srv:
        srv.load("m16", block=net, example=mx.nd.array(xs[0][None]))
        futs = [srv.submit("m16", x) for x in xs]
        for x, f in zip(xs, futs):
            y = f.get(timeout=60.0)
            assert y.dtype == np.float16
            ref = net(mx.nd.array(x[None])).asnumpy()[0]
            assert y.tobytes() == ref.tobytes()


def test_batches_assemble_and_cap_at_max_batch():
    """With a generous wait window the queue fills, batches cap at
    GRAFT_SERVE_MAX_BATCH and size > 1 actually happens."""
    blackbox.set_enabled(True)
    try:
        blackbox._ring.clear()
        net = _mlp()
        rs = np.random.RandomState(4)
        xs = [rs.randn(DIN).astype(np.float32) for _ in range(10)]
        with serving.Server(max_batch=4, max_wait_ms=150) as srv:
            srv.load("m", block=net, example=mx.nd.array(xs[0][None]))
            srv.warmup("m", xs[0])
            futs = [srv.submit("m", x) for x in xs]
            for f in futs:
                f.get(timeout=60.0)
        sizes = [e["data"]["size"] for e in blackbox.events()
                 if e["kind"] == "serve_batch"]
        assert sizes and max(sizes) > 1
        assert all(s <= 4 for s in sizes)
        assert sum(sizes) == len(xs)
    finally:
        blackbox.set_enabled(None)


def test_max_wait_timeout_flushes_partial_batch():
    """A single request must not wait for a full batch: the max-wait
    flush dispatches it after ~GRAFT_SERVE_MAX_WAIT_MS."""
    net = _mlp()
    x = np.random.RandomState(5).randn(DIN).astype(np.float32)
    with serving.Server(max_batch=32, max_wait_ms=40) as srv:
        srv.load("m", block=net, example=mx.nd.array(x[None]))
        srv.warmup("m", x, buckets=[1])
        t0 = time.perf_counter()
        y = srv.submit("m", x).get(timeout=60.0)
        dt = time.perf_counter() - t0
    ref = net(mx.nd.array(x[None])).asnumpy()[0]
    assert y.tobytes() == ref.tobytes()
    assert dt >= 0.040                   # held for the wait window
    assert dt < 10.0                     # but flushed, not starved


# ---------------------------------------------------------------------------
# SLO decomposition
# ---------------------------------------------------------------------------

def test_per_request_decomposition_conserves_exactly():
    net = _mlp()
    _xs, _outs, futs = _serve(net, n_req=12)
    for f in futs:
        rec = f.record
        comp = rec["components"]
        total = sum(comp[c] for c in serving.slo.COMPONENTS)
        assert total == rec["wall_s"]            # EXACT, not approx
        for c in ("queue_wait", "batch_assembly", "device_compute"):
            assert comp[c] >= 0.0
        assert comp["host_io"] > -1e-9           # residual, ~>= 0
        assert rec["wall_s"] > 0.0


def test_slo_ring_quantiles_and_metrics():
    serving.slo.reset()
    net = _mlp()
    _xs, _outs, futs = _serve(net, n_req=10)
    s = serving.slo.summary()
    assert s["ok"] >= 10
    assert 0 < s["p50_ms"] <= s["p99_ms"]
    snap = mx.telemetry.compact_snapshot()
    assert snap.get('graft_serve_requests_total{model="m"}', 0) >= 10
    assert snap.get('graft_serve_latency_seconds{quantile="p50"}', 0) > 0
    assert snap.get('graft_serve_batch_size_count', 0) >= 1


def test_serve_batch_journal_in_flight_bracket():
    """Batches journal into the flight recorder with size/bucket/model,
    and the dispatch runs inside a serve_batch bracket."""
    blackbox.set_enabled(True)
    try:
        blackbox._ring.clear()
        net = _mlp()
        _serve(net, n_req=6)
        evts = [e["data"] for e in blackbox.events()
                if e["kind"] == "serve_batch"]
        assert evts
        for e in evts:
            assert e["model"] == "m"
            assert e["size"] >= 1 and e["bucket"] >= e["size"]
            assert "compute_ms" in e
    finally:
        blackbox.set_enabled(None)


# ---------------------------------------------------------------------------
# residency: LRU eviction + reload
# ---------------------------------------------------------------------------

def test_lru_eviction_and_transparent_reload():
    nets = [_mlp(seed=s) for s in (1, 2, 3)]
    x = np.random.RandomState(9).randn(DIN).astype(np.float32)
    probe = serving.ModelRegistry()
    model_bytes = probe.load_block("p", nets[0],
                                   mx.nd.array(x[None])).nbytes
    budget = 2 * model_bytes + 1                         # exactly 2 fit
    reg = serving.ModelRegistry(memory_bytes=budget)
    ha = reg.load_block("a", nets[0], mx.nd.array(x[None]))
    hb = reg.load_block("b", nets[1], mx.nd.array(x[None]))
    assert ha.resident and hb.resident
    hc = reg.load_block("c", nets[2], mx.nd.array(x[None]))
    # a was least-recently used -> evicted
    assert not ha.resident and hb.resident and hc.resident
    assert reg.resident_bytes() <= budget
    # touching b keeps it hot; acquiring a reloads it and evicts the LRU
    reg.acquire("b")
    entry, params, version = reg.acquire("a")
    assert ha.resident and version == 1 and params
    assert not hc.resident                  # c was now least-recently used
    assert reg.reloads_total == 1 and reg.evictions_total == 2
    # the reloaded weights still serve bit-identically
    y = ha.predict(x[None])
    ref = nets[0](mx.nd.array(x[None])).asnumpy()
    assert np.asarray(y).tobytes() == ref.tobytes()


def test_eviction_with_requests_through_server():
    nets = [_mlp(seed=s) for s in (1, 2)]
    x = np.random.RandomState(8).randn(DIN).astype(np.float32)
    with serving.Server(memory_bytes=1, max_batch=4, max_wait_ms=2) as srv:
        srv.load("a", block=nets[0], example=mx.nd.array(x[None]))
        srv.load("b", block=nets[1], example=mx.nd.array(x[None]))
        # only b resident; a request to a reloads it transparently
        assert not srv.registry.get("a").resident
        ya = srv.predict("a", x)
        refa = nets[0](mx.nd.array(x[None])).asnumpy()[0]
        assert ya.tobytes() == refa.tobytes()
        assert srv.registry.reloads_total >= 1


def test_eviction_reload_restores_load_time_weights():
    """An evicted model reloads the weights REGISTERED at load time —
    training the source block further must not fast-forward a served
    model without a version bump (new weights ship via swap only)."""
    import jax.numpy as jnp
    net = _mlp(seed=6)
    x = np.random.RandomState(10).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    h = reg.load_block("m", net, mx.nd.array(x[None]))
    ref = np.asarray(h.predict(x[None]))
    # "retrain" the source block after registration
    for _n, p in net.collect_params().items():
        p.data()._write(p.data()._read() * 3.0)
    assert reg.evict("m") and not h.resident
    got = np.asarray(h.predict(x[None]))        # transparent reload
    assert got.tobytes() == ref.tobytes()       # load-time weights
    assert h.version == 1
    assert got.tobytes() != net(mx.nd.array(x[None])).asnumpy().tobytes()


def test_reload_runs_outside_the_registry_lock():
    """ISSUE 12 satellite (ROADMAP 11e): a cold model's transparent
    reload — seconds of parse + H2D in production — must not stall
    OTHER models' dispatches under the registry lock.  A deliberately
    gated slow loader holds model a's reload open while the main thread
    acquires model b: with the reload under the lock this blocks until
    the gate opens; outside it, b returns immediately."""
    nets = [_mlp(seed=1), _mlp(seed=2)]
    x = np.random.RandomState(21).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    ha = reg.load_block("a", nets[0], mx.nd.array(x[None]))
    reg.load_block("b", nets[1], mx.nd.array(x[None]))
    assert reg.evict("a") and not ha.resident
    orig_loader = reg.get("a")._loader
    started, release = threading.Event(), threading.Event()

    def slow_loader():
        started.set()
        release.wait(10.0)
        return orig_loader()

    reg.get("a")._loader = slow_loader
    reloader = threading.Thread(target=lambda: reg.acquire("a"),
                                daemon=True)
    reloader.start()
    assert started.wait(10.0)
    t0 = time.perf_counter()
    _entry, params, _v = reg.acquire("b")       # must not block on a's
    blocked_s = time.perf_counter() - t0        # in-flight reload
    still_loading = not release.is_set() and reloader.is_alive()
    release.set()
    reloader.join(10.0)
    assert still_loading, "gate opened early — the probe proved nothing"
    assert params and blocked_s < 5.0
    assert ha.resident                          # a's reload completed
    y = ha.predict(x[None])
    ref = nets[0](mx.nd.array(x[None])).asnumpy()
    assert np.asarray(y).tobytes() == ref.tobytes()


def test_reload_latch_serializes_concurrent_acquires():
    """Concurrent acquires of the SAME cold model run the loader ONCE:
    followers wait on the per-entry latch (not the registry lock) and
    then see the installed weights."""
    net = _mlp(seed=3)
    x = np.random.RandomState(22).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    ha = reg.load_block("a", net, mx.nd.array(x[None]))
    assert reg.evict("a")
    orig_loader = reg.get("a")._loader
    calls = [0]
    gate = threading.Event()

    def slow_loader():
        calls[0] += 1
        gate.wait(10.0)
        return orig_loader()

    reg.get("a")._loader = slow_loader
    results, errors = [], []

    def worker():
        try:
            _e, params, version = reg.acquire("a")
            results.append((len(params), version))
        except Exception as exc:        # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)                     # let every follower reach the latch
    gate.set()
    for t in threads:
        t.join(10.0)
    assert not errors
    assert len(results) == 4
    assert calls[0] == 1, "the latch must serialize to ONE loader run"
    assert len({r for r in results}) == 1
    assert reg.reloads_total == 1
    assert ha.resident


def test_reload_failure_releases_latch():
    """A loader that raises must release the latch so a later acquire
    retries (and can succeed) instead of deadlocking every waiter."""
    net = _mlp(seed=4)
    x = np.random.RandomState(23).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    ha = reg.load_block("a", net, mx.nd.array(x[None]))
    assert reg.evict("a")
    orig_loader = reg.get("a")._loader
    boom = [True]

    def flaky_loader():
        if boom[0]:
            raise IOError("weights store down")
        return orig_loader()

    reg.get("a")._loader = flaky_loader
    with pytest.raises(IOError):
        reg.acquire("a")
    assert reg.get("a")._loading is None        # latch released
    boom[0] = False
    _e, params, _v = reg.acquire("a")           # retry succeeds
    assert params and ha.resident
    # a loader whose MAPPING is malformed fails INSIDE the locked
    # install step (past the load itself) — the latch must still open
    # and a later acquire must still retry, not deadlock every waiter
    assert reg.evict("a")
    reg.get("a")._loader = lambda: {"w": object()}   # no .nbytes
    with pytest.raises(Exception):
        reg.acquire("a")
    assert reg.get("a")._loading is None
    reg.get("a")._loader = orig_loader
    _e, params, _v = reg.acquire("a")
    assert params and ha.resident


def test_reload_failure_does_not_clobber_successor_latch(monkeypatch):
    """A reload that fails PAST the install step (which already cleared
    the latch) must clear only its OWN latch in the failure handler: a
    successor may have observed ``_loading is None`` and installed a
    fresh latch — nulling that would let a third thread start a
    duplicate loader run for the same model."""
    from incubator_mxnet_tpu.serving import registry as registry_mod
    net = _mlp(seed=5)
    x = np.random.RandomState(24).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    reg.load_block("a", net, mx.nd.array(x[None]))
    assert reg.evict("a")
    entry = reg.get("a")
    successor = threading.Event()

    def exploding_nbytes(params):
        # the install step cleared entry._loading just before this call;
        # simulate the successor thread that observes None and installs
        # ITS latch before our failure handler runs
        entry._loading = successor
        raise TypeError("malformed mapping")

    with monkeypatch.context() as m:
        m.setattr(registry_mod, "_nbytes", exploding_nbytes)
        with pytest.raises(TypeError):
            reg.acquire("a")
    assert entry._loading is successor, \
        "failure handler clobbered the successor's latch"
    # with the simulated successor gone, a plain retry still succeeds
    entry._loading = None
    _e, params, _v = reg.acquire("a")
    assert params and entry._resident


# ---------------------------------------------------------------------------
# hot-swap
# ---------------------------------------------------------------------------

def test_swap_ticket_old_version_serves_until_commit():
    net = _mlp()
    x = np.random.RandomState(11).randn(DIN).astype(np.float32)
    with serving.Server(max_batch=4, max_wait_ms=1) as srv:
        srv.load("m", block=net, example=mx.nd.array(x[None]))
        old = srv.predict("m", x)
        _fn, pv = net.serving_fn(mx.nd.array(x[None]))
        ticket = srv.begin_swap("m", {n: np.asarray(v) * 2.0
                                      for n, v in pv.items()})
        mid = srv.predict("m", x)           # still the old version
        assert mid.tobytes() == old.tobytes()
        assert srv.registry.get("m").version == 1
        assert ticket.commit() == 2
        new = srv.predict("m", x)
        assert new.tobytes() != old.tobytes()
        assert srv.registry.get("m").version == 2
        assert srv.registry.swaps_total == 1
    snap = mx.telemetry.compact_snapshot()
    assert snap.get('graft_serve_model_events_total{kind="swap"}', 0) >= 1


def test_hot_swap_mid_traffic_no_torn_weights():
    """Hammer one model from client threads while versions flip: every
    response must be ENTIRELY old-version or ENTIRELY new-version
    bytes."""
    net = _mlp()
    x = np.random.RandomState(12).randn(DIN).astype(np.float32)
    with serving.Server(max_batch=4, max_wait_ms=1) as srv:
        srv.load("m", block=net, example=mx.nd.array(x[None]))
        srv.warmup("m", x)
        oracle = {srv.predict("m", x).tobytes()}    # v1 bytes
        _fn, pv = net.serving_fn(mx.nd.array(x[None]))
        stop = threading.Event()
        bad = []

        def traffic():
            while not stop.is_set():
                y = srv.predict("m", x, timeout=60.0)
                if y.tobytes() not in oracle:
                    bad.append(y)
                    return

        threads = [threading.Thread(target=traffic) for _ in range(3)]
        for k in (3.0, 5.0, 7.0):       # three version flips mid-traffic
            new = {n: np.asarray(v) * k for n, v in pv.items()}
            ticket = srv.begin_swap("m", new)
            if len(oracle) == 1:        # start traffic after v1 oracle
                for t in threads:
                    t.start()
            # the new version's oracle bytes, computed OUT of band
            entry, params, _v = srv.registry.acquire("m")
            import jax.numpy as jnp
            oracle.add(np.asarray(entry.jit_for(1)(
                {n: jnp.asarray(v) for n, v in new.items()},
                jnp.asarray(x[None])))[0].tobytes())
            ticket.commit()
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        assert not bad, "a response saw torn weights"
        assert srv.registry.get("m").version == 4


# ---------------------------------------------------------------------------
# parity probe (fused mode) + exact mode immunity
# ---------------------------------------------------------------------------

def _coupled_requests(srv, n=6):
    rs = np.random.RandomState(13)
    xs = [rs.randn(DIN).astype(np.float32) for _ in range(n)]
    futs = [srv.submit("c", x) for x in xs]
    return xs, [f.get(timeout=60.0) for f in futs]


def test_parity_probe_demotes_fused_mismatch(monkeypatch):
    """A row-coupled model under fused batching is CAUGHT by the probe
    and demoted to per-request dispatch — responses stay bit-equal to
    the unbatched forward, the fallback is counted."""
    monkeypatch.setenv("GRAFT_SERVE_BATCH_MODE", "fused")
    net = _BatchCoupled()
    net.initialize(ctx=mx.cpu())
    with serving.Server(max_batch=8, max_wait_ms=100) as srv:
        srv.load("c", block=net, example=mx.nd.array(
            np.zeros((1, DIN), np.float32)))
        xs, outs = _coupled_requests(srv)
        demoted = bool(srv.registry.get("c").no_batch)
    assert demoted
    for x, y in zip(xs, outs):
        ref = net(mx.nd.array(x[None])).asnumpy()[0]
        assert y.tobytes() == ref.tobytes()
    snap = mx.telemetry.compact_snapshot()
    assert snap.get('graft_serve_parity_fallbacks_total{model="c"}', 0) >= 1


def test_trace_shadows_are_thread_local():
    """A jit trace of the serving fn runs on the DISPATCHER thread and
    installs shadow params on the block for the trace's duration; an
    eager forward on another thread during that window must see the
    REAL params, not the in-flight tracers (the cross-thread leak that
    intermittently threw UnexpectedTracerError under serving load)."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ndarray import NDArray
    net = _mlp()
    x = np.random.RandomState(20).randn(1, DIN).astype(np.float32)
    ref = net(mx.nd.array(x)).asnumpy()
    # zero-valued shadows: if they leaked across threads, the eager
    # forward below would compute tanh(0) == 0 everywhere
    shadows = {p.name: NDArray(jnp.zeros(p.shape, jnp.float32))
               for _n, p in net.collect_params().items()}
    entered, release = threading.Event(), threading.Event()

    def holder():
        with net._trace_params(shadows):
            entered.set()
            release.wait(10)

    t = threading.Thread(target=holder)
    t.start()
    assert entered.wait(10)
    try:
        out = net(mx.nd.array(x)).asnumpy()    # other-thread shadows open
    finally:
        release.set()
        t.join(10)
    assert out.tobytes() == ref.tobytes()
    assert np.abs(out).sum() > 0


def test_exact_mode_serves_row_coupled_model_with_parity():
    """Default (exact) batch mode: every row is its own subgraph, so
    even a row-coupled forward keeps batched == unbatched bit-parity
    and no demotion happens."""
    net = _BatchCoupled()
    net.initialize(ctx=mx.cpu())
    with serving.Server(max_batch=8, max_wait_ms=100) as srv:
        srv.load("c", block=net, example=mx.nd.array(
            np.zeros((1, DIN), np.float32)))
        xs, outs = _coupled_requests(srv)
        assert not srv.registry.get("c").no_batch
    for x, y in zip(xs, outs):
        ref = net(mx.nd.array(x[None])).asnumpy()[0]
        assert y.tobytes() == ref.tobytes()


def test_parity_verdicts_reset_on_reregistration(monkeypatch):
    """Demotion is a property of the HANDLE (its program), not the
    name: unloading and re-registering a different model under the same
    name starts with fresh verdicts."""
    monkeypatch.setenv("GRAFT_SERVE_BATCH_MODE", "fused")
    coupled = _BatchCoupled()
    coupled.initialize(ctx=mx.cpu())
    with serving.Server(max_batch=8, max_wait_ms=100) as srv:
        srv.load("c", block=coupled, example=mx.nd.array(
            np.zeros((1, DIN), np.float32)))
        _coupled_requests(srv)
        assert srv.registry.get("c").no_batch         # demoted
        srv.registry.unload("c")
        clean = _mlp()
        srv.load("c", block=clean, example=mx.nd.array(
            np.zeros((1, DIN), np.float32)))
        assert not srv.registry.get("c").no_batch     # fresh handle
        xs, outs = _coupled_requests(srv)
        for x, y in zip(xs, outs):
            ref = clean(mx.nd.array(x[None])).asnumpy()[0]
            assert y.tobytes() == ref.tobytes()


def test_dispatcher_survives_unexpected_dispatch_error(monkeypatch):
    """An exception OUTSIDE the batch error path (e.g. in jit_for) must
    fail the batch's futures, not kill the dispatcher thread — later
    submits still serve."""
    net = _mlp()
    x = np.random.RandomState(21).randn(DIN).astype(np.float32)
    with serving.Server(max_batch=4, max_wait_ms=1) as srv:
        srv.load("m", block=net, example=mx.nd.array(x[None]))
        real = serving.ModelHandle.jit_for

        def boom(self, bucket, mode=None):
            raise RuntimeError("jit_for exploded")

        monkeypatch.setattr(serving.ModelHandle, "jit_for", boom)
        fut = srv.submit("m", x)
        with pytest.raises(RuntimeError):
            fut.get(timeout=30.0)
        monkeypatch.setattr(serving.ModelHandle, "jit_for", real)
        y = srv.predict("m", x, timeout=30.0)     # dispatcher survived
        ref = net(mx.nd.array(x[None])).asnumpy()[0]
        assert y.tobytes() == ref.tobytes()


def test_overlapping_swaps_keep_versions_monotonic():
    """Two overlapping swap tickets get DISTINCT, increasing versions
    (assigned at commit); the last commit wins the weights."""
    net = _mlp()
    x = np.random.RandomState(22).randn(DIN).astype(np.float32)
    reg = serving.ModelRegistry()
    h = reg.load_block("m", net, mx.nd.array(x[None]))
    _fn, pv = net.serving_fn(mx.nd.array(x[None]))
    ta = reg.begin_swap("m", {n: np.asarray(v) * 2 for n, v in pv.items()})
    tb = reg.begin_swap("m", {n: np.asarray(v) * 3 for n, v in pv.items()})
    vb = tb.commit()
    va = ta.commit()
    assert (vb, va) == (2, 3) and h.version == 3
    # last commit (A, the *2 weights) wins
    _entry, params, _v = reg.acquire("m")
    import jax.numpy as jnp
    name0 = sorted(pv)[0]
    assert np.asarray(params[name0]).tobytes() == \
        np.asarray(jnp.asarray(np.asarray(pv[name0]) * 2)).tobytes()


def test_predictor_unset_input_runs_as_zeros():
    """C-predict contract: inputs never set_input()-ed bind as zeros."""
    from incubator_mxnet_tpu import symbol as sym
    from incubator_mxnet_tpu import predict as predict_mod
    rng = np.random.RandomState(23)
    net = sym.FullyConnected(sym.var("data") + sym.var("extra"),
                             num_hidden=3, name="fcz")
    w = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    import incubator_mxnet_tpu.ndarray.utils as ndu
    import io, tempfile, os
    path = tempfile.mktemp()
    mx.nd.save(path, {"arg:fcz_weight": mx.nd.array(w),
                      "arg:fcz_bias": mx.nd.array(b)})
    with open(path, "rb") as f:
        param_bytes = f.read()
    os.unlink(path)
    pred = predict_mod.create_predictor(
        net.tojson(), param_bytes, {"data": (2, 4), "extra": (2, 4)})
    xd = rng.randn(2, 4).astype(np.float32)
    pred.set_input("data", xd.tobytes())      # "extra" left unset
    assert pred.forward()
    got = np.frombuffer(pred.output_bytes(0), np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, xd @ w.T + b, rtol=1e-5)


# ---------------------------------------------------------------------------
# watchdog: a stalled batch is named
# ---------------------------------------------------------------------------

def test_watchdog_names_stalled_batch(monkeypatch, tmp_path):
    blackbox.set_enabled(True)
    dump_path = str(tmp_path / "serve_wd.json")
    net = _mlp()
    x = np.random.RandomState(14).randn(DIN).astype(np.float32)
    try:
        with serving.Server(max_batch=4, max_wait_ms=1) as srv:
            srv.load("m", block=net, example=mx.nd.array(x[None]))
            srv.warmup("m", x, buckets=[1])
            entry = srv.registry.get("m")
            real = entry.jit_for(1)

            def stalled(params, *xv):
                time.sleep(1.2)             # the synthetic stuck batch
                return real(params, *xv)

            # ModelHandle is slotted: patch at class level
            monkeypatch.setattr(
                serving.ModelHandle, "jit_for",
                lambda self, bucket, mode=None: stalled)
            wd = watchdog.start(timeout=0.3, interval=0.05, abort=False,
                                path=dump_path)
            assert wd is not None
            try:
                fut = srv.submit("m", x)
                deadline = time.time() + 3
                while wd.trips == 0 and time.time() < deadline:
                    time.sleep(0.02)
                assert wd.trips >= 1
                fut.get(timeout=60.0)       # the stall ends; batch lands
            finally:
                watchdog.stop()
        with open(dump_path) as f:
            doc = json.load(f)
        assert blackbox.validate_dump(doc) == []
        assert doc["reason"] == "watchdog"
        wdinfo = doc["watchdog"]
        assert wdinfo["tripped_site"] == "serve_batch"
        assert wdinfo["tripped_detail"]["model"] == "m"
        assert "batch" in wdinfo["tripped_detail"]
        assert wdinfo["tripped_detail"]["size"] == 1
    finally:
        blackbox.set_enabled(None)


# ---------------------------------------------------------------------------
# grafttsan: serving threads + KVStore._store
# ---------------------------------------------------------------------------

def test_tsan_clean_threaded_serving():
    """GRAFT_TSAN=1 over threaded submits, batched dispatches and a
    hot-swap: the serving locks uphold the single-owner discipline, so
    the detector must stay silent."""
    tsan.set_enabled(True)
    tsan.clear()
    try:
        net = _mlp()
        xs, outs, _f = _serve(net, n_req=10)
        reg = serving.ModelRegistry()
        h = reg.load_block("s", net, mx.nd.array(xs[0][None]))
        _fn, pv = net.serving_fn(mx.nd.array(xs[0][None]))
        reg.swap("s", {n: np.asarray(v) * 2 for n, v in pv.items()})
        assert h.version == 2
        reports = tsan.reports()
    finally:
        tsan.set_enabled(None)
        tsan.clear()
    assert reports == [], "tsan reports on clean serving: %r" % reports


def test_tsan_tracks_kvstore_store_cells():
    """The satellite: KVStore._store values are tracked cells under
    GRAFT_TSAN — an unsynchronized cross-thread write racing a pull
    read is an EH204 naming the store key."""
    tsan.set_enabled(True)
    tsan.clear()
    try:
        kv = mx.kvstore.create("local")
        w = mx.nd.array(np.ones((4, 4), np.float32))
        kv.init("w0", w)
        out = mx.nd.zeros((4, 4))
        kv.pull("w0", out=out)              # read on the main thread
        import jax.numpy as jnp

        def rogue():
            # no handle/sync edge: races the main thread's reads
            kv._store["w0"]._write(jnp.ones((4, 4)) * 3)

        t = threading.Thread(target=rogue)
        t.start()
        t.join()
        reports = tsan.reports()
    finally:
        tsan.set_enabled(None)
        tsan.clear()
    assert any(r.code == "EH204" and "_store[w0]" in r.message
               for r in reports), reports


def test_tsan_clean_kvstore_single_thread():
    tsan.set_enabled(True)
    tsan.clear()
    try:
        kv = mx.kvstore.create("local")
        kv.init("a", mx.nd.ones((3, 3)))
        kv.push("a", mx.nd.ones((3, 3)))
        out = mx.nd.zeros((3, 3))
        kv.pull("a", out=out)
        reports = tsan.reports()
    finally:
        tsan.set_enabled(None)
        tsan.clear()
    assert reports == []


# ---------------------------------------------------------------------------
# nd.load_buffer + the rebased C-predict surface
# ---------------------------------------------------------------------------

def test_load_buffer_matches_load(tmp_path):
    rs = np.random.RandomState(15)
    data = {"arg:w": mx.nd.array(rs.randn(3, 4).astype(np.float32)),
            "aux:m": mx.nd.array(rs.randn(3).astype(np.float32)),
            "plain": mx.nd.array(rs.randn(2, 2).astype(np.float32))}
    path = str(tmp_path / "m.params")
    mx.nd.save(path, data)
    with open(path, "rb") as f:
        buf = f.read()
    from_file = mx.nd.load(path)
    from_buf = mx.nd.load_buffer(buf)
    assert sorted(from_file) == sorted(from_buf)
    for k in from_file:
        assert from_file[k].asnumpy().tobytes() == \
            from_buf[k].asnumpy().tobytes()


def test_predictor_in_memory_shared_loader(monkeypatch, tmp_path):
    """The rebased Predictor parses params via nd.load_buffer (no temp
    file) and serves through a serving-registry handle."""
    import tempfile
    from incubator_mxnet_tpu import symbol as sym
    from incubator_mxnet_tpu import predict as predict_mod

    rng = np.random.RandomState(0)
    net = sym.softmax(sym.FullyConnected(sym.var("data"), num_hidden=3,
                                         name="fcp"))
    w = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    params_path = str(tmp_path / "m.params")
    mx.nd.save(params_path, {"arg:fcp_weight": mx.nd.array(w),
                             "arg:fcp_bias": mx.nd.array(b)})
    with open(params_path, "rb") as f:
        param_bytes = f.read()

    def no_tempfile(*a, **kw):
        raise AssertionError("Predictor must not round-trip param bytes "
                             "through a temp file")

    monkeypatch.setattr(tempfile, "NamedTemporaryFile", no_tempfile)
    pred = predict_mod.create_predictor(net.tojson(), param_bytes,
                                        {"data": (2, 4)})
    name = pred._name
    assert name in serving.default_registry().models()
    x = rng.randn(2, 4).astype(np.float32)
    pred.set_input("data", x.tobytes())
    assert pred.forward()
    assert pred.output_shape(0) == (2, 3)
    got = np.frombuffer(pred.output_bytes(0), np.float32).reshape(2, 3)
    logits = x @ w.T + b
    e = np.exp(logits - logits.max(1, keepdims=True))
    ref = e / e.sum(1, keepdims=True)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    del pred
    import gc
    gc.collect()
    assert name not in serving.default_registry().models()


# ---------------------------------------------------------------------------
# module-loaded models + the CLI selftest
# ---------------------------------------------------------------------------

def test_module_serving_fn_parity():
    from incubator_mxnet_tpu import symbol as sym
    from incubator_mxnet_tpu.module import Module
    net = sym.tanh(sym.FullyConnected(sym.var("data"), num_hidden=5,
                                      name="fc"))
    mod = Module(symbol=net, data_names=("data",), label_names=None,
                 context=mx.cpu())
    mod.bind(data_shapes=[("data", (1, 6))], label_shapes=None,
             for_training=False)
    mod.init_params(initializer=mx.initializer.Uniform(0.1))
    rs = np.random.RandomState(16)
    xs = [rs.randn(6).astype(np.float32) for _ in range(5)]
    with serving.Server(max_batch=4, max_wait_ms=3) as srv:
        srv.load("mod", module=mod)
        futs = [srv.submit("mod", x) for x in xs]
        for x, f in zip(xs, futs):
            y = f.get(timeout=60.0)
            ref = mod.predict(mx.nd.array(x[None])).asnumpy()[0]
            assert y.tobytes() == ref.tobytes()


def test_serving_selftest_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_mxnet_tpu.serving",
         "--selftest"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftserve selftest OK" in proc.stdout
