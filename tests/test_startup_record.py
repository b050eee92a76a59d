"""The start-up record (``telemetry.startup()``): the package's import, the
set-up spans and a build record for every stage of every program the process
builds, put down to the span that was open when JAX built it; and the two
counters that say the same (``graft_program_builds_total``,
``graft_program_build_seconds_total``).
"""
import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, telemetry
from incubator_mxnet_tpu.telemetry import blackbox, tracing

# the process's own, before a test below puts an empty record in its place
IMPORTS = [r for r in telemetry.startup() if r[0] == "import"]
SECONDS = "graft_program_build_seconds_total"
BUILDS = "graft_program_builds_total"


@pytest.fixture(autouse=True)
def empty_record(monkeypatch):
    """A pytest worker has built a thousand programs by now: each test reads
    a record of its own."""
    monkeypatch.setattr(blackbox, "_startup", [])
    monkeypatch.setattr(blackbox, "_startup_dropped", [0])
    # and no trace kept back from the test before
    monkeypatch.setattr(tracing, "_building", tracing._Building())
    monkeypatch.setattr(blackbox, "_enabled_override", True)
    monkeypatch.setattr(telemetry.metrics, "_enabled_override", True)


def _builds(name=None):
    return [r for r in telemetry.startup()
            if r[0] == "build" and name in (None, r[1])]


def _counter(metric):
    samples = telemetry.registry().snapshot().get(metric, {"samples": []})
    return collections.Counter({
        tuple(sorted(s["labels"].items())): s["value"]
        for s in samples["samples"]})


def test_a_build_is_put_down_to_the_span_and_step_that_asked():
    @jax.jit
    def first_called_at_step_seven(x):
        return jnp.tanh(x) * 3.0

    x = jnp.ones((3, 5))
    before = telemetry.spans()
    with telemetry.phase_span("dispatch", step=7):
        t0 = tracing.time.perf_counter()
        first_called_at_step_seven(x)
        t1 = tracing.time.perf_counter()
    records = _builds("first_called_at_step_seven")
    assert [r[6]["stage"] for r in records] == ["trace", "lower", "backend"]
    for kind, name, start, end, parent, step, attrs in records:
        assert (parent, step, attrs["owner"]) == ("dispatch", 7, "dispatch")
        assert t0 - 1e-3 <= start <= end <= t1
    assert records[2][6]["cache"] in ("hit", "miss", "off")
    assert records[2][6]["retrieval_s"] >= 0.0
    # the stages follow one another: the same clock as the span's own record
    assert records[0][3] <= records[1][2] + 1e-3
    span = [s for s in telemetry.spans() if s not in before][-1]
    assert span[0] == "dispatch" and span[1] <= records[0][2] + 1e-3
    assert records[2][3] <= span[2]

    held = len(telemetry.startup())
    with telemetry.phase_span("dispatch", step=8):
        first_called_at_step_seven(x)
    assert len(telemetry.startup()) == held     # warm: JAX reports nothing
    # a new shape at step 9 is a rebuild, and names its step
    with telemetry.phase_span("dispatch", step=9):
        first_called_at_step_seven(jnp.ones((4, 5)))
    again = _builds("first_called_at_step_seven")[3:]
    assert [(r[5], r[6]["stage"]) for r in again] == [
        (9, "trace"), (9, "lower"), (9, "backend")]


def test_an_enclosed_trace_is_no_record_and_no_sum():
    @jax.jit
    def inner_of_the_step(x):
        return x * 2.0

    @jax.jit
    def the_step(x):
        return inner_of_the_step(x) + inner_of_the_step(x.T).T

    x = jnp.ones((2, 7))
    seconds = _counter(SECONDS)
    with telemetry.phase_span("dispatch", step=3):
        the_step(x)
    assert _builds("inner_of_the_step") == []
    trace, lower, backend = _builds("the_step")
    assert trace[6]["enclosed"] >= 2        # both shapes of the inner one
    grew = _counter(SECONDS) - seconds
    assert grew[(("owner", "dispatch"), ("stage", "trace"))] == \
        pytest.approx(trace[3] - trace[2])


def test_owner_under_no_span_is_the_operator_library_or_the_user():
    a = mx.nd.array(np.arange(77, dtype=np.float32).reshape(7, 11))
    mx.nd.waitall()
    held = len(telemetry.startup())
    (mx.nd.relu(a) + a).asnumpy()
    ours = telemetry.startup()[held:]
    assert ours and {r[6]["owner"] for r in ours} == {"eager"}
    assert {r[4] for r in ours} == {None}
    # the module is "jit(<unknown>)" at the later stages (the registry jits
    # a functools.partial): they take the name of the trace before them
    assert "<unknown>" not in {r[1] for r in ours}

    jax.jit(lambda x: x - 77.0)(jnp.ones(7))
    mine = _builds("<lambda>")
    assert [r[6]["owner"] for r in mine] == ["user"] * 3
    assert mine[0][5] == blackbox.current_step()


def test_a_cached_trace_is_no_build(monkeypatch):
    """JAX reports a trace for every call that takes its Python path, as
    each operator does under ``autograd.record()``: kept back, bounded, and
    no record unless a lowering follows."""
    a = mx.nd.array(np.ones((3, 19), np.float32))
    a.attach_grad()
    with mx.autograd.record():
        mx.nd.relu(a)
    held = len(telemetry.startup())
    assert held >= 3
    monkeypatch.setattr(tracing, "_TRACES_HELD", 8)
    for _ in range(30):
        with mx.autograd.record():
            mx.nd.relu(a)
    assert len(telemetry.startup()) == held
    assert 0 < len(tracing._building.traces) <= 8


def test_an_engine_flush_owns_its_program_with_its_cause():
    a = mx.nd.array(np.ones((5, 13), np.float32))
    with mx.engine.bulk(4):
        b = (a * 5.0 + a) * a
    b.asnumpy()
    flushed = [r for r in _builds() if r[6]["owner"] == "engine_flush"]
    assert {r[6]["stage"] for r in flushed} == {"trace", "lower", "backend"}
    assert all(r[6]["cause"] for r in flushed)


def test_the_oldest_are_kept_and_the_rest_counted(monkeypatch):
    x = jnp.ones(2)
    del blackbox._startup[:]
    monkeypatch.setattr(blackbox, "STARTUP_SIZE", 4)
    ring = len(blackbox._spans)
    for i in range(6):
        with telemetry.phase_span("initialize", step=i):
            pass
    held = telemetry.startup()
    assert [r[5] for r in held] == [0, 1, 2, 3] and held.dropped == 2
    assert all(r[:2] == ("span", "initialize") for r in held)
    # a set-up span goes to the ring as every span does, which evicts
    assert [s[4] for s in telemetry.spans()[-6:]] == list(range(6))
    assert len(blackbox._spans) == min(ring + 6, blackbox._spans.maxlen)
    jax.jit(lambda x: x * 1024.0)(x)
    assert len(telemetry.startup()) == 4
    assert telemetry.startup().dropped == 5


def test_the_package_import_record():
    (record,) = IMPORTS
    kind, name, start, end, parent, step, attrs = record
    assert (name, parent, step) == ("package_import", None, None)
    groups = attrs["groups_s"]
    assert len(groups) >= 5 and all(s >= 0.0 for s in groups.values())
    assert sum(groups.values()) <= end - start + 1e-9
    assert attrs["jax_preloaded"] is True       # conftest imports jax first
    assert isinstance(attrs["backend_open"], bool)


def test_the_counters_move_by_what_the_records_say():
    seconds, builds = _counter(SECONDS), _counter(BUILDS)

    @jax.jit
    def counted_under_update(x):
        return jnp.cumsum(x) / 7.0

    with telemetry.phase_span("update", step=2):
        counted_under_update(jnp.ones(9))
    jax.jit(lambda x: x + 4096.0)(jnp.ones(3))
    mx.nd.sqrt(mx.nd.array(np.ones((3, 17), np.float32))).asnumpy()

    want_s, want_n = collections.Counter(), collections.Counter()
    for _, _, start, end, _, _, attrs in _builds():
        want_s[(("owner", attrs["owner"]), ("stage", attrs["stage"]))] += \
            end - start
        if attrs["stage"] == "backend":
            want_n[(("cache", attrs["cache"]), ("owner", attrs["owner"]))] += 1
    grew_s = _counter(SECONDS) - seconds
    assert {("owner", "update"), ("owner", "user"), ("owner", "eager")} <= {
        k[0] for k in want_s}
    assert set(grew_s) == set(want_s)
    for key, value in want_s.items():
        assert grew_s[key] == pytest.approx(value, abs=1e-6)
    assert _counter(BUILDS) - builds == want_n


def test_set_up_spans_are_in_the_record_and_in_the_ring():
    net = gluon.nn.HybridSequential(prefix="startup_")
    with net.name_scope():
        net.add(gluon.nn.Dense(4), gluon.nn.Dense(2))
    net.initialize()
    x = mx.nd.array(np.ones((3, 5), np.float32))
    net(x).asnumpy()
    kept = [r for r in telemetry.startup() if r[0] == "span"]
    assert [r[1] for r in kept] == ["initialize", "deferred_init",
                                    "deferred_init"]
    ring = telemetry.spans()
    assert all(tuple(r[1:6]) in ring for r in kept)
    # the initializers' programs are the deferred pass's, the operators' not
    owners = collections.Counter(
        r[6]["owner"] for r in _builds() if r[6]["stage"] == "backend")
    assert owners["deferred_init"] >= 1 and owners["eager"] >= 1

    trainer = mx.parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        mesh=mx.parallel.make_mesh({"dp": 1}, jax.devices()[:1]))
    y = mx.nd.array(np.ones((3, 2), np.float32))
    trainer.step(x, y)
    trainer.compiled_step(x, y)
    kept = [r for r in telemetry.startup() if r[0] == "span"][3:]
    assert [(r[1], r[4]) for r in kept] == [("gather_params", "place"),
                                            ("memory_analysis", None)]
    # the step that ran is read back: nothing is built a second time
    step = _builds("dp_train_step")
    assert [(r[4], r[5], r[6]["stage"]) for r in step] == [
        ("dispatch", 1, "trace"), ("dispatch", 1, "lower"),
        ("dispatch", 1, "backend")]
    assert step[0][6]["enclosed"] > 0


def test_a_dump_holds_the_record(tmp_path):
    x = jnp.ones(5)
    with telemetry.phase_span("fwd", step=5):
        jax.jit(lambda x: x * 555.0)(x)
    path = blackbox.dump(str(tmp_path / "dump.json"), reason="test")
    with open(path) as f:
        doc = json.load(f)
    assert blackbox.validate_dump(doc) == []
    assert doc["startup"]["dropped"] == 0
    assert [(r[0], r[4], r[5], r[6]["stage"])
            for r in doc["startup"]["records"] if r[4]] == [
        ("build", "fwd", 5, "trace"), ("build", "fwd", 5, "lower"),
        ("build", "fwd", 5, "backend")]


def test_nothing_is_recorded_with_the_recorder_off(monkeypatch):
    monkeypatch.setattr(blackbox, "_enabled_override", None)
    monkeypatch.setenv("GRAFT_BLACKBOX", "0")
    x = jnp.ones(2)
    builds = _counter(BUILDS)
    with telemetry.phase_span("initialize"):
        jax.jit(lambda x: x * 2048.0)(x)
    assert telemetry.startup() == [] and telemetry.startup().dropped == 0
    # the counters are the metrics registry's, under its own switch
    assert sum((_counter(BUILDS) - builds).values()) == 1
    monkeypatch.setattr(telemetry.metrics, "_enabled_override", False)
    assert tracing.phase_span("dispatch") is tracing._NULL
    jax.jit(lambda x: x * 8192.0)(x)
    assert telemetry.startup() == []
