"""Counts out of the compiled step: ``telemetry.step_counter`` inside the
fused step, the record on the step's id in the flight recorder, and
``telemetry.step_counters()``; the routed layer as its first user.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel, telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from incubator_mxnet_tpu.parallel import moe as moe_module
from incubator_mxnet_tpu.parallel.moe import ExpertParallelMoE
from incubator_mxnet_tpu.telemetry import blackbox, tracing

D, TOKENS, CLASSES = 16, 64, 5


class TwoRouted(gluon.HybridBlock):
    """Two routed layers, each holding four of eight experts, and a head."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.a = ExpertParallelMoE(32, 8, top_k=2, dispatch="grouped",
                                       experts_held=(0, 4), ep_axis=None,
                                       in_units=D)
            self.b = ExpertParallelMoE(32, 8, top_k=2, dispatch="grouped",
                                       experts_held=(2, 4), ep_axis=None,
                                       in_units=D)
            self.out = nn.Dense(CLASSES, in_units=D)

    def hybrid_forward(self, F, x):
        return self.out(x + self.b(x + self.a(x)))


def _dense():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=D, activation="relu"),
            nn.Dense(CLASSES, in_units=8))
    return net


def _trainer(net, seed=11, **kw):
    mx.random.seed(seed)
    net.initialize(mx.init.Normal(0.5))
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-2}, mesh=make_mesh({"dp": 1}, jax.devices()[:1]),
        **kw)


@pytest.fixture
def batch():
    rs = np.random.RandomState(5)
    return (rs.randn(TOKENS, D).astype(np.float32),
            rs.randint(0, CLASSES, TOKENS))


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Each test reads its own records, whatever ran before it."""
    blackbox._counts.clear()
    yield
    blackbox.set_enabled(None)
    blackbox.configure()
    blackbox._counts.clear()


def _step_span_ids(since):
    return [s[4] for s in telemetry.spans(since=since) if s[0] == "step"]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_a_concrete_value_outside_a_collection_is_the_callers():
    assert tracing.step_counter("rows", jnp.arange(3)) is False
    assert tracing.step_counter("rows", 7) is False
    assert telemetry.step_counters() == []


def test_a_traced_value_outside_a_collection_is_dropped():
    seen = []

    def f(x):
        seen.append(tracing.step_counter("rows", x.sum()))
        return x * 2

    assert float(jax.jit(f)(jnp.ones(3)).sum()) == 6.0
    assert seen == [True]


def test_a_collection_keeps_call_order_labels_and_static_numbers():
    def f(x):
        with tracing.collect_step_counters() as found:
            assert tracing.step_counter("rows", x.sum(), layer="a")
            assert tracing.step_counter("made", 12, layer="a")
            assert tracing.step_counter("rows", x.max(), layer="b")
        counts, labels = tracing.stack_step_counters(found)
        assert labels == {"rows": [{"layer": "a"}, {"layer": "b"}],
                          "made": [{"layer": "a"}]}
        return counts

    counts = jax.jit(f)(jnp.arange(4, dtype=jnp.int32))
    assert counts["rows"].tolist() == [6, 3]
    assert counts["made"].tolist() == [12]
    assert tracing.stack_step_counters([]) == ({}, {})


def test_a_nested_collection_shadows_the_outer_one():
    with tracing.collect_step_counters() as outer:
        tracing.step_counter("rows", 1)
        with tracing.collect_step_counters() as inner:
            tracing.step_counter("rows", 2)
        tracing.step_counter("rows", 3)
    assert [v for _, _, v in outer] == [1, 3]
    assert [v for _, _, v in inner] == [2]
    assert tracing.step_counter("rows", 4) is False     # closed again


def test_a_collection_is_its_threads_own():
    seen = []
    with tracing.collect_step_counters() as found:
        t = threading.Thread(
            target=lambda: seen.append(tracing.step_counter("rows", 1)))
        t.start()
        t.join()
    assert seen == [False] and found == []


# ---------------------------------------------------------------------------
# the channel out of the fused step
# ---------------------------------------------------------------------------

def test_a_model_that_emits_nothing_keeps_the_parents_results(batch):
    x, y = batch
    tr = _trainer(_dense())
    tr.step(x, y)
    xs, ys = tr._prepare_inputs(x, y, jax.sharding.PartitionSpec("dp"))
    out = tr.compile(xs, ys)(tr._params, tr._opt_state, tr._rng_key, xs, ys,
                             tr._lr_dev)
    assert out[4] == {}
    # parameters, optimizer state, the key and the loss: no fifth array
    assert len(jax.tree.leaves(out)) == len(jax.tree.leaves(out[:4]))
    assert telemetry.step_counters() == []
    assert blackbox.snapshot()["step_counters"] is None


def test_records_carry_the_step_spans_ids_and_the_eager_routing(batch):
    x, y = batch
    net = TwoRouted()
    tr = _trainer(net)
    since = time.perf_counter()
    want = []
    for step in range(3):
        if step:
            tr.sync_params()
        net(mx.nd.array(x))         # the routing of the weights as they are
        want.append([np.asarray(layer.last_expert_load)
                     for layer in (net.a, net.b)])
        tr.step(x, y)
    records = telemetry.step_counters()
    assert [sid for sid, _ in records] == _step_span_ids(since) == [1, 2, 3]
    for (_, counts), loads in zip(records, want):
        assert counts["moe_held_rows"].dtype == np.int32
        np.testing.assert_array_equal(counts["moe_held_rows"], loads)
        assert counts["moe_assignments"].tolist() == [TOKENS * 2, TOKENS * 2]
    # the routers moved: the three steps are not one count thrice
    assert not np.array_equal(records[0][1]["moe_held_rows"],
                              records[2][1]["moe_held_rows"])
    assert [sid for sid, _ in telemetry.step_counters(since_step=3)] == [3]


def test_loss_and_parameters_are_bit_identical_without_the_emission(
        batch, monkeypatch):
    x, y = batch

    def three_steps():
        tr = _trainer(TwoRouted(prefix="net_"))
        losses = [np.asarray(tr.step(x, y)) for _ in range(3)]
        return losses, {n: np.asarray(v) for n, v in tr._params.items()}

    losses, params = three_steps()
    assert len(telemetry.step_counters()) == 3
    blackbox._counts.clear()
    monkeypatch.setattr(
        moe_module, "step_counter",
        lambda name, value, **labels: isinstance(value, jax.core.Tracer))
    stubbed_losses, stubbed = three_steps()
    assert telemetry.step_counters() == []
    for a, b in zip(losses, stubbed_losses):
        assert a.tobytes() == b.tobytes()
    assert set(params) == set(stubbed)
    for name in params:
        assert params[name].tobytes() == stubbed[name].tobytes(), name


def test_the_record_holds_device_arrays_until_it_is_read(batch, monkeypatch):
    x, y = batch
    tr = _trainer(TwoRouted())
    tr.step(x, y)                   # compiled and placed

    def no_read(*_a, **_k):
        raise AssertionError("a host read inside step()")
    monkeypatch.setattr(jax, "device_get", no_read)
    loss = tr.step(x, y)
    monkeypatch.undo()
    assert isinstance(loss, jax.Array)      # the loss and nothing else
    step, counts, labels = blackbox._counts[-1]
    assert step == 2
    assert all(isinstance(v, jax.Array) for v in counts.values())
    assert [row["layer"] for row in labels["moe_held_rows"]] == [
        tr.block.a.name, tr.block.b.name]
    (_, read), = telemetry.step_counters(since_step=2)
    assert all(isinstance(v, np.ndarray) for v in read.values())


def test_the_record_is_bounded_and_the_switch_drops(batch):
    x, y = batch
    tr = _trainer(TwoRouted())
    blackbox.configure(size=8)
    try:
        for _ in range(11):
            tr.step(x, y)
        assert [sid for sid, _ in telemetry.step_counters()] == list(
            range(4, 12))
        blackbox.set_enabled(False)
        tr.step(x, y)
        blackbox.set_enabled(True)
        assert telemetry.step_counters()[-1][0] == 11
    finally:
        import os
        os.environ.pop("GRAFT_BLACKBOX_SIZE", None)


def test_step_multi_stacks_the_counts_along_k(batch):
    x, y = batch
    tr = _trainer(TwoRouted())
    tr.step_multi(np.stack([x] * 3), np.stack([y] * 3))
    (sid, counts), = telemetry.step_counters()
    assert sid == 1
    assert counts["moe_held_rows"].shape == (3, 2, 4)
    assert counts["moe_assignments"].tolist() == [[TOKENS * 2] * 2] * 3
    # three chained steps of one batch: the first row is a single step's
    single = _trainer(TwoRouted())
    single.step(x, y)
    np.testing.assert_array_equal(
        counts["moe_held_rows"][0],
        telemetry.step_counters()[-1][1]["moe_held_rows"])


def test_a_hybridized_child_is_inlined_and_its_counts_are_the_steps(batch):
    x, y = batch
    net = TwoRouted()
    tr = _trainer(net)
    net.a.hybridize()       # a child of the step's Block: no jit of its own
    for _ in range(2):
        loss = tr.step(x, y)
    assert np.isfinite(float(loss))
    (_, first), (_, second) = telemetry.step_counters()
    assert first["moe_held_rows"].shape == (2, 4)
    assert second["moe_assignments"].tolist() == [TOKENS * 2] * 2


class CountingLoss(gluon.loss.SoftmaxCrossEntropyLoss):
    """A Block outside the step's own: hybridized, it is a ``CachedOp``
    traced inside the fused step, a ``jax.jit`` within the step's trace."""

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        assert tracing.step_counter("loss_rows", pred._read().sum())
        return super().hybrid_forward(F, pred, label, sample_weight)


def test_a_cachedop_inside_the_step_leaks_no_tracer_and_emits_nothing(batch):
    x, y = batch
    mx.random.seed(11)
    net = TwoRouted()
    net.initialize(mx.init.Normal(0.5))
    loss_block = CountingLoss()
    loss_block.hybridize()
    tr = DataParallelTrainer(net, loss_block, "adam", {"learning_rate": 1e-2},
                             mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    for _ in range(2):
        loss = tr.step(x, y)        # no UnexpectedTracerError
    assert np.isfinite(float(loss))
    assert loss_block._cached_op is not None
    for _, counts in telemetry.step_counters():
        assert set(counts) == {"moe_held_rows", "moe_assignments"}


def test_an_eager_call_still_writes_the_host_counters(batch):
    x, _ = batch
    mx.random.seed(3)
    layer = ExpertParallelMoE(32, 8, top_k=2, dispatch="grouped",
                              experts_held=(0, 4), ep_axis=None, in_units=D)
    layer.initialize(mx.init.Normal(0.5))

    def held():
        snap = telemetry.registry().snapshot(collect=False)
        return sum(s["value"] for s in snap.get(
            "graft_moe_assignments_total", {"samples": []})["samples"]
            if s["labels"]["held"] == "yes")

    before = held()
    layer(mx.nd.array(x))
    assert held() - before == int(np.asarray(layer.last_expert_load).sum())
    assert telemetry.step_counters() == []


def test_the_capacity_path_counts_the_rows_it_drops():
    mesh = make_mesh({"dp": 2, "ep": 4}, jax.devices("cpu")[:8])
    mx.random.seed(4)
    net = nn.HybridSequential()
    net.add(ExpertParallelMoE(hidden_size=8, num_experts=4, top_k=1,
                              dispatch="capacity", capacity_factor=0.5))
    net.add(nn.Dense(2))
    net.initialize(mx.init.Xavier())
    rs = np.random.RandomState(5)
    x = rs.randn(16, 6).astype(np.float32)
    y = (rs.rand(16) > 0.5).astype(np.float32)
    with parallel.use_mesh(mesh):
        net(mx.nd.array(x))         # deferred shapes, and the eager count
    want = net[0].last_drop_fraction * 16
    assert want > 0
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=mesh)
    tr.step(mx.nd.array(x), mx.nd.array(y))
    (_, counts), = telemetry.step_counters()
    assert counts["moe_dropped_rows"].shape == (1,)
    assert float(counts["moe_dropped_rows"][0]) == pytest.approx(want)


def test_the_dump_carries_the_newest_record_with_its_labels(batch, tmp_path):
    x, y = batch
    net = TwoRouted()
    tr = _trainer(net)
    for _ in range(2):
        loss = tr.step(x, y)
    # a dump waits for no step: it carries the newest record that is done
    loss.block_until_ready()
    path = blackbox.dump(path=str(tmp_path / "bb.json"), reason="test")
    doc = json.loads(open(path).read())
    assert blackbox.validate_dump(doc) == []
    newest = doc["step_counters"]
    assert newest["step"] == 2
    rows = newest["counts"]["moe_held_rows"]
    assert rows["labels"] == [{"layer": net.a.name}, {"layer": net.b.name}]
    assert rows["values"] == telemetry.step_counters()[-1][1][
        "moe_held_rows"].tolist()


def test_the_two_unread_cachedop_counters_are_gone(batch):
    x, _ = batch
    net = _dense()
    net.initialize()
    net.hybridize()
    with mx.autograd.record():
        loss = net(mx.nd.array(x)).sum()
    loss.backward()
    snap = telemetry.registry().snapshot(collect=False)
    assert "graft_cachedop_recorded_calls_total" not in snap
    assert "graft_cachedop_replays_total" not in snap
    assert snap["graft_cachedop_residual_bytes"]["samples"][0]["value"] > 0
