"""The fused step: ``parallel.DataParallelTrainer``, forward + backward +
optimizer update as ONE donated XLA program, the path nine of the
benchmark's ten cells run.

The contract under test (parallel/data_parallel.py), on the CPU with small
models:

* **Parity** with the MXNet loop (``record`` / ``backward`` /
  ``gluon.Trainer.step``) over five steps, final parameters and optimizer
  state leaf by leaf, per optimizer, compute dtype and model.  Under
  ``dtype="bfloat16"`` the loop's twin says in ``hybrid_forward`` what the
  step does to its operands: trainable parameters and the input cast to
  bfloat16, BatchNorm's moving statistics left alone, the output cast back
  before the loss.
* **What compiles** — a new learning rate does not, a new batch size, a
  new trailing shape and a new input dtype each do once.
* **What moves** — a ``grad_req="null"`` parameter does not;
  ``step_multi`` over K batches lands where K ``step`` calls do; a mesh of
  2, 4 or 8 devices where one device does.
* **What is donated** — parameters, optimizer state and the key, when the
  trainer was built to donate; ``compiled_step`` runs and donates nothing.
* **What reaches the model** — token ids unrounded, ``uint8`` images cast
  on the device.

Tolerances.  SGD's formulas are the registered ops of the eager optimizer
with the same constants, and agree to the last few bits.  The step's Adam
folds the bias correction into its rate in float32 on the device where the
eager optimizer does so in Python's float64: 2e-6.  Under bfloat16 the
Conv + BatchNorm model differs at bfloat16's own resolution, because XLA
keeps float32 between the fused step's ops where the loop's ops, each a
program, round to bfloat16: 4e-3, a tenth of what five steps move.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.analysis.engine_check import _ordered_float_bits
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from incubator_mxnet_tpu.parallel.data_parallel import pure_optimizer
from incubator_mxnet_tpu.telemetry import xray

STEPS = 5
LOSS = gluon.loss.SoftmaxCrossEntropyLoss()


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def ulp_diff(a, b):
    """Elementwise ULP distance of two float arrays of one shape and
    dtype, over the engine oracle's monotone integer key (EH104's)."""
    a, b = (np.asarray(jax.device_get(v)) for v in (a, b))
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, a.dtype,
                                                       b.shape, b.dtype)
    ka, kb = _ordered_float_bits(a), _ordered_float_bits(b)
    return (np.maximum(ka, kb) - np.minimum(ka, kb)).astype(np.int64)


def max_ulp_diff(a, b):
    d = ulp_diff(a, b)
    return int(d.max()) if d.size else 0


def assert_close(got, want, atol, what):
    """Every element within 8 ULP or within ``atol``: the first holds the
    large values to their last bits, the second the values near zero,
    where one ULP is nothing."""
    got, want = np.asarray(got), np.asarray(want)
    bad = (ulp_diff(got, want) > 8) & (np.abs(got - want) > atol)
    assert not bad.any(), "%s: %d of %d elements differ, worst by %g" % (
        what, bad.sum(), bad.size, np.abs(got - want).max())


# ---------------------------------------------------------------------------
# models, and their twins for the loop under bfloat16
# ---------------------------------------------------------------------------

def _bf16(F, v):
    return None if v is None else F.cast(v, dtype="bfloat16")


class _CastDense(nn.Dense):
    def hybrid_forward(self, F, x, weight, bias=None):
        return super().hybrid_forward(F, x, _bf16(F, weight), _bf16(F, bias))


class _CastConv2D(nn.Conv2D):
    def hybrid_forward(self, F, x, weight, bias=None):
        return super().hybrid_forward(F, x, _bf16(F, weight), _bf16(F, bias))


class _CastBatchNorm(nn.BatchNorm):
    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        return super().hybrid_forward(F, x, _bf16(F, gamma), _bf16(F, beta),
                                      running_mean, running_var)


class _CastEnds(gluon.HybridBlock):
    """bfloat16 in, float32 out, around a body of the layers above."""

    def __init__(self, body, **kw):
        super().__init__(**kw)
        self.body = body

    def hybrid_forward(self, F, x):
        return F.cast(self.body(_bf16(F, x)), dtype="float32")


def dense_stack(cast=False):
    dense = _CastDense if cast else nn.Dense
    net = nn.HybridSequential(prefix="m_")
    with net.name_scope():
        net.add(dense(16, activation="relu", in_units=8, prefix="fc1_"))
        net.add(dense(3, in_units=16, prefix="fc2_"))
    return net


def conv_bn(cast=False):
    """No bias under the BatchNorm: its gradient is rounding noise, which
    Adam divides by its own root."""
    conv, norm, dense = ((_CastConv2D, _CastBatchNorm, _CastDense) if cast
                         else (nn.Conv2D, nn.BatchNorm, nn.Dense))
    net = nn.HybridSequential(prefix="m_")
    with net.name_scope():
        net.add(conv(4, kernel_size=3, padding=1, in_channels=2,
                     use_bias=False, prefix="conv_"))
        net.add(norm(in_channels=4, prefix="bn_"))
        net.add(nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Flatten())
        net.add(dense(3, in_units=4, prefix="fc_"))
    return net


SAMPLE = {dense_stack: (8,), conv_bn: (2, 6, 6)}


def seeded(model, cast=False, frozen=()):
    """The model with its trainable weights drawn from one seed, so that
    two builds hold the same values; ``frozen`` names get
    ``grad_req="null"``."""
    net = model(cast)
    net.initialize(ctx=mx.cpu())
    rs = np.random.RandomState(7)
    params = net.collect_params()
    for name in sorted(params):
        if params[name].grad_req != "null":
            params[name].set_data(mx.nd.array(
                rs.uniform(-0.5, 0.5, params[name].shape).astype(np.float32)))
        if name in frozen:
            params[name].grad_req = "null"
    return net


def batches(model, n, rows=8, seed=3, sample=None):
    rs = np.random.RandomState(seed)
    shape = (rows,) + (sample or SAMPLE[model])
    return [(rs.randn(*shape).astype(np.float32),
             rs.randint(0, 3, rows).astype(np.float32)) for _ in range(n)]


def fused(net, optimizer="sgd", hyper=None, **kw):
    kw.setdefault("mesh", make_mesh({"dp": 1}, jax.devices()[:1]))
    return DataParallelTrainer(
        net, LOSS, optimizer,
        dict(hyper or {"learning_rate": 0.05, "momentum": 0.9}), **kw)


def eager_step(net, trainer, x, y):
    """The MXNet loop's step, as a user writes it."""
    with autograd.record():
        loss = LOSS(net(mx.nd.array(x)), mx.nd.array(y))
    loss.backward()
    trainer.step(x.shape[0])
    return loss


def loop_state(trainer, name):
    """The eager optimizer's state of the parameter ``name`` as a tuple of
    arrays, in the order the fused step keeps them."""
    index = [p.name for p in trainer._params].index(name)
    state = trainer._updaters[0].states[index]
    if state is None:
        return ()
    leaves = state if isinstance(state, (tuple, list)) else (state,)
    return tuple(leaf.asnumpy() for leaf in leaves)


# ---------------------------------------------------------------------------
# parity with the MXNet loop
# ---------------------------------------------------------------------------

OPTIMIZERS = {
    "sgd": ("sgd", {"learning_rate": 0.05}),
    "momentum": ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    "momentum-wd-clip": ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
                                 "wd": 1e-3, "clip_gradient": 0.05}),
    "rescale": ("sgd", {"learning_rate": 0.05, "rescale_grad": 0.5}),
    "adam": ("adam", {"learning_rate": 0.01}),
    "adam-wd": ("adam", {"learning_rate": 0.01, "wd": 1e-3}),
}


@pytest.mark.parametrize("model", [dense_stack, conv_bn],
                         ids=lambda m: m.__name__)
@pytest.mark.parametrize("dtype", [None, "bfloat16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_fused_step_matches_the_mxnet_loop(opt, dtype, model):
    optimizer, hyper = OPTIMIZERS[opt]
    twin = seeded(model, cast=dtype is not None)
    loop_net = _CastEnds(twin) if dtype else twin
    loop = gluon.Trainer(twin.collect_params(), optimizer, dict(hyper),
                         kvstore=None)
    net = seeded(model)
    trainer = fused(net, optimizer, hyper, dtype=dtype)
    for x, y in batches(model, STEPS):
        want_loss = eager_step(loop_net, loop, x, y)
        loss = trainer.step(x, y)
    atol = 4e-3 if (dtype and model is conv_bn) else 2e-6
    assert abs(float(loss) - float(want_loss.mean().asscalar())) \
        <= max(atol, 1e-5)

    want = twin.collect_params()
    assert set(trainer._params) == set(want)
    for name, value in trainer._params.items():
        assert value.dtype == jnp.float32, name     # masters stay float32
        assert_close(value, want[name].data().asnumpy(), atol, name)
    moving = [n for n, p in want.items() if p.grad_req == "null"]
    assert bool(moving) == (model is conv_bn)
    for name in moving:                             # and they did move
        assert max_ulp_diff(trainer._params[name],
                            seeded(model).collect_params()[name]
                            .data().asnumpy()) > 0, name
    assert set(trainer._opt_state) == set(want) - set(moving)
    for name, state in trainer._opt_state.items():
        leaves = loop_state(loop, name)
        if optimizer == "adam":
            assert int(state[2]) == STEPS           # the step count
            state = state[:2]
        assert len(state) == len(leaves), name
        for k, (got, ref) in enumerate(zip(state, leaves)):
            assert_close(got, ref, atol, "%s state %d" % (name, k))


# ---------------------------------------------------------------------------
# what compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt", ["momentum", "adam"])
def test_a_new_learning_rate_is_applied_and_compiles_nothing(opt):
    optimizer, hyper = OPTIMIZERS[opt]
    twin = seeded(dense_stack)
    loop = gluon.Trainer(twin.collect_params(), optimizer, dict(hyper),
                         kvstore=None)
    trainer = fused(seeded(dense_stack), optimizer, hyper)
    data = batches(dense_stack, 6)
    for x, y in data[:3]:
        eager_step(twin, loop, x, y)
        trainer.step(x, y)
    program = trainer.compile(*data[0])
    before = {n: np.asarray(v) for n, v in trainer._params.items()}
    loop.set_learning_rate(0.002)
    trainer.set_learning_rate(0.002)
    assert trainer.learning_rate == 0.002
    for x, y in data[3:]:
        eager_step(twin, loop, x, y)
        trainer.step(x, y)
    # the same jitted function, holding the one executable it had
    assert trainer.compile(*data[0]) is program
    assert len(trainer._jit_cache) == 1
    assert program._cache_size() == 1
    # and the new rate is the one the three later steps took
    for name, value in trainer._params.items():
        assert_close(value, twin.collect_params()[name].data().asnumpy(),
                     2e-6, name)
        assert max_ulp_diff(value, before[name]) > 0, name


def _new_batch_size(data):
    return [(x[:4], y[:4]) for x, y in data]


def _new_trailing_shape(data):
    return batches(conv_bn, len(data), sample=(2, 9, 5), seed=5)


def _new_input_dtype(data):
    return [((x * 40).astype(np.int16), y) for x, y in data]


@pytest.mark.parametrize("change", [_new_batch_size, _new_trailing_shape,
                                    _new_input_dtype],
                         ids=lambda f: f.__name__[1:])
def test_a_new_input_signature_compiles_once_and_is_then_cached(change):
    trainer = fused(seeded(conv_bn))
    first = batches(conv_bn, 2)
    second = change(first)
    for x, y in first:
        trainer.step(x, y)
    assert len(trainer._jit_cache) == 1
    for x, y in second + first + second:
        trainer.step(x, y)
    assert len(trainer._jit_cache) == 2
    programs = {id(trainer.compile(x, y)): trainer.compile(x, y)
                for x, y in first + second}
    assert len(programs) == 2
    assert [p._cache_size() for p in programs.values()] == [1, 1]


# ---------------------------------------------------------------------------
# what moves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt", ["momentum", "adam"])
def test_a_frozen_parameter_stays_bit_identical(opt):
    optimizer, hyper = OPTIMIZERS[opt]
    frozen = "m_fc1_weight"
    net = seeded(dense_stack, frozen=(frozen,))
    start = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    trainer = fused(net, optimizer, hyper)
    for x, y in batches(dense_stack, 3):
        trainer.step(x, y)
    assert frozen not in trainer._opt_state
    assert sorted(trainer._opt_state) == sorted(set(start) - {frozen})
    for name, value in trainer._params.items():
        moved = max_ulp_diff(value, start[name])
        assert (moved == 0) == (name == frozen), (name, moved)


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("opt", ["momentum", "adam"])
def test_step_multi_lands_where_k_steps_do(opt, k):
    optimizer, hyper = OPTIMIZERS[opt]
    data = batches(dense_stack, k)
    single = fused(seeded(dense_stack), optimizer, hyper)
    for x, y in data:
        last = single.step(x, y)
    multi = fused(seeded(dense_stack), optimizer, hyper)
    loss = multi.step_multi(np.stack([x for x, _ in data]),
                            np.stack([y for _, y in data]))
    assert abs(float(loss) - float(last)) <= 1e-6
    for name, value in multi._params.items():
        assert_close(value, single._params[name], 1e-6, name)
    for got, want in zip(jax.tree.leaves(multi._opt_state),
                         jax.tree.leaves(single._opt_state)):
        assert_close(got, want, 1e-6, "state")


@pytest.mark.parametrize("devices", [2, 4, 8])
def test_a_mesh_gives_the_single_device_result(devices):
    """The batch is cut over ``dp`` and the gradient all-reduced inside
    the program; only the order of the sum over rows differs."""
    data = batches(conv_bn, 3, rows=16)
    single = fused(seeded(conv_bn))
    sharded = fused(seeded(conv_bn), mesh=make_mesh({"dp": devices}))
    for x, y in data:
        want = single.step(x, y)
        loss = sharded.step(x, y)
    assert len(loss.sharding.device_set) == devices
    assert abs(float(loss) - float(want)) <= 1e-5
    for name, value in sharded._params.items():
        assert value.sharding.is_fully_replicated
        assert_close(value, single._params[name], 1e-5, name)


# ---------------------------------------------------------------------------
# what a layer counts inside the step
# ---------------------------------------------------------------------------

def two_batchnorms(momentum):
    """BatchNorm on the input itself and on a hidden layer."""
    net = nn.HybridSequential(prefix="c_")
    with net.name_scope():
        net.add(nn.BatchNorm(in_channels=8, momentum=momentum, prefix="bn0_"))
        net.add(nn.Dense(16, in_units=8, use_bias=False, prefix="fc1_"))
        net.add(nn.BatchNorm(in_channels=16, momentum=momentum,
                             prefix="bn1_"))
        net.add(nn.Activation("relu"))
        net.add(nn.Dense(3, in_units=16, prefix="fc2_"))
    return net


def test_batchnorm_second_passes_leave_the_step_as_counts():
    """``bn_second_pass`` by step from ``telemetry.step_counters()``, a 0 or
    1 a BatchNorm layer in call order, in a bfloat16 step: where the input's
    channels lie 100 standard deviations from zero the first layer takes the
    exact second pass, in every such step; with the input around zero none
    does.  The hidden layer, fed normalised values, never does.  (A float32
    step takes the two passes in every layer.)"""
    from incubator_mxnet_tpu import telemetry

    rs = np.random.RandomState(5)
    noise = rs.randn(16, 8).astype(np.float32)
    x = np.asarray(jnp.asarray(100.0 + noise, jnp.bfloat16), np.float32)
    y = rs.randint(0, 3, 16).astype(np.float32)
    taken = {}
    for dtype in ("bfloat16", None):
        net = two_batchnorms(momentum=0.0)
        net.initialize(mx.init.Xavier(), ctx=mx.cpu())
        trainer = fused(net, hyper={"learning_rate": 0.01}, dtype=dtype)
        before = len(telemetry.step_counters())
        for batch in (x, x, noise, x):
            trainer.step(batch, y)
        records = telemetry.step_counters()[before:]
        assert [step for step, _ in records] == [1, 2, 3, 4]
        taken[dtype] = [counts["bn_second_pass"].tolist()
                        for _, counts in records]
        # the exact branch's statistics are the batch's: the running mean is
        # the batch mean to float32's last bits, not 100 +- a cancelled
        # difference
        mean = np.asarray(trainer._params["c_bn0_running_mean"])
        np.testing.assert_allclose(mean, x.mean(axis=0), rtol=3e-7)
        var = np.asarray(trainer._params["c_bn0_running_var"])
        np.testing.assert_allclose(var, x.astype(np.float64).var(axis=0),
                                   rtol=1e-4)
    assert taken["bfloat16"] == [[1, 0], [1, 0], [0, 0], [1, 0]]
    assert taken[None] == [[1, 1]] * 4


def test_a_step_at_rate_zero_repeats_its_loss_to_the_bit():
    """Training BatchNorm reads its batch and its weights and nothing else:
    at learning rate 0 the running statistics move (momentum) and the loss
    of the same batch does not, in bfloat16 either.  ``benchmark/chip``'s
    ``loss_fell`` tells a step that updates nothing by this."""
    net = two_batchnorms(momentum=0.9)
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    rs = np.random.RandomState(6)
    x = (2.0 + rs.randn(16, 8)).astype(np.float32)
    y = rs.randint(0, 3, 16).astype(np.float32)
    trainer = fused(net, hyper={"learning_rate": 0.0}, dtype="bfloat16")
    losses, means = [], []
    for _ in range(4):
        losses.append(np.asarray(trainer.step(x, y)).tobytes())
        means.append(np.asarray(trainer._params["c_bn0_running_mean"]))
    assert len(set(losses)) == 1
    assert not np.array_equal(means[0], means[-1])


# ---------------------------------------------------------------------------
# what is donated
# ---------------------------------------------------------------------------

def test_compiled_step_runs_nothing_and_donates_nothing():
    trainer = fused(seeded(conv_bn))
    (x, y), = batches(conv_bn, 1)
    trainer.step(x, y)
    held = jax.tree.leaves((trainer._params, trainer._opt_state,
                            trainer._rng_key))
    values = [np.asarray(v) for v in held]
    compiled = trainer.compiled_step(x, y)
    assert "xray:forward" in compiled.as_text()
    now = jax.tree.leaves((trainer._params, trainer._opt_state,
                           trainer._rng_key))
    assert all(a is b for a, b in zip(held, now))
    assert not any(v.is_deleted() for v in held)
    for v, was in zip(held, values):
        np.testing.assert_array_equal(np.asarray(v), was)
    assert len(trainer._jit_cache) == 1             # the step's own
    assert np.isfinite(float(trainer.step(x, y)))   # and it still steps


@pytest.mark.parametrize("donate", [True, False])
def test_the_lowered_step_marks_what_the_trainer_donates(donate):
    trainer = fused(seeded(conv_bn), donate=donate)
    (x, y), = batches(conv_bn, 1)
    trainer.step(x, y)
    xs, ys = trainer._prepare_inputs(x, y, jax.sharding.PartitionSpec("dp"))
    args = (trainer._params, trainer._opt_state, trainer._rng_key, xs, ys,
            trainer._lr_dev)
    lowered = trainer.compile(xs, ys).lower(*xray.abstract(args))
    marks = [jax.tree.leaves(info, is_leaf=lambda i: hasattr(i, "donated"))
             for info in lowered.args_info[0]]
    assert [len(m) for m in marks] == [len(jax.tree.leaves(a)) for a in args]
    params, state, key, data, label, lr = (
        [i.donated for i in m] for m in marks)
    assert params and state and key
    assert set(params + state + key) == {donate}
    assert not any(data + label + lr)       # the batch and the rate: never


@pytest.mark.parametrize("donate", [True, False])
def test_a_step_consumes_what_it_donates_and_nothing_else(donate):
    trainer = fused(seeded(conv_bn), donate=donate)
    (x, y), = batches(conv_bn, 1)
    x, y = jnp.asarray(x), jnp.asarray(y)
    trainer.step(x, y)
    held = jax.tree.leaves((trainer._params, trainer._opt_state,
                            trainer._rng_key))
    lr = trainer._lr_dev
    trainer.step(x, y)
    assert {v.is_deleted() for v in held} == {donate}
    assert not (x.is_deleted() or y.is_deleted() or lr.is_deleted())


# ---------------------------------------------------------------------------
# what reaches the model
# ---------------------------------------------------------------------------

def test_token_ids_reach_the_embedding_unrounded_under_bfloat16():
    """bfloat16 holds 8 bits: 257 would read row 256, 999 row 1000 (past
    the table).  Integer inputs wider than 16 bits are index data and
    are not cast."""
    net = nn.HybridSequential(prefix="ids_")
    with net.name_scope():
        net.add(nn.Embedding(1000, 4, prefix="table_"))
        net.add(nn.Flatten())
        net.add(nn.Dense(3, in_units=8, prefix="fc_"))
    net.initialize(mx.init.Xavier())
    start = net.collect_params()["ids_table_weight"].data().asnumpy()
    trainer = fused(net, "sgd", {"learning_rate": 0.5}, dtype="bfloat16")
    ids = np.array([[257, 999], [513, 257]], np.int32)
    trainer.step(ids, np.array([0, 2], np.float32))
    moved = np.flatnonzero(np.any(
        np.asarray(trainer._params["ids_table_weight"]) != start, axis=1))
    assert moved.tolist() == [257, 513, 999]


@pytest.mark.parametrize("dtype", [None, "bfloat16"], ids=["f32", "bf16"])
def test_a_uint8_image_batch_is_cast_on_the_device(dtype):
    rs = np.random.RandomState(9)
    images = rs.randint(0, 256, (8, 2, 6, 6)).astype(np.uint8)
    labels = rs.randint(0, 3, 8).astype(np.float32)
    raw = fused(seeded(conv_bn), dtype=dtype)
    cast = fused(seeded(conv_bn), dtype=dtype)
    for _ in range(2):
        a = raw.step(images, labels)                # as the pipeline hands it
        b = cast.step(images.astype(np.float32), labels)
    assert float(a) == float(b)
    for name, value in raw._params.items():
        assert max_ulp_diff(value, cast._params[name]) == 0, name


# ---------------------------------------------------------------------------
# what it does not do
# ---------------------------------------------------------------------------

def test_an_optimizer_outside_the_fused_step_is_refused_by_name():
    with pytest.raises(ValueError) as err:
        pure_optimizer("rmsprop", gamma1=0.9)
    assert "'rmsprop'" in str(err.value)
    assert "sgd and adam" in str(err.value)
    with pytest.raises(ValueError, match="rmsprop"):
        fused(seeded(dense_stack), "RMSProp", {"learning_rate": 0.01})
