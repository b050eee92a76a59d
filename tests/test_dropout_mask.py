"""Dropout's masks (``ops/nn.py::inverted_dropout``: the backend's bit
generator, 32 bits an element, compared as integers), on the CPU.

The mask itself: the share kept, the kept values bit for bit, ``p == 0``,
inference mode, ``mode="always"`` and ``axes`` as before, and no correlation
between two sites of one trace, two steps of a trainer, or neighbours along
an axis.  What the callers rely on: one key gives one mask in every program
(eager, ``jit``, ``cachedop_forward`` and the draw made again inside
``cachedop_backward``, the fused step's ``vjp``), shards of a ``dp`` mesh
draw bits of their own, and the fused step of a toy OPT stages one
``rng_bit_generator`` a Dropout site and no threefry of a mask's size.
"""
import importlib.util
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, random_state
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.ops import get_op
from incubator_mxnet_tpu.telemetry import metrics

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the benchmark's own fixture, as tests/test_lfm2_chip_bench.py reaches it
_spec = importlib.util.spec_from_file_location(
    "chip_bench_conftest", ROOT / "tests" / "chip_bench" / "conftest.py")
_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conftest)
chip_run, bench_catalog = _conftest.chip_run, _conftest.bench_catalog

N = 1 << 22
DROPOUT = get_op("Dropout").fcompute


def _kept(shape, p, key=0, **kw):
    """The mask of one site, read from what it does to ones."""
    out = DROPOUT(jnp.ones(shape, jnp.float32), p=p, is_train=True,
                  rng=jax.random.PRNGKey(key), **kw)
    return np.asarray(out) != 0


def _correlation(a, b):
    a, b = a.ravel().astype(np.float64), b.ravel().astype(np.float64)
    return float(np.corrcoef(a, b)[0, 1])


def _uncorrelated(a, b):
    """Within four standard deviations of 0 (1 / sqrt(n) each)."""
    return abs(_correlation(a, b)) < 4 / np.sqrt(a.size)


# ---------------------------------------------------------------------------
# the mask itself
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [0.1, 0.5, 0.9])
def test_kept_share_is_one_minus_p(p):
    share = _kept((N,), p, key=int(p * 10)).mean()
    assert abs(share - (1 - p)) < 4 * np.sqrt(p * (1 - p) / N)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("p", [0.1, 0.5])
def test_kept_elements_are_x_over_keep_bit_for_bit(dtype, p):
    x = jnp.asarray(np.random.randn(64, 1024) + 3.0, dtype)
    out = DROPOUT(x, p=p, is_train=True, rng=jax.random.PRNGKey(5))
    assert out.dtype == x.dtype
    want = x / (1.0 - p)
    kept = np.asarray(out != 0)
    assert 0 < kept.mean() < 1
    np.testing.assert_array_equal(np.asarray(out)[kept],
                                  np.asarray(want)[kept])
    assert not np.asarray(out)[~kept].any()


@pytest.mark.parametrize("case", ["p0", "inference", "always", "axes"])
def test_switches_behave_as_before(case):
    x = jnp.asarray(np.random.rand(8, 16, 32) + 1.0, jnp.float32)
    key = jax.random.PRNGKey(2)
    if case == "p0":
        assert DROPOUT(x, p=0.0, is_train=True, rng=key) is x
    elif case == "inference":
        assert DROPOUT(x, p=0.5, is_train=False, rng=key) is x
    elif case == "always":
        out = np.asarray(DROPOUT(x, p=0.5, mode="always", is_train=False,
                                 rng=key))
        assert 0.3 < (out == 0).mean() < 0.7
    else:
        # one draw an (i, k) pair, shared along axis 1
        kept = _kept(x.shape, 0.5, axes=(1,))
        assert (kept == kept[:, :1, :]).all()
        assert 0.3 < kept[:, 0, :].mean() < 0.7


def test_threshold_is_the_floor_of_keep_times_two_to_the_32():
    """32 bits an element and an integer compare: the jaxpr of a site is
    the four-word threefry, one generator call of the mask's shape and a
    ``lt`` against ``floor(keep * 2**32)``."""
    jaxpr = jax.make_jaxpr(lambda x, k: DROPOUT(x, p=0.1, is_train=True,
                                                rng=k))(
        jnp.ones((4, 8)), jax.random.PRNGKey(0))
    names = [e.primitive.name for e in jaxpr.eqns]
    gen = jaxpr.eqns[names.index("rng_bit_generator")]
    assert gen.params["shape"] == (4, 8)
    assert gen.params["dtype"] == jnp.uint32
    lt = jaxpr.eqns[names.index("lt")]
    assert int(lt.invars[1].val) == int(0.9 * 2 ** 32) == 3865470566
    # no float on the way to the mask
    assert not any(jnp.issubdtype(v.aval.dtype, jnp.floating)
                   for e in jaxpr.eqns[:names.index("lt") + 1]
                   for v in e.outvars)


def test_two_sites_of_one_shape_in_one_trace_draw_different_masks():
    def two_sites(x, key):
        with random_state.use_key(key), autograd.train_mode():
            a = mx.nd.Dropout(mx.nd.NDArray(x), p=0.5)
            b = mx.nd.Dropout(mx.nd.NDArray(x), p=0.5)
        return a._read(), b._read()

    a, b = jax.jit(two_sites)(jnp.ones((N,), jnp.float32),
                              jax.random.PRNGKey(11))
    a, b = np.asarray(a) != 0, np.asarray(b) != 0
    assert abs(a.mean() - 0.5) < 4 * 0.5 / np.sqrt(N)
    assert _uncorrelated(a, b)


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_neighbours_along_an_axis_are_uncorrelated(axis):
    kept = _kept((64, 256, 256), 0.5, key=3)
    lo = [slice(None)] * 3
    hi = [slice(None)] * 3
    lo[axis], hi[axis] = slice(0, -1), slice(1, None)
    assert _uncorrelated(kept[tuple(lo)], kept[tuple(hi)])


class _MaskedScale(gluon.HybridBlock):
    """``Dropout(x * w)``: with one row of ones, momentum-free SGD moves
    ``w`` exactly where the step's mask kept an element."""

    def __init__(self, width, p, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.w = self.params.get("w", shape=(1, width),
                                     init=mx.init.One())
            self.drop = nn.Dropout(p)

    def hybrid_forward(self, F, x, w):
        return self.drop(F.broadcast_mul(x, w))


def test_successive_trainer_steps_draw_different_masks():
    from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    width = 1 << 18
    net = _MaskedScale(width, 0.5, prefix="masked_")
    net.initialize()
    trainer = DataParallelTrainer(
        net, gluon.loss.L2Loss(), optimizer="sgd",
        optimizer_params={"learning_rate": 1.0},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    x = np.ones((1, width), np.float32)
    y = np.full((1, width), -1.0, np.float32)
    seen = [np.ones((1, width), np.float32)]
    for _ in range(2):
        trainer.step(x, y)
        seen.append(np.asarray(trainer._params["masked_w"]))
    first, second = seen[1] != seen[0], seen[2] != seen[1]
    for kept in (first, second):
        assert abs(kept.mean() - 0.5) < 4 * 0.5 / np.sqrt(width)
    assert _uncorrelated(first, second)


# ---------------------------------------------------------------------------
# what the callers rely on
# ---------------------------------------------------------------------------

def test_one_key_gives_one_mask_eagerly_and_under_jit():
    x = jnp.ones((256, 1024), jnp.float32)
    key = jax.random.PRNGKey(9)

    def site(x, key):
        return DROPOUT(x, p=0.3, is_train=True, rng=key)

    eager = np.asarray(site(x, key))
    np.testing.assert_array_equal(eager, np.asarray(jax.jit(site)(x, key)))
    # a second program around the same draw: XLA fuses it otherwise
    other = jax.jit(lambda x, key: jnp.tanh(site(x * 2.0, key)) + 1.0)
    np.testing.assert_array_equal(eager != 0,
                                  np.asarray(other(x, key)) != 1.0)
    assert (eager != np.asarray(site(x, jax.random.PRNGKey(10)))).any()


def test_cachedop_backward_meets_the_forward_programs_mask():
    """The Gluon loop: ``cachedop_backward`` draws the mask again from the
    step's key, in a program of its own (the forward hands it the matrix
    products' outputs, not the draws)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dropout(0.5))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.rand(64, 512).astype(np.float32) + 1.0)
    x.attach_grad()
    with autograd.record():
        y = net(x)
    y.backward()
    out, grad = y.asnumpy(), x.grad.asnumpy()
    assert 0.4 < (out != 0).mean() < 0.6
    np.testing.assert_array_equal(out != 0, grad != 0)
    np.testing.assert_array_equal(grad[grad != 0], np.float32(2.0))


def test_fused_steps_vjp_meets_its_forwards_mask():
    """The fused step: forward and pullback of one ``jax.vjp`` in one
    program, as ``DataParallelTrainer`` stages them."""
    from incubator_mxnet_tpu.gluon.block import functionalize
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dropout(0.5))
    net.initialize()
    x = mx.nd.array(np.random.rand(64, 512).astype(np.float32) + 1.0)
    fn, params = functionalize(net, x, train=True)

    @jax.jit
    def step(xv, key):
        out, pullback = jax.vjp(lambda v: fn(params, v, rng=key), xv)
        grad, = pullback(jnp.ones_like(out))
        return out, grad

    out, grad = step(x._read(), jax.random.PRNGKey(4))
    assert 0.4 < float((out != 0).mean()) < 0.6
    np.testing.assert_array_equal(np.asarray(out != 0),
                                  np.asarray(grad != 0))


def test_shards_of_a_dp_mesh_draw_bits_of_their_own():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()
    assert len(devices) == 8
    mesh = Mesh(np.array(devices), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    x = jax.device_put(jnp.ones((8, 1 << 16), jnp.float32), rows)
    key = jax.device_put(jax.random.PRNGKey(6), NamedSharding(mesh, P()))
    site = jax.jit(lambda x, key: DROPOUT(x, p=0.5, is_train=True, rng=key),
                   out_shardings=rows)
    out = site(x, key)
    assert out.sharding.is_equivalent_to(rows, 2)
    kept = np.asarray(out) != 0
    for i in range(8):
        assert abs(kept[i].mean() - 0.5) < 4 * 0.5 / np.sqrt(kept[i].size)
        for j in range(i):
            assert _uncorrelated(kept[i], kept[j]), (i, j)


def _primitives(jaxpr, found):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def test_fused_step_of_a_toy_opt_stages_one_generator_call_a_site(
        bench_catalog):
    """The OPT cells' program at the rehearsal's sizes: two layers, two
    Dropout sites each.  Four ``rng_bit_generator`` of the activations'
    shape and no threefry over more than four words (the key's split and
    each site's state).  The four sites have one shape and one ``p``, so
    they share one traced body and the counter reads one trace."""
    from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    sizes, module = bench_catalog.config("opt_6b7_l2", rehearse=True)
    traffic = bench_catalog.traffic("fused_s2048", rehearse=True)
    net = module.build(sizes)
    net.initialize(module.initializer(sizes))
    opt = dict(sizes["optimizer"])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer=opt.pop("name"),
        optimizer_params=opt, dtype=traffic["dtype"],
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    batch, seq = traffic["batch_per_chip"], traffic["seq_len"]
    tokens = np.random.randint(0, sizes["vocab_size"], (batch, seq))
    x, y = trainer._prepare_inputs(tokens.astype(np.int32),
                                   tokens.astype(np.int32),
                                   jax.sharding.PartitionSpec("dp"))
    metrics.set_enabled(True)
    counter = metrics.registry().counter(
        "graft_dropout_mask_traces_total", "", ("op",))
    get_op("Dropout")._jit_cache.clear()    # no body traced before this one
    before = counter.value(op="Dropout")
    jaxpr = jax.make_jaxpr(trainer._make_step(train=True))(
        trainer._params, trainer._opt_state, trainer._rng_key, x, y,
        trainer._lr_dev)
    assert counter.value(op="Dropout") - before == 1
    eqns = _primitives(jaxpr.jaxpr, [])
    draws = [e for e in eqns if e.primitive.name == "rng_bit_generator"]
    assert [e.params["shape"] for e in draws] == [
        (batch, seq, sizes["hidden_size"])] * 4
    threefry = [e for e in eqns if e.primitive.name in (
        "threefry2x32", "random_bits", "random_split", "random_fold_in")]
    assert threefry, "the key's split and the sites' states are threefry"
    for e in threefry:
        assert all(int(np.prod(v.aval.shape)) <= 4 for v in e.outvars
                   if v.aval.dtype == jnp.uint32), e


def test_fused_rnn_draws_its_masks_in_the_same_function():
    """``ops/rnn.py``'s inter-layer dropout goes through the one helper:
    a trace of a two-layer LSTM with ``p`` counts one ``RNN`` draw and
    stages one generator call."""
    from incubator_mxnet_tpu.ops.rnn import _rnn
    seq, batch, size, hidden = 5, 3, 4, 6
    rs = np.random.RandomState(0)
    inputs = [rs.randn(seq, batch, size), np.zeros((2, batch, hidden)),
              np.zeros((2, batch, hidden))]
    for layer in range(2):
        inputs += [rs.randn(4 * hidden, size if layer == 0 else hidden),
                   rs.randn(4 * hidden, hidden)]
    inputs += [np.zeros(4 * hidden)] * 4
    inputs = [jnp.asarray(v, jnp.float32) for v in inputs]
    metrics.set_enabled(True)
    counter = metrics.registry().counter(
        "graft_dropout_mask_traces_total", "", ("op",))
    before = counter.value(op="RNN")
    jaxpr = jax.make_jaxpr(lambda key, *vals: _rnn(
        *vals, state_size=hidden, num_layers=2, mode="lstm", p=0.5,
        rng=key, is_train=True))(jax.random.PRNGKey(0), *inputs)
    assert counter.value(op="RNN") - before == 1
    draws = [e for e in _primitives(jaxpr.jaxpr, [])
             if e.primitive.name == "rng_bit_generator"]
    assert [e.params["shape"] for e in draws] == [(seq, batch, hidden)]
