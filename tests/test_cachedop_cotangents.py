"""A recorded CachedOp's backward computes the cotangents the pass will use.

The reverse pass tells the node which of its inputs it has a use for
(``autograd._wanted``: a grad buffer, a place among ``grad``'s variables, a
history on the tape); the backward program returns those and XLA drops what
only the others needed.  What "the parent" gave is the same node asked for
everything, which is what a call of its ``vjp`` without the set does.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.block import functionalize
from incubator_mxnet_tpu.telemetry import metrics, xray

_SHAPE = (2, 3, 6, 6)


def _conv_block(prefix):
    """conv, BatchNorm, ReLU, Dense: 8 parameters, the two moving
    statistics among them with ``grad_req="null"``."""
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
                nn.BatchNorm(in_channels=4), nn.Activation("relu"),
                nn.Dense(5, in_units=4 * 6 * 6))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _dense_block(prefix):
    """Two Dense layers: every parameter is trained."""
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(7, activation="relu", in_units=3 * 6 * 6),
                nn.Dense(5, in_units=7))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _images(seed=0):
    return mx.nd.array(np.random.RandomState(seed).randn(*_SHAPE)
                       .astype(np.float32))


def _params(net):
    """The Block's parameters in the order the tape node lists them."""
    return [p for _, p in sorted(net.collect_params().items())]


def _ask_for_everything(out):
    """(the tape node that made ``out``, every cotangent of it for a seed of
    ones): the parent's backward program, whatever the pass would want."""
    node = out._tape_ref[0]
    return node, [np.asarray(g) for g in node.vjp(jnp.ones_like(out._read()))]


def _assert_delivered(net, want, but=()):
    for p, w in zip(_params(net), want):
        if p.grad_req != "null" and p.name not in but:
            np.testing.assert_array_equal(p.grad().asnumpy(), w,
                                          err_msg=p.name)


def _skipped():
    snap = metrics.registry().snapshot(collect=False)
    samples = snap.get("graft_cachedop_cotangents_skipped_total",
                       {}).get("samples", [])
    read = {s["labels"]["kind"]: s["value"] for s in samples}
    return np.array([read.get("parameter", 0), read.get("input", 0)])


@pytest.fixture
def backward_programs(monkeypatch):
    """The backward programs handed to the registry while the test runs, as
    callables that give each one's optimized HLO."""
    found = []
    register = xray.register_program

    def spy(name, jitted, args, phase=None):
        if name == "cachedop_backward":
            avals = xray.abstract(args)
            found.append(lambda: jitted.lower(*avals).compile().as_text())
        return register(name, jitted, args, phase=phase)

    monkeypatch.setattr(xray, "register_program", spy)
    return found


def _convolutions(program):
    return len(re.findall(r" convolution\(", program()))


def _plain_gradient(f, *at):
    return [np.asarray(g) for g in jax.grad(
        lambda *a: jnp.sum(f(*a)), argnums=tuple(range(len(at))))(*at)]


def test_unmarked_input_costs_one_convolution_fewer(backward_programs):
    net, x = _conv_block("cc_unmarked_"), _images()
    with autograd.record():
        out = net(x)
    _, want = _ask_for_everything(out)
    out.backward()
    _assert_delivered(net, want)
    assert x.grad is None
    # asked for everything: the convolution's two gradients; as the pass
    # ran it: the weights' alone
    assert [_convolutions(p) for p in backward_programs] == [2, 1]


def test_marked_input_gets_the_plain_functions_gradient(backward_programs):
    net, x = _conv_block("cc_marked_"), _images(1)
    x.attach_grad()
    fn, params = functionalize(net, x, train=True)
    with autograd.record():
        out = net(x)
    _, want = _ask_for_everything(out)
    out.backward()
    _assert_delivered(net, want)
    np.testing.assert_array_equal(x.grad.asnumpy(), want[-1])
    plain, = _plain_gradient(lambda v: fn(params, v), x._read())
    np.testing.assert_allclose(x.grad.asnumpy(), plain, rtol=1e-5, atol=1e-6)
    assert [_convolutions(p) for p in backward_programs] == [2, 2]


@pytest.mark.parametrize("upstream", ["eager_op", "hybridized_block"])
def test_cotangent_travels_on_to_what_made_the_input(upstream):
    net, x = _conv_block("cc_chain_%s_" % upstream), _images(2)
    if upstream == "eager_op":
        scale = mx.nd.array(np.random.RandomState(3).rand(1, 3, 1, 1)
                            .astype(np.float32) + 0.5)
        scale.attach_grad()
        first = lambda: x * scale                               # noqa: E731
        fn, params = functionalize(net, x, train=True)
        plain, = _plain_gradient(lambda s: fn(params, x._read() * s),
                                 scale._read())
        received = lambda: scale.grad                           # noqa: E731
    else:
        stem = nn.HybridSequential(prefix="cc_chain_stem_")
        with stem.name_scope():
            stem.add(nn.Conv2D(3, 1, in_channels=3, use_bias=False))
        stem.initialize(mx.init.Xavier())
        stem.hybridize()
        first = lambda: stem(x)                                 # noqa: E731
        (weight,) = _params(stem)
        stem_fn, stem_params = functionalize(stem, x, train=True)
        fn, params = functionalize(net, x, train=True)
        plain, = _plain_gradient(
            lambda w: fn(params, stem_fn({weight.name: w}, x._read())),
            stem_params[weight.name])
        received = weight.grad
    before = _skipped()
    with autograd.record():
        out = net(first())
    _, want = _ask_for_everything(out)
    out.backward()
    _assert_delivered(net, want)
    np.testing.assert_allclose(received().asnumpy(), plain, rtol=1e-5,
                               atol=1e-6)
    # the net's input has a history: only the stem's own input, which has
    # none, goes without its cotangent
    assert list(_skipped() - before) == [2, upstream == "hybridized_block"]


def test_grad_of_an_unmarked_input_is_the_true_gradient():
    net, x = _conv_block("cc_listed_"), _images(4)
    with autograd.record():
        out = net(x)
    _, want = _ask_for_everything(out)
    g, = autograd.grad([out], [x])
    np.testing.assert_array_equal(g.asnumpy(), want[-1])
    assert np.abs(want[-1]).max() > 0
    _assert_delivered(net, want)


def test_frozen_parameter_costs_nothing_and_add_still_accumulates(
        backward_programs):
    net, x = _conv_block("cc_frozen_"), _images(5)
    by_name = {p.name: p for p in _params(net)}
    frozen = by_name["cc_frozen_conv0_weight"]
    added = by_name["cc_frozen_dense0_weight"]
    frozen.grad_req = "null"
    added.grad_req = "add"
    before = _skipped()
    total = 0
    for _ in range(2):
        with autograd.record():
            out = net(x)
        node, want = _ask_for_everything(out)
        ct = jnp.ones_like(out._read())
        # what the pass is about to say, said by hand: no cotangent comes
        # back for what is not wanted, the others are the parent's
        wanted = autograd._wanted(node, {id(node)}, set())
        assert [p.name for p, w in zip(_params(net), wanted) if not w] == [
            "cc_frozen_batchnorm0_running_mean",
            "cc_frozen_batchnorm0_running_var", frozen.name]
        assert wanted[-1] is False
        for g, w, parents in zip(node.vjp(ct, wanted), wanted, want):
            assert (g is None) == (not w)
            if w:
                np.testing.assert_array_equal(np.asarray(g), parents)
        out.backward()
        _assert_delivered(net, want, but=(added.name,))
        total = total + want[_params(net).index(added)]
        np.testing.assert_array_equal(added.grad().asnumpy(), total)
    with pytest.raises(RuntimeError):
        frozen.grad()
    # two calls by hand and two passes, 3 parameters and the input each
    assert list(_skipped() - before) == [12, 4]
    # neither of the convolution's gradients is left in the program (the
    # registry is told whenever the program that ran is another than the
    # last: by hand, the pass, by hand, the pass)
    assert [_convolutions(p) for p in backward_programs] == [2, 0, 2, 0]


def test_retained_passes_that_want_different_sets():
    net, x = _conv_block("cc_retained_"), _images(6)
    with autograd.record():
        out = net(x)
    node, want = _ask_for_everything(out)
    out.backward(retain_graph=True)
    _assert_delivered(net, want)
    g, = autograd.grad([out], [x], retain_graph=True)
    np.testing.assert_array_equal(g.asnumpy(), want[-1])
    x.attach_grad()
    out.backward(retain_graph=True)
    np.testing.assert_array_equal(x.grad.asnumpy(), want[-1])
    _assert_delivered(net, want)
    (entry,) = net._cached_op._cache.values()
    # everything; all but the moving statistics and x; all but those two
    assert entry["backward"]._cache_size() == 3
    out.backward()
    ct = jnp.ones_like(out._read())
    for args in ((ct,), (ct, (True,) * len(node.inputs))):
        with pytest.raises(RuntimeError, match="already backpropagated"):
            node.vjp(*args)


def test_create_graph_goes_through_the_replayed_forward():
    net, x = _dense_block("cc_higher_"), _images(7)
    x.attach_grad()
    fn, params = functionalize(net, x, train=True)

    def loss_of(v):
        return jnp.sum(fn(params, v) ** 2)

    first = jax.grad(loss_of)
    second = jax.grad(lambda v: jnp.sum(first(v) ** 2))
    before = _skipped()
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
        g, = autograd.grad([loss], [x], create_graph=True)
        again = (g * g).sum()
    np.testing.assert_allclose(g.asnumpy(), np.asarray(first(x._read())),
                               rtol=1e-4, atol=1e-5)
    again.backward()
    np.testing.assert_allclose(x.grad.asnumpy(),
                               np.asarray(second(x._read())),
                               rtol=1e-4, atol=1e-5)
    # the first pass ran the replayed forward's vjp, not the backward
    # program; the second reached the node itself, and wanted everything
    assert list(_skipped() - before) == [0, 0]


@pytest.mark.parametrize("block,marked,skipped", [
    (_conv_block, False, [2, 1]),
    (_conv_block, True, [2, 0]),
    (_dense_block, False, [0, 1]),
    (_dense_block, True, [0, 0]),
], ids=["moving_stats_and_input", "moving_stats", "input", "nothing"])
def test_counter_reads_what_the_pass_left_out(block, marked, skipped):
    net, x = block("cc_count_%d%d_" % tuple(skipped)), _images(8)
    if marked:
        x.attach_grad()
    before = _skipped()
    for _ in range(3):
        with autograd.record():
            out = net(x)
        out.backward()
    assert list(_skipped() - before) == [3 * n for n in skipped]
    kinds = {s["labels"]["kind"] for s in metrics.registry().snapshot(
        collect=False)["graft_cachedop_cotangents_skipped_total"]["samples"]}
    assert kinds == {"parameter", "input"}


def test_a_steady_loop_traces_the_backward_once(backward_programs):
    net, x = _conv_block("cc_loop_"), _images(9)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    for _ in range(5):
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(_SHAPE[0])
    (entry,) = net._cached_op._cache.values()
    assert entry["backward"]._cache_size() == 1
    assert len(backward_programs) == 1
    program = xray.programs()["cachedop_backward"]
    assert program.memory["temp_bytes"] >= 0, program.error
    assert _convolutions(backward_programs[0]) == 1
