"""Autograd tests (parity: tests/python/unittest/test_autograd.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def test_record_scope_flags():
    assert not ag.is_recording()
    assert not ag.is_training()
    with ag.record():
        assert ag.is_recording()
        assert ag.is_training()
        with ag.pause():
            assert not ag.is_recording()
    assert not ag.is_recording()
    with ag.record(train_mode=False):
        assert ag.is_recording()
        assert not ag.is_training()
    with ag.train_mode():
        assert ag.is_training()


def test_simple_backward():
    x = mx.nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with ag.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, [2.0, 4.0, 6.0])


def test_chain_and_broadcast_backward():
    x = mx.nd.array(np.random.rand(3, 4).astype(np.float32))
    w = mx.nd.array(np.random.rand(4, 2).astype(np.float32))
    x.attach_grad()
    w.attach_grad()
    with ag.record():
        y = mx.nd.dot(x, w)
        z = (mx.nd.relu(y) * 2).sum()
    z.backward()
    y_np = x.asnumpy() @ w.asnumpy()
    gy = 2 * (y_np > 0)
    assert_almost_equal(x.grad, gy @ w.asnumpy().T, rtol=1e-4, atol=1e-4)
    assert_almost_equal(w.grad, x.asnumpy().T @ gy, rtol=1e-4, atol=1e-4)


def test_backward_head_grad():
    x = mx.nd.array([1.0, 2.0])
    x.attach_grad()
    with ag.record():
        y = x * 3
    y.backward(mx.nd.array([10.0, 100.0]))
    assert_almost_equal(x.grad, [30.0, 300.0])


def test_grad_accumulation_add():
    x = mx.nd.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with ag.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, [6.0, 12.0])  # 3 * 2x


def test_detach_blocks_grad():
    x = mx.nd.array([2.0])
    x.attach_grad()
    with ag.record():
        y = x * x
        z = y.detach() * x
    z.backward()
    assert_almost_equal(x.grad, [4.0])  # only d(z)/dx through the last x


def test_stop_gradient_op():
    x = mx.nd.array([3.0])
    x.attach_grad()
    with ag.record():
        y = mx.nd.BlockGrad(x * x) + x
    y.backward()
    assert_almost_equal(x.grad, [1.0])


def test_autograd_grad_api():
    x = mx.nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with ag.record():
        y = (x * x * x).sum()
    (gx,) = ag.grad([y], [x])
    assert_almost_equal(gx, 3 * np.array([1.0, 4.0, 9.0]))


def test_training_flag_affects_dropout():
    x = mx.nd.ones((100, 100))
    with ag.record(train_mode=True):
        y = mx.nd.Dropout(x, p=0.5)
    # under training, roughly half dropped and survivors scaled by 2
    frac = float((y == 0).mean().asscalar())
    assert 0.3 < frac < 0.7
    with ag.record(train_mode=False):
        z = mx.nd.Dropout(x, p=0.5)
    assert_almost_equal(z, np.ones((100, 100)))
    # predict-mode outside autograd
    w = mx.nd.Dropout(x, p=0.5)
    assert_almost_equal(w, np.ones((100, 100)))


def test_retain_graph():
    x = mx.nd.array([2.0])
    x.attach_grad()
    with ag.record():
        y = x * x
    y.backward(retain_graph=True)
    g1 = x.grad.asnumpy().copy()
    y.backward()
    assert_almost_equal(x.grad, g1)


def test_softmax_output_integrated_grad():
    data = mx.nd.array(np.random.randn(4, 5).astype(np.float32))
    label = mx.nd.array([0.0, 1.0, 2.0, 3.0])
    data.attach_grad()
    with ag.record():
        out = mx.nd.SoftmaxOutput(data, label)
    out.backward()
    p = np.exp(data.asnumpy())
    p /= p.sum(axis=1, keepdims=True)
    oh = np.eye(5, dtype=np.float32)[[0, 1, 2, 3]]
    assert_almost_equal(data.grad, p - oh, rtol=1e-4, atol=1e-4)


def test_custom_function():
    class Sigmoid(ag.Function):
        def forward(self, x):
            y = 1.0 / (1.0 + mx.nd.exp(-x))
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = mx.nd.array(np.random.randn(5).astype(np.float32))
    x.attach_grad()
    f = Sigmoid()
    with ag.record():
        y = f(x)
    y.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad, s * (1 - s), rtol=1e-4, atol=1e-4)


def test_numeric_gradient_harness():
    from incubator_mxnet_tpu.test_utils import check_numeric_gradient
    x = mx.nd.array(np.random.rand(3, 3).astype(np.float32) + 0.5)
    check_numeric_gradient(lambda a: mx.nd.log(a * a + 1.0), [x])


def _node_with_release(calls):
    """y = 3 x recorded as one hand-made tape node that says when the tape
    lets go of what its vjp holds."""
    from incubator_mxnet_tpu.ops.registry import Operator
    x = mx.nd.array([1.0, 2.0])
    x.attach_grad()
    y = mx.nd.array([3.0, 6.0])
    op = Operator("_triple", lambda a: a, num_inputs=1, num_outputs=1)
    ag._record(op, [x], [y], lambda ct: (3.0 * ct,),
               release=lambda: calls.append("released"))
    return x, y


@pytest.mark.parametrize("retain", [False, True])
def test_tape_releases_a_node_after_its_last_pass_only(retain):
    calls = []
    with ag.record():
        x, y = _node_with_release(calls)
    y.backward(retain_graph=retain)
    assert_almost_equal(x.grad.asnumpy(), np.array([3.0, 3.0]))
    assert calls == ([] if retain else ["released"])
    if retain:
        y.backward()
        assert calls == ["released"]
        assert_almost_equal(x.grad.asnumpy(), np.array([3.0, 3.0]))


def test_a_node_the_pass_does_not_reach_is_not_released():
    calls = []
    with ag.record():
        _x, _y = _node_with_release(calls)
        z = mx.nd.array([1.0])
        z.attach_grad()
        w = (z * 2).sum()
    w.backward()
    assert calls == []
    assert_almost_equal(z.grad.asnumpy(), np.array([2.0]))
