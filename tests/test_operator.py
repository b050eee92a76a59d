"""Operator correctness tests (parity: tests/python/unittest/test_operator.py
subset — vs numpy references + numeric gradients; SURVEY §4.1)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.test_utils import (assert_almost_equal,
                                            check_numeric_gradient)


def _nd(x):
    return mx.nd.array(np.asarray(x, np.float32))


def test_unary_ops_vs_numpy():
    x_np = np.random.uniform(0.5, 2.0, (3, 4)).astype(np.float32)
    x = _nd(x_np)
    cases = {
        "sqrt": np.sqrt, "exp": np.exp, "log": np.log, "square": np.square,
        "sin": np.sin, "cos": np.cos, "tanh": np.tanh, "abs": np.abs,
        "sigmoid": lambda a: 1 / (1 + np.exp(-a)), "rsqrt": lambda a: 1 / np.sqrt(a),
        "log1p": np.log1p, "expm1": np.expm1, "floor": np.floor, "ceil": np.ceil,
        "sign": np.sign, "reciprocal": lambda a: 1 / a,
    }
    for name, ref in cases.items():
        out = getattr(mx.nd, name)(x)
        assert_almost_equal(out, ref(x_np), rtol=1e-4, atol=1e-5, names=(name, "np"))


def test_activation_ops():
    x_np = np.random.randn(4, 5).astype(np.float32)
    x = _nd(x_np)
    assert_almost_equal(mx.nd.Activation(x, act_type="relu"), np.maximum(x_np, 0))
    assert_almost_equal(mx.nd.Activation(x, act_type="softrelu"),
                        np.log1p(np.exp(x_np)), rtol=1e-4, atol=1e-5)
    assert_almost_equal(mx.nd.LeakyReLU(x, act_type="leaky", slope=0.1),
                        np.where(x_np > 0, x_np, 0.1 * x_np))
    e = np.where(x_np > 0, x_np, 0.25 * (np.exp(x_np) - 1))
    assert_almost_equal(mx.nd.LeakyReLU(x, act_type="elu", slope=0.25), e,
                        rtol=1e-4, atol=1e-5)


def test_softmax_ops():
    x_np = np.random.randn(3, 6).astype(np.float32)
    x = _nd(x_np)
    e = np.exp(x_np - x_np.max(axis=-1, keepdims=True))
    p = e / e.sum(axis=-1, keepdims=True)
    assert_almost_equal(mx.nd.softmax(x), p, rtol=1e-4, atol=1e-5)
    assert_almost_equal(mx.nd.log_softmax(x), np.log(p), rtol=1e-4, atol=1e-4)
    # temperature
    assert_almost_equal(mx.nd.softmax(x, temperature=2.0),
                        np.exp(x_np / 2 - (x_np / 2).max(-1, keepdims=True)) /
                        np.exp(x_np / 2 - (x_np / 2).max(-1, keepdims=True)).sum(-1, keepdims=True),
                        rtol=1e-4, atol=1e-5)


def test_fully_connected():
    x = np.random.randn(4, 7).astype(np.float32)
    w = np.random.randn(3, 7).astype(np.float32)
    b = np.random.randn(3).astype(np.float32)
    out = mx.nd.FullyConnected(_nd(x), _nd(w), _nd(b), num_hidden=3)
    assert_almost_equal(out, x @ w.T + b, rtol=1e-4, atol=1e-4)
    out2 = mx.nd.FullyConnected(_nd(x), _nd(w), num_hidden=3, no_bias=True)
    assert_almost_equal(out2, x @ w.T, rtol=1e-4, atol=1e-4)
    # flatten semantics: (N, ...) collapses
    x4 = np.random.randn(2, 3, 2, 2).astype(np.float32)
    w4 = np.random.randn(5, 12).astype(np.float32)
    out3 = mx.nd.FullyConnected(_nd(x4), _nd(w4), num_hidden=5, no_bias=True)
    assert_almost_equal(out3, x4.reshape(2, -1) @ w4.T, rtol=1e-4, atol=1e-4)


def _np_conv2d(x, w, stride, pad):
    n, c, h, wd = x.shape
    oc, _, kh, kw = w.shape
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    out = np.zeros((n, oc, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh, j * stride:j * stride + kw]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out


def test_convolution_vs_numpy():
    x = np.random.randn(2, 3, 7, 7).astype(np.float32)
    w = np.random.randn(4, 3, 3, 3).astype(np.float32)
    b = np.random.randn(4).astype(np.float32)
    out = mx.nd.Convolution(_nd(x), _nd(w), _nd(b), kernel=(3, 3), num_filter=4,
                            stride=(2, 2), pad=(1, 1))
    ref = _np_conv2d(x, w, 2, 1) + b.reshape(1, -1, 1, 1)
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-3)


def test_convolution_grouped_and_1x1():
    x = np.random.randn(1, 4, 5, 5).astype(np.float32)
    w = np.random.randn(4, 2, 1, 1).astype(np.float32)
    out = mx.nd.Convolution(_nd(x), _nd(w), kernel=(1, 1), num_filter=4,
                            num_group=2, no_bias=True)
    assert out.shape == (1, 4, 5, 5)


def test_pooling():
    x = np.random.randn(2, 3, 6, 6).astype(np.float32)
    mp = mx.nd.Pooling(_nd(x), kernel=(2, 2), stride=(2, 2), pool_type="max")
    ref = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
    assert_almost_equal(mp, ref)
    ap = mx.nd.Pooling(_nd(x), kernel=(2, 2), stride=(2, 2), pool_type="avg")
    refa = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
    assert_almost_equal(ap, refa, rtol=1e-4, atol=1e-5)
    gp = mx.nd.Pooling(_nd(x), global_pool=True, pool_type="max", kernel=(1, 1))
    assert gp.shape == (2, 3, 1, 1)
    assert_almost_equal(gp, x.max(axis=(2, 3), keepdims=True))


def test_batchnorm_train_and_inference():
    x = np.random.randn(8, 4, 3, 3).astype(np.float32)
    gamma = np.random.rand(4).astype(np.float32) + 0.5
    beta = np.random.randn(4).astype(np.float32)
    mean = np.zeros(4, np.float32)
    var = np.ones(4, np.float32)
    # inference mode: uses moving stats
    out = mx.nd.BatchNorm(_nd(x), _nd(gamma), _nd(beta), _nd(mean), _nd(var),
                          fix_gamma=False, eps=1e-5)
    ref = (x - mean.reshape(1, -1, 1, 1)) / np.sqrt(var.reshape(1, -1, 1, 1) + 1e-5)
    ref = ref * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-4)
    # train mode: uses batch stats
    with mx.autograd.record():
        out_t = mx.nd.BatchNorm(_nd(x), _nd(gamma), _nd(beta), _nd(mean), _nd(var),
                                fix_gamma=False, eps=1e-5)
    bm = x.mean(axis=(0, 2, 3))
    bv = x.var(axis=(0, 2, 3))
    ref_t = (x - bm.reshape(1, -1, 1, 1)) / np.sqrt(bv.reshape(1, -1, 1, 1) + 1e-5)
    ref_t = ref_t * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)
    assert_almost_equal(out_t, ref_t, rtol=1e-3, atol=1e-3)


def test_layernorm():
    x = np.random.randn(4, 10).astype(np.float32)
    g = np.random.rand(10).astype(np.float32)
    b = np.random.randn(10).astype(np.float32)
    out = mx.nd.LayerNorm(_nd(x), _nd(g), _nd(b), eps=1e-5)
    mu = x.mean(-1, keepdims=True)
    sig = x.var(-1, keepdims=True)
    assert_almost_equal(out, (x - mu) / np.sqrt(sig + 1e-5) * g + b,
                        rtol=1e-4, atol=1e-4)


def test_transpose_slice_ops():
    x = np.arange(24).reshape(2, 3, 4).astype(np.float32)
    assert_almost_equal(mx.nd.transpose(_nd(x)), x.T)
    assert_almost_equal(mx.nd.transpose(_nd(x), axes=(1, 0, 2)), x.transpose(1, 0, 2))
    assert_almost_equal(mx.nd.slice_axis(_nd(x), axis=1, begin=1, end=3), x[:, 1:3])
    assert_almost_equal(mx.nd.slice(_nd(x), begin=(0, 1), end=(2, 3)), x[0:2, 1:3])
    assert_almost_equal(mx.nd.flip(_nd(x), axis=2), x[:, :, ::-1])
    assert_almost_equal(mx.nd.expand_dims(_nd(x), axis=1), x[:, None])
    assert_almost_equal(mx.nd.tile(_nd(x[0]), reps=(2, 1)), np.tile(x[0], (2, 1)))
    assert_almost_equal(mx.nd.repeat(_nd(x), repeats=2, axis=0), np.repeat(x, 2, 0))


def test_pad_op():
    x = np.random.randn(1, 1, 3, 3).astype(np.float32)
    out = mx.nd.pad(_nd(x), mode="constant", pad_width=(0, 0, 0, 0, 1, 1, 2, 2),
                    constant_value=5.0)
    ref = np.pad(x, ((0, 0), (0, 0), (1, 1), (2, 2)), constant_values=5.0)
    assert_almost_equal(out, ref)


def test_ordering_ops():
    x = np.array([[3.0, 1.0, 2.0], [0.0, 5.0, 4.0]], np.float32)
    assert_almost_equal(mx.nd.topk(_nd(x), k=2), [[0, 2], [1, 2]])
    assert_almost_equal(mx.nd.topk(_nd(x), k=2, ret_typ="value"), [[3, 2], [5, 4]])
    assert_almost_equal(mx.nd.sort(_nd(x)), np.sort(x))
    assert_almost_equal(mx.nd.sort(_nd(x), is_ascend=False), -np.sort(-x))
    assert_almost_equal(mx.nd.argsort(_nd(x)), np.argsort(x))
    assert_almost_equal(mx.nd.argmax(_nd(x), axis=1), [0, 1])
    assert_almost_equal(mx.nd.argmin(_nd(x), axis=0), [1, 0, 0])


def test_where_clip():
    cond = np.array([[1, 0], [0, 1]], np.float32)
    x = np.ones((2, 2), np.float32)
    y = np.zeros((2, 2), np.float32)
    assert_almost_equal(mx.nd.where(_nd(cond), _nd(x), _nd(y)), cond)
    a = np.array([-2.0, 0.5, 3.0], np.float32)
    assert_almost_equal(mx.nd.clip(_nd(a), a_min=-1.0, a_max=1.0), np.clip(a, -1, 1))


def test_sequence_ops():
    # (T, N, D) = (4, 2, 3)
    x = np.random.randn(4, 2, 3).astype(np.float32)
    slen = np.array([2.0, 4.0], np.float32)
    last = mx.nd.SequenceLast(_nd(x), _nd(slen), use_sequence_length=True)
    assert_almost_equal(last, np.stack([x[1, 0], x[3, 1]]))
    masked = mx.nd.SequenceMask(_nd(x), _nd(slen), use_sequence_length=True, value=-1.0)
    ref = x.copy()
    ref[2:, 0] = -1.0
    assert_almost_equal(masked, ref)
    rev = mx.nd.SequenceReverse(_nd(x), _nd(slen), use_sequence_length=True)
    ref2 = x.copy()
    ref2[:2, 0] = x[:2, 0][::-1]
    ref2[:, 1] = x[:, 1][::-1]
    assert_almost_equal(rev, ref2)


def test_gather_scatter():
    data = np.arange(9).reshape(3, 3).astype(np.float32)
    idx = np.array([[0, 2], [1, 0]], np.float32)  # (M=2, N=2)
    out = mx.nd.gather_nd(_nd(data), _nd(idx))
    assert_almost_equal(out, [data[0, 1], data[2, 0]])
    s = mx.nd.scatter_nd(_nd(np.array([5.0, 6.0])), _nd(idx), shape=(3, 3))
    ref = np.zeros((3, 3), np.float32)
    ref[0, 1] = 5
    ref[2, 0] = 6
    assert_almost_equal(s, ref)


def test_pick():
    x = np.random.randn(3, 4).astype(np.float32)
    idx = np.array([0.0, 2.0, 3.0], np.float32)
    out = mx.nd.pick(_nd(x), _nd(idx))
    assert_almost_equal(out, x[np.arange(3), [0, 2, 3]])


def test_numeric_gradients_core_ops():
    x = mx.nd.array(np.random.rand(3, 4).astype(np.float32) + 0.5)
    w = mx.nd.array(np.random.rand(4, 2).astype(np.float32))
    check_numeric_gradient(lambda a: mx.nd.tanh(a), [x])
    check_numeric_gradient(lambda a, b: mx.nd.dot(a, b), [x, w])
    check_numeric_gradient(lambda a: mx.nd.softmax(a), [x])
    check_numeric_gradient(lambda a: mx.nd.Pooling(
        a.reshape((1, 1, 3, 4)), kernel=(2, 2), stride=(1, 1), pool_type="avg"), [x])


def test_lrn():
    x = np.random.randn(2, 5, 3, 3).astype(np.float32)
    out = mx.nd.LRN(_nd(x), nsize=3, alpha=1e-4, beta=0.75, knorm=2.0)
    # numpy reference
    sq = x ** 2
    ref = np.zeros_like(x)
    for c in range(5):
        lo, hi = max(0, c - 1), min(5, c + 2)
        s = sq[:, lo:hi].sum(axis=1)
        ref[:, c] = x[:, c] * (2.0 + 1e-4 / 3 * s) ** -0.75
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_deconvolution_shape_inverse():
    # deconv inverts conv spatial shape math
    x = np.random.randn(1, 2, 5, 5).astype(np.float32)
    w = np.random.randn(2, 3, 3, 3).astype(np.float32)  # (in, out, kh, kw)
    out = mx.nd.Deconvolution(_nd(x), _nd(w), kernel=(3, 3), num_filter=3,
                              stride=(2, 2), pad=(1, 1), adj=(1, 1))
    assert out.shape == (1, 3, 10, 10)


def test_regression_outputs():
    d = np.random.randn(4, 3).astype(np.float32)
    l = np.random.randn(4, 3).astype(np.float32)
    data = _nd(d)
    data.attach_grad()
    with mx.autograd.record():
        out = mx.nd.LinearRegressionOutput(data, _nd(l))
    assert_almost_equal(out, d)
    out.backward()
    assert_almost_equal(data.grad, d - l, rtol=1e-4, atol=1e-5)


def test_l2_normalization():
    x = np.random.randn(2, 3, 4).astype(np.float32)
    out = mx.nd.L2Normalization(_nd(x), mode="instance")
    ref = x / np.sqrt((x ** 2).sum(axis=(1, 2), keepdims=True) + 1e-10)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_batchnorm_large_mean_variance():
    """Shifted one-pass variance must survive |mean| >> std channels
    (round-2 review: naive E[x^2]-E[x]^2 cancels catastrophically)."""
    rs = np.random.RandomState(0)
    x = (rs.randn(64, 4, 3, 3) * 0.03 + 1000.0).astype(np.float32)
    gamma = mx.nd.array(np.ones(4, np.float32))
    beta = mx.nd.array(np.zeros(4, np.float32))
    mmean = mx.nd.array(np.zeros(4, np.float32))  # stale running mean
    mvar = mx.nd.array(np.ones(4, np.float32))
    with mx.autograd.record():
        out = mx.nd.BatchNorm(mx.nd.array(x), gamma, beta, mmean, mvar,
                              fix_gamma=False)
    got = out.asnumpy()
    want = (x - x.mean(axis=(0, 2, 3), keepdims=True)) / \
        np.sqrt(x.var(axis=(0, 2, 3), keepdims=True) + 1e-3)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def _bn_reference(x, gamma, beta, dy, axis, eps, fix_gamma):
    """Training BatchNorm and its three gradients, two passes in float64."""
    red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    m = x.size / x.shape[axis]
    mean = x.mean(axis=red, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=red, keepdims=True)
    inv = 1.0 / np.sqrt(var + eps)
    g = np.ones_like(mean) if fix_gamma else gamma.reshape(mean.shape)
    xhat = (x - mean) * inv
    out = xhat * g + beta.reshape(mean.shape)
    dbeta = dy.sum(axis=red, keepdims=True)
    dgamma = (dy * xhat).sum(axis=red, keepdims=True)
    dx = g * inv * (dy - (dbeta + xhat * dgamma) / m)
    if fix_gamma:
        dgamma = np.zeros_like(dgamma)
    return out, mean.ravel(), var.ravel(), dx, dgamma.ravel(), dbeta.ravel()


@pytest.mark.parametrize("fix_gamma", [False, True])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("off", [0.0, 1.0, 3.0, 10.0, 1e4])
def test_batchnorm_one_pass_statistics(off, dtype, axis, fix_gamma):
    """A bfloat16 activation's statistics are the sum and the sum of squares
    in one pass, and the exact second pass where a channel's batch mean lies
    ``off`` > 16 batch standard deviations from the sums' origin, zero; a
    float32 activation's are the two passes always.  Results and gradients
    are the two-pass reference's either way, and the second pass is counted
    where it ran (the rule's extra result, as the op counts it)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import nn as ops_nn
    from incubator_mxnet_tpu.telemetry.tracing import collect_step_counters

    rs = np.random.RandomState(7)
    shape = (16, 6, 5, 5) if axis == 1 else (16, 5, 5, 6)
    cshape = [1, 1, 1, 1]
    cshape[axis] = 6
    red = tuple(i for i in range(4) if i != axis % 4)
    spread = rs.uniform(0.2, 2.0, 6).reshape(cshape)
    noise = rs.randn(*shape)
    noise = (noise - noise.mean(axis=red, keepdims=True)) / noise.std(
        axis=red, keepdims=True)
    # every channel's mean ``off`` of its own standard deviations from zero
    # (bfloat16's rounding of values that far out leaves the far case far)
    data = jnp.asarray(spread * (noise + off), dtype)
    gamma = jnp.asarray(rs.uniform(0.5, 1.5, 6), dtype)
    beta = jnp.asarray(rs.uniform(-1.0, 1.0, 6), dtype)
    dy = jnp.asarray(rs.randn(*shape), dtype)
    eps = 1e-3
    x64, g64, b64, dy64 = (np.asarray(v, np.float64)
                           for v in (data, gamma, beta, dy))
    want = _bn_reference(x64, g64, b64, dy64, axis, eps, fix_gamma)
    # the running statistics are not read in training: poisoned ones (a
    # forward that overflowed on a step the loss scaler then skipped) leave
    # it as it is
    running_mean = jnp.asarray([np.nan, np.inf, -np.inf, 3e38, 0.0, 1.0],
                               jnp.float32)
    running_var = jnp.asarray([np.nan, np.inf, 0.0, 3e38, 1.0, 1.0],
                              jnp.float32)

    def op(d, g, b):
        return ops_nn._batch_norm(d, g, b, running_mean, running_var,
                                  eps=eps, fix_gamma=fix_gamma, axis=axis,
                                  is_train=True)

    with collect_step_counters() as found:
        (out, mean, var), pullback = jax.vjp(op, data, gamma, beta)
    dx, dgamma, dbeta = pullback((dy, jnp.zeros_like(mean),
                                  jnp.zeros_like(var)))
    (name, _labels, second_pass), = found
    assert name == "bn_second_pass"
    limit = {"bfloat16": 16.0, "float32": -1.0}[dtype]
    assert int(second_pass) == (1 if off > limit else 0)
    # the rule's own extra result says the same
    assert int(ops_nn._bn_train_core(data, gamma, beta, axis, eps,
                                     fix_gamma)[3]) == int(second_pass)

    assert out.dtype == data.dtype and mean.dtype == var.dtype == jnp.float32
    # float32 statistics whatever the data.  The CPU's float32 sum of 400
    # terms of one sign is good to 1e-5 of itself; the one-pass variance is
    # the difference of two such numbers 1 + off^2 times its size (257 at
    # the guard's limit).  The far case is the two-pass form's, as good as
    # its mean: that sum's 1e-6 of ``off`` deviations, squared in the variance
    if off <= limit:
        loose = loose_var = 2e-5 * (1.0 + off ** 2)
    else:
        loose = max(2e-6 * off, 2e-5)
        loose_var = 2e-5 + loose ** 2
    np.testing.assert_allclose(mean, want[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(var, want[2], rtol=loose_var)
    tol = dict(rtol=loose, atol=loose) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-2)        # bfloat16 keeps 8 bits
    got = [np.asarray(v, np.float64) for v in (out, dx, dgamma, dbeta)]
    np.testing.assert_allclose(got[0], want[0], **tol)
    np.testing.assert_allclose(got[1], want[3], **tol)
    sums = dict(tol, atol=tol["atol"] * 20)   # sums of 400 terms
    np.testing.assert_allclose(got[2], want[4], **sums)
    np.testing.assert_allclose(got[3], want[5], **sums)


def test_batchnorm_not_a_number_takes_the_exact_pass():
    """The guard's predicate is written for unordered values: a channel whose
    sums are not numbers (an overflowed activation) takes the exact branch,
    which gives what the two passes always gave, and is counted."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import nn as ops_nn

    x = np.random.RandomState(1).randn(8, 3, 4, 4).astype(np.float32)
    x[0, 1, 0, 0] = np.inf
    ones, zeros = jnp.ones(3), jnp.zeros(3)
    out, mean, var, second_pass = ops_nn._bn_train_core(
        jnp.asarray(x), ones, zeros, 1, 1e-3, False)
    assert int(second_pass) == 1
    assert np.isinf(float(mean[1])) and np.isnan(float(var[1]))
    good = [0, 2]
    np.testing.assert_allclose(np.asarray(mean)[good],
                               x[:, good].mean(axis=(0, 2, 3)), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(var)[good],
                               x[:, good].var(axis=(0, 2, 3)), rtol=1e-5)
    assert np.isfinite(np.asarray(out)[:, good]).all()
