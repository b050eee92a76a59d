"""Gluon tests (parity model: tests/python/unittest/test_gluon.py)."""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, autograd
from incubator_mxnet_tpu.gluon import nn


def test_parameter():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier", ctx=mx.cpu(0))
    assert p.data().shape == (10, 10)
    assert p.grad().shape == (10, 10)
    assert p.list_data()[0].shape == (10, 10)


def test_paramdict():
    params = gluon.ParameterDict("net_")
    params.get("weight", shape=(10, 10))
    assert list(params.keys()) == ["net_weight"]
    params.initialize(ctx=mx.cpu(0))
    params.save("/tmp/test_paramdict.params")
    params.load("/tmp/test_paramdict.params", mx.cpu(0))


def test_parameter_sharing():
    class Net(gluon.Block):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5, in_units=5)
                self.dense1 = nn.Dense(5, in_units=5)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    net1 = Net(prefix="net1_")
    net2 = Net(prefix="net2_", params=net1.collect_params())
    net1.collect_params().initialize()
    net2(mx.nd.zeros((3, 5)))
    net1.save_params("/tmp/net1.params")
    net3 = Net(prefix="net3_")
    net3.load_params("/tmp/net1.params", mx.cpu())


def test_basic_dense_shapes():
    model = nn.Sequential()
    model.add(nn.Dense(128, activation="tanh", in_units=10),
              nn.Dense(64, activation="tanh", in_units=128),
              nn.Dense(32, in_units=64))
    model.initialize()
    x = mx.nd.array(np.random.randn(2, 10).astype(np.float32))
    assert model(x).shape == (2, 32)


def test_dense_flatten_false():
    model = nn.Dense(10, flatten=False, in_units=5)
    model.initialize()
    x = mx.nd.array(np.random.randn(2, 3, 5).astype(np.float32))
    assert model(x).shape == (2, 3, 10)


def test_deferred_init_and_hybridize():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.random.randn(3, 7).astype(np.float32))
    y0 = net(x)
    net.hybridize()
    y1 = net(x)
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(), rtol=1e-5, atol=1e-5)


def test_hybrid_training_matches_eager():
    def build():
        mx.random.seed(42)
        net = nn.HybridSequential(prefix="m_")
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu", in_units=6))
            net.add(nn.Dense(3, in_units=8))
        net.initialize(mx.init.Xavier())
        return net

    x = mx.nd.array(np.random.randn(4, 6).astype(np.float32))
    label = mx.nd.array(np.array([0, 1, 2, 1], np.float32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    losses = []
    for hybrid in (False, True):
        net = build()
        if hybrid:
            net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        cur = []
        for _ in range(3):
            with autograd.record():
                L = loss_fn(net(x), label)
            L.backward()
            trainer.step(4)
            cur.append(float(L.mean().asscalar()))
        losses.append(cur)
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_conv_pool_stack():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, kernel_size=3, padding=1, activation="relu"))
        net.add(nn.MaxPool2D(2))
        net.add(nn.Conv2D(16, kernel_size=3, padding=1))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Flatten())
        net.add(nn.Dense(10))
    net.initialize()
    x = mx.nd.array(np.random.randn(2, 3, 16, 16).astype(np.float32))
    assert net(x).shape == (2, 10)
    net.hybridize()
    assert net(x).shape == (2, 10)


def test_batchnorm_moving_stats():
    bn = nn.BatchNorm(in_channels=4)
    bn.initialize()
    x = mx.nd.array((np.random.randn(8, 4, 3, 3) * 3 + 1).astype(np.float32))
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    rv = bn.running_var.data().asnumpy()
    assert not np.allclose(rm, 0)
    assert not np.allclose(rv, 1)
    # inference mode must not move stats
    before = rm.copy()
    bn(x)
    np.testing.assert_allclose(bn.running_mean.data().asnumpy(), before)


def test_conv_transpose():
    net = nn.Conv2DTranspose(4, kernel_size=4, strides=2, padding=1,
                             in_channels=3)
    net.initialize()
    x = mx.nd.array(np.random.randn(1, 3, 8, 8).astype(np.float32))
    assert net(x).shape == (1, 4, 16, 16)


def test_embedding_block():
    emb = nn.Embedding(10, 5)
    emb.initialize()
    idx = mx.nd.array(np.array([1, 2, 3], np.float32))
    assert emb(idx).shape == (3, 5)


def test_losses_basic():
    pred = mx.nd.array(np.random.randn(4, 5).astype(np.float32))
    label_sparse = mx.nd.array(np.array([0, 1, 2, 3], np.float32))
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label_sparse)
    assert l.shape == (4,)
    # L2
    a = mx.nd.array(np.ones((3, 2), np.float32))
    b = mx.nd.array(np.zeros((3, 2), np.float32))
    l2 = gluon.loss.L2Loss()(a, b)
    np.testing.assert_allclose(l2.asnumpy(), np.full(3, 0.5), rtol=1e-6)
    l1 = gluon.loss.L1Loss()(a, b)
    np.testing.assert_allclose(l1.asnumpy(), np.ones(3), rtol=1e-6)
    # BCE matches manual
    p = mx.nd.array(np.array([[0.5, -0.5]], np.float32))
    t = mx.nd.array(np.array([[1.0, 0.0]], np.float32))
    got = gluon.loss.SigmoidBinaryCrossEntropyLoss()(p, t).asnumpy()
    x = np.array([[0.5, -0.5]])
    ref = (np.maximum(x, 0) - x * np.array([[1.0, 0.0]])
           + np.log1p(np.exp(-np.abs(x)))).mean(axis=1)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_huber_hinge_triplet():
    pred = mx.nd.array(np.array([[2.0], [0.3]], np.float32))
    label = mx.nd.array(np.array([[0.0], [0.0]], np.float32))
    h = gluon.loss.HuberLoss(rho=1)(pred, label).asnumpy()
    np.testing.assert_allclose(h, [1.5, 0.5 * 0.09], rtol=1e-5)
    hi = gluon.loss.HingeLoss()(pred, mx.nd.array(np.array([[1.0], [-1.0]],
                                                           np.float32))).asnumpy()
    np.testing.assert_allclose(hi, [0.0, 1.3], rtol=1e-5)


def test_ctc_loss_matches_simple_case():
    # T=2, C=3 (blank=0), label "1": paths: (b,1),(1,b),(1,1)
    logits = np.zeros((2, 1, 3), np.float32)  # uniform → each path (1/3)^2
    loss = gluon.loss.CTCLoss(layout="TNC")(
        mx.nd.array(logits), mx.nd.array(np.array([[1]], np.float32)))
    expected = -np.log(3 * (1 / 9))
    np.testing.assert_allclose(loss.asnumpy(), [expected], rtol=1e-4)


def test_trainer_step_and_state_io():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.ones((4, 3), np.float32))
    with autograd.record():
        L = net(x).sum()
    L.backward()
    w_before = net.weight.data().asnumpy().copy()
    trainer.step(4)
    assert not np.allclose(net.weight.data().asnumpy(), w_before)
    trainer.save_states("/tmp/trainer.states")
    trainer.load_states("/tmp/trainer.states")


def test_clip_global_norm():
    arrays = [mx.nd.array(np.ones((2, 2), np.float32) * 3),
              mx.nd.array(np.ones((2,), np.float32) * 4)]
    norm = gluon.utils.clip_global_norm(arrays, 1.0)
    total = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert abs(total - 1.0) < 1e-4
    assert abs(norm - np.sqrt(9 * 4 + 16 * 2)) < 1e-3


def test_split_and_load():
    data = mx.nd.array(np.arange(12).reshape(6, 2).astype(np.float32))
    slices = gluon.utils.split_and_load(data, [mx.cpu(0), mx.cpu(0)])
    assert len(slices) == 2
    np.testing.assert_allclose(slices[0].asnumpy(), data.asnumpy()[:3])


def test_block_save_load_params():
    net = nn.HybridSequential(prefix="ckpt_")
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3))
    net.initialize()
    y0 = net(mx.nd.ones((1, 3)))
    net.save_params("/tmp/blk.params")
    net2 = nn.HybridSequential(prefix="ckpt_")
    with net2.name_scope():
        net2.add(nn.Dense(4, in_units=3))
    net2.load_params("/tmp/blk.params")
    y1 = net2(mx.nd.ones((1, 3)))
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(), rtol=1e-6)


def test_sequential_getitem_len():
    net = nn.Sequential()
    for _ in range(3):
        net.add(nn.Dense(2))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)


def test_lambda_blocks():
    net = nn.Sequential()
    net.add(nn.HybridLambda(lambda F, x: F.Activation(x, act_type="relu")))
    net.add(nn.Lambda(lambda x: x * 2))
    x = mx.nd.array(np.array([[-1.0, 2.0]], np.float32))
    np.testing.assert_allclose(net(x).asnumpy(), [[0.0, 4.0]])


def test_dataset_dataloader():
    X = np.random.randn(10, 3).astype(np.float32)
    Y = np.arange(10).astype(np.float32)
    dataset = gluon.data.ArrayDataset(X, Y)
    loader = gluon.data.DataLoader(dataset, batch_size=4, shuffle=False)
    batches = list(loader)
    assert len(batches) == 3
    xb, yb = batches[0]
    assert xb.shape == (4, 3)
    np.testing.assert_allclose(yb.asnumpy(), [0, 1, 2, 3])
    # threaded path
    loader2 = gluon.data.DataLoader(dataset, batch_size=4, num_workers=2)
    assert len(list(loader2)) == 3


def test_dataset_transform():
    X = np.ones((4, 2), np.float32)
    ds = gluon.data.ArrayDataset(X, np.zeros(4, np.float32))
    ds2 = ds.transform_first(lambda x: x * 3)
    x, y = ds2[0]
    np.testing.assert_allclose(np.asarray(x), [3, 3])


def test_rnn_cells_and_layers():
    cell = gluon.rnn.GRUCell(6, input_size=4)
    cell.initialize()
    x = mx.nd.array(np.random.randn(2, 5, 4).astype(np.float32))
    outs, state = cell.unroll(5, x, layout="NTC", merge_outputs=True)
    assert outs.shape == (2, 5, 6)

    stack = gluon.rnn.SequentialRNNCell()
    stack.add(gluon.rnn.LSTMCell(6, input_size=4))
    stack.add(gluon.rnn.LSTMCell(6, input_size=6))
    stack.initialize()
    outs, states = stack.unroll(5, x, layout="NTC", merge_outputs=True)
    assert outs.shape == (2, 5, 6)
    assert len(states) == 4

    layer = gluon.rnn.GRU(6, num_layers=1, layout="NTC", input_size=4)
    layer.initialize()
    out = layer(x)
    assert out.shape == (2, 5, 6)


def test_rnn_layer_vs_cell_consistency():
    """Fused RNN op must match the unrolled cell math (reference guarantees
    the same; SURVEY §2.2 RNN row)."""
    T, N, C, H = 4, 2, 3, 5
    x = mx.nd.array(np.random.randn(T, N, C).astype(np.float32))

    layer = gluon.rnn.LSTM(H, num_layers=1, layout="TNC", input_size=C)
    layer.initialize()
    out_layer = layer(x)

    cell = gluon.rnn.LSTMCell(H, input_size=C)
    cell.initialize()
    # copy layer weights into cell
    cp = {p.name.split("_", 1)[1]: p for p in layer.collect_params().values()}
    cell.i2h_weight.set_data(cp["l0_i2h_weight"].data())
    cell.h2h_weight.set_data(cp["l0_h2h_weight"].data())
    cell.i2h_bias.set_data(cp["l0_i2h_bias"].data())
    cell.h2h_bias.set_data(cp["l0_h2h_bias"].data())
    out_cell, _ = cell.unroll(T, x, layout="TNC", merge_outputs=True)
    np.testing.assert_allclose(out_layer.asnumpy(), out_cell.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_bidirectional_cell():
    l_cell = gluon.rnn.LSTMCell(4, input_size=3)
    r_cell = gluon.rnn.LSTMCell(4, input_size=3)
    bi = gluon.rnn.BidirectionalCell(l_cell, r_cell)
    bi.initialize()
    x = mx.nd.array(np.random.randn(2, 5, 3).astype(np.float32))
    outs, states = bi.unroll(5, x, layout="NTC", merge_outputs=True)
    assert outs.shape == (2, 5, 8)


def test_model_zoo_smoke():
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    x = mx.nd.array(np.random.randn(1, 3, 32, 32).astype(np.float32))
    net = vision.get_model("resnet18_v1", classes=10, thumbnail=True)
    net.initialize(mx.init.Xavier())
    assert net(x).shape == (1, 10)
    net = vision.get_model("resnet18_v2", classes=10, thumbnail=True)
    net.initialize(mx.init.Xavier())
    assert net(x).shape == (1, 10)
    net = vision.get_model("mobilenet0.25", classes=10)
    net.initialize(mx.init.Xavier())
    x224 = mx.nd.array(np.random.randn(1, 3, 224, 224).astype(np.float32))
    assert net(x224).shape == (1, 10)


def test_constant_param():
    const = gluon.Constant("const", np.array([[1.0, 2.0]], np.float32))
    const.initialize()
    np.testing.assert_allclose(const.data().asnumpy(), [[1.0, 2.0]])
    assert const.grad_req == "null"


def test_cast():
    net = nn.Dense(3, in_units=2)
    net.initialize()
    net.cast("bfloat16")
    assert net.weight.data().dtype == np.dtype("bfloat16")
    x = mx.nd.array(np.ones((1, 2), np.float32)).astype("bfloat16")
    assert net(x).dtype == np.dtype("bfloat16")


def test_functionalize_threads_rng():
    """functionalize's rng keyword must control stochastic ops: same key
    -> same dropout mask, fresh keys -> different masks (review finding:
    the first cut baked one host key into the trace)."""
    import jax
    from incubator_mxnet_tpu.gluon.block import functionalize

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32), nn.Dropout(0.5))
    net.initialize(mx.init.Xavier())
    x = mx.nd.random.uniform(shape=(4, 8))
    fn, params = functionalize(net, x, train=True)
    jfn = jax.jit(fn)
    xv = x._read()
    a = np.asarray(jfn(params, xv, rng=jax.random.PRNGKey(1)))
    b = np.asarray(jfn(params, xv, rng=jax.random.PRNGKey(1)))
    c = np.asarray(jfn(params, xv, rng=jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any(), "different keys must give different masks"
    assert ((a == 0).mean() > 0.2), "dropout inactive in train trace"


# ---------------------------------------------------------------------------
# The recorded CachedOp: the forward takes the vjp once and hands its
# backward the outputs of the matrix products and of the reductions
# ---------------------------------------------------------------------------

class _Residual(gluon.HybridBlock):
    """relu(x + BN(conv(relu(BN(conv(x))))))."""

    def __init__(self, channels, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.body = nn.HybridSequential(prefix="")
            self.body.add(nn.Conv2D(channels, 3, padding=1, use_bias=False,
                                    in_channels=channels),
                          nn.BatchNorm(in_channels=channels),
                          nn.Activation("relu"),
                          nn.Conv2D(channels, 3, padding=1, use_bias=False,
                                    in_channels=channels),
                          nn.BatchNorm(in_channels=channels))

    def hybrid_forward(self, F, x):
        return F.Activation(x + self.body(x), act_type="relu")


def _conv_net():
    net = nn.HybridSequential(prefix="rc_conv_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, in_channels=3),
                nn.BatchNorm(in_channels=8), nn.Activation("relu"),
                _Residual(8), _Residual(8),
                nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(5, in_units=8))
    return net, (4, 3, 12, 12)


def _dropout_net():
    net = nn.HybridSequential(prefix="rc_drop_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=12),
                nn.Dropout(0.5), nn.Dense(5, in_units=16))
    return net, (8, 12)


def _attention_net():
    net = nn.HybridSequential(prefix="rc_attn_")
    with net.name_scope():
        net.add(nn.Dense(16, flatten=False, in_units=6),
                nn.MultiHeadAttention(16, 4, causal=True),
                nn.Dense(5, flatten=False, in_units=16))
    return net, (2, 8, 6)


_RECORDED_NETS = {"conv_bn_relu_residual": _conv_net,
                  "dense_dropout": _dropout_net,
                  "attention": _attention_net}
_N_CONVOLUTIONS = 5         # of _conv_net


@pytest.fixture(params=sorted(_RECORDED_NETS))
def recorded_net(request):
    """(net, x, the parameters it was initialized with), not hybridized."""
    mx.random.seed(29)
    net, shape = _RECORDED_NETS[request.param]()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(29).randn(*shape)
                    .astype(np.float32))
    x.attach_grad()
    net(x)          # the shapes a layer left open are inferred here
    start = {k: p.data().asnumpy().copy()
             for k, p in net.collect_params().items()}
    return net, x, start


def _restart(net, start):
    for k, p in net.collect_params().items():
        p.set_data(mx.nd.array(start[k]))


def _loss(net, x):
    out = net(x)
    return out, (out * out).sum()


def _grads(net, x):
    grads = {k: p.grad().asnumpy().copy()
             for k, p in net.collect_params().items() if p.grad_req != "null"}
    grads["x"] = x.grad.asnumpy().copy()
    return grads


def _assert_same(got, want, rtol=1e-4, atol=1e-5):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=rtol, atol=atol,
                                   err_msg=k)


def _counters():
    from incubator_mxnet_tpu.telemetry import metrics
    snap = metrics.registry().snapshot(collect=False)
    samples = snap.get("graft_cachedop_residual_bytes", {}).get("samples", [])
    return {"residual_bytes": samples[0]["value"] if samples else 0}


@pytest.fixture
def raw_calls(monkeypatch):
    """Calls of every CachedOp's functionalized forward made from here on:
    one when a program of an entry is traced, one a replay for
    ``create_graph``; a recorded step that hits its entry makes none."""
    import functools
    from incubator_mxnet_tpu.gluon.block import CachedOp
    calls = []
    make = CachedOp._make_fn

    def counting(self, *args, **kwargs):
        raw = make(self, *args, **kwargs)

        @functools.wraps(raw)
        def cachedop_forward(*vals):
            calls.append(1)
            return raw(*vals)
        return cachedop_forward
    monkeypatch.setattr(CachedOp, "_make_fn", counting)
    return calls


def _reference_grads(net, x, seed):
    """What the un-hybridized eager tape gives; where the net draws random
    numbers (the eager ops draw a key each, the CachedOp one for its whole
    program), ``jax.vjp`` over the functionalized forward under the key the
    CachedOp will draw."""
    import jax
    from incubator_mxnet_tpu import random_state
    from incubator_mxnet_tpu.gluon.block import functionalize
    if not any(isinstance(c, nn.Dropout) for c in net._children):
        with autograd.record():
            _, loss = _loss(net, x)
        loss.backward()
        return _grads(net, x)
    fn, params = functionalize(net, x, train=True)
    mx.random.seed(seed)
    key = random_state.next_key()
    out, pullback = jax.vjp(lambda p, v: fn(p, v, rng=key), params,
                            x._read())
    p_g, x_g = pullback(2 * out)
    grads = {k: np.asarray(v) for k, v in p_g.items()
             if net.collect_params()[k].grad_req != "null"}
    grads["x"] = np.asarray(x_g)
    return grads


def test_recorded_gradients_equal_the_eager_tapes(recorded_net):
    net, x, start = recorded_net
    want = _reference_grads(net, x, seed=3)
    _restart(net, start)
    net.hybridize()
    mx.random.seed(3)
    with autograd.record():
        _, loss = _loss(net, x)
    loss.backward()
    _assert_same(_grads(net, x), want)


def test_moving_statistics_move_once_a_recorded_step():
    mx.random.seed(29)
    net, shape = _conv_net()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(2).randn(*shape)
                    .astype(np.float32) * 2 + 1)
    start = {k: p.data().asnumpy().copy()
             for k, p in net.collect_params().items()}
    after = []
    for hybrid in (False, True):
        _restart(net, start)
        if hybrid:
            net.hybridize()
        with autograd.record():
            _, loss = _loss(net, x)
        loss.backward()
        after.append({k: p.data().asnumpy().copy()
                      for k, p in net.collect_params().items()
                      if "running" in k})
    assert len(after[0]) == 10
    for k, eager in after[0].items():
        assert not np.allclose(eager, start[k]), k
        np.testing.assert_allclose(after[1][k], eager, rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_retained_graph_gives_the_same_gradients_twice(recorded_net):
    net, x, _ = recorded_net
    net.hybridize()
    with autograd.record():
        out, loss = _loss(net, x)
    node = out._tape_ref[0]
    loss.backward(retain_graph=True)
    first = _grads(net, x)
    assert node.release.__self__, "a retained pass freed the residuals"
    loss.backward(retain_graph=True)
    second = _grads(net, x)
    for k in first:
        np.testing.assert_array_equal(first[k], second[k], err_msg=k)
    loss.backward()
    for k, g in _grads(net, x).items():
        np.testing.assert_array_equal(first[k], g, err_msg=k)
    # that pass was the node's last: the residuals are gone with it, and
    # the node has left the tape, so one more backward walks nothing
    assert node.used and node.release.__self__ == []
    with pytest.raises(RuntimeError, match="already backpropagated"):
        node.vjp(out._read())
    loss.backward()
    for k, g in _grads(net, x).items():
        np.testing.assert_array_equal(first[k], g, err_msg=k)


def test_two_recorded_calls_before_one_backward(recorded_net):
    net, x, start = recorded_net
    x2 = mx.nd.array(x.asnumpy()[::-1].copy() * 0.5)
    x2.attach_grad()
    if any(isinstance(c, nn.Dropout) for c in net._children):
        # an eager Dropout draws a key of its own: the reference is the
        # hybridized net, one call and one backward at a time
        net.hybridize()
    want = None
    for arr, seed in ((x, 5), (x2, 6)):
        mx.random.seed(seed)
        with autograd.record():
            _, loss = _loss(net, arr)
        loss.backward()
        g = _grads(net, arr)
        g.pop("x")
        want = g if want is None else {k: want[k] + g[k] for k in g}
    want_x, want_x2 = x.grad.asnumpy().copy(), x2.grad.asnumpy().copy()
    _restart(net, start)
    net.hybridize()
    mx.random.seed(5)
    with autograd.record():
        _, first = _loss(net, x)
        mx.random.seed(6)
        _, second = _loss(net, x2)
        loss = first + second
    loss.backward()
    got = _grads(net, x)
    got.pop("x")
    _assert_same(got, want)
    np.testing.assert_allclose(x.grad.asnumpy(), want_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(x2.grad.asnumpy(), want_x2, rtol=1e-4,
                               atol=1e-5)


def test_create_graph_through_a_hybridized_block_counts_one_replay(
        recorded_net, raw_calls):
    net, x, _ = recorded_net
    net.hybridize()
    mx.random.seed(8)
    with autograd.record():
        _, loss = _loss(net, x)
    loss.backward(retain_graph=True)
    want = x.grad.asnumpy().copy()
    before = len(raw_calls)         # the recorded forward's one trace
    g, = autograd.grad([loss], [x], create_graph=True, retain_graph=True)
    np.testing.assert_allclose(g.asnumpy(), want, rtol=1e-4, atol=1e-5)
    assert (before, len(raw_calls)) == (1, 2)
    # and the gradient is itself on the tape: its own gradient flows to x
    (g * g).sum().backward()
    assert np.isfinite(x.grad.asnumpy()).all()
    assert np.abs(x.grad.asnumpy()).sum() > 0


def _made_by(jaxpr, var, outer=()):
    """The primitive that made ``var`` in ``jaxpr``, through the calls that
    only wrap it (an operator's own ``jit``, also where it hands an argument
    of its own on) and the marker JAX puts on a saved value (a
    ``reduce_precision`` to the value's own precision).  ``outer``: the
    (jaxpr, call) pairs around ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if var not in eqn.outvars:
            continue
        if eqn.primitive.name == "reduce_precision":
            return _made_by(jaxpr, eqn.invars[0], outer)
        if eqn.primitive.name == "name":    # ``checkpoint_name``
            return "name:" + eqn.params["name"]
        inner = eqn.params.get("jaxpr")
        if inner is not None:
            inner = getattr(inner, "jaxpr", inner)
            return _made_by(inner, inner.outvars[eqn.outvars.index(var)],
                            outer + ((jaxpr, eqn),))
        return eqn.primitive.name
    if outer and var in jaxpr.invars:
        around, call = outer[-1]
        return _made_by(around, call.invars[jaxpr.invars.index(var)],
                        outer[:-1])
    return "an argument"


def _residual_makers(entry, args, n_residuals):
    """The residuals of a recorded forward as its jaxpr's variables, and the
    primitive that made each."""
    import jax
    jaxpr = jax.make_jaxpr(entry["record"])(*args).jaxpr
    (call,) = jaxpr.eqns
    inner = call.params["jaxpr"].jaxpr
    kept = inner.outvars[-n_residuals:]
    return kept, [_made_by(inner, v) for v in kept]


def test_backward_program_runs_no_forward_convolution(recorded_net):
    import re
    import jax
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.gluon import block as block_module
    from jax.experimental.compilation_cache import compilation_cache
    net, x, _ = recorded_net
    net.hybridize()
    with autograd.record():
        _, loss = _loss(net, x)
    loss.backward()
    (entry,) = net._cached_op._cache.values()
    params = {k: p.data()._read() for k, p in net.collect_params().items()}
    args = (params, [x._read()], jax.random.PRNGKey(0))
    out_vals, _aux, residuals, pullback = jax.eval_shape(entry["record"],
                                                         *args)
    # the optimized HLO of this build, not of a cached one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = {"forward": entry["record"].lower(*args).compile().as_text(),
               "backward": entry["backward"].lower(
                   residuals, pullback, *args,
                   tuple(out_vals)).compile().as_text()}
        made_again = [path for path
                      in telemetry.programs()["cachedop_backward"].ops.values()
                      if "rematted_computation" in path]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    convolutions = {k: len(re.findall(r" convolution\(", text))
                    for k, text in hlo.items()}
    n = _N_CONVOLUTIONS if "conv" in net.prefix else 0
    # a convolution's backward is two (its input's and its weights'
    # gradient); the parent's program held a third, the forward's own
    assert convolutions == {"forward": n, "backward": 2 * n}
    # what the backward makes again is elementwise
    assert made_again
    assert not [path for path in made_again
                if path.endswith(("conv_general_dilated", "dot_general"))]

    # every residual was made by a matrix product or a reduction, and none
    # is a parameter or an input (those are the backward's own arguments)
    assert len(residuals) > 0
    kept, made = _residual_makers(entry, args, len(residuals))
    # ... but for BatchNorm's input, under the name its rule gave it
    # (``checkpoint_name``: here a convolution's result, with or without a
    # bias, which is then not kept a second time under the convolution's own)
    named = [v for v, how in zip(kept, made) if how == "name:bn_input"]
    rest = [how for how in made if how != "name:bn_input"]
    assert set(rest) <= block_module._KEPT_PRIMITIVES, made
    assert len(named) == n
    assert all(v.aval.shape == (4, 8, 12, 12) for v in named)
    assert ("dot_general" in rest) != bool(n)
    assert "conv_general_dilated" not in rest
    assert entry["residual_bytes"] == _counters()["residual_bytes"] == sum(
        int(np.prod(r.shape)) * r.dtype.itemsize for r in residuals)


def test_a_bfloat16_batchnorm_keeps_its_input_and_the_guards_sum():
    """In 16 bits BatchNorm's variance is the one-pass form's under a guard:
    of a layer the recorded forward keeps the input once, under its name,
    and besides the reductions only the exact second pass's sum, a
    per-channel vector that leaves the ``cond`` (``_KEPT_PRIMITIVES`` has no
    ``cond``: the branch's own reduction is what the policy kept)."""
    import jax
    from incubator_mxnet_tpu.gluon import block as block_module
    mx.random.seed(29)
    net, shape = _conv_net()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    x = mx.nd.array(np.random.RandomState(29).randn(*shape)).astype(
        "bfloat16")
    x.attach_grad()
    net.hybridize()
    with autograd.record():
        _, loss = _loss(net, x)
    loss.backward()
    (entry,) = net._cached_op._cache.values()
    params = {k: p.data()._read() for k, p in net.collect_params().items()}
    args = (params, [x._read()], jax.random.PRNGKey(0))
    _out, _aux, residuals, _pullback = jax.eval_shape(entry["record"], *args)
    kept, made = _residual_makers(entry, args, len(residuals))
    named = [v for v, how in zip(kept, made) if how == "name:bn_input"]
    by_cond = [v for v, how in zip(kept, made) if how == "cond"]
    rest = [how for how in made if how not in ("name:bn_input", "cond")]
    assert set(rest) <= block_module._KEPT_PRIMITIVES, made
    assert len(named) == len(by_cond) == _N_CONVOLUTIONS
    assert all(v.aval.shape == (4, 8, 12, 12) for v in named)
    assert all(v.aval.shape == (8,) for v in by_cond)
    assert "conv_general_dilated" not in rest


def test_outside_record_no_residual_is_made(recorded_net):
    net, x, _ = recorded_net
    net.hybridize()
    before = _counters()
    eager = net(x)
    with autograd.predict_mode():
        net(x)
    with autograd.train_mode():
        net(x)
    assert _counters() == before
    assert eager._tape_ref is None
    entries = net._cached_op._cache.values()
    assert len(entries) == 2        # inference and training mode
    assert all("residual_bytes" not in entry for entry in entries)


def test_counters_of_three_recorded_loop_steps(recorded_net, raw_calls):
    import time
    from incubator_mxnet_tpu import telemetry
    net, x, _ = recorded_net
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    since = time.perf_counter()
    for _ in range(3):
        with autograd.record():
            _, loss = _loss(net, x)
        loss.backward()
        trainer.step(x.shape[0])
    after = _counters()
    # three recorded forwards, three backwards, and the forward traced once:
    # no step replayed it
    spans = [s[0] for s in telemetry.spans(since=since)]
    assert (spans.count("fwd"), spans.count("bwd")) == (3, 3)
    assert len(raw_calls) == 1
    (entry,) = net._cached_op._cache.values()
    assert after["residual_bytes"] == entry["residual_bytes"] > 0
