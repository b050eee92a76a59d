"""Parallelism tests on the 8-device virtual CPU mesh: data parallel
(fused step), ring attention (sp), pipeline (pp), flash attention kernel,
tensor-parallel sharding. The driver's dryrun_multichip covers the same
surface; these pin numerics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import make_mesh, DataParallelTrainer
from incubator_mxnet_tpu.parallel.ring_attention import ring_attention
from incubator_mxnet_tpu.parallel.pipeline import pipeline_apply
from incubator_mxnet_tpu.ops.attention import (flash_attention,
                                               _attention_reference)


def test_data_parallel_trainer_matches_single_device():
    def build():
        mx.random.seed(7)
        net = nn.HybridSequential(prefix="dp_")
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", in_units=8))
            net.add(nn.Dense(3, in_units=16))
        net.initialize(mx.init.Xavier())
        return net

    rs = np.random.RandomState(0)
    x = rs.randn(16, 8).astype(np.float32)
    y = (rs.rand(16) * 3).astype(np.float32)

    losses = {}
    for ndev in (1, 8):
        net = build()
        mesh = make_mesh({"dp": ndev})
        tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 mesh=mesh)
        cur = [float(tr.step(mx.nd.array(x), mx.nd.array(y)))
               for _ in range(4)]
        losses[ndev] = cur
    np.testing.assert_allclose(losses[1], losses[8], rtol=1e-4)


@pytest.mark.parametrize("rows", ["apart", "together"])
def test_batchnorm_statistics_are_the_whole_batch_under_a_dp_mesh(rows):
    """BatchNorm's two sums are taken over the whole batch, not a device's
    rows: the fused bfloat16 step over four devices gives the one-device
    step's statistics, and the guard is decided once, from the reduced sums,
    the same on every device.  ``apart``: device i's rows lie around 1000 i,
    three of the quarters hundreds of their own deviations from zero but the
    whole batch under two of its own: no second pass.  ``together``: all
    rows around 1000: every device takes it, in both steps."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import blackbox

    rs = np.random.RandomState(11)
    centre = (np.repeat(np.arange(4.0), 4)[:, None] if rows == "apart"
              else np.ones((16, 1)))
    x = 1000.0 * centre + 8.0 * rs.randn(16, 8)
    x = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    y = (rs.rand(16) * 3).astype(np.float32)

    def build():
        mx.random.seed(7)
        net = nn.HybridSequential(prefix="bnmesh_")
        with net.name_scope():
            net.add(nn.BatchNorm(in_channels=8, momentum=0.0))
            net.add(nn.Dense(3, in_units=8))
        net.initialize(mx.init.Xavier())
        return net

    state, taken = {}, {}
    for ndev in (1, 4):
        tr = DataParallelTrainer(
            build(), gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.01},
            mesh=make_mesh({"dp": ndev}), dtype="bfloat16")
        before = len(telemetry.step_counters())
        losses = [float(tr.step(mx.nd.array(x), mx.nd.array(y)))
                  for _ in range(2)]
        taken[ndev] = [counts["bn_second_pass"].tolist() for _, counts
                       in telemetry.step_counters()[before:]]
        state[ndev] = dict({n: np.asarray(v) for n, v in tr._params.items()},
                           losses=np.asarray(losses))
        # every device holds the same decision
        _step, counts, _labels = blackbox._counts[-2]
        copies = [np.asarray(s.data) for s
                  in counts["bn_second_pass"].addressable_shards]
        assert len(copies) == ndev
        assert all((c == copies[0]).all() for c in copies)
    assert taken[1] == taken[4] == ([[0], [0]] if rows == "apart"
                                    else [[1], [1]])
    mean = state[4]["bnmesh_batchnorm0_running_mean"]
    np.testing.assert_allclose(mean, x.astype(np.float64).mean(axis=0),
                               rtol=1e-6)
    np.testing.assert_allclose(
        state[4]["bnmesh_batchnorm0_running_var"],
        x.astype(np.float64).var(axis=0), rtol=1e-4)
    for name, want in state[1].items():
        np.testing.assert_allclose(state[4][name], want, rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_ring_attention_matches_reference():
    mesh = make_mesh({"sp": 8})
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 4, 64, 16
    q = rs.randn(B, H, S, D).astype(np.float32)
    k = rs.randn(B, H, S, D).astype(np.float32)
    v = rs.randn(B, H, S, D).astype(np.float32)
    out = ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         mesh, axis="sp")
    ref = _attention_reference(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal():
    mesh = make_mesh({"sp": 4})
    rs = np.random.RandomState(1)
    B, H, S, D = 1, 2, 32, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    out = ring_attention(q, k, v, mesh, axis="sp", causal=True)
    ref = _attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_fallback_and_grad():
    rs = np.random.RandomState(2)
    B, H, S, D = 1, 2, 16, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    out = flash_attention(q, k, v)
    ref = _attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    g = jax.grad(lambda a: flash_attention(a, k, v).sum())(q)
    g_ref = jax.grad(lambda a: _attention_reference(a, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4,
                               atol=1e-5)


def test_flash_attention_op_surface():
    rs = np.random.RandomState(3)
    q = mx.nd.array(rs.randn(1, 2, 8, 4).astype(np.float32))
    k = mx.nd.array(rs.randn(1, 2, 8, 4).astype(np.float32))
    v = mx.nd.array(rs.randn(1, 2, 8, 4).astype(np.float32))
    out = mx.nd._contrib_FlashAttention(q, k, v, causal=True)
    assert out.shape == (1, 2, 8, 4)


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pp": 4})
    rs = np.random.RandomState(0)
    D = 16
    # 4 stages of y = relu(x @ W + b), identical shapes
    Ws = rs.randn(4, D, D).astype(np.float32) * 0.3
    bs = rs.randn(4, D).astype(np.float32) * 0.1
    params = {"W": jnp.asarray(Ws), "b": jnp.asarray(bs)}

    def stage(p, x):
        return jax.nn.relu(x @ p["W"] + p["b"])

    x = jnp.asarray(rs.randn(8, D).astype(np.float32))
    out = pipeline_apply(stage, params, x, mesh, axis="pp",
                         num_microbatches=4)
    ref = x
    for i in range(4):
        ref = jax.nn.relu(ref @ params["W"][i] + params["b"][i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_tensor_parallel_matmul_sharding():
    """GSPMD tensor parallelism: column-parallel matmul over 'tp' — the
    strictly-more-general replacement for ctx_group placement (SURVEY §2.4
    model-parallelism row)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh({"tp": 8})
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 32).astype(np.float32))
    w = jnp.asarray(rs.randn(32, 64).astype(np.float32))
    w_sh = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))
    x_rep = jax.device_put(x, NamedSharding(mesh, P()))

    @jax.jit
    def f(a, b):
        return a @ b

    out = f(x_rep, w_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), rtol=1e-4)
    # output is column-sharded over tp
    assert out.sharding.spec == P(None, "tp")


def test_dp_sp_2d_mesh_attention():
    """2-D mesh: batch over dp, sequence over sp — the composition the
    multi-chip dry run exercises."""
    mesh = make_mesh({"dp": 2, "sp": 4})
    rs = np.random.RandomState(4)
    B, H, S, D = 4, 2, 32, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    import functools
    from incubator_mxnet_tpu.parallel.ring_attention import _ring_body
    spec = P("dp", None, "sp", None)
    stat = P("dp", None, "sp")
    fn = shard_map(functools.partial(_ring_body, axis_name="sp",
                                     causal=False, scale=D ** -0.5),
                   mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=(spec, stat, stat),
                   check_vma=False)
    out, _, _ = fn(q, k, v)
    ref = _attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("sq,sk,causal", [(16, 16, False), (16, 16, True),
                                          (8, 24, True), (24, 8, True),
                                          (128, 256, True)])
def test_flash_attention_grads_match_reference(sq, sk, causal):
    """Chunked flash backward vs autodiff of the dense reference, covering
    KV-cache decode shapes (Sq < Sk) and rows with no visible keys
    (Sq > Sk) — round-1 advisor findings on the causal mask + O(S²) bwd."""
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 2, sq, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 2, sk, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 2, sk, 16).astype(np.float32))
    g = jnp.asarray(rs.randn(1, 2, sq, 16).astype(np.float32))

    out, vjp = jax.vjp(lambda a, b, c: flash_attention(a, b, c, causal),
                       q, k, v)
    ref_out, ref_vjp = jax.vjp(
        lambda a, b, c: _attention_reference(a, b, c, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


def test_mixed_precision_matches_f32():
    """bf16 compute + f32 masters tracks the f32 loss curve (reference
    mp_sgd semantics, src/operator/optimizer_op.cc mp_* ops)."""
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(8, kernel_size=3, padding=1))
            net.add(nn.BatchNorm())
            net.add(nn.Activation("relu"))
            net.add(nn.GlobalAvgPool2D())
            net.add(nn.Flatten())
            net.add(nn.Dense(4))
        return net

    rs = np.random.RandomState(0)
    x = rs.randn(16, 3, 8, 8).astype(np.float32)
    y = (rs.rand(16) * 4).astype(np.float32)
    losses = {}
    for dt in (None, "bfloat16"):
        mx.random.seed(0)
        net = build()
        net.initialize(mx.init.Xavier())
        tr = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=make_mesh({"dp": 8}), dtype=dt)
        losses[dt] = [float(tr.step(mx.nd.array(x), mx.nd.array(y)))
                      for _ in range(8)]
    assert losses["bfloat16"][-1] < losses["bfloat16"][0]  # it learns
    np.testing.assert_allclose(losses[None], losses["bfloat16"], atol=0.05)


def test_sync_params_then_eager_eval():
    """sync_params must leave Block params usable by eager single-device
    forward (mesh-sharded buffers pulled to host first)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=make_mesh({"dp": 8}))
    x = mx.nd.array(np.ones((8, 6), np.float32))
    y = mx.nd.array(np.zeros(8, np.float32))
    tr.step(x, y)
    tr.sync_params()
    out = net(mx.nd.array(np.ones((2, 6), np.float32)))
    assert out.shape == (2, 4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match_reference(causal):
    """Ring attention's custom vjp (dK/dV touring the ring) must equal the
    single-device reference autodiff (VERDICT r1: was inference-only)."""
    mesh = make_mesh({"sp": 4})
    rs = np.random.RandomState(11)
    B, H, S, D = 1, 2, 32, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))

    out, vjp = jax.vjp(lambda a, b, c: ring_attention(a, b, c, mesh, "sp",
                                                      causal), q, k, v)
    ref_out, ref_vjp = jax.vjp(
        lambda a, b, c: _attention_reference(a, b, c, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-5)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


def test_ring_attention_trains_in_jit():
    """grad-of-ring-attention inside jit over a dp×sp mesh (the long-context
    training configuration)."""
    mesh = make_mesh({"dp": 2, "sp": 4})
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(2, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(2, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(2, 2, 16, 8).astype(np.float32))

    @jax.jit
    def f(q, k, v):
        return jax.grad(
            lambda a: ring_attention(a, k, v, mesh, "sp", True).sum())(q)

    gq = f(q, k, v)
    g_ref = jax.grad(
        lambda a: _attention_reference(a, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(g_ref),
                               rtol=1e-3, atol=1e-4)


def test_pipeline_train_step_matches_sequential():
    """GPipe backward: grads per stage equal the unpipelined chain's grads;
    a few SGD steps reduce the loss (VERDICT r1: was forward-only)."""
    from incubator_mxnet_tpu.parallel.pipeline import (pipeline_train_step,
                                                       make_pipeline_trainer)
    mesh = make_mesh({"pp": 4})
    rs = np.random.RandomState(0)
    D = 8
    Ws = (rs.randn(4, D, D) * 0.4).astype(np.float32)
    bs = (rs.randn(4, D) * 0.1).astype(np.float32)
    params = {"W": jnp.asarray(Ws), "b": jnp.asarray(bs)}
    x = jnp.asarray(rs.randn(8, D).astype(np.float32))
    y = jnp.asarray(rs.randn(8, D).astype(np.float32))

    def stage(p, a):
        return jnp.tanh(a @ p["W"] + p["b"])

    def loss_fn(out, y):
        return jnp.sum((out - y) ** 2, axis=-1)

    loss, grads = pipeline_train_step(stage, params, x, y, loss_fn, mesh,
                                      num_microbatches=4)

    def seq_objective(params):
        a = x
        for i in range(4):
            a = jnp.tanh(a @ params["W"][i] + params["b"][i])
        return jnp.mean(jnp.sum((a - y) ** 2, axis=-1))

    ref_loss, ref_grads = jax.value_and_grad(seq_objective)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for name in ("W", "b"):
        np.testing.assert_allclose(np.asarray(grads[name]),
                                   np.asarray(ref_grads[name]),
                                   rtol=1e-4, atol=1e-5)

    train = make_pipeline_trainer(stage, loss_fn, mesh, num_microbatches=4,
                                  learning_rate=0.05)
    p, losses = params, []
    for _ in range(10):
        p, l = train(p, x, y)
        losses.append(float(l))
    assert losses[-1] < 0.5 * losses[0], losses
