"""``ops/ssm.py``: the selective scan's ``jnp`` form against a step-by-step
loop, value and all six gradients, at lengths that are and are not multiples
of the chunk, in float32 and with bf16 operands; the Pallas kernels in
interpret mode against the ``jnp`` form; the compiled gradient's temporaries
hold no L x channels x states array; the op, its counters and the layers
that call it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops import ssm


def _loop(x, delta, A, B, C, D):
    """The recurrence of the module docstring, one step at a time."""
    x, delta, B, C = (t.astype(jnp.float32) for t in (x, delta, B, C))

    def step(h, xs):
        xt, dt, bt, ct = xs
        h = (jnp.exp(dt[:, :, None] * A) * h
             + (dt * xt)[:, :, None] * bt[:, None, :])
        return h, (h * ct[:, None, :]).sum(-1) + D * xt

    h0 = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, delta, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _operands(batch, length, channels, states, dtype, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(batch, length, channels)
    delta = np.log1p(np.exp(rs.randn(batch, length, channels) - 2.0))
    A = -np.exp(0.5 * rs.randn(channels, states))
    B, C = (rs.randn(batch, length, states) for _ in range(2))
    D = rs.randn(channels)
    g = rs.randn(batch, length, channels)

    def cast(t, to):
        return jnp.asarray(t, jnp.float32).astype(to)

    return (cast(x, dtype), cast(delta, dtype), cast(A, jnp.float32),
            cast(B, dtype), cast(C, dtype), cast(D, jnp.float32)), cast(
                g, dtype)


def _err(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-9)


NAMES = ("x", "delta", "A", "B", "C", "D")


@pytest.mark.parametrize("dtype,limit", [("float32", 2e-6),
                                         ("bfloat16", 2e-2)])
@pytest.mark.parametrize("length,chunk", [(64, 16), (50, 16), (40, None),
                                          (130, None)])
def test_jnp_form_against_a_step_by_step_loop(length, chunk, dtype, limit,
                                              monkeypatch):
    """Value and the six gradients; 50 and 130 are no multiples of the chunk
    (``CHUNK`` set to 16, and the 128 it is), 40 is shorter than ``CHUNK``.
    With bf16 operands both sides see the same rounded operands and compute
    in float32: what differs is the rounding of y and of the gradients."""
    if chunk is not None:
        monkeypatch.setattr(ssm, "CHUNK", chunk)
    ops, g = _operands(2, length, 24, 8, jnp.dtype(dtype))
    y, vjp = jax.vjp(ssm.selective_scan, *ops)
    want, want_vjp = jax.vjp(_loop, *ops)
    assert y.dtype == ops[0].dtype and y.shape == ops[0].shape
    assert _err(y, want) < limit
    for name, got, ref in zip(NAMES, vjp(g), want_vjp(g.astype(want.dtype))):
        assert got.dtype == ref.dtype
        assert _err(got, ref) < 4 * limit, name


@pytest.mark.parametrize("dtype,limit", [("float32", 2e-6),
                                         ("bfloat16", 1e-2)])
def test_kernels_in_interpret_mode_against_the_jnp_form(dtype, limit):
    """Both Mosaic kernels on two channel blocks of 128, two chunks of 32
    steps and two rows: y, the states at the chunks' borders and the six
    gradients."""
    ops, g = _operands(2, 64, 256, 16, jnp.dtype(dtype))
    y, borders = ssm._forward_pallas(*ops, chunk=32, interpret=True)
    want, want_borders = ssm._forward_jnp(*ops, chunk=32)
    assert borders.shape == (2, 2, 16, 256) and borders.dtype == jnp.float32
    assert _err(y, want) < limit
    assert _err(borders, want_borders) < 2e-6
    assert not np.asarray(borders[:, 0]).any()          # h_0 = 0
    got = ssm._backward_pallas(*ops, want_borders, g, chunk=32,
                               interpret=True)
    ref = ssm._backward_jnp(*ops, want_borders, g, chunk=32)
    for name, a, b in zip(NAMES, got, ref):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _err(a, b) < 2 * limit, name


def test_which_shapes_the_kernels_take():
    x = jnp.zeros((1, 64, 256))
    assert ssm._kernels_take(x, jnp.zeros((256, 16)), 32)
    assert not ssm._kernels_take(x, jnp.zeros((256, 12)), 32)   # 12 states
    assert not ssm._kernels_take(x, jnp.zeros((256, 16)), 24)   # bf16 rows
    assert not ssm._kernels_take(jnp.zeros((1, 64, 200)),
                                 jnp.zeros((200, 16)), 32)      # lanes
    assert ssm._channel_block(5120) == 512
    assert ssm._channel_block(384) == 128


def test_no_array_of_every_state_reaches_memory(monkeypatch):
    """L 4096 x 256 channels x 16 states in float32 are 67 MB; the compiled
    gradient of the scan at a chunk of 64 keeps its temporaries far below
    that, and what it saves for the backward is the 64 border states."""
    length, channels, states = 4096, 256, 16
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, length, channels), (1, length, channels), (channels, states),
        (1, length, states), (1, length, states), (channels,))]

    monkeypatch.setattr(ssm, "CHUNK", 64)

    def loss(*a):
        return ssm.selective_scan(*a).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *shapes).compile()
    every_state = 4 * length * channels * states
    assert compiled.memory_analysis().temp_size_in_bytes < every_state // 3
    _, borders = jax.eval_shape(
        lambda *a: ssm._forward_jnp(*a, chunk=64), *shapes)
    assert borders.shape == (1, length // 64, states, channels)


def test_the_op_and_its_counters():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry

    def snap(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in telemetry.registry().snapshot().get(
                    name, {"samples": []})["samples"]}

    before = snap("graft_ssm_scan_traces_total")
    ops, _ = _operands(1, 32, 128, 16, jnp.float32, seed=3)
    y = mx.nd._contrib_SelectiveScan(*(mx.nd.NDArray(t) for t in ops))
    assert _err(y._read(), _loop(*ops)) < 2e-6
    after = snap("graft_ssm_scan_traces_total")
    grew = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    # the eager op is a jitted function: its operands are tracers, and the
    # program picks the form for the platform it is lowered for
    assert grew == {(("path", "lowering_platform"),): 1}
    assert snap("graft_ssm_scan_chunk") == {(): 32.0}
    assert snap("graft_ssm_state_elems") == {(): 128.0 * 16}
    # 12 states: no kernel takes it, on any platform
    ops, _ = _operands(1, 32, 128, 12, jnp.float32, seed=3)
    ssm.selective_scan(*ops)
    grew = snap("graft_ssm_scan_traces_total")
    assert grew[(("path", "jnp"),)] - after.get((("path", "jnp"),), 0) == 1


def test_mamba_initialises_as_mamba_does():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    mx.random.seed(4)
    layer = nn.Mamba(64, prefix="m_")
    layer.initialize(mx.init.Normal(0.02))
    read = {n: np.asarray(p.data()._read())
            for n, p in layer.collect_params().items()}
    assert {n: v.shape for n, v in read.items()} == {
        "m_in_weight": (256, 64), "m_conv_weight": (128, 4),
        "m_conv_bias": (128,), "m_x_weight": (4 + 32, 128),
        "m_dt_weight": (128, 4), "m_dt_bias": (128,),
        "m_A_log": (128, 16), "m_D": (128,), "m_out_weight": (64, 128)}
    np.testing.assert_allclose(
        read["m_A_log"], np.broadcast_to(np.log(np.arange(1, 17)), (128, 16)),
        rtol=1e-6)
    assert (read["m_D"] == 1).all()
    # the taps and their bias: a depthwise Conv1d's default, +- conv^-1/2
    for name in ("m_conv_weight", "m_conv_bias"):
        assert 0.4 < np.abs(read[name]).max() <= 0.5, name
        assert 0.2 < read[name].std() < 0.35, name
    assert np.abs(read["m_dt_weight"]).max() <= 4 ** -0.5
    step = np.log1p(np.exp(read["m_dt_bias"]))      # softplus of the bias
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert step.max() / step.min() > 10             # log-uniform, not one
    assert 0.01 < read["m_in_weight"].std() < 0.03
