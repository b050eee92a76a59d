"""The configuration ``phi4_mini_flash_l5`` (``configs/phi4_flash.py``), the
layers its cell brought (``gluon.nn.Mamba``, ``GatedMemoryUnit``,
``DifferentialAttention``) and the readers (``layer_metrics/ssm.py``,
``diff_attention.py``), on the CPU: each layer and the whole Block against
the module's plain reference at the rehearsal's size, value, loss and
gradients; the reference's own mixer against ``transformers``'
``MambaMixer.slow_forward`` and its hand-written adjoint against ``jax.grad``
of a ``lax.scan``; the departures a tolerance would hide; the table's slice
against the uncut head; the file's published widths; the readers' needs
counted by hand and their metrics read from a synthetic trace.  The cell's
rehearsals are ``tests/chip_bench/test_chip_bench_rehearsal.py``'s, which
find it in ``BENCHMARK.json``; the scan itself is
``tests/test_selective_scan.py``'s.
"""
import importlib.util
import json
import pathlib
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the benchmark's own fixtures, as tests/test_lfm2_chip_bench.py takes them
_spec = importlib.util.spec_from_file_location(
    "chip_bench_conftest", ROOT / "tests" / "chip_bench" / "conftest.py")
_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conftest)
chip_run, bench_catalog = _conftest.chip_run, _conftest.bench_catalog
CELL, CONFIG = "phi4flash_sambay_fused_1row", "phi4_mini_flash_l5"
US = 1000


@pytest.fixture(scope="module")
def phi(bench_catalog):
    """(the rehearsal's sizes, module): d 64, 8 query over 4 K/V heads of 8
    (4 differential heads over 2 value heads of 16), a window of 16, an MLP
    of 128, 128 channels of 8 states with a step's rank of 4, the five kinds
    of layer once."""
    return bench_catalog.config(CONFIG, rehearse=True)


def _net(module, sizes, seed=7):
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    net = module.build(sizes)
    net.initialize(module.initializer(sizes))
    return net


def _params(module, net):
    import jax.numpy as jnp
    cut = len(module.PREFIX)
    return {n[cut:]: p.data()._read().astype(jnp.float32)
            for n, p in net.collect_params().items()}


def _logits(net, tokens):
    """The Block's eager float32 forward, as ``run.py`` makes it."""
    import incubator_mxnet_tpu as mx
    with mx.autograd.train_mode():
        return np.asarray(net(mx.nd.array(tokens, dtype="int32"))._read())


def _tokens(sizes, rows=2, seq=80, seed=0):
    rs = np.random.RandomState(seed)
    vocab = sizes[sizes["input"]["vocab_key"]]
    return rs.randint(0, vocab, (rows, seq)).astype(np.int32)


def _err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


def _reference(module, sizes, params, tokens):
    import jax
    return np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        params, tokens))


def _seeded(layer, x, std=0.3, seed=5):
    """The layer initialised at a scale at which every part shows, and its
    functional form: (fn, values by the reference's names)."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    mx.random.seed(seed)
    layer.initialize(mx.init.Normal(std))
    fn, values = functionalize(layer, *x, train=True)
    return fn, {n: jnp.asarray(v) for n, v in values.items()}


def _agree(got, want, names, limit=1e-4):
    for name in names:
        assert _err(got[name], want[name]) < limit, name


# ---------------------------------------------------------------------------
# the layers against the plain reference
# ---------------------------------------------------------------------------

def test_mamba_layer_agrees_with_the_reference(phi):
    """Value, the memory it hands on and every parameter's gradient, both
    results' cotangents at once."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    sizes, module = phi
    d = sizes["hidden_size"]
    layer = nn.Mamba(d, state=sizes["mamba_d_state"],
                     conv=sizes["mamba_d_conv"], expand=sizes["mamba_expand"],
                     dt_rank=sizes["mamba_dt_rank"], return_memory=True,
                     prefix="mixer_")
    rs = np.random.RandomState(1)
    x = rs.randn(2, 50, d).astype(np.float32)
    fn, values = _seeded(layer, [mx.nd.array(x)])
    assert {n: tuple(v.shape) for n, v in values.items()} == {
        "mixer_in_weight": (256, d), "mixer_conv_weight": (128, 4),
        "mixer_conv_bias": (128,), "mixer_x_weight": (4 + 2 * 8, 128),
        "mixer_dt_weight": (128, 4), "mixer_dt_bias": (128,),
        "mixer_A_log": (128, 8), "mixer_D": (128,),
        "mixer_out_weight": (d, 128)}
    g = (jnp.asarray(rs.randn(2, 50, d).astype(np.float32)),
         jnp.asarray(rs.randn(2, 50, 128).astype(np.float32)))
    (out, memory), vjp = jax.vjp(lambda p: fn(p, jnp.asarray(x)), values)
    (w_out, w_memory), w_vjp = jax.vjp(
        lambda p: module._mamba(sizes, p, jnp.asarray(x)), dict(values))
    assert _err(out, w_out) < 1e-5 and _err(memory, w_memory) < 1e-5
    _agree(vjp(g)[0], w_vjp(g)[0], values)
    alone = nn.Mamba(d, state=8, dt_rank=4, prefix="alone_")
    alone.initialize()
    assert alone(mx.nd.array(x)).shape == (2, 50, d)    # no memory asked


def test_gated_memory_unit_agrees_with_the_reference(phi):
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    sizes, module = phi
    d = sizes["hidden_size"]
    rs = np.random.RandomState(2)
    x, m = (rs.randn(2, 50, w).astype(np.float32) for w in (d, 2 * d))
    fn, values = _seeded(nn.GatedMemoryUnit(d, 2 * d, prefix="mixer_"),
                         [mx.nd.array(x), mx.nd.array(m)])
    assert sorted(values) == ["mixer_in_weight", "mixer_out_weight"]
    g = jnp.asarray(rs.randn(2, 50, d).astype(np.float32))
    out, vjp = jax.vjp(lambda p, a, b: fn(p, a, b), values, jnp.asarray(x),
                       jnp.asarray(m))
    want, w_vjp = jax.vjp(lambda p, a, b: module._gmu(p, a, b), dict(values),
                          jnp.asarray(x), jnp.asarray(m))
    assert _err(out, want) < 1e-5
    (gp, gx, gm), (wp, wx, wm) = vjp(g), w_vjp(g)
    _agree(gp, wp, values)
    assert _err(gx, wx) < 1e-4 and _err(gm, wm) < 1e-4


@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_agrees_with_the_reference(phi, kind):
    """Each kind of layer alone: value, the k and v a full layer hands on,
    and the gradient of every parameter (of a cross layer also that of the
    handed k, v)."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    sizes, module = phi
    d, heads, kv_heads = (sizes["hidden_size"], sizes["num_attention_heads"],
                          sizes["num_key_value_heads"])
    dim = d // heads
    depth = {"window": 15, "full": 17, "cross": 19}[kind]
    theirs_kind = {"window": module.WINDOW, "full": module.FULL,
                   "cross": module.CROSS}[kind]
    rs = np.random.RandomState(3)
    x = rs.randn(2, 48, d).astype(np.float32)
    # the handed-over k, v: the program's (B, G, S, D) and (B, G/2, S, 2D),
    # the reference's (B, S, G/2, 2, D) and (B, S, G/2, 2D)
    k = rs.randn(2, 48, kv_heads // 2, 2, dim).astype(np.float32)
    v = rs.randn(2, 48, kv_heads // 2, 2 * dim).astype(np.float32)
    ours_kv = (jnp.asarray(k).reshape(2, 48, kv_heads, dim).transpose(
        0, 2, 1, 3), jnp.asarray(v).transpose(0, 2, 1, 3))
    layer = nn.DifferentialAttention(
        d, heads, kv_heads, depth,
        window=sizes["sliding_window"] if kind == "window" else None,
        cross=kind == "cross", return_kv=kind == "full", prefix="mixer_")
    inputs = [mx.nd.array(x)]
    if kind == "cross":
        # ``functionalize`` takes arrays: k and v apart, handed on as a pair
        class Handed(gluon.HybridBlock):
            def __init__(self, inner):
                super().__init__(prefix="")
                self.inner = inner

            def hybrid_forward(self, F, x, k, v):
                return self.inner(x, (k, v))

        layer = Handed(layer)
        inputs += [mx.nd.NDArray(t) for t in ours_kv]
    fn, values = _seeded(layer, inputs)
    want_names = {"mixer_out_weight", "mixer_out_bias", "mixer_lambda_q1",
                  "mixer_lambda_k1", "mixer_lambda_q2", "mixer_lambda_k2",
                  "mixer_subln_gamma"} | (
        {"mixer_q_weight", "mixer_q_bias"} if kind == "cross" else
        {"mixer_qkv_weight", "mixer_qkv_bias"})
    assert set(values) == want_names
    if kind != "cross":
        assert values["mixer_qkv_weight"].shape == (d + 2 * kv_heads * dim, d)
    gain = 1.0 + 0.1 * rs.randn(2 * dim).astype(np.float32)
    values["mixer_subln_gamma"] = jnp.asarray(gain)
    g = jnp.asarray(rs.randn(2, 48, d).astype(np.float32))

    def ours(p, kv):
        out = fn(p, jnp.asarray(x), *kv) if kind == "cross" else fn(
            p, jnp.asarray(x))
        return out[0] if isinstance(out, (tuple, list)) else out

    def theirs(p, kv):
        return module._attention(sizes, p, jnp.asarray(x), theirs_kind,
                                 depth, handed=kv)[0]

    got, vjp = jax.vjp(ours, values, ours_kv)
    want, w_vjp = jax.vjp(theirs, dict(values),
                          (jnp.asarray(k), jnp.asarray(v)))
    assert _err(got, want) < 1e-5
    (gp, gkv), (wp, wkv) = vjp(g), w_vjp(g)
    _agree(gp, wp, values)
    if kind == "cross":
        assert _err(gkv[0].transpose(0, 2, 1, 3).reshape(k.shape),
                    wkv[0]) < 1e-4
        assert _err(gkv[1].transpose(0, 2, 1, 3), wkv[1]) < 1e-4
    if kind == "full":
        _, ok, ov = fn(values, jnp.asarray(x))
        _, (wk, wv) = module._attention(sizes, values, jnp.asarray(x),
                                        theirs_kind, depth)
        assert ok.shape == (2, kv_heads, 48, dim)
        assert ov.shape == (2, kv_heads // 2, 48, 2 * dim)
        assert _err(ok.transpose(0, 2, 1, 3).reshape(wk.shape), wk) < 1e-5
        assert _err(ov.transpose(0, 2, 1, 3), wv) < 1e-5


def test_differential_attention_refuses_what_it_cannot_be():
    from incubator_mxnet_tpu.gluon import nn
    with pytest.raises(ValueError, match="pair up"):
        nn.DifferentialAttention(64, 7, 1, 0)
    with pytest.raises(ValueError, match="pair up"):
        nn.DifferentialAttention(64, 8, 3, 0)
    with pytest.raises(ValueError, match="cross layer"):
        nn.DifferentialAttention(64, 8, 4, 0, cross=True, window=16)
    import incubator_mxnet_tpu as mx
    layer = nn.DifferentialAttention(64, 8, 4, 19, cross=True, prefix="c_")
    layer.initialize()
    with pytest.raises(ValueError, match="takes the"):
        layer(mx.nd.array(np.zeros((1, 16, 64), np.float32)))
    # lambda_init = 0.8 - 0.6 exp(-0.3 l), from the published index
    assert layer._lambda_init == pytest.approx(0.8 - 0.6 * np.exp(-5.7))


def test_cross_layers_are_counted(phi):
    from incubator_mxnet_tpu import telemetry

    def count():
        snap = telemetry.registry().snapshot().get(
            "graft_shared_kv_reads_total", {"samples": []})
        return sum(s["value"] for s in snap["samples"])

    sizes, module = phi
    before = count()
    _logits(_net(module, sizes), _tokens(sizes, rows=1, seq=16))
    assert count() - before == 1                # one cross layer in the cut


# ---------------------------------------------------------------------------
# the Block against the plain reference
# ---------------------------------------------------------------------------

def test_block_agrees_with_the_reference(phi):
    sizes, module = phi
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    want = _reference(module, sizes, params, tokens)
    assert got.shape == (2, 80, sizes["vocab_rows_held"])
    assert _err(got, want) < 1e-5 < module.TOLERANCE["block_f32"]
    shapes = {n: tuple(v.shape) for n, v in params.items()}
    assert shapes["embed_tokens_weight"] == (512, 64)
    assert not [n for n in shapes if "head" in n]           # tied
    assert shapes["layers_0_mixer_qkv_weight"] == (64 + 2 * 32, 64)
    assert shapes["layers_1_mixer_A_log"] == (128, 8)
    assert shapes["layers_2_mixer_qkv_bias"] == (128,)
    assert shapes["layers_3_mixer_in_weight"] == (128, 64)
    assert "layers_3_mixer_conv_weight" not in shapes       # a GMU has none
    assert shapes["layers_4_mixer_q_weight"] == (64, 64)
    assert "layers_4_mixer_qkv_weight" not in shapes        # q only
    for i in range(5):
        assert shapes["layers_%d_mlp_w1_weight" % i] == (128, 64)
        assert shapes["layers_%d_input_layernorm_beta" % i] == (64,)


def test_loss_and_gradients_agree_with_the_reference(phi):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by
    parameter."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = phi
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    labels = _tokens(sizes, seed=1).astype(np.float32)
    fn, values = functionalize(net, mx.nd.array(tokens, dtype="int32"),
                               train=True)

    def block_loss(p):
        out = fn(p, jnp.asarray(tokens))
        out = out[0] if isinstance(out, (tuple, list)) else out
        return module.reference_loss(out, labels)

    cut = len(module.PREFIX)
    loss, grads = jax.value_and_grad(block_loss)(values)
    got = {n[cut:]: np.asarray(g) for n, g in grads.items()}
    want_loss, want = jax.value_and_grad(lambda p: module.reference_loss(
        module.reference(sizes, p, tokens), labels))(_params(module, net))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want)
    for name, g in want.items():
        g = np.asarray(g)
        assert np.abs(got[name] - g).max() / max(np.abs(g).max(),
                                                 1e-12) < 2e-3, name


# ---------------------------------------------------------------------------
# the reference held to what this PR did not write
# ---------------------------------------------------------------------------

def test_reference_mixer_against_transformers_slow_forward(phi):
    """``transformers``' ``MambaMixer.slow_forward`` (torch, CPU, float32)
    with the same weights loaded into it: the reference's mixer gives its
    output, and the reference's hand-written adjoint gives torch's
    gradients of the input, of ``A_log`` and of the step's bias."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import MambaConfig
    from transformers.models.mamba.modeling_mamba import MambaMixer
    import jax
    import jax.numpy as jnp
    sizes, module = phi
    d, inner = sizes["hidden_size"], 2 * sizes["hidden_size"]
    state, rank = sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    rs = np.random.RandomState(4)
    p = {"mixer_in_weight": 0.3 * rs.randn(2 * inner, d),
         "mixer_conv_weight": 0.3 * rs.randn(inner, 4),
         "mixer_conv_bias": 0.1 * rs.randn(inner),
         "mixer_x_weight": 0.3 * rs.randn(rank + 2 * state, inner),
         "mixer_dt_weight": 0.5 * rs.randn(inner, rank),
         "mixer_dt_bias": rs.randn(inner) - 2.0,
         "mixer_A_log": np.log(np.arange(1, state + 1.0)) + 0.1 * rs.randn(
             inner, state),
         "mixer_D": 1.0 + 0.1 * rs.randn(inner),
         "mixer_out_weight": 0.3 * rs.randn(d, inner)}
    p = {n: v.astype(np.float32) for n, v in p.items()}
    x = rs.randn(2, 40, d).astype(np.float32)
    g = rs.randn(2, 40, d).astype(np.float32)

    mixer = MambaMixer(MambaConfig(
        hidden_size=d, state_size=state, conv_kernel=4, expand=2,
        time_step_rank=rank, use_bias=False, use_conv_bias=True,
        hidden_act="silu"), 0)
    load = {"in_proj.weight": p["mixer_in_weight"],
            "conv1d.weight": p["mixer_conv_weight"][:, None, :],
            "conv1d.bias": p["mixer_conv_bias"],
            "x_proj.weight": p["mixer_x_weight"],
            "dt_proj.weight": p["mixer_dt_weight"],
            "dt_proj.bias": p["mixer_dt_bias"],
            "A_log": p["mixer_A_log"], "D": p["mixer_D"],
            "out_proj.weight": p["mixer_out_weight"]}
    mixer.load_state_dict({n: torch.tensor(v) for n, v in load.items()})
    xt = torch.tensor(x, requires_grad=True)
    theirs = mixer.slow_forward(xt)
    theirs.backward(torch.tensor(g))

    ours, vjp = jax.vjp(
        lambda q, u: module._mamba(sizes, q, u)[0],
        {n: jnp.asarray(v) for n, v in p.items()}, jnp.asarray(x))
    assert _err(ours, theirs.detach().numpy()) < 1e-5
    gp, gx = vjp(jnp.asarray(g))
    assert _err(gx, xt.grad.numpy()) < 1e-4
    assert _err(gp["mixer_A_log"], mixer.A_log.grad.numpy()) < 1e-4
    assert _err(gp["mixer_dt_bias"], mixer.dt_proj.bias.grad.numpy()) < 1e-4
    assert _err(gp["mixer_conv_weight"],
                mixer.conv1d.weight.grad.numpy()[:, 0]) < 1e-4


def test_reference_adjoint_against_the_gradient_of_a_scan(phi):
    """``_recurrence`` is a ``while_loop`` with a gradient written out step
    by step (the control's evaluator binds a ``while`` as it is): the same
    recurrence as a ``lax.scan`` under ``jax.grad`` gives the same five
    gradients; with the state held in bfloat16 the value moves."""
    import jax
    import jax.numpy as jnp
    _, module = phi
    rs = np.random.RandomState(6)
    x, delta = rs.randn(2, 30, 12), np.log1p(np.exp(rs.randn(2, 30, 12) - 1))
    A = -np.exp(0.5 * rs.randn(12, 4))
    B, C, g = rs.randn(2, 30, 4), rs.randn(2, 30, 4), rs.randn(2, 30, 12)
    ops = tuple(jnp.asarray(t, jnp.float32) for t in (x, delta, A, B, C))

    def scanned(x, delta, A, B, C):
        def step(h, xs):
            xt, dt, bt, ct = xs
            h = (jnp.exp(dt[:, :, None] * A) * h
                 + (dt * xt)[:, :, None] * bt[:, None, :])
            return h, (h * ct[:, None, :]).sum(-1)
        h0 = jnp.zeros((x.shape[0],) + A.shape)
        return jnp.moveaxis(jax.lax.scan(step, h0, tuple(
            jnp.moveaxis(t, 1, 0) for t in (x, delta, B, C)))[1], 0, 1)

    got, vjp = jax.vjp(module._recurrence(), *ops)
    want, w_vjp = jax.vjp(scanned, *ops)
    assert _err(got, want) < 1e-6
    for a, b in zip(vjp(jnp.asarray(g, jnp.float32)),
                    w_vjp(jnp.asarray(g, jnp.float32))):
        assert _err(a, b) < 1e-5
    rounded = module._recurrence(jnp.bfloat16)(*ops)
    assert 1e-4 < _err(rounded, want) < 5e-2


def test_the_state_control_reads_the_forward_check_too(chip_run, phi):
    """``control_state.py`` at the rehearsal's sizes: the reference with the
    scan's state in bfloat16 in the program's place.  It reads the first
    steps' three gaps and, by ``reference_check``'s measure, its logits
    against the reference's: the rounding reaches the state-space layer's
    own gradients and the logits, and nothing else is changed (the first
    loss moves by rounding alone).  At this size no limit is passed; at the
    cell's the forward check's is (PERF.md section 4)."""
    import sys
    _, module = phi
    spec = importlib.util.spec_from_file_location(
        "chip_bench_control_state", ROOT / "benchmark" / "chip"
        / "control_state.py")
    control = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("run", chip_run)     # its ``import run``
    spec.loader.exec_module(control)
    line, = control.main(["--workload", CELL, "--seeds", "11", "--rehearse"])
    assert line["control"] == "bf16_scan_state" and line["seed"] == 11
    assert set(line["gaps"]) == set(line["limits"]) == set(line["at"]) == {
        "steps_loss", "first_grad_norm", "param_change_norm", "block_f32"}
    assert line["limits"]["block_f32"] == module.TOLERANCE["block_f32"]
    assert line["limits"]["first_grad_norm"] == module.TOLERANCE[
        "first_grad_norm_rehearsal"]
    assert 0.0 < line["gaps"]["block_f32"] < line["limits"]["block_f32"]
    assert 0.0 < line["gaps"]["first_grad_norm"]
    assert line["at"]["first_grad_norm"].startswith("layers_1_mixer_")
    assert line["gaps"]["steps_loss"] < 1e-5
    assert line["correct"] is True


# ---------------------------------------------------------------------------
# the departures a tolerance would hide
# ---------------------------------------------------------------------------

def _rotary(t):
    """Rotate-half positions on the last axis of t (b, S, ..., D), theta
    1e4."""
    import jax.numpy as jnp
    seq, dim = t.shape[1], t.shape[-1]
    freq = 1e4 ** (-2.0 * jnp.arange(dim // 2) / dim)
    angle = jnp.arange(seq)[:, None] * freq
    angle = angle.reshape((1, seq) + (1,) * (t.ndim - 3) + (dim // 2,))
    cos, sin = (jnp.concatenate([f(angle)] * 2, -1)
                for f in (jnp.cos, jnp.sin))
    t1, t2 = t[..., :dim // 2], t[..., dim // 2:]
    return t * cos + jnp.concatenate([-t2, t1], -1) * sin


# A departure of the mathematics, made around the reference and not in it:
# (module, sizes, params) -> (functions to put in the module's place, sizes,
# params).  The cut's layers: 0 window, 1 Mamba, 2 full, 3 GMU, 4 cross.

def _with_memory(change):
    """The Mamba layer handing on ``change(memory, D x, silu(z))``."""
    def departure(module, sizes, params):
        whole = module._mamba

        def mamba(cfg, p, u):
            out, memory = whole(cfg, p, u)
            inner = module._inner(cfg)
            xz = u @ p["mixer_in_weight"].T
            x = module._silu(module._causal_conv(
                xz[..., :inner], p["mixer_conv_weight"],
                p["mixer_conv_bias"]))
            return out, change(memory, p["mixer_D"] * x,
                               module._silu(xz[..., inner:]))
        return {"_mamba": mamba}, sizes, params
    return departure


def _with_params(change):
    """``change(name, value, sizes, module)`` on every parameter."""
    def departure(module, sizes, params):
        return {}, sizes, {n: change(n, v, sizes, module)
                           for n, v in params.items()}
    return departure


def _with_sizes(**changed):
    return lambda module, sizes, params: ({}, dict(sizes, **changed), params)


def _taps_reversed(name, value, sizes, module):
    return value[:, ::-1] if name.endswith("conv_weight") else value


def _first_key_twice(name, value, sizes, module):
    """The second key of every pair made the first: q2 meets k1."""
    import jax.numpy as jnp
    if "mixer_qkv_" not in name:
        return value
    d, kv = sizes["hidden_size"], sizes["num_key_value_heads"]
    wide = kv * (d // sizes["num_attention_heads"])
    k = value[d:d + wide].reshape((kv // 2, 2, -1) + value.shape[1:])
    k = jnp.stack([k[:, 0], k[:, 0]], 1).reshape((wide,) + value.shape[1:])
    return jnp.concatenate([value[:d], k, value[d + wide:]])


def _factor_taken_back(name, value, sizes, module):
    if not name.endswith("mixer_subln_gamma"):
        return value
    depth = sizes["published_layer_index"][int(name.split("_")[1])]
    return value / (1.0 - module._lambda_init(depth))


def _lambda_without_init(module, sizes, params):
    with_init = module._lambda
    return {"_lambda": lambda p, depth: with_init(p, depth)
            - module._lambda_init(depth)}, sizes, params


def _cross_projects_its_own(module, sizes, params):
    whole = module._attention
    maker = {n[len("layers_2_"):]: v for n, v in params.items()
             if n.startswith("layers_2_")}

    def attention(cfg, p, u, kind, depth, handed=None):
        if kind == module.CROSS:
            handed = module._project_kv(cfg, maker, u)
        return whole(cfg, p, u, kind, depth, handed)
    return {"_attention": attention}, sizes, params


def _rotary_added(module, sizes, params):
    q_of, kv_of = module._project_q, module._project_kv

    def kv(cfg, p, u):
        k, v = kv_of(cfg, p, u)
        return _rotary(k), v
    return {"_project_q": lambda *a: _rotary(q_of(*a)),
            "_project_kv": kv}, sizes, params


DEPARTURES = {
    "skip_left_out_of_the_memory": _with_memory(
        lambda memory, skip, gate: memory - skip),
    "memory_taken_after_the_gate": _with_memory(
        lambda memory, skip, gate: memory * gate),
    "step_bias_dropped": _with_params(
        lambda name, value, sizes, module:
        0 * value if name == "layers_1_mixer_dt_bias" else value),
    "taps_reversed": _with_params(_taps_reversed),
    "lambda_init_dropped_from_lambda": _lambda_without_init,
    "second_softmax_meets_the_first_key": _with_params(_first_key_twice),
    "factor_after_the_norm_dropped": _with_params(_factor_taken_back),
    "head_norm_dropped": lambda module, sizes, params: (
        {"_head_norm": lambda o, gain, eps: o}, sizes, params),
    "lambda_init_from_the_cuts_index": _with_sizes(
        published_layer_index=[0, 1, 2, 3, 4]),
    "cross_layer_projects_its_own_kv": _cross_projects_its_own,
    "window_one_short": _with_sizes(sliding_window=15),
    "window_one_long": _with_sizes(sliding_window=17),
    "rotary_positions_added": _rotary_added,
}


@pytest.fixture(scope="module")
def seen(phi):
    """The Block's logits on seeded weights at a scale at which every part
    shows, with the weights: shared by the knock-outs below."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    sizes, module = phi
    sizes = dict(sizes, init_std=0.2)
    net = _net(module, sizes)
    rs = np.random.RandomState(9)
    for name, p in net.collect_params().items():
        # gains, biases and the skip away from their neutral values
        if name.endswith(("gamma", "beta", "bias", "mixer_D")):
            shape = p.data().shape
            base = 0.0 if name.endswith(("beta", "bias")) else 1.0
            if name.endswith("dt_bias"):
                base = -1.0
            p.set_data(mx.nd.array(
                (base + 0.2 * rs.randn(*shape)).astype(np.float32)))
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    assert _err(got, _reference(module, sizes, params, tokens)) < 2e-5
    return sizes, got, params, tokens, jnp


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_reference_tolerance_catches_a_departure(phi, seen, monkeypatch,
                                                 departure):
    """Each moves the logits by more than ``block_f32`` allows."""
    _, module = phi
    sizes, got, params, tokens, _ = seen
    patches, changed, params = DEPARTURES[departure](module, sizes, params)
    for name, fn in patches.items():
        monkeypatch.setattr(module, name, fn)
    want = _reference(module, changed, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


@pytest.mark.parametrize("knock_out", [
    "layers_0_mixer_lambda_q1", "layers_1_mixer_conv_bias",
    "layers_1_mixer_D", "layers_2_mixer_qkv_bias",
    "layers_3_mixer_in_weight", "layers_4_mixer_q_bias",
    "layers_4_mixer_subln_gamma", "layers_2_input_layernorm_beta"])
def test_reference_tolerance_catches_a_part_left_out(phi, seen, knock_out):
    _, module = phi
    sizes, got, params, tokens, jnp = seen
    params = dict(params)
    params[knock_out] = jnp.zeros_like(params[knock_out])
    want = _reference(module, sizes, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


def test_the_tables_slice_is_the_models(phi):
    """The uncut reference at the small size, all 2048 rows of the table
    held, on ids drawn from the first 512: the logits of the rows held are
    the same columns of the whole head's (a vocabulary-parallel head: each
    chip's slice of the logits needs its rows alone; the loss's sum over
    the slices is the deployment's exchange, left out)."""
    import jax.numpy as jnp
    sizes, module = phi
    net = _net(module, sizes)
    params = _params(module, net)
    rs = np.random.RandomState(8)
    held = sizes["vocab_rows_held"]
    whole = dict(params, embed_tokens_weight=jnp.concatenate([
        params["embed_tokens_weight"],
        jnp.asarray(0.02 * rs.randn(3 * held, sizes["hidden_size"]),
                    jnp.float32)]))
    tokens = _tokens(sizes)
    cut = _reference(module, sizes, params, tokens)
    uncut = _reference(module, dict(sizes, vocab_rows_held=4 * held), whole,
                       tokens)
    assert uncut.shape == (2, 80, 4 * held)
    assert _err(uncut[..., :held], cut) < 1e-6
    assert _err(_logits(net, tokens), uncut[..., :held]) < 1e-5


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

# the catalog's config for Phi-4-mini-flash-reasoning (model-configs guide,
# architectures.jsonl), every key
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def test_file_keeps_the_published_widths(bench_catalog):
    """Every key of the catalog's config is in the file at its published
    value but the depth, which is the file's ``reduced`` beside the
    vocabulary rows held; what the issue asks the file to state is stated;
    the parameters are ISSUE 38's, counted from the shapes."""
    sizes, module = bench_catalog.config(CONFIG)
    assert {k: sizes[k] for k in PUBLISHED} == dict(PUBLISHED,
                                                    num_hidden_layers=5)
    assert sorted(sizes["reduced"]) == ["num_hidden_layers",
                                        "vocab_rows_held"]
    assert sizes["vocab_rows_held"] == 196 * 128 == 25088
    assert sizes["vocab_rows_held"] * 8 >= sizes["vocab_size"]  # an eighth
    assert sizes["layer_types"] == [
        "sliding_attention", "mamba", "full_attention", "gmu",
        "cross_attention"] == list(module.KINDS)
    assert sizes["published_layer_index"] == [15, 16, 17, 18, 19]
    assert (sizes["mamba_d_state"], sizes["mamba_d_conv"],
            sizes["mamba_expand"], sizes["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert sizes["mamba_dt_rank"] == -(-sizes["hidden_size"] // 16)
    for key in ("mamba_constants", "differential_attention", "no_positions",
                "biases", "hand_over", "optimizer", "schedule",
                "initializer"):
        assert sizes["assumed"][key], key
    for key in ("source", "deployment", "depth_note", "first_loss_with",
                "first_loss_note", "readers_names_note"):
        assert sizes[key], key
    assert "8 chips" in sizes["deployment"]
    assert "cannot be kept" in sizes["reduced"]["num_hidden_layers"]
    assert sizes["input"]["vocab_key"] == "vocab_rows_held"
    assert sizes["head_dim"] == 2560 // 40 == 64
    with pytest.raises(ValueError, match="name each"):
        module._checked(dict(sizes, layer_types=["attention"] * 5))
    with pytest.raises(ValueError, match="head_dim"):
        module._checked(dict(sizes, head_dim=128))
    with pytest.raises(ValueError, match="no earlier layer"):
        module._checked(dict(sizes, layer_types=[
            "gmu", "mamba", "full_attention", "sliding_attention",
            "cross_attention"]))
    # 577,404,032 parameters at the real widths, counted from the shapes
    d, f, inner = 2560, 10240, 5120
    mlp, norms = 3 * d * f, 4 * d
    attention = (d * 5120 + 5120) + (d * d + d) + 4 * 64 + 128
    cross = 2 * (d * d + d) + 4 * 64 + 128
    mamba = (d * 2 * inner + inner * 4 + inner + 192 * inner + 160 * inner
             + inner + inner * 16 + inner + inner * d)
    gmu = 2 * d * inner
    assert (mlp, attention, cross, mamba, gmu) == (
        78643200, 19668864, 13112704, 41241600, 26214400)
    layers = [attention, mamba, attention, gmu, cross]
    assert [m + mlp + norms for m in layers] == [
        98322304, 119895040, 98322304, 104867840, 91766144]
    assert sum(layers) + 5 * (mlp + norms) + 25088 * d + 2 * d == 577404032
    assert module.matmul_params(sizes) == (
        5 * mlp + 41123840 + 2 * (d * 5120 + d * d) + 2 * d * d + gmu
        + 25088 * d)
    assert module.matmul_params(sizes) == pytest.approx(577.1e6, rel=1e-3)
    traffic = bench_catalog.traffic("fused_s4096")
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["driver"], traffic["dtype"]) == (1, 4096, "fused",
                                                     "bfloat16")
    assert module.keys_seen(4096) / 4096 == 2048.5
    assert module.keys_seen(4096, 512) / 4096 == pytest.approx(480.06, abs=.01)
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * module.matmul_params(sizes)
        + 46080 * (2 * 2048.5 + module.keys_seen(4096, 512) / 4096))
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        3.67e9, rel=2e-3)


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s4096", 1)
    (config,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
        "blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "vocab_rows_held"]
    assert [w["name"] for w in spec["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in spec[g]
            if m.get("workloads") == [CELL]}
    # no end-to-end entry of its own: that list is a benchmark PR's to change
    assert not [m for m in spec["end_to_end"] if CELL in m["name"]]
    assert mine == {
        "place_span_ms_p50." + CELL, "dispatch_span_ms_p50." + CELL,
        "ssm_scan_fwd_ms_per_step", "ssm_scan_bwd_ms_per_step",
        "ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
        "ssm_proj_ms_per_step", "gmu_ms_per_step",
        "diff_flash_fwd_ms_per_step", "diff_flash_bwd_ms_per_step",
        "diff_flash_fwd_roofline", "diff_flash_bwd_roofline",
        "diff_combine_ms_per_step"}
    for m in spec["per_layer"]:
        if m["name"] in mine:
            assert m["moves"] == "samples_per_s_per_chip", m["name"]
            if m["name"].endswith("_roofline"):
                assert (m["unit"], m["better"]) == ("%", "higher")
    # no list that was there names the cell
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CELL in m.get("workloads", ()):
                assert m["workloads"] == [CELL], m["name"]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("layer_metrics_" + name)]
    return reader


def test_scan_needs_by_hand(bench_catalog):
    ssm = _reader(bench_catalog, "ssm")
    (f_flops, f_bytes), (b_flops, b_bytes) = ssm.needs(
        batch=1, length=4096, channels=5120, states=16, dtype_bytes=2)
    wide, narrow = 4096 * 5120 * 2, 4096 * 16 * 2
    assert f_bytes == 3 * wide + 2 * narrow         # x, delta, y; B, C
    # g, x, delta read, dx, ddelta written; B, C read, dB, dC written; dA, dD
    assert b_bytes == 5 * wide + 4 * narrow + 4 * 5120 * 17
    assert f_flops == 7 * 4096 * 5120 * 16 and b_flops == 2 * f_flops
    # the bytes bound it: 126 MB at 819 GB/s against 2.3 GFLOP at 197 TFLOP/s
    assert f_bytes / 819e9 == pytest.approx(0.1539e-3, rel=1e-3)
    assert f_flops / 197e12 < 0.1 * f_bytes / 819e9


def test_diff_needs_by_hand(bench_catalog):
    diff = _reader(bench_catalog, "diff_attention")
    assert diff.keys_seen(4096) == 4096 * 4097 // 2
    assert diff.keys_seen(4096, 512) == 512 * 513 // 2 + (4096 - 512) * 512
    assert diff.keys_seen(300, 512) == 300 * 301 // 2
    (f_flops, f_bytes), (b_flops, b_bytes) = diff.needs(
        batch=1, heads=40, kv_heads=20, seq=4096, dim=64, window=512,
        dtype_bytes=2)
    pairs = 40 * diff.keys_seen(4096, 512)
    assert f_flops == pairs * (2 * 64 + 2 * 128)    # ISSUE 38: 384 a pair
    assert b_flops == pairs * 2 * (4 * 64 + 3 * 128)
    rows = 4096 * 2
    q, k, v, o = 40 * 64 * rows, 20 * 64 * rows, 10 * 128 * rows, \
        40 * 128 * rows
    assert f_bytes == q + k + v + o
    assert b_bytes == 2 * (q + k + v) + 2 * o       # + dq, dk, dv; o, dO
    # a full layer at one width is latent_attention.py's count
    latent = _reader(bench_catalog, "latent_attention")
    assert diff.needs(1, 16, 16, 4096, 64, None, 2)[0][0] == latent.needs(
        1, 16, 4096, 64, 128, 2)[0][0]


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


_L = "jit(dp_train_step)/xray:forward/phi4flash_layers_%d/"
_B = "jit(dp_train_step)/xray:backward/transpose(jvp(phi4flash_layers_%d))/"
_OPS = {    # short HLO name -> op_name path, as telemetry.programs() has them
    "selective_scan_pallas.1":
        _L % 1 + "phi4flash_layers_1_mixer/ssm_scan/pallas_call",
    "fusion.2": _L % 1 + "phi4flash_layers_1_mixer/ssm_scan/pad",
    "selective_scan_bwd.3":
        _B % 1 + "transpose(jvp(phi4flash_layers_1_mixer))/"
        "transpose(jvp(ssm_scan))/pallas_call",
    "fusion.4":
        _L % 1 + "phi4flash_layers_1_mixer/ssm_proj/"
        "phi4flash_layers_1_mixer_in_/dot_general",
    "fusion.5":
        _B % 1 + "transpose(jvp(phi4flash_layers_1_mixer))/"
        "transpose(jvp(ssm_proj))/mul",
    "fusion.6": _L % 3 + "phi4flash_layers_3_mixer/gmu/"
        "phi4flash_layers_3_mixer_out_/dot_general",
    "flash_window_pallas.7":
        _L % 0 + "phi4flash_layers_0_mixer/attn_diff/attn_window/"
        "flash_attention_pallas/pallas_call",
    "flash_attention_pallas.8":
        _L % 4 + "phi4flash_layers_4_mixer/attn_diff/attn_cross/"
        "flash_attention_pallas/pallas_call",
    "flash_attention_bwd_dkv.9":
        _B % 2 + "transpose(jvp(phi4flash_layers_2_mixer))/"
        "transpose(jvp(attn_diff))/transpose(jvp(attn_full))/"
        "flash_attention_bwd/pallas_call",
    "flash_window_bwd_dq.10":
        _B % 0 + "transpose(jvp(phi4flash_layers_0_mixer))/"
        "transpose(jvp(attn_diff))/transpose(jvp(attn_window))/"
        "flash_attention_bwd/pallas_call",
    "flash_attention_pallas.11":        # a kernel of some other layer
        "jit(dp_train_step)/xray:forward/other/attn_full/"
        "flash_attention_pallas/pallas_call",
    "fusion.12":
        _L % 2 + "phi4flash_layers_2_mixer/attn_diff_combine/rms_norm",
    "fusion.13": _L % 2 + "phi4flash_layers_2_mlp/dot_general",
}


def _planes(op_events):
    """A trace whose window [200, 400] us holds two steps of the train
    program and the device ops given."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(77)", 200, 96),
                _ev("jit_dp_train_step(77)", 300, 96)]},
            {"name": "XLA Ops", "events": op_events}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            _ev("bench:wait", e - 1, 1) for e in (0, 50, 200, 300, 400)]}]},
    ]


def _run(chip_run, bench_catalog, planes, ops=_OPS, **kw):
    sizes, _ = bench_catalog.config(CONFIG)
    recorded = {"planes": planes, "ops": {}} if planes else None
    programs = {"dp_train_step": types.SimpleNamespace(
        ops=ops, phase=None, error=None)}
    snapshot = {"graft_ssm_scan_chunk": {"samples": [
        {"labels": {}, "value": 128.0}]}}
    telemetry = types.SimpleNamespace(
        programs=lambda: programs, spans=lambda since=None: [],
        registry=lambda: types.SimpleNamespace(snapshot=lambda: snapshot))
    args = dict(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded) if recorded else None,
        stamps=[], traced_stamps=(None, None), rehearse=False, sizes=sizes,
        traffic=bench_catalog.traffic("fused_s4096"),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    args.update(kw)
    return chip_run.Run(**args)


def _step(at):
    """A step: the scan forward 10 + 1 (the kernel and a pad beside it),
    backward 24; the projections 4 + 2; the GMU 3; diff forward kernels
    6 + 5, backward 7 + 4; another layer's kernel 9 (not under
    ``attn_diff``: not counted); the combine 2; an MLP's product 8."""
    durations = [("selective_scan_pallas.1", 10), ("fusion.2", 1),
                 ("selective_scan_bwd.3", 24), ("fusion.4", 4),
                 ("fusion.5", 2), ("fusion.6", 3),
                 ("flash_window_pallas.7", 6),
                 ("flash_attention_pallas.8", 5),
                 ("flash_attention_bwd_dkv.9", 7),
                 ("flash_window_bwd_dq.10", 4),
                 ("flash_attention_pallas.11", 9), ("fusion.12", 2),
                 ("fusion.13", 8)]
    events = []
    for name, dur in durations:
        events.append(_ev("%" + name, at, dur))
        at += dur
    return events


def test_readers_from_a_synthetic_trace(chip_run, bench_catalog):
    ssm = _reader(bench_catalog, "ssm")
    diff = _reader(bench_catalog, "diff_attention")
    run = _run(chip_run, bench_catalog, _planes(_step(200) + _step(300)))
    (f_flops, f_bytes), (b_flops, b_bytes) = ssm.needs(1, 4096, 5120, 16, 2)
    assert ssm.read(run) == pytest.approx({
        "ssm_scan_fwd_ms_per_step": 0.011, "ssm_scan_bwd_ms_per_step": 0.024,
        "ssm_scan_fwd_roofline": 100 * (f_bytes / 819e9) / 11e-6,
        "ssm_scan_bwd_roofline": 100 * (b_bytes / 819e9) / 24e-6,
        "ssm_proj_ms_per_step": 0.006, "gmu_ms_per_step": 0.003}, rel=1e-6)
    facts = run.facts["ssm_scan"]
    assert facts["chunk"] == 128.0 and facts["state_elems"] is None
    assert facts["ssm_scan_fwd"] == {
        "roof": "HBM bytes/s", "calls_per_step": 1,
        "least_ms_per_call": pytest.approx(1e3 * f_bytes / 819e9)}
    least = [0.0, 0.0]
    for window in (512, None, None):
        for way, (flops, _) in enumerate(diff.needs(1, 40, 20, 4096, 64,
                                                    window, 2)):
            least[way] += flops / 197e12
    assert diff.read(run) == pytest.approx({
        "diff_flash_fwd_ms_per_step": 0.011,
        "diff_flash_bwd_ms_per_step": 0.011,
        "diff_flash_fwd_roofline": 100 * least[0] / 11e-6,
        "diff_flash_bwd_roofline": 100 * least[1] / 11e-6,
        "diff_combine_ms_per_step": 0.002}, rel=1e-6)
    facts = run.facts["diff_flash"]
    assert facts["diff_flash_bwd"]["calls_per_step"] == 3
    roofs = facts["roof_and_least_ms_by_kind_fwd_bwd"]
    assert roofs["sliding_attention"][0][0] == "bf16 FLOP/s"


def test_readers_are_silent_where_there_is_nothing(chip_run, bench_catalog):
    """Another configuration, a program without a registry (a parent
    commit), a program whose ops lie under none of the scopes, a run without
    a trace: no metric and no error; a rehearsal names what the registry has
    an op under."""
    ssm = _reader(bench_catalog, "ssm")
    diff = _reader(bench_catalog, "diff_attention")
    for reader in (ssm, diff):
        for other in ("opt_6b7_l2", "lfm2_8b_a1b_ep4_l5",
                      "mellum2_12b_a2b5_ep8_l4", "kimi_vl_a3b_ep8_l5",
                      "resnet50_v1"):
            sizes, _ = bench_catalog.config(other)
            assert reader.read(_run(chip_run, bench_catalog, None,
                                    sizes=sizes)) == {}
        assert reader.read(_run(chip_run, bench_catalog, None,
                                mx=types.SimpleNamespace())) == {}
        assert reader.read(_run(chip_run, bench_catalog, None)) == {}
        planes = _planes([_ev("%flash_attention_pallas.11", 200, 5),
                          _ev("%fusion.13", 300, 9)])
        assert reader.read(_run(chip_run, bench_catalog, planes)) == {}
    named = ssm.read(_run(chip_run, bench_catalog, None, rehearse=True,
                          peaks=None))
    assert set(named) == {
        "ssm_scan_fwd_ms_per_step", "ssm_scan_fwd_roofline",
        "ssm_scan_bwd_ms_per_step", "ssm_scan_bwd_roofline",
        "ssm_proj_ms_per_step", "gmu_ms_per_step"}
    named = diff.read(_run(chip_run, bench_catalog, None, rehearse=True,
                           peaks=None))
    assert set(named) == {
        "diff_flash_fwd_ms_per_step", "diff_flash_fwd_roofline",
        "diff_flash_bwd_ms_per_step", "diff_flash_bwd_roofline",
        "diff_combine_ms_per_step"}
    only_gmu = {k: v for k, v in _OPS.items() if k == "fusion.6"}
    assert set(ssm.read(_run(chip_run, bench_catalog, None, ops=only_gmu,
                             rehearse=True, peaks=None))) == {
        "gmu_ms_per_step"}
    # the accepted readers that index layer_types and head_dim wherever
    # num_key_value_heads or sliding_window is in a file take this one
    run = _run(chip_run, bench_catalog, _planes(_step(200) + _step(300)))
    for name in ("gqa_flash", "mixed_flash", "flash_fwd", "latent_attention",
                 "moe", "scope_times"):
        assert isinstance(_reader(bench_catalog, name).read(run), dict), name


# ---------------------------------------------------------------------------
# the accepted transformer configurations stage the programs they staged
# ---------------------------------------------------------------------------

# sha256 of the jaxpr (object addresses struck out) of value and gradient of
# each accepted configuration's Block at its rehearsal's sizes, on two rows
# of 128 seeded ids, as commit 17215a6 (the parent of the PR that brought the
# state-space layers) traced it.  A PR that changes one of these layers on
# purpose records its digest anew; one that adds a layer or an option beside
# them may not move them.
# PR 39 changed ``Embedding``, a layer of all four, on purpose: its lookup is a
# ``custom_vjp_call`` whose backward is the same scatter-add (one more
# ``custom_vjp_call``, ``jit`` and ``broadcast_in_dim`` in each jaxpr,
# nothing else by a count of the primitives); the digests are that PR's.
BLOCKS_AS_THEY_WERE = {
    "opt_6b7_l2": "66eab7b35c352de8",
    "lfm2_8b_a1b_ep4_l5": "93e5d78bf78895d2",
    "mellum2_12b_a2b5_ep8_l4": "756fceeee33a4b65",
    "kimi_vl_a3b_ep8_l5": "d2afc9b214b23f1d",
}


@pytest.mark.parametrize("config", sorted(BLOCKS_AS_THEY_WERE))
def test_accepted_blocks_trace_to_the_jaxprs_they_did(bench_catalog, config):
    import hashlib
    import re
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = bench_catalog.config(config, rehearse=True)
    sizes = dict(sizes, **(sizes.get("first_loss_with") or {}))
    mx.random.seed(1)
    net = module.build(sizes)
    net.initialize(module.initializer(sizes))
    tokens = np.random.RandomState(0).randint(0, 100, (2, 128)).astype(
        np.int32)
    fn, values = functionalize(net, mx.nd.array(tokens, dtype="int32"),
                               train=True)

    def loss(p, t):
        out = fn(p, t)
        out = out[0] if isinstance(out, (tuple, list)) else out
        return out.astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(
        values, jnp.asarray(tokens)))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        BLOCKS_AS_THEY_WERE[config]
