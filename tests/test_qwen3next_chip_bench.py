"""The configuration ``qwen3_next_80b_a3b_ep32_l4`` (``configs/qwen3_next.py``),
what its cell brought (``gluon.nn.GatedDeltaNet``, ``MultiHeadAttention``'s
``rotary_dim``, ``ExpertParallelMoE``'s ``shared_gate``) and the reader
(``layer_metrics/delta_rule.py``), on the CPU: each layer and the whole Block
against the module's plain reference at the rehearsal's size, value, loss and
gradients; the shares of the experts added up against the uncut layer; the
reference's hand-written adjoint against ``jax.grad`` of a ``lax.scan``; the
reference's three kinds of layer against ``transformers``' published modules
under torch on the same weights; the file's published widths, its parameter
count and FLOPs by hand; the reader's needs and what it names where.  The
cell's rehearsals are ``tests/chip_bench/test_chip_bench_rehearsal.py``'s,
which find it in ``BENCHMARK.json``; the op itself is
``tests/test_delta_rule.py``'s.
"""
import importlib.util
import json
import pathlib
import sys
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
CELL, CONFIG = "qwen3next_gdn_fused_1row", "qwen3_next_80b_a3b_ep32_l4"
PREFIX = "qwen3next_"


@pytest.fixture(scope="module")
def qwen(bench_catalog):
    """(the rehearsal's sizes, module): d 64, 2 key and 4 value heads of 8,
    4 query over 2 K/V heads of 16 with 4 rotary channels, experts of 32,
    top-4 of 32 with experts 0-3 held, three DeltaNet layers and a full
    one."""
    return bench_catalog.config(CONFIG, rehearse=True)


@pytest.fixture(autouse=True)
def full_precision():
    import jax
    with jax.default_matmul_precision("highest"):
        yield


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

def test_gated_delta_net_agrees_with_the_reference(qwen):
    """Value and every parameter's gradient, over a length that is no whole
    number of chunks."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    sizes, module = qwen
    d = sizes["hidden_size"]
    layer = nn.GatedDeltaNet(d, 2, 4, 8, 8, conv=4, epsilon=1e-6,
                             prefix="linear_attn_")
    rs = np.random.RandomState(1)
    x = rs.randn(2, 150, d).astype(np.float32)
    fn, values = _seeded(layer, [mx.nd.array(x)])
    assert {n: tuple(v.shape) for n, v in values.items()} == {
        "linear_attn_in_qkvz_weight": (2 * 16 + 2 * 32, d),
        "linear_attn_in_ba_weight": (8, d),
        "linear_attn_conv_weight": (64, 4), "linear_attn_A_log": (4,),
        "linear_attn_dt_bias": (4,), "linear_attn_norm_gamma": (8,),
        "linear_attn_out_weight": (d, 32)}
    # the layer's own draws, where the seeded Normal(0.3) does not reach
    assert np.all(np.asarray(values["linear_attn_dt_bias"]) == 1.0)
    a_log = np.asarray(values["linear_attn_A_log"])
    assert np.all(a_log >= np.log(1e-4)) and np.all(a_log <= np.log(16.0))
    assert np.abs(np.asarray(values["linear_attn_conv_weight"])).max() <= 0.5
    values["linear_attn_norm_gamma"] = jnp.asarray(
        1.0 + 0.1 * rs.randn(8).astype(np.float32))
    g = jnp.asarray(rs.randn(2, 150, d).astype(np.float32))
    out, vjp = jax.vjp(lambda p: fn(p, jnp.asarray(x)), values)
    want, w_vjp = jax.vjp(
        lambda p: module._delta_net(sizes, p, jnp.asarray(x)), dict(values))
    assert out.shape == (2, 150, d)
    assert _err(out, want) < 1e-5
    _agree(vjp(g)[0], w_vjp(g)[0], values)
    with pytest.raises(ValueError, match="multiple"):
        nn.GatedDeltaNet(d, 3, 4, 8, 8)


@pytest.mark.parametrize("rotary_dim", [16, 64, None])
def test_partial_rotary_is_rotate_half_on_the_first_channels(rotary_dim):
    """``_contrib_RotaryEmbedding(dim=16)`` on heads of 64: channel i < 8
    pairs with i + 8 and turns by t * base^(-2i/16), channels 16-63 pass;
    ``dim`` 64 and None are the whole head; None stages the program the op
    staged before the argument."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import get_op
    rope = get_op("_contrib_RotaryEmbedding").fcompute
    rs = np.random.RandomState(2)
    x = rs.randn(2, 3, 40, 64).astype(np.float32)
    base, dim = 1e4, rotary_dim or 64
    got = np.asarray(rope(jnp.asarray(x), base=base, dim=rotary_dim))
    t = np.arange(40, dtype=np.float64)[:, None]
    ang = t * base ** (-2.0 * np.arange(dim // 2) / dim)
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., dim:]], axis=-1)
    assert np.abs(got - want).max() < 1e-5
    if rotary_dim is None:
        before, after = (str(jax.make_jaxpr(lambda a, kw=kw: rope(
            a, base=base, **kw))(jnp.asarray(x))) for kw in ({}, {"dim": None}))
        assert before == after
    else:
        assert np.array_equal(got[..., dim:], x[..., dim:])
    with pytest.raises(ValueError, match="counts channels"):
        rope(jnp.asarray(x), dim=128)


def test_attention_layer_agrees_with_the_reference(qwen):
    """``MultiHeadAttention(gate, qk_norm, head_dim, rotary_dim)`` as the
    configuration builds it against ``_attention``: value and gradients;
    without ``rotary_dim`` the layer is the one it was."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    sizes, module = qwen
    d = sizes["hidden_size"]
    rs = np.random.RandomState(3)
    x = rs.randn(2, 48, d).astype(np.float32)

    def layer(**kw):
        return nn.MultiHeadAttention(
            d, sizes["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=sizes["num_key_value_heads"],
            head_dim=sizes["head_dim"], qk_norm=True, qk_norm_epsilon=1e-6,
            gate=True, rotary_base=float(sizes["rope_theta"]),
            prefix="self_attn_", **kw)

    fn, values = _seeded(layer(rotary_dim=module._rotary_dim(sizes)),
                         [mx.nd.array(x)])
    assert module._rotary_dim(sizes) == 4 and sizes["head_dim"] == 16
    for name in ("q", "k"):
        values["self_attn_%s_norm_gamma" % name] = jnp.asarray(
            1.0 + 0.1 * rs.randn(16).astype(np.float32))
    g = jnp.asarray(rs.randn(2, 48, d).astype(np.float32))
    out, vjp = jax.vjp(lambda p: fn(p, jnp.asarray(x)), values)
    want, w_vjp = jax.vjp(
        lambda p: module._attention(sizes, p, jnp.asarray(x)), dict(values))
    assert _err(out, want) < 1e-5
    _agree(vjp(g)[0], w_vjp(g)[0], values)
    whole, _ = _seeded(layer(), [mx.nd.array(x)])
    assert _err(whole(values, jnp.asarray(x)), want) > 1e-2
    with pytest.raises(ValueError, match="needs rotary_base"):
        nn.MultiHeadAttention(d, 4, rotary_dim=4)


def _moe(sizes, held, **kw):
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    return ExpertParallelMoE(
        sizes["moe_intermediate_size"], sizes["num_experts_published"],
        top_k=sizes["num_experts_per_tok"], dispatch="grouped",
        experts_held=held, router="softmax", norm_topk=True, gated=True,
        in_units=sizes["hidden_size"],
        shared_hidden_size=sizes["shared_expert_intermediate_size"],
        ep_axis=None, prefix="mlp_", **kw)


def test_shared_gate_scales_the_shared_expert(qwen):
    """``shared_gate=True``: sigmoid(w_s . x) on the shared expert's output,
    value and gradients against ``_routed``; ``False`` has no such
    parameter."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    sizes, module = qwen
    d = sizes["hidden_size"]
    rs = np.random.RandomState(4)
    x = rs.randn(96, d).astype(np.float32)
    fn, values = _seeded(_moe(sizes, (0, 4), shared_gate=True),
                         [mx.nd.array(x)])
    assert values["mlp_shared_gate_weight"].shape == (d,)
    g = jnp.asarray(rs.randn(96, d).astype(np.float32))
    out, vjp = jax.vjp(lambda p: fn(p, jnp.asarray(x)), values)
    want, w_vjp = jax.vjp(
        lambda p: module._routed(sizes, p, jnp.asarray(x)), dict(values))
    assert _err(out, want) < 1e-5
    _agree(vjp(g)[0], w_vjp(g)[0], values, limit=2e-4)
    _, plain = _seeded(_moe(sizes, (0, 4)), [mx.nd.array(x)])
    assert "mlp_shared_gate_weight" not in plain
    assert set(values) - set(plain) == {"mlp_shared_gate_weight"}
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    with pytest.raises(ValueError, match="needs shared_hidden_size"):
        ExpertParallelMoE(32, 8, shared_gate=True, in_units=d)


def test_the_shares_add_up_to_the_uncut_layer(qwen):
    """32 experts in 4 shares of 8: what the program's four shares give
    (each a layer of its own holding experts 8 i .. 8 i + 7 of the same
    router), with the shared expert under its gate, which every chip
    computes alike, counted once, is what the uncut reference gives for the
    whole layer."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    sizes, module = qwen
    d, experts = sizes["hidden_size"], sizes["num_experts_published"]
    assert experts == 32
    rs = np.random.RandomState(5)
    x = rs.randn(64, d).astype(np.float32)
    hidden = sizes["moe_intermediate_size"]
    whole = {"mlp_gate_weight": rs.randn(d, experts),
             "mlp_expert_w1": 0.3 * rs.randn(experts, d, hidden),
             "mlp_expert_w3": 0.3 * rs.randn(experts, d, hidden),
             "mlp_expert_w2": 0.3 * rs.randn(experts, hidden, d),
             "mlp_shared_gate_weight": 0.3 * rs.randn(d)}
    for n in (1, 3):
        whole["mlp_shared_experts_w%d_weight" % n] = 0.3 * rs.randn(hidden, d)
    whole["mlp_shared_experts_w2_weight"] = 0.3 * rs.randn(d, hidden)
    whole = {n: jnp.asarray(v, jnp.float32) for n, v in whole.items()}
    uncut = module._routed(dict(sizes, num_experts=experts), whole,
                           jnp.asarray(x))
    shared = module._shared(whole, jnp.asarray(x))
    total, ours = 0.0, 0.0
    for first in range(0, experts, 8):
        share = dict(whole, **{n: whole[n][first:first + 8] for n in (
            "mlp_expert_w1", "mlp_expert_w3", "mlp_expert_w2")})
        cut = dict(sizes, num_experts=8, first_expert=first)
        total = total + module._routed(cut, share, jnp.asarray(x)) - shared
        layer = _moe(sizes, (first, 8), shared_gate=True)
        fn, values = _seeded(layer, [mx.nd.array(x)])
        assert {n: v.shape for n, v in values.items()} == {
            n: v.shape for n, v in share.items()}
        ours = ours + fn(share, jnp.asarray(x)) - shared
    assert _err(total + shared, uncut) < 1e-5
    assert _err(ours + shared, uncut) < 1e-5
    assert _err(shared, uncut) > 1e-2           # the shared expert shows


# ---------------------------------------------------------------------------
# the Block against the plain reference
# ---------------------------------------------------------------------------

def test_block_agrees_with_the_reference(qwen):
    import jax
    sizes, module = qwen
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    want = np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        params, tokens))
    assert got.shape == (2, 80, sizes["vocab_rows_held"])
    assert _err(got, want) < 1e-5 < module.TOLERANCE["block_f32"]
    shapes = {n: tuple(v.shape) for n, v in params.items()}
    assert shapes["embed_tokens_weight"] == shapes["lm_head_weight"] == (
        512, 64)
    for i in range(3):
        assert shapes["layers_%d_linear_attn_in_qkvz_weight" % i] == (96, 64)
        assert "layers_%d_self_attn_q_weight" % i not in shapes
    assert shapes["layers_3_self_attn_q_weight"] == (64, 64)
    assert shapes["layers_3_self_attn_gate_weight"] == (64, 64)
    assert shapes["layers_3_self_attn_k_weight"] == (32, 64)
    assert "layers_3_linear_attn_A_log" not in shapes
    for i in range(4):
        assert shapes["layers_%d_mlp_gate_weight" % i] == (64, 32)
        assert shapes["layers_%d_mlp_expert_w1" % i] == (4, 64, 32)
        assert shapes["layers_%d_mlp_shared_gate_weight" % i] == (64,)


def test_loss_and_gradients_agree_with_the_reference(qwen):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by
    parameter."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = qwen
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


@pytest.mark.parametrize("knock_out", [
    "linear_attn_conv_weight", "linear_attn_in_ba_weight",
    "self_attn_gate_weight", "mlp_shared_experts_w2_weight", "mlp_expert_w2"])
def test_reference_tolerance_catches_a_part_left_out(qwen, knock_out):
    """A part zeroed on the reference's side alone reads far over the
    forward check's limit."""
    import jax
    import jax.numpy as jnp
    sizes, module = qwen
    net = _net(module, sizes)
    tokens = _tokens(sizes, rows=1)
    got = _logits(net, tokens)
    params = {n: (jnp.zeros_like(v) if n.endswith(knock_out) else v)
              for n, v in _params(module, net).items()}
    want = jax.jit(lambda p, t: module.reference(sizes, p, t))(params, tokens)
    assert _err(got, want) > 10 * module.TOLERANCE["block_f32"]


# ---------------------------------------------------------------------------
# the reference held to what this PR did not write
# ---------------------------------------------------------------------------

def _scanned(q, k, v, g, beta):
    """The recurrence of ``_recurrence`` as a ``lax.scan`` over tokens."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - (state * kt[..., None]).sum(-2))
        state = state + kt[..., None] * u[..., None, :]
        return state, (state * qt[..., None]).sum(-2)

    zero = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:])
    return jnp.moveaxis(jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))[1], 0, 1)


def test_reference_adjoint_against_the_gradient_of_a_scan(qwen):
    """``_recurrence`` is a ``while_loop`` with a gradient written out token
    by token (the control's evaluator binds a ``while`` as it is): the same
    recurrence as a ``lax.scan`` under ``jax.grad`` gives the same five
    gradients; with the state held in bfloat16 the value moves."""
    import jax
    import jax.numpy as jnp
    _, module = qwen
    rs = np.random.RandomState(6)
    q, k = (rs.randn(2, 30, 3, 5) for _ in range(2))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v, w = rs.randn(2, 30, 3, 7), rs.randn(2, 30, 3, 7)
    g, beta = -np.exp(rs.randn(2, 30, 3) - 1), rs.rand(2, 30, 3)
    ops = tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))
    got, vjp = jax.vjp(module._recurrence(), *ops)
    want, w_vjp = jax.vjp(_scanned, *ops)
    assert _err(got, want) < 1e-6
    for a, b in zip(vjp(jnp.asarray(w, jnp.float32)),
                    w_vjp(jnp.asarray(w, jnp.float32))):
        assert _err(a, b) < 1e-5
    rounded = module._recurrence(jnp.bfloat16)(*ops)
    assert 1e-4 < _err(rounded, want) < 5e-2


def _published(sizes):
    from transformers import Qwen3NextConfig
    return Qwen3NextConfig(
        hidden_size=sizes["hidden_size"], head_dim=sizes["head_dim"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        linear_num_key_heads=sizes["linear_num_key_heads"],
        linear_num_value_heads=sizes["linear_num_value_heads"],
        linear_key_head_dim=sizes["linear_key_head_dim"],
        linear_value_head_dim=sizes["linear_value_head_dim"],
        linear_conv_kernel_dim=sizes["linear_conv_kernel_dim"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        shared_expert_intermediate_size=sizes[
            "shared_expert_intermediate_size"],
        num_experts=sizes["num_experts_published"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        norm_topk_prob=True, rms_norm_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"],
        partial_rotary_factor=sizes["partial_rotary_factor"],
        num_hidden_layers=4, vocab_size=512, attention_dropout=0.0,
        attention_bias=False, hidden_act="silu")


def _interleaved(blocks, key_heads, per):
    """The rows of a projection laid out as blocks (``assumed.column_layout``)
    in the published order: a key head's share of each block in turn.
    ``blocks``: [(rows, heads the block has)]; ``per``: rows a head."""
    rows = []
    for h in range(key_heads):
        for block, heads in blocks:
            share = heads // key_heads
            width = block.shape[0] // heads
            rows.append(block[h * share * width:(h + 1) * share * width])
    return np.concatenate(rows, axis=0)


def test_reference_delta_net_against_the_published_module(qwen):
    """``Qwen3NextGatedDeltaNet`` (torch, CPU, float32; its
    ``torch_chunk_gated_delta_rule``) with the same weights loaded into it,
    the blocks of columns interleaved by key head as it keeps them: the
    reference's mixer gives its output and its gradients of the input, of
    ``A_log``, ``dt_bias`` and the taps."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    import jax
    import jax.numpy as jnp
    sizes, module = qwen
    d = sizes["hidden_size"]
    hk, hv, dk, dv = 2, 4, 8, 8
    key, value = hk * dk, hv * dv
    rs = np.random.RandomState(7)
    p = {"linear_attn_in_qkvz_weight": 0.3 * rs.randn(2 * key + 2 * value, d),
         "linear_attn_in_ba_weight": 0.3 * rs.randn(2 * hv, d),
         "linear_attn_conv_weight": 0.4 * rs.randn(2 * key + value, 4),
         "linear_attn_A_log": np.log(rs.uniform(0.1, 4.0, hv)),
         "linear_attn_dt_bias": 1.0 + 0.1 * rs.randn(hv),
         "linear_attn_norm_gamma": 1.0 + 0.1 * rs.randn(dv),
         "linear_attn_out_weight": 0.3 * rs.randn(d, value)}
    p = {n: v.astype(np.float32) for n, v in p.items()}
    x = rs.randn(2, 100, d).astype(np.float32)
    g = rs.randn(2, 100, d).astype(np.float32)
    mixer = hf.Qwen3NextGatedDeltaNet(_published(sizes), 0)
    w = p["linear_attn_in_qkvz_weight"]
    qkvz = _interleaved([(w[:key], hk), (w[key:2 * key], hk),
                         (w[2 * key:2 * key + value], hv),
                         (w[2 * key + value:], hv)], hk, None)
    w = p["linear_attn_in_ba_weight"]
    ba = _interleaved([(w[:hv], hv), (w[hv:], hv)], hk, None)
    load = {"in_proj_qkvz.weight": qkvz, "in_proj_ba.weight": ba,
            "conv1d.weight": p["linear_attn_conv_weight"][:, None, :],
            "A_log": p["linear_attn_A_log"],
            "dt_bias": p["linear_attn_dt_bias"],
            "norm.weight": p["linear_attn_norm_gamma"],
            "out_proj.weight": p["linear_attn_out_weight"]}
    mixer.load_state_dict({n: torch.tensor(v) for n, v in load.items()})
    xt = torch.tensor(x, requires_grad=True)
    theirs = mixer(xt)
    theirs.backward(torch.tensor(g))
    ours, vjp = jax.vjp(
        lambda q, u: module._delta_net(sizes, q, u),
        {n: jnp.asarray(v) for n, v in p.items()}, jnp.asarray(x))
    assert _err(ours, theirs.detach().numpy()) < 2e-5
    gp, gx = vjp(jnp.asarray(g))
    assert _err(gx, xt.grad.numpy()) < 2e-4
    assert _err(gp["linear_attn_A_log"], mixer.A_log.grad.numpy()) < 2e-4
    assert _err(gp["linear_attn_dt_bias"], mixer.dt_bias.grad.numpy()) < 2e-4
    assert _err(gp["linear_attn_conv_weight"],
                mixer.conv1d.weight.grad.numpy()[:, 0]) < 2e-4


def test_reference_attention_against_the_published_module(qwen):
    """``Qwen3NextAttention`` with its rotary table: q and its gate from one
    projection whose heads alternate [q_h | gate_h]; head norms with the
    gain ``1 + w``."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    import jax.numpy as jnp
    sizes, module = qwen
    d, heads, kv, dim = (sizes["hidden_size"], sizes["num_attention_heads"],
                         sizes["num_key_value_heads"], sizes["head_dim"])
    rs = np.random.RandomState(8)
    p = {"self_attn_q_weight": 0.3 * rs.randn(heads * dim, d),
         "self_attn_gate_weight": 0.3 * rs.randn(heads * dim, d),
         "self_attn_k_weight": 0.3 * rs.randn(kv * dim, d),
         "self_attn_v_weight": 0.3 * rs.randn(kv * dim, d),
         "self_attn_out_weight": 0.3 * rs.randn(d, heads * dim),
         "self_attn_q_norm_gamma": 1.0 + 0.1 * rs.randn(dim),
         "self_attn_k_norm_gamma": 1.0 + 0.1 * rs.randn(dim)}
    p = {n: v.astype(np.float32) for n, v in p.items()}
    x = rs.randn(2, 50, d).astype(np.float32)
    config = _published(sizes)
    config._attn_implementation = "eager"
    layer = hf.Qwen3NextAttention(config, 3)
    both = np.concatenate([
        p["self_attn_q_weight"].reshape(heads, dim, d),
        p["self_attn_gate_weight"].reshape(heads, dim, d)], axis=1)
    load = {"q_proj.weight": both.reshape(2 * heads * dim, d),
            "k_proj.weight": p["self_attn_k_weight"],
            "v_proj.weight": p["self_attn_v_weight"],
            "o_proj.weight": p["self_attn_out_weight"],
            "q_norm.weight": p["self_attn_q_norm_gamma"] - 1.0,
            "k_norm.weight": p["self_attn_k_norm_gamma"] - 1.0}
    layer.load_state_dict({n: torch.tensor(v) for n, v in load.items()})
    xt = torch.tensor(x)
    positions = torch.arange(50)[None].expand(2, -1)
    cos_sin = hf.Qwen3NextRotaryEmbedding(config)(xt, positions)
    assert cos_sin[0].shape[-1] == module._rotary_dim(sizes) == 4
    mask = torch.full((50, 50), float("-inf")).triu(1)[None, None]
    theirs, _ = layer(xt, cos_sin, mask)
    ours = module._attention(sizes, {n: jnp.asarray(v) for n, v in p.items()},
                             jnp.asarray(x))
    assert _err(ours, theirs.detach().numpy()) < 2e-5


def test_reference_routed_layer_against_the_published_module(qwen):
    """``Qwen3NextSparseMoeBlock`` with every expert held: softmax over all
    experts, the top-k over their sum, a shared expert under a scalar
    gate."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    import jax.numpy as jnp
    sizes, module = qwen
    d, experts = sizes["hidden_size"], sizes["num_experts_published"]
    hidden = sizes["moe_intermediate_size"]
    rs = np.random.RandomState(9)
    p = {"mlp_gate_weight": rs.randn(d, experts),
         "mlp_expert_w1": 0.3 * rs.randn(experts, d, hidden),
         "mlp_expert_w3": 0.3 * rs.randn(experts, d, hidden),
         "mlp_expert_w2": 0.3 * rs.randn(experts, hidden, d),
         "mlp_shared_gate_weight": 0.3 * rs.randn(d),
         "mlp_shared_experts_w1_weight": 0.3 * rs.randn(hidden, d),
         "mlp_shared_experts_w3_weight": 0.3 * rs.randn(hidden, d),
         "mlp_shared_experts_w2_weight": 0.3 * rs.randn(d, hidden)}
    p = {n: v.astype(np.float32) for n, v in p.items()}
    x = rs.randn(2, 40, d).astype(np.float32)
    block = hf.Qwen3NextSparseMoeBlock(_published(sizes))
    load = {"gate.weight": p["mlp_gate_weight"].T,
            "shared_expert_gate.weight": p["mlp_shared_gate_weight"][None],
            "shared_expert.gate_proj.weight":
                p["mlp_shared_experts_w1_weight"],
            "shared_expert.up_proj.weight": p["mlp_shared_experts_w3_weight"],
            "shared_expert.down_proj.weight":
                p["mlp_shared_experts_w2_weight"]}
    for e in range(experts):
        load["experts.%d.gate_proj.weight" % e] = p["mlp_expert_w1"][e].T
        load["experts.%d.up_proj.weight" % e] = p["mlp_expert_w3"][e].T
        load["experts.%d.down_proj.weight" % e] = p["mlp_expert_w2"][e].T
    block.load_state_dict({n: torch.tensor(np.ascontiguousarray(v))
                           for n, v in load.items()})
    theirs, _ = block(torch.tensor(x))
    ours = module._routed(dict(sizes, num_experts=experts),
                          {n: jnp.asarray(v) for n, v in p.items()},
                          jnp.asarray(x))
    assert _err(ours, theirs.detach().numpy()) < 2e-5


# ---------------------------------------------------------------------------
# the file: published widths, parameters, FLOPs
# ---------------------------------------------------------------------------

def _catalog_row():
    path = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    for line in path.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "Qwen3-Next-80B-A3B-Instruct":
            return row
    pytest.skip("the catalog has no such row")


def test_file_keeps_the_published_widths(bench_catalog):
    """Every number of the catalog's ``config`` under the same key, but for
    the three ``reduced`` names; what is held stated beside what is
    published."""
    sizes, _ = bench_catalog.config(CONFIG)
    spec = next(c for c in bench_catalog.spec["configs"]
                if c["name"] == CONFIG)
    assert spec["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_rows_held"]
    assert set(sizes["reduced"]) == set(spec["reduced"])
    row = _catalog_row()
    assert spec["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in spec["reduced"]:
            assert sizes[key] != value, key
        else:
            assert sizes[key] == value, key
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_rows_held"]) == (4, 16, 18992)
    assert sizes["num_experts_published"] == row["config"]["num_experts"]
    assert sizes["num_hidden_layers_published"] == 48
    assert sizes["vocab_rows_held"] * 8 == sizes["vocab_size"]
    assert sizes["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    for name in ("norm_gains", "column_layout", "conv_taps", "A_log",
                 "route_norm_epsilon", "mtp_head", "auxiliary_loss",
                 "dropout", "embedding_init_std_reading"):
        assert sizes["assumed"][name], name


def test_parameters_at_the_published_widths_by_shapes_alone(bench_catalog):
    """424,340,544 = three DeltaNet mixers of 33,718,464, a full-attention
    mixer of 27,263,488, four layers' router 1,048,576, shared expert
    3,145,728 with its gate 2048, two norms 4096 and sixteen experts of
    3,145,728, the final norm and the two tables' 18992 rows."""
    sizes, module = bench_catalog.config(CONFIG)
    net = module.build(sizes)
    d = 2048
    # the attention layer's projections learn their input width from the
    # first call: d for q, k, v and the gate, heads x head_dim for the out
    shapes = {n: tuple(w or (4096 if "_out_" in n else d) for w in p.shape)
              for n, p in net.collect_params().items()}
    assert [n for n, p in net.collect_params().items() if 0 in p.shape] == [
        PREFIX + "layers_3_self_attn_%s_weight" % w
        for w in ("q", "k", "v", "out", "gate")]
    count = sum(int(np.prod(s)) for s in shapes.values())
    mixer = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048 + 32 + 32 + 128
    full = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    layer = 2048 * 512 + 3 * d * 512 + d + 2 * d + 16 * 3 * d * 512
    assert (mixer, full) == (33718464, 27263488)
    assert count == 3 * mixer + full + 4 * layer + d + 2 * 18992 * d
    assert count == sizes["parameters"] == 424340544


def test_train_flops_by_hand(bench_catalog):
    sizes, module = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s4096")
    d = 2048
    mixers = 3 * (d * 12288 + d * 64 + 4096 * d) + (
        3 * d * 4096 + 2 * d * 512)
    routed = 4 * (d * 512 + 3 * d * 512 + d + 10 * 16 / 512 * 3 * d * 512)
    matmul = mixers + routed + 18992 * d
    assert module.matmul_params(sizes) == pytest.approx(matmul)
    attention = 3 * (2 * 2 * 16 * 256) * (4096 + 1) / 2
    rule = 3 * 3 * (3 * 2 * 128 * 128 * 32)
    assert module.delta_rule_flops(sizes) == 3 * 2 * 128 * 128 * 32
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * matmul + attention + rule)
    assert 1.2e9 < module.train_flops_per_sample(sizes, traffic) < 1.3e9


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s4096", 1)
    (config,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert config["file"] == "benchmark/chip/configs/%s.json" % CONFIG
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_rows_held"]
    assert [w["name"] for w in spec["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
    # appended after the cell that was last (PR 40's); a later PR's entries
    # come after these, so nothing here says that they are the last
    before = "trinitymini_gated_fused_1row"
    names = [w["name"] for w in spec["workloads"]]
    assert names.index(CELL) == names.index(before) + 1
    configs = [c["name"] for c in spec["configs"]]
    assert configs.index(CONFIG) == configs.index("trinity_mini_ep16_l5") + 1
    mine = [m for m in spec["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["workloads"] == [CELL] for m in mine)
    at = spec["per_layer"].index(mine[0])
    assert spec["per_layer"][at:at + len(mine)] == mine   # together
    assert at > max(i for i, m in enumerate(spec["per_layer"])
                    if before in m.get("workloads", ()))
    # BENCHMARK.json holds at most 128 per-layer metrics and had 123: five
    # entries, the rule's own.  What the readers that are there read in this
    # cell (the flash kernels at 256, the gate, the convolution, the routed
    # layer's scopes and counters, the two spans) has no entry, and no list
    # that was there names the cell: PERF.md, section 7
    assert len(spec["per_layer"]) <= 128
    assert [m["name"] for m in mine] == [
        "gdn_scan_fwd_ms_per_step", "gdn_scan_bwd_ms_per_step",
        "gdn_scan_fwd_roofline", "gdn_scan_bwd_roofline",
        "gdn_proj_ms_per_step"]
    assert not any(CELL in m.get("workloads", ()) for m in spec["end_to_end"])
    layers = {m["name"]: m["layer"] for m in mine}
    assert layers["gdn_scan_fwd_roofline"] == "pallas kernel"
    assert layers["gdn_proj_ms_per_step"] == "sequence blocks"


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def _reader(bench_catalog):
    return bench_catalog._module(
        bench_catalog.dir / "layer_metrics" / "delta_rule.py")


def test_rule_needs_by_hand(bench_catalog):
    """One call at the cell's shape: three products of 128 x 128 a token and
    value head forward, twice that backward; q, k for 16 heads, v and o for
    32, the two gates in float32."""
    fwd, bwd = _reader(bench_catalog).needs(1, 4096, 16, 32, 128, 128, 2)
    products = 3 * 2 * 128 * 128 * 32 * 4096
    qk, vo = 2 * 4096 * 16 * 128 * 2, 4096 * 32 * 128 * 2
    gates = 2 * 4096 * 32 * 4
    assert fwd == (products, qk + 2 * vo + gates)
    assert bwd == (2 * products, 2 * (qk + vo + gates) + vo)
    assert products == 12884901888


def _fake_run(chip_run, sizes, ops, rehearse=True):
    programs = {"dp_train_step": types.SimpleNamespace(ops=ops, phase=None)}
    telemetry = types.SimpleNamespace(
        programs=lambda: programs, spans=lambda since=0.0: [],
        registry=lambda: types.SimpleNamespace(snapshot=dict))
    return chip_run.Run(sizes=sizes, mx=types.SimpleNamespace(
        telemetry=telemetry), rehearse=rehearse, reduced=None, peaks=None,
        traffic={"batch_per_chip": 1, "seq_len": 4096, "dtype": "bfloat16"})


def test_reader_names_what_the_program_staged_and_nothing_elsewhere(
        chip_run, bench_catalog):
    reader = _reader(bench_catalog)
    sizes, _ = bench_catalog.config(CONFIG)
    step = "jit(dp_train_step)/xray:%s/%s/"
    ops = {"fusion.1": step % ("forward", "jvp(l0/gdn_scan)") + "exp",
           "fusion.2": step % ("backward", "transpose(jvp(l0/gdn_scan))")
           + "while/body/dot_general",
           "fusion.3": step % ("forward", "jvp(l0/gdn_proj/in_qkvz)")
           + "dot_general",
           "fusion.4": step % ("forward", "jvp(l0/gdn_norm)") + "mul"}
    named = reader.read(_fake_run(chip_run, sizes, ops))
    assert sorted(named) == sorted([
        "gdn_scan_fwd_ms_per_step", "gdn_scan_fwd_roofline",
        "gdn_scan_bwd_ms_per_step", "gdn_scan_bwd_roofline",
        "gdn_proj_ms_per_step"])
    only_forward = {k: v for k, v in ops.items() if k != "fusion.2"}
    assert "gdn_scan_bwd_roofline" not in reader.read(
        _fake_run(chip_run, sizes, only_forward))
    # a configuration without the layer: no name, whatever is staged
    for other in ("trinity_mini_ep16_l5", "phi4_mini_flash_l5",
                  "resnet50_v1"):
        theirs, _ = bench_catalog.config(other)
        assert reader.read(_fake_run(chip_run, theirs, ops)) == {}
    # a program without the accessors (an older commit): nothing, no raise
    run = _fake_run(chip_run, sizes, ops)
    run.mx = types.SimpleNamespace()
    assert reader.read(run) == {}
    # an untraced chip run reads nothing
    assert reader.read(_fake_run(chip_run, sizes, ops, rehearse=False)) == {}


def test_reader_splits_a_traced_window_by_scope(chip_run, bench_catalog):
    """The traced branch over a made-up window of two steps: the scan's
    time by phase, its share from ``needs``, and the scopes that have no
    entry (``BENCHMARK.json`` was full) as a fact."""
    reader = _reader(bench_catalog)
    sizes, _ = bench_catalog.config(CONFIG)
    step = "jit(dp_train_step)/xray:%s/%s/"
    ops = {"fusion.1": step % ("forward", "jvp(l0/gdn_scan)") + "exp",
           "fusion.2": step % ("backward", "transpose(jvp(l0/gdn_scan))")
           + "while/body/dot_general",
           "fusion.3": step % ("forward", "jvp(l0/gdn_proj/in_qkvz)")
           + "dot_general",
           "fusion.4": step % ("forward", "jvp(l0/gdn_norm)") + "mul",
           "fusion.5": step % ("backward", "transpose(jvp(l3/moe_router))")
           + "sort",
           "fusion.6": step % ("forward", "jvp(l0/dense0)") + "dot_general"}
    run = _fake_run(chip_run, sizes, ops, rehearse=False)
    run.reduced = {"steps": 2, "op_s": {
        "%fusion.1": 0.034, "%fusion.2": 0.062, "%fusion.3~2": 0.030,
        "%fusion.4": 0.004, "%fusion.5": 0.006, "%fusion.6": 0.5}}
    run.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run.facts = {}
    read = reader.read(run)
    assert sorted(read) == sorted([
        "gdn_scan_fwd_ms_per_step", "gdn_scan_fwd_roofline",
        "gdn_scan_bwd_ms_per_step", "gdn_scan_bwd_roofline",
        "gdn_proj_ms_per_step"])
    assert read["gdn_scan_fwd_ms_per_step"] == pytest.approx(17.0)
    assert read["gdn_scan_bwd_ms_per_step"] == pytest.approx(31.0)
    assert read["gdn_proj_ms_per_step"] == pytest.approx(15.0)
    (fwd, fwd_bytes), _ = reader.needs(1, 4096, 16, 32, 128, 128, 2)
    least = max(fwd / 197e12, fwd_bytes / 819e9)
    assert read["gdn_scan_fwd_roofline"] == pytest.approx(
        100 * 3 * least / 0.017)
    assert 0 < read["gdn_scan_bwd_roofline"] < read[
        "gdn_scan_fwd_roofline"] < 100
    facts = run.facts["gdn_scan"]
    assert facts["ms_per_step_by_scope"] == pytest.approx(
        {"gdn_norm": 2.0, "moe_router": 3.0})
    assert facts["gdn_scan_fwd"]["calls_per_step"] == 3


def test_the_state_control_reads_the_forward_check_too(chip_run, qwen):
    """``control_delta_state.py`` at the rehearsal's sizes: the reference
    with the rule's state and decay in bfloat16 in the program's place reads
    the first steps' three gaps and, by ``reference_check``'s measure, its
    logits against the reference's."""
    _, module = qwen
    sys.modules.setdefault("run", chip_run)     # its ``import run``
    bench = str(ROOT / "benchmark" / "chip")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "chip_bench_control_delta_state",
        ROOT / "benchmark" / "chip" / "control_delta_state.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    line, = control.main(["--workload", CELL, "--seeds", "11", "--rehearse"])
    assert line["control"] == "bf16_scan_state" and line["seed"] == 11
    assert set(line["gaps"]) == set(line["limits"]) == set(line["at"]) == {
        "steps_loss", "first_grad_norm", "param_change_norm", "block_f32"}
    assert line["limits"]["block_f32"] == module.TOLERANCE["block_f32"]
    assert 0.0 < line["gaps"]["block_f32"]
    assert 0.0 < line["gaps"]["first_grad_norm"]
    alone, = control.main(["--workload", CELL, "--seeds", "11", "--rehearse",
                           "--forward-only"])
    assert set(alone["gaps"]) == {"block_f32"}
    assert alone["gaps"]["block_f32"] == line["gaps"]["block_f32"]
    with pytest.raises(SystemExit, match="no delta-rule layer"):
        control.main(["--workload", "trinitymini_gated_fused_1row",
                      "--seeds", "1", "--rehearse"])


# ---------------------------------------------------------------------------
# the cell's kernels at its shapes, for a chip that is described, not
# attached (on-chip-measurement guide, section 2: nothing at import)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever libtpu raises here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_flash_kernels_compile_at_the_cells_shape(bench_catalog, one_chip,
                                                  no_compile_cache):
    """Forward and both backward kernels at (1, 16, 4096, 256) in bf16,
    causal: the seventh shape of ``ops/attention.py``, 8 blocks of 512 a
    side at a head two lane tiles wide."""
    import functools
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import get_op
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s4096")
    shape = (traffic["batch_per_chip"], sizes["num_attention_heads"],
             traffic["seq_len"], sizes["head_dim"])
    assert shape == (1, 16, 4096, 256)
    spec = jax.ShapeDtypeStruct(shape, jnp.dtype(traffic["dtype"]),
                                sharding=one_chip)
    attend = functools.partial(get_op("_contrib_FlashAttention").fcompute,
                               causal=True)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(spec, spec, spec).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_pallas", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert kernel in text, kernel


def test_delta_rule_compiles_at_the_cells_shape(bench_catalog, one_chip,
                                                no_compile_cache):
    """Value and gradient of the rule at 16 key and 32 value heads of 128
    over a row of 4096 in bf16: what it keeps between the two passes is the
    operands and the 64 border states, 134 MB; no array a token a state;
    the walk as the two kernels (PR 49)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import delta_rule
    bf16, f32 = jnp.bfloat16, jnp.float32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qk, v, gate = (spec((1, 16, 4096, 128), bf16),
                   spec((1, 32, 4096, 128), bf16), spec((1, 32, 4096), f32))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(
            lambda *t: delta_rule.gated_delta_rule(*t).astype(f32).sum(),
            argnums=(0, 1, 2, 3, 4))).lower(qk, qk, v, gate, gate).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3 << 30
    text = compiled.as_text()
    assert "4096,128,128]" not in text
    # lowered for the TPU the walk over the chunks' states is the two Mosaic
    # kernels, and no ``while`` is left of the scan
    for kernel in ("delta_rule_pallas", "delta_rule_bwd"):
        assert kernel in text, kernel
    assert " while(" not in text
