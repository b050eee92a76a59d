"""The configuration ``lfm2_8b_a1b_ep4_l5`` (``configs/lfm2_moe.py``) and the
readers its cell brought (``layer_metrics/moe.py``, ``scope_times.py``,
``gqa_flash.py``), on the CPU: the model the program builds against the
module's plain reference at a toy size with every kind of layer, forward
and gradients; what the reference's tolerance catches; the file's published
widths; the selection bias through Adam steps; the readers' needs counted
by hand and their metrics read from a synthetic trace.  The cell's
rehearsals are ``test_chip_bench_rehearsal.py``'s, which find it in
``BENCHMARK.json``.
"""
import importlib.util
import json
import pathlib
import re
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the benchmark's own fixtures (``chip_run``: ``benchmark/chip/run.py`` as a
# module; ``bench_catalog``).  This file lies beside the package's tests and
# not in ``tests/chip_bench``, whose files all start in the first wave of a
# parallel run: the dp=4 cell's traced rehearsal there needs six toy steps
# inside an 8 s window and loses them to one more busy worker (PR 26).
_spec = importlib.util.spec_from_file_location(
    "chip_bench_conftest", ROOT / "tests" / "chip_bench" / "conftest.py")
_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conftest)
chip_run, bench_catalog = _conftest.chip_run, _conftest.bench_catalog
CELL, CONFIG = "lfm2moe_fused_s8192", "lfm2_8b_a1b_ep4_l5"
US = 1000


@pytest.fixture(scope="module")
def lfm2(bench_catalog):
    """(toy sizes with every layer kind, module): the rehearsal's widths,
    a conv and an attention layer below and above ``num_dense_layers``."""
    sizes, module = bench_catalog.config(CONFIG, rehearse=True)
    sizes = dict(sizes, num_hidden_layers=4, num_dense_layers=2,
                 layer_types=["conv", "full_attention", "full_attention",
                              "conv"])
    return sizes, module


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


def _tokens(sizes, rows=2, seq=24, seed=0):
    rs = np.random.RandomState(seed)
    vocab = sizes[sizes["input"]["vocab_key"]]
    return rs.randint(0, vocab, (rows, seq)).astype(np.int32)


def test_lfm2_block_agrees_with_the_reference(lfm2):
    import jax
    sizes, module = lfm2
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    want = np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        _params(module, net), tokens))
    assert got.shape == (2, 24, sizes["vocab_rows_held"])
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-5 < module.TOLERANCE["block_f32"]
    # every kind of layer is in it, by the parameters it brought
    names = set(_params(module, net))
    for part in ("layers_0_conv_conv_weight", "layers_0_feed_forward_w3_weight",
                 "layers_1_self_attn_k_norm_gamma",
                 "layers_2_feed_forward_expert_w3",
                 "layers_2_feed_forward_expert_bias",
                 "layers_3_conv_in_weight", "embedding_norm_gamma"):
        assert part in names, part
    assert not any(n.endswith("bias") and "expert_bias" not in n
                   for n in names)                  # no bias anywhere


def test_lfm2_gradients_agree_with_the_reference(lfm2):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by
    parameter; the selection bias gets none on either side."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = lfm2
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
    got = {n[cut:]: np.asarray(g)
           for n, g in jax.grad(block_loss)(values).items()}
    want = jax.grad(lambda p: module.reference_loss(
        module.reference(sizes, p, tokens), labels))(_params(module, net))
    assert set(got) == set(want)
    for name, g in want.items():
        g = np.asarray(g)
        if name.endswith("expert_bias"):
            assert not g.any() and not got[name].any()
            continue
        scale = max(np.abs(g).max(), 1e-8)
        assert np.abs(got[name] - g).max() / scale < 2e-3, name


@pytest.mark.parametrize("knock_out", [
    "layers_0_conv_conv_weight", "layers_1_self_attn_q_norm_gamma",
    "layers_2_feed_forward_expert_w3", "layers_2_feed_forward_gate_weight",
    "layers_3_ffn_norm_gamma", "layers_0_feed_forward_w2_weight"])
def test_lfm2_reference_tolerance_catches(lfm2, knock_out):
    """A part of the model left out of the reference moves the logits by
    more than ``block_f32`` allows."""
    import jax
    import jax.numpy as jnp
    sizes, module = lfm2
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    params[knock_out] = jnp.zeros_like(params[knock_out])
    want = np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        params, tokens))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err > module.TOLERANCE["block_f32"], err


def test_lfm2_reference_follows_the_program_through_a_tie_only(lfm2):
    """Top-k is discontinuous, so the reference verifies the program's
    choice instead of replaying it: where the experts the Block chose on
    its last eager call score within ``ROUTING_TIE`` of the reference's own
    k-th best, they are followed; a choice that is worse, or names an
    expert twice, is not.  Experts 1 (held) and 2 (absent) of layer 2 are
    given one router column and no bias, an exact tie."""
    import weakref
    import jax
    import incubator_mxnet_tpu as mx
    sizes, module = lfm2
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    _logits(net, tokens)                    # resolves the deferred shapes
    routed = net.layers[2].ffn
    gate = routed.gate_weight.data().asnumpy().copy()
    bias = routed.expert_bias.data().asnumpy().copy()
    bias[:] = 0.0           # ranks by the token, not by the bias
    gate[:, 2] = gate[:, 1]
    routed.gate_weight.set_data(mx.nd.array(gate))
    routed.expert_bias.set_data(mx.nd.array(bias))
    got = _logits(net, tokens)
    params = _params(module, net)

    def reference(follow=True):
        # the record of the program's choice is read once a model built
        module._built = weakref.ref(net) if follow else None
        return np.asarray(jax.jit(
            lambda p, t: module.reference(sizes, p, t))(params, tokens))

    def err(a, b):
        return np.abs(a - b).max() / np.abs(b).max()

    followed = reference()
    assert err(got, followed) < 1e-5
    assert module._built is None            # read once
    alone = reference(follow=False)
    assert err(followed, alone) < 1e-5      # both sides broke the tie alike

    chose = np.asarray(routed.last_chosen)
    k = chose.shape[1]
    assert chose.shape == (tokens.size, sizes["num_experts_per_tok"])
    straddle = (chose == 1).any(1) & ~(chose == 2).any(1)
    assert straddle.sum() >= 3              # 1 is in, its twin 2 is out
    # the program breaks the tie the other way: as good a top-k
    other = np.where((chose == 1) & straddle[:, None], 2, chose)
    routed.last_chosen = jax.numpy.asarray(other)
    swapped = reference()
    rows = np.abs(swapped - alone).reshape(tokens.size, -1).max(1)
    assert (rows[straddle] > module.TOLERANCE["block_f32"]
            * np.abs(alone).max()).all()
    # ... and a program that chooses a worse expert, or one twice, is not
    # followed: the reference's own choice stands, and the error would show
    scores = 1 / (1 + np.exp(-np.asarray(
        _router_input(module, sizes, params, tokens, 2)) @ gate)) + bias
    worst = scores.argmin(1)
    assert not (chose == worst[:, None]).any()
    for wrong in (np.concatenate([chose[:, :k - 1], worst[:, None]], 1),
                  np.concatenate([chose[:, :k - 1], chose[:, :1]], 1)):
        routed.last_chosen = jax.numpy.asarray(wrong.astype(chose.dtype))
        np.testing.assert_array_equal(reference(), alone)
    # and a record of another number of tokens is not read at all
    routed.last_chosen = jax.numpy.asarray(other[:-1])
    np.testing.assert_array_equal(reference(), alone)


def _router_input(module, sizes, params, tokens, layer):
    """What routed layer ``layer``'s router is given, by the reference's
    own functions: (tokens, d)."""
    eps = sizes["norm_eps"]
    h = params["embed_tokens_weight"][tokens]
    for i, kind in enumerate(sizes["layer_types"]):
        p = {n[len("layers_%d_" % i):]: v for n, v in params.items()
             if n.startswith("layers_%d_" % i)}
        a = module._rms_norm(h, p["operator_norm_gamma"], eps)
        h = h + (module._short_conv(sizes, p, a) if kind == "conv"
                 else module._attention(sizes, p, a))
        f = module._rms_norm(h, p["ffn_norm_gamma"], eps)
        if i == layer:
            return f.reshape(-1, f.shape[-1])
        h = h + (module._gated_mlp(f, *(p["feed_forward_w%d_weight" % n]
                                        for n in (1, 3, 2)))
                 if i < sizes["num_dense_layers"]
                 else module._routed(sizes, p, f))


def test_lfm2_rope_is_in_the_reference_and_the_block(lfm2):
    """Rotary positions are no parameter, so no knock-out reaches them: a
    reference with another base disagrees with the Block."""
    import jax
    sizes, module = lfm2
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    other = dict(sizes, rope_theta=100.0)
    want = np.asarray(jax.jit(lambda p, t: module.reference(other, p, t))(
        _params(module, net), tokens))
    assert np.abs(got - want).max() / np.abs(want).max() > \
        module.TOLERANCE["block_f32"]


def test_lfm2_file_keeps_the_published_widths(bench_catalog):
    """Every number of the catalog's config for LFM2-8B-A1B that is not cut
    is in the file under its own key; the cut ones are the file's
    ``reduced``, and what the issue asks the file to state is stated."""
    sizes, module = bench_catalog.config(CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert {k: sizes[k] for k in published} == published
    assert sorted(sizes["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_rows_held"])
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"],
            sizes["num_experts"], sizes["num_experts_published"],
            sizes["vocab_rows_held"]) == (5, 1, 8, 32, 16384)
    assert sizes["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                    "conv"]
    for key in ("source", "assumed", "deployment", "depth_note",
                "expert_load_note", "first_loss_with", "first_loss_note"):
        assert sizes[key], key
    assert sizes["input"]["vocab_key"] == "vocab_rows_held"
    # 507.8 M parameters at the real widths, counted from the shapes
    d, h, f = 2048, 1792, 7168
    conv = 3 * d * d + 3 * d + d * d + 2 * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 64 + 2 * d
    routed = d * 32 + 32 + 8 * 3 * d * h
    total = (16384 * d + conv + 3 * d * f + attn + routed
             + 3 * (conv + routed) + d)
    assert total == 507820288
    traffic = bench_catalog.traffic("fused_s8192")
    assert module.matmul_params(sizes) == pytest.approx(
        16384 * d + 4 * 4 * d * d + 2 * d * d + 2 * d * 512 + 3 * d * f
        + 4 * (d * 32 + 4 * 0.25 * 3 * d * h))
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * module.matmul_params(sizes) + 6 * 8192 * d)


def test_lfm2_selection_bias_survives_adam_bit_for_bit(lfm2):
    """``build`` hands the selection bias to the optimizer (the harness's
    first steps read every parameter's gradient from its state): its
    gradient is exactly zero, and three Adam steps of the fused trainer
    leave it where it was, bit for bit, with zero moments."""
    import jax
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    sizes, module = lfm2
    net = _net(module, sizes)
    opt = dict(sizes["optimizer"], learning_rate=1e-2)
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer=opt.pop("name"), optimizer_params=opt,
        mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    import jax.numpy as jnp
    x = jnp.asarray(_tokens(sizes))
    y = jnp.asarray(_tokens(sizes, seed=1).astype(np.float32))
    trainer.compile(x, y)
    names = [n for n in trainer._params if n.endswith("expert_bias")]
    assert len(names) == 2
    before = {n: np.asarray(trainer._params[n]).copy() for n in names}
    other = "lfm2_layers_2_feed_forward_gate_weight"
    moved = np.asarray(trainer._params[other]).copy()
    losses = [float(np.asarray(trainer.step(x, y))) for _ in range(3)]
    assert losses[-1] < losses[0]
    for n in names:
        assert before[n].any()
        assert np.array_equal(np.asarray(trainer._params[n]), before[n])
        mean, var, _ = trainer._opt_state[n]
        assert not np.asarray(mean).any() and not np.asarray(var).any()
    assert not np.array_equal(np.asarray(trainer._params[other]), moved)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("layer_metrics_" + name)]
    return reader


def test_expert_needs_by_hand(bench_catalog):
    moe = _reader(bench_catalog, "moe")
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s8192")
    assert moe.routed_layers(sizes) == 4
    # 8192 tokens x top-4 x 8 of 32 experts held
    rows = moe.balanced_assignments(sizes, traffic)
    assert rows == 8192 * 4 * 8 / 32 == 8192
    flops, nbytes = moe.needs(rows, 2048, 1792, 8, 2)
    # three products d x h a row, forward once and backward twice
    assert flops == 3 * 3 * 2 * 8192 * 2048 * 1792
    # a pass: the 8 experts' three matrices, the rows in and as many out
    assert nbytes == 3 * (3 * 8 * 2048 * 1792 + 2 * 8192 * 2048) * 2
    assert flops / 197e12 == pytest.approx(2.74703e-3, rel=1e-4)
    assert nbytes / 819e9 == pytest.approx(0.8909e-3, rel=1e-3)


def test_gqa_flash_needs_by_hand(bench_catalog):
    gqa = _reader(bench_catalog, "gqa_flash")
    (f_flops, f_bytes), (b_flops, b_bytes) = gqa.needs(
        batch=1, heads=32, kv_heads=8, seq=8192, head_dim=64, dtype_bytes=2)
    square = 32 * 8192 * 8192 * 64
    assert f_flops == 2 * square and b_flops == 7 * square
    q_like, kv_like = 32 * 8192 * 64 * 2, 8 * 8192 * 64 * 2
    assert f_bytes == 2 * q_like + 2 * kv_like      # q, o; k, v
    assert b_bytes == 4 * q_like + 4 * kv_like      # q, o, dO, dq; k, v, dk, dv
    assert f_flops / 197e12 == pytest.approx(1.3953e-3, rel=1e-4)
    # with as many K/V heads as query heads it is flash_fwd.py's count
    fwd = _reader(bench_catalog, "flash_fwd")
    assert gqa.needs(4, 32, 32, 2048, 128, 2)[0] == fwd.needs(
        4, 32, 2048, 128, 2)


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


class _Program:
    def __init__(self, ops):
        self.phase, self.ops, self.error = None, ops, None


def _path(phase, scopes, op):
    inner = "lfm2_layers/lfm2_layers_1/" + scopes
    wrapped = ("jvp(%s)" if phase == "forward" else "transpose(jvp(%s))")
    return "jit(dp_train_step)/xray:%s/%s/%s" % (phase, wrapped % inner, op)


def test_scope_times_and_rooflines_from_a_synthetic_trace(chip_run,
                                                          bench_catalog):
    """The window [200, 400] us holds 2 steps.  A step: router 4, dispatch
    6 and combine 2 (one metric), experts 20 forward and 30 backward, short
    conv 3, the forward kernel 10, the two backward kernels 12 and 8."""
    def step(at):
        return [_ev("%fusion.1", at, 4), _ev("%gather.2", at + 4, 6),
                _ev("%fusion.3", at + 10, 2),
                _ev("%ragged-dot-none.4", at + 12, 20),
                _ev("%ragged-dot-none.5", at + 32, 30),
                _ev("%fusion.6", at + 62, 3),
                _ev("%flash_attention_pallas.7", at + 65, 10),
                _ev("%flash_attention_bwd_dkv.8", at + 75, 12),
                _ev("%flash_attention_bwd_dq.9", at + 87, 8)]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(77)", 200, 96),
                _ev("jit_dp_train_step(77)", 300, 96)]},
            {"name": "XLA Ops", "events": step(200) + step(300)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            _ev("bench:wait", e - 1, 1) for e in (0, 50, 200, 300, 400)]}]},
    ]
    ffn = "lfm2_layers_1_feed_forward/"
    registry = {"dp_train_step": _Program({
        "fusion.1": _path("forward", ffn + "moe_router", "dot_general"),
        "gather.2": _path("forward", ffn + "moe_dispatch", "gather"),
        "fusion.3": _path("backward", ffn + "moe_combine", "mul"),
        "ragged-dot-none.4": _path("forward", ffn + "moe_experts",
                                   "ragged_dot_general"),
        "ragged-dot-none.5": _path("backward", ffn + "moe_experts",
                                   "ragged_dot_general"),
        "fusion.6": _path("forward", "lfm2_layers_1_conv/short_conv", "mul"),
        "flash_attention_pallas.7": _path(
            "forward", "lfm2_layers_1_self_attn/flash_attention_pallas",
            "pallas_call"),
        "flash_attention_bwd_dkv.8": _path(
            "backward", "lfm2_layers_1_self_attn/flash_attention_bwd",
            "pallas_call"),
        "flash_attention_bwd_dq.9": _path(
            "backward", "lfm2_layers_1_self_attn/flash_attention_bwd",
            "pallas_call")})}
    sizes, _ = bench_catalog.config(CONFIG)
    recorded = {"planes": planes, "ops": {}}
    telemetry = types.SimpleNamespace(
        programs=lambda: registry, spans=lambda since=None: [],
        registry=lambda: types.SimpleNamespace(snapshot=lambda: {
            "graft_moe_assignments_total": {"samples": [
                {"labels": {"held": "yes"}, "value": 30.0},
                {"labels": {"held": "no"}, "value": 70.0}]},
            "graft_moe_expert_load_max_over_mean": {"samples": [
                {"labels": {}, "value": 1.5}]}}))
    run = chip_run.Run(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded), stamps=[],
        traced_stamps=(None, None), rehearse=False, sizes=sizes,
        traffic=bench_catalog.traffic("fused_s8192"),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    read = {}
    for name in ("scope_times", "moe", "gqa_flash"):
        read.update(_reader(bench_catalog, name).read(run))
    # four routed layers' least time over the 50 us a step under the scope;
    # one attention layer's over 10 us forward and 20 us backward
    assert read == pytest.approx({
        "moe_router_ms_per_step": 0.004, "moe_dispatch_ms_per_step": 0.008,
        "moe_experts_ms_per_step": 0.050, "short_conv_ms_per_step": 0.003,
        "moe_experts_roofline": 100 * 4 * 2.74703e-3 / 50e-6,
        "moe_held_assignment_pct": 30.0, "moe_load_max_over_mean": 1.5,
        "gqa_flash_fwd_roofline": 100 * 1.3953e-3 / 10e-6,
        "gqa_flash_bwd_roofline": 100 * 7 * 1.3953e-3 / 2 / 20e-6},
        rel=1e-3)
    assert run.facts["moe_experts"]["roof"] == "bf16 FLOP/s"
    assert run.facts["gqa_flash"]["calls_per_step"] == 1


def test_readers_find_nothing_in_a_cell_without_these_layers(chip_run,
                                                             bench_catalog):
    """On an OPT cell, or on a program with no registry (the parent): no
    metric and no error."""
    sizes, _ = bench_catalog.config("opt_6b7_l2")
    run = chip_run.Run(mx=types.SimpleNamespace(), recorded=None,
                       reduced=None, stamps=[0.0, 1.0],
                       traced_stamps=(None, None), rehearse=False,
                       sizes=sizes, traffic={}, peaks=None)
    for name in ("scope_times", "moe", "gqa_flash"):
        assert _reader(bench_catalog, name).read(run) == {}
    lfm2, _ = bench_catalog.config(CONFIG)
    run.sizes = lfm2
    for name in ("scope_times", "moe", "gqa_flash"):
        assert _reader(bench_catalog, name).read(run) == {}


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s8192", 1)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in spec[g]
            if m.get("workloads") == [CELL]}
    # no end-to-end entry of its own: that list is a benchmark PR's to
    # change, so the cell reports setup_s and samples_per_s_per_chip, and
    # its peak stands in [facts] and device.memory_peak_bytes
    assert not [m for m in spec["end_to_end"] if CELL in m["name"]]
    assert mine == {
        "place_span_ms_p50." + CELL,
        "dispatch_span_ms_p50." + CELL, "flash_fwd_ms_per_step." + CELL,
        "flash_bwd_ms_per_step." + CELL, "moe_router_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_experts_ms_per_step",
        "moe_experts_roofline", "short_conv_ms_per_step",
        "gqa_flash_fwd_roofline", "gqa_flash_bwd_roofline",
        "moe_held_assignment_pct", "moe_load_max_over_mean"}
    # flash_fwd.py and flash_bwd.py count num_hidden_layers calls a step:
    # their shares would read five times too high here
    assert not {"flash_fwd_roofline." + CELL,
                "flash_bwd_roofline." + CELL} & mine


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


@pytest.mark.parametrize(
    "config, traffic, router, shape, buffer_rows, parts", [
        (CONFIG, "fused_s8192", "sigmoid", (8192, 2048, 1792, 8, 32), 32768,
         2),
        # PR 30: top-8 of 64 by softmax, a buffer of tokens x 8 rows
        ("mellum2_12b_a2b5_ep8_l4", "fused_s8192", "softmax",
         (8192, 2304, 896, 8, 64), 65536, 4),
        # PR 32's cell: top-6 of 64 by sigmoid over a row of 4096; its
        # buffer of 100.7 MB is under the layer's limit and stays whole
        ("kimi_vl_a3b_ep8_l5", "fused_s4096", "sigmoid",
         (4096, 2048, 1408, 8, 64), 24576, 1),
        # PR 40's cell: top-8 of 128 by sigmoid, LFM2's buffer to the byte
        # over Kimi-VL's row of 4096
        ("trinity_mini_ep16_l5", "fused_s4096", "sigmoid",
         (4096, 2048, 1024, 8, 128), 32768, 2)])
def test_routed_layer_compiles_for_the_chip_at_the_cells_shape(
        bench_catalog, one_chip, no_compile_cache, config, traffic, router,
        shape, buffer_rows, parts):
    """Forward and backward of the grouped dispatch over a cell's row of
    tokens with experts 0-7 held, in bf16 (LFM2: top-4 of 32; Mellum2:
    top-8 of 64; Kimi-VL: top-6 of 64; Trinity-Mini: top-8 of 128): the
    products are the Pallas grouped matmul (``gmm``, ``tgmm``, and over the
    row buffers of several parts ``tgmm_parts``, which the TPU's compiler
    takes at LFM2's, Mellum2's and Trinity-Mini's shapes), not XLA's
    expansion of ``ragged_dot``, no row moves by a scatter, no pass masks
    the buffer at the tokens' width, and no view of the buffer by choice is
    written out.  PR 41: a buffer over the layer's limit is worked in
    ``parts`` parts of the tokens, every count a part, nothing of the whole
    buffer's shape is written, and the compiler gives kernel results and
    sums of a part's shape the second memory space (``S(1)`` in a layout)
    that it gives no array of 128 MiB."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    sizes, _ = bench_catalog.config(config)
    traffic = bench_catalog.traffic(traffic)
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    d, h = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held, experts = sizes["num_experts"], sizes["num_experts_published"]
    assert (tokens, d, h, held, experts) == shape
    assert tokens * sizes["num_experts_per_tok"] == buffer_rows
    assert moe._parts(tokens, sizes["num_experts_per_tok"], d, 2) == parts
    part_rows = buffer_rows // parts

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, gate, w1, w3, w2, bias):
        _, chosen, weights = moe._route(
            x, gate, bias if router == "sigmoid" else None,
            top_k=sizes["num_experts_per_tok"],
            router=router, norm_topk=True,
            scaling=float(sizes.get("routed_scaling_factor",
                                    sizes.get("route_scale", 1.0))))
        out, _ = moe.grouped_moe_apply(x, chosen, weights, w1, w3, w2, 0)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((tokens, d)), spec((d, experts)), spec((held, d, h)),
        spec((held, d, h)), spec((held, h, d)),
        spec((experts,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    names = [line.split("=")[0].strip().lstrip("%").split(".")[0]
             for line in calls]
    # a part: three products forward and three for the rows; three for the
    # weights whatever the parts (PR 46: until then three a part), megablox's
    # ``tgmm`` over one buffer and ``tgmm_parts`` over the buffers of several
    assert (names.count("gmm"), names.count("tgmm"),
            names.count("tgmm_parts")) == (
        6 * parts, 3 * (parts == 1), 3 * (parts > 1)), names
    # ... each a weight-shaped result written once, in float32 where the
    # tokens go in parts, and no float32 sum of that shape beside them
    weight_shaped = r" = (\w+)\[%d,(?:%d,%d|%d,%d)\]\S* " % (held, d, h, h, d)
    assert [found for line in calls
            for found in re.findall(weight_shaped + r"custom-call\(", line)
            ] == ["f32" if parts > 1 else "bf16"] * 3
    assert not re.search(weight_shaped.replace(r"(\w+)", "f32") + r"add\(",
                         text)
    big_scatters = [line for line in text.splitlines()
                    if " scatter(" in line
                    and "[%d,%d]" % (part_rows, d) in line]
    assert not big_scatters
    # PR 31: nothing masks the whole buffer at the tokens' width.  What the
    # program writes of [part_rows, d], a part: the four gathers (in and
    # back, forward and backward), the kernels' results and the sum of the
    # two hidden products' row cotangents; the layer until PR 31 also wrote
    # four masked or converted copies (``select_n`` /
    # ``convert_element_type``)
    entry = text[text.index("ENTRY "):]

    def written(rows, ops):
        return [((re.findall(r'op_name="[^"]*/([\w\-]+)"', line) or [""])[0],
                 "S(1)" in line.split(" = ")[1].split(" ")[0])
                for line in entry.splitlines()
                if re.match(r"\s*(ROOT )?%%[\w.\-]+ = bf16\[%d,%d\]\S* (%s)\("
                            % (rows, d, ops), line)]
    wrote = written(part_rows, "fusion|add|select|convert")
    assert sorted(op for op, _ in wrote) == (
        ["add_any"] * parts + ["gather"] * 4 * parts), wrote
    results = [(op, fast) for op, fast in written(part_rows, "custom-call")
               if op == "pallas_call"]
    assert len(results) == 3 * parts            # a part: w2, and d_xs twice
    # PR 41: with the buffer in parts, a kernel's result and a sum of a
    # part's shape lie in the second memory space, as Kimi-VL's whole
    # buffer's always did and no array of LFM2's, Mellum2's or Trinity-
    # Mini's whole buffer ever did.  A gather's result does in some cells
    # (Trinity-Mini's two forward ones, Kimi-VL's) and not in LFM2's, and
    # on the chip the gathers of all of them move a row in 8-12 ns (PERF.md
    # section 6, PR 41): what the placement witnesses is that the arrays
    # are under the compiler's limit, which is what the parts are for
    assert any(fast for _, fast in results), results
    assert any(fast for op, fast in wrote if op == "add_any"), wrote
    if parts > 1:
        assert not re.search(r" = \w+\[%d,%d\]" % (buffer_rows, d), entry)
    # PR 35: the assignments lie choice-major, so a view of the buffer by
    # choice splits its leading dimension and is a bitcast; token-major,
    # at top-4 and top-6 (no whole sublane tile), the program wrote each
    # (N, k, d) view out as a ``reshape``, one of them in float32, and the
    # weighted sum's cotangent as a float32 ``broadcast`` beside them
    relayouts = [
        line.strip()[:100] for line in entry.splitlines()
        for found in [re.match(r"\s*(ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                               r"(reshape|broadcast|copy|transpose)\(", line)]
        if found and np.prod([int(v) for v in found.group(2).split(",")])
        >= part_rows * d]
    assert not relayouts, relayouts


def test_flash_kernels_compile_at_the_cells_shape(bench_catalog, one_chip,
                                                  no_compile_cache):
    """Forward and both backward kernels at (1, 32, 8192, 64) in bf16,
    causal: 16 blocks of 512 a side and a head folded into 128 lanes."""
    import functools
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import get_op
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s8192")
    heads = sizes["num_attention_heads"]
    shape = (traffic["batch_per_chip"], heads, traffic["seq_len"],
             sizes["hidden_size"] // heads)
    assert shape == (1, 32, 8192, 64)
    spec = jax.ShapeDtypeStruct(shape, jnp.dtype(traffic["dtype"]),
                                sharding=one_chip)
    attend = functools.partial(get_op("_contrib_FlashAttention").fcompute,
                               causal=True)
    compiled = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(spec, spec, spec).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_pallas", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert kernel in text, kernel


def test_traced_rehearsal_counts_tokens_routed_differently(chip_run, capsys,
                                                           monkeypatch):
    """``layer_metrics/moe_routing.py``: a traced run's facts say how many
    tokens of the batch's first row bf16 routes to other experts than
    float32, and how many of those touch the share; the counters beside
    them are the eager float32 forward's."""
    monkeypatch.setattr(chip_run, "TRACE_SECONDS", 0.3)
    rc = chip_run.main(["--workload", CELL, "--seed", "2147483659",
                        "--seconds", "4", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    facts = json.loads(lines[-2][len("[facts] "):])
    assert rc == 0 and result["correct"] is True, facts["checks"]
    routing = facts["moe_routing"]
    assert "error" not in routing, routing
    assert (routing["tokens"], routing["layers"]) == (128, 4)
    assert 0 <= routing["of_them_on_a_held_expert"] <= routing[
        "tokens_routed_differently"] <= 128
    assert sum(routing["by_layer"].values()) >= routing[
        "tokens_routed_differently"]
    # the counters are the process's: at least the reference check's eager
    # pass of one row of 128 tokens through four routed layers, top-4
    assert facts["moe"]["assignments_counted"] >= 128 * 4 * 4
    assert {"moe_held_assignment_pct", "moe_load_max_over_mean",
            "moe_experts_roofline", "gqa_flash_fwd_roofline",
            "short_conv_ms_per_step"} <= set(result["metrics"])
