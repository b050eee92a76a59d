"""The configuration ``mellum2_12b_a2b5_ep8_l4`` (``configs/mellum2_moe.py``)
and the reader its cell brought (``layer_metrics/mixed_flash.py``), on the
CPU: the model the program builds against the module's plain reference at
the rehearsal's size with both kinds of layer, forward, loss and gradients;
what the reference's tolerance catches; the eight shares of a routed layer
against the uncut one; the file's published widths; the reader's needs
counted by hand and its metrics read from a synthetic trace.  The cell's
rehearsals are ``tests/chip_bench/test_chip_bench_rehearsal.py``'s, which
find it in ``BENCHMARK.json``.
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
CELL, CONFIG = "mellum2_fused_s8192", "mellum2_12b_a2b5_ep8_l4"
US = 1000


@pytest.fixture(scope="module")
def mellum(bench_catalog):
    """(the rehearsal's sizes, module): d 64, 8 query heads over 1 K/V head
    of 16, window 32, top-8 of 16 experts with 2 held, sliding x 3 + full."""
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
    return np.abs(got - want).max() / np.abs(want).max()


def test_mellum2_block_agrees_with_the_reference(mellum):
    import jax
    sizes, module = mellum
    net = _net(module, sizes)
    tokens = _tokens(sizes)             # 80 positions: the window of 32 binds
    got = _logits(net, tokens)
    want = np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        _params(module, net), tokens))
    assert got.shape == (2, 80, sizes["vocab_rows_held"])
    assert _err(got, want) < 1e-5 < module.TOLERANCE["block_f32"]
    shapes = {n: tuple(v.shape) for n, v in _params(module, net).items()}
    assert shapes["layers_0_self_attn_q_weight"] == (8 * 16, 64)
    assert shapes["layers_3_self_attn_k_weight"] == (1 * 16, 64)
    assert shapes["layers_2_self_attn_out_weight"] == (64, 8 * 16)
    assert shapes["layers_1_mlp_gate_weight"] == (64, 16)
    assert shapes["layers_1_mlp_expert_w3"] == (2, 64, 32)
    assert shapes["lm_head_weight"] == shapes["embed_tokens_weight"] == (
        512, 64)
    assert not any(n.endswith("bias") for n in shapes)      # no bias anywhere


def test_mellum2_loss_and_gradients_agree_with_the_reference(mellum):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by
    parameter."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = mellum
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
        scale = max(np.abs(g).max(), 1e-8)
        assert np.abs(got[name] - g).max() / scale < 2e-3, name


@pytest.mark.parametrize("knock_out", [
    "layers_0_self_attn_q_weight", "layers_3_self_attn_v_weight",
    "layers_1_mlp_expert_w3", "layers_2_mlp_expert_w2",
    "layers_3_post_attention_layernorm_gamma",
    "layers_1_input_layernorm_gamma"])
def test_mellum2_reference_tolerance_catches(mellum, knock_out):
    """A part of the model left out of the reference moves the logits by
    more than ``block_f32`` allows."""
    import jax
    import jax.numpy as jnp
    sizes, module = mellum
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    params[knock_out] = jnp.zeros_like(params[knock_out])
    want = np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        params, tokens))
    assert _err(got, want) > module.TOLERANCE["block_f32"]


@pytest.mark.parametrize("other", [
    {"sliding_window": 16}, {"sliding_window": 80},
    {"rope_parameters": {
        "full_attention": {"rope_type": "default", "rope_theta": 500000},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}},
    {"layer_types": ["sliding_attention"] * 4},
    {"norm_topk_prob": False}])
def test_mellum2_masks_and_positions_are_in_both_sides(mellum, other):
    """The window, YaRN, the layer pattern and the renormalisation are no
    parameters, so no knock-out reaches them: a reference with another
    window, without YaRN, with no full layer or with raw probabilities
    disagrees with the Block."""
    import jax
    sizes, module = mellum
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    changed = dict(sizes, **other)
    want = np.asarray(jax.jit(lambda p, t: module.reference(changed, p, t))(
        _params(module, net), tokens))
    assert _err(got, want) > module.TOLERANCE["block_f32"]


def test_mellum2_reference_follows_the_program_through_a_tie_only(mellum):
    """Experts 1 (held) and 2 (absent) of layer 2 are given one router
    column, an exact tie of two probabilities: the reference follows the
    choice the Block made on its last eager call, and one that is no top-k
    of its own scores it does not."""
    import weakref
    import jax
    import incubator_mxnet_tpu as mx
    sizes, module = mellum
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    _logits(net, tokens)                    # resolves the deferred shapes
    routed = net.layers[2].mlp
    gate = routed.gate_weight.data().asnumpy().copy()
    gate[:, 2] = gate[:, 1]
    routed.gate_weight.set_data(mx.nd.array(gate))
    got = _logits(net, tokens)
    params = _params(module, net)

    def reference(follow=True):
        module._built = weakref.ref(net) if follow else None
        return np.asarray(jax.jit(
            lambda p, t: module.reference(sizes, p, t))(params, tokens))

    followed = reference()
    assert _err(got, followed) < 1e-5
    assert module._built is None            # read once
    alone = reference(follow=False)
    chose = np.asarray(routed.last_chosen)
    assert chose.shape == (tokens.size, sizes["num_experts_per_tok"])
    # a choice with an expert twice is not followed
    wrong = np.concatenate([chose[:, :-1], chose[:, :1]], 1)
    routed.last_chosen = jax.numpy.asarray(wrong.astype(chose.dtype))
    np.testing.assert_array_equal(reference(), alone)
    # nor a record of another number of tokens
    routed.last_chosen = jax.numpy.asarray(chose[:-1])
    np.testing.assert_array_equal(reference(), alone)


def test_the_eight_shares_add_up_to_the_uncut_layer(mellum):
    """One routed layer at d 32, experts of 16, top-8 of 64 by softmax,
    renormalised: the program's eight shares (``experts_held=(8 i, 8)``,
    the same router, each its slice of the experts) add up to what the
    reference gives for the layer with all 64 experts held."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    _, module = mellum
    d, h, experts, held, k = 32, 16, 64, 8, 8
    rs = np.random.RandomState(3)
    gate = rs.randn(d, experts).astype(np.float32)
    w1, w3 = (rs.randn(experts, d, h).astype(np.float32) * 0.3
              for _ in range(2))
    w2 = rs.randn(experts, h, d).astype(np.float32) * 0.3
    x = rs.randn(96, d).astype(np.float32)
    total = np.zeros((96, d), np.float32)
    for share in range(experts // held):
        first = share * held
        layer = ExpertParallelMoE(
            h, experts, top_k=k, dispatch="grouped",
            experts_held=(first, held), router="softmax", norm_topk=True,
            gated=True, in_units=d, ep_axis=None, prefix="share%d_" % share)
        layer.initialize()
        layer(mx.nd.array(x))
        layer.gate_weight.set_data(mx.nd.array(gate))
        for p, w in ((layer.expert_w1, w1), (layer.expert_w3, w3),
                     (layer.expert_w2, w2)):
            p.set_data(mx.nd.array(w[first:first + held]))
        part = np.asarray(layer(mx.nd.array(x))._read())
        assert np.abs(part).max() > 0
        total += part
    uncut = {"num_experts": experts, "num_experts_published": experts,
             "num_experts_per_tok": k, "norm_topk_prob": True}
    want = np.asarray(module._routed(uncut, {
        "mlp_gate_weight": jnp.asarray(gate), "mlp_expert_w1": jnp.asarray(w1),
        "mlp_expert_w3": jnp.asarray(w3), "mlp_expert_w2": jnp.asarray(w2)},
        jnp.asarray(x)))
    assert _err(total, want) < 1e-5
    # and one share alone is what the reference gives for that share
    share0 = dict(uncut, num_experts=held)
    want0 = np.asarray(module._routed(share0, {
        "mlp_gate_weight": jnp.asarray(gate),
        "mlp_expert_w1": jnp.asarray(w1[:held]),
        "mlp_expert_w3": jnp.asarray(w3[:held]),
        "mlp_expert_w2": jnp.asarray(w2[:held])}, jnp.asarray(x)))
    assert 0.05 < np.abs(want0).max() / np.abs(want).max() < 0.95


def test_mellum2_file_keeps_the_published_widths(bench_catalog):
    """Every number of the catalog's config for Mellum2-12B-A2.5B that is
    not cut is in the file under its own key; the cut ones are the file's
    ``reduced``, and what the issue asks the file to state is stated."""
    sizes, module = bench_catalog.config(CONFIG)
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "vocab_size": 98304,
        "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    assert {k: sizes[k] for k in published} == published
    assert sorted(sizes["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_rows_held"])
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"],
            sizes["num_experts"], sizes["num_experts_published"],
            sizes["vocab_rows_held"]) == (4, 0, 8, 64, 12288)
    assert sizes["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert sizes["mlp_layer_types"] == ["sparse"] * 4
    for key in ("source", "assumed", "deployment", "depth_note",
                "expert_load_note", "first_loss_with", "first_loss_note"):
        assert sizes[key], key
    assert sizes["input"]["vocab_key"] == "vocab_rows_held"
    # 340.3 M parameters at the real widths, counted from the shapes
    d, h = 2304, 896
    attn = 2 * d * 4096 + 2 * d * 512
    layer = attn + d * 64 + 2 * d + 8 * 3 * d * h
    assert (attn, layer) == (21233664, 70930944)
    assert 4 * layer + 2 * 12288 * d + d == 340349184
    traffic = bench_catalog.traffic("fused_s8192")
    assert module.matmul_params(sizes) == pytest.approx(
        4 * (attn + d * 64 + 8 * 8 / 64 * 3 * d * h) + 12288 * d)
    assert module.mean_keys(sizes, "full_attention", 8192) == 4096.5
    assert module.mean_keys(sizes, "sliding_attention", 8192) == pytest.approx(
        960.0625)
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * module.matmul_params(sizes)
        + 3 * 4 * 4096 * (4096.5 + 3 * 960.0625))
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        1.1746e9, rel=1e-3)


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("layer_metrics_" + name)]
    return reader


def test_mixed_flash_needs_by_hand(bench_catalog):
    mixed = _reader(bench_catalog, "mixed_flash")
    assert mixed.keys_seen(8192) == 8192 * 8193 // 2
    # 1024 rows of a growing triangle, then 7168 rows of 1024 keys
    assert mixed.keys_seen(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    assert mixed.keys_seen(512, 1024) == mixed.keys_seen(512)
    (f_flops, f_bytes), (b_flops, b_bytes) = mixed.needs(
        batch=1, heads=32, kv_heads=4, seq=8192, head_dim=128, window=1024,
        dtype_bytes=2)
    product = 2 * 32 * 128 * 7864832
    assert f_flops == 2 * product and b_flops == 7 * product
    q_like, kv_like = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2
    assert f_bytes == 2 * q_like + 2 * kv_like      # q, o; k, v
    assert b_bytes == 4 * q_like + 4 * kv_like      # q, o, dO, dq; k, v, dk, dv
    assert f_flops / 197e12 == pytest.approx(0.6541e-3, rel=1e-3)
    # without a window it is gqa_flash.py's count but for the diagonal
    gqa = _reader(bench_catalog, "gqa_flash")
    full = mixed.needs(1, 32, 4, 8192, 128, None, 2)
    assert full[0][0] == pytest.approx(
        gqa.needs(1, 32, 4, 8192, 128, 2)[0][0], rel=2e-4)
    assert full[0][1] == gqa.needs(1, 32, 4, 8192, 128, 2)[0][1]


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


def _snapshot(window_label=True, gauges=True):
    snap = {"graft_flash_attention_traces_total": {"samples": [
        {"labels": dict({"path": "lowering_platform"},
                        **({"window": w} if window_label else {})),
         "value": 1.0} for w in ("none", "1024")]}}
    if gauges:
        snap["graft_flash_blocks_visited"] = {"samples": [
            {"labels": {"kind": "window"}, "value": 32 * 45.0},
            {"labels": {"kind": "full"}, "value": 32 * 136.0}]}
        snap["graft_flash_blocks_causal"] = {"samples": [
            {"labels": {"kind": "window"}, "value": 32 * 136.0},
            {"labels": {"kind": "full"}, "value": 32 * 136.0}]}
    return snap


def _run(chip_run, bench_catalog, planes, snapshot, **kw):
    sizes, _ = bench_catalog.config(CONFIG)
    recorded = {"planes": planes, "ops": {}} if planes else None
    telemetry = types.SimpleNamespace(
        programs=lambda: {}, spans=lambda since=None: [],
        registry=lambda: types.SimpleNamespace(snapshot=lambda: snapshot))
    args = dict(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded) if recorded else None,
        stamps=[], traced_stamps=(None, None), rehearse=False, sizes=sizes,
        traffic=bench_catalog.traffic("fused_s8192"),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    args.update(kw)
    return chip_run.Run(**args)


def test_mixed_flash_from_a_synthetic_trace(chip_run, bench_catalog):
    """The window [200, 400] us holds 2 steps.  A step: the full layer's
    forward kernel 10, its backward kernels 12 + 8; three window layers'
    forward kernels 3 each, their backward kernels 4 + 2 each."""
    def step(at):
        events = [_ev("%flash_attention_pallas.1", at, 10),
                  _ev("%flash_attention_bwd_dkv.2", at + 10, 12),
                  _ev("%flash_attention_bwd_dq.3", at + 22, 8)]
        for n in range(3):
            base = at + 30 + 9 * n
            events += [_ev("%%flash_window_pallas.%d" % (4 + n), base, 3),
                       _ev("%%flash_window_bwd_dkv.%d" % (7 + n), base + 3, 4),
                       _ev("%%flash_window_bwd_dq.%d" % (10 + n), base + 7, 2)]
        return events
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(77)", 200, 96),
                _ev("jit_dp_train_step(77)", 300, 96)]},
            {"name": "XLA Ops", "events": step(200) + step(300)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            _ev("bench:wait", e - 1, 1) for e in (0, 50, 200, 300, 400)]}]},
    ]
    mixed = _reader(bench_catalog, "mixed_flash")
    run = _run(chip_run, bench_catalog, planes, _snapshot())
    full_fwd = 2 * 2 * 32 * 128 * (8192 * 8193 // 2) / 197e12
    win_fwd = 2 * 2 * 32 * 128 * 7864832 / 197e12
    assert mixed.read(run) == pytest.approx({
        "full_flash_fwd_ms_per_step": 0.010,
        "full_flash_bwd_ms_per_step": 0.020,
        "window_flash_fwd_ms_per_step": 0.009,
        "window_flash_bwd_ms_per_step": 0.018,
        "full_flash_fwd_roofline": 100 * full_fwd / 10e-6,
        "full_flash_bwd_roofline": 100 * 3.5 * full_fwd / 20e-6,
        "window_flash_fwd_roofline": 100 * 3 * win_fwd / 9e-6,
        "window_flash_bwd_roofline": 100 * 3 * 3.5 * win_fwd / 18e-6,
        "window_blocks_visited_pct": 100 * 45 / 136}, rel=1e-6)
    facts = run.facts["mixed_flash"]
    assert facts["window_flash_fwd"]["calls_per_step"] == 3
    assert facts["full_flash_bwd"]["roof"] == "bf16 FLOP/s"


def test_mixed_flash_reads_nothing_where_there_is_nothing(chip_run,
                                                          bench_catalog):
    """A parent's program (no window label, no gauge), a cell without a
    window, a run without a trace: no metric and no error; a rehearsal
    names the kinds the program counted."""
    mixed = _reader(bench_catalog, "mixed_flash")
    run = _run(chip_run, bench_catalog, None, _snapshot(False, False))
    assert mixed.read(run) == {}
    run = _run(chip_run, bench_catalog, None, {}, mx=types.SimpleNamespace())
    assert mixed.read(run) == {}
    for other in ("opt_6b7_l2", "lfm2_8b_a1b_ep4_l5"):
        sizes, _ = bench_catalog.config(other)
        run = _run(chip_run, bench_catalog, None, _snapshot(), sizes=sizes)
        assert mixed.read(run) == {}
    run = _run(chip_run, bench_catalog, None, _snapshot(), rehearse=True,
               peaks=None)
    assert set(mixed.read(run)) == set(
        mixed.names("full") + mixed.names("window")
        + ["window_blocks_visited_pct"])


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s8192", 1)
    # appended behind what was there (later PRs append behind it in turn)
    cells = [w["name"] for w in spec["workloads"]]
    assert cells.index(CELL) == cells.index("lfm2moe_fused_s8192") + 1
    configs = [c["name"] for c in spec["configs"]]
    assert configs.index(CONFIG) == configs.index("lfm2_8b_a1b_ep4_l5") + 1
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in spec[g]
            if m.get("workloads") == [CELL]}
    # no end-to-end entry of its own: that list is a benchmark PR's to change
    assert not [m for m in spec["end_to_end"] if CELL in m["name"]]
    assert mine == {
        "place_span_ms_p50." + CELL, "dispatch_span_ms_p50." + CELL,
        "moe_router_ms_per_step." + CELL, "moe_dispatch_ms_per_step." + CELL,
        "moe_experts_ms_per_step." + CELL, "moe_experts_roofline." + CELL,
        "moe_held_assignment_pct." + CELL, "moe_load_max_over_mean." + CELL,
        "full_flash_fwd_ms_per_step", "full_flash_bwd_ms_per_step",
        "full_flash_fwd_roofline", "full_flash_bwd_roofline",
        "window_flash_fwd_ms_per_step", "window_flash_bwd_ms_per_step",
        "window_flash_fwd_roofline", "window_flash_bwd_roofline",
        "window_blocks_visited_pct"}
    # flash_fwd.py, flash_bwd.py and gqa_flash.py take the head as
    # hidden_size // num_attention_heads (72 here): not declared
    assert not {m for m in mine if m.startswith(("flash_", "gqa_flash"))}
    # no list that was there names the cell; PR 34's four metrics of the
    # routed layer (``moe_step.py``) came later, with the three routed
    # cells in one list each
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CELL in m.get("workloads", ()) and not m["name"].startswith(
                    ("moe_step_", "moe_experts_rows_")):
                assert m["workloads"] == [CELL], m["name"]


def test_expert_needs_at_top_8(bench_catalog):
    """``layer_metrics/moe.py`` as it is, read over this configuration: four
    routed layers, 8192 balanced assignments on the share."""
    moe = _reader(bench_catalog, "moe")
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s8192")
    assert moe.routed_layers(sizes) == 4
    assert moe.balanced_assignments(sizes, traffic) == 8192 * 8 * 8 / 64
    flops, _ = moe.needs(8192, 2304, 896, 8, 2)
    assert flops == 3 * 3 * 2 * 8192 * 2304 * 896
