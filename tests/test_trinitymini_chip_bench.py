"""The configuration ``trinity_mini_ep16_l5`` (``configs/trinity_afmoe.py``),
the keyword its cell brought (``gluon.nn.MultiHeadAttention(gate=True)``) and
the reader (``layer_metrics/gated_attention.py``), on the CPU: the gated
attention layer of each kind and the whole Block against the module's plain
reference at the rehearsal's size, value, loss and gradients; the departures
a tolerance would hide; the sixteen shares of a routed layer plus the shared
expert once against the uncut layer; the file's published widths; the
reader's metrics read from a synthetic trace.  The cell's rehearsals are
``tests/chip_bench/test_chip_bench_rehearsal.py``'s, which find it in
``BENCHMARK.json``; the layer with the keyword off is
``tests/test_window_attention.py``'s.
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
CELL, CONFIG = "trinitymini_gated_fused_1row", "trinity_mini_ep16_l5"
US = 1000


@pytest.fixture(scope="module")
def trinity(bench_catalog):
    """(the rehearsal's sizes, module): d 64, 8 query heads over 2 K/V heads
    of 16, a window of 32, a dense layer of 128, top-8 of 16 experts of 32
    with 2 held beside a shared expert of 32, one dense + four routed."""
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


def _reference(module, sizes, params, tokens):
    import jax
    return np.asarray(jax.jit(lambda p, t: module.reference(sizes, p, t))(
        params, tokens))


# ---------------------------------------------------------------------------
# the layer and the Block against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_gated_layer_agrees_with_the_reference(trinity, kind):
    """``gluon.nn.MultiHeadAttention`` as the cell builds it (grouped K/V
    heads of their own width, QK-norm, the gate; a window and rotary
    positions on a sliding layer, neither on a full one) against the
    reference's ``_attention`` on the same weights: value and the gradient of
    every projection, of the gate and of the two head norms' gains."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = trinity
    d, heads, kv, dim = (sizes["hidden_size"], sizes["num_attention_heads"],
                         sizes["num_key_value_heads"], sizes["head_dim"])
    sliding = kind == "sliding_attention"
    layer = nn.MultiHeadAttention(
        d, heads, causal=True, use_bias=False, num_kv_heads=kv, head_dim=dim,
        qk_norm=True, qk_norm_epsilon=sizes["rms_norm_eps"], gate=True,
        window=sizes["sliding_window"] if sliding else None,
        rotary_base=float(sizes["rope_theta"]) if sliding else None,
        prefix="self_attn_")
    mx.random.seed(5)
    layer.initialize(mx.init.Normal(0.3))
    rs = np.random.RandomState(1)
    x = rs.randn(2, 80, d).astype(np.float32)
    layer(mx.nd.array(x))
    for norm in (layer.q_norm, layer.k_norm):
        norm.gamma.set_data(mx.nd.array(
            1.0 + 0.2 * rs.randn(dim).astype(np.float32)))
    fn, values = functionalize(layer, mx.nd.array(x), train=True)
    shapes = {n: tuple(v.shape) for n, v in values.items()}
    assert shapes == {
        "self_attn_q_weight": (heads * dim, d),
        "self_attn_k_weight": (kv * dim, d),
        "self_attn_v_weight": (kv * dim, d),
        "self_attn_out_weight": (d, heads * dim),
        "self_attn_q_norm_gamma": (dim,), "self_attn_k_norm_gamma": (dim,),
        "self_attn_gate_weight": (heads * dim, d)}
    g = rs.randn(2, 80, d).astype(np.float32)
    got, vjp = jax.vjp(lambda p: fn(p, jnp.asarray(x)), values)
    want, want_vjp = jax.vjp(
        lambda p: module._attention(sizes, p, jnp.asarray(x), kind),
        dict(values))
    assert _err(np.asarray(got), np.asarray(want)) < 1e-5
    (got_g,), (want_g,) = vjp(jnp.asarray(g)), want_vjp(jnp.asarray(g))
    for name in shapes:
        assert _err(np.asarray(got_g[name]),
                    np.asarray(want_g[name])) < 1e-4, name
    # the other kind's mathematics is another layer
    other = [k for k in module.KINDS if k != kind][0]
    assert _err(np.asarray(got), np.asarray(module._attention(
        sizes, dict(values), jnp.asarray(x), other))) > 1e-2


def test_block_agrees_with_the_reference(trinity):
    sizes, module = trinity
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    want = _reference(module, sizes, params, tokens)
    assert got.shape == (2, 80, sizes["vocab_rows_held"])
    assert _err(got, want) < 1e-5 < module.TOLERANCE["block_f32"]
    shapes = {n: tuple(v.shape) for n, v in params.items()}
    assert shapes["layers_0_mlp_w1_weight"] == (128, 64)        # dense
    assert "layers_0_mlp_gate_weight" not in shapes
    assert shapes["layers_1_mlp_gate_weight"] == (64, 16)
    assert shapes["layers_1_mlp_expert_bias"] == (16,)
    assert shapes["layers_4_mlp_expert_w3"] == (2, 64, 32)
    assert shapes["layers_2_mlp_shared_experts_w1_weight"] == (32, 64)
    assert shapes["layers_3_self_attn_gate_weight"] == (8 * 16, 64)
    assert shapes["layers_3_self_attn_k_weight"] == (2 * 16, 64)
    assert shapes["lm_head_weight"] == shapes["embed_tokens_weight"] == (
        512, 64)
    # four norms a layer and two a head, one after the last layer
    assert sorted(n for n in shapes if n.startswith("layers_2_")
                  and n.endswith("gamma")) == sorted(
        "layers_2_" + n + "_gamma" for n in (
            "input_layernorm", "post_attention_layernorm",
            "pre_mlp_layernorm", "post_mlp_layernorm", "self_attn_q_norm",
            "self_attn_k_norm"))
    assert [n for n in shapes if n.endswith("bias")] == [
        "layers_%d_mlp_expert_bias" % i for i in range(1, 5)]


def test_loss_and_gradients_agree_with_the_reference(trinity):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by parameter;
    no gradient reaches the selection bias."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = trinity
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    labels = _tokens(sizes, seed=1).astype(np.float32)
    fn, values = functionalize(net, mx.nd.array(tokens, dtype="int32"),
                               train=True)

    def block_loss(p):
        return module.reference_loss(fn(p, jnp.asarray(tokens)), labels)

    cut = len(module.PREFIX)
    loss, grads = jax.value_and_grad(block_loss)(values)
    got = {n[cut:]: np.asarray(g) for n, g in grads.items()}
    want_loss, want = jax.value_and_grad(lambda p: module.reference_loss(
        module.reference(sizes, p, tokens), labels))(_params(module, net))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want)
    for name, g in want.items():
        g = np.asarray(g)
        if name.endswith("expert_bias"):
            assert not g.any() and not got[name].any()
            continue
        scale = max(np.abs(g).max(), 1e-8)
        assert np.abs(got[name] - g).max() / scale < 2e-3, name


# a departure of the mathematics that no parameter carries: what to put in
# the reference module's place, or which size to change
DEPARTURES = {
    "no_gate": {"_gate": lambda logits: 1.0},
    "no_qk_norm": {"_head_norm": lambda x, gain, eps: x},
    "positions_on_the_full_layer": {
        "_positions": lambda cfg, kind: float(cfg["rope_theta"])},
    "no_positions_on_the_sliding_layers": {
        "_positions": lambda cfg, kind: None},
    "branch_norms_left_out": {"_branch_out": lambda out, gain, eps: out},
    "no_embedding_multiplier": {"mup_enabled": False},
    "shared_expert_dropped": {"num_shared_experts": 0},
    "no_route_scale": {"route_scale": 1.0},
    "raw_scores_not_renormalised": {"route_norm": False},
    "window_one_short": {"sliding_window": 31},
    "every_layer_full": {"sliding_window": 10 ** 6},
}


@pytest.fixture(scope="module")
def seen(trinity):
    """The Block's logits on seeded weights with every gain and the
    selection bias away from their neutral values, with the weights: shared
    by the knock-outs below."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    sizes, module = trinity
    sizes = dict(sizes, init_std=0.2, expert_bias_std=0.3)
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    _logits(net, tokens)                # resolves the deferred shapes
    rs = np.random.RandomState(9)
    for name, p in net.collect_params().items():
        if name.endswith("gamma"):
            p.set_data(mx.nd.array(1.0 + 0.3 * rs.randn(
                *p.data().shape).astype(np.float32)))
    got = _logits(net, tokens)
    params = _params(module, net)
    assert _err(got, _reference(module, sizes, params, tokens)) < 1e-5
    return sizes, got, params, tokens, jnp


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_reference_tolerance_catches_a_departure(trinity, seen, monkeypatch,
                                                 departure):
    """No gate, no QK-norm, positions put on the full layer or taken off the
    sliding ones, the branches' norms left out, the embedding's multiplier,
    the shared expert, ``route_scale``, the renormalisation, another window:
    each moves the logits by more than ``block_f32`` allows."""
    _, module = trinity
    sizes, got, params, tokens, _ = seen
    changed = dict(sizes)
    for key, value in DEPARTURES[departure].items():
        if callable(value):
            monkeypatch.setattr(module, key, value)
        else:
            changed[key] = value
    want = _reference(module, changed, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


@pytest.mark.parametrize("knock_out", [
    "layers_0_self_attn_gate_weight", "layers_2_self_attn_gate_weight",
    "layers_1_self_attn_q_norm_gamma", "layers_2_self_attn_k_norm_gamma",
    "layers_3_post_attention_layernorm_gamma",
    "layers_0_post_mlp_layernorm_gamma", "layers_4_post_mlp_layernorm_gamma",
    "layers_2_pre_mlp_layernorm_gamma", "layers_0_mlp_w3_weight",
    "layers_1_mlp_shared_experts_w2_weight", "layers_4_mlp_expert_w1",
    "layers_3_mlp_expert_bias"])
def test_reference_tolerance_catches_a_part_left_out(trinity, seen,
                                                     knock_out):
    """A zeroed gate projection leaves a gate of one half everywhere, a
    zeroed head norm uniform attention, a zeroed branch norm no branch, a
    zeroed selection bias another choice of experts."""
    _, module = trinity
    sizes, got, params, tokens, jnp = seen
    params = dict(params)
    params[knock_out] = jnp.zeros_like(params[knock_out])
    want = _reference(module, sizes, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


def test_gate_traces_are_counted(trinity):
    from incubator_mxnet_tpu import telemetry

    def count():
        snap = telemetry.registry().snapshot().get(
            "graft_attention_gate_traces_total", {"samples": []})
        return sum(s["value"] for s in snap["samples"])

    sizes, module = trinity
    before = count()
    _logits(_net(module, sizes), _tokens(sizes, rows=1, seq=16))
    assert count() - before == 5                # five gated layers


def test_the_sixteen_shares_and_the_shared_expert_once_add_up(trinity):
    """One routed layer at d 32, experts of 16, top-8 of 128 by sigmoid with
    a selection bias, renormalised, scaled by 2.826, beside a shared expert
    of 16: the program's sixteen routed shares (``experts_held=(8 i, 8)``,
    the same router, each its slice of the experts, no shared expert) plus
    the shared expert counted once add up to what the reference gives for
    the layer with all 128 experts held."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    _, module = trinity
    d, h, experts, held, k = 32, 16, 128, 8, 8
    rs = np.random.RandomState(3)
    gate = rs.randn(d, experts).astype(np.float32)
    bias = 0.05 * rs.randn(experts).astype(np.float32)
    w1, w3 = (rs.randn(experts, d, h).astype(np.float32) * 0.3
              for _ in range(2))
    w2 = rs.randn(experts, h, d).astype(np.float32) * 0.3
    s1, s3 = (rs.randn(h, d).astype(np.float32) * 0.3 for _ in range(2))
    s2 = rs.randn(d, h).astype(np.float32) * 0.3
    x = rs.randn(96, d).astype(np.float32)

    def layer(first, with_shared, prefix):
        moe = ExpertParallelMoE(
            h, experts, top_k=k, dispatch="grouped",
            experts_held=(first, held), router="sigmoid",
            selection_bias=True, norm_topk=True, scaling=2.826, gated=True,
            in_units=d, ep_axis=None, prefix=prefix,
            shared_hidden_size=h if with_shared else None)
        moe.initialize()
        moe(mx.nd.array(x))
        moe.gate_weight.set_data(mx.nd.array(gate))
        moe.expert_bias.set_data(mx.nd.array(bias))
        for p, w in ((moe.expert_w1, w1), (moe.expert_w3, w3),
                     (moe.expert_w2, w2)):
            p.set_data(mx.nd.array(w[first:first + held]))
        if with_shared:
            for block, w in ((moe.shared_experts.w1, s1),
                             (moe.shared_experts.w3, s3),
                             (moe.shared_experts.w2, s2)):
                block.weight.set_data(mx.nd.array(w))
        return moe

    parts = [np.asarray(layer(held * i, False, "share%d_" % i)(
        mx.nd.array(x))._read()) for i in range(experts // held)]
    assert len(parts) == 16
    # what every chip computes alike: this chip's layer less its routed part
    chip0 = np.asarray(layer(0, True, "chip0_")(mx.nd.array(x))._read())
    once = chip0 - parts[0]
    uncut = {"num_experts": experts, "num_experts_published": experts,
             "num_experts_per_tok": k, "route_norm": True,
             "route_scale": 2.826, "num_shared_experts": 1}
    weights = {"mlp_gate_weight": gate, "mlp_expert_bias": bias,
               "mlp_expert_w1": w1, "mlp_expert_w3": w3, "mlp_expert_w2": w2,
               "mlp_shared_experts_w1_weight": s1,
               "mlp_shared_experts_w3_weight": s3,
               "mlp_shared_experts_w2_weight": s2}
    want = np.asarray(module._routed(
        uncut, {n: jnp.asarray(v) for n, v in weights.items()},
        jnp.asarray(x)))
    assert _err(sum(parts) + once, want) < 1e-5
    # counted sixteen times it is not the layer, nor left out
    assert _err(sum(parts) + 16 * once, want) > 1e-2
    assert _err(sum(parts), want) > 1e-2
    # and this chip's layer is what the reference gives for its share
    share0 = dict(uncut, num_experts=held)
    want0 = np.asarray(module._routed(
        share0, {n: jnp.asarray(v[:held] if n.startswith("mlp_expert_w")
                                else v) for n, v in weights.items()},
        jnp.asarray(x)))
    assert _err(chip0, want0) < 1e-5


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

# the catalog's config for Trinity-Mini (model-configs guide,
# architectures.jsonl), every key but ``layer_types`` (32 entries there:
# three sliding layers to one full one, eight times)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
PUBLISHED_LAYER_TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 8


def test_file_keeps_the_published_widths(bench_catalog):
    """Every key of the catalog's config is in the file at its published
    value but the cut ones, which are the file's ``reduced`` beside the
    vocabulary rows held; what the issue asks the file to state is stated;
    the parameters and the FLOPs are counted by hand."""
    sizes, module = bench_catalog.config(CONFIG)
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8}
    assert {k: sizes[k] for k in PUBLISHED} == dict(PUBLISHED, **cut)
    assert sizes["layer_types"] == PUBLISHED_LAYER_TYPES[1:6] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert sorted(sizes["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_rows_held"])
    assert (sizes["num_experts_published"],
            sizes["num_hidden_layers_published"],
            sizes["num_dense_layers_published"]) == (128, 32, 2)
    assert sizes["vocab_rows_held"] == 200192 // 8 == 25024
    for key in ("embedding_multiplier", "qk_norm", "attention_gate",
                "nope_on_full_layers", "four_norms", "expert_bias",
                "selection_bias", "route_norm_epsilon", "auxiliary_loss",
                "optimizer", "schedule", "norm_gains", "initializer",
                "embedding_init_std_reading"):
        assert sizes["assumed"][key], key
    for key in ("source", "deployment", "depth_note", "expert_load_note",
                "first_loss_with", "first_loss_note"):
        assert sizes[key], key
    assert "16 chips" in sizes["deployment"]
    assert sizes["input"]["vocab_key"] == "vocab_rows_held"
    with pytest.raises(ValueError, match="layer_types"):
        module._checked(dict(sizes, num_hidden_layers=4))
    with pytest.raises(ValueError, match="sigmoid"):
        module._checked(dict(sizes, score_func="softmax"))
    # 504,147,712 parameters at the real widths, counted from the shapes
    d = 2048
    attn = 3 * d * 4096 + 2 * d * 512 + 2 * 128
    norms = 4 * d
    dense = attn + norms + 3 * d * 6144
    routed = (attn + norms + d * 128 + 128 + 3 * d * 1024
              + 8 * 3 * d * 1024)
    assert (attn, dense, routed) == (27263232, 65020160, 84156800)
    assert dense + 4 * routed + 2 * 25024 * d + d == 504147712 == sizes[
        "parameters"]
    assert module.attention_params(sizes) == attn - 256
    traffic = bench_catalog.traffic("fused_s4096")
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["driver"], traffic["dtype"]) == (1, 4096, "fused",
                                                     "bfloat16")
    # train_flops_per_sample by hand
    assert module.matmul_params(sizes) == pytest.approx(
        5 * (attn - 256) + 3 * d * 6144
        + 4 * (3 * d * 1024 + d * 128 + 8 * 8 / 128 * 3 * d * 1024)
        + 25024 * d)
    assert module.matmul_params(sizes) == pytest.approx(264.1e6, rel=1e-3)
    assert module.mean_keys(sizes, "full_attention", 4096) == 2048.5
    assert module.mean_keys(sizes, "sliding_attention", 4096) == 1536.25
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * module.matmul_params(sizes)
        + 3 * 32 * 512 * (2048.5 + 4 * 1536.25))
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        1.987e9, rel=1e-3)


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s4096", 1)
    (config,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert config["source"] == ("https://huggingface.co/arcee-ai/"
                                "Trinity-Mini/blob/main/config.json")
    assert config["file"] == "benchmark/chip/configs/%s.json" % CONFIG
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_rows_held"]
    assert [w["name"] for w in spec["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
    # appended by PR 40 as the tenth cell; later PRs append after it
    assert spec["workloads"][9] is cell and spec["configs"][7] is config
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "resnet50_fused_dp4_b1024"]
    # no end-to-end entry of its own: that list is a benchmark PR's to change
    assert not [m for m in spec["end_to_end"] if CELL in m["name"]]
    mine = [m for m in spec["per_layer"] if CELL in m.get("workloads", ())]
    accepted = [
        "place_span_ms_p50", "dispatch_span_ms_p50",
        "full_flash_fwd_ms_per_step", "full_flash_fwd_roofline",
        "full_flash_bwd_ms_per_step", "full_flash_bwd_roofline",
        "window_flash_fwd_ms_per_step", "window_flash_fwd_roofline",
        "window_flash_bwd_ms_per_step", "window_flash_bwd_roofline",
        "window_blocks_visited_pct", "moe_router_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_experts_ms_per_step",
        "moe_experts_roofline", "moe_held_assignment_pct",
        "moe_load_max_over_mean", "moe_shared_ms_per_step",
        "moe_step_held_assignment_pct", "moe_step_held_drift_pct",
        "moe_step_load_max_over_mean", "moe_experts_rows_roofline"]
    assert [m["name"] for m in mine] == ["attn_gate_ms_per_step"] + [
        n + "." + CELL for n in accepted]
    # appended together (a later PR's entries come after them)
    at = spec["per_layer"].index(mine[0])
    assert spec["per_layer"][at:at + len(mine)] == mine
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for m in mine:
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "samples_per_s_per_chip", m["name"]
        base = by_name.get(m["name"].partition(".")[0])
        if base is not m:               # as the accepted entry but the cell
            assert {k: v for k, v in m.items() if k not in (
                "name", "workloads")} == {k: v for k, v in base.items()
                                          if k not in ("name", "workloads")}
    assert by_name["attn_gate_ms_per_step"] == {
        "name": "attn_gate_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "sequence blocks",
        "moves": "samples_per_s_per_chip", "workloads": [CELL]}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("layer_metrics_" + name)]
    return reader


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


_LAYER = "jit(dp_train_step)/xray:forward/trinitymini_layers_0_self_attn/"
_BACK = ("jit(dp_train_step)/xray:backward/transpose(jvp("
         "trinitymini_layers_0_self_attn))/")
_OPS = {    # short HLO name -> op_name path, as telemetry.programs() has them
    "fusion.1": _LAYER + "attn_gate/trinitymini_layers_0_self_attn_gate_/"
                         "dot_general",
    "fusion.2": _LAYER + "attn_gate/logistic",
    "fusion.3": _BACK + "transpose(jvp(attn_gate))/mul",
    "fusion.4": _BACK + "transpose(jvp(attn_gate))/transpose(jvp("
                        "trinitymini_layers_0_self_attn_gate_))/dot_general",
    "flash_window_pallas.5": _LAYER + "attn_window/flash_window_pallas/"
                                      "pallas_call",
    "fusion.6": "jit(dp_train_step)/xray:forward/trinitymini_layers_1_mlp/"
                "moe_shared/trinitymini_layers_1_mlp_shared_experts_w1_/"
                "dot_general",
    "fusion.7": "jit(dp_train_step)/xray:forward/trinitymini_layers_1_mlp/"
                "moe_experts/gmm",
    "fusion.8": _LAYER + "trinitymini_layers_0_self_attn_out_/dot_general",
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


def _run(chip_run, bench_catalog, planes, ops=_OPS, config=CONFIG,
         cell=CELL, **kw):
    sizes, _ = bench_catalog.config(config)
    recorded = {"planes": planes, "ops": {}} if planes else None
    programs = {"dp_train_step": types.SimpleNamespace(
        ops=ops, phase=None, error=None)}
    telemetry = types.SimpleNamespace(
        programs=lambda: programs, spans=lambda since=None: [],
        registry=lambda: types.SimpleNamespace(snapshot=lambda: {}))
    args = dict(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded) if recorded else None,
        stamps=[], traced_stamps=(None, None), rehearse=False, sizes=sizes,
        cell=bench_catalog.cell(cell),
        traffic=bench_catalog.traffic("fused_s4096"),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    args.update(kw)
    return chip_run.Run(**args)


def _step(at):
    """A step: the gate's projection 7, its sigmoid 1, the product's
    backward 2, the projection's backward 9; a window kernel 10, the shared
    expert 6, the routed experts 9 and ``proj_out`` 4, under no such
    scope."""
    return [_ev("%fusion.1", at, 7), _ev("%fusion.2", at + 7, 1),
            _ev("%fusion.3", at + 8, 2), _ev("%fusion.4", at + 10, 9),
            _ev("%flash_window_pallas.5", at + 19, 10),
            _ev("%fusion.6", at + 29, 6), _ev("%fusion.7", at + 35, 9),
            _ev("%fusion.8", at + 44, 4)]


def test_gate_reader_from_a_synthetic_trace(chip_run, bench_catalog):
    gated = _reader(bench_catalog, "gated_attention")
    run = _run(chip_run, bench_catalog, _planes(_step(200) + _step(300)))
    assert gated.read(run) == pytest.approx({
        "attn_gate_ms_per_step": 0.019,
        "moe_shared_ms_per_step." + CELL: 0.006}, rel=1e-6)
    facts = run.facts["attn_gate"]
    assert facts["ops"] == 4
    assert facts["largest_ops_ms_per_step"][0] == ["%fusion.4",
                                                   pytest.approx(0.009)]
    # the accepted reader of ``moe_shared`` says nothing of this file, and
    # in its own cell this one leaves the shared expert to it
    latent = _reader(bench_catalog, "latent_attention")
    assert latent.read(run) == {}
    kimi = _run(chip_run, bench_catalog, _planes(_step(200) + _step(300)),
                config="kimi_vl_a3b_ep8_l5", cell="kimivl_mla_fused_1row")
    assert gated.read(kimi) == pytest.approx({"attn_gate_ms_per_step": 0.019})
    assert latent.read(kimi)["moe_shared_ms_per_step"] == pytest.approx(0.006)


def test_gate_reader_is_silent_where_there_is_nothing(chip_run,
                                                      bench_catalog):
    """A program without a registry (a parent commit), a program whose ops
    lie under neither scope (a commit before the gate), a run without a
    trace: no metric and no error; a rehearsal names what the registry has
    an op under."""
    gated = _reader(bench_catalog, "gated_attention")
    assert gated.read(_run(chip_run, bench_catalog, None,
                           mx=types.SimpleNamespace())) == {}
    assert gated.read(_run(chip_run, bench_catalog, None)) == {}
    ungated = {k: v for k, v in _OPS.items()
               if k in ("flash_window_pallas.5", "fusion.7", "fusion.8")}
    planes = _planes([_ev("%flash_window_pallas.5", 200, 5),
                      _ev("%fusion.7", 300, 9), _ev("%fusion.8", 320, 4)])
    run = _run(chip_run, bench_catalog, planes, ops=ungated)
    assert gated.read(run) == {} and "attn_gate" not in run.facts
    for other, cell in (("opt_6b7_l2", "opt6b7_fused_s2048"),
                        ("mellum2_12b_a2b5_ep8_l4", "mellum2_fused_s8192"),
                        ("resnet50_v1", "resnet50_fused_b256")):
        assert gated.read(_run(chip_run, bench_catalog, planes, ops=ungated,
                               config=other, cell=cell)) == {}
    named = gated.read(_run(chip_run, bench_catalog, None, rehearse=True,
                            peaks=None))
    assert set(named) == {"attn_gate_ms_per_step",
                          "moe_shared_ms_per_step." + CELL}
    assert gated.read(_run(chip_run, bench_catalog, None, ops=ungated,
                           rehearse=True, peaks=None)) == {}


def test_accepted_readers_at_this_shape(bench_catalog):
    """``layer_metrics/moe.py`` and ``mixed_flash.py`` as they are, read over
    this configuration: four routed layers, 2048 balanced assignments on the
    share (256 an expert); one full and four window layers at H 32, G 4,
    D 128, W 2048 over S 4096."""
    moe = _reader(bench_catalog, "moe")
    mixed = _reader(bench_catalog, "mixed_flash")
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s4096")
    assert moe.routed_layers(sizes) == 4
    assert moe.balanced_assignments(sizes, traffic) == 4096 * 8 * 8 / 128
    assert moe.balanced_assignments(sizes, traffic) / 8 == 256
    flops, _ = moe.needs(2048, 2048, 1024, 8, 2)
    assert flops == 3 * 3 * 2 * 2048 * 2048 * 1024
    assert [list(sizes["layer_types"]).count(k) for k in (
        "full_attention", "sliding_attention")] == [1, 4]
    assert mixed.keys_seen(4096) == 4096 * 4097 // 2
    assert mixed.keys_seen(4096, 2048) == 2048 * 2049 // 2 + 2048 * 2048
    (f_flops, f_bytes), (b_flops, _) = mixed.needs(1, 32, 4, 4096, 128, 2048,
                                                   2)
    assert f_flops == 2 * 2 * 32 * 128 * mixed.keys_seen(4096, 2048)
    assert b_flops == 7 * 2 * 32 * 128 * mixed.keys_seen(4096, 2048)
    assert f_bytes == 2 * (32 + 4) * 4096 * 128 * 2
    # 32 x 512 FLOP a visited pair forward, ISSUE 40's count
    assert f_flops == 32 * 512 * mixed.keys_seen(4096, 2048)
