"""The configuration ``kimi_vl_a3b_ep8_l5`` (``configs/kimi_vl_moe.py``), the
two layers its cell brought (``gluon.nn.LatentAttention``,
``ExpertParallelMoE(shared_hidden_size=...)``) and the reader
(``layer_metrics/latent_attention.py``), on the CPU: the latent layer and the
whole Block against the module's plain reference at the rehearsal's size,
value, loss and gradients; the departures a tolerance would hide; the eight
shares of a routed layer plus the shared expert once against the uncut
layer; the file's published widths; the reader's needs counted by hand and
its metrics read from a synthetic trace.  The cell's rehearsals are
``tests/chip_bench/test_chip_bench_rehearsal.py``'s, which find it in
``BENCHMARK.json``; the kernels at two widths are
``tests/test_flash_attention.py``'s.
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
CELL, CONFIG = "kimivl_mla_fused_1row", "kimi_vl_a3b_ep8_l5"
US = 1000


@pytest.fixture(scope="module")
def kimi(bench_catalog):
    """(the rehearsal's sizes, module): d 64, 4 heads, a latent of 24, keys
    of 16 + 8 over values of 12, a dense layer of 128, top-6 of 16 experts of
    32 with 2 held beside a shared expert of 64, one dense + four routed."""
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
# the layers and the Block against the plain reference
# ---------------------------------------------------------------------------

def test_latent_layer_agrees_with_the_reference(kimi):
    """``gluon.nn.LatentAttention`` alone, at unequal widths, against the
    reference's ``_attention`` on the same weights: value and the gradient
    of every projection and of the latent's gain."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = kimi
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    layer = nn.LatentAttention(
        d, heads, sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        rotary_base=float(sizes["rope_theta"]),
        latent_norm_epsilon=sizes["latent_norm_eps"], prefix="self_attn_")
    mx.random.seed(5)
    layer.initialize(mx.init.Normal(0.3))
    rs = np.random.RandomState(1)
    x = rs.randn(2, 80, d).astype(np.float32)
    gain = 1.0 + 0.1 * rs.randn(sizes["kv_lora_rank"]).astype(np.float32)
    layer(mx.nd.array(x))
    layer.kv_norm.gamma.set_data(mx.nd.array(gain))
    fn, values = functionalize(layer, mx.nd.array(x), train=True)
    shapes = {n: tuple(v.shape) for n, v in values.items()}
    assert shapes == {
        "self_attn_q_weight": (heads * 24, d),
        "self_attn_kv_a_weight": (24 + 8, d),
        "self_attn_kv_norm_gamma": (24,),
        "self_attn_kv_b_weight": (heads * (16 + 12), 24),
        "self_attn_out_weight": (d, heads * 12)}
    g = rs.randn(2, 80, d).astype(np.float32)

    def ours(p):
        out = fn(p, jnp.asarray(x))
        return out[0] if isinstance(out, (tuple, list)) else out

    got, vjp = jax.vjp(ours, values)
    want, want_vjp = jax.vjp(
        lambda p: module._attention(sizes, p, jnp.asarray(x)), dict(values))
    assert _err(np.asarray(got), np.asarray(want)) < 1e-5
    (got_g,), (want_g,) = vjp(jnp.asarray(g)), want_vjp(jnp.asarray(g))
    for name in shapes:
        assert _err(np.asarray(got_g[name]),
                    np.asarray(want_g[name])) < 1e-4, name


def test_block_agrees_with_the_reference(kimi):
    sizes, module = kimi
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
    # ONE shared expert of n_shared_experts x moe_intermediate_size
    assert shapes["layers_2_mlp_shared_experts_w1_weight"] == (2 * 32, 64)
    assert shapes["layers_2_mlp_shared_experts_w2_weight"] == (64, 2 * 32)
    assert shapes["layers_3_self_attn_kv_a_weight"] == (24 + 8, 64)
    assert shapes["lm_head_weight"] == shapes["embed_tokens_weight"] == (
        512, 64)
    assert [n for n in shapes if n.endswith("bias")] == [
        "layers_%d_mlp_expert_bias" % i for i in range(1, 5)]


def test_loss_and_gradients_agree_with_the_reference(kimi):
    """The fused step's own differentiation (``jax.grad`` through the
    functionalized Block) against the reference's, parameter by parameter;
    no gradient reaches the selection bias."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize
    sizes, module = kimi
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
        if name.endswith("expert_bias"):
            assert not g.any() and not got[name].any()
            continue
        scale = max(np.abs(g).max(), 1e-8)
        assert np.abs(got[name] - g).max() / scale < 2e-3, name


def _only_head_zero_sees_the_rotary_key(k_pe, heads):
    import jax.numpy as jnp
    first = jnp.arange(heads)[None, None, :, None] == 0
    return jnp.where(first, k_pe[:, :, None], 0.0)


# a departure of the mathematics that no parameter carries: what to put in
# the reference module's place, or which size to change
DEPARTURES = {
    "latent_norm_left_out": {"_latent_norm": lambda cfg, c, gain: c},
    "rotary_key_not_shared": {
        "_shared_key": _only_head_zero_sees_the_rotary_key},
    "scale_of_the_nope_part_alone": {
        "_score_scale": lambda cfg: cfg["qk_nope_head_dim"] ** -0.5},
    "shared_expert_dropped": {"n_shared_experts": 0},
    "no_scaling_factor": {"routed_scaling_factor": 1.0},
    "latent_norms_epsilon_the_layers": {"latent_norm_eps": 1e-1},
    "raw_scores_not_renormalised": {"norm_topk_prob": False},
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_reference_tolerance_catches_a_departure(kimi, monkeypatch,
                                                 departure):
    """The latent norm left out, the rotary key kept from all heads but one,
    the scale at the root of 128, the shared expert dropped, the scaling
    factor: each moves the logits by more than ``block_f32`` allows."""
    sizes, module = kimi
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    assert _err(got, _reference(module, sizes, params, tokens)) < 1e-5
    changed = dict(sizes)
    for key, value in DEPARTURES[departure].items():
        if callable(value):
            monkeypatch.setattr(module, key, value)
        else:
            changed[key] = value
    want = _reference(module, changed, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


@pytest.mark.parametrize("knock_out", [
    "layers_0_self_attn_kv_a_weight", "layers_3_self_attn_kv_b_weight",
    "layers_2_self_attn_kv_norm_gamma", "layers_0_mlp_w3_weight",
    "layers_1_mlp_shared_experts_w2_weight", "layers_4_mlp_expert_w1",
    "layers_2_post_attention_layernorm_gamma"])
def test_reference_tolerance_catches_a_part_left_out(kimi, knock_out):
    import jax.numpy as jnp
    sizes, module = kimi
    net = _net(module, sizes)
    tokens = _tokens(sizes)
    got = _logits(net, tokens)
    params = _params(module, net)
    params[knock_out] = jnp.zeros_like(params[knock_out])
    want = _reference(module, sizes, params, tokens)
    assert _err(got, want) > module.TOLERANCE["block_f32"]


def test_the_shared_expert_weighs_one_for_every_token(kimi):
    """Doubling the shared expert's down-projection doubles what it adds
    and leaves the routed sum alone: it is outside the router's weights."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    rs = np.random.RandomState(2)
    x = rs.randn(48, 32).astype(np.float32)

    def layer(shared):
        mx.random.seed(11)
        moe = ExpertParallelMoE(
            16, 8, top_k=2, dispatch="grouped", router="sigmoid",
            scaling=2.5, gated=True, in_units=32, ep_axis=None,
            shared_hidden_size=shared, prefix="moe_")
        moe.initialize(mx.init.Normal(0.3))
        return moe

    plain, both = layer(None), layer(24)
    assert plain.shared_experts is None
    assert sorted(plain.collect_params()) == [
        "moe_expert_w1", "moe_expert_w2", "moe_expert_w3", "moe_gate_weight"]
    routed = np.asarray(plain(mx.nd.array(x))._read())
    for name, p in plain.collect_params().items():
        both.collect_params()[name].set_data(p.data())
    total = np.asarray(both(mx.nd.array(x))._read())
    mlp = both.shared_experts
    w1, w3, w2 = (np.asarray(b.weight.data()._read())
                  for b in (mlp.w1, mlp.w3, mlp.w2))
    gate = x @ w1.T
    want = (gate / (1.0 + np.exp(-gate)) * (x @ w3.T)) @ w2.T
    assert _err(total - routed, want) < 1e-5
    mlp.w2.weight.set_data(mx.nd.array(2.0 * w2))
    doubled = np.asarray(both(mx.nd.array(x))._read())
    assert _err(doubled - routed, 2.0 * want) < 1e-5
    # under an ``ep`` mesh the experts are sharded and the shared one is not
    sharded = ExpertParallelMoE(16, 8, top_k=2, gated=True, in_units=32,
                                shared_hidden_size=24, prefix="ep_")
    assert sharded.expert_w1.sharding == ("ep", None, None)
    assert all(getattr(p, "sharding", None) is None
               for p in sharded.shared_experts.collect_params().values())
    with pytest.raises(ValueError, match="in_units"):
        ExpertParallelMoE(16, 8, shared_hidden_size=24)
    assert jnp.asarray(total).dtype == jnp.float32


def test_shared_traces_are_counted(kimi):
    from incubator_mxnet_tpu import telemetry

    def count():
        snap = telemetry.registry().snapshot().get(
            "graft_moe_shared_traces_total", {"samples": []})
        return sum(s["value"] for s in snap["samples"])

    sizes, module = kimi
    before = count()
    _logits(_net(module, sizes), _tokens(sizes, rows=1, seq=16))
    assert count() - before == 4                # four routed layers


def test_the_eight_shares_and_the_shared_expert_once_add_up(kimi):
    """One routed layer at d 32, experts of 16, top-6 of 64 by sigmoid with
    a selection bias, renormalised, scaled by 2.446, beside a shared expert
    of 24: the program's eight routed shares (``experts_held=(8 i, 8)``,
    the same router, each its slice of the experts, no shared expert) plus
    the shared expert counted once add up to what the reference gives for
    the layer with all 64 experts held."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import ExpertParallelMoE
    _, module = kimi
    d, h, experts, held, k, shared = 32, 16, 64, 8, 6, 24
    rs = np.random.RandomState(3)
    gate = rs.randn(d, experts).astype(np.float32)
    bias = 0.05 * rs.randn(experts).astype(np.float32)
    w1, w3 = (rs.randn(experts, d, h).astype(np.float32) * 0.3
              for _ in range(2))
    w2 = rs.randn(experts, h, d).astype(np.float32) * 0.3
    s1, s3 = (rs.randn(shared, d).astype(np.float32) * 0.3 for _ in range(2))
    s2 = rs.randn(d, shared).astype(np.float32) * 0.3
    x = rs.randn(96, d).astype(np.float32)

    def layer(first, with_shared, prefix):
        moe = ExpertParallelMoE(
            h, experts, top_k=k, dispatch="grouped",
            experts_held=(first, held), router="sigmoid",
            selection_bias=True, norm_topk=True, scaling=2.446, gated=True,
            in_units=d, ep_axis=None, prefix=prefix,
            shared_hidden_size=shared if with_shared else None)
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

    parts = [np.asarray(layer(8 * i, False, "share%d_" % i)(
        mx.nd.array(x))._read()) for i in range(experts // held)]
    assert all(np.abs(p).max() > 0 for p in parts)
    # what every chip computes alike: this chip's layer less its routed part
    chip0 = np.asarray(layer(0, True, "chip0_")(mx.nd.array(x))._read())
    once = chip0 - parts[0]
    uncut = {"n_routed_experts": experts,
             "n_routed_experts_published": experts, "num_experts_per_tok": k,
             "norm_topk_prob": True, "routed_scaling_factor": 2.446,
             "n_shared_experts": 1}
    weights = {"mlp_gate_weight": gate, "mlp_expert_bias": bias,
               "mlp_expert_w1": w1, "mlp_expert_w3": w3, "mlp_expert_w2": w2,
               "mlp_shared_experts_w1_weight": s1,
               "mlp_shared_experts_w3_weight": s3,
               "mlp_shared_experts_w2_weight": s2}
    want = np.asarray(module._routed(
        uncut, {n: jnp.asarray(v) for n, v in weights.items()},
        jnp.asarray(x)))
    assert _err(sum(parts) + once, want) < 1e-5
    # counted eight times it is not the layer, nor left out
    assert _err(sum(parts) + 8 * once, want) > 1e-2
    assert _err(sum(parts), want) > 1e-2
    # and this chip's layer is what the reference gives for its share
    share0 = dict(uncut, n_routed_experts=held)
    want0 = np.asarray(module._routed(
        share0, {n: jnp.asarray(v[:held] if n.startswith("mlp_expert_w")
                                else v) for n, v in weights.items()},
        jnp.asarray(x)))
    assert _err(chip0, want0) < 1e-5


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

# the catalog's config for Kimi-VL-A3B-Instruct (model-configs guide,
# architectures.jsonl), every key
PUBLISHED = {
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2,
    "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
    "moe_layer_freq": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


def test_file_keeps_the_published_widths(bench_catalog):
    """Every key of the catalog's config is in the file at its published
    value but the two cut ones, which are the file's ``reduced`` beside the
    vocabulary rows held and the readers' alias; what the issue asks the
    file to state is stated; the bytes are ISSUE 32's."""
    sizes, module = bench_catalog.config(CONFIG)
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8}
    assert {k: sizes[k] for k in PUBLISHED} == dict(PUBLISHED, **cut)
    assert sorted(sizes["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "num_experts",
        "vocab_rows_held"])
    # the same numbers under the names the accepted readers use
    assert (sizes["num_experts"], sizes["num_experts_published"],
            sizes["num_dense_layers"]) == (
        sizes["n_routed_experts"], sizes["n_routed_experts_published"],
        sizes["first_k_dense_replace"]) == (8, 64, 1)
    assert sizes["vocab_rows_held"] == 163840 // 8 == 20480
    assert sizes["layer_types"] == ["full_attention"] * 5
    for key in ("text_only", "latent_norm_eps", "rotary_layout", "optimizer",
                "auxiliary_loss", "selection_bias", "initializer"):
        assert sizes["assumed"][key], key
    assert "Muon" in sizes["assumed"]["optimizer"]
    for key in ("source", "deployment", "depth_note", "expert_load_note",
                "first_loss_with", "first_loss_note", "readers_names_note"):
        assert sizes[key], key
    assert "8 chips" in sizes["deployment"]
    assert sizes["input"]["vocab_key"] == "vocab_rows_held"
    with pytest.raises(ValueError, match="readers' name"):
        module._checked(dict(sizes, num_experts=64))
    # 568,484,352 parameters at the real widths, counted from the shapes
    d = 2048
    attn = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    dense = attn + 3 * d * 11264 + 2 * d
    routed = attn + 3 * d * 2816 + d * 64 + 8 * 3 * d * 1408 + 2 * d
    assert (attn, dense, routed) == (13763072, 82973184, 100405760)
    assert dense + 4 * routed + 2 * 20480 * d + d == 568484352
    assert module.attention_params(sizes) == attn - 512
    traffic = bench_catalog.traffic("fused_s4096")
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["driver"], traffic["dtype"]) == (1, 4096, "fused",
                                                     "bfloat16")
    assert module.matmul_params(sizes) == pytest.approx(
        5 * (attn - 512) + 3 * d * 11264
        + 4 * (3 * d * 2816 + d * 64 + 6 * 8 / 64 * 3 * d * 1408)
        + 20480 * d)
    assert module.matmul_params(sizes) == pytest.approx(275.6e6, rel=1e-3)
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        6 * module.matmul_params(sizes)
        + 5 * 3 * 16 * (2 * 192 + 2 * 128) * 2048.5)
    assert module.train_flops_per_sample(sizes, traffic) == pytest.approx(
        1.968e9, rel=1e-3)


def test_the_cell_is_declared_as_the_issue_names_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fused_s4096", 1)
    (config,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert config["source"] == ("https://huggingface.co/moonshotai/"
                                "Kimi-VL-A3B-Instruct/blob/main/config.json")
    assert [w["name"] for w in spec["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in spec[g]
            if m.get("workloads") == [CELL]}
    # no end-to-end entry of its own: that list is a benchmark PR's to change
    assert not [m for m in spec["end_to_end"] if CELL in m["name"]]
    assert mine == {
        "place_span_ms_p50." + CELL, "dispatch_span_ms_p50." + CELL,
        "moe_router_ms_per_step." + CELL, "moe_dispatch_ms_per_step." + CELL,
        "moe_experts_ms_per_step." + CELL, "moe_experts_roofline." + CELL,
        "moe_held_assignment_pct." + CELL, "moe_load_max_over_mean." + CELL,
        "latent_flash_fwd_ms_per_step", "latent_flash_fwd_roofline",
        "latent_flash_bwd_ms_per_step", "latent_flash_bwd_roofline",
        "latent_proj_ms_per_step", "moe_shared_ms_per_step"}
    for m in spec["per_layer"]:
        if m["name"] in mine:
            assert m["moves"] == "samples_per_s_per_chip", m["name"]
    # no list that was there names the cell; PR 34's four metrics of the
    # routed layer (``moe_step.py``) came later, with the three routed
    # cells in one list each
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CELL in m.get("workloads", ()) and not m["name"].startswith(
                    ("moe_step_", "moe_experts_rows_")):
                assert m["workloads"] == [CELL], m["name"]


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("layer_metrics_" + name)]
    return reader


def test_latent_needs_by_hand(bench_catalog):
    latent = _reader(bench_catalog, "latent_attention")
    sizes, _ = bench_catalog.config(CONFIG)
    assert latent.widths(sizes) == (192, 128)
    (f_flops, f_bytes), (b_flops, b_bytes) = latent.needs(
        batch=1, heads=16, seq=4096, qk_dim=192, v_dim=128, dtype_bytes=2)
    pairs = 16 * (4096 * 4097 // 2)
    assert f_flops == pairs * (2 * 192 + 2 * 128)   # ISSUE 32: a pair a head
    assert b_flops == pairs * 2 * (4 * 192 + 3 * 128)
    rows = 16 * 4096 * 2
    assert f_bytes == rows * (192 + 192 + 128 + 128)        # q, k; v, o
    assert b_bytes == rows * (4 * 192 + 4 * 128)
    assert f_flops / 197e12 == pytest.approx(0.4362e-3, rel=1e-3)
    # at one width it is mixed_flash.py's count for full heads
    mixed = _reader(bench_catalog, "mixed_flash")
    assert latent.needs(1, 32, 8192, 128, 128, 2)[0] == mixed.needs(
        1, 32, 32, 8192, 128, None, 2)[0]
    assert latent.under("jit(f)/xray:backward/transpose(jvp(attn_latent))/"
                        "flash_attention_bwd/pallas_call", "attn_latent")
    assert not latent.under("jit(f)/attn_latent_proj/dot", "attn_latent")
    assert not latent.under(None, "attn_latent")


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


_OPS = {    # short HLO name -> op_name path, as telemetry.programs() has them
    "flash_attention_pallas.1":
        "jit(dp_train_step)/xray:forward/kimivl_layers_0_self_attn/"
        "attn_latent/flash_attention_pallas/pallas_call",
    "flash_attention_bwd_dkv.2":
        "jit(dp_train_step)/xray:backward/transpose(jvp("
        "kimivl_layers_0_self_attn))/transpose(jvp(attn_latent))/"
        "flash_attention_bwd/pallas_call",
    "flash_attention_bwd_dq.3":
        "jit(dp_train_step)/xray:backward/transpose(jvp("
        "kimivl_layers_0_self_attn))/transpose(jvp(attn_latent))/"
        "flash_attention_bwd/pallas_call",
    "flash_attention_pallas.9":         # a kernel of some other layer
        "jit(dp_train_step)/xray:forward/other_attn/attn_full/"
        "flash_attention_pallas/pallas_call",
    "fusion.4":
        "jit(dp_train_step)/xray:forward/kimivl_layers_0_self_attn/"
        "attn_latent_proj/kimivl_layers_0_self_attn_q_/dot_general",
    "fusion.5":
        "jit(dp_train_step)/xray:backward/transpose(jvp("
        "kimivl_layers_0_self_attn))/transpose(jvp(attn_latent_proj))/"
        "rope/mul",
    "fusion.6":
        "jit(dp_train_step)/xray:forward/kimivl_layers_1_mlp/moe_shared/"
        "kimivl_layers_1_mlp_shared_experts_w1_/dot_general",
    "fusion.7":
        "jit(dp_train_step)/xray:forward/kimivl_layers_1_mlp/moe_experts/gmm",
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
    telemetry = types.SimpleNamespace(
        programs=lambda: programs, spans=lambda since=None: [],
        registry=lambda: types.SimpleNamespace(snapshot=lambda: {}))
    args = dict(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded) if recorded else None,
        stamps=[], traced_stamps=(None, None), rehearse=False, sizes=sizes,
        traffic=bench_catalog.traffic("fused_s4096"),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    args.update(kw)
    return chip_run.Run(**args)


def test_latent_reader_from_a_synthetic_trace(chip_run, bench_catalog):
    """The window [200, 400] us holds 2 steps.  A step: the latent forward
    kernel 10, its backward kernels 12 + 8, another layer's forward kernel
    5 (not under ``attn_latent``: not counted), two ops under
    ``attn_latent_proj`` 4 + 2 (one below a Dense's name, one below
    ``rope``), the shared expert 6, the routed experts 9."""
    def step(at):
        return [_ev("%flash_attention_pallas.1", at, 10),
                _ev("%flash_attention_bwd_dkv.2", at + 10, 12),
                _ev("%flash_attention_bwd_dq.3", at + 22, 8),
                _ev("%flash_attention_pallas.9", at + 30, 5),
                _ev("%fusion.4", at + 35, 4), _ev("%fusion.5", at + 39, 2),
                _ev("%fusion.6", at + 41, 6), _ev("%fusion.7", at + 47, 9)]
    latent = _reader(bench_catalog, "latent_attention")
    run = _run(chip_run, bench_catalog, _planes(step(200) + step(300)))
    fwd = 16 * (4096 * 4097 // 2) * (2 * 192 + 2 * 128) / 197e12
    bwd = 16 * (4096 * 4097 // 2) * 2 * (4 * 192 + 3 * 128) / 197e12
    assert latent.read(run) == pytest.approx({
        "latent_flash_fwd_ms_per_step": 0.010,
        "latent_flash_bwd_ms_per_step": 0.020,
        "latent_flash_fwd_roofline": 100 * 5 * fwd / 10e-6,
        "latent_flash_bwd_roofline": 100 * 5 * bwd / 20e-6,
        "latent_proj_ms_per_step": 0.006,
        "moe_shared_ms_per_step": 0.006}, rel=1e-6)
    facts = run.facts["latent_flash"]
    assert facts["latent_flash_fwd"]["calls_per_step"] == 5
    assert (facts["latent_flash_bwd"]["qk_dim"],
            facts["latent_flash_bwd"]["v_dim"]) == (192, 128)
    assert facts["latent_flash_bwd"]["roof"] == "bf16 FLOP/s"


def test_latent_reader_is_silent_where_there_is_nothing(chip_run,
                                                        bench_catalog):
    """Another configuration, a program without a registry (a parent
    commit), a program whose ops lie under none of the scopes, a run without
    a trace: no metric and no error; a rehearsal names what the registry has
    an op under."""
    latent = _reader(bench_catalog, "latent_attention")
    for other in ("opt_6b7_l2", "lfm2_8b_a1b_ep4_l5",
                  "mellum2_12b_a2b5_ep8_l4", "resnet50_v1"):
        sizes, _ = bench_catalog.config(other)
        assert latent.read(_run(chip_run, bench_catalog, None,
                                sizes=sizes)) == {}
    assert latent.read(_run(chip_run, bench_catalog, None,
                            mx=types.SimpleNamespace())) == {}
    assert latent.read(_run(chip_run, bench_catalog, None)) == {}
    planes = _planes([_ev("%flash_attention_pallas.9", 200, 5),
                      _ev("%fusion.7", 300, 9)])
    assert latent.read(_run(chip_run, bench_catalog, planes)) == {}
    named = latent.read(_run(chip_run, bench_catalog, None, rehearse=True,
                             peaks=None))
    assert set(named) == {
        "latent_flash_fwd_ms_per_step", "latent_flash_fwd_roofline",
        "latent_flash_bwd_ms_per_step", "latent_flash_bwd_roofline",
        "latent_proj_ms_per_step", "moe_shared_ms_per_step"}
    only_shared = {k: v for k, v in _OPS.items() if k == "fusion.6"}
    assert set(latent.read(_run(
        chip_run, bench_catalog, None, ops=only_shared, rehearse=True,
        peaks=None))) == {"moe_shared_ms_per_step"}


def test_expert_needs_at_top_6(bench_catalog):
    """``layer_metrics/moe.py`` as it is, read over this configuration: four
    routed layers, 3072 balanced assignments on the share (384 an expert)."""
    moe = _reader(bench_catalog, "moe")
    sizes, _ = bench_catalog.config(CONFIG)
    traffic = bench_catalog.traffic("fused_s4096")
    assert moe.routed_layers(sizes) == 4
    assert moe.balanced_assignments(sizes, traffic) == 4096 * 6 * 8 / 64
    assert moe.balanced_assignments(sizes, traffic) / 8 == 384
    flops, _ = moe.needs(3072, 2048, 1408, 8, 2)
    assert flops == 3 * 3 * 2 * 3072 * 2048 * 1408
