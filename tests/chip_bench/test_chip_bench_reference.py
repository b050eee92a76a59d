"""Each configuration's plain float32 reference against the Gluon Block, at
a small size on the CPU; and the flash forward kernel compiled at the OPT
cell's shape for a described (not attached) v5e.

Tolerance 1e-4, largest |logit error| over largest |reference logit|: both
sides compute in float32 on the CPU and differ only in the order of sums
(measured 3e-7 to 9e-5 here).  The knock-outs show what the tolerance
catches: a dropped bias, LayerNorm, residual block or shortcut moves the
logits by far more.
"""
import numpy as np
import pytest


def _block_and_reference(mx, module, sizes, x, perturb=None, seed=7):
    """As the harness compares them: the Block in train mode, over the
    sizes with the configuration's ``first_loss_with`` applied (the plain
    reference draws no dropout mask)."""
    import jax
    import jax.numpy as jnp
    sizes = dict(sizes, **sizes.get("first_loss_with", {}))
    mx.random.seed(seed)
    net = module.build(sizes)
    net.initialize(module.initializer(sizes))
    with mx.autograd.train_mode():
        got = net(x).asnumpy()
    rs = np.random.RandomState(seed)
    params = {}
    for name, p in net.collect_params().items():
        v = np.asarray(p.data().asnumpy(), np.float32)
        if name.endswith(("_bias", "_beta")):
            # zero at initialisation: give them values, in the Block too,
            # so that a reference without them would be caught
            v = rs.normal(0, 0.5, v.shape).astype(np.float32)
            p.set_data(mx.nd.array(v))
        params[name[len(module.PREFIX):]] = jnp.asarray(v)
    with mx.autograd.train_mode():
        got = net(x).asnumpy()
    if perturb:
        perturb(params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(module.reference(sizes, params,
                                           jnp.asarray(x.asnumpy())))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _zero(*suffixes):
    def perturb(params):
        hit = [n for n in params if n.endswith(suffixes)]
        assert hit, suffixes
        for n in hit:
            params[n] = params[n] * 0
    return perturb


RESNET_SIZES = {
    "thumbnail18": {},       # the rehearsal's own sizes
    "bottleneck50_64px": {"model": "resnet50_v1", "num_layers": 50,
                          "block": "bottleneck", "stage_blocks": [3, 4, 6, 3],
                          "stage_channels": [64, 256, 512, 1024, 2048],
                          "classes": 20, "image_size": 64,
                          "thumbnail": False},
}


@pytest.mark.parametrize("variant", sorted(RESNET_SIZES))
def test_resnet_reference_agrees_with_the_block(bench_catalog, variant):
    import incubator_mxnet_tpu as mx
    sizes, module = bench_catalog.config("resnet50_v1", rehearse=True)
    sizes = dict(sizes, **RESNET_SIZES[variant])
    x = mx.nd.array(np.random.RandomState(0).rand(
        4, 3, sizes["image_size"], sizes["image_size"]).astype(np.float32))
    assert _block_and_reference(mx, module, sizes, x) < 1e-4


@pytest.mark.parametrize("knock_out", [
    ("stage2_conv2_weight",),            # a residual block's trunk
    ("stage3_batchnorm2_gamma",),        # a shortcut's BatchNorm
    ("dense0_bias",)])
def test_resnet_reference_tolerance_catches(bench_catalog, knock_out):
    import incubator_mxnet_tpu as mx
    sizes, module = bench_catalog.config("resnet50_v1", rehearse=True)
    x = mx.nd.array(np.random.RandomState(0).rand(4, 3, 32, 32)
                    .astype(np.float32))
    err = _block_and_reference(mx, module, sizes, x, _zero(*knock_out))
    assert err > module.TOLERANCE["block_f32"]


def _tokens(mx, sizes, rows=2, seq=128):
    return mx.nd.array(np.random.RandomState(0).randint(
        0, sizes["vocab_size"], (rows, seq)), dtype=np.int32)


def test_opt_reference_agrees_with_the_block(bench_catalog):
    import incubator_mxnet_tpu as mx
    sizes, module = bench_catalog.config("opt_6b7_l2", rehearse=True)
    assert _block_and_reference(mx, module, sizes, _tokens(mx, sizes)) < 1e-4
    # tied: one table serves the embedding and the head
    tables = [n for n, p in module.build(sizes).collect_params().items()
              if tuple(p.shape) == (sizes["vocab_size"], sizes["hidden_size"])]
    assert tables == [module.PREFIX + "embedding0_weight"]


def test_opt_trains_with_dropout_and_is_checked_without(bench_catalog):
    """The timed model draws dropout masks in train mode, as published, so
    it leaves the reference; the model of ``first_loss_with`` draws none.
    Both have the same parameters by name: the step's program differs by
    the masks alone."""
    import incubator_mxnet_tpu as mx
    sizes, module = bench_catalog.config("opt_6b7_l2", rehearse=True)
    assert sizes["dropout"] == 0.1 and sizes["first_loss_with"]
    x = _tokens(mx, sizes)
    timed = dict(sizes, first_loss_with={})
    assert _block_and_reference(mx, module, timed, x) > 1e-2
    assert _block_and_reference(mx, module, sizes, x) < 1e-4
    quiet = dict(sizes, **sizes["first_loss_with"])
    assert (list(module.build(timed).collect_params())
            == list(module.build(quiet).collect_params()))


@pytest.mark.parametrize("knock_out", [
    ("decoderlayer1_dense1_weight",),                 # a layer's FFN
    ("decoderlayer0_multiheadattention0_v_bias",),    # a projection bias
    ("decoderlayer1_layernorm1_gamma",),
    ("pos_embed",)])
def test_opt_reference_tolerance_catches(bench_catalog, knock_out):
    import incubator_mxnet_tpu as mx
    sizes, module = bench_catalog.config("opt_6b7_l2", rehearse=True)
    err = _block_and_reference(mx, module, sizes, _tokens(mx, sizes),
                               _zero(*knock_out))
    assert err > module.TOLERANCE["block_f32"]


def test_reference_loss_is_mean_cross_entropy(bench_catalog):
    import jax.numpy as jnp
    _, module = bench_catalog.config("opt_6b7_l2", rehearse=True)
    logits = jnp.log(jnp.asarray([[[0.5, 0.25, 0.25], [0.1, 0.1, 0.8]]]))
    labels = jnp.asarray([[0.0, 2.0]])
    want = -(np.log(0.5) + np.log(0.8)) / 2
    assert abs(float(module.reference_loss(logits, labels)) - want) < 1e-6


# ---------------------------------------------------------------------------
# the kernel at the cell's shape, for a chip that is described, not attached
# (on-chip-measurement guide, section 2: nothing at import, one file)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever libtpu raises here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


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


def test_flash_forward_compiles_at_the_opt_cells_shape(
        bench_catalog, topo, no_compile_cache):
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu.ops.registry import get_op

    sizes, _ = bench_catalog.config("opt_6b7_l2")
    traffic = bench_catalog.traffic("fused_s2048")
    heads = sizes["num_attention_heads"]
    shape = (traffic["batch_per_chip"], heads, traffic["seq_len"],
             sizes["hidden_size"] // heads)
    assert shape == (4, 32, 2048, 128)
    spec = jax.ShapeDtypeStruct(
        shape, jnp.dtype(traffic["dtype"]),
        sharding=SingleDeviceSharding(topo.devices[0]))
    op = get_op("_contrib_FlashAttention")
    compiled = jax.jit(functools.partial(op.fcompute, causal=True)).lower(
        spec, spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
