"""``Embedding``'s backward rule (``ops/tensor.py``): the table's gradient
as one scatter-add of whole rows or as four, a column block each; which of
the two the shapes, dtype and platform choose, and that both are the plain
``zeros.at[ids].add(g)``."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.ops import tensor as T

ROWS, DIM = 24, 16
IDS = {
    "duplicates": [3, 1, 3, 3, 7, 1, 0, 23],
    "out_of_range": [-5, 0, 2, 23, 24, 99, -1, 2],     # clipped, both sides
    "two_dim": [[3, 1, 3, 9], [7, 0, 40, -2]],         # (B, T)
}
FORMS = {"scatter": T._table_grad_scatter,
         "column_parts": T._table_grad_column_parts}
TOL = {jnp.float32: 1e-6, jnp.bfloat16: 2e-2}          # of the largest entry


def _plain(ids, g, rows):
    """The reference: clipped ids, float32 accumulation."""
    ids = jnp.clip(jnp.asarray(ids).reshape(-1), 0, rows - 1)
    flat = jnp.asarray(g, jnp.float32).reshape(ids.shape[0], -1)
    return jnp.zeros((rows, flat.shape[1]), jnp.float32).at[ids].add(flat)


def _cotangent(ids, dtype, seed=0):
    shape = np.shape(ids) + (DIM,)
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _close(got, want, dtype):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL[dtype] * np.abs(want).max()


@pytest.fixture
def forced(monkeypatch):
    """Make the rule answer with one form, whatever the operands."""
    def force(form):
        monkeypatch.setattr(T, "embedding_grad_form", lambda *a: form)
    return force


@pytest.mark.parametrize("case", sorted(IDS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_each_form_is_the_plain_scatter_add(form, dtype, case):
    ids = jnp.asarray(IDS[case], jnp.int32)
    g = _cotangent(ids, dtype)
    got = FORMS[form](ids, g, ROWS)
    assert got.dtype == dtype and got.shape == (ROWS, DIM)
    _close(got, _plain(ids, g, ROWS), dtype)


@pytest.mark.parametrize("case", sorted(IDS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_op_under_each_form_of_the_rule(forced, form, dtype, case):
    """``jax.grad`` of the operator itself, eager and jitted: a concrete
    cotangent takes the forced form on this CPU, a traced one the branch of
    the platform the program is lowered for."""
    forced(form)
    ids = jnp.asarray(IDS[case], jnp.int32)
    weight = jnp.asarray(np.random.RandomState(1).randn(ROWS, DIM), dtype)
    g = _cotangent(ids, dtype, seed=2)

    def loss(w):
        out = T._embedding(ids.astype(jnp.float32), w,     # MXNet's ids
                           input_dim=ROWS, output_dim=DIM)
        return (out.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    want = _plain(ids, g, ROWS)
    _close(jax.grad(loss)(weight), want, dtype)
    _close(jax.jit(jax.grad(loss))(weight), want, dtype)
    # the lookup itself is the clipped take
    rows = jnp.clip(ids, 0, ROWS - 1)
    np.testing.assert_array_equal(
        np.asarray(T._embedding(ids, weight), np.float32),
        np.asarray(weight[rows], np.float32))


def test_an_integer_input_gets_no_gradient():
    ids = jnp.asarray(IDS["duplicates"], jnp.int32)
    weight = jnp.ones((ROWS, DIM))
    _, pull = jax.vjp(lambda w, i: T._lookup(ROWS, w, i), weight, ids)
    d_weight, d_ids = pull(jnp.ones(ids.shape + (DIM,)))
    assert d_ids.dtype == jax.dtypes.float0 and d_ids.shape == ids.shape
    assert float(d_weight.sum()) == ids.size * DIM
    assert 0 in mx.ops.registry.get_op("Embedding").nograd_inputs


class _Tied(gluon.HybridBlock):
    """A token table that is also the head: ``Dense(params=embed.params)``."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed = nn.Embedding(ROWS, DIM, prefix="embed_")
            self.head = nn.Dense(ROWS, flatten=False, in_units=DIM,
                                 use_bias=False, params=self.embed.params)

    def hybrid_forward(self, F, tokens):
        return self.head(self.embed(tokens))


def _tied_gradient(weight, tokens):
    """d/dW of sum(tanh(W[tokens] @ W^T)), the two uses written out."""
    def loss(w):
        return jnp.tanh(w[tokens] @ w.T).sum()
    return jax.grad(loss)(weight)


@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["imperative", "hybridized"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_tied_table_sums_both_uses_under_record(forced, form, hybridize):
    forced(form)
    net = _Tied(prefix="tied_")
    net.initialize(mx.init.Normal(0.5))
    if hybridize:
        net.hybridize()
    tokens = np.asarray(IDS["two_dim"]).clip(0, ROWS - 1)
    x = mx.nd.array(tokens.astype(np.float32))
    with autograd.record():
        loss = mx.nd.tanh(net(x)).sum()
    loss.backward()
    weight = net.embed.weight
    want = _tied_gradient(weight.data()._read(), jnp.asarray(tokens))
    _close(weight.grad()._read(), want, jnp.float32)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_grad_req_add_accumulates_over_two_passes(forced, form):
    forced(form)
    embed = nn.Embedding(ROWS, DIM, prefix="acc_")
    embed.initialize(mx.init.Normal(0.5))
    embed.weight.grad_req = "add"
    ids = np.asarray(IDS["duplicates"], np.float32)
    g = _cotangent(IDS["duplicates"], jnp.float32, seed=5)
    for _ in range(2):
        with autograd.record():
            loss = (embed(mx.nd.array(ids)) * mx.nd.NDArray(g)).sum()
        loss.backward()
    _close(embed.weight.grad()._read(),
           2 * _plain(IDS["duplicates"], g, ROWS), jnp.float32)


def test_jax_grad_of_the_hybridized_block_takes_the_rule():
    """The fused step's way: the Block traced as a pure function of its
    parameters, differentiated by ``jax.grad`` inside one ``jit``."""
    net = _Tied(prefix="pure_")
    net.initialize(mx.init.Normal(0.5))
    net.hybridize()
    tokens = jnp.asarray(np.asarray(IDS["two_dim"]).clip(0, ROWS - 1))
    weight = net.embed.weight.data()._read()
    before = _snap()

    @jax.jit
    def grad(w):
        def loss(w):
            out = T._embedding(tokens, w)
            return jnp.tanh(jnp.einsum("btd,vd->btv", out, w)).sum()
        return jax.grad(loss)(w)

    _close(grad(weight), _tied_gradient(weight, tokens), jnp.float32)
    assert _grew(before) == {"scatter": 1}      # rows of 16 columns


# ---------------------------------------------------------------------------
# the rule, as a function of (n, rows, dim, dtype, platform)
# ---------------------------------------------------------------------------

CELLS = {       # n looked-up rows, the table the chip holds
    "phi4flash_sambay_fused_1row": (4096, 25088, 2560),
    "opt6b7_fused_s2048": (8192, 50272, 4096),
    "kimivl_mla_fused_1row": (4096, 20480, 2048),
    "lfm2moe_fused_s8192": (8192, 16384, 2048),
    "mellum2_fused_s8192": (8192, 12288, 2304),
}


@pytest.mark.parametrize("cell, want", [
    ("phi4flash_sambay_fused_1row", "column_parts"),    # 2560: 2.4 us a row
    ("mellum2_fused_s8192", "scatter"),     # 2304: never over 0.62
    ("opt6b7_fused_s2048", "scatter"),      # 4096, a power of two: 0.37
    ("kimivl_mla_fused_1row", "scatter"),
    ("lfm2moe_fused_s8192", "scatter"),
])
def test_the_rule_at_the_benchmarks_shapes(cell, want):
    n, rows, dim = CELLS[cell]
    form = T.embedding_grad_form
    assert form(n, rows, dim, jnp.bfloat16, "tpu") == want
    assert form(n, rows, dim, jnp.float16, "tpu") == want
    # what was not measured stays the parent's: float32, every other chip
    assert form(n, rows, dim, jnp.float32, "tpu") == "scatter"
    assert form(n, rows, dim, jnp.bfloat16, "cpu") == "scatter"
    assert form(n, rows, dim, jnp.bfloat16, "gpu") == "scatter"


@pytest.mark.parametrize("n, rows, dim, want", [
    (4096, 25088, 5120, "column_parts"),    # the sweep's worst: 10.7 us a row
    (4096, 25088, 3584, "column_parts"),
    (4096, 25088, 4096, "scatter"),         # a power of two: never seen slow
    (4096, 25088, 2304, "scatter"),         # under 2560: 0.62 at worst
    (700, 33278, 650, "scatter"),           # a word LM's table
    (4096, 25088, 2562, "scatter"),         # no four equal blocks
    (4096, 200064, 2560, "column_parts"),   # Phi-4's whole table: 3.8 ms of
    (512, 200064, 2560, "scatter"),         # passes, worth it for 4096 rows
    (8, 25088, 2560, "scatter"),
    (0, 25088, 2560, "scatter"),
])
def test_the_rule_follows_width_table_and_rows(n, rows, dim, want):
    assert T.embedding_grad_form(n, rows, dim, jnp.bfloat16, "tpu") == want


def _snap():
    samples = telemetry.registry().snapshot().get(
        "graft_embedding_grad_traces_total", {"samples": []})["samples"]
    return {s["labels"]["path"]: s["value"] for s in samples}


def _grew(before):
    after = _snap()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_the_counter_says_which_form_a_traced_backward_took():
    n, rows, dim = 64, 48, 2560         # wide bf16 rows, no power of two
    ids = (jnp.arange(n, dtype=jnp.int32) * 7) % rows
    g = jnp.ones((n, dim), jnp.bfloat16)
    assert T.embedding_grad_form(n, rows, dim, g.dtype, "tpu") == \
        "column_parts"
    before = _snap()
    T._table_grad(ids, g, rows)                 # concrete, on this CPU
    assert _grew(before) == {"scatter": 1}
    before = _snap()
    got = jax.jit(lambda i, c: T._table_grad(i, c, rows))(ids, g)
    # traced: the blocks where the program is lowered for a TPU; here the
    # CPU's branch ran, the whole-row scatter
    assert _grew(before) == {"column_parts_on_tpu": 1}
    _close(got, _plain(ids, g, rows), jnp.bfloat16)
    before = _snap()
    jax.jit(lambda i, c: T._table_grad(i, c, rows))(
        ids, g.astype(jnp.float32))
    assert _grew(before) == {"scatter": 1}


# ---------------------------------------------------------------------------
# compiled for a described v5e (no chip): which op the table's gradient is
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
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _table_gradient_program(one_chip, ids_shape, rows, dim):
    def table_grad(weight, ids, g):
        _, pull = jax.vjp(lambda w: T._embedding(ids, w), weight)
        return pull(g)[0]
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    return jax.jit(table_grad).lower(
        struct((rows, dim), jnp.bfloat16), struct(ids_shape, jnp.int32),
        struct(ids_shape + (dim,), jnp.bfloat16)).compile().as_text()


def _scatters_into(text, rows, dim):
    return re.findall(r"= bf16\[%d,%d\]\S* scatter\(" % (rows, dim), text)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_phi4s_table_gradient_compiles_to_column_blocks_for_the_chip(
        one_chip, no_compile_cache, precision):
    """(1, 4096) ids over 25088 x 2560 in bf16: no scatter of whole rows,
    four of 640 columns, whatever matmul precision the caller asked for
    (``run.py::reference_check`` asks for ``highest``)."""
    if precision is None:
        text = _table_gradient_program(one_chip, (1, 4096), 25088, 2560)
    else:
        with jax.default_matmul_precision(precision):
            text = _table_gradient_program(one_chip, (1, 4096), 25088, 2560)
    assert not _scatters_into(text, 25088, 2560)
    blocks = _scatters_into(text, 25088, 640)
    assert len(blocks) == 4
    assert text.count("embed_grad") >= 4 and "dot_general" not in text


def test_opts_table_gradient_stays_the_compilers_scatter(
        one_chip, no_compile_cache):
    """(4, 2048) ids over 50272 x 4096: a power of two wide, one scatter."""
    text = _table_gradient_program(one_chip, (4, 2048), 50272, 4096)
    assert len(_scatters_into(text, 50272, 4096)) == 1
    assert not _scatters_into(text, 50272, 1024)
