"""What Mellum2 brought to the package's layers (PR 30), on the CPU: the
causal window of ``ops/attention.py`` through its three paths (the Pallas
kernels under the interpreter, the ``jnp`` forward, the scan backward)
against an explicit-mask reference; YaRN in ``_contrib_RotaryEmbedding``
against its formulas; ``gluon.nn.MultiHeadAttention`` with a head that is
not ``units // num_heads``, a window and scaled rotary positions against a
plain implementation, and unchanged where the new arguments are left out.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.ops import attention as A
from incubator_mxnet_tpu.ops.registry import get_op


def _masked(q, k, v, scale, window):
    """softmax(q k^T scale + mask) v with the mask written out: query r
    (of Sq, the last of which is the last key's) sees key c where
    r + off - window < c <= r + off."""
    sq, sk = q.shape[2], k.shape[2]
    r = jnp.arange(sq)[:, None] + sk - sq
    c = jnp.arange(sk)[None, :]
    seen = c <= r
    if window is not None:
        seen &= c > r - window
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(seen, s, A._NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _problem(sq, sk, d=128, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(1, 2, s, d), jnp.float32)
                 for s in (sq, sk, sk, sq))


def _close(got, want, tol=2e-5):
    """Largest error over the largest reference value, or over 1 where the
    reference is all but zero (a window of one key has no dQ, dK)."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert got.shape == want.shape and np.all(np.isfinite(got))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1.0) <= tol


WINDOWS = {"1": lambda s: 1, "100": lambda s: 100, "128": lambda s: 128,
           "256": lambda s: 256, "S": lambda s: s, "2S": lambda s: 2 * s}


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("sq,sk", [(256, 256), (384, 384), (1024, 1024),
                                   (256, 512), (512, 256)])
def test_window_paths_match_the_explicit_mask(sq, sk, window):
    """Forward and the three gradients, by the kernels (blocks of 128 and of
    the length's own size), by the jnp forward and by the scan backward."""
    w = WINDOWS[window](sk)
    q, k, v, g = _problem(sq, sk)
    scale = 128 ** -0.5
    want, vjp = jax.vjp(lambda a, b, c: _masked(a, b, c, scale, w), q, k, v)
    want = (want, *vjp(g))
    win = A._window_of(w, True, sk)
    assert win == (None if w >= sk else w)
    for block in (128, None):
        out, m, l = A._flash_forward_pallas(q, k, v, True, scale, block,
                                            block, interpret=True,
                                            window=win)
        grads = A._flash_backward_pallas(q, k, v, out, m, l, g, True, scale,
                                         block, block, interpret=True,
                                         window=win)
        for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *grads),
                              want):
            assert _close(a, b), (name, block)
    out, m, l = A._attention_reference_stats(q, k, v, True, scale, win)
    scan = A._flash_backward_scan(q, k, v, out, m, l, g, True, scale,
                                  window=win)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *scan), want):
        assert _close(a, b), name


def test_the_op_differentiates_through_the_window():
    """The registered operator, as a Block calls it: gradients through the
    custom rule agree with autodiff of the explicit mask, and a window that
    holds every key is the causal call."""
    q, k, v, g = _problem(256, 256, seed=3)
    scale = 128 ** -0.5
    op = get_op("_contrib_FlashAttention").fcompute

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * g)

    got = jax.grad(loss(lambda a, b, c: op(a, b, c, causal=True, scale=scale,
                                           window=100)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda a, b, c: _masked(a, b, c, scale, 100)),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert _close(a, b)
    whole = op(q, k, v, causal=True, scale=scale, window=256)
    np.testing.assert_array_equal(
        np.asarray(whole), np.asarray(op(q, k, v, causal=True, scale=scale)))
    assert not _close(op(q, k, v, causal=True, scale=scale, window=100),
                      whole, tol=1e-2)


def test_a_window_needs_causal_and_the_ring_has_none():
    q = jnp.ones((1, 1, 128, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        A.flash_attention(q, q, q, False, None, 16)
    with pytest.raises(ValueError, match="at least 1"):
        A.flash_attention(q, q, q, True, None, 0)
    with pytest.raises(ValueError, match="no window"):
        get_op("_contrib_RingAttention").fcompute(q, q, q, causal=True,
                                                  window=16)
    with pytest.raises(ValueError, match="causal"):
        nn.MultiHeadAttention(64, 4, window=16)
    with pytest.raises(ValueError, match="seq_axis"):
        nn.MultiHeadAttention(64, 4, causal=True, window=16, seq_axis="sp")


@pytest.mark.parametrize("sq,sk,window,visited,causal", [
    (8192, 8192, 1024, 45, 136),        # the cell's: 3 key blocks a query block
    (8192, 8192, None, 136, 136),
    (2048, 2048, 1024, 9, 10),
    (1024, 2048, 512, 4, 7)])
def test_blocks_visited_by_hand(sq, sk, window, visited, causal):
    assert A.blocks_visited(sq, sk, window) == (visited, causal)


def test_a_banded_grid_has_no_step_outside_the_band():
    """At the cell's shape the windowed grids' last axis is 3 long where the
    causal grid's is 16, and a step's block is its own inside the band."""
    for q_major in (True, False):
        band = A._Band(512, 512, 16, 16, 0, 1024, q_major)
        assert band.steps == 3
    band = A._Band(512, 512, 16, 16, 0, 1024, True)
    edges = [band._edges(i, max, min, lambda a, b: a // b) for i in range(16)]
    assert edges[0] == (0, 0) and edges[1] == (0, 1) and edges[5] == (3, 5)
    assert sum(b - a + 1 for a, b in edges) == 45
    band = A._Band(512, 512, 16, 16, 0, 1024, False)        # by key block
    edges = [band._edges(i, max, min, lambda a, b: a // b) for i in range(16)]
    assert edges[0] == (0, 2) and edges[14] == (14, 15) and edges[15] == (15, 15)


def test_traces_are_counted_by_window_and_blocks_are_gauged():
    def samples(name):
        return telemetry.registry().snapshot().get(
            name, {"samples": []})["samples"]

    def counted():
        return {(s["labels"]["path"], s["labels"]["window"]): s["value"]
                for s in samples("graft_flash_attention_traces_total")}

    before = counted()
    q = jnp.ones((1, 2, 256, 8), jnp.float32)
    jax.jit(lambda a: A.flash_attention(a, a, a, True, None, 100))(q)
    jax.jit(lambda a: A.flash_attention(a, a, a, True))(q)
    new = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert new[("lowering_platform", "100")] == 1
    assert new[("lowering_platform", "none")] == 1
    gauges = {(name, s["labels"]["kind"]): s["value"]
              for name in ("graft_flash_blocks_visited",
                           "graft_flash_blocks_causal") for s in samples(name)}
    # two (batch, head) slices of one 256-block each
    assert gauges[("graft_flash_blocks_visited", "window")] == 2
    assert gauges[("graft_flash_blocks_causal", "window")] == 2
    assert gauges[("graft_flash_blocks_visited", "full")] == 2


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

MELLUM_YARN = {"factor": 16, "original_max_position": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _yarn_by_hand(dim, theta, factor, length, beta_fast, beta_slow):
    pair = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2 * pair / dim)

    def c(turns):
        return dim * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), dim - 1)
    ramp = np.clip((pair - low) / (high - low), 0, 1)
    return low, high, freq / factor * ramp + freq * (1 - ramp)


def test_yarn_at_the_published_numbers():
    low, high, want = _yarn_by_hand(128, 500000.0, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    assert MELLUM_YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-15)
    from incubator_mxnet_tpu.ops.nn import yarn_inverse_frequencies
    got = np.asarray(yarn_inverse_frequencies(128, 500000.0, 16, 8192, 32, 1))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 500000.0 ** (-2 * np.arange(64) / 128)
    # fast pairs keep their frequency, slow ones turn a sixteenth as far
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)


@pytest.mark.parametrize("dim,theta,scaling", [
    (128, 500000.0, MELLUM_YARN),
    (16, 500000.0, MELLUM_YARN),
    (64, 10000.0, {"factor": 4, "original_max_position": 2048})])
def test_rotary_scaling_matches_the_formulas(dim, theta, scaling):
    rs = np.random.RandomState(5)
    x = rs.randn(1, 2, 48, dim).astype(np.float32)
    op = get_op("_contrib_RotaryEmbedding").fcompute
    got = np.asarray(op(jnp.asarray(x), base=theta, scaling=scaling))
    _, _, inv = _yarn_by_hand(dim, theta, scaling["factor"],
                              scaling["original_max_position"],
                              scaling.get("beta_fast", 32),
                              scaling.get("beta_slow", 1))
    factor = scaling.get("attention_factor",
                         0.1 * math.log(scaling["factor"]) + 1)
    ang = np.arange(48)[:, None] * inv[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1) * factor
    sin = np.concatenate([np.sin(ang)] * 2, -1) * factor
    turned = np.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    np.testing.assert_allclose(got, x * cos + turned * sin, atol=2e-5)
    # and scaling=None is the operator as it was
    plain = np.asarray(op(jnp.asarray(x), base=theta))
    ang = np.arange(48)[:, None] * theta ** (-2 * np.arange(dim // 2) / dim)
    np.testing.assert_allclose(
        plain, x * np.concatenate([np.cos(ang)] * 2, -1)
        + turned * np.concatenate([np.sin(ang)] * 2, -1), atol=2e-5)
    assert np.abs(plain - got).max() > 0.1


def test_rotary_scaling_refuses_what_it_does_not_know():
    op = get_op("_contrib_RotaryEmbedding").fcompute
    x = jnp.ones((1, 1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="scaling takes"):
        op(x, base=10.0, scaling={"factor": 2})
    with pytest.raises(ValueError, match="scaling takes"):
        op(x, base=10.0, scaling=dict(MELLUM_YARN, rope_type="yarn"))
    with pytest.raises(ValueError, match="rotary_base"):
        nn.MultiHeadAttention(64, 4, rotary_scaling=MELLUM_YARN)


# ---------------------------------------------------------------------------
# MultiHeadAttention
# ---------------------------------------------------------------------------

def _w(block, name):
    return np.asarray(getattr(block, name).weight.data()._read(), np.float64)


def _attention_plain(x, attn, heads, kv, dim, window, theta, scaling):
    """(B, S, E) -> (B, S, E) in float64: heads of ``dim`` whatever E is,
    each K/V head serving a run of query heads, rotate-half positions (YaRN
    where ``scaling``), a causal window."""
    b, s, _ = x.shape

    def heads_of(name, n):
        return (x @ _w(attn, name).T).reshape(b, s, n, dim)

    if scaling is None:
        inv = theta ** (-2 * np.arange(dim // 2) / dim)
        factor = 1.0
    else:
        _, _, inv = _yarn_by_hand(dim, theta, scaling["factor"],
                                  scaling["original_max_position"],
                                  scaling["beta_fast"], scaling["beta_slow"])
        factor = scaling["attention_factor"]
    ang = np.arange(s)[:, None] * inv[None]
    cos = (np.concatenate([np.cos(ang)] * 2, -1) * factor)[:, None]
    sin = (np.concatenate([np.sin(ang)] * 2, -1) * factor)[:, None]

    def rotate(t):
        turned = np.concatenate([-t[..., dim // 2:], t[..., :dim // 2]], -1)
        return t * cos + turned * sin

    q, k, v = rotate(heads_of("proj_q", heads)), rotate(
        heads_of("proj_k", kv)), heads_of("proj_v", kv)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & (j > i - (window or s))
    out = np.zeros((b, s, heads, dim))
    for h in range(heads):
        g = h // (heads // kv)
        scores = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, g]) / math.sqrt(
            dim)
        scores = np.where(seen, scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, h] = np.einsum("bqk,bkd->bqd", p, v[:, :, g])
    return out.reshape(b, s, heads * dim) @ _w(attn, "proj_out").T


@pytest.mark.parametrize("window,scaling", [
    (None, None), (24, None), (None, MELLUM_YARN), (24, MELLUM_YARN)])
def test_attention_with_a_head_of_its_own_width(window, scaling):
    """8 query heads over 1 K/V head of 16 at a model width of 48 (a head
    that is not ``units // num_heads`` = 6), as Mellum2's 32 over 4 of 128
    at 2304, in blocks of 8 : 1."""
    units, heads, kv, dim, seq = 48, 8, 1, 16, 40
    mx.random.seed(11)
    attn = nn.MultiHeadAttention(
        units, heads, causal=True, use_bias=False, num_kv_heads=kv,
        rotary_base=500000.0, head_dim=dim, window=window,
        rotary_scaling=scaling)
    attn.initialize(mx.init.Normal(0.3))
    x = np.random.RandomState(2).randn(2, seq, units).astype(np.float32)
    got = np.asarray(attn(mx.nd.array(x))._read())
    shapes = {n.rsplit("_", 2)[-2]: p.shape
              for n, p in attn.collect_params().items()}
    assert shapes == {"q": (128, 48), "k": (16, 48), "v": (16, 48),
                      "out": (48, 128)}
    want = _attention_plain(x.astype(np.float64), attn, heads, kv, dim,
                            window, 500000.0, scaling)
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5
    if window is not None:
        whole = _attention_plain(x.astype(np.float64), attn, heads, kv, dim,
                                 None, 500000.0, scaling)
        assert np.abs(got - whole).max() / np.abs(whole).max() > 1e-2


def test_attention_without_the_new_arguments_is_the_layer_it_was():
    """Same parameters (names and shapes) and the same output bit for bit
    as with the defaults written out; the scale is 1 / sqrt(units //
    num_heads)."""
    x = mx.nd.array(np.random.RandomState(0).randn(2, 24, 64).astype("f"))
    made = []

    def make(**kw):
        attn = nn.MultiHeadAttention(64, 4, causal=True, num_kv_heads=2,
                                     rotary_base=10000.0, prefix="a_", **kw)
        attn.initialize(mx.init.Normal(0.2))
        attn(x)                         # resolves the deferred shapes
        if made:                        # the first one's weights
            for name, p in attn.collect_params().items():
                p.set_data(made[0].collect_params()[name].data())
        made.append(attn)
        return attn

    old, new = make(), make(head_dim=None, window=None, rotary_scaling=None)
    a, b = old(x), new(x)
    assert {n: p.shape for n, p in old.collect_params().items()} == {
        n: p.shape for n, p in new.collect_params().items()}
    assert old.proj_q.weight.shape == (64, 64)
    assert old.proj_k.weight.shape == (32, 64)
    np.testing.assert_array_equal(np.asarray(a._read()), np.asarray(b._read()))
    want = _attention_plain(
        np.asarray(x._read(), np.float64), old, 4, 2, 16, None, 10000.0, None)
    assert np.abs(np.asarray(a._read()) - want).max() < 1e-4
    # head_dim given as what it would have been: the same layer again
    same = make(head_dim=16)
    np.testing.assert_array_equal(np.asarray(same(x)._read()),
                                  np.asarray(a._read()))


def test_host_table_is_exact_far_out_and_factor_one_is_plain_rope():
    """``scaling`` makes the table on the host: at 16,000 positions it is
    float32's rounding of the float64 cosines, and a scaling of factor 1 is
    the plain rotary operator."""
    from incubator_mxnet_tpu.ops.nn import _host_table
    inv = 500000.0 ** (-2 * np.arange(64) / 128)
    cos, sin = (np.asarray(t, np.float64) for t in _host_table(16001, inv))
    assert cos.shape == sin.shape == (16001, 64)
    ang = np.arange(16001)[:, None] * inv[None]
    assert np.abs(cos - np.cos(ang)).max() < 3e-7
    assert np.abs(sin - np.sin(ang)).max() < 3e-7
    op = get_op("_contrib_RotaryEmbedding").fcompute
    x = jnp.asarray(np.random.RandomState(1).randn(1, 2, 300, 128),
                    jnp.float32)
    one = op(x, base=500000.0, scaling={
        "factor": 1.0, "original_max_position": 8192,
        "attention_factor": 1.0})
    np.testing.assert_allclose(np.asarray(one),
                               np.asarray(op(x, base=500000.0)), atol=2e-4)


# ---------------------------------------------------------------------------
# the gate (PR 40): off it is the layer the three cells stage, on it is the
# ungated layer's kernel output under a sigmoid of the layer's input
# ---------------------------------------------------------------------------

_PLAIN_ROPE = {"factor": 1.0, "original_max_position": 131072,
               "attention_factor": 1.0}
# ``MultiHeadAttention`` as the OPT, LFM2 and Mellum2 configurations build it,
# at their files' rehearsal widths on (2, 128, 64), with the parameters'
# shapes and the sha256 (16 digits) of the StableHLO text of its forward and
# of the gradient of its sum as commit e500d6e, the parent of the PR that
# brought the gate, lowered them on the CPU.  A PR that changes what those
# layers stage on purpose records the digests anew; one that adds a keyword
# beside them may not move them.
STAGED_WITHOUT_A_GATE = {
    "opt6b7": (dict(units=64, num_heads=2, causal=True, use_bias=True),
               "26d4f67dea4d089e"),
    "lfm2moe": (dict(units=64, num_heads=4, causal=True, use_bias=False,
                     num_kv_heads=2, qk_norm=True, qk_norm_epsilon=1e-5,
                     rotary_base=1e6), "97196d9c21e49001"),
    "mellum2_window": (dict(units=64, num_heads=8, causal=True,
                            use_bias=False, num_kv_heads=1, rotary_base=5e5,
                            head_dim=16, rotary_scaling=_PLAIN_ROPE,
                            window=32), "2cef21df3e05d3d1"),
    "mellum2_full": (dict(units=64, num_heads=8, causal=True, use_bias=False,
                          num_kv_heads=1, rotary_base=5e5, head_dim=16,
                          rotary_scaling=MELLUM_YARN), "df65bba18f71148f"),
}


def _staged(**kw):
    """(the layer, {parameter: shape}, the digest of what it lowers to)."""
    import hashlib
    from incubator_mxnet_tpu.gluon.block import functionalize
    attn = nn.MultiHeadAttention(kw.pop("units"), kw.pop("num_heads"),
                                 prefix="attn_", **kw)
    attn.initialize(mx.init.Normal(0.1))
    x = np.zeros((2, 128, 64), np.float32)
    fn, params = functionalize(attn, x, train=True)
    fwd = jax.jit(fn).lower(params, x).as_text()
    bwd = jax.jit(jax.grad(lambda p, a: fn(p, a).sum())).lower(
        params, x).as_text()
    return (attn, {n: tuple(v.shape) for n, v in params.items()},
            hashlib.sha256((fwd + bwd).encode()).hexdigest()[:16])


@pytest.mark.parametrize("cell", sorted(STAGED_WITHOUT_A_GATE))
def test_without_the_gate_the_layer_stages_the_program_it_staged(cell):
    kw, digest = STAGED_WITHOUT_A_GATE[cell]
    attn, shapes, staged = _staged(**kw)
    assert attn.proj_gate is None
    assert not [n for n in shapes if "gate" in n]
    assert _staged(gate=False, **kw)[1:] == (shapes, staged)
    assert staged == digest
    # and the gate is one parameter more, another program
    _, gated, other = _staged(gate=True, **kw)
    width = kw["num_heads"] * kw.get("head_dim",
                                     kw["units"] // kw["num_heads"])
    assert {n: s for n, s in gated.items() if n not in shapes} == {
        "attn_gate_weight": (width, 64)}
    assert other != digest


@pytest.mark.parametrize("window,base", [(None, None), (24, 10000.0)])
def test_the_gate_is_a_sigmoid_of_the_input_on_the_kernels_output(window,
                                                                  base):
    """8 query heads over 2 K/V heads of 16 at a width of 48 with QK-norm,
    as Trinity-Mini's full layers (no positions) and sliding ones: forward
    and every gradient agree with the gate applied by hand to what the
    ungated layer hands ``proj_out``."""
    from incubator_mxnet_tpu.gluon.block import functionalize
    units, heads, kv, dim, seq = 48, 8, 2, 16, 40

    def make(gate):
        mx.random.seed(11)
        attn = nn.MultiHeadAttention(
            units, heads, causal=True, use_bias=False, num_kv_heads=kv,
            head_dim=dim, qk_norm=True, rotary_base=base, window=window,
            gate=gate, prefix="a_")
        attn.initialize(mx.init.Normal(0.3))
        return attn

    x = np.random.RandomState(2).randn(2, seq, units).astype(np.float32)
    fn, params = functionalize(make(True), x, train=True)
    plain_fn, plain = functionalize(make(False), x, train=True)
    assert set(params) - set(plain) == {"a_gate_weight"}
    assert params["a_gate_weight"].shape == (heads * dim, units)
    w_out = params["a_out_weight"]

    def by_hand(p, a):
        # the ungated layer with an identity in proj_out's place hands over
        # the heads' outputs, (B, S, H * D)
        shared = {n: p[n] for n in plain if n != "a_out_weight"}
        ctx = plain_fn(dict(shared, a_out_weight=jnp.eye(
            heads * dim, dtype=w_out.dtype)), a)
        gate = jax.nn.sigmoid(a @ p["a_gate_weight"].T)
        return (gate * ctx) @ p["a_out_weight"].T

    got, vjp = jax.vjp(fn, params, jnp.asarray(x))
    want, want_vjp = jax.vjp(by_hand, params, jnp.asarray(x))
    assert _close(got, want)
    g = jnp.asarray(np.random.RandomState(3).randn(*got.shape), jnp.float32)
    (got_p, got_x), (want_p, want_x) = vjp(g), want_vjp(g)
    assert _close(got_x, want_x, 1e-4)
    for name in params:
        assert _close(got_p[name], want_p[name], 1e-4), name
    # a gate of one half everywhere is half the ungated layer
    halved = fn(dict(params, a_gate_weight=jnp.zeros_like(
        params["a_gate_weight"])), jnp.asarray(x))
    assert _close(halved, 0.5 * plain_fn(
        {n: params[n] for n in plain}, jnp.asarray(x)))
    with pytest.raises(ValueError, match="self-attention only"):
        make(True)(mx.nd.array(x), mx.nd.array(x))
