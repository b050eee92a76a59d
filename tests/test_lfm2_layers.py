"""The layers LFM2-class hybrid MoE decoders need, against plain ``jax.numpy``:
``gluon.nn.RMSNorm`` / ``GatedMLP`` / ``ShortConv``, ``MultiHeadAttention``
with grouped K/V heads, QK RMSNorm and rotary positions, and
``parallel.ExpertParallelMoE(dispatch="grouped")`` holding a share of the
router's experts (the chip benchmark's cell ``lfm2moe_fused_s8192`` runs
them all; its whole model is held to its reference in
``tests/test_lfm2_chip_bench.py``).
"""
import contextlib
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import (DataParallelTrainer,
                                          ExpertParallelMoE, make_mesh)


def _w(block, name):
    (p,) = [p for n, p in block.collect_params().items() if n.endswith(name)]
    return p.data().asnumpy()


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(x, g, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


# ---------------------------------------------------------------------------
# RMSNorm, GatedMLP, ShortConv
# ---------------------------------------------------------------------------

def test_rms_norm_matches_the_formula_and_keeps_the_dtype():
    rs = np.random.RandomState(0)
    x = rs.randn(3, 5, 16).astype(np.float32) * 3 + 1
    layer = nn.RMSNorm(epsilon=1e-5)
    layer.initialize()
    layer(nd.array(x))
    gain = rs.rand(16).astype(np.float32) + 0.5
    layer.gamma.set_data(nd.array(gain))
    np.testing.assert_allclose(layer(nd.array(x)).asnumpy(),
                               _rms(x, gain), rtol=2e-6, atol=2e-6)
    # bf16 in, bf16 out, the statistic in float32
    out = mx.nd.NDArray(jnp.asarray(x, jnp.bfloat16))
    got = nd.RMSNorm(out, mx.nd.NDArray(jnp.asarray(gain, jnp.bfloat16)))
    assert got._read().dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got._read(), np.float32),
                               _rms(x, gain), rtol=3e-2, atol=3e-2)


def test_gated_mlp_is_swiglu():
    rs = np.random.RandomState(1)
    x = rs.randn(2, 7, 8).astype(np.float32)
    mlp = nn.GatedMLP(8, 20)
    mlp.initialize(mx.init.Normal(0.3))
    got = mlp(nd.array(x)).asnumpy()
    w1, w3, w2 = (_w(mlp, "w%d_weight" % i) for i in (1, 3, 2))
    want = (_silu(x @ w1.T) * (x @ w3.T)) @ w2.T
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert sorted(n.rsplit("_", 2)[-2] for n in mlp.collect_params()) == [
        "w1", "w2", "w3"]                       # no bias anywhere


def _short_conv_loop(x, w_in, taps, w_out):
    """The gated short convolution row by row, as its equations read."""
    batch, seq, d = x.shape
    out = np.zeros_like(x)
    for b in range(batch):
        bcu = x[b] @ w_in.T
        gate_b, gate_c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
        bu = gate_b * u
        for t in range(seq):
            v = np.zeros(d, x.dtype)
            for j in range(taps.shape[1]):
                src = t - (taps.shape[1] - 1) + j
                if src >= 0:
                    v += taps[:, j] * bu[src]
            out[b, t] = (gate_c[t] * v) @ w_out.T
    return out


@pytest.mark.parametrize("kernel", [3, 4])
def test_short_conv_equals_the_explicit_loop(kernel):
    rs = np.random.RandomState(2)
    x = rs.randn(2, 9, 6).astype(np.float32)
    conv = nn.ShortConv(6, kernel=kernel)
    conv.initialize(mx.init.Normal(0.5))
    got = conv(nd.array(x)).asnumpy()
    want = _short_conv_loop(x, _w(conv, "in_weight"),
                            _w(conv, "conv_weight"), _w(conv, "out_weight"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_short_conv_sees_no_later_row():
    """Changing input rows from t on leaves every output row before t as it
    was, bit for bit; and row t itself does change."""
    rs = np.random.RandomState(3)
    x = rs.randn(1, 12, 6).astype(np.float32)
    conv = nn.ShortConv(6)
    conv.initialize(mx.init.Normal(0.5))
    base = conv(nd.array(x)).asnumpy()
    for t in (1, 5, 11):
        changed = x.copy()
        changed[:, t:] += rs.randn(*changed[:, t:].shape).astype(np.float32)
        out = conv(nd.array(changed)).asnumpy()
        assert np.array_equal(out[:, :t], base[:, :t])
        assert not np.allclose(out[:, t], base[:, t])


def test_causal_conv_op_checks_its_weight():
    with pytest.raises(Exception):
        nd._contrib_CausalConv1D(nd.zeros((1, 4, 6)), nd.zeros((6, 2)),
                                 kernel=3)


# ---------------------------------------------------------------------------
# MultiHeadAttention: grouped K/V heads, QK norm, rotary positions
# ---------------------------------------------------------------------------

def _rotate_half(x, base):
    """x (B, S, H, D): position t turns the pair (i, i + D/2) by
    t * base^(-2i/D)."""
    seq, dim = x.shape[1], x.shape[-1]
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(seq)[:, None] * freq[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[None, :, None]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[None, :, None]
    turned = np.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + turned * sin


def _attention_plain(x, attn, heads, kv, qk_norm, base):
    batch, seq, d = x.shape
    dim = d // heads
    q = (x @ _w(attn, "q_weight").T).reshape(batch, seq, heads, dim)
    k = (x @ _w(attn, "k_weight").T).reshape(batch, seq, kv, dim)
    v = (x @ _w(attn, "v_weight").T).reshape(batch, seq, kv, dim)
    if qk_norm:
        q = _rms(q, _w(attn, "q_norm_gamma"))
        k = _rms(k, _w(attn, "k_norm_gamma"))
    if base is not None:
        q, k = _rotate_half(q, base), _rotate_half(k, base)
    out = np.zeros((batch, seq, heads, dim))
    mask = np.tril(np.ones((seq, seq), bool))
    for h in range(heads):
        g = h // (heads // kv)              # the K/V head that serves h
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, g]) / np.sqrt(dim)
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, h] = np.einsum("bqk,bkd->bqd", p, v[:, :, g])
    return out.reshape(batch, seq, d) @ _w(attn, "out_weight").T


@pytest.mark.parametrize("kv,qk_norm,base", [
    (2, True, 1e6), (4, False, 10000.0), (1, True, None), (2, False, None)])
def test_attention_arguments_against_plain_numpy(kv, qk_norm, base):
    rs = np.random.RandomState(4)
    x = rs.randn(2, 10, 16).astype(np.float32)
    attn = nn.MultiHeadAttention(16, 4, causal=True, use_bias=False,
                                 num_kv_heads=kv, qk_norm=qk_norm,
                                 rotary_base=base)
    attn.initialize(mx.init.Normal(0.4))
    got = attn(nd.array(x)).asnumpy()
    if qk_norm:
        for name in ("q_norm_gamma", "k_norm_gamma"):
            (p,) = [p for n, p in attn.collect_params().items()
                    if n.endswith(name)]
            p.set_data(nd.array(rs.rand(4).astype(np.float32) + 0.5))
        got = attn(nd.array(x)).asnumpy()
    assert _w(attn, "k_weight").shape == (kv * 4, 16)
    want = _attention_plain(x.astype(np.float64), attn, 4, kv, qk_norm, base)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _parent_attention(x, q_w, k_w, v_w, o_w, biases, heads):
    """``MultiHeadAttention.hybrid_forward`` as it stood before it took
    grouped heads, QK norm or positions, op for op."""
    B, S, E = x.shape

    def split(t):
        return nd.transpose(nd.reshape(t, shape=(B, S, heads, -1)),
                            axes=(0, 2, 1, 3))

    def dense(t, w, b):
        return nd.FullyConnected(t, w, b, no_bias=False, num_hidden=E,
                                 flatten=False)

    q, k, v = (split(dense(x, w, b))
               for w, b in zip((q_w, k_w, v_w), biases[:3]))
    out = nd._contrib_FlashAttention(q, k, v, causal=True,
                                     scale=1.0 / float(np.sqrt(E // heads)))
    out = nd.reshape(nd.transpose(out, axes=(0, 2, 1, 3)), shape=(B, S, E))
    return dense(out, o_w, biases[3])


def test_attention_with_todays_arguments_is_bit_identical():
    """The OPT cells' layer: the new arguments left at their defaults give
    the parent's parameters, by name and shape, and its output bit for
    bit; hybridized, the same jaxpr as without the new code paths."""
    rs = np.random.RandomState(5)
    x = nd.array(rs.randn(2, 128, 32).astype(np.float32))
    attn = nn.MultiHeadAttention(32, 4, causal=True, use_bias=True,
                                 prefix="a_")
    attn.initialize(mx.init.Normal(0.3))
    got = attn(x).asnumpy()
    params = attn.collect_params()
    assert set(params) == {"a_%s_%s" % (p, k) for p in ("q", "k", "v", "out")
                           for k in ("weight", "bias")}
    w = [params["a_%s_weight" % n].data() for n in ("q", "k", "v", "out")]
    b = [params["a_%s_bias" % n].data() for n in ("q", "k", "v", "out")]
    want = _parent_attention(x, *w, b, heads=4).asnumpy()
    assert np.array_equal(got, want)
    assert attn.q_norm is None and attn.k_norm is None


def test_attention_rejects_heads_that_do_not_divide():
    with pytest.raises(ValueError):
        nn.MultiHeadAttention(16, 4, num_kv_heads=3)
    with pytest.raises(ValueError):
        nn.MultiHeadAttention(16, 4, num_kv_heads=2, fused_qkv=True)


# ---------------------------------------------------------------------------
# ExpertParallelMoE(dispatch="grouped")
# ---------------------------------------------------------------------------

ROUTING = dict(hidden_size=12, num_experts=8, top_k=3, router="sigmoid",
               selection_bias=True, gated=True)


def _moe_pair(dispatch, x, rs, **kw):
    """A dense layer with seeded weights and a non-zero selection bias, and
    a layer of ``dispatch`` sharing its parameters."""
    mx.random.seed(11)
    kw = dict(ROUTING, **kw)
    dense = ExpertParallelMoE(dispatch="dense", prefix="m_", **kw)
    dense.initialize(mx.init.Normal(0.5))
    dense(x)
    if dense.expert_bias is not None:
        dense.expert_bias.set_data(nd.array(
            rs.randn(kw["num_experts"]).astype(np.float32) * 0.3))
    other = ExpertParallelMoE(dispatch=dispatch, prefix="m_",
                              params=dense.collect_params(), **kw)
    return dense, other


def _moe_plain(x, layer, first=0, count=None):
    """The routed layer by its equations, every expert for every token:
    sigmoid scores, the top-k of score + bias, weights the scores at the
    chosen over (their sum + 1e-6); experts ``first .. first + count - 1``
    of the layer's stacked weights are summed."""
    gw, bias = _w(layer, "gate_weight"), _w(layer, "expert_bias")
    w1, w3, w2 = (_w(layer, "expert_w%d" % i) for i in (1, 3, 2))
    count = w1.shape[0] if count is None else count
    k = layer._top_k
    scores = 1.0 / (1.0 + np.exp(-(x @ gw)))
    chosen = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :k]
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        total = scores[n, chosen[n]].sum() + 1e-6
        for e in chosen[n]:
            if first <= e < first + count:
                y = (_silu(x[n] @ w1[e]) * (x[n] @ w3[e])) @ w2[e]
                out[n] += scores[n, e] / total * y
    return out


def test_grouped_equals_dense_for_top_k():
    rs = np.random.RandomState(6)
    x = nd.array(rs.randn(40, 10).astype(np.float32))
    dense, grouped = _moe_pair("grouped", x, rs)
    want = _moe_plain(x.asnumpy(), dense)
    np.testing.assert_allclose(dense(x).asnumpy(), want, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(grouped(x).asnumpy(), want, rtol=1e-4,
                               atol=1e-5)
    assert int(np.asarray(grouped.last_expert_load).sum()) == 40 * 3
    # softmax scores, ReLU experts, no bias: the layer as it was
    dense, grouped = _moe_pair("grouped", x, rs, router="softmax",
                               selection_bias=False, gated=False)
    np.testing.assert_allclose(grouped(x).asnumpy(), dense(x).asnumpy(),
                               rtol=1e-4, atol=1e-5)


def _share(full, first, count, x):
    """A grouped layer holding experts ``first .. first + count - 1`` of
    ``full``'s, the router and its bias whole."""
    share = ExpertParallelMoE(dispatch="grouped", experts_held=(first, count),
                              prefix="s%d_" % first, **ROUTING)
    share.initialize(mx.init.Normal(0.5))
    share(x)
    share.gate_weight.set_data(full.gate_weight.data())
    share.expert_bias.set_data(full.expert_bias.data())
    for name in ("expert_w1", "expert_w2", "expert_w3"):
        getattr(share, name).set_data(
            getattr(full, name).data()[first:first + count])
    return share


def test_the_four_shares_add_up_to_the_uncut_layer():
    """A residual layer y = x + FFN(x) cut into four expert-parallel shares
    of two experts: the shares' routed parts, with the residual counted
    once, are the uncut layer; and each share is what the equations give
    for its experts alone."""
    rs = np.random.RandomState(7)
    x = nd.array(rs.randn(48, 10).astype(np.float32))
    full, _ = _moe_pair("grouped", x, rs)
    uncut = x.asnumpy() + _moe_plain(x.asnumpy(), full)
    total = x.asnumpy().copy()
    assignments = 0
    for first in range(0, 8, 2):
        share = _share(full, first, 2, x)
        part = share(x).asnumpy()
        np.testing.assert_allclose(
            part, _moe_plain(x.asnumpy(), full, first, 2), rtol=1e-4,
            atol=1e-5)
        total += part
        assignments += int(np.asarray(share.last_expert_load).sum())
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    assert assignments == 48 * 3            # every assignment on one share


def test_grouped_drops_nothing_under_skew():
    """A router that sends every token to the same held expert first: that
    expert gets as many rows as there are tokens, eight times a balanced
    share, and the result is still the equations'."""
    rs = np.random.RandomState(8)
    x = nd.array(np.abs(rs.randn(64, 10)).astype(np.float32))
    full, _ = _moe_pair("grouped", x, rs)
    share = _share(full, 2, 2, x)
    gate = np.zeros((10, 8), np.float32)
    gate[:, 3] = 5.0                        # positive inputs: expert 3 wins
    for layer in (full, share):
        layer.gate_weight.set_data(nd.array(gate))
    got = share(x).asnumpy()
    load = np.asarray(share.last_expert_load)
    assert load[1] == 64                    # expert 3 is the share's second
    np.testing.assert_allclose(got, _moe_plain(x.asnumpy(), full, 2, 2),
                               rtol=1e-4, atol=1e-5)
    # and a share none of whose experts is chosen adds exactly nothing
    gate[:, 3] = 0.0
    gate[:, [0, 1, 7]] = 5.0
    bias = np.zeros(8, np.float32)
    for layer in (full, share):
        layer.gate_weight.set_data(nd.array(gate))
        layer.expert_bias.set_data(nd.array(bias))
    assert np.array_equal(share(x).asnumpy(), np.zeros((64, 10), np.float32))
    assert int(np.asarray(share.last_expert_load).sum()) == 0


def test_grouped_gradients_match_dense_and_spare_the_bias():
    """Through the fused step's own route (``jax.vjp`` over the layer):
    the grouped dispatch's gradients are the dense path's, the router's
    included, and the selection bias gets none."""
    from incubator_mxnet_tpu.gluon.block import functionalize
    rs = np.random.RandomState(9)
    x = nd.array(rs.randn(32, 10).astype(np.float32))
    dense, grouped = _moe_pair("grouped", x, rs)
    grads = {}
    for name, layer in (("dense", dense), ("grouped", grouped)):
        fn, params = functionalize(layer, x, train=True)

        def loss(p, inp):
            out = fn(p, inp)
            out = out[0] if isinstance(out, (tuple, list)) else out
            return jnp.sum(jnp.square(out))
        grads[name] = jax.grad(loss, argnums=(0, 1))(params, x._read())
    for (a, ga), (b, gb) in zip(sorted(grads["dense"][0].items()),
                                sorted(grads["grouped"][0].items())):
        assert a == b
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=2e-4,
                                   atol=2e-5, err_msg=a)
        if a.endswith("expert_bias"):
            assert not np.asarray(ga).any() and not np.asarray(gb).any()
        else:
            assert np.asarray(gb).any(), a
    np.testing.assert_allclose(np.asarray(grads["dense"][1]),
                               np.asarray(grads["grouped"][1]), rtol=2e-4,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# grouped dispatch against the layer that masked its whole buffer (PR 26-30)
# ---------------------------------------------------------------------------

def _every_row_masked(x, chosen, weights, w1, w3, w2, first,
                      token_major=False):
    """``grouped_moe_apply`` as it stood until PR 31: the rows gathered in
    and every product's rows are masked with ``in_group`` over the whole
    buffer, and the rows move by plain indexing with the sort's
    permutation and its inverse.  The plain reference today's layer, which
    masks nothing it does not read, is held to.  It numbers its assignments
    as the layer does since PR 35, choice x N + token; ``token_major`` is
    the numbering until then, token x k + choice, which puts the rows of a
    group in another order and so the sums of the weights' gradients."""
    from incubator_mxnet_tpu.parallel.moe import grouped_dot
    n, k = chosen.shape
    count = w1.shape[0]
    local = (chosen if token_major else chosen.T).reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
        jnp.int32)
    back = jnp.argsort(order)
    in_group = (jnp.arange(n * k) < group_sizes.sum())[:, None]

    def dot(rows, w):
        return jnp.where(in_group, grouped_dot(rows, w, group_sizes),
                         jnp.zeros((), rows.dtype))
    rows = jnp.repeat(x, k, axis=0) if token_major else jnp.tile(x, (k, 1))
    xs = jnp.where(in_group, rows[order], jnp.zeros((), x.dtype))
    h = jax.nn.silu(dot(xs, w1).astype(jnp.float32)) * dot(xs, w3).astype(
        jnp.float32)
    ys = dot(h.astype(xs.dtype), w2)[back].astype(jnp.float32)
    if token_major:
        out = (ys.reshape(n, k, -1) * weights[:, :, None]).sum(axis=1)
    else:
        out = (ys.reshape(k, n, -1) * weights.T[:, :, None]).sum(axis=0)
    return out.astype(x.dtype)


def _dense_share(x, chosen, weights, w1, w3, w2, first):
    """``dispatch="dense"``'s equations over the held experts alone: every
    held expert for every token, weighted by ``combine``."""
    count = w1.shape[0]
    combine = (jax.nn.one_hot(chosen - first, count, dtype=x.dtype)
               * weights[:, :, None]).sum(1)                  # (N, count)
    h = jax.nn.silu(jnp.einsum("nd,edh->neh", x, w1)) * jnp.einsum(
        "nd,edh->neh", x, w3)
    return jnp.einsum("ne,ned->nd", combine, jnp.einsum("neh,ehd->ned", h, w2))


# a product of under 64 rows takes another route through the CPU's compiler
TOKENS, WIDTH, HIDDEN = 72, 12, 20
SHARES = [(4, 8, 32), (8, 8, 64), (2, 2, 8), (2, 8, 8)]   # top_k, count, E


def _choices(router, top_k, count, experts):
    """(N, k) experts a token, distinct within a token, experts 0 ..
    count - 1 held: ``balanced`` sends the share count / experts;
    ``all_held`` every assignment (the routed cells' trained steps);
    ``none_held`` no assignment; ``one_token`` a single token's single
    choice.  A layer that holds every expert has only ``all_held``."""
    step = np.arange(top_k)
    on_held = (np.arange(TOKENS)[:, None] + step) % count
    if router == "all_held" or count == experts:
        return on_held
    if router == "balanced":
        return (np.arange(TOKENS)[:, None] * top_k + step) % experts
    chosen = count + (np.arange(TOKENS)[:, None] + step) % (experts - count)
    if router == "one_token":
        chosen[TOKENS // 2, top_k - 1] = count - 1
    return chosen


def _layer_inputs(top_k, count, experts, router, seed=14):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(TOKENS, WIDTH), jnp.float32)
    gate = jnp.asarray(rs.randn(WIDTH, experts) * 0.5, jnp.float32)
    w1, w3 = (jnp.asarray(rs.randn(count, WIDTH, HIDDEN) * 0.3, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rs.randn(count, HIDDEN, WIDTH) * 0.3, jnp.float32)
    chosen = jnp.asarray(_choices(router, top_k, count, experts), jnp.int32)
    return chosen, (x, gate, w1, w3, w2)


def _through(apply, chosen):
    """``apply`` behind a router whose choice is given and whose weights
    are the gate's sigmoid scores at the chosen, renormalised: the gate is
    differentiated through the weights, as in ``_route``."""
    def fn(x, gate, w1, w3, w2):
        scores = jax.nn.sigmoid(x @ gate)
        weights = jnp.take_along_axis(scores, chosen, axis=1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        out = apply(x, chosen, weights, w1, w3, w2, 0)
        return out[0] if isinstance(out, tuple) else out
    return fn


def _value_and_gradients(apply, chosen, args):
    out, pullback = jax.vjp(_through(apply, chosen), *args)
    return (out,) + pullback(jnp.cos(out))


@pytest.mark.parametrize("router", ["balanced", "all_held", "none_held",
                                    "one_token"])
@pytest.mark.parametrize("top_k,count,experts", SHARES)
def test_grouped_equals_the_layer_that_masked_every_row(top_k, count,
                                                        experts, router):
    """Value and gradients (x, gate, w1, w3, w2) against the layer that
    masked its whole buffer, to the last bit in float32 (the sums are the
    same sums in the same order), and against the dense equations."""
    from incubator_mxnet_tpu.parallel import moe
    chosen, args = _layer_inputs(top_k, count, experts, router)
    held = int((np.asarray(chosen) < count).sum())
    assert held == {"balanced": TOKENS * top_k * count // experts,
                    "all_held": TOKENS * top_k, "none_held": 0,
                    "one_token": 1}[
        "all_held" if count == experts else router]
    got, want, dense = (_value_and_gradients(apply, chosen, args)
                        for apply in (moe.grouped_moe_apply,
                                      _every_row_masked, _dense_share))
    for g, w, d in zip(got, want, dense):
        assert np.array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_allclose(np.asarray(g), np.asarray(d), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("router", ["balanced", "all_held", "none_held",
                                    "one_token"])
@pytest.mark.parametrize("top_k,count,experts", SHARES)
def test_grouped_order_of_assignments_is_only_rounding(top_k, count, experts,
                                                       router):
    """The same layer against the reference that numbers its assignments
    token x k + choice, as the layer did until PR 35: value and gradients
    agree within float32 rounding, so the order of the assignment axis
    changes the order of sums (the rows of a group, for the weights'
    gradients) and nothing else."""
    import functools
    from incubator_mxnet_tpu.parallel import moe
    chosen, args = _layer_inputs(top_k, count, experts, router)
    got, want = (_value_and_gradients(apply, chosen, args)
                 for apply in (moe.grouped_moe_apply, functools.partial(
                     _every_row_masked, token_major=True)))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("router", ["balanced", "none_held", "one_token"])
def test_grouped_reads_no_row_that_no_kernel_wrote(monkeypatch, router):
    """The Pallas grouped matmul leaves the rows behind the groups as its
    buffer held them; ``lax.ragged_dot`` on the CPU zeroes them and would
    hide a reader.  With a grouped product that writes NaN there, forward
    (its result) and backward (the rows' cotangent), the layer's value and
    gradients are what they are without: nothing reads those rows."""
    from incubator_mxnet_tpu.parallel import moe
    clean = moe.grouped_dot

    def poison(rows, group_sizes):
        behind = jnp.arange(rows.shape[0])[:, None] >= group_sizes.sum()
        return jnp.where(behind, jnp.nan, rows)

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return poison(clean(lhs, rhs, group_sizes), group_sizes)

    def forward(lhs, rhs, group_sizes):
        return dirty(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def backward(res, g):
        lhs, rhs, group_sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: clean(a, b, group_sizes), lhs,
                               rhs)[1](g)
        return poison(d_lhs, group_sizes), d_rhs, None

    dirty.defvjp(forward, backward)
    chosen, args = _layer_inputs(4, 8, 32, router)
    want = _value_and_gradients(moe.grouped_moe_apply, chosen, args)
    monkeypatch.setattr(moe, "grouped_dot", dirty)
    got = _value_and_gradients(moe.grouped_moe_apply, chosen, args)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_grouped_masks_no_row_of_the_tokens_width():
    """What crosses between the tokens' order and the buffer's is not
    masked over the whole buffer: in the layer's jaxpr, forward and
    backward, no ``select_n`` writes (A, d) (the two gathers fill the rows
    of assignments in no group themselves, ``_zero_filled``), and the
    hidden products' masks, two forward and their two transposes, are
    (A, h)."""
    from incubator_mxnet_tpu.parallel import moe
    chosen, args = _layer_inputs(4, 8, 32, "balanced")
    jaxpr = jax.make_jaxpr(lambda *a: _value_and_gradients(
        moe.grouped_moe_apply, chosen, a))(*args)
    assignments = TOKENS * 4
    selects = [aval.shape for primitive, aval in _outputs(jaxpr.jaxpr)
               if primitive == "select_n" and aval.ndim == 2
               and aval.shape[0] == assignments]
    assert sorted(selects) == [(assignments, HIDDEN)] * 4, selects
    masked = jax.make_jaxpr(lambda *a: _value_and_gradients(
        _every_row_masked, chosen, a))(*args)
    assert [aval.shape for primitive, aval in _outputs(masked.jaxpr)
            if primitive == "select_n"].count((assignments, WIDTH)) == 4


def _outputs(jaxpr, by=None):
    """(primitive, type) of every equation's outputs, sub-jaxprs
    included; with ``by="name"`` the equation's ``name`` parameter (a
    jitted function's) in the primitive's place."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                yield (eqn.params.get(by) if by else eqn.primitive.name,
                       v.aval)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _outputs(sub, by)


# ---------------------------------------------------------------------------
# the tokens in parts (PR 41): a buffer a part, the same result
# ---------------------------------------------------------------------------

def _skewed(parts, top_k=4, count=8, experts=32):
    """``balanced`` choices with expert 0 empty in the first of ``parts``
    equal parts of the tokens and chosen by every token of the last."""
    chosen = _choices("balanced", top_k, count, experts).copy()
    of_part = TOKENS // parts
    chosen[:of_part][chosen[:of_part] == 0] = count + 1        # absent
    chosen[-of_part:, 0] = 0       # a token's run starts at a multiple of 4
    assert not (chosen[:of_part] == 0).any()
    assert (chosen[-of_part:] == 0).sum() == of_part
    assert all(len(set(row)) == top_k for row in chosen)
    return jnp.asarray(chosen, jnp.int32)


def _limit_for(parts, top_k, dtype):
    """The limit at which ``TOKENS`` tokens go through in ``parts``."""
    return TOKENS // parts * top_k * WIDTH * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("parts", [2, 4])
def test_parted_equals_one_part_in_float32(monkeypatch, parts):
    """Value, the five cotangents and ``group_sizes`` of the layer run in 2
    and 4 parts against one part, float32: a token's output does not
    depend on which tokens share its buffer, and the weights' gradients are
    the same products summed a part at a time."""
    from incubator_mxnet_tpu.parallel import moe
    _, args = _layer_inputs(4, 8, 32, "balanced")
    chosen = _skewed(parts)
    assert moe._parts(TOKENS, 4, WIDTH, 4) == 1
    want = _value_and_gradients(moe.grouped_moe_apply, chosen, args)
    weights = jnp.full(chosen.shape, 0.25, jnp.float32)
    _, load = moe.grouped_moe_apply(args[0], chosen, weights, *args[2:], 0)
    monkeypatch.setattr(moe, "_PART_BYTES", _limit_for(parts, 4, "float32"))
    assert moe._parts(TOKENS, 4, WIDTH, 4) == parts
    got = _value_and_gradients(moe.grouped_moe_apply, chosen, args)
    for g, w in zip(got, want):
        assert np.asarray(w).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)
    _, parted_load = moe.grouped_moe_apply(args[0], chosen, weights,
                                           *args[2:], 0)
    assert np.array_equal(np.asarray(parted_load), np.asarray(load))
    assert int(load.sum()) == int((np.asarray(chosen) < 8).sum())


def test_parted_weight_gradients_in_bf16_are_one_rounding(monkeypatch):
    """In bf16 the experts' weight gradients of four parts lie no farther
    from the float32 layer's than one part's do: the parts' products leave
    in float32, are summed in float32 and rounded once.  Rounded a part in
    bf16 and added in bf16 they would lie farther, which the last lines
    show on the same numbers."""
    from incubator_mxnet_tpu.parallel import moe
    _, args = _layer_inputs(4, 8, 32, "balanced")
    chosen = _skewed(4)
    exact = _value_and_gradients(moe.grouped_moe_apply, chosen, args)[3:]
    # the router stays in float32, as ``_route`` scores
    half = tuple(a if i == 1 else a.astype(jnp.bfloat16)
                 for i, a in enumerate(args))

    def gap(grads):
        assert all(g.dtype == jnp.bfloat16 for g in grads)
        return max(float(np.abs(np.asarray(g, np.float32) - np.asarray(e))
                         .max() / np.abs(np.asarray(e)).max())
                   for g, e in zip(grads, exact))

    one = gap(_value_and_gradients(moe.grouped_moe_apply, chosen, half)[3:])
    monkeypatch.setattr(moe, "_PART_BYTES", _limit_for(4, 4, "bfloat16"))
    parted = _value_and_gradients(moe.grouped_moe_apply, chosen, half)
    assert gap(parted[3:]) <= 1.1 * one
    # PR 46: no sum of the parts' weight cotangents is left to autodiff
    # (until then three float32 ``add_any`` a weight): the rule over the
    # parts adds them itself, off the TPU three float32 ``add`` a weight
    jaxpr = jax.make_jaxpr(lambda *a: _value_and_gradients(
        moe.grouped_moe_apply, chosen, a))(*half)
    made = list(_outputs(jaxpr.jaxpr))
    assert not [aval for primitive, aval in made
                if primitive == "add_any" and aval.ndim == 3]
    sums = [aval.dtype for primitive, aval in made
            if primitive == "add" and aval.ndim == 3]
    assert len(sums) == 3 * 3 and set(sums) == {jnp.dtype("float32")}
    # ... and in the branch a TPU takes (rows in whole tiles of 128) one
    # kernel over all parts' rows: a float32 result a weight, no ``tgmm``
    rows = (jnp.zeros((128, WIDTH), jnp.bfloat16),) * 4
    sizes = (jnp.zeros((8,), jnp.int32),) * 4
    kernels = [(name, aval.shape, aval.dtype.name) for name, aval in _outputs(
        jax.make_jaxpr(lambda lhs, w, g: jax.vjp(
            lambda lhs, w: moe.parted_dot(lhs, w, sizes), lhs, w)[1](g))(
                rows, args[2], (jnp.zeros((128, HIDDEN), jnp.bfloat16),) * 4
            ).jaxpr, "name") if name in ("gmm", "tgmm", "tgmm_parts")]
    assert sorted(kernels) == (
        [("gmm", (128, WIDTH), "bfloat16")] * 4         # the rows' cotangents
        + [("gmm", (128, HIDDEN), "bfloat16")] * 4      # forward
        + [("tgmm_parts", (8, WIDTH, HIDDEN), "float32")])
    # ... and what bf16 parts added in bf16 would have given: each part's
    # rows alone (their cotangent is the whole layer's, cos(out))
    x = half[0]
    naive = [jnp.zeros_like(w) for w in half[2:]]
    monkeypatch.undo()
    for rows in np.split(np.arange(TOKENS), 4):
        grads = _value_and_gradients(moe.grouped_moe_apply, chosen[rows],
                                     (x[rows],) + half[1:])[3:]
        naive = [n + g for n, g in zip(naive, grads)]
    assert gap(naive) > gap(parted[3:])


def _ragged_weight_gradient(lhs, rhs, sizes):
    """The float32 sum over the parts of ``lax.ragged_dot``'s transpose for
    the weights, rows behind a part's groups counting nothing."""
    total = 0
    for rows, g, of_part in zip(lhs, rhs, sizes):
        held = (jnp.arange(rows.shape[0]) < of_part.sum())[:, None]
        rows, g = (jnp.where(held, v.astype(jnp.float32), 0)
                   for v in (rows, g))
        _, pullback = jax.vjp(
            lambda w: lax.ragged_dot(rows, w, of_part, precision="highest"),
            jnp.zeros((len(of_part), rows.shape[1], g.shape[1])))
        total = total + pullback(g)[0]
    return total


# rows a group has in each part, row tiles of 128 over buffers of 256 rows:
# group 1 is empty in every part, group 0 in one, group 3 in most; borders
# lie inside a row tile (100, 137), on one (128, 256) and a part is full
_PART_SIZES = {2: [[100, 0, 37, 60], [0, 0, 200, 56]],
               4: [[10, 0, 20, 30], [0, 0, 256, 0], [128, 0, 128, 0],
                   [1, 0, 0, 3]]}


@pytest.mark.parametrize("parts", sorted(_PART_SIZES))
def test_the_parts_row_tiles_are_visited_group_by_group(parts):
    """``tgmm_parts._steps`` against megablox's own ``make_group_metadata``
    of each part: a part's steps are the (group, row tile) pairs megablox
    visits for that part's sizes, in its order (where a group has rows; a
    group without rows gets one step in either), and the parts' steps are
    laid out group-major, part after part within a group.  Every part's
    operands show its own tile at its own steps and its last own tile in
    between."""
    import importlib
    from incubator_mxnet_tpu.parallel import tgmm_parts
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    rs = np.random.RandomState(parts)
    cases = [_PART_SIZES[parts]] + [
        rs.multinomial(rs.randint(0, 1025), np.ones(5) / 5, size=parts)
        * (rs.rand(parts, 5) < 0.7) for _ in range(6)]
    for sizes in cases:
        sizes = [jnp.asarray(s, jnp.int32) for s in sizes]
        groups, rows, tm = len(sizes[0]), 1024, 128
        (offsets, group_ids, part_ids, tile_at), steps = tgmm_parts._steps(
            sizes, rows, tm)
        steps, slots = int(steps), len(group_ids)
        assert slots == parts * (rows // tm + groups - 1) >= steps
        np.testing.assert_array_equal(
            offsets.reshape(parts, -1)[:, 1:], np.cumsum(sizes, axis=1))
        tile_at = np.asarray(tile_at).reshape(parts, slots)
        assert tile_at.min() >= 0 and tile_at.max() < rows // tm
        order = list(zip(np.asarray(group_ids[:steps]),
                         np.asarray(part_ids[:steps])))
        assert order == sorted(order)
        for part, of_part in enumerate(sizes):
            (_, ids, tiles), count = megablox.make_group_metadata(
                group_sizes=of_part, m=rows, tm=tm, start_group=0,
                num_nonzero_groups=groups, visit_empty_groups=True)
            own = np.asarray(part_ids[:steps]) == part
            assert own.sum() == int(count)
            np.testing.assert_array_equal(group_ids[:steps][own],
                                          ids[:int(count)])
            with_rows = np.asarray(of_part)[np.asarray(ids[:int(count)])] > 0
            np.testing.assert_array_equal(
                tile_at[part, :steps][own][with_rows],
                np.asarray(tiles[:int(count)])[with_rows])
            # between its own steps a part's operands stay where they were
            shown = tile_at[part, :steps]
            assert (np.diff(shown)[~own[1:]] == 0).all()
            assert shown[0] == 0 and (np.diff(shown) >= 0).all()


@pytest.mark.parametrize("out_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("parts", sorted(_PART_SIZES))
def test_the_weight_gradient_over_the_parts_row_buffers(parts, out_dtype):
    """``tgmm_parts`` in the interpreter over the bf16 row buffers of 2 and 4
    parts against the float32 sum of the parts' ``lax.ragged_dot``
    transposes: under skewed sizes, with a group empty in one part, a group
    empty in every part (its block is zeros), borders inside a row tile,
    NaN in the rows behind the groups, and a column tile that does not
    divide its dimension.  In float32 it is the sum to float32's rounding,
    in bf16 that sum rounded once."""
    from incubator_mxnet_tpu.parallel.tgmm_parts import tgmm_parts
    rs = np.random.RandomState(46)
    rows, k, n = 256, 256, 384
    sizes = tuple(jnp.asarray(s, jnp.int32) for s in _PART_SIZES[parts])

    def buffers(width):
        made = (jnp.asarray(rs.randn(rows, width), jnp.bfloat16)
                for _ in sizes)
        return tuple(v.at[int(s.sum()):].set(jnp.nan)
                     for v, s in zip(made, sizes))

    lhs, rhs = buffers(k), buffers(n)
    want = _ragged_weight_gradient(lhs, rhs, sizes)
    with _time_limit(240):
        got = tgmm_parts(lhs, rhs, sizes, jnp.dtype(out_dtype),
                         (128, 128, 256), interpret=True)
    assert got.dtype == out_dtype and np.isfinite(got).all()
    assert not np.asarray(got[1]).any() and np.asarray(want[0]).any()
    if out_dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    else:
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=2.0 ** -8, atol=1e-4)


@pytest.mark.parametrize("cell,shape,parts", [
    ("kimivl_mla_fused_1row", (4096, 6, 2048), 1),
    ("lfm2moe_fused_s8192", (8192, 4, 2048), 2),
    ("trinitymini_gated_fused_1row", (4096, 8, 2048), 2),
    ("mellum2_fused_s8192", (8192, 8, 2304), 4)])
def test_parts_follow_the_buffers_bytes(cell, shape, parts):
    """``_parts`` from the shapes and the rows' dtype alone, at the routed
    cells' (N, k, d) in bf16: Kimi-VL's 100,663,296 B stay one part, and
    every part is under 128 MiB with whole row tiles."""
    from incubator_mxnet_tpu.parallel import moe
    n, k, d = shape
    assert 100663296 <= moe._PART_BYTES < 128 << 20
    assert moe._parts(n, k, d, 2) == parts
    rows = n // parts * k
    assert rows * d * 2 <= moe._PART_BYTES and rows % 256 == 0
    # the tier-1 shapes lie far under the limit; float32 rows are twice
    # the bytes; a row of tokens that no split brings under it is split
    # as finely as its row tiles allow
    assert moe._parts(TOKENS, 4, WIDTH, 4) == 1
    assert moe._parts(n, k, d, 4) == 2 * parts
    assert moe._parts(512, 1, 1 << 20, 2) == 2


def test_a_buffer_under_the_limit_is_staged_as_one_part(monkeypatch):
    """Under the limit the layer stages ``_grouped_part`` and nothing
    else: no slice of the tokens, no concatenation, the weights as they
    came.  Over it, a slice of x, chosen and weights a part and one
    concatenation of the parts' outputs."""
    from incubator_mxnet_tpu.parallel import moe
    chosen, (x, _, w1, w3, w2) = _layer_inputs(4, 8, 32, "balanced")
    weights = jnp.full(chosen.shape, 0.25, jnp.float32)
    staged, one = (str(jax.make_jaxpr(fn)(x, chosen, weights, w1, w3, w2, 0))
                   for fn in (moe.grouped_moe_apply, moe._grouped_part))
    assert staged == one
    monkeypatch.setattr(moe, "_PART_BYTES", _limit_for(3, 4, "float32"))
    # a function of its own: ``make_jaxpr`` keeps a function's trace
    parted = jax.make_jaxpr(lambda *a: moe.grouped_moe_apply(*a, 0))(
        x, chosen, weights, w1, w3, w2)
    made = [(primitive, aval.shape) for primitive, aval
            in _outputs(parted.jaxpr)]
    assert made.count(("concatenate", (TOKENS, WIDTH))) == 1
    assert made.count(("split", (TOKENS // 3, WIDTH))) == 3
    assert made.count(("ragged_dot_general", (TOKENS // 3 * 4, HIDDEN))) == 6


def test_grouped_counters_and_arguments():
    rs = np.random.RandomState(10)
    x = nd.array(rs.randn(16, 10).astype(np.float32))
    registry = mx.telemetry.registry()

    def count(name, **labels):
        samples = registry.snapshot().get(name, {"samples": []})["samples"]
        return sum(s["value"] for s in samples if s["labels"] == labels)

    before = {k: count("graft_moe_assignments_total", held=k)
              for k in ("yes", "no")}
    traces = count("graft_moe_dispatch_traces_total", path="grouped")
    full, _ = _moe_pair("grouped", x, rs)
    share = _share(full, 0, 2, x)
    share(x)
    held = int(np.asarray(share.last_expert_load).sum())
    assert count("graft_moe_dispatch_traces_total", path="grouped") > traces
    # _share's shape pass and this call: two calls of the share
    assert count("graft_moe_assignments_total", held="yes") - before[
        "yes"] >= held
    assert count("graft_moe_assignments_total", held="no") - before[
        "no"] >= 16 * 3 - held
    assert registry.snapshot()[
        "graft_moe_expert_load_max_over_mean"]["samples"][0]["value"] >= 1.0
    with pytest.raises(ValueError):
        ExpertParallelMoE(4, 8, dispatch="dense", experts_held=(0, 2))
    with pytest.raises(ValueError):
        ExpertParallelMoE(4, 8, dispatch="grouped", experts_held=(6, 4))
    with pytest.raises(ValueError):
        ExpertParallelMoE(4, 8, dispatch="capacity", router="sigmoid")
    with pytest.raises(ValueError):
        ExpertParallelMoE(4, 8, router="tanh")


def test_grouped_buffer_part_gauges(monkeypatch):
    """``graft_moe_buffer_parts`` / ``graft_moe_buffer_part_bytes``: the
    parts the last traced grouped call ran in and a part's bytes."""
    from incubator_mxnet_tpu.parallel import moe
    rs = np.random.RandomState(10)
    x = nd.array(rs.randn(16, 10).astype(np.float32))
    registry = mx.telemetry.registry()

    def gauge(name):
        (sample,) = registry.snapshot()[name]["samples"]
        return sample["value"]

    _, layer = _moe_pair("grouped", x, rs)
    layer(x)
    assert gauge("graft_moe_buffer_parts") == 1
    assert gauge("graft_moe_buffer_part_bytes") == 16 * 3 * 10 * 4
    monkeypatch.setattr(moe, "_PART_BYTES", 16 * 3 * 10 * 4 // 2)
    want = layer(x).asnumpy()
    assert gauge("graft_moe_buffer_parts") == 2
    assert gauge("graft_moe_buffer_part_bytes") == 8 * 3 * 10 * 4
    monkeypatch.undo()
    np.testing.assert_allclose(layer(x).asnumpy(), want, rtol=1e-6,
                               atol=1e-6)


def test_grouped_product_tile_gauges():
    """``graft_moe_product_tile{product, dim}``: the tiles the last traced
    kernel of each kind took for megablox's k and n, from shapes, and
    ``graft_moe_ragged_tile_traces_total{product}``, counted when one of
    them does not divide its dimension.  The kernels are traced, as a
    step lowered for the TPU traces them; nothing runs."""
    from incubator_mxnet_tpu.parallel import moe
    registry = mx.telemetry.registry()

    def samples(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in registry.snapshot().get(name, {"samples": []})[
                    "samples"]}

    def tiles(product):
        found = samples("graft_moe_product_tile")
        return tuple(found[(("dim", dim), ("product", product))]
                     for dim in "kn")

    def ragged():
        found = samples("graft_moe_ragged_tile_traces_total")
        return [found.get((("product", p),), 0)
                for p in ("gmm", "gmm_t", "tgmm")]

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    before = ragged()
    sizes = spec(2, dtype=jnp.int32)
    # Kimi-VL's w1, (k, n) = (2048, 1408), in bf16: 1408 whole
    jax.eval_shape(moe._gmm, spec(256, 2048), spec(2, 2048, 1408), sizes)
    assert tiles("gmm") == (2048, 1408)
    jax.eval_shape(moe._gmm_bwd, spec(256, 2048), spec(2, 2048, 1408), sizes,
                   spec(256, 1408))
    assert tiles("gmm_t") == (1408, 1024)
    assert tiles("tgmm") == (1024, 1408)
    assert ragged() == before
    # the same weights in float32, as a layer in parts hands them over:
    # ``tgmm``'s float32 result does not fit whole, two tiles of 768
    jax.eval_shape(moe._gmm_bwd, spec(256, 2048),
                   spec(2, 2048, 1408, dtype=jnp.float32), sizes,
                   spec(256, 1408))
    assert tiles("tgmm") == (1024, 768)
    assert ragged() == [before[0], before[1], before[2] + 1]
    # LFM2's w2: (1792, 2048); every tile divides
    jax.eval_shape(moe._gmm_bwd, spec(256, 1792), spec(2, 1792, 2048), sizes,
                   spec(256, 2048))
    assert tiles("gmm_t") == (2048, 896)
    assert tiles("tgmm") == (896, 1024)
    assert ragged() == [before[0], before[1], before[2] + 1]


def test_weight_gradient_form_counter():
    """``graft_moe_weight_grad_traces_total{form, parts}``: a trace of the
    backward rule's Pallas branch counts how the weights' gradient is
    formed, megablox's ``tgmm`` over one buffer or ``tgmm_parts`` over the
    buffers of p parts; nothing runs."""
    from incubator_mxnet_tpu.parallel import moe
    registry = mx.telemetry.registry()

    def counts():
        found = registry.snapshot().get(
            "graft_moe_weight_grad_traces_total", {"samples": []})["samples"]
        return {(s["labels"]["form"], s["labels"]["parts"]): s["value"]
                for s in found}

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    before = counts()
    sizes, w = spec(2, dtype=jnp.int32), spec(2, 256, 384)
    jax.eval_shape(moe._gmm_bwd, spec(256, 256), w, sizes, spec(256, 384))
    jax.eval_shape(moe._gmm_parts_bwd, *[spec(256, 256)] * 4,
                   *[spec(256, 384)] * 4, *[sizes] * 4, w)
    moved = {key: value - before.get(key, 0)
             for key, value in counts().items()}
    assert {k: v for k, v in moved.items() if v} == {
        ("tgmm", "1"): 1, ("tgmm_parts", "4"): 1}


# (k, n) of the routed cells' stacked weights, w1 / w3 then w2, the bytes
# of an element of ``tgmm``'s result (4 where the tokens go in parts), and
# the tiles (rows, k, n) in megablox's order of the three kernels over each.
# LFM2's, Mellum2's and Trinity-Mini's are the literals of the tree before
# PR 42: the guard that those three steps stay the programs they were
_CELL_TILES = [
    ("lfm2moe_fused_s8192", (2048, 1792), 4,
     ((256, 2048, 896), (256, 1792, 1024), (256, 1024, 896))),
    ("lfm2moe_fused_s8192", (1792, 2048), 4,
     ((256, 1792, 1024), (256, 2048, 896), (256, 896, 1024))),
    ("mellum2_fused_s8192", (2304, 896), 4,
     ((256, 768, 896), (256, 896, 768), (256, 768, 896))),
    ("mellum2_fused_s8192", (896, 2304), 4,
     ((256, 896, 768), (256, 768, 896), (256, 896, 768))),
    ("trinitymini_gated_fused_1row", (2048, 1024), 4,
     ((256, 2048, 1024), (256, 1024, 1024), (256, 1024, 1024))),
    ("trinitymini_gated_fused_1row", (1024, 2048), 4,
     ((256, 1024, 1024), (256, 2048, 1024), (256, 1024, 1024))),
    # PR 42: 1408 = 11 x 128 whole (it ran in eleven tiles of 128) ...
    ("kimivl_mla_fused_1row", (2048, 1408), 2,
     ((256, 2048, 1408), (256, 1408, 1024), (256, 1024, 1408))),
    ("kimivl_mla_fused_1row", (1408, 2048), 2,
     ((256, 1408, 1024), (256, 2048, 1408), (256, 1408, 1024))),
    # ... and where the whole does not fit the VMEM (``tgmm`` with a
    # float32 result: no cell today) in two tiles of 768, the second ragged
    ("kimivl_in_parts", (2048, 1408), 4,
     ((256, 2048, 1408), (256, 1408, 1024), (256, 1024, 768))),
    ("kimivl_in_parts", (1408, 2048), 4,
     ((256, 1408, 1024), (256, 2048, 1408), (256, 768, 1024)))]


@pytest.mark.parametrize("cell,kn,out_itemsize,product,tiling", [
    pytest.param(cell, kn, out_itemsize if product == "tgmm" else 2, product,
                 tiling,
                 id="%s-%dx%d-%s" % ((cell.split("_f")[0],) + kn + (product,)))
    for cell, kn, out_itemsize, tilings in _CELL_TILES
    for product, tiling in zip(("gmm", "gmm_t", "tgmm"), tilings)])
def test_product_tiles_by_cell(cell, kn, out_itemsize, product, tiling):
    """``_tiling`` from shapes and itemsizes alone, at the four routed cells'
    weights in bf16: no written dimension of 512 or more in tiles under 512,
    every tile whole lanes, the contracted dimension of a ``gmm`` in tiles
    that divide it (no masked accumulation), and the kernel's blocks by the
    layer's own reckoning within the VMEM a kernel gets."""
    from incubator_mxnet_tpu.parallel import moe
    assert moe._tiling(product, 24576, *kn, 2, out_itemsize) == tiling
    rows, tk, tn = tiling
    k, n = kn[::-1] if product == "gmm_t" else kn
    for size, tile in ((k, tk), (n, tn)):
        assert tile % 128 == 0 and tile <= max(size, 128)
        assert tile >= min(size, 512)
    assert product == "tgmm" or k % tk == 0
    assert moe._VMEM_BYTES == 16 << 20
    assert moe._block_bytes(product, tiling, 2, out_itemsize) <= 16 << 20


@pytest.mark.parametrize("cell,kn,parts,tiling,blocks", [
    pytest.param(cell, kn, parts, tilings[2], blocks,
                 id="%s-%dx%d" % ((cell.split("_f")[0],) + kn))
    for (cell, kn, _, tilings), (parts, blocks) in zip(_CELL_TILES[:6], [
        (2, 16908288), (2, 16908288), (4, 16777216), (4, 16777216),
        (2, 18874368), (2, 18874368)])])
def test_weight_gradient_blocks_of_the_parted_cells(cell, kn, parts, tiling,
                                                    blocks):
    """``tgmm_parts`` at the three parted cells' weights: the tiles are one
    part's ``tgmm``'s (``_tiling`` does not ask how many parts there are),
    ``_block_bytes`` counts every part's operand blocks twice, and the kernel
    is given the VMEM one part's kernel gets and those blocks on top, which
    holds what it reckons to hold (LFM2's and Trinity-Mini's are over the
    compiler's own 16 MiB: the TPU's compiler refused Trinity-Mini's at it)."""
    from incubator_mxnet_tpu.parallel import moe
    rows, tk, tn = tiling
    assert moe._tiling("tgmm", 32768 // parts * 2, *kn, 2, 4) == tiling
    one = moe._block_bytes("tgmm", tiling, 2, 4)
    assert one <= moe._VMEM_BYTES
    assert moe._block_bytes("tgmm", tiling, 2, 4, parts=parts) == blocks
    assert blocks - one == (parts - 1) * 2 * 2 * rows * (tk + tn)
    given = []

    def kernel(lhs, rhs, group_sizes, out_dtype, tiles, vmem_bytes):
        given.append((tiles, vmem_bytes))
        return jnp.zeros((8,) + kn, out_dtype)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    import incubator_mxnet_tpu.parallel.tgmm_parts as module
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(module, "tgmm_parts", kernel)
        _, d_rhs = jax.eval_shape(
            moe._gmm_parts_bwd, *[spec(rows * 4, kn[0])] * parts,
            *[spec(rows * 4, kn[1])] * parts,
            *[spec(8, dtype=jnp.int32)] * parts,
            spec(8, *kn, dtype=jnp.float32))
    assert d_rhs.dtype == jnp.float32
    assert given == [(tiling, moe._VMEM_BYTES + blocks - one)]
    assert blocks <= given[0][1] <= 24 << 20


@contextlib.contextmanager
def _time_limit(seconds):
    """Fail the test, and do not hang the run, after ``seconds``."""
    def late(signum, frame):
        raise TimeoutError("no result within %d s" % seconds)
    was = signal.signal(signal.SIGALRM, late)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, was)


def test_a_ragged_column_tile_is_the_product_tile_for_tile():
    """Megablox's three kernels in the interpreter at a tile that does not
    divide its dimension, 1408 in two tiles of ``_tile(1408)``: over rows
    in three uneven groups and an empty one they give ``lax.ragged_dot``
    and its ``vjp`` to float32's rounding, and, bit for bit, what the same
    kernels give in eleven tiles of 128: a column tile changes neither the
    order of a sum nor a dtype, so PR 42's step is its parent's to the
    bit."""
    import importlib
    from incubator_mxnet_tpu.parallel import moe
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    wide, rows, k, n = moe._tile(1408), 384, 256, 1408
    assert 1408 % wide and wide >= 512
    rs = np.random.RandomState(42)
    sizes = jnp.asarray([100, 0, 180, 60], jnp.int32)
    held = int(sizes.sum())
    in_group = (jnp.arange(rows) < held)[:, None]
    x = jnp.asarray(rs.randn(rows, k), jnp.float32)
    w = jnp.asarray(rs.randn(4, k, n) / 16, jnp.float32)
    g = jnp.asarray(rs.randn(rows, n), jnp.float32)

    def ragged(x, w):
        return lax.ragged_dot(x, w, sizes, precision="highest")

    want, pullback = jax.vjp(ragged, x, w)
    _, want_w = pullback(jnp.where(in_group, g, 0))

    def kernels(tile):
        run = dict(interpret=True)
        return (megablox.gmm(x, w, sizes, x.dtype, (128, k, tile), **run),
                megablox.gmm(g[:, :k], w.swapaxes(1, 2), sizes, x.dtype,
                             (128, k, tile), transpose_rhs=True, **run),
                megablox.tgmm(x.T, g, sizes, x.dtype, (128, k, tile), **run),
                megablox.tgmm(g.T, x, sizes, x.dtype, (128, tile, k), **run))

    with _time_limit(240):
        got, narrow = kernels(wide), kernels(128)
    for a, b in zip(got, narrow):
        a, b = (v[:held] if v.ndim == 2 else v for v in (a, b))
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
    # the rows' cotangent through a weight whose *rows* are the 1408
    _, pullback_t = jax.vjp(
        lambda h: ragged(h, w.swapaxes(1, 2)), jnp.zeros((rows, n)))
    for a, b in ((got[0][:held], want[:held]),
                 (got[1][:held], pullback_t(g[:, :k])[0][:held]),
                 (got[2], want_w), (got[3], want_w.swapaxes(1, 2))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_grouped_trains_in_the_fused_step_in_bf16():
    """A share of the experts under ``DataParallelTrainer`` in bf16 over a
    one-device mesh without an ``ep`` axis: the loss falls and the
    selection bias (a buffer) stays where it was."""
    rs = np.random.RandomState(12)
    x = rs.randn(32, 8).astype(np.float32)
    y = (rs.rand(32) * 3).astype(np.float32)
    mx.random.seed(13)
    net = gluon.nn.HybridSequential()
    net.add(ExpertParallelMoE(16, 8, top_k=2, dispatch="grouped",
                              experts_held=(2, 4), router="sigmoid",
                              selection_bias=mx.init.Normal(0.1),
                              gated=True, ep_axis=None, in_units=8))
    net.add(gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    bias = net[0].expert_bias.data().asnumpy().copy()
    assert bias.any()
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="adam",
        optimizer_params={"learning_rate": 0.01},
        mesh=make_mesh({"dp": 1}, jax.devices()[:1]), dtype="bfloat16")
    first = float(np.asarray(trainer.step(nd.array(x), nd.array(y))))
    for _ in range(30):
        last = trainer.step(nd.array(x), nd.array(y))
    assert float(np.asarray(last)) < first
    assert np.array_equal(
        np.asarray(trainer._params[net[0].expert_bias.name]), bias)
