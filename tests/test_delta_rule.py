"""``ops/delta_rule.py``: the chunked gated delta rule against the recurrence
one token at a time, in float32, forward and every gradient, over lengths
(whole chunks and not), head counts with and without shared key heads, decays
near one and near zero, beta at 0 and at 1; what the backward keeps; bf16
operands; the op's registration, refusals and counters; the two Mosaic
kernels of the walk over the chunks' states in interpret mode against the
``lax.scan`` they stand in for, which shapes they take and which form a
trace counts.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops import delta_rule


def recurrence(q, k, v, g, beta):
    """The module docstring's recurrence as a ``lax.scan`` over tokens."""
    heads = v.shape[1]
    if k.shape[1] != heads:
        q, k = (jnp.repeat(t, heads // k.shape[1], axis=1) for t in (q, k))

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    zero = jnp.zeros(v.shape[:2] + (k.shape[-1], v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t.astype(jnp.float32), 2, 0)
        for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2)


def operands(seq, key_heads, heads, decay, beta=None, dk=16, dv=24, batch=2,
             seed=0, dtype=jnp.float32):
    """Seeded (q, k, v, g, beta) as the layer hands them: q and k at length
    one a head (q over sqrt(dk)), g = -decay * U(0, 1), beta U(0, 1) or the
    constant given."""
    ks = jax.random.split(jax.random.key(seed + seq), 5)
    q, k = (jax.random.normal(key, (batch, key_heads, seq, dk))
            for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, heads, seq, dv))
    g = -decay * jax.random.uniform(ks[3], (batch, heads, seq))
    b = (jax.random.uniform(ks[4], (batch, heads, seq)) if beta is None
         else jnp.full((batch, heads, seq), float(beta)))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, b)


def err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


@pytest.fixture(autouse=True)
def full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# the decay's running sum inside a chunk is a float32 of up to 64 * decay:
# exp of a difference of two such sums is good to 64 * decay * 6e-8
CASES = {
    "one_chunk": dict(seq=64, key_heads=2, heads=2, decay=0.1),
    "two_chunks_shared_keys": dict(seq=128, key_heads=2, heads=4, decay=1.0),
    "five_chunks_one_key_head": dict(seq=320, key_heads=1, heads=2,
                                     decay=0.5),
    "no_whole_chunk": dict(seq=100, key_heads=2, heads=4, decay=1.0),
    "shorter_than_a_chunk": dict(seq=24, key_heads=2, heads=2, decay=1.0),
    "decay_near_one": dict(seq=128, key_heads=2, heads=2, decay=1e-4),
    "decay_strong": dict(seq=128, key_heads=2, heads=4, decay=20.0),
    "beta_zero": dict(seq=128, key_heads=2, heads=2, decay=1.0, beta=0),
    "beta_one": dict(seq=128, key_heads=2, heads=4, decay=1.0, beta=1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_is_the_recurrence(case):
    ops = operands(**CASES[case])
    weight = jax.random.normal(jax.random.key(9), ops[2].shape)
    got = delta_rule.gated_delta_rule(*ops)
    want = recurrence(*ops)
    assert got.shape == ops[2].shape and got.dtype == ops[2].dtype
    limit = 1e-5 + 64 * CASES[case]["decay"] * 1e-7
    assert err(got, want) < limit
    if case == "beta_zero":
        assert not np.asarray(got).any()        # nothing is ever written

    def loss(fn):
        return lambda *t: (fn(*t) * weight).sum()

    grads = jax.grad(loss(delta_rule.gated_delta_rule),
                     argnums=(0, 1, 2, 3, 4))(*ops)
    wanted = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*ops)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, wanted):
        assert a.shape == b.shape, name
        if np.abs(np.asarray(b)).max() > 1e-12:
            assert err(a, b) < 5 * limit, name


def test_repeated_keys_stay_exact():
    """Every key the same, beta one, no decay: (I + L) is all ones below the
    diagonal, whose inverse by powers of L cancels catastrophically; forward
    substitution keeps it (module docstring)."""
    q, k, v, g, beta = operands(128, 1, 1, 0.0, beta=1)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    got = delta_rule.gated_delta_rule(q, k, v, g, beta)
    assert err(got, recurrence(q, k, v, g, beta)) < 1e-5


def test_backward_keeps_operands_and_chunk_borders_only():
    """The residuals of the rule, read off ``jax.vjp``'s closure: the five
    operands (keys not repeated) and one (Dk, Dv) state a chunk; nothing a
    token a (Dk, Dv)."""
    q, k, v, g, beta = operands(256, 2, 4, 1.0)
    _, vjp = jax.vjp(delta_rule.gated_delta_rule, q, k, v, g, beta)
    kept = sorted(tuple(t.shape) for t in jax.tree.leaves(vjp)
                  if hasattr(t, "shape") and t.size > 1)
    chunks = 256 // delta_rule.CHUNK
    assert kept == sorted([q.shape, k.shape, v.shape, g.shape, beta.shape,
                           (chunks, 2, 4, 16, 24)])
    # and the jaxpr of value-and-gradient holds no (.., 256, 16, 24) array
    text = str(jax.make_jaxpr(jax.grad(
        lambda *t: delta_rule.gated_delta_rule(*t).sum(),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta))
    assert "256,16,24]" not in text and "64,16,24]" not in text


def test_bfloat16_operands_stay_near_float32():
    """bf16 q, k, v with float32 state, decays and solve: within 2 % of the
    float32 result's range (a bf16 operand is good to 4e-3, and a token's
    output sums a few hundred products), values and gradients; the result is
    bf16."""
    f32 = operands(192, 2, 4, 1.0)
    b16 = operands(192, 2, 4, 1.0, dtype=jnp.bfloat16)
    got = delta_rule.gated_delta_rule(*b16)
    assert got.dtype == jnp.bfloat16
    want = delta_rule.gated_delta_rule(*f32)
    assert 1e-4 < err(got, want) < 2e-2

    def loss(*t):
        return delta_rule.gated_delta_rule(*t).astype(jnp.float32).sum()

    for a, b in zip(jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*b16),
                    jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*f32)):
        assert a.dtype == b.dtype or a.dtype == jnp.bfloat16
        assert err(a, b) < 3e-2


def test_registered_op_counters_and_refusals():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry

    def count(form):
        snap = telemetry.registry().snapshot().get(
            "graft_delta_rule_traces_total", {"samples": []})
        return sum(s["value"] for s in snap["samples"]
                   if s["labels"]["form"] == form)

    def gauge(name):
        return telemetry.registry().snapshot()[name]["samples"][0]["value"]

    ops = operands(128, 2, 4, 1.0)
    before = count("chunked"), count("chunked_bwd")
    out = mx.nd._contrib_GatedDeltaRule(*(mx.nd.NDArray(t) for t in ops))
    assert err(out._read(), recurrence(*ops)) < 1e-5
    assert count("chunked") == before[0] + 1
    jax.grad(lambda *t: delta_rule.gated_delta_rule(*t).sum())(*ops)
    assert count("chunked_bwd") == before[1] + 1
    assert gauge("graft_delta_rule_chunk") == 64
    assert gauge("graft_delta_rule_state_bytes") == 2 * 4 * 16 * 24 * 4
    q, k, v, g, beta = ops
    with pytest.raises(ValueError, match="gated_delta_rule takes"):
        delta_rule.gated_delta_rule(q[:, :1], k, v, g, beta)
    with pytest.raises(ValueError, match="gated_delta_rule takes"):
        delta_rule.gated_delta_rule(q, k, v[:, :3], g[:, :3], beta[:, :3])
    with pytest.raises(ValueError, match="gated_delta_rule takes"):
        delta_rule.gated_delta_rule(q, k, v, g[:, :, :64], beta)


# ---------------------------------------------------------------------------
# the kernels of the walk, in interpret mode, against ``_inter`` / ``_inter_bwd``
# ---------------------------------------------------------------------------

def count(form):
    from incubator_mxnet_tpu import telemetry
    snap = telemetry.registry().snapshot().get(
        "graft_delta_rule_traces_total", {"samples": []})
    return sum(s["value"] for s in snap["samples"]
               if s["labels"]["form"] == form)


FORMS = ("kernel", "kernel_bwd", "lowering_platform",
         "lowering_platform_bwd", "chunked", "chunked_bwd")

# keys and values one lane tile wide, which the kernels take; one row where
# the case does not say
KERNEL_CASES = {
    "one_chunk": dict(seq=64, key_heads=2, heads=2, decay=0.1),
    "two_chunks_shared_keys": dict(seq=128, key_heads=1, heads=2, decay=1.0),
    "five_chunks_two_rows": dict(seq=320, key_heads=2, heads=2, decay=0.5,
                                 batch=2),
    "three_heads_one_a_block": dict(seq=128, key_heads=3, heads=3,
                                    decay=1.0),
    "three_blocks_of_eight_heads": dict(seq=128, key_heads=8, heads=24,
                                        decay=1.0),
    "bfloat16_operands": dict(seq=128, key_heads=2, heads=4, decay=1.0,
                              dtype=jnp.bfloat16),
    "decay_near_one": dict(seq=128, key_heads=2, heads=2, decay=1e-4),
    "decay_strong": dict(seq=128, key_heads=2, heads=4, decay=20.0),
    "beta_zero": dict(seq=128, key_heads=2, heads=2, decay=1.0, beta=0),
    "beta_one": dict(seq=128, key_heads=2, heads=4, decay=1.0, beta=1),
}


def kernel_parts(case):
    """``_intra``'s six arrays for the case, and the limit of the file."""
    given = dict(dict(batch=1), **KERNEL_CASES[case])
    parts = delta_rule._intra(*operands(dk=128, dv=128, **given),
                              delta_rule.CHUNK)
    assert delta_rule._kernels_take(parts)
    return parts, 1e-5 + 64 * given["decay"] * 1e-7


def close(got, want, limit, name):
    assert got.shape == want.shape and got.dtype == want.dtype, name
    if np.abs(np.asarray(want)).max() > 1e-12:
        assert err(got, want) < limit, name
    else:
        assert np.abs(np.asarray(got)).max() < 1e-12, name


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_forward_kernel_in_interpret_mode_against_the_scan(case):
    """The chunks' outputs and the states at their borders."""
    parts, limit = kernel_parts(case)
    out, borders = delta_rule._inter_pallas(*parts, interpret=True)
    want, want_borders = delta_rule._inter(parts)
    chunks, (batch, heads) = parts[0].shape[2], parts[0].shape[:2]
    assert borders.shape == (chunks, batch, heads, 128, 128)
    assert not np.asarray(borders[0]).any()             # S_0 = 0
    close(out, want, limit, "out")
    close(borders, want_borders, limit, "borders")


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_backward_kernel_in_interpret_mode_against_the_scan(case):
    """All six cotangents of ``parts`` from a seeded cotangent of the
    outputs and the scan's own borders."""
    parts, limit = kernel_parts(case)
    _, borders = delta_rule._inter(parts)
    g_out = jax.random.normal(jax.random.key(5), parts[0].shape)
    got = delta_rule._inter_bwd_pallas(*parts, borders, g_out,
                                       interpret=True)
    want = delta_rule._inter_bwd(parts, borders, g_out)
    assert len(got) == len(want) == 6
    for name, a, b in zip(("w", "kc", "qg", "a", "kd", "last"), got, want):
        close(a, b, 5 * limit, name)


@pytest.mark.parametrize("key_heads,heads", [(2, 2), (2, 4)])
def test_rule_over_the_kernels_is_the_recurrence(key_heads, heads,
                                                 monkeypatch):
    """``gated_delta_rule`` with both kernels put in its way in interpret
    mode, over a length that is no whole chunk: value and five gradients."""
    monkeypatch.setattr(
        delta_rule, "_choose", lambda operands, phase, pallas, fallback:
        functools.partial(pallas, interpret=True)(*operands))
    ops = operands(150, key_heads, heads, 1.0, dk=128, dv=128, batch=1)
    weight = jax.random.normal(jax.random.key(9), ops[2].shape)
    limit = 1e-5 + 64 * 1e-7
    assert err(delta_rule.gated_delta_rule(*ops), recurrence(*ops)) < limit

    def loss(fn):
        return lambda *t: (fn(*t) * weight).sum()

    grads = jax.grad(loss(delta_rule.gated_delta_rule),
                     argnums=(0, 1, 2, 3, 4))(*ops)
    wanted = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*ops)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, wanted):
        assert a.shape == b.shape, name
        assert err(a, b) < 5 * limit, name


def test_kernels_at_default_precision_take_their_factors_in_bfloat16():
    """Where a float32 product is one bf16 pass (the timed step) kc, qg, a
    and kd reach the kernels in bf16 and w stays float32; the outputs leave
    in the dtype asked for and their cotangent comes in its own.  Against
    the scan in full float32 the results are bf16 operands apart."""
    parts, _ = kernel_parts("bfloat16_operands")
    want, borders = delta_rule._inter(parts)
    g_out = jax.random.normal(jax.random.key(5), want.shape)
    wanted = delta_rule._inter_bwd(parts, borders, g_out)
    bf16 = jnp.bfloat16
    with jax.default_matmul_precision("default"):
        handed = delta_rule._layout(
            parts, False, jax.lax.Precision.DEFAULT)[3]
        assert [t.dtype for t in handed] == [jnp.float32] + [bf16] * 4 + [
            jnp.float32]
        out, got_borders = delta_rule._inter_pallas(*parts, dtype=bf16,
                                                    interpret=True)
        got = delta_rule._inter_bwd_pallas(
            *parts, borders, g_out.astype(bf16), interpret=True)
    assert out.dtype == bf16 and got_borders.dtype == jnp.float32
    assert 1e-4 < err(out, want) < 2e-2
    assert err(got_borders, borders) < 2e-2
    for name, a, b in zip(("w", "kc", "qg", "a", "kd", "last"), got, wanted):
        assert a.dtype == jnp.float32 and a.shape == b.shape, name
        assert err(a, b) < 3e-2, name
    assert all(t.dtype == jnp.float32 for t in delta_rule._layout(
        parts, True, jax.lax.Precision.HIGHEST)[3])


def test_which_shapes_the_kernels_take():
    def parts(chunk, dk, dv, heads=4):
        lead = (1, heads, 2, chunk)
        return (jnp.zeros(lead + (dv,)), jnp.zeros(lead + (dk,)))

    assert delta_rule._kernels_take(parts(64, 128, 128))
    assert delta_rule._kernels_take(parts(64, 128, 256))
    assert not delta_rule._kernels_take(parts(64, 16, 24))      # lanes
    assert not delta_rule._kernels_take(parts(64, 128, 64))
    assert not delta_rule._kernels_take(parts(56, 128, 128))    # bf16 rows
    assert [delta_rule._head_block(h)
            for h in (32, 24, 12, 6, 3)] == [16, 8, 4, 2, 1]


def test_kernels_get_the_ambient_matmul_precision():
    """A kernel's body does not inherit it: it is read where the call is
    traced.  Mosaic has two, so whatever asks for more than one bf16 pass
    gets full float32."""
    highest, default = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
    assert delta_rule._ambient_precision() == highest       # the fixture's
    for name, want in (("default", default), ("bfloat16", default),
                       ("BF16_BF16_F32", default), ("float32", highest),
                       ("high", highest), ("BF16_BF16_F32_X3", highest)):
        with jax.default_matmul_precision(name):
            assert delta_rule._ambient_precision() == want, name
    f32 = jax.random.normal(jax.random.key(0), (64, 128))
    full = delta_rule._dot(f32, f32, delta_rule._NT, highest)
    one_pass = delta_rule._dot(f32, f32, delta_rule._NT, default)
    assert one_pass.dtype == jnp.float32
    assert 1e-4 < err(one_pass, full) < 2e-2                # bf16 operands


def test_traces_count_the_form_taken():
    """At a shape the kernels do not take, and with concrete operands on the
    CPU at one they do, the scan runs and is counted ``chunked``; traced
    operands at such a shape leave the choice to the lowering, which off the
    TPU takes the scan too."""
    def counts():
        return {form: count(form) for form in FORMS}

    def grew(before):
        return {form: n - before[form] for form, n in counts().items()
                if n != before[form]}

    def value_and_grads(*t):
        return jax.value_and_grad(
            lambda *t: delta_rule.gated_delta_rule(*t).sum(),
            argnums=(0, 1, 2, 3, 4))(*t)

    before = counts()
    value_and_grads(*operands(128, 2, 4, 1.0))              # dk 16, dv 24
    assert grew(before) == {"chunked": 1, "chunked_bwd": 1}
    wide = operands(128, 2, 2, 1.0, dk=128, dv=128, batch=1)
    before = counts()
    eager = value_and_grads(*wide)
    assert grew(before) == {"chunked": 1, "chunked_bwd": 1}
    before = counts()
    staged = jax.jit(value_and_grads)(*wide)
    assert grew(before) == {"lowering_platform": 1,
                            "lowering_platform_bwd": 1}
    for a, b in zip(jax.tree.leaves(staged), jax.tree.leaves(eager)):
        assert err(a, b) < 1e-5
