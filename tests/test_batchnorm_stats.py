"""Training BatchNorm's statistics as the programs hold them
(``ops/nn.py::_bn_train_stats``): the sum and the sum of squares in one
pass, neither waiting for the other, and the exact second pass (the mean
square about the mean) only inside the guard's ``cond``.

* the traced forward of ``Convolution -> BatchNorm -> relu`` under
  ``jax.vjp``, on the CPU: which reductions read the activation, and what
  each depends on;
* the same forward compiled for a v5e that is described, not attached
  (on-chip-measurement guide, section 2: nothing at import, the topology in a
  fixture): both sums ride the convolution's output fusion and no pass of
  their own reads the activation.

Values and gradients are ``tests/test_operator.py``'s
(``test_batchnorm_one_pass_statistics``).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.registry import get_op
from incubator_mxnet_tpu.telemetry.tracing import collect_step_counters

BATCH, C_IN, C_OUT, SIDE = 4, 3, 8, 6


def forward(x, w, gamma, beta, running_mean, running_var):
    """The three registered ops as a train step's forward calls them, under
    ``jax.vjp`` (the rule's forward is what gets staged)."""
    conv = get_op("Convolution").raw(
        dict(kernel=(1, 1), num_filter=w.shape[0], no_bias=True), True)
    norm = get_op("BatchNorm").raw(dict(fix_gamma=False, eps=1e-5), True)

    def f(x, w, gamma, beta):
        y = conv(x, w)
        out, mean, var = norm(y, gamma, beta, running_mean, running_var)
        return jax.nn.relu(out), (mean, var)

    (out, stats), _pullback = jax.vjp(f, x, w, gamma, beta)
    return out, stats


def _inner(eqn):
    """The jaxprs an equation calls, ``cond``'s branches apart."""
    found = []
    for value in eqn.params.values():
        for v in (value if isinstance(value, (tuple, list)) else (value,)):
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                found.append(v)
    return found


def reductions(jaxpr, tainted, seen, inside_cond=False):
    """Walk ``jaxpr`` (and what it calls) in order.  ``tainted`` maps a
    variable to the activation reductions its value depends on; ``seen``
    gets ``(primitive, inside a cond, the reductions its operand depends
    on)`` for every reduction over an operand of the activation's size.
    Returns the taints of the jaxpr's results."""
    size = BATCH * C_OUT * SIDE * SIDE
    for eqn in jaxpr.eqns:
        ins = [tainted.get(v, frozenset()) for v in eqn.invars
               if not hasattr(v, "val")]
        above = frozenset().union(*ins) if ins else frozenset()
        out = above
        subs = _inner(eqn)
        if subs:
            cond = inside_cond or eqn.primitive.name == "cond"
            for sub in subs:
                operands = eqn.invars[len(eqn.invars) - len(sub.invars):]
                inner = {iv: tainted.get(ov, frozenset())
                         for iv, ov in zip(sub.invars, operands)
                         if not hasattr(ov, "val")}
                if eqn.primitive.name == "cond":
                    inner = {iv: t | above for iv, t in inner.items()}
                got = reductions(sub, inner, seen, cond)
                out = out.union(*got) if got else out
        elif eqn.primitive.name.startswith("reduce") and any(
                getattr(v.aval, "size", 0) == size for v in eqn.invars):
            seen.append((eqn.primitive.name, inside_cond, above))
            out = above | {len(seen)}
        for v in eqn.outvars:
            tainted[v] = out
    return [tainted.get(v, frozenset()) for v in jaxpr.outvars
            if not hasattr(v, "val")]


def test_two_independent_sums_outside_the_cond_and_the_second_pass_inside():
    struct = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(forward)(
        struct((BATCH, C_IN, SIDE, SIDE), jnp.bfloat16),
        struct((C_OUT, C_IN, 1, 1), jnp.bfloat16),
        struct((C_OUT,), jnp.bfloat16), struct((C_OUT,), jnp.bfloat16),
        struct((C_OUT,), jnp.float32), struct((C_OUT,), jnp.float32))
    seen = []
    reductions(jaxpr.jaxpr, {}, seen)
    outside = [r for r in seen if not r[1]]
    inside = [r for r in seen if r[1]]
    # one pass: two sums over the activation, and neither operand depends on
    # a reduction over it (a shift taken from the activation would)
    assert [name for name, _, _ in outside] == ["reduce_sum", "reduce_sum"]
    assert all(not above for _, _, above in outside)
    # the exact form, in the taken-rarely branch only: the mean square about
    # the mean; the branch is entered on a predicate made of the two sums and
    # subtracts the first, so it depends on both
    assert [(name, above) for name, _, above in inside] == [
        ("reduce_sum", {1, 2})]


def test_the_forward_runs_and_counts_no_second_pass():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.rand(BATCH, C_IN, SIDE, SIDE), jnp.bfloat16)
    w = jnp.asarray(rs.randn(C_OUT, C_IN, 1, 1), jnp.bfloat16)
    ones, zeros = jnp.ones(C_OUT), jnp.zeros(C_OUT)
    with collect_step_counters() as found:
        out, (mean, var) = forward(x, w, ones.astype(jnp.bfloat16),
                                   zeros.astype(jnp.bfloat16), zeros, ones)
    assert [(name, int(taken)) for name, _, taken in found] == [
        ("bn_second_pass", 0)]
    y = np.einsum("nchw,oc->nohw", np.asarray(x, np.float64),
                  np.asarray(w, np.float64)[:, :, 0, 0])
    y = np.asarray(jnp.asarray(y, jnp.bfloat16), np.float64)
    np.testing.assert_allclose(mean, y.mean(axis=(0, 2, 3)), atol=2e-2)
    np.testing.assert_allclose(var, y.var(axis=(0, 2, 3)), rtol=5e-2)
    assert out.dtype == jnp.bfloat16 and float(out.min()) >= 0.0


# ---------------------------------------------------------------------------
# compiled for a described v5e (no chip): where the two sums are taken
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


def test_both_sums_ride_the_convolutions_fusion_on_a_v5e(one_chip,
                                                         no_compile_cache):
    """ResNet-50's stage-1 expansion at the fused cell's shape: ``bf16[256,
    256, 56, 56]`` out of a 1x1 convolution.  The convolution's output
    fusion hands out the activation and two ``f32[256]`` sums; outside the
    ``conditional`` nothing else reduces the activation (the parent's
    variance was a ``kLoop`` fusion of its own that read it again)."""
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    text = jax.jit(forward).lower(
        struct((256, 64, 56, 56), jnp.bfloat16),
        struct((256, 64, 1, 1), jnp.bfloat16),
        struct((256,), jnp.bfloat16), struct((256,), jnp.bfloat16),
        struct((256,), jnp.float32), struct((256,), jnp.float32)
    ).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    activation = "bf16[256,256,56,56]"
    made = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) (fusion|reduce)"
                     r"\((.*?)\), (.*)", line)
            for line in entry.splitlines()]
    made = [m.groups() for m in made if m is not None]
    produced = [results for _, results, op, _, rest in made
                if "kind=kOutput" in rest and "conv_general_dilated" in rest]
    assert len(produced) == 1, produced
    assert produced[0].count(activation) == 1
    assert produced[0].count("f32[256]") == 2
    assert entry.count(" conditional(") == 1
    # every other instruction of the entry computation that makes only
    # per-channel vectors takes only vectors: none reads the activation
    holds = {m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = " + re.escape(activation), entry)}
    assert holds
    for _, results, _, operands, _ in made:
        if activation not in results:
            assert not holds & set(re.findall(r"%([\w.\-]+)", operands)), \
                (results, operands)
