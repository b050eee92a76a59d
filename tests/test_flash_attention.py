"""The Pallas flash-attention kernels of ``ops/attention.py``, forward and
backward: run by the Pallas interpreter on the CPU against autodiff of the
dense reference, and compiled at the OPT cell's shape for a chip that is
described, not attached (on-chip-measurement guide, section 2: nothing at
import, the topology in a fixture).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import attention as A

# the largest error over the largest reference value: f32 kernels differ
# from the reference in the order of their sums alone; bf16 results are
# rounded to 8 bits, and so are p and dS on their way into the MXU
TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}


def _problem(dtype, sq, sk, d, seed=0, dv=None):
    """q, k, v, dO: keys ``d`` wide, values (and dO) ``dv``, or ``d``."""
    rs = np.random.RandomState(seed)
    dv = d if dv is None else dv
    return tuple(jnp.asarray(rs.randn(1, 2, s, w), dtype)
                 for s, w in ((sq, d), (sk, d), (sk, dv), (sq, dv)))


def _reference(q, k, v, g, causal, scale):
    """out, m, l, dq, dk, dv of the dense reference on the same (rounded)
    operands, upcast, with every matmul at full precision."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        (out, m, l), vjp = jax.vjp(
            lambda a, b, c: A._attention_reference_stats(a, b, c, causal,
                                                         scale), *f32)
        grads = vjp((g.astype(jnp.float32), jnp.zeros_like(m),
                     jnp.zeros_like(l)))
    return (out, m, l, *grads)


def _kernels(q, k, v, g, causal, scale, block=128):
    """The same six from the kernels themselves, in 128-blocks so that
    every length here spans several."""
    out, m, l = A._flash_forward_pallas(q, k, v, causal, scale, block, block,
                                        interpret=True)
    grads = A._flash_backward_pallas(q, k, v, out, m, l, g, causal, scale,
                                     block, block, interpret=True)
    return (out, m, l, *grads)


def _error(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert got.shape == want.shape and np.all(np.isfinite(got))
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sq,sk", [(256, 256), (128, 256), (256, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_match_autodiff_of_the_reference(dtype, causal, sq, sk, d):
    q, k, v, g = _problem(jnp.dtype(dtype), sq, sk, d)
    scale = d ** -0.5
    want = _reference(q, k, v, g, causal, scale)
    got = _kernels(q, k, v, g, causal, scale)
    for name, a, b in zip(("out", "m", "l", "dq", "dk", "dv"), got, want):
        # the statistics are f32 whatever the operands are
        tol = TOLERANCE["float32" if name in ("m", "l") else dtype]
        assert _error(a, b) <= tol, name
    # the jnp scan, fed the kernel's statistics, is the other backward
    scan = A._flash_backward_scan(q, k, v, *got[:3], g, causal=causal,
                                  scale=scale)
    for name, a, b in zip(("dq", "dk", "dv"), scan, want[3:]):
        assert _error(a, b) <= TOLERANCE[dtype], name


# keys over values: latent attention's 192 over 128 (the key padded to 256
# lanes, the value not), both whole lanes, both padded, a value wider than
# the key, differential attention's 64 over 128 (a pair's two value heads
# side by side: the key padded to 128 lanes)
TWO_WIDTHS = [(192, 128), (256, 128), (48, 16), (128, 256), (64, 128)]


@pytest.mark.parametrize("dqk,dv", TWO_WIDTHS)
@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_take_a_value_width_of_its_own(dtype, window, dqk, dv):
    """q and k ``dqk`` wide, v, the output and dO ``dv`` wide: forward, dQ,
    dK and dV of the kernels, and of the jnp scan fed the kernel's
    statistics, against autodiff of the dense reference; every result has
    its operand's own width."""
    q, k, v, g = _problem(jnp.dtype(dtype), 256, 256, dqk, seed=3, dv=dv)
    scale = dqk ** -0.5
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(lambda a, b, c: A._attention_reference(
            a, b, c, True, scale, window), *f32)
        want = (want, *vjp(g.astype(jnp.float32)))
    out, m, l = A._flash_forward_pallas(q, k, v, True, scale, 128, 128,
                                        interpret=True, window=window)
    got = (out, *A._flash_backward_pallas(
        q, k, v, out, m, l, g, True, scale, 128, 128, interpret=True,
        window=window))
    assert [x.shape[-1] for x in got] == [dv, dqk, dqk, dv]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert _error(a, b) <= TOLERANCE[dtype], name
    scan = A._flash_backward_scan(q, k, v, out, m, l, g, causal=True,
                                  scale=scale, window=window)
    for name, a, b in zip(("dq", "dk", "dv"), scan, want[1:]):
        assert _error(a, b) <= TOLERANCE[dtype], name


def test_the_op_differentiates_at_two_widths_and_gauges_them():
    """``flash_attention`` off the TPU (the reference forward, the scan
    backward) at keys of 24 over values of 8, with the default scale
    1 / sqrt(24); the gauge says which widths the call was traced at."""
    q, k, v, g = _problem(jnp.float32, 128, 128, 24, seed=4, dv=8)
    out, vjp = jax.vjp(lambda a, b, c: A.flash_attention(a, b, c, True),
                       q, k, v)
    want, want_vjp = jax.vjp(lambda a, b, c: A._attention_reference(
        a, b, c, True, 24 ** -0.5), q, k, v)
    assert out.shape == (1, 2, 128, 8)
    for a, b in zip((out, *vjp(g)), (want, *want_vjp(g))):
        assert _error(a, b) <= TOLERANCE["float32"]
    gauge = telemetry.registry().snapshot()["graft_flash_head_dim"]
    assert {s["labels"]["part"]: s["value"] for s in gauge["samples"]} == {
        "qk": 24.0, "v": 8.0}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_that_see_no_key_are_finite_and_carry_no_dq_dk(dtype):
    """Sq 256 > Sk 128, causal, the diagonal anchored at the end: the first
    128 rows see nothing.  Their output is the reference's (uniform over
    every key), they reach dV and nothing else."""
    sq, sk, d = 256, 128, 128
    q, k, v, g = _problem(jnp.dtype(dtype), sq, sk, d, seed=1)
    out, m, l, dq, dk, dv = _kernels(q, k, v, g, True, d ** -0.5)
    blind = sq - sk
    for x in (out, m, l, dq, dk, dv):
        assert np.all(np.isfinite(np.asarray(x, np.float32)))
    assert np.all(np.asarray(m)[..., :blind] == np.float32(A._NEG_INF))
    assert np.all(np.asarray(l)[..., :blind] == sk)
    assert np.all(np.asarray(dq, np.float32)[:, :, :blind] == 0.0)
    # dK is that of the rows that see: the square problem below them
    _, _, _, _, dk_seeing, _ = _kernels(
        q[:, :, blind:], k, v, g[:, :, blind:], True, d ** -0.5)
    assert _error(dk, dk_seeing) <= TOLERANCE[dtype]


def test_one_bf16_pass_is_the_highest_product_of_the_upcast_operands():
    """A product of two bf16 numbers is exact in f32: one pass with f32
    accumulation is the six-pass product up to the order of the sums."""
    rs = np.random.RandomState(2)
    q, k = (jnp.asarray(rs.randn(128, 128), jnp.bfloat16) for _ in range(2))
    one_pass = A._dot(q, k, A._NT)
    assert one_pass.dtype == jnp.float32
    six = A._dot(q.astype(jnp.float32), k.astype(jnp.float32), A._NT)
    assert _error(one_pass, six) <= 1e-6


def _paths():
    snap = telemetry.registry().snapshot().get(
        "graft_flash_attention_traces_total", {"samples": []})
    return {s["labels"]["path"]: int(s["value"]) for s in snap["samples"]}


def test_backward_traces_are_counted_under_bwd_labels():
    before = _paths()
    q = jnp.ones((1, 1, 128, 8), jnp.float32)
    short = q[:, :, :16]
    loss = lambda a: A.flash_attention(a, a, a, True).sum()   # noqa: E731
    jax.grad(loss)(q)
    jax.jit(jax.grad(loss))(q)
    jax.grad(loss)(short)
    new = {p: c - before.get(p, 0) for p, c in _paths().items()}
    new = {p: c for p, c in new.items() if c}
    assert new == {"reference_off_tpu": 1, "bwd_scan_off_tpu": 1,
                   "lowering_platform": 1, "bwd_lowering_platform": 1,
                   "reference_unaligned": 1, "bwd_scan_unaligned": 1}
    assert not any(p.startswith("reference") for p in A._BWD_PATHS)


# ---------------------------------------------------------------------------
# the three kernels at the cell's shape, for a chip that is described
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
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_grad_compiles_to_three_kernels_at_the_opt_cells_shape(
        topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        (4, 32, 2048, 128), jnp.bfloat16,
        sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        return A.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec, spec, spec).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.match(r"\s*(?:ROOT )?%(\S+) =", line).group(1)
             for line in calls]
    op_names = [re.search(r'op_name="([^"]+)"', line).group(1)
                for line in calls]
    # what layer_metrics/flash_fwd.py books against the forward's FLOPs
    forward = [n for n in names if "flash_attention_pallas" in n]
    assert len(forward) == 1 and len(calls) == 3, names
    assert all("flash_attention_bwd" in o
               for n, o in zip(names, op_names) if n not in forward)
    # the scan is gone from the TPU's program, and its score tiles with it
    scoped = [line for line in text.splitlines()
              if "flash_attention_bwd" in line]
    assert scoped and not any(re.search(r"\bwhile\(", s) for s in scoped)
    assert not re.search(r"f32\[[\d,]*2048,1024\]", text)


def _loss_of(window):
    def loss(q, k, v):
        return A.flash_attention(q, k, v, True, None, window).astype(
            jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


def _kernels_of_the_grad(window, spec):
    """The names of the Mosaic calls, sorted, in the gradient of
    ``flash_attention`` compiled for q, k, v of ``spec``."""
    text = jax.jit(_loss_of(window)).lower(spec, spec, spec).compile(
        ).as_text()
    return sorted(re.match(r"\s*(?:ROOT )?%(\S+) =", line).group(1).split(
        ".")[0] for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)


def test_window_grad_compiles_to_three_named_kernels_at_the_mellum_cells_shape(
        topo, no_compile_cache):
    """(1, 32, 8192, 128) bf16 under a window of 1024: Mosaic takes the three
    banded kernels, and a trace tells them from the full layers' by name."""
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        (1, 32, 8192, 128), jnp.bfloat16,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert _kernels_of_the_grad(1024, spec) == [
        "flash_window_bwd_dkv", "flash_window_bwd_dq", "flash_window_pallas"]


@pytest.mark.parametrize("window, names", [
    (2048, ["flash_window_bwd_dkv", "flash_window_bwd_dq",
            "flash_window_pallas"]),
    (None, ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
            "flash_attention_pallas"])])
def test_grad_compiles_at_the_gated_cells_shapes(topo, no_compile_cache,
                                                 window, names):
    """(1, 32, 4096, 128) bf16, ``trinitymini_gated_fused_1row``'s row: under
    a window of 2048, half the row (its four sliding layers), and without one
    (its full layer), Mosaic takes three kernels of the kind's names."""
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        (1, 32, 4096, 128), jnp.bfloat16,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert _kernels_of_the_grad(window, spec) == names


# sha256 of each kernel's Mosaic module printed without debug locations, as
# commit 0112fb9 (the parent of the PR that brought windows) lowered
# ``grad(flash_attention(q, k, v, causal=True))`` for a v5e, in the order
# forward, dK/dV, dQ: the OPT cells' shape and LFM2's.  A PR that changes the
# causal kernels on purpose records them anew; one that adds an option beside
# them may not move them.  Mellum2's two (the full layer's shape here, its
# window of 1024 below) are as commit f52de4a, the parent of the PR that gave
# v a width of its own, lowered them.
KERNELS_WITHOUT_A_WINDOW = {
    (4, 32, 2048, 128): ["70a2c89c474f497a", "904bd27c9616a081",
                         "37cf7cdab58b8d03"],
    (1, 32, 8192, 64): ["709ed1065112820b", "3706ba7af9bf260f",
                        "71b2bad4e5eb6fb8"],
    (1, 32, 8192, 128): ["a00e90e60e16610c", "dfe140416ee8fb32",
                         "dc71e49c8d147f3b"],
}
KERNELS_UNDER_MELLUMS_WINDOW = {
    ((1, 32, 8192, 128), 1024): ["c016666f86115bb6", "03e4f20088f8eb17",
                                 "33ed446b6a779ae9"],
}
# what each call tells the compiler of its cost, as the same commits did:
# (FLOPs, bytes) forward, dK/dV, dQ.  With one width the two-width count is
# the one-width count.
COST_ESTIMATES = {
    (4, 32, 2048, 128): [(274877906944, 270532608),
                         (549755813888, 405798912),
                         (412316860416, 338690048)],
    (1, 32, 8192, 64): [(1099511627776, 270532608),
                        (2199023255552, 405798912),
                        (1649267441664, 338690048)],
}


def _mosaic_modules(lowered_text):
    """The Mosaic modules of a lowering's ``tpu_custom_call``s as text with
    no debug locations (a kernel's line numbers are no part of it)."""
    import base64
    import json
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    found = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                         lowered_text):
        raw = (m.group(1).replace("\\22", '"').replace("\\5C", "\\")
               .replace("\\0A", " "))
        body = base64.b64decode(
            json.loads(raw)["custom_call_config"]["body"])
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            found.append(ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False))
    return found


@pytest.mark.parametrize("shape", sorted(KERNELS_WITHOUT_A_WINDOW))
def test_without_a_window_the_kernels_are_the_programs_they_were(
        topo, no_compile_cache, shape):
    """``window=None`` (and a window that holds every key) lower to the
    kernels the OPT and LFM2 cells ran before the window existed."""
    import hashlib
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=SingleDeviceSharding(topo.devices[0]))
    for window in (None, shape[2]):
        text = jax.jit(_loss_of(window)).lower(spec, spec, spec).as_text()
        digests = [hashlib.sha256(asm.encode()).hexdigest()[:16]
                   for asm in _mosaic_modules(text)]
        assert digests == KERNELS_WITHOUT_A_WINDOW[shape], window
    if shape in COST_ESTIMATES:
        costs = [(int(f), int(b)) for b, f in re.findall(
            r'bytes_accessed\\22:(\d+),\\0A\\22flops\\22:(\d+)', text)]
        assert costs == COST_ESTIMATES[shape]


@pytest.mark.parametrize("shape,window", sorted(KERNELS_UNDER_MELLUMS_WINDOW))
def test_one_width_under_a_window_is_the_program_it_was(
        topo, no_compile_cache, shape, window):
    """Mellum2's window layers: q, k and v of one width lower to the banded
    kernels they lowered to before v could have a width of its own."""
    import hashlib
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=SingleDeviceSharding(topo.devices[0]))
    text = jax.jit(_loss_of(window)).lower(spec, spec, spec).as_text()
    digests = [hashlib.sha256(asm.encode()).hexdigest()[:16]
               for asm in _mosaic_modules(text)]
    assert digests == KERNELS_UNDER_MELLUMS_WINDOW[shape, window]


def test_grad_compiles_at_the_latent_cells_two_widths(topo, no_compile_cache):
    """(1, 16, 4096, 192) keys over (1, 16, 4096, 128) values in bf16,
    causal: Mosaic takes the three kernels; the forward writes 128 lanes a
    head, dK comes at the key's 256 padded lanes and dV at the value's 128:
    v is not padded to the key's width."""
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((1, 16, 4096, 192), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16, sharding=chip)
    text = jax.jit(_loss_of(None)).lower(qk, qk, v).compile().as_text()
    calls = {re.match(r"\s*(?:ROOT )?%(\w+)", line).group(1):
             line.split(" custom-call(")[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert sorted(calls) == ["flash_attention_bwd_dkv",
                             "flash_attention_bwd_dq",
                             "flash_attention_pallas"]
    assert "bf16[16,4096,128]" in calls["flash_attention_pallas"]
    assert "bf16[16,4096,256]" not in calls["flash_attention_pallas"]
    assert ("bf16[16,4096,256]" in calls["flash_attention_bwd_dkv"]
            and "bf16[16,4096,128]" in calls["flash_attention_bwd_dkv"])
    assert "bf16[16,4096,256]" in calls["flash_attention_bwd_dq"]


@pytest.mark.parametrize("window", [512, None])
def test_grad_compiles_at_the_differential_cells_shape(topo, no_compile_cache,
                                                       window):
    """(1, 40, 4096, 64) keys over (1, 40, 4096, 128) values in bf16, under
    a window of 512 and without: the first shape that uses the banded grid
    and two head widths in one call (``phi4flash_sambay_fused_1row``).
    Mosaic takes the three kernels of the kind; q, k run at 128 padded lanes
    and v, out at their own 128."""
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((1, 40, 4096, 64), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((1, 40, 4096, 128), jnp.bfloat16, sharding=chip)
    text = jax.jit(_loss_of(window)).lower(qk, qk, v).compile().as_text()
    names = sorted(re.match(r"\s*(?:ROOT )?%(\w+?)(?:\.\d+)? =", line).group(1)
                   for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line)
    stem = "flash_attention" if window is None else "flash_window"
    assert names == [stem + "_bwd_dkv", stem + "_bwd_dq", stem + "_pallas"]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_scan_kernels_compile_at_the_state_space_cells_shape(
        topo, no_compile_cache, dtype):
    """``ops/ssm.py``'s two kernels at one row of 4096 steps, 5120 channels
    of 16 states (``phi4flash_sambay_fused_1row``; float32 is the forward
    check's): Mosaic takes both, and nothing of size L x 5120 x 16 is among
    the program's temporaries (1.34 GB in float32)."""
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu.ops import ssm
    chip = SingleDeviceSharding(topo.devices[0])
    length, channels, states = 4096, 5120, 16

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)

    ops = (spec(1, length, channels), spec(1, length, channels),
           spec(channels, states), spec(1, length, states),
           spec(1, length, states), spec(channels))
    assert ssm._kernels_take(ops[0], ops[2], ssm.CHUNK)

    def loss(*a):
        # the kernels themselves: under ``jit`` alone ``_choose`` would leave
        # the choice to the lowering, which is this test's too
        return ssm.selective_scan(*a).astype(jnp.float32).sum()

    # as ``run.py::reference_check`` calls the Block: Mosaic refuses a bf16
    # product at float32 precision, which the context would hand the
    # kernels' one-hot products (found on the chip, PR 38)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
            *ops).compile()
    text = compiled.as_text()
    names = sorted(re.match(r"\s*(?:ROOT )?%(\w+?)(?:\.\d+)? =", line).group(1)
                   for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line)
    assert names == ["selective_scan_bwd", "selective_scan_pallas"]
    every_state = 4 * length * channels * states
    assert compiled.memory_analysis().temp_size_in_bytes < every_state // 4
