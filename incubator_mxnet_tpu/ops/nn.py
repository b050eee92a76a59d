"""Core neural-network operators.

TPU-native equivalents of src/operator/nn/ (Convolution, FullyConnected,
BatchNorm, Pooling, Activation, Dropout, LRN, softmax, LayerNorm, ...) and
the legacy output/loss ops (softmax_output.cc, regression_output.cc).
Where the reference dispatches to cuDNN (src/operator/nn/cudnn/), we lower to
XLA convolutions / reduce_window — the TPU's MXU + fusion pipeline is the
"cuDNN" here, with autotuning owned by XLA (SURVEY §2.2 cuDNN row).

Layout note: the public API keeps MXNet's NCHW/OIHW conventions; XLA:TPU's
layout assignment re-tiles internally, so user code ports unchanged.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import metrics as _metrics
from ..telemetry.tracing import step_counter
from .registry import register


def _fc_param_shapes(data_shape, params):
    """ref: fully_connected.cc FInferShape fills weight/bias from data."""
    nh = params.get("num_hidden", 0)
    flatten = params.get("flatten", True)
    in_units = int(np.prod(data_shape[1:])) if flatten else data_shape[-1]
    return {"weight": (nh, in_units), "bias": (nh,)}


def _conv_param_shapes(data_shape, params):
    """ref: convolution.cc FInferShape."""
    nf = params.get("num_filter", 0)
    ng = params.get("num_group", 1)
    kernel = tuple(params.get("kernel", ()))
    return {"weight": (nf, data_shape[1] // ng) + kernel, "bias": (nf,)}


def _deconv_param_shapes(data_shape, params):
    """ref: deconvolution-inl.h — weight is (in, out/groups, *k)."""
    nf = params.get("num_filter", 0)
    ng = params.get("num_group", 1)
    kernel = tuple(params.get("kernel", ()))
    return {"weight": (data_shape[1], nf // ng) + kernel, "bias": (nf,)}


def _channel_param_shapes(data_shape, params):
    c = data_shape[params.get("axis", 1) % len(data_shape)]
    return {"gamma": (c,), "beta": (c,), "moving_mean": (c,),
            "moving_var": (c,)}


def _layernorm_param_shapes(data_shape, params):
    c = data_shape[params.get("axis", -1) % len(data_shape)]
    return {"gamma": (c,), "beta": (c,)}


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(v)
    return (v,) * n


# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------

@register("FullyConnected", num_inputs=None,
          input_names=("data", "weight", "bias"),
          finfer_params=_fc_param_shapes)
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False, flatten=True):
    """y = x·Wᵀ + b on the MXU (ref: fully_connected.cc:1)."""
    x = data.reshape((data.shape[0], -1)) if flatten else data
    out = jnp.dot(x, weight.T, preferred_element_type=jnp.promote_types(x.dtype, weight.dtype))
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: src/operator/nn/convolution.cc:383-509)
# ---------------------------------------------------------------------------

@register("Convolution", num_inputs=None,
          input_names=("data", "weight", "bias"),
          finfer_params=_conv_param_shapes, aliases=("Convolution_v1",))
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                 num_filter=0, num_group=1, no_bias=False, workspace=1024,
                 cudnn_tune=None, cudnn_off=False, layout=None):
    """N-d convolution, NCHW/OIHW (ref: convolution.cc; cuDNN path replaced
    by XLA's conv which tiles directly onto the MXU)."""
    nd = len(kernel) if kernel else data.ndim - 2
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    spatial = "".join("DHW"[3 - nd + i] for i in range(nd))
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.promote_types(data.dtype, weight.dtype))
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", num_inputs=None,
          input_names=("data", "weight", "bias"),
          finfer_params=_deconv_param_shapes)
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                   adj=(), target_shape=(), num_filter=0, num_group=1, no_bias=True,
                   workspace=512, cudnn_tune=None, cudnn_off=False, layout=None):
    """Transposed convolution (ref: src/operator/nn/deconvolution.cc).

    Implemented as the gradient of Convolution: lhs-dilated conv with the
    spatially-flipped kernel — exactly what XLA fuses best.  MXNet deconv
    weight layout is (in_c, out_c/g, kH, kW) i.e. IOHW.
    """
    nd = len(kernel)
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    adj = _pair(adj, nd) if adj else (0,) * nd
    spatial = "".join("DHW"[3 - nd + i] for i in range(nd))
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NC" + spatial, "IO" + spatial, "NC" + spatial))
    # effective kernel extent k' = dilate*(k-1)+1; output pad per side:
    pads = []
    for i in range(nd):
        k_eff = dilate[i] * (kernel[i] - 1) + 1
        lo = k_eff - 1 - pad[i]
        hi = k_eff - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    out = lax.conv_general_dilated(
        data, jnp.flip(weight, axis=tuple(range(2, 2 + nd))),
        window_strides=(1,) * nd,
        padding=pads,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.promote_types(data.dtype, weight.dtype))
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc, pool.h)
# ---------------------------------------------------------------------------

@register("Pooling", num_inputs=1, aliases=("Pooling_v1",))
def _pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(), pad=(),
             pooling_convention="valid", cudnn_off=False, p_value=2,
             count_include_pad=True):
    """max/avg/sum/lp pooling via lax.reduce_window (ref: pooling.cc)."""
    nd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    kernel = _pair(kernel, nd)
    stride = _pair(stride, nd) if stride else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    if pooling_convention == "full":
        # ceil-mode: pad high edge so ceil((x+2p-k)/s)+1 windows fit
        pads = []
        for i in range(nd):
            x = data.shape[2 + i]
            out_sz = int(np.ceil((x + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - x - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
    else:
        pads = [(p, p) for p in pad]
    padding = ((0, 0), (0, 0)) + tuple(pads)
    if pool_type == "max":
        if jnp.issubdtype(data.dtype, jnp.floating):
            # literal -inf: keeps XLA's select-and-scatter autodiff path
            return lax.reduce_window(data, -jnp.inf, lax.max,
                                     window, strides, padding)
        init = jnp.asarray(jnp.iinfo(data.dtype).min, data.dtype)
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        zero = (0.0 if jnp.issubdtype(data.dtype, jnp.floating)
                else jnp.asarray(0, data.dtype))
        s = lax.reduce_window(data, zero, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / float(np.prod(kernel))
        ones = jnp.ones(data.shape, data.dtype)
        cnt = lax.reduce_window(ones, zero, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        p = jnp.abs(data) ** p_value
        zero = (0.0 if jnp.issubdtype(p.dtype, jnp.floating)
                else jnp.asarray(0, p.dtype))
        s = lax.reduce_window(p, zero, lax.add, window, strides, padding)
        return s ** (1.0 / p_value)
    raise ValueError("unknown pool_type %r" % pool_type)


@register("UpSampling", num_inputs=None)
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
                multi_input_mode="concat", workspace=512):
    """ref: src/operator/upsampling.cc (nearest + bilinear via XLA resize)."""
    data = args[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


# ---------------------------------------------------------------------------
# Normalization (ref: src/operator/nn/batch_norm.cc, layer_norm.cc, lrn.cc)
# ---------------------------------------------------------------------------

def _bn_reduce_layout(data, axis):
    axis = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    m = float(np.prod([data.shape[i] for i in red]))
    return axis, red, bshape, m


# The one-pass variance S2/m - mean^2 is a difference of two float32 numbers
# 1 + mean^2/var times its size, each as good as its own sum's rounding (which
# the two-pass form's mean square has as well): it keeps float32's 24 bits less
# log2(1 + mean^2/var).  With mean^2 <= 2^8 var the relative error of var is
# about 2^8 * 2^-24 = 1.5e-5 times that sum's own factor (half of it in
# rsqrt(var + eps)), under the 2^-9 to which a bf16 result is rounded (2^-12 a
# float16 one) and far under a batch statistic's own sampling noise.  Past it
# (a channel whose batch mean lies more than 16 of its own standard deviations
# from zero) the exact two-pass form is taken.  A constant, not a setting:
# what it trades is a rounding, and the op decides from its own sums.
#
# A float32 activation shows every bit the difference loses, and is given the
# two passes as before: with this limit a 53-layer float32 ResNet at batch 4
# stands 1.5e-4 to 2.7e-4 of its largest logit from its two-pass reference
# (the two-pass form itself 2e-5 to 3e-5, sums in another order); with 2^4 it
# stands 4e-5 from it, but then the first layers of a ResNet-50 take the
# guarded branch in every step, which on the TPU costs more than the second
# pass it guards (the conditional is handed the activation in another layout).
_BN_ONE_PASS_LIMIT = 2.0 ** 8


def _bn_train_stats(data, axis):
    """Batch mean/var in f32 over a (possibly bf16) activation, and whether
    the second pass was taken (int32 0 or 1).

    A 16-bit activation, one pass: ``S1 = sum(x)`` and ``S2 = sum(x^2)``.
    Neither sum needs the other's result, so XLA takes both into the fusion
    that writes the activation (two ``jnp.sum``: the TPU compiler joins them
    in a convolution's output fusion and leaves a variadic ``lax.reduce`` a
    pass of its own).  Both read the input at its native precision with an
    f32 accumulator (the convert is in-register: no f32 copy of the
    activation in HBM).  ``mean = S1/m``, ``var = S2/m - mean^2``.

    The sums are taken about zero, a constant, and not about the layer's
    running mean: a shift that is auxiliary state would make the training
    forward a function of that state (the same weights and batch giving
    another loss in the last bits step after step, and a poisoned running
    mean reaching the training), which it never was.

    The guard: where any channel's mean lies further from zero than
    ``_BN_ONE_PASS_LIMIT`` allows (|mean| >> std, where the difference
    cancels) the variance is the two-pass form's, the mean square about the
    mean, under a ``lax.cond``; the mean is the same sum either way.  The
    predicate is written so that a NaN (unordered) takes the exact branch.

    Any other activation (float32): the two passes, always.
    """
    _, red, bshape, m = _bn_reduce_layout(data, axis)
    mean = jnp.sum(data, axis=red, dtype=jnp.float32) / m

    def second_pass(x):
        return jnp.mean(
            jnp.square(x.astype(jnp.float32) - mean.reshape(bshape)), axis=red)

    if data.dtype.itemsize != 2:
        return mean, second_pass(data), jnp.ones((), jnp.int32)
    var = jnp.maximum(
        jnp.sum(jnp.square(data.astype(jnp.float32)), axis=red) / m
        - jnp.square(mean), 0.0)
    far = jnp.any(~(jnp.square(mean) <= _BN_ONE_PASS_LIMIT * var))
    # Two barriers hold the conditional to what it is given and what it
    # gives.  On its operand: partitioned over a mesh, the branch's parameter
    # gets the default layout and the activation was copied into it outside
    # the conditional, in every step (15 ms of a 109-ms step at dp=4); behind
    # the barrier the copy stays inside the branch.  On its result: without
    # it XLA's conditional code motion takes the normalise pass's x - mean
    # into both branches and hands it out as a float32 copy of the
    # activation.
    held = lax.optimization_barrier(data)
    var = lax.cond(far, lambda: second_pass(held), lambda: var)
    return mean, lax.optimization_barrier(var), far.astype(jnp.int32)


def _bn_train_core_fwd(data, gamma, beta, axis, eps, fix_gamma):
    axis, _, bshape, _ = _bn_reduce_layout(data, axis)
    mean, var, second_pass = _bn_train_stats(data, axis)
    inv = lax.rsqrt(var + eps)
    g = jnp.ones_like(inv) if fix_gamma else gamma.astype(jnp.float32)
    scale = g * inv
    out = ((data.astype(jnp.float32) - mean.reshape(bshape))
           * scale.reshape(bshape)
           + beta.astype(jnp.float32).reshape(bshape)).astype(data.dtype)
    return out, mean, var, second_pass, inv, scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train_core(data, gamma, beta, axis, eps, fix_gamma):
    """Training-mode BN with a hand-derived backward; returns ``(out,
    batch_mean, batch_var, second_pass)``.

    Forward: the statistics in one pass, ``_bn_train_stats``;
    ``second_pass`` (int32, 1 where the guard sent the variance through the
    exact second pass) is a result because a traced value must not leave the
    rule any other way.

    Autodiff of the statistics chain costs ~2 extra full passes
    over the activation in f32; the closed-form BN backward (the same
    d-gamma/d-beta/dx decomposition cuDNN and batch_norm.cc:89 use) needs
    exactly two fused reductions over (dy, x) plus one elementwise pass —
    on the ResNet-50 bench this was worth ~20% end-to-end.
    """
    out, mean, var, second_pass, _, _ = _bn_train_core_fwd(
        data, gamma, beta, axis, eps, fix_gamma)
    return out, mean, var, second_pass


def _bn_train_core_fwd_rule(data, gamma, beta, axis, eps, fix_gamma):
    # symbolic_zeros=True wraps primal inputs in CustomVJPPrimal
    data, gamma, beta = data.value, gamma.value, beta.value
    # the backward reads the input as it is, and the guard's branch is handed
    # it whole: under a ``jax.checkpoint`` that keeps by primitive
    # (``CachedOp``'s) the name makes this the one value that is kept, where
    # without it the forward would write its producer's result to keep and
    # this one (that result plus a bias) for the ``cond``
    data = checkpoint_name(data, "bn_input")
    out, mean, var, second_pass, inv, scale = _bn_train_core_fwd(
        data, gamma, beta, axis, eps, fix_gamma)
    return (out, mean, var, second_pass), (data, gamma, mean, inv, scale)


def _bn_train_core_bwd_rule(axis, eps, fix_gamma, res, cotangents):
    from jax.custom_derivatives import SymbolicZero
    dy, ct_mean, ct_var, _ = cotangents
    data, gamma, mean, inv, scale = res
    axis, red, bshape, m = _bn_reduce_layout(data, axis)
    xc = data.astype(jnp.float32) - mean.reshape(bshape)
    if isinstance(dy, SymbolicZero):
        dx = jnp.zeros(data.shape, jnp.float32)
        dgamma_raw = jnp.zeros_like(mean)
        dbeta = jnp.zeros_like(mean)
    else:
        dyf = dy.astype(jnp.float32)
        xhat = xc * inv.reshape(bshape)
        # both reductions read (dy, x) once — XLA multi-output fuses them
        dbeta = jnp.sum(dyf, axis=red)
        dgamma_raw = jnp.sum(dyf * xhat, axis=red)
        dx = scale.reshape(bshape) * \
            (dyf - (dbeta.reshape(bshape) +
                    xhat * dgamma_raw.reshape(bshape)) / m)
    # Cotangents on the batch-statistics outputs (graphs that differentiate
    # through output_mean_var) fold straight into dx: dmean/dx = 1/m and
    # dvar/dx = 2(x-mean)/m (the cross-term through the mean cancels).  In
    # ordinary training graphs they are SymbolicZero and cost nothing.
    if not isinstance(ct_mean, SymbolicZero):
        dx = dx + ct_mean.astype(jnp.float32).reshape(bshape) / m
    if not isinstance(ct_var, SymbolicZero):
        dx = dx + ct_var.astype(jnp.float32).reshape(bshape) * 2.0 * xc / m
    dx = dx.astype(data.dtype)
    dgamma = (jnp.zeros_like(gamma) if fix_gamma
              else dgamma_raw.astype(gamma.dtype))
    return dx, dgamma, dbeta.astype(gamma.dtype)


_bn_train_core.defvjp(_bn_train_core_fwd_rule, _bn_train_core_bwd_rule,
                      symbolic_zeros=True)


@register("BatchNorm", num_inputs=5, num_outputs=3, num_visible_outputs=1,
          takes_is_train=True, nograd_inputs=(3, 4), aliases=("BatchNorm_v1",),
          step_counters=True,
          input_names=("data", "gamma", "beta", "moving_mean", "moving_var"),
          aux_input_names=("moving_mean", "moving_var"),
          finfer_params=_channel_param_shapes,
          fvisible=lambda params, n: n if params.get("output_mean_var") else 1)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False,
                axis=1, cudnn_off=False, is_train=False):
    """ref: batch_norm.cc:89.  Outputs (out, batch_mean, batch_var); the
    front-end updates the moving_* aux states with `momentum` outside the op,
    mirroring how the reference mutates aux arrays in-place."""
    if is_train and not use_global_stats:
        out, mean, var, second_pass = _bn_train_core(
            data, gamma, beta, axis, eps, bool(fix_gamma))
        step_counter("bn_second_pass", second_pass)
        return out, mean, var
    # inference / global-stats path: pure elementwise, autodiff is optimal
    axis, _, bshape, _ = _bn_reduce_layout(data, axis)
    mean = moving_mean.astype(jnp.float32)
    var = moving_var.astype(jnp.float32)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(var + eps)
    # normalize in f32 then cast once: x·s + (β − μ·s) folded in bf16
    # loses the large-mean channels to cancellation (bf16 mantissa ~8
    # bits), while (x − μ) first keeps only the final rounding; XLA
    # converts in-register so the HBM traffic stays at input precision
    out = (data.astype(jnp.float32) - mean.reshape(bshape)) * \
        (g.astype(jnp.float32) * inv).reshape(bshape) + \
        beta.astype(jnp.float32).reshape(bshape)
    return out.astype(data.dtype), mean, var


@register("LayerNorm", num_inputs=3, input_names=("data", "gamma", "beta"),
          finfer_params=_layernorm_param_shapes)
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """ref: src/operator/nn/layer_norm.cc"""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("RMSNorm", num_inputs=2, input_names=("data", "gamma"),
          finfer_params=lambda ds, p: {"gamma": (ds[-1],)})
def _rms_norm(data, gamma, eps=1e-5):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis (Zhang &
    Sennrich, arXiv:1910.07467): no mean is taken off and there is no
    shift.  The statistic is taken in float32 whatever the data's type, as
    the published decoder models do; the result has the data's type."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


_YARN_KEYS = ("factor", "original_max_position", "beta_fast", "beta_slow",
              "attention_factor")


def yarn_inverse_frequencies(dim, base, factor, original_max_position,
                             beta_fast=32.0, beta_slow=1.0):
    """YaRN's D/2 inverse frequencies (Peng et al., arXiv:2309.00071, the
    "NTK-by-parts" blend as HF ``transformers`` computes it): channel pair
    i turns by ``f_i = base^(-2i/D)`` a position where it makes more than
    ``beta_fast`` turns over the original context (kept, "extrapolated"),
    by ``f_i / factor`` where it makes fewer than ``beta_slow``
    ("interpolated"), and by a linear blend between the two pairs ``low``
    and ``high`` at which it makes exactly those.  On the host, float64."""
    pair = np.arange(dim // 2, dtype=np.float64)
    extrapolated = float(base) ** (-2.0 * pair / dim)

    def pair_of(turns):
        return (dim * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def _host_table(seq, inv, split=64):
    """cos and sin of ``t * inv`` for t < seq, (seq, len(inv)) float32,
    exact to float32's rounding at any position: position t = split * a + b
    turns by the sum of two angles whose cosines and sines are taken on the
    host in float64 ((seq / split + split) rows of constants, not seq), and
    the device only adds the angles: cos(A + B) = cos A cos B - sin A sin B."""
    inv = np.asarray(inv, np.float64)
    coarse = np.arange(0, seq, split, dtype=np.float64)[:, None] * inv
    fine = np.arange(split, dtype=np.float64)[:, None] * inv
    ca, sa = (jnp.asarray(f(coarse), jnp.float32)[:, None] for f in
              (np.cos, np.sin))
    cb, sb = (jnp.asarray(f(fine), jnp.float32)[None] for f in
              (np.cos, np.sin))
    return tuple(t.reshape(-1, inv.size)[:seq]
                 for t in (ca * cb - sa * sb, sa * cb + ca * sb))


@register("_contrib_RotaryEmbedding", num_inputs=1)
def _rotary_embedding(data, base=10000.0, scaling=None, dim=None):
    """Rotary positions (Su et al., arXiv:2104.09864) over the whole last
    axis of (B, H, S, D), rotate-half convention: channel i pairs with
    channel i + D/2, position t (row t of S) turns the pair by
    t * base^(-2i/D).  Angles and the rotation in float32; the result has
    the data's type.

    ``dim``: None, or the even number of a head's leading channels that are
    turned, as a head of ``dim`` channels would be (channel i pairs with
    i + dim/2 and turns by t * base^(-2i/dim)); the other D - dim pass as
    they are (a partial rotary: Qwen3-Next turns 64 of 256).  None stages
    the program it staged before the argument existed.

    ``scaling``: None, or YaRN's parameters as a mapping with the keys
    ``factor``, ``original_max_position`` and optionally ``beta_fast``
    (32), ``beta_slow`` (1) and ``attention_factor`` (0.1 ln(factor) + 1):
    the pairs turn by ``yarn_inverse_frequencies`` and the cosines and
    sines are multiplied by ``attention_factor``, at every position (the
    scaling is static, not by the sequence's length); ``factor`` 1 is the
    plain frequencies.  With a scaling the cosines and sines come from the
    host (``_host_table``): a TPU's float32 cosine of an angle of thousands
    of radians is good to a hundredth of a radian and no better (PERF.md,
    PR 30: two compilations of the same formula parted by 1e-2 at 8192
    positions), which a layer that attends far back shows."""
    if dim is not None and dim != data.shape[-1]:
        if not 0 < dim < data.shape[-1]:
            raise ValueError("dim (%d) counts channels of a head of %d"
                             % (dim, data.shape[-1]))
        with jax.named_scope("rope"):
            turned = _rotary_embedding(data[..., :dim], base, scaling)
            return jnp.concatenate([turned, data[..., dim:]], axis=-1)
    d, seq = data.shape[-1], data.shape[-2]
    if d % 2:
        raise ValueError("rotary positions need an even head dimension, "
                         "got %d" % d)
    _metrics.rotary_dim(d)
    with jax.named_scope("rope"):
        if scaling is None:
            inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv
            cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
            sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
        else:
            scaling = dict(scaling)
            unknown = set(scaling) - set(_YARN_KEYS)
            if unknown or not {"factor", "original_max_position"} <= set(
                    scaling):
                raise ValueError(
                    "scaling takes the keys %s (the first two required), "
                    "got %s" % (", ".join(_YARN_KEYS), sorted(scaling)))
            factor = scaling.pop("attention_factor", None)
            if factor is None:
                factor = 0.1 * math.log(scaling["factor"]) + 1.0
            cos, sin = (jnp.concatenate([t * factor] * 2, axis=-1)
                        for t in _host_table(seq, yarn_inverse_frequencies(
                            d, base, **scaling)))
        x = data.astype(jnp.float32)
        turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
        return (x * cos + turned * sin).astype(data.dtype)


@register("_contrib_CausalConv1D", num_inputs=2,
          input_names=("data", "weight"),
          finfer_params=lambda ds, p: {"weight": (ds[-1], p["kernel"])})
def _causal_conv1d(data, weight, kernel=3):
    """Depthwise causal convolution along the sequence of (B, S, C) with
    ``weight`` (C, kernel) and no bias: out[t] = sum_j weight[:, j] *
    data[t - (kernel - 1) + j], rows before the first taken as zero, so no
    output row sees a later input row.  ``kernel`` shifted multiply-adds:
    at kernel 3 that is cheaper on the VPU than a convolution's set-up."""
    if weight.shape != (data.shape[-1], kernel):
        raise ValueError("weight %s does not fit data %s at kernel %d"
                         % (weight.shape, data.shape, kernel))
    seq = data.shape[1]
    padded = jnp.pad(data, ((0, 0), (kernel - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * weight[:, j] for j in range(kernel))


@register("InstanceNorm", num_inputs=3,
          input_names=("data", "gamma", "beta"),
          finfer_params=lambda ds, p: {"gamma": (ds[1],), "beta": (ds[1],)})
def _instance_norm(data, gamma, beta, eps=1e-3):
    """ref: src/operator/instance_norm.cc"""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LRN", num_inputs=1)
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (ref: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    summed = lax.reduce_window(sq, 0.0, lax.add, (1, nsize, 1, 1), (1, 1, 1, 1),
                               ((0, 0), (half, half), (0, 0), (0, 0)))
    return data * jnp.power(knorm + (alpha / nsize) * summed, -beta)


# ---------------------------------------------------------------------------
# Activations (ref: src/operator/nn/activation.cc, leaky_relu.cc, softmax.cc)
# ---------------------------------------------------------------------------

@register("Activation", num_inputs=1)
def _activation(data, act_type="relu"):
    """ref: activation.cc"""
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "silu":
        return jax.nn.silu(data)
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU", num_inputs=None, needs_rng=True, takes_is_train=True,
          fargnames=lambda p: ("data", "gamma") if p.get("act_type") == "prelu"
          else ("data",))
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334, rng=None, is_train=False):
    """ref: src/operator/leaky_relu.cc (leaky/elu/prelu/rrelu)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "rrelu":
        if is_train:
            s = jax.random.uniform(rng, data.shape, data.dtype, lower_bound, upper_bound)
        else:
            s = jnp.asarray((lower_bound + upper_bound) / 2.0, data.dtype)
        return jnp.where(data > 0, data, s * data)
    raise ValueError("unknown act_type %r" % act_type)


@register("softmax", num_inputs=1)
def _softmax(data, axis=-1, temperature=None):
    """ref: src/operator/nn/softmax.cc"""
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax", num_inputs=1)
def _log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("SoftmaxActivation", num_inputs=1)
def _softmax_activation(data, mode="instance"):
    """ref: src/operator/nn/softmax_activation.cc"""
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def inverted_dropout(data, p, mask_shape, rng, op):
    """``data`` under a fresh dropout mask of ``mask_shape`` (which broadcasts
    to it): a kept element is ``data / (1 - p)`` in ``data``'s dtype, the
    rest 0.  Every dropout mask in the package is drawn here.

    ``rng`` is the framework's raw ``uint32[2]`` key.  Its threefry gives the
    four words of state of the backend's bit generator (XLA's
    ``RngBitGenerator``: on the TPU the chip's own), which draws 32 bits an
    element in one op; an element is kept where its word is under
    ``floor((1 - p) * 2**32)``, so the share kept is exact to 2**-32 and no
    float is made.  (A ``jax.random.bernoulli`` mask is a threefry of the
    mask's size, which XLA computes again inside every fusion that reads
    it.)  The same key and shape give the same mask in any program of one
    backend and device layout, not across backends (docs/robustness.md).
    ``op`` labels the trace in ``graft_dropout_mask_traces_total``."""
    _metrics.dropout_mask_trace(op)
    keep = 1.0 - p
    with jax.named_scope("dropout"):
        state = jax.random.bits(rng, (4,), jnp.uint32)
        _, bits = lax.rng_bit_generator(state, mask_shape, dtype=jnp.uint32)
        mask = bits < jnp.uint32(int(keep * 2 ** 32))
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype))


@register("Dropout", num_inputs=1, needs_rng=True, takes_is_train=True)
def _dropout(data, p=0.5, mode="training", axes=(), rng=None, is_train=False):
    """Inverted dropout (ref: src/operator/nn/dropout.cc)."""
    if (not is_train and mode != "always") or p == 0.0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    return inverted_dropout(data, p, shape, rng, "Dropout")


# ---------------------------------------------------------------------------
# Legacy output/loss ops with integrated gradients
# (ref: src/operator/softmax_output.cc, regression_output.cc, svm_output.cc)
# ---------------------------------------------------------------------------

def _custom_loss_fwd_bwd(fwd_fn, grad_fn):
    """Build an op whose backward ignores upstream grad, like the reference's
    *Output ops: backward of SoftmaxOutput is (softmax - onehot(label)) no
    matter what (softmax_output.cc)."""
    @jax.custom_vjp
    def f(data, label):
        return fwd_fn(data, label)

    def fwd(data, label):
        return fwd_fn(data, label), (data, label)

    def bwd(res, g):
        data, label = res
        return grad_fn(data, label), jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", num_inputs=2, nograd_inputs=(1,),
          input_names=("data", "label"), aliases=("Softmax",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False,
                    use_ignore=False, preserve_shape=False, normalization="null",
                    out_grad=False, smooth_alpha=0.0):
    """ref: src/operator/softmax_output.cc — fwd softmax, bwd p - onehot(y)."""
    axis = 1 if (multi_output or preserve_shape or data.ndim > 2) else -1

    def fwd_fn(d, l):
        return jax.nn.softmax(d, axis=axis)

    def grad_fn(d, l):
        p = jax.nn.softmax(d, axis=axis)
        k = d.shape[axis]
        lab = l.astype(jnp.int32)
        oh = jax.nn.one_hot(lab, k, dtype=d.dtype, axis=axis)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / (k - 1) * (1 - oh)
        g = p - oh
        if use_ignore:
            valid = (l != ignore_label).astype(d.dtype)
            g = g * jnp.expand_dims(valid, axis)
        scale = grad_scale
        if normalization == "batch":
            scale = scale / d.shape[0]
        elif normalization == "valid" and use_ignore:
            nvalid = jnp.maximum(jnp.sum(l != ignore_label), 1).astype(d.dtype)
            return g * (grad_scale / nvalid)
        return g * scale

    return _custom_loss_fwd_bwd(fwd_fn, grad_fn)(data, label)


@register("LinearRegressionOutput", num_inputs=2, nograd_inputs=(1,),
          input_names=("data", "label"))
def _linear_regression_output(data, label, grad_scale=1.0):
    """ref: regression_output.cc — fwd identity, bwd (pred - label)."""
    return _custom_loss_fwd_bwd(
        lambda d, l: d,
        lambda d, l: (d - l.reshape(d.shape)) * grad_scale)(data, label)


@register("MAERegressionOutput", num_inputs=2, nograd_inputs=(1,),
          input_names=("data", "label"))
def _mae_regression_output(data, label, grad_scale=1.0):
    return _custom_loss_fwd_bwd(
        lambda d, l: d,
        lambda d, l: jnp.sign(d - l.reshape(d.shape)) * grad_scale)(data, label)


@register("LogisticRegressionOutput", num_inputs=2, nograd_inputs=(1,),
          input_names=("data", "label"))
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _custom_loss_fwd_bwd(
        lambda d, l: jax.nn.sigmoid(d),
        lambda d, l: (jax.nn.sigmoid(d) - l.reshape(d.shape)) * grad_scale)(data, label)


@register("SVMOutput", num_inputs=2, nograd_inputs=(1,),
          input_names=("data", "label"))
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    """ref: src/operator/svm_output.cc"""
    def grad_fn(d, l):
        k = d.shape[1]
        oh = jax.nn.one_hot(l.astype(jnp.int32), k, dtype=d.dtype)
        if use_linear:
            viol = ((margin - d) * oh + (margin + d) * (1 - oh)) > 0
            g = jnp.where(viol, (1 - oh) - oh, 0.0) * regularization_coefficient
        else:
            score_y = jnp.sum(d * oh, axis=1, keepdims=True)
            viol = (d - score_y + margin) > 0
            g_other = jnp.where(viol & (oh == 0), 2.0 * (d - score_y + margin), 0.0)
            g = g_other - oh * jnp.sum(g_other, axis=1, keepdims=True)
            g = g * regularization_coefficient
        return g.astype(d.dtype)

    return _custom_loss_fwd_bwd(lambda d, l: d, grad_fn)(data, label)
