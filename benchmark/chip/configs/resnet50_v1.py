"""ResNet v1 (He et al., arXiv:1512.03385) for the chip benchmark.

* ``build``      the model zoo's own network (the system under test);
* ``reference``  the same network in plain float32 ``jax.numpy`` with
                 train-mode BatchNorm, written from the paper's Table 1 and
                 the MXNet 1.x zoo's two quirks (see the JSON's
                 ``assumed``), sharing no code with ``gluon``;
* ``train_flops_per_sample``  model FLOPs of one image, forward and
                 backward, from the enumerated convolution and dense shapes.

Both block kinds (the 50-layer bottleneck and the 18-layer basic block of
the CPU rehearsal) come from the sizes in the JSON beside this file.
"""

PREFIX = "resnet_"


def build(cfg):
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    return getattr(vision, cfg["model"])(classes=cfg["classes"],
                                         thumbnail=cfg["thumbnail"],
                                         prefix=PREFIX)


def initializer(cfg):
    import incubator_mxnet_tpu as mx
    return mx.init.Xavier()


def sample_shape(cfg, traffic):
    return (cfg["in_channels"], cfg["image_size"], cfg["image_size"])


def samples_per_row(cfg, traffic):
    return 1


def check_rows(cfg, traffic):
    """Rows the Block's own forward is compared on: train-mode BatchNorm
    takes its statistics over the batch, so more than one."""
    return 2


# ---------------------------------------------------------------------------
# the network as a list of layers, from the sizes alone
# ---------------------------------------------------------------------------

def _block_plan(cfg, width, stride):
    """(out channels, kernel, stride, padding, bias) of a block's trunk."""
    if cfg["block"] == "bottleneck":
        return [(width // 4, 1, stride, 0, True),
                (width // 4, 3, 1, 1, False),
                (width, 1, 1, 0, True)]
    return [(width, 3, stride, 1, False), (width, 3, 1, 1, False)]


def _walk(cfg):
    """Yield the network's structure: ("stem", conv) then one
    ("block", stage, trunk convs, shortcut conv or None) per residual
    block.  A conv is (name, c_in, c_out, kernel, stride, pad, bias,
    batchnorm name); names are the zoo's checkpoint keys without prefix."""
    ch = cfg["stage_channels"]
    if cfg["thumbnail"]:
        yield "stem", ("conv0_", cfg["in_channels"], ch[0], 3, 1, 1, False,
                       None)
    else:
        yield "stem", ("conv0_", cfg["in_channels"], ch[0], 7, 2, 3, False,
                       "batchnorm0_")
    for s, reps in enumerate(cfg["stage_blocks"]):
        width, c_in = ch[s + 1], ch[s]
        n_conv = n_bn = 0
        for b in range(reps):
            stride = 2 if (b == 0 and s > 0) else 1
            trunk, c = [], c_in
            for c_out, k, st, pad, bias in _block_plan(cfg, width, stride):
                trunk.append(("stage%d_conv%d_" % (s + 1, n_conv), c, c_out,
                              k, st, pad, bias,
                              "stage%d_batchnorm%d_" % (s + 1, n_bn)))
                n_conv, n_bn, c = n_conv + 1, n_bn + 1, c_out
            shortcut = None
            if b == 0 and width != c_in:
                shortcut = ("stage%d_conv%d_" % (s + 1, n_conv), c_in, width,
                            1, stride, 0, False,
                            "stage%d_batchnorm%d_" % (s + 1, n_bn))
                n_conv, n_bn = n_conv + 1, n_bn + 1
            yield "block", (trunk, shortcut)
            c_in = width


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

def reference(cfg, params, images):
    """Logits (B, classes) for float32 images (B, C, H, W), BatchNorm in
    train mode (statistics of this batch, biased variance).  ``params``
    maps a parameter's name without the model prefix to a float32 array:
    convolution weights (out, in, kh, kw), the dense weight (out, in).
    Call it under ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    p = params
    eps = cfg["batchnorm_eps"]

    def conv_bn(x, spec):
        name, _, _, k, stride, pad, bias, bn = spec
        y = lax.conv_general_dilated(
            x, p[name + "weight"], (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if bias:
            y = y + p[name + "bias"][None, :, None, None]
        if bn is None:
            return y
        mean = y.mean((0, 2, 3), keepdims=True)
        var = ((y - mean) ** 2).mean((0, 2, 3), keepdims=True)
        return ((y - mean) / jnp.sqrt(var + eps)
                * p[bn + "gamma"][None, :, None, None]
                + p[bn + "beta"][None, :, None, None])

    x = images
    for kind, item in _walk(cfg):
        if kind == "stem":
            x = conv_bn(x, item)
            if not cfg["thumbnail"]:
                x = jax.nn.relu(x)
                x = lax.reduce_window(
                    x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                    [(0, 0), (0, 0), (1, 1), (1, 1)])
            continue
        trunk, shortcut = item
        y = x
        for i, spec in enumerate(trunk):
            y = conv_bn(y, spec)
            if i + 1 < len(trunk):
                y = jax.nn.relu(y)
        if shortcut is not None:
            x = conv_bn(x, shortcut)
        x = jax.nn.relu(x + y)
    x = x.mean((2, 3))
    return x @ p["dense0_weight"].T + p["dense0_bias"]


def reference_loss(logits, labels):
    """Mean softmax cross-entropy over the batch (the job's loss)."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -picked.mean()


# block_f32: largest |logit error| over largest |reference logit|, the
#   Block's eager float32 forward in train mode against the reference, both
#   at full float32 matmul precision.  The two differ in the order of sums
#   only; measured on the chip 7e-6 to 1.0e-5 in 26 runs (my chip runs,
#   PR 22).  Zeroing one convolution of one residual block, one shortcut's
#   BatchNorm gain or the dense bias moves the logits by far more
#   (tests/chip_bench/test_chip_bench_reference.py).  At XLA's default
#   precision (bf16 passes) the same forward is off by 7e-2 to 8e-2 over its
#   53 convolutions (my chip run, PR 22): a lower precision fails too.
# step_loss: |first step's loss - reference loss| over the reference loss;
#   the step computes in bf16, and the Gluon loop's float32 convolutions run
#   at XLA's default precision (bf16 passes).  Measured, signed, over the
#   seeds: standard deviation 8e-4 and at most 1.8e-3 in 30 runs of the
#   fused step, 1.2e-3 and at most 3.4e-3 in 25 runs of the Gluon loop (my
#   chip runs, PR 22).  6e-3 is five of the wider deviation, 0.04 nats at a
#   loss of 7.5.
# step_loss_rehearsal: the CPU rehearsal's mean is over 8 thumbnails of a
#   ResNet-18; it checks the control flow.
TOLERANCE = {"block_f32": 1e-3, "step_loss": 6e-3,
             "step_loss_rehearsal": 1e-2}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def forward_macs_per_sample(cfg):
    """Multiply-accumulates of one image's forward pass in the
    convolutions and the dense layer (BatchNorm, ReLU, pooling and the
    residual adds are not matmul work and count nothing)."""
    size = cfg["image_size"]
    macs = 0
    for kind, item in _walk(cfg):
        if kind == "stem":
            specs, size_in = [item], size
        else:
            trunk, shortcut = item
            specs, size_in = list(trunk), size
        out = size_in
        for _, c_in, c_out, k, stride, pad, _, _ in specs:
            out = (out + 2 * pad - k) // stride + 1
            macs += out * out * c_in * c_out * k * k
        if kind == "block" and shortcut is not None:
            _, c_in, c_out, k, stride, pad, _, _ = shortcut
            o = (size_in + 2 * pad - k) // stride + 1
            macs += o * o * c_in * c_out * k * k
        size = out
        if kind == "stem" and not cfg["thumbnail"]:
            size = (size + 2 - 3) // 2 + 1           # the 3x3/2 max pool
    return macs + cfg["stage_channels"][-1] * cfg["classes"]


def train_flops_per_sample(cfg, traffic):
    """Forward and backward: 2 FLOPs a MAC, and the backward pass is twice
    the forward (gradients with respect to inputs and to weights)."""
    return 3 * 2.0 * forward_macs_per_sample(cfg)
