"""Differential attention (``gluon.nn.DifferentialAttention`` over
``ops/attention.py`` at keys of D over values of 2 D), from the scopes the
program stages it under.

``diff_flash_fwd_ms_per_step``  device time of the forward flash kernels
                                whose ``op_name`` path lies under the scope
                                ``attn_diff``, a step, all layers (a window
                                layer's are ``flash_window_*``, a full or
                                cross layer's ``flash_attention_*``);
``diff_flash_bwd_ms_per_step``  the same of the backward kernels (dK/dV and
                                dQ; not the ``jnp`` around them);
``diff_flash_fwd_roofline``, ``diff_flash_bwd_roofline``
                                the least time the chip could take for what
                                the layers need, over those kernels' time,
                                in %;
``diff_combine_ms_per_step``    self time of every op under the scope
                                ``attn_diff_combine``: lambda, the
                                subtraction of the two softmaxes' results,
                                the RMSNorm over a head's 2 D channels and
                                the factor after it, forward and backward.

What one call needs (``needs``) on B rows of S, H query heads of D over G
K/V heads (H / 2 differential heads over G / 2 value heads 2 D wide), a
query t seeing min(t + 1, W) keys (W = S without a window), K their sum over
a row.  Forward: Q K^T at D and P V at 2 D for each of the H query heads,
2 * B*H*K * (D + 2 D) FLOPs; q read at H x D, k at G x D, v at G/2 x 2 D, o
written at H x 2 D.  Backward: seven products a pair (the scores in each of
the two kernels, dK, dQ at D; dP in each, dV at 2 D), 2 * B*H*K * (4 D +
3 * 2 D); q, k, v, o, dO read and dq, dk, dv written.  The widths are the
model's: the layer repeats k and v to H heads before the kernel and the
kernels pad a key of 64 to 128 lanes and visit whole blocks, so they move
and multiply more than is counted, which shows as a lower share.  The least
times of the attention layers of ``layer_types`` (``sliding_attention``,
``full_attention``, ``cross_attention``) are added up; ``roof`` in the run's
facts names each one's bound.

Left out where the configuration has no ``cross_attention`` layer (the
differential layers' decoder-hybrid-decoder has one), where the program
has no registry of its ops (an older commit), and each metric where nothing
ran under its scope.  On the CPU rehearsal a metric is named where the
registry has an op under its scope.
"""
import re

from chipbench import peaks, scopes

DIFF, COMBINE = "attn_diff", "attn_diff_combine"
_KERNELS = {
    "diff_flash_fwd": re.compile(r"flash_(attention|window)_pallas"),
    "diff_flash_bwd": re.compile(r"flash_(attention|window)_bwd_(dkv|dq)\b")}
WINDOW, CROSS = "sliding_attention", "cross_attention"
ATTENTION = (WINDOW, "full_attention", CROSS)


def keys_seen(seq, window=None):
    """Sum over a row's queries of the keys each sees: min(t + 1, W)."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def needs(batch, heads, kv_heads, seq, dim, window, dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    pairs = batch * heads * keys_seen(seq, window)
    q, k = (batch * n * seq * dim * dtype_bytes for n in (heads, kv_heads))
    v, o = (batch * n * seq * 2 * dim * dtype_bytes
            for n in (kv_heads // 2, heads))
    return ((2 * pairs * (dim + 2 * dim), q + k + v + o),
            (2 * pairs * (4 * dim + 3 * 2 * dim),
             2 * (q + k + v) + 2 * o))


def read(run):
    sizes = run.sizes
    kinds = [k for k in sizes.get("layer_types", ()) if k in ATTENTION]
    if CROSS not in kinds:
        return {}
    paths = scopes.op_paths(run)
    if paths is None:
        return {}
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if not (run.rehearse and not r):
            return {}
        # the rehearsal's names: what the registry has an op under
        out = {}
        if any(scopes.under(p, COMBINE) for p in paths.values()):
            out["diff_combine_ms_per_step"] = 0.0
        if any(scopes.under(p, DIFF) for p in paths.values()):
            out.update({stem + end: 0.0 for stem in _KERNELS
                        for end in ("_ms_per_step", "_roofline")})
        return out
    steps = r["steps"]
    seconds = dict.fromkeys((*_KERNELS, COMBINE), 0.0)
    for op, path, s in scopes.traced_ops(run, paths):
        if scopes.under(path, COMBINE):
            seconds[COMBINE] += s
        if scopes.under(path, DIFF):
            for stem, kernel in _KERNELS.items():
                if kernel.search(op):
                    seconds[stem] += s
    out = {}
    if seconds[COMBINE]:
        out["diff_combine_ms_per_step"] = 1e3 * seconds[COMBINE] / steps
    traffic, heads = run.traffic, sizes["num_attention_heads"]
    least_s, roofs = [0.0, 0.0], {}
    for kind in kinds:
        needed = needs(traffic["batch_per_chip"], heads,
                       sizes["num_key_value_heads"], traffic["seq_len"],
                       sizes["hidden_size"] // heads,
                       sizes["sliding_window"] if kind == WINDOW else None,
                       peaks.DTYPE_BYTES[traffic["dtype"]])
        for way, (flops, nbytes) in enumerate(needed):
            least, roof = peaks.least_time(flops, nbytes, run.peaks)
            least_s[way] += least
            roofs.setdefault(kind, []).append([roof, 1e3 * least])
    facts = {}
    for stem, least in zip(_KERNELS, least_s):
        kernel_s = seconds[stem] / steps
        if not kernel_s:
            continue
        out[stem + "_ms_per_step"] = 1e3 * kernel_s
        out[stem + "_roofline"] = 100.0 * least / kernel_s
        facts[stem] = {"least_ms_per_step": 1e3 * least,
                       "calls_per_step": len(kinds)}
    if facts:
        run.facts["diff_flash"] = dict(
            facts, roof_and_least_ms_by_kind_fwd_bwd=roofs)
    return out
