"""The flash kernels under grouped-query attention at a head dimension below
the MXU's 128 lanes (``gluon.nn.MultiHeadAttention(num_kv_heads=...)`` over
``ops/attention.py``).

``gqa_flash_fwd_roofline``  the least time the chip could take for what the
                            algorithm needs, over the forward kernel's
                            time, in %;
``gqa_flash_bwd_roofline``  the same of the two backward kernels.

What grouped-query causal attention needs for one call on B sequences of S,
H query heads over G key/value heads of D: forward two matmuls over the
lower triangle for every query head, 2 * 2 * B*H*S*S*D / 2 FLOPs, q read and
o written for H heads, k and v read for G; backward seven matmuls,
7 * 2 * B*H*S*S*D / 2, q, o, dO read and dq written for H heads, k, v read
and dk, dv written for G.  D is the configuration's, not the kernel's:
``ops/attention.py::_fold`` pads 64 to 128 lanes, so the MXU multiplies
twice what is counted here, and the layer repeats K/V to H heads before the
kernel, so the kernel moves more bytes than are counted.  Both show as a
lower share: that is what these two metrics are for (``flash_fwd.py`` /
``flash_bwd.py`` count ``num_hidden_layers`` calls a step and full K/V
heads, so their shares are not declared for such a cell).  Calls a step:
the ``full_attention`` entries of ``layer_types``.  Left out where the
configuration has no grouped K/V heads or the trace no such kernel.
"""
import re

from chipbench import peaks, program

FWD = re.compile(r"flash_attention_pallas")
BWD = re.compile(r"flash_attention_bwd_(dkv|dq)\b")


def needs(batch, heads, kv_heads, seq, head_dim, dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    square = batch * heads * seq * seq * head_dim
    q_like = batch * heads * seq * head_dim * dtype_bytes
    kv_like = batch * kv_heads * seq * head_dim * dtype_bytes
    return ((2 * 2 * square / 2, 2 * q_like + 2 * kv_like),
            (7 * 2 * square / 2, 4 * q_like + 4 * kv_like))


def read(run):
    sizes = run.sizes
    if "num_key_value_heads" not in sizes:
        return {}
    names = ("gqa_flash_fwd_roofline", "gqa_flash_bwd_roofline")
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if run.rehearse and not r and \
                program.FLASH_BWD in program.offers(run):
            return dict.fromkeys(names, 0.0)
        return {}
    calls = list(sizes["layer_types"]).count("full_attention")
    heads, traffic = sizes["num_attention_heads"], run.traffic
    needed = needs(traffic["batch_per_chip"], heads,
                   sizes["num_key_value_heads"], traffic["seq_len"],
                   sizes["hidden_size"] // heads,
                   peaks.DTYPE_BYTES[traffic["dtype"]])
    out, facts = {}, {"calls_per_step": calls}
    for name, kernel, (flops, nbytes) in zip(names, (FWD, BWD), needed):
        kernel_s = sum(s for op, s in r["op_s"].items()
                       if kernel.search(op)) / r["steps"]
        if not kernel_s or not calls:
            continue
        least, roof = peaks.least_time(flops, nbytes, run.peaks)
        facts[name] = {"roof": roof, "least_ms_per_call": 1e3 * least,
                       "kernel_ms_per_step": 1e3 * kernel_s}
        out[name] = 100.0 * least * calls / kernel_s
    run.facts["gqa_flash"] = facts
    return out
