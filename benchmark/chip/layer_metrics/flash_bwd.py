"""The flash-attention backward (``ops/attention.py::_flash_bwd``).

``flash_bwd_ms_per_step``  self time, a step, of every op the program
                           stages under its scope ``flash_attention_bwd``:
                           the two Mosaic kernels of a layer (dK/dV and dQ)
                           and the ``jnp`` around them (``delta``, 1/l),
                           found through ``telemetry.programs()``.
``flash_bwd_roofline``     the least time the chip could take for what the
                           algorithm needs, over the two kernels' own time
                           (their Mosaic calls by name, as the forward's
                           are read; not the ``jnp`` around them), in %.

What the causal backward needs for one layer on (B, H, S, D): seven matmuls
over the lower triangle (the scores and dP in each of the two kernels, dV,
dK, dQ), 7 * 2 * B*H*S*S*D / 2 FLOPs; q, k, v, o and dO read and dq, dk, dv
written once in the compute dtype, 8 * B*H*S*D * bytes.  Against the bf16
peak and the HBM peak; ``roof`` in the run's facts says which is the
larger.  The kernels visit whole blocks, ten of sixteen 512-blocks a head
at S 2048 where the triangle is eight, so a share of 50 % is 62 % of the
MXU peak for the blocks visited (PERF.md, PR 24).  Left out where the
configuration has no attention, the program no such scope or the trace no
such kernel.
"""
import re

from chipbench import peaks, program

# ``%flash_attention_bwd_dkv.N`` and ``%flash_attention_bwd_dq.N``: the
# HLO instruction of a ``pallas_call`` takes the kernel's name
KERNELS = re.compile(r"flash_attention_bwd_(dkv|dq)\b")


def needs(batch, heads, seq, head_dim, dtype_bytes):
    """(FLOPs, bytes) of one layer's causal backward."""
    flops = 7 * 2 * batch * heads * seq * seq * head_dim / 2
    return flops, 8 * batch * heads * seq * head_dim * dtype_bytes


def read(run):
    sizes = run.sizes
    if "num_attention_heads" not in sizes:
        return {}
    split = program.device_split(run)
    if split is None:
        if run.rehearse and not run.reduced and \
                program.FLASH_BWD in program.offers(run):
            return {"flash_bwd_ms_per_step": 0.0}
        return {}
    if not split["flash_bwd_s"]:
        return {}
    out = {"flash_bwd_ms_per_step":
           1e3 * split["flash_bwd_s"] / split["steps"]}
    kernel_s = sum(s for name, s in run.reduced["op_s"].items()
                   if KERNELS.search(name)) / split["steps"]
    if kernel_s:
        traffic, heads = run.traffic, sizes["num_attention_heads"]
        flops, nbytes = needs(
            traffic["batch_per_chip"], heads, traffic["seq_len"],
            sizes["hidden_size"] // heads,
            peaks.DTYPE_BYTES[traffic["dtype"]])
        least, roof = peaks.least_time(flops, nbytes, run.peaks)
        layers = sizes["num_hidden_layers"]
        run.facts["flash_bwd"] = {
            "roof": roof, "least_ms_per_layer": 1e3 * least,
            "layers": layers, "kernels_ms_per_step": 1e3 * kernel_s}
        out["flash_bwd_roofline"] = 100.0 * least * layers / kernel_s
    return out
