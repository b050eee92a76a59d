"""The Pallas flash-attention forward kernel (``ops/attention.py``).

``flash_fwd_ms_per_step``  device time of the kernel's Mosaic custom calls,
                           a step (one call a layer; the backward's two
                           kernels are ``flash_bwd.py``'s).
``flash_fwd_roofline``     the least time the chip could take for what the
                           algorithm needs, over the kernel's time, in %.

What causal attention needs for one call on (B, H, S, D): two matmuls over
the lower triangle, 2 * 2 * B*H*S*S*D / 2 FLOPs, and q, k, v read and the
output written once in the compute dtype, 4 * B*H*S*D * bytes.  Against the
bf16 peak and the HBM peak; ``roof`` in the run's facts says which is the
larger.  Since PR 24 the kernel multiplies in the operands' dtype and skips
the blocks above the diagonal; it still visits whole blocks (ten of sixteen
512-blocks a head at S 2048 where the triangle is eight) and the softmax
runs on the VPU beside the MXU, so 40 % here is about 47 % of the MXU peak
for the blocks visited (PERF.md, PR 24).  Left out where the configuration
has no attention or the trace no such kernel.
"""
import re

from chipbench import peaks

# the kernel's call is named after the ``named_scope`` it is traced under
KERNEL = re.compile(r"flash_attention_pallas")


def needs(batch, heads, seq, head_dim, dtype_bytes):
    """(FLOPs, bytes) of one causal forward call."""
    flops = 2 * 2 * batch * heads * seq * seq * head_dim / 2
    return flops, 4 * batch * heads * seq * head_dim * dtype_bytes


def kernel_seconds(run):
    return sum(s for name, s in run.reduced["op_s"].items()
               if KERNEL.search(name))


def read(run):
    r, sizes, traffic = run.reduced, run.sizes, run.traffic
    if not r or not r["steps"] or "num_attention_heads" not in sizes:
        return {}
    kernel_s = kernel_seconds(run) / r["steps"]
    if not kernel_s:
        return {}
    heads = sizes["num_attention_heads"]
    flops, nbytes = needs(traffic["batch_per_chip"], heads, traffic["seq_len"],
                          sizes["hidden_size"] // heads,
                          peaks.DTYPE_BYTES[traffic["dtype"]])
    least, roof = peaks.least_time(flops, nbytes, run.peaks)
    calls = sizes["num_hidden_layers"]
    run.facts["flash_fwd"] = {
        "roof": roof, "least_ms_per_call": 1e3 * least,
        "calls_per_step": calls}
    return {"flash_fwd_ms_per_step": 1e3 * kernel_s,
            "flash_fwd_roofline": 100.0 * least * calls / kernel_s}
