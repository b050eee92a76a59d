"""Latent attention (``gluon.nn.LatentAttention`` over ``ops/attention.py``
at two head widths) and the routed layer's shared expert
(``parallel.ExpertParallelMoE(shared_hidden_size=...)``), from the scopes
the program stages them under.

``latent_flash_fwd_ms_per_step``  device time of the forward flash kernels
                                  whose ``op_name`` path lies under the
                                  scope ``attn_latent``, a step, all layers;
``latent_flash_bwd_ms_per_step``  the same of the two backward kernels
                                  (dK/dV and dQ; not the ``jnp`` around
                                  them);
``latent_flash_fwd_roofline``, ``latent_flash_bwd_roofline``
                                  the least time the chip could take for
                                  what the layers need, over those kernels'
                                  time, in %;
``latent_proj_ms_per_step``       self time of every op under the scope
                                  ``attn_latent_proj``: the down- and
                                  up-projections, the latent's RMSNorm, the
                                  rotary part, the rotary key's repeat to
                                  all heads and the concatenations
                                  (``proj_out`` is the Block's own);
``moe_shared_ms_per_step``        self time of every op under the scope
                                  ``moe_shared``: the shared expert's three
                                  products and its activation.

A scope counts wherever it lies on an op's path, not as the innermost one:
the Dense layers and ``rope`` put their own names below it
(``scope_times.py`` takes the innermost, which suits scopes with no Block
inside).  Forward and backward together.

What one causal call needs (``needs``) on B sequences of S, H heads with
keys ``Dqk`` wide and values ``Dv`` wide: query t sees t + 1 keys, K =
S (S + 1) / 2 a sequence.  Forward: Q K^T at ``Dqk`` and P V at ``Dv``,
2 * B*H*K * (Dqk + Dv) FLOPs; q, k read at ``Dqk``, v read and o written at
``Dv``.  Backward: seven products a pair (the scores in each of the two
kernels, dK, dQ at ``Dqk``; dP in each, dV at ``Dv``), 2 * B*H*K *
(4 Dqk + 3 Dv); q, k read and dq, dk written at ``Dqk``, v, o, dO read and dv
written at ``Dv``.  The widths are the configuration's (``qk_nope_head_dim +
qk_rope_head_dim`` and ``v_head_dim``: the model's work), not the lanes the
kernel pads a key of 192 to, and the rotary key counts for every head, as
the layer hands it over; calls a step: ``num_hidden_layers``.  Against the
bf16 peak and the HBM peak; ``roof`` in the run's facts says which is the
larger.

Left out where the configuration has no ``kv_lora_rank``, where the program
has no registry of its ops (an older commit), and each metric where nothing
ran under its scope.  On the CPU rehearsal a metric is named where the
registry has an op under its scope.
"""
import re

from chipbench import peaks, program

LATENT, PROJ, SHARED = "attn_latent", "attn_latent_proj", "moe_shared"
FWD = re.compile(r"flash_attention_pallas")
BWD = re.compile(r"flash_attention_bwd_(dkv|dq)\b")
# "transpose(jvp(attn_latent))": what a derived op's scopes are wrapped in
_WRAPPED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+|\)+$")
_BY_SCOPE = {"latent_proj_ms_per_step": PROJ,
             "moe_shared_ms_per_step": SHARED}
_KERNELS = {"latent_flash_fwd": FWD, "latent_flash_bwd": BWD}


def needs(batch, heads, seq, qk_dim, v_dim, dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    pairs = batch * heads * (seq * (seq + 1) // 2)
    rows = batch * heads * seq * dtype_bytes
    return ((2 * pairs * (qk_dim + v_dim), rows * (2 * qk_dim + 2 * v_dim)),
            (2 * pairs * (4 * qk_dim + 3 * v_dim),
             rows * (4 * qk_dim + 4 * v_dim)))


def under(path, scope):
    """Whether ``scope`` is a component of the ``op_name`` path."""
    return any(_WRAPPED.sub("", part) == scope
               for part in (path or "").split("/"))


def op_paths(run):
    """{short HLO name: op_name path} over the registry's programs; None
    where the program has no registry."""
    t = program.telemetry(run)
    if t is None:
        return None
    paths = {}
    for p in t.programs().values():
        paths.update(p.ops)
    return paths


def widths(sizes):
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
            sizes["v_head_dim"])


def read(run):
    sizes = run.sizes
    if "kv_lora_rank" not in sizes:
        return {}
    paths = op_paths(run)
    if paths is None:
        return {}
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if not (run.rehearse and not r):
            return {}
        # the rehearsal's names: what the registry has an op under
        out = {m: 0.0 for m, scope in _BY_SCOPE.items()
               if any(under(p, scope) for p in paths.values())}
        if any(under(p, LATENT) for p in paths.values()):
            out.update({stem + end: 0.0 for stem in _KERNELS
                        for end in ("_ms_per_step", "_roofline")})
        return out
    steps = r["steps"]
    seconds = dict.fromkeys((*_BY_SCOPE, *_KERNELS), 0.0)
    for op, s in r["op_s"].items():
        path = paths.get(op.lstrip("%").partition("~")[0])
        for metric, scope in _BY_SCOPE.items():
            if under(path, scope):
                seconds[metric] += s
        if under(path, LATENT):
            for stem, kernel in _KERNELS.items():
                if kernel.search(op):
                    seconds[stem] += s
    out = {m: 1e3 * seconds[m] / steps for m in _BY_SCOPE if seconds[m]}
    traffic, facts = run.traffic, {}
    qk_dim, v_dim = widths(sizes)
    needed = needs(traffic["batch_per_chip"], sizes["num_attention_heads"],
                   traffic["seq_len"], qk_dim, v_dim,
                   peaks.DTYPE_BYTES[traffic["dtype"]])
    calls = sizes["num_hidden_layers"]
    for stem, (flops, nbytes) in zip(_KERNELS, needed):
        kernel_s = seconds[stem] / steps
        if not kernel_s:
            continue
        least, roof = peaks.least_time(flops, nbytes, run.peaks)
        out[stem + "_ms_per_step"] = 1e3 * kernel_s
        out[stem + "_roofline"] = 100.0 * least * calls / kernel_s
        facts[stem] = {"roof": roof, "least_ms_per_call": 1e3 * least,
                       "calls_per_step": calls, "qk_dim": qk_dim,
                       "v_dim": v_dim}
    if facts:
        run.facts["latent_flash"] = facts
    return out
