"""The Gated DeltaNet layer (``gluon.nn.GatedDeltaNet`` over
``ops/delta_rule.py``), from the scopes the program stages it under.

``gdn_scan_fwd_ms_per_step``, ``gdn_scan_bwd_ms_per_step``
    self time of every op under the scope ``gdn_scan`` (the heads' L2 norms,
    the gates and decays, and the delta rule itself: the chunks' batched
    products and the scan over their states, or a kernel's calls: whatever
    implements it), a step, all layers; forward and backward told apart by
    the ``xray:`` phase of the op's path;
``gdn_scan_fwd_roofline``, ``gdn_scan_bwd_roofline``
    what the recurrence needs at the chip's published peaks over that time,
    in %;
``gdn_proj_ms_per_step``
    self time of every op under ``gdn_proj``: ``W_qkvz``, ``W_ba`` and
    ``W_out``, forward and backward.

``BENCHMARK.json`` had room for five entries when the layer came (123 of
its 128 per-layer metrics were taken), so the rest of the layer's split is
a fact and no metric: ``facts["gdn_scan"]["ms_per_step_by_scope"]`` has the
same sum under ``gdn_norm`` (the head's RMSNorm and its ``silu(z)`` gate),
under ``short_conv`` (the convolution and its SiLU) and under the scopes of
the other layers of a cell whose readers' metrics it could not list
(``OTHER_SCOPES``), each where it took time.

What one call needs (``needs``) on B rows of L tokens, H value heads of
key_dim x value_dim: **the recurrence's own work, whatever implements it**:
forward three products of key_dim x value_dim a token and value head
(``S'^T k``, ``k u^T``, ``S^T q``), 2 FLOPs a multiply-add, backward twice
that; q, k (for the key heads), v, g, beta read and o written forward, the
same read again with o's cotangent and the five gradients written backward,
in the operands' dtype.  A chunked form does more arithmetic than that (the
chunk's own products and its triangular solve), so the share reads what the
implementation leaves of the MXU's peak for the model's work, and a share
over 100 % is a wrong count.  Calls a step: the ``linear_attention`` entries
of ``layer_types``.

Left out where the configuration has no ``linear_num_value_heads`` (no
other cell's line gains a name), where the program has no registry of its
ops (an older commit), and each metric where nothing ran under its scope.
On the CPU rehearsal a metric is named where the registry has an op under
its scope.
"""
from chipbench import peaks, program, scopes

SCAN, PROJ = "gdn_scan", "gdn_proj"
_BY_SCOPE = {"gdn_proj_ms_per_step": PROJ}
OTHER_SCOPES = ("gdn_norm", "short_conv", "attn_gate", "moe_router",
                "moe_dispatch", "moe_combine", "moe_experts", "moe_shared")
_PHASES = {"gdn_scan_fwd": "forward", "gdn_scan_bwd": "backward"}
PRODUCTS = 3                    # S'^T k, k u^T, S^T q


def needs(batch, length, key_heads, value_heads, key_dim, value_dim,
          dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    tokens = batch * length
    once = PRODUCTS * 2 * key_dim * value_dim * value_heads * tokens
    qk = 2 * tokens * key_heads * key_dim * dtype_bytes
    vo = tokens * value_heads * value_dim * dtype_bytes
    gates = 2 * tokens * value_heads * 4
    return ((once, qk + 2 * vo + gates),
            (2 * once, 2 * (qk + vo + gates) + vo))


def read(run):
    sizes = run.sizes
    if "linear_num_value_heads" not in sizes:
        return {}
    paths = scopes.op_paths(run)
    if paths is None:
        return {}
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if not (run.rehearse and not r):
            return {}
        # the rehearsal's names: what the registry has an op under
        out = {m: 0.0 for m, scope in _BY_SCOPE.items()
               if any(scopes.under(p, scope) for p in paths.values())}
        for stem, phase in _PHASES.items():
            if any(scopes.under(p, SCAN) and program.phase_of(p) == phase
                   for p in paths.values()):
                out.update({stem + "_ms_per_step": 0.0,
                            stem + "_roofline": 0.0})
        return out
    steps = r["steps"]
    seconds = dict.fromkeys((*_BY_SCOPE, *_PHASES), 0.0)
    other = dict.fromkeys(OTHER_SCOPES, 0.0)
    for _, path, s in scopes.traced_ops(run, paths):
        for scope in OTHER_SCOPES:
            if scopes.under(path, scope):
                other[scope] += s
        for metric, scope in _BY_SCOPE.items():
            if scopes.under(path, scope):
                seconds[metric] += s
        if scopes.under(path, SCAN):
            for stem, phase in _PHASES.items():
                if program.phase_of(path) == phase:
                    seconds[stem] += s
    out = {m: 1e3 * seconds[m] / steps for m in _BY_SCOPE if seconds[m]}
    traffic = run.traffic
    needed = needs(traffic["batch_per_chip"], traffic["seq_len"],
                   sizes["linear_num_key_heads"],
                   sizes["linear_num_value_heads"],
                   sizes["linear_key_head_dim"],
                   sizes["linear_value_head_dim"],
                   peaks.DTYPE_BYTES[traffic["dtype"]])
    calls = list(sizes["layer_types"]).count("linear_attention")
    facts = {"chunk": scopes.gauge(run, "graft_delta_rule_chunk"),
             "state_bytes": scopes.gauge(run, "graft_delta_rule_state_bytes"),
             "ms_per_step_by_scope": {scope: 1e3 * s / steps
                                      for scope, s in other.items() if s}}
    for stem, (flops, nbytes) in zip(_PHASES, needed):
        scan_s = seconds[stem] / steps
        if not scan_s or not calls:
            continue
        least, roof = peaks.least_time(flops, nbytes, run.peaks)
        out[stem + "_ms_per_step"] = 1e3 * scan_s
        out[stem + "_roofline"] = 100.0 * least * calls / scan_s
        facts[stem] = {"roof": roof, "least_ms_per_call": 1e3 * least,
                       "calls_per_step": calls}
    run.facts["gdn_scan"] = facts
    return out
