"""Gated attention (``gluon.nn.MultiHeadAttention(gate=True)``) from the
scope the program stages the gate under, and the routed layer's shared
expert for a cell without latent attention.

``attn_gate_ms_per_step``
    self time of every op under the scope ``attn_gate``: the gate's
    projection of the layer's input, its sigmoid and the product onto the
    heads' outputs, forward and backward (the projection's two gradients,
    the sigmoid's, the two of the product), a step, all layers.  What the
    compiler fuses across the scope's edge goes where the fusion's own path
    says (on the v5e the sigmoid and the product ride in neighbours' fusions
    and the scope reads the projections: PERF.md section 5).
``moe_shared_ms_per_step.<cell>``
    self time of every op under ``moe_shared``
    (``ExpertParallelMoE(shared_hidden_size=...)``).  The accepted reader of
    that scope, ``latent_attention.py``, reads it only where the
    configuration has a ``kv_lora_rank``; for any other configuration it is
    read here, under the cell's own entry.

A scope counts wherever it lies on an op's path (``chipbench/scopes.py``):
the Dense layer puts its own name below ``attn_gate``.  Left out where the
program has no registry of its ops (an older commit) and each metric where
nothing ran under its scope (a commit before the gate, a model without one).
On the CPU rehearsal a metric is named where the registry has an op under its
scope.  ``facts["attn_gate"]`` lists the scope's largest ops.
"""
from chipbench import scopes

GATE, SHARED = "attn_gate", "moe_shared"


def _metrics(run):
    """{metric: scope} for this run's cell."""
    wanted = {"attn_gate_ms_per_step": GATE}
    cell = getattr(run, "cell", None)
    if cell and "kv_lora_rank" not in run.sizes:
        wanted["moe_shared_ms_per_step." + cell["name"]] = SHARED
    return wanted


def read(run):
    paths = scopes.op_paths(run)
    if paths is None:
        return {}
    wanted = _metrics(run)
    r = run.reduced
    if not r or not r["steps"]:
        if not (run.rehearse and not r):
            return {}
        # the rehearsal's names: what the registry has an op under
        return {m: 0.0 for m, scope in wanted.items()
                if any(scopes.under(p, scope) for p in paths.values())}
    seconds, gate_ops = dict.fromkeys(wanted, 0.0), []
    for op, path, s in scopes.traced_ops(run, paths):
        for metric, scope in wanted.items():
            if scopes.under(path, scope):
                seconds[metric] += s
                if scope == GATE:
                    gate_ops.append([op, 1e3 * s / r["steps"]])
    if gate_ops:
        run.facts["attn_gate"] = {
            "ops": len(gate_ops),
            "largest_ops_ms_per_step": sorted(gate_ops,
                                              key=lambda o: -o[1])[:8]}
    return {m: 1e3 * s / r["steps"] for m, s in seconds.items() if s}
