"""The routed expert layer (``parallel/moe.py``, ``dispatch="grouped"``):
the experts' share of their roofline, and what the program counted of its
own routing (``scope_times.py`` has the layer's device time by scope).

``moe_experts_roofline``      the least time the chip could take for what
                              the experts need, over the self time of the
                              ops under the scope ``moe_experts``, in %;
``moe_held_assignment_pct``   of the (token, expert) assignments the router
                              made on the program's eager calls, the share
                              that fell on a held expert (its counter
                              ``graft_moe_assignments_total{held}``);
``moe_load_max_over_mean``    the largest held expert's load over the mean
                              held expert's, last eager call (its gauge).

What the experts need a step: each of the ``A`` assignments a held expert
gets goes through three products d x h (up, gate, down), forward once and
backward twice (for the rows and for the weights): 3 * 3 * 2 * A * d * h
FLOPs; each pass reads the held experts' weights and the gathered rows and
writes as many rows, in the compute dtype.  ``A`` is what a balanced router
sends to the share: tokens * top_k * held / experts, a routed layer: a
count from shapes.  What a run's router really sent is not in a trace; the
program's counter has it for the eager forward of set-up
(``facts["moe"]``), and a router that sends the share more than that makes
the kernel's share read lower, not higher.  Against the bf16 peak and the
HBM peak; ``roof`` in the run's facts says which is the larger.  Left out
where the configuration routes nothing or the program stages no such scope.
"""
from chipbench import peaks, program

EXPERTS = "moe_experts"             # parallel/moe.py's scope
_COUNTER = "graft_moe_assignments_total"
_GAUGE = "graft_moe_expert_load_max_over_mean"


def needs(assignments, d_model, d_expert, held, dtype_bytes):
    """(FLOPs, bytes) of one routed layer's experts, forward and backward,
    over ``assignments`` rows."""
    flops = 3 * 3 * 2 * assignments * d_model * d_expert
    nbytes = 3 * (3 * held * d_model * d_expert
                  + 2 * assignments * d_model) * dtype_bytes
    return flops, nbytes


def routed_layers(sizes):
    return sizes["num_hidden_layers"] - min(sizes["num_dense_layers"],
                                            sizes["num_hidden_layers"])


def balanced_assignments(sizes, traffic):
    """Assignments a held expert of one routed layer gets a step, all held
    experts together, from a balanced router."""
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    return (tokens * sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def counters(run):
    """The program's own counts of its eager grouped calls."""
    registry = getattr(getattr(run.mx, "telemetry", None), "registry", None)
    if registry is None:
        return {}
    snap = registry().snapshot()
    by_held = {s["labels"]["held"]: s["value"]
               for s in snap.get(_COUNTER, {"samples": []})["samples"]}
    total = sum(by_held.values())
    if not total:
        return {}
    out = {"moe_held_assignment_pct": 100.0 * by_held.get("yes", 0) / total}
    gauge = snap.get(_GAUGE, {"samples": []})["samples"]
    if gauge:
        out["moe_load_max_over_mean"] = gauge[0]["value"]
    run.facts["moe"] = dict(out, assignments_counted=total)
    return out


def read(run):
    sizes = run.sizes
    if "num_experts_published" not in sizes or not routed_layers(sizes):
        return {}
    out = counters(run)
    split = program.device_split(run)
    if split is None:
        if run.rehearse and not run.reduced and out:
            # the rehearsal's name: the layer counted grouped calls
            out["moe_experts_roofline"] = 0.0
        return out
    experts_s = split["scope_s"].get(EXPERTS, 0.0) / split["steps"]
    if not experts_s:
        return out
    layers = routed_layers(sizes)
    assignments = balanced_assignments(sizes, run.traffic)
    flops, nbytes = needs(assignments, sizes["hidden_size"],
                          sizes["moe_intermediate_size"],
                          sizes["num_experts"],
                          peaks.DTYPE_BYTES[run.traffic["dtype"]])
    least, roof = peaks.least_time(flops, nbytes, run.peaks)
    run.facts["moe_experts"] = {
        "roof": roof, "least_ms_per_layer": 1e3 * least, "layers": layers,
        "balanced_assignments_per_layer": assignments}
    out["moe_experts_roofline"] = 100.0 * least * layers / experts_s
    return out
