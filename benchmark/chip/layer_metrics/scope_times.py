"""Device time under the scopes a layer of the program stages itself under,
below the train step's phases and the Blocks' names.

``moe_router_ms_per_step``    ``moe_router`` (``parallel/moe.py``): gate
                              product, scores, top-k, the weights;
``moe_dispatch_ms_per_step``  ``moe_dispatch`` (sort by expert, the gather
                              into the buffer) and ``moe_combine`` (weights,
                              the gather back, the sum over a token's
                              experts);
``moe_experts_ms_per_step``   ``moe_experts``: the grouped matrix products
                              and the activation between them;
``short_conv_ms_per_step``    ``short_conv`` (``gluon.nn.ShortConv``): the
                              two element-wise gates and the depthwise
                              causal taps between them; the projections on
                              either side are Dense layers under their own
                              names (``facts["device_split"]``).

Each is the self time, a step, of every op whose innermost scope in
``telemetry.programs()`` is one of the metric's, forward and backward, all
layers together (``chipbench/program.py::device_split``).  A metric whose
scopes took no time is left out, so a cell whose program stages none of
them reports none; on the CPU rehearsal a metric is named where the
registry has an op under its scope.  ``facts["scope_times"]`` lists, for
each scope that took time, its largest ops (name, category and result type
as the trace has them, ms a step): what a reader of a slow scope asks next.
"""
from chipbench import program

SCOPES = {"moe_router_ms_per_step": ("moe_router",),
          "moe_dispatch_ms_per_step": ("moe_dispatch", "moe_combine"),
          "moe_experts_ms_per_step": ("moe_experts",),
          "short_conv_ms_per_step": ("short_conv",)}


def staged(run, scopes):
    """Whether some program of the registry has an op under one of the
    scopes."""
    t = program.telemetry(run)
    return t is not None and any(
        scope in path for p in t.programs().values()
        for path in p.ops.values() for scope in scopes)


def read(run):
    split = program.device_split(run)
    if split is None:
        if run.rehearse and not run.reduced:
            return {m: 0.0 for m, scopes in SCOPES.items()
                    if staged(run, scopes)}
        return {}
    found = {m: sum(split["scope_s"].get(s, 0.0) for s in scopes)
             for m, scopes in SCOPES.items()}
    if any(found.values()):
        run.facts["scope_times"] = largest_ops(run, split["steps"])
    return {m: 1e3 * s / split["steps"] for m, s in found.items() if s}


def largest_ops(run, steps, most=6):
    """{scope: [[op, category and result type, ms a step], ...]} over the
    scopes of ``SCOPES``: every op of the trace put to the innermost scope
    of its ``op_name`` path in whichever program of the registry names it
    (an op's short name may recur across programs; a fact, not a sum that
    anything is held to)."""
    paths = {}
    for p in program.telemetry(run).programs().values():
        paths.update(p.ops)
    wanted = {s for scopes in SCOPES.values() for s in scopes}
    by_scope = {}
    for op, seconds in run.reduced["op_s"].items():
        scope = program.scope_of(paths.get(op.lstrip("%").partition("~")[0]))
        if scope in wanted:
            by_scope.setdefault(scope, []).append(
                [op, " ".join(run.recorded["ops"].get(op, ())),
                 1e3 * seconds / steps])
    return {scope: sorted(ops, key=lambda o: -o[2])[:most]
            for scope, ops in by_scope.items()}
