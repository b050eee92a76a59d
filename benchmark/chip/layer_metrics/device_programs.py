"""``device_programs_per_step``: XLA program executions on the first
device's plane of the trace, over the steps of the traced window.  A count:
1 for the fused step; for the Gluon loop it says how many programs the
CachedOp, autograd and the bucketed update launch a step."""


def read(run):
    r = run.reduced
    if not r or not r["steps"]:
        return {}
    return {"device_programs_per_step": r["programs"] / r["steps"]}
