"""The gradient all-reduce GSPMD puts into the fused step, on the first
device of the trace.

``allreduce_ms_per_step``          union of the collective operations'
                                   intervals, a step.
``allreduce_exposed_ms_per_step``  the part of it during which no other
                                   operation ran on that device.
Nothing on one chip: a program there has no collective.
"""


def read(run):
    r = run.reduced
    if not r or not r["steps"] or r["devices"] < 2:
        return {}
    return {
        "allreduce_ms_per_step": 1e3 * r["collective_s"] / r["steps"],
        "allreduce_exposed_ms_per_step":
            1e3 * r["collective_exposed_s"] / r["steps"],
    }
