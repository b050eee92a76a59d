"""The step, from the host: completion stamps and enqueue cost.

``step_ms_p50`` / ``step_ms_p90``  differences of consecutive completion
    stamps over the whole window, the steps inside the traced sub-window
    and the two on each side of it left out (starting and stopping the
    profiler stalls the loop).
``step_host_ms_p50``  host clock around the call that enqueues a step: what
    the host pays a step.  It bounds throughput only where the device
    idles (``device_idle_pct``).
"""
from chipbench import timing


def read(run):
    steps = run.step_samples
    if len(steps) < 10:
        return {}
    return {"step_ms_p50": 1e3 * timing.percentile(steps, 50),
            "step_ms_p90": 1e3 * timing.percentile(steps, 90),
            "step_host_ms_p50": 1e3 * timing.percentile(
                run.spans["enqueue"], 50)}
