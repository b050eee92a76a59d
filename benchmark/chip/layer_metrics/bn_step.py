"""BatchNorm's guard out of the compiled step: how often a layer's batch
statistics went through the exact second pass.

``ops/nn.py`` takes a training BatchNorm's mean and variance as two sums in
one pass (the sum and the sum of squares), and falls back to the two-pass
form where a channel's mean lies too far from zero, in its own standard
deviations, for the difference to keep float32's digits.
Each layer emits whether it did with ``telemetry.step_counter``
(``bn_second_pass``, an int32 0 or 1 a BatchNorm layer, in call order); the
counts leave the fused step beside the loss and ``telemetry.step_counters()``
hands them out by the id the ``step`` span carries.  Under a mesh the counts
are replicated, and one copy is read.

``bn_second_pass_per_step``  the second passes taken in the window's steps,
                             all layers together, over those steps: 0 says
                             every statistic of the window was the one-pass
                             form's, so a step's time is that form's.

``facts["bn_step"]``: the layers that emit, the window's steps read, the
passes taken in them and in the steps before the window (set-up's
warm-up), and what reading the records cost.  A
program without the accessor, or whose step emits nothing (the parent of the
PR that brought the guard), reports none of this.
"""
import time

from chipbench import program

TAKEN = "bn_second_pass"


def read(run):
    began = time.perf_counter()
    t = program.telemetry(run)
    counters = getattr(t, "step_counters", None)
    if counters is None:
        return {}
    ids = [step for name, _s, _e, _parent, step
           in t.spans(since=run.window_span[0]) if name == "step"]
    if not ids:
        return {}
    first = min(ids)
    records = [(step, counts[TAKEN]) for step, counts in counters()
               if TAKEN in counts]
    # a later record under an id is the window's: an earlier trainer of the
    # process (set-up's) numbers its own steps from 1 too
    by_id = {step: taken for step, taken in records if step >= first}
    window = [int(by_id[i].sum()) for i in ids if i in by_id]
    if not window:
        return {}
    run.facts["bn_step"] = {
        "layers": int(records[-1][1].size),
        "steps_read": len(window), "second_passes": sum(window),
        "second_passes_before_the_window": [
            int(taken.sum()) for step, taken in records if step < first],
        "read_s": time.perf_counter() - began}
    return {"bn_second_pass_per_step": sum(window) / len(window)}
