"""The routed expert layer's counts out of the compiled step itself: the
rows its held experts really got, step by step (``moe.py`` reads the
program's eager calls of set-up and reckons the experts' roofline against
a balanced router's share; a trained router sends the held experts several
times that).

The layer emits them with ``telemetry.step_counter`` (``parallel/moe.py``:
``moe_held_rows``, an int a held expert a routed layer, and
``moe_assignments``, every (token, expert) pair a layer's router made); they
leave the fused step beside the loss and ``telemetry.step_counters()`` hands
them out by the id the ``step`` span carries.  The window's steps are the
``step`` spans that began in it, in order: the i-th is the step whose loss
stamp i closed.

``moe_step_held_assignment_pct``  median over the window's untraced steps of
                                  the held rows over the assignments made,
                                  all routed layers together, in %;
``moe_step_held_drift_pct``       the mean held rows of the window's last
                                  tenth of steps over its first tenth's,
                                  less one, in %: 0 is a router that stands
                                  still;
``moe_step_load_max_over_mean``   median over those steps of the largest
                                  held expert's rows over the mean held
                                  expert's, in the layer where that is
                                  worst;
``moe_experts_rows_roofline``     ``moe.py::needs`` for the rows that arrived
                                  in the steps the device ran inside the
                                  traced window (joined by step id), a layer
                                  at a time, over the self time of the ops
                                  under the scope ``moe_experts`` there, in %.
                                  It reads the same work whatever implements
                                  the layer; over 100 the count is wrong.

``facts["moe_step"]``: a layer's held rows at the first, the median and the
last step, the window's blocks of steps (``timing.block_seconds``) as [mean
held rows a step, seconds] so that a stalled block can be told from a routed
one, and what reading the records cost (it is what waits for the device,
after the window).  A program without the accessor, or whose step emits
nothing, reports none of this.
"""
import statistics
import time

from chipbench import peaks, program, timing, trace

HELD, MADE = "moe_held_rows", "moe_assignments"
EXPERTS = "moe_experts"             # parallel/moe.py's scope


def window_records(run):
    """``[(held, made)]`` of the window's steps in order, numpy arrays
    ``int[layers, experts held]`` and ``int[layers]``, None where a step's
    record is gone (the recorder is bounded); None without the accessor or
    where no step of the window emitted."""
    t = program.telemetry(run)
    read = getattr(t, "step_counters", None)
    if read is None:
        return None
    ids = [step for name, _s, _e, _parent, step
           in t.spans(since=run.window_span[0]) if name == "step"]
    if not ids:
        return None
    by_id = {step: counts for step, counts in read(since_step=min(ids))
             if HELD in counts and MADE in counts}
    if not by_id:
        return None
    return [(by_id[i][HELD], by_id[i][MADE]) if i in by_id else None
            for i in ids]


def untraced(run, n):
    """The window's steps, by place, that count as untraced: as
    ``timing.untraced_steps`` leaves out the traced stretch and its margins
    (sample k of the step times is step k + 1's), and the first, which fills
    the pipeline."""
    first, last = run.traced_stamps
    if first is None:
        return list(range(1, n))
    last = n if last is None else last
    return list(range(1, max(1, first - 2))) + list(range(last + 2, n))


def traced(run, n):
    """The steps, by place, that the device ran inside the traced window
    (``trace.step_window``: from the end of the third traced wait to the end
    of the last; traced wait j closed stamp ``first + j``)."""
    first = run.traced_stamps[0]
    if first is None or not run.recorded:
        return []
    waits = sum(1 for name, _, _ in trace.host_spans(run.recorded)
                if name == trace.SPAN_PREFIX + "wait")
    return [i for i in range(first + 3, first + waits) if i < n]


def counted(run, records):
    """The three counts' metrics and the facts, from the records alone."""
    have = [i for i in untraced(run, len(records)) if records[i] is not None]
    if not have:
        return {}
    share, worst = [], []
    for i in have:
        held, made = records[i]
        share.append(100.0 * float(held.sum()) / float(made.sum()))
        worst.append(max((float(h.max() / h.mean()) for h in held
                          if h.any()), default=0.0))
    held_rows = [float(r[0].sum()) for r in records if r is not None]
    tenth = max(1, len(held_rows) // 10)
    out = {"moe_step_held_assignment_pct": statistics.median(share),
           "moe_step_held_drift_pct": 100.0 * (
               statistics.fmean(held_rows[-tenth:])
               / statistics.fmean(held_rows[:tenth]) - 1.0),
           "moe_step_load_max_over_mean": statistics.median(worst)}
    by_layer = [[int(records[i][0][layer].sum()) for i in have]
                for layer in range(len(records[have[0]][0]))]
    size, seconds = timing.block_seconds(run.stamps)
    blocks = []
    for b, s in enumerate(seconds):
        inside = [float(r[0].sum()) for r in records[b * size + 1:
                                                     (b + 1) * size + 1]
                  if r is not None]
        blocks.append([statistics.fmean(inside) if inside else None, s])
    run.facts["moe_step"] = dict(
        out, steps_counted=len(have), assignments_per_step=int(
            records[have[0]][1].sum()),
        layer_rows_first_median_last=[
            [rows[0], int(statistics.median(rows)), rows[-1]]
            for rows in by_layer],
        block_steps=size, blocks_rows_and_seconds=blocks)
    return out


def roofline(run, records):
    """``moe_experts_rows_roofline``: None without a device trace, the
    scope's time or the traced steps' records."""
    split = program.device_split(run)
    if split is None:
        return None
    experts_s = split["scope_s"].get(EXPERTS, 0.0)
    steps = [records[i] for i in traced(run, len(records))]
    if not experts_s or len(steps) != split["steps"] or None in steps:
        return None
    moe = run.catalog._module(run.catalog.dir / "layer_metrics" / "moe.py")
    sizes = run.sizes
    least = 0.0
    for held, _made in steps:
        for rows in held:
            flops, nbytes = moe.needs(
                int(rows.sum()), sizes["hidden_size"],
                sizes["moe_intermediate_size"], len(rows),
                peaks.DTYPE_BYTES[run.traffic["dtype"]])
            least += peaks.least_time(flops, nbytes, run.peaks)[0]
    run.facts["moe_step"].update(
        traced_steps=len(steps), traced_rows_per_step=statistics.fmean(
            float(held.sum()) for held, _ in steps),
        least_ms_per_step=1e3 * least / len(steps),
        experts_ms_per_step=1e3 * experts_s / len(steps))
    return 100.0 * least / experts_s


def read(run):
    began = time.perf_counter()
    records = window_records(run)
    if records is None:
        return {}
    read_s = time.perf_counter() - began
    out = counted(run, records)
    if not out:
        return {}
    run.facts["moe_step"].update(records=sum(r is not None for r in records),
                                 read_s=read_s)
    if run.rehearse and not run.reduced:
        out["moe_experts_rows_roofline"] = 0.0      # the rehearsal's name
    else:
        share = roofline(run, records)
        if share is not None:
            out["moe_experts_rows_roofline"] = share
    return out
