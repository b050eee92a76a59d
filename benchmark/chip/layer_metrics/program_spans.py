"""The program's own host spans (``telemetry.phase_span``), from
``telemetry.spans()``.

``place_span_ms_p50`` / ``dispatch_span_ms_p50``
    the fused step's ``place`` (``_prepare_inputs``) and ``dispatch`` (the
    jitted call) inside ``DataParallelTrainer.step``.
``fwd_span_ms_p50`` / ``bwd_span_ms_p50`` / ``kvstore_span_ms_p50`` /
``update_span_ms_p50``
    the Gluon loop's ``fwd`` (``CachedOp.__call__``), ``bwd``
    (``autograd.backward``), ``kvstore`` and ``update``
    (``gluon.Trainer.step``).
``engine_flushes_per_step``
    ``engine_flush`` spans (``engine.flush``) a step.

Medians over the spans that began in the window's untraced steps and are
still in the recorder (it is bounded: ``facts["program_spans"]`` has the
counts and what the records cover); fewer than ten leave the metric out
(on the CPU rehearsal, whose values ``run.py`` blanks, one span names it).
The same spans are in a profiler trace as ``mx:<name>``; with a device
trace, ``facts["idle_gaps_by_program_span"]`` puts the first device's idle
gaps down to them, on the trace's clock.
"""
from chipbench import program, timing

_SPANS = {"place_span_ms_p50": "place", "dispatch_span_ms_p50": "dispatch",
          "fwd_span_ms_p50": "fwd", "bwd_span_ms_p50": "bwd",
          "kvstore_span_ms_p50": "kvstore", "update_span_ms_p50": "update"}
FLUSH = "engine_flush"
FEWEST = 10


def read(run):
    t = program.telemetry(run)
    if t is None:
        return {}
    stamps = run.stamps
    spans = t.spans(since=stamps[0])
    ranges = program.untraced_ranges(run)
    by_name = {}
    for name, start, end, _parent, _step in spans:
        if any(lo <= start < hi for lo, hi in ranges):
            by_name.setdefault(name, []).append(end - start)
    fewest = 1 if run.rehearse else FEWEST
    out = {metric: 1e3 * timing.percentile(by_name[name], 50)
           for metric, name in _SPANS.items()
           if len(by_name.get(name, ())) >= fewest}
    # the recorder is bounded: count flushes over the steps it still covers
    covered_from = max(stamps[0], spans[0][1]) if spans else stamps[-1]
    covered = [s for s in stamps if s >= covered_from]
    steps = len(covered) - 1
    if steps >= fewest:
        flushes = sum(1 for name, start, *_ in spans
                      if name == FLUSH and covered[0] <= start < covered[-1])
        out["engine_flushes_per_step"] = flushes / steps
    run.facts["program_spans"] = {
        "samples": {name: len(v) for name, v in by_name.items()},
        "held": len(spans), "steps_covered": max(steps, 0),
        "window_covered_share":
            (stamps[-1] - covered_from) / (stamps[-1] - stamps[0])}
    gaps = program.idle_gaps(run)
    if gaps is not None:
        run.facts["idle_gaps_by_program_span"] = gaps
    return out
