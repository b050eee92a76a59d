"""What the program says of itself, joined to the profiler's trace.

The program marks its own train step from inside (``telemetry.programs()``:
its jitted programs by the name the trace's ``XLA Modules`` line gives them,
each with a phase of its own or the ``op_name`` path of every op of its
optimized HLO; ``telemetry.spans()``: its host spans as ``(name, start, end,
parent, step)`` on ``time.perf_counter()``).  The readers under
``layer_metrics/`` get both through this module, after the window, from what
``run.py`` kept of the trace (``run.recorded``, ``run.reduced``):

``device_split``  the device's self time by phase, by Block and for the flash
                  backward, every op instance put to the program whose
                  module event it ran in;
``clock_offset``  the profiler's clock against ``time.perf_counter()``, from
                  the ``bench:wait`` spans the trace kept and the stamps the
                  loop took right after each;
``spans_on_trace_clock`` / ``idle_gaps``  the program's spans on the
                  profiler's clock, and the first device's idle gaps by the
                  innermost of them.

A program without these accessors (an older commit) gives ``None``
everywhere, and the readers then report nothing.
"""
import bisect
import collections
import re
import statistics

from chipbench import trace

SPAN_PREFIX = "mx:"                 # as the program writes them into a trace
NO_SPAN = "(no program span)"
FLASH_BWD = "flash_attention_bwd"   # ops/attention.py's scope

# the first ``xray:<phase>`` scope of an op_name path; a derived op's path is
# wrapped in call syntax ("transpose(jvp(xray:forward/...))"), so brackets
# end a token
_PHASE = re.compile(r"xray:([^/\"\\()\[\s]+)")
# "transpose(jvp(stage1_conv0))": the transformations a derived op's scopes
# are wrapped in
_WRAPPED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+|\)+$")
# what control flow puts on the name stack: no scope of the program's
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
              "custom_jvp_call", "custom_vjp_call", "pjit", "scan"}


def telemetry(run):
    """The program's ``telemetry`` module if it has both accessors."""
    t = getattr(run.mx, "telemetry", None)
    if callable(getattr(t, "programs", None)) and \
            callable(getattr(t, "spans", None)):
        return t
    return None


def program_of(module_event):
    """``jit_dp_train_step(1234)`` on the modules line -> ``dp_train_step``,
    the program's name in ``telemetry.programs()``.  The benchmark's own
    copy of the rule (as ``phase_of`` is): how a metric is computed stays
    with the benchmark."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    if name.startswith("jit_"):
        name = name[4:]
    return re.sub(r"\.\d+$", "", name)


def phase_of(path):
    m = _PHASE.search(path or "")
    return m.group(1) if m else None


def scope_of(path):
    """The innermost ``named_scope`` of an op_name path (a Block's name,
    ``flash_attention_bwd``): of the components below the ``xray:`` scope,
    or below the program's own ``jit(...)`` where it has none, up to the
    first nested ``jit(...)`` or the primitive.  None where there is
    none."""
    _, found, below = (path or "").partition("xray:")
    parts = (below if found else path or "").split("/")[1:-1]
    scopes = []
    for part in parts:
        part = _WRAPPED.sub("", part)
        if "(" in part:                     # a nested jit: scopes end here
            break
        if part and part not in _STRUCTURE:
            scopes.append(part)
    return scopes[-1] if scopes else None


def kind_of(scope):
    """``resnet_stage1_batchnorm3`` -> ``batchnorm``: a Block's name cut to
    what it is, so that the many small ones of a kind add up."""
    return re.sub(r"\d+$", "", scope.rsplit("_", 1)[-1]) or scope


def device_split(run):
    """Self time on the device inside the traced window, in seconds and
    averaged over the device planes like ``trace.reduce``'s sums:

    ``phase_s`` {phase: s}, ``unattributed_s`` (ops with no phase: no scope,
    or a program the registry does not know; ``unattributed_op_s`` has
    them by op), ``program_s`` {program: s},
    ``scope_s`` {innermost scope: s}, ``flash_bwd_s``, ``steps``, ``busy_s``
    (``trace.reduce``'s own, to hold the sum to) and ``programs``
    {name: what the registry said of it}.  None without a device trace or
    without the program's registry.  Memoised on the run."""
    if "device_split" in run.__dict__:
        return run.device_split
    run.device_split = None
    t = telemetry(run)
    if t is None or not run.reduced or not run.reduced["steps"]:
        return None
    registry = t.programs()
    planes = trace.device_planes(run.recorded)
    window, steps = trace.step_window(trace.host_spans(run.recorded))
    n = len(planes)
    phase_s, program_s = collections.Counter(), collections.Counter()
    scope_s, unattributed_s = collections.Counter(), collections.Counter()
    flash_bwd = 0.0
    for plane in planes:
        modules = sorted((s, s + d, name) for name, s, d
                         in trace._line(plane, trace.MODULES_LINE))
        starts = [m[0] for m in modules]
        inside = [((name, s), max(s, window[0]), min(s + d, window[1]))
                  for name, s, d in trace._line(plane, trace.OPS_LINE)
                  if s + d > window[0] and s < window[1]]
        for (name, start), own in trace.self_times(inside):
            own = own / 1e9 / n
            i = bisect.bisect_right(starts, start) - 1
            program = (program_of(modules[i][2])
                       if i >= 0 and start < modules[i][1] else "")
            program_s[program] += own
            known = registry.get(program)
            path = phase = None
            if known is not None:
                # short HLO name: no "%", no "~2" of record()'s own
                path = known.ops.get(name.lstrip("%").partition("~")[0])
                phase = known.phase or phase_of(path)
            if phase is None:
                unattributed_s[name] += own
            else:
                phase_s[phase] += own
            scope = scope_of(path)
            if scope is not None:
                scope_s[scope] += own
            if path and FLASH_BWD in path:
                flash_bwd += own
    run.device_split = {
        "phase_s": dict(phase_s),
        "unattributed_s": sum(unattributed_s.values()),
        "unattributed_op_s": dict(unattributed_s),
        "program_s": dict(program_s), "scope_s": dict(scope_s),
        "flash_bwd_s": flash_bwd, "steps": steps,
        "busy_s": run.reduced["busy_s"],
        "programs": {name: {"phase": p.phase, "ops": len(p.ops),
                            "error": p.error}
                     for name, p in registry.items()}}
    return run.device_split


def offers(run):
    """What the registry alone can say, with no device trace (the CPU
    rehearsal): the phases some program's ops or whole would go to, and
    whether any op sits under the flash backward's scope."""
    t = telemetry(run)
    if t is None:
        return set()
    found = set()
    for p in t.programs().values():
        found.add("any")
        if p.phase:
            found.add(p.phase)
            continue
        for path in p.ops.values():
            found.add(phase_of(path))
            if FLASH_BWD in path:
                found.add(FLASH_BWD)
    return found - {None}


def clock_offset(run):
    """``(offset_s, error_s, waits)``: what to add to a
    ``time.perf_counter()`` reading to get the profiler's clock in seconds,
    the spread of that over the traced waits (the distance between its
    quartiles: the alignment error), and how many waits it rests on.

    Stamp *k* of the loop is ``perf_counter()`` taken right after the
    *k*-th ``bench:wait`` span closes; the trace holds those spans from the
    first wait after the profiler started, which closed stamp
    ``traced_stamps[0] + 1``.  None without at least two traced waits."""
    first = run.traced_stamps[0]
    if first is None or not run.recorded:
        return None
    ends = [e for name, _, e in trace.host_spans(run.recorded)
            if name == trace.SPAN_PREFIX + "wait"]
    stamps = run.stamps[first:first + len(ends)]
    if len(ends) < 2 or len(stamps) != len(ends):
        return None
    deltas = [e / 1e9 - s for e, s in zip(ends, stamps)]
    q1, _, q3 = statistics.quantiles(deltas, n=4)
    return statistics.median(deltas), q3 - q1, len(deltas)


def spans_on_trace_clock(run, offset_s):
    """The program's spans as ``trace``'s functions take them: ``(mx:<name>,
    start_ns, end_ns)`` on the profiler's clock, oldest first."""
    return [(SPAN_PREFIX + name, round((start + offset_s) * 1e9),
             round((end + offset_s) * 1e9))
            for name, start, end, _parent, _step
            in telemetry(run).spans(since=run.stamps[0] - 60.0)]


def idle_gaps(run):
    """The first device's idle time inside the traced window by the
    innermost program span it falls in, with how well the two clocks were
    aligned and how much of the window the span records still cover (the
    recorder is bounded).  None without a device trace, the program's spans
    or the alignment."""
    if telemetry(run) is None or not run.reduced:
        return None
    aligned = clock_offset(run)
    if aligned is None:
        return None
    offset_s, error_s, waits = aligned
    window, _ = trace.step_window(trace.host_spans(run.recorded))
    ops = trace._line(trace.device_planes(run.recorded)[0], trace.OPS_LINE)
    busy = trace.union((max(s, window[0]), min(s + d, window[1]))
                       for _, s, d in ops
                       if s + d > window[0] and s < window[1])
    spans = spans_on_trace_clock(run, offset_s)
    inside = [(name, max(s, window[0]), min(e, window[1]))
              for name, s, e in spans if e > window[0] and s < window[1]]
    oldest = min((s for _, s, _ in spans), default=window[1])
    gaps = trace.attribute_gaps(trace.subtract([window], busy), inside,
                                none=NO_SPAN)
    return {"seconds": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
            "alignment_error_us": 1e6 * error_s, "waits": waits,
            "offset_s": offset_s, "spans_in_window": len(inside),
            "window_covered_share": min(1.0, max(
                0.0, (window[1] - oldest) / (window[1] - window[0])))}


def untraced_ranges(run):
    """The parts of the window, on ``time.perf_counter()``, whose steps
    count as untraced: all of it but the steps between the stamps at which
    the profiler was started and stopped and the margins
    ``timing.untraced_steps`` leaves on each side."""
    stamps = run.stamps
    first, last = run.traced_stamps
    if first is None:
        return [(stamps[0], stamps[-1])]
    last = len(stamps) if last is None else last
    before = (stamps[0], stamps[max(0, first - 3)])
    after = (stamps[min(last + 1, len(stamps) - 1)], stamps[-1])
    return [r for r in (before, after) if r[1] > r[0]]
