"""Segment-aware tracing: chrome-trace glue between the deferred engine
and the profiler event stream.

Since the engine executes *bulk segments* (PR 1) rather than individual
ops, a per-op dispatch span times a ~0µs record and attributes nothing
to the flush that actually runs the program.  graftscope fixes the
attribution (the fusion-boundary view of "Operator Fusion in XLA",
PAPERS.md):

* every deferred op RECORD becomes a complete ("X") event with
  ``args={"deferred": true, "segment": <id>}`` — its duration is the
  record cost, never presented as op runtime;
* every segment FLUSH becomes a span (``bulk_segment_flush``, cat
  ``engine``) carrying cause / node count / program length / cache
  hit-miss, with ``device_time: true`` when ``profiler.sync`` blocked
  until ready (true device latency);
* chrome-trace flow events (``ph: "s"`` at record, ``ph: "f"`` at
  flush) link each deferred op to exactly one flush, so the trace UI
  draws arrows from where an op was *issued* to where its cost *landed*.

Also here: :func:`phase_span`, the program's one span primitive: the
training-loop phases (fwd/bwd/update/kvstore) of gluon ``Trainer``,
``CachedOp`` and ``Module.forward_backward``, the fused step's
step/place/dispatch, engine flushes.  Each span lands in the JAX
profiler's trace as ``mx:<name>`` (beside the device events, on their
clock), in the flight recorder's span record (``telemetry.spans()``), in
the chrome trace (cat ``phase``) and in the ``graft_phase_seconds``
histogram.  Beside it :func:`step_counter`, the one primitive for a count
made *inside* a compiled step, where no span can look, and the program's one
listener on ``jax.monitoring`` (:func:`_on_jax_event`): a build record for
every stage of every program the process builds, put down to the span that
was open when JAX built it (``telemetry.startup()``).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax

from . import blackbox as _blackbox
from . import metrics as _metrics

__all__ = ["phase_span", "step_counter", "collect_step_counters",
           "stack_step_counters", "jit_with_step_counters", "current_step",
           "operator_jitted",
           "next_segment_id", "record_active",
           "deferred_op_event", "segment_flush_span",
           "segment_summary", "validate_chrome_trace",
           "process_metadata_events", "trace_header"]

_segment_ids = itertools.count(1)

FLOW_NAME = "bulk"
FLOW_CAT = "engine.flow"
SEGMENT_SPAN = "bulk_segment_flush"

current_step = _blackbox.current_step


def next_segment_id():
    return next(_segment_ids)


def _prof():
    from .. import profiler
    return profiler


def record_active():
    """Whether deferred-op record events should be captured at all."""
    p = _prof()
    return p._P.active() and p.profile_imperative_enabled()


def _flow_id(segment, index):
    return "%d/%d" % (segment, index)


def deferred_op_event(name, begin_us, end_us, segment, index):
    """One deferred op record: the X event (marked deferred, owning
    segment) + the flow start binding it to the segment flush."""
    p = _prof()
    p.record_event(name, begin_us, end_us,
                   args={"deferred": True, "segment": segment})
    p.append_raw_event({"name": FLOW_NAME, "cat": FLOW_CAT, "ph": "s",
                        "id": _flow_id(segment, index), "ts": begin_us,
                        "pid": 0, "tid": 0})


def segment_flush_span(segment, cause, begin_us, end_us, flow_indices,
                       program_len, live_outputs, cache_hit, recorded,
                       device_time, error=False):
    """The flush span + one flow finish per op that emitted a flow start
    (``flow_indices`` — only those, so a profiler toggled mid-segment
    never leaves a dangling arrow).  ``error`` marks a flush whose
    replay raised — the span STILL closes its flow links so crash-time
    traces validate (no dangling ``s`` events)."""
    p = _prof()
    args = {"segment": segment, "cause": cause,
            "nodes": program_len,
            "live_outputs": live_outputs,
            "cache": "hit" if cache_hit else "miss",
            "recorded": bool(recorded),
            "device_time": bool(device_time),
            "step": current_step()}
    if error:
        args["error"] = True
    p.record_event(SEGMENT_SPAN, begin_us, end_us, cat="engine", args=args)
    # bind each flow to the enclosing flush slice (bp: "e")
    ts = begin_us + min(1.0, max(end_us - begin_us, 0.0) / 2)
    for i in flow_indices:
        p.append_raw_event({"name": FLOW_NAME, "cat": FLOW_CAT, "ph": "f",
                            "bp": "e", "id": _flow_id(segment, i),
                            "ts": ts, "pid": 0, "tid": 0,
                            "args": {"segment": segment}})


_open = threading.local()        # .stack: this thread's open spans


class _PhaseSpan(object):
    """One program span: a training-loop phase (fwd/bwd/update/kvstore), a
    fused step and its parts, an engine flush.  It is written where the
    device events are, as ``mx:<phase>`` in the JAX profiler's trace
    (``TraceAnnotation``: a flag test while no trace runs), and its record
    (name, start, end, parent, step id, on ``time.perf_counter()``) goes
    to the flight recorder (``telemetry.spans()``).  It also emits a
    chrome event (cat "phase") when ``mx.profiler`` runs and feeds
    graft_phase_seconds.  The span closes on the exception
    path too: the chrome event (marked ``error``), the histogram
    observation AND the flight-recorder phase bracket all land, so a
    crash mid-phase leaves a well-formed trace and a dump that names the
    phase."""

    __slots__ = ("phase", "args", "step", "parent", "_begin", "_t0", "_bb",
                 "_ann")

    def __init__(self, phase, args=None, step=None):
        self.phase = phase
        self.args = args
        self.step = step

    def __enter__(self):
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        outer = stack[-1] if stack else None
        self.parent = outer.phase if outer is not None else None
        if self.step is None:
            # a span belongs to its parent's step; one opened outside any
            # other, to the step in progress on this thread
            self.step = (outer.step if outer is not None
                         else current_step())
        stack.append(self)
        self._begin = _prof()._now_us()
        self._bb = _blackbox.phase_begin(self.phase)
        self._ann = jax.profiler.TraceAnnotation("mx:" + self.phase,
                                                 **(self.args or {}))
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ann.__exit__(exc_type, exc, tb)
        t1 = time.perf_counter()
        stack = _open.stack
        if stack and stack[-1] is self:
            stack.pop()
        else:                       # closed out of order: generators
            stack.remove(self)
        p = _prof()
        if p._P.active():
            args = {"phase": self.phase}
            if exc_type is not None:
                args["error"] = True
            if self.args:
                args.update(self.args)
            p.record_event(self.phase, self._begin, p._now_us(),
                           cat="phase", args=args)
        _metrics.phase(self.phase, t1 - self._t0)
        _blackbox.phase_end(self._bb, self.phase, self._t0, t1,
                            parent=self.parent, step=self.step,
                            error=exc_type is not None)
        return False


class _NullSpan(object):
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def phase_span(phase, args=None, step=None):
    """Context manager for one program span (``_PhaseSpan``).  ``args``
    (strings and numbers) ride the profiler events; ``step`` is given by
    a span that starts a step, and inherited by those opened in it.  Free
    when the profiler, telemetry AND the flight recorder are all off."""
    if not _metrics.enabled() and not _prof()._P.active() \
            and not _blackbox.enabled():
        return _NULL
    return _PhaseSpan(phase, args, step)


# ---------------------------------------------------------------------------
# build records: every program the process builds, by the span that asked
# ---------------------------------------------------------------------------

_COMPILE = "/jax/core/compile/"
_STAGES = {_COMPILE + "jaxpr_trace_duration": "trace",
           _COMPILE + "jaxpr_to_mlir_module_duration": "lower",
           _COMPILE + "backend_compile_duration": "backend"}
_CACHE = "/jax/compilation_cache/"
_CACHE_SAID = {_CACHE + "cache_hits": "hit", _CACHE + "cache_misses": "miss"}
_CACHE_RETRIEVAL = _CACHE + "cache_retrieval_time_sec"

class _Building(threading.local):
    """This thread's program in the making."""

    def __init__(self):
        # the trace stages nothing has followed yet, the newest last:
        # (name, start, end, span, step, enclosed)
        self.traces = []
        self.name = None        # the function the program was traced from
        # what the persistent cache said since the last stage
        self.cache, self.retrieval_s = "off", 0.0


_building = _Building()
_TRACES_HELD = 4096
# names the operator library jits under: jit_with_step_counters' own
# function, and those operator_jitted is told of
_operators = {"counted"}


def operator_jitted(fcompute):
    """``ops/registry.py`` says which function it is about to ``jax.jit``:
    JAX calls the trace by that function's ``__name__``, and a build of that
    name under no span is the operator library's (owner ``eager``)."""
    _operators.add(getattr(fcompute, "__name__", None))


def _on_jax_event(event, seconds=None, fun_name=None, **_):
    """The package's one listener on ``jax.monitoring``, for its plain and
    its duration events alike.  JAX reports a stage of a program when the
    stage ends, on the thread that asked for the program.

    A trace is kept back, and is a record only as the first stage of a
    build: JAX reports one for every call that takes its Python path, a
    cached one too (each operator under ``autograd.record()``, 10 us), and
    traces nest (a step's trace holds those of the jitted functions it
    calls, a lowering traces what its rules stage late), the enclosed ending
    first.  So a later stage that began before a trace counts it among its
    ``enclosed``, the lowering that follows a trace takes it for its
    program's, and the others are dropped.  Silent, but for that, once every
    shape is warm: JAX has nothing else to report of a cached program."""
    stage = _STAGES.get(event)
    build = _building
    if stage is None:
        if event in _CACHE_SAID:
            build.cache = _CACHE_SAID[event]
        elif event == _CACHE_RETRIEVAL:
            build.retrieval_s = seconds
        return
    end = time.perf_counter()
    start = end - max(seconds, 0.0)     # JAX's are wall-clock seconds
    traces = build.traces
    enclosed = 0
    while traces and traces[-1][1] >= start:
        enclosed += 1 + traces.pop()[5]
    stack = getattr(_open, "stack", None)
    span = stack[-1] if stack else None
    step = span.step if span is not None else current_step()
    if stage == "trace":
        if len(traces) >= _TRACES_HELD:
            del traces[:_TRACES_HELD // 2]
        traces.append((fun_name, start, end, span, step, enclosed))
        return
    attrs = {}
    if stage == "lower":
        # "jit(f)" from here on; a functools.partial (every operator's jit)
        # is "jit(<unknown>)", and its trace had the function's name
        build.name = fun_name[fun_name.index("(") + 1:-1] \
            if fun_name.endswith(")") and "(" in fun_name else fun_name
        if traces:
            if build.name == "<unknown>":
                build.name = traces[-1][0]
            _build_record(build.name, "trace", *traces[-1][1:])
        del traces[:]
    else:
        attrs = {"cache": build.cache, "retrieval_s": build.retrieval_s}
    _build_record(build.name or fun_name, stage, start, end, span, step,
                  enclosed, **attrs)
    if stage == "backend":
        build.name = None
    # what the cache says from here on is of the stage that follows
    build.cache, build.retrieval_s = "off", 0.0


def _build_record(name, stage, start, end, span, step, enclosed, **attrs):
    """One stage of a build into the start-up record
    (``blackbox.startup_add``, kind ``build``) and the counters
    (``metrics.program_build``): the stage's interval on
    ``time.perf_counter()``, the innermost span open at the time as
    ``parent`` with its step id, and in ``attrs`` the ``stage`` (``trace``,
    ``lower``, ``backend``), the ``owner`` (the parent, or for a build under
    no span ``eager`` where a registered operator's compute function was
    traced and ``user`` otherwise), the traces it ``enclosed`` and, at
    ``backend``, the persistent cache's answer (``cache``: ``hit``,
    ``miss`` or ``off``; ``retrieval_s``)."""
    if span is not None:
        parent = owner = span.phase
        if span.args:                   # an engine flush's cause
            attrs = dict(span.args, **attrs)
    else:
        parent, owner = None, "eager" if name in _operators else "user"
    attrs["stage"], attrs["owner"] = stage, owner
    if enclosed:
        attrs["enclosed"] = enclosed
    _blackbox.startup_add("build", name, start, end, parent, step, attrs)
    _metrics.program_build(owner, stage, end - start, attrs.get("cache"))


jax.monitoring.register_event_listener(_on_jax_event)
jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


_counting = threading.local()    # .open: the collection of the trace in hand


@contextlib.contextmanager
def collect_step_counters():
    """Open this thread's collection for :func:`step_counter` (the pattern
    of ``random_state.use_key``) and yield it: ``(name, labels, value)`` in
    call order.  Opened by whoever compiles a step, around the forward it
    traces (``DataParallelTrainer``).  It shadows one that is open: a nested
    ``jax.jit``'s tracers must not reach the outer step's results, so
    ``CachedOp``'s trace opens one and drops it."""
    outer = getattr(_counting, "open", None)
    # graftlint: disable=GL304 -- the collection belongs to the trace in hand, opened and closed around it
    _counting.open = found = []
    try:
        yield found
    finally:
        # graftlint: disable=GL304 -- as above: the outer trace's collection is put back
        _counting.open = outer


def step_counter(name, value, **labels):
    """A count made inside a compiled step, at a layer boundary: a sum or a
    count, a scalar or a short vector, integer or float32 (a static number
    counts too).  With a collection open it leaves the step with its
    results and ``telemetry.step_counters()`` hands it out by step id; with
    none a traced value is dropped.  False for a concrete value with no
    collection open: an eager call, whose host counters are the caller's
    to write.  Called under a ``jax.jit`` of its own (a registered
    operator's), the count has to leave that as a result first:
    :func:`jit_with_step_counters`."""
    found = getattr(_counting, "open", None)
    if found is not None:
        found.append((name, labels, value))
        return True
    return isinstance(value, jax.core.Tracer)


@jax.tree_util.register_pytree_node_class
class _Counted:
    """One step count as a result of a ``jax.jit``: the value is the leaf,
    the name and the labels are the tree's static part."""

    def __init__(self, name, labels, value):
        self.name, self.labels, self.value = name, labels, value

    def tree_flatten(self):
        return (self.value,), (self.name, tuple(sorted(self.labels.items())))

    @classmethod
    def tree_unflatten(cls, static, leaves):
        return cls(static[0], dict(static[1]), leaves[0])


def jit_with_step_counters(fn):
    """``jax.jit(fn)`` for a function that calls :func:`step_counter`: a
    traced value leaves a ``jit`` only as a result, so the counts of
    ``fn``'s trace are results of the jitted function, and each call counts
    them again in the caller's collection (the whole step's, where the call
    is part of its trace; none in an eager call, where they are dropped)."""

    def counted(*args, **kwargs):
        with collect_step_counters() as found:
            out = fn(*args, **kwargs)
        return out, [_Counted(*count) for count in found]

    jitted = jax.jit(counted)

    def call(*args, **kwargs):
        out, counts = jitted(*args, **kwargs)
        for count in counts:
            step_counter(count.name, count.value, **count.labels)
        return out

    return call


def stack_step_counters(found):
    """``({name: its values stacked in call order}, {name: [labels of each
    row]})`` of a collection; two empty dicts of an empty one."""
    rows = {}
    for name, labels, value in found:
        rows.setdefault(name, []).append((labels, value))
    return ({n: jax.numpy.stack([jax.numpy.asarray(v) for _, v in r])
             for n, r in rows.items()},
            {n: [labels for labels, _ in r] for n, r in rows.items()})


# ---------------------------------------------------------------------------
# trace identity: process/thread metadata + wall-clock anchor
# ---------------------------------------------------------------------------

def process_metadata_events(rank=None, role=None, pid=None):
    """Chrome-trace ``M`` metadata events labeling this process's track
    (``process_name``/``process_sort_index``/``thread_name``).  The
    merged cross-rank trace (telemetry/aggregate.py) emits one set per
    rank so each rank renders as its own named process row; the profiler
    prepends a set to every single-rank dump so the merge can identify
    the rank without side channels."""
    if rank is None:
        rank = _blackbox._rank[0]
    name = "rank %d" % rank
    if role:
        name += " (%s)" % role
    if pid is None:
        pid = 0
    return [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": name}},
        {"name": "process_sort_index", "ph": "M", "pid": pid,
         "args": {"sort_index": int(rank)}},
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": "main"}},
    ]


def trace_header():
    """(metadata events, otherData) for a chrome-trace dump.  The wall
    anchor maps the profiler's monotonic microsecond clock to wall-clock
    seconds, which is what lets the aggregator put N ranks' traces (and
    flight-recorder dumps) on one timeline."""
    from .. import profiler as _p
    other = {"rank": _blackbox._rank[0],
             "wall_anchor": {"perf_us": _p._now_us(),
                             "wall_s": time.time()}}
    if _blackbox._clock_offset[0] is not None:
        other["clock_offset_s"] = _blackbox._clock_offset[0]
    return process_metadata_events(), other


# ---------------------------------------------------------------------------
# trace analysis (CLI + smoke-tier validation)
# ---------------------------------------------------------------------------

def segment_summary(events, top=10):
    """Top-``top`` segment flushes by duration from a chrome-trace event
    list, plus per-cause totals — the fusion-boundary attribution view."""
    segs = [e for e in events
            if e.get("name") == SEGMENT_SPAN and e.get("ph") == "X"]
    segs.sort(key=lambda e: -e.get("dur", 0))
    causes = {}
    for e in segs:
        c = e.get("args", {}).get("cause", "?")
        agg = causes.setdefault(c, {"flushes": 0, "total_us": 0.0,
                                    "nodes": 0})
        agg["flushes"] += 1
        agg["total_us"] += e.get("dur", 0)
        agg["nodes"] += e.get("args", {}).get("nodes", 0)
    return {
        "top_segments": [{
            "segment": e.get("args", {}).get("segment"),
            "cause": e.get("args", {}).get("cause"),
            "nodes": e.get("args", {}).get("nodes"),
            "duration_us": round(e.get("dur", 0), 3),
            "cache": e.get("args", {}).get("cache"),
            "device_time": e.get("args", {}).get("device_time"),
        } for e in segs[:top]],
        "flush_causes_us": {c: round(v["total_us"], 3)
                            for c, v in causes.items()},
        "segments_total": len(segs),
    }


def validate_chrome_trace(trace):
    """Schema + flow-link validation of a dumped trace dict.  Returns a
    list of problems (empty == valid).  Used by the lint smoke tier.
    Accepts ``M`` metadata events (process_name/thread_name rows of
    merged cross-rank traces) and multi-hop flows (``s`` → any number of
    ``t`` steps → ``f``, the shape the cross-rank collective links
    use)."""
    problems = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    starts, finishes, hops = {}, {}, {}
    for i, e in enumerate(events):
        if not isinstance(e, dict) or "ph" not in e or "name" not in e:
            problems.append("event %d: missing ph/name" % i)
            continue
        ph = e["ph"]
        if ph in ("X", "s", "t", "f", "i", "C") and "ts" not in e:
            problems.append("event %d (%s): missing ts" % (i, ph))
        if ph == "X" and e.get("dur", 0) < 0:
            problems.append("event %d: negative dur" % i)
        if ph == "M" and not isinstance(e.get("args"), dict):
            problems.append("event %d (M): missing args" % i)
        if ph == "s":
            starts.setdefault(e.get("id"), []).append(i)
        elif ph == "t":
            hops.setdefault(e.get("id"), []).append(i)
        elif ph == "f":
            finishes.setdefault(e.get("id"), []).append(i)
    for fid, idxs in starts.items():
        if len(idxs) != 1:
            problems.append("flow id %r started %d times" % (fid, len(idxs)))
        if fid not in finishes:
            problems.append("flow id %r never finishes" % fid)
    for fid, idxs in finishes.items():
        if len(idxs) != 1:
            problems.append("flow id %r finished %d times" % (fid, len(idxs)))
        if fid not in starts:
            problems.append("flow id %r finishes without a start" % fid)
    for fid in hops:
        if fid not in starts:
            problems.append("flow id %r has a step without a start" % fid)
    return problems
