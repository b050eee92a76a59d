"""Profiler: chrome://tracing dump + per-op aggregate statistics.

TPU-native rebirth of src/profiler/profiler.h:256 (Profiler singleton,
ProfileDomain/Task/Event/Frame/Counter/Marker object model, chrome-trace
JSON writer at profiler.h:87,437) and python/mxnet/profiler.py
(set_config:28, set_state:79, dump:105, custom objects :151+).

Design differences, by design:

* The reference times each op on the engine worker thread
  (ProfileOperator wrapped in ExecuteOprBlock, threaded_engine.h:339).
  Here ops dispatch asynchronously into XLA, so per-op events record the
  *dispatch* span, and an optional ``sync=True`` config blocks each op
  until ready to capture true device latency (the NaiveEngine-style
  bisection mode).
* ``set_config(xprof_dir=...)`` additionally starts ``jax.profiler`` so
  the XLA-level trace (fusion boundaries, HBM traffic) lands in
  TensorBoard/XProf — the TPU-native counterpart of the VTune bridge
  (src/profiler/vtune.cc).
* Aggregate stats (aggregate_stats.cc, MXAggregateProfileStatsPrint)
  come from the same event stream via :func:`dumps`.
"""
from __future__ import annotations

import json
import threading
import time

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "pause",
           "resume", "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

_lock = threading.Lock()


class _ProfilerState:
    """Process-wide profiler singleton (ref: profiler.h Profiler::Get)."""

    def __init__(self):
        self.running = False
        self.paused = False
        self.filename = "profile.json"
        self.profile_imperative = True
        self.profile_symbolic = True
        self.profile_memory = False  # reference default: opt-in (docs/faq/env_var.md profile options)
        self.profile_api = True
        self.aggregate_stats = False
        self.sync = False
        self.xprof_dir = None
        self.events = []            # chrome trace event dicts
        self.continuous_dump = False

    def active(self):
        return self.running and not self.paused


_P = _ProfilerState()


def set_config(**kwargs):
    """ref: profiler.py set_config / MXSetProfilerConfig.

    Recognized keys: filename, profile_all, profile_imperative,
    profile_symbolic, profile_memory, profile_api, aggregate_stats,
    continuous_dump, sync (block each op for true device latency),
    xprof_dir (also capture a jax.profiler/XProf trace).
    """
    if kwargs.pop("profile_all", False):
        _P.profile_imperative = _P.profile_symbolic = True
        _P.profile_memory = _P.profile_api = True
    for key in ("filename", "profile_imperative", "profile_symbolic",
                "profile_memory", "profile_api", "aggregate_stats",
                "continuous_dump", "sync", "xprof_dir"):
        if key in kwargs:
            setattr(_P, key, kwargs.pop(key))
    if kwargs:
        raise ValueError("unknown profiler config keys: %s" % list(kwargs))


def set_state(state="stop"):
    """ref: profiler.py set_state / MXSetProfilerState ('run'|'stop')."""
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if state == "run" and not _P.running:
        _P.running = True
        _P.paused = False
        if _P.xprof_dir:
            import jax
            jax.profiler.start_trace(_P.xprof_dir)
    elif state == "stop" and _P.running:
        _P.running = False
        if _P.xprof_dir:
            import jax
            jax.profiler.stop_trace()
        if _P.continuous_dump:
            dump()


def state():
    return "run" if _P.running else "stop"


def pause():
    """ref: profiler.py pause / MXProfilePause."""
    _P.paused = True


def resume():
    """ref: profiler.py resume."""
    _P.paused = False


def _now_us():
    return time.perf_counter_ns() / 1e3


def record_event(name, begin_us, end_us, cat="operator", tid=0, args=None):
    """Append one complete ('ph: X') event; called from the dispatch hooks."""
    ev = {"name": name, "cat": cat, "ph": "X", "ts": begin_us,
          "dur": end_us - begin_us, "pid": 0, "tid": tid}
    if args:
        ev["args"] = args
    with _lock:
        _P.events.append(ev)


def append_raw_event(ev):
    """Append a pre-built chrome-trace event dict (flow events etc. from
    telemetry.tracing — the profiler stays the single event sink)."""
    with _lock:
        _P.events.append(ev)


def profile_imperative_enabled():
    return _P.profile_imperative


class _OpSpan:
    """Context manager timing one op dispatch (ProfileOperator reborn,
    threaded_engine.h:339-350).

    Under async dispatch the measured span is DISPATCH time, not device
    time — the event says so (``args.device_time``) so traces of a real
    model body cannot be misread; ``sync=True`` config blocks until
    ready inside the span and flips the flag (see invoke/Executor)."""

    __slots__ = ("name", "begin", "args")

    def __init__(self, name, args=None):
        self.name = name
        self.args = args

    def __enter__(self):
        self.begin = _now_us()
        return self

    def __exit__(self, *exc):
        # the span closes on the exception path too (marked, so a trace
        # of a crashing op is well-formed AND says the op failed)
        if exc and exc[0] is not None:
            self.args = dict(self.args or {}, error=True)
        record_event(self.name, self.begin, _now_us(), args=self.args)
        return False


def op_span(name, kind="imperative", args=None):
    """Hook used by ndarray.invoke / Executor.forward; returns a context
    manager (or None when profiling is off, keeping the hot path free)."""
    if not _P.active():
        return None
    if kind == "imperative" and not _P.profile_imperative:
        return None
    if kind == "symbolic" and not _P.profile_symbolic:
        return None
    return _OpSpan(name, args)


def want_sync():
    """Whether ops should block until ready inside the span (sync mode)."""
    return _P.active() and _P.sync


def dump(finished=True):
    """Write the chrome://tracing JSON (ref: Profiler::DumpProfile,
    profiler.h:304; python profiler.py dump:105).  Open the file at
    chrome://tracing or https://ui.perfetto.dev.

    Every dump leads with process/thread ``M`` metadata (rank-labeled
    track) and carries an ``otherData.wall_anchor`` mapping the
    profiler's monotonic clock to wall time — the identity + alignment
    data ``telemetry --analyze`` needs to merge N ranks' traces onto one
    timeline."""
    with _lock:
        events = list(_P.events)
        if finished:
            _P.events = []
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    try:
        from .telemetry import tracing as _ttracing
        meta, other = _ttracing.trace_header()
        doc["traceEvents"] = meta + events
        doc["otherData"] = other
    except Exception:
        pass                    # a dump must never fail on metadata glue
    with open(_P.filename, "w") as f:
        json.dump(doc, f)
    return _P.filename


_sampled_peak = {}   # device -> max live bytes seen by the fallback


def device_memory():
    """Per-device memory statistics — the storage-manager accounting of
    SURVEY §2.1 (ref: src/profiler/storage_profiler.h hooked at
    storage.cc:77-79; here the XLA per-device allocator IS the storage
    manager).  Primary source: ``Device.memory_stats()`` (real TPU
    runtimes report allocator counters incl. true peak).  Backends that
    report nothing (the host CPU backend) fall back to summing
    ``jax.live_arrays()`` shards per device — exact live bytes, with
    ``peak_bytes_in_use`` the max live bytes ever *sampled* by this
    function (``source`` says which accounting answered)."""
    import jax
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats:
            out.append({
                "device": str(d),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
                "num_allocs": int(stats.get("num_allocs", 0)),
                "source": "allocator",
            })
        else:
            out.append({"device": str(d), "bytes_in_use": 0,
                        "peak_bytes_in_use": 0, "bytes_limit": 0,
                        "num_allocs": 0, "source": "live_arrays"})
    fallback = {m["device"]: m for m in out if m["source"] == "live_arrays"}
    if fallback:
        for arr in jax.live_arrays():
            try:
                shards = arr.addressable_shards
            except Exception:
                continue
            for sh in shards:
                m = fallback.get(str(sh.device))
                if m is not None:
                    m["bytes_in_use"] += int(sh.data.nbytes)
                    m["num_allocs"] += 1
        for dev, m in fallback.items():
            peak = max(_sampled_peak.get(dev, 0), m["bytes_in_use"])
            _sampled_peak[dev] = peak
            m["peak_bytes_in_use"] = peak
    return out


def record_memory_snapshot(name="device_memory"):
    """Append chrome-trace counter events ("C" phase) with each device's
    live bytes — storage_profiler's counter stream for the trace view."""
    if not _P.active():
        return
    ts = _now_us()
    with _lock:
        for m in device_memory():
            _P.events.append({
                "name": name, "cat": "memory", "ph": "C", "ts": ts,
                "pid": m["device"],
                "args": {"bytes_in_use": m["bytes_in_use"],
                         "peak_bytes_in_use": m["peak_bytes_in_use"]},
            })


def dumps(reset=False):
    """Aggregate per-op statistics table (ref: aggregate_stats.cc /
    MXAggregateProfileStatsPrint; python profiler.py dumps:127), plus a
    per-device memory section when ``profile_memory`` is configured."""
    with _lock:
        events = list(_P.events)
        if reset:
            _P.events = []
    stats = {}
    for ev in events:
        if "dur" not in ev:
            continue   # counter ("C") / instant ("i") events have no span
        s = stats.setdefault((ev["cat"], ev["name"]),
                             [0, 0.0, float("inf"), 0.0])
        dur = ev["dur"]
        s[0] += 1
        s[1] += dur
        s[2] = min(s[2], dur)
        s[3] = max(s[3], dur)
    lines = ["%-32s %8s %12s %12s %12s %12s"
             % ("Name", "Calls", "Total(us)", "Min(us)", "Max(us)", "Avg(us)")]
    for (cat, name), (cnt, tot, mn, mx) in sorted(
            stats.items(), key=lambda kv: -kv[1][1]):
        lines.append("%-32s %8d %12.1f %12.1f %12.1f %12.1f"
                     % (name[:32], cnt, tot, mn, mx, tot / cnt))
    if _P.profile_memory:
        lines.append("")
        lines.append("%-24s %16s %16s %16s %12s"
                     % ("Device memory", "InUse(bytes)", "Peak(bytes)",
                        "Limit(bytes)", "Allocs"))
        for m in device_memory():
            lines.append("%-24s %16d %16d %16d %12d"
                         % (m["device"][:24], m["bytes_in_use"],
                            m["peak_bytes_in_use"], m["bytes_limit"],
                            m["num_allocs"]))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Custom instrumentation objects (ref: python/mxnet/profiler.py:151-446 —
# Domain/Task/Frame/Event/Counter/Marker over the C ProfileObject model)
# ---------------------------------------------------------------------------

class Domain(object):
    """Named grouping for custom events (ref: profiler.py Domain:151)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)

    def __str__(self):
        return self.name


class _DurationObject(object):
    """start/stop pair emitting one complete event (Task/Frame/Event)."""

    _cat = "task"

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._begin = None

    def start(self):
        self._begin = _now_us()

    def stop(self):
        if self._begin is None:
            raise RuntimeError("%s %r stopped before start"
                               % (type(self).__name__, self.name))
        if _P.active():
            record_event(self.name, self._begin, _now_us(), cat=self._cat,
                         args={"domain": str(self.domain)}
                         if self.domain else None)
        self._begin = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def __str__(self):
        return self.name


class Task(_DurationObject):
    """ref: profiler.py Task:210."""
    _cat = "task"


class Frame(_DurationObject):
    """ref: profiler.py Frame:252 (per-iteration frames)."""
    _cat = "frame"


class Event(_DurationObject):
    """ref: profiler.py Event:294 (domain-less duration)."""
    _cat = "event"

    def __init__(self, name):
        super().__init__(None, name)


class Counter(object):
    """Monotonic user counter (ref: profiler.py Counter:330)."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self.value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self.value = value
        if _P.active():
            with _lock:
                _P.events.append({"name": self.name, "cat": "counter",
                                  "ph": "C", "ts": _now_us(), "pid": 0,
                                  "args": {self.name: value}})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self

    def __str__(self):
        return self.name


class Marker(object):
    """Instant event (ref: profiler.py Marker:400)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if _P.active():
            with _lock:
                _P.events.append({"name": self.name, "cat": "marker",
                                  "ph": "i", "ts": _now_us(), "pid": 0,
                                  "tid": 0,
                                  "s": {"process": "p", "global": "g",
                                        "thread": "t"}.get(scope, "p")})
