"""graftwatch flight recorder — the always-on black box.

graftscope (metrics + tracing) only helps while the process is healthy
and a profiler is attached.  Production TPU jobs die differently: a
stalled collective, a device OOM, a worker that simply vanishes — and a
multi-hour run leaves nothing to debug with.  The flight recorder is the
answer: a bounded, lock-cheap ring buffer of structured events that is
ALWAYS recording (independent of ``GRAFT_TELEMETRY`` and the profiler)
and is dumped to JSON when the process dies or hangs:

* engine segment flushes (cause / node count / latency / cache),
* kvstore push/pull/reduce_many collectives (keys / bytes / rank),
* ``Trainer.step`` / ``Module.update`` boundaries with per-phase
  latencies and the device-memory highwater,
* dist heartbeats (per-worker last-seen + step skew) and watchdog trips.

Dump triggers: unhandled exception (``sys.excepthook`` chain), SIGTERM /
SIGINT (handler chain), an explicit :func:`dump` call, or a watchdog
trip (:mod:`~incubator_mxnet_tpu.telemetry.watchdog`).  The dump also
captures what was IN FLIGHT (the open engine flush / collective / phase
brackets) and the most recent bracket failures, so a crash mid-step
names the phase it died in.

Environment: ``GRAFT_BLACKBOX`` (default on) master switch;
``GRAFT_BLACKBOX_SIZE`` ring capacity (default 4096 events);
``GRAFT_BLACKBOX_PATH`` dump destination (default
``<tmpdir>/graft_blackbox.<pid>.json``).

Render a dump with ``python -m incubator_mxnet_tpu.telemetry
--blackbox PATH [--json]``; validate one with ``--blackbox --selftest``.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import tempfile
import threading
import time
import traceback
from collections import deque
from contextlib import contextmanager as _contextmanager
from contextlib import nullcontext as _nullcontext

from ..analysis import lockstep as _lockstep

__all__ = ["enabled", "set_enabled", "record", "events", "stats",
           "in_flight", "inflight_entries", "progress", "last_progress",
           "collective", "phase_begin", "phase_end", "spans", "step_journal",
           "step_counts", "step_counters", "startup", "startup_add",
           "package_imported",
           "SETUP_SPANS", "current_step", "advance_step",
           "workers_seen", "set_rank", "set_clock_offset", "dump",
           "snapshot", "default_path", "validate_dump", "summarize_dump",
           "install_hooks", "configure", "selftest", "SCHEMA",
           "register_emergency", "unregister_emergency"]

SCHEMA = "graft-blackbox/1"
_DEFAULT_SIZE = 4096

_enabled_override = None


def set_enabled(flag):
    """Force the recorder on/off (None = defer to GRAFT_BLACKBOX)."""
    global _enabled_override
    _enabled_override = flag


def enabled():
    if _enabled_override is not None:
        return bool(_enabled_override)
    return os.environ.get("GRAFT_BLACKBOX", "1").strip().lower() \
        not in ("0", "false", "no", "off")


def _ring_size():
    try:
        n = int(os.environ.get("GRAFT_BLACKBOX_SIZE", str(_DEFAULT_SIZE)))
    except ValueError:
        return _DEFAULT_SIZE
    return max(n, 8)


# the ring: deque.append is GIL-atomic and O(1) with maxlen eviction —
# the hot path is one time.time() + one append, no lock
_ring = deque(maxlen=_ring_size())
_stats = [0]                    # events recorded ever (dropped = _stats[0]
#                                 - len(_ring)); single-slot list keeps the
#                                 increment one bytecode away from atomic —
#                                 a lost count under contention is harmless
# closed program spans (tracing.phase_span), oldest first: (name, start,
# end, parent, step) on time.perf_counter().  A deque of their own, under
# the ring's switch and size: a loop that flushes 40 times a step would
# otherwise push a step's spans out of the ring within seconds
_spans = deque(maxlen=_ring_size())
# what the compiled steps counted (tracing.step_counter), oldest first:
# (step id, {name: device array}, {name: [labels of each row]}), unread
_counts = deque(maxlen=_ring_size())
# the start-up record: how the process got going, and every program it
# built.  ``(kind, name, start, end, parent, step, attrs)`` on
# time.perf_counter(): the package's import (kind "import"), the set-up
# spans (kind "span": SETUP_SPANS, which go to the span ring as well) and a
# record a stage of every program JAX built (kind "build": tracing.py's
# listener).  Set-up is the beginning of a process, so the OLDEST are kept:
# once full the record takes no more and counts what it turned away.  The
# set-up of the benchmark's largest cells leaves some 1,100 records (three a
# program); the span ring's default size holds them with room for a job's
# later rebuilds
STARTUP_SIZE = _DEFAULT_SIZE
SETUP_SPANS = frozenset(["initialize", "deferred_init", "gather_params",
                         "memory_analysis"])
_startup = []
_startup_dropped = [0]
_rank = [0]
_clock_offset = [None]          # latest heartbeat clock/arrival offset
#                                 estimate vs the freshest-arriving rank
#                                 (parallel/dist.py), recorded in dump
#                                 headers so aggregate.py can align
#                                 single dumps without matched anchors
_started_at = time.time()


def configure(size=None):
    """Re-size the ring (tests / live re-tuning).  Keeps newest events."""
    global _ring, _spans, _counts
    if size is not None:
        os.environ["GRAFT_BLACKBOX_SIZE"] = str(int(size))
    _ring = deque(_ring, maxlen=_ring_size())
    _spans = deque(_spans, maxlen=_ring_size())
    _counts = deque(_counts, maxlen=_ring_size())


def set_rank(rank):
    """Stamp the dist rank onto every future dump (parallel/dist.py)."""
    _rank[0] = int(rank)


def set_clock_offset(seconds):
    """Record this rank's latest clock/arrival offset estimate from the
    dist heartbeat (upper bound: includes arrival skew).  Lands in dump
    headers as ``clock_offset_s`` for the cross-rank aggregator."""
    _clock_offset[0] = float(seconds)


def record(kind, **fields):
    """Append one structured event.  THE hot path: a disabled recorder
    costs one env lookup; an enabled one adds one tuple + deque append.
    Every event carries the recording thread's ``step`` id — the join
    key the cross-rank aggregator uses."""
    if not enabled():
        return
    fields.setdefault("step", current_step())
    _stats[0] += 1
    _ring.append((time.time(), kind, fields))


def startup_add(kind, name, start, end, parent=None, step=None, attrs=None):
    """Keep one start-up record, unless the recorder is off or the record
    full (the oldest are kept, the rest counted)."""
    if not enabled():
        return
    if len(_startup) >= STARTUP_SIZE:
        _startup_dropped[0] += 1
        return
    _startup.append((kind, name, start, end, parent, step,
                     {} if attrs is None else attrs))


def package_imported(stamps, jax_preloaded):
    """The ``package_import`` record, from the stamps the package's
    ``__init__`` took on ``time.perf_counter()``: ``[(group, its end)]``
    from ``("start", its first line)`` on.  ``attrs``: the groups' seconds
    in import order, whether ``jax`` had been imported before the package
    (else the first group that needs it holds JAX's import) and whether a
    backend was open by the end (importing the package opens none)."""
    from jax._src import xla_bridge
    groups = {name: end - begun for (name, end), (_, begun)
              in zip(stamps[1:], stamps)}
    startup_add("import", "package_import", stamps[0][1], stamps[-1][1],
                attrs={"groups_s": groups, "jax_preloaded": jax_preloaded,
                       "backend_open": xla_bridge.backends_are_initialized()})


class _Startup(list):
    """What :func:`startup` hands out: the records, oldest first, and how
    many the full record turned away (``dropped``)."""
    dropped = 0


def startup():
    """The start-up record: ``(kind, name, start_s, end_s, parent, step,
    attrs)`` on ``time.perf_counter()``, oldest first, which no eviction
    touches.  ``kind`` is ``"import"`` (``package_import``), ``"span"`` (a
    set-up span: ``SETUP_SPANS``) or ``"build"`` (one stage of one program
    JAX built: ``attrs`` has its ``stage``, ``owner`` and, for the
    ``backend`` stage, what the persistent cache said).  The list's
    ``dropped`` counts the records that came once it held
    ``STARTUP_SIZE``."""
    held = _Startup(_startup)
    held.dropped = _startup_dropped[0]
    return held


def events():
    """Snapshot of the ring as dicts (oldest first)."""
    return [{"ts": t, "kind": k, "data": dict(f)} for t, k, f in list(_ring)]


def stats():
    """Recorder status summary (benches embed this)."""
    counts = {}
    for _t, k, _f in list(_ring):
        counts[k] = counts.get(k, 0) + 1
    return {"enabled": enabled(), "ring_size": _ring_size(),
            "events_held": len(_ring), "events_total": _stats[0],
            "counts": counts}


# ---------------------------------------------------------------------------
# in-flight brackets: what the process was DOING when it died/hung
# ---------------------------------------------------------------------------

_inflight_lock = threading.Lock()
_inflight = {}                  # thread ident -> [entry dict, ...] (stack)
_failures = deque(maxlen=16)    # brackets that exited with an exception
_last_progress = [time.time(), "startup"]


def progress(site):
    """A bracket completed: wall-clock progress for the watchdog."""
    _last_progress[0] = time.time()
    _last_progress[1] = site


def last_progress():
    return {"ts": _last_progress[0], "site": _last_progress[1],
            "age": time.time() - _last_progress[0]}


def _push_inflight(site, detail):
    tid = threading.get_ident()
    # the numeric ident rides the entry so the watchdog's typed
    # escalation (GRAFT_WATCHDOG_ESCALATE) can raise into the exact
    # thread that owns the stuck bracket
    entry = {"site": site, "detail": detail, "since": time.time(),
             "thread": threading.current_thread().name, "tid": tid}
    with _inflight_lock:
        _inflight.setdefault(tid, []).append(entry)
    return entry


def _pop_inflight(entry, error=None):
    tid = threading.get_ident()
    with _inflight_lock:
        stack = _inflight.get(tid)
        if stack:
            try:
                stack.remove(entry)
            except ValueError:
                pass
            if not stack:
                _inflight.pop(tid, None)
    if error is not None:
        _failures.append(dict(entry, error=error,
                              seconds=time.time() - entry["since"]))
    else:
        progress(entry["site"])


def inflight_entries():
    """Live references to the open bracket entries (the watchdog marks
    tripped ones in place)."""
    with _inflight_lock:
        return [e for stack in _inflight.values() for e in stack]


_NULL = _nullcontext()          # stateless: safe to share across threads


class _InFlight(object):
    __slots__ = ("site", "detail", "entry")

    def __init__(self, site, detail):
        self.site = site
        self.detail = detail
        self.entry = None

    def __enter__(self):
        self.entry = _push_inflight(self.site, self.detail)
        return self

    def __exit__(self, et, ev, tb):
        _pop_inflight(self.entry, error=repr(ev) if et is not None else None)
        return False


def in_flight(site, detail=None):
    """Bracket one potentially-hanging operation (engine flush, dist
    collective): the watchdog times these, and an open bracket at dump
    time IS the "what was it doing" answer."""
    if not enabled():
        return _NULL
    return _InFlight(site, detail or {})


# ---------------------------------------------------------------------------
# collectives: kvstore push/pull/reduce_many brackets + slow-call EWMA
# ---------------------------------------------------------------------------

_ewma_lock = threading.Lock()
_ewma = {}                      # path -> EWMA seconds
_EWMA_FLOOR = 1e-3              # ignore sub-ms noise for straggler calls
# async brackets stay open from issue until the consumer waits, so their
# "latency" measures how long the result was LEFT in flight (graftlap:
# mostly the rest of the backward pass; graftduplex pulls: until the
# next forward first touches a weight), not wire health — feeding that
# into the straggler EWMA would cry wolf on every well-overlapped step
_NO_STRAGGLER_PATHS = frozenset(["reduce_many_async", "pull_many_async"])


def _straggler_factor():
    try:
        return float(os.environ.get("GRAFT_STRAGGLER_FACTOR", "3"))
    except ValueError:
        return 3.0


# collective sequence numbers: one process-wide monotonic counter.  The
# collective issue order is SPMD-identical across ranks (the lockstep
# contract every dist path already keeps), so the same seq on two ranks
# IS the same wire collective — the matching key the cross-rank trace
# aggregator and straggler table join on.
_collective_seq = itertools.count(1)


class _Collective(object):
    __slots__ = ("path", "fields", "entry", "_t0", "_bb")

    def __init__(self, path, fields, bb=True):
        self.path = path
        self.fields = fields
        self.entry = None
        self._bb = bb           # False: recorder off, bracket kept alive
        #                         only for chrome spans

    def __enter__(self):
        self._t0 = time.perf_counter()
        seq = next(_collective_seq)
        fields = dict(self.fields, seq=seq, step=current_step())
        self.fields = fields
        # lockstep divergence auditor: fold this collective's identity
        # into the rank's rolling stream hash at the moment its seq is
        # assigned (the SPMD issue order IS what the hash witnesses);
        # host-service ps_* paths are excluded inside fold()
        _lockstep.fold(seq, self.path, n_keys=fields.get("n_keys"),
                       nbytes=fields.get("nbytes"),
                       keys=fields.get("keys")
                       or ([fields["bucket"]] if fields.get("bucket")
                           else None))
        if self._bb:
            self.entry = _push_inflight(
                "collective", dict(fields, path=self.path))
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self._t0
        err = repr(ev) if et is not None else None
        if self._bb:
            _pop_inflight(self.entry, error=err)
            fields = dict(self.fields, path=self.path, rank=_rank[0],
                          latency_ms=round(dt * 1e3, 3))
            if err is not None:
                fields["error"] = err
            record("collective", **fields)
        self._trace_span(dt)
        if self._bb and err is None:
            self._straggler_check(dt)
        return False

    def _trace_span(self, dt):
        """Chrome-trace collective span (cat ``collective``) so traces —
        not just flight-recorder dumps — carry the per-collective
        enter/exit the cross-rank aggregator keys on."""
        from .. import profiler as _prof
        if not _prof._P.active():
            return
        end_us = _prof._now_us()
        args = {"path": self.path, "rank": _rank[0]}
        for k in ("seq", "step", "n_keys", "nbytes", "bucket"):
            if self.fields.get(k) is not None:
                args[k] = self.fields[k]
        _prof.record_event(self.fields.get("bucket") or self.path,
                           end_us - dt * 1e6, end_us, cat="collective",
                           args=args)

    def _straggler_check(self, dt):
        """Slow-collective detection: a call beyond ``factor`` × its own
        EWMA (per path) earns a log line + a ring event.  The EWMA only
        updates on healthy calls so one straggler can't poison it."""
        if self.path in _NO_STRAGGLER_PATHS:
            return
        factor = _straggler_factor()
        with _ewma_lock:
            prev = _ewma.get(self.path)
            slow = (prev is not None and prev > _EWMA_FLOOR
                    and dt > factor * prev)
            if not slow:
                _ewma[self.path] = dt if prev is None \
                    else 0.8 * prev + 0.2 * dt
        if slow:
            record("slow_collective", path=self.path, rank=_rank[0],
                   latency_ms=round(dt * 1e3, 3),
                   ewma_ms=round(prev * 1e3, 3), factor=factor)
            from . import metrics as _metrics
            _metrics.collective_slow(self.path)
            import logging
            logging.getLogger("graftwatch").warning(
                "slow collective: %s took %.1fms (EWMA %.1fms, factor %g) "
                "on rank %d", self.path, dt * 1e3, prev * 1e3, factor,
                _rank[0])


def collective(path, **fields):
    """Bracket one kvstore collective (push/pull/reduce_many/ps_*):
    records a ``collective`` ring event with latency + key/byte counts,
    feeds the straggler EWMA, and shows up in-flight while running.
    With the recorder off, the profiler's chrome collective spans must
    survive — the bracket then runs in light mode (no ring/in-flight/
    EWMA, same seq/step stamping)."""
    if enabled():
        return _Collective(path, fields)
    if _profiler_active():
        return _Collective(path, fields, bb=False)
    return _NULL


def _profiler_active():
    from .. import profiler as _prof
    return _prof._P.active()


# ---------------------------------------------------------------------------
# step journal: Trainer.step / Module.update boundaries with phase latencies
# ---------------------------------------------------------------------------

_tls = threading.local()
_step_counters = {}


def current_step():
    """Id of the step in progress on the calling thread: one more than
    the steps it has closed.  Spans, ring events, flush spans and
    collective spans carry it — the key the cross-rank aggregator and
    the benchmark's span readers join on."""
    return getattr(_tls, "steps_closed", 0) + 1


def advance_step():
    """Close the calling thread's step (a step journal's exit, a served
    batch); returns the id it had."""
    _tls.steps_closed = closed = getattr(_tls, "steps_closed", 0) + 1
    return closed


def phase_begin(phase):
    """Called by tracing._PhaseSpan.__enter__: the phase becomes an
    in-flight bracket so a crash/hang mid-phase names it."""
    if not enabled():
        return None
    return _push_inflight("phase", {"phase": phase})


def phase_end(entry, phase, start, end, parent=None, step=None,
              error=False):
    """Close the phase bracket and keep the span's record: name, start,
    end (``time.perf_counter()``), the span it was opened in and the step
    it belongs to.  A span opened directly in a step journal also lands
    its latency on the journal (spans nested in it are part of it)."""
    if entry is not None:
        _pop_inflight(entry, error="exception in phase %r" % phase
                      if error else None)
    if not enabled():
        return
    _spans.append((phase, start, end, parent, step))
    if phase in SETUP_SPANS:
        startup_add("span", phase, start, end, parent, step)
    j = getattr(_tls, "step", None)
    if j is not None and parent is None:
        j["phases"][phase] = j["phases"].get(phase, 0.0) + end - start
        if error:
            j["error_phase"] = phase


def spans(since=None):
    """The closed program spans still held, oldest first, as ``(name,
    start_s, end_s, parent, step)`` on ``time.perf_counter()``; with
    ``since``, those that began at or after it."""
    held = list(_spans)
    if since is None:
        return held
    return [s for s in held if s[1] >= since]


def step_counts(step, counts, labels):
    """Keep what the compiled step ``step`` (the id its ``step`` span
    carries) counted: device arrays, kept unread, so nothing here waits for
    the device."""
    if enabled():
        _counts.append((step, counts, labels))


def step_counters(since_step=None):
    """``[(step id, {name: numpy array})]`` of the compiled steps' counts
    still held, oldest first; with ``since_step``, of the steps from that id
    on.  Reading is what waits for a step: the reader's time."""
    import jax
    held = [(step, counts) for step, counts, _ in list(_counts)
            if since_step is None or step >= since_step]
    # one device_get: every copy is started before the first is waited for
    return list(zip([step for step, _ in held],
                    jax.device_get([counts for _, counts in held])))


def _newest_step_counts():
    """The newest record whose step is done (a dump must not wait for a
    device that hangs), as plain lists with each row's labels."""
    for step, counts, labels in reversed(list(_counts)):
        if all(v.is_ready() for v in counts.values()):
            return {"step": step, "counts": {
                n: {"labels": labels.get(n), "values": v.tolist()}
                for n, v in counts.items()}}
    return None


def _device_mem_peak():
    """Cheap device-memory highwater: allocator counters only (the
    live_arrays fallback walk is too slow for a per-step journal)."""
    try:
        import jax
        total, found = 0, False
        for d in jax.local_devices():
            s = d.memory_stats() or {}
            if "peak_bytes_in_use" in s:
                total += int(s.get("peak_bytes_in_use", 0))
                found = True
        return total if found else None
    except Exception:
        return None


class _StepJournal(object):
    __slots__ = ("origin", "fields", "entry", "journal", "prev", "_t0")

    def __init__(self, origin, fields):
        self.origin = origin
        self.fields = fields

    def __enter__(self):
        index = _step_counters[self.origin] = \
            _step_counters.get(self.origin, 0) + 1
        self._t0 = time.perf_counter()
        self.journal = {"phases": {}}
        self.prev = getattr(_tls, "step", None)
        _tls.step = self.journal
        self.entry = _push_inflight(
            "step", dict(self.fields, origin=self.origin, index=index))
        return self

    def __exit__(self, et, ev, tb):
        _tls.step = self.prev
        err = repr(ev) if et is not None else None
        _pop_inflight(self.entry, error=err)
        fields = dict(self.fields, origin=self.origin,
                      index=self.entry["detail"]["index"],
                      latency_ms=round(
                          (time.perf_counter() - self._t0) * 1e3, 3),
                      phases={k: round(v, 6)
                              for k, v in self.journal["phases"].items()})
        mem = _device_mem_peak()
        if mem is not None:
            fields["device_mem_peak"] = mem
        if "error_phase" in self.journal:
            fields["error_phase"] = self.journal["error_phase"]
        if err is not None:
            fields["error"] = err
        # the journal boundary IS the step boundary: the step event's
        # `step` matches the id stamped on the step's flushes/collectives
        fields["step"] = advance_step()
        record("step", **fields)
        return False


@_contextmanager
def _count_only_step():
    """Step boundary while the flight recorder is off: the step id that
    chrome spans carry must still advance at step end."""
    try:
        yield
    finally:
        advance_step()


def step_journal(origin, **fields):
    """Bracket one optimizer step (gluon ``Trainer.step`` /
    ``Module.update``): phase latencies recorded inside land on ONE
    ``step`` ring event with the device-memory highwater — and the
    journal exit advances the thread's step id (also when the recorder
    itself is disabled)."""
    if not enabled():
        return _count_only_step()
    return _StepJournal(origin, fields)


# ---------------------------------------------------------------------------
# dist worker table (straggler view)
# ---------------------------------------------------------------------------

_workers_lock = threading.Lock()
_workers = {}                   # rank -> {"step", "lag_s", "at"}


def workers_seen(table, skew=None, step=None):
    """Update the per-worker last-seen table from one dist heartbeat
    (parallel/dist.py piggybacks it on the kvstore sync path)."""
    if not enabled():
        return
    now = time.time()
    with _workers_lock:
        for r, info in table.items():
            _workers[int(r)] = dict(info, at=now)
    fields = {"workers": len(table)}
    if skew is not None:
        fields["skew_s"] = round(float(skew), 6)
    if step is not None:
        fields["step"] = int(step)
    record("dist_heartbeat", **fields)


# ---------------------------------------------------------------------------
# dump
# ---------------------------------------------------------------------------

def default_path():
    """Dump destination.  A shared ``GRAFT_BLACKBOX_PATH`` is suffixed
    with the dist rank for ranks > 0 — N workers honoring the same env
    var used to overwrite each other's post-mortems; now rank 0 keeps
    the configured path (single-process behavior unchanged) and every
    other rank writes ``<stem>.rank<r><ext>`` alongside it, ready for
    ``--analyze`` to consume the whole set.  A ``{rank}`` placeholder
    substitutes exactly; a path whose filename already names this rank
    (``rank<r>`` in the basename — the old per-worker guidance) is kept
    verbatim, so existing per-rank deployments keep their paths."""
    path = os.environ.get("GRAFT_BLACKBOX_PATH")
    if path:
        if "{rank}" in path:
            return path.replace("{rank}", str(_rank[0]))
        if _rank[0] and "rank%d" % _rank[0] not in os.path.basename(path):
            root, ext = os.path.splitext(path)
            path = "%s.rank%d%s" % (root, _rank[0], ext)
        return path
    return os.path.join(
        tempfile.gettempdir(), "graft_blackbox.%d.json" % os.getpid())


def _thread_stacks():
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in frames.items():
        label = "%s (%d)" % (names.get(ident, "?"), ident)
        out[label] = traceback.format_stack(frame)
    return out


def snapshot(reason="manual", extra=None):
    """The dump document (JSON-able).  Includes the ring, the open
    in-flight brackets, recent bracket failures, the per-worker
    last-seen table, and formatted thread stacks."""
    now = time.time()
    with _inflight_lock:
        infl = [dict(e, age=round(now - e["since"], 6))
                for stack in _inflight.values() for e in stack]
    with _workers_lock:
        workers = {str(r): dict(v) for r, v in _workers.items()}
    doc = {
        "schema": SCHEMA,
        "pid": os.getpid(),
        "rank": _rank[0],
        "clock_offset_s": _clock_offset[0],
        "reason": reason,
        "dumped_at": now,
        "started_at": _started_at,
        "ring_size": _ring_size(),
        "events_total": _stats[0],
        "last_progress": last_progress(),
        "in_flight": infl,
        "failures": [dict(f) for f in _failures],
        "workers": workers,
        "events": events(),
        # [name, start, end, parent, step] on the perf_counter clock, which
        # perf_anchor ties to the events' wall clock
        "spans": [list(sp) for sp in spans()],
        # the same clock: [kind, name, start, end, parent, step, attrs]
        "startup": {"records": [list(r) for r in _startup],
                    "dropped": _startup_dropped[0]},
        "step_counters": _newest_step_counts(),
        "perf_anchor": {"perf_s": time.perf_counter(), "wall_s": now},
        "threads": _thread_stacks(),
    }
    try:
        # the lockstep divergence table rides every dump: a watchdog
        # hang dump then carries the per-seq collective stream for
        # telemetry --analyze to pinpoint the divergent rank offline
        doc["lockstep"] = _lockstep.snapshot()
    except Exception:
        pass                    # a dying process must still dump
    if extra:
        doc.update(extra)
    return doc


def dump(path=None, reason="manual", extra=None):
    """Write the flight-recorder dump; returns the path (or None when
    the write failed — a dying process must not die twice)."""
    path = path or default_path()
    doc = snapshot(reason=reason, extra=extra)
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True, default=str)
    except OSError:
        return None
    record("dump", path=path, reason=reason)
    return path


# ---------------------------------------------------------------------------
# crash hooks: unhandled exception + SIGTERM/SIGINT
# ---------------------------------------------------------------------------

_hooks_installed = [False]
_signals_installed = [False]
_prev_excepthook = None
_prev_signals = {}
_emergency_callbacks = []       # run best-effort on SIGTERM/SIGINT BEFORE
#                                 the dump (graftarmor emergency snapshot)


def register_emergency(fn):
    """Register a callback the signal handler runs (best-effort, before
    the flight-recorder dump) when the process is being terminated —
    the armor checkpointer hangs its emergency snapshot here.  Errors
    are swallowed: a dying process must still dump and exit."""
    if fn not in _emergency_callbacks:
        _emergency_callbacks.append(fn)
    return fn


def unregister_emergency(fn):
    try:
        _emergency_callbacks.remove(fn)
    except ValueError:
        pass


def _excepthook(exc_type, exc, tb):
    try:
        if enabled() and (_ring or inflight_entries()):
            frames = traceback.format_exception(exc_type, exc, tb)
            dump(reason="exception", extra={"exception": {
                "type": getattr(exc_type, "__name__", str(exc_type)),
                "value": str(exc),
                "traceback": frames[-20:],
            }})
    except Exception:
        pass                    # never mask the original crash
    if _prev_excepthook is not None:
        _prev_excepthook(exc_type, exc, tb)


def _signal_handler(signum, frame):
    for fn in list(_emergency_callbacks):
        try:
            fn(signum)
        except Exception:
            pass                # emergency work is best-effort only
    try:
        if enabled() and (_ring or inflight_entries()):
            dump(reason="signal:%d" % signum)
    except Exception:
        pass
    prev = _prev_signals.get(signum)
    import signal as _signal
    if callable(prev):
        prev(signum, frame)
    else:
        # restore the default disposition and re-raise so the exit code
        # still says "killed by signal"
        _signal.signal(signum, _signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def install_hooks():
    """Chain the excepthook + SIGTERM/SIGINT handlers (idempotent).  A
    signal the process explicitly IGNORES (SIG_IGN — e.g. worker pools
    parking SIGINT) is left alone: chaining over it would turn an
    ignored signal fatal.  A non-main-thread call skips the signal half
    WITHOUT latching it, so a later main-thread call (telemetry re-init,
    ``watchdog.start``) still gets to install the handlers."""
    global _prev_excepthook
    if not _hooks_installed[0]:
        _hooks_installed[0] = True
        _prev_excepthook = sys.excepthook
        sys.excepthook = _excepthook
    if _signals_installed[0]:
        return
    import signal as _signal
    try:
        for signum in (_signal.SIGTERM, _signal.SIGINT):
            if signum not in _prev_signals \
                    and _signal.getsignal(signum) is not _signal.SIG_IGN:
                _prev_signals[signum] = _signal.signal(signum,
                                                       _signal_handler)
        _signals_installed[0] = True
    except ValueError:          # not the main thread: retry later
        pass


# ---------------------------------------------------------------------------
# dump validation + summary (the --blackbox CLI rides these)
# ---------------------------------------------------------------------------

def validate_dump(doc):
    """Schema check of a dump document.  Returns a list of problems
    (empty == valid) — same contract as tracing.validate_chrome_trace."""
    problems = []
    if not isinstance(doc, dict):
        return ["dump is not a JSON object"]
    if doc.get("schema") != SCHEMA:
        problems.append("schema is %r, expected %r"
                        % (doc.get("schema"), SCHEMA))
    for key, typ in (("pid", int), ("reason", str), ("dumped_at", (int, float)),
                     ("ring_size", int), ("events_total", int),
                     ("events", list), ("in_flight", list),
                     ("failures", list), ("workers", dict),
                     ("last_progress", dict)):
        if key not in doc:
            problems.append("missing key %r" % key)
        elif not isinstance(doc[key], typ):
            problems.append("key %r has type %s" % (key,
                                                    type(doc[key]).__name__))
    for i, e in enumerate(doc.get("events") or []):
        if not isinstance(e, dict):
            problems.append("event %d: not an object" % i)
            continue
        if not isinstance(e.get("ts"), (int, float)):
            problems.append("event %d: missing/invalid ts" % i)
        if not isinstance(e.get("kind"), str) or not e.get("kind"):
            problems.append("event %d: missing/invalid kind" % i)
        if not isinstance(e.get("data"), dict):
            problems.append("event %d: missing/invalid data" % i)
    for i, e in enumerate(doc.get("in_flight") or []):
        if not isinstance(e, dict) or "site" not in e or "since" not in e:
            problems.append("in_flight %d: missing site/since" % i)
    if isinstance(doc.get("events"), list) and \
            isinstance(doc.get("events_total"), int) and \
            doc["events_total"] < len(doc["events"]):
        problems.append("events_total < events held (counter went backwards)")
    return problems


def summarize_dump(doc, last=10):
    """Reconstruct the final timeline from a dump: the last flushes,
    steps and collectives, what was in flight, per-worker last-seen."""
    evs = doc.get("events") or []
    t_dump = doc.get("dumped_at", 0.0)

    def tail(kind, n=last):
        rows = [e for e in evs if e.get("kind") == kind]
        return [dict(e["data"], age_s=round(t_dump - e["ts"], 3))
                for e in rows[-n:]]

    counts = {}
    for e in evs:
        counts[e.get("kind", "?")] = counts.get(e.get("kind", "?"), 0) + 1
    workers = {r: dict(v, info_age_s=round(t_dump - v.get("at", t_dump), 3))
               for r, v in (doc.get("workers") or {}).items()}
    return {
        "reason": doc.get("reason"),
        "pid": doc.get("pid"),
        "rank": doc.get("rank"),
        "dumped_at": t_dump,
        "events_total": doc.get("events_total"),
        "events_held": len(evs),
        "counts": counts,
        "last_progress": doc.get("last_progress"),
        "in_flight": doc.get("in_flight") or [],
        "failures": doc.get("failures") or [],
        "last_flushes": tail("engine_flush"),
        "last_steps": tail("step", 5),
        "last_collectives": tail("collective", 5),
        "slow_collectives": tail("slow_collective", 5),
        "watchdog": doc.get("watchdog"),
        "exception": doc.get("exception"),
        "workers": workers,
    }


def selftest():
    """Exercise the full recorder pipeline on a tiny real workload and
    validate the dump schema (the lint smoke tier).  Returns a list of
    problems — empty means pass."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import engine

    prev_override = _enabled_override
    prev_size = os.environ.get("GRAFT_BLACKBOX_SIZE")
    set_enabled(True)
    configure(size=_DEFAULT_SIZE)   # pin: an ambient tiny ring (legal
    #                                 config) must not evict the events
    #                                 this smoke asserts on
    held = None
    path = None
    try:
        a = mx.nd.array(np.ones((4, 4), np.float32))
        for _ in range(10):                      # >= 8 engine_flush events
            with engine.bulk(8):
                ((a * a) + a).asnumpy()
        kv = mx.kv.create("local")
        kv.init("bb", mx.nd.ones((4,)))
        kv.push("bb", mx.nd.ones((4,)))
        out = mx.nd.zeros((4,))
        kv.pull("bb", out=out)
        with step_journal("selftest", batch_size=1):
            from . import tracing
            with tracing.phase_span("update"):
                (a + 1).asnumpy()
        held = _push_inflight("selftest", {"why": "held open across dump"})
        fd, path = tempfile.mkstemp(suffix=".json", prefix="graft_bb_self_")
        os.close(fd)
        dump(path=path, reason="selftest")
        with open(path) as f:
            doc = json.load(f)
        problems = validate_dump(doc)
        flushes = [e for e in doc["events"] if e["kind"] == "engine_flush"]
        if len(flushes) < 8:
            problems.append("expected >= 8 engine_flush events, got %d"
                            % len(flushes))
        if not any(e["kind"] == "collective" for e in doc["events"]):
            problems.append("no collective events (kvstore brackets gone)")
        steps = [e for e in doc["events"] if e["kind"] == "step"]
        if not steps:
            problems.append("no step events (step journal gone)")
        elif "update" not in steps[-1]["data"].get("phases", {}):
            problems.append("step event lost its phase latencies")
        if not any(e.get("site") == "selftest" for e in doc["in_flight"]):
            problems.append("held-open bracket missing from in_flight")
        try:
            summarize_dump(doc)
        except Exception as exc:
            problems.append("summarize_dump raised: %r" % exc)
        return problems
    finally:
        if held is not None:
            _pop_inflight(held)
        if path:
            try:
                os.unlink(path)
            except OSError:
                pass
        set_enabled(prev_override)
        if prev_size is None:
            os.environ.pop("GRAFT_BLACKBOX_SIZE", None)
        else:
            os.environ["GRAFT_BLACKBOX_SIZE"] = prev_size
        configure()
