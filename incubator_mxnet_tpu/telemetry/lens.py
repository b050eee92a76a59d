"""graftlens — per-step wall-time attribution.

graftscope tells you what each *span* cost and graftwatch tells you what
the process was doing when it died — but neither answers the question
that drives every perf decision on this roadmap: **where did this step's
wall time go?**  (EQuARX shows collective cost dominating distributed
step time; the XLA fusion analysis shows device-time attribution is the
prerequisite to every fusion/overlap decision — both need a per-step
decomposition, not a pile of spans.)

The lens decomposes every training step's wall clock into six components
that sum EXACTLY to the step's wall time (the conservation contract,
enforced by tests/test_lens.py):

* ``data_wait``         — blocked in ``DataIter.next()`` / ``DataLoader``
                          waiting for a batch,
* ``forward``           — inside ``autograd.record()`` scopes and/or the
                          ``fwd`` phase span (Module),
* ``backward_compute``  — the ``bwd`` phase span (``autograd.backward``),
* ``exposed_comm``      — host time *visibly* spent on communication:
                          sync kvstore collective brackets,
                          ``ReduceHandle.wait`` blocks, and the trainer's
                          ``kvstore`` phase (reduce packing + waits),
* ``optimizer_update``  — the ``update`` phase span,
* ``host_gap``          — everything else (python glue, metric updates,
                          logging, user code between batches).

A *step window* runs from the end of the previous ``Trainer.step`` /
``Module.update`` journal to the end of the current one, so the data
fetch and forward of batch N land on step N — the whole loop is
attributed, not just the optimizer call.  Sources report timestamped
intervals; at step end the window is swept once and every elementary
slice is attributed to the highest-priority covering category
(``exposed_comm > optimizer_update > backward_compute > forward >
data_wait``), so overlapping instrumentation (a collective bracket
inside the kvstore phase, a record scope around a fwd span) can never
double-count.  ``host_gap`` is the residual — the six components sum to
the window by construction.

Separately from the swept component, every step carries
``comm_blocked_s`` (host time blocked in collectives) and
``comm_inflight_s`` (summed issue→wait-return wall time of the same
collectives — an upper bound on issue→ready, the same convention as
graftlap's ``graft_trainer_overlap_ratio``).  On the serial reduce path
the two are EQUAL by construction; under graftlap overlap
``comm_blocked_s < comm_inflight_s`` — the difference bounds the
communication hidden under backward.

Steps live in an in-process ring of the last ``GRAFT_LENS_RING``
(default 64) records, are published as ``graft_lens_*``
gauges/histograms, are folded into the graftwatch step journal (the
``lens`` field of ``step`` ring events), and — with
``GRAFT_STEP_REPORT=N`` — print a one-line attribution report to stderr
every N steps.  ``python -m incubator_mxnet_tpu.telemetry --steps``
renders the ring; ``--analyze`` (telemetry/aggregate.py) merges
per-rank artifacts into one cross-rank trace with straggler analytics.

Master switch: ``GRAFT_LENS`` (default on; ``set_enabled`` overrides).
The hot path per source event is one ``perf_counter`` + one list append;
``lens_overhead_pct`` in ``bench_eager.py`` keeps the cost under the 2%
bar.

graftpulse (PR 12) — the ASYNC device-time ledger: PR 11's device
ledger filled only under profiler sync mode (every dispatch blocked
until ready, so dispatch→return WAS device latency) and serving
dispatches; ordinary production async train loops — the whole point of
the engine's deferred dispatch — left it empty.  Now every engine flush
and eager op dispatch that is NOT sync-booked hands its result arrays
to a 1-thread REAPER (``device_async``): the reaper calls
``jax.block_until_ready`` OFF the caller thread and books
dispatch→device-done into the issuing thread's window.  Bookings merge
through a per-window watermark (the union of spans, never their sum),
so concurrent in-flight dispatches cannot overcount and sync-mode
bookings plus callbacks can never double-book the same span.  The
ledger keeps its exact-sum contract — ``device_busy_s + device_idle_s
== wall`` per window, busy clamped at wall — and ``busy`` is an upper
bound on true device time when the reaper queue backs up (a span's
"done" is observed at reap time).  Switch: ``GRAFT_PULSE`` (default
on; ``set_pulse`` overrides); ``pulse_overhead_pct`` in bench_eager.py
keeps the enqueue cost under the 2% bar.  For runs where callbacks are
unavailable, ``telemetry --ingest-xla PATH`` (telemetry/aggregate.py)
rebuilds the same per-step ledger offline from a chrome trace.

graftpulse — the MEMORY timeline: the step journal's single
device-mem highwater becomes a per-site allocation watermark ledger.
``mem_sample(site)`` reads the device allocator counters (cheap;
auto-disabled after the first sample on backends that report none —
set a sampler explicitly to override) at engine flush boundaries and
per fused/duplex bucket, feeding ``graft_mem_peak_bytes{site}`` /
``graft_mem_bytes_in_use``, a global timeline ring
(``mem_timeline()``/``mem_summary()``), and a per-step ``mem`` field
(peak + per-site peaks within the window) — the signal the ROADMAP's
liveness-aware memory planner will plan against.  Switch:
``GRAFT_MEM_TIMELINE`` (default on).

``add_observer(fn)`` registers a step observer called with every
finalized record — telemetry/autotune.py's controller closes the loop
from these signals back into DataLoader workers / bucket bytes /
bucket order.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque

from . import metrics as _metrics

__all__ = ["enabled", "set_enabled", "ring_size", "configure", "interval",
           "phase", "io_wait", "comm", "device", "step_end", "current_step",
           "steps", "summary", "compact", "reset", "COMPONENTS", "ABBREV",
           "pulse_enabled", "set_pulse", "pulse_active", "device_async",
           "pulse_drain", "pulse_stats", "mem_enabled", "set_mem_sampler",
           "mem_sample", "mem_timeline", "mem_summary", "live_arrays_sampler",
           "add_observer", "remove_observer"]

COMPONENTS = ("data_wait", "forward", "backward_compute", "exposed_comm",
              "optimizer_update", "host_gap")

# sweep priority, highest first: a slice covered by several categories is
# attributed to the first one here (host_gap is the residual, never swept)
_PRIORITY = ("exposed_comm", "optimizer_update", "backward_compute",
             "forward", "data_wait")
_PRIORITY_INDEX = {c: i for i, c in enumerate(_PRIORITY)}

# phase-span name -> lens category (tracing._PhaseSpan feeds these)
_PHASE_CATEGORY = {"kvstore": "exposed_comm", "update": "optimizer_update",
                   "bwd": "backward_compute", "fwd": "forward"}

_DEFAULT_RING = 64

_enabled_override = None
_generation = [0]       # bumped on every toggle: step windows spanning a
#                         disabled period are dropped, not booked as one
#                         giant host_gap "ghost step"


def set_enabled(flag):
    """Force the lens on/off (None = defer to GRAFT_LENS).  Toggling
    invalidates every thread's open window — the first step after a
    re-enable starts a fresh window instead of billing the whole
    disabled period to host_gap."""
    global _enabled_override
    _enabled_override = flag
    _generation[0] += 1


_OFF_VALUES = ("0", "false", "no", "off")
_lens_env_memo = ["\x00", True]     # raw value -> parsed (both flags sit
_pulse_env_memo = ["\x00", True]    # on EVERY eager dispatch: memoize the
#                                     strip/lower/member parse, keyed on
#                                     the raw string so setting the env
#                                     var mid-process still takes effect)


def enabled():
    if _enabled_override is not None:
        return bool(_enabled_override)
    raw = os.environ.get("GRAFT_LENS", "1")
    if raw != _lens_env_memo[0]:
        _lens_env_memo[1] = raw.strip().lower() not in _OFF_VALUES
        _lens_env_memo[0] = raw
    return _lens_env_memo[1]


def ring_size():
    try:
        n = int(os.environ.get("GRAFT_LENS_RING", str(_DEFAULT_RING)))
    except ValueError:
        return _DEFAULT_RING
    return max(n, 4)


_ring = deque(maxlen=ring_size())


def configure(size=None):
    """Re-size the step ring (keeps the newest records)."""
    global _ring
    if size is not None:
        os.environ["GRAFT_LENS_RING"] = str(int(size))
    _ring = deque(_ring, maxlen=ring_size())


class _ThreadState(object):
    """Per-thread step window: open intervals + counters.  Training loops
    are single-threaded; a second stepping thread gets its own windows
    (records from all threads share the ring)."""

    __slots__ = ("intervals", "prev_end", "completed", "io_n", "coll_n",
                 "comm_blocked", "comm_inflight", "device_s", "device_n",
                 "device_first", "device_mark", "mem_peak", "mem_in_use",
                 "mem_alloc_peak", "mem_sites", "gen", "__weakref__")

    def __init__(self):
        self.intervals = []      # (category, t0, t1) in perf_counter secs
        self.prev_end = None     # previous step's end (window start)
        self.completed = 0       # steps finalized on this thread
        self.io_n = 0
        self.coll_n = 0
        self.comm_blocked = 0.0
        self.comm_inflight = 0.0
        self.device_s = 0.0      # device-busy ledger (sync-mode flushes,
        self.device_n = 0        #  serving batch dispatches, and the
        #                          async pulse reaper's done-callbacks)
        self.device_first = None  # earliest device span start (the first
        #                          window on a device-only thread starts
        #                          here, not at step_end)
        self.device_mark = None  # union watermark: end of the last booked
        #                          device span — overlapping spans (async
        #                          in-flight pipelining, sync+callback
        #                          double delivery) book only their part
        #                          past the mark, so busy is the UNION of
        #                          spans, never their sum
        self.mem_peak = 0        # window-local live-bytes watermark
        self.mem_in_use = 0
        self.mem_alloc_peak = 0  # allocator's lifetime peak as sampled
        self.mem_sites = {}      # site -> live-bytes mark in the window
        self.gen = _generation[0]

    def reset_window(self):
        self.intervals = []
        self.prev_end = None
        self.io_n = self.coll_n = 0
        self.comm_blocked = self.comm_inflight = 0.0
        self.device_s = 0.0
        self.device_n = 0
        self.device_first = None
        # device_mark survives: it is an absolute perf_counter instant
        # (span-union bookkeeping), not window state
        self.mem_peak = 0
        self.mem_in_use = 0
        self.mem_alloc_peak = 0
        self.mem_sites = {}
        self.gen = _generation[0]


_tls = threading.local()


def _state():
    st = getattr(_tls, "lens", None)
    if st is None:
        st = _tls.lens = _ThreadState()
    elif st.gen != _generation[0]:
        st.reset_window()       # a toggle happened: the open window is
        #                         unreliable, start fresh (step ids keep
        #                         counting)
    return st


def current_step():
    """Id of the calling thread's IN-PROGRESS step window (the one the
    next ``step_end`` will finalize), or None when the lens is off or
    the thread has produced no lens activity yet.  graftwatch stamps it
    onto every flight-recorder event and tracing onto flush spans /
    collective spans — the key the cross-rank aggregator joins on."""
    if not enabled():
        return None
    st = getattr(_tls, "lens", None)
    if st is None:
        return None
    return st.completed + 1


# A loop that never crosses a step boundary (serving / evaluation — io
# and forward hooks fire, step_end never does) must not grow the open
# window without bound.  Past the cap the OLDEST intervals are dropped:
# if a step eventually closes, the early slices degrade into host_gap
# (conservation still holds); a window that large is degenerate anyway.
_MAX_OPEN_INTERVALS = 8192


def _append_interval(st, item):
    iv = st.intervals
    if len(iv) >= _MAX_OPEN_INTERVALS:
        del iv[:_MAX_OPEN_INTERVALS // 2]
    iv.append(item)


def interval(category, t0, t1):
    """Report one attributed interval (perf_counter seconds).  THE hot
    path: an env lookup, a getattr and a list append."""
    if t1 <= t0 or not enabled():
        return
    _append_interval(_state(), (category, t0, t1))


def phase(name, t0, t1):
    """One closed phase span (tracing._PhaseSpan)."""
    cat = _PHASE_CATEGORY.get(name)
    if cat is not None:
        interval(cat, t0, t1)


def io_wait(t0, t1):
    """Host blocked waiting for a data batch (io/DataLoader)."""
    if t1 <= t0 or not enabled():
        return
    st = _state()
    st.io_n += 1
    _append_interval(st, ("data_wait", t0, t1))


def comm(t0, t1, inflight=None):
    """Host blocked in one collective.  ``inflight`` is the collective's
    issue→wait-return wall time when it differs from the blocked span
    (graftlap async reduces: issued mid-backward, waited in step; an
    upper bound on issue→ready when waits queue behind each other) —
    sync collectives leave it None and the two book equal."""
    if not enabled():
        return
    st = _state()
    st.coll_n += 1
    blocked = max(t1 - t0, 0.0)
    st.comm_blocked += blocked
    st.comm_inflight += blocked if inflight is None \
        else max(float(inflight), 0.0)
    if blocked > 0.0:
        _append_interval(st, ("exposed_comm", t0, t1))


# One lock guards every thread-state's device/mem ledger fields: the
# pulse reaper books into FOREIGN thread states (the issuing thread's),
# and step_end reads-and-resets the same fields.  Taken once per flush /
# step / sample — never per op record — so contention is negligible.
_device_lock = threading.Lock()


def _book_device_locked(st, t0, t1):
    """Merge one device span into ``st``'s ledger (call under
    ``_device_lock``): only the part past the union watermark books, so
    overlapping spans — pipelined async dispatches, a sync booking plus
    a late callback for the same results — count once."""
    if st.device_mark is not None and t0 < st.device_mark:
        t0 = st.device_mark
    if t1 <= t0:
        return
    st.device_s += t1 - t0
    st.device_n += 1
    st.device_mark = t1
    if st.device_first is None:
        st.device_first = t0


def device(t0, t1):
    """Book one DEVICE-busy span into the window's device ledger
    (ROADMAP device-time lens carry-forward, PR 8).  Sources: engine
    flushes and eager op dispatches under ``profiler.sync`` (both block
    until ready, so dispatch→ready IS device latency), the serving
    runtime's batch dispatch (issue → ``block_until_ready``), and —
    PR 12 — the async pulse reaper's done-callbacks (``device_async``).
    Unlike the six host components the device ledger is a PARALLEL
    decomposition: ``device_busy_s`` vs ``device_idle_s = wall - busy``
    (its own exact-sum contract), so comm/compute overlap is measurable
    on the device, not just as host wall.  Spans merge through a
    watermark (union, not sum) so no source pair can double-book."""
    if t1 <= t0 or not enabled():
        return
    st = _state()
    with _device_lock:
        _book_device_locked(st, t0, t1)


# ---------------------------------------------------------------------------
# graftpulse: the async device-time reaper (GRAFT_PULSE)
# ---------------------------------------------------------------------------

_pulse_override = None


def set_pulse(flag):
    """Force the async device ledger on/off (None = defer to
    GRAFT_PULSE)."""
    global _pulse_override
    _pulse_override = flag


def pulse_enabled():
    if _pulse_override is not None:
        return bool(_pulse_override)
    raw = os.environ.get("GRAFT_PULSE", "1")
    if raw != _pulse_env_memo[0]:
        _pulse_env_memo[1] = raw.strip().lower() not in _OFF_VALUES
        _pulse_env_memo[0] = raw
    return _pulse_env_memo[1]


def pulse_active():
    """The dispatch-site gate: both the lens and the pulse ledger on."""
    return pulse_enabled() and enabled()


_pulse_queue = deque()          # (state, gen, t_dispatch, values)
_pulse_wake = threading.Event()
_pulse_thread = [None]
_pulse_idle = threading.Condition()
_pulse_busy = [False]           # reaper mid-item (toggled under _idle)
_pulse_counts = {"enqueued": 0, "booked": 0, "dropped": 0}
_PULSE_WAKE_INTERVAL_S = 0.02   # min gap between caller-side wakes
#                                 (measured knee: shorter gaps pay one
#                                 GIL handoff per wake, longer ones pile
#                                 the whole backlog onto the drain)
_pulse_last_wake = [0.0]


def _reaper_loop():
    import jax
    while True:
        items = None
        with _pulse_idle:
            # batch-pop-and-mark-busy is atomic vs pulse_drain: the
            # queue can never look empty while items are mid-reap
            if _pulse_queue:
                items = [_pulse_queue.popleft()
                         for _ in range(len(_pulse_queue))]
                _pulse_busy[0] = True
            else:
                _pulse_busy[0] = False
                _pulse_idle.notify_all()
        if not items:
            _pulse_wake.wait(0.2)
            _pulse_wake.clear()
            continue
        # Group the batch per issuing thread-state: one thread's
        # dispatches execute device-ordered, so the LAST result's
        # readiness covers its whole group (one leaf-walk instead of
        # N — per-item ready-waits and bookings made the reaper a
        # GIL-contending metronome, the dominant ledger cost).  All
        # group spans share the batch t1, so their union is exactly
        # min(t0) -> t1: ONE merged booking per group, identical to
        # what N per-item bookings would have produced.
        groups = {}
        for it in items:
            groups.setdefault(id(it[0]), []).append(it)
        good_groups = []
        for its in groups.values():
            try:
                jax.block_until_ready(its[-1][3])
                good_groups.append(its)
            except Exception:
                # salvage per item: one failed dispatch (it surfaces on
                # the caller's read path) must not drop the whole group
                ok = []
                for it in its:
                    try:
                        jax.block_until_ready(it[3])
                        ok.append(it)
                    except Exception:
                        _pulse_counts["dropped"] += 1
                if ok:
                    good_groups.append(ok)
        t1 = time.perf_counter()
        with _device_lock:
            lens_on = enabled()
            for its in good_groups:
                st = its[0][0]
                live = [it for it in its if it[0].gen == it[1]] \
                    if lens_on else []
                _pulse_counts["dropped"] += len(its) - len(live)
                #                             (lens toggled mid-flight:
                #                              those windows are gone)
                if not live:
                    continue
                before = st.device_n
                _book_device_locked(st, min(it[2] for it in live), t1)
                if st.device_n > before:
                    # spans count real dispatches, not merged bookings
                    st.device_n += len(live) - 1
                _pulse_counts["booked"] += len(live)
        # drop every reference to the batch's result arrays BEFORE the
        # next park: locals surviving into the 0.2s idle wait would pin
        # dead buffers and make live-arrays memory accounting flicker
        st = it = its = ok = live = items = groups = good_groups = None


_pulse_spawn_lock = threading.Lock()


def _ensure_reaper():
    t = _pulse_thread[0]
    if t is not None and t.is_alive():
        return      # the hot-path fast exit: no lock once one is live
    with _pulse_spawn_lock:
        # re-check under the lock: two threads' FIRST concurrent
        # enqueues both see no live reaper — unserialized, each would
        # spawn one, and two loops fighting over _pulse_busy let
        # pulse_drain return while the loser still holds unbooked spans
        t = _pulse_thread[0]
        if t is not None and t.is_alive():
            return
        t = threading.Thread(target=_reaper_loop,
                             name="graft-pulse-reaper", daemon=True)
        _pulse_thread[0] = t
        t.start()


def device_async(values, t_dispatch):
    """Register a done-callback for one async dispatch's result arrays:
    the 1-thread reaper blocks-until-ready OFF the caller thread and
    books dispatch→device-done into THIS thread's window (captured
    here).  The caller-side cost is one deque append + an event set —
    lock-free, never a wait (the GIL orders the append; the counters
    are stats, not synchronization).  Holding ``values`` until reaped
    delays their buffers' release by the reap latency; the reaper runs
    on a ~``_PULSE_WAKE_INTERVAL_S`` cadence under traffic, so the
    overhang — and the booking delay — is up to one wake interval.
    Windows shorter than the cadence may therefore batch several
    steps' device spans into one window (each still conserving);
    ``pulse_drain()`` forces settlement where freshness matters."""
    if values is None or not pulse_active():
        return
    st = _state()
    _pulse_counts["enqueued"] += 1
    _pulse_queue.append((st, st.gen, t_dispatch, values))
    _ensure_reaper()    # full is_alive check: a fork's child inherits a
    #                     non-None dead thread — skipping the check there
    #                     would pin every result buffer ever enqueued
    if t_dispatch - _pulse_last_wake[0] > _PULSE_WAKE_INTERVAL_S \
            and not _pulse_wake.is_set():
        # RATE-LIMITED wake: waking the reaper per dispatch made it a
        # GIL-contending metronome (one thread handoff per op — the
        # dominant ledger cost, measured); dispatches between wakes
        # coalesce into one batch pop.  The 0.2s reaper poll and
        # pulse_drain's explicit kick are the backstop, so a skipped
        # wake delays a booking, never loses it.
        _pulse_last_wake[0] = t_dispatch
        _pulse_wake.set()


def pulse_drain(timeout=10.0):
    """Block until every enqueued callback has been reaped (tests, step
    benchmarks, end-of-run reports).  Returns True when drained."""
    deadline = time.monotonic() + timeout
    if _pulse_queue or _pulse_busy[0]:
        # full check: revives a dead reaper too.  The busy flag alone
        # can be latched True with an EMPTY queue — a fork mid-batch
        # gives the child a dead thread and no live reaper to clear it
        # — and only a fresh reaper's first empty pop resets it; gating
        # on the queue alone would burn the whole timeout
        _ensure_reaper()
    with _pulse_idle:
        while _pulse_queue or _pulse_busy[0]:
            _pulse_wake.set()
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            _pulse_idle.wait(min(remaining, 0.05))
    return True


def pulse_stats():
    """{"enqueued", "booked", "dropped", "pending"} — reaper counters
    (tests; the no-double-booking contract asserts enqueued == 0 under
    sync mode)."""
    return dict(_pulse_counts,
                pending=len(_pulse_queue) + (1 if _pulse_busy[0] else 0))


def reset_pulse_stats():
    for k in _pulse_counts:
        _pulse_counts[k] = 0


# ---------------------------------------------------------------------------
# graftpulse: the per-site memory timeline (GRAFT_MEM_TIMELINE)
# ---------------------------------------------------------------------------

_MEM_RING_SIZE = 512
_mem_ring = deque(maxlen=_MEM_RING_SIZE)    # {"t","site","in_use","peak"}
_mem_sampler = [None]       # explicit override (tests / --mem demo)
_mem_auto_dead = [False]    # default sampler found no allocator stats


_mem_env_memo = ["\x00", True]  # same raw-keyed memo as GRAFT_LENS/_PULSE:
#                                 this flag sits on every flush boundary
#                                 and every fused/duplex bucket apply


def mem_enabled():
    raw = os.environ.get("GRAFT_MEM_TIMELINE", "1")
    if raw != _mem_env_memo[0]:
        _mem_env_memo[1] = raw.strip().lower() not in _OFF_VALUES
        _mem_env_memo[0] = raw
    return _mem_env_memo[1]


def set_mem_sampler(fn):
    """Install a sampler ``fn() -> (bytes_in_use, peak_bytes) | None``
    (None = revert to the allocator-counter default).  Re-arms the
    auto-disable latch."""
    _mem_sampler[0] = fn
    _mem_auto_dead[0] = False


def _allocator_sampler():
    """Allocator counters summed over local devices — the cheap default
    (real TPU/GPU runtimes).  Returns None when no device reports any
    (host CPU): the caller then latches the ledger off, so backends
    without counters pay one probe total, not one per flush."""
    try:
        import jax
        in_use = peak = 0
        found = False
        for d in jax.local_devices():
            s = d.memory_stats() or {}
            if s:
                in_use += int(s.get("bytes_in_use", 0))
                peak += int(s.get("peak_bytes_in_use", 0))
                found = True
        return (in_use, peak) if found else None
    except Exception:
        return None


def live_arrays_sampler():
    """Exact live bytes via ``profiler.device_memory()``'s live-arrays
    walk — too slow for per-flush production sampling, right for the
    ``--mem`` CLI demo and tests on allocator-less backends."""
    from .. import profiler as _profiler
    ms = _profiler.device_memory()
    return (sum(m["bytes_in_use"] for m in ms),
            sum(m["peak_bytes_in_use"] for m in ms))


def mem_sample(site):
    """Sample the device-memory watermark at one attribution site (an
    engine flush boundary, a fused/duplex bucket, a serving batch) into
    the timeline ring, the calling thread's step window and the
    ``graft_mem_peak_bytes{site}`` gauges."""
    if not enabled() or not mem_enabled():
        return None
    fn = _mem_sampler[0]
    if fn is None:
        if _mem_auto_dead[0]:
            return None
        fn = _allocator_sampler
    sample = None
    try:
        sample = fn()
    except Exception:
        sample = None
    if sample is None:
        if _mem_sampler[0] is None:
            _mem_auto_dead[0] = True
        return None
    in_use, peak = int(sample[0]), int(sample[1])
    peak = max(peak, in_use)
    st = _state()
    with _device_lock:
        st.mem_in_use = in_use
        # attribution is by LIVE bytes at the site boundary: the
        # allocator's peak counter is a process-lifetime high-water mark
        # (never resets), so keying sites off it would tie every site to
        # one constant once the global peak is first reached — in_use is
        # what differentiates which bucket/flush drives the footprint.
        # The raw allocator peak rides along separately (alloc_peak): it
        # bounds spikes BETWEEN samples that in_use snapshots miss
        st.mem_peak = max(st.mem_peak, in_use)
        st.mem_alloc_peak = max(st.mem_alloc_peak, peak)
        site_mark = max(st.mem_sites.get(site, 0), in_use)
        st.mem_sites[site] = site_mark
    _mem_ring.append({"t": time.time(), "site": site,
                      "in_use": in_use, "peak": peak})
    _metrics.mem_sample(site, in_use, site_mark)
    return in_use, peak


def mem_timeline():
    """The memory timeline ring, oldest first (copies)."""
    return [dict(r) for r in list(_mem_ring)]


def mem_summary():
    """Per-site aggregation over the ring: samples, live-bytes watermark
    (what differentiates sites — the allocator peak is lifetime-
    cumulative and ties them), raw allocator peak, last in-use."""
    out = {}
    for r in list(_mem_ring):
        s = out.setdefault(r["site"], {"samples": 0, "peak_bytes": 0,
                                       "alloc_peak_bytes": 0,
                                       "last_in_use": 0})
        s["samples"] += 1
        s["peak_bytes"] = max(s["peak_bytes"], r["in_use"])
        s["alloc_peak_bytes"] = max(s["alloc_peak_bytes"], r["peak"])
        s["last_in_use"] = r["in_use"]
    return out


def reset_mem():
    _mem_ring.clear()
    _mem_auto_dead[0] = False


# ---------------------------------------------------------------------------
# step observers (the autotuner's feed)
# ---------------------------------------------------------------------------

_observers = []


def add_observer(fn):
    """Register ``fn(record)`` to run after every finalized step window
    (telemetry/autotune.py's controller).  Idempotent."""
    if fn not in _observers:
        _observers.append(fn)


def remove_observer(fn):
    try:
        _observers.remove(fn)
    except ValueError:
        pass


def _notify_observers(rec):
    for fn in list(_observers):
        try:
            fn(rec)
        except Exception:
            import logging
            logging.getLogger("graftlens").exception(
                "lens step observer %r raised", fn)


def _attribute(intervals, w0, w1):
    """Sweep the window once: every elementary slice goes to the
    highest-priority category covering it.  Returns (per-category
    seconds, total attributed seconds) — total <= w1 - w0 always, so
    the residual (host_gap) is non-negative by construction."""
    comp = {c: 0.0 for c in _PRIORITY}
    marks = []
    for cat, t0, t1 in intervals:
        t0 = max(t0, w0)
        t1 = min(t1, w1)
        if t1 <= t0:
            continue
        pr = _PRIORITY_INDEX[cat]
        marks.append((t0, 1, pr))
        marks.append((t1, 0, pr))    # closes sort before opens at ties
    if not marks:
        return comp, 0.0
    marks.sort()
    active = [0] * len(_PRIORITY)
    last_t = None
    total = 0.0
    for t, kind, pr in marks:
        if last_t is not None and t > last_t and any(active):
            for i, n in enumerate(active):
                if n > 0:
                    d = t - last_t
                    comp[_PRIORITY[i]] += d
                    total += d
                    break
        active[pr] += 1 if kind == 1 else -1
        last_t = t
    return comp, total


def step_end(origin="step", extra=None):
    """Finalize the calling thread's step window (called from the
    graftwatch step journal).  Returns the ring record (None when the
    lens is off)."""
    if not enabled():
        return None
    st = _state()
    now = time.perf_counter()
    w0 = st.prev_end
    # device/mem ledger fields are shared with the pulse reaper thread:
    # snapshot-and-reset them under the lock so a callback landing mid-
    # finalize books entirely into this window or entirely into the next
    with _device_lock:
        device_s, device_n = st.device_s, st.device_n
        device_first = st.device_first
        mem_peak, mem_in_use = st.mem_peak, st.mem_in_use
        mem_alloc_peak = st.mem_alloc_peak
        mem_sites = st.mem_sites
        st.device_s = 0.0
        st.device_n = 0
        st.device_first = None
        st.mem_peak = 0
        st.mem_in_use = 0
        st.mem_alloc_peak = 0
        st.mem_sites = {}
    if w0 is None:      # first step: window starts at the first activity
        w0 = min((t0 for _c, t0, _t1 in st.intervals), default=now)
        if device_first is not None:
            w0 = min(w0, device_first)
    wall = max(now - w0, 0.0)
    comp, attributed = _attribute(st.intervals, w0, now)
    comp["host_gap"] = max(wall - attributed, 0.0)
    st.completed += 1
    rec = {
        "step": st.completed,
        "origin": origin,
        "ended_at": time.time(),
        "wall_s": wall,
        "components": comp,
        "comm_blocked_s": st.comm_blocked,
        "comm_inflight_s": st.comm_inflight,
        "collectives": st.coll_n,
        "io_waits": st.io_n,
        "thread": threading.current_thread().name,
    }
    if device_n:
        # device ledger: busy + idle == wall EXACTLY (idle is wall - busy
        # by construction; busy clamps at wall — a span straddling the
        # window boundary books whole into the window it completed in)
        busy = min(device_s, wall)
        rec["device"] = {"busy_s": busy, "idle_s": wall - busy,
                         "spans": device_n}
    if mem_sites:
        # peak_bytes is the window's LIVE-bytes watermark (== max over
        # sites by construction — the attribution conservation); the raw
        # allocator peak (a lifetime high-water mark) rides along for
        # spikes between samples
        rec["mem"] = {"peak_bytes": mem_peak, "in_use_bytes": mem_in_use,
                      "alloc_peak_bytes": mem_alloc_peak,
                      "sites": mem_sites}
    if extra:
        rec.update(extra)
    st.intervals = []
    st.prev_end = now
    st.io_n = st.coll_n = 0
    st.comm_blocked = st.comm_inflight = 0.0
    _ring.append(rec)
    _metrics.lens_step(rec)
    _maybe_report(rec)
    _notify_observers(rec)
    return rec


def compact(rec):
    """Millisecond-rounded view of one record — what the graftwatch step
    journal embeds under its ``lens`` field."""
    out = {"wall_ms": round(rec["wall_s"] * 1e3, 3)}
    for c in COMPONENTS:
        out[c + "_ms"] = round(rec["components"][c] * 1e3, 3)
    out["comm_blocked_ms"] = round(rec["comm_blocked_s"] * 1e3, 3)
    out["comm_inflight_ms"] = round(rec["comm_inflight_s"] * 1e3, 3)
    if "device" in rec:
        out["device_busy_ms"] = round(rec["device"]["busy_s"] * 1e3, 3)
    if "mem" in rec:
        out["mem_peak_bytes"] = rec["mem"]["peak_bytes"]
    if rec.get("compiled"):
        # graftstep: a whole-step compiled window — one donated XLA
        # program booked as a single device span; flagged so step rings
        # distinguish compiled from bucketed-eager windows at a glance
        out["compiled"] = True
    return out


def steps():
    """The ring, oldest first (copies)."""
    return [dict(r, components=dict(r["components"])) for r in list(_ring)]


def reset():
    """Drop the ring AND the calling thread's open window (tests)."""
    _ring.clear()
    _mem_ring.clear()
    _tls.lens = None


def summary(records=None):
    """Aggregate view over the ring (or an explicit record list)."""
    recs = list(_ring) if records is None else list(records)
    if not recs:
        return {"steps": 0}
    wall = sum(r["wall_s"] for r in recs)
    comp = {c: sum(r["components"][c] for r in recs) for c in COMPONENTS}
    return {
        "steps": len(recs),
        "wall_s": wall,
        "mean_step_ms": round(wall / len(recs) * 1e3, 3),
        "components_s": {c: round(v, 6) for c, v in comp.items()},
        "fractions": {c: round(comp[c] / wall, 4) if wall > 0 else 0.0
                      for c in COMPONENTS},
        "comm_blocked_s": round(sum(r["comm_blocked_s"] for r in recs), 6),
        "comm_inflight_s": round(sum(r["comm_inflight_s"] for r in recs), 6),
    }


# ---------------------------------------------------------------------------
# GRAFT_STEP_REPORT=N: the live attribution line
# ---------------------------------------------------------------------------

def _report_every():
    try:
        return int(os.environ.get("GRAFT_STEP_REPORT", "0"))
    except ValueError:
        return 0


ABBREV = (("data_wait", "data"), ("forward", "fwd"),
           ("backward_compute", "bwd"), ("exposed_comm", "comm"),
           ("optimizer_update", "upd"), ("host_gap", "gap"))


def format_step(rec):
    parts = " ".join("%s %.2f" % (short, rec["components"][c] * 1e3)
                     for c, short in ABBREV)
    line = "graftlens step %d (%s): %.2fms | %s [ms]" % (
        rec["step"], rec["origin"], rec["wall_s"] * 1e3, parts)
    if rec["comm_inflight_s"] > rec["comm_blocked_s"]:
        line += " | comm exposed %.2f / in-flight %.2f ms" % (
            rec["comm_blocked_s"] * 1e3, rec["comm_inflight_s"] * 1e3)
    return line


def _maybe_report(rec):
    n = _report_every()
    if n <= 0 or rec["step"] % n:
        return
    lines = [format_step(rec)]
    agg = summary(list(_ring)[-n:])
    if agg.get("steps", 0) > 1:
        fr = agg["fractions"]
        lines.append(
            "graftlens last %d steps: mean %.2fms | %s" % (
                agg["steps"], agg["mean_step_ms"],
                " ".join("%s %d%%" % (short, round(fr[c] * 100))
                         for c, short in ABBREV)))
    sys.stderr.write("\n".join(lines) + "\n")
