"""graftwatch hang watchdog.

A background thread that times the flight recorder's in-flight brackets
(engine flushes, dist collectives, training phases/steps — see
:mod:`~incubator_mxnet_tpu.telemetry.blackbox`).  When a bracket stays
open longer than ``GRAFT_WATCHDOG_TIMEOUT`` seconds of wall clock, the
watchdog declares a hang and:

1. writes the flight-recorder dump (``reason="watchdog"``) naming the
   stuck bracket — for a stalled flush that is the segment id, cause and
   node count; for a stalled collective the path/keys/bytes/rank,
2. dumps every thread's stack via :mod:`faulthandler` to stderr (the
   crash-safe spelling; the JSON dump also embeds formatted stacks),
3. bumps ``graft_watchdog_trips_total`` and, when
   ``GRAFT_WATCHDOG_ABORT`` is set, kills the process with exit code 134
   so a supervisor restarts it instead of letting it hang forever.

The watchdog is OFF unless ``GRAFT_WATCHDOG_TIMEOUT`` is set to a
positive number of seconds (``maybe_start`` runs at telemetry import),
or :func:`start` is called explicitly.  Each open bracket trips at most
once; progress (any bracket closing) rearms the idle gauges.
"""
from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time

from . import blackbox as _blackbox
from . import metrics as _metrics

__all__ = ["Watchdog", "start", "stop", "active", "maybe_start",
           "configured_timeout", "register_dead_nodes_provider"]

_ABORT_EXIT_CODE = 134          # 128 + SIGABRT, the classic watchdog code


def configured_timeout():
    """GRAFT_WATCHDOG_TIMEOUT in seconds, or None when unset/invalid."""
    raw = os.environ.get("GRAFT_WATCHDOG_TIMEOUT", "").strip()
    if not raw:
        return None
    try:
        t = float(raw)
    except ValueError:
        return None
    return t if t > 0 else None


def _abort_configured():
    return os.environ.get("GRAFT_WATCHDOG_ABORT", "").strip().lower() \
        in ("1", "true", "yes", "on")


def _escalate_configured():
    """GRAFT_WATCHDOG_ESCALATE: on trip, raise a typed error INTO the
    thread blocked on the stuck bracket (graftarmor fail-fast) instead
    of only dumping.  The raise lands at the next Python bytecode the
    thread executes — socket waits and lock waits surface it; a thread
    parked inside a C-level XLA collective does not return to bytecode,
    so for those GRAFT_WATCHDOG_ABORT remains the only hard stop
    (docs/robustness.md)."""
    return os.environ.get("GRAFT_WATCHDOG_ESCALATE", "").strip().lower() \
        in ("1", "true", "yes", "on")


# -- graftarmor: dead-rank attribution --------------------------------------

_dead_provider = [None]


def register_dead_nodes_provider(fn):
    """Install a callable returning the currently-dead worker ranks
    (DistKVStore registers its PS heartbeat table).  Queried at trip
    time only, in a sacrificial daemon thread — the provider may need a
    client lock HELD BY the very RPC that hung, so the watchdog must
    never call it synchronously."""
    _dead_provider[0] = fn


def _query_dead_ranks(timeout=2.0):
    fn = _dead_provider[0]
    if fn is None:
        return []
    box = []

    def _run():
        try:
            box.append(list(fn()))
        except Exception:
            pass

    t = threading.Thread(target=_run, daemon=True,
                         name="graftwatch-deadnodes")
    t.start()
    t.join(timeout)
    return box[0] if box else []


class Watchdog(threading.Thread):
    """The poller.  ``interval`` defaults to timeout/4 clamped to
    [50ms, 1s] so a trip lands within ~1.25x the configured timeout."""

    def __init__(self, timeout, interval=None, abort=None, path=None):
        super().__init__(name="graftwatch-watchdog", daemon=True)
        self.timeout = float(timeout)
        self.interval = interval if interval is not None \
            else min(max(self.timeout / 4.0, 0.05), 1.0)
        self.abort = _abort_configured() if abort is None else bool(abort)
        self.path = path
        self.trips = 0
        self._stop_evt = threading.Event()

    def run(self):
        while not self._stop_evt.wait(self.interval):
            self.poll()

    def stop(self):
        self._stop_evt.set()

    def poll(self, now=None):
        """One scan: refresh the graft_watchdog_* gauges, trip when
        brackets outlive the timeout.  The trip reports the NEWEST
        (innermost) expired bracket — a stalled collective inside a
        step opens step → phase → collective, and the innermost one is
        the thing actually stuck; the enclosing brackets expire with it
        and are marked tripped as part of the same incident (one dump
        per hang, not one per nesting level).  Split out for tests."""
        now = time.time() if now is None else now
        entries = _blackbox.inflight_entries()
        oldest_age = max((now - e["since"] for e in entries), default=0.0)
        progress_age = now - _blackbox.last_progress()["ts"]
        _metrics.watchdog_status(len(entries), oldest_age, progress_age)
        # async_pending brackets (graftlap reduces issued mid-backward)
        # are deliberately left open until their consumer waits — they
        # age only from _begin_wait's re-stamp, never from issue time
        expired = [e for e in entries
                   if now - e["since"] > self.timeout
                   and not e.get("tripped")
                   and not e.get("async_pending")]
        if expired:
            target = max(expired, key=lambda e: e["since"])   # innermost
            for e in expired:
                e["tripped"] = True
            self.trip(target, now - target["since"])

    def trip(self, entry, age):
        """Declare the hang: dump, stacks, metrics, then (optionally)
        escalate a typed error into the stuck thread and/or abort."""
        self.trips += 1
        detail = entry.get("detail") or {}
        dead = _query_dead_ranks()
        _blackbox.record("watchdog_trip", site=entry["site"],
                         detail=detail, age_s=round(age, 3),
                         timeout_s=self.timeout,
                         thread=entry.get("thread"),
                         dead_ranks=dead)
        _metrics.watchdog_trip(entry["site"])
        path = _blackbox.dump(
            path=self.path, reason="watchdog", extra={"watchdog": {
                "timeout_s": self.timeout,
                "tripped_site": entry["site"],
                "tripped_detail": detail,
                "tripped_thread": entry.get("thread"),
                "age_s": round(age, 3),
                "trips": self.trips,
                "abort": self.abort,
                "dead_ranks": dead,
            }})
        sys.stderr.write(
            "graftwatch: WATCHDOG TRIP — %r in flight for %.1fs "
            "(timeout %.1fs), detail=%r, dead_ranks=%r; dump: %s\n"
            % (entry["site"], age, self.timeout, detail, dead, path))
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:
            pass
        if _escalate_configured():
            self.escalate(entry, age, dead)
        if self.abort:
            sys.stderr.write("graftwatch: GRAFT_WATCHDOG_ABORT set — "
                             "exiting %d\n" % _ABORT_EXIT_CODE)
            os._exit(_ABORT_EXIT_CODE)

    def escalate(self, entry, age, dead_ranks=()):
        """Raise a typed hang error INTO the thread that opened the
        stuck bracket (graftarmor fail-fast): a ps_* bracket becomes
        :class:`~..armor.errors.PSUnavailableError`, any other
        collective :class:`~..armor.errors.CollectiveTimeoutError`,
        both naming the dead ranks.  Uses PyThreadState_SetAsyncExc,
        which instantiates the exception CLASS with no arguments — so
        the payload rides a dynamically-built zero-arg subclass.  The
        raise lands only when the target thread next executes Python
        bytecode (socket/lock waits: yes; C-blocked XLA: no — see
        GRAFT_WATCHDOG_ABORT).  Returns True if an escalation was
        delivered."""
        tid = entry.get("tid")
        if tid is None or entry.get("site") != "collective":
            return False
        from ..armor.errors import (CollectiveTimeoutError,
                                    PSUnavailableError)
        detail = entry.get("detail") or {}
        path = str(detail.get("path", ""))
        if path.startswith("ps_"):
            base, args = PSUnavailableError, (
                path, 0)
            kwargs = {"last_error": "watchdog trip after %.1fs" % age,
                      "dead_ranks": tuple(dead_ranks)}
        else:
            base, args = CollectiveTimeoutError, (
                path or entry["site"], round(age, 3), self.timeout)
            kwargs = {"dead_ranks": tuple(dead_ranks), "detail": detail}
        exc_cls = type(base.__name__, (base,), {
            "__init__": (lambda self, _b=base, _a=args, _k=kwargs:
                         _b.__init__(self, *_a, **_k)),
            "__module__": base.__module__,
        })
        import ctypes
        delivered = ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(tid), ctypes.py_object(exc_cls))
        if delivered > 1:       # hit more than one thread state: undo
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(tid), None)
            return False
        if delivered == 1:
            _metrics.watchdog_escalation(path or entry["site"])
            _blackbox.record("watchdog_escalation", site=entry["site"],
                             path=path, tid=tid, error=base.__name__,
                             dead_ranks=list(dead_ranks))
            sys.stderr.write(
                "graftwatch: escalating %s into thread %d (path=%r, "
                "dead_ranks=%r)\n"
                % (base.__name__, tid, path, list(dead_ranks)))
            return True
        return False


_active = [None]


def active():
    """The running Watchdog instance, or None."""
    wd = _active[0]
    return wd if wd is not None and wd.is_alive() else None


def start(timeout=None, interval=None, abort=None, path=None):
    """Start (or replace) the watchdog thread.  ``timeout`` falls back
    to GRAFT_WATCHDOG_TIMEOUT; returns the Watchdog (None if no timeout
    is configured anywhere, or the flight recorder is disabled — the
    watchdog times the recorder's in-flight brackets, so GRAFT_BLACKBOX=0
    leaves it nothing to watch; warned, never silent)."""
    timeout = timeout if timeout is not None else configured_timeout()
    if timeout is None or timeout <= 0:
        return None
    if not _blackbox.enabled():
        import logging
        logging.getLogger("graftwatch").warning(
            "watchdog requested (timeout %.1fs) but the flight recorder "
            "is disabled (GRAFT_BLACKBOX=0) — the watchdog times the "
            "recorder's in-flight brackets, so it is NOT starting; "
            "re-enable the recorder to get hang protection", timeout)
        return None
    # signal/excepthook chains ride the same start path: a main-thread
    # start() installs them even if the first import ran on a worker
    # thread (where signal.signal is unavailable)
    _blackbox.install_hooks()
    stop()
    wd = Watchdog(timeout, interval=interval, abort=abort, path=path)
    _active[0] = wd
    wd.start()
    return wd


def stop():
    wd = _active[0]
    _active[0] = None
    if wd is not None:
        wd.stop()
        if wd.is_alive() and wd is not threading.current_thread():
            wd.join(timeout=2.0)
    return wd


def maybe_start():
    """Telemetry-import hook: run the watchdog iff the env asks for it
    (start() itself warns-and-declines when the recorder is off)."""
    if _active[0] is None and configured_timeout() is not None:
        return start()
    return None
