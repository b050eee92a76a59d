"""graftscope + graftwatch — observability for the deferred engine.

Four quarters (see docs/observability.md for the full guide):

* :mod:`~incubator_mxnet_tpu.telemetry.tracing` — chrome-trace spans per
  bulk-segment flush with flow links from each deferred op's record
  event, so a trace of a bulked model body shows *where* cost actually
  lands (the profiler still owns the event buffer and ``dump()``).
* :mod:`~incubator_mxnet_tpu.telemetry.metrics` — the process-wide
  Counter/Gauge/Histogram registry (engine flush causes, kvstore bytes
  and compression ratio, io batches/sec, autograd tape sizes, device
  memory, training phase latencies, watchdog/dist liveness) with JSON
  snapshot and Prometheus text expositions.
* :mod:`~incubator_mxnet_tpu.telemetry.blackbox` — the always-on flight
  recorder: a bounded ring of structured events (engine flushes,
  kvstore collectives, step boundaries, dist heartbeats) dumped to JSON
  on unhandled exception, SIGTERM/SIGINT, ``blackbox.dump()`` or a
  watchdog trip.  Independent of ``GRAFT_TELEMETRY`` and the profiler.
* :mod:`~incubator_mxnet_tpu.telemetry.watchdog` — the hang watchdog: a
  thread that trips when an engine flush / dist collective / phase stays
  in flight past ``GRAFT_WATCHDOG_TIMEOUT``, writing the dump + thread
  stacks (and aborting under ``GRAFT_WATCHDOG_ABORT``).
* :mod:`~incubator_mxnet_tpu.telemetry.aggregate` — cross-rank trace
  merging: N per-rank chrome traces / blackbox dumps → ONE merged trace
  with per-rank tracks, cross-rank flow links per collective, and a
  straggler table (last-to-enter/exit rank + spreads).

What the program marks in itself, for a reader of a profiler trace
(``benchmark/chip/layer_metrics/`` is one):

* :func:`phase_span` — the one span primitive: ``mx:<name>`` in the JAX
  profiler's trace beside the device events, and a record (name, start,
  end, parent, step id, on ``time.perf_counter()``) that :func:`spans`
  hands out, oldest first.
* :func:`startup` — the start-up record, which no eviction touches: the
  package's import, the set-up spans (``initialize``, ``deferred_init``,
  ``gather_params``, ``memory_analysis``) and a build record for every stage
  (trace, lower, backend compile or cache read) of every program the
  process builds, each put down to the span that was open when JAX built
  it.
* :func:`step_counter` — a count made inside a compiled step (the rows a
  routed layer's held experts got): it leaves the fused step beside the
  loss and :func:`step_counters` hands it out by the ``step`` span's id.
* :func:`programs` — the jitted programs of the train paths by the name
  the trace's ``XLA Modules`` line gives them, each with its phase or its
  ops' ``op_name`` paths (``xray:forward|backward|update`` scopes, Block
  names) and its ``memory_analysis()`` numbers, resolved when asked for.

CLI::

    python -m incubator_mxnet_tpu.telemetry --summary [--json]
    python -m incubator_mxnet_tpu.telemetry --blackbox PATH [--json]
    python -m incubator_mxnet_tpu.telemetry --analyze R0.json R1.json \
        [--json | --merged OUT.json]

Environment: ``GRAFT_TELEMETRY=0`` disables metric collection;
``GRAFT_TELEMETRY_SNAPSHOT=<path>`` writes the JSON snapshot at process
exit; ``GRAFT_TELEMETRY_TOPK`` sets the CLI's segment table size;
``GRAFT_BLACKBOX[_SIZE|_PATH]`` control the flight recorder;
``GRAFT_WATCHDOG_TIMEOUT``/``GRAFT_WATCHDOG_ABORT`` the watchdog.
"""
from __future__ import annotations

import os as _os

from . import metrics
from . import tracing
from . import blackbox
from . import watchdog
from . import aggregate
from . import xray
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      compact_snapshot, enabled, parse_prometheus_text,
                      registry, set_enabled, write_snapshot)
from .tracing import phase_span, step_counter
from .blackbox import spans, startup, step_counters
from .xray import programs

__all__ = ["metrics", "tracing", "blackbox", "watchdog",
           "aggregate", "xray",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "registry", "enabled", "set_enabled", "parse_prometheus_text",
           "compact_snapshot", "write_snapshot", "phase_span", "spans",
           "startup", "step_counter", "step_counters", "programs"]

_snapshot_path = _os.environ.get("GRAFT_TELEMETRY_SNAPSHOT")
if _snapshot_path:
    import atexit as _atexit

    _atexit.register(lambda: write_snapshot(_snapshot_path))

# graftwatch is ALWAYS-ON by default: the crash hooks (excepthook +
# SIGTERM/SIGINT chains) install unconditionally — they re-check
# enabled() at fire time and only write a dump when the recorder holds
# events, so a process that starts with GRAFT_BLACKBOX=0 and calls
# blackbox.set_enabled(True) later still gets its post-mortem.  The
# watchdog thread only starts when GRAFT_WATCHDOG_TIMEOUT asks for it.
blackbox.install_hooks()
watchdog.maybe_start()
