"""Unified metrics registry: Counter / Gauge / Histogram with labels.

The process-wide telemetry spine of the framework (graftscope).  Every
subsystem that previously kept its own ad-hoc counters reports here:

* engine        — flush causes + segment-length histogram (the registry
                  absorbs ``engine.flush_stats()``: the counters ARE the
                  backing data the dict view is rebuilt from),
* kvstore       — push/pull raw bytes, wire bytes after gradient
                  compression, cumulative compression ratio,
* io            — batches delivered per iterator + batches/sec EWMA,
* autograd      — tape size at backward time (histogram) and the live
                  tape-node gauge,
* device memory — per-device in-use/peak/limit gauges (sampled from
                  ``profiler.device_memory()`` at snapshot time),
* training loop — per-phase (fwd/bwd/update/kvstore) latency histograms.

Two expositions: :meth:`MetricsRegistry.snapshot` (JSON-able dict, what
the benches embed) and :meth:`MetricsRegistry.prometheus_text` (the
Prometheus text format, round-trippable via
:func:`parse_prometheus_text`).  ``GRAFT_TELEMETRY=0`` turns every
increment into a no-op; the CLI (`python -m incubator_mxnet_tpu.telemetry`)
renders the snapshot of the default registry.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
           "enabled", "set_enabled", "parse_prometheus_text",
           "compact_snapshot"]

_enabled_override = None


def set_enabled(flag):
    """Force telemetry on/off (None = defer to GRAFT_TELEMETRY)."""
    global _enabled_override
    _enabled_override = flag


def enabled():
    if _enabled_override is not None:
        return bool(_enabled_override)
    return os.environ.get("GRAFT_TELEMETRY", "1").strip().lower() \
        not in ("0", "false", "no", "off")


def _label_key(labelnames, labels):
    if set(labels) != set(labelnames):
        raise ValueError("expected labels %s, got %s"
                         % (list(labelnames), sorted(labels)))
    return tuple(str(labels[n]) for n in labelnames)


class _Metric(object):
    kind = "untyped"

    def __init__(self, name, help="", labelnames=()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._series = {}          # label-value tuple -> sample
        self._lock = threading.Lock()

    def _sample(self, labels):
        key = _label_key(self.labelnames, labels)
        s = self._series.get(key)
        if s is None:
            with self._lock:
                s = self._series.setdefault(key, self._new_sample())
        return s

    def clear(self):
        with self._lock:
            self._series.clear()

    def labels_of(self, key):
        return dict(zip(self.labelnames, key))

    def samples(self):
        """[(labels dict, sample payload)] — payload shape is per-kind.

        The export runs UNDER the lock: a histogram payload reads
        several list slots, and exporting outside the lock let a
        concurrent ``observe`` tear the snapshot (bucket counts from
        one observation, sum from the next) — the watchdog samples from
        a background thread, so snapshots must be self-consistent."""
        with self._lock:
            return [(self.labels_of(k), self._export(s))
                    for k, s in self._series.items()]


class Counter(_Metric):
    """Monotonic counter (per label set)."""

    kind = "counter"

    def _new_sample(self):
        return [0.0]

    def inc(self, value=1, **labels):
        if not enabled():
            return
        if value < 0:
            raise ValueError("counters only go up (got %r)" % value)
        s = self._sample(labels)
        with self._lock:
            s[0] += value

    def set(self, value, **labels):
        """Collector-side absolute set (for mirroring external counters)."""
        if not enabled():
            return
        s = self._sample(labels)
        with self._lock:
            s[0] = float(value)

    def value(self, **labels):
        return self._sample(labels)[0]

    def _export(self, s):
        return s[0]


class Gauge(_Metric):
    """Point-in-time value (per label set)."""

    kind = "gauge"

    def _new_sample(self):
        return [0.0]

    def set(self, value, **labels):
        if not enabled():
            return
        s = self._sample(labels)
        with self._lock:
            s[0] = float(value)

    def inc(self, value=1, **labels):
        if not enabled():
            return
        s = self._sample(labels)
        with self._lock:
            s[0] += value

    def dec(self, value=1, **labels):
        self.inc(-value, **labels)

    def value(self, **labels):
        return self._sample(labels)[0]

    def _export(self, s):
        return s[0]


_DEFAULT_BUCKETS = (0.0001, 0.001, 0.01, 0.1, 1.0, 10.0)


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics: each bucket
    counts observations ≤ its upper bound; +Inf is implicit)."""

    kind = "histogram"

    def __init__(self, name, help="", labelnames=(), buckets=_DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets))

    def _new_sample(self):
        # [counts per bucket..., +Inf count, sum]
        return [0] * (len(self.buckets) + 1) + [0.0]

    def observe(self, value, **labels):
        if not enabled():
            return
        s = self._sample(labels)
        with self._lock:
            for i, b in enumerate(self.buckets):
                if value <= b:
                    s[i] += 1
            s[len(self.buckets)] += 1      # +Inf
            s[-1] += value

    def _export(self, s):
        return {"buckets": {("%g" % b): s[i]
                            for i, b in enumerate(self.buckets)},
                "count": s[len(self.buckets)],
                "sum": s[-1]}


class MetricsRegistry(object):
    """Named metric store + pull-collectors + expositions."""

    def __init__(self):
        self._metrics = {}
        self._collectors = []
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name, help, labelnames, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, labelnames, **kw)
                self._metrics[name] = m
                return m
        if type(m) is not cls or m.labelnames != tuple(labelnames):
            raise ValueError("metric %r re-registered with a different "
                             "kind/labels" % name)
        return m

    def counter(self, name, help="", labelnames=()):
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=_DEFAULT_BUCKETS):
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def register_collector(self, fn):
        """``fn(registry)`` runs before every snapshot/exposition — the
        pull path for gauges sampled from live state (device memory,
        autograd tape size)."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def _collect(self):
        for fn in list(self._collectors):
            try:
                fn(self)
            except Exception:
                pass        # a broken collector must not kill exposition

    def metrics(self):
        with self._lock:
            return list(self._metrics.values())

    def reset(self, prefix=None):
        """Zero every series (or only metrics whose name starts with
        ``prefix``) — keeps registrations and collectors."""
        for m in self.metrics():
            if prefix is None or m.name.startswith(prefix):
                m.clear()

    def snapshot(self, collect=True):
        """JSON-able dict of everything the registry holds."""
        if collect:
            self._collect()
        out = {}
        for m in self.metrics():
            out[m.name] = {
                "kind": m.kind,
                "help": m.help,
                "samples": [{"labels": labels, "value": payload}
                            for labels, payload in m.samples()],
            }
        return out

    def prometheus_text(self, collect=True):
        """Prometheus text exposition format v0.0.4."""
        if collect:
            self._collect()
        lines = []
        for m in sorted(self.metrics(), key=lambda m: m.name):
            lines.append("# HELP %s %s" % (m.name, m.help or m.name))
            lines.append("# TYPE %s %s" % (m.name, m.kind))
            for labels, payload in m.samples():
                if m.kind == "histogram":
                    for le, cnt in payload["buckets"].items():
                        lines.append("%s_bucket%s %s" % (
                            m.name, _fmt_labels(labels, le=le), _fmt(cnt)))
                    lines.append("%s_bucket%s %s" % (
                        m.name, _fmt_labels(labels, le="+Inf"),
                        _fmt(payload["count"])))
                    lines.append("%s_sum%s %s" % (
                        m.name, _fmt_labels(labels), _fmt(payload["sum"])))
                    lines.append("%s_count%s %s" % (
                        m.name, _fmt_labels(labels), _fmt(payload["count"])))
                else:
                    lines.append("%s%s %s" % (m.name, _fmt_labels(labels),
                                              _fmt(payload)))
        return "\n".join(lines) + "\n"


def _fmt(v):
    f = float(v)
    return ("%d" % int(f)) if f == int(f) else repr(f)


def _fmt_labels(labels, **extra):
    items = list(labels.items()) + list(extra.items())
    if not items:
        return ""
    body = ",".join('%s="%s"' % (k, str(v).replace("\\", "\\\\")
                                 .replace('"', '\\"').replace("\n", "\\n"))
                    for k, v in items)
    return "{%s}" % body


def parse_prometheus_text(text):
    """Parse the text exposition back into
    ``{metric_name: {frozenset(label items): value}}`` — the inverse used
    by the round-trip tests (histogram series appear under their
    ``_bucket``/``_sum``/``_count`` sample names, as on the wire)."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if "{" in line:
            name, rest = line.split("{", 1)
            labelstr, value = rest.rsplit("}", 1)
            labels = {}
            for part in _split_labels(labelstr):
                k, v = part.split("=", 1)
                v = v.strip()
                if len(v) >= 2 and v[0] == '"' and v[-1] == '"':
                    v = v[1:-1]
                labels[k] = _unescape(v)
        else:
            name, value = line.rsplit(" ", 1)
            labels = {}
        out.setdefault(name.strip(), {})[
            frozenset(labels.items())] = float(value)
    return out


_UNESCAPES = {"n": "\n", "\\": "\\", '"': '"'}


def _unescape(v):
    """Left-to-right escape decoding — sequential str.replace passes
    corrupt values like a literal backslash followed by 'n'."""
    out = []
    i = 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append(_UNESCAPES.get(nxt, "\\" + nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _split_labels(s):
    parts, buf, in_q, esc = [], [], False, False
    for ch in s:
        if esc:
            buf.append(ch)
            esc = False
        elif ch == "\\":
            buf.append(ch)
            esc = True
        elif ch == '"':
            buf.append(ch)
            in_q = not in_q
        elif ch == "," and not in_q:
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        parts.append("".join(buf))
    return [p for p in parts if p.strip()]


# ---------------------------------------------------------------------------
# default registry + the graft_* metric catalog (see docs/observability.md)
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry()


def registry():
    """The process-wide default registry."""
    return _REGISTRY


_SEGMENT_LEN_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
_PHASE_BUCKETS = (1e-4, 1e-3, 1e-2, 0.1, 0.5, 1.0, 5.0, 30.0)


def engine_flush(cause, n_instructions):
    """Engine flush accounting (called once per executed flush)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_engine_flushes_total",
              "Bulk-segment flushes by cause",
              ("cause",)).inc(cause=cause)
    r.histogram("graft_engine_segment_length",
                "Instructions per flushed bulk segment", (),
                buckets=_SEGMENT_LEN_BUCKETS).observe(n_instructions)
    r.counter("graft_engine_deferred_ops_total",
              "Ops recorded into bulk segments").inc(n_instructions)


def reset_engine_metrics():
    """Paired with ``engine.reset_flush_stats()`` so both views agree."""
    _REGISTRY.reset(prefix="graft_engine_")


def kvstore_push(raw_bytes, wire_bytes):
    """One kvstore push: raw gradient bytes vs post-compression wire
    bytes (equal when no compressor is attached)."""
    if not enabled():
        return
    r = _REGISTRY
    pushed = r.counter("graft_kvstore_push_bytes_total",
                       "Raw bytes pushed into the kvstore")
    pushed.inc(raw_bytes)
    wire = r.counter("graft_kvstore_wire_bytes_total",
                     "Bytes on the wire after gradient compression")
    wire.inc(wire_bytes)
    if wire.value() > 0:
        r.gauge("graft_kvstore_compression_ratio",
                "Cumulative push raw/wire byte ratio").set(
            pushed.value() / wire.value())


def kvstore_pull(nbytes):
    if not enabled():
        return
    _REGISTRY.counter("graft_kvstore_pull_bytes_total",
                      "Bytes pulled out of the kvstore").inc(nbytes)


def trainer_state_shard_bytes(nbytes, n_shards):
    """graftzero ZeRO-1 gauge: optimizer-state bytes this rank holds for
    its shard (max over per-context updaters), plus the shard count —
    the acceptance gate \"per-rank state ~1/N of unsharded\" reads the
    pair straight off these."""
    if not enabled():
        return
    r = _REGISTRY
    r.gauge("graft_trainer_state_shard_bytes",
            "Optimizer-state bytes held for this rank's ZeRO-1 shard").set(
        float(nbytes))
    r.gauge("graft_trainer_state_shards",
            "ZeRO-1 shard count (ranks/contexts owning state)").set(
        float(n_shards))


_io_rate = {}          # iterator name -> [last perf_counter, ewma rate]
_io_lock = threading.Lock()


def io_batch(iter_name):
    """One data batch delivered by an io pipeline iterator; maintains a
    batches/sec EWMA gauge per iterator class."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_io_batches_total",
              "Batches delivered by io pipeline iterators",
              ("iter",)).inc(iter=iter_name)
    now = time.perf_counter()
    with _io_lock:
        st = _io_rate.get(iter_name)
        if st is None:
            _io_rate[iter_name] = [now, 0.0]
            return
        dt = now - st[0]
        st[0] = now
        if dt <= 0:
            return
        inst = 1.0 / dt
        st[1] = inst if st[1] == 0.0 else 0.8 * st[1] + 0.2 * inst
        rate = st[1]
    r.gauge("graft_io_batches_per_sec",
            "EWMA batches/sec per io iterator",
            ("iter",)).set(rate, iter=iter_name)


def autograd_backward(tape_len):
    """Tape size at the start of a backward pass."""
    if not enabled():
        return
    _REGISTRY.histogram("graft_autograd_tape_size",
                        "Tape nodes walked per backward pass", (),
                        buckets=_SEGMENT_LEN_BUCKETS).observe(tape_len)


def phase(name, seconds):
    """One training-loop phase (fwd/bwd/update/kvstore) completion."""
    if not enabled():
        return
    _REGISTRY.histogram("graft_phase_seconds",
                        "Training-loop phase latency", ("phase",),
                        buckets=_PHASE_BUCKETS).observe(seconds, phase=name)


def program_build(owner, stage, seconds, cache=None):
    """One stage (``trace``, ``lower``, ``backend``) of one program JAX
    built, from the package's listener on ``jax.monitoring``
    (``tracing._on_jax_event``).  ``owner`` is the span that was open when
    JAX built it (``dispatch``, ``fwd``, ``bwd``, ``update``,
    ``engine_flush``, ``deferred_init``...), ``eager`` for a registered
    operator's program under no span, ``user`` for any other.  The
    ``backend`` stage comes with what the persistent cache said (``hit``,
    ``miss``, ``off``) and counts the program."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_program_build_seconds_total",
              "Seconds JAX spent building programs, by the span that asked "
              "and the stage", ("owner", "stage")
              ).inc(seconds, owner=owner, stage=stage)
    if cache is not None:
        r.counter("graft_program_builds_total",
                  "Programs JAX built (a backend compile or a read of the "
                  "persistent cache), by the span that asked",
                  ("owner", "cache")).inc(owner=owner, cache=cache)


def _collect_device_memory(reg):
    """Snapshot-time gauges from the XLA per-device allocator (falls back
    to live_arrays accounting — see profiler.device_memory)."""
    from .. import profiler
    g = reg.gauge("graft_device_memory_bytes",
                  "Per-device memory from the storage accounting",
                  ("device", "kind"))
    for m in profiler.device_memory():
        g.set(m["bytes_in_use"], device=m["device"], kind="in_use")
        g.set(m["peak_bytes_in_use"], device=m["device"], kind="peak")
        g.set(m["bytes_limit"], device=m["device"], kind="limit")


def _collect_autograd_tape(reg):
    from .. import autograd
    reg.gauge("graft_autograd_tape_nodes",
              "Live tape nodes on the calling thread").set(
        len(autograd._st().tape))


def _collect_engine_stats(reg):
    """Mirror ``engine.flush_stats()`` so a snapshot is complete even if
    a flush path bypassed the incremental counters (defensive sync —
    values are authoritative from the engine's own dicts)."""
    from .. import engine
    stats = engine.flush_stats()
    c = reg.counter("graft_engine_flushes_total",
                    "Bulk-segment flushes by cause", ("cause",))
    for cause, n in stats["causes"].items():
        c.set(n, cause=cause)
    g = reg.gauge("graft_engine_replay_cache_size",
                  "Entries in the engine's bounded program caches "
                  "(GRAFT_REPLAY_CACHE_SIZE)", ("cache",))
    for name, n in engine.cache_sizes().items():
        g.set(n, cache=name)
    from .. import optimizer as _opt
    g.set(len(_opt._FUSED_STEP_CACHE), cache="fused_update")


_BUCKET_BYTE_BUCKETS = (4096, 65536, 262144, 1 << 20, 4 << 20, 16 << 20,
                        64 << 20)


def trainer_buckets(bucket_bytes_list, n_leftover):
    """One bucket plan build by the fused Trainer.step path: bucket count
    gauge + per-bucket payload-bytes histogram (graftfuse)."""
    if not enabled():
        return
    r = _REGISTRY
    r.gauge("graft_trainer_bucket_count",
            "Gradient buckets in the current fused-step plan").set(
        len(bucket_bytes_list))
    r.gauge("graft_trainer_bucket_leftover_params",
            "Params the fused-step plan left on the per-param path").set(
        n_leftover)
    h = r.histogram("graft_trainer_bucket_bytes",
                    "Payload bytes per gradient bucket", (),
                    buckets=_BUCKET_BYTE_BUCKETS)
    for nb in bucket_bytes_list:
        h.observe(nb)


def trainer_overlap(n_overlapped, n_serial, exposed_s, inflight_s):
    """One overlapped ``Trainer.step`` (graftlap): how much of the bucket
    reduces' in-flight wall time was hidden under backward.

    ``exposed_s`` is the time step() actually spent blocked in
    ``ReduceHandle.wait``; ``inflight_s`` is the summed issue-to-ready
    wall time of the overlapped handles.  The ratio gauge is
    ``1 - exposed/inflight`` — 1.0 means every overlapped reduce landed
    before step() looked at it, 0.0 means nothing was hidden (the serial
    cost in a different place)."""
    if not enabled():
        return
    r = _REGISTRY
    c = r.counter("graft_trainer_overlap_buckets_total",
                  "Bucket reduces by issue mode (overlapped = put on the "
                  "wire mid-backward; serial = reduced inside step())",
                  ("mode",))
    c.inc(n_overlapped, mode="overlapped")
    c.inc(n_serial, mode="serial")
    r.histogram("graft_trainer_overlap_exposed_seconds",
                "Per-step reduce wait time NOT hidden under backward", (),
                buckets=_PHASE_BUCKETS).observe(exposed_s)
    if inflight_s > 0:
        r.gauge("graft_trainer_overlap_ratio",
                "Fraction of overlapped-reduce in-flight wall time hidden "
                "under the backward pass (last overlapped step)").set(
            max(0.0, min(1.0, 1.0 - exposed_s / inflight_s)))


def trainer_pull_overlap(n_overlapped, n_serial, exposed_s, inflight_s,
                         stale=0):
    """One round of weight pulls on the update_on_kvstore path
    (graftduplex): how much of the pull/broadcast in-flight wall time was
    hidden under the next forward (first-touch waits) and data loading.

    ``exposed_s`` is host time actually blocked in ``PullHandle.wait``;
    ``inflight_s`` the summed issue→wait-return wall time.  Mirrors
    ``trainer_overlap`` on the reduce side; the serial pull path reports
    with ``exposed == inflight`` so the two configurations stay
    comparable."""
    if not enabled():
        return
    r = _REGISTRY
    c = r.counter("graft_trainer_pull_buckets_total",
                  "Weight-pull groups by issue mode (overlapped = async "
                  "PullHandle waited at first touch; serial = pulled "
                  "synchronously inside the step)", ("mode",))
    c.inc(n_overlapped, mode="overlapped")
    c.inc(n_serial, mode="serial")
    r.histogram("graft_trainer_pull_exposed_seconds",
                "Per-round pull wait time NOT hidden under the next "
                "forward", (), buckets=_PHASE_BUCKETS).observe(exposed_s)
    if stale:
        r.counter("graft_trainer_pull_stale_total",
                  "Out arrays whose async-pulled value was dropped "
                  "because the array was overwritten between issue and "
                  "wait (abandon-and-fallback)").inc(stale)
    if inflight_s > 0:
        r.gauge("graft_trainer_pull_overlap_ratio",
                "Fraction of async weight-pull in-flight wall time hidden "
                "under data loading / the next forward (last pull-bearing "
                "round)").set(
            max(0.0, min(1.0, 1.0 - exposed_s / inflight_s)))


def trainer_fused_update(n_params):
    """One fused multi-tensor optimizer dispatch (per bucket, per
    context); latency lands on the existing ``update`` phase span."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_trainer_bucket_fused_updates_total",
              "Fused multi-tensor optimizer update dispatches").inc()
    r.counter("graft_trainer_bucket_fused_params_total",
              "Parameters updated through fused bucket dispatches").inc(
        n_params)


def flash_attention_trace(path, window=None):
    """One trace of ``ops.attention.flash_attention``, forward or backward,
    labeled by the path it took and by its window (``"none"`` or the
    width in keys).  Forward: ``pallas`` /
    ``reference_off_tpu`` (concrete operands, chosen by where they live),
    ``lowering_platform`` (traced operands: Pallas when the enclosing
    program is lowered for a TPU, jnp otherwise) or ``reference_unaligned``
    (a length that is not a multiple of 128 — the O(S²) jnp path on every
    platform).  Backward, in the same order: ``bwd_pallas`` /
    ``bwd_scan_off_tpu``, ``bwd_lowering_platform``, ``bwd_scan_unaligned``
    (the chunked jnp scan where the forward took its jnp path)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_flash_attention_traces_total",
                      "flash_attention traces by execution path and window",
                      ("path", "window")).inc(
        path=path, window="none" if window is None else str(window))


def flash_blocks(visited, causal, window=None):
    """The (query block, key block) pairs in which the Pallas forward of
    the causal ``flash_attention`` call just traced runs a product, over
    all of its (batch, head) slices, and those of the causal triangle alone:
    two gauges, by ``kind`` (``window`` / ``full``), set when the call is
    traced.  Static counts from shapes; nothing is read back in a step."""
    if not enabled():
        return
    kind = "full" if window is None else "window"
    for name, value, what in (
            ("graft_flash_blocks_visited", visited, "run a product in"),
            ("graft_flash_blocks_causal", causal, "of the causal triangle")):
        _REGISTRY.gauge(name, "Block pairs of the last traced causal flash "
                        "forward %s, by kind of layer" % what,
                        ("kind",)).set(float(value), kind=kind)


def flash_head_dim(qk, v):
    """The head widths of the ``flash_attention`` call just traced: q's and
    k's (``part="qk"``) and v's, which is the output's (``part="v"``).  One
    gauge, set from shapes when the call is traced: a reader tells a latent
    layer's kernels (192 over 128) from a layer of one width by it."""
    if not enabled():
        return
    gauge = _REGISTRY.gauge("graft_flash_head_dim",
                            "Head widths of the last traced flash_attention "
                            "call: q and k, and v", ("part",))
    gauge.set(float(qk), part="qk")
    gauge.set(float(v), part="v")


def ssm_scan_trace(path):
    """One trace of ``ops.ssm.selective_scan``, forward or backward, labeled
    by the form it took: ``pallas`` (concrete operands on a TPU),
    ``lowering_platform`` (traced operands: the Mosaic kernels when the
    enclosing program is lowered for a TPU, the ``jnp`` form otherwise) or
    ``jnp`` (off the TPU, or a shape the kernels do not take)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_ssm_scan_traces_total",
                      "selective_scan traces by form", ("path",)).inc(
        path=path)


def ssm_scan_shape(chunk, state_elems):
    """The chunk (time steps between two saved states) and the state's size
    (channels x states) of the ``selective_scan`` call just traced: two
    gauges, set from shapes."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_ssm_scan_chunk",
                    "Time steps between two saved states of the last traced "
                    "selective_scan call").set(float(chunk))
    _REGISTRY.gauge("graft_ssm_state_elems",
                    "Channels x states of the last traced selective_scan "
                    "call").set(float(state_elems))


def delta_rule_trace(form):
    """One trace of ``ops.delta_rule.gated_delta_rule``, forward or (with
    ``_bwd`` after the name) its backward rule, labeled by the form the walk
    over the chunks' states took: ``kernel`` (concrete operands on a TPU:
    the Mosaic kernels), ``lowering_platform`` (traced operands: the kernels
    when the enclosing program is lowered for a TPU, the ``lax.scan``
    otherwise) or ``chunked`` (the ``lax.scan``: off the TPU, or a shape the
    kernels do not take).  Every chunk's operands are batched matmuls in all
    three."""
    if not enabled():
        return
    _REGISTRY.counter("graft_delta_rule_traces_total",
                      "gated_delta_rule traces by form", ("form",)).inc(
        form=form)


def delta_rule_shape(chunk, state_bytes):
    """The chunk (time steps between two saved states) and the bytes of the
    float32 state (rows x heads x key_dim x value_dim x 4) of the
    ``gated_delta_rule`` call just traced: two gauges, set from shapes."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_delta_rule_chunk",
                    "Time steps between two saved states of the last traced "
                    "gated_delta_rule call").set(float(chunk))
    _REGISTRY.gauge("graft_delta_rule_state_bytes",
                    "Bytes of the float32 state of the last traced "
                    "gated_delta_rule call").set(float(state_bytes))


def rotary_dim(dim):
    """The channels of a head that the ``_contrib_RotaryEmbedding`` call just
    traced turns (the whole head, or the first ``dim`` of it): a gauge, set
    from shapes."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_rotary_dim",
                    "Channels of a head turned by the last traced rotary "
                    "embedding").set(float(dim))


def shared_kv_read():
    """One trace of an attention layer that projects no k, v of its own and
    attends onto another layer's (``DifferentialAttention(cross=True)``)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_shared_kv_reads_total",
                      "Cross-attention layer traces that read another "
                      "layer's k, v").inc()


def moe_shared_trace():
    """One trace of an ``ExpertParallelMoE`` forward that has a shared
    expert (``shared_hidden_size``): the always-on ``GatedMLP`` added to the
    routed sum."""
    if not enabled():
        return
    _REGISTRY.counter("graft_moe_shared_traces_total",
                      "ExpertParallelMoE forward traces with a shared "
                      "expert").inc()


def attention_gate_trace():
    """One trace of a ``MultiHeadAttention`` forward with ``gate=True``: the
    sigmoid of a projection of the layer's input multiplied onto the heads'
    outputs before ``proj_out``."""
    if not enabled():
        return
    _REGISTRY.counter("graft_attention_gate_traces_total",
                      "MultiHeadAttention forward traces with an output "
                      "gate").inc()


def moe_dispatch_trace(path):
    """One trace of ``parallel.moe.ExpertParallelMoE``'s routed forward,
    labeled by its dispatch mode (``dense`` / ``grouped``): beside
    ``graft_flash_attention_traces_total``, what a run's reader asks to see
    which expert path its program staged."""
    if not enabled():
        return
    _REGISTRY.counter("graft_moe_dispatch_traces_total",
                      "ExpertParallelMoE forward traces by dispatch mode",
                      ("path",)).inc(path=path)


def moe_buffer_parts(parts, part_bytes):
    """One trace of ``parallel.moe.grouped_moe_apply``: the equal parts its
    tokens went through the layer in (1: the buffer of all assignments is
    under the layer's limit) and the bytes of a part's buffer, both from
    shapes.  The last traced call's, as ``graft_flash_blocks_visited``."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_moe_buffer_parts",
                    "Equal parts of its tokens the last traced grouped "
                    "dispatch ran in").set(parts)
    _REGISTRY.gauge("graft_moe_buffer_part_bytes",
                    "Bytes of a part's buffer of assignments, last traced "
                    "grouped dispatch").set(part_bytes)


def moe_weight_grad_trace(form, parts):
    """One trace of the Pallas branch of a grouped product's backward rule
    in ``parallel.moe``: how the experts' weight gradient is formed
    (``form``: ``tgmm``, megablox's kernel over the one buffer of a layer
    whose tokens are one part; ``tgmm_parts``, one kernel over the row
    buffers of ``parts`` parts, each block of the result written once).
    Beside ``graft_moe_buffer_parts``: what a run's reader asks to see
    which form its step staged."""
    if not enabled():
        return
    _REGISTRY.counter("graft_moe_weight_grad_traces_total",
                      "Grouped-product backward traces by the form of the "
                      "experts' weight gradient",
                      ("form", "parts")).inc(form=form, parts=str(parts))


def moe_product_tile(product, dims, tiles):
    """One trace of a kernel of ``parallel.moe.grouped_dot`` on its Pallas
    path (``product``: ``gmm``, the rows' cotangent ``gmm_t``, the weights'
    ``tgmm``): the tiles its two weight dimensions were staged with,
    megablox's ``k`` and ``n`` in that order, both from shapes.  The last
    traced kernel's of each kind, as ``graft_moe_buffer_parts``; a tile
    that does not divide its dimension (a ragged last tile, which the
    kernel computes and drops) counts the trace."""
    if not enabled():
        return
    gauge = _REGISTRY.gauge("graft_moe_product_tile",
                            "Tile of a grouped product's weight dimension, "
                            "last traced kernel of its kind",
                            ("product", "dim"))
    for dim, tile in zip("kn", tiles):
        gauge.set(tile, product=product, dim=dim)
    if any(size % tile for size, tile in zip(dims, tiles)):
        _REGISTRY.counter("graft_moe_ragged_tile_traces_total",
                          "Grouped-product kernel traces with a tile that "
                          "does not divide its dimension",
                          ("product",)).inc(product=product)


def dropout_mask_trace(op):
    """One trace of ``ops.nn.inverted_dropout``, the function that draws
    every dropout mask, labeled by the operator that asked (``Dropout`` /
    ``RNN``): beside the flash and expert counters, what a run's reader
    asks to see that its program staged a draw and not none.  Sites of one
    shape, dtype and ``p`` share a traced body (the operator's ``jit``
    cache), so this counts the distinct sites a process traced; a
    program's own count is its ``rng-bit-generator`` ops under the scope
    ``dropout``."""
    if not enabled():
        return
    _REGISTRY.counter("graft_dropout_mask_traces_total",
                      "Traces of the function that draws dropout masks "
                      "from the backend's bit generator, by operator",
                      ("op",)).inc(op=op)


def embedding_grad_trace(path):
    """One trace of ``Embedding``'s backward (``ops.tensor._table_grad``),
    labeled by the form the table's gradient took: ``scatter`` (one
    ``scatter-add`` of whole rows, the transpose of the lookup),
    ``column_parts`` (a ``scatter-add`` a quarter of the row at a time,
    concrete operands on a TPU) or ``column_parts_on_tpu`` (traced operands:
    the column blocks where the enclosing program is lowered for a TPU, the
    whole-row scatter elsewhere)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_embedding_grad_traces_total",
                      "Traces of Embedding's backward by the form of the "
                      "table's gradient", ("path",)).inc(path=path)


def cachedop_recorded(residual_bytes):
    """One forward of a hybridized Block under ``autograd.record()``: it
    took the vjp once and kept, for its backward, ``residual_bytes`` of
    residuals beyond its parameters and inputs (from the shapes of the
    entry's leaves, reckoned once an entry; nothing is read back)."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_cachedop_residual_bytes",
                    "Bytes the last recorded CachedOp forward kept for its "
                    "backward, beyond parameters and inputs"
                    ).set(residual_bytes)


def cachedop_cotangents_skipped(parameters, inputs):
    """One backward call of a recorded CachedOp node: of the node's inputs,
    how many cotangents its backward program did not compute because the
    pass had no use for them (``autograd._wanted``), parameters (a
    ``grad_req`` of ``"null"``) and inputs (no grad buffer, no history on
    the tape, not asked of ``autograd.grad``) apart.  Both kinds are there
    from the first call on, at 0 where everything was wanted."""
    if not enabled():
        return
    counter = _REGISTRY.counter(
        "graft_cachedop_cotangents_skipped_total",
        "Cotangents a CachedOp's backward program left out because the "
        "pass had no use for them", ("kind",))
    counter.inc(parameters, kind="parameter")
    counter.inc(inputs, kind="input")


def moe_assignments(load, assignments):
    """One eager ``grouped`` call: ``load`` is the assignments each held
    expert got, ``assignments`` all the (token, expert) pairs the router
    made.  Counts them by whether a held expert got them, and keeps the
    last call's largest load over the mean load."""
    if not enabled():
        return
    held = int(sum(load))
    counter = _REGISTRY.counter(
        "graft_moe_assignments_total",
        "Routed (token, expert) assignments by whether the layer holds "
        "the expert", ("held",))
    counter.inc(held, held="yes")
    counter.inc(assignments - held, held="no")
    if held:
        _REGISTRY.gauge(
            "graft_moe_expert_load_max_over_mean",
            "Largest held expert's assignments over the mean, last eager "
            "grouped call").set(float(max(load)) * len(load) / held)


# -- graftwatch: watchdog + dist liveness ------------------------------------

_SKEW_BUCKETS = (1e-4, 1e-3, 1e-2, 0.1, 0.5, 1.0, 5.0, 30.0)


def watchdog_status(n_inflight, oldest_age, progress_age):
    """One watchdog poll: liveness gauges refreshed from the background
    thread (telemetry/watchdog.py)."""
    if not enabled():
        return
    r = _REGISTRY
    r.gauge("graft_watchdog_inflight",
            "Open flight-recorder brackets (flushes/collectives/phases)"
            ).set(n_inflight)
    r.gauge("graft_watchdog_oldest_inflight_seconds",
            "Age of the oldest open bracket").set(oldest_age)
    r.gauge("graft_watchdog_progress_age_seconds",
            "Wall-clock seconds since the last bracket completed").set(
        progress_age)


def watchdog_trip(site):
    """One declared hang (per tripped bracket site)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_watchdog_trips_total",
                      "Watchdog hang declarations", ("site",)).inc(site=site)


def dist_dead_nodes(n):
    """Workers whose heartbeats stopped (DistKVStore.num_dead_nodes)."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_dist_dead_nodes",
                    "Workers whose parameter-service heartbeats stopped"
                    ).set(n)


def dist_worker_skew(seconds):
    """Per-step cross-worker arrival skew from the dist heartbeat."""
    if not enabled():
        return
    _REGISTRY.histogram("graft_dist_worker_skew_seconds",
                        "Per-step worker arrival skew (dist heartbeat)", (),
                        buckets=_SKEW_BUCKETS).observe(seconds)


def collective_slow(path):
    """One collective beyond GRAFT_STRAGGLER_FACTOR x its own EWMA."""
    if not enabled():
        return
    _REGISTRY.counter("graft_dist_slow_collectives_total",
                      "Collectives slower than the straggler threshold",
                      ("path",)).inc(path=path)


def tsan_report(code):
    """One grafttsan race report (EH2xx, analysis/tsan.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_tsan_reports_total",
                      "Happens-before race reports by diagnostic code",
                      ("code",)).inc(code=code)


def lockstep_divergence():
    """One detected SPMD lockstep divergence (analysis/lockstep.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_lockstep_divergence_total",
                      "Cross-rank collective-stream divergences detected"
                      ).inc()


# -- graftserve: production serving runtime -----------------------------------

_SERVE_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
_SERVE_LATENCY_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5,
                          1.0, 5.0)


def serve_request(model, wall_s, components):
    """One completed serving request: per-request latency + the four-way
    decomposition (queue_wait/batch_assembly/device_compute/host_io,
    serving/slo.py — the components sum EXACTLY to ``wall_s``)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_serve_requests_total",
              "Serving requests completed", ("model",)).inc(model=model)
    r.histogram("graft_serve_request_seconds",
                "End-to-end request latency (enqueue to response ready)",
                ("model",), buckets=_SERVE_LATENCY_BUCKETS).observe(
        wall_s, model=model)
    h = r.histogram("graft_serve_component_seconds",
                    "Per-request latency by SLO component", ("component",),
                    buckets=_SERVE_LATENCY_BUCKETS)
    for c, v in components.items():
        h.observe(v, component=c)


def serve_quantiles(p50_s, p99_s):
    """Rolling-window latency quantiles (serving/slo.py recomputes them
    over the request ring after every batch)."""
    if not enabled():
        return
    g = _REGISTRY.gauge("graft_serve_latency_seconds",
                        "Rolling request-latency quantiles over the last "
                        "GRAFT_SERVE_RING requests", ("quantile",))
    g.set(p50_s, quantile="p50")
    g.set(p99_s, quantile="p99")


def serve_batch(model, size, bucket):
    """One dispatched serving batch: ``size`` real requests padded to
    the ``bucket`` compiled batch signature."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_serve_batches_total",
              "Serving batches dispatched", ("model",)).inc(model=model)
    r.histogram("graft_serve_batch_size",
                "Real requests per dispatched batch", (),
                buckets=_SERVE_BATCH_BUCKETS).observe(size)
    if bucket > size:
        r.counter("graft_serve_padding_rows_total",
                  "Padding rows dispatched to reach a batch bucket").inc(
            bucket - size)


def serve_queue_depth(depth):
    """Requests currently queued across all models (set on every
    enqueue/pick)."""
    if not enabled():
        return
    _REGISTRY.gauge("graft_serve_queue_depth",
                    "Requests waiting in the dynamic batcher").set(depth)


def serve_errors(model, n=1):
    """Requests failed by a dispatch/model error."""
    if not enabled():
        return
    _REGISTRY.counter("graft_serve_errors_total",
                      "Serving requests failed", ("model",)).inc(
        n, model=model)


def serve_model_event(kind):
    """Registry lifecycle tick: ``load``/``reload``/``evict``/``swap``/
    ``unload`` (serving/registry.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_serve_model_events_total",
                      "Model registry lifecycle events (load/reload/"
                      "evict/swap/unload)", ("kind",)).inc(kind=kind)


def serve_residency(resident_bytes, resident_models, budget_bytes):
    """Registry residency snapshot after every load/evict/swap."""
    if not enabled():
        return
    r = _REGISTRY
    r.gauge("graft_serve_resident_bytes",
            "Model weight bytes resident in the serving registry").set(
        resident_bytes)
    r.gauge("graft_serve_resident_models",
            "Models with resident weights in the serving registry").set(
        resident_models)
    # always published (0 = unlimited) so an unlimited registry can't
    # inherit a stale budget value from an earlier bounded one
    r.gauge("graft_serve_memory_budget_bytes",
            "GRAFT_SERVE_MEMORY_BYTES residency budget (0 = "
            "unlimited)").set(budget_bytes)


def serve_parity_fallback(model):
    """One (model, shape, bucket) signature demoted to per-request
    dispatch because its batched output failed the bit-parity probe."""
    if not enabled():
        return
    _REGISTRY.counter("graft_serve_parity_fallbacks_total",
                      "Batch signatures demoted to per-request dispatch "
                      "by the parity probe", ("model",)).inc(model=model)


# -- graftarmor: fault injection, RPC self-healing, checkpointing -------------

_CKPT_WRITE_BUCKETS = (1e-3, 1e-2, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0)


def fault_injected(site, kind):
    """One fault fired by the armor injection registry (armor/faults.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_faults_injected_total",
                      "Faults injected by GRAFT_FAULTS, by site and kind",
                      ("site", "kind")).inc(site=site, kind=kind)


def rpc_retry(cmd):
    """One retried parameter-service RPC attempt (parallel/ps.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_rpc_retries_total",
                      "Parameter-service RPC attempts retried after a "
                      "transient failure", ("cmd",)).inc(cmd=cmd)


def rpc_reconnect():
    """One PSClient socket rebuild after a disconnect."""
    if not enabled():
        return
    _REGISTRY.counter("graft_rpc_reconnects_total",
                      "PSClient reconnects after a broken connection").inc()


def rpc_gave_up(cmd):
    """One RPC that exhausted GRAFT_RPC_RETRIES and surfaced a typed
    PSUnavailableError."""
    if not enabled():
        return
    _REGISTRY.counter("graft_rpc_gave_up_total",
                      "Parameter-service RPCs that exhausted their retry "
                      "budget", ("cmd",)).inc(cmd=cmd)


def watchdog_escalation(site):
    """One typed hang exception raised into a waiting thread
    (GRAFT_WATCHDOG_ESCALATE, telemetry/watchdog.py)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_watchdog_escalations_total",
                      "Typed hang exceptions escalated into waiting "
                      "threads", ("site",)).inc(site=site)


def checkpoint_saved(seconds, nbytes, step):
    """One atomic training snapshot written (armor/checkpoint.py)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_checkpoint_saves_total",
              "Atomic training snapshots written").inc()
    r.histogram("graft_checkpoint_write_seconds",
                "Wall time of one snapshot write (drain + serialize + "
                "rename)", (), buckets=_CKPT_WRITE_BUCKETS).observe(seconds)
    r.gauge("graft_checkpoint_last_bytes",
            "Payload bytes of the last snapshot written").set(nbytes)
    r.gauge("graft_checkpoint_last_step",
            "Step counter captured by the last snapshot").set(step)


def checkpoint_restored(step):
    """One successful resume() from a snapshot."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_checkpoint_restores_total",
              "Training resumes restored from a snapshot").inc()
    r.gauge("graft_checkpoint_last_step",
            "Step counter captured by the last snapshot").set(step)


def elastic_epoch(epoch):
    """One membership-epoch transition applied (elastic/membership.py)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_elastic_epochs_total",
              "Membership-epoch transitions applied").inc()
    r.gauge("graft_elastic_epoch",
            "Current membership epoch of this rank").set(epoch)


def elastic_repartition(world_size, moved_keys=0):
    """One deterministic re-partition run (PS key ranges, shard owners,
    bucket plans rebuilt for a new world size)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("graft_elastic_repartitions_total",
              "Deterministic re-partitions run at membership-epoch "
              "boundaries").inc()
    r.gauge("graft_elastic_world_size",
            "Live world size after the last re-partition").set(world_size)
    if moved_keys:
        r.counter("graft_elastic_moved_keys_total",
                  "PS keys whose owning server changed across "
                  "re-partitions").inc(moved_keys)


def elastic_rejoin_seconds(seconds, nbytes=0):
    """One checkpoint-streamed rejoin completed (elastic/rejoin.py)."""
    if not enabled():
        return
    r = _REGISTRY
    r.histogram("graft_elastic_rejoin_seconds",
                "Wall time of one checkpoint-streamed rejoin (fetch + "
                "validate + restore)", (),
                buckets=_CKPT_WRITE_BUCKETS).observe(seconds)
    if nbytes:
        r.gauge("graft_elastic_rejoin_last_bytes",
                "Snapshot bytes streamed by the last rejoin").set(nbytes)


def serve_shed(model, n=1):
    """Requests shed by the batcher because their deadline expired
    before dispatch (serving/batcher.py load shedding)."""
    if not enabled():
        return
    _REGISTRY.counter("graft_serve_shed_total",
                      "Serving requests shed at dispatch because their "
                      "deadline_ms had already expired", ("model",)).inc(
        n, model=model)


_REGISTRY.register_collector(_collect_device_memory)
_REGISTRY.register_collector(_collect_autograd_tape)
_REGISTRY.register_collector(_collect_engine_stats)


def compact_snapshot(reg=None):
    """Flat ``{"name{label=v}": value}`` view (histograms export their
    ``_count``/``_sum``) — the form the benches embed in BENCH JSON."""
    reg = reg or _REGISTRY
    out = {}
    reg._collect()
    for m in reg.metrics():
        for labels, payload in m.samples():
            key = m.name + _fmt_labels(labels)
            if m.kind == "histogram":
                out[m.name + "_count" + _fmt_labels(labels)] = \
                    payload["count"]
                out[m.name + "_sum" + _fmt_labels(labels)] = \
                    round(payload["sum"], 6)
            else:
                out[key] = payload
    return out


def write_snapshot(path, reg=None):
    """Dump the JSON snapshot to ``path`` (GRAFT_TELEMETRY_SNAPSHOT)."""
    reg = reg or _REGISTRY
    with open(path, "w") as f:
        json.dump(reg.snapshot(), f, indent=2, sort_keys=True)
    return path
