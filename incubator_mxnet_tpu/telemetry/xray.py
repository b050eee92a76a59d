"""graftxray — in-program phase attribution for the compiled train steps.

A fused train step is ONE donated XLA program, opaque from outside: a
profiler names its device ops after post-fusion HLO instructions.  This
module reopens the program:

* **Phase provenance at trace time.**  ``parallel/data_parallel.py``
  threads ``jax.named_scope`` markers (``xray:forward``,
  ``xray:backward``, ``xray:update``) through its trace, and a
  ``HybridBlock`` adds its name while it is traced, so every HLO op of
  the compiled program carries its phase and its Block in its
  ``op_name`` metadata — fusion keeps the representative op's scope, so
  the attribution survives XLA's optimizer.  :func:`op_paths_from_hlo`
  parses the OPTIMIZED HLO of the compiled executable (the names the
  profiler trace references).

* **The program registry.**  The train paths hand their jitted programs
  over by name (:func:`register_program`: ``dp_train_step``,
  ``cachedop_forward``, ``cachedop_backward``, ``trainer_bucket_update``),
  lazily: nothing is lowered or printed until a reader asks :func:`programs`
  (``telemetry.programs()``) for a program's ops or memory numbers.

* **Exact-sum conservation.**  :func:`attribute` partitions a chrome
  trace's device ops over the phases; durations accumulate as integer
  nanoseconds: ``sum(phase device times) + unattributed == program device
  span`` holds EXACTLY (``conservation_ok``).  The capture is whoever's
  profiler session it is (``mx.profiler.set_config(xprof_dir=...)``, the
  chip benchmark's tracer); this module starts none.
"""
from __future__ import annotations

import gzip
import json
import re
import threading
import weakref

import jax

__all__ = [
    "reset", "register_program", "programs", "Program", "abstract",
    "scope_map_from_hlo", "op_paths_from_hlo", "phase_of", "attribute",
    "parse_trace",
    "merge_intervals", "device_pids", "is_device_event", "step_spans",
    "step_rows", "load_trace", "DEVICE_PID_HINTS",
]


# ---------------------------------------------------------------------------
# shared trace-parsing core — ONE parser for :func:`attribute` and the
# offline ``telemetry --ingest-xla`` CLI (aggregate.ingest_xla delegates
# here); same interval union, same ``_row`` step-window convention
# ---------------------------------------------------------------------------

DEVICE_PID_HINTS = ("tpu", "gpu", "/device:", "accelerator")


def merge_intervals(ivs):
    """Union of (t0, t1) intervals: (merged list, total covered)."""
    if not ivs:
        return [], 0.0
    ivs = sorted(ivs)
    out = [list(ivs[0])]
    for t0, t1 in ivs[1:]:
        if t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out, sum(t1 - t0 for t0, t1 in out)


def device_pids(events):
    """Device-named process tracks from the metadata stream."""
    pids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pname = str((ev.get("args") or {}).get("name", "")).lower()
            if any(h in pname for h in DEVICE_PID_HINTS):
                pids.add(ev.get("pid"))
    return pids


def is_device_event(ev, dpids):
    """Does this complete ("X") span represent DEVICE execution?  Four
    signals, any one suffices: our own sync-mode spans carry
    ``args.device_time``; XLA profiler traces put device ops on
    device-named process tracks; a ``cat`` naming the device; an
    ``args.hlo_op``/``hlo_module`` stamp (the XLA op stream — on the
    CPU backend these land on a '/host:CPU' track that the pid hints
    alone would miss)."""
    args = ev.get("args") or {}
    if args.get("device_time"):
        return True
    if "hlo_op" in args or "hlo_module" in args:
        return True
    if ev.get("pid") in dpids:
        return True
    pid = str(ev.get("pid", "")).lower()
    cat = str(ev.get("cat", "")).lower()
    return any(h in pid for h in DEVICE_PID_HINTS) or "device" in cat


def load_trace(path_or_doc):
    """Chrome-trace events from a path (``.json`` or ``.json.gz``), a
    parsed dict, or a bare event list."""
    doc = path_or_doc
    if isinstance(path_or_doc, str):
        opener = gzip.open if path_or_doc.endswith(".gz") else open
        with opener(path_or_doc, "rt") as f:
            doc = json.load(f)
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError("not a chrome trace: no traceEvents list")
    return events


def step_spans(events):
    """Group device-busy spans by their ``args.step`` stamp (None pools
    the unstamped).  Returns ``(by_step, n_device, dpids)`` —
    ``by_step`` maps step id → [(t0, t1), ...] in seconds."""
    dpids = device_pids(events)
    by_step = {}
    n_device = 0
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if not is_device_event(ev, dpids):
            continue
        n_device += 1
        t0 = float(ev["ts"]) * 1e-6
        t1 = t0 + float(ev["dur"]) * 1e-6
        step = (ev.get("args") or {}).get("step")
        if step is not None:
            try:        # externally produced traces stamp steps as
                step = int(step)    # strings — normalize so "7" and 7
            except (TypeError, ValueError):     # pool together
                pass
        by_step.setdefault(step, []).append((t0, t1))
    return by_step, n_device, dpids


def step_rows(by_step):
    """The device-ledger row convention shared by ``--ingest-xla`` and
    :func:`attribute`: per-step busy unions, step windows
    chained previous-end → this-end (so ``busy_s + idle_s == wall_s``
    holds exactly per row), and a UNION total
    (not a sum — the pooled unattributed row's window overlaps the
    stamped rows').  Returns ``(rows, nonmono, total)``."""
    nonmono = []

    def _row(step, w0):
        merged, busy = merge_intervals(by_step[step])
        if w0 is None:
            w0 = merged[0][0]
        w1 = merged[-1][1]
        if w1 < w0:
            # id order disagrees with time order (a restarted step
            # counter, a merged multi-capture): the chained window start
            # sits past every span of this step, so wall/busy clamp to
            # 0 — real device time vanishes from the row.  Surface it
            # instead of zeroing silently
            nonmono.append(step)
        wall = max(w1 - w0, 0.0)
        busy = min(busy, wall)
        return {"step": step, "wall_s": round(wall, 6),
                "busy_s": round(busy, 6),
                "idle_s": round(wall - busy, 6),
                "busy_fraction": round(busy / wall, 4) if wall > 0
                else 0.0,
                "spans": len(by_step[step])}, w1

    rows = []
    # non-numeric stamps sort after numeric ones (never against them —
    # a mixed int/str sort would TypeError)
    stamped = sorted((s for s in by_step if s is not None),
                     key=lambda s: (1, str(s)) if isinstance(s, str)
                     else (0, s))
    prev_end = None
    for step in stamped:
        row, prev_end = _row(step, prev_end)
        rows.append(row)
    if None in by_step:
        rows.append(_row(None, None)[0])
    if by_step:
        merged, total_busy = merge_intervals(
            [sp for spans in by_step.values() for sp in spans])
        total_wall = merged[-1][1] - merged[0][0]
        total_busy = min(total_busy, total_wall)
    else:
        total_wall = total_busy = 0.0
    total = {"wall_s": round(total_wall, 6),
             "busy_s": round(total_busy, 6),
             "idle_s": round(total_wall - total_busy, 6),
             "busy_fraction": round(total_busy / total_wall, 4)
             if total_wall > 0 else 0.0}
    return rows, nonmono, total


# ---------------------------------------------------------------------------
# scope maps — op→phase tables parsed from the OPTIMIZED HLO of a
# compiled program.  The profiler's chrome trace names events after
# post-fusion HLO ops (``args.hlo_op``) and does NOT carry the
# named_scope strings; the scopes live in each op's metadata
# ``op_name`` path, which the executable's ``as_text()`` preserves.
# ---------------------------------------------------------------------------

# parens and whitespace are excluded: XLA wraps DERIVED ops' op_name
# paths in call syntax ("transpose(.../xray:forward)"), and a token
# class admitting ")" would mint a spurious "forward)" phase next to
# "forward"
_SCOPE_TOKEN = re.compile(r"xray:([^/\"\\()\s]+)")
_HLO_META = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=.*?"
    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def phase_of(op_name_path):
    """First ``xray:<phase>`` token of an HLO op_name path, or None."""
    m = _SCOPE_TOKEN.search(op_name_path or "")
    return m.group(1) if m else None


def op_paths_from_hlo(hlo_text):
    """Parse ``metadata={op_name="..."}`` from optimized HLO text into
    ``{hlo_op_name: op_name path}``: the whole path, in which
    :func:`phase_of` finds the phase and a reader a ``named_scope`` of its
    own (``flash_attention_bwd``, a Block's name)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _phases(op_paths):
    """``{op: phase}`` of the ops whose path carries an ``xray:`` scope."""
    phases = ((op, phase_of(path)) for op, path in op_paths.items())
    return {op: phase for op, phase in phases if phase is not None}


def scope_map_from_hlo(hlo_text):
    """``{hlo_op_name: phase}`` of optimized HLO text (ops without an
    ``xray:`` scope are left out — they pool into "unattributed" at
    attribution time, which is what the conservation contract accounts
    for)."""
    return _phases(op_paths_from_hlo(hlo_text))


def _norm_module(name):
    """A program's name in a trace (``args.hlo_module``, or an event of
    the ``XLA Modules`` line) → registry key: strip the ``jit_`` prefix,
    the ``(fingerprint)`` the modules line appends and any ``.N``
    uniquifier suffix."""
    name = re.sub(r"\(\d+\)$", "", str(name or ""))
    if name.startswith("jit_"):
        name = name[4:]
    return re.sub(r"\.\d+$", "", name)


# ---------------------------------------------------------------------------
# program registry — the train paths feed it (register_program), captures
# resolve scope maps from it lazily
# ---------------------------------------------------------------------------

_reg_lock = threading.Lock()
_programs = {}              # name -> Program


def _cost_summary(compiled):
    """flops / bytes-accessed / peak-alloc estimates of one compiled
    executable (``jax.stages.Compiled``) — best-effort: backends that
    expose neither analysis yield an empty dict."""
    out = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if "flops" in ca:
                out["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for field, key in (("temp_size_in_bytes", "temp_bytes"),
                           ("argument_size_in_bytes", "argument_bytes"),
                           ("output_size_in_bytes", "output_bytes"),
                           ("generated_code_size_in_bytes", "code_bytes")):
            v = getattr(ma, field, None)
            if v is not None:
                out[key] = float(v)
    except Exception:
        pass
    return out


def abstract(args):
    """The shapes, dtypes and shardings of a tree of arrays: what
    ``jitted.lower`` needs, and nothing that holds a buffer."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        args)


class Program(object):
    """One registered program of a train path.

    ``name`` is the jitted function's, as the trace's ``XLA Modules`` line
    has it once through :func:`_norm_module`; ``phase`` is the phase all
    of its device time belongs to, or None when its ops carry ``xray:``
    scopes of their own.  ``ops`` ({HLO op: op_name path}, of the
    optimized HLO) and ``memory`` (:func:`_cost_summary`) are read from
    the compiled executable the first time either is asked for: the
    registry holds the jitted function weakly with the abstract arguments
    it ran on, and lowers and compiles from them then (a read of the
    compile cache for a program that has run).  A program that is gone,
    or will not compile, has neither."""

    def __init__(self, name, compiled, phase=None):
        self.name, self.phase = name, phase
        self._compiled = compiled       # () -> jax.stages.Compiled or None
        self._ops = self._memory = None
        self.error = None               # why ops and memory are empty

    def _resolve(self):
        if self._ops is None:
            self._ops, self._memory = {}, {}
            try:
                compiled = self._compiled()
                if compiled is not None:
                    self._ops = op_paths_from_hlo(compiled.as_text())
                    self._memory = _cost_summary(compiled)
                else:
                    self.error = "the program is gone"
            except Exception as e:      # observation never fails a step
                self.error = repr(e)

    @property
    def ops(self):
        self._resolve()
        return self._ops

    @property
    def memory(self):
        self._resolve()
        return self._memory

    def scope_map(self):
        """``{HLO op: phase}`` of the ops that carry an ``xray:`` scope."""
        return _phases(self.ops)


def register_program(name, jitted, args, phase=None):
    """Hand a jitted program of a train path to the registry, lazily:
    the function (held weakly) and the abstract form of the arguments it
    was just called with.  Nothing is lowered, compiled or printed until
    a reader asks (:func:`programs`).  ``phase`` says that the whole
    program belongs to one phase (``forward``, ``backward``, ``update``);
    without it the program's own ``xray:`` scopes say."""
    ref, avals = weakref.ref(jitted), abstract(args)

    def compiled():
        fn = ref()
        return None if fn is None else fn.lower(*avals).compile()

    with _reg_lock:
        _programs[name] = Program(name, compiled, phase)


def programs():
    """The registered programs by name (:class:`Program`)."""
    with _reg_lock:
        return dict(_programs)


def _scope_maps():
    """Resolve the registry into ``{program_name: {op: phase}}``,
    parsing each live executable's optimized HLO lazily (once per
    build)."""
    return {name: prog.scope_map() for name, prog in programs().items()
            if prog.ops}


# ---------------------------------------------------------------------------
# attribution — the conservation-exact partition
# ---------------------------------------------------------------------------

def attribute(events, scope_maps=None, top_k=8):
    """Partition a capture's device ops over xray phases.

    Every device-op span lands in EXACTLY ONE bin — its scope's phase,
    or ``unattributed`` (scope-less ops, ops of unregistered programs)
    — and durations accumulate as integer nanoseconds, so::

        sum(phase device seconds) + unattributed == program device span

    holds exactly (``conservation_ok``).  The span here is the summed
    device-busy time of the capture; the union window rides along as
    ``span`` (true device-side t0/t1 in the trace timebase) and the
    shared step-row ledger as ``ledger``.
    """
    if scope_maps is None:
        scope_maps = _scope_maps()
    by_step, n_device, dpids = step_spans(events)
    phase_ns = {}
    op_ns = {}                  # (phase, op) -> [ns, count]
    module_ns = {}
    unattr_ns = 0
    total_ns = 0
    all_iv = []
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if not is_device_event(ev, dpids):
            continue
        args = ev.get("args") or {}
        ns = int(round(float(ev["dur"]) * 1000.0))   # trace dur is µs
        total_ns += ns
        t0 = float(ev["ts"]) * 1e-6
        all_iv.append((t0, t0 + float(ev["dur"]) * 1e-6))
        module = _norm_module(args.get("hlo_module"))
        op = str(args.get("hlo_op") or ev.get("name") or "")
        phase = scope_maps.get(module, {}).get(op) if module else None
        if module:
            module_ns[module] = module_ns.get(module, 0) + ns
        if phase is None:
            unattr_ns += ns
            key = (None, op)
        else:
            phase_ns[phase] = phase_ns.get(phase, 0) + ns
            key = (phase, op)
        cell = op_ns.setdefault(key, [0, 0])
        cell[0] += ns
        cell[1] += 1
    rows, nonmono, total = step_rows(by_step)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1][0])[:top_k]
    merged, _busy = merge_intervals(all_iv)
    span = {"t0": merged[0][0], "t1": merged[-1][1]} if merged else None
    return {
        "device_events": n_device,
        "phases": {p: {"device_s": ns * 1e-9,
                       "share": ns / total_ns if total_ns else 0.0}
                   for p, ns in sorted(phase_ns.items())},
        "unattributed_s": unattr_ns * 1e-9,
        "program_device_s": total_ns * 1e-9,
        "conservation_ok": sum(phase_ns.values()) + unattr_ns == total_ns,
        "span": span,
        "modules": {m: ns * 1e-9 for m, ns in sorted(module_ns.items())},
        "top_ops": [{"op": op or "<unnamed>", "phase": ph,
                     "device_s": cell[0] * 1e-9, "count": cell[1]}
                    for (ph, op), cell in top],
        "ledger": {"steps": rows, "total": total,
                   "nonmonotonic_steps": sorted(nonmono, key=str)},
    }


def parse_trace(path_or_doc, scope_maps=None):
    """One-call offline twin of a live capture: load + attribute."""
    return attribute(load_trace(path_or_doc), scope_maps=scope_maps)


def reset():
    """Drop the program registry (tests)."""
    with _reg_lock:
        _programs.clear()
