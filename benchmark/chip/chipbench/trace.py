"""From the profiler's trace to numbers.

Two stages, so that the second can be checked on a small recorded trace
(``testdata/``, ``tests/chip_bench/test_chip_bench_trace.py``):

1. ``record`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
   keeps what the reduction needs, as plain lists: every event of the device
   planes, and the host's ``bench:`` spans (``jax.profiler.TraceAnnotation``
   written by the harness and the drivers).
2. ``reduce`` turns that into the window, the busy union, the per-operation
   and per-category sums, program executions, collective time and its
   exposed part, and the idle gaps by the host span they fall in.

All times in a recorded trace are nanoseconds on the profiler's one clock,
which host and device events share.
"""
import bisect
import collections
import glob
import math
import os
import re

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")

# On this runtime an operation's event is named by its whole HLO instruction,
# "%fusion.46 = (f32[64]{...}, ...) fusion(...), kind=kLoop, calls=...", and
# carries no category statistic: name, category and result type are cut from
# that text.
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def parse_instruction(text):
    """``(short name, category, result type)`` of an HLO instruction's
    text.  The category is the opcode, with the fusion kind or the
    custom-call target after a colon; the result type is the largest array
    of the result."""
    short, _, rest = text.partition(" = ")
    rest = " " + rest
    opcode = _OPCODE.search(rest)
    category = opcode.group(1) if opcode else ""
    detail = {"fusion": _KIND, "custom-call": _TARGET}.get(category)
    detail = detail.search(rest) if detail else None
    if detail:
        category += ":" + detail.group(1)
    arrays = _ARRAY.findall(rest[:opcode.start()] if opcode else rest)
    return short, category, max(arrays, key=_elements, default="")


def _elements(array):
    dims = array[array.index("[") + 1:-1]
    return math.prod(int(d) for d in dims.split(",")) if dims else 1


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError("the profiler left no .xplane.pb under %s"
                           % trace_dir)
    return found[-1]


def record(xplane_path):
    """Stage 1.  Returns ``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, duration_ns], ...]}]}], "ops": {name: [category,
    result type]}}`` with the device planes whole and the host planes cut to
    their ``bench:`` spans.  An operation's name is its short HLO name
    (``%fusion.46``); where two different instructions share one (programs
    number their instructions alike) the later ones get ``~2``, ``~3``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes, ops, names, taken = [], {}, {}, collections.Counter()
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                text = ev.name
                if on_device:
                    if text not in names:
                        short, category, result = parse_instruction(text)
                        taken[short] += 1
                        if taken[short] > 1:
                            short += "~%d" % taken[short]
                        names[text] = short
                        ops[short] = [category, result]
                    text = names[text]
                elif not text.startswith(SPAN_PREFIX):
                    continue
                events.append([text, int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "ops": ops}


# ---------------------------------------------------------------------------
# stage 2
# ---------------------------------------------------------------------------

def union(intervals):
    """Merge ``(start, end)`` intervals; returns them sorted and disjoint."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """The parts of the disjoint sorted ``intervals`` that the disjoint
    sorted ``cover`` does not cover."""
    out, j = [], 0
    for start, end in intervals:
        while j < len(cover) and cover[j][1] <= start:
            j += 1
        k, cur = j, start
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def self_times(events):
    """``(name, self nanoseconds)`` of every ``(name, start, end)`` event of
    one line: its duration less that of the events nested directly in it (a
    ``while`` holds its body's operations), so that the sums count every
    nanosecond once."""
    out, stack = [], []              # stack of [name, end, self]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            out.append((stack[-1][0], stack.pop()[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    out += [(name, own) for name, _, own in stack]
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace):
    found = [(int(DEVICE_PLANE.match(p["name"]).group(1)), p)
             for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return [p for _, p in sorted(found, key=lambda item: item[0])]


def host_spans(trace):
    """``(name, start, end)`` of every ``bench:`` span on a host plane."""
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans += [(n, s, s + d) for n, s, d in line["events"]
                      if n.startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda sp: sp[1])


def step_window(spans, skip=2):
    """The window and the steps in it: from the end of the third
    ``bench:wait`` span to the end of the last.  Each wait ends when the
    host saw a step's loss ready, so the window holds a whole number of
    steps; the first ``skip`` are left out because starting the profiler
    drains the pipeline, and stopping it comes after the last wait."""
    ends = [e for n, _, e in spans if n == SPAN_PREFIX + "wait"][skip:]
    if len(ends) < 2:
        return None, 0
    return (ends[0], ends[-1]), len(ends) - 1


def innermost_timeline(spans):
    """Cut overlapping (nested) spans into disjoint segments, each named by
    the shortest span that covers it.  Returns ``(starts, ends, names)``."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    by_length = sorted(spans, key=lambda sp: sp[2] - sp[1])
    starts, ends, names = [], [], []
    for lo, hi in zip(bounds, bounds[1:]):
        for name, s, e in by_length:
            if s <= lo and hi <= e:
                starts.append(lo), ends.append(hi), names.append(name)
                break
    return starts, ends, names


def attribute_gaps(gaps, spans, none="(no bench span)"):
    """Seconds of idle time by the innermost host span they fall in."""
    starts, ends, names = innermost_timeline(spans)
    by_name = collections.Counter()
    for lo, hi in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(starts) and starts[i] < hi:
            part = min(hi, ends[i]) - max(lo, starts[i])
            if part > 0:
                by_name[names[i]] += part
                covered += part
            i += 1
        if hi - lo > covered:
            by_name[none] += hi - lo - covered
    return {n: ns / 1e9 for n, ns in by_name.items()}


def reduce(trace):
    """Stage 2.  Returns None when the trace holds no device plane or fewer
    than two ``bench:wait`` spans (the CPU rehearsal); otherwise a dict, all
    times in seconds and averaged over the device planes unless said:

    ``window_s``, ``steps``, ``devices``, ``busy_s``, ``idle_share``,
    ``op_s`` {operation name: seconds} and ``category_s`` {category:
    seconds}, both of self time (an operation nested in another counts
    once),
    ``programs`` (program executions that started in the window on the
    first device), ``collective_s`` and ``collective_exposed_s`` (first
    device: the collectives' union, and the part during which no other
    operation ran there), ``idle_gaps_s`` {host span: seconds} of the first
    device's idle time."""
    planes = device_planes(trace)
    spans = host_spans(trace)
    window, steps = step_window(spans)
    if not planes or window is None:
        return None
    ops = trace.get("ops", {})
    n = len(planes)
    spans_inside = [(name, max(s, window[0]), min(e, window[1]))
                    for name, s, e in spans
                    if e > window[0] and s < window[1]]
    busy_s = 0.0
    op_s, category_s = collections.Counter(), collections.Counter()
    first = {}
    for i, plane in enumerate(planes):
        events = _line(plane, OPS_LINE)
        inside = [(name, max(s, window[0]), min(s + d, window[1]))
                  for name, s, d in events
                  if s + d > window[0] and s < window[1]]
        busy = union((s, e) for _, s, e in inside)
        busy_s += total(busy) / 1e9 / n
        for name, own in self_times(inside):
            op_s[name] += own / 1e9 / n
            category_s[ops.get(name, ["", ""])[0]] += own / 1e9 / n
        if i == 0:
            # a collective may run asynchronously beside the operations
            # of the main line: both lines count
            in_flight = [(name, max(s, window[0]), min(s + d, window[1]))
                         for name, s, d in _line(plane, ASYNC_OPS_LINE)
                         if s + d > window[0] and s < window[1]]
            coll = union((s, e) for name, s, e in inside + in_flight
                         if COLLECTIVE.search(name))
            other = union((s, e) for name, s, e in inside
                          if not COLLECTIVE.search(name))
            first = {
                "programs": sum(1 for _, s, _ in _line(plane, MODULES_LINE)
                                if window[0] <= s < window[1]),
                "collective_s": total(coll) / 1e9,
                "collective_exposed_s": total(subtract(coll, other)) / 1e9,
                "idle_gaps_s": attribute_gaps(
                    subtract([window], busy), spans_inside),
            }
    window_s = (window[1] - window[0]) / 1e9
    return dict(first, window_s=window_s, steps=steps, devices=n,
                busy_s=busy_s, idle_share=1.0 - busy_s / window_s,
                op_s=dict(op_s), category_s=dict(category_s))


def breakdown(reduced, ops, limit=10):
    """The ``breakdown`` of a traced run's result line: the categories and
    the operations that took most device time (XLA's own names, category
    and result type in brackets) and the idle time by host span; at most
    ``limit`` each."""
    cats = sorted(reduced["category_s"].items(), key=lambda kv: -kv[1])
    names = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])
    n_cat = min(4, len(cats))
    device_ops = [["category:" + (c or "none"), s] for c, s in cats[:n_cat]]
    device_ops += [["%s [%s %s]" % ((name,) + tuple(ops.get(name, ["", ""]))),
                    s] for name, s in names[:limit - n_cat]]
    gaps = sorted(reduced["idle_gaps_s"].items(), key=lambda kv: -kv[1])
    return {"device_ops": device_ops,
            "idle_gaps": [[name, s] for name, s in gaps[:limit]]}
