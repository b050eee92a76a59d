"""Cross-rank trace aggregation + straggler analytics.

One rank's trace answers *where did my step time go*; it cannot answer
the second question that dominates distributed step time (EQuARX,
arXiv:2506.17615): **which rank made everyone wait?**  A
sync collective exits everywhere at once, so the rank that *entered*
last paid nothing and billed its lateness to every peer — visible only
by putting all ranks' timelines side by side.

This module merges N per-rank artifacts — chrome traces dumped by the
profiler and/or graftwatch flight-recorder dumps, mixed freely — into:

* **one merged chrome trace**: each rank is its own labeled process
  track (``process_name`` metadata), every collective/flush/step lands
  at its clock-aligned wall time, and each cross-rank collective gets a
  flow link (``s`` on the first rank to enter, ``t`` hops, ``f`` on the
  last) so the trace UI draws the arrow from the early rank into the
  straggler;
* **a straggler table**: per (step, collective): last-to-enter rank,
  last-to-exit rank, enter-spread and exit-spread seconds, plus a blame
  summary: how often each rank entered last and how many seconds its
  peers waited for it; the worst rank is the one that cost the most
  seconds, so a dozen ties at start-up, a tenth of a millisecond apart,
  do not outvote four waits of 200 ms.

Clock alignment uses the sync points the system already has: the
piggybacked heartbeat ``(ts, step)`` samples (graftwatch, PR 6) and
SYNC collective exits matched by the SPMD-lockstep sequence number — a
sync allreduce returns everywhere at (nearly) the same instant, so the
median pairwise delta of matched anchors IS the clock offset.  Async
reduces (graftlap's ``reduce_many_async``) are excluded from anchors
and from exit stats: their recorded exit is the host-local wait-return,
not a wire instant (their issue-time *enter* remains valid straggler
evidence).  A lone dump falls back to the ``clock_offset_s`` recorded
in its header.  Note the consequence: exit spreads are measured
*around the median sync behavior*, so they surface per-collective
anomalies, while enter spreads carry the full straggler signal.

CLI: ``python -m incubator_mxnet_tpu.telemetry --analyze R0.json
R1.json [--json | --merged OUT.json]``; ``--analyze --selftest`` is the
lint smoke tier (two synthetic rank dumps with a deliberately delayed
rank → merged trace must validate, every reduced bucket must get a
cross-rank flow link, and the table must blame the delayed rank).
"""
from __future__ import annotations

import json
import os
import statistics
import tempfile

from . import tracing as _tracing

__all__ = ["load_artifact", "parse_artifact", "clock_offsets",
           "merged_trace", "straggler_table", "lockstep_check",
           "analyze", "selftest", "ingest_xla"]

_BLACKBOX_SCHEMA = "graft-blackbox/1"


# ---------------------------------------------------------------------------
# artifact loading: blackbox dumps + chrome traces → one common shape
# ---------------------------------------------------------------------------

def load_artifact(path):
    """Parse one per-rank artifact file (auto-detects the format)."""
    with open(path) as f:
        doc = json.load(f)
    return parse_artifact(doc, source=os.path.basename(path))


def parse_artifact(doc, source="<memory>"):
    """Parse an already-loaded artifact dict.  Returns the common
    artifact shape: ``{kind, source, rank, collectives, heartbeats,
    spans, events, clock_offset_s}`` with all times in wall-clock
    seconds."""
    if isinstance(doc, dict) and doc.get("schema") == _BLACKBOX_SCHEMA:
        return _parse_dump(doc, source)
    if isinstance(doc, dict) and isinstance(doc.get("traceEvents"), list):
        return _parse_trace(doc, source)
    raise ValueError("%s: neither a graftwatch dump (schema %r) nor a "
                     "chrome trace (traceEvents)" % (source,
                                                     _BLACKBOX_SCHEMA))


def _collective_key(data, per_path_seq):
    """Cross-rank matching key for one collective.  The lockstep ``seq``
    stamp is exact; artifacts predating it fall back to per-path
    occurrence order (still correct under the lockstep contract)."""
    seq = data.get("seq")
    if seq is not None:
        return ("seq", int(seq))
    path = data.get("path") or "collective"
    n = per_path_seq[path] = per_path_seq.get(path, 0) + 1
    return ("path", path, n)


def _parse_dump(doc, source):
    rank = doc.get("rank")
    colls, hbs, spans = [], [], []
    per_path_seq = {}
    for e in doc.get("events") or []:
        kind, data = e.get("kind"), e.get("data") or {}
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        if kind == "collective":
            dur = max(float(data.get("latency_ms") or 0.0) / 1e3, 0.0)
            colls.append({
                "key": _collective_key(data, per_path_seq),
                "step": data.get("step"),
                "label": data.get("bucket") or data.get("path",
                                                        "collective"),
                "path": data.get("path"),
                "enter": ts - dur, "exit": ts,
                "nbytes": data.get("nbytes"),
                "n_keys": data.get("n_keys"),
            })
        elif kind == "dist_heartbeat":
            hbs.append({"hb": data.get("step"), "ts": ts})
        else:
            spans.append({"kind": kind, "ts": ts, "data": data})
    return {"kind": "blackbox", "source": source,
            "rank": int(rank) if rank is not None else None,
            "collectives": colls, "heartbeats": hbs, "spans": spans,
            "events": None, "anchor": None,
            "clock_offset_s": doc.get("clock_offset_s"),
            "lockstep": doc.get("lockstep")}


def _parse_trace(doc, source):
    events = doc["traceEvents"]
    other = doc.get("otherData") or {}
    rank = other.get("rank")
    if rank is None:
        for e in events:
            if isinstance(e, dict) and e.get("ph") == "M" \
                    and e.get("name") == "process_name":
                name = (e.get("args") or {}).get("name", "")
                parts = name.split()
                if len(parts) >= 2 and parts[0] == "rank":
                    try:
                        rank = int(parts[1])
                    except ValueError:
                        pass
                    break
    anchor = other.get("wall_anchor")
    wall = _wall_fn(anchor)
    colls = []
    per_path_seq = {}
    for e in events:
        if isinstance(e, dict) and e.get("ph") == "X" \
                and e.get("cat") == "collective":
            args = e.get("args") or {}
            enter = wall(e.get("ts", 0.0))
            colls.append({
                "key": _collective_key(args, per_path_seq),
                "step": args.get("step"),
                "label": args.get("bucket") or args.get("path",
                                                        e.get("name")),
                "path": args.get("path"),
                "enter": enter,
                "exit": wall(e.get("ts", 0.0) + e.get("dur", 0.0)),
                "nbytes": args.get("nbytes"),
                "n_keys": args.get("n_keys"),
            })
    return {"kind": "trace", "source": source,
            "rank": int(rank) if rank is not None else None,
            "collectives": colls, "heartbeats": [], "spans": [],
            "events": events, "anchor": anchor,
            "clock_offset_s": other.get("clock_offset_s")}


def _wall_fn(anchor):
    if anchor and "wall_s" in anchor and "perf_us" in anchor:
        wall_s, perf_us = float(anchor["wall_s"]), float(anchor["perf_us"])
        return lambda ts_us: wall_s + (ts_us - perf_us) / 1e6
    return lambda ts_us: ts_us / 1e6


def _assign_ranks(artifacts):
    """Fill missing ranks with unclaimed ints.  Several artifacts MAY
    share a rank (that rank's profiler trace AND its blackbox dump —
    'mixed freely'): they merge onto one track and their collectives
    dedup per (key, rank)."""
    claimed = {a["rank"] for a in artifacts if a["rank"] is not None}
    nxt = 0
    for a in artifacts:
        if a["rank"] is None:
            while nxt in claimed:
                nxt += 1
            a["rank"] = nxt
            claimed.add(nxt)
    return []


# ---------------------------------------------------------------------------
# clock alignment
# ---------------------------------------------------------------------------

# Async reduces (graftlap) and async weight pulls (graftduplex) are
# recorded at wait-return/abandon time — a HOST-local instant, not the
# wire-synchronized exit a sync allreduce has.  They are valid
# straggler-ENTER evidence (enter = issue time) but must never serve as
# clock anchors or exit-spread evidence: a healthy 40ms host lag before
# wait() would otherwise fabricate a 40ms clock offset and blame an
# innocent rank.  Sync pull collectives (path "pull") keep full exit
# standing.  Mirror of blackbox._NO_STRAGGLER_PATHS.
_ASYNC_PATHS = frozenset(["reduce_many_async", "pull_many_async"])


def _anchors(artifact):
    out = {}
    for h in artifact["heartbeats"]:
        if h["hb"] is not None:
            out[("hb", h["hb"])] = h["ts"]
    for c in artifact["collectives"]:
        if c.get("path") not in _ASYNC_PATHS:
            out[("c",) + c["key"]] = c["exit"]
    return out


def clock_offsets(artifacts):
    """Per-rank clock offset (seconds to SUBTRACT from that rank's
    timestamps) relative to the first artifact's rank, from the median
    delta of matched sync anchors (heartbeats by step, sync collective
    exits by lockstep seq).  Artifacts sharing a rank (trace + dump of
    one process share one clock) pool their anchors."""
    anchors_by_rank, hints = {}, {}
    for a in artifacts:
        anchors_by_rank.setdefault(a["rank"], {}).update(_anchors(a))
        if a.get("clock_offset_s") is not None:
            hints.setdefault(a["rank"], float(a["clock_offset_s"]))
    ref_rank = artifacts[0]["rank"]
    ref_anchors = anchors_by_rank[ref_rank]
    out = {ref_rank: 0.0}
    for rank, mine in anchors_by_rank.items():
        if rank == ref_rank:
            continue
        deltas = [mine[k] - ref_anchors[k] for k in mine
                  if k in ref_anchors]
        if deltas:
            off = statistics.median(deltas)
        elif rank in hints and ref_rank in hints:
            off = hints[ref_rank] - hints[rank]
        else:
            off = 0.0
        out[rank] = off
    return out


# ---------------------------------------------------------------------------
# the merged trace
# ---------------------------------------------------------------------------

def _matched_collectives(artifacts):
    """key -> [(rank, collective)], one entry per (key, rank): a rank's
    trace and dump both record the same wire collective — the first
    artifact claiming a (key, rank) wins, so same-rank artifacts can
    never fake a cross-rank match against themselves."""
    by_key = {}
    seen = set()
    for a in artifacts:
        for c in a["collectives"]:
            if (c["key"], a["rank"]) in seen:
                continue
            seen.add((c["key"], a["rank"]))
            by_key.setdefault(c["key"], []).append((a["rank"], c))
    return by_key


def _min_time(a):
    times = [c["enter"] for c in a["collectives"]]
    times += [h["ts"] for h in a["heartbeats"]]
    # span events are stamped at their END; the merged X event starts at
    # ts - latency, so the time base must cover the start or rel()'s
    # clamp-to-zero would stretch the earliest span over the origin
    times += [s["ts"] - max(float(s["data"].get("latency_ms") or 0.0),
                            0.0) / 1e3
              for s in a["spans"]]
    if a["kind"] == "trace":
        wall = _wall_fn(a["anchor"])
        times += [wall(e["ts"]) for e in a["events"]
                  if isinstance(e, dict) and isinstance(e.get("ts"),
                                                        (int, float))]
    return min(times) if times else 0.0


def merged_trace(artifacts, offsets=None):
    """Build ONE chrome trace over all ranks: per-rank process tracks
    (pid = rank), clock-aligned events, and one cross-rank flow link per
    collective observed on >= 2 ranks.  Returns ``(trace_dict,
    n_cross_rank_links)``."""
    offsets = offsets if offsets is not None else clock_offsets(artifacts)
    t0 = min((_min_time(a) - offsets[a["rank"]] for a in artifacts),
             default=0.0)

    def rel(ts, rank):
        return max((ts - offsets[rank] - t0) * 1e6, 0.0)

    events = []
    labeled = set()
    for a in artifacts:
        rank = a["rank"]
        if rank not in labeled:     # one metadata set per TRACK, even
            labeled.add(rank)       # when several artifacts share it
            role = "+".join(sorted({x["kind"] for x in artifacts
                                    if x["rank"] == rank}))
            events += _tracing.process_metadata_events(
                rank=rank, role=role, pid=rank)
        if a["kind"] == "blackbox":
            events += _dump_events(a, rank, rel)
        else:
            events += _trace_events(a, rank, rel)
    links = _cross_rank_links(artifacts, offsets, rel, events)
    ranks = sorted(a["rank"] for a in artifacts)
    trace = {"traceEvents": events, "displayTimeUnit": "ms",
             "otherData": {"merged_ranks": ranks,
                           "clock_offsets_s": {str(r): round(offsets[r], 6)
                                               for r in offsets},
                           "time_base_wall_s": t0}}
    return trace, links


def _dump_events(a, rank, rel):
    out = []
    for c in a["collectives"]:
        dur_us = max((c["exit"] - c["enter"]) * 1e6, 0.01)
        args = {"path": c["path"]}
        for k in ("step", "nbytes", "n_keys"):
            if c.get(k) is not None:
                args[k] = c[k]
        if c["key"][0] == "seq":
            args["seq"] = c["key"][1]
        out.append({"name": c["label"], "cat": "collective", "ph": "X",
                    "ts": rel(c["enter"], rank), "dur": dur_us,
                    "pid": rank, "tid": 0, "args": args})
    for s in a["spans"]:
        data, kind, ts = s["data"], s["kind"], s["ts"]
        if kind in ("engine_flush", "step"):
            dur = max(float(data.get("latency_ms") or 0.0) / 1e3, 0.0)
            name = "bulk_segment_flush" if kind == "engine_flush" \
                else "step"
            cat = "engine" if kind == "engine_flush" else "step"
            out.append({"name": name, "cat": cat, "ph": "X",
                        "ts": rel(ts - dur, rank),
                        "dur": max(dur * 1e6, 0.01),
                        "pid": rank, "tid": 0, "args": data})
        else:
            out.append({"name": kind, "cat": "blackbox", "ph": "i",
                        "ts": rel(ts, rank), "pid": rank, "tid": 0,
                        "s": "t", "args": data})
    for h in a["heartbeats"]:
        out.append({"name": "heartbeat", "cat": "dist", "ph": "i",
                    "ts": rel(h["ts"], rank), "pid": rank, "tid": 0,
                    "s": "t", "args": {"hb": h["hb"]}})
    return out


def _trace_events(a, rank, rel):
    wall = _wall_fn(a["anchor"])
    out = []
    for e in a["events"]:
        if not isinstance(e, dict):
            continue
        ph = e.get("ph")
        if ph == "M":
            continue            # replaced by the merge's own metadata
        ne = dict(e)
        ne["pid"] = rank
        if isinstance(ne.get("ts"), (int, float)):
            ne["ts"] = rel(wall(ne["ts"]), rank)
        if ph in ("s", "t", "f") and "id" in ne:
            # namespace single-rank flow ids so two ranks' segment
            # counters can never collide in the merged id space
            ne["id"] = "r%d/%s" % (rank, ne["id"])
        out.append(ne)
    return out


def _cross_rank_links(artifacts, offsets, rel, events):
    """One flow chain per collective seen on >= 2 ranks: s on the first
    rank to enter, t hops through the middle, f on the last — the arrow
    the trace UI draws INTO the straggler.  Bind points sit mid-slice so
    each hop attaches to that rank's collective span."""
    links = 0
    for key, rcs in sorted(_matched_collectives(artifacts).items(),
                           key=lambda kv: str(kv[0])):
        if len(rcs) < 2:
            continue
        rcs = sorted(rcs, key=lambda rc: rc[1]["enter"] - offsets[rc[0]])
        fid = "xr/" + "/".join(str(p) for p in key)
        for i, (rank, c) in enumerate(rcs):
            mid = rel(c["enter"], rank) \
                + max((c["exit"] - c["enter"]) * 1e6, 0.01) / 2.0
            ph = "s" if i == 0 else ("f" if i == len(rcs) - 1 else "t")
            ev = {"name": "xrank_collective", "cat": "xrank.flow",
                  "ph": ph, "id": fid, "ts": mid, "pid": rank, "tid": 0,
                  "args": {"step": c.get("step"), "label": c["label"]}}
            if ph == "f":
                ev["bp"] = "e"
            events.append(ev)
        links += 1
    return links


# ---------------------------------------------------------------------------
# straggler analytics
# ---------------------------------------------------------------------------

def straggler_table(artifacts, offsets=None):
    """Per (step × collective) rows + a blame summary.  ``rows`` are in
    key order; each carries last-to-enter/exit rank and the aligned
    enter/exit spreads in seconds."""
    offsets = offsets if offsets is not None else clock_offsets(artifacts)
    rows = []
    blame = {a["rank"]: 0 for a in artifacts}
    waited = {a["rank"]: 0.0 for a in artifacts}    # for it, by its peers
    for key, rcs in sorted(_matched_collectives(artifacts).items(),
                           key=lambda kv: str(kv[0])):
        if len(rcs) < 2:
            continue
        enters = {r: c["enter"] - offsets[r] for r, c in rcs}
        last_enter = max(enters, key=enters.get)
        step = next((c.get("step") for _r, c in rcs
                     if c.get("step") is not None), None)
        is_async = rcs[0][1].get("path") in _ASYNC_PATHS
        if is_async:
            # wait-return times are host-local: exit stats would blame
            # whichever rank's host got to wait() last, not the wire
            last_exit, exit_spread = None, None
        else:
            exits = {r: c["exit"] - offsets[r] for r, c in rcs}
            last_exit = max(exits, key=exits.get)
            exit_spread = round(max(exits.values())
                                - min(exits.values()), 6)
        rows.append({
            "key": list(key),
            "step": step,
            "label": rcs[0][1]["label"],
            "ranks": sorted(enters),
            "last_to_enter": last_enter,
            "last_to_exit": last_exit,
            "enter_spread_s": round(max(enters.values())
                                    - min(enters.values()), 6),
            "exit_spread_s": exit_spread,
        })
        blame[last_enter] = blame.get(last_enter, 0) + 1
        waited[last_enter] = waited.get(last_enter, 0.0) \
            + rows[-1]["enter_spread_s"]
    matched = len(rows)
    summary = {
        "collectives_matched": matched,
        "blame": {str(r): n for r, n in sorted(blame.items())},
        "wait_caused_s": {str(r): round(w, 6)
                          for r, w in sorted(waited.items())},
        "worst_rank": (max(waited, key=lambda r: waited[r])
                       if matched else None),
        "max_enter_spread_s": round(max((r["enter_spread_s"]
                                         for r in rows), default=0.0), 6),
        "mean_enter_spread_s": round(
            sum(r["enter_spread_s"] for r in rows) / matched, 6)
        if matched else 0.0,
    }
    return rows, summary


# ---------------------------------------------------------------------------
# lockstep divergence cross-check (grafttsan's auditor, offline half)
# ---------------------------------------------------------------------------

# host parameter-service RPCs are rank-asymmetric by design (async SGD):
# mirror of analysis/lockstep.py EXCLUDED_PATHS
_PS_PATHS = frozenset(["ps_push", "ps_pull", "ps_push_async"])


def lockstep_check(artifacts):
    """Audit the SPMD lockstep contract across rank artifacts: for every
    collective seq observed on >= 2 ranks, the identity ``(path,
    n_keys, nbytes, label)`` must agree — a mismatch names the exact
    divergent collective the online rolling hash (analysis/lockstep.py)
    could only bound.  Holes — a rank missing a seq inside its observed
    range while peers have it — catch skipped collectives.  Any online
    ``lockstep_divergence`` reports recorded in the dumps are surfaced
    too."""
    ranks = sorted({a["rank"] for a in artifacts})
    # a ps_* bracket consumes the shared seq counter at rank-dependent
    # timing (the dist_async background client), so on a ps-bearing
    # artifact set seq N on one rank is simply a DIFFERENT collective
    # than seq N on another — seq matching would blame healthy ranks.
    # The lockstep contract is a sync-wire contract; decline the audit
    # for async-wire sets (the online fold-index hash still covers them).
    has_ps = any(c.get("path") in _PS_PATHS
                 for a in artifacts for c in a["collectives"])
    by_seq = {}
    if not has_ps:
        for key, rcs in _matched_collectives(artifacts).items():
            if key[0] != "seq":
                continue
            sigs = {r: (c.get("path"), c.get("n_keys"), c.get("nbytes"),
                        c.get("label"))
                    for r, c in rcs}
            if sigs:
                by_seq[key[1]] = sigs
    mismatches, holes = [], []
    seq_range = {}              # rank -> (min seq, max seq) observed
    for seq, sigs in by_seq.items():
        for r in sigs:
            lo, hi = seq_range.get(r, (seq, seq))
            seq_range[r] = (min(lo, seq), max(hi, seq))
    for seq in sorted(by_seq):
        sigs = by_seq[seq]
        if len(set(sigs.values())) > 1:
            counts = {}
            for v in sigs.values():
                counts[v] = counts.get(v, 0) + 1
            majority = max(counts, key=counts.get)
            mismatches.append({
                "seq": seq,
                "per_rank": {str(r): list(v)
                             for r, v in sorted(sigs.items())},
                "divergent_ranks": sorted(r for r, v in sigs.items()
                                          if v != majority),
            })
        for r, (lo, hi) in seq_range.items():
            # only a hole INSIDE the rank's own observed range is
            # evidence (ring eviction trims the edges legitimately)
            if r not in sigs and lo < seq < hi:
                holes.append({"seq": seq, "missing_rank": r})
    online = []
    for a in artifacts:
        for s in a["spans"]:
            if s["kind"] == "lockstep_divergence":
                online.append(dict(s["data"], rank=a["rank"]))
        ls = a.get("lockstep") or {}
        if ls.get("divergence"):
            online.append(dict(ls["divergence"], rank=a["rank"],
                               source="dump-header"))
    bad_seqs = [m["seq"] for m in mismatches] + [h["seq"] for h in holes]
    divergent = sorted({r for m in mismatches
                        for r in m["divergent_ranks"]}
                       | {h["missing_rank"] for h in holes})
    report = {
        "seqs_checked": len(by_seq),
        "ranks": ranks,
        "first_divergent_seq": min(bad_seqs) if bad_seqs else None,
        "divergent_ranks": divergent,
        "mismatches": mismatches[:10],
        "holes": holes[:10],
        "online_reports": online[:10],
    }
    if has_ps:
        report["note"] = ("async wire (ps_* collectives present): seq "
                          "matching skipped — wire seqs are rank-skewed "
                          "by the background client; the online "
                          "fold-index hash remains authoritative")
    return report


# ---------------------------------------------------------------------------
# the full analysis (CLI entry)
# ---------------------------------------------------------------------------

def analyze(paths, merged_out=None):
    """Load every artifact, align clocks, merge, and analyze.  Returns
    ``(report, merged_trace_dict)``; the report's ``problems`` list is
    empty on a fully valid result (the CLI's exit code)."""
    artifacts = [load_artifact(p) for p in paths]
    problems = _assign_ranks(artifacts)
    offsets = clock_offsets(artifacts)
    trace, links = merged_trace(artifacts, offsets)
    problems += _tracing.validate_chrome_trace(trace)
    rows, summary = straggler_table(artifacts, offsets)
    ranks_info = {}
    for a in artifacts:
        info = ranks_info.setdefault(str(a["rank"]), {
            "sources": [], "collectives": 0, "heartbeats": 0})
        info["sources"].append("%s (%s)" % (a["source"], a["kind"]))
        info["collectives"] += len(a["collectives"])
        info["heartbeats"] += len(a["heartbeats"])
    report = {
        "ranks": ranks_info,
        "clock_offsets_s": {str(r): round(offsets[r], 6) for r in offsets},
        "merged_events": len(trace["traceEvents"]),
        "cross_rank_flow_links": links,
        "straggler_summary": summary,
        "stragglers": rows,
        "lockstep": lockstep_check(artifacts),
        "problems": problems,
    }
    if merged_out:
        with open(merged_out, "w") as f:
            json.dump(trace, f)
        report["merged_path"] = merged_out
    return report, trace


# ---------------------------------------------------------------------------
# profiler-trace ingestion
# ---------------------------------------------------------------------------

# the trace-parsing core (interval union, device-event detection, the
# per-step row convention) is SHARED with the online graftxray capture
# path — one parser, online + offline (telemetry/xray.py); the private
# names stay as aliases for the existing callers and tests
from . import xray as _xray

_merge_intervals = _xray.merge_intervals
_DEVICE_PID_HINTS = _xray.DEVICE_PID_HINTS


def ingest_xla(path_or_doc):
    """Rebuild the per-step device ledger OFFLINE from a chrome trace
    (``mx.profiler`` sync mode, external XLA profiler captures).

    Device-busy spans are unioned per step (``args.step`` stamps, the
    step id flush spans carry; unstamped device spans pool into one
    unattributed window).  Step windows run from the previous step's
    window end to this step's, so ``busy_s + idle_s == wall_s`` holds
    exactly per row.  The grouping, the union and the row
    convention are the graftxray shared core (``xray.step_spans`` /
    ``xray.step_rows``) — the online capture parser and this offline
    CLI cannot drift apart.  Returns the report dict (``steps`` rows +
    ``total``); CLI: ``telemetry --ingest-xla PATH [--json]``."""
    events = _xray.load_trace(path_or_doc)
    by_step, n_device, _dpids = _xray.step_spans(events)
    rows, nonmono, total = _xray.step_rows(by_step)
    report = {
        "device_events": n_device,
        "steps": rows,
        "total": total,
        "problems": [] if n_device else [
            "no device-busy spans found (no args.device_time spans, no "
            "device-named process track, no device cat) — was the trace "
            "captured with profiler sync mode or an XLA profiler?"],
    }
    if nonmono:
        report["problems"].append(
            "step ids are not time-monotonic (steps %s have every span "
            "before the previous step's window end — a restarted step "
            "counter or merged captures?): their wall_s/busy_s clamped "
            "to 0 and real device time is missing from those rows"
            % sorted(nonmono, key=str))
    return report


# ---------------------------------------------------------------------------
# selftest (lint smoke tier)
# ---------------------------------------------------------------------------

def _synthetic_dump(rank, delay_s, base=1700000000.0, steps=3,
                    buckets=("bucket[float32:4p:4096B]",
                             "bucket[float32:3p:3072B]")):
    """A minimal but schema-faithful flight-recorder dump: per step, one
    reduce collective per bucket (the delayed rank enters ``delay_s``
    late; every rank exits together, as a sync allreduce does) plus one
    piggybacked heartbeat."""
    events = []
    seq = 0
    for step in range(1, steps + 1):
        t_step = base + step * 0.5
        for b, label in enumerate(buckets):
            seq += 1
            slot = t_step + b * 0.05
            enter = slot + (delay_s if rank == 1 else 0.0)
            exit_ = slot + delay_s + 0.005
            events.append({"ts": exit_, "kind": "collective", "data": {
                "path": "reduce_many", "seq": seq, "step": step,
                "bucket": label, "n_keys": 1, "nbytes": 4096,
                "rank": rank,
                "latency_ms": round((exit_ - enter) * 1e3, 3)}})
        hb_t = t_step + 0.2
        events.append({"ts": hb_t, "kind": "dist_heartbeat",
                       "data": {"workers": 2, "step": step,
                                "skew_s": delay_s}})
        events.append({"ts": hb_t + 0.01, "kind": "engine_flush",
                       "data": {"segment": step, "cause": "autograd",
                                "nodes": 8, "live_outputs": 1,
                                "cache": "hit", "latency_ms": 2.0,
                                "step": step}})
        events.append({"ts": hb_t + 0.02, "kind": "step",
                       "data": {"origin": "trainer", "index": step,
                                "step": step, "latency_ms": 40.0,
                                "phases": {"kvstore": 0.02,
                                           "update": 0.01}}})
    return {
        "schema": _BLACKBOX_SCHEMA, "pid": 1000 + rank, "rank": rank,
        "clock_offset_s": 0.0, "reason": "manual",
        "dumped_at": base + 100.0, "started_at": base,
        "ring_size": 4096, "events_total": len(events),
        "last_progress": {"ts": base + 100.0, "site": "selftest",
                          "age": 0.0},
        "in_flight": [], "failures": [], "workers": {},
        "events": events, "threads": {},
    }


def selftest():
    """Exercise the whole aggregation pipeline on two synthetic rank
    dumps with rank 1 deliberately delayed.  Returns a list of problems
    — empty means pass (wired into tools/run_lint.sh)."""
    delay = 0.15
    buckets = ("bucket[float32:4p:4096B]", "bucket[float32:3p:3072B]")
    paths = []
    problems = []
    try:
        for rank in (0, 1):
            fd, p = tempfile.mkstemp(suffix=".json",
                                     prefix="graft_analyze_self_r%d_" % rank)
            with os.fdopen(fd, "w") as f:
                json.dump(_synthetic_dump(rank, delay, buckets=buckets), f)
            paths.append(p)
        fd, merged_path = tempfile.mkstemp(suffix=".json",
                                           prefix="graft_analyze_self_merged_")
        os.close(fd)
        paths.append(merged_path)
        report, trace = analyze(paths[:2], merged_out=merged_path)
        problems += list(report["problems"])
        # per-rank tracks present
        names = {(e.get("pid"), (e.get("args") or {}).get("name"))
                 for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        for r in (0, 1):
            if not any(pid == r for pid, _n in names):
                problems.append("merged trace lost rank %d's track" % r)
        # >= 1 cross-rank flow link per reduced bucket
        rows = report["stragglers"]
        for label in buckets:
            if not any(r["label"] == label for r in rows):
                problems.append("no straggler row for %s" % label)
        if report["cross_rank_flow_links"] < len(buckets):
            problems.append("expected >= %d cross-rank flow links, got %d"
                            % (len(buckets),
                               report["cross_rank_flow_links"]))
        # the table must blame the delayed rank
        summary = report["straggler_summary"]
        if summary["worst_rank"] != 1:
            problems.append("straggler table blamed rank %r, expected the "
                            "delayed rank 1" % (summary["worst_rank"],))
        if not (0.9 * delay < summary["max_enter_spread_s"]
                < 1.1 * delay + 0.01):
            problems.append("enter spread %.3fs does not reflect the "
                            "%.3fs delay" % (summary["max_enter_spread_s"],
                                             delay))
        if summary["collectives_matched"] == 0:
            problems.append("straggler table empty")
        # the merged file written by --merged must itself validate
        with open(merged_path) as f:
            problems += _tracing.validate_chrome_trace(json.load(f))
        return problems
    finally:
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass
