"""graftscope + graftwatch CLI.

    python -m incubator_mxnet_tpu.telemetry --summary [--json]
        Run one bulked training step (gluon Trainer on CPU, a kvstore
        attached) with segment tracing on, then render the top-k segment
        flushes by device time and the metrics snapshot (flush causes,
        kvstore bytes, device-memory gauges) FROM THAT RUN.

    python -m incubator_mxnet_tpu.telemetry --analyze R0.json R1.json...
        [--json | --merged OUT.json]
        Cross-rank analysis: merge N per-rank chrome traces and/or
        flight-recorder dumps into one clock-aligned trace (per-rank
        process tracks, cross-rank flow links per collective) and print
        the straggler table (last-to-enter/exit rank, enter/exit
        spreads, per-rank blame counts).  --merged writes the merged
        chrome trace; exits 1 on schema problems.

    python -m incubator_mxnet_tpu.telemetry --analyze --selftest
        Lint smoke tier for the aggregator: two synthetic rank dumps
        (rank 1 deliberately delayed) must merge into a schema-valid
        trace whose straggler table blames rank 1.

    python -m incubator_mxnet_tpu.telemetry --summary --trace T.json
        Same report over an existing chrome-trace dump (segment table
        from the file; the metrics section reflects this process).

    python -m incubator_mxnet_tpu.telemetry --blackbox PATH [--json]
        Post-mortem: reconstruct the final timeline from a flight-
        recorder dump — reason, what was in flight (stuck segment /
        collective / phase), the last engine flushes, step journal with
        phase latencies, per-worker last-seen, watchdog verdict.
        Exits 1 when the dump fails schema validation.

    python -m incubator_mxnet_tpu.telemetry --selftest
        Lint smoke tier: bulk a 3-op program, dump a trace, validate the
        chrome-trace schema + non-empty flow links.  Exit 1 on any
        regression.

    python -m incubator_mxnet_tpu.telemetry --blackbox --selftest
        Lint smoke tier for the flight recorder: exercise the full
        pipeline (flushes, collectives, a step journal, an in-flight
        bracket) and validate the dump schema.

``GRAFT_TELEMETRY_TOPK`` (default 10) sizes the segment table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

# pin jax to CPU before anything initializes a backend: the CLI must
# work (and stay fast) on machines whose TPU is busy or absent
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _demo_training_step():
    """One bulked gluon training step with every telemetry surface lit:
    engine segments, autograd, kvstore push/pull, io batches."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, engine, gluon, io, profiler

    net = gluon.nn.Dense(8)
    net.initialize()
    kvs = mx.kv.create("local")
    x = mx.nd.array(np.random.RandomState(0).rand(4, 16).astype(np.float32))
    y = mx.nd.array(np.zeros((4, 8), np.float32))
    it = io.NDArrayIter(data=x.asnumpy(), label=y.asnumpy(), batch_size=4)
    net(x).asnumpy()                       # param init outside the trace
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kvs)

    fd, path = tempfile.mkstemp(suffix=".json", prefix="graftscope_")
    os.close(fd)
    profiler.set_config(filename=path, profile_all=True)
    profiler.set_state("run")
    for batch in it:
        data = batch.data[0]
        with engine.bulk(64):
            with autograd.record():
                out = net(data)
                loss = (out * out).mean()
            loss.backward()
        trainer.step(batch_size=data.shape[0])
        loss.asnumpy()
    profiler.set_state("stop")
    profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    os.unlink(path)
    return trace


def _summary(trace_events, top):
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import tracing
    report = tracing.segment_summary(trace_events, top=top)
    snap = telemetry.registry().snapshot()
    report["metrics"] = snap
    report["flush_causes"] = {
        s["labels"]["cause"]: s["value"]
        for s in snap.get("graft_engine_flushes_total",
                          {"samples": []})["samples"]}
    report["kvstore_bytes"] = {
        k.replace("graft_kvstore_", "").replace("_total", ""): v
        for k, v in telemetry.compact_snapshot().items()
        if k.startswith("graft_kvstore_")}
    report["device_memory"] = [
        dict(s["labels"], bytes=s["value"])
        for s in snap.get("graft_device_memory_bytes",
                          {"samples": []})["samples"]]
    return report


def _render_text(report):
    lines = ["graftscope summary", "=" * 60]
    lines.append("top segments by flush time (%d total):"
                 % report["segments_total"])
    lines.append("%-8s %-12s %6s %12s %6s %s"
                 % ("segment", "cause", "nodes", "dur(us)", "cache",
                    "device_time"))
    for s in report["top_segments"]:
        lines.append("%-8s %-12s %6s %12.1f %6s %s"
                     % (s["segment"], s["cause"], s["nodes"],
                        s["duration_us"], s["cache"], s["device_time"]))
    lines.append("")
    lines.append("flush time by cause (us): %s"
                 % json.dumps(report["flush_causes_us"]))
    lines.append("flush counts by cause:    %s"
                 % json.dumps(report["flush_causes"]))
    lines.append("kvstore bytes:            %s"
                 % json.dumps(report["kvstore_bytes"]))
    lines.append("")
    lines.append("device memory:")
    for m in report["device_memory"]:
        lines.append("  %-24s %-8s %16d" % (m["device"], m["kind"],
                                            int(m["bytes"])))
    lines.append("")
    lines.append("full metrics snapshot: %d metric families"
                 % len(report["metrics"]))
    for k, v in sorted(report["metrics"].items()):
        lines.append("  %-40s %s (%d series)"
                     % (k, v["kind"], len(v["samples"])))
    return "\n".join(lines)


def selftest():
    """Trace a 3-op bulked program and validate the dump (lint tier).
    Returns a list of problems — empty means pass."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import engine, profiler
    from incubator_mxnet_tpu.telemetry import tracing

    fd, path = tempfile.mkstemp(suffix=".json", prefix="graftscope_self_")
    os.close(fd)
    profiler.set_config(filename=path, profile_all=True)
    profiler.set_state("run")
    a = mx.nd.array(np.ones((8, 8), np.float32))
    with engine.bulk(16):
        b = a * a
        c = b + a
        d = c - a
        d.asnumpy()
    profiler.set_state("stop")
    profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    os.unlink(path)
    problems = tracing.validate_chrome_trace(trace)
    events = trace["traceEvents"]
    flows = [e for e in events if e.get("ph") in ("s", "f")]
    if not flows:
        problems.append("no flow events in the trace (record→flush links "
                        "are gone)")
    deferred = [e for e in events
                if e.get("args", {}).get("deferred") is True]
    if len(deferred) < 3:
        problems.append("expected >=3 deferred op records, got %d"
                        % len(deferred))
    segs = [e for e in events if e.get("name") == tracing.SEGMENT_SPAN]
    if not segs:
        problems.append("no bulk_segment_flush span")
    elif segs[0].get("args", {}).get("nodes") != 3:
        problems.append("segment span nodes=%r, expected 3"
                        % segs[0].get("args", {}).get("nodes"))
    return problems


def _render_blackbox_text(report):
    """Human rendering of summarize_dump(): the final-timeline view."""
    import datetime

    def when(ts):
        try:
            return datetime.datetime.fromtimestamp(ts).isoformat(
                timespec="milliseconds")
        except (OverflowError, OSError, ValueError, TypeError):
            return str(ts)

    lines = ["graftwatch post-mortem", "=" * 60]
    lines.append("reason: %-12s pid: %-8s rank: %s"
                 % (report["reason"], report["pid"], report["rank"]))
    lines.append("dumped at: %s" % when(report["dumped_at"]))
    lp = report.get("last_progress") or {}
    lines.append("last progress: %.3fs before dump (%s)"
                 % (lp.get("age", 0.0), lp.get("site", "?")))
    lines.append("events: %s held of %s recorded  %s"
                 % (report["events_held"], report["events_total"],
                    json.dumps(report["counts"])))
    if report.get("watchdog"):
        wd = report["watchdog"]
        lines.append("")
        lines.append("WATCHDOG TRIP: %r stuck %.1fs (timeout %.1fs) "
                     "detail=%s" % (wd.get("tripped_site"),
                                    wd.get("age_s", 0.0),
                                    wd.get("timeout_s", 0.0),
                                    json.dumps(wd.get("tripped_detail"))))
    if report.get("exception"):
        ex = report["exception"]
        lines.append("")
        lines.append("EXCEPTION: %s: %s" % (ex.get("type"), ex.get("value")))
    if report["in_flight"]:
        lines.append("")
        lines.append("in flight at dump time:")
        for e in report["in_flight"]:
            lines.append("  %-12s age %8.3fs  thread %-12s %s"
                         % (e.get("site"), e.get("age", 0.0),
                            e.get("thread", "?"),
                            json.dumps(e.get("detail"))))
    if report["failures"]:
        lines.append("")
        lines.append("recent bracket failures:")
        for e in report["failures"]:
            lines.append("  %-12s after %7.3fs  %s — %s"
                         % (e.get("site"), e.get("seconds", 0.0),
                            json.dumps(e.get("detail")), e.get("error")))
    lines.append("")
    lines.append("last engine flushes (newest last):")
    lines.append("  %9s %-12s %6s %6s %10s %6s"
                 % ("age(s)", "cause", "nodes", "live", "lat(ms)", "cache"))
    for e in report["last_flushes"]:
        lines.append("  %9.3f %-12s %6s %6s %10.3f %6s%s"
                     % (e.get("age_s", 0.0), e.get("cause"),
                        e.get("nodes"), e.get("live_outputs"),
                        e.get("latency_ms", 0.0), e.get("cache"),
                        "  ERROR: %s" % e["error"] if "error" in e else ""))
    if report["last_steps"]:
        lines.append("")
        lines.append("last steps:")
        for e in report["last_steps"]:
            lines.append("  %9.3fs ago  %-8s #%-6s %8.3fms  phases %s%s%s"
                         % (e.get("age_s", 0.0), e.get("origin"),
                            e.get("index"), e.get("latency_ms", 0.0),
                            json.dumps(e.get("phases")),
                            "  mem_peak %d" % e["device_mem_peak"]
                            if "device_mem_peak" in e else "",
                            "  ERROR %s" % (e.get("error_phase")
                                            or e.get("error"))
                            if ("error" in e or "error_phase" in e) else ""))
    if report["last_collectives"]:
        lines.append("")
        lines.append("last collectives:")
        for e in report["last_collectives"]:
            lines.append("  %9.3fs ago  %-12s keys %-5s bytes %-10s "
                         "%8.3fms rank %s"
                         % (e.get("age_s", 0.0), e.get("path"),
                            e.get("n_keys"), e.get("nbytes", "?"),
                            e.get("latency_ms", 0.0), e.get("rank")))
    if report["slow_collectives"]:
        lines.append("")
        lines.append("slow collectives (beyond EWMA x factor):")
        for e in report["slow_collectives"]:
            lines.append("  %9.3fs ago  %-12s %8.3fms (ewma %.3fms)"
                         % (e.get("age_s", 0.0), e.get("path"),
                            e.get("latency_ms", 0.0), e.get("ewma_ms", 0.0)))
    if report["workers"]:
        lines.append("")
        lines.append("per-worker last seen (dist heartbeat):")
        for r in sorted(report["workers"], key=str):
            w = report["workers"][r]
            lines.append("  rank %-4s step %-8s lag %8.3fs  info age %.3fs"
                         % (r, w.get("step"), w.get("lag_s", 0.0),
                            w.get("info_age_s", 0.0)))
    return "\n".join(lines)


def _render_analyze_text(report):
    lines = ["cross-rank analysis", "=" * 72]
    for r in sorted(report["ranks"], key=int):
        info = report["ranks"][r]
        lines.append("rank %-3s %-40s collectives %-5d heartbeats %d"
                     % (r, ", ".join(info["sources"]),
                        info["collectives"], info["heartbeats"]))
    lines.append("clock offsets vs first rank (s): %s"
                 % json.dumps(report["clock_offsets_s"]))
    lines.append("merged trace: %d events, %d cross-rank flow links%s"
                 % (report["merged_events"],
                    report["cross_rank_flow_links"],
                    ", written to %s" % report["merged_path"]
                    if "merged_path" in report else ""))
    rows = sorted(report["stragglers"],
                  key=lambda r: -r["enter_spread_s"])[:10]
    if rows:
        lines.append("")
        lines.append("straggler table (top %d by enter spread):" % len(rows))
        lines.append("%-6s %-28s %-6s %-10s %-9s %14s %14s"
                     % ("step", "collective", "ranks", "last-enter",
                        "last-exit", "enter-sprd(ms)", "exit-sprd(ms)"))
        for r in rows:
            # async reduces carry no wire-synchronized exit (host-local
            # wait-return): their exit columns render as "-"
            exit_rank = "-" if r["last_to_exit"] is None \
                else r["last_to_exit"]
            exit_sprd = "%14s" % "-" if r["exit_spread_s"] is None \
                else "%14.3f" % (r["exit_spread_s"] * 1e3)
            lines.append("%-6s %-28s %-6d %-10s %-9s %14.3f %s"
                         % (r["step"], r["label"][:28], len(r["ranks"]),
                            r["last_to_enter"], exit_rank,
                            r["enter_spread_s"] * 1e3, exit_sprd))
        s = report["straggler_summary"]
        lines.append("")
        lines.append("blame (times last-to-enter): %s"
                     % json.dumps(s["blame"]))
        lines.append("waiting caused (s, by last-to-enter): %s"
                     % json.dumps(s["wait_caused_s"]))
        lines.append("worst rank: %s   max enter spread: %.3fms   "
                     "mean: %.3fms"
                     % (s["worst_rank"], s["max_enter_spread_s"] * 1e3,
                        s["mean_enter_spread_s"] * 1e3))
    else:
        lines.append("no cross-rank collectives matched (single artifact "
                     "or disjoint sequences)")
    ls = report.get("lockstep") or {}
    lines.append("")
    if ls.get("first_divergent_seq") is not None:
        lines.append("LOCKSTEP DIVERGENCE: rank(s) %s diverged — first "
                     "bad seq %s (%d mismatch(es), %d hole(s) over %d "
                     "matched seq(s))"
                     % (ls.get("divergent_ranks"),
                        ls["first_divergent_seq"],
                        len(ls.get("mismatches") or ()),
                        len(ls.get("holes") or ()),
                        ls.get("seqs_checked", 0)))
        for m in (ls.get("mismatches") or ())[:3]:
            lines.append("  seq %-6s per-rank (path, n_keys, nbytes, "
                         "label): %s" % (m["seq"],
                                         json.dumps(m["per_rank"])))
        for h in (ls.get("holes") or ())[:3]:
            lines.append("  seq %-6s missing on rank %s"
                         % (h["seq"], h["missing_rank"]))
    elif ls.get("seqs_checked"):
        lines.append("lockstep: %d matched collective seq(s), streams "
                     "identical on ranks %s"
                     % (ls["seqs_checked"], ls.get("ranks")))
    elif ls.get("note"):
        lines.append("lockstep: audit declined — %s" % ls["note"])
    for r in ls.get("online_reports") or ():
        lines.append("  online divergence report (rank %s): first bad "
                     "stream position <= %s, hashes %s"
                     % (r.get("rank"),
                        r.get("first_divergent_fold",
                              r.get("first_divergent_seq")),
                        json.dumps(r.get("rank_hashes"))))
    for p in report["problems"]:
        lines.append("PROBLEM: %s" % p)
    return "\n".join(lines)


def _render_ingest_text(report):
    lines = ["device-ledger ingestion", "=" * 60]
    lines.append("device-busy spans: %d" % report["device_events"])
    lines.append("%-8s %10s %10s %10s %7s %6s"
                 % ("step", "wall(ms)", "busy(ms)", "idle(ms)", "busy%",
                    "spans"))
    for r in report["steps"]:
        lines.append("%-8s %10.3f %10.3f %10.3f %6.1f%% %6d"
                     % (r["step"] if r["step"] is not None else "-",
                        r["wall_s"] * 1e3, r["busy_s"] * 1e3,
                        r["idle_s"] * 1e3, r["busy_fraction"] * 100,
                        r["spans"]))
    t = report["total"]
    lines.append("total    %10.3f %10.3f %10.3f %6.1f%%"
                 % (t["wall_s"] * 1e3, t["busy_s"] * 1e3,
                    t["idle_s"] * 1e3, t["busy_fraction"] * 100))
    for p in report["problems"]:
        lines.append("PROBLEM: %s" % p)
    return "\n".join(lines)


def run_ingest(path, as_json):
    from incubator_mxnet_tpu.telemetry import aggregate
    report = aggregate.ingest_xla(path)
    if as_json:
        print(json.dumps(report, indent=2, sort_keys=True, default=str))
    else:
        print(_render_ingest_text(report))
    return 1 if report["problems"] else 0


def run_analyze(paths, merged_out, as_json):
    from incubator_mxnet_tpu.telemetry import aggregate
    report, _trace = aggregate.analyze(paths, merged_out=merged_out)
    if as_json:
        print(json.dumps(report, indent=2, sort_keys=True, default=str))
    else:
        print(_render_analyze_text(report))
    return 1 if report["problems"] else 0


def analyze_selftest():
    from incubator_mxnet_tpu.telemetry import aggregate
    problems = aggregate.selftest()
    if problems:
        for p in problems:
            print("analyze selftest FAIL: %s" % p,
                  file=sys.stderr)
        return 1
    print("analyze selftest OK (merged trace valid, straggler "
          "table blames the delayed rank)")
    return 0


def blackbox_selftest():
    """Flight-recorder lint smoke: full-pipeline dump + schema check."""
    from incubator_mxnet_tpu.telemetry import blackbox
    problems = blackbox.selftest()
    if problems:
        for p in problems:
            print("graftwatch selftest FAIL: %s" % p, file=sys.stderr)
        return 1
    print("graftwatch selftest OK (ring + brackets + dump schema valid)")
    return 0


def render_blackbox(path, as_json):
    from incubator_mxnet_tpu.telemetry import blackbox
    with open(path) as f:
        doc = json.load(f)
    problems = blackbox.validate_dump(doc)
    report = blackbox.summarize_dump(doc)
    if as_json:
        out = dict(report, problems=problems)
        print(json.dumps(out, indent=2, sort_keys=True, default=str))
    else:
        print(_render_blackbox_text(report))
        for p in problems:
            print("graftwatch: dump schema problem: %s" % p,
                  file=sys.stderr)
    return 1 if problems else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m incubator_mxnet_tpu.telemetry",
        description="graftscope: segment-aware tracing + metrics summary; "
                    "graftwatch: flight-recorder post-mortems + "
                    "cross-rank straggler analysis")
    ap.add_argument("--summary", action="store_true",
                    help="run (or load) a traced workload and report")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report")
    ap.add_argument("--trace", metavar="PATH",
                    help="summarize an existing chrome-trace dump instead "
                         "of running the demo step")
    ap.add_argument("--blackbox", metavar="PATH", nargs="?", const="",
                    default=None,
                    help="render a flight-recorder dump (with --selftest: "
                         "validate the recorder pipeline instead)")
    ap.add_argument("--analyze", metavar="ARTIFACT", nargs="*",
                    default=None,
                    help="merge + analyze N per-rank chrome traces and/or "
                         "blackbox dumps (with --selftest: synthetic "
                         "2-rank smoke)")
    ap.add_argument("--merged", metavar="OUT",
                    help="with --analyze: write the merged chrome trace "
                         "here")
    ap.add_argument("--ingest-xla", metavar="TRACE", dest="ingest_xla",
                    help="rebuild the per-step device ledger offline "
                         "from a sync-mode chrome trace")
    ap.add_argument("--top", type=int,
                    default=int(os.environ.get("GRAFT_TELEMETRY_TOPK",
                                               "10")),
                    help="segment table size (GRAFT_TELEMETRY_TOPK)")
    ap.add_argument("--selftest", action="store_true",
                    help="trace a 3-op bulked program and validate the "
                         "dump (CI smoke tier)")
    args = ap.parse_args(argv)

    if args.analyze is not None:
        if args.selftest:
            return analyze_selftest()
        if not args.analyze:
            ap.error("--analyze needs artifact PATHs (or --selftest)")
        return run_analyze(args.analyze, args.merged, args.json)

    if args.ingest_xla:
        return run_ingest(args.ingest_xla, args.json)

    if args.blackbox is not None:
        if args.selftest:
            return blackbox_selftest()
        if not args.blackbox:
            ap.error("--blackbox needs a dump PATH (or --selftest)")
        return render_blackbox(args.blackbox, args.json)

    if args.selftest:
        problems = selftest()
        if problems:
            for p in problems:
                print("graftscope selftest FAIL: %s" % p, file=sys.stderr)
            return 1
        print("graftscope selftest OK (schema + flow links valid)")
        return 0

    if not args.summary:
        ap.print_help()
        return 2

    if args.trace:
        with open(args.trace) as f:
            events = json.load(f)["traceEvents"]
    else:
        events = _demo_training_step()["traceEvents"]
    report = _summary(events, args.top)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True, default=str))
    else:
        print(_render_text(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
