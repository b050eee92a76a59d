"""graftlint — CLI for the op-contract + concurrency + compile-safety
linters.

Usage::

    python -m incubator_mxnet_tpu.analysis.graftlint [--all] [--json]
           [--ops NAME[,NAME...]] [--list-rules] [--baseline PATH]

Imports the full ops package (registration side effects populate the
registry and the registration log), runs every contract rule (GL1xx),
then the static concurrency rules (GL2xx — lock-order inversions,
unguarded thread-shared globals, ``_sched_*`` protocol completeness,
daemon threads without shutdown paths; analysis/concurrency.py) and the
compile-safety rules (GL3xx — host round-trips / traced branching /
constant-baked hyperparameters / donation hazards in trace-eligible
closures; analysis/compile_safety.py) over the package sources, and
exits non-zero on unsuppressed findings.  ``--ops`` restricts to the
op-contract + registry compile-safety passes.  ``--json`` emits the
machine-readable report to stdout, ``--report PATH`` writes it to a file
alongside the human summary (one linter pass serves both),
``--contracts`` dumps every registered op's machine-readable contract
(Operator.contract()).

Baselines: ``--write-baseline PATH`` snapshots the current unsuppressed
findings; a later run with ``--baseline PATH`` fails ONLY on findings
not in the snapshot (new code held strict, legacy debt non-blocking) —
masked findings are still printed and counted.

Linting is platform-independent and a CPU program: the CLI pins jax to
the CPU before the ops import, so a lint run never opens an accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _force_cpu_platform():
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass   # backend already initialized (in-process callers): lint
        #        works on whatever platform the host chose


def _report_json(diags):
    active = [d for d in diags if not d.suppressed]
    counts = {}
    for d in active:
        counts[d.code] = counts.get(d.code, 0) + 1
    return {
        "version": 1,
        "total": len(active),
        "suppressed": sum(1 for d in diags if d.suppressed),
        "counts": counts,
        "diagnostics": [d.as_dict() for d in diags],
    }


def _baseline_key(d):
    """Identity of a finding across unrelated edits: code + site + the
    file's basename (absolute paths differ per checkout; line numbers
    drift with every edit above them, so they are deliberately NOT part
    of the key — the baseline masks by count per key instead)."""
    return "%s|%s|%s" % (d.code, d.op_name,
                         os.path.basename(d.file) if d.file else "-")


def _baseline_counts(diags):
    counts = {}
    for d in diags:
        if d.suppressed:
            continue
        k = _baseline_key(d)
        counts[k] = counts.get(k, 0) + 1
    return counts


def write_baseline(path, diags):
    with open(path, "w") as f:
        json.dump({"version": 1, "counts": _baseline_counts(diags)},
                  f, indent=2, sort_keys=True)


def apply_baseline(path, diags):
    """Split active findings into (new, masked) against a snapshot.
    Per key, up to the snapshot's count is masked; anything beyond it
    (or any unseen key) is new and fails the run."""
    with open(path) as f:
        doc = json.load(f)
    budget = dict(doc.get("counts") or {})
    new, masked = [], []
    for d in diags:
        if d.suppressed:
            continue
        k = _baseline_key(d)
        if budget.get(k, 0) > 0:
            budget[k] -= 1
            masked.append(d)
        else:
            new.append(d)
    return new, masked


def main(argv=None):
    from . import compile_safety, concurrency, contracts

    ap = argparse.ArgumentParser(
        prog="graftlint", description="op-contract static analyzer")
    ap.add_argument("--all", action="store_true",
                    help="lint every registered op (default when no --ops)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names to lint")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable JSON report on stdout")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the JSON report to PATH (single pass)")
    ap.add_argument("--contracts", action="store_true",
                    help="dump every op's machine-readable contract as "
                         "JSON and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the diagnostic codes and exit")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="fail only on findings NOT in this snapshot "
                         "(legacy debt stays non-blocking)")
    ap.add_argument("--write-baseline", default=None, metavar="PATH",
                    help="snapshot the current unsuppressed findings "
                         "and exit 0")
    args = ap.parse_args(argv)

    if args.list_rules:
        rules = dict(contracts.RULES)
        rules.update(concurrency.RULES)
        rules.update(compile_safety.RULES)
        for code in sorted(rules):
            print("%s  %s" % (code, rules[code]))
        return 0

    _force_cpu_platform()
    # registration side effects; engine hazards (pass 2) live at runtime
    # behind GRAFT_ENGINE_CHECK=1, not here
    import incubator_mxnet_tpu.ops  # noqa: F401
    import incubator_mxnet_tpu.operator  # noqa: F401  custom-op registry

    names = None
    if args.ops:
        names = {n for n in args.ops.split(",") if n}

    if args.contracts:
        from ..ops.registry import _REGISTRY
        out = {n: op.contract() for n, op in sorted(_REGISTRY.items())
               if names is None or n in names}
        print(json.dumps(out, indent=2, default=str))
        return 0

    diags = contracts.lint_all(names=names)
    diags += compile_safety.lint_registry(names=names)
    if names is None:
        # the concurrency + compile-safety tiers lint the package
        # sources, not ops — an --ops-restricted run (fixture tests)
        # skips them
        diags += concurrency.lint_package()
        diags += compile_safety.lint_package()
    active = [d for d in diags if not d.suppressed]

    if args.write_baseline:
        write_baseline(args.write_baseline, diags)
        print("graftlint: baseline of %d finding(s) written to %s"
              % (len(active), args.write_baseline))
        return 0

    masked = []
    if args.baseline:
        active, masked = apply_baseline(args.baseline, diags)

    report = _report_json(diags)
    if args.baseline:
        report["baseline"] = {"path": args.baseline,
                              "masked": len(masked),
                              "new": len(active)}

    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, default=str)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        for d in diags:
            print(repr(d))
        print("graftlint: %d finding(s), %d suppressed, %d op name(s) "
              "checked" % (len(active),
                           sum(1 for d in diags if d.suppressed),
                           len(names) if names is not None else
                           _registry_size()))
        if masked:
            print("graftlint: %d baseline-masked finding(s) (%s)"
                  % (len(masked), args.baseline))
        if args.report:
            print("graftlint: JSON report at %s" % args.report)
    return 1 if active else 0


def _registry_size():
    from ..ops.registry import _REGISTRY
    return len(_REGISTRY)


if __name__ == "__main__":
    sys.exit(main())
