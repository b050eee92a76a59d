#!/usr/bin/env bash
# lint tier of the verify recipe, two sub-tiers:
#
# 1. graftlint — the op-contract static analyzer must be clean
#    (suppressed findings are allowed; unsuppressed ones fail the build).
#    Thin wrapper over the canonical entry point — graftlint itself pins
#    jax to CPU and one pass produces both the human summary and the
#    machine-readable JSON report (for bench/verdict diagnostic tracking).
# 2. telemetry smoke — dump a chrome trace from a 3-op bulked program and
#    validate the schema + record→flush flow links (graftscope); a trace
#    regression exits non-zero just like a lint finding.
# 3. graftfuse + graftlap + graftduplex smoke — bench_eager.py --smoke
#    steps a many-small-param Trainer through the bucketed fused path
#    (asserting bit-parity with the per-param path), through the
#    overlapped reduce path (grad-ready hooks issuing bucket allreduces
#    mid-backward, asserting bit-parity with the serial bucketed path),
#    AND through the full-duplex update_on_kvstore step (reduces
#    overlapped + per-bucket async weight pulls waited at first touch,
#    duplex_step_* parity asserted), so a fused-step, overlap or duplex
#    regression fails this tier.
# 4. graftwatch smoke — telemetry --blackbox --selftest exercises the
#    flight recorder end-to-end (engine flushes, kvstore collectives, a
#    step journal, an in-flight bracket) and validates the dump schema.
# 5. cross-rank analysis smoke — telemetry --analyze --selftest merges
#    two synthetic rank dumps (rank 1 deliberately delayed) and requires
#    a schema-valid merged trace with cross-rank flow links per reduced
#    bucket plus a straggler table blaming rank 1.
# 6. grafttsan smoke — analysis.tsan --selftest forces one race per
#    EH2xx rule through the real instrumented paths (handles, scheduler
#    regions, bulk segments, tracked arrays), requires the exact
#    diagnostic with both stacks, and requires a clean workload to stay
#    silent.  graftlint --all (tier 1) now also runs the GL2xx static
#    concurrency rules over the package sources; bench_eager --smoke
#    reports tsan_overhead_pct (detector default-off; informational).
# 7. graftserve smoke — serving --selftest drives threaded traffic
#    through the dynamic batcher (bit-parity vs the eager forward, SLO
#    conservation, atomic hot-swap, LRU residency), and
#    bench_serving.py --smoke emits the serving BENCH JSON (p50/p99 vs
#    offered QPS) asserting batched dispatch >= 3x the serial
#    Module.predict loop with bit-equal outputs.
# 8. graftarmor smoke — armor --selftest exercises the robustness layer
#    end-to-end: deterministic fault-grammar replay, PS wire self-healing
#    against a real ParameterServer (retry + idempotent server-side dedup
#    + typed give-up), atomic checkpoint round-trip with last-valid
#    resume after corruption, and watchdog hang escalation delivering a
#    typed error naming the dead rank; bench_eager --smoke (tier 3)
#    additionally reports armor_overhead_pct (retry plumbing with zero
#    faults armed) against its < 2% budget in BENCH JSON.
# 9. (the autotuner's selftest went with the autotuner, PR 28.)
# 10. (the whole-step compiler's selftest went with the compiler, PR 43;
#    the fused step's behaviours are tier-1 tests: tests/test_fused_step.py.)
# 11. graftguard smoke — analysis.compile_safety --selftest forces every
#    GL30x fixture (plus its clean twin) through the compile-safety
#    linter and runs the GL3xx pass over the package sources and the op
#    registry (graftlint --all, tier 1, runs the same pass).
# 12. (graftxray's capture-harness smoke went with the harness, PR 23;
#    its scope map, registry and exact-sum attribution are tier-1 tests:
#    tests/test_xray.py, tests/test_program_tracing.py.)
# 13. graftzero smoke — parallel.quant --selftest proves the block-scaled
#    quantization kernels (int8/2bit encode/decode round-trips, the
#    documented per-element error bounds, packed-field summability,
#    wire-byte accounting, shard ownership maps, error-feedback
#    convergence in exact arithmetic); bench_eager --smoke (tier 3)
#    additionally gates the int8 wire-bytes ratio (>= 3.5x below f32),
#    the GRAFT_QUANT_REDUCE=0 escape hatch (bit-identical, < 2%
#    overhead) and the ZeRO-1 shard parity + ~1/N state-bytes claim via
#    an 8-device child run.
# 14. graftelastic smoke — elastic --selftest runs kill → re-partition →
#    rejoin → byte-parity in one subprocess: the membership algebra and
#    re-partition plans are pure/deterministic, a simulated 3-rank
#    cluster that loses and regains a rank reproduces the unfaulted loss
#    trajectory byte-for-byte with lockstep digests agreeing across two
#    membership epochs, a chunked armor snapshot round-trips through a
#    REAL ParameterServer wire (torn stream -> typed corruption error),
#    seeded membership.join/repartition chaos replays deterministically
#    (drop -> the rank keeps the old epoch; stuck quiesce -> typed
#    QuiesceTimeoutError), ZeRO shard state re-partitions across changed
#    world sizes both directions (refusing with ShardOwnershipError when
#    GRAFT_ELASTIC is off), and GRAFT_ELASTIC=0 leaves the step fence
#    untaken; bench_eager --smoke (tier 3) additionally gates
#    elastic_overhead_pct (enabled-idle fence) against its < 2% budget
#    in BENCH JSON.
#
# Usage: tools/run_lint.sh [report.json]
set -uo pipefail
cd "$(dirname "$0")/.."

REPORT="${1:-/tmp/graftlint_report.json}"
python -m incubator_mxnet_tpu.analysis.graftlint --all --report "$REPORT" \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.analysis.tsan --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_eager.py --smoke \
    || exit $?
python -m incubator_mxnet_tpu.telemetry --blackbox --selftest \
    || exit $?
python -m incubator_mxnet_tpu.telemetry --analyze --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.serving --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_serving.py --smoke \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.armor --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.analysis.compile_safety --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.parallel.quant --selftest \
    || exit $?
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m incubator_mxnet_tpu.elastic --selftest \
    || exit $?
exec python -m incubator_mxnet_tpu.telemetry --selftest
