"""The readers of what the program says of itself (``chipbench/program.py``,
``layer_metrics/device_split.py``, ``flash_bwd.py``, ``program_spans.py``),
on a synthetic trace, registry and span record worked out by hand; the CPU
rehearsal naming every one of those metrics in the cells that report it; and
the drivers' reading of a step's temporaries through the program's public
accessors against the private trees it used to be read from.
"""
import importlib
import json
import pathlib
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
US = 1000

# the metrics read from the program's own scopes, programs and spans (PR 23):
# names of metrics, which the rehearsal below looks for wherever a cell
# reports them; which cells those are is BENCHMARK.json's to say
NEW = ["fwd_device_ms_per_step", "bwd_device_ms_per_step",
       "update_device_ms_per_step", "device_unattributed_pct",
       "flash_bwd_ms_per_step", "place_span_ms_p50", "dispatch_span_ms_p50",
       "fwd_span_ms_p50", "bwd_span_ms_p50", "kvstore_span_ms_p50",
       "update_span_ms_p50", "engine_flushes_per_step"]


@pytest.fixture(scope="module")
def program(chip_run):
    """``chipbench/program.py`` (``run.py`` put its directory on the path)."""
    return importlib.import_module("chipbench.program")


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


class _Program:
    def __init__(self, phase=None, ops=None):
        self.phase, self.ops, self.error = phase, ops or {}, None


FWD = "jit(dp_train_step)/xray:forward/jvp(net_stage1/net_stage1_conv0)/" \
    "jit(<unknown>)/conv_general_dilated"
BWD = "jit(dp_train_step)/xray:backward/transpose(jvp(net_stage1/" \
    "net_stage1_conv0))/jit(<unknown>)/conv_general_dilated"
FLASH = "jit(dp_train_step)/xray:backward/transpose(jvp(net_attn0/" \
    "flash_attention_bwd))/while/body/closed_call/dot_general"
UPD = "jit(dp_train_step)/xray:update[3]/jit(<unknown>)/sub"


def _run(chip_run, planes, registry, spans=(), stamps=(), traced=(None, None),
         sizes=None):
    """A Run as ``report()`` hands it to the readers, from a hand-made
    recorded trace, registry and span record."""
    recorded = {"planes": planes, "ops": {}}
    telemetry = types.SimpleNamespace(
        programs=lambda: registry,
        spans=lambda since=None: [s for s in spans
                                  if since is None or s[1] >= since])
    return chip_run.Run(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded), stamps=list(stamps),
        traced_stamps=traced, rehearse=False, sizes=sizes or {})


def _host(ends_us, extra=()):
    waits = [_ev("bench:wait", e - 1, 1) for e in ends_us]
    return {"name": "/host:CPU", "lines": [
        {"name": "main", "events": waits + list(extra)}]}


# The window is [200, 400] us and holds 2 steps (waits end at 0, 50, 200,
# 300, 400; the first two are skipped).  Step program [200, 290] and [300,
# 390]; a while [240, 280] holds the flash backward's body op [250, 270]; an
# unknown program runs [392, 398].
FUSED_PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            _ev("jit_dp_train_step(77)", 200, 90),
            _ev("jit_dp_train_step(77)", 300, 90),
            _ev("jit_convert(5)", 392, 6)]},
        {"name": "XLA Ops", "events": [
            _ev("%fusion.1", 200, 30),          # forward, 30
            _ev("%while.2", 240, 40),           # backward, 40 - 20 nested
            _ev("%fusion.3", 250, 20),          # flash backward body, 20
            _ev("%fusion.4~2", 280, 10),        # update, 10 (renamed copy)
            _ev("%copy.5", 300, 10),            # no scope: unattributed
            _ev("%fusion.1", 310, 30),
            _ev("%fusion.4~2", 380, 10),
            _ev("%convert.9", 392, 6)]}]},      # unknown program
    _host([0, 50, 200, 300, 400]),
]
FUSED_REGISTRY = {"dp_train_step": _Program(ops={
    "fusion.1": FWD, "while.2": FLASH.rsplit("/", 3)[0], "fusion.3": FLASH,
    "fusion.4": UPD, "copy.5": "params['w']"})}


def test_device_split_partitions_the_busy_time(chip_run, program, bench_catalog):
    run = _run(chip_run, FUSED_PLANES, FUSED_REGISTRY,
               sizes={"num_attention_heads": 2})
    split = program.device_split(run)
    us = {k: round(v * 1e6, 6) for k, v in split["phase_s"].items()}
    assert us == {"forward": 60.0, "backward": 40.0, "update": 20.0}
    assert split["unattributed_s"] == pytest.approx(16e-6)
    assert split["flash_bwd_s"] == pytest.approx(40e-6)
    assert split["steps"] == 2
    # conservation: every op's self time in exactly one bin
    assert sum(split["phase_s"].values()) + split["unattributed_s"] \
        == pytest.approx(run.reduced["busy_s"])
    assert split["program_s"] == pytest.approx(
        {"dp_train_step": 130e-6, "convert": 6e-6})
    # innermost scope below the phase: the leaf Block, the flash scope
    assert split["scope_s"] == pytest.approx(
        {"net_stage1_conv0": 60e-6, "flash_attention_bwd": 40e-6})

    read = {}                                       # found by the glob
    for reader in bench_catalog.readers():
        if reader.__name__.endswith(("device_split", "flash_bwd")):
            read.update(reader.read(run))
    assert read == pytest.approx({
        "fwd_device_ms_per_step": 0.030, "bwd_device_ms_per_step": 0.020,
        "update_device_ms_per_step": 0.010,
        "device_unattributed_pct": 100 * 16 / 136,
        "flash_bwd_ms_per_step": 0.020})
    facts = run.facts["device_split"]
    assert facts["residue_pct_of_busy"] == pytest.approx(0.0, abs=1e-9)
    assert facts["device_ms_by_block"][0] == ["net_stage1_conv0",
                                              pytest.approx(0.030)]
    assert facts["device_ms_by_block_kind"][0] == ["conv",
                                                   pytest.approx(0.030)]
    assert [op for op, _ in facts["unattributed_ops"]] == [
        "%copy.5 ", "%convert.9 "]
    assert program.kind_of("net_stage1_batchnorm12") == "batchnorm"


def test_whole_phase_programs_go_by_the_modules_line(chip_run, program):
    """The Gluon loop: ops of different programs share short names (the
    recorder renames them ``~2``); the module event they ran in decides."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_cachedop_forward(1)", 200, 30),
                _ev("jit_cachedop_backward(2)", 230, 60),
                _ev("jit_trainer_bucket_update(3)", 290, 4),
                _ev("jit_trainer_bucket_update(4)", 294, 4),
                _ev("jit_cachedop_forward(1)", 300, 30),
                _ev("jit_cachedop_backward(2)", 330, 60),
                _ev("jit_broadcast_in_dim(9)", 395, 2)]},
            {"name": "XLA Ops", "events": [
                _ev("%fusion.1", 200, 30), _ev("%fusion.1~2", 230, 60),
                _ev("%fusion.1~3", 290, 4), _ev("%fusion.1~4", 294, 4),
                _ev("%fusion.1", 300, 30), _ev("%fusion.1~2", 330, 60),
                _ev("%broadcast.2", 395, 2)]}]},
        _host([0, 50, 200, 300, 400]),
    ]
    registry = {"cachedop_forward": _Program("forward"),
                "cachedop_backward": _Program("backward"),
                "trainer_bucket_update": _Program("update")}
    split = program.device_split(_run(chip_run, planes, registry))
    assert {k: round(v * 1e6, 6) for k, v in split["phase_s"].items()} == {
        "forward": 60.0, "backward": 120.0, "update": 8.0}
    assert split["unattributed_s"] == pytest.approx(2e-6)


def test_program_without_the_accessors_reads_nothing(chip_run, bench_catalog):
    """The parent commit: no ``telemetry.programs``, no ``telemetry.spans``."""
    run = _run(chip_run, FUSED_PLANES, FUSED_REGISTRY, stamps=[1.0, 2.0])
    run.mx = types.SimpleNamespace(telemetry=types.SimpleNamespace())
    for reader in bench_catalog.readers():
        if reader.__name__.endswith(("device_split", "flash_bwd",
                                     "program_spans")):
            assert reader.read(run) == {}
    assert "idle_gaps_by_program_span" not in run.facts


@pytest.mark.parametrize("path, phase, scope", [
    (FWD, "forward", "net_stage1_conv0"),
    (BWD, "backward", "net_stage1_conv0"),
    (FLASH, "backward", "flash_attention_bwd"),
    (UPD, "update", None),
    ("jit(dp_train_step)/xray:forward/jvp()/reduce_sum", "forward", None),
    ("jit(dp_train_step)/jit(_threefry_split)/slice", None, None),
    # a whole-phase program has no xray scope: below its own jit(...)
    ("jit(cachedop_backward)/transpose(jvp(net_stage1/net_stage1_batchnorm2))"
     "/jit(<unknown>)/mul", None, "net_stage1_batchnorm2"),
    ("", None, None),
])
def test_phase_and_scope_of_an_op_name_path(chip_run, program, path, phase, scope):
    assert program.phase_of(path) == phase
    assert program.scope_of(path) == scope


@pytest.mark.parametrize("event, name", [
    ("jit_dp_train_step(6823471011644943011)", "dp_train_step"),
    ("jit_cachedop_backward(4898258525160917217)", "cachedop_backward"),
    ("jit_trainer_bucket_update.3(12)", "trainer_bucket_update"),
    ("convert_element_type(1)", "convert_element_type"),
])
def test_program_names_from_the_modules_line(chip_run, program, event, name):
    assert program.program_of(event) == name


def _aligned(chip_run, shift_s, jitter_us=(0, 0, 0, 0, 0, 0)):
    """Six traced waits whose ends sit ``shift_s`` (+ a few us of jitter)
    from the stamps the loop took, after 5 untraced stamps."""
    ends_us = [1000 * (k + 1) for k in range(6)]
    traced = [e * 1e-6 - shift_s - j * 1e-6
              for e, j in zip(ends_us, jitter_us)]
    stamps = [traced[0] - 0.001 * k for k in range(5, 0, -1)] + traced
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(1)", 3000 + 1000 * k, 900)
                for k in range(3)]},
            {"name": "XLA Ops", "events": [
                _ev("%fusion.1", 3000 + 1000 * k, 900) for k in range(3)]}]},
        _host(ends_us),
    ]
    return planes, stamps


def test_clock_offset_is_recovered_to_the_microsecond(chip_run, program):
    planes, stamps = _aligned(chip_run, shift_s=-1234.567891,
                              jitter_us=(1, 2, 1, 40, 1, 2))
    run = _run(chip_run, planes, FUSED_REGISTRY, stamps=stamps,
               traced=(5, 11))
    offset, error, waits = program.clock_offset(run)
    # the median ignores the one wait the host was held up after (40 us)
    assert offset == pytest.approx(-1234.567891 + 1.5e-6, abs=1e-6)
    assert waits == 6 and error < 50e-6
    # too few waits, or stamps that do not match them: no alignment
    run.traced_stamps = (10, 11)
    assert program.clock_offset(run) is None


def test_idle_gaps_fall_to_program_spans(chip_run, program):
    """The device idles for the last 100 us of each of the window's three
    steps; the program's spans, on perf_counter, say the host was in
    ``place`` and then ``dispatch`` during the first, in no span later."""
    shift = 500.0                           # perf_counter = trace - 500 s
    planes, stamps = _aligned(chip_run, shift_s=shift)
    at = lambda us: us * 1e-6 - shift       # noqa: E731
    spans = [("place", at(3850), at(3990), "step", 7),
             ("dispatch", at(3990), at(4100), "step", 7),
             ("step", at(3800), at(4200), None, 7)]
    run = _run(chip_run, planes, FUSED_REGISTRY, spans=spans, stamps=stamps,
               traced=(5, 11))
    gaps = program.idle_gaps(run)
    assert gaps["alignment_error_us"] == pytest.approx(0.0, abs=1e-3)
    assert gaps["seconds"] == pytest.approx({
        "mx:place": 90e-6, "mx:dispatch": 10e-6,
        program.NO_SPAN: 200e-6}, abs=2e-9)
    # the window [3000, 6000] us; the oldest span held began at 3800
    assert gaps["window_covered_share"] == pytest.approx(2200 / 3000)


def _spans_run(chip_run, n_before, per_step=("place", "dispatch")):
    """``n_before`` untraced steps of 10 ms, then a traced stretch, each
    step with its spans; ``dispatch`` takes 2 ms, ``place`` 0.1 ms."""
    stamps = [10.0 + 0.010 * k for k in range(n_before + 12)]
    first = n_before + 3                    # profiler started at this stamp
    dur = {"place": 1e-4, "dispatch": 2e-3, "engine_flush": 5e-5}
    spans = []
    for k, t in enumerate(stamps[:-1]):
        for i, name in enumerate(per_step):
            spans.append((name, t + 1e-3 * (i + 1),
                          t + 1e-3 * (i + 1) + dur[name], "step", k))
    return _run(chip_run, [], {}, spans=spans, stamps=stamps,
                traced=(first, None))


def test_span_medians_need_ten_untraced_samples(chip_run, bench_catalog):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("program_spans")]
    enough = reader.read(_spans_run(chip_run, n_before=12))
    assert enough["dispatch_span_ms_p50"] == pytest.approx(2.0)
    assert enough["place_span_ms_p50"] == pytest.approx(0.1)
    short = _spans_run(chip_run, n_before=9)
    got = reader.read(short)
    assert "dispatch_span_ms_p50" not in got and "place_span_ms_p50" not in got
    assert short.facts["program_spans"]["samples"] == {"place": 9,
                                                       "dispatch": 9}


def test_flushes_are_counted_over_the_steps_the_record_covers(
        chip_run, bench_catalog):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("program_spans")]
    run = _spans_run(chip_run, n_before=12,
                     per_step=("engine_flush", "engine_flush", "dispatch"))
    # the bounded recorder has dropped the first five steps' spans
    held = [s for s in run.mx.telemetry.spans() if s[4] >= 5]
    run.mx.telemetry.spans = lambda since=None: held
    got = reader.read(run)
    assert got["engine_flushes_per_step"] == pytest.approx(2.0)
    facts = run.facts["program_spans"]
    assert facts["steps_covered"] == len(run.stamps) - 1 - 6
    assert 0.7 < facts["window_covered_share"] < 0.8


def test_flash_backward_roofline_by_hand(chip_run, bench_catalog):
    """The two backward kernels by their names in the trace, a layer each
    step, against what the causal backward needs: the ``jnp`` around them is
    in ``flash_bwd_ms_per_step`` and not in the roofline's time."""
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith("flash_bwd")]
    flops, nbytes = reader.needs(batch=4, heads=32, seq=2048, head_dim=128,
                                 dtype_bytes=2)
    # seven matmuls over the lower triangle: scores and dP in each of the
    # two kernels, dV, dK, dQ; each 2*B*H*S*S*D over the square
    assert flops == 7 * 4 * 32 * 2048 * 2048 * 128
    # q, k, v, o, dO read, dq, dk, dv written
    assert nbytes == 8 * 4 * 32 * 2048 * 128 * 2
    assert flops / 197e12 == pytest.approx(2.4418e-3, rel=1e-4)

    # the window [200, 400] us holds 2 steps of a one-layer model: dK/dV
    # runs 30 us and dQ 20 us a step, a delta fusion under the same scope 5
    scope = FLASH.rsplit("/", 3)[0]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(77)", 200, 90),
                _ev("jit_dp_train_step(77)", 300, 90)]},
            {"name": "XLA Ops", "events": [
                _ev("%fusion.7", 200, 5),
                _ev("%flash_attention_bwd_dkv.2", 210, 30),
                _ev("%flash_attention_bwd_dq.3", 240, 20),
                _ev("%flash_attention_pallas.1", 260, 10),
                _ev("%fusion.7", 300, 5),
                _ev("%flash_attention_bwd_dkv.2", 310, 30),
                _ev("%flash_attention_bwd_dq.3", 340, 20)]}]},
        _host([0, 50, 200, 300, 400]),
    ]
    registry = {"dp_train_step": _Program(ops={
        "fusion.7": scope + "/mul", "flash_attention_bwd_dkv.2": scope,
        "flash_attention_bwd_dq.3": scope,
        "flash_attention_pallas.1":
            "jit(dp_train_step)/xray:forward/flash_attention_pallas"})}
    run = _run(chip_run, planes, registry,
               sizes={"num_attention_heads": 32, "hidden_size": 4096,
                      "num_hidden_layers": 1})
    run.traffic = {"batch_per_chip": 4, "seq_len": 2048, "dtype": "bfloat16"}
    run.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    got = reader.read(run)
    assert got["flash_bwd_ms_per_step"] == pytest.approx(0.055)
    # 2.4418 ms at the bf16 peak over 0.050 ms of kernels: a count that is
    # too high or a time that leaves work out shows as a share over 100
    assert got["flash_bwd_roofline"] == pytest.approx(
        100 * 2.4418e-3 / 0.050e-3, rel=1e-4)
    assert run.facts["flash_bwd"]["roof"] == "bf16 FLOP/s"
    assert run.facts["flash_bwd"]["kernels_ms_per_step"] == pytest.approx(
        0.050)
    # the forward's reader takes the forward's kernel alone
    (fwd,) = [r for r in bench_catalog.readers()
              if r.__name__.endswith("flash_fwd")]
    assert fwd.kernel_seconds(run) == pytest.approx(10e-6)   # the window's
    # no kernel of that name in the trace (the ``jnp`` path): the time is
    # reported, the share is not
    assert set(reader.read(_run(
        chip_run, FUSED_PLANES, FUSED_REGISTRY,
        sizes={"num_attention_heads": 2}))) == {"flash_bwd_ms_per_step"}


def _private_fused_temp_bytes(job):
    """As ``drivers/fused.py`` read it until PR 25: lowered from the
    trainer's private argument trees."""
    t = job.trainer
    return int(t.compile(job.x, job.y).lower(
        t._params, t._opt_state, t._rng_key, job.x, job.y,
        t._lr_dev).compile().memory_analysis().temp_size_in_bytes)


def _private_gluon_temp_bytes(job):
    """As ``drivers/gluon_loop.py`` read it until PR 25: the CachedOp's
    backward from the Block's private cache."""
    import jax
    (entry,) = job.net._cached_op._cache.values()
    params = job.net.collect_params()
    vals = {n: params[n].data()._read()
            for n in job.net._cached_op._param_names}
    out = jax.eval_shape(entry["jit"], vals, [job.x._read()],
                         jax.random.PRNGKey(0))[0]
    return int(entry["vjp"].lower(
        vals, [job.x._read()], jax.random.PRNGKey(0),
        tuple(out)).compile().memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("cell, private", [
    ("resnet50_fused_b256", _private_fused_temp_bytes),
    ("opt6b7_fused_adam", _private_fused_temp_bytes),
    ("resnet50_gluon_b128", _private_gluon_temp_bytes)])
def test_temporaries_read_through_public_accessors(chip_run, cell, private):
    """``temp_bytes`` asks ``DataParallelTrainer.compiled_step`` and
    ``telemetry.programs()`` and gets, to the byte, what the private trees
    gave: ``peak_hbm_gb`` is bounded at 1 % and repeats to the byte."""
    run = chip_run.open_run(chip_run.parse(
        ["--workload", cell, "--seed", "11", "--rehearse"]), None)
    job = chip_run.set_up(run)
    assert run.temp_bytes is not None and run.temp_bytes > 0
    assert "gluon_temp_bytes_unreadable" not in run.facts
    assert run.temp_bytes == private(job)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_rehearsal_names_every_new_metric(chip_run, bench_catalog, capsys,
                                          monkeypatch, cell):
    """A short traced stretch inside the window, so that some steps are
    untraced however slow the machine is: the line names each new metric
    the cell lists (a rehearsal's values are blanked, and one span names
    a span metric there; the chip's rule of ten is the tests' above)."""
    monkeypatch.setattr(chip_run, "TRACE_SECONDS", 0.3)
    rc = chip_run.main(["--workload", cell, "--seed", "2147483659",
                        "--seconds", "8", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    facts = json.loads(lines[-2][len("[facts] "):])
    assert rc == 0 and result["correct"] is True, facts["checks"]
    listed = {m["name"] for m in bench_catalog.metrics("per_layer", cell)
              if m["name"] in NEW}
    assert listed <= set(result["metrics"]), (
        listed - set(result["metrics"]), facts.get("program_spans"))
    assert all(result["metrics"][n]["value"] is None for n in listed)
    assert facts["compile_window"]["programs"] == 0
