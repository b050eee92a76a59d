"""``layer_metrics/startup.py`` on a made-up start-up record, worked out by
hand: the seven readings, every second of a set-up phase counted once, a
build inside the window beside the idle gap it lies across, agreement and
disagreement with a made-up ``compile_log``, and silence on a program that
has no ``telemetry.startup``.
"""
import types

import pytest

US = 1000
SHIFT = 500.0                       # perf_counter = the trace's clock - 500 s


def at(us):
    """A time of the traced stretch, given in us on the trace's clock, on
    ``time.perf_counter()``."""
    return us * 1e-6 - SHIFT


@pytest.fixture(scope="module")
def reader(bench_catalog):
    (found,) = [r for r in bench_catalog.readers()
                if r.__name__.endswith("layer_metrics_startup")]
    return found


class _Record(list):
    dropped = 0


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


def _build(name, start, seconds, stage, owner, parent=None, step=1, **attrs):
    return ("build", name, start, start + seconds, parent, step,
            dict(attrs, stage=stage, owner=owner))


def _program(name, start, stages, owner, cache="hit", **kw):
    """The three stages of one program, one after the other."""
    out, t = [], start
    for stage, seconds in zip(("trace", "lower", "backend"), stages):
        if seconds:
            extra = {"cache": cache, "retrieval_s": 0.0} \
                if stage == "backend" else {}
            out.append(_build(name, t, seconds, stage, owner, **extra, **kw))
            t += seconds
    return out


def _made_up_run(chip_run, accessor=True):
    """Six traced waits a millisecond apart after five untraced stamps; the
    device runs 900 us of each of the traced window's three steps and idles
    100.  Set-up took 30 s: import 10, init 2, reference 8, driver 1,
    warm-up 6, memory 3."""
    ends_us = [1000 * (k + 1) for k in range(6)]
    traced = [at(e) for e in ends_us]
    stamps = [traced[0] - 0.001 * k for k in range(5, 0, -1)] + traced
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dp_train_step(1)", 3000 + 1000 * k, 900)
                for k in range(3)]},
            {"name": "XLA Ops", "events": [
                _ev("%fusion.1", 3000 + 1000 * k, 900) for k in range(3)]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            _ev("bench:wait", e - 1, 1) for e in ends_us]}]},
    ]
    start, done = stamps[0] - 0.5, stamps[-1] + 0.001
    t0 = start - 30.0
    records = _Record(
        [("import", "package_import", t0 + 6.0, t0 + 9.5, None, None,
          {"groups_s": {"ops": 3.0}, "jax_preloaded": True,
           "backend_open": True}),
         ("span", "initialize", t0 + 10.5, t0 + 11.5, None, 1, {})]
        + _program("_uniform", t0 + 10.6, (0.1, 0.2, 0.1), "initialize",
                   parent="initialize")
        + [("span", "deferred_init", t0 + 12.5, t0 + 14.5, None, 1, {}),
           # one a layer, inside the pass: not counted a second time
           ("span", "deferred_init", t0 + 12.6, t0 + 13.6, "deferred_init",
            1, {})]
        + _program("_normal", t0 + 12.7, (0.1, 0.2, 0.3), "deferred_init",
                   parent="deferred_init")
        + _program("_convolution", t0 + 15.0, (0.2, 0.3, 0.4), "eager",
                   enclosed=5)
        + _program("<lambda>", t0 + 16.0, (1.0, 1.0, 1.0), "user",
                   cache="miss")
        + [("span", "gather_params", t0 + 21.1, t0 + 21.6, "place", 1, {})]
        + _program("dp_train_step", t0 + 22.0, (2.0, 1.0, 1.5), "dispatch",
                   parent="dispatch", enclosed=800)
        + [("span", "memory_analysis", t0 + 27.2, t0 + 29.2, None, 4, {})]
        + _program("dp_train_step", t0 + 27.3, (0.0, 1.0, 0.8),
                   "memory_analysis", parent="memory_analysis", step=4)
        # a new shape at step 41, inside the traced window
        + _program("dp_train_step", at(3850), (50e-6, 50e-6, 100e-6),
                   "dispatch", cache="miss", parent="dispatch", step=41)
        # the registry resolving its programs for the readers, afterwards
        + _program("dp_train_step", done + 1.0, (0.0, 0.5, 0.5), "user"))
    telemetry = types.SimpleNamespace(
        programs=dict, spans=lambda since=None: [])
    if accessor:
        telemetry.startup = lambda: records
    recorded = {"planes": planes, "ops": {}}
    backend_s = 0.1 + 0.3 + 0.4 + 1.0 + 1.5 + 0.8
    return chip_run.Run(
        mx=types.SimpleNamespace(telemetry=telemetry), recorded=recorded,
        reduced=chip_run.trace.reduce(recorded), stamps=stamps,
        traced_stamps=(5, 11), rehearse=False, sizes={},
        window_span=(start, done), setup_s=30.0,
        setup={"init": 2.0, "reference": 8.0, "driver": 1.0, "warmup": 6.0,
               "memory": 3.0},
        facts={"import_s": 10.0},
        compile_setup={"programs": 6, "compile_s": 1.0,
                       "cache_retrieval_s": backend_s - 1.0},
        compile_window={"programs": 1, "compile_s": 100e-6,
                        "cache_retrieval_s": 0.0})


def test_the_seven_readings_from_a_made_up_record(chip_run, reader):
    run = _made_up_run(chip_run)
    assert reader.read(run) == pytest.approx({
        "setup_pkg_import_s": 3.5,
        "setup_deferred_init_s": 2.0,           # the pass, not its layers
        "setup_step_trace_s": 3.0,              # trace 2.0 + lower 1.0
        "setup_step_load_s": 1.5,
        # initialize 0.4 + deferred_init 0.6 + eager 0.9: the 5 and the 800
        # enclosed traces are no seconds of their own
        "setup_eager_build_s": 1.9,
        "setup_eager_programs": 3,
        "window_builds": 1})
    facts = run.facts["startup"]
    assert "startup_unread" not in run.facts
    assert facts["outside_package_s"] == pytest.approx(6.5)
    assert facts["user_s"] == pytest.approx(
        {"trace": 1.0, "lower": 1.0, "backend": 1.0})
    assert facts["user_programs"] == 1 and facts["records_dropped"] == 0
    assert "user" not in facts["seconds_by_owner_stage"]
    assert facts["seconds_by_owner_stage"]["memory_analysis"] == \
        pytest.approx({"lower": 1.0, "backend": 0.8})
    first = facts["costliest_programs"][0]
    assert (first["name"], first["owner"], first["cache"]) == (
        "dp_train_step", "dispatch", "hit")
    assert first["seconds"] == pytest.approx(4.5)
    assert facts["costliest_programs"][1]["cache"] == "miss"    # the user's


def test_every_second_of_a_phase_is_counted_once(chip_run, reader):
    run = _made_up_run(chip_run)
    reader.read(run)
    phases = run.facts["startup"]["by_phase"]
    assert list(phases) == ["import", "init", "reference", "driver", "warmup",
                            "memory"]
    for seconds in phases.values():
        whole = seconds.pop("phase_s")
        assert sum(seconds.values()) == pytest.approx(whole)
    near = lambda want: pytest.approx(want, abs=1e-6)       # noqa: E731
    assert phases["import"] == near(
        {"package_import": 3.5, "unrecorded": 6.5})
    assert phases["init"] == near(
        {"initialize": 0.6, "eager.trace": 0.1, "eager.lower": 0.2,
         "eager.backend": 0.1, "unrecorded": 1.0})
    assert phases["reference"] == near(
        {"deferred_init": 1.4, "eager.trace": 0.3, "eager.lower": 0.5,
         "eager.backend": 0.7, "user.trace": 1.0, "user.lower": 1.0,
         "user.backend": 1.0, "unrecorded": 2.1})
    assert phases["driver"] == near({"unrecorded": 1.0})
    assert phases["warmup"] == near(
        {"gather_params": 0.5, "step.trace": 2.0, "step.lower": 1.0,
         "step.backend": 1.5, "unrecorded": 1.0})
    assert phases["memory"] == near(
        {"memory_analysis": 0.2, "memory_analysis.lower": 1.0,
         "memory_analysis.backend": 0.8, "unrecorded": 1.0})


def test_a_model_built_twice_is_one_phase(chip_run, reader):
    """``first_loss``: ``init`` is the sum of two stretches, so everything
    between the import and the driver is ``model``, and the reference's own
    steps, which ``setup_s`` leaves out, lie in it."""
    run = _made_up_run(chip_run)
    run.setup = {"init": 2.0, "reference": 3.0, "first_loss": 3.0,
                 "reference_steps": 5.0, "driver": 1.0, "warmup": 6.0,
                 "memory": 3.0}
    run.setup_s = 25.0
    reader.read(run)
    phases = run.facts["startup"]["by_phase"]
    assert list(phases) == ["import", "model", "driver", "warmup", "memory"]
    assert phases["model"]["phase_s"] == pytest.approx(10.0)
    assert phases["model"]["unrecorded"] == pytest.approx(3.1)
    assert phases["warmup"]["step.trace"] == pytest.approx(2.0)


def test_a_window_build_has_its_idle_gap_beside_its_name(chip_run, reader):
    run = _made_up_run(chip_run)
    reader.read(run)
    trace, lower, backend = run.facts["startup"]["window_builds"]
    assert (backend["name"], backend["owner"], backend["step"]) == (
        "dp_train_step", "dispatch", 41)
    assert backend["seconds"] == pytest.approx(100e-6)
    # the device idles over [3900, 4000] us: the lowering [3900, 3950] and
    # the compile [3950, 4050] lie across it, the trace [3850, 3900] does not
    assert [row["idle_gap_s"] for row in (trace, lower, backend)] == \
        pytest.approx([0.0, 100e-6, 100e-6], abs=1e-9)
    # without a device trace the build is named all the same
    run = _made_up_run(chip_run)
    run.reduced = run.recorded = None
    assert reader.read(run)["window_builds"] == 1
    assert [row["stage"] for row in run.facts["startup"]["window_builds"]] \
        == ["trace", "lower", "backend"]
    assert "idle_gap_s" not in run.facts["startup"]["window_builds"][0]


@pytest.mark.parametrize("change, agrees", [
    ({}, True),
    # the benchmark's listener saw a program the record has not
    ({"programs": 7}, False),
    # the same programs, other seconds
    ({"compile_s": 1.2}, False),
    # the record may never hold more than the yardstick
    ({"programs": 5}, False),
])
def test_agreement_with_the_compile_log(chip_run, reader, change, agrees):
    run = _made_up_run(chip_run)
    run.compile_setup.update(change)
    reader.read(run)
    said = run.facts["startup"]["agrees_with_compile_log"]
    assert said["agrees"] is agrees
    assert said["programs"] == [7, 1 + run.compile_setup["programs"]]
    assert said["seconds"][0] == pytest.approx(4.1001)


def test_a_full_record_may_fall_short_by_what_it_dropped(chip_run, reader):
    run = _made_up_run(chip_run)
    run.compile_setup["programs"] = 9
    run.mx.telemetry.startup().dropped = 12
    reader.read(run)
    said = run.facts["startup"]["agrees_with_compile_log"]
    assert said["agrees"] is True and said["records_dropped"] == 12


def test_a_program_without_the_record_reads_nothing(chip_run, reader):
    """The parent commit under this PR's benchmark files."""
    run = _made_up_run(chip_run, accessor=False)
    assert reader.read(run) == {} and run.facts == {"import_s": 10.0}
    run.mx = types.SimpleNamespace(telemetry=types.SimpleNamespace())
    assert reader.read(run) == {} and run.facts == {"import_s": 10.0}


def test_a_record_that_cannot_be_read_fails_no_run(chip_run, reader):
    run = _made_up_run(chip_run)
    run.mx.telemetry.startup = lambda: [("build", "short")]
    assert reader.read(run) == {}
    assert "startup" not in run.facts and "startup_unread" in run.facts
