"""Where set-up goes, told from inside the program: ``telemetry.startup()``,
the record of the package's import, its set-up spans and every stage
(``trace``, ``lower``, ``backend``: a compile or a read of the persistent
cache) of every program the process built, each put down to the program
span that was open when JAX built it (its ``owner``).

``setup_pkg_import_s``     the ``package_import`` record: ``import
                           incubator_mxnet_tpu``, JAX's own import left out
                           where ``run.py`` made it first.
``setup_deferred_init_s``  the ``deferred_init`` spans before the window:
                           the eager pass that fills the Block's open
                           shapes, the operators' programs it builds inside.
``setup_step_trace_s``     ``trace`` + ``lower`` of the programs the train
                           step's spans asked for (``dispatch``, ``fwd``,
                           ``bwd``, ``update``): Python time no cache saves.
``setup_step_load_s``      ``backend`` of the same: a read of the cache on a
                           warm run, a compile on a cold one.
``setup_eager_build_s``    every stage of the programs of the operator
                           library and the engine (owners ``eager``,
                           ``engine_flush``, ``initialize``,
                           ``deferred_init``).
``setup_eager_programs``   how many programs those were.
``window_builds``          programs whose ``backend`` stage began inside the
                           measured window; must be 0.  The twin of
                           ``window_compiles``, from inside.

A trace that lies inside another stage (a jitted function's inside its
step's) is no record: the program counts it on the record that encloses it
(``enclosed``), so every second here is counted once.  ``facts["startup"]`` has the
seconds by owner and stage (owner ``user`` apart: a ``jax.jit`` the package
did not make, which in a cell is the plain reference's and the benchmark's
own programs), the ten costliest programs, ``outside_package_s``
(``facts.import_s`` less the package's import: JAX's import and the
backend's opening, which ``run.py`` makes first), the records the full
record turned away, ``agrees_with_compile_log`` (the record's ``backend``
stages from the end of the package's import to the end of the window against
``compile_log.py``'s count and seconds, which it may fall short of by what
the record dropped and never exceed), ``by_phase`` (``run.py``'s set-up
phases laid over the record: the seconds of each that lie in a record, by
what the record is, every second counted once, and those that lie in none)
and, with a device trace, each build inside the window with the first
device's idle gaps it lies across.

A program without ``telemetry.startup`` (an older commit) reads nothing and
leaves no fact.
"""
import collections

from chipbench import program, trace

STEP_OWNERS = ("dispatch", "fwd", "bwd", "update")
EAGER_OWNERS = ("eager", "engine_flush", "initialize", "deferred_init")
USER = "user"
COSTLIEST = 10
AGREE_WITHIN = 0.01


def _builds(records):
    """``(name, start, end, parent, step, attrs)`` of the build records."""
    return [r[1:] for r in records if r[0] == "build"]


def _group(owner):
    return ("step" if owner in STEP_OWNERS else
            "eager" if owner in EAGER_OWNERS else owner)


def _programs(builds):
    """One entry a program: its stages run one after the other and end with
    ``backend``; ``[seconds, name, owner, cache, step]``."""
    found, open_ = [], {}
    for name, start, end, _parent, step, attrs in builds:
        entry = open_.setdefault(name, [0.0, name, attrs["owner"], None, step])
        entry[0] += end - start
        if attrs["stage"] == "backend":
            entry[3] = attrs.get("cache")
            found.append(open_.pop(name))
    return found + list(open_.values())


def _agreement(run, imported, builds, dropped):
    """The record's ``backend`` stages against the benchmark's own listener
    (``chipbench/compile_log.py``, registered once the package is imported)
    up to the end of the window."""
    logs = [getattr(run, "compile_setup", None),
            getattr(run, "compile_window", None)]
    if None in logs:
        return None
    done = run.window_span[1]
    mine = [end - start for _, start, end, _, _, attrs in builds
            if attrs["stage"] == "backend" and imported <= start < done]
    theirs = sum(log["programs"] for log in logs)
    theirs_s = sum(log["compile_s"] + log["cache_retrieval_s"]
                   for log in logs)
    short = theirs - len(mine)
    return {"programs": [len(mine), theirs], "seconds": [sum(mine), theirs_s],
            "records_dropped": dropped,
            "agrees": (0 <= short <= dropped and (
                short > 0 or abs(sum(mine) - theirs_s)
                <= AGREE_WITHIN * max(theirs_s, 1e-9)))}


def _phases(run):
    """``run.py``'s set-up phases as ``[(name, start, end)]`` on
    ``time.perf_counter()``, rebuilt from their seconds: they follow one
    another from the process's start to the window's.  Where the model is
    built twice (``first_loss``) ``init`` is the sum of two stretches that
    cannot be told apart, and everything between the import and the driver
    is one phase, ``model``."""
    setup, start = run.setup, run.window_span[0]
    began = start - run.setup_s - setup.get("reference_steps", 0.0)
    first = [("import", run.facts["import_s"])]
    if "first_loss" not in setup:
        first += [(n, setup[n]) for n in ("init", "reference") if n in setup]
    last = [(n, setup[n]) for n in ("driver", "warmup", "memory")
            if n in setup]
    out, at = [], began
    for name, seconds in first:
        out.append((name, at, at + seconds))
        at += seconds
    end = start - sum(s for _, s in last)
    if "first_loss" in setup:
        out.append(("model", at, end))
    for name, seconds in last:
        out.append((name, end, end + seconds))
        end += seconds
    return out


def _by_phase(run, records):
    """Each phase's seconds by the innermost record they lie in: a build by
    its owner's group and its stage, a span or the import by its name, and
    ``unrecorded`` for what lies in none."""
    events = [(name, s, e) for kind, name, s, e, *_ in records
              if kind != "build"]
    events += [("%s.%s" % (_group(a["owner"]), a["stage"]), s, e)
               for _, s, e, _, _, a in _builds(records)]
    out = {}
    for phase, lo, hi in _phases(run):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        seconds = collections.Counter()
        for name, own in trace.self_times(inside):
            seconds[name] += own
        seconds["unrecorded"] = hi - lo - sum(seconds.values())
        out[phase] = dict(seconds, phase_s=hi - lo)
    return out


def _window_builds(run, builds):
    """Each build inside the window and, where the traced part of the window
    holds it, the first device's idle gaps it lies across, in seconds."""
    start, done = run.window_span
    inside = [b for b in builds if start <= b[1] < done]
    gaps = []
    aligned = program.clock_offset(run) if run.reduced else None
    if aligned is not None:
        window, _ = trace.step_window(trace.host_spans(run.recorded))
        ops = trace._line(trace.device_planes(run.recorded)[0],
                          trace.OPS_LINE)
        busy = trace.union((max(s, window[0]), min(s + d, window[1]))
                           for _, s, d in ops
                           if s + d > window[0] and s < window[1])
        gaps = trace.subtract([window], busy)
    out = []
    for name, s, e, parent, step, attrs in inside:
        row = {"name": name, "stage": attrs["stage"],
               "owner": attrs["owner"], "parent": parent, "step": step,
               "seconds": e - s, "at_s": s - start}
        if aligned is not None:
            lo, hi = ((t + aligned[0]) * 1e9 for t in (s, e))
            if lo >= window[0] and hi <= window[1]:
                row["idle_gap_s"] = sum(
                    g1 - g0 for g0, g1 in gaps if g1 > lo and g0 < hi) / 1e9
        out.append(row)
    return out


def read(run):
    t = program.telemetry(run)
    if t is None or not callable(getattr(t, "startup", None)):
        return {}
    try:
        return _read(run, t.startup())
    except Exception as e:      # noqa: BLE001 - a reading fails no run
        run.facts["startup_unread"] = repr(e)
        return {}


def _read(run, records):
    start, done = run.window_span
    before = [r for r in records if r[2] < start]
    builds, every_build = _builds(before), _builds(records)
    imports = [r for r in records if r[0] == "import"]

    by_owner = collections.defaultdict(collections.Counter)
    programs = collections.Counter()
    for _, s, e, _, _, attrs in builds:
        by_owner[attrs["owner"]][attrs["stage"]] += e - s
        programs[attrs["owner"]] += attrs["stage"] == "backend"

    def seconds(owners, stages):
        return sum(by_owner[o][st] for o in owners for st in stages
                   if o in by_owner)

    out = {
        "setup_deferred_init_s": sum(
            e - s for kind, name, s, e, parent, *_ in before
            if kind == "span" and name == "deferred_init"
            and parent != "deferred_init"),
        "setup_step_trace_s": seconds(STEP_OWNERS, ("trace", "lower")),
        "setup_step_load_s": seconds(STEP_OWNERS, ("backend",)),
        "setup_eager_build_s": seconds(EAGER_OWNERS,
                                       ("trace", "lower", "backend")),
        "setup_eager_programs": sum(programs[o] for o in EAGER_OWNERS),
        "window_builds": sum(
            1 for _, s, _, _, _, attrs in every_build
            if attrs["stage"] == "backend" and start <= s < done)}
    facts = {
        "seconds_by_owner_stage": {o: dict(v) for o, v in by_owner.items()
                                   if o != USER},
        "programs_by_owner": {o: n for o, n in programs.items() if o != USER},
        "user_s": dict(by_owner.get(USER, {})),
        "user_programs": programs[USER],
        "costliest_programs": [
            {"seconds": p[0], "name": p[1], "owner": p[2], "cache": p[3],
             "step": p[4]}
            for p in sorted(_programs(builds), reverse=True)[:COSTLIEST]],
        "records": len(records), "records_dropped": records.dropped,
        "window_builds": _window_builds(run, every_build)}
    if imports:
        _, _, s, e, _, _, attrs = imports[0]
        out["setup_pkg_import_s"] = e - s
        facts["package_import"] = attrs
        facts["outside_package_s"] = run.facts["import_s"] - (e - s)
        facts["agrees_with_compile_log"] = _agreement(
            run, e, every_build, records.dropped)
        facts["by_phase"] = _by_phase(run, before)
    run.facts["startup"] = dict(out, **facts)
    return out
