"""The chip benchmark: one cell, one process, one result line.

    python3 benchmark/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/``) under a traffic mix (``traffic/``), driven by the driver the
mix names (``drivers/``).  The run builds the model through ``gluon`` from
``--seed``, holds its forward and its first loss to the configuration's
plain reference, warms up the cell's own shapes (all of that is
``setup_s``), then measures train steps for ``--seconds`` with two steps in
flight; throughput is every sample of the window's steps over the time from
its start to when their work is done (``chipbench/timing.py``).  Where the
configuration's step draws random numbers, the reference checks are made on
the model its JSON names under ``first_loss_with`` (``set_up``).
With ``--trace 1`` a few seconds inside the window are traced with the JAX
profiler and the per-layer readers (``layer_metrics/``) report from the
trace, the host spans and the counters.

The last line of stdout is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced); the line before it, ``[facts] {...}``, holds what a reader of the
run wants beside it.  Without a TPU, with fewer chips than the cell asks
for, or with a device kind that has no published peak on record, the exit
code is non-zero and no result line is printed.  ``--rehearse`` is the CPU
rehearsal of the control flow at toy sizes: its result says
``"rehearsal": true`` and carries no device metric.
"""
import time

_T0 = time.perf_counter()           # set-up counts from here

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import gc                           # noqa: E402
import json                         # noqa: E402
import pathlib                      # noqa: E402
import shutil                       # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from chipbench import (catalog, compile_log, first_steps,  # noqa: E402
                       inputs, peaks, timing, trace)

TRACE_SECONDS = 3.0                 # the traced sub-window
WARMUP_STEPS = 3


class Run:
    """What one run knows; drivers fill it and readers read it."""

    def __init__(self, **kw):
        self.setup = {}             # phase -> seconds
        self.spans = {}             # host span name -> [seconds]
        self.facts = {}
        self.checks = {}            # name -> bool: all must hold
        self.recorded = self.reduced = None
        self.traced_stamps = (None, None)
        self.__dict__.update(kw)

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.setup[name] = self.setup.get(name, 0.0) + time.perf_counter() - t0

    @contextlib.contextmanager
    def span(self, name):
        """A host span: into the profiler's trace under ``bench:<name>`` and
        into ``spans`` on the host clock."""
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU only: toy sizes, no device metric")
    return ap.parse_args(argv)


def require_devices(jax, chips, rehearse):
    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform != "cpu":
            raise SystemExit("chip benchmark: --rehearse is the CPU "
                             "rehearsal; found %s" % dev.platform)
    elif dev.platform != "tpu":
        raise SystemExit("chip benchmark: needs a TPU, JAX found platform "
                         "%r (%s x%d)" % (dev.platform, dev.device_kind,
                                          len(devices)))
    if len(devices) < chips:
        raise SystemExit("chip benchmark: the cell needs %d chip(s), JAX "
                         "sees %d" % (chips, len(devices)))
    return devices, (None if rehearse else peaks.peaks_for(dev.device_kind))


def reference_check(run, net, x, y):
    """The Block's own float32 forward on a few seeded rows against the
    configuration's plain reference, and the reference's loss on the whole
    batch for the first step to be held to.  Doubles as the eager pass that
    resolves the Block's deferred shapes."""
    jax, mx, mod, sizes = run.jax, run.mx, run.module, run.sizes
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    rows = mod.check_rows(sizes, run.traffic)
    sample = jax.device_put(np.asarray(x[:rows]), run.devices[0])
    # both sides at full float32 matmul precision (the TPU's default for a
    # float32 matmul is bf16 passes): this check is of the Block's
    # mathematics, and is tight; the precision the job computes in is held
    # to the reference by the first step's loss
    with jax.default_matmul_precision("highest"):
        with mx.autograd.train_mode():
            got = net(mx.nd.NDArray(sample))._read()
        params = {name[len(mod.PREFIX):]: p.data()._read().astype(jnp.float32)
                  for name, p in net.collect_params().items()}
        want = jax.jit(lambda p, s: mod.reference(sizes, p, s))(params, sample)
        everywhere = jax.device_put(
            params, NamedSharding(run.mesh, PartitionSpec()))
        loss = jax.jit(lambda p, a, b: mod.reference_loss(
            mod.reference(sizes, p, a), b))(everywhere, x, y)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    run.facts["block_vs_reference_rel_err"] = err
    run.facts["reference_loss"] = run.reference_loss = float(loss)
    run.checks["block_agrees_with_reference"] = (
        err <= mod.TOLERANCE["block_f32"])


def memory_in_use(devices):
    """Largest ``bytes_in_use`` and allocator peak over the devices; zeros
    where the backend reports nothing (the CPU rehearsal)."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(int(s.get("bytes_in_use", 0)) for s in stats),
            max(int(s.get("peak_bytes_in_use", 0)) for s in stats))


class Tracer:
    """Traces TRACE_SECONDS in the middle of the window: started and
    stopped from the loop's stamp callback."""

    def __init__(self, run, seconds):
        self.run, self.dir = run, None
        self.start_at = time.perf_counter() + seconds / 3.0
        self.stop_at = None
        self.first_stamp = self.last_stamp = None

    def on_stamp(self, n):
        now = time.perf_counter()
        if self.dir is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            options = self.run.jax.profiler.ProfileOptions()
            # the host spans are TraceAnnotations; tracing every Python
            # call as well would slow the very host path that is measured
            options.python_tracer_level = 0
            self.run.jax.profiler.start_trace(self.dir,
                                              profiler_options=options)
            self.stop_at = time.perf_counter() + TRACE_SECONDS
            self.first_stamp = n
        elif self.stop_at is not None and now >= self.stop_at:
            self.run.jax.profiler.stop_trace()
            self.stop_at, self.last_stamp = None, n

    def finish(self):
        """Record and reduce the trace, then remove the profiler's files."""
        if self.dir is None:
            return
        try:
            if self.stop_at is not None:        # the window ended first
                self.run.jax.profiler.stop_trace()
            self.run.recorded = trace.record(trace.find_xplane(self.dir))
            self.run.reduced = trace.reduce(self.run.recorded)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def open_run(args, root):
    """Find the cell's parts by name, check the devices, import the
    program.  Nothing of JAX or the program is imported before the device
    check."""
    cat = catalog.Catalog(root or HERE.parent.parent)
    if str(cat.root) not in sys.path:
        sys.path.insert(0, str(cat.root))
    cell = cat.cell(args.workload)
    sizes, module = cat.config(cell["config"], args.rehearse)
    traffic = cat.traffic(cell["traffic"], args.rehearse)
    driver = cat.driver(traffic["driver"])

    import jax
    devices, peak = require_devices(jax, cell["chips"], args.rehearse)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import make_mesh
    devices = devices[:cell["chips"]]
    run = Run(jax=jax, mx=mx, args=args, cell=cell, sizes=sizes,
              module=module, traffic=traffic, driver=driver, devices=devices,
              peaks=peak, catalog=cat, chips=len(devices),
              rehearse=args.rehearse, log=compile_log.CompileLog(jax),
              mesh=make_mesh({"dp": len(devices)}, devices))
    run.facts["import_s"] = time.perf_counter() - _T0
    return run


def mean_loss(loss):
    import numpy as np
    return float(np.mean(np.asarray(loss)))


def seeded_net(run, sizes):
    """The configuration's model through ``gluon``, weights drawn from
    ``--seed``: the same seed and sizes give the same weights."""
    run.mx.random.seed(run.args.seed)
    net = run.module.build(sizes)
    net.initialize(run.module.initializer(sizes))
    return net


def set_up(run):
    """Weights and inputs from the seed, the reference checks, the driver's
    job, the warm-up of the cell's own shapes.  Everything up to the first
    timed step is ``setup_s``.

    Where a configuration's step draws random numbers (dropout), neither
    its forward nor its loss can be held to the plain reference.  Its JSON
    then names, under ``first_loss_with``, the sizes that switch the
    randomness off; the reference checks are made on a model built with
    them, and the first loss is that of one step of the same driver's job
    over it: the same builder, seed and program but for the masks.  Where
    the optimizer has a plain twin (``chipbench/first_steps.py``) that job
    makes three steps, and once it is freed the reference follows them:
    the reference's own time, left out of ``setup_s``.  The model is freed
    before the timed one is built, because a chip does not hold both."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    jax, mx, module, sizes, traffic = (run.jax, run.mx, run.module, run.sizes,
                                       run.traffic)
    quiet = sizes.get("first_loss_with")
    with run.phase("init"):
        net = seeded_net(run, dict(sizes, **quiet) if quiet else sizes)
        rows = traffic["batch_per_chip"] * run.chips
        x, y = inputs.make_batch(
            jax, run.args.seed, rows, module.sample_shape(sizes, traffic),
            sizes, NamedSharding(run.mesh, PartitionSpec("dp")))
        mx.nd.waitall()
    run.samples_per_step = rows * module.samples_per_row(sizes, traffic)
    with run.phase("reference"):
        reference_check(run, net, x, y)
    run.first_loss = None           # the timed job's own, unless:
    run.first_steps = None
    if quiet:
        with run.phase("first_loss"):
            start = None
            if first_steps.applies(sizes):
                cut = len(module.PREFIX)
                start = {n[cut:]: np.asarray(p.data()._read(), np.float32)
                         for n, p in net.collect_params().items()}
            job = run.driver.setup(run, net, x, y)
            run.first_loss = mean_loss(job.step())
            if start is not None:
                got = first_steps.program_side(run, job, run.first_loss,
                                               start)
            del job, net
            gc.collect()            # a Block and its children are a cycle
            run.facts["bytes_in_use_once_freed"] = memory_in_use(
                run.devices)[0]
        if start is not None:
            with run.phase("reference_steps"):
                want = first_steps.reference_side(
                    jax, module, sizes, start, x, y,
                    module.check_rows(sizes, traffic), run.devices[0])
                gaps, where = first_steps.compare(got, want)
                run.first_steps = {"program": got, "reference": want,
                                   "gaps": gaps, "where": where}
                del start
        with run.phase("init"):
            net = seeded_net(run, sizes)
            mx.nd.waitall()
    with run.phase("driver"):
        job = run.driver.setup(run, net, x, y)
    with run.phase("warmup"):
        run.warmup_losses = [job.step() for _ in range(WARMUP_STEPS)]
        run.warmup_losses[-1].block_until_ready()
        mx.nd.waitall()
    with run.phase("memory"):
        run.bytes_in_use, _ = memory_in_use(run.devices)
        run.temp_bytes = job.temp_bytes()
    run.setup_mark = run.log.mark()
    run.setup_s = (time.perf_counter() - _T0
                   - run.setup.get("reference_steps", 0.0))
    return job


@contextlib.contextmanager
def collector_log(run):
    """Python's cyclic collector while the block runs, for ``window_facts``:
    each collection as ``(generation, clock at its start, seconds)``.  A
    full collection stops the loop for some 90 ms in the Gluon cell."""
    run.collections, began = [], [0.0]

    def note(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            run.collections.append((info["generation"], began[0],
                                    time.perf_counter() - began[0]))
    gc.callbacks.append(note)
    try:
        yield
    finally:
        gc.callbacks.remove(note)


def measure(run, job):
    """The window: train steps for ``--seconds``, two in flight; with
    ``--trace 1`` a traced sub-window inside it."""
    args = run.args
    tracer = Tracer(run, args.seconds) if args.trace else None
    run.spans.clear()               # the window's own spans, not set-up's
    with collector_log(run):
        run.stamps, run.losses, run.window_span = timing.run_window(
            job.step, args.seconds, run.span,
            on_stamp=tracer.on_stamp if tracer else None,
            settle=run.mx.nd.waitall)
    run.window_mark = run.log.mark()
    if tracer:
        tracer.finish()
        run.traced_stamps = (tracer.first_stamp, tracer.last_stamp)


def attention_paths(mx):
    """``flash_attention`` traces by path, the op's own counter."""
    snap = mx.telemetry.registry().snapshot().get(
        "graft_flash_attention_traces_total", {"samples": []})
    return {s["labels"]["path"]: int(s["value"]) for s in snap["samples"]}


def window_facts(run, steps):
    """The window's median block beside its rate: what a reader needs to
    tell a single stall from a slow stretch, and where it fell.  The rate
    of the window's first half is what a run of half the length would have
    read."""
    size, blocks = timing.block_seconds(run.stamps)
    slowest = sorted(range(len(steps)), key=steps.__getitem__)[-3:][::-1]
    start, done = run.window_span
    half = [t for t in run.stamps if t - start <= run.args.seconds / 2.0]
    return {
        "median_block_samples_per_s_per_chip": timing.median_block_rate(
            run.stamps, run.samples_per_step) / run.chips,
        "first_half_samples_per_s_per_chip": None if not half else (
            timing.window_rate(len(half), run.samples_per_step, start,
                               half[-1]) / run.chips),
        "lost_pct": 100.0 * timing.lost_share(run.stamps),
        "seconds": done - start,
        "last_loss_to_done_s": done - run.stamps[-1],
        "block_steps": size, "blocks": len(blocks),
        "slowest_block_s": max(blocks), "block_s": blocks,
        # [seconds, seconds into the window at which the step began, the
        # host's spans between the two stamps: the enqueueing of the step
        # two on and the wait for the next loss]
        "slowest_steps": [[steps[i], run.stamps[i] - run.stamps[0],
                           host_spans(run.spans, i)] for i in slowest],
        "host_spans_p50_s": {k: timing.percentile(v, 50)
                             for k, v in run.spans.items()},
        # Python's cyclic collector: [collections, seconds] a generation,
        # and [seconds into the window, seconds] of each full collection
        "collector": [[sum(1 for g, _, _ in run.collections if g == n),
                       sum(d for g, _, d in run.collections if g == n)]
                      for n in range(3)],
        "full_collections": [[t - start, d] for g, t, d in run.collections
                             if g == 2]}


def host_spans(spans, i):
    """The host spans between stamp ``i`` and stamp ``i + 1``: step
    ``i + 2`` is enqueued (a driver's own spans lie inside ``enqueue``),
    then loss ``i + 1`` is waited for."""
    at = {name: i + 1 if name == "wait" else i + 2 for name in spans}
    return {name: spans[name][k] for name, k in at.items()
            if k < len(spans[name])}


def report(run, job):
    """Checks, metrics and facts of a finished run; returns the result."""
    import numpy as np
    args, cell, devices = run.args, run.cell, run.devices

    values = [mean_loss(v) for v in run.losses]
    failed = sum(1 for v in values if not np.isfinite(v))
    # over the whole run, warm-up included: its first steps start from the
    # seeded weights
    history = [mean_loss(v) for v in run.warmup_losses] + values
    first_loss = history[0] if run.first_loss is None else run.first_loss
    k = min(10, len(history) // 2)
    run.compile_setup = run.log.between(0, run.setup_mark)
    run.compile_window = run.log.between(run.setup_mark, run.window_mark)
    tolerance = run.module.TOLERANCE[
        "step_loss_rehearsal" if run.rehearse else "step_loss"]
    paths = attention_paths(run.mx)
    run.checks.update(
        first_loss_agrees_with_reference=abs(
            first_loss - run.reference_loss) <= tolerance * run.reference_loss,
        losses_finite=failed == 0,
        loss_fell=(statistics.fmean(history[-k:])
                   < statistics.fmean(history[:k])),
        no_compile_in_window=run.compile_window["programs"] == 0,
        # on the chip attention runs the Pallas kernel or nothing: the jnp
        # paths are the CPU's (and the rehearsal's)
        attention_took_no_reference_path=run.rehearse or not any(
            n for p, n in paths.items() if p.startswith("reference")),
        **job.checks())
    if run.first_steps is not None:
        suffix = "_rehearsal" if run.rehearse else ""
        limits = {k: run.module.TOLERANCE[k + suffix]
                  for k in run.first_steps["gaps"]}
        run.checks.update({k + "_agrees_with_reference": gap <= limits[k]
                           for k, gap in run.first_steps["gaps"].items()})
        run.facts["first_steps"] = dict(
            {k: {"gap": gap, "limit": limits[k],
                 "at": run.first_steps["where"][k]}
             for k, gap in run.first_steps["gaps"].items()},
            losses=run.first_steps["program"]["losses"],
            reference_losses=run.first_steps["reference"]["losses"])

    in_use_after, allocator_peak = memory_in_use(devices)
    in_use = max(run.bytes_in_use, in_use_after)
    peak_bytes = (None if run.temp_bytes is None
                  else in_use + run.temp_bytes)
    # all the work of the window over all of its time: a stall inside it
    # counts, whoever's it is (``window_lost_pct`` says how much went)
    run.throughput = timing.window_rate(
        len(run.stamps), run.samples_per_step, *run.window_span) / run.chips
    all_steps = timing.step_seconds(run.stamps)
    run.step_samples = (timing.untraced_steps(run.stamps, run.traced_stamps)
                        or all_steps)
    end_to_end = {
        "setup_s": run.setup_s,
        "samples_per_s_per_chip": run.throughput,
        "peak_hbm_gb": None if peak_bytes is None else peak_bytes / 1e9,
    }
    if args.trace:
        have = {}
        for reader in run.catalog.readers():
            have.update(reader.read(run) or {})
        wanted = run.catalog.metrics("per_layer", cell["name"])
    else:
        have = end_to_end
        wanted = run.catalog.metrics("end_to_end", cell["name"])
    # a reader that found nothing to read leaves its metric out of the line.
    # "<metric>.<anything>" is read as "<metric>": a metric that lists its
    # cells is given to a later cell by an entry of that cell's own
    found = {m["name"]: have.get(m["name"],
                                 have.get(m["name"].partition(".")[0]))
             for m in wanted}
    metrics = {m["name"]: {"value": found[m["name"]], "unit": m["unit"]}
               for m in wanted if found[m["name"]] is not None}
    if run.rehearse:
        # a CPU run gives no device number: the names the cell would
        # report, for the rehearsal to check, and none of the values
        metrics = {name: dict(m, value=None) for name, m in metrics.items()}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(run.jax.devices()),
              "memory_peak_bytes": max(allocator_peak, peak_bytes or 0)}
    result = {"correct": all(run.checks.values()), "attempted": len(values),
              "failed": failed, "metrics": metrics, "device": device}
    if run.rehearse:
        result["rehearsal"] = True
    if run.reduced is not None:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        result["breakdown"] = trace.breakdown(run.reduced,
                                              run.recorded["ops"])

    run.facts.update(
        workload=cell["name"], seed=args.seed, seconds=args.seconds,
        checks=run.checks, attention_paths=paths,
        metrics_left_out=[m["name"] for m in wanted
                          if m["name"] not in metrics],
        setup_phases_s=run.setup, compile_setup=run.compile_setup,
        compile_window=run.compile_window, steps=len(values),
        samples_per_step=run.samples_per_step,
        median_step_samples_per_s_per_chip=(
            run.samples_per_step / timing.percentile(run.step_samples, 50)
            / run.chips),
        window=window_facts(run, all_steps),
        first_loss=first_loss, first_loss_of_the_timed_job=history[0],
        losses_first=values[:5],
        losses_last=values[-5:],
        end_to_end={k: v for k, v in end_to_end.items() if v is not None},
        bytes_in_use=in_use, allocator_peak_bytes=allocator_peak,
        step_temp_bytes=run.temp_bytes,
        compile_cache_dir=run.jax.config.jax_compilation_cache_dir)
    first, last = run.traced_stamps
    inside = all_steps[first + 1:last - 2] if last else []
    if inside:
        run.facts["step_ms_p50_traced_and_not"] = [
            1e3 * timing.percentile(inside, 50),
            1e3 * timing.percentile(run.step_samples, 50)]
    if run.reduced is not None:
        run.facts["trace"] = {
            k: run.reduced[k] for k in (
                "window_s", "steps", "devices", "busy_s", "idle_share",
                "programs", "collective_s", "collective_exposed_s",
                "category_s")}
    return result


def main(argv=None, root=None):
    """``root`` is the checkout that holds ``BENCHMARK.json`` and
    ``benchmark/chip`` (this file's own by default)."""
    run = open_run(parse(argv), root)
    job = set_up(run)
    measure(run, job)
    result = report(run, job)
    print("[facts] %s" % json.dumps(run.facts, default=str))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
