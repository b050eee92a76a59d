"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main path once, in ONE process, through the entry points
a user calls (``gluon.model_zoo.vision``, ``gluon.nn``, ``gluon.loss``,
``parallel.make_mesh``, ``parallel.DataParallelTrainer``, the op registry),
at the full width of the models the benches measure, with random weights
made from a seed:

1. device     — a TPU or exit non-zero; versions and compile-cache directory
2. resnet50   — ResNet-50 b256/chip bf16, 5 fused train steps
3. flash      — ``_contrib_FlashAttention`` (Pallas, compiled by Mosaic),
                forward and backward through ``autograd``, against the
                jnp reference at full f32 precision
4. lm         — the decoder LM of ``bench_transformer.py`` at its published
                width, 3 fused train steps (the kernel under
                ``value_and_grad`` + donation in one program)
5. dropout    — one key gives one dropout mask in every program on the
                chip's own bit generator: eagerly, under ``jit`` inside
                other work, and in the Gluon loop's forward and backward
                programs (``CachedOp`` runs the forward again in the second)
6. cache      — a second ResNet trainer after ``jax.clear_caches()``: its
                step must come out of the persistent compile cache

Any failed check raises: the exit code is non-zero and no result line is
printed.  The facts of the run are the second-last line of stdout,
``[result] {...}``; the last line is one JSON object with these keys and
no others, the device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py              one chip
    python chip_smoke.py --chips 4    phase 2 over a dp=4 mesh, global b1024
    python chip_smoke.py --rehearse   CPU only: toy sizes, phases 3, 4 and 5
                                      skipped by name, "rehearsal": true

Times printed here are smoke readings from a handful of steps, not
benchmark numbers.
"""
import argparse
import functools
import importlib.metadata
import json
import os
import statistics
import sys
import time

import numpy as np

SKIPPED = "skipped: no chip"

# Full sizes are the benches' own (bench.py, bench_transformer.py).  The LM
# keeps its published width — d_model, heads, FFN, sequence, batch and vocab
# set every kernel and matmul shape — and is cut in DEPTH from 4 layers to
# 2: depth only repeats those shapes, and doubles compile time and HBM.
FULL = {
    "resnet": dict(model="resnet50_v1", classes=1000, image=224,
                   thumbnail=False, batch_per_chip=256),
    "flash": [dict(shape=(8, 32, 1024, 128), dtype="bfloat16", causal=True,
                   tol=2e-2),
              # D=64 is zero-padded to the 128 lanes inside the kernel
              dict(shape=(2, 4, 256, 64), dtype="float32", causal=False,
                   tol=2e-3),
              # the benchmark's OPT cell: four 512-blocks a side, six of
              # the sixteen skipped
              dict(shape=(4, 32, 2048, 128), dtype="bfloat16", causal=True,
                   tol=2e-2),
              # Sq < Sk: the diagonal is anchored at the end of the keys
              dict(shape=(2, 4, 128, 128), kv_len=256, dtype="float32",
                   causal=True, tol=2e-3)],
    "lm": dict(vocab=16384, d_model=4096, n_heads=32, d_ffn=16384,
               n_layers=2, seq_len=1024, batch=8),
}
REHEARSAL = {
    "resnet": dict(model="resnet18_v1", classes=10, image=32,
                   thumbnail=True, batch_per_chip=8),
}


def check(cond, msg):
    if not cond:
        raise RuntimeError("chip_smoke: " + msg)


class CompileLog:
    """JAX's own compile and persistent-cache events (``jax.monitoring``),
    so that a window of the run can say how many programs it compiled, how
    long the largest took, and how many came out of the cache instead."""

    def __init__(self, jax):
        self.events = []        # (name, seconds or None)
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.append((name, None)))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.append((name, secs)))

    def mark(self):
        return len(self.events)

    def since(self, mark):
        ev = self.events[mark:]

        def secs(name):
            return [s for n, s in ev if n == name]

        compiles = secs("/jax/core/compile/backend_compile_duration")
        saved = secs("/jax/compilation_cache/compile_time_saved_sec")
        requests = len(secs(
            "/jax/compilation_cache/compile_requests_use_cache"))
        hits = len(secs("/jax/compilation_cache/cache_hits"))
        retrieval = sum(secs(
            "/jax/compilation_cache/cache_retrieval_time_sec"))
        # a compile event encloses the cache lookup, hit or miss
        return {
            "programs": len(compiles),
            "compile_s": round(sum(compiles), 3),
            "compile_s_without_cache_reads": round(
                sum(compiles) - retrieval, 3),
            "largest_compile_s": round(max(compiles, default=0.0), 3),
            "compiles_under_1s": sum(1 for s in compiles if s < 1.0),
            "compile_s_under_1s": round(
                sum(s for s in compiles if s < 1.0), 3),
            "cache_requests": requests,
            "cache_hits": hits,
            "cache_misses": requests - hits,
            "largest_saved_s": round(max(saved, default=0.0), 3),
            "cache_retrieval_s": round(retrieval, 3),
        }


def require_devices(jax, n_chips, rehearse):
    """The device check, before anything of the repo is imported."""
    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        check(dev.platform == "cpu",
              "--rehearse is the CPU rehearsal; found %s" % dev.platform)
    elif dev.platform != "tpu":
        sys.exit("chip_smoke: needs a TPU, JAX found platform %r (%s x%d)"
                 % (dev.platform, dev.device_kind, len(devices)))
    check(len(devices) >= n_chips, "--chips %d but JAX sees %d device(s)"
          % (n_chips, len(devices)))
    return devices


def phase_device(jax, devices, n_chips):
    """What runs where; read after the package import, which is what
    places the compile cache."""
    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return None

    dev = devices[0]
    facts = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices), "chips_used": n_chips,
        "jax": version("jax"), "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "runtime": dev.client.platform_version.splitlines()[0],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_dir_from_env":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "compile_cache_min_compile_secs":
            jax.config.jax_persistent_cache_min_compile_time_secs,
    }
    print("[1 device] %s" % json.dumps(facts))
    return facts


def phase_resnet(mx, jax, devices, size, log, n_steps=5):
    """ResNet through DataParallelTrainer on a dp mesh over ``devices``."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel import make_mesh, DataParallelTrainer
    from incubator_mxnet_tpu.parallel.mesh import NamedSharding, P

    n = len(devices)
    mx.random.seed(0)
    t0 = time.perf_counter()
    # A fixed prefix, not Gluon's per-process name counter: parameter
    # names are the keys of the step's argument trees, JAX writes them
    # into the program, and phase 6's second instance must be the same
    # program to be found in the cache.
    net = getattr(vision, size["model"])(classes=size["classes"],
                                         thumbnail=size["thumbnail"],
                                         prefix="smoke_resnet_")
    net.initialize(mx.init.Xavier())
    mx.nd.waitall()
    init_s = time.perf_counter() - t0

    mesh = make_mesh({"dp": n}, devices)
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
        mesh=mesh, dtype="bfloat16")

    # one fixed batch, placed once the way the step wants it (a batch that
    # sits whole on device 0 would be re-split over the mesh every step)
    batch = size["batch_per_chip"] * n
    rs = np.random.RandomState(0)
    batch_sh = NamedSharding(mesh, P("dp"))
    x = jax.device_put(rs.rand(batch, 3, size["image"], size["image"])
                       .astype(np.float32), batch_sh)
    y = jax.device_put((rs.rand(batch) * size["classes"])
                       .astype(np.float32), batch_sh)

    # set-up, in the trainer's own two stages: compile() resolves deferred
    # parameter shapes with an eager pass and places parameters and
    # optimizer state on the mesh; the first step() traces and compiles
    # the fused program
    mark = log.mark()
    t0 = time.perf_counter()
    trainer.compile(x, y)
    mx.nd.waitall()
    shape_pass_s = time.perf_counter() - t0
    shape_pass = log.since(mark)

    mark = log.mark()
    losses, step_s = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        loss = trainer.step(x, y)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    first_step = log.since(mark)
    steady_s = statistics.median(step_s[1:])

    check(all(np.isfinite(losses)), "resnet loss not finite: %r" % losses)
    want = set(devices)
    check(loss.sharding.device_set == want,
          "loss lives on %s, not on the mesh" % loss.sharding.device_set)
    for name, v in trainer._params.items():
        check(v.sharding.device_set == want, "parameter %s lives on %s, not "
              "on the mesh" % (name, v.sharding.device_set))

    names = {str(d) for d in devices}
    mem = [m for m in mx.profiler.device_memory() if m["device"] in names]
    check(len(mem) == n, "device_memory() answered for %d of %d devices"
          % (len(mem), n))
    peaks = [m["peak_bytes_in_use"] for m in mem]
    check(devices[0].platform != "tpu"
          or all(m["source"] == "allocator" for m in mem),
          "device_memory() fell back to live_arrays on the chip: %r" % mem)

    facts = {
        "model": size["model"], "global_batch": batch, "dp": n,
        "dtype": "bfloat16", "losses": [round(v, 4) for v in losses],
        "setup_s": {"init": round(init_s, 2),
                    "shape_pass_and_placement": round(shape_pass_s, 2),
                    "step_trace_and_compile": round(step_s[0] - steady_s, 2)},
        "shape_pass_compiles": shape_pass,
        "first_step_compiles": first_step,
        "steady_ms_per_step_smoke": round(steady_s * 1e3, 2),
        "peak_bytes_in_use": peaks, "memory_source": mem[0]["source"],
        "n_params": len(trainer._params),
    }
    print("[resnet dp=%d] %s" % (n, json.dumps(facts)))
    return facts


def _flash_counts(mx, since=None):
    """flash_attention traces by path (the op's own counter), optionally
    as the difference to an earlier reading."""
    snap = mx.telemetry.registry().snapshot().get(
        "graft_flash_attention_traces_total", {"samples": []})
    now = {s["labels"]["path"]: int(s["value"]) for s in snap["samples"]}
    if since is None:
        return now
    return {p: c - since.get(p, 0) for p, c in now.items()}


def phase_flash(mx, jax, cases):
    """The registered op, forward and backward through autograd, against
    the jnp reference held to full f32 precision."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import get_op
    from incubator_mxnet_tpu.ops.attention import _attention_reference

    op = get_op("_contrib_FlashAttention")
    before = _flash_counts(mx)
    out = []
    for case in cases:
        shape, dtype, causal = case["shape"], case["dtype"], case["causal"]
        kv_shape = shape[:2] + (case.get("kv_len", shape[2]),) + shape[3:]
        shapes = (shape, kv_shape, kv_shape, shape)         # q, k, v, w
        rs = np.random.RandomState(1)
        *nd, w = (mx.nd.array(rs.randn(*s).astype(np.float32), dtype=dtype)
                  for s in shapes)
        for a in nd:
            a.attach_grad()
        with mx.autograd.record():
            o = mx.nd.flash_attention(*nd, causal=causal)
            total = (o * w).sum()
        total.backward()
        got = [o] + [a.grad for a in nd]

        # the reference sees the same (rounded) inputs, upcast to f32, and
        # runs its matmuls at full precision (the TPU default is bf16); a
        # batch row at a time, its (Sq, Sk) scores are what fills the HBM
        @jax.jit
        def reference(q, k, v, w):
            out, vjp = jax.vjp(
                lambda a, b, c: _attention_reference(a, b, c, causal),
                q, k, v)
            return (out, *vjp(w))

        f32 = [a.asnumpy().astype(np.float32) for a in nd + [w]]
        with jax.default_matmul_precision("highest"):
            rows = [reference(*(a[b:b + 1] for a in f32))
                    for b in range(shape[0])]
        ref = [np.concatenate([np.asarray(r) for r in part])
               for part in zip(*rows)]
        errs = {}
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
            g = g.asnumpy().astype(np.float32)
            check(g.shape == r.shape and np.all(np.isfinite(g)),
                  "flash %s %s: bad shape or non-finite" % (shape, name))
            errs[name] = float(np.max(np.abs(g - r)) /
                               max(1.0, float(np.max(np.abs(r)))))
            check(errs[name] <= case["tol"], "flash %s %s %s: error %.3g "
                  "over tolerance %.3g" % (shape, dtype, name, errs[name],
                                           case["tol"]))

        # the same op, attributes, shapes and dtype, lowered for this
        # device: a Mosaic custom call is the compiled Pallas kernel (the
        # interpreter would have left plain HLO, the jnp path no call)
        q_spec, kv_spec = (jax.ShapeDtypeStruct(s, jnp.dtype(dtype))
                           for s in (shape, kv_shape))
        text = jax.jit(functools.partial(op.fcompute, causal=causal)).lower(
            q_spec, kv_spec, kv_spec).as_text()
        check("tpu_custom_call" in text, "flash %s %s lowered without a "
              "Mosaic custom call: the Pallas kernel did not run"
              % (shape, dtype))
        out.append({"shape": list(shape), "kv_len": kv_shape[2],
                    "dtype": dtype, "causal": causal,
                    "rel_err": {n: float("%.3g" % e)
                                for n, e in errs.items()},
                    "tol": case["tol"], "mosaic_custom_call": True})
    paths = _flash_counts(mx, since=before)
    check(not any(c for p, c in paths.items() if p.startswith("reference")),
          "flash_attention took a jnp path on the chip: %r" % paths)
    check(any(c for p, c in paths.items() if not p.startswith("bwd_")) and
          any(c for p, c in paths.items() if p.startswith("bwd_")),
          "flash_attention was not traced in both directions: %r" % paths)
    facts = {"cases": out, "traces_by_path": paths}
    print("[3 flash] %s" % json.dumps(facts))
    return facts


def phase_lm(mx, jax, devices, size, log, n_steps=3):
    """bench_transformer.py's decoder LM through DataParallelTrainer."""
    from bench_transformer import build_lm
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_mesh, DataParallelTrainer

    before = _flash_counts(mx)
    mx.random.seed(0)
    net = build_lm(size["vocab"], size["d_model"], size["n_heads"],
                   size["d_ffn"], size["n_layers"], size["seq_len"])
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, devices[:1]), dtype="bfloat16")
    rs = np.random.RandomState(0)
    tokens = (size["batch"], size["seq_len"])
    x = mx.nd.array(rs.randint(0, size["vocab"], tokens), dtype=np.int32)
    y = mx.nd.array(rs.randint(0, size["vocab"], tokens).astype(np.float32))

    mark = log.mark()
    losses, step_s = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        loss = trainer.step(x, y)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), "lm loss not finite: %r" % losses)
    check(len(set(losses)) == n_steps, "lm loss did not change: %r" % losses)
    paths = _flash_counts(mx, since=before)
    check(paths.get("lowering_platform", 0) > 0
          and not any(c for p, c in paths.items()
                      if p.startswith("reference")),
          "the fused step did not trace the Pallas path only: %r" % paths)
    peak = max(m["peak_bytes_in_use"] for m in mx.profiler.device_memory()
               if m["device"] == str(devices[0]))
    facts = {
        **{k: size[k] for k in ("d_model", "n_heads", "d_ffn", "n_layers",
                                "seq_len", "batch", "vocab")},
        "depth_cut_from": 4, "dtype": "bfloat16",
        "n_params": int(sum(int(np.prod(p.shape))
                            for p in net.collect_params().values())),
        "losses": [round(v, 4) for v in losses],
        "first_step_s": round(step_s[0], 2),
        "steady_ms_per_step_smoke": round(min(step_s[1:]) * 1e3, 2),
        "compiles": log.since(mark),
        "flash_traces_by_path": paths,
        "peak_bytes_in_use_so_far": peak,
    }
    print("[4 lm] %s" % json.dumps(facts))
    return facts


def phase_dropout(mx, jax, shape=(4, 2048, 4096), p=0.1):
    """``RNG_DEFAULT`` is the backend's own algorithm: that the same key
    and shape give the same mask in two different programs is checked
    here, on the chip, at the OPT cell's shape (the tier-1 tests hold it
    on the CPU)."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ops.registry import get_op

    dropout = functools.partial(get_op("Dropout").fcompute, p=p,
                                is_train=True)
    x = jnp.ones(shape, jnp.bfloat16)
    key = jax.random.PRNGKey(27)
    eager = np.asarray(dropout(x, rng=key) != 0)
    # a second program: the draw between other work, so XLA fuses it anew
    staged = jax.jit(lambda x, key: jnp.tanh(dropout(x * 2, rng=key)) + 1)
    check((eager == np.asarray(staged(x, key) != 1)).all(),
          "one key drew two masks, eagerly and inside a jitted program")
    share = float(eager.mean())
    sigma = (p * (1 - p) / eager.size) ** 0.5
    check(abs(share - (1 - p)) < 4 * sigma,
          "dropout kept %.6f of the elements, not %.2f" % (share, 1 - p))

    # the Gluon loop: cachedop_backward draws the mask again from the step's
    # key (a draw is no residual of the forward's) and must meet
    # cachedop_forward's mask
    net = nn.HybridSequential()
    net.add(nn.Dropout(p))
    net.initialize()
    net.hybridize()
    nd = mx.nd.array(np.ones(shape[1:], np.float32))
    nd.attach_grad()
    with mx.autograd.record():
        out = net(nd)
    out.backward()
    out, grad = out.asnumpy(), nd.grad.asnumpy()
    check(((out != 0) == (grad != 0)).all() and (out == 0).any(),
          "cachedop_backward drew another mask than cachedop_forward")
    facts = {"shape": list(shape), "p": p, "kept_share": round(share, 6),
             "eager_equals_jitted": True,
             "cachedop_backward_meets_forward": True}
    print("[5 dropout] %s" % json.dumps(facts))
    return facts


def phase_cache(mx, jax, devices, size, log, first):
    """The same ResNet step again, as a new process would meet it: JAX's
    in-memory caches dropped, a new net and trainer, the persistent cache
    left as phase 2 filled it."""
    jax.clear_caches()
    second = phase_resnet(mx, jax, devices, size, log, n_steps=2)
    cold, warm = first["first_step_compiles"], second["first_step_compiles"]
    # what compiling the fused step costs, whichever way phase 2 met it
    step_compile_s = max(cold["largest_compile_s"], cold["largest_saved_s"])
    check(warm["cache_hits"] >= 1
          and warm["compile_s_without_cache_reads"] < 0.1 * step_compile_s,
          "the second trainer compiled its step again instead of reading "
          "the cache: first %r, second %r" % (cold, warm))
    facts = {
        "first_setup_s": first["setup_s"], "second_setup_s": second["setup_s"],
        "step_compile_s": step_compile_s,
        "second_step_from_cache": True,
        "second_cache_hits": warm["cache_hits"],
        "second_cache_misses": warm["cache_misses"],
        "second_cache_retrieval_s": warm["cache_retrieval_s"],
        "second_shape_pass_compiles": second["shape_pass_compiles"],
    }
    print("[6 cache] %s" % json.dumps(facts))
    return facts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU only: toy sizes, Pallas phases skipped")
    args = ap.parse_args(argv)

    import jax
    devices = require_devices(jax, args.chips, args.rehearse)
    import incubator_mxnet_tpu as mx
    device = phase_device(jax, devices, args.chips)
    devices = devices[:args.chips]
    log = CompileLog(jax)
    sizes = REHEARSAL if args.rehearse else FULL

    resnet = phase_resnet(mx, jax, devices, sizes["resnet"], log)
    check(resnet["losses"][-1] < resnet["losses"][0], "resnet loss did not "
          "fall, the weights did not move: %r" % resnet["losses"])
    # read here, before the one-chip phases: the peak is a maximum over
    # the life of the process
    peaks = resnet["peak_bytes_in_use"]
    check(args.rehearse or min(peaks) >= 0.75 * max(peaks),
          "per-device peak memory differs by more than 25%%: %r" % peaks)
    if args.rehearse:
        flash = lm = dropout = SKIPPED
    else:
        flash = phase_flash(mx, jax, sizes["flash"])
        lm = phase_lm(mx, jax, devices, sizes["lm"], log)
        dropout = phase_dropout(mx, jax)
    cache = phase_cache(mx, jax, devices, sizes["resnet"], log, resnet)

    def brief(facts):
        """The result line keeps the facts; the per-window compile-event
        counts stay in the phase lines above it."""
        if not isinstance(facts, dict):
            return facts
        return {k: v for k, v in facts.items() if not k.endswith("compiles")}

    verdict = {"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}
    print("[result] %s" % json.dumps({
        **verdict,
        "rehearsal": args.rehearse,
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "chips_used": args.chips,
        "compile_cache": {k: device[k] for k in (
            "compile_cache_dir", "compile_cache_dir_from_env",
            "compile_cache_min_compile_secs")},
        "resnet": brief(resnet), "flash_attention": flash,
        "transformer_lm": brief(lm), "dropout_masks": dropout,
        "second_compile": brief(cache),
    }))
    # the last line: exactly these keys, for whoever runs the script
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
