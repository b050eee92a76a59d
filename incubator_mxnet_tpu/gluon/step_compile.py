"""graftstep — whole-step compiled training: fwd+bwd+fused update as ONE
donated XLA program.

The steady-state train step has so far dispatched as bucketed-eager
segments: the CachedOp forward (one jit), the tape walk's vjp programs,
``concat_ctx_sum`` flats, ``reduce_many``, and one fused optimizer
program per bucket.  This module hands the WHOLE step to XLA instead
(the paper's hybridization idea carried to its endpoint — see
arXiv:1810.09868 / arXiv:2301.13062 on what whole-program compilation
unlocks): the forward re-records into the same pure-jittable trace
``CachedOp`` compiles (``block.hybrid_forward_dispatch`` under shadow
params), ``jax.vjp`` supplies the fused backward seeded by
``autograd.head_seed`` (the exact ``loss.backward()`` convention), and
``optimizer.fused_formula_applier``'s per-bucket multi-tensor formulas
run inside the same program with the parameter/state buffers DONATED
(``jax.jit(..., donate_argnums=...)``) so XLA reuses the old weight
memory for the new weights — cross-op fusion plus zero double-buffering
that no amount of eager-side overlap can reach.

Topology::

    no kvstore   →  ONE program:   (params, states, inputs, rng, lr, wd,
                                    rescale) → (loss, aux, params', states')
    kvstore      →  program A:     (params, inputs, rng) → (loss, aux, flats)
                    reduce_many    — the existing wire, AT the boundary
                    program B:     (params, states, reduced, lr, wd,
                                    rescale) → (params', states')   [donated]

Cross-worker reduce stays at the program boundary (``KVStore.reduce_many``
on the per-bucket flats, labeled ``compiled_step``) — the same bytes, the
same reduction algebra, one collective bracket per step.

**Guards and fallback.**  Each compiled entry is keyed on (input
shapes/dtypes, param-set identity, per-param shape/dtype/grad_req,
optimizer signature, context count, kvstore identity, bucket target):
any guard miss runs the bit-identical bucketed-eager path — the same
``record → backward → Trainer.step`` triple the user would have written
— and re-traces lazily, so a static-shape loop shows ZERO retraces after
step 2 (step 1 falls back and builds, step 2 onward dispatches
compiled).  ``GRAFT_STEP_COMPILE=0`` is the kill-switch: every call runs
the eager triple.

**lr as operand.**  Unlike graftfuse's constant-baked programs,
lr/wd/rescale enter the compiled step as traced OPERANDS —
``set_learning_rate`` (and schedulers, and batch-size changes) must not
retrace a steady-state program.  Operands can shift LLVM's
fma-contraction choices by ~1 ULP vs the constant layout (measured on
bf16 mp_sgd), so compiled-vs-eager parity is asserted under a small
documented ULP tolerance (:func:`max_ulp_diff`, the EH104 convention)
rather than byte equality.

**Overlap semantics.**  Compiled-step mode DISABLES the mid-backward
reduce overlap (``BucketScheduler``) and the duplex pull overlap for its
own steps: there is no eager backward for grad-ready hooks to fire in —
the overlap the scheduler bought by hand is subsumed by XLA scheduling
inside the single program, and the boundary reduce issues immediately
after program A with no host work in between.  Fallback steps re-enter
``Trainer.step`` and keep their normal overlap behavior.

**Telemetry.**  Host time lands on the ``fwd``/``kvstore``/``update``
phase spans and the step journal carries ``compiled=True``
(docs/observability.md, "Whole-step compilation").

Per-param gradient buffers are NOT materialized on compiled steps
(``param.grad()`` holds stale values): the gradients live only inside
the program.  Loops that read grads (clipping, logging) should run those
steps eagerly or read the compiled loss outputs instead.

``python -m incubator_mxnet_tpu.gluon.step_compile --selftest`` runs the
lint-tier check: trace → at most 2 guarded retraces → ULP-parity assert
against the bucketed-eager twin.
"""
from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd
from .. import engine as _engine
from .. import optimizer as opt
from ..analysis import compile_safety as _csafety
from .. import random_state
from ..ndarray import NDArray
from ..telemetry import blackbox as _blackbox
from ..telemetry import metrics as _tmetrics
from ..telemetry import tracing as _ttracing
from ..telemetry import xray as _xray
from .block import HybridBlock, _flatten, _regroup, _fmt_key, \
    _install_first_touch

__all__ = ["CompiledStep", "step_compile_enabled", "max_ulp_diff",
           "selftest", "main"]


def step_compile_enabled(override=None):
    """GRAFT_STEP_COMPILE (default on): whether :class:`CompiledStep`
    actually compiles.  Off = the kill-switch — every ``cstep(...)``
    call runs the bit-identical bucketed-eager triple instead, so a
    suspect compiled program can be ruled out without touching the
    training loop."""
    if override is not None:
        return bool(override)
    return os.environ.get("GRAFT_STEP_COMPILE", "1").strip().lower() \
        not in ("0", "false", "no", "off")


def _donation_supported():
    """Buffer donation is honored on TPU/GPU; the CPU backend ignores it
    with a UserWarning per dispatch — skip the argnums there so the
    steady-state loop stays warning-free (the program is identical
    either way; only the aliasing hint differs)."""
    return jax.default_backend() != "cpu"


def max_ulp_diff(a, b):
    """Largest elementwise ULP distance between two equal-shape float
    arrays (inf on shape/dtype mismatch; 0/inf exact-compare for
    non-floats).  The EH104-style oracle the graftstep parity tests
    assert under: compiled programs pass lr/wd/rescale as traced
    operands where graftfuse bakes constants, which can shift
    fma-contraction by ~1 ULP per step."""
    a = np.asarray(jax.device_get(a))
    b = np.asarray(jax.device_get(b))
    if a.shape != b.shape or a.dtype != b.dtype:
        return float("inf")
    is_float = a.dtype.kind == "f" or a.dtype.name in ("bfloat16",)
    if not is_float:
        return 0.0 if np.array_equal(a, b) else float("inf")
    nbits = a.dtype.itemsize * 8
    ib = {16: np.int16, 32: np.int32, 64: np.int64}[nbits]
    ai = a.view(ib).astype(np.int64)
    bi = b.view(ib).astype(np.int64)
    # two's-complement int view → monotone key over the reals (the
    # classic radix trick; ±0.0 map to the same key)
    int_min = -(1 << (nbits - 1))
    ak = np.where(ai >= 0, ai, int_min - ai)
    bk = np.where(bi >= 0, bi, int_min - bi)
    if ak.size == 0:
        return 0.0
    return int(np.max(np.abs(ak - bk)))


class _Ineligible(object):
    """Permanent marker entry: this guard signature can never compile
    (multi-context, non-fused optimizer, store-side update, …) — every
    hit takes the eager fallback without re-deriving why."""

    __slots__ = ("reason",)

    def __init__(self, reason):
        self.reason = reason


class CompiledStep(object):
    """One training step — forward, backward, fused optimizer update —
    re-dispatched as a single donated XLA program (two, at a kvstore
    boundary).  Built via :meth:`Trainer.compile_step`; call it in place
    of the ``record → backward → step`` triple::

        cstep = trainer.compile_step(net, loss=loss_fn)
        for data, label in loader:
            out = cstep(data, label, batch_size=data.shape[0])

    With ``loss=None`` the block's output IS the head: backward seeds
    ones exactly as ``out.backward()`` would (``autograd.head_seed``).
    With a ``loss`` callable the LAST positional arg is the label and
    the head is ``loss(block(*args[:-1]), label)``.

    Counters: ``retraces`` (guard misses that built an entry — must stay
    at 1 on a static loop), ``compiled_steps``, ``fallback_steps``;
    ``forward_order`` is the recorded first-touch parameter order the
    trainer's pull scheduling reuses (graftduplex pull priority).
    """

    def __init__(self, trainer, block, loss=None, enabled=None):
        if not isinstance(block, HybridBlock):
            raise TypeError(
                "CompiledStep requires a HybridBlock (the compiled step "
                "rides the CachedOp functionalized trace); got %s"
                % type(block))
        self._trainer = trainer
        self._block = block
        self._loss = loss
        self._enabled_override = enabled
        self._entries = _engine.BoundedCache()
        self.retraces = 0
        self.compiled_steps = 0
        self.fallback_steps = 0
        self.forward_order = None
        # graftguard (GRAFT_COMPILE_CHECK): lazily-created runtime
        # auditor + the last guard key, diffed on every miss so EH301
        # can name exactly which component churned
        self._auditor = None
        self._last_guard_key = None

    # -- public -------------------------------------------------------------
    def enabled(self):
        return step_compile_enabled(self._enabled_override)

    def __call__(self, *args, batch_size=1):
        if autograd.is_recording():
            raise RuntimeError(
                "CompiledStep called inside autograd.record(): the "
                "compiled step IS the whole record/backward/step triple "
                "— call it outside any recording scope")
        args = tuple(a if isinstance(a, NDArray) else _as_nd(a)
                     for a in args)
        tr = self._trainer
        if not self.enabled():
            return self._fallback(args, batch_size, "disabled")
        if _csafety.refresh():
            if self._auditor is None:
                self._auditor = _csafety.StepAuditor("trainer")
            self._auditor.note_call()
        if not tr._kv_initialized:
            # first step: kvstore init + optimizer state creation ride
            # the eager path, then the trace builds lazily below
            return self._miss(args, batch_size, "first-step")
        key = self._guard_key(args)
        entry = self._entries.get(key)
        if entry is None:
            return self._miss(args, batch_size, "guard-miss")
        self._last_guard_key = key
        if isinstance(entry, _Ineligible):
            return self._fallback(args, batch_size, entry.reason)
        plan_sig = self._plan_sig()
        if plan_sig != entry["plan_sig"]:
            # the bucket plan moved under us (GRAFT_BUCKET_BYTES moved,
            # state arity flip): treat as a guard miss and rebuild
            self._entries[key] = None
            return self._miss(args, batch_size, "plan-change")
        return self._dispatch(entry, args, batch_size)

    # -- fallback: the bit-identical bucketed-eager triple ------------------
    def _fallback(self, args, batch_size, reason):
        self.fallback_steps += 1
        _tmetrics.trainer_compiled_fallback(reason)
        block, loss = self._block, self._loss
        with autograd.record():
            if loss is not None:
                out = loss(block(*args[:-1]), args[-1])
            else:
                out = block(*args)
            heads, _fmt = _flatten(out, "output")
        autograd.backward(list(heads))
        self._trainer.step(batch_size)
        return out

    def _miss(self, args, batch_size, reason):
        out = self._fallback(args, batch_size, reason)
        # lazy re-trace AFTER the eager step: states now exist, the plan
        # is fresh, and the next hit on this signature dispatches
        # compiled — one fallback step per distinct signature
        key = self._guard_key(args)
        # every miss names WHICH guard component churned: the diff feeds
        # the always-on graft_step_retraces_total{reason} metric and the
        # blackbox, and (when GRAFT_COMPILE_CHECK is on) the EH301
        # retrace-storm detector
        if reason == "guard-miss":
            component, detail = _csafety.diff_guard_key(
                self._last_guard_key, key)
        else:
            component, detail = reason, None
        self._last_guard_key = key
        _tmetrics.step_retrace(component)
        _blackbox.record("step_compile", event="miss", reason=reason,
                         component=component, detail=detail)
        if self._auditor is not None and _csafety._ACTIVE[0]:
            self._auditor.note_miss(component, detail)
        try:
            if self._entries.get(key) is None:
                self._build(key, args)
        except Exception as e:   # never let trace failures kill training
            self._entries[key] = _Ineligible("trace-error")
            _blackbox.record("step_compile", event="ineligible",
                             reason="trace-error", error=repr(e))
        return out

    # -- guards -------------------------------------------------------------
    def _quant_cfg(self):
        """graftzero wire config for the compiled boundary: (mode, block)
        when the quantized bucket wire is on, else None.  Part of the
        guard key, so toggling ``GRAFT_QUANT_REDUCE`` re-traces exactly
        once — the encode/decode live INSIDE the donated programs."""
        tr = self._trainer
        kv = tr._kvstore_obj
        if kv is None:
            return None
        from ..parallel import quant as _quant
        mode = _quant.resolve_mode(getattr(kv, "_quant_override", None))
        if mode is None:
            return None
        return (mode, _quant.resolve_block())

    def _guard_key(self, args):
        tr = self._trainer
        o = tr._optimizer
        flat_args, in_fmt = _flatten(args, "input")
        kv = tr._kvstore_obj
        return (
            tuple(None if a is None else
                  (tuple(a.shape), str(a.dtype)) for a in flat_args),
            _fmt_key(in_fmt),
            tuple(id(p) for p in tr._params),          # param-set identity
            tuple((p.name,
                   None if p.shape is None else tuple(p.shape),
                   str(np.dtype(p.dtype)), p.grad_req)
                  for p in tr._params),
            (type(o), bool(o.multi_precision),
             getattr(o, "momentum", None), o.clip_gradient,
             getattr(o, "beta1", None), getattr(o, "beta2", None),
             getattr(o, "epsilon", None)),
            len(tr._contexts),
            None if kv is None else (type(kv).__name__,
                                     bool(tr._update_on_kvstore)),
            tr._bucket_target_bytes(),
            self._quant_cfg(),
        )

    def _plan_sig(self):
        """Structural signature of the trainer's CURRENT bucket plan —
        compared against the entry's so a bucket-target move or a
        state-arity flip re-traces instead of running a stale program."""
        plan = self._trainer._fused_plan()
        if plan is None:
            return None
        buckets, leftover = plan
        return (tuple((tuple(b.indices), b.kind, str(np.dtype(b.dtype)))
                      for b in buckets), tuple(leftover))

    # -- build --------------------------------------------------------------
    def _ineligible(self, key, reason):
        self._entries[key] = _Ineligible(reason)
        _blackbox.record("step_compile", event="ineligible", reason=reason)
        _tmetrics.step_guard_entries(len(self._entries))
        return None

    def _build(self, key, args):
        tr = self._trainer
        if len(tr._contexts) != 1:
            return self._ineligible(key, "multi-context")
        if tr._update_on_kvstore:
            return self._ineligible(key, "update-on-kvstore")
        plan = tr._fused_plan()
        if plan is None:
            return self._ineligible(key, "no-fused-plan")
        buckets, leftover = plan
        if leftover:
            return self._ineligible(key, "leftover-params")
        if any(p.grad_req == "add" for p in tr._params):
            # grad accumulation spans steps; a single fused program
            # cannot replicate the cross-step accumulate semantics
            return self._ineligible(key, "grad-req-add")
        block_params = self._block.collect_params()
        by_name = {p.name: i for i, p in enumerate(tr._params)}
        for name, bp in block_params.items():
            i = by_name.get(name)
            if i is not None and tr._params[i] is not bp:
                return self._ineligible(key, "param-identity-mismatch")

        trainable = tuple(i for b in buckets for i in b.indices)
        tpos = {i: k for k, i in enumerate(trainable)}
        train_names = tuple(tr._params[i].name for i in trainable)
        train_set = set(train_names)
        frozen_names = tuple(sorted(n for n in block_params
                                    if n not in train_set))
        updater = tr._updaters[0]
        bspecs = []
        for b in buckets:
            arrs0 = opt._fused_state_arrays(
                b.kind, updater.ensure_state(
                    b.indices[0], tr._params[b.indices[0]].list_data()[0]))
            arity = len(arrs0)
            has_state = arity >= (2 if b.kind == "mp_sgd" else 1)
            cfg = opt._fused_config(tr._optimizer, b.kind)
            shapes = tuple(tuple(tr._params[i].shape) for i in b.indices)
            bspecs.append({
                "indices": tuple(b.indices), "kind": b.kind,
                "arity": arity, "has_state": has_state,
                "shapes": shapes,
                # nests inside xray:update[k] at the call sites; the
                # hyphen spelling keeps it OUT of phase attribution
                # (which keys on "xray:" tokens) while the raw trace
                # still names the formula kind
                "apply": opt.fused_formula_applier(
                    b.kind, cfg, has_state,
                    scope="xray-apply-%s" % b.kind),
            })

        flat_args, in_fmt = _flatten(args, "input")
        entry = {
            "plan_sig": self._plan_sig(),
            "trainable": trainable, "tpos": tpos,
            "train_names": train_names, "frozen_names": frozen_names,
            "bspecs": bspecs, "in_fmt": in_fmt,
            "touch": [], "fmt_cell": {},
            "n_in": len(flat_args),
        }
        # graftguard EH303: the fused-config scalars baked into the
        # formula appliers at trace time, re-hashed per dispatch —
        # drift under an unchanged guard key means a silently frozen
        # value inside the compiled program
        entry["bake_kinds"] = tuple(s["kind"] for s in bspecs)
        entry["bake_sig"] = tuple(
            tuple(opt._fused_config(tr._optimizer, s["kind"]))
            for s in bspecs)

        raw_fwd = self._make_raw_fwd(entry)
        fwd_bwd = self._make_fwd_bwd(entry, raw_fwd)
        donate = (0, 1) if _donation_supported() else ()
        kv = tr._kvstore_obj
        # programs carry stable __name__s so the XLA module names
        # ("jit_gstep_one", …) are joinable against graftxray's program
        # registry and a profiler trace's hlo_module column
        entry["aot"] = {}
        if kv is None:
            one = self._make_one_program(entry, fwd_bwd)
            one.__name__ = "gstep_one"
            entry["one"] = jax.jit(one, donate_argnums=donate)
            entry["fwd_bwd"] = entry["update"] = None
            # un-jitted twin for the EH304 divergence sentinel: same
            # closure, eager dispatch — zero cost unless sampled
            entry["one_raw"] = one
            entry["fwd_bwd_raw"] = entry["update_raw"] = None
        else:
            update = self._make_update_program(entry)
            update.__name__ = "gstep_update"
            entry["one"] = None
            entry["one_raw"] = None
            qcfg = self._quant_cfg()
            entry["quant"] = qcfg
            if qcfg is None:
                def gstep_fwd_bwd(tv, fv, iv, rng):
                    return fwd_bwd(tv, fv, iv, rng, True)

                entry["fwd_bwd"] = jax.jit(gstep_fwd_bwd)
                entry["update"] = jax.jit(update, donate_argnums=donate)
                entry["fwd_bwd_raw"] = gstep_fwd_bwd
                entry["update_raw"] = update
            else:
                # graftzero: the quantize (error-feedback encode) and
                # dequantize live INSIDE the donated programs — the host
                # boundary ships only packed codes + per-block scales
                # (kv.reduce_quantized).  Residuals ride as operands and
                # outputs of program A, stored back in the Updater store
                # under the same keys the eager BucketQuantizer uses, so
                # eager and compiled steps share one EF trajectory.
                from ..parallel import quant as _quant
                mode, qblock = qcfg
                sizes = tuple(
                    int(sum(int(np.prod(s)) if s else 1
                            for s in spec["shapes"]))
                    for spec in bspecs)
                qdtypes = tuple(
                    np.dtype(tr._params[spec["indices"][0]].dtype)
                    for spec in bspecs)
                entry["qsizes"] = sizes
                entry["qdtypes"] = qdtypes

                def gstep_fwd_bwd_q(tv, fv, iv, rng, res):
                    outs, aux, flats = fwd_bwd(tv, fv, iv, rng, True)
                    codes, scales, new_res = [], [], []
                    for k, f in enumerate(flats):
                        with jax.named_scope("xray:quant[%d]" % k):
                            acc = f.astype(jnp.float32) + res[k]
                            c, s = _quant.encode(acc, mode, qblock)
                            codes.append(c)
                            scales.append(s)
                            new_res.append(acc - _quant.decode(
                                c, s, sizes[k], mode, qblock))
                    return (outs, aux, tuple(codes), tuple(scales),
                            tuple(new_res))

                def gstep_update_q(train_vals, state_vals, payloads,
                                   lrs, wds, rescale):
                    flats = []
                    for k in range(len(sizes)):
                        with jax.named_scope("xray:dequant[%d]" % k):
                            c, s = payloads[k]
                            flats.append(_quant.decode(
                                c, s, sizes[k], mode,
                                qblock).astype(qdtypes[k]))
                    return update(train_vals, state_vals, tuple(flats),
                                  lrs, wds, rescale)

                gstep_fwd_bwd_q.__name__ = "gstep_fwd_bwd_q"
                gstep_update_q.__name__ = "gstep_update_q"
                entry["fwd_bwd"] = jax.jit(gstep_fwd_bwd_q)
                entry["update"] = jax.jit(gstep_update_q,
                                          donate_argnums=donate)
                entry["fwd_bwd_raw"] = gstep_fwd_bwd_q
                entry["update_raw"] = gstep_update_q

        # dry abstract trace NOW (jax.eval_shape: no compile, no FLOPs):
        # trace errors surface here as a clean ineligible entry instead
        # of mid-loop, the output fmt lands in fmt_cell, and the shadow
        # first-touch hooks record the forward-use order
        avals = self._avals(entry, args)
        try:
            jax.eval_shape(lambda tv, fv, iv, rng:
                           fwd_bwd(tv, fv, iv, rng, kv is not None), *avals)
        except Exception as e:
            return self._ineligible(key, "trace-error: %s" % type(e).__name__)
        self._feed_first_touch(entry)
        self._entries[key] = entry
        self.retraces += 1
        _tmetrics.trainer_compiled_retrace()
        _tmetrics.step_guard_entries(len(self._entries))
        _blackbox.record("step_compile", event="trace",
                         n_params=len(trainable), n_buckets=len(bspecs),
                         kv=kv is not None, donated=bool(donate),
                         retraces=self.retraces)
        return entry

    def _avals(self, entry, args):
        tr = self._trainer
        flat_args, _ = _flatten(args, "input")

        def av(x):
            return jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype))

        tv = tuple(av(tr._params[i].list_data()[0]._read())
                   for i in entry["trainable"])
        block_params = self._block.collect_params()
        fv = tuple(av(block_params[n].list_data()[0]._read())
                   for n in entry["frozen_names"])
        iv = tuple(None if a is None else av(a._read()) for a in flat_args)
        rng = av(random_state.next_key())
        return tv, fv, iv, rng

    def _feed_first_touch(self, entry):
        """graftduplex pull priority: the forward-use order recorded by
        the trace shadows becomes the trainer's first-touch order — the
        PullScheduler issues weight pulls in the order the next forward
        will consume them, and ``GRAFT_BUCKET_ORDER=touch`` packs
        buckets by it."""
        tr = self._trainer
        by_name = {p.name: i for i, p in enumerate(tr._params)}
        order = tuple(by_name[n] for n in entry["touch"] if n in by_name)
        if order:
            self.forward_order = order
            tr.note_first_touch_order(order)

    # -- traced pieces ------------------------------------------------------
    def _make_raw_fwd(self, entry):
        block, loss = self._block, self._loss
        train_names = entry["train_names"]
        frozen_names = entry["frozen_names"]
        in_fmt = entry["in_fmt"]
        touch = entry["touch"]
        fmt_cell = entry["fmt_cell"]

        def raw_fwd(train_vals, frozen_vals, input_vals, rng):
            shadows = {}
            for n, v in zip(train_names, train_vals):
                shadows[n] = NDArray(v)
            for n, v in zip(frozen_names, frozen_vals):
                shadows[n] = NDArray(v)
            if not touch:
                _install_first_touch(shadows, touch)
            nd_in = [None if v is None else NDArray(v) for v in input_vals]
            if loss is not None:
                label_nd, nd_in = nd_in[-1], nd_in[:-1]
            args, _ = _regroup(nd_in, in_fmt if loss is None
                               else in_fmt[:-1] if isinstance(in_fmt, list)
                               else in_fmt)
            if not isinstance(args, list):
                args = [args]
            # graftxray phase marker: every op staged by the forward
            # (and therefore its vjp RESIDUALS' producers) carries
            # "xray:forward" in its HLO op_name metadata — the profiler
            # attribution joins on it (telemetry/xray.py)
            with jax.named_scope("xray:forward"):
                with random_state.use_key(rng):
                    with autograd._scope(recording=False, training=True):
                        with block._trace_params(shadows):
                            out = block.hybrid_forward_dispatch(*args)
                            if loss is not None:
                                out = loss(out, label_nd)
            flat_out, fmt = _flatten(out, "output")
            # graftlint: disable=GL304 -- trace-time output-fmt memo, written once per trace
            fmt_cell["fmt"] = fmt
            out_vals = tuple(o._read() for o in flat_out)
            for n in train_names:
                if shadows[n]._version > 0:
                    raise RuntimeError(
                        "trainable parameter %r mutated inside the "
                        "forward trace — unsupported in a compiled step "
                        "(the optimizer update owns that buffer)" % n)
            aux = {n: shadows[n]._read() for n in frozen_names
                   if shadows[n]._version > 0}
            return out_vals, aux

        return raw_fwd

    def _make_fwd_bwd(self, entry, raw_fwd):
        bspecs = entry["bspecs"]
        tpos = entry["tpos"]

        def fwd_bwd(train_vals, frozen_vals, input_vals, rng, flat_mode):
            outs, vjp_fn, aux = jax.vjp(
                lambda tv: raw_fwd(tv, frozen_vals, input_vals, rng),
                tuple(train_vals), has_aux=True)
            # graftxray: ops staged by the vjp application (the whole
            # backward sweep + head seeding + flat packing) are tagged
            # "xray:backward"; the vjp's forward ops already carry
            # "xray:forward" from raw_fwd
            with jax.named_scope("xray:backward"):
                # seed exactly as loss.backward() seeds a bare head
                cts = tuple(autograd.head_seed(o) for o in outs)
                (grads,) = vjp_fn(cts)
                if not flat_mode:
                    return outs, aux, grads
                flats = tuple(
                    _engine.flatten_arrays(
                        tuple(grads[tpos[i]] for i in spec["indices"]))
                    for spec in bspecs)
            return outs, aux, flats

        return fwd_bwd

    def _make_one_program(self, entry, fwd_bwd):
        """No-kvstore topology: fwd+bwd+update in ONE jitted program, the
        per-param-gradient formula layout (flat_mode=False) the eager
        storeless ``_bucketed_update`` uses — same math, one dispatch."""
        bspecs = entry["bspecs"]
        tpos = entry["tpos"]

        def one(train_vals, state_vals, frozen_vals, input_vals, rng,
                lrs, wds, rescale):
            outs, aux, grads = fwd_bwd(train_vals, frozen_vals,
                                       input_vals, rng, False)
            new_w = list(train_vals)
            new_s = []
            for k, spec in enumerate(bspecs):
                with jax.named_scope("xray:update[%d]" % k):
                    ws = tuple(train_vals[tpos[i]] for i in spec["indices"])
                    gs = tuple(grads[tpos[i]] for i in spec["indices"])
                    nw, ns = spec["apply"](ws, gs, state_vals[k],
                                           lrs[k], wds[k], rescale)
                for pos, i in enumerate(spec["indices"]):
                    new_w[tpos[i]] = nw[pos]
                new_s.append(ns)
            return outs, aux, tuple(new_w), tuple(new_s)

        return one

    def _make_update_program(self, entry):
        """Kvstore topology, program B: unflatten each bucket's REDUCED
        flat (the same static slicing the graftfuse flat_mode programs
        inline) and apply the per-bucket formulas — params/states
        donated, so XLA aliases the old weight buffers for the new."""
        bspecs = entry["bspecs"]
        tpos = entry["tpos"]

        def update(train_vals, state_vals, flats, lrs, wds, rescale):
            new_w = list(train_vals)
            new_s = []
            for k, spec in enumerate(bspecs):
                with jax.named_scope("xray:update[%d]" % k):
                    ws = tuple(train_vals[tpos[i]] for i in spec["indices"])
                    gs = _engine.unflatten(flats[k], spec["shapes"])
                    nw, ns = spec["apply"](ws, gs, state_vals[k],
                                           lrs[k], wds[k], rescale)
                for pos, i in enumerate(spec["indices"]):
                    new_w[tpos[i]] = nw[pos]
                new_s.append(ns)
            return tuple(new_w), tuple(new_s)

        return update

    # -- dispatch -----------------------------------------------------------
    def _gather(self, entry, args):
        tr = self._trainer
        flat_args, _ = _flatten(args, "input")
        if _engine.in_bulk():
            # land any open deferred segment ONCE with an attributed
            # cause (param/state leaves may be deferred values)
            _engine.flush(cause="step_compile")
        train_nds = [tr._params[i].list_data()[0]
                     for i in entry["trainable"]]
        train_vals = tuple(a._read() for a in train_nds)
        block_params = self._block.collect_params()
        frozen_nds = [block_params[n].list_data()[0]
                      for n in entry["frozen_names"]]
        frozen_vals = tuple(a._read() for a in frozen_nds)
        input_vals = tuple(None if a is None else a._read()
                           for a in flat_args)
        updater = tr._updaters[0]
        state_nds, state_vals = [], []
        for spec in entry["bspecs"]:
            nds = []
            for i in spec["indices"]:
                arrs = opt._fused_state_arrays(
                    spec["kind"], updater.ensure_state(
                        i, tr._params[i].list_data()[0]))
                if len(arrs) != spec["arity"]:
                    return None     # state store moved: caller falls back
                nds.append(arrs)
            state_nds.append(nds)
            state_vals.append(tuple(tuple(a._read() for a in arrs)
                                    for arrs in nds))
        return (train_vals, frozen_vals, input_vals, frozen_nds,
                state_nds, tuple(state_vals), train_nds)

    def _gather_residuals(self, entry):
        """graftzero EF operands: one f32 residual per bucket, read from
        (and later written back to) the Updater store under the SAME
        keys the eager BucketQuantizer uses — eager and compiled steps
        share one error-feedback trajectory, and checkpoint/resume
        carries it."""
        from ..parallel import quant as _quant
        updater = self._trainer._updaters[0]
        keys, vals = [], []
        for k, spec in enumerate(entry["bspecs"]):
            key = _quant.residual_key(spec["indices"],
                                      entry["qdtypes"][k])
            r = updater.states.get(key)
            if r is None:
                r = jnp.zeros((entry["qsizes"][k],), jnp.float32)
            elif not isinstance(r, jnp.ndarray):
                # set_states round trip parks residuals as host numpy
                r = jnp.asarray(np.asarray(r), dtype=jnp.float32)
            keys.append(key)
            vals.append(r)
        return keys, tuple(vals)

    def _aot(self, entry, kind, cargs):
        """Resolve the executable for program ``kind`` ("one",
        "fwd_bwd", "update").  The first dispatch AOT-lowers and
        compiles the jit wrapper (``.lower(*args).compile()``) — the
        same trace+compile the first jit call would have paid, done
        explicitly so the :class:`jax.stages.Compiled` handle exists:
        graftxray reads its HLO text (phase scope maps) and
        cost/memory analysis (``xray.note_program`` → blackbox
        ``xray_cost`` / retrace ``xray_cost_diff`` journals).  lr/wd/
        rescale ride as weak-typed scalar OPERANDS, so later calls with
        different values reuse the same executable (probed; the
        selftest's set_learning_rate leg asserts it).  Any AOT failure
        pins the plain jit wrapper instead — dispatch never breaks for
        want of introspection."""
        c = entry["aot"].get(kind)
        if c is None:
            jfn = entry[kind]
            try:
                c = jfn.lower(*cargs).compile()
                _xray.note_program(
                    "gstep_" + kind, c,
                    label="%s/%dp/%db" % (kind, len(entry["trainable"]),
                                          len(entry["bspecs"])))
            except Exception:
                c = jfn
            entry["aot"][kind] = c
        return c

    def _dispatch(self, entry, args, batch_size):
        tr = self._trainer
        optimizer = tr._optimizer
        optimizer.rescale_grad = tr._scale / batch_size
        gathered = self._gather(entry, args)
        if gathered is None:
            return self._miss(args, batch_size, "state-arity")
        (train_vals, frozen_vals, input_vals, frozen_nds,
         state_nds, state_vals, train_nds) = gathered
        # host bookkeeping ticks in the exact _bucketed_update order
        # (bucket outer, param inner) — update counts, schedulers and
        # Adam's bias correction see the same sequence as eager; the
        # resolved scalars then ride as traced OPERANDS (no retrace on
        # set_learning_rate / wd / batch-size changes)
        lrs, wds = [], []
        for spec in entry["bspecs"]:
            lr_b, wd_b = [], []
            for i in spec["indices"]:
                lr, wd = opt.fused_lr_wd(optimizer, i, spec["kind"])
                lr_b.append(lr)
                wd_b.append(wd)
            lrs.append(tuple(lr_b))
            wds.append(tuple(wd_b))
        lrs, wds = tuple(lrs), tuple(wds)
        rescale = float(optimizer.rescale_grad)
        rng = random_state.next_key()
        kv = tr._kvstore_obj
        ctx = tr._contexts[0]

        # graftguard (GRAFT_COMPILE_CHECK): EH303 re-hashes the fused
        # config against the trace-time bake, EH302 poisons the donated
        # buffers for the dispatch window, EH304 replays the un-jitted
        # twin on sampled steps (same operands, same rng key)
        aud = self._auditor if _csafety._ACTIVE[0] else None
        sentinel = deep = False
        if aud is not None:
            deep = aud.deep_due()
            if deep:
                aud.check_bake(
                    entry["bake_kinds"], entry["bake_sig"],
                    tuple(tuple(opt._fused_config(optimizer, k))
                          for k in entry["bake_kinds"]))
            sentinel = aud.sentinel_due()

        try:
            with _blackbox.step_journal("trainer", batch_size=batch_size,
                                        fused=True, overlapped=False,
                                        duplex=False, compiled=True):
                with _ttracing.phase_span("kvstore"):
                    # settle any in-flight pulls from a preceding
                    # fallback step; compiled steps never arm the
                    # mid-backward scheduler (no eager backward → no
                    # grad-ready hooks)
                    tr._pull_scheduler.finish()
                    if tr._scheduler._armed:
                        tr._scheduler.disarm()
                with _engine.offband():
                    if kv is None:
                        with _ttracing.phase_span("update"):
                            ref = None
                            if sentinel:
                                ref = entry["one_raw"](
                                    train_vals, state_vals, frozen_vals,
                                    input_vals, rng, lrs, wds, rescale)
                            if deep:
                                aud.poison(_donated_nds(train_nds,
                                                        state_nds),
                                           "one")
                            cargs = (train_vals, state_vals, frozen_vals,
                                     input_vals, rng, lrs, wds, rescale)
                            one_c = self._aot(entry, "one", cargs)
                            outs, aux, new_w, new_s = one_c(*cargs)
                            if ref is not None:
                                aud.check_parity(
                                    "one", (outs, aux, new_w, new_s),
                                    ref)
                            self._write_back(entry, new_w, new_s,
                                             state_nds, frozen_nds, aux)
                    else:
                        qcfg = entry.get("quant")
                        with _ttracing.phase_span("fwd"):
                            if qcfg is None:
                                cargs = (train_vals, frozen_vals,
                                         input_vals, rng)
                            else:
                                res_keys, res_vals = \
                                    self._gather_residuals(entry)
                                cargs = (train_vals, frozen_vals,
                                         input_vals, rng, res_vals)
                            fb_c = self._aot(entry, "fwd_bwd", cargs)
                            fb_out = fb_c(*cargs)
                            if qcfg is None:
                                outs, aux, flats = fb_out
                            else:
                                (outs, aux, qcodes, qscales,
                                 new_res) = fb_out
                                # EF residual write-back NOW — it is
                                # this step's local quantization error,
                                # independent of the wire reduce; same
                                # store keys as the eager quantizer
                                updater = tr._updaters[0]
                                for rk, r in zip(res_keys, new_res):
                                    updater.states[rk] = r
                        with _ttracing.phase_span("kvstore"):
                            # cross-worker reduce AT the program
                            # boundary: the existing wire, same bytes,
                            # same algebra — or (graftzero) the packed
                            # quantized payload, ONE collective batch
                            if qcfg is None:
                                flat_nds = [NDArray(f, ctx=ctx)
                                            for f in flats]
                                kv.reduce_many(flat_nds,
                                               label="compiled_step")
                                reduced = tuple(f._read()
                                                for f in flat_nds)
                            else:
                                mode, qblock = qcfg
                                pairs = [(NDArray(c, ctx=ctx),
                                          NDArray(s, ctx=ctx))
                                         for c, s in zip(qcodes,
                                                         qscales)]
                                kv.reduce_quantized(
                                    pairs, list(entry["qsizes"]),
                                    mode, qblock,
                                    label="compiled_step")
                                reduced = tuple(
                                    (c._read(), s._read())
                                    for c, s in pairs)
                        with _ttracing.phase_span("update"):
                            ref_u = None
                            if sentinel:
                                aud.check_parity(
                                    "fwd_bwd", fb_out,
                                    entry["fwd_bwd_raw"](*cargs))
                                ref_u = entry["update_raw"](
                                    train_vals, state_vals, reduced,
                                    lrs, wds, rescale)
                            if deep:
                                aud.poison(_donated_nds(train_nds,
                                                        state_nds),
                                           "update")
                            cargs = (train_vals, state_vals, reduced,
                                     lrs, wds, rescale)
                            up_c = self._aot(entry, "update", cargs)
                            new_w, new_s = up_c(*cargs)
                            if ref_u is not None:
                                aud.check_parity("update",
                                                 (new_w, new_s), ref_u)
                            self._write_back(entry, new_w, new_s,
                                             state_nds, frozen_nds, aux)
        finally:
            if aud is not None:
                aud.sweep()
        self.compiled_steps += 1
        _tmetrics.trainer_compiled_step(len(entry["trainable"]))
        out_arrays = [NDArray(v, ctx=ctx) for v in outs]
        out, _ = _regroup(out_arrays, entry["fmt_cell"].get(
            "fmt", ["0"] * len(out_arrays)))
        return out

    def _write_back(self, entry, new_w, new_s, state_nds, frozen_nds, aux):
        tr = self._trainer
        tpos = entry["tpos"]
        for k, spec in enumerate(entry["bspecs"]):
            for pos, i in enumerate(spec["indices"]):
                tr._params[i].list_data()[0]._write(new_w[tpos[i]])
                for arr, val in zip(state_nds[k][pos], new_s[k][pos]):
                    arr._write(val)
        if aux:
            for n, nd in zip(entry["frozen_names"], frozen_nds):
                if n in aux:
                    nd._write(aux[n])

def _donated_nds(train_nds, state_nds):
    """The NDArrays whose buffers a dispatch donates (program positions
    0/1: train_vals + state_vals).  Poisoned by contract even where
    ``_donation_supported()`` is False — CPU CI must catch the
    read-after-donate that only real TPUs would corrupt.  Takes the
    arrays _gather already resolved (re-walking the param store per
    dispatch was measurable against the < 2% auditor budget)."""
    nds = list(train_nds)
    for bucket in state_nds:
        for arrs in bucket:
            nds.extend(arrs)
    return nds


def _as_nd(a):
    from .. import ndarray as _nd
    return _nd.array(np.asarray(a))


# ---------------------------------------------------------------------------
# selftest: trace → ≤2 guarded retraces → ULP-parity assert (lint tier)
# ---------------------------------------------------------------------------

# operand-vs-constant scalar layout can shift fma contraction ~1 ULP per
# step; a handful of steps compound to a few ULP.  EH104 convention.
SELFTEST_ULP_TOL = 8


def _make_net(prefix, n_params=4, shape=(1, 5)):
    from . import nn  # noqa: F401  (package side effects)

    class _Net(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                for k in range(n_params):
                    setattr(self, "w%d" % k,
                            self.params.get("w%d" % k, shape=shape))

        def hybrid_forward(self, F, x, **ps):
            acc = None
            for k in range(n_params):
                y = (ps["w%d" % k] * ps["w%d" % k] * x).sum()
                acc = y if acc is None else acc + y
            return acc

    return _Net(prefix=prefix)


def _seed_params(net, seed=7):
    import incubator_mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    net.initialize(ctx=mx.cpu())
    for name in sorted(net.collect_params()):
        p = net.collect_params()[name]
        p.set_data(mx.nd.array(
            rng.uniform(-1, 1, p.shape).astype(np.float32)))


def selftest(verbose=False):
    """Returns a list of problems — empty means pass.  Exercises: lazy
    trace on step 1, compiled dispatch with ZERO retraces after step 2,
    one guarded retrace on a shape change (≤2 total), no retrace on
    set_learning_rate, and params+states ULP-parity vs the
    bucketed-eager twin throughout."""
    import incubator_mxnet_tpu as mx
    from . import Trainer

    problems = []
    net_e = _make_net("graftstep_e_")
    net_c = _make_net("graftstep_c_")
    _seed_params(net_e)
    _seed_params(net_c)
    tr_e = Trainer(net_e.collect_params(), "sgd",
                   {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
                   kvstore=None)
    tr_c = Trainer(net_c.collect_params(), "sgd",
                   {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
                   kvstore=None)
    cstep = CompiledStep(tr_c, net_c, enabled=True)

    def eager_step(x):
        with autograd.record():
            out = net_e(x)
        out.backward()
        tr_e.step(1)
        return out

    def parity(tag):
        names = sorted(net_e.collect_params())
        for ne, nc in zip(names, sorted(net_c.collect_params())):
            a = net_e.collect_params()[ne].data()._read()
            b = net_c.collect_params()[nc].data()._read()
            ulp = max_ulp_diff(a, b)
            if ulp > SELFTEST_ULP_TOL:
                problems.append("%s: weight %s diverged by %s ULP"
                                % (tag, ne, ulp))
        se, sc = tr_e._updaters[0].states, tr_c._updaters[0].states
        for i in se:
            for ae, ac in zip(opt._fused_state_arrays("sgd", se[i]),
                              opt._fused_state_arrays("sgd", sc[i])):
                ulp = max_ulp_diff(ae._read(), ac._read())
                if ulp > SELFTEST_ULP_TOL:
                    problems.append("%s: state[%d] diverged by %s ULP"
                                    % (tag, i, ulp))

    rngx = np.random.RandomState(3)
    for step in range(6):
        x = mx.nd.array(rngx.uniform(0.5, 1.5, (6, 5)).astype(np.float32))
        eager_step(x)
        cstep(x)
        if verbose:
            print("step %d retraces=%d compiled=%d fallback=%d"
                  % (step, cstep.retraces, cstep.compiled_steps,
                     cstep.fallback_steps))
    parity("static-loop")
    if cstep.retraces != 1:
        problems.append("static loop traced %d times (want exactly 1 — "
                        "zero retraces after step 2)" % cstep.retraces)
    if cstep.compiled_steps != 5:
        problems.append("expected 5 compiled dispatches after the lazy "
                        "step-1 trace, got %d" % cstep.compiled_steps)
    # lr change must NOT retrace (lr is a traced operand)
    tr_e.set_learning_rate(0.01)
    tr_c.set_learning_rate(0.01)
    x = mx.nd.array(rngx.uniform(0.5, 1.5, (6, 5)).astype(np.float32))
    eager_step(x)
    cstep(x)
    if cstep.retraces != 1:
        problems.append("set_learning_rate retraced the compiled step "
                        "(lr must ride as an operand)")
    parity("post-lr-change")
    # shape change: ONE guarded retrace (≤ 2 total), then compiled again
    for _ in range(2):
        x2 = mx.nd.array(rngx.uniform(0.5, 1.5, (3, 5)).astype(np.float32))
        eager_step(x2)
        cstep(x2)
    if cstep.retraces != 2:
        problems.append("shape change cost %d retraces (want exactly 2 "
                        "entries total)" % cstep.retraces)
    parity("post-shape-change")
    if cstep.forward_order is None:
        problems.append("first-touch forward order was not recorded by "
                        "the step trace")
    return problems


def main(argv=None):
    import argparse
    import sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="python -m incubator_mxnet_tpu.gluon.step_compile",
        description="graftstep whole-step compilation selftest")
    ap.add_argument("--selftest", action="store_true",
                    help="trace → ≤2 guarded retraces → ULP-parity "
                         "assert vs the bucketed-eager twin (CI tier)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if not args.selftest:
        ap.print_help()
        return 2
    problems = selftest(verbose=args.verbose)
    if problems:
        for p in problems:
            print("graftstep selftest FAIL: %s" % p, file=sys.stderr)
        return 1
    print("graftstep selftest OK (1 lazy trace, 0 steady-state retraces, "
          "1 guarded retrace on shape change, ULP parity held)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
