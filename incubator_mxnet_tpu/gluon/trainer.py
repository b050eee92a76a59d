"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py, 238 LoC).

Applies an Optimizer to a set of Parameters. When a KVStore is attached the
gradient path mirrors the reference (trainer.py:156 _update → kvstore
push/pull or update_on_kvstore); on a device mesh the same step lowers to
psum-over-ICI via the parallel package instead of Comm/NCCL reductions.

graftfuse (the bucketed step path): ``step`` no longer walks parameters
one at a time.  Dense float parameters are greedily packed — per dtype,
in tape or index order (``GRAFT_BUCKET_ORDER``, see ``_plan_order``) —
into flat buckets of ~``GRAFT_BUCKET_BYTES`` (default 4 MiB); each
bucket's gradients are concatenated into ONE buffer, reduced across
contexts as one elementwise tree-sum and across workers as one
collective (``KVStore.reduce_many`` → ``_cross_worker_reduce_many``), and
applied through ONE jitted multi-tensor optimizer program per
(optimizer-class, bucket signature) — ``optimizer.fused_bucket_update``.
The whole step stays on device (no ``_read()`` round trips between reduce
and update) and is bit-identical to the per-param path (the fused program
runs the same registered op formulas element-for-element).  Per-param
fallbacks: ``ignore_stale_grad``, sparse grads, and optimizers without a
fused kernel (anything but exact SGD/Adam).  Gradient compression no
longer forces the serial per-key path: ``set_gradient_compression`` and
``GRAFT_QUANT_REDUCE=int8|2bit`` route the BUCKET wire through graftzero's
block-scaled quantization (``parallel.quant``) with error-feedback
residuals kept in the Updater store.  One
behavioral delta on the fused path: reduced gradients are consumed
directly by the update and are NOT written back into
``param.list_grad()`` (``allreduce_grads()`` — the grad-accumulation API
— keeps exact per-key write-back semantics).

graftlap (PR 7) moved each bucket's reduce ISSUE into the backward pass:
``overlap.BucketScheduler`` arms grad-ready hooks at the end of every
bucketed step, the next backward delivers each parameter's gradient the
moment it finalizes, and complete buckets ship through
``KVStore.reduce_many_async`` while the walk continues — ``step()`` only
waits.

graftduplex (PR 9) finishes the wire: the ``update_on_kvstore`` path —
previously 100% serial — gets its own bucket plan (``_duplex_plan``):
bucket reduces ride the same grad-ready hooks mid-backward, the
store-side optimizer applies each bucket's split pieces
(``KVStore.apply_reduced``), and each bucket's weight pull goes straight
back on the wire as a ``PullHandle`` (``KVStore.pull_many_async``)
waited at FIRST USE in the next forward (``overlap.PullScheduler``
first-touch hooks) — the step is full-duplex: gradients stream out
under backward while updated weights stream back under data loading and
the next forward's early layers.  Serial fallbacks mirror the reduce
side: ``GRAFT_OVERLAP_PULL=0``, a stale (user-overwritten) weight
between steps, compression, sparse params; the dist_async parameter
service keeps per-group async pulls (background-thread RPC) without the
bucket plan.
"""
from __future__ import annotations

import os
import time

import numpy as np

from .. import elastic as _elastic
from .. import engine as _engine
from .. import optimizer as opt
from .. import overlap as _overlap
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]

_DEFAULT_BUCKET_BYTES = _overlap.DEFAULT_BUCKET_BYTES

# back-compat aliases: the bucket/scheduler types moved to overlap.py so
# Module can ride the same machinery (graftduplex)
_Bucket = _overlap.Bucket
_BucketScheduler = _overlap.BucketScheduler


class Trainer(object):
    """ref: gluon/trainer.py class Trainer."""

    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got %s." % (type(params)))
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    "got list of %s." % (type(param)))
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params if optimizer_params else {}
        self._scale = optimizer_params.get("rescale_grad", 1.0)
        self._contexts = self._check_contexts()
        self._init_optimizer(optimizer, optimizer_params)
        self._kv_initialized = False
        self._kvstore = kvstore
        # reference parity (trainer.py update_on_kvstore kwarg): None =
        # auto (store type decides), True/False forces — the switch that
        # selects between the local fused update and the store-side
        # (server-semantics) update the duplex path overlaps
        self._update_on_kvstore_arg = update_on_kvstore
        self._scheduler = _BucketScheduler(self)
        self._pull_scheduler = _overlap.PullScheduler()
        self._bucket_lateness = {}      # param idx -> blocked-wait EWMA
        #                                 (tape-order packing tie-breaker)
        # graftelastic: membership attachment + change listeners; inert
        # (two empty attributes) unless GRAFT_ELASTIC wires them up
        self._membership = None
        self._membership_cbs = []

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            assert contexts is None or contexts == ctx, \
                "All Parameters must be initialized on the same set of contexts, " \
                "but Parameter %s is initialized on %s while previous Parameters " \
                "are initialized on %s." % (param.name, str(ctx), str(contexts))
            contexts = ctx
        return contexts

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer " \
                "instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _init_kvstore(self):
        """Attach kvstore if requested (ref: trainer.py _init_kvstore)."""
        from .. import kvstore as kvs_mod
        arg_arrays = {param.name: param.data(self._contexts[0])
                      for param in self._params}
        kvstore, update_on_kvstore = kvs_mod.create_kvstore(
            self._kvstore, len(self._contexts), arg_arrays)
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if "dist" in kvstore.type:
                # dist_sync: the store is the in-graph allreduce of GRADS
                # (push then pull grads, update locally).  dist_async: the
                # store IS the weights — the host parameter server applies
                # every push with the server-side optimizer and pulls
                # return weights (kvstore_dist_server.h async mode)
                update_on_kvstore = "async" in kvstore.type
            if self._update_on_kvstore_arg is not None:
                # explicit user choice (reference trainer.py kwarg);
                # dist_async cannot update locally — its weights live on
                # the parameter server (same reference restriction)
                if "async" in kvstore.type \
                        and not self._update_on_kvstore_arg:
                    raise ValueError(
                        "Cannot set update_on_kvstore=False on dist_async")
                update_on_kvstore = bool(self._update_on_kvstore_arg)
            # one batched init: on dist stores this is a single rank-0
            # broadcast collective for all params, not one per key
            kvstore.init(list(range(len(self._params))),
                         [p.list_data()[0] for p in self._params])
            # pull EVERY param (frozen ones included): on dist stores the
            # init above broadcast rank 0's values, and a frozen layer left
            # at its local random init would make ranks diverge forever
            for i, param in enumerate(self._params):
                kvstore.pull(i, param.list_data(), priority=-i)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            self._kvstore_obj = kvstore
            self._update_on_kvstore = update_on_kvstore
        else:
            self._kvstore_obj = None
            self._update_on_kvstore = False
        self._kv_initialized = True

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate can be accessed.")
        return self._optimizer.lr

    def set_learning_rate(self, lr):
        """ref: trainer.py set_learning_rate."""
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate is mutated.")
        self._optimizer.lr = lr

    # -- graftelastic: membership fencing -----------------------------------
    def attach_membership(self, membership):
        """Attach this rank's :class:`~..elastic.Membership` state
        machine: ``step()`` becomes its fence — queued membership
        changes apply at the top of the next step, never
        mid-collective."""
        self._membership = membership

    def on_membership_change(self, fn):
        """Register ``fn(view)`` to run after every applied membership
        change (plans already invalidated; ``view`` is the new
        :class:`~..elastic.MembershipView`).  Returns ``fn`` so it
        works as a decorator."""
        self._membership_cbs.append(fn)
        return fn

    def _membership_changed(self, view):
        """The re-partition hook :meth:`~..elastic.Membership.apply_pending`
        calls on this trainer: every world-size-derived artifact —
        fused/duplex bucket plans, the quantizer's store binding, armed
        overlap hooks, in-flight pulls — is dropped and rebuilt lazily
        for the new view on the next step."""
        self._pull_scheduler.finish()
        self._scheduler.disarm()
        self._fused_plan_cache = None
        self._duplex_plan_cache = None
        self._quant_cache = None
        for fn in self._membership_cbs:
            fn(view)

    def step(self, batch_size, ignore_stale_grad=False):
        """Apply one optimization step with grads scaled by 1/batch_size
        (ref: trainer.py:156 step).  Takes the bucketed fused path when
        the plan allows it; falls back to the (batched) per-param path
        otherwise — both produce bit-identical parameters."""
        # rescale BEFORE the kvstore handshake: update_on_kvstore ships a
        # pickled optimizer to the server exactly once, so the first
        # step's scaling must already be on it (reference limitation too:
        # later batch-size changes don't reach the server copy)
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            self._init_kvstore()
        # graftelastic step fence: queued membership changes land HERE —
        # between steps, before this step's plan resolves — so a
        # re-partition can never race a live collective.  Off (the
        # default) this is one memoized env read.
        if _elastic.enabled() and self._membership is not None \
                and self._membership.pending():
            self._membership.apply_pending(trainer=self,
                                           kv=self._kvstore_obj)
        if ignore_stale_grad:
            plan = None
        elif self._update_on_kvstore:
            plan = self._duplex_plan()      # store-side update: duplex
        else:
            plan = self._fused_plan()       # local fused update
        from ..telemetry import blackbox as _blackbox
        from ..telemetry import tracing as _ttracing
        # graftwatch step journal: one flight-recorder event per step
        # with kvstore/update phase latencies + device-memory highwater;
        # a crash or hang mid-step names the phase it stopped in
        overlap = plan is not None and self._overlap_enabled() \
            and self._kvstore_obj is not None
        duplex = self._update_on_kvstore and plan is not None
        with _blackbox.step_journal("trainer", batch_size=batch_size,
                                    fused=plan is not None,
                                    overlapped=overlap, duplex=duplex):
            with _ttracing.phase_span("kvstore"):
                # settle last step's in-flight weight pulls FIRST: an
                # out array rides one handle at a time, and a stale
                # (user-overwritten) weight downgrades THIS round's
                # pulls to the serial path (abandon-and-fallback)
                pull_stale = self._pull_scheduler.finish()
                if plan is None:
                    self._scheduler.disarm()
                    self._allreduce_grads()
                else:
                    reduced = self._bucketed_allreduce(plan)
            with _ttracing.phase_span("update"):
                if plan is None:
                    self._update(ignore_stale_grad,
                                 pull_stale=pull_stale)
                elif duplex:
                    self._duplex_store_update(plan, reduced, pull_stale)
                else:
                    self._bucketed_update(plan, reduced,
                                          pull_stale=pull_stale)
        # graftlap: (re-)arm the grad-ready hooks so the NEXT backward
        # issues each bucket's reduce the moment its grads finalize;
        # first step after any config change runs serial (the plan must
        # exist before hooks know the buckets)
        if overlap:
            self._scheduler.arm(plan)
        elif self._scheduler._armed:
            self._scheduler.disarm()

    def allreduce_grads(self):
        """ref: trainer.py allreduce_grads (1.3+, for grad accumulation)."""
        if not self._kv_initialized:
            self._init_kvstore()
        # the accumulation API reduces INTO param.grad() with write-back
        # semantics; anything graftlap issued against the same grads is
        # unrelated to this call — drop it so no bracket stays open
        self._scheduler.disarm()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore_obj is None:
            return
        # one batched multi-key push/pull: a single fused dist collective
        # for the whole gradient set instead of one round per key (the
        # batching role of kvstore_dist.h's big-array sharding)
        keys = [i for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        if not keys:
            return
        grads = [self._params[i].list_grad() for i in keys]
        self._kvstore_obj.push_many(keys, grads)
        if not self._update_on_kvstore:
            self._kvstore_obj.pull_many(keys, grads)

    def update(self, batch_size, ignore_stale_grad=False):
        """ref: trainer.py update (apply updates without reduce)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False, pull_stale=None):
        if self._kvstore_obj is not None and self._update_on_kvstore:
            if pull_stale is None:      # direct update() call: settle
                pull_stale = self._pull_scheduler.finish()
            keys = [i for i, p in enumerate(self._params)
                    if p.grad_req != "null"]
            if keys:
                self._pull_weights(keys, stale=pull_stale)
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            for upd, arr, grad in zip(self._updaters, param.list_data(),
                                      param.list_grad()):
                upd(i, grad, arr)

    def _pull_overlap_ok(self, keys, stale):
        """Async pulls for this round?  ``stale`` > 0 (a weight the user
        overwrote while its pull was in flight) forces one serial round —
        the abandon-and-fallback rail; sparse params always pull
        serially."""
        return self._overlap_pull_enabled() and not stale \
            and all(self._params[i]._stype == "default" for i in keys)

    def _pull_weights(self, keys, stale=0):
        """Bring updated weights back from the store for ``keys`` —
        async per ~bucket-size group with first-touch waits when the
        duplex pull side is on (graftduplex; the dist_async parameter
        service lands here and overlaps its pull RPC on a background
        thread), the synchronous ``pull_many`` otherwise."""
        _overlap.pull_round(
            self._pull_scheduler, self._kvstore_obj, keys,
            [self._params[i].list_data() for i in keys],
            [int(np.prod(self._params[i].shape))
             * np.dtype(self._params[i].dtype).itemsize for i in keys],
            self._bucket_target_bytes(),
            self._pull_overlap_ok(keys, stale))

    # -- graftfuse: the bucketed step path ---------------------------------
    _bucket_bytes_override = None     # tests/benches force a target here
    _overlap_override = None          # tests/benches force overlap on/off

    def _bucket_target_bytes(self):
        if self._bucket_bytes_override is not None:
            return int(self._bucket_bytes_override)
        try:
            return int(os.environ.get("GRAFT_BUCKET_BYTES",
                                      str(_DEFAULT_BUCKET_BYTES)))
        except ValueError:
            return _DEFAULT_BUCKET_BYTES

    def _overlap_enabled(self):
        """GRAFT_OVERLAP (default on): overlap bucket reduces with the
        backward pass (graftlap).  Like GRAFT_BLACKBOX, multi-host jobs
        must set it IDENTICALLY on every rank — the issue order of the
        overlapped collectives is part of the lockstep contract."""
        if self._overlap_override is not None:
            return bool(self._overlap_override)
        return os.environ.get("GRAFT_OVERLAP", "1").strip().lower() \
            not in ("0", "false", "no", "off")

    _overlap_pull_override = None     # tests/benches force pull overlap

    def _overlap_pull_enabled(self):
        """GRAFT_OVERLAP_PULL (default on): overlap the store→worker
        weight pulls with the next forward (graftduplex).  Same
        rank-consistency contract as GRAFT_OVERLAP."""
        return _overlap.overlap_pull_enabled(self._overlap_pull_override)

    # -- overlap.BucketScheduler host protocol ------------------------------
    _sched_autograd_hooks = True      # hooks delivered by autograd's walk

    def _sched_entries(self, b):
        out = []
        for i in b.indices:
            grads = self._params[i].list_grad()
            for j, d in enumerate(self._params[i].list_data()):
                out.append(((i, j), d, grads[j]))
        return out

    def _sched_eligible(self, b):
        return all(self._params[i].grad_req == "write" for i in b.indices)

    def _sched_kv(self):
        return self._kvstore_obj

    def _sched_flat(self, b):
        return self._bucket_flat(b)

    def _sched_pass_id(self):
        from .. import autograd
        return autograd.backward_pass_id()

    def _sched_label(self, b):
        return "bucket[%s:%dp:%dB]" % (np.dtype(b.dtype).name,
                                       len(b.indices), b.nbytes)

    # -- graftzero: quantized bucket wire + ZeRO-1 sharded update -----------
    def _quant_store(self):
        """The Updater whose ``states`` dict owns the error-feedback
        residuals: the store-side updater when the store runs the update
        (duplex), ``_updaters[0]`` otherwise — either way the store that
        ``save_states``/armor snapshots already serialize."""
        kv = self._kvstore_obj
        if self._update_on_kvstore and kv is not None \
                and kv._updater is not None:
            return kv._updater
        return self._updaters[0]

    def _quantizer(self):
        """The active :class:`~..parallel.quant.BucketQuantizer`, or
        None (quantization off — the bit-identical default path).  The
        env resolution is one dict lookup per call; the quantizer object
        is cached per (mode, block) so toggling re-resolves cleanly."""
        kv = self._kvstore_obj
        if kv is None:
            return None
        from ..parallel import quant as _quant
        mode = _quant.resolve_mode(getattr(kv, "_quant_override", None))
        if mode is None:
            return None
        block = _quant.resolve_block()
        cached = getattr(self, "_quant_cache", None)
        if cached is not None and cached[0] == (mode, block):
            return cached[1]
        q = _quant.BucketQuantizer(mode, block, self._quant_store)
        self._quant_cache = ((mode, block), q)
        return q

    @staticmethod
    def _quant_eligible(b):
        # integer buckets ride the dense wire (their sums are exact)
        return np.issubdtype(np.dtype(b.dtype), np.floating)

    def _sched_reduce_async(self, kv, b, flat):
        """The overlap scheduler's reduce-issue hook: quantize the
        bucket payload onto the wire when the quantized path is on,
        plain ``reduce_many_async`` otherwise — the scheduler itself
        issues quantized buckets unchanged."""
        q = self._quantizer()
        if q is not None and self._quant_eligible(b):
            return q.reduce_async(kv, b, flat,
                                  label=self._sched_label(b))
        return kv.reduce_many_async([flat], label=self._sched_label(b))

    def _zero_spec(self):
        """The ZeRO-1 shard layout this trainer updates under, or None:
        ``GRAFT_SHARD_OPTIMIZER=1`` on the local fused path shards the
        bucket list across contexts (the 8-dev mesh harness) or — with a
        single context on a real dist wire — across worker ranks."""
        from ..parallel import quant as _quant
        if not _quant.zero_enabled():
            return None
        kv = self._kvstore_obj if self._kv_initialized else None
        if kv is None or self._update_on_kvstore:
            return None
        n_ctx = len(self._contexts)
        if n_ctx > 1:
            return {"axis": "ctx", "n": n_ctx, "rank": 0}
        if kv.num_workers > 1:
            return {"axis": "worker", "n": int(kv.num_workers),
                    "rank": int(kv.rank)}
        return None

    def _state_shard_nbytes(self):
        """Max optimizer-state bytes held for one shard owner — what the
        ``graft_trainer_state_shard_bytes`` gauge reports (metadata
        walk, never forces a flush)."""
        return max(u.states_nbytes() for u in self._updaters)

    def _plan_order(self):
        """Parameter iteration order for bucket packing:
        ``(mode, sig_perm, build_perm)``.

        ``GRAFT_BUCKET_ORDER=tape`` (default) sorts parameters by
        DESCENDING earliest-tape-position (``autograd`` stamps
        ``_tape_pos`` on each hooked data array during the backward
        prescan): the reverse walk finalizes high positions first, so
        first-to-finalize params share the first buckets and their
        reduces hit the wire earliest — the overlap window covers more
        of backward (today's index packing often closes the last bucket
        only at end-of-walk).  Parameters without a stamp yet (first
        steps, hook-ineligible) pack after the stamped ones in index
        order.  Ties (params finalized by the same tape node) break on
        the per-param blocked-wait EWMA the step feeds back
        (``_bucket_lateness``, quantized to ms): systematically late
        params pack earlier.  The lateness tie-break applies ONLY when a
        plan is being (re)built — ``sig_perm`` (tape positions + index)
        is what the plan cache keys on, so EWMA drift can never
        invalidate a cached plan and trigger the serial fallback step a
        rebuild costs; a rebuild for a real reason (tape change, shape
        change) picks up the latest lateness.
        ``GRAFT_BUCKET_ORDER=index`` reverts to plain index packing."""
        n = len(self._params)
        if _overlap.bucket_order() != "tape":
            perm = tuple(range(n))
            return ("index", perm, perm)
        pos = []
        for p in self._params:
            d = None
            if p._data is not None:
                try:
                    d = p.list_data()[0]
                except Exception:
                    d = None
            pos.append(None if d is None
                       else getattr(d, "_tape_pos", None))
        late = self._bucket_lateness

        def _key(i, with_lateness):
            tp = pos[i]
            if tp is None:
                return (1, 0, 0, i)
            lateness = -int(round(late.get(i, 0.0) * 1e3)) \
                if with_lateness else 0
            return (0, -tp, lateness, i)

        sig_perm = tuple(sorted(range(n), key=lambda i: _key(i, False)))
        build_perm = tuple(sorted(range(n), key=lambda i: _key(i, True)))
        return ("tape", sig_perm, build_perm)

    def _note_bucket_lateness(self, b, blocked_s):
        """Feed one overlapped bucket's blocked wait back into the
        packing tie-breaker (0.8/0.2 EWMA, the straggler convention)."""
        for i in b.indices:
            prev = self._bucket_lateness.get(i)
            self._bucket_lateness[i] = blocked_s if prev is None \
                else 0.8 * prev + 0.2 * blocked_s

    def _duplex_plan(self):
        """The bucket plan for the update_on_kvstore (store-side update)
        path, or None when step() must stay on the serial per-key wire.

        Unlike ``_fused_plan`` the optimizer needs no fused kernel — the
        update runs store-side via ``KVStore.apply_reduced`` with the
        exact per-key updater — so buckets group by dtype alone.
        Fallbacks: no store, the dist_async parameter service (pushes
        must ride the PS RPC; its PULLS still overlap via
        ``_pull_weights``), sparse params, and unknown shapes.
        Compression no longer falls back: the bucket wire quantizes
        through graftzero (block-scaled, error feedback) instead of the
        per-key threshold path it used to force."""
        target = self._bucket_target_bytes()
        kv = self._kvstore_obj
        if target <= 0 or kv is None or not self._update_on_kvstore \
                or getattr(kv, "_ps", None) is not None:
            return None
        order_mode, sig_perm, perm = self._plan_order()
        sig = ("duplex", target, order_mode, sig_perm,
               len(self._contexts),
               tuple((str(p.dtype), p.shape, p.grad_req, p._stype,
                      p._grad_stype) for p in self._params))
        cached = getattr(self, "_duplex_plan_cache", None)
        if cached is not None and cached[0] == sig:
            return cached[1]
        open_buckets = {}       # dtype -> (indices, nbytes)
        buckets, leftover = [], []
        for i in perm:
            p = self._params[i]
            if p.grad_req == "null":
                continue
            dense = p._stype == "default" and p._grad_stype == "default"
            known = p.shape is not None and int(np.prod(p.shape)) > 0
            if not dense or not known:
                leftover.append(i)
                continue
            dt = np.dtype(p.dtype)
            nbytes = int(np.prod(p.shape)) * dt.itemsize
            idxs, total = open_buckets.setdefault(dt, ([], 0))
            idxs.append(i)
            total += nbytes
            if total >= target:
                buckets.append(_Bucket(idxs, None, dt, total))
                open_buckets.pop(dt)
            else:
                open_buckets[dt] = (idxs, total)
        for dt, (idxs, total) in open_buckets.items():
            buckets.append(_Bucket(idxs, None, dt, total))
        plan = (buckets, leftover) if buckets else None
        self._duplex_plan_cache = (sig, plan)
        if plan is not None:
            from ..telemetry import metrics as _tmetrics
            _tmetrics.trainer_buckets([b.nbytes for b in buckets],
                                      len(leftover))
        return plan

    def _fused_plan(self):
        """The bucket plan for the current configuration, or None when
        step() must take the per-param path wholesale.  Cached against a
        signature of everything the plan depends on, so steady-state
        steps pay one tuple comparison."""
        target = self._bucket_target_bytes()
        kv = self._kvstore_obj
        if target <= 0 or self._update_on_kvstore \
                or (kv is not None and kv._updater is not None):
            return None
        optimizer = self._optimizer
        # per-param state arity rides in the signature AND the bucket
        # key: existing states keep the formula they were created with
        # (e.g. momentum flipped mid-run only affects states created
        # afterwards, exactly like the per-param path), so a fused
        # program must never mix arities
        states0 = self._updaters[0].states
        kinds, arities = [], []
        for i, p in enumerate(self._params):
            kind = opt.fused_bucket_kind(optimizer, p.dtype) \
                if p.grad_req != "null" else None
            kinds.append(kind)
            arities.append(None if kind is None else (
                opt.fused_state_arity(optimizer, kind, states0[i])
                if i in states0 else opt.fused_state_arity(optimizer, kind)))
        order_mode, sig_perm, perm = self._plan_order()
        sig = (target, type(optimizer), bool(optimizer.multi_precision),
               getattr(optimizer, "momentum", None), tuple(arities),
               len(self._contexts), kv is not None, order_mode, sig_perm,
               tuple((str(p.dtype), p.shape, p.grad_req, p._stype,
                      p._grad_stype) for p in self._params))
        cached = getattr(self, "_fused_plan_cache", None)
        if cached is not None and cached[0] == sig:
            return cached[1]
        open_buckets = {}       # (dtype, arity) -> (indices, nbytes)
        buckets, leftover = [], []
        for i in perm:
            p = self._params[i]
            if p.grad_req == "null":
                continue
            kind = kinds[i]
            dense = p._stype == "default" and p._grad_stype == "default"
            known = p.shape is not None and int(np.prod(p.shape)) > 0
            if kind is None or not dense or not known:
                leftover.append(i)
                continue
            dt = np.dtype(p.dtype)
            bkey = (dt, arities[i])
            nbytes = int(np.prod(p.shape)) * dt.itemsize
            idxs, total = open_buckets.setdefault(bkey, ([], 0))
            idxs.append(i)
            total += nbytes
            if total >= target:
                buckets.append(_Bucket(idxs, kind, dt, total))
                open_buckets.pop(bkey)
            else:
                open_buckets[bkey] = (idxs, total)
        for (dt, _arity), (idxs, total) in open_buckets.items():
            buckets.append(_Bucket(idxs, opt.fused_bucket_kind(
                optimizer, dt), dt, total))
        plan = (buckets, leftover) if buckets else None
        self._fused_plan_cache = (sig, plan)
        if plan is not None:
            from ..telemetry import metrics as _tmetrics
            _tmetrics.trainer_buckets([b.nbytes for b in buckets],
                                      len(leftover))
        return plan

    def _bucket_flat(self, b):
        """One bucket's concatenated local gradient — delegates to the
        shared ``overlap.concat_ctx_sum`` packing math (per-context
        flatten + committed-device-safe elementwise tree-sum in context
        order), used verbatim by the serial step path, the overlapped
        mid-backward issue AND Module's bucketed reduce so all of them
        are bit-identical by construction."""
        return _overlap.concat_ctx_sum(
            [[self._params[i].list_grad()[j] for i in b.indices]
             for j in range(len(self._contexts))],
            ctx=self._contexts[0])

    def _bucketed_allreduce(self, plan):
        """Reduce every bucket's gradients with ONE concatenated buffer
        per bucket: contexts tree-sum elementwise (the same addition
        order as KVStore._reduce), workers allreduce through
        ``KVStore.reduce_many`` in one fused collective.  Returns
        {id(bucket): flat reduced NDArray}; empty when there is no store
        (the fused update then reads the per-param grads directly).

        graftlap: buckets whose reduce the scheduler already put on the
        wire mid-backward are only WAITED on here (same buffer, same
        reduction, earlier issue time); buckets that missed the overlap
        window — first step, stale grads, hook fallback — take the
        serial reduce exactly as before.  Wait order is plan order on
        every rank."""
        buckets, leftover = plan
        kv = self._kvstore_obj
        if kv is not None and leftover:
            grads = [self._params[i].list_grad() for i in leftover]
            kv.push_many(leftover, grads)
            if not self._update_on_kvstore:
                kv.pull_many(leftover, grads)
            # update_on_kvstore: the push applied the store-side update;
            # _duplex_store_update pulls the WEIGHTS back (pulling into
            # the grads here would clobber them with weight bytes)
        if kv is None:
            return {}
        overlap = self._overlap_enabled()
        issued = self._scheduler.take(plan) if overlap else {}
        serial = [b for b in buckets if id(b) not in issued]
        flats = {id(b): self._bucket_flat(b) for b in serial}
        q = self._quantizer()
        qb = [b for b in serial
              if q is not None and self._quant_eligible(b)]
        dense = [b for b in serial if id(b) not in {id(x) for x in qb}]
        if qb:
            # graftzero: float buckets ride the block-scaled quantized
            # wire — ONE batched quantized collective, EF residuals in
            # the Updater store, dequantized in place at the boundary
            q.reduce_serial(kv, qb, flats)
        if dense:
            kv.reduce_many([flats[id(b)] for b in dense])
        reduced, exposed_s, inflight_s = {}, 0.0, 0.0
        for b in buckets:
            entry = issued.get(id(b))
            if entry is None:
                reduced[id(b)] = flats[id(b)]
                continue
            flat, handle = entry
            t0 = time.perf_counter()
            handle.wait()
            t1 = time.perf_counter()
            exposed_s += t1 - t0
            inflight_s += t1 - handle.issued_at
            self._note_bucket_lateness(b, t1 - t0)
            reduced[id(b)] = flat
        if overlap:
            if issued:
                # a fully-overlapped step reduces only through
                # reduce_many_async, which skips the piggybacked dist
                # heartbeat (it would serialize the async dispatch) —
                # keep the worker-skew/last-seen telemetry alive with
                # one heartbeat from the wait side.  `issued` is
                # SPMD-symmetric, so every rank takes this collective
                # together (lockstep contract)
                kv.heartbeat()
            from ..telemetry import metrics as _tmetrics
            _tmetrics.trainer_overlap(len(issued), len(serial),
                                      exposed_s, inflight_s)
        return reduced

    def _duplex_store_update(self, plan, reduced, pull_stale=0):
        """The store-side half of the full-duplex step: split each
        bucket's reduced flat into per-key pieces, run the EXACT per-key
        store updater on them (``KVStore.apply_reduced`` — the same
        formula ``push`` would have applied, minus the second reduce),
        and put THAT bucket's weight pull straight back on the wire
        (``_pull_weights`` with the bucket as its own pull group) before
        moving to the next bucket — weights of early buckets stream back
        while later buckets are still updating, and the next forward's
        first-touch hooks absorb the wait.  Leftover (non-bucketable)
        params were pushed serially by ``_bucketed_allreduce``; their
        weights pull serially here."""
        from ..ndarray import NDArray
        buckets, leftover = plan
        kv = self._kvstore_obj
        _overlap.publish_pull_round(self._pull_scheduler)
        all_keys = [i for b in buckets for i in b.indices]
        overlap = self._pull_overlap_ok(all_keys, pull_stale)
        for b in buckets:
            flat = reduced[id(b)]
            shapes = [self._params[i].shape for i in b.indices]
            pieces = _engine.split_flat(flat._read(), shapes)
            kv.apply_reduced(
                list(b.indices),
                [NDArray(piece, ctx=self._contexts[0])
                 for piece in pieces])
            if overlap:
                # THIS bucket's weights go back on the wire before the
                # next bucket updates — the full-duplex stream
                self._pull_scheduler.issue(
                    kv, list(b.indices),
                    [self._params[i].list_data() for i in b.indices],
                    label="pull[%s:%dp:%dB]" % (np.dtype(b.dtype).name,
                                                len(b.indices), b.nbytes))
        if not overlap and all_keys:
            _overlap.serial_pull(
                kv, all_keys,
                [self._params[i].list_data() for i in all_keys])
        if leftover:
            kv.pull_many(leftover, [self._params[i].list_data()
                                    for i in leftover])

    def _bucketed_update(self, plan, reduced, pull_stale=0):
        """One fused multi-tensor optimizer dispatch per (bucket,
        context); leftover params take the per-param updater.  With
        ``GRAFT_SHARD_OPTIMIZER=1`` (graftzero ZeRO-1) the bucket list
        is sharded: each rank/context runs the fused update — and holds
        optimizer state — only for its contiguous shard, then broadcasts
        the updated weights (byte-identical to the unsharded step)."""
        shard = self._zero_spec()
        if shard is not None and plan[0]:
            return self._bucketed_update_sharded(plan, reduced, shard,
                                                 pull_stale)
        buckets, leftover = plan
        optimizer = self._optimizer
        n_ctx = len(self._contexts)
        for b in buckets:
            # bookkeeping ticks in the exact per-param order (param
            # outer, context inner) so update counts, schedulers and
            # Adam's bias correction see the same sequence
            lrs = [[0.0] * len(b.indices) for _ in range(n_ctx)]
            wds = [[0.0] * len(b.indices) for _ in range(n_ctx)]
            for pos, i in enumerate(b.indices):
                for j in range(n_ctx):
                    lr, wd = opt.fused_lr_wd(optimizer, i, b.kind)
                    lrs[j][pos] = lr
                    wds[j][pos] = wd
            flat = reduced.get(id(b))
            for j in range(n_ctx):
                weights = [self._params[i].list_data()[j]
                           for i in b.indices]
                grads = None if flat is not None else \
                    [self._params[i].list_grad()[j] for i in b.indices]
                fg = flat
                if flat is not None and j > 0:
                    # replicas commit to distinct devices: the reduced
                    # flat (context 0) must land on context j before the
                    # fused jit sees mixed placements — this transfer IS
                    # the per-context broadcast, bits preserved
                    from ..ndarray import NDArray
                    fg = NDArray(_engine.colocate(flat._read(),
                                                  weights[0]._read()),
                                 ctx=self._contexts[j])
                opt.fused_bucket_update(optimizer, self._updaters[j],
                                        b.indices, weights, grads,
                                        lrs[j], wds[j], flat_grad=fg)
        for i in leftover:
            param = self._params[i]
            for upd, arr, grad in zip(self._updaters, param.list_data(),
                                      param.list_grad()):
                upd(i, grad, arr)

    def _bucketed_update_sharded(self, plan, reduced, shard, pull_stale=0):
        """graftzero ZeRO-1: contiguous shard ownership over the bucket
        list (``parallel.quant.shard_owners``).  The lr/wd bookkeeping
        ticks in EXACTLY the unsharded (param outer, context inner)
        sequence on every rank — update counts, schedulers and Adam's
        bias correction stay identical — but only the OWNER runs the
        fused update for a bucket, so only the owner ever creates (and
        holds) its optimizer state: per-rank state bytes ~1/N, read off
        the ``graft_trainer_state_shard_bytes`` gauge.  The updated
        weights then broadcast, byte-identical to the unsharded step:

        * axis="ctx" (the device-mesh harness): the owning context
          updates; its weights go through the store's assignment branch
          (``apply_reduced`` — no updater tick) and straight back onto
          the overlapped ``pull_many_async`` wire bucket-by-bucket — a
          reduce-scatter + all-gather over the bucket flats.
        * axis="worker" (dist wire, single ctx): non-owners contribute
          a zeros flat to ONE dense ``reduce_many`` over the updated
          weight flats — an all-gather-by-sum that is exact (0 + x is
          bitwise x, modulo the irrelevant -0.0 + 0.0 corner) and keeps
          every rank's collective sequence lockstep-symmetric.

        Leftover (non-bucketable) params stay unsharded on every rank.
        """
        from ..ndarray import NDArray
        from ..parallel import quant as _quant
        from ..telemetry import metrics as _tmetrics
        buckets, leftover = plan
        kv = self._kvstore_obj
        optimizer = self._optimizer
        n_ctx = len(self._contexts)
        owners = _quant.shard_owners(len(buckets), shard["n"])
        by_ctx = shard["axis"] == "ctx"
        rank = shard["rank"]
        if by_ctx:
            _overlap.publish_pull_round(self._pull_scheduler)
            all_keys = [i for b in buckets for i in b.indices]
            overlap = self._pull_overlap_ok(all_keys, pull_stale)
        for k, b in enumerate(buckets):
            owner = owners[k]
            lrs = [0.0] * len(b.indices)
            wds = [0.0] * len(b.indices)
            # every (param, context) tick runs so the shared update
            # count advances exactly as in the unsharded loop; the
            # update itself always uses the CONTEXT-0 tick column — the
            # parity target is the unsharded step's context-0 replica
            # (the only well-defined one: Adam's shared per-index count
            # gives each unsharded context its own bias correction)
            for pos, i in enumerate(b.indices):
                for j in range(n_ctx):
                    lr, wd = opt.fused_lr_wd(optimizer, i, b.kind)
                    if j == 0:
                        lrs[pos] = lr
                        wds[pos] = wd
            if by_ctx or owner == rank:
                j = owner if by_ctx else 0
                weights = [self._params[i].list_data()[j]
                           for i in b.indices]
                grads = None if reduced.get(id(b)) is not None else \
                    [self._params[i].list_grad()[j] for i in b.indices]
                fg = reduced.get(id(b))
                if fg is not None and j > 0:
                    fg = NDArray(_engine.colocate(fg._read(),
                                                  weights[0]._read()),
                                 ctx=self._contexts[j])
                opt.fused_bucket_update(optimizer, self._updaters[j],
                                        b.indices, weights, grads,
                                        lrs, wds, flat_grad=fg)
            if by_ctx:
                kv.apply_reduced(
                    list(b.indices),
                    [self._params[i].list_data()[owner]
                     for i in b.indices])
                if overlap:
                    # THIS shard's weights go back on the wire before
                    # the next bucket updates (the duplex stream shape)
                    self._pull_scheduler.issue(
                        kv, list(b.indices),
                        [self._params[i].list_data() for i in b.indices],
                        label="zero_pull[%s:%dp:%dB]" % (
                            np.dtype(b.dtype).name, len(b.indices),
                            b.nbytes))
        if by_ctx and not overlap and all_keys:
            _overlap.serial_pull(
                kv, all_keys,
                [self._params[i].list_data() for i in all_keys])
        if not by_ctx and buckets:
            import jax.numpy as jnp
            wflats = []
            for k, b in enumerate(buckets):
                if owners[k] == rank:
                    vals = [self._params[i].list_data()[0]._read()
                            for i in b.indices]
                    wflats.append(NDArray(_engine.flatten_arrays(vals),
                                          ctx=self._contexts[0]))
                else:
                    ref = reduced[id(b)]
                    wflats.append(NDArray(jnp.zeros_like(ref._read()),
                                          ctx=self._contexts[0]))
            kv.reduce_many(wflats, label="zero_allgather")
            for k, b in enumerate(buckets):
                if owners[k] == rank:
                    continue    # owner keeps its own (identical) bytes
                shapes = [self._params[i].shape for i in b.indices]
                pieces = _engine.split_flat(wflats[k]._read(), shapes)
                for i, piece in zip(b.indices, pieces):
                    tgt = self._params[i].list_data()[0]
                    tgt._write(_engine.colocate(piece, tgt._read()))
        for i in leftover:
            param = self._params[i]
            for upd, arr, grad in zip(self._updaters, param.list_data(),
                                      param.list_grad()):
                upd(i, grad, arr)
        # per-rank optimizer-state footprint gauge: the acceptance gate
        # for "state bytes ~1/N" reads this
        _tmetrics.trainer_state_shard_bytes(self._state_shard_nbytes(),
                                            shard["n"])

    def save_states(self, fname):
        """ref: trainer.py:202 save_states."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._zero_spec() is not None:
            raise ValueError(
                "save_states cannot serialize a ZeRO-1 sharded trainer "
                "(GRAFT_SHARD_OPTIMIZER=1): each rank/context holds only "
                "its shard of the optimizer state.  Use "
                "trainer.checkpointer(...) — armor snapshots carry the "
                "shard layout and every shard's states.")
        if self._update_on_kvstore:
            if self._kvstore_obj._updater is None:
                # dist_async: optimizer state lives on the parameter
                # server (same limitation as the reference's PS mode)
                raise ValueError(
                    "Cannot save trainer states when the optimizer runs "
                    "on the parameter server (dist_async)")
            with open(fname, "wb") as fout:
                fout.write(self._kvstore_obj._updater.get_states(dump_optimizer=True))
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        """ref: trainer.py:218 load_states."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._zero_spec() is not None:
            raise ValueError(
                "load_states cannot restore into a ZeRO-1 sharded trainer "
                "(GRAFT_SHARD_OPTIMIZER=1): a flat states blob has no "
                "shard layout.  Use trainer.checkpointer(...).resume().")
        with open(fname, "rb") as f:
            states = f.read()
        if self._update_on_kvstore:
            if self._kvstore_obj._updater is None:
                raise ValueError(
                    "Cannot load trainer states when the optimizer runs "
                    "on the parameter server (dist_async)")
            self._kvstore_obj._updater.set_states(states)
            self._kvstore_obj._updater.optimizer.param_dict = {
                i: param for i, param in enumerate(self._params)}
            self._optimizer = self._kvstore_obj._updater.optimizer
        else:
            for updater in self._updaters:
                updater.set_states(states)
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = {i: param
                                      for i, param in enumerate(self._params)}

    # -- graftarmor atomic checkpoint/auto-resume ---------------------------
    def checkpointer(self, directory, every=None, keep=2, emergency=True):
        """A :class:`~incubator_mxnet_tpu.armor.checkpoint.Checkpointer`
        bound to this trainer: call ``ckpt.step_end(step)`` each step for
        periodic (GRAFT_CHECKPOINT_EVERY) atomic snapshots of params +
        optimizer state + step + RNG, ``ckpt.resume(data_iter)`` after a
        restart for last-valid-snapshot auto-resume, and get a
        best-effort emergency snapshot from the SIGTERM hook for free."""
        from ..armor.checkpoint import Checkpointer
        return Checkpointer(self, directory, every=every, keep=keep,
                            emergency=emergency)

    def save_checkpoint(self, path, step=0):
        """One atomic full-state snapshot (params + optimizer states +
        ``step`` + RNG) at ``path`` — in-flight async pushes/pulls are
        drained first so the snapshot is step-consistent.  See
        :mod:`~incubator_mxnet_tpu.armor.checkpoint`."""
        from ..armor import checkpoint as _ckpt
        return _ckpt.save_state(path, _ckpt.snapshot_trainer(self, step))

    def load_checkpoint(self, path):
        """Restore a :meth:`save_checkpoint` snapshot (validated against
        its embedded hash; raises ``CheckpointCorruptError`` on damage);
        returns the step the snapshot was taken at."""
        from ..armor import checkpoint as _ckpt
        state = _ckpt.load_state(path)
        _ckpt.restore_trainer(self, state)
        return int(state.get("step", 0))
