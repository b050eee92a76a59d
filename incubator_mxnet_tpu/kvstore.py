"""KVStore: key-value parameter synchronization.

TPU-native rebirth of src/kvstore/ + python/mxnet/kvstore.py:

* ``local`` / ``device`` — single-process multi-device reduce/broadcast
  (ref: kvstore_local.h:52, comm.h CommCPU/CommDevice).  On TPU the "device
  reduce" is an XLA all-reduce when arrays live on a mesh (parallel package);
  for per-context replica lists (Gluon Trainer, Module) it is a tree-sum in
  one fused XLA program.
* ``nccl`` maps to ``device`` — ICI collectives replace NCCL rings
  (ref: kvstore_nccl.h:62 → psum over ICI, SURVEY §2.4).
* ``dist_sync``/``dist_async`` — multi-host path built on jax.distributed
  (see parallel/dist.py); single-process fallback behaves like local with
  rank 0 of 1, so the same training scripts run anywhere.
* Gradient compression: 2-bit stochastic-threshold quantization with
  residual accumulation — same algebra as the reference
  (src/kvstore/gradient_compression.h:37-132), as an XLA kernel.
* ``set_optimizer`` runs the updater on the store (server-side optimizer,
  ref: kvstore_dist_server.h:145) — here the "server" is the store object.
"""
from __future__ import annotations

import os
import pickle
import sys
import time

import numpy as np
import jax.numpy as jnp

from .analysis import tsan as _tsan
from .armor import faults as _faults
from .base import MXNetError
from .ndarray import NDArray
from .ndarray import ndarray as _nd
from . import optimizer as opt
from .telemetry import blackbox as _blackbox
from .telemetry import metrics as _tmetrics
from .telemetry import tracing as _ttracing


def _nd_bytes(arr):
    """Logical payload size from metadata only (never forces a flush)."""
    n = 1
    for s in arr.shape:
        n *= int(s)
    return n * np.dtype(arr.dtype).itemsize


def _reduce_span(label):
    """The host side of a cross-worker reduce as a program span
    (``mx:reduce_many`` in a profiler trace): where program A ends and
    program B begins.  The collective bracket around it books the comm
    time; this names it in the trace."""
    return _ttracing.phase_span("reduce_many",
                                {"label": label} if label else None)


def _wire_bytes(nbytes, compressor):
    """Post-compression size of an ``nbytes`` payload on the wire: 2-bit
    quantization packs 16 elements per float32 word (ref:
    gradient_compression.h packing) — the single place this ratio lives,
    shared with the dist paths."""
    if compressor is None:
        return nbytes
    return max(nbytes // 16, 1)

__all__ = ["KVStore", "ReduceHandle", "PullHandle", "create",
           "create_kvstore"]


class _AsyncHandle(object):
    """Shared issue/wait split of the full-duplex wire (graftlap's
    reduces + graftduplex's pulls): the collective work is already
    dispatched at construction, ``values`` hold the in-flight results,
    and :meth:`wait` blocks until ready.  Between issue and wait the
    handle keeps an open flight-recorder bracket carrying the bucket
    label, so a collective that never lands is named by the watchdog
    and shows up in crash dumps as the stuck in-flight bucket.

    ``issued_at`` is the issue-time ``perf_counter()`` stamp — consumers
    derive the overlap ratio (fraction of in-flight wall time hidden
    under backward / the next forward) from it; :meth:`wait` records the
    split as ``blocked_s`` (host visibly waiting) vs ``inflight_s``
    (issue→wait-return, the upper bound on what was hidden)."""

    __slots__ = ("values", "label", "issued_at", "blocked_s", "inflight_s",
                 "_bracket", "_done", "__weakref__")

    def __init__(self, values, label=None, _bracket=None):
        self.values = list(values)
        self.label = label
        self.issued_at = time.perf_counter()
        self.blocked_s = 0.0
        self.inflight_s = 0.0
        self._bracket = _bracket
        self._done = False
        if _tsan._ACTIVE[0]:
            # grafttsan: the values are now in flight — issue is a
            # happens-before release; only wait() (the acquire) lets
            # another thread touch them (EH201 otherwise)
            _tsan.handle_issue(self)

    @property
    def done(self):
        return self._done

    def _close(self):
        if self._bracket is not None:
            bracket, self._bracket = self._bracket, None
            bracket.__exit__(None, None, None)

    def _begin_wait(self):
        """Flip the flight-recorder bracket from "deliberately left in
        flight" to "being waited on": re-stamp its clock and drop the
        ``async_pending`` flag so the watchdog starts aging it.  Before
        this, a long gap between issue and wait (a big backward, user
        code between backward and step, the next forward's early layers)
        is healthy overlap, not a hang — the watchdog must not trip on
        it."""
        entry = getattr(self._bracket, "entry", None)
        if entry is not None and entry.pop("async_pending", None):
            entry["since"] = time.time()

    def _materialize(self):
        """Hook for handles whose writes are deferred to wait time (the
        dist_async host parameter service: the pull RPC runs on a
        background thread and lands here)."""

    def wait(self):
        """Block until the in-flight values are ready; returns them.
        Idempotent — later calls are free.  ``blocked_s`` is the span
        blocked here and ``inflight_s`` the issue→wait-return span — an
        upper bound on the wire time the overlap hid (a handle whose
        wait queues behind earlier handles books their wait time too,
        the same convention as ``graft_trainer_overlap_ratio``)."""
        if not self._done:
            self._done = True
            if _tsan._ACTIVE[0]:
                # acquire the issue-time release: writes by the waiting
                # thread from here on (incl. _materialize's deferred
                # applies) are ordered after the issue.  The grafttsan
                # registry stays live until the blocking section below
                # returns — the wire owns the bytes until then, so a
                # third-thread write mid-wait is still an EH201 race
                _tsan.handle_acquire(self)
            self._begin_wait()
            t0 = time.perf_counter()
            try:
                # graftarmor chaos site: the wait side of every issued
                # collective (delay models a straggler; error a failed
                # wire) — injected BEFORE the block so the bracket
                # closes through the normal finally path
                _faults.fault_point("collective.wait", label=self.label,
                                    n_values=len(self.values))
                self._materialize()
                import jax
                jax.block_until_ready([v._read() for v in self.values])
            finally:
                t1 = time.perf_counter()
                self.blocked_s = t1 - t0
                self.inflight_s = t1 - self.issued_at
                self._close()
                _tsan.handle_settle(self)
        return self.values

    def abandon(self):
        """Drop the handle without consuming the result (the stale
        fallback).  Any dispatched work completes on its own; only the
        bracket closes and the values are never read."""
        self._done = True
        _tsan.handle_settle(self)   # no acquire edge: values unconsumed
        self._close()


class ReduceHandle(_AsyncHandle):
    """One asynchronously issued bucket reduce (graftlap) — see
    :class:`_AsyncHandle`; returned by :meth:`KVStore.reduce_many_async`
    with the reduce already on the wire (XLA dispatches asynchronously)."""

    __slots__ = ()


class PullHandle(_AsyncHandle):
    """One asynchronously issued weight pull/broadcast (graftduplex).

    Returned by :meth:`KVStore.pull_many_async`: the in-process stores
    rebind the out arrays at ISSUE time (each ``_write`` is an async XLA
    dispatch, so the bytes stream while the host moves on) and
    :meth:`wait` only blocks until they are ready; the dist_async host
    parameter service instead runs the pull RPC on a background thread
    and applies the fetched values at wait time, version-gated per out
    array (see ``DistKVStore.pull_many_async``).  Consumers (the
    ``overlap.PullScheduler``) wait at FIRST USE of any out array in the
    next forward, so updated weights ride under data loading and the
    early layers.  ``stale`` counts out arrays whose pulled value was
    dropped because the array was overwritten between issue and wait
    (the serial ordering — pull, then user write — is preserved)."""

    __slots__ = ("stale",)

    def __init__(self, values, label=None, _bracket=None):
        super().__init__(values, label=label, _bracket=_bracket)
        self.stale = 0


def _key_str(key):
    return str(key)


class _TwoBitCompressor(object):
    """2-bit gradient compression with residual (ref:
    src/kvstore/gradient_compression.h:37-132 — quantize_2bit kernel)."""

    def __init__(self, threshold=0.5):
        self.threshold = float(threshold)
        self.residuals = {}

    def compress(self, key, grad):
        t = self.threshold
        r = self.residuals.get(key)
        g = grad._read()
        if r is None:
            r = jnp.zeros_like(g)
        acc = r + g
        q = jnp.where(acc >= t, t, jnp.where(acc <= -t, -t, 0.0)).astype(g.dtype)
        self.residuals[key] = acc - q
        return NDArray(q, ctx=grad._ctx)


class KVStore(object):
    """Single-process store (ref: include/mxnet/kvstore.h:47-382 API)."""

    def __init__(self, type_="local"):
        self._type = type_
        self._store = {}           # key -> NDArray (the "server" copy)
        self._updater = None
        self._compressor = None
        self._quant_override = None  # set_gradient_compression("2bit")
        #                              routes the BUCKET wire onto the
        #                              block-scaled quant path (graftzero)
        self._str_keys = None

    # -- identity ----------------------------------------------------------
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        """ref: kvstore.h get_rank — single-process is rank 0."""
        from .parallel import dist
        return dist.rank()

    @property
    def num_workers(self):
        from .parallel import dist
        return dist.num_workers()

    # -- data path ---------------------------------------------------------
    def init(self, key, value):
        """ref: KVStore::Init — one-time value registration."""
        keys, values = self._normalize(key, value)
        for k, vlist in zip(keys, values):
            if k in self._store:
                raise ValueError("duplicate init of key %s" % k)
            self._store[k] = vlist[0].copy()
            if _tsan._ACTIVE[0]:
                # grafttsan tracked cell per store value (EH204): the
                # store-side updater writes (push/apply_reduced) and
                # pull reads run through NDArray._write/_read, so an
                # unsynchronized cross-thread updater-write vs pull-read
                # on the shared "server" copy is named with both stacks
                _tsan.track(self._store[k],
                            label="%s._store[%s]" % (self._type, k))

    def push(self, key, value, priority=0):
        """Aggregate value(s) into the store (ref: KVStore::Push).

        Multi-device lists are reduced (CommCPU/CommDevice::Reduce); with an
        updater set, the update is applied store-side (server semantics).
        """
        keys, values = self._normalize(key, value)
        entries = []            # ordered (key, reduced) — keys may repeat
        raw_bytes = wire_bytes = 0
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %s has not been initialized" % k)
            red = self._reduce(vlist)
            nb = _nd_bytes(red)
            raw_bytes += nb
            wire_bytes += _wire_bytes(nb, self._compressor)
            if self._compressor is not None:
                red = self._compressor.compress(k, red)
            entries.append((k, red))
        _tmetrics.kvstore_push(raw_bytes, wire_bytes)
        # one fused cross-worker collective for the whole push
        # (ref: big-array sharding amortization, kvstore_dist.h — here the
        # amortization is batching keys into a single allreduce); the
        # graftwatch bracket records it in the flight recorder and puts a
        # stalled allreduce in the watchdog's sights
        with _blackbox.collective("push", n_keys=len(entries),
                                  keys=[k for k, _ in entries[:4]],
                                  nbytes=raw_bytes, wire_bytes=wire_bytes):
            self._cross_worker_reduce_many([r for _, r in entries],
                                           compress=True)
        for k, red in entries:
            if self._updater is not None:
                self._updater(_int_key(k), red, self._store[k])
            else:
                # no updater: store holds the reduced value (ref:
                # kvstore_local.h PushImpl assigns local = merged)
                self._store[k]._write(red._read().astype(self._store[k].dtype))

    def _cross_worker_reduce_many(self, reds, heartbeat=True,
                                  compress=False):
        """Single-process store: nothing to do (dist overrides with one
        fused collective over all values; mutates them in place).
        ``heartbeat=False`` marks async issues: the dist path skips its
        piggybacked worker-heartbeat allreduce there, because reading the
        heartbeat result host-side would serialize against the bucket
        collective just dispatched — exactly the wait graftlap exists to
        avoid.  ``compress=True`` marks per-key PUSH traffic — the only
        wire the legacy 2-bit compressor may touch; bucket flats
        (``reduce_many*``) quantize through the block-scaled graftzero
        path instead and must never hit the per-key compressor's
        thresholding."""
        return reds

    def push_many(self, keys, values, priority=0):
        """Batched multi-key push: one call, one fused cross-worker
        collective for the whole key list (the batching contract of the
        reference's big-array sharding, kvstore_dist.h — here the
        amortization is key-batching).  ``push`` already accepts key
        lists; this spelling is the Trainer-facing API that guarantees
        the single-collective behavior."""
        return self.push(list(keys), list(values), priority=priority)

    def pull_many(self, keys, outs, priority=0):
        """Batched multi-key pull (companion of :meth:`push_many`)."""
        return self.pull(list(keys), outs, priority=priority)

    def reduce_many(self, values, label=None):
        """Reduce a list of dense NDArrays across workers IN PLACE with
        as few collectives as possible (one per dtype group on the dist
        wire) and return them.  This is the raw bucket wire the fused
        Trainer.step path rides: no per-key store bookkeeping, no
        server-side updater — just the allreduce.  Single-process stores
        have nothing to reduce, but the push/pull byte counters still
        observe the payload so fused vs per-param runs report comparable
        kvstore telemetry.  ``label`` names the flight-recorder bracket
        (the ZeRO weight allgather tags its own "zero_allgather")."""
        if not values:
            return values
        raw = sum(_nd_bytes(v) for v in values)
        _tmetrics.kvstore_push(raw, raw)
        _tmetrics.kvstore_pull(raw)
        extra = {"label": label} if label else {}
        with _blackbox.collective("reduce_many", n_keys=len(values),
                                  nbytes=raw, **extra):
            with _reduce_span(label):
                return self._cross_worker_reduce_many(list(values))

    def reduce_many_async(self, values, label=None):
        """Issue the cross-worker reduce of ``values`` WITHOUT waiting
        and return a :class:`ReduceHandle` (graftlap).  The collective is
        dispatched immediately — on the dist wire that is the in-graph
        XLA all-reduce, which executes asynchronously — so the caller
        (the Trainer's bucket scheduler, firing from a grad-ready hook
        mid-backward) keeps computing while the bytes move.  The handle's
        ``wait()`` is the only synchronization point; until then the
        reduce is an open flight-recorder bracket carrying ``label``, so
        the watchdog and crash dumps can name a stuck bucket.  Byte
        accounting and reduction algebra are EXACTLY ``reduce_many``'s
        (same per-value elementwise worker sum), only the wait moves."""
        values = list(values)
        if not values:
            return ReduceHandle(values, label=label)
        raw = sum(_nd_bytes(v) for v in values)
        _tmetrics.kvstore_push(raw, raw)
        _tmetrics.kvstore_pull(raw)
        bracket = _blackbox.collective(
            "reduce_many_async", n_keys=len(values), nbytes=raw,
            bucket=label)
        bracket.__enter__()
        entry = getattr(bracket, "entry", None)
        if entry is not None:
            # watchdog contract: an async bracket ages only from the
            # moment someone blocks on it (ReduceHandle._begin_wait) —
            # its open time before that measures healthy overlap
            entry["async_pending"] = True
        try:
            # graftarmor chaos site: the issue side of the async wire
            _faults.fault_point("collective.issue", label=label,
                                n_values=len(values))
            self._cross_worker_reduce_many(values, heartbeat=False)
        except BaseException:
            bracket.__exit__(*sys.exc_info())
            raise
        return ReduceHandle(values, label=label, _bracket=bracket)

    # -- graftzero: the block-scaled quantized bucket wire ------------------
    @staticmethod
    def _quant_signature(n_elems, mode, block):
        """The wire signature the lockstep auditor folds: mode, block
        size, total block count and quantized byte count.  A rank that
        disagrees on ``GRAFT_QUANT_REDUCE``/``GRAFT_QUANT_BLOCK`` folds
        a different digest and is NAMED by the heartbeat cross-check
        before the mispaired collective hangs the wire."""
        from .parallel import quant as _quant
        nb = sum(_quant.n_blocks(n, block) for n in n_elems)
        wire = sum(_quant.wire_nbytes(n, mode, block) for n in n_elems)
        return wire, "q:%s:b%d:nb%d" % (mode, int(block), nb)

    def reduce_quantized(self, payloads, n_elems, mode, block, label=None):
        """Reduce a batch of quantized bucket payloads across workers IN
        PLACE — the graftzero twin of :meth:`reduce_many`.  ``payloads``
        is ``[(codes, scales)]`` NDArray pairs (one per bucket, from
        ``parallel.quant.encode``), ``n_elems`` the per-bucket element
        counts.  Byte accounting: raw = the f32 bytes the wire replaces,
        wire = packed codes + scales (the compression-ratio gauge reads
        the bandwidth saving straight off these).  The whole batch is
        one flight-recorder bracket whose identity folds the quant
        signature (lockstep contract)."""
        if not payloads:
            return payloads
        raw = 4 * sum(int(n) for n in n_elems)
        wire, sig = self._quant_signature(n_elems, mode, block)
        _tmetrics.kvstore_push(raw, wire)
        _tmetrics.kvstore_pull(wire)
        extra = {"label": label} if label else {}
        with _blackbox.collective("reduce_quant", n_keys=len(payloads),
                                  nbytes=wire, keys=[sig], **extra):
            with _reduce_span(label):
                self._cross_worker_reduce_quantized(
                    list(payloads), list(n_elems), mode, block)
        return payloads

    def reduce_quantized_async(self, payloads, n_elems, mode, block,
                               label=None):
        """Issue the quantized payload reduce WITHOUT waiting — the
        graftzero twin of :meth:`reduce_many_async`, same bracket /
        watchdog / fault-point contract, quantized byte accounting."""
        payloads = list(payloads)
        flat_vals = [a for pair in payloads for a in pair]
        if not payloads:
            return ReduceHandle(flat_vals, label=label)
        raw = 4 * sum(int(n) for n in n_elems)
        wire, sig = self._quant_signature(n_elems, mode, block)
        _tmetrics.kvstore_push(raw, wire)
        _tmetrics.kvstore_pull(wire)
        bracket = _blackbox.collective(
            "reduce_quant_async", n_keys=len(payloads), nbytes=wire,
            keys=[sig], bucket=label)
        bracket.__enter__()
        entry = getattr(bracket, "entry", None)
        if entry is not None:
            entry["async_pending"] = True
        try:
            _faults.fault_point("collective.issue", label=label,
                                n_values=len(payloads))
            self._cross_worker_reduce_quantized(
                payloads, list(n_elems), mode, block, heartbeat=False)
        except BaseException:
            bracket.__exit__(*sys.exc_info())
            raise
        return ReduceHandle(flat_vals, label=label, _bracket=bracket)

    def _cross_worker_reduce_quantized(self, payloads, n_elems, mode,
                                       block, heartbeat=True):
        """Single-process store: the payload already IS the sum (one
        worker) — nothing moves.  The dist store overrides with the
        EQuARX-style quantized reduce-scatter + all-gather
        (``parallel.quant.reduce_payload_sum``), mutating the payload
        NDArrays in place."""
        return payloads

    def heartbeat(self):
        """Run one dist worker heartbeat outside a reduce batch.  The
        heartbeat normally piggybacks on ``_cross_worker_reduce_many``,
        but a fully-overlapped step (graftlap) reduces exclusively
        through ``reduce_many_async`` — which must skip it (the host-side
        read would serialize the async dispatch) — so the Trainer calls
        this once from the wait side instead, keeping the worker-skew
        histogram and the crash-dump last-seen table live.  Single-process
        stores have no peers: no-op (dist overrides)."""
        return None

    def apply_reduced(self, keys, values):
        """Apply ALREADY cross-worker-reduced gradients to the store —
        the update_on_kvstore leg of the full-duplex step (graftduplex).

        The duplex Trainer/Module path reduces a whole bucket as one
        concatenated buffer (``reduce_many`` / ``reduce_many_async``),
        splits it, and hands the per-key pieces here: each key gets the
        store-side updater tick (server semantics, exactly what ``push``
        would have run) or a plain assignment when no updater is set —
        but NO second reduction and no extra collective.  Key order is
        the caller's bucket order; per-key updates are independent, so
        the result is bit-identical to the per-key ``push`` path."""
        keys, vals = self._normalize(list(keys), list(values))
        for k, vlist in zip(keys, vals):
            if k not in self._store:
                raise MXNetError("key %s has not been initialized" % k)
            red = vlist[0]
            if self._updater is not None:
                self._updater(_int_key(k), red, self._store[k])
            else:
                from . import engine as _engine
                tgt = self._store[k]
                tgt._write(_engine.colocate(
                    red._read().astype(tgt.dtype), tgt._read()))

    def pull_many_async(self, keys, outs, priority=0, label=None):
        """Issue a batched multi-key pull WITHOUT waiting and return a
        :class:`PullHandle` (graftduplex — the pull-side mirror of
        :meth:`reduce_many_async`).

        For the in-process stores the broadcast writes happen NOW — each
        out array rebinds to the store value through an async XLA
        dispatch, so the bytes stream back while the host runs data
        loading and the next forward's early layers — and the handle's
        ``wait()`` (fired by the consumer's first-touch weight hooks, or
        at the latest at the start of the next step) is the only
        synchronization point.  Until then the pull is an open
        flight-recorder bracket carrying ``label``, so the watchdog and
        crash dumps can name a stuck in-flight pull bucket.  Byte
        accounting matches :meth:`pull` exactly; only the wait moves.
        The dist_async parameter service overrides this with a
        background-thread RPC + version-gated wait-time writes."""
        keys, outs_n = self._normalize(list(keys), outs)
        flat_outs = [o for olist in outs_n for o in olist]
        nbytes = sum(_nd_bytes(o) for o in flat_outs)
        bracket = _blackbox.collective(
            "pull_many_async", n_keys=len(keys), keys=keys[:4],
            nbytes=nbytes, bucket=label)
        bracket.__enter__()
        entry = getattr(bracket, "entry", None)
        if entry is not None:
            # watchdog contract (same as reduce_many_async): an async
            # bracket ages only once someone blocks on it
            entry["async_pending"] = True
        try:
            from . import engine as _engine
            for k, olist in zip(keys, outs_n):
                if k not in self._store:
                    raise MXNetError("key %s has not been initialized" % k)
                val = self._store[k]._read()
                src_dtype = np.dtype(val.dtype)
                for o in olist:
                    v = val if np.dtype(o.dtype) == src_dtype \
                        else val.astype(o.dtype)
                    o._write(_engine.colocate(v, o._read()))
        except BaseException:
            bracket.__exit__(*sys.exc_info())
            raise
        _tmetrics.kvstore_pull(nbytes)
        return PullHandle(flat_outs, label=label, _bracket=bracket)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Broadcast store value into out list (ref: KVStore::Pull)."""
        assert out is not None
        keys, outs = self._normalize(key, out)
        # one metadata pass sizes the payload for both the flight
        # recorder and the byte counter (every write below either lands
        # or raises, so the up-front sum IS the pulled total)
        nbytes = sum(_nd_bytes(o) for olist in outs for o in olist)
        from . import engine as _engine
        with _blackbox.collective("pull", n_keys=len(keys), keys=keys[:4],
                                  nbytes=nbytes):
            for k, olist in zip(keys, outs):
                if k not in self._store:
                    raise MXNetError("key %s has not been initialized" % k)
                # hoist the store read out of the replica loop, and skip
                # the astype copy when dtypes already match — the common
                # Trainer pull (grad -> grad, same dtype) is a pure rebind.
                # colocate: a multi-context replica list commits each out
                # to its own device; the broadcast must land there
                val = self._store[k]._read()
                src_dtype = np.dtype(val.dtype)
                for o in olist:
                    v = val if np.dtype(o.dtype) == src_dtype \
                        else val.astype(o.dtype)
                    o._write(_engine.colocate(v, o._read()))
        _tmetrics.kvstore_pull(nbytes)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only selected rows (ref: KVStore::PullRowSparse,
        kvstore_local.h PullRowSparseImpl)."""
        assert out is not None and row_ids is not None
        keys, outs = self._normalize(key, out)
        if isinstance(row_ids, NDArray):
            row_ids = [row_ids] * len(outs[0])
        from .ndarray.sparse import RowSparseNDArray
        for k, olist in zip(keys, outs):
            src = self._store[k]._read()
            for o, rid in zip(olist, row_ids):
                # dedup + sort row ids (PullRowSparseImpl contract)
                idx = jnp.asarray(np.unique(np.asarray(rid._read()))
                                  .astype(np.int32))
                rows = jnp.take(src, idx, axis=0)
                if isinstance(o, RowSparseNDArray):
                    # true row-sparse pull: only the requested rows
                    # materialize — O(|row_ids|) memory like the
                    # reference's PullRowSparseImpl (kvstore_local.h)
                    o.data = NDArray(rows.astype(o.data.dtype))
                    o.indices = NDArray(idx.astype(o.indices.dtype))
                else:
                    dense = jnp.zeros(o.shape, o._read().dtype)
                    dense = dense.at[idx].set(rows.astype(o._read().dtype))
                    o._write(dense)

    # -- reductions --------------------------------------------------------
    @staticmethod
    def _reduce(vlist):
        from .ndarray.sparse import BaseSparseNDArray, add_n
        if len(vlist) == 1:
            return vlist[0]
        if any(isinstance(v, BaseSparseNDArray) for v in vlist):
            # sparse-aware tree sum (ref: comm.h CommCPU ReduceRowSparse)
            return add_n(*vlist)
        from . import engine as _engine
        acc = vlist[0]._read()
        for v in vlist[1:]:
            # replicas committed to distinct devices (multi-ctx lists)
            # must be moved before the tree-sum — transfers preserve bits
            acc = acc + _engine.colocate(v._read(), acc)
        return NDArray(acc, ctx=vlist[0]._ctx)

    @staticmethod
    def _normalize(key, value):
        single = not isinstance(key, (list, tuple))
        keys = [key] if single else list(key)
        if single:
            values = [value if isinstance(value, (list, tuple)) else [value]]
        else:
            values = [v if isinstance(v, (list, tuple)) else [v] for v in value]
        return [_key_str(k) for k in keys], values

    # -- optimizer / updater ----------------------------------------------
    def set_updater(self, updater):
        """ref: kvstore.py _set_updater / KVStoreSetUpdater."""
        self._updater = updater

    def set_optimizer(self, optimizer):
        """ref: kvstore.py set_optimizer — the local store shares the live
        optimizer object (so Trainer's per-step rescale_grad / lr mutations
        apply); only the dist path pickles it to servers
        (kvstore_dist_server.h kController command channel)."""
        self._updater = opt.get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """ref: kvstore.py set_gradient_compression (2bit only, like ref).

        DEPRECATED for the Trainer step: the threshold compressor only
        ever rode the per-key serial wire (``push``), and forcing the
        step onto that wire defeated the bucket schedulers.  Calling
        this now routes ``Trainer.step``'s BUCKET reduces onto the
        block-scaled quantized wire (graftzero, ``GRAFT_QUANT_REDUCE``
        semantics with mode ``2bit``) while the per-key ``push`` API
        keeps the exact legacy threshold algebra.  ``GRAFT_QUANT_REDUCE=0``
        is the bit-identical escape hatch: it disables the bucket-wire
        quantization entirely (the env var always wins)."""
        ctype = compression_params.get("type", "2bit")
        if ctype != "2bit":
            raise ValueError("Unsupported type of gradient compression: %s" % ctype)
        import warnings
        warnings.warn(
            "set_gradient_compression is deprecated for the bucketed "
            "Trainer step: bucket reduces now ride the block-scaled "
            "quantized wire (GRAFT_QUANT_REDUCE=2bit semantics); the "
            "per-key push API keeps the legacy threshold algebra. Set "
            "GRAFT_QUANT_REDUCE=0 for the bit-identical escape hatch.",
            DeprecationWarning, stacklevel=2)
        self._compressor = _TwoBitCompressor(
            compression_params.get("threshold", 0.5))
        self._quant_override = "2bit"

    # -- distributed-only API (graceful single-process behavior) -----------
    def barrier(self):
        from .parallel import dist
        dist.barrier()

    def quiesce(self, timeout=None):
        """Drain every in-flight async operation this store owns
        (graftelastic: the mandatory prelude to a membership
        re-partition — key ranges must not move under live traffic).
        The local store issues nothing asynchronous on its own behalf,
        so the base is a no-op; ``DistKVStore`` overrides with the real
        drain and a typed ``QuiesceTimeoutError``."""
        return 0

    def send_command_to_servers(self, head, body):
        return

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for distributed training"
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for distributed training"
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())


def _int_key(k):
    try:
        return int(k)
    except ValueError:
        return k


def create(name="local"):
    """Factory (ref: kvstore.cc:40-77 KVStore::Create by type string)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device", "nccl"):
        return KVStore("device" if name in ("device", "nccl") else "local")
    if name in ("dist_sync", "dist_async", "dist_device_sync"):
        from .parallel import dist
        return dist.DistKVStore(name)
    raise ValueError("Unknown KVStore type %s" % name)


def create_kvstore(kvstore, num_device, arg_params):
    """Resolve a kvstore spec into (store, update_on_kvstore)
    (ref: python/mxnet/model.py _create_kvstore, including the
    MXNET_UPDATE_ON_KVSTORE env override — 0 keeps the update local,
    which is also the switch that routes Module onto the bucketed
    fused/overlapped reduce path, graftduplex)."""
    try:
        update_on_kvstore = bool(int(
            os.environ.get("MXNET_UPDATE_ON_KVSTORE", "1")))
    except ValueError:
        update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            kv = create(kvstore)
            if kvstore == "local":
                max_size = max(np.prod(param.shape) for param in arg_params.values())
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return kv, update_on_kvstore
