"""Multi-host distributed bootstrap + the dist_* KVStore façade.

TPU-native replacement for ps-lite (src/kvstore/kvstore_dist.h) and the
dmlc tracker (tools/launch.py): process coordination is
``jax.distributed.initialize`` (the jax coordination service plays the
scheduler/Postoffice role), data-parallel gradient sync is an XLA
all-reduce over ICI/DCN instead of ZPush/ZPull to servers.

The KVStore *API* survives intact (SURVEY §5.8): init/push/pull/
row_sparse_pull/barrier/rank/num_workers/set_optimizer — scripts written
against dist_sync run unchanged; the transport underneath is collectives.
`dist_async`'s push-immediately semantics are outside XLA's synchronous
model, so — exactly as the reference keeps them outside the device — they
live on a HOST parameter service (parallel/ps.py): rank 0 runs the server
thread, every push is applied the moment it arrives with the server-side
optimizer, pulls return current (stale-tolerant) weights.
"""
from __future__ import annotations

import logging
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import lockstep as _lockstep
from .. import elastic as _elastic
from ..kvstore import KVStore, PullHandle
from ..telemetry import blackbox as _blackbox
from ..telemetry import metrics as _tmetrics
from . import compression

__all__ = ["init_process", "rank", "num_workers", "barrier", "DistKVStore"]

_initialized = False


def init_process(coordinator_address=None, num_processes=None, process_id=None):
    """Initialize multi-host jax.distributed (replaces DMLC_ROLE/tracker env
    bootstrap, tools/launch.py:29). Reads standard env vars if args omitted."""
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MX_COORDINATOR")
    num_processes = num_processes or int(os.environ.get("MX_NUM_PROCESSES", "0")) or None
    process_id = process_id if process_id is not None else (
        int(os.environ["MX_PROCESS_ID"]) if "MX_PROCESS_ID" in os.environ else None)
    if coordinator_address:
        jax.distributed.initialize(coordinator_address, num_processes, process_id)
    _initialized = True


def rank():
    """Worker rank (ref: KVStore::get_rank / MXKVStoreGetRank)."""
    return jax.process_index()


def num_workers():
    """ref: KVStore::get_group_size."""
    return jax.process_count()


def barrier():
    """Global barrier (ref: KVStore::Barrier → ps::Postoffice::Barrier).

    Implemented as a tiny psum across all processes — every host must
    arrive before XLA returns."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("mx_kvstore_barrier")


def num_dead_nodes():
    """ref: MXKVStoreGetNumDeadNode — jax coordination service terminates
    the job on member failure, so a live process always observes 0."""
    return 0


# -- in-graph cross-worker reduction ---------------------------------------
_worker_mesh_cache = None
_sum_jit_cache = None


def worker_mesh():
    """1-D mesh with ONE device per process — the collective topology of
    the kvstore wire (the role ps-lite's server group played,
    kvstore_dist.h).  Summing over its "worker" axis lowers to an XLA
    all-reduce that rides DCN between hosts (ICI within a slice)."""
    global _worker_mesh_cache
    if _worker_mesh_cache is None:
        devs, seen = [], set()
        for d in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
            if d.process_index not in seen:
                seen.add(d.process_index)
                devs.append(d)
        _worker_mesh_cache = Mesh(np.array(devs), ("worker",))
    return _worker_mesh_cache


def _global_sum(flat):
    """Sum one flat buffer across all processes IN-GRAPH: each process
    contributes its shard of a (num_workers, n) global array laid out over
    the worker mesh; a jitted sum(axis=0) with replicated output lowers to
    one XLA all-reduce.  Unlike `multihost_utils.process_allgather` (the
    round-2 wire), the reduction executes inside XLA — no host round-trip
    of the gathered buffer, no Python-side sum, and the payload on the wire
    is the reduce, not an N× gather.  ref: kvstore_dist.h ZPush/ZPull pair
    collapsed into a single all-reduce."""
    global _sum_jit_cache
    mesh = worker_mesh()
    if _sum_jit_cache is None:
        _sum_jit_cache = jax.jit(
            lambda a: a.sum(axis=0),
            out_shardings=NamedSharding(mesh, P()))
    me = jax.process_index()
    my_dev = next(d for d in mesh.devices.flat if d.process_index == me)
    piece = jax.device_put(flat[None], my_dev)
    garr = jax.make_array_from_single_device_arrays(
        (num_workers(),) + tuple(flat.shape),
        NamedSharding(mesh, P("worker")), [piece])
    out = _sum_jit_cache(garr)
    return jnp.asarray(out.addressable_data(0))


_ps_counter = [0]   # SPMD-identical creation index → rendezvous key


class _PSPullHandle(PullHandle):
    """Pull handle whose writes are deferred to wait time: the host
    parameter-service RPC runs on a background thread (issuing it inline
    would block — exactly the wait graftduplex exists to move), and the
    fetched weights are applied at ``wait()``, version-gated per out
    array so a weight the user overwrote between issue and wait keeps
    the user's value (the serial pull-then-write ordering)."""

    __slots__ = ("_fn",)

    def __init__(self, values, fn, label=None, _bracket=None):
        super().__init__(values, label=label, _bracket=_bracket)
        self._fn = fn

    def _materialize(self):
        fn, self._fn = self._fn, None
        if fn is not None:
            self.stale = fn()


class DistKVStore(KVStore):
    """dist_sync / dist_device_sync / dist_async over jax.distributed.

    ``dist_sync``: the wire is an in-graph XLA all-reduce (below).
    ``dist_async``: true parameter-server semantics on a HOST service —
    rank 0 runs a ParameterServer thread applying every push immediately
    with the server-side optimizer; pulls return current (possibly
    stale) weights.  See parallel/ps.py; matches
    kvstore_dist_server.h:306-314 async handling."""

    def __init__(self, type_):
        super().__init__(type_)
        init_process()
        _blackbox.set_rank(rank())      # stamp dumps with this worker
        from ..armor import faults as _faults
        _faults.set_rank(rank())        # rank= clause filters (graftarmor)
        self._hb_step = 0               # dist heartbeat step counter
        self._ps_server = None
        self._ps = None
        self._pull_pool = None          # lazy 1-thread PS client executor
        #                                 (async pulls AND duplex pushes:
        #                                 one worker = FIFO = wire order)
        self._push_futs = []            # in-flight async push futures
        self._push_issue_idx = 0        # submission order, asserted on
        #                                 the wire by lockstep.note_order
        if type_ == "dist_async":
            from . import ps
            idx = _ps_counter[0]
            _ps_counter[0] += 1
            n_srv = int(os.environ.get("MXTPU_PS_NUM_SERVERS", "1"))
            if num_workers() <= 1:
                self._ps_server = ps.ServerGroup(n_srv)
                self._ps = ps.GroupClient(self._ps_server.address, rank=0)
            elif rank() == 0:
                self._ps_server = ps.ServerGroup(n_srv)
                ps.publish_address(self._ps_server.address, idx)
                self._ps = ps.GroupClient(self._ps_server.address, rank=0)
            else:
                self._ps = ps.GroupClient(ps.lookup_address(idx),
                                          rank=rank())
        if self._ps is not None:
            # hand the watchdog a dead-rank source so a trip on a stuck
            # ps_* bracket can NAME the dead peers (satellite: the trip
            # dump carries the dead-rank table).  Weakref: the provider
            # must not keep a closed store alive.
            import weakref
            from ..telemetry import watchdog as _watchdog
            ref = weakref.ref(self)
            def _dead_ranks():
                store = ref()
                if store is None or store._ps is None:
                    return []
                return list(store._ps.dead_nodes(window=5.0))
            _watchdog.register_dead_nodes_provider(_dead_ranks)

    # -- dist_async: the host parameter service -----------------------------
    def _async_np(self, nd_value):
        # native dtype on the wire: integer keys must sum exactly, same
        # contract the sync path keeps (dtype-grouped allreduce below)
        import numpy as _np
        return _np.asarray(nd_value._read())

    def init(self, key, value):
        if self._ps is None:
            return DistKVStore._sync_init(self, key, value)
        super(DistKVStore, self).init(key, value)   # local shapes/dtypes
        keys, values = self._normalize(key, value)
        self._ps.init({str(k): self._async_np(v[0])
                       for k, v in zip(keys, values)})
        barrier()   # every rank sees initialized keys before first push

    def push(self, key, value, priority=0):
        if self._ps is None:
            return super().push(key, value, priority)
        from ..ndarray.sparse import BaseSparseNDArray
        from ..kvstore import _nd_bytes, _wire_bytes
        from ..telemetry import metrics as _tmetrics
        keys, values = self._normalize(key, value)
        batch = {}
        raw_bytes = wire_bytes = 0
        for k, vlist in zip(keys, values):
            if k not in self._store:
                from ..base import MXNetError
                raise MXNetError("key %s has not been initialized" % k)
            red = self._reduce(vlist)
            if isinstance(red, BaseSparseNDArray):
                red = red.tostype("default")
            nb = _nd_bytes(red)
            raw_bytes += nb
            wire_bytes += _wire_bytes(nb, self._compressor)
            if self._compressor is not None:
                red = self._compressor.compress(k, red)
            batch[str(k)] = self._async_np(red)
        _tmetrics.kvstore_push(raw_bytes, wire_bytes)
        if not self._duplex_push_enabled():
            with _blackbox.collective("ps_push", n_keys=len(batch),
                                      nbytes=raw_bytes):
                self._ps.push(batch)    # applied immediately server-side
            return
        # graftduplex push side (ROADMAP, PR 9 follow-up): the reduce/
        # compress above ran on the caller's thread (deterministic
        # content), and the RPCs now ride the SAME 1-thread background
        # client as the async pulls — per ~bucket-size group, so early
        # groups stream to the server while the caller returns to its
        # backward.  One executor worker = FIFO = submission order on
        # the wire, which lockstep.note_order asserts per executed RPC;
        # sync pulls/barriers drain the queue first (read-your-writes).
        from .. import overlap as _overlap
        items = list(batch.items())
        sizes = [v.nbytes for _k, v in items]
        pool = self._pull_executor()
        for group in _overlap.plan_pull_groups(
                list(range(len(items))), sizes, self._push_group_bytes()):
            chunk = {items[i][0]: items[i][1] for i in group}
            nb = sum(sizes[i] for i in group)
            idx = self._push_issue_idx
            self._push_issue_idx += 1
            self._push_futs.append(
                pool.submit(self._ps_push_task, chunk, idx, nb))
        self._reap_pushes()

    _duplex_push_override = None    # tests/benches force on/off

    def _duplex_push_enabled(self):
        """GRAFT_DUPLEX_PUSH (default on): batch dist_async gradient
        pushes onto the background PS client instead of blocking the
        step on the RPC.  Same-worker read-your-writes is preserved
        (sync pulls and barriers drain the queue; async pulls ride the
        same FIFO executor); cross-worker ordering was never promised —
        async SGD staleness is the semantics."""
        if self._ps is None:
            return False
        if self._duplex_push_override is not None:
            return bool(self._duplex_push_override)
        return os.environ.get("GRAFT_DUPLEX_PUSH", "1").strip().lower() \
            not in ("0", "false", "no", "off")

    def _push_group_bytes(self):
        from .. import overlap as _overlap
        try:
            return int(os.environ.get(
                "GRAFT_BUCKET_BYTES", str(_overlap.DEFAULT_BUCKET_BYTES)))
        except ValueError:
            return _overlap.DEFAULT_BUCKET_BYTES

    def _ps_push_task(self, chunk, idx, nbytes):
        """One push group's RPC, on the background client thread.  The
        bracket opens HERE (enter/exit must share a thread), so an RPC
        stuck on a dead server is a named in-flight collective for the
        watchdog; note_order records an issue-order violation if the
        executor ever reorders submissions."""
        _lockstep.note_order("ps_push_async", idx)
        with _blackbox.collective("ps_push_async", n_keys=len(chunk),
                                  nbytes=nbytes):
            self._ps.push(chunk)

    def _reap_pushes(self):
        """Drop completed push futures; surface the first failure at the
        next push instead of never.  Done futures are pruned BEFORE the
        raise, so one failed RPC cannot re-raise its stale exception on
        every later call forever.  A failure surfacing here is already
        POST-RETRY: the PSClient wire retried/reconnected through its
        GRAFT_RPC_RETRIES budget before letting the push task fail, so
        what lands is a PSUnavailableError, not a transient hiccup."""
        pending, failed = [], None
        for f in self._push_futs:
            if not f.done():
                pending.append(f)
                continue
            exc = f.exception()
            if exc is not None and failed is None:
                failed = exc
        self._push_futs = pending
        if failed is not None:
            raise failed

    def _drain_pushes(self):
        """Wait every queued async push (the read-your-writes point:
        sync pulls, barriers, shutdown).  EVERY future is waited even
        when one fails — a caller catching the error must still hold
        read-your-writes for its next sync pull."""
        futs, self._push_futs = self._push_futs, []
        failed = None
        for f in futs:
            try:
                f.result()
            except BaseException as exc:
                if failed is None:
                    failed = exc
        if failed is not None:
            raise failed

    def barrier(self):
        self._drain_pushes()    # a barrier promises peers see our pushes
        super().barrier()

    @staticmethod
    def _quiesce_timeout():
        """GRAFT_QUIESCE_TIMEOUT in seconds (default 30): the drain
        budget for ``quiesce`` — long enough for a queued push burst,
        short enough that a dead peer surfaces as a typed error rather
        than a hung membership fence."""
        try:
            t = float(os.environ.get("GRAFT_QUIESCE_TIMEOUT", "30"))
        except ValueError:
            return 30.0
        return t if t > 0 else 30.0

    def quiesce(self, timeout=None):
        """Drain every in-flight async operation this store owns —
        queued duplex pushes AND anything riding the background pull
        thread — within a deadline (graftelastic: the mandatory prelude
        to a membership re-partition; key ranges must not move under
        live traffic).  Unlike ``_drain_pushes`` (unbounded, the
        read-your-writes point) this wait is BOUNDED: work stuck on a
        dead peer raises :class:`~..armor.errors.QuiesceTimeoutError`
        naming the undrained count instead of hanging the fence, and
        the undrained futures stay owned (``close``/``barrier`` still
        wait them).  A push that FAILED still counts as drained — the
        wire is quiet either way — but the first failure re-raises
        after the drain so the caller sees it.  Returns the number of
        operations drained."""
        from concurrent.futures import wait as _fwait
        from ..armor.errors import QuiesceTimeoutError
        budget = self._quiesce_timeout() if timeout is None \
            else float(timeout)
        t0 = time.monotonic()
        futs, self._push_futs = self._push_futs, []
        if self._pull_pool is not None:
            # a sentinel rides the 1-thread FIFO pull executor: when it
            # runs, every pull submitted before it has finished
            futs = futs + [self._pull_pool.submit(lambda: None)]
        done, not_done = _fwait(futs, timeout=budget)
        if not_done:
            self._push_futs = list(not_done) + self._push_futs
            raise QuiesceTimeoutError(
                "kvstore.quiesce", time.monotonic() - t0, budget,
                pending=len(not_done))
        failed = None
        for f in done:
            exc = f.exception()
            if exc is not None and failed is None:
                failed = exc
        if failed is not None:
            raise failed
        return len(done)

    def close(self):
        """Shut down the background PS client (draining queued pushes),
        the client sockets, and — on the hosting rank — the parameter-
        server threads.  Without this the 1-thread executor and the
        server's accept/handler threads outlive the store (GL204) and
        show up as phantom in-flight work in crash dumps."""
        try:
            self._drain_pushes()
        except Exception:
            pass                # teardown: the job is over either way
        if self._pull_pool is not None:
            self._pull_pool.shutdown(wait=True)
            self._pull_pool = None
        if self._ps is not None:
            try:
                self._ps.close()
            except Exception:
                pass
            self._ps = None
        if self._ps_server is not None:
            try:
                self._ps_server.shutdown()
            except Exception:
                pass
            self._ps_server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass                # interpreter teardown

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if self._ps is None:
            return super().pull(key, out=out, priority=priority,
                                ignore_sparse=ignore_sparse)
        self._drain_pushes()    # a sync pull reads our own pushes
        import jax.numpy as _jnp
        from ..kvstore import _nd_bytes
        from ..telemetry import metrics as _tmetrics
        assert out is not None
        keys, outs = self._normalize(key, out)
        with _blackbox.collective("ps_pull", n_keys=len(keys)):
            fetched = self._ps.pull([str(k) for k in keys])
        pulled = 0
        for k, olist in zip(keys, outs):
            v = fetched[str(k)]
            for o in olist:
                o._write(_jnp.asarray(v).astype(o.dtype))
                pulled += _nd_bytes(o)
            # refresh the local mirror so row_sparse_pull etc. see it
            self._store[k]._write(_jnp.asarray(v).astype(
                self._store[k].dtype))
        _tmetrics.kvstore_pull(pulled)

    def _pull_executor(self):
        """One background thread for async PS pulls: a single worker
        serializes the GroupClient (it is not thread-safe) and keeps the
        issue order deterministic."""
        if self._pull_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pull_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="graft-ps-pull")
        return self._pull_pool

    def pull_many_async(self, keys, outs, priority=0, label=None):
        """Async weight pull from the host parameter service: the RPC is
        submitted to a background thread at issue time and the fetched
        values are applied at ``wait()`` — version-gated per out array,
        so an array the user overwrote between issue and wait keeps the
        user's bytes (serial pull-then-write ordering) and counts toward
        the handle's ``stale`` total (the consumer's abandon-and-fallback
        signal).  The sync wire (no PS) takes the base issue-time-write
        path."""
        if self._ps is None:
            return super().pull_many_async(keys, outs, priority=priority,
                                           label=label)
        from ..kvstore import _nd_bytes
        keys_n, outs_n = self._normalize(list(keys), outs)
        flat_outs = [o for olist in outs_n for o in olist]
        nbytes = sum(_nd_bytes(o) for o in flat_outs)
        bracket = _blackbox.collective(
            "pull_many_async", n_keys=len(keys_n), keys=keys_n[:4],
            nbytes=nbytes, bucket=label)
        bracket.__enter__()
        entry = getattr(bracket, "entry", None)
        if entry is not None:
            entry["async_pending"] = True
        try:
            fut = self._pull_executor().submit(
                self._ps.pull, [str(k) for k in keys_n])
        except BaseException:
            import sys as _sys
            bracket.__exit__(*_sys.exc_info())
            raise
        versions = [[o._version for o in olist] for olist in outs_n]
        store = self._store

        def _apply():
            import jax.numpy as _jnp
            fetched = fut.result()
            stale = 0
            for k, olist, vers in zip(keys_n, outs_n, versions):
                v = fetched[str(k)]
                # refresh the local mirror (the sync pull does too)
                store[k]._write(_jnp.asarray(v).astype(store[k].dtype))
                for o, ver in zip(olist, vers):
                    if o._version != ver:
                        stale += 1      # overwritten since issue: the
                        continue        # user's write wins
                    o._write(_jnp.asarray(v).astype(o.dtype))
            return stale

        _tmetrics.kvstore_pull(nbytes)
        return _PSPullHandle(flat_outs, _apply, label=label,
                             _bracket=bracket)

    def set_optimizer(self, optimizer):
        if self._ps is None:
            return DistKVStore._sync_set_optimizer(self, optimizer)
        self._drain_pushes()    # updater flip applies to LATER pushes
        self._ps.set_optimizer(optimizer)   # pickled to the server role

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        if self._ps is None:
            return super().row_sparse_pull(key, out=out, priority=priority,
                                           row_ids=row_ids)
        self._drain_pushes()    # row reads must see our own pushes
        import jax.numpy as _jnp
        keys, _ = self._normalize(key, out)
        if row_ids is not None:
            # ship ONLY the requested rows (kvstore_dist_server.h:223):
            # scatter them into the local mirror, then let the base
            # implementation row-select from it
            id_list = row_ids if isinstance(row_ids, (list, tuple)) \
                else [row_ids] * len(keys)
            for k, ids_nd in zip(keys, id_list):
                ids = np.asarray(ids_nd._read()
                                 if hasattr(ids_nd, "_read")
                                 else ids_nd).astype(np.int64).ravel()
                if not len(ids):
                    continue        # nothing requested: no wire traffic
                rows = self._ps.pull_rows({str(k): ids})[str(k)]
                # scatter ON DEVICE: no full-table host round-trip
                cur = self._store[k]._read()
                self._store[k]._write(cur.at[_jnp.asarray(ids)].set(
                    _jnp.asarray(rows, cur.dtype)))
        else:
            # full refresh: the mirror otherwise holds init-time values
            # forever on the async path
            fetched = self._ps.pull([str(k) for k in keys])
            for k in keys:
                self._store[k]._write(_jnp.asarray(fetched[str(k)]).astype(
                    self._store[k].dtype))
        return super().row_sparse_pull(key, out=out, priority=priority,
                                       row_ids=row_ids)

    def num_dead_nodes(self, node_id=0, timeout_sec=5):
        """Workers whose heartbeats stopped (ref: MXKVStoreGetNumDeadNode,
        kvstore_dist.h:109-115).  Only the async parameter service keeps
        heartbeats; on the sync wire the jax coordination service
        terminates the job on member failure, so a live process always
        observes 0.  Either way the answer is SURFACED, not just
        returned: the ``graft_dist_dead_nodes`` gauge tracks it and a
        nonzero count lands in the flight recorder (graftwatch — a
        silent return left post-mortems blind to the lost worker)."""
        if self._ps is None:
            dead = []
        else:
            dead = list(self._ps.dead_nodes(window=float(timeout_sec)))
        _tmetrics.dist_dead_nodes(len(dead))
        if dead:
            _blackbox.record("dead_nodes", dead=dead,
                             window_s=float(timeout_sec), rank=rank())
        return len(dead)

    def _sync_init(self, key, value):
        """Rank 0's value defines the key globally (ref: kvstore_dist.h
        Init — the first pushed value wins server-side), so workers that
        initialized with different seeds still start in sync."""
        super().init(key, value)
        if num_workers() > 1:
            from jax.experimental import multihost_utils
            keys, _ = self._normalize(key, value)
            vals = {k: np.asarray(self._store[k]._read()) for k in keys}
            vals = multihost_utils.broadcast_one_to_all(vals)
            for k in keys:
                self._store[k]._write(jnp.asarray(vals[k]).astype(
                    self._store[k].dtype))

    def _cross_worker_reduce_sparse(self, red):
        """Union/sum a sparse value across workers.  Row-sparse ships only
        (row_ids, rows) — padded to the global max row count so every
        process issues identically-shaped collectives (the fixed-order
        contract that keeps ranks in lockstep) — then the union rows are
        segment-summed and written back via .data/.indices (sparse arrays
        reject dense in-place writes).  ref: kvstore_dist.h PushRowSparse /
        comm.h ReduceRowSparse."""
        from jax.experimental import multihost_utils
        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray
        if isinstance(red, RowSparseNDArray):
            idx = np.asarray(red.indices._read()).astype(np.int64)
            dat = np.asarray(red.data._read())
            counts = np.asarray(multihost_utils.process_allgather(
                jnp.asarray([idx.shape[0]], jnp.int32)))
            maxn = max(int(counts.max()), 1)
            pad = maxn - idx.shape[0]
            idx_p = np.concatenate([idx, np.full((pad,), -1, np.int64)])
            dat_p = np.concatenate(
                [dat, np.zeros((pad,) + dat.shape[1:], dat.dtype)])
            g = multihost_utils.process_allgather(
                {"i": jnp.asarray(idx_p), "d": jnp.asarray(dat_p)})
            all_i = np.asarray(g["i"]).reshape(-1)
            all_d = np.asarray(g["d"]).reshape((-1,) + dat.shape[1:])
            keep = all_i >= 0
            all_i, all_d = all_i[keep], all_d[keep]
            uniq, inv = np.unique(all_i, return_inverse=True)
            summed = np.zeros((len(uniq),) + dat.shape[1:], dat.dtype)
            np.add.at(summed, inv, all_d)
            red.data = NDArray(jnp.asarray(summed))
            red.indices = NDArray(jnp.asarray(uniq).astype(
                np.asarray(red.indices._read()).dtype))
            return red
        # CSR (and any future stype): reduce dense, rebuild the compressed
        # form host-side — CSR pushes are rare enough that clarity wins.
        # GUARD: the densify materializes rows*cols on every worker, so
        # above MXTPU_CSR_DENSIFY_BOUND bytes (default 256MB) it switches
        # to a chunked row-band path — each band is densified, summed and
        # re-sparsified separately, bounding peak host memory at the band
        # size.  Band count derives only from shape+bound, so every rank
        # issues the same collective sequence (lockstep contract).
        import os
        import warnings
        bound = int(os.environ.get("MXTPU_CSR_DENSIFY_BOUND", str(1 << 28)))
        nbytes = int(np.prod(red.shape)) * np.dtype(red.dtype).itemsize
        if nbytes <= bound:
            dense = np.asarray(_global_sum(
                red._read().ravel())).reshape(red.shape)
            r, c = np.nonzero(dense)
            red.data = NDArray(jnp.asarray(dense[r, c]))
            red.indices = NDArray(jnp.asarray(c.astype(np.int64)))
            red.indptr = NDArray(jnp.asarray(np.searchsorted(
                r, np.arange(red.shape[0] + 1)).astype(np.int64)))
            return red
        warnings.warn(
            "CSR cross-worker reduce of %s (%d bytes dense) exceeds "
            "MXTPU_CSR_DENSIFY_BOUND=%d; using the chunked row-band path "
            "(slower, bounded memory)" % (red.shape, nbytes, bound))
        nrows, ncols = red.shape
        row_bytes = ncols * np.dtype(red.dtype).itemsize
        band = max(1, bound // max(row_bytes, 1))
        indptr = np.asarray(red.indptr._read()).astype(np.int64)
        indices = np.asarray(red.indices._read()).astype(np.int64)
        data = np.asarray(red.data._read())
        cs, vs, ptr_parts = [], [], [np.zeros(1, np.int64)]
        for r0 in range(0, nrows, band):
            r1 = min(r0 + band, nrows)
            ptr = indptr[r0:r1 + 1]
            dense_b = np.zeros((r1 - r0, ncols), data.dtype)
            if ptr[-1] > ptr[0]:
                rows = np.repeat(np.arange(r0, r1), np.diff(ptr)) - r0
                dense_b[rows, indices[ptr[0]:ptr[-1]]] = \
                    data[ptr[0]:ptr[-1]]
            dense_b = np.asarray(_global_sum(
                dense_b.ravel())).reshape(r1 - r0, ncols)
            r, c = np.nonzero(dense_b)
            cs.append(c)
            vs.append(dense_b[r, c])
            ptr_parts.append(ptr_parts[-1][-1] + np.searchsorted(
                r, np.arange(1, r1 - r0 + 1)).astype(np.int64))
        red.data = NDArray(jnp.asarray(np.concatenate(vs)))
        red.indices = NDArray(jnp.asarray(np.concatenate(cs)))
        red.indptr = NDArray(jnp.asarray(
            np.concatenate(ptr_parts)))
        return red

    def _cross_worker_reduce_many(self, reds, heartbeat=True,
                                  compress=False):
        """All values of one push in as few collectives as possible:
        same-dtype values pack into one flat buffer (native dtype, so
        integer sums stay exact) and go through ONE in-graph all-reduce —
        latency-bound DCN rounds amortize over the whole push (the
        batching role of the reference's big-array sharding,
        kvstore_dist.h MXNET_KVSTORE_BIGARRAY_BOUND).  Iteration order is
        the caller's key order, which every rank derives from the same
        enumerate() over parameters — ranks stay in collective lockstep.
        Mutates in place."""
        if num_workers() <= 1 or not reds:
            return reds
        from ..ndarray.sparse import BaseSparseNDArray
        groups = {}
        for r in reds:
            if isinstance(r, BaseSparseNDArray):
                self._cross_worker_reduce_sparse(r)    # row-id union path
            else:
                groups.setdefault(np.dtype(r.dtype), []).append(r)
        # pack/unpack glue is jitted (engine.flatten_arrays / split_flat)
        # so an N-value push costs 2 dispatches of host glue instead of
        # ~2N (one ravel per value + one slice per write-back)
        from .. import engine as _engine
        # the legacy threshold compressor only applies to per-key PUSH
        # traffic (the caller already quantized to {-t, 0, +t}); bucket
        # flats from reduce_many* arrive compress=False — they either
        # ride dense or went through the block-scaled graftzero wire
        # (_cross_worker_reduce_quantized) before reaching a collective
        compress = compress and (self._compressor is not None)
        for dtype, group in groups.items():
            vals = [r._read() for r in group]
            flat = _engine.flatten_arrays(tuple(vals))
            if compress and np.issubdtype(dtype, np.floating):
                # the push already quantized values to {-t, 0, +t}
                # (residual kept worker-side); the wire is a compressed
                # reduce-scatter (all-to-all of the packed 2-bit shards)
                # + an all-gather of exact int8 shard sums — per-worker
                # bytes are W-INDEPENDENT (~1.25n vs dense's ~8n), unlike
                # the old allgather-of-codes that shipped (W-1)·n/4 and
                # decoded O(W·n) per worker
                # (gradient_compression.h:37-132 + kvstore_dist_server.h
                # DataHandleCompressed, sharded across workers)
                t = self._compressor.threshold
                words = compression.encode_2bit(flat, t)
                summed = compression.allreduce_packed_sum(
                    words, t, flat.shape[0], worker_mesh()).astype(flat.dtype)
            else:
                summed = _global_sum(flat)
            pieces = _engine.split_flat(summed, [v.shape for v in vals])
            for r, piece in zip(group, pieces):
                r._write(piece)
        # graftwatch straggler detection piggybacks on this sync path:
        # one tiny extra allreduce per reduce BATCH (not per key) carries
        # every worker's arrival timestamp + step counter.  Gated on the
        # recorder switch, which therefore must be set CONSISTENTLY
        # across ranks (collective-lockstep contract) — see docs.  Async
        # issues (graftlap, heartbeat=False) skip it: reading the
        # heartbeat table host-side blocks on everything dispatched
        # before it on the same devices, which would turn the async
        # issue into a synchronous reduce.  Every rank derives
        # ``heartbeat`` from the same code path, so the collective
        # sequence stays in lockstep.
        if heartbeat and _blackbox.enabled():
            self._heartbeat_skew()
        return reds

    def _cross_worker_reduce_quantized(self, payloads, n_elems, mode,
                                       block, heartbeat=True):
        """graftzero: one EQuARX-style quantized collective per bucket
        payload — all-to-all of the packed codes + scales shards,
        per-shard dequant + f32 sum, re-quantize, narrow all-gather
        (``parallel.quant.reduce_payload_sum``; no f32 collective).
        Mutates the payload NDArrays in place; same heartbeat piggyback
        contract as the dense reduce."""
        if num_workers() <= 1 or not payloads:
            return payloads
        from . import quant as _quant
        mesh = worker_mesh()
        for (codes, scales), n in zip(payloads, n_elems):
            oc, osc = _quant.reduce_payload_sum(
                codes._read(), scales._read(), int(n), mode, int(block),
                mesh)
            codes._write(oc)
            scales._write(osc)
        if heartbeat and _blackbox.enabled():
            self._heartbeat_skew()
        return payloads

    def heartbeat(self):
        """One worker heartbeat on demand (the Trainer's overlapped-step
        wait side): same gating as the reduce-batch piggyback — recorder
        on (rank-consistent, lockstep contract) and real peers."""
        if num_workers() > 1 and _blackbox.enabled():
            self._heartbeat_skew()

    def _heartbeat_skew(self):
        """Per-worker step heartbeat: each rank contributes its arrival
        time (ms, int32 — jax x64 is off and float32 cannot hold epoch
        milliseconds) and step count in its own slot of a (2W,) vector;
        the allreduce sum hands every rank the full table.  Feeds the
        per-step worker-skew histogram, the flight recorder's last-seen
        table, and a straggler log line when the skew is extreme.

        With GRAFT_LOCKSTEP_CHECK on (default; set it IDENTICALLY on
        every rank — the vector SHAPE depends on it) the vector widens
        to (6W,) and additionally carries each rank's collective-stream
        rolling hash + FOLD COUNT (the audited-stream position, NOT the
        wire seq — ps_* brackets skew wire seqs rank-dependently; see
        analysis/lockstep.py) PLUS the lagged-prefix pair (the rolling
        hash as it stood GRAFT_LOCKSTEP_LAG folds earlier): every rank
        then cross-checks the table and a rank whose stream diverged is
        named BEFORE a mispaired collective turns into a silent hang —
        and when the accumulated prefix points bracket the divergence
        to adjacent folds, observe() pins the EXACT collective online
        (PR 10's online-bisection carry-forward)."""
        W = num_workers()
        self._hb_step += 1
        now_ms = int(time.time() * 1000) % (1 << 31)
        audit = _lockstep.enabled()
        elastic = _elastic.enabled()
        base_slots = (6 if audit else 2) * W
        # graftelastic: W MORE per-rank slots carry each rank's
        # membership epoch, so a survivor that fenced a change names
        # the laggards on the very next heartbeat.  The
        # SHAPE depends on GRAFT_ELASTIC — set it IDENTICALLY on every
        # rank, exactly like the audit knob above.
        vec = np.zeros((base_slots + (W if elastic else 0),), np.int32)
        vec[rank()] = now_ms
        vec[W + rank()] = self._hb_step % (1 << 31)
        if audit:
            folds, rolling, lag_fold, lag_hash = _lockstep.state_lagged()
            vec[2 * W + rank()] = rolling
            vec[3 * W + rank()] = folds % (1 << 31)
            vec[4 * W + rank()] = lag_hash
            vec[5 * W + rank()] = lag_fold % (1 << 31)
        if elastic:
            vec[base_slots + rank()] = _lockstep.epoch() % (1 << 31)
        out = np.asarray(_global_sum(jnp.asarray(vec))).astype(np.int64)
        ts_ms, steps = out[:W], out[W:2 * W]
        if audit:
            hashes, folds_by_rank = out[2 * W:3 * W], out[3 * W:4 * W]
            lag_hashes, lag_folds = out[4 * W:5 * W], out[5 * W:6 * W]
            _lockstep.observe({r: (int(folds_by_rank[r]), int(hashes[r]),
                                   int(lag_folds[r]), int(lag_hashes[r]))
                               for r in range(W)}, my_rank=rank())
        if elastic:
            epochs = out[base_slots:base_slots + W]
            mine = int(epochs[rank()])
            ahead = int(epochs.max())
            if ahead > mine:
                # only the LAGGARD raises: peers that already fenced the
                # change keep going; this rank must stop issuing
                # collectives against the stale view and apply its
                # pending change (or rejoin) before the next step
                from ..armor.errors import MembershipChangedError
                raise MembershipChangedError(
                    mine, ahead, detail="rank(s) %s heartbeat at a newer "
                    "membership epoch — apply the pending change at the "
                    "step fence before the next collective" % sorted(
                        r for r in range(W) if int(epochs[r]) > mine))
        # mod-wrap unwrap: a rank that crossed the 2^31 ms boundary while
        # others have not would otherwise read as ~24 days of skew
        if ts_ms.max() - ts_ms.min() > (1 << 30):
            ts_ms = np.where(ts_ms < (1 << 30), ts_ms + (1 << 31), ts_ms)
        skew = float(ts_ms.max() - ts_ms.min()) / 1e3
        _tmetrics.dist_worker_skew(skew)
        base = max(int(ts_ms.max()), now_ms)
        # this rank's lag behind the freshest arrival: an upper-bound
        # clock-offset estimate stamped into dump headers so the trace
        # aggregator can align a LONE dump (matched heartbeat/collective
        # anchors are preferred when several ranks' artifacts are given)
        _blackbox.set_clock_offset(float(base - ts_ms[rank()]) / 1e3)
        _blackbox.workers_seen(
            {r: {"lag_s": round(float(base - ts_ms[r]) / 1e3, 6),
                 "step": int(steps[r])} for r in range(W)},
            skew=skew, step=self._hb_step)

    def _sync_set_optimizer(self, optimizer):
        """dist_sync path: pickle round-trip, as the reference ships the
        optimizer to servers (kvstore.py set_optimizer →
        _send_command_to_servers); the updater runs store-side locally."""
        import pickle
        from .. import optimizer as opt
        self._updater = opt.get_updater(pickle.loads(pickle.dumps(optimizer)))
