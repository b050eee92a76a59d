"""graftduplex — the full-duplex step schedulers, shared by
``gluon.Trainer`` and ``module.Module``.

Two sides of one wire:

* :class:`BucketScheduler` (the push/reduce side, graftlap PR 7): armed
  with a bucket plan, it hangs grad-ready hooks on the host's gradient
  carriers; the moment the last (param, context) gradient of a bucket
  finalizes MID-BACKWARD, the bucket's concatenated flat buffer is built
  with the host's own packing math and shipped through
  ``KVStore.reduce_many_async`` while backward keeps producing
  earlier-layer gradients.  PR 9 generalizes it behind a small host
  protocol (``_sched_*`` methods) so ``Module``'s executor grad arrays
  ride the same machinery ``gluon.Trainer`` got.

* :class:`PullScheduler` (the pull/broadcast side, new): after the
  store-side update, each bucket's weight pull is issued as a
  ``KVStore.pull_many_async`` handle and FIRST-TOUCH hooks are installed
  on the out arrays — the next forward's first read of any covered
  weight waits that bucket's handle (``NDArray._touch_hook``, checked at
  the top of ``_read``), so updated weights stream back under data
  loading and the early layers.  Version stamps taken at issue gate the
  apply: an array the user overwrote between steps keeps the user's
  bytes (the serial pull-then-write ordering) and flags the round stale,
  which the consumer answers by falling back to the serial pull for the
  next round — exactly mirroring the reduce side's stale-grad fallback.

Both schedulers degrade to the bit-identical serial paths, never to
wrong values.  Env switches: ``GRAFT_OVERLAP`` (reduce side),
``GRAFT_OVERLAP_PULL`` (pull side), ``GRAFT_BUCKET_ORDER`` (tape|index
bucket packing — see ``gluon.Trainer._plan_order``).
"""
from __future__ import annotations

import os
import time
import weakref

import numpy as np

from . import engine as _engine
from .analysis import tsan as _tsan

__all__ = ["Bucket", "BucketScheduler", "PullScheduler", "bucket_order",
           "overlap_pull_enabled", "plan_pull_groups", "concat_ctx_sum",
           "publish_pull_round", "serial_pull", "pull_round"]

DEFAULT_BUCKET_BYTES = 4 << 20      # 4 MiB, the classic DDP bucket size


class Bucket(object):
    """One dtype-homogeneous gradient bucket of a fused/duplex step
    plan (``kind`` carries the fused-optimizer tag on the Trainer's
    local-update path; None on store-update/Module plans)."""
    __slots__ = ("indices", "kind", "dtype", "nbytes")

    def __init__(self, indices, kind, dtype, nbytes):
        self.indices = tuple(indices)
        self.kind = kind
        self.dtype = dtype
        self.nbytes = nbytes


def bucket_order():
    """GRAFT_BUCKET_ORDER: ``tape`` (default) packs buckets by reverse
    tape order — autograd stamps each hooked parameter's earliest tape
    position during the backward prescan, and parameters whose gradients
    finalize FIRST (the last-used layers) pack into the first buckets,
    so the first reduce goes on the wire earlier in the walk and the
    overlap window covers more of backward.  ``index`` reverts to plain
    parameter-index packing (the PR 4 behavior)."""
    v = os.environ.get("GRAFT_BUCKET_ORDER", "tape").strip().lower()
    return "index" if v == "index" else "tape"


def overlap_pull_enabled(override=None):
    """GRAFT_OVERLAP_PULL (default on): overlap the update_on_kvstore
    weight pulls with the next forward (graftduplex).  Like
    GRAFT_OVERLAP, multi-host jobs must set it IDENTICALLY on every
    rank — the issue order of the pull collectives is part of the
    lockstep contract."""
    if override is not None:
        return bool(override)
    return os.environ.get("GRAFT_OVERLAP_PULL", "1").strip().lower() \
        not in ("0", "false", "no", "off")


def plan_pull_groups(keys, nbytes_per_key, target):
    """Greedily group ``keys`` (index order) into pull groups of
    ~``target`` bytes — the per-bucket granularity of the async
    pull/broadcast when no bucket plan exists (the dist_async parameter
    service path).  Returns a list of key-lists covering every key."""
    if target <= 0:
        return [list(keys)] if keys else []
    groups, cur, cur_bytes = [], [], 0
    for k, nb in zip(keys, nbytes_per_key):
        cur.append(k)
        cur_bytes += nb
        if cur_bytes >= target:
            groups.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        groups.append(cur)
    return groups


def publish_pull_round(sched):
    """Publish the PREVIOUS round's pull-overlap telemetry before a new
    round issues (the round's waits finished at first-touch during the
    last forward and in the consumer's finish() at step start)."""
    from .telemetry import metrics as _tmetrics
    n, exposed_s, inflight_s, stale_seen = sched.take_stats()
    if n:
        _tmetrics.trainer_pull_overlap(n, 0, exposed_s, inflight_s,
                                       stale=stale_seen)


def serial_pull(kv, keys, outs):
    """The synchronous batched pull, reported on the same pull telemetry
    (exposed == inflight) so serial and duplex runs stay comparable on
    one gauge."""
    from .telemetry import metrics as _tmetrics
    t0 = time.perf_counter()
    kv.pull_many(keys, outs)
    dt = time.perf_counter() - t0
    _tmetrics.trainer_pull_overlap(0, 1, dt, dt)


def pull_round(sched, kv, keys, outs, sizes, target, overlap):
    """One whole pull round, shared by ``gluon.Trainer._update`` and
    ``Module``'s update_on_kvstore path: publish the previous round,
    then either the serial batched pull (``overlap=False`` — the
    kill-switch / stale / sparse fallbacks) or async per ~``target``-byte
    group with first-touch waits.  ``outs[i]`` is the out-NDArray list
    (one per context replica) for ``keys[i]``; ``sizes[i]`` its payload
    bytes."""
    publish_pull_round(sched)
    if not overlap:
        serial_pull(kv, keys, outs)
        return
    by_key = dict(zip(keys, outs))
    for gkeys in plan_pull_groups(keys, sizes, target):
        sched.issue(kv, gkeys, [by_key[k] for k in gkeys],
                    label="pull[%dp]" % len(gkeys))


def concat_ctx_sum(grads_by_ctx, ctx=None):
    """One bucket's concatenated local gradient: per-context flatten
    (one jitted dispatch each) + elementwise context tree-sum in context
    order — THE packing math, shared verbatim by the serial step paths
    (Trainer and Module) and the overlapped mid-backward issue so all of
    them are bit-identical by construction.  ``grads_by_ctx`` is a list
    over contexts of equally-ordered gradient NDArray lists; replicas
    committed to distinct devices are colocated before the sum
    (transfers preserve bits)."""
    from .ndarray import NDArray
    per_ctx = [
        _engine.flatten_arrays(tuple(g._read() for g in ctx_grads))
        for ctx_grads in grads_by_ctx]
    acc = per_ctx[0]
    for f in per_ctx[1:]:
        acc = acc + _engine.colocate(f, acc)
    return NDArray(acc, ctx=ctx)


class BucketScheduler(object):
    """graftlap/graftduplex: issue each bucket's gradient allreduce
    DURING backward.

    Armed by the host's step with the current bucket plan, the scheduler
    hangs a grad-ready hook on every eligible gradient carrier (autograd
    fires it the moment that parameter's gradient is final — see
    ``autograd._run_backward``; ``symbol.Executor.backward`` fires the
    same hook as it writes each bound grad array).  When the last
    (param, context) pair of a bucket reports ready, the bucket's
    concatenated flat gradient is built with the host's OWN serial-path
    math (``_sched_flat``) and shipped through
    ``KVStore.reduce_many_async`` — an in-flight handle with its own
    flight-recorder bracket — while backward keeps producing
    earlier-layer gradients.  The host's step then only *waits* on the
    handles.  Because the hook order is the reverse-topological walk of
    a tape every rank shares (SPMD), the issue order of the collectives
    is identical on every worker: the lockstep contract holds.

    The host protocol (duck-typed; ``gluon.Trainer`` and
    ``module.Module`` implement it):

    * ``_sched_entries(bucket)`` → ``[(key, carrier, grad), ...]`` —
      the (param, context) keys of the bucket, the NDArray each hook
      sits on, and the gradient NDArray whose ``_version`` gates
      consumption;
    * ``_sched_eligible(bucket)`` → only ``grad_req == "write"`` buckets
      may arm ("add" accumulation means grads are not final per pass);
    * ``_sched_kv()`` / ``_sched_flat(bucket)`` / ``_sched_label(bucket)``;
    * ``_sched_pass_id()`` — a monotonic backward-pass id (autograd's
      for the Trainer, the executor group's backward counter for
      Module);
    * ``_sched_autograd_hooks`` — True when autograd delivers the hooks
      (the tape prescan is then gated on this scheduler's registration).

    Safety rails (each one degrades to the serial bucketed reduce,
    never to wrong values):

    * hooks fire only on a plain full backward — ``retain_graph``,
      ``create_graph`` and explicit-variables passes suppress them;
    * a hook under a NEW pass id abandons every handle of the previous
      pass before scheduling restarts (a second backward overwrote the
      reduced grads);
    * at consume time every grad's ``_version`` must still match its
      issue-time stamp (gradient clipping or any other post-backward
      mutation invalidates the handle);
    * a scheduler exception marks it broken for the step instead of
      propagating into the user's backward.
    """

    __slots__ = ("_host_ref", "_armed", "_waiting", "_hooked",
                 "_buckets", "_pass_id", "_broken", "_plan", "_hook",
                 "_fire_count", "issue_log", "issued_total", "taken_total",
                 "__weakref__")

    def __init__(self, host):
        self._host_ref = weakref.ref(host)
        # ONE hook closure, created once (`self._on_ready` builds a fresh
        # bound method per attribute access, so ad-hoc accessors would
        # never pass disarm's identity check and hooks would leak), and
        # holding the scheduler WEAKLY: a bound method would pin the
        # scheduler — and through nothing else, the arrays its hooks sit
        # on — alive long after the host is dropped, keeping the
        # autograd hook-source gate open forever.  With the weakref the
        # scheduler dies with its host; orphaned hook attrs left on
        # carrier arrays degrade to a dead-ref no-op until overwritten.
        sched_ref = weakref.ref(self)

        def _hook(arr, _ref=sched_ref):
            sched = _ref()
            if sched is not None:
                sched._on_ready(arr)
        self._hook = _hook
        self._armed = False
        self._waiting = {}      # id(carrier NDArray) -> (bucket state, key)
        self._hooked = []       # carrier NDArrays carrying our hook
        self._buckets = {}      # id(bucket) -> state dict
        self._pass_id = None
        self._broken = False
        self._plan = None       # the armed plan, held STRONGLY: identity
        #                         (same cached tuple) means same plan, and
        #                         the ref pins it so a recycled id() can
        #                         never alias a new plan
        self._fire_count = 0    # hooks consumed this pass (tape-order
        #                         evidence: how early each bucket closed)
        self.issue_log = []     # [(bucket indices, fire_count at issue)]
        #                         for the current pass
        self.issued_total = 0   # buckets issued mid-backward (ever)
        self.taken_total = 0    # issued buckets actually consumed by step

    # -- arming -------------------------------------------------------------
    def arm(self, plan):
        """Install hooks for ``plan``'s eligible buckets (called at the
        end of every overlapped step, so the NEXT backward schedules).
        Steady state — same (cached) plan object, scheduler healthy —
        skips the reinstall: the next backward's first hook resets the
        pending sets via the pass-id rollover, so re-arming is O(1)."""
        with _tsan.region(self, "arm"):
            self._arm(plan)

    def _arm(self, plan):
        if self._armed and not self._broken and self._plan is plan:
            self._abandon_all()
            for state in self._buckets.values():
                state["handle"] = None
                state["flat"] = None
            self._pass_id = None    # next hook rebuilds pending sets
            return
        self.disarm()
        host = self._host_ref()
        if host is None:
            return
        buckets, _leftover = plan
        for b in buckets:
            if not host._sched_eligible(b):
                continue        # "add" accumulation: never final per pass
            entries = host._sched_entries(b)
            if not entries:
                continue
            state = {"bucket": b, "pending": set(), "handle": None,
                     "flat": None, "versions": None,
                     "grads": [g for _k, _c, g in entries],
                     "all_keys": frozenset(k for k, _c, _g in entries)}
            for key, carrier, _grad in entries:
                state["pending"].add(key)
                self._waiting[id(carrier)] = (state, key)
                carrier._grad_ready_hook = self._hook
                self._hooked.append(carrier)
            self._buckets[id(b)] = state
        self._armed = bool(self._buckets)
        if self._armed and getattr(host, "_sched_autograd_hooks", True):
            from . import autograd
            autograd.register_hook_source(self)
        self._plan = plan if self._armed else None
        self._pass_id = None
        self._broken = False

    def disarm(self):
        """Drop hooks and abandon anything still in flight."""
        with _tsan.region(self, "disarm"):
            self._disarm()

    def _disarm(self):
        for d in self._hooked:
            if getattr(d, "_grad_ready_hook", None) is self._hook:
                d._grad_ready_hook = None
        self._hooked = []
        self._waiting = {}
        self._abandon_all()
        self._buckets = {}
        self._armed = False
        self._plan = None
        from . import autograd
        autograd.unregister_hook_source(self)

    def _abandon_all(self):
        for state in self._buckets.values():
            if state["handle"] is not None:
                state["handle"].abandon()
                state["handle"] = None

    # -- the hook (fires inside the host's backward) ------------------------
    def _on_ready(self, arr):
        # grafttsan region: the hook mutates pending sets / handles; a
        # consumer (arm/disarm/take) on another thread racing it is the
        # EH202 hazard.  Per-gradient hot path — the raw flag keeps the
        # disabled cost to one attribute load + index (the _write/_read
        # convention); the once-per-step entry points go through region()
        if _tsan._ACTIVE[0]:
            with _tsan.region(self, "_on_ready"):
                self._on_ready_locked(arr)
        else:
            self._on_ready_locked(arr)

    def _on_ready_locked(self, arr):
        if not self._armed or self._broken:
            return
        host = self._host_ref()
        if host is None:
            # the host is gone but something still holds the scheduler
            # (a kept `t._scheduler` ref): clean up after ourselves
            self.disarm()
            return
        try:
            pass_id = host._sched_pass_id()
            if pass_id != self._pass_id:
                # new backward pass: everything issued for the previous
                # one reduces grads that were just overwritten — discard
                # and start this pass clean
                self._abandon_all()
                for state in self._buckets.values():
                    state["pending"] = set(state["all_keys"])
                self._pass_id = pass_id
                self._fire_count = 0
                self.issue_log = []
            entry = self._waiting.get(id(arr))
            if entry is None:
                return
            state, key = entry
            self._fire_count += 1
            state["pending"].discard(key)
            if not state["pending"] and state["handle"] is None:
                self._issue(host, state)
        except Exception:
            self._broken = True
            self._abandon_all()
            raise               # _fire_ready_hook catches + logs; the
            #                     user's backward pass is unaffected

    def _issue(self, host, state):
        """All grads of one bucket are final: build the flat buffer and
        put its reduce on the wire, without joining (or flushing) any
        bulk segment the surrounding code has open."""
        kv = host._sched_kv()
        if kv is None:
            return
        b = state["bucket"]
        with _engine.offband():
            flat = host._sched_flat(b)
            state["versions"] = [g._version for g in state["grads"]]
            state["flat"] = flat
            # graftzero: hosts with a quantized-wire hook (Trainer) issue
            # the bucket through it — the scheduler itself is payload-
            # agnostic and issues quantized buckets unchanged
            issue = getattr(host, "_sched_reduce_async", None)
            if issue is not None:
                state["handle"] = issue(kv, b, flat)
            else:
                state["handle"] = kv.reduce_many_async(
                    [flat], label=host._sched_label(b))
        self.issue_log.append((b.indices, self._fire_count))
        self.issued_total += 1

    # -- consuming (the host's step) ----------------------------------------
    def take(self, plan):
        """Hand the step the buckets whose reduces are validly in flight:
        ``{id(bucket): (flat NDArray, ReduceHandle)}``.  Stale handles
        (grad versions moved since issue) are abandoned; everything is
        one-shot — the caller re-arms for the next step."""
        with _tsan.region(self, "take"):
            return self._take(plan)

    def _take(self, plan):
        out = {}
        if self._host_ref() is None or not self._armed or self._broken:
            self._abandon_all()
            return out
        buckets, _leftover = plan
        by_id = {id(b): b for b in buckets}
        for bid, state in self._buckets.items():
            handle = state["handle"]
            if handle is None:
                continue
            b = by_id.get(bid)
            if b is None:
                handle.abandon()        # plan changed under us
                continue
            if [g._version for g in state["grads"]] != state["versions"]:
                handle.abandon()        # stale grads: serial fallback
                continue
            out[bid] = (state["flat"], handle)
            state["handle"] = None      # consumed
        self.taken_total += len(out)
        return out


class PullScheduler(object):
    """graftduplex pull side: in-flight weight pulls waited at FIRST USE.

    ``issue`` puts one group's pull on the wire
    (``KVStore.pull_many_async``) and installs a first-touch hook on
    every out array (``NDArray._touch_hook``, checked at the top of
    ``_read``) — the next forward's first read of ANY covered weight
    waits that group's handle before the value is returned, so a
    read-modify-write between steps (`w *= 0.5`) sees the pulled bytes
    exactly as the serial pull-then-mutate ordering would.  A direct
    overwrite without a read bumps the array's ``_version`` past the
    issue-time stamp: the pulled value for that array is dropped (the
    user's write wins — again the serial ordering) and the round is
    flagged stale, which consumers answer with one serial-pull round
    (abandon-and-fallback, mirroring the reduce side's stale-grad rail).
    ``finish()`` — called at the start of the next step — waits whatever
    the forward never touched, so no handle outlives its step."""

    __slots__ = ("_hook", "_groups", "_by_arr", "issued_total",
                 "touched_total", "finished_total", "stale_total",
                 "exposed_s", "inflight_s", "__weakref__")

    def __init__(self):
        sched_ref = weakref.ref(self)

        def _hook(arr, _ref=sched_ref):
            sched = _ref()
            if sched is None:
                arr._touch_hook = None      # dead scheduler: self-clean
                return
            sched._on_touch(arr)
        self._hook = _hook
        self._groups = {}       # id(group) -> group dict
        self._by_arr = {}       # id(out NDArray) -> group
        self.issued_total = 0   # groups ever issued
        self.touched_total = 0  # groups finished by a first-touch read
        self.finished_total = 0     # groups finished since take_stats
        self.stale_total = 0        # stale outs since take_stats
        self.exposed_s = 0.0        # blocked wait since take_stats
        self.inflight_s = 0.0       # issue→wait-return since take_stats

    @property
    def inflight_groups(self):
        return len(self._groups)

    def issue(self, kv, keys, outs, label=None):
        """Put one group's pull on the wire; ``outs`` is a list (per
        key) of out-NDArray lists (one per context replica)."""
        # graftarmor chaos site: the duplex pull-issue edge (error here
        # models a wire that dies between step N's update and step N+1's
        # prefetch — the consumer's abandon-and-fallback rail)
        from .armor import faults as _faults
        _faults.fault_point("overlap.pull_issue", n_keys=len(keys),
                            bucket=label)
        with _tsan.region(self, "issue"):
            return self._issue(kv, keys, outs, label=label)

    def _issue(self, kv, keys, outs, label=None):
        flat = [o for olist in outs for o in olist]
        for o in flat:
            g = self._by_arr.get(id(o))
            if g is not None:
                self._finish_group(g)   # an array rides ONE group at a
                #                         time (callers finish() first;
                #                         this is the defensive rail)
        handle = kv.pull_many_async(keys, outs, label=label)
        group = {"handle": handle, "outs": flat,
                 "versions": [o._version for o in flat]}
        self._groups[id(group)] = group
        for o in flat:
            self._by_arr[id(o)] = group
            o._touch_hook = self._hook
        self.issued_total += 1
        return handle

    # -- the first-touch hook (fires inside NDArray._read) ------------------
    def _on_touch(self, arr):
        # same single-owner contract as the reduce side's _on_ready: a
        # first-touch hook racing issue/finish from another thread is
        # EH202 under GRAFT_TSAN (raw-flag guard: this sits inside the
        # _read hot path)
        if _tsan._ACTIVE[0]:
            with _tsan.region(self, "_on_touch"):
                self._on_touch_locked(arr)
        else:
            self._on_touch_locked(arr)

    def _on_touch_locked(self, arr):
        arr._touch_hook = None
        group = self._by_arr.get(id(arr))
        if group is None:
            return
        self.touched_total += 1
        self._finish_group(group)

    def _finish_group(self, group):
        # clear the group's hooks FIRST: handle.wait() reads the out
        # arrays, and a still-hooked sibling would re-enter this path
        # mid-wait
        for o in group["outs"]:
            if getattr(o, "_touch_hook", None) is self._hook:
                o._touch_hook = None
            self._by_arr.pop(id(o), None)
        self._groups.pop(id(group), None)
        handle = group["handle"]
        stale = sum(1 for o, v in zip(group["outs"], group["versions"])
                    if o._version != v)
        handle.wait()       # PS handles apply version-gated writes here;
        #                     in-process handles wrote at issue (any later
        #                     user write already sits on top — serial
        #                     order) and only block-until-ready
        self.stale_total += max(stale, getattr(handle, "stale", 0))
        self.exposed_s += handle.blocked_s
        self.inflight_s += handle.inflight_s
        self.finished_total += 1

    # -- consumer API --------------------------------------------------------
    def finish(self):
        """Wait every outstanding group (called before issuing the next
        round, and by teardown).  Returns the stale-out count observed
        since the last :meth:`take_stats` — nonzero means the consumer
        should run the NEXT round serial (abandon-and-fallback)."""
        with _tsan.region(self, "finish"):
            for group in list(self._groups.values()):
                self._finish_group(group)
            return self.stale_total

    def abandon_all(self):
        """Drop every outstanding group without consuming (teardown
        fallback): hooks clear, brackets close, deferred writes (the PS
        path) are lost — only reached when waiting is no longer safe."""
        with _tsan.region(self, "abandon_all"):
            self._abandon_all()

    def _abandon_all(self):
        for group in list(self._groups.values()):
            for o in group["outs"]:
                if getattr(o, "_touch_hook", None) is self._hook:
                    o._touch_hook = None
                self._by_arr.pop(id(o), None)
            group["handle"].abandon()
        self._groups = {}

    def __del__(self):
        # a consumer dropped with pulls in flight must not leak open
        # flight-recorder brackets (they would sit in every later crash
        # dump as phantom in-flight collectives): settle them — waiting
        # applies any deferred PS writes the out arrays still expect
        try:
            self.finish()
        except Exception:
            try:
                self.abandon_all()
            except Exception:
                pass        # interpreter teardown: nothing to save

    def take_stats(self):
        """(groups, exposed_s, inflight_s, stale) accumulated since the
        last call — the consumer publishes them as the pull-overlap
        telemetry round."""
        out = (self.finished_total, self.exposed_s, self.inflight_s,
               self.stale_total)
        self.finished_total = 0
        self.stale_total = 0
        self.exposed_s = 0.0
        self.inflight_s = 0.0
        return out
