"""Engine op-bulking: defer eager ops, replay them as ONE XLA program.

The reference's engine amortized per-op push overhead by appending
consecutive eager ops into a bulk segment executed as one engine op
(src/engine/threaded_engine.h:472-509 BulkAppend/BulkFlush,
MXNET_EXEC_BULK_EXEC_*).  The TPU-native analogue: inside a

    with mx.engine.bulk(64):
        for ...:
            eager small ops

scope, pure eager op invocations are RECORDED instead of dispatched; the
pending program is flushed — compiled (once, cached by program shape) and
executed as a single jitted replay — when the scope closes, the segment
reaches ``size`` ops, or any deferred value is materialized (asnumpy,
_read, in-place write, autograd capture).  Steady-state loops hit the
replay cache, so N small ops cost one dispatch (measured ~5x on the
eager micro-benchmark, bench_eager.py).

Autograd-recording ops ARE deferrable (round 4 — the reference bulks
*training* segments first and foremost, MXNET_EXEC_BULK_EXEC_TRAIN,
threaded_engine.h:472-509): a segment containing recorded ops becomes
ONE tape node at flush — the forward is the single jitted replay, and
the backward is a single jitted vjp of the whole replay program, so an
N-op recorded chain costs one dispatch forward and one backward instead
of N + 2N.  Ops that ran under ``autograd.pause()`` inside the segment
are wrapped in ``stop_gradient`` so the tape semantics match eager
execution exactly.

``out=`` stores and mutating ops (optimizer updates) ARE deferrable
(round 5, matching the reference's bulking of optimizer updates inside
train segments): the write target's buffer is rebound to the pending
output at record time, provided the target is a plain non-view NDArray
and the inferred output matches its shape/dtype exactly — otherwise the
op runs eagerly with the usual astype/write-through fixups.

VIEW ops are deferrable (round 6 — the reference bulks the reshape/
transpose glue of real model bodies into the same segment,
threaded_engine.h:472-509): a view taken of a deferred value becomes a
new _Pending whose program node is the corresponding shape op
(``_bulk_view_extract``: flat slice + reshape, exactly NDArray._read's
concrete math), so reshape/reshape_like/expand_dims/``__getitem__``
basic slicing/at/slice over a pending keep the segment open —
transpose/swapaxes/squeeze are ordinary registered ops and defer
through the normal path.  Write-through to a deferred view records a
``_bulk_view_write`` (lax.dynamic_update_slice into the base's flat
buffer) in the same program and rebinds the base to the new pending.
Liveness treats base and view as one ownership group: the view holds a
strong ref to its base NDArray, so a live view keeps its base's pending
live, and a dead view's extract node is eliminated like any other dead
value.  Views still MATERIALIZE (one flush, counted under the ``view``
flush cause) when the base pending belongs to another scope/segment,
for sparse storage, and for fancy/multi-axis indexing — those read
concrete buffers by construction.

Out of scope for deferral (dispatched eagerly, exactly as before):
recorded ops with ``out=``, sparse storage, ops that manage their own
mesh placement (no_jit), and NaiveEngine mode.

Strict mode (round 7, ``GRAFT_ENGINE_CHECK=1`` or ``set_engine_check``):
every segment is verified against the hazards the deferral machinery
could silently mis-handle — a read/write version vector per base+view
ownership group catches stale-extract write-after-read (EH101) and
double-write rebinds (EH102) at record time; flush validates operand
references against the ``ext`` set (EH103) and replays the segment
UNFUSED, bit-comparing every live output against the fused result (the
fusion-equivalence oracle, EH104).  Violations raise structured
``EngineHazardError``s (analysis/engine_check.py; docs/static_analysis.md).
Debug-only: the oracle doubles execution per flush.

Every flush is attributed to a cause — ``scope-close`` (bulk.__exit__),
``size-cap`` (segment hit ``size``), ``view`` (a non-deferrable view
materialized its base), ``read`` (asnumpy/_read of a deferred value),
``autograd`` (backward landing the segment's tape node) — and the
per-flush instruction count feeds a segment-length histogram; see
``flush_stats()`` / ``reset_flush_stats()``.  bench_eager.py reports
both so segment fragmentation is visible per round.
"""
from __future__ import annotations

import os
import threading
import time
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from .analysis.engine_check import (EngineHazardError,
                                    check_segment_integrity, oracle_compare)
from .analysis import tsan as _tsan
from . import profiler as _profiler
from .telemetry import blackbox as _blackbox
from .telemetry import metrics as _tmetrics
from .telemetry import tracing as _ttracing

__all__ = ["bulk", "offband", "flush", "flush_stats",
           "reset_flush_stats",
           "EngineHazardError", "engine_check_enabled", "set_engine_check",
           "BoundedCache", "cache_sizes", "flatten_arrays", "unflatten",
           "split_flat", "colocate"]


# --- strict-mode switch (GRAFT_ENGINE_CHECK=1) -----------------------------
# Read per bulk-scope entry (not at import) so tests and debug sessions can
# toggle it without reimporting; set_engine_check overrides the env var.
_engine_check_override = None


def set_engine_check(flag):
    """Force strict mode on/off (None = defer to GRAFT_ENGINE_CHECK)."""
    global _engine_check_override
    _engine_check_override = flag


def engine_check_enabled():
    if _engine_check_override is not None:
        return bool(_engine_check_override)
    return os.environ.get("GRAFT_ENGINE_CHECK", "").strip().lower() \
        in ("1", "true", "yes", "on")


class _Pending(object):
    """Placeholder for a deferred value (knows shape/dtype for metadata
    queries; ``value`` is filled at flush).  ``owners`` holds weakrefs to
    the NDArrays exposing this value: a pending with no live owner at
    flush time is dead (an intermediate the chain rebound) and is NOT
    returned from the replay program — dead-value elimination keeps the
    per-flush output count at what the user actually kept."""
    __slots__ = ("shape", "dtype", "slot", "value", "state", "epoch",
                 "owners", "error", "__weakref__")

    def __init__(self, shape, dtype, slot, state):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.slot = slot
        self.value = None
        self.state = state
        self.epoch = state.epoch
        self.owners = []
        self.error = None

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n


class _BulkState(object):
    def __init__(self, size, check=False):
        self.size = size
        self.check = bool(check)  # strict-mode verifier (GRAFT_ENGINE_CHECK)
        # the scope belongs to the thread that opened it: a deferred
        # value resolved from any OTHER thread flushes this state while
        # its owner may still be recording — grafttsan's EH203 hazard
        self.owner_tid = threading.get_ident()
        if _tsan.enabled():
            _tsan.segment_open(self)    # remember the opening stack
        self.extract_meta = {}   # id(extract _Pending) -> (view weakref,
        #                          base weakref, base._version at record):
        #                          the read side of the strict-mode
        #                          version vector (writes bump
        #                          NDArray._version, so staleness is
        #                          recorded-version != current-version)
        self.epoch = 0           # bumped per flush: "t" refs are only
        #                          valid within their own segment
        self.instructions = []   # (op_name, params, pkey, is_train,
        #                           in_refs, rng_slot, n_out, rec)
        self.ext = []            # concrete jax operands (program inputs)
        self.ext_ids = {}        # id(owner NDArray)|id(value) -> slot
        self.ext_owners = []     # weakref to the NDArray exposing a slot
        self.ext_pins = []       # strong refs pinning owner ids for the
        #                          segment (id() recycling would corrupt
        #                          the dedup table otherwise)
        self.pendings = []       # _Pending objects in slot order
        self.any_recorded = False
        self.seg_id = None       # telemetry segment id, assigned at the
        #                          first recorded instruction (flush spans
        #                          + record-event flow links share it)
        self.flow_marks = []     # instruction indices that emitted a flow
        #                          start ("s") — flush finishes exactly
        #                          these, never a dangling arrow

    def add_ext(self, v, owner=None):
        # dedup by (owner NDArray, buffer): two distinct NDArrays can
        # share a buffer (x and x.detach()) but must keep separate
        # gradient slots, and one NDArray can RE-BIND its buffer
        # mid-segment (an in-place write between deferred ops) and must
        # then get a fresh slot — keying on either identity alone loses
        # one of the two cases.  Owners are pinned in ext_pins so ids
        # cannot be recycled mid-segment (values are pinned via ext).
        key = (id(owner) if owner is not None else None, id(v))
        slot = self.ext_ids.get(key)
        if slot is None:
            self.ext.append(v)
            self.ext_owners.append(weakref.ref(owner) if owner is not None
                                   else None)
            self.ext_pins.append(owner)
            slot = len(self.ext) - 1
            self.ext_ids[key] = slot
        return slot


class BoundedCache(object):
    """Insertion/recency-ordered dict with size-bounded LRU eviction.

    The engine's program caches (`_replay_cache`, `_infer_cache`,
    `_seg_vjp_cache`) and the optimizer's fused-bucket-update cache grow
    one entry per distinct program shape; a long-running trainer that
    keeps changing shapes (dynamic batching, progressive resizing) would
    otherwise hold every compiled program it ever built.  The bound is
    ``GRAFT_REPLAY_CACHE_SIZE`` (default 1024; <= 0 means unbounded),
    read at every insertion so tests and live sessions can re-tune it.
    Eviction drops the least-recently-used entry — closures that already
    captured an evicted value (e.g. a segment vjp held by live tape
    nodes) keep working; only future lookups rebuild."""

    DEFAULT_SIZE = 1024

    def __init__(self, env="GRAFT_REPLAY_CACHE_SIZE"):
        from collections import OrderedDict
        self._env = env
        self._d = OrderedDict()

    def _bound(self):
        try:
            return int(os.environ.get(self._env, str(self.DEFAULT_SIZE)))
        except ValueError:
            return self.DEFAULT_SIZE

    def get(self, key, default=None):
        try:
            self._d.move_to_end(key)
        except KeyError:
            return default
        return self._d[key]

    def __getitem__(self, key):
        self._d.move_to_end(key)
        return self._d[key]

    def __setitem__(self, key, value):
        self._d[key] = value
        self._d.move_to_end(key)
        bound = self._bound()
        if bound > 0:
            while len(self._d) > bound:
                self._d.popitem(last=False)

    def __contains__(self, key):
        return key in self._d

    def __iter__(self):
        return iter(self._d)

    def __len__(self):
        return len(self._d)

    def clear(self):
        self._d.clear()


_tls = threading.local()
_replay_cache = BoundedCache()
_infer_cache = BoundedCache()   # (op, input sig, params, train) -> output
# sig; shape inference via jax.eval_shape costs ~a dispatch itself, so
# recording would be slower than executing without this memo

_FLUSH_CAUSES = ("scope-close", "size-cap", "view", "read", "autograd",
                 "monitor")
_flush_causes = {c: 0 for c in _FLUSH_CAUSES}
_segment_hist = {}   # instructions-per-flush -> count


def flush_stats():
    """Flush-cause counters and the segment-length histogram (counted
    only for flushes that actually executed instructions)."""
    return {"causes": dict(_flush_causes),
            "segment_lengths": dict(_segment_hist)}


def reset_flush_stats():
    for c in _FLUSH_CAUSES:
        _flush_causes[c] = 0
    _segment_hist.clear()
    _tmetrics.reset_engine_metrics()   # keep both views of one event
    #                                    stream in agreement


def _current():
    return getattr(_tls, "state", None)


class bulk(object):
    """Context manager: defer up to ``size`` eager ops per segment."""

    def __init__(self, size=64):
        self.size = max(int(size), 1)
        self._prev = None

    def __enter__(self):
        self._prev = _current()
        _tls.state = _BulkState(self.size, check=engine_check_enabled())
        return self

    def __exit__(self, *exc):
        try:
            flush(cause="scope-close")
        finally:
            _tls.state = self._prev


class offband(object):
    """PUBLIC API — dispatch eagerly ALONGSIDE an open bulk segment
    without joining or flushing it.

    Introduced for graftlap (the Trainer's bucket scheduler firing a
    gradient allreduce from a grad-ready hook mid-backward): work issued
    inside the scope must not become a deferred instruction of whatever
    segment the caller happens to have open (it has to hit the wire
    NOW), and it must not force that segment to materialize either (the
    deferred ops are unrelated).  Inside this scope the bulk state is
    stashed and ops dispatch through the ordinary eager path — XLA's
    async dispatch keeps them concurrent with everything else — while
    the surrounding segment's pending program survives untouched and
    flushes at its own boundary.

    Now documented for user code (ROADMAP "engine offband for user
    code"): any *dispatch now, alongside the open segment* need fits —
    async checkpointing, metric pushes, ad-hoc collectives::

        with mx.engine.bulk(64):
            body()                       # defers into one segment
            with mx.engine.offband():
                checkpoint_shard.copy()  # dispatches immediately
            more_body()                  # same segment keeps recording

    Values produced inside the scope are ordinary concrete NDArrays;
    values from the surrounding segment remain deferred and reading one
    inside the scope still materializes its segment (same rule as any
    read).  See docs/observability.md "Off-band dispatch"."""

    def __enter__(self):
        self._prev = _current()
        _tls.state = None
        return self

    def __exit__(self, *exc):
        _tls.state = self._prev


def maybe_defer(op, params, vals, is_train, kw, rec=False, nd_inputs=None,
                out_reqs=None):
    """Called from the eager invoke: record the op if a bulk scope is
    active and every input is deferrable.  Returns a tuple of _Pending
    outputs, or None to dispatch eagerly.  ``rec`` marks ops being taped
    by autograd: the flush builds one tape node for the whole segment;
    ``nd_inputs`` are the NDArray wrappers (gradient delivery targets).
    ``out_reqs`` — [(slot, shape, dtype_str)] constraints from ``out=``
    write targets: deferral is refused (BEFORE anything is recorded)
    unless the inferred output matches exactly, because a deferred store
    rebinds the target's buffer without the eager path's astype/reshape
    fixups."""
    st = _current()
    if st is None:
        return None
    if len(st.instructions) >= st.size:
        # flush BEFORE recording the next op (never right after one: the
        # freshly created outputs get their owner refs only once invoke
        # wraps them — flushing in between would mis-classify them dead)
        flush(cause="size-cap")
    # deferred records are traced as near-zero "record" events, never as
    # op runtime: the cost lands on the owning segment's flush span, and
    # a chrome-trace flow (s→f) draws the record→flush attribution arrow
    trace = _ttracing.record_active()
    t0 = _profiler._now_us() if trace else 0.0
    from .ops.registry import _hashable
    # stage input refs WITHOUT touching st yet: if we bail (stale
    # pending, failed inference) no orphan ext entries may pollute the
    # replay-cache key
    staged = []
    shapes = []
    for i, v in enumerate(vals):
        if type(v) is _Pending:
            if v.state is not st or v.epoch != st.epoch:
                return None       # cross-scope/segment value: materialize
            staged.append(("t", v, None))
        else:
            owner = nd_inputs[i] if nd_inputs is not None else None
            staged.append(("e", v, owner))
        shapes.append((tuple(v.shape), str(v.dtype)))
    if st.check:
        _strict_check_record(st, op, vals, nd_inputs)
    pkey = _hashable(params)
    ikey = (op.name, tuple(shapes), pkey, bool(is_train))
    out_sig = _infer_cache.get(ikey)
    if out_sig is None:
        try:
            out_sig = op.infer(shapes, params, is_train)
        except Exception:
            return None           # shape inference failed: run eagerly
        _infer_cache[ikey] = out_sig
    if out_reqs is not None:
        for slot, shp, dt in out_reqs:
            if slot >= len(out_sig):
                return None
            oshp, odt = out_sig[slot]
            if tuple(oshp) != tuple(shp) or str(odt) != str(dt):
                return None
    in_refs = [(tag, v.slot if tag == "t" else st.add_ext(v, owner))
               for tag, v, owner in staged]
    rng_slot = st.add_ext(kw["rng"]) if "rng" in kw else None
    outs = []
    for shp, dt in out_sig:
        p = _Pending(shp, dt, len(st.pendings), st)
        st.pendings.append(p)
        outs.append(p)
    st.instructions.append((op.name, dict(params), pkey,
                            bool(is_train), tuple(in_refs), rng_slot,
                            len(outs), bool(rec)))
    st.any_recorded |= bool(rec)
    if st.seg_id is None:
        st.seg_id = _ttracing.next_segment_id()
    if trace:
        idx = len(st.instructions) - 1
        st.flow_marks.append(idx)
        _ttracing.deferred_op_event(op.name, t0, _profiler._now_us(),
                                    st.seg_id, idx)
    return tuple(outs)


def _strict_check_record(st, op, vals, nd_inputs):
    """Record-time hazard checks (GRAFT_ENGINE_CHECK=1): consult the
    read/write version vector of each input's base+view ownership group.
    Reads are the extract_meta entries stamped by defer_view_read; writes
    are NDArray._version bumps — staleness is a version mismatch."""
    for pos, v in enumerate(vals):
        if type(v) is not _Pending:
            continue
        meta = st.extract_meta.get(id(v))
        if meta is None:
            continue
        view_ref, base_ref, ver = meta
        base = base_ref()
        view = view_ref()
        # Staleness is only hazardous when the pending arrives THROUGH
        # the view it extracts: eager semantics would re-read the view
        # post-write there (_read_deferred re-extracts, so a stale
        # arrival means that guard was bypassed).  Reaching the same
        # pending through a different owner — e.g. `w[:] = v` stored the
        # extract into a copy target — is a legal snapshot read of the
        # pre-write value, exactly what the recorded program replays.
        consumer = (nd_inputs[pos] if nd_inputs is not None
                    and pos < len(nd_inputs) else None)
        if base is not None and view is not None and consumer is view \
                and base._version != ver:
            raise EngineHazardError(
                "EH101", "op %r consumes view (shape %s offset %d) "
                "through a _bulk_view_extract recorded at base version %d "
                "but the base has been rebound to version %d since — the "
                "fused replay would read the pre-write value where eager "
                "execution reads the post-write one" % (
                    op.name, view._shape, view._offset, ver, base._version),
                op=op.name, input=pos, recorded_version=ver,
                current_version=base._version,
                group_views=len(base._live_views()))
    if op.name == "_bulk_view_write" and nd_inputs:
        base = nd_inputs[0]
        if base is not None and vals and base._data is not vals[0]:
            raise EngineHazardError(
                "EH102", "_bulk_view_write over a base operand that is no "
                "longer the base's current binding (version %d) — the "
                "rebind would silently discard intervening write(s) "
                "(lost update); ownership group has %d live view(s)"
                % (base._version, len(base._live_views())),
                base_version=base._version,
                group_views=len(base._live_views()))


def defer_view_read(view):
    """Record a ``_bulk_view_extract`` node for a (base, offset, shape)
    view whose base is deferred: the view's value becomes a new _Pending
    in the same program instead of a materialization point.  Returns the
    pending (registered as owned by ``view``), or None when deferral is
    impossible (no scope / cross-scope base) — caller falls back to the
    concrete read, which flushes under the ``view`` cause.

    Recorded with rec=False: in eager execution a view created outside
    recording enters the tape as a constant leaf, so the replay's
    stop_gradient wrap reproduces those semantics exactly.  Views created
    *inside* record() never reach here — reshape/__getitem__ route through
    the registered Reshape/slice_axis ops under recording."""
    st = _current()
    if st is None:
        return None
    base = view._base
    if type(base._data) is not _Pending or base._data.value is not None:
        return None
    from .ops.registry import get_op
    pend = maybe_defer(get_op("_bulk_view_extract"),
                       {"offset": int(view._offset),
                        "shape": tuple(view._shape)},
                       [base._data], False, {}, nd_inputs=[base])
    if pend is None:
        return None
    p = pend[0]
    p.owners.append(weakref.ref(view))
    if st.check:
        # read-side entry of the strict-mode version vector: this extract
        # is valid exactly while the base stays at its current version
        st.extract_meta[id(p)] = (weakref.ref(view), weakref.ref(base),
                                  base._version)
    return p


def defer_view_write(view, value):
    """Record a ``_bulk_view_write`` node: the base's buffer is rebound to
    a new pending whose program node scatters ``value`` (concrete array or
    same-segment pending) over the view's span — write-through to a
    deferred view stays inside the segment.  Returns the base's new
    pending (owned by the base NDArray), or None to fall back to the
    concrete write-through path."""
    st = _current()
    if st is None:
        return None
    base = view._base
    bval = base._data
    if not (type(bval) is _Pending and bval.value is None) \
            and not (type(value) is _Pending and value.value is None):
        return None          # nothing deferred: the concrete path is fine
    from .ops.registry import get_op
    pend = maybe_defer(get_op("_bulk_view_write"),
                       {"offset": int(view._offset)},
                       [bval, value], False, {}, nd_inputs=[base, None])
    if pend is None:
        return None
    p = pend[0]
    p.owners.append(weakref.ref(base))
    return p


def resolve(pending, cause="read"):
    """Materialize one deferred value (flushes its segment if needed)."""
    if pending.value is None:
        if _tsan._ACTIVE[0]:
            # a foreign-thread resolve flushes the owner's open segment
            # mid-recording (EH203) — report before the flush proceeds
            _tsan.check_segment(pending.state)
        flush(pending.state, cause=cause)
    if pending.error is not None:
        raise RuntimeError("bulk engine: the deferred segment holding this "
                           "value failed to execute") from pending.error
    if pending.value is None:  # liveness tracking invariant violated
        raise RuntimeError("bulk engine: deferred value was eliminated as "
                           "dead but later read — please report")
    return pending.value


def _build_replay(instrs, live):
    """Pure replay fn over the ext operand list.  Ops taped by autograd
    keep their gradients; ops that ran outside recording (pause scopes,
    non-differentiable ops) are wrapped in stop_gradient so the segment's
    single vjp matches eager tape semantics exactly."""
    from .ops.registry import get_op
    plan = [(get_op(name).raw(p, train), in_refs, rng_slot, n_out, rec)
            for name, p, _k, train, in_refs, rng_slot, n_out, rec in instrs]

    def replay(ext_vals):
        tmp = []
        for raw, in_refs, rng_slot, n_out, rec in plan:
            args = [ext_vals[i] if tag == "e" else tmp[i]
                    for tag, i in in_refs]
            kw = {"rng": ext_vals[rng_slot]} if rng_slot is not None \
                else {}
            res = raw(*args, **kw)
            if not isinstance(res, tuple):
                res = (res,)
            if not rec:
                res = tuple(jax.lax.stop_gradient(r) for r in res)
            tmp.extend(res)
        return tuple(tmp[i] for i in live)

    return replay


def _rec_reachable_ext(instrs):
    """Ext slots whose gradient path reaches a recorded instruction
    through recorded-op chains only (stop_gradient blocks every other
    path, so those slots are the exact tape-input set).  Inputs an op
    declares ``nograd_inputs`` never receive gradient in eager backward
    (_run_backward's per-op skip), so slots reaching recorded ops SOLELY
    through such positions are excluded too — e.g. BatchNorm's
    moving_mean/moving_var (inputs 3-4) must not land on the tape node."""
    from .ops.registry import get_op
    ext_slots = set()
    pend_deps = []
    for name, _p, _k, _train, in_refs, _rng, n_out, rec in instrs:
        if rec:
            nograd = set(get_op(name).nograd_inputs)
            deps = set()
            for pos, (tag, i) in enumerate(in_refs):
                if pos in nograd:
                    continue
                if tag == "e":
                    deps.add(i)
                else:
                    deps |= pend_deps[i]
            ext_slots |= deps
            out_deps = frozenset(deps)
        else:
            out_deps = frozenset()
        pend_deps.extend([out_deps] * n_out)
    return ext_slots


def _record_segment_node(key, replay, ext, ext_owners, pendings, live,
                         instrs):
    """One tape node for the whole recorded segment: forward already ran
    (the replay); backward is a single jitted vjp of the replay program
    w.r.t. the float ext operands (the reference's train-segment bulking,
    threaded_engine.h MXNET_EXEC_BULK_EXEC_TRAIN)."""
    from . import autograd
    from .operator import Operator

    grad_slots = [i for i, v in enumerate(ext)
                  if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)]
    # Only ext operands that can actually RECEIVE gradient belong on the
    # tape node: slots feeding recorded instructions, directly or through
    # chains of recorded ops (non-recorded outputs are stop_gradient'd,
    # so paths through them are dead — and eager semantics would not put
    # those inputs on the tape at all).
    reachable = _rec_reachable_ext(instrs)
    in_pairs = [(s, ext_owners[s]()) for s in grad_slots
                if s in reachable
                and ext_owners[s] is not None and ext_owners[s]() is not None]
    out_pairs = []          # (position in `live` results, owner NDArray)
    for pos, i in enumerate(live):
        p = pendings[i]
        if not jnp.issubdtype(jnp.dtype(p.dtype), jnp.floating):
            continue
        owner = next((w() for w in p.owners if w() is not None), None)
        if owner is not None:
            out_pairs.append((pos, owner))
    if not in_pairs or not out_pairs:
        return
    out_pos = tuple(pos for pos, _ in out_pairs)

    vjp_key = (key, tuple(grad_slots), out_pos)
    vjp_fn = _seg_vjp_cache.get(vjp_key)
    if vjp_fn is None:
        def vjp_calc(ext_vals, cts):
            def f(fvals):
                full = list(ext_vals)
                for s, v in zip(grad_slots, fvals):
                    full[s] = v
                outs = replay(full)
                return tuple(outs[pos] for pos in out_pos)
            _, pullback = jax.vjp(f, tuple(ext_vals[s]
                                           for s in grad_slots))
            return pullback(tuple(cts))[0]
        vjp_fn = jax.jit(vjp_calc)
        _seg_vjp_cache[vjp_key] = vjp_fn

    keep = {s: j for j, s in enumerate(grad_slots)}
    in_slots = [s for s, _ in in_pairs]
    nd_inputs = [nd for _, nd in in_pairs]
    nd_outputs = [nd for _, nd in out_pairs]

    def seg_vjp(ct):
        cts = ct if isinstance(ct, tuple) else (ct,)
        grads = vjp_fn(ext, tuple(cts))
        return tuple(grads[keep[s]] for s in in_slots)

    def seg_fn(*in_vals):
        full = list(ext)
        for s, v in zip(in_slots, in_vals):
            full[s] = v
        outs = replay(full)
        picked = tuple(outs[pos] for pos in out_pos)
        return picked[0] if len(picked) == 1 else picked

    op = Operator("_BulkSegment", lambda *a: a,
                  num_inputs=len(nd_inputs), num_outputs=len(nd_outputs))
    # re-wrap outputs? no: the live NDArrays already exist — record against
    # them so downstream recorded ops chain through this node
    autograd._record(op, nd_inputs, nd_outputs, seg_vjp, fn=seg_fn)


def flush(state=None, cause="read"):
    """Compile (cached) + run the pending segment; fill every _Pending."""
    st = state if state is not None else _current()
    if st is None or not st.instructions:
        return
    _flush_causes[cause] = _flush_causes.get(cause, 0) + 1
    _segment_hist[len(st.instructions)] = \
        _segment_hist.get(len(st.instructions), 0) + 1
    _tmetrics.engine_flush(cause, len(st.instructions))
    instrs = st.instructions
    ext = st.ext
    ext_owners = st.ext_owners
    pendings = st.pendings
    recorded = st.any_recorded
    seg_id = st.seg_id
    flow_marks = st.flow_marks
    # reset the scope so new ops start a fresh segment (and so re-entrant
    # flushes from _read during execution see an empty program)
    st.instructions, st.ext, st.pendings = [], [], []
    st.ext_ids = {}
    st.ext_owners = []
    st.ext_pins = []
    st.any_recorded = False
    st.extract_meta = {}
    st.seg_id = None
    st.flow_marks = []
    st.epoch += 1

    err = None
    if st.check:
        # EH103 — validate operand references AFTER the state reset, so a
        # hazard raised here leaves the scope reusable (the scope-close
        # flush sees an empty program instead of re-raising); stamp the
        # hazard on every pending so later reads surface IT, not the
        # misleading liveness invariant error
        try:
            check_segment_integrity(instrs, len(ext))
        except EngineHazardError as exc:
            for p in pendings:
                p.error = exc
            err = exc

    # only values still EXPOSED through a live NDArray leave the program:
    # the owner must not just be alive, its buffer must still be this
    # pending — a chained out= store rebinds the owner to each successive
    # pending, and without the `_data is p` check every superseded
    # intermediate would escape the program as a dead output (review
    # finding, round 5: N-long update chains shipped N-1 dead buffers).
    # A view owner additionally needs its extract to be CURRENT: once the
    # base version moves past the view's cache, every read recomputes
    # from the base and the stale extract can never be resolved — it is
    # dead even though `_data is p` still holds
    live = tuple(i for i, p in enumerate(pendings)
                 if any(o is not None and o._data is p
                        and (o._base is None
                             or o._cache_version == o._base._version)
                        for o in (w() for w in p.owners)))
    key = (tuple((name, pkey, train, in_refs, rng_slot, n_out, rec)
                 for name, _p, pkey, train, in_refs, rng_slot, n_out, rec
                 in instrs),
           tuple((tuple(v.shape), str(v.dtype)) for v in ext),
           live)
    prof_on = _profiler._P.active()
    bb_on = _blackbox.enabled()
    span_begin = _profiler._now_us() if prof_on else 0.0
    t0 = time.perf_counter() if bb_on else 0.0
    results = None
    cache_hit = False
    if err is None:
        entry = _replay_cache.get(key)
        cache_hit = entry is not None
        if entry is None:
            replay = _build_replay(instrs, live)
            entry = (jax.jit(replay), replay)
            _replay_cache[key] = entry
        fn, replay = entry
        try:
            # the program span (mx:engine_flush in a profiler trace, a
            # record in telemetry.spans()) around the graftwatch bracket:
            # a stalled dispatch shows up in-flight (the watchdog names
            # this segment when it trips)
            with _ttracing.phase_span("engine_flush", {"cause": cause}), \
                    _blackbox.in_flight("engine_flush",
                                        {"segment": seg_id, "cause": cause,
                                         "nodes": len(instrs)}):
                results = fn(ext)
                if st.check and results:
                    # EH104 — the fusion-equivalence oracle: replay the
                    # segment UNFUSED (the same replay closure outside jit
                    # dispatches each op eagerly) and bit-compare every
                    # live output.  Costs a full second execution per
                    # flush; debug-only by construction.
                    oracle_compare(results, replay(ext), instrs, live)
        except Exception as exc:
            # stamp every pending with the real cause: later reads raise
            # THIS instead of a misleading liveness error
            for p in pendings:
                p.error = exc
            err = exc
    if bb_on:
        fields = {"segment": seg_id, "cause": cause, "nodes": len(instrs),
                  "live_outputs": len(live),
                  "cache": "hit" if cache_hit else "miss",
                  "latency_ms": round((time.perf_counter() - t0) * 1e3, 3)}
        if err is not None:
            fields["error"] = repr(err)
        _blackbox.record("engine_flush", **fields)
    if prof_on or flow_marks:
        # the segment span is where op cost actually lands: with
        # profiler.sync the dispatch blocks until ready, so the span IS
        # device latency (the flush-level analogue of sync-mode op spans).
        # A segment whose records emitted flow starts ALWAYS closes its
        # links here — profiler stopped mid-segment OR replay raised —
        # a dangling arrow would fail the trace validator
        device_time = _profiler.want_sync()
        if device_time and results:
            jax.block_until_ready(results)
        begin = span_begin if prof_on else _profiler._now_us()
        _ttracing.segment_flush_span(
            seg_id, cause, begin, _profiler._now_us(),
            flow_marks, len(instrs), len(live), cache_hit,
            recorded, device_time, error=err is not None)
    if err is not None:
        raise err
    for i, v in zip(live, results):
        pendings[i].value = v
    if recorded:
        _record_segment_node(key, replay, ext, ext_owners, pendings, live,
                             instrs)
    if results:
        # nd.waitall()'s WaitForAll contract covers bulk dispatches too
        from .ndarray import ndarray as _nd
        devs = getattr(results[0], "devices", None)
        if devs is not None:
            try:
                _nd._DISPATCH_DEVICES.update(devs())
            except Exception:
                pass


_seg_vjp_cache = BoundedCache()


def cache_sizes():
    """Current entry counts of the engine's bounded program caches (the
    ``graft_engine_replay_cache_size`` gauge reads these)."""
    return {"replay": len(_replay_cache),
            "infer": len(_infer_cache),
            "seg_vjp": len(_seg_vjp_cache),
            "split": len(_split_cache)}


# ---------------------------------------------------------------------------
# shared flatten/unflatten glue (graftfuse)
# ---------------------------------------------------------------------------
# The bucketed Trainer.step path and the dist kvstore's dtype-grouped
# allreduce both pack many small arrays into one flat buffer and back.
# ONE jitted flattener (jax's jit cache specializes it per signature) and
# one statically-sliced unflatten live here so the packing math exists in
# exactly one place.

@jax.jit
def flatten_arrays(arrs):
    """Concatenate a tuple of arrays into one flat buffer (one dispatch)."""
    return jnp.concatenate([a.reshape(-1) for a in arrs])


def unflatten(flat, shapes):
    """Pure slicing of ``flat`` back into ``shapes`` — static offsets, so
    it traces cleanly inside an outer jit (the fused optimizer programs
    inline it; XLA fuses the slices away)."""
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offs = np.concatenate([[0], np.cumsum(sizes)]).tolist()
    return tuple(
        jax.lax.slice(flat, (offs[i],), (offs[i + 1],)).reshape(shapes[i])
        for i in range(len(shapes)))


_split_cache = BoundedCache()


def split_flat(flat, shapes):
    """Eager companion of :func:`unflatten`: one cached jitted dispatch
    that splits a flat buffer into per-shape arrays."""
    shapes = tuple(tuple(s) for s in shapes)
    key = (shapes, str(flat.dtype))
    fn = _split_cache.get(key)
    if fn is None:
        fn = jax.jit(lambda f: unflatten(f, shapes))
        _split_cache[key] = fn
    return fn(flat)


def colocate(val, ref):
    """``val`` on ``ref``'s committed device (a no-op when they already
    share one, or when placement cannot be determined).

    The committed-device-safe glue for multi-context replica math: a
    context list like ``[cpu(0) .. cpu(7)]`` commits each replica to its
    own jax device, and jax refuses elementwise ops (and jit calls) that
    mix arrays committed to different devices — so every cross-context
    tree-sum, flat-bucket broadcast and store→replica pull must move the
    operand first.  Transfers preserve bits, so the bit-parity contracts
    of the fused/overlapped step paths are unaffected."""
    try:
        vd = val.devices()
        rd = ref.devices()
    except Exception:
        return val          # tracers / non-jax values carry no placement
    if vd == rd or len(rd) != 1:
        return val
    return jax.device_put(val, next(iter(rd)))
