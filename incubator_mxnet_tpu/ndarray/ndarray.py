"""NDArray: the mutable n-dimensional array over immutable XLA buffers.

TPU-native rebirth of include/mxnet/ndarray.h + src/ndarray/ndarray.cc:

* The reference's ``Chunk`` (storage handle + engine variable) becomes a
  root ``jax.Array`` plus a monotonically increasing version counter — the
  version counter is the dependency-engine variable reborn (SURVEY §7 hard
  part #1).  In-place ops swap the root buffer and bump the version.
* Views (``Slice``/``At``/``Reshape``, ndarray.h:523) are (base, elem-offset,
  shape) triples — exactly the contiguous row-major views the reference
  supports — that re-materialize lazily when the base version moves, and
  write through with a scatter into the base buffer.
* Async semantics: every op call is an XLA async dispatch; ``wait_to_read``/
  ``waitall`` map to ``jax.block_until_ready`` — the WaitToRead/WaitForAll
  contract of the engine (include/mxnet/engine.h) holds verbatim.
* ``asnumpy`` is the sync point, as in the reference (ndarray.h:304).
"""
from __future__ import annotations

import weakref

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import Context, current_context, cpu
from ..ops.registry import get_op, Operator
from .. import random_state
from .. import config as _config
from ..analysis import tsan as _tsan

# MXTPU_ENGINE_TYPE=NaiveEngine → block after every dispatch (the
# reference's synchronous debug engine, src/engine/naive_engine.cc);
# read once at import like dmlc::GetEnv's static locals.
_NAIVE_ENGINE = _config.naive_engine()

# devices that have received dispatches, for waitall() (WaitForAll):
# XLA executes compute in dispatch order per device stream, so enqueueing
# a trivial computation and blocking on it drains everything before it —
# a stream barrier, with no output buffers pinned.
_DISPATCH_DEVICES = set()

__all__ = ["NDArray", "array", "empty", "invoke", "waitall",
           "concatenate", "moveaxis", "imperative_invoke"]


def _default_dtype_for(source):
    if isinstance(source, np.ndarray):
        if source.dtype == np.float64 and not jax.config.jax_enable_x64:
            return np.float32
        return source.dtype
    return np.float32


class NDArray:
    """Mutable array handle (parity: python/mxnet/ndarray/ndarray.py NDArray)."""

    __array_priority__ = 1000.0  # beat numpy in mixed expressions

    # graftduplex first-touch hook: set per-instance by overlap.
    # PullScheduler on arrays with an async weight pull in flight; the
    # FIRST read waits the pull before the value escapes.  A class-level
    # default keeps the hot-path check in _read to one attribute load
    # that normally resolves here (None).
    _touch_hook = None

    def __init__(self, data=None, ctx=None, base=None, offset=0, shape=None):
        self._ctx = ctx if ctx is not None else current_context()
        if base is not None:
            # view
            self._base = base
            self._offset = int(offset)
            self._shape = tuple(shape)
            self._data = None
            self._cache_version = -1
            base._register_view(self)
        else:
            self._base = None
            self._offset = 0
            self._data = data
            self._shape = tuple(data.shape) if data is not None else None
            self._cache_version = 0
        self._version = 0
        # autograd state
        self._grad = None
        self._grad_req = "null"
        self._tape_ref = None  # (TapeNode, out_index) set by autograd

    # -- storage access ----------------------------------------------------
    def _root(self):
        return self._base if self._base is not None else self

    # -- view-group bookkeeping --------------------------------------------
    # Every root tracks weakrefs to the views cut from it, so base+views
    # form an inspectable OWNERSHIP GROUP: the strict-mode engine verifier
    # (GRAFT_ENGINE_CHECK=1, engine.py) walks the group to report which
    # sibling extracts a hazardous rebind invalidated, and liveness
    # debugging can enumerate who still exposes a buffer.  A plain list of
    # weakrefs, NOT a WeakSet: NDArray.__eq__ is elementwise broadcast, so
    # any hash-bucket collision inside a WeakSet would try to truth-test
    # an array.
    def _register_view(self, view):
        views = getattr(self, "_views", None)
        if views is None:
            views = self._views = []
        views.append(weakref.ref(view))
        # amortized O(1) on the hot __getitem__/reshape path: compact the
        # dead refs only once the list doubles past the last compaction
        if len(views) >= getattr(self, "_views_compact_at", 32):
            views[:] = [w for w in views if w() is not None]
            self._views_compact_at = max(32, 2 * len(views))

    def _live_views(self):
        """Live view NDArrays cut from this root (empty for views)."""
        views = getattr(self, "_views", None)
        if not views:
            return ()
        alive = [w() for w in views]
        views[:] = [w for w, v in zip(views, alive) if v is not None]
        return tuple(v for v in alive if v is not None)

    def _view_group(self):
        """(root, live views of that root) — the ownership group this
        array belongs to, whichever side of the base/view split it is."""
        root = self._root()
        return root, root._live_views()

    def _read(self, cause="read"):
        """Current jax.Array value (no host sync).  ``cause`` labels any
        flush this read forces: "read" for direct host reads of deferred
        values, "view" only when the _read_deferred fallback lands here
        after a view failed to defer."""
        th = self._touch_hook
        if th is not None:
            # first use of a weight with an async pull in flight: the
            # hook clears itself, then waits the pull group so the value
            # returned below is the pulled one (graftduplex)
            th(self)
        if _tsan._ACTIVE[0]:
            _tsan.on_read(self)     # EH204 for tracked shared arrays
        eng = _engine_mod()
        if self._base is None:
            if type(self._data) is eng._Pending:
                self._data = eng.resolve(self._data, cause=cause)
            return self._data
        b = self._base
        bth = b._touch_hook
        if bth is not None:
            # a view read IS a first use of its base: the slice below
            # reads b._data, so a pending pull on the base must land
            # first (the dist_async path defers its writes to wait time)
            bth(b)
        if (type(self._data) is eng._Pending
                and self._cache_version == b._version):
            # a deferred view extraction for the current base version:
            # resolving it flushes the shared segment (base fills too)
            self._data = eng.resolve(self._data, cause=cause)
            return self._data
        if type(b._data) is eng._Pending:
            b._data = eng.resolve(b._data, cause=cause)
        if self._cache_version != b._version or self._data is None \
                or type(self._data) is eng._Pending:
            flat = b._data.reshape((-1,))
            size = int(np.prod(self._shape)) if self._shape else 1
            self._data = jax.lax.slice(flat, (self._offset,), (self._offset + size,)).reshape(self._shape)
            self._cache_version = b._version
        return self._data

    def _read_deferred(self):
        """Like _read, but inside an active bulk scope an unresolved
        deferred value is returned as its _Pending placeholder so op
        chains keep deferring (engine.py maybe_defer).  A view over a
        deferred base becomes a recorded ``_bulk_view_extract`` pending
        (round 6) instead of a materialization point."""
        eng = _engine_mod()
        d = self._data
        if self._base is None:
            if type(d) is eng._Pending and d.value is None:
                return d
            return self._read()
        b = self._base
        if type(b._data) is eng._Pending and b._data.value is None:
            if (type(d) is eng._Pending and d.value is None
                    and self._cache_version == b._version):
                return d            # extraction already recorded this epoch
            p = eng.defer_view_read(self)
            if p is not None:
                self._data = p
                self._cache_version = b._version
                return p
            # deferral failed (cross-scope base …): this flush IS view
            # fragmentation — attribute it so the counters catch it
            return self._read(cause="view")
        return self._read()

    def _write(self, value):
        """Replace contents (in-place semantics; bumps the version 'var').

        ``value`` may be a _Pending (deferred op output): roots simply
        rebind to it, and a view over a deferred base records the
        write-through as a ``_bulk_view_write`` node so the whole
        read-modify-write stays in one segment."""
        if _tsan._ACTIVE[0]:
            # grafttsan: a cross-thread write to an array an async
            # reduce/pull handle still holds (EH201), or to a tracked
            # shared array without a happens-before edge (EH204).  The
            # raw flag (not enabled()) keeps the disabled cost of this
            # hot path to one attribute load + index
            _tsan.on_write(self)
        eng = _engine_mod()
        if type(value) is eng._Pending:
            value.owners.append(weakref.ref(self))
        if self._base is None:
            self._data = value
            self._version += 1
            return
        b = self._base
        newbase = eng.defer_view_write(self, value)
        if newbase is None:
            # non-deferrable write-through: any flush these resolves force
            # is view fragmentation
            if type(value) is eng._Pending:
                value = eng.resolve(value, cause="view")
            if type(b._data) is eng._Pending:
                b._data = eng.resolve(b._data, cause="view")
            flat = b._data.reshape((-1,))
            flat = jax.lax.dynamic_update_slice(
                flat, value.reshape((-1,)).astype(b._data.dtype),
                (self._offset,))
            newbase = flat.reshape(b._data.shape)
        b._data = newbase
        b._version += 1
        self._data = value
        self._cache_version = b._version

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @property
    def ndim(self):
        return len(self._shape)

    @property
    def size(self):
        return int(np.prod(self._shape)) if self._shape else 1

    @property
    def dtype(self):
        d = self._root()._data
        if d is not None:
            # the root's buffer answers for views too, and works for
            # concrete arrays AND deferred placeholders — metadata
            # queries must not force a bulk flush
            return np.dtype(d.dtype)
        return np.dtype(self._read().dtype)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        return invoke(get_op("transpose"), [self], {})

    @property
    def grad(self):
        return self._grad

    # -- conversion --------------------------------------------------------
    def asnumpy(self):
        """Host copy; blocks — the reference's WaitToRead+copy sync point."""
        return np.asarray(self._read())

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def astype(self, dtype, copy=True):
        out = invoke(get_op("Cast"), [self], {"dtype": np.dtype(dtype).name})
        return out

    def copy(self):
        return invoke(get_op("_copy"), [self], {})

    def copyto(self, other):
        """ref: ndarray.py copyto / CopyFromTo (src/ndarray/ndarray.cc)."""
        if isinstance(other, NDArray):
            other._write(self._read().astype(other.dtype))
            return other
        if isinstance(other, Context):
            data = jax.device_put(self._read(), Context(other).jax_device())
            return NDArray(data, ctx=Context(other))
        raise TypeError("copyto does not support type %s" % type(other))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def detach(self):
        """Strip autograd history (ref: ndarray.h:523 Detach)."""
        out = NDArray(self._read(), ctx=self._ctx)
        return out

    # -- autograd ----------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """ref: python/mxnet/ndarray/ndarray.py attach_grad → MarkVariables."""
        from .. import autograd
        grad = NDArray(jnp.zeros_like(self._read()), ctx=self._ctx)
        self._grad = grad
        self._grad_req = grad_req
        autograd.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- sync --------------------------------------------------------------
    def wait_to_read(self):
        jax.block_until_ready(self._read())

    def wait_to_write(self):
        jax.block_until_ready(self._read())

    # -- shape manipulation (views) ---------------------------------------
    def reshape(self, *shape, **kwargs):
        """Returns a *view* sharing storage (ref: ndarray.h Reshape)."""
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        from ..ops.tensor import infer_reshape
        new_shape = infer_reshape(self._shape, shape, kwargs.get("reverse", False))
        if int(np.prod(new_shape)) != self.size:
            raise ValueError("cannot reshape %s into %s" % (self._shape, new_shape))
        from .. import autograd
        if autograd.is_recording():
            # under recording, views must be tape ops so gradients flow
            # (the reference records Reshape nodes on the tape too)
            return invoke(get_op("Reshape"), [self], {"shape": tuple(new_shape)})
        root = self._root()
        return NDArray(ctx=self._ctx, base=root, offset=self._offset, shape=new_shape)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        shape = list(self._shape)
        shape.insert(axis if axis >= 0 else axis + self.ndim + 1, 1)
        return self.reshape(tuple(shape))

    def flatten(self):
        return invoke(get_op("Flatten"), [self], {})

    def _view_slice(self, start, stop):
        """Axis-0 contiguous view (ref: NDArray::Slice, ndarray.h:304)."""
        n = self._shape[0]
        start = 0 if start is None else (start + n if start < 0 else start)
        stop = n if stop is None else (stop + n if stop < 0 else min(stop, n))
        if not 0 <= start <= stop <= n:
            raise IndexError("slice [%s:%s) out of range for axis size %d" % (start, stop, n))
        row = int(np.prod(self._shape[1:])) if len(self._shape) > 1 else 1
        root = self._root()
        return NDArray(ctx=self._ctx, base=root,
                       offset=self._offset + start * row,
                       shape=(stop - start,) + self._shape[1:])

    def slice(self, start, stop):
        return self._view_slice(start, stop)

    def at(self, idx):
        """ref: NDArray::At — index into axis 0, drop the axis."""
        v = self._view_slice(idx, idx + 1)
        return v.reshape(self._shape[1:] if len(self._shape) > 1 else (1,))

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        from .. import autograd
        if isinstance(key, int):
            if autograd.is_recording():
                n = self._shape[0]
                k = key + n if key < 0 else key
                out = invoke(get_op("slice_axis"), [self],
                             {"axis": 0, "begin": k, "end": k + 1})
                return invoke(get_op("Reshape"), [out], {"shape": tuple(self._shape[1:]) or (1,)})
            return self.at(key)
        if isinstance(key, slice):
            if key.step is None or key.step == 1:
                if autograd.is_recording():
                    n = self._shape[0]
                    b = 0 if key.start is None else (key.start + n if key.start < 0 else key.start)
                    e = n if key.stop is None else (key.stop + n if key.stop < 0 else min(key.stop, n))
                    return invoke(get_op("slice_axis"), [self],
                                  {"axis": 0, "begin": b, "end": e})
                return self._view_slice(key.start, key.stop)
            return NDArray(self._read()[key], ctx=self._ctx)
        if isinstance(key, NDArray):
            return NDArray(jnp.take(self._read(), key._read().astype(jnp.int32), axis=0),
                           ctx=self._ctx)
        if isinstance(key, (list, np.ndarray)):
            return NDArray(jnp.take(self._read(), jnp.asarray(key, jnp.int32), axis=0),
                           ctx=self._ctx)
        if isinstance(key, tuple):
            # general basic indexing → copy (matches reference semantics for
            # multi-axis indexing)
            key = tuple(k._read().astype(jnp.int32) if isinstance(k, NDArray) else k
                        for k in key)
            return NDArray(self._read()[key], ctx=self._ctx)
        raise TypeError("indexing with %r not supported" % (key,))

    def __setitem__(self, key, value):
        if isinstance(key, slice) and key.start is None and key.stop is None and key.step is None:
            # full-slice store: shape/dtype metadata suffices, so no read
            # of self — a deferred target (or view over one) stays in the
            # open bulk segment and the store records as a program node
            dt = self.dtype
            if isinstance(value, NDArray):
                if value._shape == self._shape and np.dtype(value.dtype) == dt:
                    self._write(value._read_deferred())
                else:
                    self._write(jnp.broadcast_to(value._read().astype(dt),
                                                 self._shape))
            elif isinstance(value, (int, float, bool, np.generic)):
                self._write(jnp.full(self._shape, value, dt))
            else:
                self._write(jnp.broadcast_to(jnp.asarray(value).astype(dt),
                                             self._shape))
            return
        if isinstance(value, NDArray):
            val = value._read()
        elif isinstance(value, (int, float, bool, np.generic)):
            val = None  # fill scalar below
        else:
            val = jnp.asarray(value)
        cur = self._read()
        key2 = key
        if isinstance(key2, NDArray):
            key2 = key2._read().astype(jnp.int32)
        elif isinstance(key2, tuple):
            key2 = tuple(k._read().astype(jnp.int32) if isinstance(k, NDArray) else k
                         for k in key2)
        if val is None:
            new = cur.at[key2].set(value)
        else:
            new = cur.at[key2].set(val.astype(cur.dtype))
        self._write(new)

    # -- python protocol ---------------------------------------------------
    def __len__(self):
        return self._shape[0]

    def __iter__(self):
        for i in range(self._shape[0]):
            yield self[i]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except Exception as e:  # pragma: no cover
            body = "<unreadable: %s>" % e
        shape_info = "x".join(str(s) for s in self._shape)
        return "\n%s\n<%s %s @%s>" % (body, type(self).__name__, shape_info, self._ctx)

    # -- arithmetic --------------------------------------------------------
    def _binop(self, other, op_name, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke(get_op(op_name), [a, b], {})
        if isinstance(other, (int, float, bool, np.generic)):
            return invoke(get_op(scalar_op), [self], {"scalar": float(other)})
        if isinstance(other, np.ndarray):
            o = array(other, ctx=self._ctx)
            a, b = (o, self) if reverse else (self, o)
            return invoke(get_op(op_name), [a, b], {})
        return NotImplemented

    def __add__(self, o):
        return self._binop(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        if isinstance(o, (int, float, bool, np.generic)):
            return invoke(get_op("_rminus_scalar"), [self], {"scalar": float(o)})
        return self._binop(o, "broadcast_sub", "_minus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, o):
        if isinstance(o, (int, float, bool, np.generic)):
            return invoke(get_op("_rdiv_scalar"), [self], {"scalar": float(o)})
        return self._binop(o, "broadcast_div", "_div_scalar", reverse=True)

    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        if isinstance(o, (int, float, bool, np.generic)):
            return invoke(get_op("_rmod_scalar"), [self], {"scalar": float(o)})
        return self._binop(o, "broadcast_mod", "_mod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        if isinstance(o, (int, float, bool, np.generic)):
            return invoke(get_op("_rpower_scalar"), [self], {"scalar": float(o)})
        return self._binop(o, "broadcast_power", "_power_scalar", reverse=True)

    def __neg__(self):
        return invoke(get_op("negative"), [self], {})

    def __abs__(self):
        return invoke(get_op("abs"), [self], {})

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def _inplace(self, other, op_name, scalar_op):
        res = self._binop(other, op_name, scalar_op)
        if res._shape == self._shape \
                and np.dtype(res.dtype) == np.dtype(self.dtype):
            # may hand a _Pending to _write: the read-modify-write stays
            # inside the open bulk segment (views write through as a
            # recorded scatter node)
            self._write(res._read_deferred())
        else:
            self._write(res._read().astype(self.dtype))
        return self

    def __iadd__(self, o):
        return self._inplace(o, "broadcast_add", "_plus_scalar")

    def __isub__(self, o):
        return self._inplace(o, "broadcast_sub", "_minus_scalar")

    def __imul__(self, o):
        return self._inplace(o, "broadcast_mul", "_mul_scalar")

    def __itruediv__(self, o):
        return self._inplace(o, "broadcast_div", "_div_scalar")

    __idiv__ = __itruediv__

    # convenience methods mirroring the reference's method surface
    def sum(self, *args, **kwargs):
        return _call("sum", self, *args, **kwargs)

    def mean(self, *args, **kwargs):
        return _call("mean", self, *args, **kwargs)

    def max(self, *args, **kwargs):
        return _call("max", self, *args, **kwargs)

    def min(self, *args, **kwargs):
        return _call("min", self, *args, **kwargs)

    def argmax(self, *args, **kwargs):
        return _call("argmax", self, *args, **kwargs)

    def argmin(self, *args, **kwargs):
        return _call("argmin", self, *args, **kwargs)

    def abs(self):
        return invoke(get_op("abs"), [self], {})

    def square(self):
        return invoke(get_op("square"), [self], {})

    def sqrt(self):
        return invoke(get_op("sqrt"), [self], {})

    def exp(self):
        return invoke(get_op("exp"), [self], {})

    def log(self):
        return invoke(get_op("log"), [self], {})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke(get_op("transpose"), [self], {"axes": axes})

    def clip(self, a_min, a_max):
        return invoke(get_op("clip"), [self], {"a_min": a_min, "a_max": a_max})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse
        return sparse.cast_storage(self, stype)

    def as_nd_ndarray(self):
        return self


def _call(name, *args, **kwargs):
    from . import register as _reg
    return getattr(_reg.module_surface, name)(*args, **kwargs)


_ENGINE = None


def _engine_mod():
    global _ENGINE
    if _ENGINE is None:
        from .. import engine
        _ENGINE = engine
    return _ENGINE


# ---------------------------------------------------------------------------
# eager op invocation (the imperative runtime; ref: src/imperative/imperative.cc)
# ---------------------------------------------------------------------------

def invoke(op: Operator, inputs, params, out=None):
    """Eager dispatch of one operator — Imperative::Invoke reborn.

    inputs: list[NDArray]; params: dict of static attributes.
    Handles: jit-cached dispatch, PRNG key supply, autograd tape recording
    (jax.vjp), aux-output write-back for mutating ops, `out=` stores.
    """
    from .. import autograd

    params = {k: v for k, v in params.items() if v is not None or k in ("axis",)}
    ctx_override = params.pop("ctx", None)
    params.pop("name", None)
    is_train = autograd.is_training()
    recording = autograd.is_recording() and op.differentiable

    # engine bulking (threaded_engine.h BulkAppend reborn): inside a
    # `with mx.engine.bulk()` scope, pure eager ops are recorded and later
    # replayed as ONE jitted program instead of dispatched one by one
    kw = {}
    if op.needs_rng:
        kw["rng"] = random_state.next_key()

    _eng = _engine_mod()
    if (_eng._current() is not None
            and ctx_override is None
            and not _NAIVE_ENGINE and not getattr(op, "no_jit", False)
            and not (out is not None and recording)):
        # ``out=`` stores and mutating ops (optimizer updates) are
        # deferrable too (round 5 — the reference bulks optimizer updates
        # inside train segments, threaded_engine.h:472-509): the write
        # plan below rebinds each target's buffer to its pending output
        # at record time, so downstream deferred ops chain through the
        # updated value and the whole train step flushes as ONE program.
        # Requirements: non-view plain-dense targets and exact
        # shape/dtype match (checked via out_reqs before recording —
        # the eager path's astype/write-through fixups don't apply to a
        # buffer rebind).
        write_plan = None       # [(output slot, target NDArray)]
        deferrable = True
        if out is not None:
            touts = [out] if isinstance(out, NDArray) else list(out)
            if op.mutate_inputs:
                write_plan = [(0, touts[0])] + [
                    (j + 1, inputs[idx])
                    for j, idx in enumerate(op.mutate_inputs[1:])]
            elif op.fvisible is None and len(touts) <= op.num_visible_outputs:
                # visible outputs come first, so target i <- output i
                write_plan = list(enumerate(touts))
            else:
                deferrable = False  # dynamic visibility: eager fixups apply
            deferrable = deferrable and all(
                type(t) is NDArray and t._base is None
                for _, t in (write_plan or ()))
        if deferrable:
            vals = [a._read_deferred() for a in inputs]
            out_reqs = None if write_plan is None else [
                (slot, t._shape, str(np.dtype(t.dtype)))
                for slot, t in write_plan]
            pend = _eng.maybe_defer(op, params, vals, is_train, kw,
                                    rec=recording, nd_inputs=inputs,
                                    out_reqs=out_reqs)
            if pend is not None:
                if write_plan is not None:
                    for slot, t in write_plan:
                        t._write(pend[slot])   # registers t as owner
                    return touts[0] if len(touts) == 1 else touts
                ctx = inputs[0]._ctx if inputs else current_context()
                out_arrays = []
                for p in pend:
                    nd_out = NDArray(p, ctx=ctx)
                    p.owners.append(weakref.ref(nd_out))
                    out_arrays.append(nd_out)
                n_vis = op.visible_outputs(params, len(out_arrays))
                visible = out_arrays[:n_vis]
                return visible[0] if len(visible) == 1 else visible

    vals = [a._read() for a in inputs]

    from .. import profiler as _profiler
    # async dispatch: the span is dispatch time unless sync mode blocks
    # until ready inside it — the event says which (graftscope satellite:
    # op durations must never masquerade as device latency)
    _span = _profiler.op_span(op.name, "imperative",
                              args={"device_time": _profiler.want_sync()})
    if _span is not None:
        _span.__enter__()
    try:
        if recording:
            fn = op.bind(params, is_train)
            if kw:
                rng = kw["rng"]
                wrapped = lambda *xs: fn(*xs, rng=rng)
            else:
                wrapped = fn
            out_vals, vjp_fn = jax.vjp(wrapped, *vals)
        else:
            fn = op.bind(params, is_train)
            out_vals = fn(*vals, **kw)
            vjp_fn = None
    except Exception as exc:
        # close the span on the exception path too: a crash-time trace
        # must not lose the op that raised (graftwatch satellite)
        if _span is not None:
            _span.__exit__(type(exc), exc, None)
        raise
    if _span is not None:
        if _profiler.want_sync():
            # sync mode: the span closes when the device is done, so its
            # duration is this op's device latency (cache-miss calls
            # still include jit compile)
            jax.block_until_ready(out_vals)
        _span.__exit__()
    if _NAIVE_ENGINE:
        jax.block_until_ready(out_vals)
    first = out_vals[0] if isinstance(out_vals, tuple) else out_vals
    devs = getattr(first, "devices", None)
    if devs is not None:
        try:
            _DISPATCH_DEVICES.update(devs())
        except Exception:       # tracers inside jit have no devices
            pass

    if not isinstance(out_vals, tuple):
        out_vals = (out_vals,)

    if ctx_override is not None:
        ctx = Context(ctx_override)
        dev = ctx.jax_device()
        out_vals = tuple(jax.device_put(v, dev) for v in out_vals)
    else:
        ctx = inputs[0]._ctx if inputs else current_context()
    out_arrays = [NDArray(v, ctx=ctx) for v in out_vals]

    if recording:
        autograd._record(op, list(inputs), out_arrays, vjp_fn, fn=wrapped)

    n_visible = op.visible_outputs(params, len(out_arrays))

    # mutating ops (optimizer updates): write hidden state outputs back into
    # the declared mutable inputs (ref: optimizer ops write their state in
    # place via kWriteInplace)
    if out is not None and op.mutate_inputs:
        targets = [out] if isinstance(out, NDArray) else list(out)
        targets[0]._write(out_vals[0].astype(targets[0].dtype))
        for extra_val, in_idx in zip(out_vals[1:], op.mutate_inputs[1:]):
            inputs[in_idx]._write(extra_val.astype(inputs[in_idx].dtype))
        return targets[0] if len(targets) == 1 else targets
    if out is not None:
        targets = [out] if isinstance(out, NDArray) else list(out)
        for t, v in zip(targets, out_vals[:n_visible]):
            t._write(v.astype(t.dtype))
        return targets[0] if len(targets) == 1 else targets

    visible = out_arrays[:n_visible]
    if len(visible) == 1:
        return visible[0]
    return visible


def imperative_invoke(op_name, *inputs, out=None, **params):
    """String-name invoke (parity with MXImperativeInvoke, c_api_ndarray.cc:117)."""
    return invoke(get_op(op_name), list(inputs), params, out=out)


# ---------------------------------------------------------------------------
# creation & utilities (parity: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    """ref: python/mxnet/ndarray/utils.py array"""
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
        if dtype is None:
            dtype = src.dtype
    elif isinstance(source_array, np.ndarray):
        src = source_array
        if dtype is None:
            dtype = _default_dtype_for(src)
    else:
        # python lists/scalars default to float32, like the reference
        # (python/mxnet/ndarray/utils.py array)
        src = np.asarray(source_array)
        if dtype is None:
            dtype = np.float32 if src.dtype.kind in "fiub" else src.dtype
    src = src.astype(dtype, copy=False)
    ctx = ctx if ctx is not None else current_context()
    # device_put straight from host memory: jnp.asarray first would bounce
    # the buffer through the DEFAULT device (an accelerator upload + a
    # download when ctx is cpu, per data-pipeline batch)
    data = jax.device_put(np.ascontiguousarray(src), ctx.jax_device())
    return NDArray(data, ctx=ctx)


def empty(shape, ctx=None, dtype="float32"):
    ctx = ctx if ctx is not None else current_context()
    if isinstance(shape, int):
        shape = (shape,)
    # Allocate directly ON the target device.  jnp.zeros would materialize
    # on the default device first and device_put would then bounce the
    # buffer through the host — for a cpu-ctx scratch array (parameter
    # init) that is an accelerator->host download of the full tensor per
    # call.
    with jax.default_device(ctx.jax_device()):
        data = jnp.zeros(shape, jnp.dtype(dtype))
    return NDArray(data, ctx=ctx)


def waitall():
    """Block until all outstanding work has executed
    (ref: mx.nd.waitall → Engine::WaitForAll, threaded_engine.cc).

    Enqueues a barrier computation on every device that has seen
    dispatches and blocks on it — in-order execution per stream makes
    that equivalent to draining the queues, without pinning any user
    buffer."""
    for d in list(_DISPATCH_DEVICES):
        try:
            token = jax.device_put(jnp.zeros((), jnp.float32), d)
            jax.block_until_ready(_WAITALL_BARRIER(token))
        except Exception:           # device gone / backend quirk
            pass
    _DISPATCH_DEVICES.clear()


@jax.jit
def _WAITALL_BARRIER(t):
    # compiled once; executes after everything queued before it per stream
    return t + 1


def concatenate(arrays, axis=0, always_copy=True):
    return invoke(get_op("Concat"), list(arrays), {"dim": axis})


def moveaxis(tensor, source, destination):
    axes = list(range(tensor.ndim))
    axes.remove(source % tensor.ndim)
    axes.insert(destination % tensor.ndim, source % tensor.ndim)
    return invoke(get_op("transpose"), [tensor], {"axes": tuple(axes)})
