"""Gluon Block / HybridBlock: define-by-run layers with jit hybridization.

TPU-native rebirth of python/mxnet/gluon/block.py:

* ``Block`` (block.py:123) — imperative container with auto-registered
  children and Parameters, prefix scoping via ``_BlockScope``.
* ``HybridBlock`` (block.py:376) — on ``hybridize()``, the forward is traced
  ONCE per input signature into a **CachedOp = jax.jit of the functionalized
  forward** (block.py:436-439 traces to a symbolic CachedOp; here XLA is the
  graph executor, so tracing and compiling are the same step).  The
  functionalization:
    - parameters enter as pytree leaves (so donation/sharding apply),
    - the framework PRNG is threaded in as an explicit key,
    - in-place parameter writes during the trace (BatchNorm moving stats)
      are detected via the NDArray version counter and returned as extra
      outputs, then written back eagerly — MXNet's mutable aux-state
      semantics preserved over functional XLA.
* Under autograd recording the forward program takes the ``jax.vjp`` once
  and returns, beside its outputs, what the pullback needs that only the
  forward can give: the outputs of the matrix products (convolutions,
  ``dot_general``, the Pallas kernels) and of the reductions (``_kept``).
  One tape node is recorded for the whole CachedOp; its backward program
  starts from those residuals, makes the elementwise values between them
  again and runs no forward convolution — ``_CachedOp`` keeps its forward's
  activations for its fused backward the same way
  (src/imperative/cached_op.cc:434), and frees them after it, as the tape
  node does here.  Outside ``record()`` no residual is made.  The reverse
  pass tells the node which of its inputs' cotangents it has a use for
  (``autograd._wanted``) and the backward program, compiled once a set,
  returns those alone: the gradient of a batch nobody marked, or of a
  ``grad_req="null"`` parameter, is not computed.
"""
from __future__ import annotations

import copy
import threading

import numpy as np
import jax

from ..base import MXNetError
from ..context import current_context
from ..ndarray import NDArray
from .. import ndarray as _nd
from ..ops.registry import Operator
from .. import autograd
from .. import random_state
from ..telemetry import metrics as _tmetrics
from ..telemetry import tracing as _ttracing
from ..telemetry import xray as _xray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "functionalize"]


class _BlockScope(object):
    """Name/prefix scope for Blocks (ref: block.py class _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix+params pair for the new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import Prefix
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


def _flatten(args, inout_str):
    """Flatten nested list/tuple of NDArrays (ref: block.py _flatten)."""
    if isinstance(args, NDArray):
        return [args], int(0)
    if args is None:
        return [None], int(-1)
    assert isinstance(args, (list, tuple)), \
        "HybridBlock %s must be (nested) list of NDArray, but got %s of type %s" \
        % (inout_str, str(args), str(type(args)))
    flat = []
    fmts = []
    for i in args:
        arg, fmt = _flatten(i, inout_str)
        flat.extend(arg)
        fmts.append(fmt)
    return flat, fmts


def _regroup(args, fmt):
    """Inverse of _flatten (ref: block.py _regroup)."""
    if isinstance(fmt, int):
        if fmt == -1:
            return None, args[1:]
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    ret = []
    for i in fmt:
        res, args = _regroup(args, i)
        ret.append(res)
    return ret, args


class Block(object):
    """Base class for all neural network layers (ref: block.py:123)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = []
        self._reg_params = {}

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self.__dict__.items()
            if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Auto-register children and params (ref: block.py __setattr__)."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
                raise TypeError("Changing attribute type for {name} from {type1} to {type2}"
                                "is not allowed.".format(name=name,
                                                         type1=type(existing),
                                                         type2=type(value)))
            if isinstance(existing, Block):
                for i, c in enumerate(self._children):
                    if c is existing:
                        self._children[i] = value
            elif isinstance(value, Block):
                self.register_child(value)
        elif isinstance(value, Block):
            self.register_child(value)
        if isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed. " \
                "If you want to share parameters between blocks, please set " \
                "'params' at Block construction instead." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """ref: block.py name_scope."""
        return self._scope

    @property
    def params(self):
        """ParameterDict of this Block only (not children)."""
        return self._params

    def collect_params(self, select=None):
        """Recursively collect Parameters (ref: block.py collect_params,
        with the 1.3+ `select` regex for forward-compat)."""
        import re
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children:
            ret.update(cld.collect_params(select=select))
        return ret

    def save_params(self, filename):
        """ref: block.py:295 save_params."""
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        """ref: block.py:303 load_params."""
        self.collect_params().load(filename, ctx, allow_missing, ignore_extra,
                                   self.prefix)

    def register_child(self, block):
        """ref: block.py register_child."""
        self._children.append(block)

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        """ref: block.py initialize."""
        from .. import initializer
        if init is None:
            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Recursively activate hybridization on HybridBlock children."""
        for cld in self._children:
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        """ref: block.py cast."""
        for child in self._children:
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    lines = [(num_spaces * " ") + line for line in lines]
    return "\n".join([first] + lines)


# What a recorded forward keeps for its backward is decided by the primitive
# that made a value, never by a model's or a layer's name: the matrix
# products and the kernels that stand for them (a ``custom_vjp``'s forward
# saves its kernel's outputs, so what it chose to save stays saved), and the
# reductions (BatchNorm's statistics, pooling, a softmax's sums).  Every
# other value (elementwise work on those, a gather, a dropout's draw from
# the step's key) the backward makes again, fused into its consumers where
# XLA can.
_KEPT_PRIMITIVES = frozenset((
    "conv_general_dilated", "dot_general", "ragged_dot",
    "ragged_dot_general", "pallas_call",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_window", "reduce_window_sum", "reduce_window_max",
    "reduce_window_min"))
# ... and the values an operator names for it
# (``jax.ad_checkpoint.checkpoint_name``), these names and no other: the one
# BatchNorm's backward rule reads as it is, its input.
_KEPT_NAMES = frozenset(("bn_input",))


def _kept(prim, *_avals, **params):
    """``jax.checkpoint`` policy of the recorded forward."""
    if prim.name == "name":
        return params["name"] in _KEPT_NAMES
    return prim.name in _KEPT_PRIMITIVES


class _TraceParam(object):
    """Shadow for a Parameter during CachedOp tracing: .data() returns the
    tracer-backed NDArray; writes land on the shadow and are harvested."""

    __slots__ = ("param", "shadow")

    def __init__(self, param, shadow):
        self.param = param
        self.shadow = shadow


class CachedOp(object):
    """jit-compiled trace of a HybridBlock forward.

    The TPU-native _CachedOp (ref: src/imperative/cached_op.cc): cache key is
    (input shapes/dtypes, train flag) — the reference's static-shape
    specialization (cached_op.cc:179 GetForwardGraph keyed on shapes) becomes
    XLA's compile cache. Bucketed shapes therefore each compile once and hit
    thereafter, which is how BucketingModule-style workloads stay fast.
    """

    def __init__(self, block):
        self.block = block
        self._cache = {}
        # the param set only changes on structural mutation, which calls
        # _clear_cached_op (→ a fresh CachedOp); cache the walk here
        self._params = block._active_params
        self._param_names = sorted(self._params.keys())
        self._noted = None      # (entry, recorded or not) the registry has
        self._noted_backward = None     # (entry, cotangents left out) it has

    def _make_fn(self, param_names, n_inputs, in_fmt, train):
        block = self.block

        # the function's name is the compiled program's: what a profiler
        # trace and telemetry.programs() call it
        def cachedop_forward(param_vals, input_vals, rng):
            shadows = {name: NDArray(param_vals[name]) for name in param_names}
            nd_in = [None if v is None else NDArray(v) for v in input_vals]
            args, _ = _regroup(nd_in, in_fmt)
            if not isinstance(args, list):
                args = [args]
            # this trace's counts (telemetry.step_counter) are dropped, and
            # kept from a fused step's collection that may be open around it
            with _ttracing.collect_step_counters(), \
                    random_state.use_key(rng):
                with autograd._scope(recording=False, training=train):
                    with block._trace_params(shadows):
                        out = block.hybrid_forward_dispatch(*args)
            flat_out, out_fmt = _flatten(out, "output")
            out_vals = tuple(o._read() for o in flat_out)
            # harvest in-place writes to parameters (aux states): shadow
            # version counter moved ⇒ the trace mutated it
            aux_updates = {name: sh._read() for name, sh in shadows.items()
                           if sh._version > 0}
            # graftlint: disable=GL304 -- trace-time output-fmt memo, written once per trace
            self._last_out_fmt = out_fmt
            return out_vals, aux_updates

        return cachedop_forward

    def __call__(self, *args):
        block = self.block
        flat_args, in_fmt = _flatten(args, "input")
        params = self._params
        param_names = self._param_names
        param_vals = {}
        for name in param_names:
            p = params[name]
            if p._data is None:
                if not p._deferred_init or p.shape is None or \
                        0 in p.shape or np.prod(p.shape) <= 0:
                    # unresolved deferred shape (or not initialized): p.data()
                    # raises the right error; forward() catches Deferred and
                    # runs the eager shape-inference pass first
                    p.data()
                p._finish_deferred_init()
            param_vals[name] = p.data()._read()
        input_vals = [None if a is None else a._read() for a in flat_args]
        train = autograd.is_training()
        recording = autograd.is_recording()

        key = (tuple(None if v is None else (v.shape, str(v.dtype))
                     for v in input_vals),
               tuple((param_vals[n].shape, str(param_vals[n].dtype))
                     for n in param_names),
               _fmt_key(in_fmt), train)
        entry = self._cache.get(key)
        if entry is None:
            raw = self._make_fn(param_names, len(input_vals), in_fmt, train)
            recorded, backward = _make_recorded(raw)
            entry = {"raw": raw, "jit": jax.jit(raw),
                     "record": jax.jit(recorded),
                     "backward": jax.jit(backward)}
            self._cache[key] = entry

        rng = random_state.next_key()
        args = (param_vals, input_vals, rng)
        with _ttracing.phase_span("fwd"):
            if recording:
                out_vals, aux_updates, residuals, pullback = \
                    entry["record"](*args)
            else:
                out_vals, aux_updates = entry["jit"](*args)
        if (id(entry), recording) != self._noted:
            # the programs that just ran go to the registry, by shape alone
            self._noted = (id(entry), recording)
            _xray.register_program(
                "cachedop_forward", entry["record" if recording else "jit"],
                args, phase="forward")
        if "out_fmt" not in entry:
            # fn ran (traced) at least once for this entry, setting the fmt
            entry["out_fmt"] = self._last_out_fmt
        if recording and "residual_bytes" not in entry:
            entry["residual_bytes"] = sum(r.nbytes for r in residuals)

        ctx = flat_args[0]._ctx if flat_args else current_context()
        out_arrays = [NDArray(v, ctx=ctx) for v in out_vals]

        # write back mutated aux states (moving mean/var)
        for name, val in aux_updates.items():
            params[name].data()._write(val)

        if recording:
            _tmetrics.cachedop_recorded(entry["residual_bytes"])
            real_idx = [i for i, a in enumerate(flat_args) if a is not None]
            tape_inputs = [params[n].data() for n in param_names] + \
                [flat_args[i] for i in real_idx]
            # the one reference to this call's residuals: the tape drops it
            # after a pass that does not retain the graph, so they are freed
            # when the backward is done, as MXNet frees its activations
            held = [residuals]

            n_par = len(param_names)

            def tape_vjp(ct, wanted=None):
                # ``wanted``: one bool a tape input, from the pass
                # (autograd._wanted); without it every cotangent is computed
                if not held:
                    raise RuntimeError(
                        "graph already backpropagated: this block's "
                        "residuals were freed by a pass without "
                        "retain_graph=True")
                cts = ct if isinstance(ct, tuple) else (ct,)
                if wanted is None:
                    wanted = (True,) * len(tape_inputs)
                # the cotangents left out, as the program knows them: the
                # parameters by name, the inputs by place
                left_out = (
                    tuple(n for n, w in zip(param_names, wanted) if not w),
                    tuple(i for i, w in zip(real_idx, wanted[n_par:])
                          if not w))
                bwd_args = (held[0], pullback) + args + (
                    cts, _Static(left_out))
                pv_g, iv_g = entry["backward"](*bwd_args)
                if (id(entry), left_out) != self._noted_backward:
                    # the backward this pass ran goes to the registry
                    self._noted_backward = (id(entry), left_out)
                    _xray.register_program(
                        "cachedop_backward", entry["backward"], bwd_args,
                        phase="backward")
                _tmetrics.cachedop_cotangents_skipped(*map(len, left_out))
                return tuple(pv_g.get(n) for n in param_names) + \
                    tuple(iv_g[i] for i in real_idx)

            raw = entry["raw"]

            def tape_fn(*vals):
                # replayable pure function of the tape inputs — lets
                # autograd's create_graph build grad-of-grad through the
                # whole compiled block (same rng → same dropout masks): the
                # only place left where a recorded forward is traced again
                pv = dict(zip(param_names, vals[:n_par]))
                iv = list(input_vals)
                for j, idx in enumerate(real_idx):
                    iv[idx] = vals[n_par + j]
                outs, _aux = raw(pv, iv, rng)
                return outs[0] if len(outs) == 1 else tuple(outs)

            op = Operator("_CachedOp", lambda *a: a,
                          num_inputs=len(tape_inputs),
                          num_outputs=len(out_arrays))
            autograd._record(op, tape_inputs, out_arrays, tape_vjp,
                             fn=tape_fn, release=held.clear, selective=True)

        out, _ = _regroup(out_arrays, entry["out_fmt"])
        return out


def _make_recorded(raw):
    """The two programs of a recorded call of ``raw``, the functionalized
    forward: the forward that takes the vjp once, and the backward that is
    its pullback.

    The pullback is a pytree.  Those of its leaves that are the program's
    own arguments (parameters, inputs, the key: the backward takes them
    again as arguments) stay where they are; the others are the residuals,
    and only they leave the forward program.  ``pullback`` says, leaf by
    leaf, which is which: it is static, the forward's fourth output."""

    def cachedop_forward(param_vals, input_vals, rng):
        out_vals, vjp_fn, aux_updates = jax.vjp(
            # the two passes are two programs: XLA has nothing to merge
            # them by, so no barrier is asked for (prevent_cse)
            jax.checkpoint(lambda pv, iv: raw(pv, iv, rng), policy=_kept,
                           prevent_cse=False),
            param_vals, input_vals, has_aux=True)
        leaves, treedef = jax.tree.flatten(vjp_fn)
        given = {id(leaf): i for i, leaf in enumerate(
            jax.tree.leaves((param_vals, input_vals, rng)))}
        sources = tuple(given.get(id(leaf)) for leaf in leaves)
        residuals = [leaf for leaf, src in zip(leaves, sources)
                     if src is None]
        return out_vals, aux_updates, residuals, _Static((treedef, sources))

    def cachedop_backward(residuals, pullback, param_vals, input_vals, rng,
                          cts, left_out=_NOTHING_LEFT_OUT):
        treedef, sources = pullback.value
        given = jax.tree.leaves((param_vals, input_vals, rng))
        kept = iter(residuals)
        vjp_fn = jax.tree.unflatten(
            treedef, [next(kept) if src is None else given[src]
                      for src in sources])
        pv_g, iv_g = vjp_fn(cts)
        # the cotangents the pass has no use for do not leave the program,
        # and XLA drops what only they needed
        names, places = left_out.value
        return ({name: g for name, g in pv_g.items() if name not in names},
                [None if i in places else g for i, g in enumerate(iv_g)])

    return cachedop_forward, cachedop_backward


@jax.tree_util.register_static
class _Static(object):
    """A hashable value that crosses ``jax.jit`` as structure, not data."""

    __slots__ = ("value", "_hash")

    def __init__(self, value):
        self.value = value
        self._hash = hash(value)    # asked for at every dispatch

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return isinstance(other, _Static) and self.value == other.value


_NOTHING_LEFT_OUT = _Static(((), ()))


def _fmt_key(fmt):
    if isinstance(fmt, list):
        return tuple(_fmt_key(f) for f in fmt)
    return fmt


class HybridBlock(Block):
    """Block that can be traced+compiled (ref: block.py:376 HybridBlock).

    Subclasses implement ``hybrid_forward(F, x, *, weight=..., ...)``; F is
    the ndarray module eagerly and (conceptually) the symbol module under
    tracing — with XLA, both paths run the same jax ops, so F is always the
    ndarray module and tracing happens at the jax level.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._shadow_tls = threading.local()

    # trace shadows are installed for the DURATION OF A JIT TRACE
    # (_trace_params) — and traces run on whatever thread triggered the
    # compile (the serving batcher's dispatcher, a CachedOp first call).
    # They must be THREAD-LOCAL: a plain attribute would leak another
    # thread's in-flight tracers into a concurrent eager forward on this
    # same block (UnexpectedTracerError at best, silently tracing the
    # eager caller's math at worst).
    @property
    def _trace_shadows(self):
        return getattr(self._shadow_tls, "shadows", None)

    @_trace_shadows.setter
    def _trace_shadows(self, value):
        self._shadow_tls.shadows = value

    @property
    def _active_params(self):
        """name → Parameter used by this block subtree's forward."""
        out = {}
        for name, p in self.collect_params().items():
            out[name] = p
        return out

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, (HybridBlock, Parameter)):
            # a new child OR a new Parameter invalidates the traced graph —
            # the CachedOp snapshots the param set at construction
            self._clear_cached_op()

    def _clear_cached_op(self):
        self._cached_op = None

    def register_child(self, block):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                "Children of HybridBlock must also be HybridBlock, "
                "but %s has type %s. If you are using Sequential, "
                "please try HybridSequential instead." % (
                    str(block), str(type(block))))
        super().register_child(block)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        """ref: block.py hybridize — subsequent calls compile & cache."""
        self._active = active
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Deferred-shape resolution by a dry trace (ref: block.py infer_shape)."""
        self._deferred_infer_shape(*args)

    def _deferred_infer_shape(self, *args):
        """Run shape inference via jax.eval_shape over the eager forward to
        fill deferred parameter shapes (ref: block.py _deferred_infer_shape
        which re-infers through the symbolic graph)."""
        try:
            self.forward_eager_infer(*args)
        except DeferredInitializationError:
            raise
        except Exception as e:
            raise ValueError("Deferred initialization failed because shape "
                             "cannot be inferred: %s" % e)

    def forward_eager_infer(self, *args):
        # default: child blocks implement shape hints via their own
        # hybrid_forward's deferred logic (each layer fills in its params)
        pass

    # dispatch helper used by both eager and traced paths
    def hybrid_forward_dispatch(self, *args):
        params = {}
        shadows = self._trace_shadows
        deferred = [p for p in self._reg_params.values()
                    if p._data is None and p._deferred_init]
        if deferred and (shadows is None or
                         any(p.name not in shadows for p in deferred)):
            # layer-local shape inference from the live input (the reference
            # resolves deferred shapes via symbolic infer_shape,
            # block.py _deferred_infer_shape; here each layer fills its own),
            # under the set-up span ``deferred_init``: the initializers'
            # programs are put down to it
            with _ttracing.phase_span("deferred_init"):
                self._pre_infer(*args)
                for p in deferred:
                    p._finish_deferred_init()
        for name, p in self._reg_params.items():
            if shadows is not None and p.name in shadows:
                params[name] = shadows[p.name]
            else:
                params[name] = p.data()
        from .. import ndarray as F
        return self.hybrid_forward(F, *args, **params)

    def _pre_infer(self, *args):
        """Fill deferred parameter shapes from the first input. Layers with
        in_units/in_channels==0 override this."""
        return

    from contextlib import contextmanager

    @contextmanager
    def _trace_params(self, shadows):
        """Install shadow tracer NDArrays for all params in the subtree."""
        stack = [self]
        blocks = []
        while stack:
            b = stack.pop()
            blocks.append(b)
            stack.extend(b._children)
        prev = [getattr(b, "_trace_shadows", None) for b in blocks]
        for b in blocks:
            b._trace_shadows = shadows
        try:
            yield
        finally:
            for b, p in zip(blocks, prev):
                b._trace_shadows = p

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, x, *args):
        """Defines the forward computation (ref: block.py:561 forward)."""
        if self._trace_shadows is not None:
            # inside an enclosing CachedOp trace: inline into the parent's
            # single jit (the reference inlines subgraphs too, cached_op.cc:69).
            # Only here, while being traced, does the Block put its name on
            # the name stack: every op staged below carries it in its
            # op_name path, where a reader of telemetry.programs() finds it
            with jax.named_scope(self.name):
                return self.hybrid_forward_dispatch(x, *args)
        if self._active:
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            try:
                return self._cached_op(x, *args)
            except DeferredInitializationError:
                self._run_deferred_init(x, *args)
                return self._cached_op(x, *args)
        try:
            return self.hybrid_forward_dispatch(x, *args)
        except DeferredInitializationError:
            self._run_deferred_init(x, *args)
            return self.hybrid_forward_dispatch(x, *args)

    def _run_deferred_init(self, *args):
        """First-call shape resolution: one eager pass lets every layer in
        the subtree fill its own deferred parameter shapes.  Under the
        set-up span ``deferred_init``, whoever makes the pass (a first call
        here or of the ``CachedOp``, ``DataParallelTrainer``): the
        operators' programs it builds are put down to it."""
        with _ttracing.phase_span("deferred_init"), autograd.pause():
            self.hybrid_forward_dispatch(*args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to define the computation (ref: block.py hybrid_forward)."""
        raise NotImplementedError

    def serving_fn(self, *example_args, train=False):
        """graftserve forward entry point: ``(fn, param_vals)`` where
        ``fn(param_vals, *input_vals)`` is the pure jittable inference
        forward (the same functionalized trace ``CachedOp`` compiles)
        and ``param_vals`` the name→raw-array weight snapshot the
        serving :class:`~incubator_mxnet_tpu.serving.ModelRegistry`
        treats as the residency unit.  One ``jax.jit`` of ``fn`` serves
        every (shape-bucket) batch as ONE device call — XLA's compile
        cache keys on the padded batch signature."""
        return functionalize(self, *example_args, train=train)


class SymbolBlock(HybridBlock):
    """Build a HybridBlock from a Symbol (ref: block.py:599 SymbolBlock).

    Constructed lazily: the symbol executor lives in the symbol module
    (phase 5); SymbolBlock wraps its traced callable.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from ..symbol import Symbol
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if not isinstance(outputs, Symbol):
            raise TypeError("outputs must be a Symbol")
        syms = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        self._in_names = [s.name for s in syms]
        self._sym = outputs
        # register all non-input arguments as parameters
        arg_names = [n for n in outputs.list_arguments() if n not in self._in_names]
        aux_names = list(outputs.list_auxiliary_states())
        for n in arg_names:
            self.params.get(n.removeprefix(self.params.prefix) if n.startswith(self.params.prefix) else n,
                            allow_deferred_init=True, grad_req="write")
        for n in aux_names:
            self.params.get(n.removeprefix(self.params.prefix) if n.startswith(self.params.prefix) else n,
                            allow_deferred_init=True, grad_req="null")

    def forward(self, *args):
        in_map = dict(zip(self._in_names, args))
        param_map = {}
        for name, p in self.params.items():
            short = name[len(self.params.prefix):] if name.startswith(self.params.prefix) else name
            param_map[short] = p.data()
        merged = dict(param_map)
        merged.update(in_map)
        return self._sym.eval_dict(merged)

    def hybrid_forward(self, F, *args, **kwargs):  # pragma: no cover
        raise NotImplementedError


def functionalize(block, *example_args, train=False):
    """Extract the pure jittable forward of a HybridBlock.

    Returns ``(fn, param_vals)`` where ``fn(param_vals, *input_vals)``
    maps raw jax arrays to raw jax array outputs (a single array, or a
    tuple when the block returns several).  This is the same
    functionalized trace ``CachedOp`` compiles per signature — exposed
    so callers can compose the forward into LARGER XLA programs
    (``lax.scan`` chains for steady-state serving benchmarks, custom
    pjit shardings, export pipelines) instead of paying one dispatch per
    call.  ref: src/imperative/cached_op.cc — the reference's _CachedOp
    handle plays this role for its graph executor.

    ``example_args`` resolve deferred shapes with one eager pass;
    ``train`` picks the training/inference trace (BatchNorm stats etc.).
    Aux-state writes inside the trace (moving averages) are DISCARDED —
    use the block's normal call path for stateful training.

    RNG ops (dropout etc.) draw from the ``rng`` keyword — a jax PRNG
    key that is part of the traced signature, exactly as in CachedOp's
    compiled trace.  It defaults to a FIXED key: stochastic blocks must
    pass a fresh ``rng=`` per call or every call reuses the same masks.
    """
    import jax as _jax
    from ..ndarray import NDArray
    from .. import autograd

    block(*[NDArray(a) if not isinstance(a, NDArray) else a
            for a in example_args])        # resolve deferred init
    params = block.collect_params()
    param_vals = {name: p.data()._read() for name, p in params.items()}

    def fn(param_vals, *input_vals, rng=None):
        if rng is None:
            rng = _jax.random.PRNGKey(0)
        shadows = {name: NDArray(v) for name, v in param_vals.items()}
        nd_in = [NDArray(v) for v in input_vals]
        with random_state.use_key(rng):
            with autograd._scope(recording=False, training=train):
                with block._trace_params(shadows):
                    out = block.hybrid_forward_dispatch(*nd_in)
        flat, _fmt = _flatten(out, "output")
        vals = tuple(o._read() for o in flat)
        return vals[0] if len(vals) == 1 else vals

    return fn, param_vals
