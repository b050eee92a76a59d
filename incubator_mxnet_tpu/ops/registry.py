"""Single operator registry serving both execution modes.

This is the TPU-native rebirth of the reference's NNVM op registry
(src/operator/*, NNVM_REGISTER_OP; include/mxnet/op_attr_types.h): ONE
registration per operator feeds

  * the eager NDArray front-end  (reference: src/imperative/imperative.cc:86)
  * the autograd tape            (reference: src/imperative/imperative.cc:182)
  * the symbolic graph executor  (reference: src/executor/graph_executor.cc)

Differences from the reference, by design (SURVEY §7):

  * ``fcompute`` is a pure JAX function — XLA is the kernel library, Pallas
    the escape hatch — instead of per-device FCompute<cpu|gpu> pairs.
  * There are no hand-written FInferShape/FInferType attributes: shape and
    dtype inference is ``jax.eval_shape`` over the same fcompute, so the two
    can never disagree (reference needed 363 files of paired infer+compute).
  * There is no FGradient twin-op: gradients come from ``jax.vjp`` over the
    same fcompute (the tape stores the vjp closure).
  * Scheduling/async: each eager call dispatches through a cached
    ``jax.jit``; XLA's async dispatch + donation plays the role of the
    ThreadedEngine (src/engine/threaded_engine.cc) — ops are issued without
    blocking Python and dependencies resolve in data-flow order on device.
"""
from __future__ import annotations

import functools
import inspect
from typing import Callable, Optional

import jax

from ..telemetry.tracing import jit_with_step_counters, operator_jitted

__all__ = ["Operator", "register", "get_op", "list_ops", "alias",
           "registration_log"]

_REGISTRY: dict[str, "Operator"] = {}

# Every register()/alias() call appends one entry here so static analysis
# (analysis/graftlint) can see registration ORDER and collisions — the
# dict alone silently keeps only the last binding per name.  Entries:
# {"name", "op", "alias_of" (canonical name or None), "file", "line",
#  "collided_with" (the Operator this binding displaced, or None)}.
_REGISTRATION_LOG: list[dict] = []


def _source_of(fcompute):
    """(file, line) of an fcompute, or (None, None) for C callables."""
    code = getattr(fcompute, "__code__", None)
    if code is None:
        return None, None
    return code.co_filename, code.co_firstlineno


def _log_registration(name, op, alias_of=None):
    prev = _REGISTRY.get(name)
    fname, line = _source_of(op.fcompute)
    _REGISTRATION_LOG.append({
        "name": name, "op": op, "alias_of": alias_of,
        "file": fname, "line": line,
        "collided_with": prev if (prev is not None and prev is not op)
        else None,
    })


def registration_log():
    """The append-only log of every registration (canonical + alias)."""
    return list(_REGISTRATION_LOG)


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


class Operator:
    """One registered operator.

    Parameters
    ----------
    name : canonical op name (e.g. ``Convolution``, ``broadcast_add``).
    fcompute : pure function ``(*inputs, **params) -> array | tuple``.
        If ``needs_rng``, it must accept a keyword ``rng`` (a jax PRNG key).
        If ``takes_is_train``, it must accept keyword ``is_train`` (static).
    num_inputs : fixed arity, or ``None`` for variadic (e.g. ``concat``).
    num_outputs : number of outputs produced by fcompute.
    num_visible_outputs : outputs exposed to the user (extra outputs are
        auxiliary, e.g. BatchNorm's batch mean/var); defaults to num_outputs.
    differentiable : whether vjp should be recorded on the tape.
    nograd_inputs : indices of inputs that never receive gradient
        (e.g. integer indices of ``take``).
    step_counters : fcompute calls ``telemetry.step_counter``.  A traced
        value cannot leave the op's ``jax.jit`` but as a result, so the jit
        hands the counts out and they are counted again where the op was
        called (``tracing.jit_with_step_counters``).
    """

    def __init__(self, name: str, fcompute: Callable, *, num_inputs: Optional[int] = 1,
                 num_outputs: int = 1, num_visible_outputs: Optional[int] = None,
                 differentiable: bool = True, needs_rng: bool = False,
                 takes_is_train: bool = False, nograd_inputs=(), mutate_inputs=(),
                 input_names=None, aux_input_names=(), fargnames=None,
                 finfer_params=None, fvisible=None, fnum_outputs=None,
                 no_jit: bool = False, step_counters: bool = False,
                 doc: str = ""):
        self.name = name
        self.fcompute = fcompute
        self.num_inputs = num_inputs
        self.num_outputs = num_outputs
        self.num_visible_outputs = (num_outputs if num_visible_outputs is None
                                    else num_visible_outputs)
        self.differentiable = differentiable
        self.needs_rng = needs_rng
        self.takes_is_train = takes_is_train
        self.nograd_inputs = tuple(nograd_inputs)
        self.mutate_inputs = tuple(mutate_inputs)
        self.input_names = input_names
        self.aux_input_names = tuple(aux_input_names)
        self.fargnames = fargnames
        self.finfer_params = finfer_params
        self.fvisible = fvisible
        self.fnum_outputs = fnum_outputs   # params → output count (split etc.)
        self.no_jit = no_jit   # ops that manage their own device placement
        # (multi-device shard_map bodies): the eager micro-jit would pin
        # them to the default device and clash with the op's mesh
        self.step_counters = step_counters
        self.doc = doc
        self._jit_cache: dict = {}
        # Populated EAGERLY so registry introspection (graftlint, symbol
        # executors) never mutates Operator instances mid-flight — the
        # lazy first-call cache made concurrent readers race on attribute
        # creation and made linting observable as a state change.  The
        # __defaults__ fast path keeps default-free throwaway Operators
        # (the per-flush _BulkSegment lambda, engine.py) off
        # inspect.signature entirely.
        if getattr(fcompute, "__defaults__", None) \
                or getattr(fcompute, "__kwdefaults__", None):
            try:
                sig = inspect.signature(fcompute)
                self._defaults = {k: v.default
                                  for k, v in sig.parameters.items()
                                  if v.default is not inspect.Parameter.empty}
            except (TypeError, ValueError):
                self._defaults = {}
        else:
            self._defaults = {}

    def arg_names(self, params: dict):
        """Required input names given static params, or None if unnamed
        (parity: FListInputNames, which ConvolutionParam et al. vary by
        no_bias — include/mxnet/op_attr_types.h). Falls back to the
        fcompute's own default for no_bias (Deconvolution defaults True)."""
        if self.fargnames is not None:
            return list(self.fargnames(params))
        if self.input_names is None:
            return None
        names = list(self.input_names)
        if "bias" in names:
            no_bias = params.get("no_bias", self._param_default("no_bias"))
            if no_bias:
                names.remove("bias")
        return names

    def _param_default(self, pname):
        return self._defaults.get(pname)

    def contract(self):
        """Machine-readable registration contract for static analysis.

        Everything the op promised at registration time, in plain data —
        analysis/graftlint verifies these promises against the fcompute
        signature and body without importing anything op-specific."""
        fname, line = _source_of(self.fcompute)
        return {
            "name": self.name,
            "num_inputs": self.num_inputs,
            "num_outputs": self.num_outputs,
            "num_visible_outputs": self.num_visible_outputs,
            "differentiable": self.differentiable,
            "needs_rng": self.needs_rng,
            "takes_is_train": self.takes_is_train,
            "nograd_inputs": list(self.nograd_inputs),
            "mutate_inputs": list(self.mutate_inputs),
            "input_names": (None if self.input_names is None
                            else list(self.input_names)),
            "aux_input_names": list(self.aux_input_names),
            "has_fargnames": self.fargnames is not None,
            "has_finfer_params": self.finfer_params is not None,
            "has_fvisible": self.fvisible is not None,
            "has_fnum_outputs": self.fnum_outputs is not None,
            "no_jit": self.no_jit,
            "step_counters": self.step_counters,
            "param_defaults": dict(self._defaults),
            "source_file": fname,
            "source_line": line,
        }

    def visible_outputs(self, params: dict, n_outputs: int) -> int:
        """How many of ``n_outputs`` are user-visible (rest are aux, e.g.
        BatchNorm batch stats unless output_mean_var)."""
        if self.fvisible is not None:
            return self.fvisible(params, n_outputs)
        return n_outputs - (self.num_outputs - self.num_visible_outputs)

    # ---- compiled dispatch -------------------------------------------------
    def bind(self, params: dict, is_train: bool = False):
        """Return the cached jitted callable for this (params, is_train) combo.

        The returned callable takes the op's array inputs positionally (plus
        ``rng=`` if needs_rng).  This cache is the analogue of the reference's
        CachedOp / engine op-bulking: steady-state eager calls are a dict hit
        + an XLA async dispatch.
        """
        if self.no_jit:
            return self.raw(params, is_train)
        key = (_hashable(params), bool(is_train))
        fn = self._jit_cache.get(key)
        if fn is None:
            kw = dict(params)
            if self.takes_is_train:
                kw["is_train"] = bool(is_train)
            raw = functools.partial(self.fcompute, **kw)
            operator_jitted(self.fcompute)  # its builds: the library's
            fn = (jit_with_step_counters(raw) if self.step_counters
                  else jax.jit(raw))
            self._jit_cache[key] = fn
        return fn

    def raw(self, params: dict, is_train: bool = False):
        """Un-jitted closure (used when tracing inside an outer jit)."""
        kw = dict(params)
        if self.takes_is_train:
            kw["is_train"] = bool(is_train)
        return functools.partial(self.fcompute, **kw)

    def infer(self, input_shapes_dtypes, params: dict, is_train: bool = False):
        """Shape/dtype inference via jax.eval_shape (replaces FInferShape/Type)."""
        structs = [jax.ShapeDtypeStruct(s, d) for (s, d) in input_shapes_dtypes]
        fn = self.raw(params, is_train)
        if self.needs_rng:
            out = jax.eval_shape(functools.partial(fn, rng=jax.ShapeDtypeStruct((2,), "uint32")), *structs)
        else:
            out = jax.eval_shape(fn, *structs)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return [(tuple(o.shape), o.dtype) for o in out]

    def __repr__(self):
        return "Operator(%s)" % self.name


def register(name, **kwargs):
    """Decorator: register ``fcompute`` under ``name`` (+ optional aliases)."""
    aliases = kwargs.pop("aliases", ())

    def dec(fcompute):
        op = Operator(name, fcompute, doc=fcompute.__doc__ or "", **kwargs)
        _log_registration(name, op)
        _REGISTRY[name] = op
        for a in aliases:
            _log_registration(a, op, alias_of=name)
            _REGISTRY[a] = op
        return fcompute

    return dec


def alias(existing, *names):
    op = _REGISTRY[existing]
    for n in names:
        _log_registration(n, op, alias_of=existing)
        _REGISTRY[n] = op


def get_op(name: str) -> Operator:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError("Operator %r is not registered (have %d ops)"
                       % (name, len(_REGISTRY))) from None


def list_ops():
    return sorted(_REGISTRY)
