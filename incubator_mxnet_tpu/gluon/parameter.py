"""Gluon Parameter / ParameterDict.

TPU-native rebirth of python/mxnet/gluon/parameter.py (775 LoC): same public
surface — deferred shape init, per-context replicas, ``grad_req``,
save/load — but device replication is logical: one device buffer per
Context, with the sharded/pjit path (parallel package) treating a Parameter
as a named leaf in the train-state pytree.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np
import jax.numpy as jnp

from ..base import MXNetError
from ..context import Context, current_context, cpu
from ..ndarray import NDArray
from .. import ndarray as _nd
from .. import initializer
from .. import autograd
from ..telemetry import tracing as _ttracing

__all__ = ["DeferredInitializationError", "Parameter", "Constant", "ParameterDict"]


class DeferredInitializationError(MXNetError):
    """Error for unfinished deferred initialization (ref: parameter.py:36)."""


class Parameter(object):
    """A trainable parameter (ref: gluon/parameter.py class Parameter).

    Holds one NDArray per context.  ``shape`` entries of 0 are inferred on
    first forward (deferred init), matching the reference contract.
    """

    def __init__(self, name, grad_req="write", shape=None, dtype=np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default",
                 sharding=None):
        self._var = None
        self._data = None   # OrderedDict[Context, NDArray]
        self._grad = None
        self._ctx_list = None
        self._deferred_init = ()
        self.name = name
        # per-dimension mesh axis names, e.g. ("tp", None): the GSPMD
        # rebirth of ctx_group model parallelism (SURVEY §2.4 — placement
        # is a sharding annotation, the compiler inserts the collectives)
        self.sharding = tuple(sharding) if sharding is not None else None
        self._differentiable = differentiable
        self._allow_deferred_init = allow_deferred_init
        self._grad_req = None
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.grad_req = grad_req
        self.init = init
        self._stype = stype
        self._grad_stype = grad_stype

    def __repr__(self):
        s = "Parameter {name} (shape={shape}, dtype={dtype})"
        return s.format(name=self.name, shape=self.shape, dtype=self.dtype)

    # -- grad_req ----------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), \
            "grad_req must be one of 'write', 'add', or 'null', but got '%s'" % req
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null" and self._grad is not None:
            self._grad = None
            for d in self._check_and_get(self._data, list):
                d._grad = None
                d._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    # -- helpers -----------------------------------------------------------
    def _check_and_get(self, arr_dict, ctx):
        if arr_dict is not None:
            if ctx is list:
                return list(arr_dict.values())
            if ctx is None:
                if len(arr_dict) == 1:
                    return list(arr_dict.values())[0]
                ctx = current_context()
            if isinstance(ctx, Context):
                if ctx in arr_dict:
                    return arr_dict[ctx]
                # device_typeid fallback: tpu() matches tpu(0)
                for c, v in arr_dict.items():
                    if c.device_type == ctx.device_type:
                        return v
            raise RuntimeError(
                "Parameter %s was not initialized on context %s. "
                "It was only initialized on %s." % (
                    self.name, str(ctx), str(self._ctx_list)))
        if self._deferred_init:
            raise DeferredInitializationError(
                "Parameter %s has not been initialized yet because initialization "
                "was deferred. Actual initialization happens during the first "
                "forward pass. Please pass one batch of data through the network "
                "before accessing Parameters." % self.name)
        raise RuntimeError(
            "Parameter %s has not been initialized. Note that you should initialize "
            "parameters and create Trainer with Block.collect_params() instead of "
            "Block.params because the later does not include Parameters of "
            "nested child Blocks" % self.name)

    def _load_init(self, data, ctx):
        """Re-init from loaded data (ref: parameter.py _load_init)."""
        if self.shape:
            for self_dim, data_dim in zip(self.shape, data.shape):
                assert self_dim in (0, data_dim), \
                    "Failed loading Parameter '%s' from saved params: " \
                    "shape incompatible expected %s vs saved %s" % (
                        self.name, str(self.shape), str(data.shape))
            self.shape = tuple(i if i != 0 else j
                               for i, j in zip(self.shape, data.shape))
        if self.dtype:
            assert np.dtype(self.dtype).type == data.dtype.type, \
                "Failed loading Parameter '%s' from saved params: " \
                "dtype incompatible expected %s vs saved %s" % (
                    self.name, str(self.dtype), str(data.dtype))
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is None:
            if self._deferred_init:
                assert ctx is None or set(ctx) == set(self._deferred_init[1]), \
                    "Failed to load Parameter '%s' on %s because it was " \
                    "previous initialized on %s." % (
                        self.name, str(ctx), str(self.list_ctx()))
                ctx = self._deferred_init[1]
            elif ctx is None:
                ctx = [current_context()]
            self._init_impl(data, ctx)
        else:
            assert ctx is None or set(ctx) == set(self.list_ctx()), \
                "Failed to load Parameter '%s' on %s because it was " \
                "previous initialized on %s." % (
                    self.name, str(ctx), str(self.list_ctx()))
            self.set_data(data)
        self._deferred_init = ()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        if isinstance(init, str):
            init = initializer.create(init)
        assert self.shape is not None and np.prod(self.shape) > 0, \
            "Cannot initialize Parameter '%s' because it has invalid shape: %s. " \
            "Please specify in_units, in_channels, etc for `Block`s." % (
                self.name, str(self.shape))
        with autograd.pause():
            if data is None:
                data = _nd.empty(self.shape, dtype=self.dtype, ctx=cpu())
                # the __init__ attr routes straight to the param's own
                # initializer; otherwise default_init's suffix dispatch runs
                # (ref: parameter.py _finish_deferred_init → InitDesc attrs)
                attrs = {"__init__": init.dumps()} \
                    if isinstance(init, initializer.Initializer) else {}
                initializer.create(default_init)(
                    initializer.InitDesc(self.name, attrs), data)
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._ctx_list = list(ctx_list)
        self._data = OrderedDict()
        for ctx in self._ctx_list:
            self._data[ctx] = data.copyto(ctx) if isinstance(data, NDArray) \
                else _nd.array(data, ctx=ctx, dtype=self.dtype)
        self._init_grad()

    def _init_grad(self):
        if self.grad_req == "null":
            self._grad = None
            return
        self._grad = OrderedDict()
        for ctx, d in self._data.items():
            g = _nd.array(np.zeros(d.shape, np.dtype(self.dtype)), ctx=ctx)
            self._grad[ctx] = g
            d._grad = g
            d._grad_req = self.grad_req
            autograd.mark_variables([d], [g], self.grad_req)

    def _reduce(self):
        """Average over contexts (ref: parameter.py _reduce)."""
        data = self.list_data()
        if len(data) == 1:
            return data[0].copyto(cpu())
        acc = data[0].asnumpy().astype(np.float64)
        for d in data[1:]:
            acc = acc + d.asnumpy()
        return _nd.array((acc / len(data)).astype(self.dtype), ctx=cpu())

    # -- public API --------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """ref: gluon/parameter.py Parameter.initialize."""
        if default_init is None:
            default_init = initializer.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if self.shape is None or np.prod(self.shape) <= 0:
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError("Cannot initialize Parameter '%s' because it has "
                             "invalid shape: %s." % (self.name, str(self.shape)))
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def reset_ctx(self, ctx):
        """Move to new contexts (ref: parameter.py reset_ctx)."""
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data:
            data = self._reduce()
            with autograd.pause():
                self._init_impl(data, ctx)
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise ValueError("Cannot reset context for Parameter '%s' because it "
                             "has not been initialized." % self.name)

    def set_data(self, data):
        """ref: parameter.py set_data."""
        self.shape = tuple(data.shape)
        if self._data is None:
            assert self._deferred_init, \
                "Parameter '%s' has not been initialized" % self.name
            self._deferred_init = self._deferred_init[:3] + (data,)
            return
        for arr in self._data.values():
            arr._write(jnp.asarray(
                data.asnumpy() if isinstance(data, NDArray) else data,
                arr._read().dtype))

    def data(self, ctx=None):
        """Returns this parameter on one context (ref: parameter.py data)."""
        return self._check_and_get(self._data, ctx)

    def list_data(self):
        return self._check_and_get(self._data, list)

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter '%s' "
                "because grad_req='null'" % self.name)
        return self._check_and_get(self._grad, ctx)

    def list_grad(self):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter '%s' "
                "because grad_req='null'" % self.name)
        return self._check_and_get(self._grad, list)

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError("Parameter '%s' has not been initialized" % self.name)
        return self._ctx_list

    def zero_grad(self):
        """ref: parameter.py zero_grad."""
        if self._grad is None:
            return
        for g in self._grad.values():
            g._write(jnp.zeros(g.shape, g._read().dtype))

    def var(self):
        """Symbol view of this parameter (ref: parameter.py var)."""
        if self._var is None:
            from ..symbol import var as _sym_var
            self._var = _sym_var(self.name, shape=self.shape, dtype=self.dtype,
                                 lr_mult=self.lr_mult, wd_mult=self.wd_mult,
                                 init=self.init)
        return self._var

    def cast(self, dtype):
        """ref: parameter.py cast."""
        self.dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            self._data = OrderedDict(
                (ctx, d.astype(dtype)) for ctx, d in self._data.items())
            self._init_grad()


class Constant(Parameter):
    """Non-trainable constant (ref: gluon/parameter.py class Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _nd.array(value)
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                arr._write(value._read())
        # registry key must equal __name__.lower() so dumps() round-trips
        Init.__name__ = "Constant_" + name
        initializer._INIT_REGISTRY[Init.__name__.lower()] = Init

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=Init())


def _attr_equal(a, b):
    """Attribute equivalence for Parameter reconciliation: initializer
    instances compare by configuration (dumps), not identity."""
    if a == b:
        return True
    if isinstance(a, initializer.Initializer) and \
            isinstance(b, initializer.Initializer):
        return a.dumps() == b.dumps()
    return False


class ParameterDict(object):
    """Prefix-scoped dict of Parameters (ref: gluon/parameter.py:560)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __getitem__(self, key):
        return self._params[key]

    def __repr__(self):
        s = "{name}(\n{content}\n)"
        name = self._prefix + " " if self._prefix else ""
        return s.format(name=name, content="\n".join(
            "  " + repr(v) for v in self.values()))

    def __iter__(self):
        return iter(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._shared._params[name]
        return None

    def get(self, name, **kwargs):
        """Get-or-create with attribute reconciliation (ref: parameter.py get)."""
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and len(v) == len(existing):
                        inferred_shape = []
                        matched = True
                        for dim1, dim2 in zip(v, existing):
                            if dim1 != dim2 and dim1 * dim2 != 0:
                                matched = False
                                break
                            elif dim1 == dim2:
                                inferred_shape.append(dim1)
                            elif dim1 == 0:
                                inferred_shape.append(dim2)
                            else:
                                inferred_shape.append(dim1)
                        if matched:
                            param.shape = tuple(inferred_shape)
                            continue
                    assert v is None or _attr_equal(v, existing), \
                        "Cannot retrieve Parameter '%s' because desired attribute " \
                        "does not match with stored for attribute '%s': " \
                        "desired '%s' vs stored '%s'." % (
                            name, k, str(v), str(getattr(param, k)))
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        """ref: parameter.py get_constant."""
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named '{}'. Please specify value "
                               "if you want to create a new constant.".format(name))
            param = Constant(name, value)
            self._params[name] = param
        elif value is not None:
            assert isinstance(param, Constant), \
                "Parameter '{}' already exists but it is not a constant.".format(name)
        return param

    def update(self, other):
        """ref: parameter.py ParameterDict.update."""
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    "Cannot update self with other because they have different " \
                    "Parameters with the same name '%s'" % k
            else:
                self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        """ref: parameter.py ParameterDict.initialize.  ``Block.initialize``
        comes here: the set-up span ``initialize``."""
        if init is None:
            init = initializer.Uniform()
        if verbose:
            init.set_verbosity(verbose=verbose)
        with _ttracing.phase_span("initialize"):
            for _, v in self.items():
                v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        """ref: parameter.py ParameterDict.save → NDArray save format."""
        from ..ndarray import save as nd_save
        arg_dict = {}
        for param in self.values():
            weight = param._reduce()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    "Prefix '%s' is to be striped before saving, but Parameter's "
                    "name '%s' does not start with '%s'." % (
                        strip_prefix, param.name, strip_prefix))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd_save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        """ref: parameter.py ParameterDict.load."""
        from ..ndarray import load as nd_load
        if restore_prefix:
            for name in self.keys():
                assert name.startswith(restore_prefix), \
                    "restore_prefix is '%s' but Parameters name '%s' does not start " \
                    "with '%s'" % (restore_prefix, name, restore_prefix)
        lprefix = len(restore_prefix)
        loaded = nd_load(filename)
        arg_dict = {restore_prefix + k.split(":", 1)[-1]: v
                    for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    "Parameter '%s' is missing in file '%s'" % (name[lprefix:], filename)
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    "Parameter '%s' loaded from file '%s' is not present in " \
                    "ParameterDict" % (name[lprefix:], filename)
                continue
            self[name]._load_init(arg_dict[name], ctx)
