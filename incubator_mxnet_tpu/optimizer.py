"""Optimizer registry and weight-update machinery.

TPU-native rebirth of python/mxnet/optimizer.py (1,519 LoC): the same
registry of optimizers, the same ``update(index, weight, grad, state)``
contract, dispatching to the *fused update operators* in
``ops/optimizer_ops.py`` (reference: src/operator/optimizer_op.cc) so the
whole update compiles to a handful of XLA elementwise kernels on the TPU's
VPU — the reason the reference fused them by hand.

The ``Updater`` wrapper (ref: optimizer.py get_updater) carries per-index
state dicts and is picklable, which is what lets a KVStore server run the
optimizer remotely (ref: kvstore_dist_server.h:145 server-side updater).
"""
from __future__ import annotations

import math
import pickle

import numpy as np

from .base import MXNetError
from .engine import BoundedCache, unflatten
from .ndarray import NDArray, invoke
from .ndarray import ndarray as _nd_mod
from .ops.registry import get_op
import jax
import jax.numpy as jnp

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdaGrad", "AdaDelta", "RMSProp",
           "Ftrl", "FTML", "Signum", "SGLD", "DCASGD", "LBSGD", "Test",
           "create", "register", "get_updater", "Updater",
           "fused_bucket_kind", "fused_bucket_update", "fused_lr_wd",
           "fused_state_arity"]


class Optimizer(object):
    """Base optimizer (ref: python/mxnet/optimizer.py class Optimizer).

    Tracks per-parameter learning-rate/wd multipliers, update counts and
    the rescale/clip policy shared by every optimizer.
    """

    opt_registry = {}

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.multi_precision = multi_precision

        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = dict(param_idx2name)
        self.sym_info = None
        self.param_dict = param_dict if param_dict else {}
        self.lr_mult = {}
        self.wd_mult = {}

    # -- registry ----------------------------------------------------------
    @staticmethod
    def register(klass):
        """ref: optimizer.py Optimizer.register."""
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        """ref: optimizer.py create_optimizer."""
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    # -- state -------------------------------------------------------------
    def create_state(self, index, weight):
        """Return optimizer state for one parameter (momentum etc.)."""
        return None

    def create_state_multi_precision(self, index, weight):
        """ref: optimizer.py — fp16/bf16 weights get an f32 master copy."""
        if self.multi_precision and weight.dtype in (np.dtype("float16"),
                                                     np.dtype("bfloat16")):
            weight_master_copy = weight.astype("float32")
            return (self.create_state(index, weight_master_copy), weight_master_copy)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype in (np.dtype("float16"),
                                                     np.dtype("bfloat16")):
            inner_state, weight32 = state
            g32 = grad.astype("float32")
            self.update(index, weight32, g32, inner_state)
            weight._write(weight32._read().astype(weight.dtype))
        else:
            self.update(index, weight, grad, state)

    # -- lr / wd policy ----------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been defined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        """ref: optimizer.py set_lr_mult (incl. __lr_mult__ symbol attrs)."""
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        """ref: optimizer.py set_wd_mult — biases/gammas default to wd 0."""
        self.wd_mult = {}
        for n in self.idx2name.values():
            # parity with reference heuristic: no decay on bias/bn params
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def __getstate__(self):
        d = dict(self.__dict__)
        d["param_dict"] = {}  # Parameters aren't picklable / needed serverside
        return d


register = Optimizer.register
create = Optimizer.create_optimizer


def _common_kwargs(opt, index):
    kw = {"lr": opt._get_lr(index), "wd": opt._get_wd(index),
          "rescale_grad": opt.rescale_grad}
    if opt.clip_gradient is not None:
        kw["clip_gradient"] = opt.clip_gradient
    return kw


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision
    (ref: optimizer.py class SGD → sgd_update/sgd_mom_update/mp_* ops)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (np.dtype("float16"),
                                                     np.dtype("bfloat16")):
            weight32 = weight.astype("float32")
            return (self.create_state(index, weight32), weight32)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = _common_kwargs(self, index)
        kw["lazy_update"] = self.lazy_update
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray) and self.lazy_update:
            # lazy update: touch only occupied rows (ref: optimizer_op.cc
            # SGDUpdateRspRspImpl — the row-sparse kernel)
            self._sparse_sgd(weight, grad, state, kw)
            return
        if state is not None:
            kw["momentum"] = self.momentum
            invoke(get_op("sgd_mom_update"), [weight, grad, state], kw, out=weight)
        else:
            invoke(get_op("sgd_update"), [weight, grad], kw, out=weight)

    def _sparse_sgd(self, weight, grad, state, kw):
        # registered ops (not inline jnp) so engine.bulk can defer the
        # lazy update into a training segment — the reference bulks
        # optimizer updates too (threaded_engine.h train segments)
        ukw = {"lr": kw["lr"], "wd": kw["wd"],
               "rescale_grad": kw["rescale_grad"],
               "clip_gradient": kw.get("clip_gradient", -1.0)}
        if state is not None:
            ukw["momentum"] = self.momentum
            invoke(get_op("_sparse_sgd_mom_update"),
                   [weight, grad.data, grad.indices, state], ukw, out=weight)
        else:
            invoke(get_op("_sparse_sgd_update"),
                   [weight, grad.data, grad.indices], ukw, out=weight)

    def update_multi_precision(self, index, weight, grad, state):
        use_mp = self.multi_precision and weight.dtype in (np.dtype("float16"),
                                                           np.dtype("bfloat16"))
        if not use_mp:
            return self.update(index, weight, grad, state)
        self._update_count(index)
        kw = _common_kwargs(self, index)
        mom, weight32 = state
        if mom is not None:
            kw["momentum"] = self.momentum
            invoke(get_op("mp_sgd_mom_update"), [weight, grad, mom, weight32],
                   kw, out=weight)
        else:
            invoke(get_op("mp_sgd_update"), [weight, grad, weight32], kw, out=weight)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (ref: optimizer.py class NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = _common_kwargs(self, index)
        if state is not None:
            kw["momentum"] = self.momentum
            invoke(get_op("nag_mom_update"), [weight, grad, state], kw, out=weight)
        else:
            invoke(get_op("sgd_update"), [weight, grad], kw, out=weight)


@register
class Adam(Optimizer):
    """Adam (ref: optimizer.py class Adam → adam_update op; bias correction
    folded into lr, as in the reference)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        return (_nd_mod.invoke(z, [weight], {}), _nd_mod.invoke(z, [weight], {}))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        kw = {"lr": lr, "wd": self._get_wd(index), "rescale_grad": self.rescale_grad,
              "beta1": self.beta1, "beta2": self.beta2, "epsilon": self.epsilon,
              "lazy_update": self.lazy_update}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        mean, var = state
        invoke(get_op("adam_update"), [weight, grad, mean, var], kw, out=weight)


@register
class AdaGrad(Optimizer):
    """ref: optimizer.py class AdaGrad (python updater in the reference —
    here it's a jitted op-free update over NDArray math)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad._read() * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight._read()
        hist = state._read() + jnp.square(g)
        state._write(hist)
        weight._write(weight._read() - lr * g / (jnp.sqrt(hist) + self.float_stable_eps))


@register
class AdaDelta(Optimizer):
    """ref: optimizer.py class AdaDelta."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        return (_nd_mod.invoke(z, [weight], {}), _nd_mod.invoke(z, [weight], {}))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad._read() * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight._read()
        acc_g, acc_delta = state
        ag = self.rho * acc_g._read() + (1 - self.rho) * jnp.square(g)
        delta = jnp.sqrt(acc_delta._read() + self.epsilon) / jnp.sqrt(ag + self.epsilon) * g
        ad = self.rho * acc_delta._read() + (1 - self.rho) * jnp.square(delta)
        acc_g._write(ag)
        acc_delta._write(ad)
        weight._write(weight._read() - delta)


@register
class RMSProp(Optimizer):
    """ref: optimizer.py class RMSProp — non-centered (rmsprop_update) and
    centered/Alex variant (rmspropalex_update)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        if self.centered:
            return (_nd_mod.invoke(z, [weight], {}), _nd_mod.invoke(z, [weight], {}),
                    _nd_mod.invoke(z, [weight], {}))
        return _nd_mod.invoke(z, [weight], {})

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = _common_kwargs(self, index)
        kw["gamma1"] = self.gamma1
        kw["epsilon"] = self.epsilon
        if self.clip_weights:
            kw["clip_weights"] = self.clip_weights
        if self.centered:
            n, g, delta = state
            kw["gamma2"] = self.gamma2
            invoke(get_op("rmspropalex_update"), [weight, grad, n, g, delta],
                   kw, out=weight)
        else:
            invoke(get_op("rmsprop_update"), [weight, grad, state], kw, out=weight)


@register
class Ftrl(Optimizer):
    """ref: optimizer.py class Ftrl → ftrl_update op."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        return (_nd_mod.invoke(z, [weight], {}), _nd_mod.invoke(z, [weight], {}))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = _common_kwargs(self, index)
        kw["lamda1"] = self.lamda1
        kw["beta"] = self.beta
        z, n = state
        invoke(get_op("ftrl_update"), [weight, grad, z, n], kw, out=weight)


@register
class FTML(Optimizer):
    """ref: optimizer.py class FTML → ftml_update op."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        return (_nd_mod.invoke(z, [weight], {}), _nd_mod.invoke(z, [weight], {}),
                _nd_mod.invoke(z, [weight], {}))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = _common_kwargs(self, index)
        kw.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon, t=t)
        d, v, z = state
        invoke(get_op("ftml_update"), [weight, grad, d, v, z], kw, out=weight)


@register
class Signum(Optimizer):
    """ref: optimizer.py class Signum → signsgd_update/signum_update ops."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = _common_kwargs(self, index)
        if state is not None:
            kw["momentum"] = self.momentum
            kw["wd_lh"] = self.wd_lh
            invoke(get_op("signum_update"), [weight, grad, state], kw, out=weight)
        else:
            invoke(get_op("signsgd_update"), [weight, grad], kw, out=weight)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (ref: optimizer.py class SGLD)."""

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad._read() * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight._read()
        from . import random_state
        import jax
        noise = jax.random.normal(random_state.next_key(), weight.shape,
                                  weight._read().dtype) * math.sqrt(lr)
        weight._write(weight._read() - lr / 2 * g + noise)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (ref: optimizer.py class DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        z = get_op("zeros_like")
        mom = None if self.momentum == 0.0 else _nd_mod.invoke(z, [weight], {})
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad._read() * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        mon, previous_weight = state
        w = weight._read()
        comp = g + wd * w + self.lamda * g * g * (w - previous_weight._read())
        if mon is not None:
            m = self.momentum * mon._read() - lr * comp
            mon._write(m)
        else:
            m = -lr * comp
        previous_weight._write(w)
        weight._write(w + m)


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (ref: optimizer.py class LBSGD, simplified warmup strategies)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.lbmult = 1.0

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def _get_lbmult(self, nup):
        nwup = self.warmup_epochs * self.updates_per_epoch
        if self.warmup_strategy == "linear" and nwup > 0 and nup < nwup:
            return 1.0 + (self.batch_scale - 1.0) * nup / nwup
        return float(self.batch_scale)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        self.lbmult = self._get_lbmult(self.num_update + self.init_updates)
        lr = self._get_lr(index) * self.lbmult
        kw = {"lr": lr, "wd": self._get_wd(index), "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        if state is not None:
            kw["momentum"] = self.momentum
            invoke(get_op("sgd_mom_update"), [weight, grad, state], kw, out=weight)
        else:
            invoke(get_op("sgd_update"), [weight, grad], kw, out=weight)


@register
class Test(Optimizer):
    """ref: optimizer.py class Test — w += rescale_grad * grad (for testing)."""

    def create_state(self, index, weight):
        return _nd_mod.invoke(get_op("zeros_like"), [weight], {})

    def update(self, index, weight, grad, state):
        weight._write(weight._read() + self.rescale_grad * grad._read())
        state._write(weight._read())


# alias casing parity: mx.optimizer.create('sgd' | 'SGD' | ...)
Optimizer.opt_registry["sgd"] = SGD
Optimizer.opt_registry["adam"] = Adam


class Updater(object):
    """Per-index stateful updater closure (ref: optimizer.py class Updater /
    get_updater) — this object is what KVStore servers pickle and run."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def ensure_state(self, index, weight):
        """Create (or context-sync) the state for ``index`` exactly as
        ``__call__`` would.  The fused bucket-update path (graftfuse)
        shares this per-index store, so save_states/load_states and
        switching between fused and per-param execution stay seamless."""
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(self.states[index], weight.context)
            self.states_synced[index] = True
        return self.states[index]

    def __call__(self, index, grad, weight):
        state = self.ensure_state(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad, state)

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, np.ndarray):
            # states loaded via set_states arrive as numpy — rehydrate so
            # the fused update ops can read them
            return _nd_mod.array(state, ctx=context)
        if isinstance(state, (tuple, list)):
            return type(state)(self.sync_state_context(i, context) for i in state)
        return state

    def set_states(self, states):
        """ref: optimizer.py Updater.set_states (pickle format).

        Loaded leaves stay numpy until first use — sync_state_context
        rehydrates them as NDArrays on the weight's context lazily.
        """
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            states, self.optimizer = states
        self.states = dict(states)
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        def to_np(s):
            if isinstance(s, NDArray):
                return s.asnumpy()
            if isinstance(s, (tuple, list)):
                return type(s)(to_np(i) for i in s)
            if hasattr(s, "shape") and hasattr(s, "dtype"):
                # device arrays parked directly in the store (graftzero's
                # error-feedback residuals) — persist as plain numpy so
                # snapshots never pickle framework device buffers
                return np.asarray(s)
            return s
        states = {k: to_np(v) for k, v in self.states.items()}
        return pickle.dumps((states, self.optimizer) if dump_optimizer else states)

    def states_nbytes(self):
        """Optimizer-state bytes this updater holds — a metadata walk
        (shape x dtype, never forces a device flush) over the int-keyed
        per-param states only; string-keyed side entries (graftzero's
        error-feedback residuals) are wire state, not optimizer state,
        and are counted by their own telemetry.  This is what the
        ``graft_trainer_state_shard_bytes`` gauge reports: under ZeRO-1
        sharding each rank's updater holds ~1/N of the unsharded total."""
        def leaf_nbytes(s):
            if isinstance(s, NDArray):
                arr = s._read()
                return int(np.dtype(arr.dtype).itemsize) * int(np.prod(arr.shape, dtype=np.int64))
            if isinstance(s, np.ndarray):
                return int(s.nbytes)
            if isinstance(s, (tuple, list)):
                return sum(leaf_nbytes(i) for i in s)
            return 0
        return sum(leaf_nbytes(v) for k, v in self.states.items()
                   if isinstance(k, int))


def get_updater(optimizer):
    """ref: optimizer.py get_updater."""
    return Updater(optimizer)


# ---------------------------------------------------------------------------
# graftfuse: multi-tensor fused bucket updates
# ---------------------------------------------------------------------------
# The per-param path dispatches one optimizer kernel per (param, context) —
# N tiny XLA programs per step, each crossing the host via invoke().  The
# fused path compiles ONE jitted program per (optimizer-class, bucket
# signature) that updates every parameter of a dtype-homogeneous bucket in
# a single dispatch: gradients arrive either as the bucket's flat reduced
# buffer (sliced/unflattened inside the program — free under XLA fusion)
# or as the per-param arrays, the per-param update formulas are the exact
# registered op fcomputes (sgd_update / sgd_mom_update / mp_* / adam_update),
# and the outputs rebind each weight/state NDArray without any device work.
# lr / wd / rescale_grad are baked into the program as constants — the
# same layout the per-param jits use, which is what makes the fused
# programs compile (and round) identically to the standalone ones; the
# cache key includes them, mirroring the per-param Operator.bind cache
# that also keys on these scalars.  Bit-exactness with the per-param path
# holds because every element goes through the same elementwise op chain
# with the same constant structure (tests/test_trainer_fused.py pins this
# down byte-for-byte).  Cached like the engine's _replay_cache, with the
# same GRAFT_REPLAY_CACHE_SIZE bound.

_FUSED_STEP_CACHE = BoundedCache()

_HALF_DTYPES = (np.dtype("float16"), np.dtype("bfloat16"))


def fused_bucket_kind(optimizer, dtype):
    """Fused-program tag for parameters of ``dtype`` under ``optimizer``,
    or None when that combination must take the per-param path.  Exact
    type checks (not isinstance): a subclass may override update() and
    silently diverge from the fused formula."""
    dtype = np.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return None
    if type(optimizer) is SGD:
        if optimizer.multi_precision and dtype in _HALF_DTYPES:
            return "mp_sgd"
        return "sgd"
    if type(optimizer) is Adam:
        if optimizer.multi_precision and dtype in _HALF_DTYPES:
            return None     # base-class mp wrapper: keep per-param
        return "adam"
    return None


def fused_lr_wd(optimizer, index, kind):
    """One per-(param, context) bookkeeping tick in the exact per-param
    sequence: bump the update count, then resolve lr (with Adam's bias
    correction folded in, as Adam.update does) and wd."""
    optimizer._update_count(index)
    lr = optimizer._get_lr(index)
    if kind == "adam":
        t = optimizer._index_update_count[index]
        lr *= math.sqrt(1.0 - optimizer.beta2 ** t) \
            / (1.0 - optimizer.beta1 ** t)
    return lr, optimizer._get_wd(index)


def _fused_state_arrays(kind, state):
    """The NDArray leaves of one per-index state, in program order."""
    if kind == "sgd":
        return () if state is None else (state,)
    if kind == "mp_sgd":
        mom, weight32 = state
        return (weight32,) if mom is None else (mom, weight32)
    if kind == "adam":
        mean, var = state
        return (mean, var)
    raise ValueError("unknown fused kind %r" % kind)


_NO_STATE = object()


def fused_state_arity(optimizer, kind, state=_NO_STATE):
    """State-leaf count a param contributes to a fused program — from its
    EXISTING per-index state when one exists (the per-param formulas key
    off the state object, not current config: a momentum flipped mid-run
    only affects states created afterwards), else from the optimizer's
    current config.  The Trainer plan buckets by (dtype, arity) so a
    fused program never mixes formula variants."""
    if state is not _NO_STATE:
        return len(_fused_state_arrays(kind, state))
    if kind == "sgd":
        return 1 if optimizer.momentum else 0
    if kind == "mp_sgd":
        return 2 if optimizer.momentum else 1
    return 2    # adam: (mean, var)


def _fused_config(optimizer, kind):
    """Static (hashable) config baked into the fused program — part of
    the cache key; everything per-step stays a traced operand."""
    clip = optimizer.clip_gradient
    clip = -1.0 if clip is None else float(clip)
    if kind in ("sgd", "mp_sgd"):
        return (float(optimizer.momentum), clip)
    if kind == "adam":
        return (float(optimizer.beta1), float(optimizer.beta2),
                float(optimizer.epsilon), clip)
    raise ValueError("unknown fused kind %r" % kind)


def _build_fused_program(kind, cfg, shapes, flat_mode, has_state,
                         lrs, wds, rescale):
    """One unflatten→update→reflatten program over a whole bucket: the
    multi-tensor update, each param through the registered op formula
    the per-param path runs.

    lr/wd/rescale are baked in as python-float CONSTANTS, exactly as the
    per-param path bakes them into each op's jitted partial — traced
    scalar operands occasionally shift LLVM's fma-contraction choices by
    1 ULP (measured on bf16 mp_sgd), and constants are the only layout
    that compiles each param's formula identically to its standalone
    program.  The per-param ``Operator.bind`` cache keys on the same
    scalars, so a changing lr schedule costs the fused path exactly the
    retraces it already cost the per-param path."""
    if kind in ("sgd", "mp_sgd"):
        momentum, clip = cfg
    else:
        beta1, beta2, epsilon, clip = cfg
    sgd_fc = get_op("sgd_update").fcompute
    sgd_mom_fc = get_op("sgd_mom_update").fcompute
    mp_sgd_fc = get_op("mp_sgd_update").fcompute
    mp_sgd_mom_fc = get_op("mp_sgd_mom_update").fcompute
    adam_fc = get_op("adam_update").fcompute

    # graftlint: disable=GL305 -- baked by design: constants are the only layout bit-identical to the per-param path, and the program cache keys on every one of them (cfg, lrs, wds, rescale; see docstring)
    def trainer_bucket_update(weights, grads, states):
        gs = unflatten(grads, shapes) if flat_mode else grads
        new_w, new_s = [], []
        for k, w in enumerate(weights):
            g = gs[k]
            lr, wd, st = lrs[k], wds[k], states[k]
            if kind == "sgd":
                if has_state:
                    w2, m2 = sgd_mom_fc(w, g, st[0], lr=lr,
                                        momentum=momentum, wd=wd,
                                        rescale_grad=rescale,
                                        clip_gradient=clip)
                    new_w.append(w2)
                    new_s.append((m2,))
                else:
                    new_w.append(sgd_fc(w, g, lr=lr, wd=wd,
                                        rescale_grad=rescale,
                                        clip_gradient=clip))
                    new_s.append(())
            elif kind == "mp_sgd":
                if has_state:
                    w2, m2, w32 = mp_sgd_mom_fc(w, g, st[0], st[1], lr=lr,
                                                momentum=momentum, wd=wd,
                                                rescale_grad=rescale,
                                                clip_gradient=clip)
                    new_w.append(w2)
                    new_s.append((m2, w32))
                else:
                    w2, w32 = mp_sgd_fc(w, g, st[0], lr=lr, wd=wd,
                                        rescale_grad=rescale,
                                        clip_gradient=clip)
                    new_w.append(w2)
                    new_s.append((w32,))
            else:
                w2, m2, v2 = adam_fc(w, g, st[0], st[1], lr=lr,
                                     beta1=beta1, beta2=beta2,
                                     epsilon=epsilon, wd=wd,
                                     rescale_grad=rescale,
                                     clip_gradient=clip)
                new_w.append(w2)
                new_s.append((m2, v2))
        return tuple(new_w), tuple(new_s)

    return jax.jit(trainer_bucket_update)


def fused_bucket_update(optimizer, updater, indices, weights, grads,
                        lrs, wds, flat_grad=None):
    """Apply one fused multi-tensor optimizer step to a bucket on one
    context: ``indices``/``weights`` are the bucket's params (index
    order), ``grads`` their per-param gradient NDArrays (ignored when
    ``flat_grad`` — the bucket's reduced flat buffer — is given), and
    ``lrs``/``wds`` the per-param scalars the caller resolved via
    :func:`fused_lr_wd`.  States come from (and go back to) ``updater``'s
    per-index store.  Everything stays on device: one jit dispatch, then
    pure buffer rebinds."""
    from .telemetry import metrics as _tmetrics
    kind = fused_bucket_kind(optimizer, weights[0].dtype)
    assert kind is not None, "caller must pre-check fused_bucket_kind"
    state_arrays = [
        _fused_state_arrays(kind, updater.ensure_state(i, w))
        for i, w in zip(indices, weights)]
    arity = len(state_arrays[0])
    # the Trainer plan buckets by (dtype, state arity); a mixed bucket
    # here means the plan went stale relative to the state store
    assert all(len(s) == arity for s in state_arrays), \
        "fused bucket with heterogeneous state arity — plan is stale"
    # "has_state" selects the momentum variant of the sgd/mp_sgd program
    # (mp always carries the f32 master copy, so momentum means 2 leaves)
    has_state = arity >= (2 if kind == "mp_sgd" else 1)
    cfg = _fused_config(optimizer, kind)
    shapes = tuple(tuple(w.shape) for w in weights)
    dtype = np.dtype(weights[0].dtype)
    flat_mode = flat_grad is not None
    lrs = tuple(float(v) for v in lrs)
    wds = tuple(float(v) for v in wds)
    rescale = float(optimizer.rescale_grad)
    key = (kind, cfg, shapes, str(dtype), flat_mode, has_state,
           lrs, wds, rescale)
    fn = _FUSED_STEP_CACHE.get(key)
    wvals = tuple(w._read() for w in weights)
    gvals = flat_grad._read() if flat_mode \
        else tuple(g._read() for g in grads)
    svals = tuple(tuple(a._read() for a in arrs) for arrs in state_arrays)
    if fn is None:
        fn = _build_fused_program(kind, cfg, shapes, flat_mode, has_state,
                                  lrs, wds, rescale)
        _FUSED_STEP_CACHE[key] = fn
        # every bucket's program has this name; the registry keeps the
        # newest, and needs only that all of them are the update phase
        from .telemetry import xray as _xray
        _xray.register_program("trainer_bucket_update", fn,
                               (wvals, gvals, svals), phase="update")
    outs_w, outs_s = fn(wvals, gvals, svals)
    for k, w in enumerate(weights):
        w._write(outs_w[k])
        for arr, val in zip(state_arrays[k], outs_s[k]):
            arr._write(val)
    _tmetrics.trainer_fused_update(len(weights))
