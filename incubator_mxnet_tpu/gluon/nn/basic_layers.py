"""Basic Gluon layers (ref: python/mxnet/gluon/nn/basic_layers.py).

Same layer set and parameter naming as the reference: Sequential,
HybridSequential, Dense, Activation, Dropout, BatchNorm, InstanceNorm,
LayerNorm, Embedding, Flatten, Lambda, HybridLambda.  All compute lowers to
registry ops (XLA kernels); hybridize() compiles whole stacks into one jit.

Beyond the reference's set, for decoder models published since: RMSNorm,
GatedMLP (SwiGLU) and ShortConv (the gated short convolution of the LFM2
family).  The chip benchmark's cell ``lfm2moe_fused_s8192`` runs all three.
"""
from __future__ import annotations

import jax
import numpy as np

from ..block import Block, HybridBlock
from ... import initializer
from ...ndarray import NDArray

__all__ = ["Sequential", "HybridSequential", "Dense", "Activation", "Dropout",
           "BatchNorm", "InstanceNorm", "LayerNorm", "RMSNorm", "GatedMLP",
           "ShortConv", "Embedding", "Flatten", "Lambda", "HybridLambda"]


class Sequential(Block):
    """Stack of Blocks (ref: basic_layers.py class Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children:
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(key=key, block=block)
                           for key, block in enumerate(self._children))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        return self._children[key]

    def __len__(self):
        return len(self._children)

    def hybridize(self, active=True, **kwargs):
        """ref: basic_layers.py Sequential.hybridize warning-free passthrough."""
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stack of HybridBlocks, hybridizable as one graph
    (ref: basic_layers.py class HybridSequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children:
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(key=key, block=block)
                           for key, block in enumerate(self._children))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        return self._children[key]

    def __len__(self):
        return len(self._children)


class Dense(HybridBlock):
    """Fully-connected layer y = act(x·Wᵀ + b)
    (ref: basic_layers.py class Dense → FullyConnected op)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        with self.name_scope():
            self._units = units
            self._in_units = in_units
            self.weight = self.params.get("weight", shape=(units, in_units),
                                          dtype=dtype,
                                          init=weight_initializer,
                                          allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get("bias", shape=(units,), dtype=dtype,
                                            init=initializer.create(bias_initializer)
                                            if isinstance(bias_initializer, str)
                                            else bias_initializer,
                                            allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _pre_infer(self, x):
        if self.weight.shape and self.weight.shape[1] == 0:
            in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
            self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        if bias is None:
            act = F.FullyConnected(x, weight, no_bias=True,
                                   num_hidden=self._units,
                                   flatten=self._flatten)
        else:
            act = F.FullyConnected(x, weight, bias, no_bias=False,
                                   num_hidden=self._units,
                                   flatten=self._flatten)
        if self.act is not None:
            act = self.act(act)
        return act

    def __repr__(self):
        shape = self.weight.shape
        return "{name}({layout}, {act})".format(
            name=self.__class__.__name__,
            act=self.act if self.act else "linear",
            layout="{0} -> {1}".format(shape[1] if shape[1] else None, shape[0]))


class Activation(HybridBlock):
    """ref: basic_layers.py class Activation → Activation op."""

    def __init__(self, activation, **kwargs):
        self._act_type = activation
        super().__init__(**kwargs)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return "{name}({_act_type})".format(name=self.__class__.__name__,
                                            **{"_act_type": self._act_type})


class Dropout(HybridBlock):
    """ref: basic_layers.py class Dropout → Dropout op (inverted, train-only)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return "{name}(p = {_rate}, axes={_axes})".format(
            name=self.__class__.__name__, _rate=self._rate, _axes=self._axes)


class BatchNorm(HybridBlock):
    """Batch normalization with moving stats (ref: basic_layers.py BatchNorm).

    Moving mean/var update happens front-end-side from the op's batch-stat
    outputs — under hybridization the in-place write is harvested from the
    trace and applied after the jit call (see gluon/block.py CachedOp).
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self._axis = axis
        self._momentum = momentum
        self.gamma = self.params.get("gamma",
                                     grad_req="write" if scale else "null",
                                     shape=(in_channels,),
                                     init=initializer.create(gamma_initializer)
                                     if isinstance(gamma_initializer, str) else gamma_initializer,
                                     allow_deferred_init=True,
                                     differentiable=scale)
        self.beta = self.params.get("beta",
                                    grad_req="write" if center else "null",
                                    shape=(in_channels,),
                                    init=initializer.create(beta_initializer)
                                    if isinstance(beta_initializer, str) else beta_initializer,
                                    allow_deferred_init=True,
                                    differentiable=center)
        self.running_mean = self.params.get("running_mean", grad_req="null",
                                            shape=(in_channels,),
                                            init=initializer.create(running_mean_initializer)
                                            if isinstance(running_mean_initializer, str)
                                            else running_mean_initializer,
                                            allow_deferred_init=True,
                                            differentiable=False)
        self.running_var = self.params.get("running_var", grad_req="null",
                                           shape=(in_channels,),
                                           init=initializer.create(running_variance_initializer)
                                           if isinstance(running_variance_initializer, str)
                                           else running_variance_initializer,
                                           allow_deferred_init=True,
                                           differentiable=False)

    def _pre_infer(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            if p.shape == (0,):
                p.shape = (c,)

    def cast(self, dtype):
        if np.dtype(dtype).name == "float16":
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        from ... import autograd
        out = F.BatchNorm(x, gamma, beta, running_mean, running_var,
                          output_mean_var=True, **self._kwargs)
        if isinstance(out, (list, tuple)):
            y, batch_mean, batch_var = out
            if autograd.is_training() and not self._kwargs["use_global_stats"]:
                m = self._momentum
                with autograd.pause():
                    running_mean._write(
                        m * running_mean._read()
                        + (1 - m) * batch_mean.detach()._read())
                    running_var._write(
                        m * running_var._read()
                        + (1 - m) * batch_var.detach()._read())
            return y
        return out

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join("=".join([k, v.__repr__()])
                              for k, v in self._kwargs.items()))


class InstanceNorm(HybridBlock):
    """ref: basic_layers.py class InstanceNorm → InstanceNorm op."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"eps": epsilon}
        self._axis = axis
        self.gamma = self.params.get("gamma",
                                     grad_req="write" if scale else "null",
                                     shape=(in_channels,),
                                     init=initializer.create(gamma_initializer)
                                     if isinstance(gamma_initializer, str) else gamma_initializer,
                                     allow_deferred_init=True)
        self.beta = self.params.get("beta",
                                    grad_req="write" if center else "null",
                                    shape=(in_channels,),
                                    init=initializer.create(beta_initializer)
                                    if isinstance(beta_initializer, str) else beta_initializer,
                                    allow_deferred_init=True)

    def _pre_infer(self, x):
        c = x.shape[1]
        for p in (self.gamma, self.beta):
            if p.shape == (0,):
                p.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, **self._kwargs)


class LayerNorm(HybridBlock):
    """Layer normalization (ref: src/operator/nn/layer_norm.cc; gluon layer
    appears in 1.3 — included for the transformer stack)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon}
        self._axis = axis
        self.gamma = self.params.get("gamma",
                                     grad_req="write" if scale else "null",
                                     shape=(in_channels,),
                                     init=initializer.create(gamma_initializer)
                                     if isinstance(gamma_initializer, str) else gamma_initializer,
                                     allow_deferred_init=True)
        self.beta = self.params.get("beta",
                                    grad_req="write" if center else "null",
                                    shape=(in_channels,),
                                    init=initializer.create(beta_initializer)
                                    if isinstance(beta_initializer, str) else beta_initializer,
                                    allow_deferred_init=True)

    def _pre_infer(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p.shape == (0,):
                p.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, **self._kwargs)


class RMSNorm(HybridBlock):
    """Root-mean-square normalization over the last axis: x · rsqrt(mean(x²)
    + epsilon) · gamma, no mean taken off and no shift (op ``RMSNorm``)."""

    def __init__(self, epsilon=1e-5, in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=initializer.One(),
                                     allow_deferred_init=True)

    def _pre_infer(self, x):
        if self.gamma.shape == (0,):
            self.gamma.shape = (x.shape[-1],)

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._epsilon)


class GatedMLP(HybridBlock):
    """SwiGLU feed-forward block W2 (silu(W1 x) ⊙ W3 x), no bias (Shazeer,
    arXiv:2002.05202).  The children are named as the published checkpoints
    name them: ``w1_`` the gate, ``w3_`` the up and ``w2_`` the down
    projection."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            dense = lambda out, inp, name: Dense(       # noqa: E731
                out, flatten=False, use_bias=False, in_units=inp, prefix=name)
            self.w1 = dense(hidden_size, units, "w1_")
            self.w3 = dense(hidden_size, units, "w3_")
            self.w2 = dense(units, hidden_size, "w2_")

    def hybrid_forward(self, F, x):
        gate = F.Activation(self.w1(x), act_type="silu")
        return self.w2(gate * self.w3(x))


class ShortConv(HybridBlock):
    """The gated short convolution of the LFM2 family (Liquid AI,
    ``Lfm2ShortConv``) on (B, S, units), no bias: ``(b, c, u) =
    split3(W_in x)``, ``v = causal_conv(b ⊙ u)`` (depthwise, ``kernel``
    taps, no output row sees a later input row), ``out = W_out (c ⊙ v)``.
    The two gates and the convolution are staged under the scope
    ``short_conv``; the projections carry their own names."""

    def __init__(self, units, kernel=3, **kwargs):
        super().__init__(**kwargs)
        self._units, self._kernel = units, int(kernel)
        with self.name_scope():
            self.in_proj = Dense(3 * units, flatten=False, use_bias=False,
                                 in_units=units, prefix="in_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, self._kernel))
            self.out_proj = Dense(units, flatten=False, use_bias=False,
                                  in_units=units, prefix="out_")

    def hybrid_forward(self, F, x, conv_weight):
        d = self._units
        bcu = self.in_proj(x)
        with jax.named_scope("short_conv"):
            b, c, u = (F.slice_axis(bcu, axis=-1, begin=i * d, end=(i + 1) * d)
                       for i in range(3))
            v = F._contrib_CausalConv1D(b * u, conv_weight,
                                        kernel=self._kernel)
            gated = c * v
        return self.out_proj(gated)


class Embedding(HybridBlock):
    """Index → vector lookup (ref: basic_layers.py class Embedding →
    Embedding op; rowsparse grad becomes a dense scatter-add on TPU)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype}
        self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                      init=weight_initializer, dtype=dtype,
                                      allow_deferred_init=True)

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, **self._kwargs)

    def __repr__(self):
        return "{name}({input_dim} -> {output_dim}, {dtype})".format(
            name=self.__class__.__name__, **self._kwargs)


class Flatten(HybridBlock):
    """ref: basic_layers.py class Flatten → Flatten op."""

    def hybrid_forward(self, F, x):
        return F.Flatten(x)

    def __repr__(self):
        return self.__class__.__name__


class Lambda(Block):
    """Wrap a function as a Block (ref: basic_layers.py class Lambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            assert hasattr(nd, function), \
                "Function name %s is not found in ndarray." % function
            self._func_impl = getattr(nd, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: {} of type {}"
                             .format(function, type(function)))

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return "{name}({function})".format(name=self.__class__.__name__,
                                           function=self._func_name)


class HybridLambda(HybridBlock):
    """Wrap a function as a HybridBlock (ref: basic_layers.py HybridLambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            assert hasattr(nd, function), \
                "Function name %s is not found in ndarray." % function
            func = getattr(nd, function)
            self._func = lambda F, *args: func(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: {} of type {}"
                             .format(function, type(function)))

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return "{name}({function})".format(name=self.__class__.__name__,
                                           function=self._func_name)
