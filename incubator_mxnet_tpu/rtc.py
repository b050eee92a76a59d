"""Runtime kernel compilation (ref: python/mxnet/rtc.py + src/common/rtc.cc).

The reference's ``CudaModule`` NVRTC-compiles CUDA C at runtime and
launches kernels on NDArrays.  The TPU-native equivalent of "user writes
a kernel, framework compiles it at runtime" is Pallas: ``PallasModule``
wraps user kernel functions, ``get_kernel().launch(...)`` places the
pallas_call and hands NDArrays through — same module/kernel/launch
shape as the reference API, with grid dims playing the same role.

``launch`` compiles the kernel for the device it runs on, or raises.
``launch(..., interpret=True)`` runs it through the Pallas interpreter
instead, which is how kernels are tested on the CPU mesh; the mode is the
caller's choice and never follows the platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


class PallasModule(object):
    """A collection of runtime-compiled kernels
    (ref: rtc.py CudaModule:42 — source string → module; here the
    "source" is a dict of Python Pallas kernel functions)."""

    def __init__(self, kernels, exports=()):
        if not isinstance(kernels, dict) or not kernels:
            raise MXNetError("PallasModule takes {name: kernel_fn}")
        self._kernels = dict(kernels)
        self.exports = tuple(exports) or tuple(kernels)
        missing = [n for n in self.exports if n not in self._kernels]
        if missing:
            raise MXNetError("exports %s name no kernel (have %s)"
                             % (missing, sorted(self._kernels)))

    def get_kernel(self, name, out_shape=None, out_dtype=None):
        """Look up an exported kernel (ref: rtc.py get_kernel:112).
        ``out_shape``/``out_dtype``: output spec; defaults to the first
        input's at launch."""
        if name not in self.exports:
            raise MXNetError("kernel %r is not exported (exports: %s)"
                             % (name, sorted(self.exports)))
        return PallasKernel(name, self._kernels[name], out_shape, out_dtype)


class PallasKernel(object):
    """One launchable kernel (ref: rtc.py CudaKernel:173)."""

    def __init__(self, name, fn, out_shape=None, out_dtype=None):
        self.name = name
        self._fn = fn
        self._out_shape = out_shape
        self._out_dtype = out_dtype
        self._compiled = {}

    def launch(self, args, ctx=None, grid_dims=(1,), block_dims=None,
               shared_mem=0, interpret=False):
        """Run the kernel over NDArray args; returns the output NDArray
        (ref: rtc.py CudaKernel.launch:185 — grid_dims maps to the Pallas
        grid; block_dims/shared_mem are CUDA-isms the TPU compiler owns).
        ``interpret=True`` evaluates the kernel with the Pallas
        interpreter (any backend); the default compiles it.
        """
        from jax.experimental import pallas as pl

        vals = [a._read() if isinstance(a, NDArray) else jnp.asarray(a)
                for a in args]
        if ctx is not None:
            dev = ctx.jax_device()
            vals = [jax.device_put(v, dev) for v in vals]
        if any(int(g) < 1 for g in grid_dims):
            raise MXNetError("grid_dims must be positive, got %r"
                             % (grid_dims,))
        # keep the full grid rank: size-1 dims still own a program_id axis
        grid = tuple(int(g) for g in grid_dims) or (1,)
        out_shape = (tuple(self._out_shape) if self._out_shape is not None
                     else tuple(vals[0].shape))
        out_dtype = (self._out_dtype if self._out_dtype is not None
                     else vals[0].dtype)
        interpret = bool(interpret)
        key = (tuple(v.shape for v in vals), tuple(str(v.dtype)
                                                   for v in vals), grid,
               interpret)
        call = self._compiled.get(key)
        if call is None:
            call = jax.jit(pl.pallas_call(
                self._fn, grid=grid,
                out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
                interpret=interpret))
            self._compiled[key] = call
        return NDArray(call(*vals), ctx=ctx)   # ctx=None → current context


def CudaModule(*args, **kwargs):  # noqa: N802 - reference name
    """The reference entry point: CUDA source cannot target a TPU.
    Raises with a pointer at PallasModule (the rtc capability here)."""
    raise MXNetError(
        "CudaModule compiles CUDA C, which has no TPU target. Use "
        "mx.rtc.PallasModule with Pallas kernel functions — the runtime "
        "kernel-compilation path on this backend.")
