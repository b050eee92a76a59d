"""Device mesh construction and axis conventions.

The reference assigns work to devices by Context lists
(DataParallelExecutorGroup) and `ctx_group` attrs (PlaceDevice pass);
TPU-natively the device topology is a named ``jax.sharding.Mesh`` and
placement is a sharding annotation.  Axis name conventions used throughout
the framework:

  "dp" — data parallel (batch dim)           ⇔ KVStore local/device/dist
  "tp" — tensor/model parallel               ⇔ ctx_group model parallelism
  "pp" — pipeline stages                     ⇔ (new capability)
  "sp" — sequence/context parallel           ⇔ (new capability, ring attn)
  "ep" — expert parallel                     ⇔ (new capability)
"""
from __future__ import annotations

import numpy as np

import jax
from jax.interpreters.partial_eval import DynamicJaxprTracer
from jax.sharding import Mesh, PartitionSpec, NamedSharding

__all__ = ["make_mesh", "data_parallel_mesh", "P", "NamedSharding", "Mesh",
           "use_mesh", "current_mesh"]

P = PartitionSpec

import threading as _threading

_mesh_tls = _threading.local()


def _stack():
    # thread-local: concurrent trainers/eval threads must not see each
    # other's scoped mesh (same reason jax's mesh managers are TLS)
    if not hasattr(_mesh_tls, "stack"):
        _mesh_tls.stack = []
    return _mesh_tls.stack


class use_mesh(object):
    """Scope a mesh as the framework-wide default: layers that need a
    device topology (gluon.nn.MultiHeadAttention's seq_axis path, the
    ring-attention op) resolve it from here when not passed explicitly —
    the role Context lists played for the reference's executors, for mesh
    axes.  Usable as a context manager or activated for the whole program
    via ``use_mesh(mesh).activate()``."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _stack().append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _stack().pop()

    def activate(self):
        _stack().append(self.mesh)
        return self.mesh


def is_staging(x):
    """True when ``x`` is a tracer from an enclosing jit's staging trace
    (as opposed to a concrete array OR an eager-autodiff tracer whose
    primitives execute immediately)."""
    return isinstance(x, DynamicJaxprTracer)


def dispatch_on_mesh(fn, mesh, in_specs, *arrays):
    """Run a collective-bearing ``fn(*arrays)`` correctly in both worlds.

    Staging inside an enclosing jit: call straight through — the caller's
    shardings flow in and outputs stay sharded.  Eager (including the
    eager autograd tape, whose vjp primitives execute immediately): place
    each operand per its PartitionSpec on ``mesh`` first.  Returns
    ``(outputs, eager)``; eager callers usually want ``gather_home`` on
    array outputs so downstream single-device ops see plain arrays.
    """
    if is_staging(arrays[0]):
        return fn(*arrays), False
    placed = [jax.device_put(a, NamedSharding(mesh, s))
              for a, s in zip(arrays, in_specs)]
    return fn(*placed), True


def gather_home(x, mesh):
    """Pull a mesh-sharded eager result onto one device (traceable and
    transposable, so the tape differentiates through it)."""
    return jax.device_put(x, mesh.devices.flat[0])


def current_mesh(required=False):
    """The innermost scoped mesh, or None (raise when ``required``)."""
    if _stack():
        return _stack()[-1]
    if required:
        raise RuntimeError(
            "no device mesh in scope — wrap the call in "
            "`with parallel.use_mesh(make_mesh({...})):` or pass mesh=")
    return None


def make_mesh(axis_sizes, devices=None):
    """Build a Mesh from {"dp": 4, "tp": 2, ...} (row-major over devices)."""
    names = tuple(axis_sizes.keys())
    sizes = tuple(int(v) for v in axis_sizes.values())
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(sizes))
    assert len(devices) >= n, \
        "mesh needs %d devices, have %d" % (n, len(devices))
    arr = np.array(devices[:n]).reshape(sizes)
    return Mesh(arr, names)


def data_parallel_mesh(num_devices=None, devices=None):
    """1-D dp mesh over all (or the first N) devices."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return make_mesh({"dp": len(devices)}, devices)
