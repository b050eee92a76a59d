"""Device/Context model.

Parity with python/mxnet/context.py (Context, cpu(), gpu(), current_context)
re-based on JAX devices.  ``tpu(i)`` is the accelerator context; ``gpu(i)`` is
kept as a compatibility alias that resolves to the i-th accelerator so that
reference scripts written against ``mx.gpu()`` run unchanged.

Context maps to a concrete ``jax.Device`` lazily (``jax_device()``): on a TPU
host that is a TPU chip, under the CPU test mesh it is one of the
``--xla_force_host_platform_device_count`` host devices, so multi-device
semantics (KVStore 'device', DataParallelExecutorGroup splits) are testable
without hardware — the same trick the reference uses by running
test_model_parallel on CPU contexts (SURVEY §4.1).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_devices"]


class Context:
    """Execution device. devtype: 'cpu', 'tpu' ('gpu' aliases 'tpu')."""

    _local = threading.local()
    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    devstr2type["gpu"] = 2

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type == "gpu":
                device_type = "tpu"
            if device_type not in self.devstr2type:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def jax_device(self):
        """Resolve to a concrete jax.Device.

        Multi-process runs: only THIS process's devices are addressable,
        so contexts index jax.local_devices() (jax.devices() is the
        global list — rank 1's "cpu(0)" must not resolve to rank 0's
        device)."""
        import jax

        multiproc = jax.process_count() > 1
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            backend = "cpu"
            try:
                devs = (jax.local_devices(backend=backend) if multiproc
                        else jax.devices(backend))
            except RuntimeError:
                devs = jax.local_devices() if multiproc else jax.devices()
            return devs[min(self.device_id, len(devs) - 1) if self.device_id >= len(devs) else self.device_id]
        # default backend: TPU if present, else host devices
        devs = jax.local_devices() if multiproc else jax.devices()
        if self.device_id >= len(devs):
            raise ValueError(
                "context %s: only %d devices available" % (self, len(devs)))
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._local, "stack"):
            Context._local.stack = []
        Context._local.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._local.stack.pop()

    @staticmethod
    def default_ctx():
        import jax

        # a backend that cannot initialise raises here: answering "cpu"
        # would run the whole program on the host without a word
        plat = jax.default_backend()
        return Context("tpu" if plat in ("tpu", "gpu") else "cpu", 0)

    def empty_cache(self):
        """Parity no-op: XLA owns HBM pooling (reference: GPUPooledStorageManager)."""


def current_context():
    if getattr(Context._local, "stack", None):
        return Context._local.stack[-1]
    return Context.default_ctx()


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Compatibility alias for reference scripts: resolves to the accelerator."""
    return Context("tpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_devices(device_type="tpu"):
    """Devices this process can address (multi-process: local only, to
    stay consistent with Context.jax_device resolution)."""
    import jax

    multiproc = jax.process_count() > 1
    if device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        try:
            return len(jax.local_devices(backend="cpu") if multiproc
                       else jax.devices("cpu"))
        except RuntimeError:
            return 1
    return len(jax.local_devices() if multiproc else jax.devices())


def gpu_memory_info(device_id=0):
    """(free, total) bytes on an accelerator device (ref: context.py
    gpu_memory_info → cudaMemGetInfo; here XLA's per-device allocator
    stats — the storage-manager accounting of SURVEY §2.1)."""
    for ctx_type in ("tpu", "gpu"):
        try:
            dev = Context(ctx_type, device_id).jax_device()
            break
        except Exception:
            dev = None
    if dev is None:
        raise MXNetError("no accelerator device %d" % device_id)
    stats = dev.memory_stats() or {}
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return total - used, total


def memory_stats(ctx=None):
    """Full allocator statistics for a context (pool stats parity:
    src/storage/pooled_storage_manager.h — XLA's BFC allocator is the
    pool here; keys include bytes_in_use, peak_bytes_in_use,
    num_allocs, bytes_limit when the backend reports them)."""
    ctx = ctx or current_context()
    dev = ctx.jax_device()
    return dict(dev.memory_stats() or {})
