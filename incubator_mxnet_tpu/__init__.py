"""incubator_mxnet_tpu — a TPU-native deep-learning framework with the
capabilities of Apache MXNet 1.2 (reference: jinhuang415/incubator-mxnet).

Not a port: JAX/XLA is the compile+execute substrate, Pallas the custom-kernel
path, pjit/shard_map + XLA collectives the distributed fabric.  See SURVEY.md
at the repo root for the blueprint and per-module docstrings for the
reference-parity map (file:line citations into /root/reference).

Import convention mirrors the reference:

    import incubator_mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu(0))
"""
import sys as _sys
import time as _time

# the start-up record's ``package_import`` (telemetry.startup()): this
# file's first and last line and the end of each group of imports, as
# plain floats until the recorder is there to take them
_stamps = [("start", _time.perf_counter())]
_jax_preloaded = "jax" in _sys.modules

__version__ = "0.1.0"

from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_devices
_stamps.append(("base_context", _time.perf_counter()))

from . import ops
_stamps.append(("ops", _time.perf_counter()))   # JAX's import, unless preloaded
from . import ndarray
from . import ndarray as nd  # canonical alias, as in mxnet
from .ndarray import NDArray

from . import autograd
from . import engine
from . import random
from . import random_state
_stamps.append(("ndarray_engine", _time.perf_counter()))

from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym  # canonical alias, as in mxnet
from .symbol import Symbol

from . import lr_scheduler
from . import optimizer
from . import optimizer as opt  # alias, as in mxnet
from . import initializer
from . import initializer as init  # alias, as in mxnet
from .initializer import Xavier

from . import name
from . import kvstore
from . import kvstore as kv  # alias, as in mxnet
from . import io
from . import recordio
from . import image
from . import metric
from . import callback
from . import monitor
from . import module
from . import module as mod  # alias, as in mxnet
from . import model
_stamps.append(("symbol_module", _time.perf_counter()))
from . import gluon
_stamps.append(("gluon", _time.perf_counter()))
from . import parallel
from . import contrib
from . import operator
from . import rnn
from . import executor_manager
from . import rtc
from . import profiler
from . import telemetry
from . import config
from . import visualization
from . import visualization as viz

# env-var driven startup behavior (SURVEY §5.6 config layer)
config.apply_compile_cache()
if config.get_bool("PROFILER_AUTOSTART"):
    import atexit as _atexit
    profiler.set_config(continuous_dump=True)
    profiler.set_state("run")
    _atexit.register(lambda: profiler.set_state("stop"))
if config.get_int("SEED") is not None:
    random.seed(config.get_int("SEED"))

_stamps.append(("parallel_rest", _time.perf_counter()))
telemetry.blackbox.package_imported(_stamps, _jax_preloaded)
del _stamps, _jax_preloaded
