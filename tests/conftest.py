"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference strategy of running multi-device semantics on CPU
contexts (tests/python/unittest/test_model_parallel.py runs on CPU; SURVEY
§4.1) — multi-chip sharding is validated on
``--xla_force_host_platform_device_count=8`` host devices.

The suite is a CPU program: the platform is pinned here, before the first
device use, whatever the machine has attached.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_rngs(request):
    """Per-test deterministic seeding with logged seed (parity:
    tests/python/unittest/common.py with_seed decorator)."""
    seed = abs(hash(request.node.nodeid)) % (2 ** 31)
    np.random.seed(seed)
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    yield
