"""Test configuration: force an 8-device virtual CPU mesh before jax loads.

Mirrors the reference's in-process multi-node harness strategy
(test/pilosa.go:298-355 boots N real servers in one process): we fake an
8-device TPU pod with XLA host devices so sharding/collective paths run in CI
without hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Hermetic: a test run neither reads nor fills the persistent compile
# cache the server places under the checkout (pilosa_tpu/compile_cache.py)
# — a warm cache would skip the compile events some tests count.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)
