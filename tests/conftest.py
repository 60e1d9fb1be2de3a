"""Test harness configuration.

All unit tests run on a virtual 8-device CPU mesh so that sharding code
paths (pjit/shard_map over a Mesh) are exercised without TPU hardware,
mirroring how the driver dry-runs the multi-chip path.  The chip is
reached only through ``chip_smoke.py``; tests never open it.
"""

import os

from fabric_tpu.utils.xla_env import (
    enable_compile_cache,
    ensure_cpu_compile_workaround,
    ensure_host_device_count,
)

# set before jax initializes its backends; subprocesses tests spawn
# inherit the same platform pin
os.environ["JAX_PLATFORMS"] = "cpu"
ensure_host_device_count(8)
ensure_cpu_compile_workaround()

# the P-256 verify graph takes minutes to compile on the CPU backend;
# share one persistent cache across test runs and CLI child processes
enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    import numpy as np

    return np.random.default_rng(20260729)
