"""Test harness configuration.

Mirrors the reference's test-ring strategy (SURVEY §5): all tests run on CPU
with a virtual 8-device mesh so distributed semantics are exercised without
TPU hardware (reference analog: DistributedQueryRunner boots a multi-node
cluster inside one JVM).

Env vars MUST be set before jax is imported anywhere.
"""

import os

# Arm the lock sanitizer (presto_tpu/obs/sanitizer.py) for the whole
# suite BEFORE any engine module creates a lock: every engine lock
# created under pytest is instrumented (held-set tracking, ordering,
# shared-attr write checks). Violations accumulate process-wide and
# never fail a test by themselves — tests/test_concurrent_serving.py
# races the serving path deliberately and asserts the count stays 0.
# Export PRESTO_TPU_LOCK_SANITIZER=0 to opt out.
os.environ.setdefault("PRESTO_TPU_LOCK_SANITIZER", "1")

# tier-1 runs on the CPU backend with eight virtual devices
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compile cache for the whole suite: programs compile once
# per canonical shape per MACHINE, not per pytest process — repeated
# tier-1 runs pay the multi-minute compile wall (the dist suite's
# shard_map programs especially) only on the first cold run.
# compilecache decides the directory: JAX_COMPILATION_CACHE_DIR where
# set, else <checkout>/.jax_cache.
from presto_tpu import compilecache as _cc  # noqa: E402

_cc.enable_persistent_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: boots real OS processes / long compiles",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)
