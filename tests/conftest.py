"""Test fixtures.

Mirrors the reference's conftest strategy (SURVEY.md §4): distributed paths
run without real hardware — here an 8-device virtual CPU mesh via
``xla_force_host_platform_device_count`` stands in for a TPU slice, and the
``ray_start_regular`` fixture boots/tears down a fresh local runtime per test.
"""

import os

# Must be set before jax is imported anywhere in the test process: the
# tests run on the virtual 8-device CPU backend.
os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent XLA compilation cache: compile-heavy tests (spmd transformer,
# ring attention, wave executor) drop ~2.5x on warm runs, and the cache
# survives across pytest processes. Same rule as the program: the
# directory JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
from ray_tpu._private.compile_cache import ensure_compile_cache  # noqa: E402

ensure_compile_cache()
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.shutdown()
    worker = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield worker
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def eight_device_mesh():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, (
        "tests require XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    yield devices[:8]
