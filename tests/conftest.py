"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU mesh so every sharding/pjit path is
exercised hermetically (no TPU needed), matching how the driver dry-runs the
multi-chip path. Both variables are read when jax is imported, so they are
set here, before any test module imports it.
"""

import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "perf: performance-tier tests (reference perf_test.clj analog)")


@pytest.fixture(autouse=True)
def _restore_compilation_cache_dir():
    """Entry points a test drives in-process (the CLI, the service
    daemon) point JAX's persistent compilation cache at a directory for
    the rest of the process; put it back so later tests in the worker
    compile as before."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
