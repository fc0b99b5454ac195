"""Test harness: run everything on CPU with 8 virtual devices so sharding
over the tile axis is exercised without TPU hardware (the driver's
dryrun_multichip uses the same trick)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# x64 + the package's compile-cache policy (JAX_COMPILATION_CACHE_DIR
# when set, else <checkout>/.jax_cache): the engine's fused step is a
# large XLA program, so a warm cache makes repeated suite runs
# compile-free.
import graphite_tpu  # noqa: E402,F401

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Single-process full-suite runs accumulate jit/tracing cache state
    until dispatch slows to a crawl (the reason tools/run_tests.sh runs
    one process per module). Dropping the in-memory caches at module
    boundaries keeps the full-suite run at per-module pace; the
    persistent compile cache above turns any re-lowering into a fast
    deserialize."""
    yield
    jax.clear_caches()
