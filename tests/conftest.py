"""Test harness config: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's CPU backend with 8 virtual devices (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Unit tests ask for the CPU (deterministic, multi-device) before jax is
# imported; engine subprocesses the tests spawn inherit the request. The
# chip is exercised by chip_smoke.py, not by this suite.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic compile caches: tests that count cache hits/misses pass their own
# tmp_path directory, which an ambient JAX_COMPILATION_CACHE_DIR would
# override (runner._setup_compilation_cache gives the variable precedence).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import asyncio
import functools
import inspect

import pytest


def pytest_collection_modifyitems(items):
    """Run ``async def`` tests via asyncio.run (no pytest-asyncio available)."""
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.obj = _sync_wrapper(item.function)


def _sync_wrapper(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(fn(*args, **kwargs))
    return wrapper


@pytest.fixture(autouse=True)
def _reset_singletons():
    from production_stack_tpu.utils import SingletonMeta
    SingletonMeta._instances.clear()
    yield
    SingletonMeta._instances.clear()
