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


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """When a test module ends, drop what it compiled. A compiled CPU
    program holds memory mappings for as long as a jit cache (or an engine
    a module-scoped fixture built) holds it, a worker process runs many
    modules, and the kernel bounds the mappings of ONE process
    (``vm.max_map_count``, 65530): tests/test_lfm2_moe.py alone ends at
    26,000, and a worker that reached the bound died inside XLA (a
    segmentation fault reading the compile cache, an abort writing it), in
    whatever test happened to run then. After the module's own fixtures are
    gone, collect and clear JAX's in-memory caches: the same module then
    ends at 800. What a later module needs again comes from the persistent
    cache or is compiled again."""
    yield
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
