"""Test configuration.

Tests run on the CPU, unconditionally, with a virtual 8-device mesh so the
sharding logic is exercised (the driver separately dry-runs multi-chip via
__graft_entry__.dryrun_multichip). The chip is reached through
chip_smoke.py, never through pytest.
"""

import asyncio
import functools
import inspect
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else .jax_cache
# in the checkout — the one location every process entry uses): the CPU
# suite is compile-dominated and a cold run overshoots the tier-1 wall
# clock. The cache is keyed by HLO + compile flags, so it cannot change
# what any test computes — it only lets re-runs (including the driver's
# verify pass after a build session) pay each compile once.
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

import pytest


def pytest_collection_modifyitems(config, items):
    # Support plain `async def test_*` without pytest-asyncio (not installed
    # in this environment): wrap them in asyncio.run.
    for item in items:
        if isinstance(item, pytest.Function) and inspect.iscoroutinefunction(item.obj):
            item.obj = _sync_wrapper(item.obj)


def _sync_wrapper(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(asyncio.wait_for(fn(*args, **kwargs), timeout=120))

    return wrapper


@pytest.fixture(autouse=True)
def _fresh_process_local_buses():
    """Isolate process-local runtime state between tests."""
    yield
    from dynamo_tpu.runtime.discovery import MemoryDiscovery
    from dynamo_tpu.runtime.distributed import LocalRequestPlane
    from dynamo_tpu.runtime.events import MemoryEventPlane

    MemoryDiscovery.reset()
    LocalRequestPlane.reset()
    MemoryEventPlane.reset()
