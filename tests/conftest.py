"""Test configuration.

JAX-dependent tests run on a virtual 8-device CPU mesh so multi-chip sharding
is exercised without TPU hardware (the driver separately dry-run-compiles the
multi-chip path; see __graft_entry__.py).  The env vars must be set before jax
is first imported, hence here at conftest import time.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache: kernel compiles dominate test wall-clock
# when every pytest process recompiles from scratch; share one cache.
from tpunode.verify.engine import enable_compile_cache

enable_compile_cache()

# Minimal async test support (pytest-asyncio is not in the image): run any
# coroutine test function on a fresh event loop.
import asyncio
import inspect

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: coroutine test (run via asyncio.run)")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        from tpunode import asyncsan, threadsan

        if asyncsan.enabled() or threadsan.enabled():
            # TPUNODE_ASYNCSAN=1: every coroutine test runs under asyncio
            # debug mode with the tight slow-callback threshold, so a
            # blocking call inside the suite logs itself with its source
            # location (ANALYSIS.md, runtime sanitizers).
            # TPUNODE_THREADSAN=1 (ISSUE 18): the lock registry arms and
            # each test's loop thread registers for blocking-acquire
            # attribution — the thread-side twin.
            async def _sanitized():
                if asyncsan.enabled():
                    asyncsan.install()
                if threadsan.enabled():
                    threadsan.install()
                await func(**kwargs)

            asyncio.run(_sanitized())
        else:
            asyncio.run(func(**kwargs))
        return True
    return None


@pytest.fixture
def threadsan_armed(monkeypatch):
    """Arm threadsan for one test (ISSUE 18): fresh registry state, env
    set so any Node/conftest install path agrees, disarmed afterwards.
    The test asserts on the yielded registry's counters/findings."""
    from tpunode.threadsan import registry

    monkeypatch.setenv("TPUNODE_THREADSAN", "1")
    registry.reset()
    registry.arm()
    yield registry
    registry.disarm()
    registry.reset()
