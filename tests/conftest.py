"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
multi-chip sharding paths compile and execute without TPU hardware."""

import asyncio
import functools
import inspect
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# pyarrow's bundled mimalloc pool segfaulted in `pa.array(...)` on the
# decode threads of this sandbox from one hour to the next (PR 25: every
# run of tests/test_pipeline_e2e.py, at the parent commit too, none with
# the system or jemalloc pool; PERF.md section 7). The tests check the
# program, not an allocator: take malloc unless the caller chose a pool.
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: outside the tier-1 budget (deselected by "
                   "`-m 'not slow'`)")


@pytest.fixture(autouse=True)
def _disarm_failpoints():
    """Failpoint hygiene (chaos satellite): no test can leak an armed
    site (or a mid-stall block, or a supervision-forced host-oracle
    degrade) into the next test — cleared after every test, pass or
    fail."""
    yield
    from etl_tpu.chaos import failpoints
    from etl_tpu.ops import engine

    failpoints.disarm_all()
    engine.clear_forced_oracle()


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests on a fresh event loop (no pytest-asyncio in the
    image)."""
    if inspect.iscoroutinefunction(pyfuncitem.function):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(asyncio.wait_for(pyfuncitem.obj(**kwargs), timeout=120))
        return True
    return None
