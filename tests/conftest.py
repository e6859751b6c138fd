"""Test config: JAX pinned to a virtual 8-device CPU mesh (multi-chip
sharding tests run without TPU hardware), asyncio helpers."""

import os

# Must be set before jax import anywhere in the test process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio
import inspect

import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--repeat", type=int, default=1, metavar="N",
        help="run each selected test N times (flaky-election hunting; "
             "used by scripts/storm_smoke.sh on the raft storm tests)")


def pytest_configure(config):
    config.addinivalue_line("markers",
                            "asyncio_plain: async test run via asyncio.run")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 "
                   "(run explicitly or without -m 'not slow')")


def pytest_generate_tests(metafunc):
    """--repeat N: parametrize every test N times (distinct node ids, so
    one flaky failure out of N is reported precisely)."""
    count = metafunc.config.getoption("--repeat")
    if count > 1:
        metafunc.fixturenames.append("__repeat")
        metafunc.parametrize("__repeat", range(count))


def pytest_collection_modifyitems(items):
    for item in items:
        if inspect.iscoroutinefunction(item.function):
            item.add_marker(pytest.mark.asyncio_plain)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio runner: any `async def test_*` runs in a fresh loop
    (no pytest-asyncio dependency in the image)."""
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
