"""Test configuration: hermetic CPU backend with 8 virtual devices.

Mirrors the reference test strategy (SURVEY.md §4): control-plane and
data-plane logic runs without real infrastructure.  Multi-chip sharding
tests use an 8-device virtual CPU mesh
(xla_force_host_platform_device_count), the TPU analogue of envtest.
"""

import os

# Tests are hermetic: forced (not setdefault) onto the CPU platform, in
# the env for subprocesses and in jax.config for this process.  The chip
# is for chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "asyncio: run the test inside a fresh asyncio event loop")
    config.addinivalue_line(
        "markers", "tpu: requires real TPU hardware (skipped on CPU backend)")
    config.addinivalue_line(
        "markers",
        "slow: multi-process / subprocess / long-parity tests.  CI "
        "default: `pytest -m 'not slow'` (~9 min hermetic core); "
        "nightly/full: `pytest tests/` (everything)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests of the reliability layer "
        "(kfserving_tpu/reliability/).  Deliberately NOT slow: the "
        "fast tier runs them (`-m 'not slow'`), and soak runs can "
        "select just them with `-m chaos`")


@pytest.fixture(autouse=True)
def _private_param_cache(tmp_path_factory):
    """Per-test mmap param-cache isolation: without this, the first
    test to load an artifact stores into the user-level default cache
    and every later load of the same config silently mmaps — tests
    asserting materialization phases (init_params/checkpoint marks)
    would then depend on execution order, and runs would leak entries
    into ~/.cache.  Subprocess replicas inherit the env, so warm-swap
    tests still share a cache WITHIN their test."""
    prior = os.environ.get("KFS_PARAM_CACHE")
    os.environ["KFS_PARAM_CACHE"] = str(
        tmp_path_factory.mktemp("param-cache"))
    yield
    if prior is None:
        os.environ.pop("KFS_PARAM_CACHE", None)
    else:
        os.environ["KFS_PARAM_CACHE"] = prior


@pytest.fixture(autouse=True)
def _metrics_registry_guard():
    """Process-wide metrics isolation: the observability registry is
    reset after EVERY test, and a test that begins with samples
    already present fails loudly — that means some earlier code
    leaked series past its teardown (bypassing this fixture), which
    would let one test's gauges/counters assert another test's
    /metrics expectations."""
    from kfserving_tpu.observability import REGISTRY

    leaked = REGISTRY.sample_names()
    if leaked:
        REGISTRY.reset()
        pytest.fail(
            "metrics registry held samples leaked from outside this "
            f"test: {sorted(leaked)[:10]}")
    yield
    REGISTRY.reset()


# Per-test limit for `async def` tests: a stuck await fails that one
# test instead of eating the whole run's budget.
ASYNC_TEST_TIMEOUT_S = 120.0


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests in a fresh event loop (no pytest-asyncio in the
    hermetic environment), each under ASYNC_TEST_TIMEOUT_S."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}

        async def bounded():
            try:
                async with asyncio.timeout(ASYNC_TEST_TIMEOUT_S) as limit:
                    await func(**kwargs)
            except TimeoutError:
                if limit.expired():  # ours, not a timeout the test raised
                    pytest.fail(
                        f"async test exceeded {ASYNC_TEST_TIMEOUT_S:.0f}s "
                        "(stuck await?)", pytrace=False)
                raise

        asyncio.run(bounded())
        return True
    return None
