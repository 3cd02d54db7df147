"""Shared fixtures + the multi-device test harness.

The suite runs on SIMULATED host devices: ``_force_host_devices`` appends
``--xla_force_host_platform_device_count=N`` (default 8, override with
``REPRO_TEST_DEVICE_COUNT``) to XLA_FLAGS before jax's first import, which is
the only moment the device count can be set. The guard makes it a no-op when
jax was already imported (e.g. under a driver that pre-initialized it) or
when XLA_FLAGS already carries an explicit count (repro.launch.dryrun's 512).

Tests that REQUIRE several devices take the ``multidevice`` fixture (skips
below 8 devices instead of failing) and carry ``@pytest.mark.multidevice``
so CI can split the matrix: the default job runs single-device with
``REPRO_TEST_DEVICE_COUNT=1 pytest -m "not multidevice"``, the multidevice
job runs ``pytest -m multidevice`` on the forced 8-device host platform.
"""
import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def _force_host_devices(n: int) -> None:
    if "jax" in sys.modules:        # jax already initialized — too late
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:              # explicit override wins (dryrun: 512)
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()


_force_host_devices(int(os.environ.get("REPRO_TEST_DEVICE_COUNT", "8")))

import jax                 # noqa: E402  (must come after the XLA_FLAGS setup)
import numpy as np         # noqa: E402
import pytest              # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice: needs >= 8 (simulated) host devices; skipped when the "
        "platform has fewer (see tests/conftest.py)")


@pytest.fixture(scope="session")
def multidevice():
    """The 8 simulated host devices backing the sharded-head test matrix."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices — run with XLA_FLAGS="
                    f"{_FLAG}=8 (tests/conftest.py sets it by default)")
    return jax.devices()[:8]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.key(0)


@pytest.fixture
def isolated_compile_cache(monkeypatch, tmp_path):
    """For tests that call an entry point (``enable_compile_cache``): point
    ``JAX_COMPILATION_CACHE_DIR`` at a temp dir so the helper places no
    cache, and restore the settings it changes afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield tmp_path / "cache"
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
