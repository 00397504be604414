"""Test configuration: force an 8-virtual-device CPU mesh (the reference's `local[N]`
Spark-test analog, SURVEY §4.5) and float64 support for gradient checks.

The platform is pinned through jax.config as well as by the caller's JAX_PLATFORMS=cpu,
so the suite means the same thing on a machine that has a chip.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


@pytest.fixture
def forced_host_devices():
    """Devices for the multi-device sharding tests (ISSUE 10), tier-1-safe.

    The device count is fixed when the XLA backend initializes (the
    module-level XLA_FLAGS above, applied only when the caller didn't force
    a count themselves), so this fixture cannot — and does not — mutate
    any global state that could leak into other tests: it merely VERIFIES
    that enough virtual devices exist and skips the test otherwise (e.g.
    when an outer harness pinned a smaller count)."""
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip(f"sharded-serving tests need 8 forced host devices, "
                    f"have {len(devices)}")
    return devices


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; long decodes (>64 tokens) and other
    # minute-scale tests opt out of it with @pytest.mark.slow
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 "
        "(-m 'not slow')")
