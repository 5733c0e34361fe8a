"""Test harness config.

Mirrors the reference's single-host multi-device emulation (SURVEY.md §4):
8 fake devices on CPU via xla_force_host_platform_device_count so every
mesh/collective/parallelism test runs hermetically without TPU hardware.
Must run before jax is first imported.
"""
import os

from _device_env import ensure_fake_devices

# PADDLE_TPU_TEST_PLATFORM=tpu runs the suite on real hardware instead of
# the hermetic 8-fake-device CPU default. ensure_fake_devices selects the
# backend via config before any backend is initialized (non-cpu platforms
# skip the fake-device flag).
_plat = os.environ.get("PADDLE_TPU_TEST_PLATFORM", "cpu")
ensure_fake_devices(8 if _plat == "cpu" else None, platform=_plat)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# full fp32 matmuls for numeric comparisons (TPU bench keeps its own default)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # the tier-1 fast lane runs `-m 'not slow'`; anything that compiles
    # beyond a module's core executable set carries this marker
    config.addinivalue_line(
        "markers", "slow: heavy test excluded from the tier-1 fast lane")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _seed_framework():
    import paddle_tpu as paddle

    paddle.seed(1234)
    yield
