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


@pytest.fixture
def host_drawn_weights(monkeypatch):
    """Weights drawn by NumPy on the host. An eager constructor compiles
    one generator program a distinct weight shape, half a second each on
    a CPU: 60 s for DenseNet-121's 120 shapes. For tests of an
    architecture (what it builds, the shapes it returns, that gradients
    flow), not of its initial values: the initializers keep their own
    tests."""
    import jax.numpy as jnp
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.nn import initializer

    draw = np.random.default_rng(1234)

    def normal(self, shape, dtype):
        x = draw.standard_normal(tuple(shape), dtype=np.float32)
        x *= self.std
        x += self.mean
        return jnp.asarray(x, convert_dtype(dtype))

    def uniform(self, shape, dtype):
        x = draw.random(tuple(shape), dtype=np.float32)
        x *= self.high - self.low
        x += self.low
        return jnp.asarray(x, convert_dtype(dtype))

    monkeypatch.setattr(initializer.Normal, "__call__", normal)
    monkeypatch.setattr(initializer.Uniform, "__call__", uniform)


@pytest.fixture
def attention_dispatches():
    """`serving_attention_dispatch_total` by path, as counted since the
    test began: the counter is process-global and counts while tracing."""
    import collections

    import chip_smoke

    before = chip_smoke._dispatch_counts()
    return lambda: collections.Counter(chip_smoke._dispatch_delta(before))


@pytest.fixture
def flash_interpreted(monkeypatch):
    """The flash kernel itself, interpreted, where the CPU would take the
    XLA reference op; gives the (is_causal, had a mask) of each call."""
    from paddle_tpu.ops import pallas_kernels

    calls, real = [], pallas_kernels.flash_attention

    def flash(q, k, v, attn_mask=None, is_causal=False, **kw):
        calls.append((is_causal, attn_mask is not None))
        return real(q, k, v, attn_mask, is_causal=is_causal,
                    **{**kw, "interpret": True})

    monkeypatch.setattr(pallas_kernels, "flash_attention_available",
                        lambda *a, **k: True)
    monkeypatch.setattr(pallas_kernels, "flash_attention", flash)
    return calls
