"""Device identity ("Place") and device selection.

Paddle-shaped Place surface (ref: paddle/phi/common/place.h, upstream layout,
unverified — mount empty). On this framework a Place names a jax device (or a
device kind); `set_device('tpu')` selects the default jax backend/platform.
"""
from __future__ import annotations

import jax


class Place:
    """Base device identity. Equality by (kind, device_id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self._device_id})"

    # paddle parity helpers
    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_gpu_place(self):  # always False here; kept for API parity
        return False

    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        devs = _devices_of_kind(self.kind)
        if not devs:
            # fall back to the default backend (tests run on CPU)
            devs = jax.devices()
        return devs[min(self._device_id, len(devs) - 1)]


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self, device_id: int = 0):
        super().__init__(device_id)


class TPUPlace(Place):
    kind = "tpu"

    def __init__(self, device_id: int = 0):
        super().__init__(device_id)


# Paddle spells the accelerator place `CUDAPlace`; we keep the name as an alias
# pointing at the accelerator (TPU) so `paddle.CUDAPlace(0)`-shaped code runs.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CustomPlace = TPUPlace


def _devices_of_kind(kind: str):
    # a backend that fails to start raises here: it is never mistaken
    # for a machine without that kind of device
    return [d for d in jax.devices() if d.platform == kind]


_CURRENT_PLACE = [None]  # lazily resolved


def _default_place() -> Place:
    return TPUPlace(0) if jax.devices()[0].platform == "tpu" else CPUPlace(0)


def set_device(device) -> Place:
    """paddle.set_device — accepts 'cpu', 'tpu', 'tpu:0', a Place, ...

    'gpu'/'xpu'/'npu' map to the accelerator for drop-in compatibility.
    """
    if isinstance(device, Place):
        _CURRENT_PLACE[0] = device
        return device
    if not isinstance(device, str):
        raise TypeError(f"set_device expects str or Place, got {type(device)}")
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "cuda", "xpu", "npu", "custom"):
        place = TPUPlace(idx)
    else:
        from ..device.plugin import is_custom_device_registered

        if is_custom_device_registered(name):
            # a registered PJRT plugin is an accelerator place; backend
            # selection itself is owned by jax (JAX_PLATFORMS)
            place = TPUPlace(idx)
        else:
            raise ValueError(f"unknown device {device!r}")
    _CURRENT_PLACE[0] = place
    return place


def get_device() -> str:
    p = _get_current_place()
    return f"{p.kind}:{p.get_device_id()}"


def _get_current_place() -> Place:
    if _CURRENT_PLACE[0] is None:
        _CURRENT_PLACE[0] = _default_place()
    return _CURRENT_PLACE[0]


def is_compiled_with_tpu() -> bool:
    return bool(_devices_of_kind("tpu"))


def device_count() -> int:
    try:
        return len(jax.devices())
    except RuntimeError:
        return 0
