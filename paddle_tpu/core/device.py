"""Device management.

Parity: python/paddle/device/__init__.py (set_device/get_device) +
phi/backends/device_manager.h DeviceManager. TPU design: devices are PJRT
devices enumerated by jax; ``set_device`` installs a default-device config
so subsequent array placements land there. The TPU is first-class (the
reference's CustomDevice plugin inversion — SURVEY §7.1).
"""

from __future__ import annotations

import jax

_current = [None]  # None = jax default


class Place:
    def __init__(self, device_id: int = 0):
        self._id = device_id

    def get_device_id(self):
        return self._id

    def __repr__(self):
        return f"{type(self).__name__}({self._id})"


class CPUPlace(Place):
    pass


class TPUPlace(Place):
    pass


class CUDAPlace(Place):
    """Kept for API parity; maps to the accelerator device on TPU builds."""


def _platform_devices(kind: str):
    try:
        return jax.devices(kind)
    except RuntimeError:
        return []


def set_device(device: str):
    """device: 'cpu', 'tpu', 'tpu:0', 'gpu'/'gpu:0' (alias for accelerator).

    Raises when the named kind or index is not attached: a request for
    an accelerator never lands on the CPU, nor on another chip."""
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name in ("tpu", "gpu", "xpu", "npu", "custom_cpu"):
        devs = [d for d in jax.devices() if d.platform != "cpu"]
    elif name == "cpu":
        devs = _platform_devices("cpu")
    else:
        raise ValueError(f"unknown device {device!r}")
    if not 0 <= idx < len(devs):
        raise RuntimeError(
            f"no device {device!r}: jax sees {len(devs)} such device(s) "
            f"(default backend {jax.default_backend()!r})")
    dev = devs[idx]
    _current[0] = dev
    jax.config.update("jax_default_device", dev)
    return dev


def get_device() -> str:
    dev = _current[0]
    if dev is None:
        dev = jax.devices()[0]
    plat = dev.platform
    name = "cpu" if plat == "cpu" else "tpu"
    return f"{name}:{dev.id}" if name != "cpu" else "cpu"


def get_default_device():
    return _current[0] or jax.devices()[0]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def synchronize():
    """Block until all enqueued work completes (parity: device.synchronize)."""
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        except Exception:
            pass
