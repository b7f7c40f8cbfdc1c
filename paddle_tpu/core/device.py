"""Device / Place abstraction.

Replaces the reference's Place/Backend system (ref:paddle/phi/common/backend.h:40,
ref:paddle/fluid/platform/place.h) and DeviceContextPool. On TPU there is no
per-op stream management — PJRT owns execution — so a Place is just a named
jax.Device plus helpers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from . import flags


class Place:
    """A device placement, e.g. Place('tpu', 0)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        """The attached device this place names. A place whose platform is
        not attached raises — asking for a chip never lands on the CPU."""
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: no {self.device_type!r} device is attached "
                f"(default backend: {jax.default_backend()!r})") from e
        return devs[min(self.device_id, len(devs) - 1)]


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0) -> Place:  # API-parity alias: maps to the accelerator
    return Place(_default_accelerator(), device_id)


def XPUPlace(device_id: int = 0) -> Place:  # API-parity alias (ref XPUPlace)
    return Place(_default_accelerator(), device_id)


@functools.lru_cache(maxsize=1)
def _default_accelerator() -> str:
    """The platform a default place lands on: ``tpu`` when a chip is
    attached, else ``cpu`` — how the library and its tests run on the CPU
    backend. A measurement or smoke path must not lean on this: it asks
    for the TPU and fails without one."""
    return "tpu" if jax.default_backend() == "tpu" else "cpu"


_current_device: Optional[Place] = None


def set_device(device: str) -> Place:
    """paddle.device.set_device equivalent: 'cpu', 'tpu', 'tpu:1'."""
    global _current_device
    if ":" in device:
        t, i = device.split(":")
        _current_device = Place(t, int(i))
    else:
        _current_device = Place(device, 0)
    return _current_device


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    global _current_device
    if _current_device is None:
        override = flags.flag("default_device")
        _current_device = Place(override, 0) if override else Place(_default_accelerator(), 0)
    return _current_device


def is_compiled_with_cuda() -> bool:  # API parity
    return False


def is_compiled_with_xpu() -> bool:  # API parity
    return False


def is_compiled_with_rocm() -> bool:  # API parity
    return False


def is_compiled_with_cinn() -> bool:  # API parity (CINN = the reference's
    return False                      # compiler; XLA plays that role here)


def is_compiled_with_tpu() -> bool:
    return _default_accelerator() == "tpu"


def device_count() -> int:
    return jax.device_count()
