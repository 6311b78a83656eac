"""Device/place management.

Reference capability: Place variant + DeviceContextPool
(/root/reference/paddle/fluid/platform/place.h:150,
 device_context.h:803, python paddle.set_device in
 python/paddle/device.py). TPU-first re-design: a Place is a thin handle on a
``jax.Device``; there are no streams or per-device contexts to manage — XLA
owns scheduling. ``set_device`` flips the default placement used by tensor
creation ops.
"""
from __future__ import annotations

import functools
import threading

import jax


class Place:
    """Device identity: ('tpu'|'cpu'|'gpu', index)."""

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

    @property
    def jax_device(self) -> "jax.Device | None":
        return _find_device(self.device_type, self.device_id)

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


def CPUPlace(idx: int = 0) -> Place:
    return Place("cpu", idx)


def TPUPlace(idx: int = 0) -> Place:
    return Place("tpu", idx)


# Alias: code written against the reference's CUDAPlace maps to the accelerator.
def CUDAPlace(idx: int = 0) -> Place:  # pragma: no cover - compat shim
    return Place(_accelerator_type(), idx)


@functools.lru_cache(maxsize=None)
def _platforms():
    plats = {}
    # local_devices, not devices: under a multi-controller run the global
    # list starts with process 0's devices, and placing this process's
    # eager tensors there is illegal (non-addressable)
    for d in jax.local_devices():
        plats.setdefault(d.platform, []).append(d)
    for d in jax.local_devices(backend="cpu") if _has_cpu_backend() else []:
        plats.setdefault("cpu", []).append(d)
    return plats


def _has_cpu_backend():
    try:
        jax.local_devices(backend="cpu")
        return True
    except RuntimeError:
        return False


def _accelerator_type() -> str:
    plats = _platforms()
    for t in ("tpu", "gpu"):
        if t in plats:
            return t
    return "cpu"


def _find_device(device_type: str, device_id: int):
    devs = _platforms().get(device_type)
    if not devs:
        return None
    if not 0 <= device_id < len(devs):
        raise RuntimeError(
            f"No {device_type}:{device_id} device: this process has "
            f"{len(devs)} {device_type} device(s)")
    return devs[device_id]


class _DeviceState(threading.local):
    def __init__(self):
        self.place: Place | None = None


_state = _DeviceState()


def set_device(device: str) -> Place:
    """paddle.set_device equivalent: 'tpu', 'cpu', 'tpu:1', 'gpu' → accelerator."""
    if ":" in device:
        dtype_, idx = device.split(":")
        idx = int(idx)
    else:
        dtype_, idx = device, 0
    if dtype_ == "gpu":  # compat: 'gpu' means 'the accelerator'
        dtype_ = _accelerator_type()
    place = Place(dtype_, idx)
    if place.jax_device is None:
        raise RuntimeError(f"No {dtype_} device available (have: {list(_platforms())})")
    _state.place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    if _state.place is None:
        _state.place = Place(_accelerator_type(), 0)
    return _state.place


def current_jax_device():
    return current_place().jax_device


def is_compiled_with_tpu() -> bool:
    return "tpu" in _platforms()


def device_count(device_type: str | None = None) -> int:
    plats = _platforms()
    t = device_type or current_place().device_type
    return len(plats.get(t, ()))
