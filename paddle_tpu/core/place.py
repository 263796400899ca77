"""Device places.

TPU-native analog of the reference Place hierarchy
(/root/reference/paddle/phi/common/place.h:31).  A Place names a logical
device; the concrete device object is a jax.Device.  ``set_device`` switches
the default placement used by tensor factories.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "CUDAPinnedPlace",
    "set_device", "get_device", "get_all_device_type", "device_count",
    "current_jax_device", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_rocm", "is_compiled_with_distribute",
]

_state = threading.local()


class Place:
    """Base device identity: (device_type, device_id)."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def get_device_id(self) -> int:
        return self.device_id

    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if _platform_matches(d.platform, self.device_type)]
        if not devs:
            # CPU is always present as a host platform.
            devs = jax.devices("cpu")
        return devs[self.device_id % len(devs)]

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


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    device_type = "tpu"


# GPU aliases kept for API-compat; on this build they resolve to the
# accelerator platform if present, else CPU.
class CUDAPlace(TPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    def __init__(self):
        super().__init__()


def _platform_matches(platform: str, device_type: str) -> bool:
    # the one accelerator platform of this installation is the native
    # "tpu" backend; the GPU-named aliases resolve to it
    return platform == ("cpu" if device_type == "cpu" else "tpu")


def _accelerator_platform():
    for d in jax.devices():
        if d.platform != "cpu":
            return d.platform
    return None


def set_device(device) -> Place:
    """Set the default device, e.g. 'tpu', 'tpu:1', 'cpu', or a Place."""
    place = _parse_device(device)
    _state.place = place
    return place


def _parse_device(device) -> Place:
    if isinstance(device, Place):
        return device
    if isinstance(device, jax.Device):
        return CPUPlace() if device.platform == "cpu" else TPUPlace(device.id)
    if not isinstance(device, str):
        raise TypeError(f"Cannot interpret device: {device!r}")
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        return CPUPlace()
    if name in ("tpu", "gpu", "cuda", "xpu", "npu", "accelerator"):
        return TPUPlace(idx)
    raise ValueError(f"Unknown device type: {device!r}")


def get_device() -> str:
    p = _current_place()
    return "cpu" if p.device_type == "cpu" else f"{p.device_type}:{p.device_id}"


def _current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = CPUPlace() if _accelerator_platform() is None else TPUPlace(0)
        _state.place = place
    return place


def current_jax_device() -> jax.Device:
    return _current_place().jax_device()


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def device_count(device_type: str | None = None) -> int:
    if device_type in (None, "tpu", "gpu"):
        n = len([d for d in jax.devices() if d.platform != "cpu"])
        if n:
            return n
    return len(jax.devices("cpu"))


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True
