"""Device/place abstraction.

Reference: phi::Place (paddle/phi/common/place.h), DeviceManager
(paddle/phi/backends/device_manager.h:128). Here a Place names a jax.Device;
the "driver" is PJRT via jax, so the ~60-virtual-method DeviceInterface of the
reference collapses to a thin identity + lookup layer.
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

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
        """The jax device this place names. Raises when the process has no
        device of this type: a TPUPlace never resolves to the host."""
        devs = [d for d in jax.devices()
                if _platform_matches(d.platform, self.device_type)]
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: jax reports {len(devs)} {self.device_type} "
                f"device(s) (platforms: "
                f"{sorted({d.platform for d in jax.devices()})})")
        return devs[self.device_id]


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(Place):  # API-compat name for a GPU place
    device_type = "gpu"


class CUDAPinnedPlace(CPUPlace):
    pass


def _platform_matches(platform: str, device_type: str) -> bool:
    if device_type == "gpu":
        return platform in ("gpu", "cuda", "rocm")
    return platform == device_type


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat in ("gpu", "cuda", "rocm"):
        return CUDAPlace(0)
    return CPUPlace()


_current_place = None


def set_device(device) -> Place:
    """paddle.set_device('tpu' | 'tpu:0' | 'cpu' | 'gpu:1')."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    cls = {"tpu": TPUPlace, "cpu": CPUPlace, "gpu": CUDAPlace, "xpu": TPUPlace}.get(name)
    if cls is None:
        raise ValueError(f"Unknown device {device!r}")
    _current_place = cls() if cls is CPUPlace else cls(idx)
    return _current_place


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.device_id}" if p.device_type != "cpu" else "cpu"


def get_place() -> Place:
    return _current_place if _current_place is not None else _default_place()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()
