"""Device/place model over the PJRT runtime.

TPU-native equivalent of the reference's Place variants + DeviceContextPool
(reference: paddle/fluid/platform/place.h:26-75,
platform/device_context.h). On the XLA stack a "place" maps to a
``jax.Device``; streams/contexts are owned by the runtime, so this layer is a
thin, cached facade used by tensor factories and the data loader.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax


class Place:
    """A logical device slot: backend platform + device index."""

    platform: str = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other) -> bool:
        return (isinstance(other, Place) and self.platform == other.platform
                and self.device_id == other.device_id)

    def __hash__(self) -> int:
        return hash((self.platform, self.device_id))

    def __repr__(self) -> str:
        return f"Place({self.platform}:{self.device_id})"

    @property
    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.platform]
        if not devs:
            from .enforce import UnavailableError
            raise UnavailableError(
                f"{self!r}: JAX has no {self.platform!r} device here "
                f"(found {sorted({d.platform for d in jax.devices()})})")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    platform = "cpu"


class TPUPlace(Place):
    platform = "tpu"


class GPUPlace(Place):
    platform = "gpu"


# Alias matching the reference's naming for CUDA places.
CUDAPlace = GPUPlace


class CUDAPinnedPlace(Place):
    """reference: platform/place.h CUDAPinnedPlace — page-locked host
    staging memory. On TPU, host staging is managed by PJRT; this place is
    accepted by the API surface and maps to host memory."""
    platform = "cpu"

    def __init__(self):
        super().__init__(0)


class NPUPlace(Place):
    """reference: platform/place.h NPUPlace (Ascend). Accepted for API
    parity; resolves to the default accelerator platform if present."""
    platform = "tpu"


class XPUPlace(Place):
    """reference: platform/place.h XPUPlace (Kunlun). Accepted for API
    parity; resolves to the default accelerator platform if present."""
    platform = "tpu"


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "gpu":
        return GPUPlace(0)
    return CPUPlace(0)


_expected_place: Optional[Place] = None


def set_device(device: Union[str, Place]) -> Place:
    """Set the global expected place, e.g. ``set_device('tpu:0')``."""
    global _expected_place
    if isinstance(device, Place):
        _expected_place = device
        return device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": GPUPlace,
           "cuda": GPUPlace}.get(name.lower())
    if cls is None:
        from .enforce import InvalidArgumentError
        raise InvalidArgumentError(f"Unknown device {device!r}")
    _expected_place = cls(idx)
    return _expected_place


def get_device() -> str:
    p = expected_place()
    return f"{p.platform}:{p.device_id}"


def expected_place() -> Place:
    return _expected_place if _expected_place is not None else _default_place()


def accelerator_held() -> Optional[str]:
    """Platform of the accelerator this process holds, or None.

    A chip belongs to one process at a time: the process that has
    initialised a JAX backend on it keeps it until it exits, and a child
    that needs the chip then fails or hangs. A process that has only
    IMPORTED jax holds nothing. Never initialises a backend itself."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    plat = jax.default_backend()
    return None if plat == "cpu" else plat


def refuse_chip_contention(child_env, child: str) -> None:
    """Raise before spawning ``child`` (a process that will run JAX
    under ``child_env``) when it would need the chip this process
    already holds: a child not pinned to ``JAX_PLATFORMS=cpu`` opens
    the accelerator. Failing here is the loud alternative to a child
    that hangs at start-up."""
    held = accelerator_held()
    if held and child_env.get("JAX_PLATFORMS", "").strip() != "cpu":
        from .enforce import UnavailableError
        raise UnavailableError(
            f"this process has initialised JAX on {held!r} and holds the "
            f"chip; {child} would need it too (one process per chip). "
            f"Start it from a process that has not touched JAX, or pin "
            f"it to the CPU with JAX_PLATFORMS=cpu in its environment")


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def device_count(platform: Optional[str] = None) -> int:
    if platform is None:
        platform = expected_place().platform
    return len([d for d in jax.devices() if d.platform == platform]) or 1
