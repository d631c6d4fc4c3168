"""Device management.

Analogue of the reference's DeviceManager/place system
(`paddle/phi/backends/device_manager.h:134`, `phi/common/place.h`): enumerate
devices, select a current device, and expose Place-like handles.  On TPU the
"device" is a PJRT device obtained from JAX; multi-chip topology is expressed
through `jax.sharding.Mesh` (see paddle_tpu.distributed), not through per-place
streams.
"""

from __future__ import annotations

import glob
import threading
from typing import List, Optional

import jax
from jax._src import hardware_utils as _hardware_utils

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CustomPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_tpu", "current_jax_device", "local_tpu_chips",
]


def local_tpu_chips() -> int:
    """TPU chips this process could open, found without opening any: the
    chips jax itself counts before it picks a platform (PCI vendor/device
    ids under sysfs), capped by the device nodes actually handed to this
    machine (`/dev/accel*`, or `/dev/vfio/<n>` from v5e on) — a one-chip
    slice of a four-chip host lists four PCI devices and one node.  It
    initialises no backend, so code that must decide something BEFORE
    the first device touch — the launcher, the examples' mesh helper, the
    chipless AOT test — can ask without taking a chip."""
    pci = _hardware_utils.num_available_tpu_chips_and_device_id()[0]
    nodes = len(glob.glob("/dev/accel*")) \
        or len(glob.glob("/dev/vfio/[0-9]*"))
    return min(pci, nodes)


class Place:
    """A device handle, equivalent to phi::Place."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    @property
    def jax_device(self) -> jax.Device:
        # jax.devices(platform) raises where that platform has no
        # backend: a TPUPlace on a machine without a chip (or a place of
        # an unknown type) is an error, never a quiet move to whatever
        # device exists.  CPUPlace resolves on a TPU host too — the host
        # CPU backend is always registered.
        return jax.devices(self.device_type)[self.device_id]


def CPUPlace(device_id: int = 0) -> Place:
    return Place("cpu", device_id)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CustomPlace(device_type: str, device_id: int = 0) -> Place:
    return Place(device_type, device_id)


_lock = threading.RLock()
_current: Optional[Place] = None


def get_all_devices() -> List[str]:
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if d.platform == device_type])


def is_compiled_with_tpu() -> bool:
    """True when the default backend's devices are TPU chips.  A backend
    that fails to initialise (``JAX_PLATFORMS=tpu`` with no chip, a chip
    another process holds) raises from ``jax.devices()`` and the error
    propagates: "no TPU" and "the TPU failed to start" are different
    answers, and only the first is False."""
    return any(d.platform == "tpu" for d in jax.devices())


def set_device(device: str | Place) -> Place:
    """Select the current device, e.g. ``set_device("tpu:0")``."""
    global _current
    if isinstance(device, str):
        if ":" in device:
            kind, idx = device.split(":", 1)
            place = Place(kind, int(idx))
        else:
            place = Place(device, 0)
    else:
        place = device
    with _lock:
        _current = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    global _current
    with _lock:
        if _current is None:
            d = jax.devices()[0]
            _current = Place(d.platform, 0)
        return _current


def current_jax_device() -> jax.Device:
    return current_place().jax_device
