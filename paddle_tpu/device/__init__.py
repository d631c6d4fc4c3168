"""paddle.device — device management + memory stats.

Parity: `python/paddle/device/__init__.py` and `device/cuda/__init__.py`
(max_memory_allocated `:312`, memory_allocated, memory_reserved,
empty_cache), backed by `paddle/phi/core/memory/stats.h` in the reference.

TPU-native: PJRT owns allocation; stats come from `Device.memory_stats()`
(bytes_in_use / peak_bytes_in_use) when the backend reports them, with a
live-array accounting fallback (sum of buffer nbytes + a process-local
peak) where the backend doesn't (e.g. the CPU test backend).
"""

from __future__ import annotations

from typing import Optional

import jax

from ..core.device import (CPUPlace, CustomPlace, Place,  # noqa: F401
                           TPUPlace, current_jax_device, device_count,
                           get_all_devices, get_device,
                           is_compiled_with_tpu, set_device)

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "memory_allocated", "memory_reserved", "max_memory_allocated",
           "max_memory_reserved", "reset_max_memory_allocated",
           "reset_max_memory_reserved", "empty_cache", "synchronize",
           "Place", "CPUPlace", "TPUPlace", "CustomPlace", "cuda"]

_peak_fallback = {"allocated": 0}


def _device(device=None) -> jax.Device:
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, int):
        return jax.devices()[device]
    return current_jax_device()


def _live_bytes(d: jax.Device) -> int:
    total = 0
    for arr in jax.live_arrays():
        try:
            if d in arr.devices():
                total += arr.nbytes // max(len(arr.devices()), 1)
        except RuntimeError:
            pass  # deleted/donated arrays
    return total


def memory_allocated(device=None) -> int:
    """Bytes currently held by tensors on `device`."""
    d = _device(device)
    stats = d.memory_stats()
    if stats and "bytes_in_use" in stats:
        cur = int(stats["bytes_in_use"])
    else:
        cur = _live_bytes(d)
    _peak_fallback["allocated"] = max(_peak_fallback["allocated"], cur)
    return cur


def max_memory_allocated(device=None) -> int:
    """Peak bytes held on `device` (PJRT peak, or process-local peak of
    observed allocations on backends without stats)."""
    d = _device(device)
    stats = d.memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    memory_allocated(device)  # refresh the fallback peak
    return _peak_fallback["allocated"]


def memory_reserved(device=None) -> int:
    d = _device(device)
    stats = d.memory_stats()
    if stats and "bytes_reserved" in stats:
        return int(stats["bytes_reserved"])
    if stats and "bytes_limit" in stats:
        return int(stats.get("bytes_in_use", 0))
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    return max_memory_allocated(device)


def reset_max_memory_allocated(device=None) -> None:
    _peak_fallback["allocated"] = 0


def reset_max_memory_reserved(device=None) -> None:
    reset_max_memory_allocated(device)


def empty_cache() -> None:
    """Release cached blocks.  PJRT manages its own pools; the effective
    equivalent is dropping dead Python references."""
    import gc
    gc.collect()


def synchronize(device=None) -> None:
    """Block until all queued work on `device` finished."""
    for arr in jax.live_arrays():
        try:
            if _device(device) in arr.devices():
                arr.block_until_ready()
        except RuntimeError:
            pass


class _CudaNamespace:
    """`paddle.device.cuda` API-compat shim: the same stats, TPU-backed."""
    memory_allocated = staticmethod(memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_allocated = staticmethod(max_memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)
    synchronize = staticmethod(synchronize)
    device_count = staticmethod(device_count)


cuda = _CudaNamespace()
