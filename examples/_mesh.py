"""Shared helper: repo-root import path + device selection.

These examples demonstrate multi-chip SPMD over n devices.  Where the
host has at least n TPU chips (and JAX_PLATFORMS does not pin the CPU)
they run on the real devices; otherwise on an n-device virtual CPU mesh
— the same code either way.  The choice is made from the chip count
under sysfs, before jax initialises a backend, and is printed.
n == 1: the default backend (the real chip when present).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ensure_devices(n=8):
    from paddle_tpu.core.device import local_tpu_chips
    import jax
    chips = local_tpu_chips()
    if n > 1 and (os.environ.get("JAX_PLATFORMS") == "cpu" or chips < n):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")
        jax.config.update("jax_platforms", "cpu")
        print(f"[examples] {n}-device virtual CPU mesh (host has {chips} "
              "TPU chip(s))")
    elif n > 1:
        print(f"[examples] {n} of the host's {chips} TPU chips")
    return jax
