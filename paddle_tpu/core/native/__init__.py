"""Native (C++) runtime components, built on demand with g++.

The reference keeps its runtime stores/allocators in C++
(`paddle/phi/core/distributed/store/tcp_store.cc`); this package holds the
TPU build's equivalents plus the lazy compiler that turns each .cc into a
cached .so loaded through ctypes (no pybind11 in the image).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Dict, Optional

_CACHE = os.path.join(tempfile.gettempdir(), "paddle_tpu_native")

# component name -> "native" | "python": which implementation each
# build() call of this process ended on.  A component that was never
# asked for is absent.  Callers behave the same either way; a run that
# must not depend on which happened (chip_smoke.py) prints this.
_outcome: Dict[str, str] = {}


def status() -> Dict[str, str]:
    """Which implementation each requested component is running on."""
    return dict(_outcome)


def build(name: str, extra_flags=()) -> Optional[ctypes.CDLL]:
    """Compile `<name>.cc` (next to this file) into a cached .so and load it.

    Returns None when no C++ toolchain is available (callers fall back to
    their pure-Python implementation).  Set PADDLE_TPU_DISABLE_NATIVE=1 to
    force the fallback.  The outcome is recorded for :func:`status`.
    """
    lib = _build(name, extra_flags)
    _outcome[name] = "native" if lib is not None else "python"
    return lib


def _build(name: str, extra_flags) -> Optional[ctypes.CDLL]:
    if os.environ.get("PADDLE_TPU_DISABLE_NATIVE"):
        return None
    src = os.path.join(os.path.dirname(__file__), f"{name}.cc")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:  # source not shipped: pure-Python fallback
        return None
    out = os.path.join(_CACHE, f"{name}-{digest}.so")
    if not os.path.exists(out):
        os.makedirs(_CACHE, exist_ok=True)
        tmp = out + f".tmp{os.getpid()}"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp,
               src, "-lpthread", *extra_flags]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
        except (OSError, subprocess.CalledProcessError):
            return None
        os.replace(tmp, out)  # atomic vs concurrent builders
    try:
        return ctypes.CDLL(out)
    except OSError:
        return None
