"""What every Pallas kernel wrapper in `ops/` shares: the one decision of
whether a kernel is compiled by Mosaic or run by the interpreter, and the
trace-time claim that tells the X-ray audit which of the two happened."""

from __future__ import annotations

import jax

__all__ = ["interpret_default", "claim"]


def interpret_default() -> bool:
    """Kernels compile through Mosaic when the default backend is a TPU
    and run under the Pallas interpreter on every other backend (CPU CI).
    The single seat of that choice: a wrapper called with
    ``interpret=None`` asks here, and nothing else in the repo decides."""
    return jax.default_backend() != "tpu"


def claim(name: str, interpret) -> None:
    """Record trace-time evidence that kernel `name` was emitted, and how.

    Interpret-mode `pallas_call` lowers to a plain `stablehlo.while` with
    no custom-call marker, so the xray HLO scan cannot see it; the claims
    channel is how the kernel-coverage audit (and `chip_smoke.py`) learns
    which kernel a program actually traced and whether Mosaic compiled it
    (no-op outside an audit capture)."""
    from ..observability.xray import claim_kernel
    claim_kernel(name, "interpret" if interpret else "custom_call")
