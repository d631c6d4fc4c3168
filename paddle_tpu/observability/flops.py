"""Shared FLOPs / MFU accounting — the ONE place the repo converts
(model shape, tokens/sec, device kind) into an MFU number.

Until ISSUE 2 three copies of the per-token FLOPs estimate lived in
`models/gpt.py`, `models/bert.py` / `models/llama.py` and (a 6N-only
variant) `distributed/auto_tuner/cost_model.py`, while `bench.py` owned
its own peak-FLOPs spec table; they could disagree, which is exactly how
the round-5 40.7%-vs-58% MFU dispute happened.  Everything now routes
through here: the models' ``flops_per_token``, the tuner's roofline
compute term, bench's MFU lines and the telemetry StepTimeline.

Accounting convention (standard MFU, PaLM appendix B shape):

* weights: ``6 * N`` FLOPs per token for a train step (2 fwd matmul +
  4 bwd), with N the parameter count;
* attention: ``12 * L * H * S`` per token — the QK^T and PV batched
  matmuls, fwd+bwd, for seq length S (per-token cost grows linearly in
  S because every token attends over the sequence).

Recompute/remat deliberately does NOT inflate the number: MFU counts
*model* FLOPs, so a remat config shows up as lower MFU, not more FLOPs.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["training_flops_per_token", "peak_flops", "mfu"]


def training_flops_per_token(n_params: float,
                             num_layers: Optional[int] = None,
                             hidden_size: Optional[int] = None,
                             seq_len: Optional[int] = None) -> float:
    """Train-step (fwd+bwd) FLOPs per token: 6N + 12*L*H*S.

    The attention term is included only when the full (L, H, S) shape is
    given; callers that only know a parameter count (the auto-tuner's
    analytic model before a concrete seq plan) get the 6N floor.
    """
    flops = 6.0 * float(n_params)
    if num_layers and hidden_size and seq_len:
        flops += 12.0 * num_layers * hidden_size * seq_len
    return flops


# bf16 peak FLOP/s per chip, keyed by a lower-cased substring of jax's
# ``device_kind`` (Google Cloud TPU documentation, the per-chip "peak
# compute" row of each generation's page).  More specific names first:
# "tpu v5" is a substring of "tpu v5 lite".  There is no CPU row and no
# default: MFU is a statement about an accelerator, and a device that
# is not in the table is an error, not 197e12.
_PEAK_TABLE = {
    "tpu v5 lite": 197e12,   # v5e
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v5": 459e12,        # v5p reports "TPU v5"
    "tpu v4": 275e12,
    "tpu v6 lite": 918e12,   # v6e (Trillium)
    "tpu v6e": 918e12,
}


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s per chip for a jax ``device_kind`` string;
    raises ``ValueError`` for a kind the table does not hold."""
    kind = (device_kind or "").lower()
    for k, v in _PEAK_TABLE.items():
        if k in kind:
            return v
    raise ValueError(
        f"no peak FLOP/s entry for device kind {device_kind!r}: MFU is "
        f"defined only for {sorted(_PEAK_TABLE)} (observability/flops.py)")


def mfu(tokens_per_sec: float, flops_per_token: float,
        device_kind: Optional[str] = None,
        peak: Optional[float] = None) -> float:
    """Model FLOPs utilization: achieved FLOP/s over peak FLOP/s (the
    table's row for `device_kind` unless an explicit `peak` is given)."""
    if peak is None:
        peak = peak_flops(device_kind)
    return tokens_per_sec * flops_per_token / peak
