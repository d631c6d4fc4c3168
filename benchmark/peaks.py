"""peaks.py — the chip's published peaks and the operation counts of the
work a step needs.  The yardstick: kept with the benchmark so that no PR
that claims a gain can move it.

Peaks are per chip, from Google Cloud's TPU documentation (the "peak
compute" and "HBM bandwidth" rows of each generation's page), keyed by a
lower-cased substring of jax's `device_kind`.  A device that is not in the
table is an error, never a default.  `training_flops_per_token` and the
FLOP/s column were copied from `paddle_tpu/observability/flops.py` (PR 24)
and a tier-1 test holds the two equal; the original is listed in `PERF.md`
for a later PR to point here or delete.
"""

from __future__ import annotations

# device_kind substring -> (bf16 FLOP/s, HBM bytes/s).  More specific
# names first: "tpu v5" is a substring of "tpu v5 lite".
PEAKS = {
    "tpu v5 lite": (197e12, 819e9),    # v5e
    "tpu v5e": (197e12, 819e9),
    "tpu v5p": (459e12, 2765e9),
    "tpu v5": (459e12, 2765e9),        # v5p reports "TPU v5"
    "tpu v4": (275e12, 1228e9),
    "tpu v6 lite": (918e12, 1640e9),   # v6e
    "tpu v6e": (918e12, 1640e9),
}


def _row(device_kind: str) -> tuple:
    kind = (device_kind or "").lower()
    for k, v in PEAKS.items():
        if k in kind:
            return v
    raise ValueError(f"no peak entry for device kind {device_kind!r}: "
                     f"the table holds {sorted(PEAKS)} (benchmark/peaks.py)")


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip; raises on a kind not in the table."""
    return _row(device_kind)[0]


def peak_bytes_per_s(device_kind: str) -> float:
    """HBM bandwidth of one chip in bytes/s; raises on an unknown kind."""
    return _row(device_kind)[1]


def training_flops_per_token(n_params: float, num_layers: int = 0,
                             hidden_size: int = 0, seq_len: int = 0) -> float:
    """Forward + backward FLOPs a token needs: 6N for the weights (2
    forward, 4 backward) plus 12*L*H*S for the two attention matmuls over
    a sequence of S.  Recomputation is not counted: it lowers MFU."""
    flops = 6.0 * float(n_params)
    if num_layers and hidden_size and seq_len:
        flops += 12.0 * num_layers * hidden_size * seq_len
    return flops


def gpt_param_count(vocab_size: int, hidden_size: int, num_layers: int,
                    max_seq_len: int, intermediate_size: int = 0) -> int:
    """Parameters of the GPT-3 block shape with a tied output head."""
    h, f = hidden_size, intermediate_size or 4 * hidden_size
    block = (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) \
        + 4 * h
    return (vocab_size + max_seq_len) * h + num_layers * block + 2 * h


def causal_attention_train_flops(batch: int, num_heads: int, seq_len: int,
                                 head_dim: int, num_layers: int) -> float:
    """FLOPs of the causal attention one train step needs: two matmuls
    forward (QK^T, PV) and four backward (dQ, dK, dV, dP), each
    2*B*nh*S*S*hd multiply-adds halved by the causal mask, per layer.
    What a kernel recomputes in its backward pass is not counted."""
    one = 2.0 * batch * num_heads * seq_len * seq_len * head_dim / 2.0
    return 6.0 * one * num_layers


def mfu(tokens_per_s: float, flops_per_token: float, device_kind: str,
        chips: int = 1) -> float:
    """Model FLOP/s utilization over `chips` chips of `device_kind`."""
    return tokens_per_s * flops_per_token / (chips * peak_flops(device_kind))
