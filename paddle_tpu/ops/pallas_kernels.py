"""Pallas TPU kernel dispatch (flash attention, fused MoE routing).

Role of the reference's hand-fused CUDA kernels
(`phi/kernels/gpu/flash_attn_kernel.cu`, `fusion/gpu/` fused ops): ops XLA
won't fuse optimally get hand-written TPU kernels.  The actual kernels live
in `pallas_flash.py` / `pallas_moe.py`; this module gates applicability and
registers the dispatched ops so the eager tape engine differentiates
through each kernel's custom VJP.

Gating: the flash kernel path is taken where kernels compile through
Mosaic (`pallas_common.interpret_default` is false: a TPU backend) with
supported shapes (seqs divisible by their blocks, head_dim in {64, 128, 256}, q
heads a multiple of kv heads).  Key-padding masks ([B, 1, 1, Sk] bool /
[B, Sk]) ride the kernel's kv_mask input; attention dropout runs inside
the kernel (per-block reseeded TPU PRNG).  Anything else — additive
biases, full [Sq, Sk] masks, probability outputs — falls back to the
fused XLA softmax(QK^T)V path, so the same model code runs everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pallas_common, pallas_flash, pallas_moe
from .registry import dispatch as _d, register_op

__all__ = ["flash_attention", "flash_attention_available",
           "as_kv_padding_mask",
           "moe_routing_indices", "moe_dispatch", "moe_combine"]


def as_kv_padding_mask(attn_mask, B, Sk):
    """If `attn_mask` (Tensor or array) is unambiguously a BOOLEAN
    key-padding mask — shape [B, 1, Sk] or [B, 1, 1, Sk] (the broadcast
    layouts models build, e.g. BERT's `unsqueeze(mask > 0, [1, 2])`) —
    return it as a [B, Sk] array; else None (caller falls back to XLA).
    Integer masks are NOT accepted: paddle's integer/float attn_mask is
    ADDITIVE (0/-10000 style), the opposite semantics.  A bare 2-D mask
    is also rejected: [B, Sk] is indistinguishable from a per-query
    [Sq, Sk] mask when B == Sq."""
    if attn_mask is None:
        return None
    v = getattr(attn_mask, "_value", attn_mask)
    if v.dtype != jnp.bool_:
        return None
    shape = tuple(v.shape)
    if shape == (B, 1, Sk) or shape == (B, 1, 1, Sk):
        return v.reshape(B, Sk)
    return None


def flash_attention_available(q, k, v, mask=None) -> bool:
    """Shape/backend applicability; `mask` here means a mask the kernel
    CANNOT absorb (callers pass attn_mask only if as_kv_padding_mask
    returned None for it).  An interpreted flash kernel is a CI device,
    not a path: off-TPU the fused XLA softmax is the implementation.
    Callers that must know which path a program took read the kernel
    claims (`flash_fwd` / `flash_bwd_*`), not this predicate."""
    if mask is not None:
        return False
    if pallas_common.interpret_default():
        return False
    return pallas_flash.supported(tuple(q.shape), tuple(k.shape))


def _fa_op(q, k, v, kv_mask, seed, *, causal, dropout_rate, mask_shape):
    return pallas_flash.flash_attention(
        q, k, v, causal, None, kv_mask, seed, mask_shape, dropout_rate)


register_op("flash_attention", _fa_op, tags=("mxu", "fused", "pallas"))


def flash_attention(q, k, v, causal=False, dropout_p=0.0, kv_mask=None):
    """Pallas flash-attention on [B, S, nh, hd] Tensors; differentiable
    through the kernel's custom VJP (FlashAttention-2 backward kernels).

    kv_mask: optional [B, Sk] 0/1 key-validity Tensor/array (padding);
    dropout_p > 0 applies in-kernel attention dropout (seeded from the
    framework RNG, so paddle.seed reproduces runs)."""
    from ..nn.functional.attention import sdpa_xla
    if not flash_attention_available(q, k, v):
        xla_mask = None
        if kv_mask is not None:
            # keep padding semantics on the fallback: [B, Sk] 0/1 ->
            # [B, 1, 1, Sk] boolean keep-mask broadcast over heads/queries
            mv = getattr(kv_mask, "_value", kv_mask)
            xla_mask = (mv != 0).reshape(mv.shape[0], 1, 1, mv.shape[-1])
        return sdpa_xla(q, k, v, xla_mask, dropout_p, causal, None, True)
    seed = None
    if dropout_p > 0.0:
        from ..framework import random as _random
        seed = jax.random.randint(_random.next_key(), (), 0,
                                  jnp.iinfo(jnp.int32).max, jnp.int32)
    mask_shape = None if kv_mask is None else \
        tuple(getattr(kv_mask, "shape", ()))
    return _d("flash_attention", (q, k, v, kv_mask, seed),
              {"causal": bool(causal), "dropout_rate": float(dropout_p),
               "mask_shape": mask_shape})


# ------------------------------------------------------- fused MoE routing
# The dense (T,E,C) einsum dispatch/combine of the MoE layer replaced by
# the one-pass index-form kernels of `pallas_moe.py` (ISSUE 18).  Unlike
# flash attention these run on every backend — interpret mode on CPU (row
# moves, not matmuls, so interpret is not the liability it is for
# attention grids) and Mosaic on TPU.

register_op(
    "moe_routing_indices",
    lambda eid, slot, keep, *, num_experts, capacity:
        pallas_moe.routing_indices(eid, slot, keep, num_experts, capacity))
register_op("moe_dispatch",
            lambda x, inv: pallas_moe.moe_dispatch(x, inv),
            tags=("fused", "pallas"))
register_op("moe_combine",
            lambda rows, w, flat: pallas_moe.moe_combine(rows, w, flat),
            tags=("fused", "pallas"))


def moe_routing_indices(eid, slot, keep, num_experts, capacity):
    """Index plumbing for the fused MoE path: flat destination slot per
    (token, choice) and the inverse slot->token map.  Integer outputs —
    the routing gradient rides the combine weights, not these."""
    return _d("moe_routing_indices", (eid, slot, keep),
              {"num_experts": int(num_experts), "capacity": int(capacity)})


def moe_dispatch(x, inv):
    """Pack token rows [T, M] into flat expert buffers [E*C, M] by the
    inverse slot map; differentiable through the kernel's custom VJP
    (scatter-add transpose)."""
    return _d("moe_dispatch", (x, inv), {})


def moe_combine(expert_rows, w, flat):
    """Mix expert output rows [E*C, M] back to tokens [T, M] with the
    combine weights w [T, k]; differentiable in both expert_rows and w."""
    return _d("moe_combine", (expert_rows, w, flat), {})
