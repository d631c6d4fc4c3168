"""FlashAttention-2 as Pallas TPU kernels (forward + backward).

Role of the reference's CUDA flash attention
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu` + vendored
`third_party/flashattn`, and the fused path of
`fused_multi_transformer_op.cu`): attention computed blockwise in VMEM so
the [S, S] score matrix never materializes in HBM.  This version carries
the reference kernel's full feature set: key-padding masks (the varlen
API's effective semantics), cross/cached attention (Sq != Sk with
end-aligned causal), GQA (fewer kv heads than q heads, resolved by index
maps — repeated K/V never touch HBM), and in-kernel dropout (the CUDA
kernel's philox dropout; here the TPU PRNG reseeded per block so the
backward kernels regenerate identical bits instead of storing the mask).

Layout follows paddle's flash-attn API: q, k, v are [B, S, nh, hd].

Kernel structure (the canonical TPU pattern — the *last* grid dimension is
sequential on TPU, so the online-softmax state lives in VMEM scratch across
k-block steps):

* forward: grid (B*nh, Sq/BQ, Sk/BK); scratch (m, l, acc); causal blocks
  above the (end-aligned) diagonal are skipped (`pl.when`), the diagonal
  block is masked with `broadcasted_iota`.  Outputs out and the logsumexp
  rows (for bwd).
* backward dq: grid (B*nh, Sq/BQ, Sk/BK), accumulates dq over k blocks.
* backward dkv: grid (B*nh, Sk/BK, Sq/BQ), accumulates dk/dv over q blocks.
  Uses the FlashAttention-2 identity ds = p * (dp - D), D = rowsum(dO * O),
  so no second softmax pass is needed.  With GQA the kernels emit per-
  q-head dk/dv ([B, nh, Sk, hd]) which XLA reduces over the head group.

All matmuls run on the MXU with f32 accumulation (`preferred_element_type`);
bf16 inputs stay bf16 in HBM.  On non-TPU backends the same kernels run
under the Pallas interpreter (CPU CI), selected automatically.

Dropout applies to the normalized probabilities (standard attention
semantics): l accumulates undropped p, acc accumulates dropped p @ v.
Each (batch*head, q-block, k-block) seeds the PRNG as
(seed, bh, qi, ki) so all three kernels see the same keep mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["flash_attention", "flash_attention_fwd",
           "flash_attention_bwd", "supported"]

_NEG_INF = -1e30


def _resolve_interpret(interpret, rate):
    """``None`` asks `pallas_common.interpret_default`.  The generic
    Pallas interpreter has no lowering for the TPU PRNG primitives, so
    dropout kernels in interpret mode (CPU CI) run under the
    TPU-semantics interpreter (``pltpu.InterpretParams``) instead."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    if interpret is True and rate > 0.0:
        return pltpu.InterpretParams()
    return interpret


def supported(q_shape, k_shape=None, dtype=None) -> bool:
    """Kernel applicability: seqs multiples of their blocks, MXU-friendly
    hd, q heads an integer multiple of kv heads."""
    if len(q_shape) != 4:
        return False
    _, Sq, nh, hd = q_shape
    if k_shape is not None:
        _, Sk, nkv, hd_k = k_shape
        if hd_k != hd or nkv == 0 or nh % nkv:
            return False
        bk = min(128, Sk)
        if Sk % bk or Sk % 8 or Sk < 8:
            return False
    bq = min(128, Sq)
    return Sq % bq == 0 and Sq % 8 == 0 and Sq >= 8 and hd in (64, 128, 256)


def _block_seed(seed, bh, qi, ki):
    """Mix block coordinates into ONE extra seed word (Mosaic's
    tpu.prng_set_seed_32 accepts at most two values).  Bit-packed so
    distinct blocks get distinct words for all practical grids
    (bh < 2^11, qi/ki < 2^10); int32 wraparound beyond that is a
    harmless (deterministic) collision."""
    return jnp.int32(seed) ^ (bh * jnp.int32(1 << 20)
                              + qi * jnp.int32(1 << 10) + ki)


def _dropout_keep(shape, rate, seed_word):
    """Regenerate the dropout keep-mask for the current block from the
    TPU PRNG: a pure function of (seed_word, shape), so the forward and
    both backward kernels redraw bit-identical masks."""
    pltpu.prng_seed(seed_word)
    bits = pltpu.prng_random_bits(shape)
    # bitcast keeps the threshold comparison unsigned
    if bits.dtype != jnp.uint32:
        bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    # keep with probability (1 - rate): threshold on the uint32 line
    thresh = jnp.uint32((1.0 - rate) * 4294967295.0)
    return bits < thresh


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, nk, offset, rate, has_mask):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # causal (end-aligned: query i attends keys <= i + offset, offset =
    # Sk - Sq): skip blocks strictly above the shifted diagonal
    run = True if not causal else (k_start <= q_start + offset + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[:, :]                       # [bq, hd]
        k = k_ref[:, :]                       # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        valid2d = None
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
            valid2d = rows + offset >= cols
        if has_mask:
            valid = mask_ref[0, :] != 0                   # [bk]
            vk = jnp.broadcast_to(valid[None, :], (bq, bk))
            valid2d = vk if valid2d is None else (valid2d & vk)
        if valid2d is not None:
            s = jnp.where(valid2d, s, _NEG_INF)
        m_prev = m_scr[:, 0]                         # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])              # [bq, bk]
        if has_mask or (causal and offset < 0):
            # a fully-masked row in this block has m_new == s == _NEG_INF,
            # making exp(s - m_new) = 1 on masked entries — zero explicitly.
            # Only a kv mask or a negative causal offset can fully mask a
            # row (offset >= 0 keeps at least key 0 valid for every query);
            # plain causal self-attention skips this VPU pass.
            p = jnp.where(valid2d, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)              # [bq]
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        v = v_ref[:, :]                        # [bk, hd]
        if rate > 0.0:
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
            p_v = jnp.where(keep, p / (1.0 - rate), 0.0)
        else:
            p_v = p
        pv = jax.lax.dot_general(
            p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, hd]
        acc_scr[:] = acc_scr[:] * alpha[:, None] + pv
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:, :] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
        # lse rows broadcast across a 128-lane dim (Mosaic tile alignment,
        # same layout as jax's reference flash kernel)
        lse_ref[:, :] = m_scr[:, :] + jnp.broadcast_to(
            jnp.log(l_safe)[:, None], lse_ref.shape)


def _bnsh(x):
    return jnp.transpose(x, (0, 2, 1, 3))  # [B, S, nh, hd] -> [B, nh, S, hd]


def _pick_block(S, target):
    """Largest block <= target that divides S (halving; terminates at <=128
    because `supported` requires S % min(128, S) == 0)."""
    b = min(target, S)
    while S % b:
        b //= 2
    return b


def _seed_arr(seed):
    if seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(seed, jnp.int32).reshape((1,))


def _mask_arr(kv_mask, B, Sk):
    """[B, Sk] (or broadcastable) 0/1 key-validity -> [B, 1, Sk] int32."""
    if kv_mask is None:
        return jnp.ones((B, 1, Sk), jnp.int32)
    m = jnp.asarray(kv_mask)
    m = jnp.broadcast_to(m.reshape(m.shape[0], 1, m.shape[-1]), (B, 1, Sk))
    return m.astype(jnp.int32)


def flash_attention_fwd(q, k, v, causal=False, interpret=None,
                        kv_mask=None, dropout_rate=0.0, seed=None,
                        block_q=512, block_k=1024):
    """Returns (out, lse); out [B, Sq, nh, hd], lse [B, nh, Sq, 128]
    (float32, rows broadcast across the 128-lane dim).

    k, v may carry fewer heads than q (GQA): nh % nkv == 0; the kernel
    resolves the head group through the k/v index maps, so the repeated
    heads never materialize.  kv_mask is a [B, Sk] 0/1 key-validity mask
    (padding); dropout_rate with `seed` (int32) applies in-kernel dropout
    to the normalized probabilities.

    Kernels run in BNSH layout so blocks are rank-2 [block, hd] after
    squeezing the (batch, head) dims — Mosaic's lane/sublane alignment
    applies to the (seq, hd) dims, which are tile-friendly."""
    interpret = _resolve_interpret(interpret, float(dropout_rate))
    pallas_common.claim("flash_fwd", interpret)
    B, Sq, nh, hd = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    rate = float(dropout_rate)
    has_mask = kv_mask is not None

    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk, offset=Sk - Sq,
                             rate=rate, has_mask=has_mask)
    grid = (B * nh, nq, nk)

    def qmap(bh, qi, ki, *_):
        return (bh // nh, bh % nh, qi, 0)

    def kmap(bh, qi, ki, *_):
        return (bh // nh, (bh % nh) // group, ki, 0)

    def mmap(bh, qi, ki, *_):
        return (bh // nh, 0, ki)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), qmap),
            pl.BlockSpec((None, None, bk, hd), kmap),
            pl.BlockSpec((None, None, bk, hd), kmap),
            pl.BlockSpec((None, 1, bk), mmap),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, hd), qmap),
            pl.BlockSpec((None, None, bq, 128), qmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, nh, Sq, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(_seed_arr(seed), _bnsh(q), _bnsh(k), _bnsh(v), _mask_arr(kv_mask, B, Sk))
    return jnp.transpose(out, (0, 2, 1, 3)), lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   mask_ref, dq_ref, dq_scr,
                   *, scale, causal, bq, bk, nk, offset, rate, has_mask):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * bq
    k_start = ki * bk
    run = True if not causal else (k_start <= q_start + offset + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[:, :]
        k = k_ref[:, :]
        v = v_ref[:, :]
        do = do_ref[:, :].astype(jnp.float32)
        lse = lse_ref[:, 0:1]                  # [bq, 1]
        # D = rowsum(dO * O) (FlashAttention-2), computed on the block
        delta = jnp.sum(do * o_ref[:, :].astype(jnp.float32), axis=1,
                        keepdims=True)         # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid2d = None
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
            valid2d = rows + offset >= cols
        if has_mask:
            valid = mask_ref[0, :] != 0
            vk = jnp.broadcast_to(valid[None, :], (bq, bk))
            valid2d = vk if valid2d is None else (valid2d & vk)
        if valid2d is not None:
            s = jnp.where(valid2d, s, _NEG_INF)
        p = jnp.exp(s - lse)                         # [bq, bk]
        if has_mask or (causal and offset < 0):
            # fully-masked rows carry lse = _NEG_INF; zero explicitly
            # (plain causal offset>=0 rows always keep key 0 — skip)
            p = jnp.where(valid2d, p, 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, bk]
        if rate > 0.0:
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[:, :] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    mask_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, nq, offset, rate, has_mask):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * bq
    k_start = ki * bk
    run = True if not causal else (k_start <= q_start + offset + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[:, :]
        k = k_ref[:, :]
        v = v_ref[:, :]
        do = do_ref[:, :].astype(jnp.float32)
        lse = lse_ref[:, 0:1]                  # [bq, 1]
        delta = jnp.sum(do * o_ref[:, :].astype(jnp.float32), axis=1,
                        keepdims=True)         # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid2d = None
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
            valid2d = rows + offset >= cols
        if has_mask:
            valid = mask_ref[0, :] != 0
            vk = jnp.broadcast_to(valid[None, :], (bq, bk))
            valid2d = vk if valid2d is None else (valid2d & vk)
        if valid2d is not None:
            s = jnp.where(valid2d, s, _NEG_INF)
        p = jnp.exp(s - lse)                         # [bq, bk]
        if has_mask or (causal and offset < 0):
            # fully-masked rows carry lse = _NEG_INF; zero explicitly
            # (plain causal offset>=0 rows always keep key 0 — skip)
            p = jnp.where(valid2d, p, 0.0)
        if rate > 0.0:
            # seeded by LOGICAL block coords (bh, qi, ki) — this kernel's
            # grid iterates (bh, ki, qi) but must regenerate the exact
            # bits the forward drew for the (qi, ki) tile
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
            p_v = jnp.where(keep, p / (1.0 - rate), 0.0)
        else:
            keep = None
            p_v = p
        # dv += (dropped p)^T @ do
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bk, hd]
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, bk]
        if rate > 0.0:
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        ds = p * (dp - delta) * scale                # [bq, bk]
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[:, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:, :] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(causal, interpret, kv_mask_shape, rate, res, g,
               block_q=512, block_k=512):
    q, k, v, out, lse, mask_arr, seed_arr = res
    interpret = _resolve_interpret(interpret, rate)
    pallas_common.claim("flash_bwd_dq", interpret)
    pallas_common.claim("flash_bwd_dkv", interpret)
    B, Sq, nh, hd = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    # kv_mask_shape records whether the FORWARD had a user mask; when it
    # didn't, the saved residual mask is the internally-built all-ones
    # array (never user data), so applying it would be the identity — the
    # unmasked train path skips the mask reads and both extra VPU
    # `where` passes entirely (round-3 applied it unconditionally, which
    # cost ~9% of the GPT-124M train step)
    has_mask = kv_mask_shape is not None

    qb, kb, vb = _bnsh(q), _bnsh(k), _bnsh(v)
    ob, gb = _bnsh(out), _bnsh(g)

    def qmap(bh, qi, ki, *_):
        return (bh // nh, bh % nh, qi, 0)

    def kmap(bh, qi, ki, *_):
        return (bh // nh, (bh % nh) // group, ki, 0)

    def mmap(bh, qi, ki, *_):
        return (bh // nh, 0, ki)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), qmap),
            pl.BlockSpec((None, None, bk, hd), kmap),
            pl.BlockSpec((None, None, bk, hd), kmap),
            pl.BlockSpec((None, None, bq, hd), qmap),
            pl.BlockSpec((None, None, bq, hd), qmap),
            pl.BlockSpec((None, None, bq, 128), qmap),
            pl.BlockSpec((None, 1, bk), mmap),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd), qmap),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, offset=Sk - Sq, rate=rate,
                          has_mask=has_mask),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, Sq, hd), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(seed_arr, qb, kb, vb, ob, gb, lse, mask_arr)

    # dkv: grid ordered (bh, ki, qi) — q is the sequential axis
    def kmap2(bh, ki, qi, *_):
        return (bh // nh, (bh % nh) // group, ki, 0)

    def kout2(bh, ki, qi, *_):
        return (bh // nh, bh % nh, ki, 0)

    def qmap2(bh, ki, qi, *_):
        return (bh // nh, bh % nh, qi, 0)

    def mmap2(bh, ki, qi, *_):
        return (bh // nh, 0, ki)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, nk, nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), qmap2),
            pl.BlockSpec((None, None, bk, hd), kmap2),
            pl.BlockSpec((None, None, bk, hd), kmap2),
            pl.BlockSpec((None, None, bq, hd), qmap2),
            pl.BlockSpec((None, None, bq, hd), qmap2),
            pl.BlockSpec((None, None, bq, 128), qmap2),
            pl.BlockSpec((None, 1, bk), mmap2),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, hd), kout2),
            pl.BlockSpec((None, None, bk, hd), kout2),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, offset=Sk - Sq, rate=rate,
                          has_mask=has_mask),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, nh, Sk, hd), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(seed_arr, qb, kb, vb, ob, gb, lse, mask_arr)
    if group > 1:
        # GQA: reduce per-q-head grads over each kv head's group
        dk = dk.reshape(B, nkv, group, Sk, hd).sum(axis=2, dtype=jnp.float32)
        dv = dv.reshape(B, nkv, group, Sk, hd).sum(axis=2, dtype=jnp.float32)
        dk = dk.astype(k.dtype)
        dv = dv.astype(v.dtype)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv), None, None


def flash_attention_bwd(q, k, v, out, lse, g, causal=False, interpret=None):
    """Public backward entry point: gradients (dq, dk, dv) of
    `flash_attention_fwd`'s output w.r.t. q/k/v, given the forward's
    residuals.  `lse` is the [B, nh, Sq, 128] lane-broadcast logsumexp the
    forward returns (callers holding [B, nh, Sq] rows may broadcast them —
    only lane 0 is read).  The FA2 identities hold for any *global*
    normalizer, so chunked/ring callers may pass a combined lse to get this
    chunk's contribution to the global gradients."""
    B, Sk = k.shape[0], k.shape[1]
    dq, dk, dv, _, _ = _flash_bwd(
        causal, interpret, None, 0.0,
        (q, k, v, out, lse, _mask_arr(None, B, Sk), _seed_arr(None)), g)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 7, 8))
def _flash_attention_core(q, k, v, causal, interpret,
                          kv_mask, seed, kv_mask_shape, dropout_rate):
    out, _ = flash_attention_fwd(q, k, v, causal, interpret,
                                 kv_mask, dropout_rate, seed)
    return out


def flash_attention(q, k, v, causal=False, interpret=None,
                    kv_mask=None, seed=None, kv_mask_shape=None,
                    dropout_rate=0.0):
    """Flash attention; q [B, Sq, nh, hd], k/v [B, Sk, nkv, hd] ->
    [B, Sq, nh, hd].  kv_mask: optional [B, Sk] 0/1 key-validity;
    seed: optional int32 scalar for dropout.  `kv_mask_shape` is the
    static mirror of kv_mask's presence (custom_vjp nondiff args must be
    static); it is derived here so a direct caller can never get a
    masked forward with an unmasked backward."""
    if kv_mask is not None and kv_mask_shape is None:
        kv_mask_shape = tuple(kv_mask.shape)
    return _flash_attention_core(q, k, v, causal, interpret, kv_mask,
                                 seed, kv_mask_shape, dropout_rate)


def _fa_fwd(q, k, v, causal, interpret, kv_mask, seed, kv_mask_shape,
            dropout_rate):
    out, lse = flash_attention_fwd(q, k, v, causal, interpret,
                                   kv_mask, dropout_rate, seed)
    B, Sk = k.shape[0], k.shape[1]
    return out, (q, k, v, out, lse, _mask_arr(kv_mask, B, Sk),
                 _seed_arr(seed))


def _fa_bwd(causal, interpret, kv_mask_shape, dropout_rate, res, g):
    return _flash_bwd(causal, interpret, kv_mask_shape, dropout_rate,
                      res, g)


_flash_attention_core.defvjp(_fa_fwd, _fa_bwd)
