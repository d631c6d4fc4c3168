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

* forward: grid (B*nh, Sq/BQ, Sk/BK); scratch (m, l, acc).  Outputs out
  and the logsumexp rows (for bwd).
* backward dq: grid (B*nh, Sq/BQ, Sk/BK), accumulates dq over k blocks;
  also writes D = rowsum(dO * O) once a q block, laid out like lse.
* backward dkv: grid (B*nh, Sk/BK, Sq/BQ), accumulates dk/dv over q blocks.
  Uses the FlashAttention-2 identity ds = p * (dp - D), so no second
  softmax pass is needed, and reads D from `flash_bwd_dq`.  With GQA the
  kernels emit per-q-head dk/dv ([B, nh, Sk, hd]) which XLA reduces over
  the head group.

Blocks come from `block_plan(Sq, Sk, hd, causal, kind)`, a function of the
shapes alone, and fall in three classes under (end-aligned) causality,
decided from the program ids: *skipped* (no valid pair: not run),
*interior* (every pair valid: no iota, compare or select) and *diagonal*
(masked).  A block is multiplied in strips — row strips in the forward,
each one softmax step; key strips in the backward — and a diagonal block
that sits squarely on the diagonal (self-attention's all do) multiplies
only the part of each strip that the causal mask leaves: the upper
triangle costs nothing, so blocks can be as long as the MXU likes (every
matmul pays a fixed cost a 128-wide weight tile on the v5e, which long
operands amortize) without paying for their masked half.

Operand policy, the same in all three kernels (amp O1): every matmul takes
its operands in the inputs' dtype and accumulates in float32
(`preferred_element_type`); `p` and `ds` are rounded to that dtype for the
matmuls that consume them, the softmax statistics, `dp` and `ds` themselves
are float32.  The softmax scale is folded into q ([rows, hd]) before
`q k^T` and into dq / dk where their scratch is written out, never applied
to a [rows, keys] matrix.  bf16 inputs stay bf16 in HBM.  On non-TPU
backends the same kernels run under the Pallas interpreter (CPU CI),
selected automatically.

Dropout applies to the normalized probabilities (standard attention
semantics): l accumulates undropped p, acc accumulates dropped p @ v.
Each (batch*head, q-block, k-block) seeds the PRNG as
(seed, bh, qi, ki) and draws the block's bits at once, so the three
kernels share their blocks under dropout (`block_plan`'s `dropout`) and
see the same keep mask.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["flash_attention", "flash_attention_fwd",
           "flash_attention_bwd", "supported", "block_plan", "block_class"]

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
# blocks
# --------------------------------------------------------------------------

def _block_runs(q_start, k_start, bq, offset):
    """Some pair of the block is valid under end-aligned causality (query
    i attends keys <= i + offset, offset = Sk - Sq).  Python ints in
    `block_plan`, traced int32 in the kernels: one predicate for both."""
    return k_start <= q_start + offset + bq - 1


def _block_interior(q_start, k_start, bk, offset):
    """Every pair of the block is valid: it lies wholly under the diagonal
    and needs no mask."""
    return k_start + bk - 1 <= q_start + offset


def block_class(q_start, k_start, bq, bk, offset, causal=True):
    """"interior", "diagonal" or "skipped": how the kernels treat the
    [bq, bk] block of the score matrix at (q_start, k_start)."""
    if not causal or _block_interior(q_start, k_start, bk, offset):
        return "interior"
    return ("diagonal" if _block_runs(q_start, k_start, bq, offset)
            else "skipped")


def _pick_block(S, target):
    """Largest power-of-two block <= target that divides S (S itself when
    it fits; halving terminates at <=128 because `supported` requires
    S % min(128, S) == 0)."""
    b = min(target, S)
    if S % b == 0:
        return b
    b = 1 << (b.bit_length() - 1)
    while S % b:
        b //= 2
    return b


class BlockPlan(NamedTuple):
    bq: int
    bk: int
    interior: int      # blocks a head that run without a mask
    diagonal: int      # blocks a head the diagonal crosses
    skipped: int       # blocks a head with no valid pair: not run


# A block is as large as the sequence allows up to this many rows / keys,
# whatever the kernel and the head width: on the v5e every matmul of a
# block pays a fixed cost a 128-wide weight tile, so long operands win,
# and the strips below keep a large block from multiplying its masked
# half (the sweep: PERF.md section 6, PR 29).  Dropout draws a block's
# bits at once ([bq, bk] uint32), so its blocks stay small.
_BLOCK, _BLOCK_DROPOUT = 2048, 512
_STRIP = 256           # rows (forward) / keys (backward) a strip
_STRIP_INTERIOR = 512  # rows a forward strip off the diagonal
_VMEM_LIMIT = 64 * 2 ** 20     # of the v5e's 128 MiB; Mosaic's default is 16


def block_plan(Sq, Sk, hd, causal, kind, dropout=False,
               block_q=None, block_k=None):
    """The (bq, bk) the `kind` kernel ("fwd", "dq", "dkv") tiles a
    [Sq, Sk] score matrix with, and how many blocks a head fall in each
    class.  A pure function of what the kernels see in their arguments.
    The sweep on the v5e chose one target for every kind and `hd` in
    `supported` (they are arguments so that a chip that wants otherwise
    changes this function and nothing else); dropout's three kernels must
    share blocks, since a block's keep bits are one draw.  `block_q` /
    `block_k` replace the target (tests)."""
    assert kind in ("fwd", "dq", "dkv"), kind
    target = _BLOCK_DROPOUT if dropout else _BLOCK
    bq = _pick_block(Sq, block_q or target)
    bk = _pick_block(Sk, block_k or target)
    classes = [block_class(qi * bq, ki * bk, bq, bk, Sk - Sq, causal)
               for qi in range(Sq // bq) for ki in range(Sk // bk)]
    return BlockPlan(bq, bk, *(classes.count(c) for c in
                               ("interior", "diagonal", "skipped")))


def _when_block(plan, q_start, k_start, offset, body):
    """Run `body(diagonal)` for the block's class: not at all above the
    diagonal, `body(False)` under it, `body(True)` across it.  A class the
    plan counts no block in gets no code (one block a head, as at the
    train cell's S = 2048, is all diagonal)."""
    if not plan.diagonal and not plan.skipped:
        body(False)
        return
    interior = _block_interior(q_start, k_start, plan.bk, offset)
    if plan.interior:
        pl.when(interior)(lambda: body(False))
    if plan.diagonal:
        pl.when(jnp.logical_and(
            _block_runs(q_start, k_start, plan.bq, offset),
            jnp.logical_not(interior)))(lambda: body(True))


def _on_diagonal(bq, bk, offset, diagonal):
    """The diagonal blocks sit squarely on the diagonal: bq == bk and
    offset a multiple of it, as in every diagonal block of
    self-attention.  Their upper triangle is then known statically."""
    return diagonal and bq == bk and offset % bk == 0


def _row_strips(bq, bk, offset, diagonal):
    """How the forward multiplies a block: (first row, rows, keys) strips,
    each one softmax step.  Squarely on the diagonal a strip meets the keys
    up to its last row and no others."""
    if _on_diagonal(bq, bk, offset, diagonal) and bq > _STRIP \
            and bq % _STRIP == 0:
        return [(r, _STRIP, r + _STRIP) for r in range(0, bq, _STRIP)]
    h = _STRIP_INTERIOR if bq % _STRIP_INTERIOR == 0 else bq
    return [(r, h, bk) for r in range(0, bq, h)]


def _col_strips(bq, bk, offset, diagonal):
    """How the backward multiplies a block: (first key, keys, first row)
    strips.  Squarely on the diagonal a strip starts at the first row that
    sees it: the block's upper triangle is not multiplied at all."""
    if bk <= _STRIP or bk % _STRIP:
        return [(0, bk, 0)]
    tri = _on_diagonal(bq, bk, offset, diagonal)
    return [(c, _STRIP, c if tri else 0) for c in range(0, bk, _STRIP)]


_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scaled(q, scale):
    """q * scale in q's dtype: the scale goes onto [rows, hd], not onto the
    [rows, keys] scores."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _scores(q, k, kmask, *, diagonal, q_start, k_start, offset):
    """Scores `q k^T` (q already scaled) of a block or a strip of one at
    (q_start, k_start): float32 with invalid pairs at `_NEG_INF`, and the
    validity they were masked by (None: every pair valid).  `kmask`:
    [1, keys] key validity or None."""
    s = _dot(q, k, _NT)
    valid = None
    if diagonal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = cols - rows <= q_start - k_start + offset
    if kmask is not None:
        vk = jnp.broadcast_to(kmask != 0, s.shape)
        valid = vk if valid is None else (valid & vk)
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    return s, valid


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, plan, nk, offset, rate, has_mask):
    bq, bk = plan.bq, plan.bk
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

    def body(diagonal):
        keep = None
        if rate > 0.0:
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
        for r, h, n in _row_strips(bq, bk, offset, diagonal):
            rows = slice(r, r + h)
            s, valid = _scores(
                _scaled(q_ref[rows, :], scale), k_ref[:n, :],
                mask_ref[:, :n] if has_mask else None, diagonal=diagonal,
                q_start=q_start + r, k_start=k_start, offset=offset)
            m_prev = m_scr[rows, 0:1]                    # [h, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                       # [h, n]
            if valid is not None and (has_mask or offset < 0):
                # a fully-masked row in this block has m_new == s ==
                # _NEG_INF, making exp(s - m_new) = 1 on masked entries —
                # zero explicitly.  Only a kv mask or a negative causal
                # offset can fully mask a row (offset >= 0 keeps at least
                # key 0 valid for every query); plain causal
                # self-attention skips this VPU pass.
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)              # [h, 1]
            l_new = (l_scr[rows, 0:1] * alpha
                     + jnp.sum(p, axis=1, keepdims=True))
            v = v_ref[:n, :]                             # [n, hd]
            if keep is not None:
                p = jnp.where(keep[rows, :n], p / (1.0 - rate), 0.0)
            acc_scr[rows, :] = (acc_scr[rows, :] * alpha
                                + _dot(p.astype(v.dtype), v, _NN))
            m_scr[rows, :] = jnp.broadcast_to(m_new, (h, 128))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (h, 128))

    _when_block(plan, q_start, k_start, offset, body)

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse rows broadcast across a 128-lane dim (Mosaic tile alignment,
        # same layout as jax's reference flash kernel)
        lse_ref[:, :] = m_scr[:, :] + jnp.broadcast_to(
            jnp.log(l_safe), lse_ref.shape)


def _bnsh(x):
    return jnp.transpose(x, (0, 2, 1, 3))  # [B, S, nh, hd] -> [B, nh, S, hd]


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


def _specs(nh, group, hd, bq, bk, q_axis):
    """BlockSpecs over a (bh, i, j) grid whose axis `q_axis` (1 or 2)
    walks the q blocks and the other the k blocks: `qside(width)` for q,
    out, dO, lse, delta and their gradients; `kside` for k and v (GQA
    resolves the kv head here); `kmask` for the [B, 1, Sk] key mask."""
    def qk(i, j):
        return (i, j) if q_axis == 1 else (j, i)

    def qside(width=hd):
        return pl.BlockSpec(
            (None, None, bq, width),
            lambda bh, i, j, *_: (bh // nh, bh % nh, qk(i, j)[0], 0))

    kside = pl.BlockSpec(
        (None, None, bk, hd),
        lambda bh, i, j, *_: (bh // nh, (bh % nh) // group, qk(i, j)[1], 0))
    kmask = pl.BlockSpec(
        (None, 1, bk), lambda bh, i, j, *_: (bh // nh, 0, qk(i, j)[1]))
    return qside, kside, kmask


_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


# The three calls are jitted on their own: a model's layers then share one
# trace and one Mosaic lowering of each kernel inside a step's program, and
# an eager caller (an SOT record of `to_static`) lowers each once, not once
# a layer.
_kernel_call = functools.partial(jax.jit, static_argnames=(
    "causal", "rate", "has_mask", "interpret", "block_q", "block_k"))


@_kernel_call
def _fwd_call(seed_arr, qb, kb, vb, mask_arr, *, causal, rate, has_mask,
              interpret, block_q=None, block_k=None):
    """`flash_fwd` on BNSH arrays -> (out [B, nh, Sq, hd], lse
    [B, nh, Sq, 128])."""
    B, nh, Sq, hd = qb.shape
    nkv, Sk = kb.shape[1], kb.shape[2]
    plan = block_plan(Sq, Sk, hd, causal, "fwd", rate > 0.0, block_q, block_k)
    bq, bk = plan.bq, plan.bk
    nk = Sk // bk
    kern = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd),
                             plan=plan, nk=nk, offset=Sk - Sq, rate=rate,
                             has_mask=has_mask)
    qside, kside, kmask = _specs(nh, nh // nkv, hd, bq, bk, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, Sq // bq, nk),
        in_specs=[qside(), kside, kside, kmask],
        out_specs=[qside(), qside(128)],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Sq, hd), qb.dtype),
            jax.ShapeDtypeStruct((B, nh, Sq, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_PARAMS,
        name="flash_fwd",
    )(seed_arr, qb, kb, vb, mask_arr)


def flash_attention_fwd(q, k, v, causal=False, interpret=None,
                        kv_mask=None, dropout_rate=0.0, seed=None,
                        block_q=None, block_k=None):
    """Returns (out, lse); out [B, Sq, nh, hd], lse [B, nh, Sq, 128]
    (float32, rows broadcast across the 128-lane dim).

    k, v may carry fewer heads than q (GQA): nh % nkv == 0; the kernel
    resolves the head group through the k/v index maps, so the repeated
    heads never materialize.  kv_mask is a [B, Sk] 0/1 key-validity mask
    (padding); dropout_rate with `seed` (int32) applies in-kernel dropout
    to the normalized probabilities.  Blocks come from `block_plan`
    unless `block_q` / `block_k` name other targets.

    Kernels run in BNSH layout so blocks are rank-2 [block, hd] after
    squeezing the (batch, head) dims — Mosaic's lane/sublane alignment
    applies to the (seq, hd) dims, which are tile-friendly."""
    rate = float(dropout_rate)
    interpret = _resolve_interpret(interpret, rate)
    pallas_common.claim("flash_fwd", interpret)
    B, Sk = k.shape[0], k.shape[1]
    out, lse = _fwd_call(
        _seed_arr(seed), _bnsh(q), _bnsh(k), _bnsh(v),
        _mask_arr(kv_mask, B, Sk), causal=causal, rate=rate,
        has_mask=kv_mask is not None, interpret=interpret,
        block_q=block_q, block_k=block_k)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_strip(q, k, v, do, lse, delta, kmask, keep, *, rate, **where):
    """What both backward kernels recompute for a strip of a block: the
    dropped probabilities `p_v` (what multiplied v in the forward) and
    `ds`, the gradient of the scores — FlashAttention-2's
    ds = p * (dp - D), D = rowsum(dO * O).  Float32, [rows, keys]; q comes
    scaled, lse and delta as [rows, 1], `keep` is the forward's dropout
    draw for the strip or None."""
    s, valid = _scores(q, k, kmask, **where)
    p = jnp.exp(s - lse)
    if valid is not None and (kmask is not None or where["offset"] < 0):
        # fully-masked rows carry lse = _NEG_INF; zero explicitly
        # (plain causal offset>=0 rows always keep key 0 — skip)
        p = jnp.where(valid, p, 0.0)
    dp = _dot(do, v, _NT)
    p_v = p
    if keep is not None:
        p_v = jnp.where(keep, p / (1.0 - rate), 0.0)
        dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
    return p_v, p * (dp - delta)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   mask_ref, dq_ref, delta_ref, dq_scr,
                   *, scale, plan, nk, offset, rate, has_mask):
    bq, bk = plan.bq, plan.bk
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # D = rowsum(dO * O), once a q block: this kernel's own strips read
        # it from the output block, and `flash_bwd_dkv` reads it back
        delta = jnp.sum(do_ref[:, :].astype(jnp.float32)
                        * o_ref[:, :].astype(jnp.float32),
                        axis=1, keepdims=True)        # [bq, 1]
        delta_ref[:, :] = jnp.broadcast_to(delta, delta_ref.shape)

    def body(diagonal):
        keep = None
        if rate > 0.0:
            # seeded by the block's LOGICAL coordinates (bh, qi, ki)
            # whatever the grid's order: the bits the forward drew for it
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
        for c, w, r in _col_strips(bq, bk, offset, diagonal):
            k = k_ref[c:c + w, :]
            _, ds = _bwd_strip(
                _scaled(q_ref[r:, :], scale), k, v_ref[c:c + w, :],
                do_ref[r:, :], lse_ref[r:, 0:1], delta_ref[r:, 0:1],
                mask_ref[:, c:c + w] if has_mask else None,
                None if keep is None else keep[r:, c:c + w], rate=rate,
                diagonal=diagonal, q_start=qi * bq + r, k_start=ki * bk + c,
                offset=offset)
            dq_scr[r:, :] = dq_scr[r:, :] + _dot(ds.astype(k.dtype), k, _NN)

    _when_block(plan, qi * bq, ki * bk, offset, body)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[:, :] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, mask_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, plan, nq, offset, rate, has_mask):
    bq, bk = plan.bq, plan.bk
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body(diagonal):
        keep = None
        if rate > 0.0:
            keep = _dropout_keep((bq, bk), rate,
                                 _block_seed(seed_ref[0], bh, qi, ki))
        for c, w, r in _col_strips(bq, bk, offset, diagonal):
            q, do = q_ref[r:, :], do_ref[r:, :]
            p_v, ds = _bwd_strip(
                _scaled(q, scale), k_ref[c:c + w, :], v_ref[c:c + w, :], do,
                lse_ref[r:, 0:1], delta_ref[r:, 0:1],
                mask_ref[:, c:c + w] if has_mask else None,
                None if keep is None else keep[r:, c:c + w], rate=rate,
                diagonal=diagonal, q_start=qi * bq + r, k_start=ki * bk + c,
                offset=offset)
            dv_scr[c:c + w, :] = dv_scr[c:c + w, :] + _dot(
                p_v.astype(do.dtype), do, _TN)           # [w, hd]
            dk_scr[c:c + w, :] = dk_scr[c:c + w, :] + _dot(
                ds.astype(q.dtype), q, _TN)

    _when_block(plan, qi * bq, ki * bk, offset, body)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[:, :] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[:, :] = dv_scr[:].astype(dv_ref.dtype)


@_kernel_call
def _dq_call(seed_arr, qb, kb, vb, ob, gb, lse, mask_arr, *, causal, rate,
             has_mask, interpret, block_q=None, block_k=None):
    """`flash_bwd_dq` on BNSH arrays -> (dq [B, nh, Sq, hd], delta
    [B, nh, Sq, 128]: D = rowsum(dO * O) laid out like lse)."""
    B, nh, Sq, hd = qb.shape
    nkv, Sk = kb.shape[1], kb.shape[2]
    plan = block_plan(Sq, Sk, hd, causal, "dq", rate > 0.0, block_q, block_k)
    bq, bk = plan.bq, plan.bk
    nk = Sk // bk
    qside, kside, kmask = _specs(nh, nh // nkv, hd, bq, bk, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, Sq // bq, nk),
        in_specs=[qside(), kside, kside, qside(), qside(), qside(128),
                  kmask],
        out_specs=[qside(), qside(128)],
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=1.0 / math.sqrt(hd),
                          plan=plan, nk=nk, offset=Sk - Sq, rate=rate,
                          has_mask=has_mask),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nh, Sq, hd), qb.dtype),
                   jax.ShapeDtypeStruct((B, nh, Sq, 128), jnp.float32)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name="flash_bwd_dq",
    )(seed_arr, qb, kb, vb, ob, gb, lse, mask_arr)


@_kernel_call
def _dkv_call(seed_arr, qb, kb, vb, gb, lse, delta, mask_arr, *, causal,
              rate, has_mask, interpret, block_q=None, block_k=None):
    """`flash_bwd_dkv` on BNSH arrays -> (dk, dv), per q head
    ([B, nh, Sk, hd]).  Grid (bh, ki, qi): q is the sequential axis."""
    B, nh, Sq, hd = qb.shape
    nkv, Sk = kb.shape[1], kb.shape[2]
    plan = block_plan(Sq, Sk, hd, causal, "dkv", rate > 0.0, block_q,
                      block_k)
    bq, bk = plan.bq, plan.bk
    nq = Sq // bq
    qside, kside, kmask = _specs(nh, nh // nkv, hd, bq, bk, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, Sk // bk, nq),
        in_specs=[qside(), kside, kside, qside(), qside(128), qside(128),
                  kmask],
        # per q head: GQA's group is summed outside
        out_specs=[pl.BlockSpec(
            (None, None, bk, hd),
            lambda bh, ki, qi, *_: (bh // nh, bh % nh, ki, 0))] * 2,
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32)] * 2,
    )
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=1.0 / math.sqrt(hd),
                          plan=plan, nq=nq, offset=Sk - Sq, rate=rate,
                          has_mask=has_mask),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nh, Sk, hd), kb.dtype),
                   jax.ShapeDtypeStruct((B, nh, Sk, hd), vb.dtype)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name="flash_bwd_dkv",
    )(seed_arr, qb, kb, vb, gb, lse, delta, mask_arr)


def _flash_bwd(causal, interpret, kv_mask_shape, rate, res, g,
               block_q=None, block_k=None):
    q, k, v, out, lse, mask_arr, seed_arr = res
    interpret = _resolve_interpret(interpret, rate)
    pallas_common.claim("flash_bwd_dq", interpret)
    pallas_common.claim("flash_bwd_dkv", interpret)
    B, Sq, nh, hd = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    qb, kb, vb, gb = _bnsh(q), _bnsh(k), _bnsh(v), _bnsh(g)
    # kv_mask_shape says whether the FORWARD had a user mask; without one
    # the saved mask is the all-ones array `_mask_arr` builds, and the
    # kernels skip its reads and both `where` passes
    kw = dict(causal=causal, rate=rate, has_mask=kv_mask_shape is not None,
              interpret=interpret, block_q=block_q, block_k=block_k)
    dq, delta = _dq_call(seed_arr, qb, kb, vb, _bnsh(out), gb, lse,
                         mask_arr, **kw)
    dk, dv = _dkv_call(seed_arr, qb, kb, vb, gb, lse, delta, mask_arr, **kw)
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
