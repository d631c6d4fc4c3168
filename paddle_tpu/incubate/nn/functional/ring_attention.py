"""Ring attention: exact long-context attention over a sequence-parallel
mesh axis.

Parity target: the reference's long-context path is flash-attention +
sequence/context parallel groups (`fleet/utils/sequence_parallel_utils.py`,
`phi/kernels/gpu/flash_attn_kernel.cu` with cu_seqlens); this module is the
TPU-native equivalent SURVEY §5.7 calls out as "where TPU should beat the
reference": each device holds S/n of the sequence, K/V blocks rotate around
the ring via `ppermute` over ICI while every hop's partial attention is
accumulated with the flash-attention online-softmax update — compute and
communication overlap, no device ever materialises the full K/V.

The hop compute runs INSIDE the Pallas flash kernels (`ops/pallas_flash.py`):
each hop is a blockwise-VMEM flash forward over this rank's queries and the
K/V chunk currently resident, emitting a normalized partial output plus its
logsumexp rows; hops merge at the jnp level with the standard two-softmax
combine on [B, S_local, H, D]-shaped carries only — the [S_q, S_k]
probability block never exists outside VMEM.  The backward re-rotates K/V
around the ring with traveling f32 dk/dv accumulators and re-derives each
hop's block gradients with the Pallas FlashAttention-2 backward kernels
against the *global* logsumexp (the FA2 identities hold chunkwise under the
global normalizer), so the memory high-water line per member is the f32
accumulators — not stacked per-hop residuals.

Layout: public API is (batch, num_heads, seq, head_dim); the Pallas kernels
run in paddle's flash layout [B, S, nh, hd] internally.

Shapes outside the kernels' support envelope (head_dim not in {64,128,256},
ragged chunk alignment, custom scale) fall back to an exact jnp online-
softmax path (`_block_update`).

Use inside `shard_map` (axis_name = the sequence/context-parallel mesh
axis), or call `ring_attention` with a mesh for the wrapped version.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ....ops import pallas_flash

__all__ = ["ring_attention_local", "ring_attention",
           "ring_attention_chunked", "ulysses_attention_local",
           "ulysses_attention"]

_NEG = -1e30

# hop kinds (lax.switch indices): this rank's queries vs the resident chunk
_SKIP, _FULL, _DIAG = 0, 1, 2


def _register():
    from ....ops.registry import register_op
    register_op("ring_attention", _ring_attention_val)
    register_op("ulysses_attention", _ulysses_attention_val)


def _check_gqa(nh: int, nkv: int) -> None:
    if nkv == 0 or nh % nkv:
        raise ValueError(
            f"GQA: num_heads ({nh}) must be a multiple of kv heads "
            f"({nkv})")


def _expand_kv_heads(q, k, v):
    """GQA support for the jnp/dense fallback paths: the Pallas kernels
    broadcast nkv < nh natively, but the fallbacks' 'bhqd,bhkd' einsums
    need matching head axes — repeat each kv head nh/nkv times (BHSD
    layout, head axis 1).  ADVICE r5 #3: without this, GQA inputs outside
    the kernel envelope crashed on einsum shapes instead of computing."""
    nh, nkv = q.shape[1], k.shape[1]
    if nkv == nh:
        return k, v
    _check_gqa(nh, nkv)
    r = nh // nkv
    return jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)


def _block_update(q, k, v, acc, m, l, q_off, k_off, causal, scale):
    """One flash-attention online-softmax step on a (S_q, S_k) block.

    jnp fallback for shapes the Pallas kernels don't cover."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off + jax.lax.iota(jnp.int32, q.shape[2])[:, None]
        kpos = k_off + jax.lax.iota(jnp.int32, k.shape[2])[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))              # (B, H, Sq)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                   # (B, H, Sq, Sk)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype),
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha[..., None] + pv
    return acc_new, m_new, l_new


# --------------------------------------------------------------------------
# Pallas-backed hop machinery (shared by the multi-device ring and the
# single-device chunked member)
# --------------------------------------------------------------------------

def _bhsd_to_bshd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


_bshd_to_bhsd = _bhsd_to_bshd  # the permutation is its own inverse


def _pallas_ok(q_bshd_shape, k_bshd_shape):
    """Whether the Pallas hop path covers these per-hop shapes (a custom
    scale never affects path selection — callers fold it into q)."""
    return pallas_flash.supported(q_bshd_shape, k_bshd_shape)


def _hop_fwd(q, kc, vc, hop_idx, interpret):
    """One ring hop, computed by the Pallas flash forward.

    q [B, Sq, nh, hd]; kc/vc [B, C, nkv, hd] (the resident chunk).
    hop_idx: _SKIP | _FULL | _DIAG (traced).  Returns the hop's normalized
    partial output (f32, [B, Sq, nh, hd]) and logsumexp rows
    (f32, [B, nh, Sq]); a skipped hop contributes lse = -1e30."""
    B, Sq, nh, hd = q.shape

    def skip(q, kc, vc):
        return (jnp.zeros((B, Sq, nh, hd), jnp.float32),
                jnp.full((B, nh, Sq), _NEG, jnp.float32))

    def mk(causal):
        def run(q, kc, vc):
            o, lse = pallas_flash.flash_attention_fwd(
                q, kc, vc, causal=causal, interpret=interpret)
            return o.astype(jnp.float32), lse[..., 0]
        return run

    return jax.lax.switch(hop_idx, (skip, mk(False), mk(True)), q, kc, vc)


def _merge(out_a, lse_a, out_b, lse_b):
    """Two-softmax combine: outs are normalized partials [B, S, nh, hd] f32,
    lses [B, nh, S].  Safe when either side is the -1e30 'empty' partial
    (its weight underflows to exactly 0; the double-empty case keeps the
    zero output)."""
    lse_m = jnp.logaddexp(lse_a, lse_b)
    tr = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]  # noqa: E731
    out = (out_a * tr(jnp.exp(lse_a - lse_m))
           + out_b * tr(jnp.exp(lse_b - lse_m)))
    return out, lse_m


def _hop_bwd(q, kc, vc, out, lse128, g, hop_idx, interpret):
    """Gradients of one hop against the GLOBAL logsumexp, via the Pallas
    FlashAttention-2 backward kernels.  All inputs BSHD; returns f32
    (dq [B,Sq,nh,hd], dk [B,C,nkv,hd], dv [B,C,nkv,hd])."""
    B, Sq, nh, hd = q.shape
    C, nkv = kc.shape[1], kc.shape[2]

    def skip(q, kc, vc, out, g):
        return (jnp.zeros((B, Sq, nh, hd), jnp.float32),
                jnp.zeros((B, C, nkv, hd), jnp.float32),
                jnp.zeros((B, C, nkv, hd), jnp.float32))

    def mk(causal):
        def run(q, kc, vc, out, g):
            dq, dk, dv = pallas_flash.flash_attention_bwd(
                q, kc, vc, out, lse128, g, causal=causal,
                interpret=interpret)
            return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                    dv.astype(jnp.float32))
        return run

    return jax.lax.switch(hop_idx, (skip, mk(False), mk(True)),
                          q, kc, vc, out, g)


def _lse128(lse):
    """[B, nh, S] -> the [B, nh, S, 128] lane-broadcast layout the backward
    kernels read (they only consume lane 0)."""
    return jnp.broadcast_to(lse[..., None], lse.shape + (128,))


def _causal_hop_idx(src, rank):
    """Which hop kind a causal rank runs against the chunk that started on
    rank `src`: earlier chunks are fully visible, own chunk is the causal
    diagonal, later chunks are masked out entirely."""
    return jnp.where(src == rank, _DIAG,
                     jnp.where(src < rank, _FULL, _SKIP)).astype(jnp.int32)


def _pvary(*xs, axis_name):
    """Mark rank-invariant scan carries as varying over the manual axis so
    carry types match the rank-dependent updates."""
    return tuple(jax.lax.pcast(x, (axis_name,), to="varying") for x in xs)


# ----------------------------------------------------- multi-device ring

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_core(q, k, v, axis_name, causal, interpret):
    out, _ = _ring_fwd(q, k, v, axis_name, causal, interpret)
    return out


def _ring_fwd(q, k, v, axis_name, causal, interpret):
    """BSHD ring forward inside shard_map: scan n hops, Pallas per hop,
    lse-merge between hops, K/V rotating via ppermute (uniform rotation so
    XLA pipelines hop i+1's permute under hop i's compute; n hops return
    the buffers home)."""
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    B, S, nh, hd = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    out0 = jnp.zeros((B, S, nh, hd), jnp.float32)
    lse0 = jnp.full((B, nh, S), _NEG, jnp.float32)
    out0, lse0 = _pvary(out0, lse0, axis_name=axis_name)

    def hop(carry, i):
        out, lse, k_cur, v_cur = carry
        src = (rank - i) % n   # chunk resident after i hops started on src
        idx = _causal_hop_idx(src, rank) if causal else jnp.int32(_FULL)
        o_h, l_h = _hop_fwd(q, k_cur, v_cur, idx, interpret)
        out, lse = _merge(out, lse, o_h, l_h)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return (out, lse, k_cur, v_cur), None

    (out, lse, _, _), _ = jax.lax.scan(hop, (out0, lse0, k, v),
                                       jnp.arange(n))
    return out.astype(q.dtype), lse


def _ring_core_fwd(q, k, v, axis_name, causal, interpret):
    out, lse = _ring_fwd(q, k, v, axis_name, causal, interpret)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(axis_name, causal, interpret, res, g):
    """Ring backward: K/V re-rotate with f32 dk/dv accumulators traveling
    alongside, so each chunk collects its gradient contributions from every
    rank and arrives home after the full rotation."""
    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    lse_b = _lse128(lse)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0, dk0, dv0 = _pvary(dq0, dk0, dv0, axis_name=axis_name)

    def hop(carry, i):
        dq, dk_cur, dv_cur, k_cur, v_cur = carry
        src = (rank - i) % n
        idx = _causal_hop_idx(src, rank) if causal else jnp.int32(_FULL)
        dq_h, dk_h, dv_h = _hop_bwd(q, k_cur, v_cur, out, lse_b, g, idx,
                                    interpret)
        dq = dq + dq_h
        dk_cur = dk_cur + dk_h
        dv_cur = dv_cur + dv_h
        k_cur, v_cur, dk_cur, dv_cur = (
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_cur, v_cur, dk_cur, dv_cur))
        return (dq, dk_cur, dv_cur, k_cur, v_cur), None

    (dq, dk, dv, _, _), _ = jax.lax.scan(
        hop, (dq0, dk0, dv0, k, v), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def _ring_local_jnp(q, k, v, axis_name, causal, scale):
    """jnp fallback (exact online softmax) for unsupported shapes.

    GQA kv heads rotate around the ring UNEXPANDED (nkv payloads) and are
    repeated per hop right before the block update — the ppermute traffic
    stays 1/(nh/nkv) of the expanded size."""
    _check_gqa(q.shape[1], k.shape[1])
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    B, H, S, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    acc0 = jnp.zeros((B, H, S, D), jnp.float32)
    m0 = jnp.full((B, H, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0, m0, l0 = _pvary(acc0, m0, l0, axis_name=axis_name)

    def hop(carry, i):
        acc, m, l, k_cur, v_cur = carry
        src = (rank - i) % n
        ke, ve = _expand_kv_heads(q, k_cur, v_cur)
        acc, m, l = _block_update(q, ke, ve, acc, m, l,
                                  q_off=rank * S, k_off=src * S,
                                  causal=causal, scale=scale)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_nxt, v_nxt), None

    (acc, m, l, _, _), _ = jax.lax.scan(
        hop, (acc0, m0, l0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def ring_attention_local(q, k, v, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None):
    """Exact attention where q/k/v are sequence-sharded over `axis_name`.

    Must run inside shard_map/pjit manual-sharding over `axis_name`.
    q, k, v: (B, H, S_local, D) — this rank's sequence slice.
    Returns (B, H, S_local, D) for this rank's queries over the FULL keys.

    Pallas flash kernels compute every hop when the shapes are in the
    kernels' envelope (head_dim 64/128/256, 8-aligned seqs); otherwise an
    exact jnp online-softmax path runs.
    """
    D = q.shape[-1]
    qs, ks, vs = (_bhsd_to_bshd(x) for x in (q, k, v))
    if _pallas_ok(qs.shape, ks.shape):
        if scale is not None and scale != D ** -0.5:
            # fold a custom scale into q so the kernels' 1/sqrt(hd) nets to
            # `scale`; AD of the pre-multiply restores the chain rule
            qs = qs * jnp.asarray(scale * D ** 0.5, qs.dtype)
        out = _ring_core(qs, ks, vs, axis_name, causal, None)
        return _bshd_to_bhsd(out)
    if scale is None:
        scale = D ** -0.5
    return _ring_local_jnp(q, k, v, axis_name, causal, scale)


def _ring_attention_val(q, k, v, mesh=None, axis_name="sp", causal=False,
                        scale=None):
    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call outputs can't declare their varying mesh axes; skip
        # the vma check (the ring math is manifestly rank-varying)
        check_vma=False)
    def run(q, k, v):
        return ring_attention_local(q, k, v, axis_name, causal, scale)

    return run(q, k, v)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None):
    """Convenience wrapper: shard q/k/v's sequence dim over `axis_name` of
    `mesh` and run `ring_attention_local` under shard_map.

    Accepts paddle Tensors or jax arrays of shape (B, H, S, D) with S
    divisible by the axis size.  Returns the same type as the input.
    Tensor inputs go through the op registry, so eager `loss.backward()`
    differentiates through the ring (AD of ppermute is the reverse permute).
    """
    from ....framework.tensor import Tensor
    from ....ops.registry import dispatch as _dispatch

    static = {"mesh": mesh, "axis_name": axis_name, "causal": causal,
              "scale": scale}
    if isinstance(q, Tensor):
        return _dispatch("ring_attention", (q, k, v), static)
    return _ring_attention_val(q, k, v, **static)




# ------------------------------------------------ single-device ring member

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunk_core(q, k, v, n_chunks, ja, causal, interpret):
    out, _ = _chunk_fwd_scan(q, k, v, n_chunks, ja, causal, interpret)
    return out


def _chunk_slices(k, v, n_chunks):
    """[B, S, nkv, hd] -> chunk-major [n, B, C, nkv, hd] scan inputs."""
    B, S, nkv, hd = k.shape
    C = S // n_chunks
    mk = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(B, n_chunks, C, nkv, hd), 1, 0)
    return mk(k), mk(v)


def _chunk_fwd_scan(q, k, v, n_chunks, ja, causal, interpret):
    """One member q-chunk (BSHD, Sq == C, global chunk index `ja`) against
    all resident K/V chunks: the exact per-device hop program of
    `_ring_fwd`, with the ring rotation replaced by a scan over the chunk
    axis."""
    k5, v5 = _chunk_slices(k, v, n_chunks)

    B, Sq, nh, hd = q.shape
    out0 = jnp.zeros((B, Sq, nh, hd), jnp.float32)
    lse0 = jnp.full((B, nh, Sq), _NEG, jnp.float32)

    def hop(carry, xs):
        out, lse = carry
        i, kc, vc = xs
        idx = _causal_hop_idx(i, ja) if causal else jnp.int32(_FULL)
        o_h, l_h = _hop_fwd(q, kc, vc, idx, interpret)
        out, lse = _merge(out, lse, o_h, l_h)
        return (out, lse), None

    (out, lse), _ = jax.lax.scan(hop, (out0, lse0),
                                 (jnp.arange(n_chunks), k5, v5))
    return out.astype(q.dtype), lse


def _chunk_core_fwd(q, k, v, n_chunks, ja, causal, interpret):
    out, lse = _chunk_fwd_scan(q, k, v, n_chunks, ja, causal, interpret)
    return out, (q, k, v, out, lse)


def _chunk_core_bwd(n_chunks, ja, causal, interpret, res, g):
    """Member backward: re-scan the chunks with the Pallas FA2 backward
    kernels against the global logsumexp; per-chunk dk/dv emit as scan
    outputs (each key chunk's grad comes only from this member's queries),
    dq accumulates in f32."""
    q, k, v, out, lse = res
    lse_b = _lse128(lse)
    k5, v5 = _chunk_slices(k, v, n_chunks)
    dq0 = jnp.zeros(q.shape, jnp.float32)

    def hop(dq, xs):
        i, kc, vc = xs
        idx = _causal_hop_idx(i, ja) if causal else jnp.int32(_FULL)
        dq_h, dk_h, dv_h = _hop_bwd(q, kc, vc, out, lse_b, g, idx,
                                    interpret)
        return dq + dq_h, (dk_h, dv_h)

    dq, (dk5, dv5) = jax.lax.scan(hop, dq0,
                                  (jnp.arange(n_chunks), k5, v5))
    unchunk = lambda x5: jnp.moveaxis(x5, 0, 1).reshape(k.shape)  # noqa: E731
    return (dq.astype(q.dtype), unchunk(dk5).astype(k.dtype),
            unchunk(dv5).astype(v.dtype))


_chunk_core.defvjp(_chunk_core_fwd, _chunk_core_bwd)


def _chunked_jnp(q, k, v, n_chunks, causal, scale, q_off):
    """jnp fallback: the original exact online-softmax member program."""
    k, v = _expand_kv_heads(q, k, v)
    B, H, Sq, D = q.shape
    C = k.shape[2] // n_chunks
    kc = k.reshape(B, H, n_chunks, C, D)
    vc = v.reshape(B, H, n_chunks, C, D)

    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)

    def hop(carry, i):
        acc, m, l = carry
        acc, m, l = _block_update(
            q, kc[:, :, i], vc[:, :, i], acc, m, l,
            q_off=q_off, k_off=i * C, causal=causal, scale=scale)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(hop, (acc0, m0, l0),
                                  jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def ring_attention_chunked(q, k, v, n_chunks: int, causal: bool = False,
                           scale: Optional[float] = None, q_off: int = 0):
    """Single-device form of one ring member: the SAME hop program as the
    multi-device `ring_attention_local` (Pallas flash per K/V chunk, lse
    merge between hops), with the ring rotation replaced by a scan over the
    resident chunks.  q is this member's query slice (q_off = its absolute
    sequence offset, for the causal mask); k/v carry the FULL context.
    Scores only ever exist as VMEM-resident flash blocks — the memory shape
    that lets an n-device ring hold n× the context.

    q: (B, H, S_q, D); k, v: (B, H, S_k, D), S_k divisible by n_chunks.
    Exact (online softmax), matching the multi-device `ring_attention`
    hop-for-hop.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    C = Sk // n_chunks
    qs, ks, vs = (_bhsd_to_bshd(x) for x in (q, k, v))
    aligned = (C > 0 and Sq % C == 0 and q_off % C == 0
               and (not causal or q_off + Sq <= Sk))
    if aligned and _pallas_ok((B, C, H, D), (B, C, k.shape[1], D)):
        if scale is not None and scale != D ** -0.5:
            qs = qs * jnp.asarray(scale * D ** 0.5, qs.dtype)
        outs = []
        for a in range(Sq // C):   # static member q-chunks
            ja = q_off // C + a
            outs.append(_chunk_core(qs[:, a * C:(a + 1) * C], ks, vs,
                                    n_chunks, ja, causal, None))
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return _bshd_to_bhsd(out)
    if scale is None:
        scale = D ** -0.5
    return _chunked_jnp(q, k, v, n_chunks, causal, scale, q_off)


# ------------------------------------------------ Ulysses (head all-to-all)

def _dense_attention(q, k, v, causal, scale):
    """Dense BHSD attention for shapes outside the Pallas envelope."""
    k, v = _expand_kv_heads(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        S = q.shape[2]
        qpos = jax.lax.iota(jnp.int32, S)[:, None]
        kpos = jax.lax.iota(jnp.int32, S)[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype)).astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name: str, causal: bool = False,
                            scale: Optional[float] = None):
    """Ulysses / segment-parallel attention (the reference's `sep` axis:
    `fleet/base/topology.py` sep dim, `fleet/meta_parallel/
    segment_parallel.py`): q/k/v arrive sequence-sharded over `axis_name`;
    an all-to-all regroups them to head-sharded over the FULL sequence,
    plain (flash) attention runs locally on H/n heads, and the reverse
    all-to-all restores sequence sharding.  Two all-to-alls instead of a
    ring of ppermutes — the cheap option when num_heads % axis_size == 0.

    Must run inside shard_map over `axis_name`.
    q, k, v: (B, H, S_local, D); H divisible by the axis size.
    Returns (B, H, S_local, D).  Differentiable (all_to_all is its own
    transpose).
    """
    n = jax.lax.axis_size(axis_name)
    B, H, Sl, D = q.shape
    if H % n or k.shape[1] % n:
        raise ValueError(
            f"ulysses_attention: num_heads ({H}) and kv heads "
            f"({k.shape[1]}) must be divisible by the '{axis_name}' axis "
            f"size ({n}); use ring_attention instead")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=1, concat_axis=2, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)     # [B, H/n, S, D]
    qs = _bhsd_to_bshd(qg)
    if _pallas_ok(qs.shape, (B, kg.shape[2], kg.shape[1], D)):
        if scale is not None and scale != D ** -0.5:
            qs = qs * jnp.asarray(scale * D ** 0.5, qs.dtype)
        out = _bshd_to_bhsd(pallas_flash.flash_attention(
            qs, _bhsd_to_bshd(kg), _bhsd_to_bshd(vg), causal=causal))
    else:
        out = _dense_attention(qg, kg, vg, causal,
                               D ** -0.5 if scale is None else scale)
    # reverse regroup: scatter seq, gather heads
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def _ulysses_attention_val(q, k, v, mesh=None, axis_name="sep",
                           causal=False, scale=None):
    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    def run(q, k, v):
        return ulysses_attention_local(q, k, v, axis_name, causal, scale)

    return run(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sep",
                      causal: bool = False, scale: Optional[float] = None):
    """Convenience wrapper: shard q/k/v's sequence dim over `axis_name` of
    `mesh` and run `ulysses_attention_local` under shard_map.  Same
    contract as `ring_attention` (Tensor inputs dispatch through the op
    registry for eager autograd)."""
    from ....framework.tensor import Tensor
    from ....ops.registry import dispatch as _dispatch

    static = {"mesh": mesh, "axis_name": axis_name, "causal": causal,
              "scale": scale}
    if isinstance(q, Tensor):
        return _dispatch("ulysses_attention", (q, k, v), static)
    return _ulysses_attention_val(q, k, v, **static)


_register()
