"""Dense latent (MLA) attention over a paged latent pool.

A layer of a `glm4_moe_lite` / DeepSeek-V3 style model keeps ONE row a
token, `c_kv | k_rope` (512 + 64 values in 640 lanes), shared by every
head, and every query attends EVERY cached row of its sequence.  In the
absorbed form that is multi-query attention whose key is the row and
whose value is the row's first `d_latent` values: `q_cat = [q_nope
W_kvb^K | q_rope]`, `score = q_cat . row`, `o_lat = sum p row[:d_latent]`
(the caller applies `W_kvb^V`).

`paged_latent_attention` is one Pallas kernel for the few-query programs
(a decode step, `s` = 1; a self-drafted verify, `s` = 2): the pool stays
in HBM, the block table and lengths sit in SMEM, and for each sequence a
loop of `ceil(blocks / group)` trips copies `group` blocks of the pool
through the table into one of two VMEM buffers (the next copy in flight
while this one is multiplied).  The `s * heads` query rows of a sequence
are ONE matmul operand: `[s * nh, 640] x [keys, 640]^T` for the scores,
`[s * nh, keys] x [keys, 512]` for the values, both against the one copy
of the rows; the online-softmax state lives in VMEM scratch.  An idle
slot (`lens` 0) and a table column past a sequence's length cost no copy
and no multiply (`ops/pallas_paged.paged_decode`'s walk).  The kernel
copies whole 640-lane rows: 1,280 B a token where the mathematics needs
the 576 values' 1,152.

A prompt chunk (hundreds of queries of one sequence) has two forms.
`paged_latent_chunk` is the same walk as a Pallas kernel for a TILE of
queries: `tile` queries x every head are one matmul operand (32 x 64 =
2,048 rows at GLM-5's widths, heads leading), only the blocks up to the
tile's last query are copied, and an optional mask `[tile, keys]`,
spread over the heads inside the kernel, admits a row to a query: under
a sparse selection's mask it is `ops/sparse_mla`'s chunk (71-77% of the
v5e's peak at 16k-32k rows; PERF.md section 6, PR 38), and with
`mask=None` it is causal attention.  `latent_chunk_attention` is the
plain XLA form `glm4_moe_lite`'s chunk still takes: the sequence's
blocks gathered whole through the table (a gather of blocks, never of
part-rows; PERF.md section 6, PR 28), then masked dense attention a tile
of queries at a time over every column of the table.
`paged_latent_attention_reference` is the jnp twin all are tested
against.

`first` masks the pool's rows below it: a multi-token-prediction module
keeps its row for position i in slot i + 1 (so that a block's content
depends only on tokens up to the block's end) and attends from slot 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["paged_latent_attention", "paged_latent_attention_reference",
           "latent_chunk_attention", "paged_latent_chunk",
           "KERNEL_MAX_QUERIES"]

_NEG_INF = -1e30
_GROUP_KEYS = 512        # keys a multiply, at most
_GROUP_BLOCKS = 8        # copies in flight a buffer, at most
_Q_TILE = 32             # queries of a chunk attended at a time (XLA form)
KERNEL_MAX_QUERIES = 4   # the kernel serves s <= this; a chunk goes to XLA
_CHUNK_TILE = 32         # queries a tile of the chunk kernel: x 64 heads,
#                          2,048 rows a matmul operand
_CHUNK_VMEM = 64 * 2 ** 20   # of the v5e's 128 MiB; Mosaic's default is 16


def _kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, kbuf, sem, walk,
            nxt, m_scr, l_scr, acc_scr, *, scale, bs, max_blocks, group, s,
            nh, d_latent, first):
    """ONE invocation walks every sequence's own blocks and nothing else
    (the structure of `pallas_paged._decode_kernel`, reading only).
    `q_ref` `[B, R, W]`: row `j * nh + h` is head h of query j, at
    position `lens - s + j`; rows past `s * nh` pad the operand."""
    B, R, _ = q_ref.shape
    keys = group * bs
    # rows of a buffer that no copy has filled yet are masked out of the
    # softmax, but 0 x NaN in the value matmul would still poison it
    kbuf[...] = jnp.zeros_like(kbuf)

    def note(i, later):
        b = B - 1 - i
        n = jnp.minimum((lens_ref[b] + bs - 1) // bs, max_blocks)
        walk[b], nxt[b] = n, later
        return jnp.where(n > 0, b, later)

    head = jax.lax.fori_loop(0, B, note, B)

    def copies(b, g, slot, do):
        def block(i, _):
            do(pltpu.make_async_copy(
                pool_hbm.at[tables_ref[b, g * group + i]],
                kbuf.at[slot, pl.ds(pl.multiple_of(i * bs, bs), bs)],
                sem.at[slot]))

        jax.lax.fori_loop(0, jnp.minimum(group, walk[b] - g * group), block,
                          None)

    def start(b, g, slot):
        copies(b, g, slot, lambda c: c.start())

    @pl.when(head < B)
    def _():
        start(head, 0, 0)

    def fold(b, g, slot):
        k = kbuf[slot]                                       # [keys, W]
        sc = jax.lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [R, keys]
        kpos = g * keys + jax.lax.broadcasted_iota(jnp.int32, (R, keys), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (R, keys), 0)
        # query j = row // nh sees keys first .. lens - s + j
        limit = lens_ref[b] - s + 1
        for j in range(1, s):
            limit = limit + (row >= j * nh).astype(jnp.int32)
        ok = jnp.logical_and(kpos < limit, kpos >= first)
        sc = jnp.where(ok, sc, _NEG_INF)
        m_prev = m_scr[:, 0]                                 # [R]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1))
        p = jnp.where(ok, jnp.exp(sc - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(k.dtype), kbuf[slot, :, pl.ds(0, d_latent)],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [R, d_latent]
        acc_scr[:] = acc_scr[:] * alpha[:, None] + pv
        l_scr[:] = l_scr[:] * alpha[:, None] + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    def sequence(b, done):
        n_groups = (walk[b] + group - 1) // group

        @pl.when(walk[b] == 0)
        def _():
            o_ref[b] = jnp.zeros((R, d_latent), o_ref.dtype)

        @pl.when(walk[b] > 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

            def walk_group(g, _):
                slot = (done + g) % 2
                last = g == n_groups - 1

                @pl.when(jnp.logical_or(jnp.logical_not(last), nxt[b] < B))
                def _():
                    start(jnp.where(last, jnp.minimum(nxt[b], B - 1), b),
                          jnp.where(last, 0, g + 1), 1 - slot)

                copies(b, g, slot, lambda c: c.wait())
                fold(b, g, slot)

            jax.lax.fori_loop(0, n_groups, walk_group, None)
            l = l_scr[:, 0]
            o_ref[b] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)[:, None]
                        ).astype(o_ref.dtype)

        return done + n_groups

    jax.lax.fori_loop(0, B, sequence, 0)


# jitted on its own so that a program of L layers lowers the kernel once
@functools.partial(jax.jit, static_argnames=(
    "scale", "d_latent", "first", "interpret"))
def _latent_pallas(q_cat, pool, tables, lens, *, scale, d_latent, first,
                   interpret):
    B, s, nh, width = q_cat.shape
    _, bs, lanes = pool.shape
    max_blocks = tables.shape[1]
    group = max(1, min(_GROUP_KEYS // bs, _GROUP_BLOCKS, max_blocks))
    rows = -(-(s * nh) // 16) * 16          # a whole bf16 sublane tile
    q = jnp.pad(q_cat.astype(pool.dtype).reshape(B, s * nh, width),
                ((0, 0), (0, rows - s * nh), (0, lanes - width)))
    whole = lambda shape: pl.BlockSpec(                      # noqa: E731
        shape, lambda i, tables, lens: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole((B, rows, lanes)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole((B, rows, d_latent)),
        scratch_shapes=[pltpu.VMEM((2, group * bs, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((B,), jnp.int32),
                        pltpu.SMEM((B,), jnp.int32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, d_latent), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, max_blocks=max_blocks,
                          group=group, s=s, nh=nh, d_latent=d_latent,
                          first=first),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, d_latent), jnp.float32),
        interpret=interpret,
        name="paged_latent_attention",
    )(tables, lens, q, pool)
    return out[:, :s * nh].reshape(B, s, nh, d_latent)


def paged_latent_attention(q_cat, pool, tables, lens, *, scale: float,
                           d_latent: int, first: int = 0, interpret=None):
    """q_cat `[B, s, nh, width]` (`width` <= the pool's lanes; `s` <=
    `KERNEL_MAX_QUERIES`), pool `[blocks + 1, bs, lanes]` whose pad lanes
    are zero, tables `[B, nb]`, lens `[B]`: the rows of each sequence,
    the queries' own (already written) included, 0 for an idle slot.
    Query j of sequence b stands at position `lens[b] - s + j` and
    attends rows `first .. lens[b] - s + j`.  Returns `sum p row[:d_latent]`
    `[B, s, nh, d_latent]` float32; an idle slot's rows are zero."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("paged_latent_attention", interpret)
    return _latent_pallas(q_cat, pool, tables, lens.astype(jnp.int32),
                          scale=float(scale), d_latent=int(d_latent),
                          first=int(first), interpret=bool(interpret))


def _chunk_kernel(tables_ref, lens_ref, q_ref, *rest, scale, bs, max_blocks,
                  group, s, tile, nh, d_latent, first, masked):
    """One tile of `tile` queries of sequence b = `program_id(0)`, every
    head: `q_ref` `[nh * tile, W]`, row `h * tile + j` head h of the
    tile's query j (heads lead, so that the `[tile, keys]` mask of a
    group spreads over the heads as a broadcast along the leading axis of
    the score block seen as `[nh, tile, keys]`).  The walk is
    `_kernel`'s, for one sequence: the blocks up to the tile's last
    query's own, `group` at a time into one of two buffers.  `o_ref` is
    the accumulator."""
    if masked:
        mask_ref, pool_hbm, o_ref, kbuf, sem, m_scr, l_scr = rest
    else:
        pool_hbm, o_ref, kbuf, sem, m_scr, l_scr = rest
    b, i = pl.program_id(0), pl.program_id(1)
    R, keys = nh * tile, group * bs
    length = lens_ref[b]
    q0 = length - s + i * tile              # the tile's first query's position
    seen = jnp.clip(q0 + tile, 0, length)   # rows its last query sees
    n = jnp.minimum((seen + bs - 1) // bs, max_blocks)
    n_groups = (n + group - 1) // group

    @pl.when(jnp.logical_and(b == 0, i == 0))
    def _():
        # rows no copy has filled are masked out of the softmax, but
        # 0 x NaN in the value matmul would still poison it
        kbuf[...] = jnp.zeros_like(kbuf)

    def copies(g, slot, do):
        def block(c, _):
            do(pltpu.make_async_copy(
                pool_hbm.at[tables_ref[b, g * group + c]],
                kbuf.at[slot, pl.ds(pl.multiple_of(c * bs, bs), bs)],
                sem.at[slot]))

        jax.lax.fori_loop(0, jnp.minimum(group, n - g * group), block, None)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_groups > 0)
    def _():
        copies(0, 0, lambda c: c.start())

    def fold(g, slot):
        k = kbuf[slot]                                       # [keys, W]
        sc = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [R, keys]
        kpos = g * keys + jax.lax.broadcasted_iota(
            jnp.int32, (tile, keys), 1)
        if masked:
            ok = mask_ref[g] != 0                            # [tile, keys]
        else:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (tile, keys), 0)
            ok = jnp.logical_and(kpos <= qpos, kpos < length)
        if first:
            ok = jnp.logical_and(ok, kpos >= first)
        sc = jnp.where(ok[None], sc.reshape(nh, tile, keys),
                       _NEG_INF).reshape(R, keys)
        m_prev = m_scr[:, :1]                                # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # a row that has seen no key yet keeps exp(masked - 0) = 0
        m_use = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(sc - m_use)
        alpha = jnp.exp(m_prev - m_use)
        pv = jax.lax.dot_general(
            p.astype(k.dtype), kbuf[slot, :, pl.ds(0, d_latent)],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [R, d_latent]
        o_ref[...] = o_ref[...] * alpha + pv
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def walk_group(g, _):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            copies(g + 1, 1 - slot, lambda c: c.start())

        copies(g, slot, lambda c: c.wait())
        fold(g, slot)

    jax.lax.fori_loop(0, n_groups, walk_group, None)
    l = l_scr[:, :1]
    o_ref[...] = o_ref[...] / jnp.where(l == 0.0, 1.0, l)


# jitted on its own so that a program of L layers lowers the kernel once
@functools.partial(jax.jit, static_argnames=(
    "scale", "d_latent", "first", "interpret"))
def _chunk_pallas(q_cat, pool, tables, lens, mask, *, scale, d_latent, first,
                  interpret):
    B, s, nh, width = q_cat.shape
    _, bs, lanes = pool.shape
    max_blocks = tables.shape[1]
    group = max(1, min(_GROUP_KEYS // bs, _GROUP_BLOCKS, max_blocks))
    keys = group * bs
    n_groups = -(-max_blocks // group)
    # whole bf16 sublane tiles of queries a head
    tile = min(_CHUNK_TILE, -(-s // 16) * 16)
    n_tiles = -(-s // tile)
    pad_s = n_tiles * tile - s

    def tiles(a):        # [B, s, ...] -> [B, n_tiles, tile, ...]
        a = jnp.pad(a, ((0, 0), (0, pad_s)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((B, n_tiles, tile) + a.shape[2:])

    q = jnp.pad(q_cat.astype(pool.dtype),
                ((0, 0),) * 3 + ((0, lanes - width),))
    q = jnp.swapaxes(tiles(q), 2, 3).reshape(B, n_tiles, nh * tile, lanes)
    per_tile = lambda *shape: pl.BlockSpec(                  # noqa: E731
        (None, None) + shape,
        lambda b, i, tables, lens: (b, i) + (0,) * len(shape))
    in_specs, args = [per_tile(nh * tile, lanes)], [q]
    if mask is not None:
        m = jnp.pad(tiles(mask.astype(jnp.int8)), ((0, 0),) * 3 + (
            (0, n_groups * keys - mask.shape[-1]),))
        args.append(jnp.swapaxes(
            m.reshape(B, n_tiles, tile, n_groups, keys), 2, 3))
        in_specs.append(per_tile(n_groups, tile, keys))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_tiles),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_tile(nh * tile, d_latent),
        scratch_shapes=[pltpu.VMEM((2, keys, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((nh * tile, 128), jnp.float32),
                        pltpu.VMEM((nh * tile, 128), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, bs=bs,
                          max_blocks=max_blocks, group=group, s=s, tile=tile,
                          nh=nh, d_latent=d_latent, first=first,
                          masked=mask is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, nh * tile, d_latent),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
        interpret=interpret,
        name="paged_latent_chunk",
    )(tables, lens, *args, pool)
    out = jnp.swapaxes(out.reshape(B, n_tiles, nh, tile, d_latent), 2, 3)
    return out.reshape(B, n_tiles * tile, nh, d_latent)[:, :s]


def paged_latent_chunk(q_cat, pool, tables, lens, mask=None, *, scale: float,
                       d_latent: int, first: int = 0, interpret=None):
    """`paged_latent_attention`'s contract for MANY queries of each
    sequence (a prompt chunk; any `s`), optionally under a selection:
    `mask` `[B, s, n]` (bool; `n` up to the table's rows) admits row r to
    query j where `mask[b, j, r]`, and must be causal itself (a sparse
    selection's `select_mask`); `mask=None` is plain causal attention,
    query j at position `lens[b] - s + j` over rows `first ..` its own.
    Only the blocks a sequence holds up to a tile's last query are read,
    whole, through the table; no row is gathered alone."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("paged_latent_chunk", interpret)
    return _chunk_pallas(q_cat, pool, tables, lens.astype(jnp.int32), mask,
                         scale=float(scale), d_latent=int(d_latent),
                         first=int(first), interpret=bool(interpret))


def _attend_rows(q, k, limit, scale, d_latent, first):
    """q `[B, t, nh, lanes]` over the linearized rows k `[B, n, lanes]`,
    query (b, t) seeing rows `first .. limit[b, t] - 1`; float32 softmax."""
    sc = jnp.einsum("bthw,bkw->bthk", q, k,
                    preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(k.shape[1], dtype=limit.dtype)
    ok = ((kpos < limit[..., None]) & (kpos >= first))[:, :, None, :]
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
    # a query with no row to see (an idle slot, a pad row) gets zeros
    p = jnp.where(ok, p, 0.0)
    return jnp.einsum("bthk,bkc->bthc", p.astype(k.dtype),
                      k[..., :d_latent], preferred_element_type=jnp.float32)


def _linearized(q_cat, pool, tables, lens):
    B, s = q_cat.shape[0], q_cat.shape[1]
    lanes = pool.shape[2]
    k = jnp.take(pool, tables, axis=0).reshape(B, -1, lanes)
    q = jnp.pad(q_cat.astype(pool.dtype),
                ((0, 0),) * 3 + ((0, lanes - q_cat.shape[-1]),))
    limit = (lens.astype(jnp.int32) - s + 1)[:, None] \
        + jnp.arange(s, dtype=jnp.int32)
    return q, k, jnp.where(lens[:, None] > 0, limit, 0)


def paged_latent_attention_reference(q_cat, pool, tables, lens, *,
                                     scale: float, d_latent: int,
                                     first: int = 0):
    """The plain jnp twin of `paged_latent_attention`, any `s`."""
    q, k, limit = _linearized(q_cat, pool, tables, lens)
    return _attend_rows(q, k, limit, scale, d_latent, first)


def latent_chunk_attention(q_cat, pool, tables, lens, *, scale: float,
                           d_latent: int, first: int = 0):
    """The same contract for a chunk of many queries, in plain XLA: the
    blocks of each sequence gathered whole, `_Q_TILE` queries at a time
    (the float32 scores of 512 queries x 20 heads over 18k rows would be
    750 MB at once)."""
    q, k, limit = _linearized(q_cat, pool, tables, lens)
    B, s = limit.shape
    if s <= _Q_TILE:
        return _attend_rows(q, k, limit, scale, d_latent, first)
    pad = -s % _Q_TILE
    n = (s + pad) // _Q_TILE

    def cut(a):      # [B, s, ...] -> [n, B, tile, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, _Q_TILE) + a.shape[2:]), 1, 0)

    o = jax.lax.map(
        lambda a: _attend_rows(a[0], k, a[1], scale, d_latent, first),
        (cut(q), cut(limit)))
    o = jnp.moveaxis(o, 0, 1)
    return o.reshape((B, n * _Q_TILE) + o.shape[3:])[:, :s]
