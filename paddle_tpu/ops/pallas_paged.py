"""Paged-KV decode attention as a Pallas TPU kernel.

Role of the reference's `block_multihead_attention` decode path
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu` +
`fluid/operators/fused/fused_multi_transformer_op.cu.h` cache-KV branch):
the KV cache lives in fixed-size physical blocks; each sequence owns a
block table mapping its logical positions to physical blocks, so cache
memory is allocated in pages instead of max-length rectangles.

TPU design: one decode step attends a single query token per sequence over
that sequence's block list.  `paged_decode` is one kernel invocation that
walks only the blocks a running sequence holds: the pools stay in HBM, the
SCALAR-PREFETCHED block table and lengths sit in SMEM, and for each
sequence a loop of `ceil(blocks / group)` trips copies `group` blocks of
all heads at a time through the table into one of two VMEM buffers (the
next copy in flight while this one is multiplied), carrying the
online-softmax state (m, l, acc) in VMEM scratch.  The gather happens in
the DMA engine's addressing, not as a data-plane gather op; an idle slot
and a table column past a sequence's length cost no grid step, no copy
and no write-back.  The step's own k/v row is stored by the same kernel
(`paged_decode_step`).  Pools whose heads are narrower than 128 lanes
reach the compiled kernel through BlockSpecs on a (B, max_blocks) grid
instead (`_copies_by_hand` says why); the chunk kernels below run
BlockSpec grids of their own.

Non-TPU backends run the same kernels under the Pallas interpreter
(`pallas_common.interpret_default`); `paged_attention_reference` and
`paged_chunk_attention_reference` are the jnp oracles tests and
`chip_smoke.py` compare against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["paged_attention", "paged_attention_reference", "BlockKVCache",
           "paged_decode_step", "paged_write_prefill",
           "paged_write_chunk", "paged_copy_block",
           "paged_chunk_attention", "paged_chunk_attention_reference",
           "paged_verify_attention"]

_NEG_INF = -1e30


def _fold(q, k, v, first_pos, seq_len, scale, m_scr, l_scr, acc_scr):
    """One online-softmax step of a decode query (q [nh, hd], a value)
    over keys/values [nh, n, hd] at positions first_pos.., masked from
    seq_len on, into the state m, l ([nh, 128], lane-broadcast) and acc
    ([nh, hd]) in VMEM scratch."""
    nh, n, _ = k.shape
    # batched matvec as [nh, 1, hd] x [nh, n, hd]: Mosaic's dot lowering
    # requires a non-empty lhs non-contracting dim set.  The unit dim is
    # inserted while the value is float32 and the cast to the pool dtype
    # follows: Mosaic has no [nh, hd] -> [nh, 1, hd] shape cast for
    # packed (bf16) vectors.
    q = q.astype(jnp.float32)[:, None, :].astype(k.dtype)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, 0, :] * scale  # [nh, n]
    pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, (nh, n), 1)
    s = jnp.where(pos < seq_len, s, _NEG_INF)
    m_prev = m_scr[:, 0]                                  # [nh]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])                       # [nh, n]
    alpha = jnp.exp(m_prev - m_new)
    pv = jax.lax.dot_general(
        p[:, None, :].astype(v.dtype), v,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, 0, :]      # [nh, hd]
    acc_scr[:] = acc_scr[:] * alpha[:, None] + pv
    l_scr[:] = l_scr[:] * alpha[:, None] + jnp.broadcast_to(
        jnp.sum(p, axis=1)[:, None], l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)


def _reset(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _result(l_scr, acc_scr, dtype):
    l = l_scr[:, 0]                                       # [nh]
    return (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)[:, None]).astype(dtype)


def _with_row(new, old, row):
    """`old` ([nh, n, hd]) with its row `row` replaced by `new` ([nh, hd]);
    through float32 for the packed-shape-cast reason of `_fold`."""
    hit = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1) == row
    return jnp.where(hit, new.astype(jnp.float32)[:, None, :],
                     old.astype(jnp.float32)).astype(old.dtype)


def _decode_kernel(tables_ref, lens_ref, q_ref, *refs, scale, bs, max_blocks,
                   group, tile, write):
    """ONE invocation walks every sequence's own blocks and nothing else.

    The pools stay in HBM (`pl.ANY`); the table and lengths are in SMEM.
    A first scalar pass notes how many table columns each slot walks (0
    for an idle one, see `_walked`) and which slot is the next that walks
    any.  Then, sequence by sequence, a loop of `ceil(blocks / group)`
    trips (dynamic) copies `group` blocks of all heads at a time through
    the table into one of two VMEM buffers - the copy of the next group,
    or of the next busy sequence's first, in flight while this one is
    multiplied - and folds them into the online-softmax state (m, l, acc
    in VMEM scratch).  A table column past a sequence's blocks is neither
    copied nor waited for: its rows of the buffer keep older (finite)
    data and are masked by position; `vbuf` is zeroed once so that the
    very first groups cannot meet uninitialised memory there.  An idle
    slot costs its zero output row.

    With `write` (`paged_decode_step`) the step's own k/v row rides in:
    it is merged into the sequence's last block as that block sits in
    VMEM, attended from there, and the aligned `tile` rows that hold it
    go back to the pool (an output aliased onto the input) while the
    group is multiplied - one tile of write-back a busy sequence."""
    if write:
        (knew_ref, vnew_ref, k_hbm, v_hbm, o_ref, ko_hbm, vo_hbm,
         kbuf, vbuf, sem, wsem, walk, nxt, m_scr, l_scr, acc_scr) = refs
    else:
        (k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, walk, nxt, m_scr, l_scr, acc_scr) = refs
    B, nh, hd = q_ref.shape
    keys = group * bs
    vbuf[...] = jnp.zeros_like(vbuf)

    def note(i, later):
        b = B - 1 - i
        n = _walked(tables_ref[b, 0], lens_ref[b], bs, max_blocks, write)
        walk[b], nxt[b] = n, later
        return jnp.where(n > 0, b, later)

    first = jax.lax.fori_loop(0, B, note, B)

    def copies(b, g, slot, do):
        """`do` (start or wait) on the copies of group g of sequence b:
        one a block the sequence holds there, K and V."""
        def block(i, _):
            blk = tables_ref[b, g * group + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                do(pltpu.make_async_copy(
                    hbm.at[:, blk], buf.at[slot, :, rows], sem.at[slot]))

        jax.lax.fori_loop(0, jnp.minimum(group, walk[b] - g * group), block,
                          None)

    def start(b, g, slot):
        copies(b, g, slot, lambda c: c.start())

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    def stored(b, g, slot):
        """Where the new row goes (position lens[b] - 1: lens counts it):
        its row in its `tile` rows of the buffer, those rows, and the two
        copies that take them home to the pool."""
        col = walk[b] - 1
        row = (lens_ref[b] - 1) % bs
        top = row // tile * tile
        rows = pl.ds(pl.multiple_of((col - g * group) * bs + top, tile), tile)
        home = [pltpu.make_async_copy(
            buf.at[slot, :, rows],
            pool.at[:, tables_ref[b, col], pl.ds(top, tile)], wsem)
            for buf, pool in ((kbuf, ko_hbm), (vbuf, vo_hbm))]
        return row - top, rows, home

    def sequence(b, done):
        """`done`: the groups walked before b (its parity is the buffer)."""
        n_groups = (walk[b] + group - 1) // group

        @pl.when(walk[b] == 0)
        def _():
            o_ref[b] = jnp.zeros((nh, hd), o_ref.dtype)

        @pl.when(walk[b] > 0)
        def _():
            _reset(m_scr, l_scr, acc_scr)

            def walk_group(g, _):
                slot = (done + g) % 2
                last = g == n_groups - 1

                # the copy to follow this one: the sequence's next group,
                # or the first group of the next sequence that has any
                @pl.when(jnp.logical_or(jnp.logical_not(last), nxt[b] < B))
                def _():
                    start(jnp.where(last, jnp.minimum(nxt[b], B - 1), b),
                          jnp.where(last, 0, g + 1), 1 - slot)

                copies(b, g, slot, lambda c: c.wait())
                if write:
                    @pl.when(last)
                    def _():
                        row, rows, home = stored(b, g, slot)
                        for new_ref, buf in ((knew_ref, kbuf),
                                             (vnew_ref, vbuf)):
                            buf[slot, :, rows, :] = _with_row(
                                new_ref[b], buf[slot, :, rows, :], row)
                        for c in home:
                            c.start()

                _fold(q_ref[b], kbuf[slot], vbuf[slot], g * keys,
                      lens_ref[b], scale, m_scr, l_scr, acc_scr)
                if write:
                    @pl.when(last)
                    def _():
                        for c in stored(b, g, slot)[2]:
                            c.wait()

            jax.lax.fori_loop(0, n_groups, walk_group, None)
            o_ref[b] = _result(l_scr, acc_scr, o_ref.dtype)

        return done + n_groups

    jax.lax.fori_loop(0, B, sequence, 0)


def _write_col(seq_len, bs, max_blocks):
    """Table column of the block that takes position seq_len - 1 (a
    position past the table clamps to the last column, as a gather of the
    table would)."""
    return jnp.minimum((seq_len - 1) // bs, max_blocks - 1)


def _walked(first_blk, seq_len, bs, max_blocks, write):
    """Table columns the kernel walks for one slot, from what it can read:
    the columns that hold positions 0..seq_len-1, and none for an idle
    slot.  Reading only, a slot of length 0 is idle.  Writing, `seq_len`
    counts the row to store, and a slot is idle when that row would be a
    sequence's first (seq_len 1) into the reserved pad block 0: the
    serving engine's free slot, length 0 over a zero table row.  A live
    sequence of length 0 has a real first block and is walked."""
    if not write:
        return jnp.minimum((seq_len + bs - 1) // bs, max_blocks)
    idle = jnp.logical_and(seq_len <= 1, first_blk == 0)
    return jnp.where(idle, 0, _write_col(seq_len, bs, max_blocks) + 1)


def _decode_grid_kernel(tables_ref, lens_ref, q_ref, *refs, scale, bs,
                        max_blocks, write):
    """The same step where Mosaic will not let a kernel copy the blocks
    itself (`_copies_by_hand`): grid (B, max_blocks), Mosaic's own
    pipeline streams block `tables[b, col]` of all heads a step, the state
    lives in scratch across a sequence's columns, and a column past the
    sequence's blocks is a skipped step.  The writing variant's aliased
    output block is the one that takes the new row; an idle slot's is the
    pad block, handed back as it came."""
    if write:
        (knew_ref, vnew_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b, col = pl.program_id(0), pl.program_id(1)
    n = _walked(tables_ref[b, 0], lens_ref[b], bs, max_blocks, write)

    def fold(k, v):
        _fold(q_ref[...], k, v, col * bs, lens_ref[b], scale,
              m_scr, l_scr, acc_scr)

    @pl.when(col == 0)
    def _():
        _reset(m_scr, l_scr, acc_scr)

    @pl.when(col < n - int(write))
    def _():
        fold(k_ref[...], v_ref[...])

    if write:
        @pl.when(col == n - 1)
        def _():
            row = (lens_ref[b] - 1) % bs
            k = _with_row(knew_ref[...], k_ref[...], row)
            v = _with_row(vnew_ref[...], v_ref[...], row)
            ko_ref[...] = k
            vo_ref[...] = v
            fold(k, v)

        @pl.when(jnp.logical_and(n == 0, col == 0))
        def _():
            ko_ref[...] = k_ref[...]
            vo_ref[...] = v_ref[...]

    @pl.when(col == max_blocks - 1)
    def _():
        o_ref[...] = _result(l_scr, acc_scr, o_ref.dtype)


def _copies_by_hand(hd, interpret) -> bool:
    """Whether `paged_decode` may copy a pool's blocks itself.  Mosaic
    (libtpu 0.0.34) pads an operand's minor dimension to 128 lanes and
    then refuses every slice of it ("Slice shape along dimension 3 must
    be aligned to tiling (128), but is 64"), a whole block of all heads
    included: a pool of narrower heads reaches the compiled kernel
    through BlockSpecs (`_decode_grid_kernel`) until its rows are 128
    lanes wide (PERF.md section 7).  The interpreter has no such rule."""
    return bool(interpret) or hd % 128 == 0


_GROUP_VMEM = 4 << 20        # bytes of the four [nh, group * bs, hd] buffers
_GROUP_KEYS = 512            # ... no more keys a multiply than this
_GROUP_BLOCKS = 8            # ... and no more copies in flight a buffer


def _copy_group(nh, bs, hd, dtype, max_blocks):
    """Blocks a copy group holds: as many as keep the two K and two V
    buffers (lanes padded to 128, rows to the dtype's sublane tile)
    within `_GROUP_VMEM`, a group within `_GROUP_KEYS` keys and
    `_GROUP_BLOCKS` copies, at least one and no more than the table is
    wide.  16 heads of 128 in bf16 blocks of 64: 4 (2 MB of K and V a
    copy; 2, 4 and 8 read the same on the v5e, PERF.md section 6)."""
    item = jnp.dtype(dtype).itemsize
    rows = -(-bs // (32 // item)) * (32 // item)
    block = nh * rows * (-(-hd // 128) * 128) * item
    return max(1, min(_GROUP_VMEM // (4 * block), _GROUP_KEYS // bs,
                      _GROUP_BLOCKS, max_blocks))


def _decode_call(q, k_cache, v_cache, block_tables, seq_lens, interpret,
                 new_rows=None):
    """`paged_decode` as a pallas_call; with `new_rows` = (k_step, v_step)
    the writing variant, whose pools come back updated."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("paged_decode", interpret)
    return _decode_pallas(q, k_cache, v_cache, block_tables, seq_lens,
                          new_rows, interpret=interpret)


# jitted on its own so that a program of L layers traces the kernel and
# lowers it through Mosaic once, and calls it L times
@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(q, k_cache, v_cache, block_tables, seq_lens, new_rows, *,
                   interpret):
    B, nh, hd = q.shape
    _, _, bs, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    write = new_rows is not None
    dtype = k_cache.dtype
    scale = 1.0 / math.sqrt(hd)
    state = [pltpu.VMEM((nh, 128), jnp.float32),
             pltpu.VMEM((nh, 128), jnp.float32),
             pltpu.VMEM((nh, hd), jnp.float32)]
    out_shape = jax.ShapeDtypeStruct((B, nh, hd), q.dtype)
    if write:
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(k_cache.shape, dtype)] * 2
    if _copies_by_hand(hd, interpret):
        group = _copy_group(nh, bs, hd, dtype, max_blocks)
        # the rows a write-back moves: the dtype's sublane tile, or the
        # whole block where the tile does not divide it
        tile = 32 // jnp.dtype(dtype).itemsize
        tile = tile if bs % tile == 0 else bs
        kern = functools.partial(_decode_kernel, scale=scale, bs=bs,
                                 max_blocks=max_blocks, group=group,
                                 tile=tile, write=write)
        rows = pl.BlockSpec((B, nh, hd), lambda i, tables, lens: (0, 0, 0))
        pool = pl.BlockSpec(memory_space=pl.ANY)
        buf = pltpu.VMEM((2, nh, group * bs, hd), dtype)
        grid = (1,)
        scratch = [buf, buf, pltpu.SemaphoreType.DMA((2,))] \
            + ([pltpu.SemaphoreType.DMA(())] if write else []) \
            + [pltpu.SMEM((B,), jnp.int32), pltpu.SMEM((B,), jnp.int32)]
        out_pool = pool
    else:
        kern = functools.partial(_decode_grid_kernel, scale=scale, bs=bs,
                                 max_blocks=max_blocks, write=write)

        def written(b, col, tables, lens):
            # an idle slot's row is zero, so this is the pad block for it
            return (0, tables[b, _write_col(lens[b], bs, max_blocks)], 0, 0)

        rows = pl.BlockSpec((None, nh, hd),
                            lambda b, col, tables, lens: (b, 0, 0))
        pool = pl.BlockSpec(
            (nh, None, bs, hd),
            lambda b, col, tables, lens: (0, tables[b, col], 0, 0))
        out_pool = pl.BlockSpec((nh, None, bs, hd), written)
        grid, scratch = (B, max_blocks), []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[rows] * (3 if write else 1) + [pool, pool],
        out_specs=[rows, out_pool, out_pool] if write else rows,
        scratch_shapes=scratch + state,
    )
    new = tuple(x.astype(dtype) for x in new_rows) if write else ()
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operands count the two scalar-prefetch ones: the pools are 5, 6
        input_output_aliases={5: 1, 6: 2} if write else {},
        interpret=interpret,
        name="paged_decode",
    )(block_tables, seq_lens, q, *new, k_cache, v_cache)


def paged_attention(q, k_cache, v_cache, block_tables, seq_lens,
                    interpret=None):
    """Decode attention over a paged KV cache.

    q:            [B, nh, hd]        one query token per sequence
    k_cache/v_cache: [nh, num_blocks, bs, hd] physical block pool — heads
        lead so each streamed block is a clean [bs, hd] tile (Mosaic needs
        the trailing two dims tileable; a squeezed head dim between them
        would break that)
    block_tables: [B, max_blocks] int32 physical block ids (pad with 0)
    seq_lens:     [B] int32 current context length per sequence
    Returns [B, nh, hd].
    """
    return _decode_call(q, k_cache, v_cache, block_tables, seq_lens,
                        interpret)


def paged_decode_step(q, k_step, v_step, k_pool, v_pool, block_tables,
                      seq_lens, interpret=None):
    """One decode step over a paged KV cache, store and attention in the
    one kernel (the in-place decode store of the reference's
    `fused_multi_transformer_op.cu.h:942-999`): each sequence's new k/v
    row lands at position ``seq_lens[b]`` through its block table and its
    query attends positions 0..seq_lens[b].

    q/k_step/v_step: [B, nh, hd]; k_pool/v_pool: [nh, num_blocks, bs, hd];
    block_tables: [B, max_blocks] int32; seq_lens: [B] lengths BEFORE the
    step.  A slot is IDLE when its length is 0 and its table's first
    entry is 0, the reserved pad block - the serving engine's free slot
    (length 0 over a zero table row; `PagedKVCache` and the engine number
    real blocks from 1): nothing is copied, merged or written for it, the
    pad block included, and its output row is zeros (discarded upstream).
    A live sequence of length 0 over a real block is not idle: its row is
    written at position 0 and attended.  Returns (out [B, nh, hd], k_pool,
    v_pool): the pools are aliased outputs, so a donated pool - or a
    `lax.scan` carry - is updated where it lies, one sublane tile of
    write-back a busy sequence (16 rows of bf16).

    Why the kernel and not `pool.at[:, blk, off].set(...)` beside it:
    XLA:TPU gives a scatter's operand the layout that makes the scattered
    dims major while Mosaic pins the default one, so the scatter
    re-laid-out the WHOLE pool before and after itself, every step
    (`_put`); and 2 x B `dynamic_update_slice` rows a layer, the form that
    needs no kernel, was no slower on the v5e (a 5.7-5.9 ms decode step of
    the 1.3B serve cell against 6.1 here) but, unrolled 768 times a
    program, took the cell's warm-up from 24 to 49 s (PERF.md §6,
    PR 27)."""
    return tuple(_decode_call(q, k_pool, v_pool, block_tables, seq_lens + 1,
                              interpret, new_rows=(k_step, v_step)))


def paged_attention_reference(q, k_cache, v_cache, block_tables, seq_lens):
    """Pure-XLA oracle: gather each sequence's blocks, masked softmax."""
    B, nh, hd = q.shape
    _, _, bs, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    # [nh, B, max_blocks, bs, hd] -> [B, S_max, nh, hd]
    k = jnp.moveaxis(k_cache[:, block_tables], 0, 3).reshape(
        B, max_blocks * bs, nh, hd)
    v = jnp.moveaxis(v_cache[:, block_tables], 0, 3).reshape(
        B, max_blocks * bs, nh, hd)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    pos = jnp.arange(max_blocks * bs)[None, None, :]
    live = pos < seq_lens[:, None, None]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jax.nn.softmax(s, axis=-1), 0.0)
    # seq_len == 0: every position masked -> zeros (matching the kernel's
    # l == 0 guard), not a uniform average over pad blocks
    return jnp.einsum("bhs,bshd->bhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _put(pool, update, blk):
    """`update` ([nh, 1, bs, hd]) over physical block `blk` of `pool`, as
    a `dynamic_update_slice`.

    Every traced write into a pool is this or `paged_decode_step`'s
    aliased output, and none is an XLA scatter.  XLA:TPU gives a scatter's
    operand the layout that makes the scattered dims major (`{3,0,2,1}`
    for a scatter over blocks and rows), while the Mosaic kernels that
    read the pool pin the default `{3,2,1,0}`: a program that scatters
    into a pool and attends through it re-lays-out the WHOLE pool on each
    side of every write — two copies of every pool a launch and one a
    scan step, 70% of the 1.3B serve cell's device time (PERF.md §6,
    PR 27).  A `dynamic_update_slice` has no layout preference, so a
    donated pool is updated where it lies."""
    return jax.lax.dynamic_update_slice(
        pool, update.astype(pool.dtype), (0, blk, 0, 0))


def _block_of(pool, blk):
    """Physical block `blk` of `pool`, heads leading: [nh, 1, bs, hd]."""
    nh, _, bs, hd = pool.shape
    return jax.lax.dynamic_slice(pool, (0, blk, 0, 0), (nh, 1, bs, hd))


@jax.jit
def paged_write_prefill(k_pool, v_pool, tables, k, v):
    """Traced bulk prefill write from empty sequences: k/v [B, S, nh, hd]
    go into each sequence's first ceil(S/bs) table blocks, one whole-block
    in-place update a block (`_put`).  The pad tail of the last block is
    written as zeros and is masked by seq_lens at attend time."""
    bs = k_pool.shape[2]
    B, S, nh, hd = k.shape
    nb = (S + bs - 1) // bs
    pad = nb * bs - S
    if pad:
        zeros = jnp.zeros((B, pad, nh, hd), k.dtype)
        k = jnp.concatenate([k, zeros], axis=1)
        v = jnp.concatenate([v, zeros], axis=1)
    blks = tables[:, :nb].reshape(-1)                       # [B*nb]
    # [B, nb*bs, nh, hd] -> [nh, B*nb, bs, hd]
    kb = jnp.moveaxis(k.reshape(B * nb, bs, nh, hd), 2, 0)
    vb = jnp.moveaxis(v.reshape(B * nb, bs, nh, hd), 2, 0)

    def put_block(i, pools):
        kp, vp = pools
        return (_put(kp, jax.lax.dynamic_slice_in_dim(kb, i, 1, 1), blks[i]),
                _put(vp, jax.lax.dynamic_slice_in_dim(vb, i, 1, 1), blks[i]))

    return jax.lax.fori_loop(0, B * nb, put_block, (k_pool, v_pool))


@functools.partial(jax.jit, static_argnames=("align",))
def paged_write_chunk(k_pool, v_pool, tables, start_lens, k, v, align=1):
    """Traced chunk write at an offset: token j of stream b's chunk
    (k/v [B, s, nkv, hd], the pool's own kv heads) lands at absolute
    position ``start_lens[b] + j`` through the block table.

    A chunk of s tokens from a traced start that is a multiple of
    `align` (1: any start; a divisor of the block size) touches at most
    n = ceil((s + bs - align) / bs) consecutive table columns: one where a
    block-diffusion model's block of `align` tokens lies inside a pool
    block, where an unaligned write of as many has to allow for two.
    Each of those
    blocks is read, merged with the chunk's rows under a position mask
    and put back in place (`_put`): a block the chunk does not reach is
    rewritten as it was.  Columns past the table go to the pad block 0
    (never a clipped read of the LAST column, which would corrupt a real
    block), as do inactive streams (length 0 over a zero table row).  One
    `fori_loop` over the B x n blocks, traced once a program (`jax.jit`),
    not B x n unrolled updates a layer: unrolled, the six chunk programs
    of the 1.3B serve cell took 11 s longer to trace and lower, against
    `setup_s` (PERF.md §6, PR 27)."""
    nh, _, bs, hd = k_pool.shape
    B, s = k.shape[0], k.shape[1]
    nb = tables.shape[1]
    n = (s + 2 * bs - align - 1) // bs
    r = start_lens % bs                                     # [B]
    cols = (start_lens // bs)[:, None] + jnp.arange(
        n, dtype=start_lens.dtype)                          # [B, n]
    blks = jnp.where(cols < nb, jnp.take_along_axis(
        tables, jnp.clip(cols, 0, nb - 1), axis=1), 0).reshape(B * n)
    row = jnp.arange(n * bs, dtype=start_lens.dtype)
    live = ((row >= r[:, None]) & (row < r[:, None] + s)).reshape(B * n, bs)

    def as_blocks(x, pool):
        # each chunk shifted to its offset in its first block, cut into
        # blocks: [B*n, nh, bs, hd]
        buf = jax.vmap(lambda xb, rb: jax.lax.dynamic_update_slice(
            jnp.zeros((n * bs, nh, hd), pool.dtype), xb, (rb, 0, 0)))(
                x.astype(pool.dtype), r)
        return jnp.transpose(buf.reshape(B * n, bs, nh, hd), (0, 2, 1, 3))

    kb, vb = as_blocks(k, k_pool), as_blocks(v, v_pool)

    def merge(i, pools):
        m = live[i][None, None, :, None]
        return tuple(
            _put(pool, jnp.where(m, new[i][:, None], _block_of(pool, blks[i])),
                 blks[i])
            for pool, new in zip(pools, (kb, vb)))

    return jax.lax.fori_loop(0, B * n, merge, (k_pool, v_pool))


def paged_copy_block(pool, src, dst, block_axis: int = 1):
    """Physical block `src` of `pool` copied over block `dst`, in place
    (the serving engine's copy-on-write; src/dst traced scalars).
    `block_axis` is where the pool keeps its blocks: 1 behind the heads
    of a (K, V) pool, 0 in a latent pool."""
    block = jax.lax.dynamic_slice_in_dim(pool, src, 1, block_axis)
    return jax.lax.dynamic_update_slice_in_dim(pool, block, dst, block_axis)


def _visible(kpos, qpos, mask_block):
    """Which keys (positions `kpos`) a query at `qpos` sees: causal over
    blocks of `mask_block` positions and full inside one, `kpos <
    (qpos // L + 1) * L`; `L = 1` is the offset-causal `kpos <= qpos`."""
    if mask_block == 1:
        return kpos <= qpos
    return kpos < (qpos // mask_block + 1) * mask_block


def _chunk_grid_kernel(tables_ref, starts_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, scale, bs, max_blocks,
                       q_blk, group, mask_block):
    """Flash-style chunk prefill, grid (B, s/q_blk, max_blocks): one
    instance = one q tile of one sequence against one physical block,
    streamed through the scalar-prefetched table (the DMA does the
    gather, like `_decode_kernel`).  Online-softmax state lives in VMEM
    scratch across the sequential block dimension.  Queries sit at
    absolute positions `start + j` (start = cached prefix length), so
    the causal mask is offset: key position <= query position, or
    `_visible`'s block form under `mask_block > 1`.

    The query tile arrives folded, `[nkv, q_blk, hd]` with row `r` the
    query head `r % group` of its kv head at chunk position `r // group`:
    the `group` query heads of a kv head (grouped-query attention; 1 where
    every query head has a pool head of its own) are rows of ONE matmul
    against that head's block, and nothing repeats K or V."""
    b = pl.program_id(0)
    qt = pl.program_id(1)
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]
    qpos = start + (qt * q_blk + jax.lax.broadcasted_iota(
        jnp.int32, (q_blk, 1), 0)[:, 0]) // group         # [q_blk]
    qpos_max = start + ((qt + 1) * q_blk - 1) // group

    @pl.when(_visible(blk * bs, qpos_max, mask_block))
    def _():
        q = q_ref[...]                                    # [nkv, q_blk, hd]
        k = k_ref[...]                                    # [nkv, bs, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [nh, q_blk, bs]
        kpos = blk * bs + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, bs), 1)
        s = jnp.where(_visible(kpos, qpos[:, None], mask_block)[None], s,
                      _NEG_INF)
        m_prev = m_scr[:, :]                              # [nh, q_blk]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [nh, q_blk, hd]
        acc_scr[:] = acc_scr[:] * alpha[:, :, None] + pv
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=2)
        m_scr[:] = m_new

    @pl.when(blk == max_blocks - 1)
    def _():
        l = l_scr[:, :]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / l_safe[:, :, None]).astype(o_ref.dtype)


def _chunk_fused_kernel(tables_ref, starts_ref, q_ref, k_ref, v_ref, o_ref,
                        *, scale, bs, max_blocks, s, group, mask_block):
    """Single-pass variant, grid (B,): the whole chunk of one sequence in
    one instance, a `fori_loop` over only the LIVE blocks (trip count
    `ceil((start + s) / bs)` — data-dependent, unlike a grid dimension).

    This is the interpret-mode (CPU fallback) strategy: the interpret
    executor copies every input buffer once per grid step, so a
    per-block grid pays `max_blocks` full k/v-pool copies per sequence
    — linear in POOL size, which loses to the dense gather at any real
    pool.  One grid step per sequence pays the pool copy once and skips
    dead table columns entirely, which is also where the win over dense
    comes from: dense attends the full padded table width.  `s` counts
    the query rows, folded as in `_chunk_grid_kernel`: `[nkv, s, hd]`."""
    b = pl.program_id(0)
    start = starts_ref[b]
    q = q_ref[...].astype(jnp.float32)                    # [nkv, s, hd]
    nh, hd = q.shape[0], q.shape[2]
    qpos = start + jax.lax.broadcasted_iota(
        jnp.int32, (s, 1), 0)[:, 0] // group
    # the keys the last query sees: to the end of its mask block
    end = ((start + (s - 1) // group) // mask_block + 1) * mask_block
    n_iter = jnp.minimum((end + bs - 1) // bs, max_blocks)

    def body(i, carry):
        m, l, acc = carry
        blk = tables_ref[b, i]
        k = k_ref[:, pl.ds(blk, 1)][:, 0]                 # [nh, bs, hd]
        v = v_ref[:, pl.ds(blk, 1)][:, 0]
        sc = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [nh, s, bs]
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        sc = jnp.where(_visible(kpos, qpos[:, None], mask_block)[None], sc,
                       _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=2))
        p = jnp.exp(sc - m_new[:, :, None])
        alpha = jnp.exp(m - m_new)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(p, axis=2),
                acc * alpha[:, :, None] + pv)

    m0 = jnp.full((nh, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((nh, s), jnp.float32)
    a0 = jnp.zeros((nh, s, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l[:, :, None]).astype(o_ref.dtype)


def _chunk_q_tile(s, nh, hd):
    """Query-tile length for the grid strategy: the largest halving of
    the chunk that divides it and keeps the float32 [nh, q_blk, hd]
    accumulator tile (padded to Mosaic's (8, 128) layout) within 1 MiB.
    Mosaic's live set per grid instance came to about five such tiles
    plus the double-buffered k/v blocks (AOT for TPU v5 lite, nh 3..32,
    hd 64/128, bf16 and f32), so this stays inside the 16 MiB
    scoped-VMEM default at every width; a whole 1024-token chunk as one
    tile does not.  Ladder buckets are multiples of the block size, so
    the halving stops at 64 or above for them; spec-verify chunks
    (s = k) are one tile."""
    nh_pad = -(-nh // 8) * 8
    hd_pad = -(-hd // 128) * 128
    q_blk = min(s, max(8, (1 << 18) // (nh_pad * hd_pad)))
    while s % q_blk:
        q_blk //= 2
    return q_blk


def paged_chunk_attention(q, k_cache, v_cache, block_tables, start_lens,
                          interpret=None, strategy=None, q_blk=None,
                          mask_block=1, _claim_name="paged_chunk_prefill"):
    """Chunked/suffix prefill attention over a paged KV cache.

    q:            [B, s, nh, hd]  chunk queries (s > 1 typical; post-RoPE)
    k_cache/v_cache: [nkv, num_blocks, bs, hd] physical block pool with
        the chunk ALREADY WRITTEN at positions start..start+s-1 (the
        write stays the caller's — `PagedChunkView` through
        `paged_write_chunk`, in place and in this layout).  `nkv` divides
        `nh`: query head `j` attends pool head `j // (nh / nkv)`
        (grouped-query attention; `nkv = nh` is one pool head a query
        head).  The `nh / nkv` query heads of a pool head are folded into
        the query rows, so a chunk of 4 positions under 8 heads a group
        is one 32-row matmul a pool head against each block; K and V are
        never repeated.
    block_tables: [B, max_blocks] int32 physical block ids (pad with 0)
    start_lens:   [B] int32 cached-prefix length per sequence; query j
        sits at absolute position start + j and attends keys 0..start+j
        (offset causal mask, `PagedChunkView`'s contract — including the
        overflow rows past the table, which attend the whole table and
        are discarded upstream)
    mask_block: `L`; a query at position `t` sees key `s` iff
        `s < (t // L + 1) * L`: causal over blocks of `L` positions, full
        inside one (block-diffusion models).  1 is the offset causal mask.
    strategy: "grid" (flash tiles over (B, s-tiles, blocks) — the TPU
        layout) or "fused" (one pass per sequence — the interpret-mode
        layout; see `_chunk_fused_kernel`).  Default: by `interpret`.
    Returns [B, s, nh, hd].
    """
    if interpret is None:
        interpret = pallas_common.interpret_default()
    if strategy is None:
        strategy = "fused" if interpret else "grid"
    pallas_common.claim(_claim_name, interpret)
    B, s, nh, hd = q.shape
    nkv, bs = k_cache.shape[0], k_cache.shape[2]
    if nh % nkv:
        raise ValueError(f"kv heads {nkv} do not divide query heads {nh}")
    group = nh // nkv
    max_blocks = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    rows = s * group               # query rows a pool head
    # [B, s, nkv, group, hd] -> [B, nkv, s * group, hd]
    q = jnp.transpose(q.reshape(B, s, nkv, group, hd),
                      (0, 2, 1, 3, 4)).reshape(B, nkv, rows, hd)

    def q_spec(tile, index):
        return pl.BlockSpec((None, nkv, tile, hd), index)

    if strategy == "fused":
        kern = functools.partial(_chunk_fused_kernel, scale=scale, bs=bs,
                                 max_blocks=max_blocks, s=rows, group=group,
                                 mask_block=mask_block)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                q_spec(rows, lambda b, tables, starts: (b, 0, 0, 0)),
                pl.BlockSpec(k_cache.shape,
                             lambda b, tables, starts: (0, 0, 0, 0)),
                pl.BlockSpec(v_cache.shape,
                             lambda b, tables, starts: (0, 0, 0, 0)),
            ],
            out_specs=q_spec(rows, lambda b, tables, starts: (b, 0, 0, 0)),
        )
    else:
        if q_blk is None:
            q_blk = _chunk_q_tile(rows, nkv, hd)
        if rows % q_blk or q_blk % group:
            raise ValueError(f"chunk of {rows} query rows ({group} a "
                             f"position) not divisible by q tile {q_blk}")
        kern = functools.partial(_chunk_grid_kernel, scale=scale, bs=bs,
                                 max_blocks=max_blocks, q_blk=q_blk,
                                 group=group, mask_block=mask_block)

        def qmap(b, qt, blk, tables, starts):
            return (b, 0, qt, 0)

        def kvmap(b, qt, blk, tables, starts):
            return (0, tables[b, blk], 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, rows // q_blk, max_blocks),
            in_specs=[
                q_spec(q_blk, qmap),
                pl.BlockSpec((nkv, None, bs, hd), kvmap),
                pl.BlockSpec((nkv, None, bs, hd), kvmap),
            ],
            out_specs=q_spec(q_blk, qmap),
            scratch_shapes=[
                pltpu.VMEM((nkv, q_blk), jnp.float32),
                pltpu.VMEM((nkv, q_blk), jnp.float32),
                pltpu.VMEM((nkv, q_blk, hd), jnp.float32),
            ],
        )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=_claim_name,
    )(block_tables, start_lens, q, k_cache, v_cache)
    return jnp.transpose(out.reshape(B, nkv, s, group, hd),
                         (0, 2, 1, 3, 4)).reshape(B, s, nh, hd)


def paged_chunk_attention_reference(q, k_cache, v_cache, block_tables,
                                    start_lens, mask_block=1):
    """Pure-XLA oracle, and what `PagedChunkView` attends through: the
    dense linearized-table gather under the mask (`mask_block` as
    `paged_chunk_attention` reads it); a pool head is read by the
    `nh / nkv` query heads of its group."""
    B, s, nh, hd = q.shape
    nkv, bs = k_cache.shape[0], k_cache.shape[2]
    nb = block_tables.shape[1]
    pos = start_lens[:, None] + jnp.arange(s, dtype=start_lens.dtype)
    k_lin = jnp.take(k_cache, block_tables, axis=1).reshape(
        nkv, B, nb * bs, hd)
    v_lin = jnp.take(v_cache, block_tables, axis=1).reshape(
        nkv, B, nb * bs, hd)
    kpos = jnp.arange(nb * bs, dtype=pos.dtype)
    mask = _visible(kpos[None, :], pos[:, :, None], mask_block)
    qg = q.astype(jnp.float32).reshape(B, s, nkv, nh // nkv, hd)
    logits = jnp.einsum("bqhgd,hbkd->bhgqk", qg,
                        k_lin.astype(jnp.float32)) / math.sqrt(hd)
    logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhgqk,hbkd->bqhgd", probs,
                      v_lin.astype(jnp.float32)).reshape(
                          B, s, nh, hd).astype(q.dtype)


def paged_verify_attention(q, k_cache, v_cache, block_tables, start_lens,
                           interpret=None, strategy=None):
    """Spec-decode verify attention: the k candidate positions of each
    stream attend the cached prefix + themselves through the block
    table.  Mathematically the chunk-prefill contract with s = k
    (candidates sit at start..start+k-1, offset causal), so it reuses
    the chunk kernel — but it is a distinct serving program with its
    own flag and audit row, hence the separate entry point and claim."""
    return paged_chunk_attention(
        q, k_cache, v_cache, block_tables, start_lens,
        interpret=interpret, strategy=strategy,
        _claim_name="paged_spec_verify")


class BlockKVCache:
    """Host-side block allocator + device block pool (the role of the
    reference's block-table manager around `block_multihead_attention`).

    append() writes one decode step's k/v into each sequence's current
    block (allocating a fresh physical block when the previous fills) with
    a single scatter; attend() runs the paged kernel.
    """

    def __init__(self, num_blocks: int, block_size: int, num_heads: int,
                 head_dim: int, batch: int, max_blocks_per_seq: int,
                 dtype=jnp.float32):
        self.bs = block_size
        self.k = jnp.zeros((num_heads, num_blocks, block_size, head_dim),
                           dtype)
        self.v = jnp.zeros_like(self.k)
        self.tables = jnp.zeros((batch, max_blocks_per_seq), jnp.int32)
        self.seq_lens = jnp.zeros((batch,), jnp.int32)
        self._free = list(range(num_blocks - 1, 0, -1))  # block 0 = pad
        self._owned = [[] for _ in range(batch)]
        self._lens = [0] * batch  # host mirror: no device sync per token

    def _alloc(self, b: int) -> int:
        if not self._free:
            raise RuntimeError("BlockKVCache: out of physical blocks")
        slot = len(self._owned[b])
        if slot >= self.tables.shape[1]:
            # out-of-bounds scatter would be silently DROPPED by XLA and
            # attention would lose the overflow tokens — fail loudly
            raise RuntimeError(
                f"BlockKVCache: sequence {b} exceeds max_blocks_per_seq="
                f"{self.tables.shape[1]}")
        blk = self._free.pop()
        self._owned[b].append(blk)
        self.tables = self.tables.at[b, slot].set(blk)
        return blk

    def append(self, k_step, v_step):
        """k_step/v_step: [B, nh, hd] — one token per sequence."""
        B = k_step.shape[0]
        rows, cols = [], []
        for b in range(B):
            pos = self._lens[b]  # host mirror: no device sync per token
            if pos % self.bs == 0:
                self._alloc(b)
            blk = self._owned[b][pos // self.bs]
            rows.append(blk)
            cols.append(pos % self.bs)
            self._lens[b] = pos + 1
        rows = jnp.asarray(rows)
        cols = jnp.asarray(cols)
        # target [nh, B, hd] slots at [:, rows, cols]
        self.k = self.k.at[:, rows, cols].set(
            jnp.moveaxis(k_step, 0, 1))
        self.v = self.v.at[:, rows, cols].set(
            jnp.moveaxis(v_step, 0, 1))
        self.seq_lens = self.seq_lens + 1

    def append_prefill(self, k, v):
        """Bulk-insert a whole prompt: k/v [B, S, nh, hd].  All sequences
        must be at the same (typically zero) length — the prefill case.
        One scatter per block column, not per token."""
        B, S = k.shape[0], k.shape[1]
        if len(set(self._lens)) != 1:
            raise RuntimeError("append_prefill needs equal sequence lengths")
        start = self._lens[0]
        if start % self.bs != 0:
            # fall back to per-token appends for a ragged tail
            for t in range(S):
                self.append(k[:, t], v[:, t])
            return
        nb = (S + self.bs - 1) // self.bs
        pad = nb * self.bs - S
        if pad:
            zeros = jnp.zeros((B, pad) + k.shape[2:], k.dtype)
            k = jnp.concatenate([k, zeros], axis=1)
            v = jnp.concatenate([v, zeros], axis=1)
        # [B, nb, bs, nh, hd] -> per block column [nh, B, bs, hd]
        kb = jnp.moveaxis(k.reshape(B, nb, self.bs, *k.shape[2:]), 3, 0)
        vb = jnp.moveaxis(v.reshape(B, nb, self.bs, *v.shape[2:]), 3, 0)
        for blk in range(nb):
            rows = []
            for b in range(B):
                rows.append(self._alloc(b))
            rows = jnp.asarray(rows)
            self.k = self.k.at[:, rows].set(kb[:, :, blk])
            self.v = self.v.at[:, rows].set(vb[:, :, blk])
        for b in range(B):
            self._lens[b] = start + S
        self.seq_lens = jnp.full_like(self.seq_lens, start + S)

    def attend(self, q, interpret=None):
        return paged_attention(q, self.k, self.v, self.tables,
                               self.seq_lens, interpret=interpret)

    def free(self, b: int):
        """Return sequence b's blocks to the pool."""
        self._free.extend(reversed(self._owned[b]))
        self._owned[b] = []
        self._lens[b] = 0
        self.tables = self.tables.at[b].set(0)
        self.seq_lens = self.seq_lens.at[b].set(0)
