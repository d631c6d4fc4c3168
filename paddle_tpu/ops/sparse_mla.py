"""Sparse selection inside paged attention, for latent (MLA) caches.

A layer of a `glm_moe_dsa` / DeepSeek-V3.2 style model keeps two pools
under ONE block table: latent rows (`c_kv | k_rope`, one a token, shared
by every head) and the indexer's keys (one small row a token).  A query
scores every cached token with the indexer (`index_scores`), keeps the
`topk` best (`select`: exact, causal, per sequence) and attends only to
those, in the absorbed form: the per-head `k_nope` / `v` expansions are
folded into the query and the output, so the scores are taken against
the latent rows themselves.

How the selected rows reach the multiply depends on how many queries
share a sequence (`sparse_latent_attention`).  The few queries of a
decode step fetch them through the block table (`gather_rows`, a
`jnp.take` of single rows: 27 ns a row on the v5e whatever its bytes)
and attend them (`attend_selected`).  The hundreds of queries of a chunk
would fetch 2,048 rows each; they attend, a tile of queries at a time,
EVERY block the sequence holds, copied whole, with the rows the selection
left out masked from the softmax (`select_mask`, then the Pallas kernel
`pallas_latent.paged_latent_chunk`): the same set, the same float32
softmax, FLOPs the chip has to spare in place of descriptors it has not.

The rest is plain XLA: a selection made of compares, counts and small
matmuls (no sort), and einsums with float32 accumulation.  Queries are
processed a tile at a time (`_Q_TILE`): the per-head index logits of a
512-query chunk against 30k keys over 32 heads would be 2 GB in float32
at once.

Pool layout: `[num_blocks + 1, block_size, lanes]`, block 0 the pad
block, `lanes` = `padded_width(width)`: a row of 576 values is kept in 640
lanes (5 tiles of 128).  The TPU tiles a minor dimension of 576 up to 640
in any case, and for a program's arguments and results it then prefers
another layout altogether (`{0,2,1}`): every program copied each latent
pool on its way in and out, 16 ms a tick on the v5e (PERF.md section 6,
PR 28).  With the pad in the shape the default layout is the dense one,
and a row is still one gather.  (Packing a block's values into lines of
128 keeps the bytes at 576 a token but makes a row 4.5 lines: the
slice-gather that reads them ran the cell's chunk program for minutes.)
Writes are read-modify-write `dynamic_update_slice`s of whole blocks, as
`pallas_paged._put` (a scatter would re-lay-out the pool).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["padded_width", "write_rows", "index_scores",
           "index_scores_xla", "select", "select_mask",
           "gather_rows", "attend_selected", "sparse_latent_attention",
           "MASKED_PASS_MAX_ROWS"]

_Q_TILE = 32        # queries scored, selected and attended at a time
# Where a chunk's two forms cross.  A tile of 32 queries x 64 heads fetches
# its 32 x 2,048 selected rows in 1.77 ms whatever the context (27.0 ns a
# row: descriptors, not bytes) and walks 16,384 / 32,768 rows under the mask
# in 0.52 / 0.96 ms, 71% / 77% of the v5e's 197 TFLOP/s (a micro-run on the
# chip; PERF.md section 6, PR 38): 29 ns a context row, equal at 60k rows.
# A table no wider than this holds the masked pass alone.
MASKED_PASS_MAX_ROWS = 60 * 1024
LANES = 128         # the minor dimension of a TPU tile


def padded_width(width: int) -> int:
    """Lanes a pool keeps for rows of `width` values: whole tiles of
    `LANES` once a row is wider than one (576 -> 640)."""
    return width if width < LANES else -(-width // LANES) * LANES


def _put(pool, block, blk):
    return jax.lax.dynamic_update_slice(
        pool, block.astype(pool.dtype), (blk, 0, 0))


def _block_of(pool, blk):
    return jax.lax.dynamic_slice(pool, (blk, 0, 0), (1,) + pool.shape[1:])


@jax.jit
def write_rows(pool, tables, start_lens, rows):
    """Row j of `rows` `[B, s, width]` lands at absolute position
    `start_lens[b] + j` of sequence b through its block table; any `s`
    (a decode step, a chunk at an offset, a prefill from empty).  The
    blocks touched are read, merged under a position mask and put back
    in place; the pool's pad lanes are written as zeros.  Columns past
    the table and inactive streams (length 0 over a zero table row) go
    to the pad block 0."""
    _, bs, w = pool.shape
    B, s = rows.shape[0], rows.shape[1]
    rows = jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, 0), (0, w - rows.shape[2])))
    nb = tables.shape[1]
    n = (s + 2 * bs - 2) // bs
    r = start_lens % bs
    cols = (start_lens // bs)[:, None] + jnp.arange(n, dtype=start_lens.dtype)
    blks = jnp.where(cols < nb, jnp.take_along_axis(
        tables, jnp.clip(cols, 0, nb - 1), axis=1), 0).reshape(B * n)
    row = jnp.arange(n * bs, dtype=start_lens.dtype)
    live = ((row >= r[:, None]) & (row < r[:, None] + s)).reshape(B * n, bs)
    buf = jax.vmap(lambda xb, rb: jax.lax.dynamic_update_slice(
        jnp.zeros((n * bs, w), pool.dtype), xb, (rb, 0)))(
            rows, r).reshape(B * n, 1, bs, w)

    def merge(i, pool):
        return _put(pool, jnp.where(live[i][None, :, None], buf[i],
                                    _block_of(pool, blks[i])), blks[i])

    return jax.lax.fori_loop(0, B * n, merge, pool)


def index_scores(q_idx, w_idx, kidx_pool, tables, pos):
    """`index_scores_xla`, or for a decode step over lines of 128 lanes
    the Pallas kernel that reads the keys in place (`ops/pallas_dsa`)."""
    from . import pallas_dsa
    if pallas_dsa.supported(q_idx, kidx_pool, tables):
        return pallas_dsa.index_scores_decode(q_idx, w_idx, kidx_pool,
                                              tables, pos)
    return index_scores_xla(q_idx, w_idx, kidx_pool, tables, pos)


def index_scores_xla(q_idx, w_idx, kidx_pool, tables, pos):
    """The indexer's score of every cached token for each query:
    `I[b, t, s] = sum_j w[b, t, j] * relu(q[b, t, j] . k[b, s])`, float32,
    `-inf` where `s > pos[b, t]` (not yet written, or the future).

    q_idx `[B, s, Hi, Di]`, w_idx `[B, s, Hi]` (already scaled),
    kidx_pool `[blocks, bs, Di]`, tables `[B, nb]`, pos `[B, s]`.
    Returns `[B, s, nb * bs]`."""
    B, nb = tables.shape
    di = q_idx.shape[-1]
    k = jnp.take(kidx_pool, tables, axis=0).reshape(B, -1, di)
    logits = jnp.einsum("bthd,bkd->bthk", q_idx.astype(k.dtype), k,
                        preferred_element_type=jnp.float32)
    score = jnp.einsum("bthk,bth->btk", jax.nn.relu(logits),
                       w_idx.astype(jnp.float32))
    kpos = jnp.arange(k.shape[1], dtype=pos.dtype)
    return jnp.where(kpos[None, None, :] <= pos[:, :, None], score, -jnp.inf)


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _cumsum_rows(m):
    """Inclusive cumulative count of a 0/1 array along its last axis, as
    (within rows of `LANES`: `[..., n / LANES, LANES]`; row totals `[...,
    n / LANES]`), both float32 and exact: a triangular matmul a row (counts
    up to 128 are exact in bfloat16 operands, the sum is float32)."""
    rows = m.reshape(m.shape[:-1] + (-1, LANES)).astype(jnp.bfloat16)
    tri = jnp.triu(jnp.ones((LANES, LANES), jnp.bfloat16))
    within = jnp.einsum("...rc,cd->...rd", rows, tri,
                        preferred_element_type=jnp.float32)
    return within, within[..., -1]


def _choose(scores, topk: int):
    """The first half of `select`: which positions are chosen, as a dense
    mask.  Returns (`u`, the scores as sortable uint32 padded to whole
    rows of `LANES` with values below -inf; `chosen`, `u`'s shape: the
    `k` best, equal values admitted by position; `k`).  While fewer than
    `k` tokens exist, `chosen` fills up with `-inf` positions: `_live`
    says which are real."""
    n = scores.shape[-1]
    k = min(int(topk), n)
    pad = -n % LANES
    u = _sortable(scores.astype(jnp.float32))
    if pad:
        u = jnp.pad(u, ((0, 0),) * (u.ndim - 1) + ((0, pad),))  # below -inf

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (u >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))
    above = u > kth[..., None]
    ties = u == kth[..., None]
    need = (k - above.sum(-1)).astype(jnp.float32)
    t_in, t_tot = _cumsum_rows(ties)
    t_rank = t_in + (jnp.cumsum(t_tot, -1) - t_tot)[..., None]
    chosen = above | (ties & (t_rank.reshape(u.shape) <= need[..., None]))
    return u, chosen, k


def _live(u):
    """Positions whose score is above -inf: tokens that exist."""
    return u > _sortable(jnp.float32(-jnp.inf))


def _compact(u, chosen, k: int, n: int):
    """The second half of `select`: a mask into positions.  Slot j of
    `idx` holds the (j + 1)-th chosen position, by a two-level cumulative
    count (which row of 128 holds it, then which lane of that row);
    `valid` is `_live` of that position."""
    c_in, c_tot = _cumsum_rows(chosen)
    c_ends = jnp.cumsum(c_tot, -1)                       # [..., rows]
    want = jnp.arange(1, k + 1, dtype=jnp.float32)
    row = (c_ends[..., None, :] < want[:, None]).sum(-1)           # [..., k]
    hot = jax.nn.one_hot(row, c_tot.shape[-1], dtype=jnp.bfloat16)
    before = jnp.einsum("...kr,...r->...k", hot, c_ends - c_tot,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    lanes = jnp.einsum("...kr,...rc->...kc", hot,
                       c_in.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    lane = (lanes < (want - before)[..., None]).sum(-1)
    idx = jnp.minimum(row * LANES + lane, n - 1).astype(jnp.int32)
    # is the j-th chosen a real token (score above -inf)?  Read the same
    # way, with no gather of scalars: its row by the one-hot, then its lane
    finite = _live(u).reshape(c_in.shape)
    in_row = jnp.einsum("...kr,...rc->...kc", hot,
                        finite.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    at = jax.nn.one_hot(lane, LANES, dtype=jnp.float32)
    return idx, (in_row * at).sum(-1) > 0


def select(scores, topk: int):
    """The `topk` best-scored positions of each query, exactly, equal
    scores to the lower position (the set `lax.top_k` returns; here in
    position order).  Returns (idx `[B, s, k]` int32, valid `[B, s, k]`):
    fewer than k tokens exist while the context is short, and the rest
    are marked invalid.

    `lax.top_k` of 2,048 in 32,768 is a full sort on the TPU: 2.91 ms a
    layer for 16 queries on the v5e against this function's 0.44 (a
    micro-run; PERF.md section 6, PR 28).  This is a selection instead,
    all of it dense vector and matmul work, in two halves: the mask
    (`_choose`: the k-th largest value by bisection over the bits of the
    float, 32 compare-and-count passes, equal values admitted by
    position) and its compaction into positions (`_compact`).  A program
    of many queries attends under the mask itself (`select_mask`) and
    compacts only for a caller that reads the positions."""
    u, chosen, k = _choose(scores, topk)
    return _compact(u, chosen, k, scores.shape[-1])


def _mask(u, chosen, n: int):
    """`_choose`'s set less the positions that hold no token, `[..., n]`."""
    return (chosen & _live(u))[..., :n]


def select_mask(scores, topk: int):
    """`select`'s set as a dense mask `[..., n]`: True at the positions
    `select` returns as valid, and nowhere else."""
    u, chosen, _ = _choose(scores, topk)
    return _mask(u, chosen, scores.shape[-1])


def gather_rows(pool, tables, idx, width: int):
    """Rows at logical positions `idx` `[B, s, k]` of each sequence,
    through its block table: `[B, s, k, width]` (the pad lanes cut)."""
    bs, w = pool.shape[1], pool.shape[2]
    blk = jnp.take_along_axis(tables[:, None, :], idx // bs, axis=2)
    rows = jnp.take(pool.reshape(-1, w), blk * bs + idx % bs, axis=0)
    return rows[..., :width]


def attend_selected(q_cat, rows, valid, scale: float, d_latent: int):
    """Absorbed-form attention of each query over its own selected rows.
    q_cat `[B, s, nh, width]` = `[q_nope W_kvb^K | q_rope]`; rows
    `[B, s, k, width]` = `[c_kv | k_rope]`.  Returns `sum p c_kv`,
    `[B, s, nh, d_latent]` float32 (the caller applies `W_kvb^V`)."""
    s = jnp.einsum("bthw,btkw->bthk", q_cat.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # a query with no valid key (a padded row of an inactive slot) gets 0
    p = jnp.where(valid[:, :, None, :], p, 0.0)
    return jnp.einsum("bthk,btkc->bthc", p.astype(rows.dtype),
                      rows[..., :d_latent],
                      preferred_element_type=jnp.float32)


def sparse_latent_attention(q_cat, q_idx, w_idx, ckv_pool, kidx_pool,
                            tables, pos, *, topk: int, scale: float,
                            d_latent: int):
    """Index, select and attend for queries `[B, s, ...]` at absolute
    positions `pos` `[B, s]`, over pools that already hold the queries'
    own rows.  Returns (o `[B, s, nh, d_latent]` float32, selected idx
    `[B, s, k]`, valid `[B, s, k]`).

    The few queries of a decode step fetch their selected rows
    (`gather_rows`) and attend them (`attend_selected`).  A chunk's many
    queries attend, a tile at a time, every block the sequence holds
    under the selection's mask (`pallas_latent.paged_latent_chunk`): the
    same set and the same float32 softmax in another order of sums, with
    no row fetched alone.  The positions come from the same mask, and a
    program that drops them never compacts it."""
    from . import pallas_latent
    B, s = pos.shape
    few = s <= pallas_latent.KERNEL_MAX_QUERIES

    def tile(args):
        qc, qi, wi, p = args
        with jax.named_scope("dsa_index"):
            scores = index_scores(qi, wi, kidx_pool, tables, p)
        if few:
            with jax.named_scope("dsa_select"):
                idx, valid = select(scores, topk)
            with jax.named_scope("mla_attend"):
                rows = gather_rows(ckv_pool, tables, idx, q_cat.shape[-1])
                o = attend_selected(qc, rows, valid, scale, d_latent)
            return o, idx, valid
        n = scores.shape[-1]
        with jax.named_scope("dsa_select"):
            u, chosen, k = _choose(scores, topk)
            idx, valid = _compact(u, chosen, k, n)
            mask = _mask(u, chosen, n)
        # the walk ends at the block of the tile's last query
        lens = p.max(axis=1) + 1

        def masked():
            return pallas_latent.paged_latent_chunk(
                qc, ckv_pool, tables, lens, mask, scale=scale,
                d_latent=d_latent)

        def gathered():
            at, real = _compact(u, chosen, k, n)
            rows = gather_rows(ckv_pool, tables, at, q_cat.shape[-1])
            return attend_selected(qc, rows, real, scale, d_latent)

        with jax.named_scope("mla_attend"):
            if n <= MASKED_PASS_MAX_ROWS:
                o = masked()
            else:
                o = jax.lax.cond(lens.max() > MASKED_PASS_MAX_ROWS,
                                 gathered, masked)
        return o, idx, valid

    if s <= _Q_TILE:
        return tile((q_cat, q_idx, w_idx, pos))
    pad = -s % _Q_TILE
    n = (s + pad) // _Q_TILE

    def cut(a):      # [B, s, ...] -> [n, B, tile, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(
            a.reshape((B, n, _Q_TILE) + a.shape[2:]), 1, 0)

    def join(a):     # [n, B, tile, ...] -> [B, s, ...]
        a = jnp.moveaxis(a, 0, 1)
        return a.reshape((B, n * _Q_TILE) + a.shape[3:])[:, :s]

    o, idx, valid = jax.lax.map(
        tile, (cut(q_cat), cut(q_idx), cut(w_idx), cut(pos)))
    return join(o), join(idx), join(valid)
