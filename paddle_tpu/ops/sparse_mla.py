"""Sparse selection inside paged attention, for latent (MLA) caches.

A layer of a `glm_moe_dsa` / DeepSeek-V3.2 style model keeps two pools
under ONE block table: latent rows (`c_kv | k_rope`, one a token, shared
by every head) and the indexer's keys (one small row a token).  A query
scores every cached token with the indexer (`index_scores`), keeps the
`topk` best (`select`: exact, causal, per sequence), fetches
those latent rows through the block table (`gather_rows`) and attends
only to them, in the absorbed form: the per-head `k_nope` / `v`
expansions are folded into the query and the output, so the scores are
taken against the latent rows themselves (`attend_selected`).

All of it is plain XLA: row gathers through `jnp.take`, a selection made
of compares, counts and small matmuls (no sort), and einsums with float32
accumulation.  Queries are processed a tile at a
time (`_Q_TILE`): the per-head index logits of a 512-query chunk against
30k keys over 32 heads would be 2 GB in float32 at once.

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
           "index_scores_xla", "select",
           "gather_rows", "attend_selected", "sparse_latent_attention"]

_Q_TILE = 32        # queries scored, selected and attended at a time
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


def select(scores, topk: int):
    """The `topk` best-scored positions of each query, exactly, equal
    scores to the lower position (the set `lax.top_k` returns; here in
    position order).  Returns (idx `[B, s, k]` int32, valid `[B, s, k]`):
    fewer than k tokens exist while the context is short, and the rest
    are marked invalid.

    `lax.top_k` of 2,048 in 32,768 is a full sort on the TPU: 2.91 ms a
    layer for 16 queries on the v5e against this function's 0.44 (a
    micro-run; PERF.md section 6, PR 28).  This is a selection instead,
    all of it dense vector and matmul work:
    the k-th largest value by bisection over the bits of the float (32
    compare-and-count passes), equal values admitted by position, and the
    chosen positions compacted by a two-level cumulative count (which
    row of 128 holds the j-th chosen, then which lane of that row)."""
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
    # compaction: slot j holds the (j + 1)-th chosen position
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
    finite = (u > _sortable(jnp.float32(-jnp.inf))).reshape(c_in.shape)
    in_row = jnp.einsum("...kr,...rc->...kc", hot,
                        finite.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    at = jax.nn.one_hot(lane, LANES, dtype=jnp.float32)
    return idx, (in_row * at).sum(-1) > 0


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
    """Index, select, gather and attend for queries `[B, s, ...]` at
    absolute positions `pos` `[B, s]`, over pools that already hold the
    queries' own rows.  Returns (o `[B, s, nh, d_latent]` float32,
    selected idx `[B, s, k]`, valid `[B, s, k]`)."""
    B, s = pos.shape

    def tile(args):
        qc, qi, wi, p = args
        with jax.named_scope("dsa_index"):
            scores = index_scores(qi, wi, kidx_pool, tables, p)
        with jax.named_scope("dsa_select"):
            idx, valid = select(scores, topk)
        with jax.named_scope("mla_attend"):
            rows = gather_rows(ckv_pool, tables, idx, q_cat.shape[-1])
            o = attend_selected(qc, rows, valid, scale, d_latent)
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
