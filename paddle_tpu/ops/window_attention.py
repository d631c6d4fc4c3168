"""Window attention over a per-slot ring of the last `W` positions.

A window layer (a query at position `t` sees key `s` iff `t - W < s <= t`)
keeps, for each batch slot, a ring of exactly `W` positions of K and of V:
position `p` sits at index `p mod W`.  What index `j` holds is recovered
from the slot's length `n` (positions `0..n-1` written, each once, in
order): the newest position at most `n - 1` that is `j` modulo `W`,
`n - 1 - ((n - 1 - j) mod W)`, negative where nothing has been written
yet.  A key is visible when that position passes the window rule and is
not negative, so a slot taken over by a new request needs no zeroing: the
previous occupant's keys sit at positions that are masked.

`window_attend` takes the ring as it stands BEFORE the step's writes and
the step's (or the chunk's) own keys beside it, so a two-position verify
reads position `p - W + 1` before the write of `p + 1` overwrites it.  A
verify whose second position is rejected leaves that position's key at
index `(p + 1) mod W`: the next step recovers it as position `p + 1 - W`,
which its query at `p + 1` does not see, and the next write there replaces
it.  So one rejected draft needs no rollback.

A chunk of `C` queries is cut into tiles of `W` queries, each scored
against its own band of `2W` keys (the ring and the chunk side by side, in
that order: tile `i` reads keys `i * W .. (i + 2) * W` of the pair), so
the work is `O(C W)` whatever the context.
Plain XLA; the shapes are static, the positions traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ring_positions", "window_attend", "ring_write"]

F32 = jnp.float32


def ring_positions(lens, window: int):
    """`[b, W]`: the position each ring index holds once the first `lens`
    `[b]` positions of each sequence were written (negative: none)."""
    last = lens[:, None] - 1
    return last - jnp.mod(last - jnp.arange(window, dtype=lens.dtype)[None],
                          window)


def window_attend(q, k, v, ring_k, ring_v, lens, *, window: int, scale):
    """Attention of `s` new queries under the window rule.

    q `[b, s, nh, hd]`; k, v `[b, s, nkv, hd]`: the new rows, at positions
    `lens + j`; ring_k, ring_v `[b, nkv, W, hd]`: the rings before these
    rows are written; `lens` `[b]`: positions already written.  Query
    head `j` reads kv head `j // (nh / nkv)`.  Rows of a chunk past its
    real length attend like the others and are discarded upstream.  A
    chunk longer than `W` that `W` divides is cut in tiles of `W` queries;
    any other is one tile.  Returns `[b, s, nh, hd]` in `q`'s dtype
    (softmax in float32)."""
    b, s, nh, hd = q.shape
    nkv, W = ring_k.shape[1], ring_k.shape[2]
    if W != window:
        raise ValueError(f"the ring holds {W} positions, the window is "
                         f"{window}")
    n = s // W if s > W and s % W == 0 else 1
    T = s // n
    qpos = lens[:, None] + jnp.arange(s, dtype=lens.dtype)[None]
    kpos = jnp.concatenate([ring_positions(lens, W), qpos], 1)   # [b, W+s]
    kt_new = jnp.swapaxes(k, 1, 2).astype(q.dtype)             # [b,nkv,s,hd]
    vt_new = jnp.swapaxes(v, 1, 2).astype(q.dtype)
    if n == 1:
        # a decode step, a verify, a short chunk: the ring and the new
        # keys are scored apart and never copied side by side
        qg = q.reshape(b, s, nkv, nh // nkv, hd)
        sc = jnp.concatenate([
            jnp.einsum("bshgd,bhkd->bhgsk", qg, ring_k.astype(q.dtype),
                       preferred_element_type=F32),
            jnp.einsum("bshgd,bhkd->bhgsk", qg, kt_new,
                       preferred_element_type=F32)], -1) * scale
        p = jax.nn.softmax(jnp.where(
            _seen(kpos[:, None], qpos[..., None], window)[:, None, None],
            sc, -jnp.inf), -1).astype(v.dtype)
        o = jnp.einsum("bhgsk,bhkd->bshgd", p[..., :W], ring_v.astype(
            v.dtype), preferred_element_type=F32) + jnp.einsum(
            "bhgsk,bhkd->bshgd", p[..., W:], vt_new.astype(v.dtype),
            preferred_element_type=F32)
        return o.reshape(b, s, nh, hd).astype(q.dtype)
    # a long chunk: tile i reads keys i * T .. i * T + W + T of the ring
    # and the chunk side by side
    keys = jnp.concatenate([ring_k.astype(q.dtype), kt_new], 2)
    vals = jnp.concatenate([ring_v.astype(v.dtype), vt_new.astype(v.dtype)],
                           2)                           # [b, nkv, W+s, hd]
    band = W + T
    kt = jnp.stack([keys[:, :, i * T:i * T + band] for i in range(n)], 1)
    vt = jnp.stack([vals[:, :, i * T:i * T + band] for i in range(n)], 1)
    pt = jnp.stack([kpos[:, i * T:i * T + band] for i in range(n)], 1)
    qt = q.reshape(b, n, T, nkv, nh // nkv, hd)
    qp = qpos.reshape(b, n, T)
    sc = jnp.einsum("bnthgd,bnhkd->bnhgtk", qt, kt,
                    preferred_element_type=F32) * scale
    seen = _seen(pt[:, :, None, :], qp[..., None], window)   # [b, n, T, k]
    p = jax.nn.softmax(jnp.where(seen[:, :, None, None], sc, -jnp.inf), -1)
    o = jnp.einsum("bnhgtk,bnhkd->bnthgd", p.astype(v.dtype), vt,
                   preferred_element_type=F32)
    return o.reshape(b, s, nh, hd).astype(q.dtype)


def _seen(kpos, qpos, window: int):
    """The window rule over broadcastable key and query positions (a
    negative key position was never written)."""
    return (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - window)


def _rows_at(new, r):
    """`new` `[b, nkv, s, hd]` at rows `r` `[b, W]` (clipped):
    `[b, nkv, W, hd]`.  A decode step's or a verify's few rows by
    selection, a chunk's by a gather of whole rows."""
    s = new.shape[2]
    r = jnp.clip(r, 0, s - 1)
    if s <= 4:
        return functools.reduce(
            lambda acc, i: jnp.where((r == i)[:, None, :, None],
                                     new[:, :, i:i + 1], acc),
            range(1, s), jnp.broadcast_to(new[:, :, :1], new.shape[:2]
                                          + (r.shape[1], new.shape[3])))
    return jnp.take_along_axis(new, r[:, None, :, None], axis=2)


def ring_write(ring, new, lens, n_new, active=None):
    """The ring `[b, nkv, W, hd]` after the rows `new` `[b, s, nkv, hd]`
    at positions `lens + j` were written, of which the first `n_new` `[b]`
    are real (a padded bucket row never reaches the ring) and only
    sequences with `active` `[b]` (None: all) write.  Each index ends up
    holding the newest position of its residue class: of `C > W` real
    rows only the last `W` land."""
    W = ring.shape[2]
    r = ring_positions(lens + n_new, W) - lens[:, None]      # [b, W]
    take = (r >= 0) & (r < n_new[:, None])
    if active is not None:
        take = take & active[:, None]
    rows = _rows_at(jnp.swapaxes(new, 1, 2).astype(ring.dtype), r)
    return jnp.where(take[:, None, :, None], rows, ring)
