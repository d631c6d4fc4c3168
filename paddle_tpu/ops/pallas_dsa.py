"""The sparse-attention indexer's decode-step scores over a paged
index-key pool, as one Pallas kernel.

`I[b, s] = sum_j w[b, j] * relu(q[b, j] . k[b, s])` for one query a
sequence against every cached index key of that sequence, read THROUGH
the block table.  The plain XLA form (`ops/sparse_mla.index_scores`)
first gathers each sequence's blocks into a `[B, context, 128]` copy and
writes the `[B, heads, context]` float32 logits out before it reduces
them over the heads: three passes over the keys' bytes and two over
eight times as many (5.2 ms of a 21.7 ms decode step of GLM-5's share on
the v5e, `dsa_index_roofline_pct` 5%; PERF.md section 6, PR 28).  Here
the keys stay where they are: a grid step copies `_GROUP` blocks of one
sequence from the pool (HBM, addressed by the table in SMEM) into VMEM
while the step before it computes (two buffers), multiplies them by the
sequence's `[heads, 128]` queries on the MXU, and reduces over the heads
in registers; steps that lie wholly beyond a sequence's context (and all
steps of an empty slot) move and compute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["index_scores_decode", "supported"]

_GROUP = 16          # blocks a grid step: 1,024 keys of 64-token blocks


def supported(q_idx, kidx_pool, tables) -> bool:
    """Shapes the kernel takes: one query a sequence, keys of one line of
    128 lanes a token (unpacked pool), a table the groups divide."""
    return (q_idx.shape[1] == 1 and q_idx.shape[-1] == 128
            and kidx_pool.shape[2] == 128 and kidx_pool.shape[1] % 16 == 0
            and tables.shape[1] % _GROUP == 0)


def _kernel(tables_ref, pos_ref, q_ref, w_ref, k_hbm, o_ref, kbuf, sem, *,
            steps, bs):
    """Grid step t = (sequence t // steps, group t % steps)."""
    t = pl.program_id(0)
    total = pl.num_programs(0)
    keys = _GROUP * bs

    def live(step):          # does the group hold a key at or before pos?
        return (step % steps) * keys <= pos_ref[step // steps]

    def copies(step, slot):
        b, g = step // steps, step % steps
        return [pltpu.make_async_copy(
            k_hbm.at[tables_ref[b, g * _GROUP + i]],
            kbuf.at[slot, pl.ds(i * bs, bs)], sem.at[slot])
            for i in range(_GROUP)]

    @pl.when(jnp.logical_and(t == 0, live(0)))
    def _():
        for c in copies(0, 0):
            c.start()

    nxt = t + 1

    @pl.when(jnp.logical_and(nxt < total, live(jnp.minimum(nxt, total - 1))))
    def _():
        for c in copies(nxt, nxt % 2):
            c.start()

    @pl.when(live(t))
    def _():
        slot = t % 2
        for c in copies(t, slot):
            c.wait()
        logits = jax.lax.dot_general(
            q_ref[0], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [H, keys]
        score = jnp.sum(jnp.maximum(logits, 0.0) * w_ref[0], axis=0,
                        keepdims=True)                      # [1, keys]
        kpos = (t % steps) * keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)
        o_ref[0] = jnp.where(kpos <= pos_ref[t // steps], score, -jnp.inf)

    @pl.when(jnp.logical_not(live(t)))
    def _():
        o_ref[0] = jnp.full((1, keys), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q, w, kidx_pool, tables, pos, *, interpret):
    B, H, D = q.shape
    bs = kidx_pool.shape[1]
    steps = tables.shape[1] // _GROUP
    keys = _GROUP * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * steps,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda t, tables, pos: (t // steps, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda t, tables, pos: (t // steps, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, keys), lambda t, tables, pos: (t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, keys, D), kidx_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, steps=steps, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * steps, 1, keys), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores",
    )(tables, pos, q.astype(kidx_pool.dtype),
      w.astype(jnp.float32)[..., None], kidx_pool)
    return out.reshape(B, steps * keys)


def index_scores_decode(q_idx, w_idx, kidx_pool, tables, pos,
                        interpret=None):
    """q_idx `[B, 1, H, 128]`, w_idx `[B, 1, H]` (already scaled),
    kidx_pool `[blocks, bs, 128]`, tables `[B, nb]`, pos `[B, 1]`: the
    float32 scores `[B, 1, nb * bs]`, `-inf` beyond `pos` (the oracle is
    `sparse_mla.index_scores_xla`)."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("dsa_index_scores", interpret)
    return _call(q_idx[:, 0], w_idx[:, 0], kidx_pool, tables, pos[:, 0],
                 interpret=interpret)[:, None]
