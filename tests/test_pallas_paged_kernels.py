"""The ISSUE 18 paged Pallas kernels, pinned against their dense oracles.

Three layers of evidence:

* **Oracle parity** — `paged_chunk_attention` (both the interpret-mode
  "fused" strategy and the TPU "grid" strategy, run here in interpret
  mode) and `paged_verify_attention` against
  `paged_chunk_attention_reference` (what `PagedChunkView` attends
  through), over the routing grid that breaks naive implementations:
  chunk start != 0, seq_len landing exactly on a block boundary, GQA
  repeat > 1, and overflow rows past the table.
* **The audit flip** — a warmed serving engine's
  `xray.kernel_coverage` rows for the two ROADMAP 5b serving suspects
  flip from dense-with-note to kernel=True via=interpret, and flip
  BACK when the flags disable the kernels: the audit reports the
  build, not the intention.
* **Stream parity** — greedy token streams are BIT-identical with the
  kernels on vs off (the serving losslessness bar every prior PR held;
  float attention outputs differ by online-softmax rounding, integer
  argmax streams must not).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import xray
from paddle_tpu.ops import pallas_paged as pp

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


def _case(B, s, start, nh_q, nh_kv, bs=8, hd=16, max_blocks=None,
          seed=0):
    """Build a pool/table/query case.  The pool is random everywhere —
    kernel and oracle read the SAME pool through the SAME tables, so
    the comparison is exact regardless of which slots hold real keys."""
    rng = np.random.RandomState(seed)
    live = -(-(start + s) // bs)
    if max_blocks is None:
        max_blocks = live + 3           # table slack: padded with block 0
    npool = live * B + 1
    k = jnp.asarray(rng.standard_normal((nh_q, npool, bs, hd)),
                    jnp.float32) * 0.5
    v = jnp.asarray(rng.standard_normal((nh_q, npool, bs, hd)),
                    jnp.float32) * 0.5
    tables = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        tables[b, :live] = 1 + b * live + np.arange(live)
    q = jnp.asarray(rng.standard_normal((B, s, nh_q, hd)),
                    jnp.float32) * 0.5
    starts = jnp.full((B,), start, jnp.int32)
    del nh_kv   # callers cut the pools to their kv heads themselves
    return q, k, v, jnp.asarray(tables), starts


# start != 0 (suffix chunk), block-boundary seq_len, start on a
# boundary, single-row chunk, and an sliver chunk overflowing its block
CASES = [
    dict(B=2, s=5, start=0),            # fresh prefill chunk
    dict(B=2, s=6, start=7),            # suffix chunk, ragged start
    dict(B=1, s=8, start=8),            # start AND end on block boundary
    dict(B=3, s=3, start=13),           # end exactly on boundary (16)
    dict(B=2, s=1, start=11),           # single-row chunk
    dict(B=2, s=4, start=30, max_blocks=5),  # last block of the table
]


@pytest.mark.parametrize("case", CASES)
def test_fused_strategy_matches_dense_oracle(case):
    q, k, v, tables, starts = _case(nh_q=2, nh_kv=2, **case)
    ref = pp.paged_chunk_attention_reference(q, k, v, tables, starts)
    out = pp.paged_chunk_attention(q, k, v, tables, starts,
                                   interpret=True, strategy="fused")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("case", CASES[:4])
def test_grid_strategy_matches_dense_oracle(case):
    # the TPU flash-tile layout, run through the interpret executor:
    # same math, different grid — q_blk must divide s
    q, k, v, tables, starts = _case(nh_q=2, nh_kv=2, **case)
    s = q.shape[1]
    q_blk = max(1, s // 2) if s % 2 == 0 else 1
    ref = pp.paged_chunk_attention_reference(q, k, v, tables, starts)
    out = pp.paged_chunk_attention(q, k, v, tables, starts,
                                   interpret=True, strategy="grid",
                                   q_blk=q_blk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_kv_head_pools_equal_pools_repeated_to_the_query_heads():
    """Grouped-query attention: the pools hold the kv heads and the
    kernel maps each group's query heads onto their pool head.  The
    result is what the same kernel gives over pools REPEATED to the query
    heads, one pool head a query head — the layout before ISSUE 32, 2 x
    the cache here and 8 x at 32 heads over 4."""
    q, k, v, tables, starts = _case(B=2, s=4, start=9, nh_q=4, nh_kv=2)
    kv_k, kv_v = k[:2], v[:2]
    rep_k, rep_v = jnp.repeat(kv_k, 2, axis=0), jnp.repeat(kv_v, 2, axis=0)
    ref = pp.paged_chunk_attention_reference(q, rep_k, rep_v, tables, starts)
    for strategy in ("fused", "grid"):
        out = pp.paged_chunk_attention(q, kv_k, kv_v, tables, starts,
                                       interpret=True, strategy=strategy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
    # the two query heads of a group produce DIFFERENT outputs (queries
    # differ), i.e. the case is not degenerate
    assert not np.allclose(np.asarray(out)[:, :, 0], np.asarray(out)[:, :, 1])


def _dense_block_masked(q, k, v, tables, starts, mask_block):
    """Plain numpy: each sequence's table linearized, K and V repeated to
    the query heads, key `s` visible to the query at `t` iff
    `s < (t // L + 1) * L`."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    tables, starts = np.asarray(tables), np.asarray(starts)
    B, s, nh, hd = q.shape
    nkv, _, bs, _ = k.shape
    out = np.zeros_like(q)
    for b in range(B):
        kl = np.repeat(k[:, tables[b]].reshape(nkv, -1, hd), nh // nkv, 0)
        vl = np.repeat(v[:, tables[b]].reshape(nkv, -1, hd), nh // nkv, 0)
        t = starts[b] + np.arange(s)
        seen = np.arange(kl.shape[1])[None, :] \
            < ((t // mask_block + 1) * mask_block)[:, None]
        sc = np.einsum("qhd,hkd->hqk", q[b], kl) / np.sqrt(hd)
        sc = np.where(seen[None], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hqk,hkd->qhd", p, vl)
    return out


@pytest.mark.parametrize("strategy", ["fused", "grid"])
@pytest.mark.parametrize("mask_block", [1, 4])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_kv_head_chunk_kernel_under_the_block_mask(group, mask_block,
                                                   strategy):
    """`nh / nkv` query heads a pool head, folded into the query rows, and
    a mask that is causal over blocks of `mask_block` and full inside one:
    both strategies against a dense float64 reference that repeats K and
    V.  Starts are multiples of the mask's block, as the engine's are."""
    nkv = 2
    q, k, v, tables, starts = _case(B=3, s=8, start=12, nh_q=nkv * group,
                                    nh_kv=nkv, seed=group)
    k, v = k[:nkv], v[:nkv]
    starts = jnp.asarray([12, 4, 16], jnp.int32)
    want = _dense_block_masked(q, k, v, tables, starts, mask_block)
    out = pp.paged_chunk_attention(
        q, k, v, tables, starts, interpret=True, strategy=strategy,
        mask_block=mask_block,
        q_blk=4 * group if strategy == "grid" else None)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-6, rtol=3e-6)
    ref = pp.paged_chunk_attention_reference(q, k, v, tables, starts,
                                             mask_block)
    np.testing.assert_allclose(np.asarray(ref), want, atol=3e-6, rtol=3e-6)
    if mask_block == 4:
        # the block's later positions are seen: not the causal answer
        causal = _dense_block_masked(q, k, v, tables, starts, 1)
        assert np.abs(causal - want).max() > 1e-3


def test_verify_kernel_matches_chunk_semantics():
    """Spec-verify is the chunk contract with s = k candidates: the
    wrapper must return exactly what the chunk kernel returns and claim
    its own audit name."""
    q, k, v, tables, starts = _case(B=2, s=4, start=17, nh_q=2, nh_kv=2)
    ref = pp.paged_chunk_attention_reference(q, k, v, tables, starts)
    with xray.capture_kernel_claims() as claims:
        out = pp.paged_verify_attention(q, k, v, tables, starts,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    assert ("paged_spec_verify", "interpret") in claims


# ------------------------------------------- pool writes (ISSUE 27)
# Every traced write into a pool is in place - the decode row inside the
# `paged_decode` kernel, blocks by `dynamic_update_slice` - because
# XLA:TPU re-lays-out the whole pool around a scatter.  The scatters they
# replaced are kept here, word for word, as the oracle: the same rows
# must land in the same slots, bit for bit.

def _scatter_token(k_pool, tables, seq_lens, k_step):
    bs = k_pool.shape[2]
    B = k_step.shape[0]
    blk = tables[jnp.arange(B), seq_lens // bs]
    return k_pool.at[:, blk, seq_lens % bs].set(
        jnp.moveaxis(k_step, 0, 1).astype(k_pool.dtype))


def _scatter_chunk(k_pool, tables, seq_lens, k):
    bs, nb, s = k_pool.shape[2], tables.shape[1], k.shape[1]
    pos = seq_lens[:, None] + jnp.arange(s, dtype=seq_lens.dtype)
    cols = pos // bs
    blk = jnp.take_along_axis(tables, jnp.clip(cols, 0, nb - 1), axis=1)
    blk = jnp.where(cols < nb, blk, 0)
    return k_pool.at[:, blk, (pos % bs).astype(jnp.int32)].set(
        jnp.transpose(k.astype(k_pool.dtype), (2, 0, 1, 3)))


def _scatter_prefill(k_pool, tables, k):
    bs = k_pool.shape[2]
    B, S, nh, hd = k.shape
    nb = (S + bs - 1) // bs
    k = jnp.concatenate(
        [k, jnp.zeros((B, nb * bs - S, nh, hd), k.dtype)], axis=1)
    kb = jnp.moveaxis(k.reshape(B * nb, bs, nh, hd), 2, 0)
    return k_pool.at[:, tables[:, :nb].reshape(-1)].set(
        kb.astype(k_pool.dtype))


def _tables(rows, width):
    t = np.zeros((len(rows), width), np.int32)
    for b, r in enumerate(rows):
        t[b, :len(r)] = r
    return t


# bs = 8, tables 3 wide (24 positions), 12 physical blocks, block 0 = pad.
# kind, lens (write positions), table rows, chunk length
WRITE_CASES = {
    "token_offset_0_and_last": ("token", [8, 15], [[1, 2], [3, 4]], 1),
    "token_first_of_a_fresh_block": ("token", [16, 0], [[1, 2, 3], [4]], 1),
    "token_inactive_slot_to_pad": ("token", [5, 0, 9],
                                   [[1], [], [6, 7]], 1),
    "token_two_inactive_slots": ("token", [0, 3, 0], [[], [5], []], 1),
    "chunk_from_mid_block": ("chunk", [5], [[1, 2, 3]], 12),
    "chunk_ends_on_a_block_edge": ("chunk", [5], [[4, 2, 9]], 11),
    "chunk_aligned_whole_blocks": ("chunk", [8], [[4, 2, 9]], 16),
    "chunk_overflows_the_table": ("chunk", [20], [[1, 2, 3]], 8),
    "verify_k4_two_streams_no_shared_block": (
        "chunk", [6, 13], [[1, 2], [3, 4]], 4),
    "verify_k4_with_inactive_stream": (
        "chunk", [7, 0, 21], [[1, 2], [], [3, 4, 5]], 4),
    "chunk_gqa_kv_heads": ("gqa", [3, 10], [[1, 2], [3, 4, 5]], 6),
    "prefill_ragged_tail": ("prefill", [0, 0], [[1, 2, 3], [4, 5, 6]], 19),
    "prefill_whole_blocks": ("prefill", [0], [[7, 3]], 16),
    "cow_block": ("cow", [], [], 0),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_pool_write_matches_the_scatter_it_replaced(case, dtype):
    from paddle_tpu.models.kv_cache import PagedChunkView
    kind, lens, rows, s = WRITE_CASES[case]
    nh, hd, bs, width, nblocks = 4, 16, 8, 3, 12
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(len(case))
    rnd = lambda *sh: jnp.asarray(                     # noqa: E731
        rng.standard_normal(sh), jnp.float32).astype(dt)
    k_pool, v_pool = rnd(nh, nblocks, bs, hd), rnd(nh, nblocks, bs, hd)
    if kind == "cow":
        src, dst = jnp.int32(7), jnp.int32(2)
        got = jax.jit(pp.paged_copy_block)(k_pool, src, dst)
        want = k_pool.at[:, dst].set(k_pool[:, src])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        return
    B = len(lens)
    tables = jnp.asarray(_tables(rows, width))
    lens = jnp.asarray(lens, jnp.int32)
    # the new rows come in float32: the write casts to the pool's dtype
    if kind == "token":
        q, k, v = (jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
                   for _ in range(3))
        out, *got = jax.jit(pp.paged_decode_step)(
            q.astype(dt), k, v, k_pool, v_pool, tables, lens)
        want = (_scatter_token(k_pool, tables, lens, k),
                _scatter_token(v_pool, tables, lens, v))
        # ...and a live slot attends through the pools the step leaves
        # behind (an inactive one attends nothing: zeros, discarded)
        ref = pp.paged_attention_reference(q.astype(dt), *got, tables,
                                           lens + 1)
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[live],
            np.asarray(ref, np.float32)[live],
            atol=2e-2 if dtype == "bfloat16" else 2e-6)
    elif kind == "prefill":
        k, v = (jnp.asarray(rng.standard_normal((B, s, nh, hd)),
                            jnp.float32) for _ in range(2))
        got = jax.jit(pp.paged_write_prefill)(k_pool, v_pool, tables, k, v)
        want = (_scatter_prefill(k_pool, tables, k),
                _scatter_prefill(v_pool, tables, v))
    else:
        # a grouped-query model's pools hold its kv heads, and the
        # chunk is written as it comes: nothing repeats K or V
        nkv = nh // 2 if kind == "gqa" else nh
        k_pool, v_pool = k_pool[:nkv], v_pool[:nkv]
        k, v = (jnp.asarray(rng.standard_normal((B, s, nkv, hd)),
                            jnp.float32) for _ in range(2))
        q = jnp.zeros((B, s, nh, hd), jnp.float32)

        @jax.jit
        def write(kp, vp, tables, lens, q, k, v):       # lens traced
            new = PagedChunkView.from_parts(
                kp, vp, tables, lens, bs)._write_chunk(q, k, v)
            return (new.k, new.v), new.seq_lens

        got, new_lens = write(k_pool, v_pool, tables, lens, q, k, v)
        np.testing.assert_array_equal(np.asarray(new_lens),
                                      np.asarray(lens) + s)
        want = (_scatter_chunk(k_pool, tables, lens, k),
                _scatter_chunk(v_pool, tables, lens, v))
    for g, w, old in zip(got, want, (k_pool, v_pool)):
        g, w, old = (np.asarray(a, np.float32) for a in (g, w, old))
        assert g.dtype == w.dtype and g.shape == w.shape
        # every real block, bit for bit
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        assert (w != old).any()                  # the case writes something
        if kind == "token":
            # an inactive slot's row went to the pad block while the
            # store was a scatter; the kernel stores nothing for it
            np.testing.assert_array_equal(g[:, 0], old[:, 0])
        else:
            np.testing.assert_array_equal(g[:, 0], w[:, 0])
    if case == "chunk_overflows_the_table":
        # positions 24..27 fell off the 3-column table: they sit in the
        # pad block's rows 0..3, and the last real block (3) kept its
        # rows 0..3 while 4..7 took positions 20..23
        g, old = np.asarray(got[0], np.float32), np.asarray(k_pool, np.float32)
        np.testing.assert_array_equal(g[:, 3, :4], old[:, 3, :4])
        assert (g[:, 3, 4:] != old[:, 3, 4:]).all()
        assert (g[:, 0, :4] != old[:, 0, :4]).all()
        np.testing.assert_array_equal(g[:, 0, 4:], old[:, 0, 4:])


# ------------------------------------------- the decode walk (ISSUE 31)
# `paged_decode` copies only the blocks a running sequence holds, a group
# of them at a time, and stores one tile a busy sequence.  Blocks of 64,
# a table 6 wide (384 positions): 16 heads give a copy group of 4 (bf16)
# or 2 (float32), so the group does not divide the table in the bf16
# cases; one head gives a group as wide as the table.

_WALK_BS, _WALK_NB = 64, 6
# attended length a slot (0 = an idle slot: length 0 over a zero row) and
# its table row; slots 7 and 8 share their first two (full) blocks
_WALK_LENS = [0, 1, 63, 64, 65, 384, 0, 130, 150]
_WALK_ROWS = [[], [1], [2], [3], [4, 5], [6, 7, 8, 9, 10, 11], [],
              [12, 13, 14], [12, 13, 15]]


@pytest.fixture
def decode_form(request):
    """`walk`: the kernel copies the blocks itself (what the interpreter
    and a 128-lane pool on the chip run); `grid`: the BlockSpec form a
    narrower pool compiles to, forced here under the interpreter."""
    if request.param == "walk":
        yield request.param
        return
    saved = pp._copies_by_hand
    pp._copies_by_hand = lambda hd, interpret: False
    pp._decode_pallas.clear_cache()
    try:
        yield request.param
    finally:
        pp._copies_by_hand = saved
        pp._decode_pallas.clear_cache()


def _walk_setup(nh, hd, dtype, seed=0, pad=None):
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(seed)
    rnd = lambda *sh: jnp.asarray(                     # noqa: E731
        rng.standard_normal(sh), jnp.float32).astype(dt)
    k_pool, v_pool = rnd(nh, 16, _WALK_BS, hd), rnd(nh, 16, _WALK_BS, hd)
    if pad is not None:
        k_pool, v_pool = k_pool.at[:, 0].set(pad), v_pool.at[:, 0].set(pad)
    B = len(_WALK_LENS)
    return (k_pool, v_pool, jnp.asarray(_tables(_WALK_ROWS, _WALK_NB)),
            jnp.asarray(_WALK_LENS, jnp.int32), rnd(B, nh, hd), rnd(B, nh, hd),
            rnd(B, nh, hd))


def _stored(pool, tables, lens, rows):
    """`pool` with row lens[b] - 1 of each busy slot's sequence set."""
    for b in np.flatnonzero(np.asarray(lens) > 0):
        pos = int(lens[b]) - 1
        pool = pool.at[:, tables[b, pos // _WALK_BS], pos % _WALK_BS].set(
            rows[b].astype(pool.dtype))
    return pool


_WALK_SHAPES = [(nh, hd, dt, "walk") for nh in (1, 16) for hd in (64, 128)
                for dt in ("bfloat16", "float32")] + [
    (1, 64, "bfloat16", "grid"), (16, 64, "float32", "grid")]


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize(
    "nh, hd, dtype, decode_form", _WALK_SHAPES, indirect=["decode_form"],
    ids=[f"nh{a}-hd{b}-{c}-{d}" for a, b, c, d in _WALK_SHAPES])
def test_decode_walk_matches_the_reference(nh, hd, dtype, decode_form,
                                           write):
    assert pp._copy_group(16, _WALK_BS, 128, jnp.bfloat16, _WALK_NB) == 4
    k_pool, v_pool, tables, lens, q, k, v = _walk_setup(nh, hd, dtype)
    if write:
        # lengths before the step: slot 1 is a live sequence of length 0
        out, k_got, v_got = jax.jit(pp.paged_decode_step)(
            q, k, v, k_pool, v_pool, tables, jnp.maximum(lens - 1, 0))
        k_pool, v_pool = (_stored(k_pool, tables, lens, k),
                          _stored(v_pool, tables, lens, v))
        for got, want in ((k_got, k_pool), (v_got, v_pool)):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
    else:
        out = jax.jit(pp.paged_attention)(q, k_pool, v_pool, tables, lens)
    ref = pp.paged_attention_reference(q, k_pool, v_pool, tables, lens)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(
        out, ref, atol=2e-2 if dtype == "bfloat16" else 5e-6)
    idle = np.asarray(lens) == 0
    assert idle.sum() == 2 and not out[idle].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("decode_form", ["walk", "grid"], indirect=True)
def test_decode_step_touches_only_the_rows_it_stores(decode_form, dtype):
    """Byte for byte: after a step every pool element but the stored rows
    of busy slots is what it was - the pad block too, which is all NaN
    here, so an idle slot that read or merged it would show in its
    output row."""
    k_pool, v_pool, tables, lens, q, k, v = _walk_setup(
        4, 128, dtype, seed=1, pad=np.nan)
    out, k_got, v_got = jax.jit(pp.paged_decode_step)(
        q, k, v, k_pool, v_pool, tables, jnp.maximum(lens - 1, 0))
    assert np.isfinite(np.asarray(out, np.float32)).all()
    bits = np.uint16 if dtype == "bfloat16" else np.uint32
    for got, old, rows in ((k_got, k_pool, k), (v_got, v_pool, v)):
        want = np.asarray(_stored(old, tables, lens, rows)).view(bits)
        got, old = np.asarray(got).view(bits), np.asarray(old).view(bits)
        np.testing.assert_array_equal(got, want)
        changed = np.argwhere((got != old).any(axis=(0, 3)))   # (blk, row)
        assert len(changed) == (np.asarray(lens) > 0).sum()
        assert not (changed[:, 0] == 0).any()


def test_chunk_kernel_claims_its_audit_name():
    q, k, v, tables, starts = _case(B=1, s=4, start=5, nh_q=2, nh_kv=2)
    with xray.capture_kernel_claims() as claims:
        pp.paged_chunk_attention(q, k, v, tables, starts, interpret=True)
    assert ("paged_chunk_prefill", "interpret") in claims
    # no capture active: claims must not leak across contexts
    with xray.capture_kernel_claims() as fresh:
        pass
    assert fresh == []


@pytest.fixture(scope="module")
def engine_pair(model):
    """Drive TWO engines — kernels on (the default) and off — ONCE for
    the whole module: each warms up (producing its audit rows) and then
    serves three greedy requests (producing its streams).  The audit
    and stream tests read the same drive; tier-1 pays the engine
    compiles a single time."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 1000, (n,)) for n in (12, 14, 7)]

    def drive(kernels_on):
        with flag_guard(serving_warmup=True, serving_prefill_chunk=8,
                        serving_pad_buckets="16",
                        serving_pallas_prefill=kernels_on,
                        serving_pallas_verify=kernels_on):
            eng = ServingEngine(model, max_batch=3, max_context=64,
                                block_size=16, spec_decode=True,
                                spec_draft="ngram", spec_k=2)
            # The xray ledger is process-global (stats() reports a
            # top-N crowded by every test before us, and the bench rung
            # namespaces lookalike entries): the only deterministic way
            # to name THIS engine's programs is to watch which entries
            # its own warmup audits.
            mine = set()
            orig = xray.attach_lowered

            def spy(entry, lowered, claims=None):
                if entry is not None:
                    mine.add(entry.key)
                return orig(entry, lowered, claims)

            xray.attach_lowered = spy
            try:
                eng.warmup()
            finally:
                xray.attach_lowered = orig
            reqs = [eng.add_request(Request(p, max_new_tokens=10))
                    for p in prompts]
            eng.run()
        assert all(r.done for r in reqs)
        rows = {r["program"]: r for r in xray.kernel_coverage()
                if r["program"] in mine}
        return rows, [list(r.output_ids) for r in reqs]

    on_rows, on_streams = drive(True)
    off_rows, off_streams = drive(False)
    return {"on": (on_rows, on_streams), "off": (off_rows, off_streams)}


def test_audit_rows_flip_with_the_kernels(engine_pair):
    """The acceptance gate of ISSUE 18, driven end to end: the serving
    warmup audit's rows for suffix/chunked prefill and spec verify
    report kernel=True via=interpret with the kernels on (the default)
    and fall back to the dense-gather note with them off."""
    on, _ = engine_pair["on"]
    cont = [r for r in on.values()
            if r["path"] == "suffix/chunked prefill"]
    spec = [r for r in on.values() if r["path"] == "spec verify chunk"]
    assert cont and spec
    for r in cont:
        assert r["kernel"] is True and r["via"] == "interpret"
        assert "paged_chunk_prefill" in r["kernels"]
        assert "note" not in r
    for r in spec:
        assert r["kernel"] is True and r["via"] == "interpret"
        assert "paged_spec_verify" in r["kernels"]
        assert "note" not in r

    off, _ = engine_pair["off"]
    cont = [r for r in off.values()
            if r["path"] == "suffix/chunked prefill"]
    spec = [r for r in off.values() if r["path"] == "spec verify chunk"]
    assert cont and spec
    for r in cont + spec:
        assert r["kernel"] is False and r["via"] is None
        assert r["kernels"] == []
        assert "dense gather" in r["note"]


def test_greedy_streams_bit_identical_kernels_on_vs_off(engine_pair):
    """The serving losslessness bar: kernels change WHERE attention is
    computed, never WHICH token argmax picks."""
    _, on_streams = engine_pair["on"]
    _, off_streams = engine_pair["off"]
    assert on_streams == off_streams
    assert all(len(s) == 10 for s in on_streams)


def test_draft_chunk_view_is_the_engines_snapshot_not_a_traced_flag_read(
        model):
    """FLAGS_serving_pallas_prefill is snapshotted at construction for
    the draft model's share of a chunk as for the target's: a spec-model
    engine built with the flag on still lowers BOTH through the chunk
    kernel when `serving.prefill_cont` is first traced after the flag
    went off (the read used to sit in a traced helper, one call below
    the program body, where graft-lint R004 does not look)."""
    paddle.seed(1)
    draft = GPTForCausalLM(gpt3_tiny())
    draft.eval()
    with flag_guard(serving_pallas_prefill=True):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, prefill_chunk=8,
                            pad_buckets="8", draft_model=draft,
                            spec_decode=True, spec_k=2)
    eng.add_request(Request(np.arange(1, 7), max_new_tokens=2))
    with flag_guard(serving_pallas_prefill=False), \
            xray.capture_kernel_claims() as claims:
        eng.step()          # the first chunk: traces serving.prefill_cont
    assert eng.prefill_chunks_total == 1
    layers = model.cfg.num_layers + draft.cfg.num_layers
    assert claims.count(("paged_chunk_prefill", "interpret")) == layers
