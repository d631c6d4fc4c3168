"""A chunk's many queries attend the sequence's live blocks under the
selection's mask (`ops/pallas_latent.paged_latent_chunk`, interpreted
here) where a decode step fetches its selected rows one by one: the same
set, the same float32 softmax, another order of sums.

Tolerance: float32 operands on both sides, whole softmax against online
softmax over groups of keys: a few roundings of values near 1, 1e-5.
Positions must be EQUAL, slot for slot.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaForCausalLM,
                                           glm_moe_dsa_tiny)
from paddle_tpu.ops import pallas_latent, sparse_mla as sm

TOL = 1e-5
NH, W, LANES, D = 4, 20, 24, 16
SCALE = 0.3


def _pools(rng, bs, nb, ctx, B=1):
    """A pool, a shuffled table whose columns past `ctx` rows point at a
    block of NaN (`dirty`: what the kernel must never copy) or at the pad
    block 0 (`clean`: what the gather form may touch and mask)."""
    live = -(-ctx // bs)
    pool = jnp.asarray(rng.randn(B * nb + 2, bs, LANES), jnp.float32)
    pool = pool.at[:, :, W:].set(0).at[0].set(0)
    poison = B * nb + 1
    pool = pool.at[poison].set(jnp.nan)
    clean = rng.permutation(B * nb).reshape(B, nb).astype(np.int32) + 1
    clean[:, live:] = 0
    dirty = clean.copy()
    dirty[:, live:] = poison
    return pool, jnp.asarray(clean), jnp.asarray(dirty)


# whole programs, not op-by-op: an eager `select` compiles a hundred small
# computations for every new shape
_select = jax.jit(sm.select, static_argnums=1)
_select_mask = jax.jit(sm.select_mask, static_argnums=1)


@jax.jit
def _gathered(q, pool, tables, idx, valid):
    return sm.attend_selected(q, sm.gather_rows(pool, tables, idx, W), valid,
                              SCALE, D)


def _scores(rng, pos, n):
    """Index scores with many exact ties, `-inf` beyond each query."""
    sc = jnp.asarray(rng.randint(0, 40, pos.shape + (n,)), jnp.float32)
    return jnp.where(jnp.arange(n)[None, None] <= pos[..., None], sc,
                     -jnp.inf)


# name -> (block size, table width, rows before the chunk, index_topk);
# None: set from the query count
CONTEXTS = {
    "shorter than index_topk": (8, 80, 0, 1024),
    "one block": (None, 3, 0, 16),           # block_size = the queries
    "a partial last block": (8, 80, 61, 16),
    "the full table": (8, 80, None, 16),     # start = table rows - queries
}


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("s", [33, 128, 512])
def test_masked_pass_is_gather_and_attend(s, context):
    bs, nb, start, topk = CONTEXTS[context]
    bs = bs or -(-s // 8) * 8
    start = nb * bs - s if start is None else start
    rng = np.random.RandomState(s)
    ctx = start + s
    pool, clean, dirty = _pools(rng, bs, nb, ctx)
    q = jnp.asarray(rng.randn(1, s, NH, W), jnp.float32)
    pos = start + jnp.arange(s, dtype=jnp.int32)[None]
    scores = _scores(rng, pos, nb * bs)
    idx, valid = _select(scores, topk)
    want = _gathered(q, pool, clean, idx, valid)
    got = pallas_latent.paged_latent_chunk(
        q, pool, dirty, jnp.asarray([ctx], jnp.int32),
        _select_mask(scores, topk), scale=SCALE, d_latent=D)
    assert got.shape == want.shape == (1, s, NH, D)
    assert bool(jnp.isfinite(got).all())      # no dead column was read
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("first", [0, 1])
@pytest.mark.parametrize("s", [5, 33, 128])
def test_without_a_mask_it_is_causal_attention(s, first):
    """`mask=None`: `paged_latent_attention`'s contract at any `s`, two
    sequences of different lengths and an idle slot."""
    rng = np.random.RandomState(s)
    bs, nb, B = 8, 24, 3
    pool, _, tables = _pools(rng, bs, nb, 0, B)
    live = rng.permutation(B * nb).reshape(B, nb).astype(np.int32) + 1
    lens = np.asarray([s + 40, s + 3, 0], np.int32)
    for b in range(B):
        n = -(-int(lens[b]) // bs)
        tables = tables.at[b, :n].set(live[b, :n])
    q = jnp.asarray(rng.randn(B, s, NH, W), jnp.float32)
    kw = dict(scale=SCALE, d_latent=D, first=first)
    got = pallas_latent.paged_latent_chunk(q, pool, tables,
                                           jnp.asarray(lens), **kw)
    clean = jnp.where(tables == B * nb + 1, 0, tables)
    want = pallas_latent.paged_latent_attention_reference(
        q, pool, clean, jnp.asarray(lens), **kw)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(got[2]).max()) == 0.0          # the idle slot


def _top_k_sets(scores, k):
    """`lax.top_k`'s set of each query (ties to the lower position), the
    real tokens only, in position order."""
    _, at = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    real = np.asarray(jnp.take_along_axis(scores, at, -1) > -jnp.inf)
    return [np.sort(a[r]) for a, r in zip(
        np.asarray(at).reshape(-1, at.shape[-1]),
        real.reshape(-1, at.shape[-1]))]


@pytest.mark.parametrize("n,k,seen", [
    (256, 16, 256),    # whole rows of 128 lanes, the selection bites
    (200, 16, 150),    # a ragged row, unwritten positions behind the query
    (96, 64, 20),      # fewer tokens than k: the rest invalid
    (384, 512, 384),   # k above the table's rows
])
def test_select_is_a_mask_and_its_compaction(n, k, seen):
    rng = np.random.RandomState(n)
    pos = jnp.asarray(rng.randint(0, seen, (2, 5)), jnp.int32)
    scores = _scores(rng, pos, n)
    idx, valid = _select(scores, k)
    mask = _select_mask(scores, k)
    assert mask.shape == scores.shape and mask.dtype == jnp.bool_
    # the two halves, put together again, are `select`
    again = jax.jit(lambda sc: sm._compact(*sm._choose(sc, k), n))(scores)
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(valid))
    want = _top_k_sets(scores, k)
    idx, valid, mask = (np.asarray(a).reshape((-1,) + a.shape[2:])
                        for a in (idx, valid, mask))
    for i, w in enumerate(want):
        # same set, in position order, the valid slots leading
        np.testing.assert_array_equal(idx[i][valid[i]], w)
        assert valid[i][:len(w)].all() and not valid[i][len(w):].any()
        np.testing.assert_array_equal(np.nonzero(mask[i])[0], w)


def _attention_inputs(rng, s, start, bs, nb, B=1):
    pool, clean, _ = _pools(rng, bs, nb, start + s, B)
    kidx = jnp.asarray(rng.randn(B * nb + 2, bs, 8), jnp.float32)
    pos = start + jnp.tile(jnp.arange(s, dtype=jnp.int32)[None], (B, 1))
    q = jnp.asarray(rng.randn(B, s, NH, W), jnp.float32)
    qi = jnp.asarray(rng.randn(B, s, 2, 8), jnp.float32)
    wi = jnp.asarray(rng.rand(B, s, 2), jnp.float32)
    return (q, qi, wi, pool, kidx, clean, pos)


def _traced_now(fn):
    """`fn` as one program, traced at this call (jax caches traces by
    function, and the tests move the constants a trace reads)."""
    return jax.jit(lambda *a: fn(*a))


def _gather_form(monkeypatch, fn, *args):
    """`fn` with every query count taken for a decode step's."""
    with monkeypatch.context() as m:
        m.setattr(pallas_latent, "KERNEL_MAX_QUERIES", 10 ** 9)
        return fn(*args)


@pytest.mark.parametrize("s", [4, 5, 32, 70])
def test_the_query_count_picks_the_form_and_the_positions_stay(
        s, monkeypatch):
    """At or under `KERNEL_MAX_QUERIES` the rows are gathered, above it
    the blocks are walked (the kernel's claim says which); either way the
    positions and `valid` are `select`'s, slot for slot."""
    from paddle_tpu.observability import xray
    args = _attention_inputs(np.random.RandomState(s), s, 19, 8, 16)
    fn = lambda *a: sm.sparse_latent_attention(          # noqa: E731
        *a, topk=12, scale=SCALE, d_latent=D)
    with xray.capture_kernel_claims() as claims:
        o, idx, valid = jax.jit(fn).lower(*args).compile()(*args)
    walked = any(c[0] == "paged_latent_chunk" for c in claims)
    assert walked == (s > pallas_latent.KERNEL_MAX_QUERIES)
    want = _gather_form(monkeypatch, _traced_now(fn), *args)
    assert float(jnp.abs(o - want[0]).max()) < TOL
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(want[2]))


def _asks_the_length(fn, *args) -> bool:
    """Does `fn`'s program hold a `cond` outside the kernel (whose
    interpreted `pl.when`s are conds too)?"""
    def conds(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "cond":
                yield eqn
            elif eqn.params.get("name") != "_chunk_pallas":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from conds(sub)
    return any(conds(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr))


def test_a_table_wider_than_the_crossing_asks_the_length(monkeypatch):
    """Past `MASKED_PASS_MAX_ROWS` rows a tile's gather is the cheaper
    form: a table that can hold so many decides by the sequence's length,
    inside the program."""
    rng = np.random.RandomState(0)
    fn = lambda *a: sm.sparse_latent_attention(          # noqa: E731
        *a, topk=12, scale=SCALE, d_latent=D)
    monkeypatch.setattr(sm, "MASKED_PASS_MAX_ROWS", 64)
    for start in (10, 80):          # ends under the crossing, and over it
        args = _attention_inputs(rng, 40, start, 8, 16)
        assert _asks_the_length(fn, *args)
        o, idx, _ = _traced_now(fn)(*args)
        want = _gather_form(monkeypatch, _traced_now(fn), *args)
        assert float(jnp.abs(o - want[0]).max()) < TOL
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want[1]))
    monkeypatch.setattr(sm, "MASKED_PASS_MAX_ROWS", 128)    # = the table
    args = _attention_inputs(rng, 40, 10, 8, 16)
    assert not _asks_the_length(fn, *args)


def test_forward_selecting_returns_the_positions_it_returned(monkeypatch):
    """The tiny model's chunk through the cache (its first queries see
    fewer rows than `index_topk`, its last several times as many):
    logits and every layer's selected positions (-1 where fewer than k
    exist) are the gather form's."""
    paddle.seed(3)
    model = GlmMoeDsaForCausalLM(glm_moe_dsa_tiny())
    model.eval()
    ids = np.random.RandomState(0).randint(1, 256, 29).astype(np.int32)

    def run():
        with paddle.no_grad():
            lg, _, sel = model.forward_selecting(
                Tensor._wrap(jnp.asarray(ids[None])),
                model.init_caches(1, block_size=8, max_context=32))
        return lg._value, sel

    (lg, sel), (lg_w, sel_w) = run(), _gather_form(monkeypatch, run)
    assert float(jnp.abs(lg - lg_w).max()) < 2e-5
    assert len(sel) == len(sel_w) > 0
    for a, b in zip(sel, sel_w):
        assert (np.asarray(a) == -1).any() and (np.asarray(a) >= 0).any()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
