"""Draft/verify speculative decoding on the serving engine's tick loop.

Role of the reference's inference-acceleration tier (the fused decoding
ops behind `fused_multi_transformer_op.cu.h` exist to make every target
forward cheaper; speculative decoding makes every target forward emit
MORE tokens): a small draft model proposes ``k`` tokens per slot inside
one compiled program, the target model judges all ``k`` proposals in a
SINGLE chunk verify forward, and per-slot accept masks keep the output
stream lossless (Leviathan et al. 2023 rejection sampling).

TPU-native shape — everything rides machinery the engine already has:

* The draft phase is a k-step ``lax.scan`` over the draft model's OWN
  paged KV pools, indexed by the SAME block table as the target (same
  physical block ids, draft-sized [d_nh, blocks, bs, d_hd] pools).
  One allocator/refcount path covers both models, and a prefix-cache
  hit shares draft KV exactly like target KV: the shared blocks were
  written to both pools at the registering admission.
* The verify forward feeds the chunk ``[last_tok, d_1..d_{k-1}]``
  through `models.kv_cache.PagedChunkView` (the PR 9 suffix-prefill
  view): per-row ``seq_lens`` offsets, writes at positions
  ``n..n+k-1``, offset causal mask against the cached prefix — chunk
  position ``j``'s logits judge ``d_{j+1}``, so k positions suffice
  (a k+1-th would score only the forgone bonus token — see below).
  Rejected positions roll back
  BY CONSTRUCTION — only ``seq_lens`` advances by the accepted count,
  stale writes beyond it are masked and overwritten by the next chunk,
  and decode positions always live in unregistered block-table columns
  (the prefix-cache immutability contract is untouched).
* Accept rule per slot: with ``a`` = leading accepted drafts, the tick
  emits ``m = 1 + min(a, k - 1)`` tokens — the accepted prefix plus
  one token chosen from the TARGET logits at the first non-emitted
  position.  Capping at ``k`` (forgoing the classic k+1-th bonus
  token) keeps the draft KV invariant "positions < seq_len are
  written" true with a single-token draft entry, so ONE compiled spec
  program serves every acceptance outcome.

LOSSLESSNESS.  Greedy rows accept iff the draft token equals the
target argmax, and every emitted token IS a target argmax over the
true emitted prefix — streams are bit-identical to the plain engine.
Sampled rows draw the draft from the per-slot filtered distribution
``q``, accept token ``d`` with probability ``min(1, p(d)/q(d))``
against the target's filtered ``p``, and correct rejections from
``max(p - q, 0)`` renormalized — the standard proof gives emitted
tokens exactly ``p``-distributed.  All randomness is derived from
``fold_in(fold_in(key(seed), tag), position)`` with disjoint tags for
draft/accept/residual draws, so each (seed, position, tag) uniform is
consumed at most once across rounds and the sampled stream is a pure
function of the request seed — reproducible, and invariant to
``spec_k``, tick boundaries, and overlap.

PER-SLOT ELIGIBILITY (ISSUE 13).  Every spec-tick variant takes a
per-slot ``kcap`` device input — the emitted-count ceiling
``min(k, remaining budget)`` — and the emit rule becomes
``m = min(1 + min(a, k-1), kcap)`` per slot.  One short-budget slot no
longer demotes the whole tick to the plain path: it just emits at most
its own cap while full-budget slots ride the full k.  Truncation
preserves the losslessness arguments verbatim: greedy emissions are a
prefix of the uncapped emission (every token still a target argmax
over its true prefix), and for sampling the uniforms at positions
``>= seq + kcap`` never condition any emitted token (``a`` beyond the
cap cannot change ``m`` or the emitted prefix), so re-drawing those
positions next tick is sound — the same argument that already covered
positions beyond a rejection.

MODEL-FREE DRAFTING (ISSUE 13).  `build_hostdraft_tick` is the spec
tick with the draft phase DELETED: the k proposed tokens arrive as a
device INPUT (the host's per-request n-gram table proposes them —
`inference/drafting.py`), so there is no draft model, no draft pools,
no draft prefill, and the verify chunk + accept/emit tail are reused
unchanged.  A deterministic proposal is the point mass ``q =
one_hot(d)``, under which the rejection correction degenerates
cleanly: accept ``u <= p(d)``, residual ``max(p - one_hot(d), 0)`` =
``p`` with ``d``'s mass removed — still exactly ``p``-distributed
output by the standard proof, built in-trace from the token input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["accept_and_choose", "build_spec_tick", "build_hostdraft_tick"]

# disjoint PRNG stream tags: fold_in(fold_in(key(seed), TAG), position)
DRAFT_FOLD = 0x51
ACCEPT_FOLD = 0x52
RESID_FOLD = 0x53


def _keys_at(seeds, pos, tag):
    """[B] seeds x ([B] or [B, k]) positions -> per-element PRNG keys
    for one of the three spec streams."""
    base = jax.vmap(lambda s: jax.random.fold_in(
        jax.random.key(s), tag))(seeds)
    if pos.ndim == 1:
        return jax.vmap(jax.random.fold_in)(base, pos)
    return jax.vmap(lambda kb, prow: jax.vmap(
        lambda p: jax.random.fold_in(kb, p))(prow))(base, pos)


def accept_and_choose(tlogits, dtoks, dprobs, do_sample, temperature,
                      top_k, top_p, seeds, seq_lens):
    """Vectorized accept masks + token choice over one verify forward.

    tlogits: [B, S >= k, V] target logits — chunk position ``j``
    judges draft token ``d_{j+1}``, so only the first k positions are
    read; dtoks: [B, k] draft tokens; dprobs: [B, k, V] draft
    FILTERED softmax (zeros for greedy-only batches); seq_lens: [B]
    dispatch-time lengths (position base for the accept/residual PRNG
    streams).  Returns ``(chosen [B, k], m [B], a [B], new_last [B])``:
    the per-position target-chosen tokens, the emitted count
    ``1 + min(a, k-1)``, the raw leading-accept count, and the token at
    the new stream head.  Callers mask inactive rows.
    """
    from ..models.generation import _process_logits_tokens
    B, k = dtoks.shape
    tl = tlogits[:, :k, :]
    t_greedy = jnp.argmax(tl, axis=-1).astype(jnp.int32)
    greedy_acc = dtoks == t_greedy

    def drawn():
        # target filtered distribution p at every scored position
        tfilt = _process_logits_tokens(tl.astype(jnp.float32),
                                       temperature, top_k, top_p)
        p = jax.nn.softmax(tfilt, axis=-1)
        pd = jnp.take_along_axis(p, dtoks[..., None], axis=-1)[..., 0]
        qd = jnp.take_along_axis(dprobs, dtoks[..., None], axis=-1)[..., 0]
        pos = seq_lens[:, None] + jnp.arange(k, dtype=seq_lens.dtype)
        u = jax.vmap(jax.vmap(jax.random.uniform))(
            _keys_at(seeds, pos, ACCEPT_FOLD))
        # u < p(d)/q(d), division-free (d was drawn from q, so qd > 0)
        acc_s = u * qd <= pd
        resid = jnp.maximum(p - dprobs, 0.0)
        # a rejection with an all-zero residual is impossible in exact
        # arithmetic (p == q makes the accept probability 1); guard the
        # float corner by falling back to the target distribution
        resid = jnp.where(jnp.sum(resid, axis=-1, keepdims=True) > 0,
                          resid, p)
        corr_s = jax.vmap(jax.vmap(jax.random.categorical))(
            _keys_at(seeds, pos, RESID_FOLD),
            jnp.log(resid)).astype(jnp.int32)
        ds = do_sample[:, None]
        return (jnp.where(ds, acc_s, greedy_acc),
                jnp.where(ds, corr_s, t_greedy))

    acc, corr = jax.lax.cond(jnp.any(do_sample), drawn,
                             lambda: (greedy_acc, t_greedy))
    chosen = jnp.where(acc, dtoks, corr)
    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
    m = 1 + jnp.minimum(a, k - 1)
    new_last = jnp.take_along_axis(chosen, (m - 1)[:, None], axis=1)[:, 0]
    return chosen, m.astype(jnp.int32), a.astype(jnp.int32), new_last


def _draft_phase(eng, dpools, tables, seq_lens, last_tok, do_sample,
                 temperature, top_k, top_p, seeds, k):
    """k-step draft scan (traced): propose one token per step from the
    draft model's paged caches.  Returns ``(dtoks [B, k], dprobs
    [B, k, V], dpools)`` — dprobs is the filtered draft softmax the
    accept test needs (zeros when no row samples: the `lax.cond` skips
    the [B, V] sort exactly like the plain tick's `_next_tokens`)."""
    from ..framework.dygraph import no_grad
    from ..framework.tensor import Tensor
    from ..models.generation import _process_logits_rows
    from ..models.kv_cache import PagedKVCache

    def body(carry, _):
        pools, lens, last = carry
        views = [PagedKVCache.from_parts(*layer, tables, lens, eng.bs)
                 for layer in pools]
        with no_grad():
            logits_t, new_views = eng.draft.forward_with_cache(
                Tensor._wrap(last[:, None]), views,
                pos_offset=Tensor._wrap(lens[:, None]))
        logits = logits_t._value[:, -1, :]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def drawn():
            filt = _process_logits_rows(logits.astype(jnp.float32),
                                        temperature, top_k, top_p)
            samp = jax.vmap(jax.random.categorical)(
                _keys_at(seeds, lens, DRAFT_FOLD), filt).astype(jnp.int32)
            return (jnp.where(do_sample, samp, greedy),
                    jax.nn.softmax(filt, axis=-1))

        nxt, probs = jax.lax.cond(
            jnp.any(do_sample), drawn,
            lambda: (greedy, jnp.zeros(logits.shape, jnp.float32)))
        active = lens > 0
        nxt = jnp.where(active, nxt, 0)
        lens = jnp.where(active, lens + 1, 0)
        new_pools = [c.pools for c in new_views]
        return (new_pools, lens, nxt), (nxt, probs)

    (dpools, _, _), (toks, probs) = jax.lax.scan(
        body, (dpools, seq_lens, last_tok), None, length=k)
    return jnp.transpose(toks), jnp.transpose(probs, (1, 0, 2)), dpools


def _finish(eng, tlogits, dtoks, dprobs, do_sample, temperature, top_k,
            top_p, seeds, seq_lens, kcap):
    """Shared accept tail of every spec-tick variant: cap the emitted
    count at the slot's ``kcap`` (per-slot eligibility — a short-budget
    slot emits at most its own remaining budget while the rest of the
    batch rides the full k), mask inactive rows, advance lengths by the
    emitted count."""
    chosen, m, a, new_last = accept_and_choose(
        tlogits, dtoks, dprobs, do_sample, temperature, top_k, top_p,
        seeds, seq_lens)
    m = jnp.minimum(m, jnp.maximum(kcap.astype(jnp.int32), 1))
    new_last = jnp.take_along_axis(chosen, (m - 1)[:, None], axis=1)[:, 0]
    active = seq_lens > 0
    counts = jnp.where(active, m, 0).astype(jnp.int32)
    accepts = jnp.where(active, a, 0).astype(jnp.int32)
    new_lens = seq_lens + counts
    new_last = jnp.where(active, new_last, 0)
    return chosen, counts, accepts, new_lens, new_last


def _verify(eng, params, pools, tables, seq_lens, last_tok, dtoks, k):
    """The target's one chunk forward over ``[last, d_1..d_{k-1}]``,
    through the engine's forward seam and its verify view (the paged
    spec-verify Pallas kernel by default; `PagedChunkView` dense when
    FLAGS_serving_pallas_verify is off).  k positions: position j's
    logits judge d_{j+1}, and the max emit m = k needs KV only through
    position n+k-1 (d_k, when emitted, becomes the NEXT tick's
    last_tok).  Including d_k would score a k+1-th position whose logits
    and KV write are provably never consumed — ~1/(k+1) of the verify
    forward for nothing; causal masking makes the other positions'
    logits bit-identical either way.  Returns ``(logits [B, k, V],
    pools)``; token choice sees the full logits in every mode (under TP
    they are replicated), which is the bit-parity contract."""
    forward = eng._forward(params)
    chunk = jnp.concatenate([last_tok[:, None], dtoks[:, :k - 1]], axis=1)
    return forward(chunk, pools, tables, seq_lens, seq_lens[:, None],
                   eng._verify_view_cls)


def build_spec_tick(eng, k):
    """Model-draft spec tick body: draft scan -> one k-token chunk
    verify forward -> accept/choose.  The draft phase runs on the
    draft's own (small) weights and pools, REPLICATED under TP, while
    the verify is the engine's forward — sharded there, so the expensive
    model scores the chunk at 1/tp weights per rank.  Returns ``(toks
    [B,k], counts, accepts, new_lens, new_last, pools, dpools)`` — the
    lens/last outputs are the device carry an overlapped next tick
    chains on."""

    def tick(params, draft_vals, pools, dpools, tables, seq_lens,
             last_tok, do_sample, temperature, top_k, top_p, seeds,
             kcap):
        eng._bind_draft(draft_vals)
        dtoks, dprobs, dpools = _draft_phase(
            eng, dpools, tables, seq_lens, last_tok, do_sample,
            temperature, top_k, top_p, seeds, k)
        logits, pools = _verify(eng, params, pools, tables, seq_lens,
                                last_tok, dtoks, k)
        out = _finish(eng, logits, dtoks, dprobs, do_sample,
                      temperature, top_k, top_p, seeds, seq_lens, kcap)
        return out + (pools, dpools)

    return tick


def build_hostdraft_tick(eng, k):
    """Host-drafted spec tick body: NO draft phase — the k proposed
    tokens ride in as a device input (``dtoks [B, k]``, proposed by the
    per-request n-gram table on the host at ~zero cost), the verify
    chunk and accept/emit tail are the model-draft path's, verbatim.
    The proposal distribution is the point mass ``one_hot(dtoks)``,
    under which the rejection test reduces to ``u <= p(d)`` and the
    residual to ``p`` minus ``d``'s mass (see the module docstring).
    Returns ``(toks [B,k], counts, accepts, new_lens, new_last,
    pools)`` — no draft pools to thread."""

    def tick(params, pools, tables, seq_lens, last_tok, dtoks,
             do_sample, temperature, top_k, top_p, seeds, kcap):
        logits, pools = _verify(eng, params, pools, tables, seq_lens,
                                last_tok, dtoks, k)
        dprobs = jax.nn.one_hot(dtoks, logits.shape[-1],
                                dtype=jnp.float32)
        out = _finish(eng, logits, dtoks, dprobs, do_sample,
                      temperature, top_k, top_p, seeds, seq_lens, kcap)
        return out + (pools,)

    return tick
