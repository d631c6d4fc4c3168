"""Autoregressive generation with KV caches.

Parity: the reference's `paddlenlp`-style `model.generate` surface
(greedy / temperature / top-k / top-p sampling, eos early stop) reduced to
the decoding core.  Eager host loop over single-token steps: the prefill
runs the full prompt once, then each step feeds one token against the
per-layer KV caches (attention is O(1) new work per step).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..framework.tensor import Tensor

__all__ = ["GenerationMixin"]


def _process_logits_rows(logits, temperature, top_k, top_p):
    """Row-wise `_process_logits`: every sampling parameter is a [B]
    array, so one compiled program can filter a batch whose rows carry
    DIFFERENT temperature/top-k/top-p (the serving engine's per-slot
    sampling inputs).  Rows with ``top_k <= 0`` / ``top_p >= 1`` skip
    that filter, matching the scalar version's Python branches, and the
    top-p cutoff is computed on the already top-k-filtered logits in the
    same order the scalar version applies them.

    logits: jnp (B, V) float; temperature/top_p float [B]; top_k int [B].
    """
    V = logits.shape[-1]
    logits = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k: threshold at the k-th largest (ascending index V - k)
    asc = jnp.sort(logits, axis=-1)
    kth = jnp.take_along_axis(
        asc, jnp.clip(V - top_k, 0, V - 1)[:, None], axis=-1)
    logits = jnp.where((top_k > 0)[:, None] & (logits < kth),
                       -jnp.inf, logits)
    # top-p: smallest set with cumulative prob >= top_p, over the
    # top-k-filtered distribution (exp(-inf) rows contribute 0)
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jnp.exp(sorted_l - jnp.max(sorted_l, axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.clip(jnp.sum(cum < top_p[:, None], axis=-1), 0, V - 1)
    pth = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
    logits = jnp.where((top_p < 1.0)[:, None] & (logits < pth),
                       -jnp.inf, logits)
    return logits


def _process_logits_tokens(logits, temperature, top_k, top_p):
    """k-token twin of `_process_logits_rows` for the speculative-decode
    verify forward: ``logits`` is [B, S, V] (one row per scored chunk
    position) and each SLOT's sampling params apply to every one of its
    S positions.  Row-major flatten keeps slot b's position s at index
    ``b * S + s``, so `jnp.repeat(params, S)` lines the params up with
    the flattened rows exactly.

    logits: jnp (B, S, V) float; temperature/top_p float [B]; top_k
    int [B].  Returns filtered logits, same shape.
    """
    B, S, V = logits.shape
    rows = _process_logits_rows(
        logits.reshape(B * S, V), jnp.repeat(temperature, S),
        jnp.repeat(top_k, S), jnp.repeat(top_p, S))
    return rows.reshape(B, S, V)


def _process_logits(logits, temperature, top_k, top_p):
    """logits: (B, V) -> filtered logits ready for sampling, computed
    where the logits already are: a numpy array (the serving engine's
    host sampler, whose row was harvested for the watchdog anyway) is
    filtered with numpy and never goes back to the device; a jax array
    is filtered with jnp."""
    xp = np if isinstance(logits, np.ndarray) else jnp
    if temperature != 1.0:
        logits = logits / max(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = xp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = xp.where(logits < kth, -xp.inf, logits)
    if top_p < 1.0:
        sorted_l = xp.sort(logits, axis=-1)[:, ::-1]
        probs = xp.exp(sorted_l - xp.max(sorted_l, axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        cum = xp.cumsum(probs, axis=-1)
        # keep the smallest set with cumulative prob >= top_p
        cutoff_idx = xp.sum(cum < top_p, axis=-1)
        kth = xp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = xp.where(logits < kth, -xp.inf, logits)
    return logits


class GenerationMixin:
    """Requires the model to implement
    `forward_with_cache(input_ids, caches, pos_offset) -> (logits, caches)`
    and `init_caches(batch_size) -> caches`."""

    def _compiled_generate(self, ids, max_new_tokens, do_sample,
                           temperature, top_k, top_p, eos_token_id,
                           cache_impl="static"):
        """Whole-generation XLA program: prefill + a `lax.scan` over
        decode steps compile into ONE dispatch.

        The eager host loop pays a host->device round trip per op per
        token — thousands of dispatches for one generation; here the entire generation is one program (the
        design the reference serves through its fused decoding ops,
        `fused_multi_transformer_op.cu`).  Sequences that hit eos are
        padded with eos to the full length (same contract as the eager
        loop's docstring; no early host exit inside a compiled loop).

        cache_impl="static": fixed [B, max_seq_len] buffers.
        cache_impl="paged": `PagedKVCache` block pool sized to
        prompt + max_new_tokens; the pools and seq_lens ride the scan
        carry, the paged Pallas kernel attends through the block table —
        the reference's `block_multi_head_attention` seat, compiled."""
        import jax
        from ..framework.dygraph import no_grad

        cap = getattr(getattr(self, "cfg", None), "max_seq_len", None)
        if cap is not None and ids.shape[1] + max_new_tokens > cap:
            # inside the compiled loop the cache length is a tracer, so the
            # eager overflow guard can't fire — check before compiling
            # (position embeddings bound BOTH cache impls)
            raise ValueError(
                f"prompt ({ids.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len ({cap})")
        sd = self.state_dict()
        keys = sorted(sd.keys())
        cache_key = (tuple(ids.shape), max_new_tokens, bool(do_sample),
                     float(temperature), int(top_k), float(top_p),
                     eos_token_id, str(ids.dtype), cache_impl)
        store = getattr(self, "_static_gen_programs", None)
        if store is None:
            store = self._static_gen_programs = {}
        fn = store.get(cache_key)
        if fn is None:
            init_kwargs = {"cache_impl": cache_impl}
            if cache_impl == "paged":
                init_kwargs["max_context"] = \
                    ids.shape[1] + max_new_tokens

            def gen(param_vals, pids, rng_key):
                for kk, vv in zip(keys, param_vals):
                    sd[kk]._value = vv
                B, prompt_len = pids.shape
                with no_grad():
                    caches = self.init_caches(B, **init_kwargs)
                    logits_t, caches = self.forward_with_cache(
                        Tensor._wrap(pids), caches, pos_offset=0)
                logits0 = logits_t._value[:, -1, :]
                finished0 = jnp.zeros((B,), bool)

                def body(carry, step):
                    logits, caches, finished = carry
                    if do_sample:
                        filtered = _process_logits(
                            logits.astype(jnp.float32), temperature,
                            top_k, top_p)
                        nxt = jax.random.categorical(
                            jax.random.fold_in(rng_key, step), filtered,
                            axis=-1)
                    else:
                        nxt = jnp.argmax(logits, axis=-1)
                    nxt = nxt.astype(pids.dtype)
                    if eos_token_id is not None:
                        nxt = jnp.where(finished, eos_token_id, nxt)
                        finished = finished | (nxt == eos_token_id)
                    lt, caches = self.forward_with_cache(
                        Tensor._wrap(nxt[:, None]), caches,
                        pos_offset=prompt_len + step)
                    return (lt._value[:, -1, :], caches, finished), nxt

                with no_grad():
                    (_, _, _), toks = jax.lax.scan(
                        body, (logits0, caches, finished0),
                        jnp.arange(max_new_tokens))
                return jnp.concatenate([pids, toks.T], axis=1)

            fn = store[cache_key] = jax.jit(gen)
        orig = {k: sd[k]._value for k in keys}
        try:
            import jax as _jax
            key = _random.next_key() if do_sample else _jax.random.key(0)
            out = fn([orig[k] for k in keys], ids, key)
            return Tensor._wrap(out)
        finally:
            for k in keys:
                sd[k]._value = orig[k]

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 cache_impl: str = "dense") -> Tensor:
        """Returns (B, prompt_len + <=max_new_tokens) int ids; after a
        sequence hits eos it is padded with eos.

        cache_impl="paged" (models supporting it) decodes against
        block-paged KV caches via the Pallas paged-attention kernel
        inside the whole-generation compiled program; "paged_eager"
        keeps the host decode loop over a `BlockKVCache` (the
        continuous-batching building block with free()/join)."""
        was_training = self.training
        self.eval()
        try:
            ids = input_ids._value if isinstance(input_ids, Tensor) \
                else jnp.asarray(input_ids)
            if ids.ndim == 1:
                ids = ids[None, :]
            B, prompt_len = ids.shape
            import inspect
            sig = inspect.signature(self.init_caches)
            if cache_impl in ("static", "paged") \
                    and "cache_impl" in sig.parameters \
                    and ("max_context" in sig.parameters
                         or cache_impl == "static"):
                return self._compiled_generate(
                    ids, max_new_tokens, do_sample, temperature, top_k,
                    top_p, eos_token_id, cache_impl=cache_impl)
            if cache_impl == "paged_eager":
                cache_impl = "paged"  # host-loop BlockKVCache path
            if "cache_impl" in sig.parameters:
                caches = self.init_caches(B, cache_impl=cache_impl)
            elif cache_impl != "dense":
                raise ValueError(
                    f"{type(self).__name__} supports only dense caches")
            else:
                caches = self.init_caches(B)
            logits_t, caches = self.forward_with_cache(
                Tensor._wrap(ids), caches, pos_offset=0)
            logits = logits_t._value[:, -1, :]

            out = [ids]
            finished = jnp.zeros((B,), bool)
            for step in range(max_new_tokens):
                if do_sample:
                    filtered = _process_logits(
                        logits.astype(jnp.float32), temperature, top_k,
                        top_p)
                    import jax
                    nxt = jax.random.categorical(_random.next_key(),
                                                 filtered, axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(ids.dtype)
                if eos_token_id is not None:
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                out.append(nxt[:, None])
                if eos_token_id is not None and bool(finished.all()):
                    break
                logits_t, caches = self.forward_with_cache(
                    Tensor._wrap(nxt[:, None]), caches,
                    pos_offset=prompt_len + step)
                logits = logits_t._value[:, -1, :]
            return Tensor._wrap(jnp.concatenate(out, axis=1))
        finally:
            if was_training:
                self.train()
