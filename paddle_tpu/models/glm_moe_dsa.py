"""GLM-5 (`model_type: glm_moe_dsa`): multi-head latent attention (MLA)
with a learned sparse index (DeepSeek sparse attention, DSA) and a
DeepSeek-V3 style mixture of experts.

Block `l`: `x = h + Attn(RMSNorm(h))`, `h' = x + FFN(RMSNorm(x))`; FFN is
a dense SwiGLU for `l < first_k_dense_replace` and `HeldExpertsLayer`
(sigmoid scores, selection bias, top-k, one shared expert, no drops)
after.  RMSNorm, SwiGLU and RoPE are `models/llama.py`'s.

Attention keeps, a token, one latent row `c_kv | k_rope` shared by every
head and one indexer key.  Each query scores all cached tokens with the
indexer, keeps the `index_topk` best and attends only to those.  Two
forms of the same mathematics:

* `forward` (no cache): the plain multi-head form; every head's `k_nope`
  and `v` are expanded from the latent, the selection is a dense mask.
* `forward_with_cache` over `LatentPagedCache` views (a decode step, a
  prefill chunk, a prefill from empty): the absorbed form; `W_kvb`'s key
  half is folded into the query and its value half into the output, so
  scores are taken against the cached latent rows themselves
  (`ops/sparse_mla.py`).  With per-query selections the heads share no
  expanded keys, so the absorbed form is the cheaper one in a prefill
  chunk too.

The chip's share: `n_experts_held` of the router's `n_routed_experts`,
from `expert_offset`; the vocabulary is `vocab_size` rows (a slice is a
smaller vocabulary).  Not modelled: the multi-token-prediction module
(next-token logits do not pass through it), and the FP8 / Hadamard form of
the released indexer kernels.  Inference only.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe import (HeldExpertsLayer,
                                              SigmoidTopKGate)
from ..incubate.nn.functional import fused_rotary_position_embedding
from .generation import GenerationMixin
from .kv_cache import CacheSpec, LatentPagedCache, PoolRow
from .llama import LlamaMLP

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "glm_moe_dsa_tiny"]


@dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_layers: int = 78
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256          # the router's width
    n_experts_held: int = 0              # 0 -> all of them
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    max_seq_len: int = 202752
    rms_eps: float = 1e-5
    rope_base: float = 1e6
    # parameters are created a block at a time and cast to this at once:
    # the held share at published widths is 3.9e9 parameters, and a whole
    # float32 copy (what a cast after construction needs) fills a chip
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.n_experts_held == 0:
            self.n_experts_held = self.n_routed_experts


def _rope(x, pos, base):
    """Interleaved-pair RoPE (`rope_interleave`) of `[B, s, h, d]` arrays
    at positions `pos` (`[s]` or `[B, s]`), through the fused rope op."""
    out, _, _ = fused_rotary_position_embedding(
        Tensor._wrap(x), None, None, position_ids=pos,
        use_neox_rotary_style=False, rotary_emb_base=base)
    return out._value


class Indexer(nn.Layer):
    """The lightning indexer: `q^I = c_q W_q`, `k^I = LayerNorm(x W_k)`,
    RoPE on the first `rope` values of each, head weights `w = x W_w`
    scaled by `Hi^-1/2 Di^-1/2`."""

    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__()
        self.cfg = cfg
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        self.wq_b = nn.Linear(cfg.q_lora_rank, hi * di, bias_attr=False)
        self.wk = nn.Linear(cfg.hidden_size, di, bias_attr=False)
        self.k_norm = nn.LayerNorm(di, epsilon=1e-6)
        self.weights_proj = nn.Linear(cfg.hidden_size, hi, bias_attr=False)

    def forward(self, x, c_q, pos):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, \
            cfg.qk_rope_head_dim
        q = self.wq_b(c_q)._value.reshape(b, s, hi, di)
        k = self.k_norm(self.wk(x))._value.reshape(b, s, 1, di)
        q = jnp.concatenate(
            [_rope(q[..., :dr], pos, cfg.rope_base), q[..., dr:]], -1)
        k = jnp.concatenate(
            [_rope(k[..., :dr], pos, cfg.rope_base), k[..., dr:]], -1)
        w = self.weights_proj(x)._value * (hi ** -0.5 * di ** -0.5)
        return q, k[:, :, 0], w


class GlmMoeDsaAttention(nn.Layer):
    """MLA, with the sparse index where the configuration has one
    (`index_topk` > 0) and over every cached row where it has none
    (`glm4_moe_lite`; `shift` is its multi-token-prediction module's
    cache layout, `LatentDenseCache.append_and_attend`)."""

    def __init__(self, cfg, shift: int = 0):
        super().__init__()
        self.cfg = cfg
        self.shift = shift
        H, nh = cfg.hidden_size, cfg.num_heads
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)   # noqa: E731
        self.q_a_proj = lin(H, cfg.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_eps)
        self.q_b_proj = lin(cfg.q_lora_rank, nh * (dn + dr))
        self.kv_a_proj = lin(H, cfg.kv_lora_rank + dr)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_eps)
        self.kv_b_proj = lin(cfg.kv_lora_rank, nh * (dn + dv))
        self.o_proj = lin(nh * dv, H)
        self.indexer = Indexer(cfg) if getattr(cfg, "index_topk", 0) \
            else None

    def forward(self, x, cache=None):
        """Without a cache: the attention's output.  Over a cache view:
        (output, the advanced view, the positions each query selected
        `[B, s, k]`, -1 where fewer than k exist; None without an
        index)."""
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        nh, dc = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        scale = 1.0 / math.sqrt(dn + dr)
        if cache is None:
            pos = jnp.arange(s)
        else:
            pos = cache.seq_lens[:, None] + jnp.arange(
                s, dtype=cache.seq_lens.dtype)
        with jax.named_scope("mla_proj"):
            c_q = self.q_a_layernorm(self.q_a_proj(x))
            q = self.q_b_proj(c_q)._value.reshape(b, s, nh, dn + dr)
            kva = self.kv_a_proj(x)
            c_kv = self.kv_a_layernorm(Tensor._wrap(kva._value[..., :dc]))
            q_rope = _rope(q[..., dn:], pos, cfg.rope_base)
            k_rope = _rope(kva._value[..., dc:].reshape(b, s, 1, dr), pos,
                           cfg.rope_base)[:, :, 0]
            q_i, k_i, w_i = self.indexer(x, c_q, pos) \
                if self.indexer is not None else (None, None, None)
            w_kvb = self.kv_b_proj.weight._value.reshape(dc, nh, dn + dv)
        if cache is None:
            o = self._dense(q[..., :dn], q_rope, c_kv, k_rope, q_i, k_i, w_i,
                            scale)
            return self.o_proj(Tensor._wrap(o.reshape(b, s, nh * dv)))
        with jax.named_scope("mla_proj"):
            q_abs = jnp.einsum("bthd,chd->bthc", q[..., :dn],
                               w_kvb[..., :dn])
            q_cat = jnp.concatenate([q_abs.astype(q.dtype), q_rope], -1)
            row = jnp.concatenate([c_kv._value, k_rope], -1)
        if self.indexer is None:
            new_cache, o_lat = cache.append_and_attend(
                q_cat, row, scale=scale, d_latent=dc, shift=self.shift)
            selected = None
        else:
            new_cache, o_lat, selected = cache.append_and_attend(
                q_cat, q_i, w_i, row, k_i, topk=cfg.index_topk, scale=scale,
                d_latent=dc)
        with jax.named_scope("mla_proj"):
            o = jnp.einsum("bthc,chv->bthv", o_lat.astype(q.dtype),
                           w_kvb[..., dn:])
            out = self.o_proj(Tensor._wrap(o.reshape(b, s, nh * dv)))
        return out, new_cache, selected

    def _dense(self, q_nope, q_rope, c_kv, k_rope, q_i, k_i, w_i, scale):
        """The plain multi-head form over the whole sequence, selection
        as a dense `[s, s]` mask (small sequences: tests, `forward`)."""
        cfg = self.cfg
        b, s, nh, dn = q_nope.shape
        f32 = jnp.float32
        kv = self.kv_b_proj(c_kv)._value.reshape(b, s, nh, -1)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        causal = jnp.tril(jnp.ones((s, s), bool))
        if self.indexer is None:
            keep = jnp.broadcast_to(causal, (b, s, s))
        else:
            keep = self._selected_mask(q_i, k_i, w_i, causal)
        sc = (jnp.einsum("bthd,bkhd->bhtk", q_nope, k_nope,
                         preferred_element_type=f32)
              + jnp.einsum("bthd,bkd->bhtk", q_rope, k_rope,
                           preferred_element_type=f32)) * scale
        p = jax.nn.softmax(jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhtk,bkhd->bthd", p.astype(v.dtype), v)

    def _selected_mask(self, q_i, k_i, w_i, causal):
        """`[b, s, s]`: the keys each query's index selects."""
        cfg, f32 = self.cfg, jnp.float32
        b, s = q_i.shape[0], q_i.shape[1]
        idx = jnp.einsum(
            "bthk,bth->btk",
            jax.nn.relu(jnp.einsum("bthd,bkd->bthk", q_i, k_i,
                                   preferred_element_type=f32)),
            w_i.astype(f32))
        idx = jnp.where(causal, idx, -jnp.inf)
        top_i = jax.lax.top_k(idx, min(cfg.index_topk, s))[1]
        return causal & jnp.zeros((b, s, s), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
            top_i].set(True)


class GlmMoeDsaBlock(nn.Layer):
    def __init__(self, cfg, layer_idx: int, shift: int = 0):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.self_attn = GlmMoeDsaAttention(cfg, shift)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_eps)
        mlp = lambda width: LlamaMLP(SimpleNamespace(         # noqa: E731
            hidden_size=cfg.hidden_size, intermediate_size=width,
            tensor_parallel=False))
        self.is_moe = layer_idx >= cfg.first_k_dense_replace
        if self.is_moe:
            self.mlp = HeldExpertsLayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                SigmoidTopKGate(cfg.hidden_size, cfg.n_routed_experts,
                                cfg.num_experts_per_tok,
                                cfg.routed_scaling_factor),
                n_experts_held=cfg.n_experts_held,
                expert_offset=cfg.expert_offset,
                shared=mlp(cfg.moe_intermediate_size
                           * cfg.n_shared_experts))
        else:
            self.mlp = mlp(cfg.intermediate_size)

    def forward(self, x, cache=None):
        """Without a cache: the block's output.  Over a cache view:
        (output, the advanced view, the attention's selected positions).
        An inactive sequence of the batch (an idle slot of a decode step:
        `cache.active`) is routed to no expert and counted nowhere; the
        rows a chunk is padded with are not told apart and are."""
        if cache is None:
            x = x + self.self_attn(self.input_layernorm(x))
            return x + self.mlp(self.post_attention_layernorm(x))
        a, new, selected = self.self_attn(self.input_layernorm(x), cache)
        x = x + a
        h = self.post_attention_layernorm(x)
        if not self.is_moe:
            return x + self.mlp(h), new, selected
        y, rows = self.mlp.forward_counted(
            h, jnp.repeat(cache.active, a.shape[1]))
        # rows given to each held expert, by kind of program:
        # [decode step or tick | chunk] x [rows | experts hit] x [held]
        kind = 0 if a.shape[1] == 1 or getattr(cache, "in_tick", False) \
            else 1
        new = new.replace(moe_rows=new.moe_rows.at[kind].add(
            jnp.stack([rows, (rows > 0).astype(rows.dtype)])))
        return x + y, new, selected


class GlmMoeDsaModel(nn.Layer):
    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size).astype(cfg.param_dtype)
        self.layers = nn.LayerList([
            GlmMoeDsaBlock(cfg, i).astype(cfg.param_dtype)
            for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size,
                               cfg.rms_eps).astype(cfg.param_dtype)

    def forward(self, input_ids, caches=None):
        """Without caches: the last hidden states.  Over one view a
        layer: (hidden states, the advanced views, each layer's selected
        positions)."""
        x = self.embed_tokens(input_ids)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new, selected = [], []
        for layer, cache in zip(self.layers, caches):
            x, c, sel = layer(x, cache)
            new.append(c)
            selected.append(sel)
        return self.norm(x), new, selected


class GlmMoeDsaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GlmMoeDsaModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False).astype(cfg.param_dtype)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def forward_with_cache(self, input_ids, caches, pos_offset=0):
        """Positions come from each view's `seq_lens`; `pos_offset` is
        accepted for the engine's one calling convention."""
        logits, new, _ = self.forward_selecting(input_ids, caches)
        return logits, new

    def forward_selecting(self, input_ids, caches):
        """`forward_with_cache` that also returns what the sparse
        attention of each layer selected: `[B, s, index_topk]` positions
        a layer, -1 where a query has fewer (what a check compares with
        a reference's selection)."""
        h, new, selected = self.model(input_ids, caches)
        with jax.named_scope("lm_head"):
            return self.lm_head(h), new, selected

    def cache_spec(self) -> CacheSpec:
        """A latent pool and an index-key pool a layer under one block
        table, and the layer's expert-row counts beside them.  A latent
        row (576 values) is kept in whole tiles of 128 lanes (640): the
        TPU tiles it so in any case, and with the pad in the shape no
        program re-lays-out the pool at its entry and exit."""
        from ..ops.sparse_mla import padded_width
        cfg = self.cfg
        why = ("the latent (MLA) and index-key pools and the held-expert "
               "layer of glm_moe_dsa have no {} path")
        return CacheSpec(
            cfg.num_layers,
            (PoolRow("ckv", trail=(padded_width(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim),)),
             PoolRow("kidx", trail=(cfg.index_head_dim,)),
             PoolRow("moe_rows", lead=(2, 2, cfg.n_experts_held),
                     dtype=jnp.int32, paged=False)),
            LatentPagedCache,
            attend_limit=cfg.index_topk,
            unsupported={
                "tp_degree": why.format("tensor-parallel (head-sharded)"),
                "draft_model": why.format("draft-model"),
                "spec_decode": why.format("speculative-verify"),
                "quant": why.format("weight-quantized")})

    def init_caches(self, batch_size, cache_impl: str = "paged",
                    block_size: int = None, max_context=None):
        if cache_impl != "paged":
            raise ValueError(
                "glm_moe_dsa caches latent rows and index keys in paged "
                f"pools; cache_impl={cache_impl!r} is not available")
        bs = block_size or 64
        nb = -(-(max_context or self.cfg.max_seq_len) // bs)
        dtype = self.model.embed_tokens.weight._value.dtype
        tables = (1 + jnp.arange(batch_size * nb, dtype=jnp.int32)
                  ).reshape(batch_size, nb)
        lens = jnp.zeros((batch_size,), jnp.int32)
        return [LatentPagedCache(*pools, tables, lens, bs) for pools in
                self.cache_spec().init_pools(batch_size * nb, bs, dtype)]

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> float:
        """Forward FLOPs a token as this share runs it: 2 x the matmul
        parameters a token meets (the held experts it is routed to, on
        average `top_k * held / width` of them), plus the indexer's
        scores over the context and the absorbed attention over the
        selection."""
        cfg = self.cfg
        ctx = seq_len or cfg.max_seq_len
        per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        n_moe = cfg.num_layers - cfg.first_k_dense_replace
        routed = sum(p.size for n, p in self.named_parameters()
                     if ".mlp.experts." in n)
        met = (self.num_params() - routed
               - self.model.embed_tokens.weight.size
               + n_moe * per_expert * cfg.num_experts_per_tok
               * cfg.n_experts_held / cfg.n_routed_experts)
        sel = min(ctx, cfg.index_topk)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        attn = cfg.num_layers * (
            2 * ctx * cfg.index_n_heads * cfg.index_head_dim
            + 2 * sel * cfg.num_heads * (width + cfg.kv_lora_rank))
        return 2.0 * met + attn


def glm_moe_dsa_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing wide."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
                qk_rope_head_dim=4, v_head_dim=16, index_n_heads=2,
                index_head_dim=8, index_topk=8, intermediate_size=96,
                moe_intermediate_size=32, n_routed_experts=8,
                num_experts_per_tok=2, first_k_dense_replace=1,
                max_seq_len=256)
    base.update(kw)
    return GlmMoeDsaConfig(**base)
