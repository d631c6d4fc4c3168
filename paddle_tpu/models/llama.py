"""Llama-2 model family (BASELINE config 5: Llama-2 7B semi-auto parallel).

Architecture: RMSNorm pre-norm, SwiGLU MLP, rotary embeddings, no biases —
matching the reference ecosystem's `semi_auto_llama.py`
(`test/auto_parallel/hybrid_strategy/semi_auto_llama.py`).  Attention runs
through the SDPA/Pallas path; RoPE through the fused rope op."""

from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu
from ..nn import functional as F
from .generation import GenerationMixin
from ..ops import creation, manipulation as _m

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny",
           "llama2_7b", "llama2_13b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0  # 0 -> same as num_heads (MHA); else GQA
    intermediate_size: int = 11008
    max_seq_len: int = 4096
    rms_eps: float = 1e-6
    rope_base: float = 10000.0
    use_recompute: bool = False
    tensor_parallel: bool = False

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_heads
        h, kvh = cfg.num_heads, cfg.num_kv_heads
        if cfg.tensor_parallel:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            mk = lambda i, o: ColumnParallelLinear(i, o, has_bias=False,
                                                   gather_output=False)
            self.q_proj = mk(cfg.hidden_size, h * self.head_dim)
            self.k_proj = mk(cfg.hidden_size, kvh * self.head_dim)
            self.v_proj = mk(cfg.hidden_size, kvh * self.head_dim)
            self.o_proj = RowParallelLinear(h * self.head_dim, cfg.hidden_size,
                                            has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = nn.Linear(cfg.hidden_size, h * self.head_dim,
                                    bias_attr=False)
            self.k_proj = nn.Linear(cfg.hidden_size, kvh * self.head_dim,
                                    bias_attr=False)
            self.v_proj = nn.Linear(cfg.hidden_size, kvh * self.head_dim,
                                    bias_attr=False)
            self.o_proj = nn.Linear(h * self.head_dim, cfg.hidden_size,
                                    bias_attr=False)

    def forward(self, x, kv_cache=None, pos_offset=None):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q = _m.reshape(self.q_proj(x), [b, s, cfg.num_heads, self.head_dim])
        k = _m.reshape(self.k_proj(x), [b, s, cfg.num_kv_heads, self.head_dim])
        v = _m.reshape(self.v_proj(x), [b, s, cfg.num_kv_heads, self.head_dim])
        if pos_offset is not None:
            offset = pos_offset
        else:
            offset = kv_cache[0].shape[1] if kv_cache is not None else 0
        import numpy as _np
        if isinstance(offset, int):
            pos = _np.arange(offset, offset + s) if offset else None
        else:  # traced offset (compiled decode loop): keep shapes static
            import jax.numpy as _jnp
            pos = _jnp.arange(s) + offset
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=pos, use_neox_rotary_style=True,
            rotary_emb_base=cfg.rope_base)
        if kv_cache is not None and not isinstance(kv_cache, tuple):
            # paged/static cache (non-tuple): both attend one q head per
            # cached kv head, so GQA caches the repeated heads
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                k = _m.repeat_interleave(k, rep, axis=2)
                v = _m.repeat_interleave(v, rep, axis=2)
            from .kv_cache import PagedKVCache, StaticKVCache
            if isinstance(kv_cache, (StaticKVCache, PagedKVCache)):
                from ..framework.tensor import Tensor as _T
                new_cache, out = kv_cache.update_and_attend(
                    q._value, k._value, v._value)
                out_t = _T._wrap(out.reshape(
                    b, s, cfg.num_heads * self.head_dim))
                return self.o_proj(out_t), new_cache
            return self._paged_forward(q, k, v, kv_cache, b, s)
        new_cache = None
        if kv_cache is not None:
            pk, pv = kv_cache
            k = _m.concat([pk, k], axis=1)
            v = _m.concat([pv, v], axis=1)
            new_cache = (k, v)
        # GQA (num_kv_heads < num_heads) is resolved inside the attention
        # functional: the Pallas kernel maps head groups via index maps
        # (repeated K/V never reach HBM), the XLA fallback repeats there
        k_len = k.shape[1]
        if k_len == s:
            mask, causal = None, True
        elif s == 1:
            mask, causal = None, False  # decode token sees all cache
        else:
            # chunked prefill: offset-aware causal mask
            import jax.numpy as _jnp
            qpos = _jnp.arange(k_len - s, k_len)[:, None]
            kpos = _jnp.arange(k_len)[None, :]
            from ..framework.tensor import Tensor as _T
            mask, causal = _T._wrap(qpos >= kpos), False
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=mask,
                                             is_causal=causal,
                                             training=self.training)
        out = _m.reshape(out, [b, s, cfg.num_heads * self.head_dim])
        out = self.o_proj(out)
        return out if new_cache is None else (out, new_cache)

    def _paged_forward(self, q, k, v, cache, b, s):
        """Decode/prefill against a paged block cache (see
        `models/gpt.py:_paged_forward`; same Pallas kernel)."""
        from ..framework.tensor import Tensor as _T
        cfg = self.cfg
        if s == 1:
            cache.append(k._value[:, 0], v._value[:, 0])
            out = cache.attend(q._value[:, 0])
            out_t = _T._wrap(out[:, None].reshape(
                b, 1, cfg.num_heads * self.head_dim))
        else:
            if cache._lens and cache._lens[0] != 0:
                raise NotImplementedError(
                    "chunked prefill against a paged cache; prefill in one "
                    "chunk or use cache_impl='dense'")
            cache.append_prefill(k._value, v._value)
            dense = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=False)
            out_t = _m.reshape(dense,
                               [b, s, cfg.num_heads * self.head_dim])
        return self.o_proj(out_t), cache


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        if cfg.tensor_parallel:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            self.gate_proj = ColumnParallelLinear(cfg.hidden_size,
                                                  cfg.intermediate_size,
                                                  has_bias=False,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(cfg.hidden_size,
                                                cfg.intermediate_size,
                                                has_bias=False,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(cfg.intermediate_size,
                                               cfg.hidden_size,
                                               has_bias=False,
                                               input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                       bias_attr=False)
            self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                     bias_attr=False)
            self.down_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                       bias_attr=False)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, kv_cache=None, pos_offset=None):
        if kv_cache is None:
            x = x + self.self_attn(self.input_layernorm(x))
        else:
            a, new_cache = self.self_attn(self.input_layernorm(x), kv_cache,
                                          pos_offset)
            x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x if kv_cache is None else (x, new_cache)


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            from ..distributed.fleet import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)

    def forward(self, input_ids, kv_caches=None, pos_offset=None):
        x = self.embed_tokens(input_ids)
        if kv_caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, kv_caches):
                x, nc = layer(x, cache, pos_offset)
                new_caches.append(nc)
            return self.norm(x), new_caches
        if self.cfg.use_recompute and self.training:
            from ..distributed.fleet import recompute
            for layer in self.layers:
                x = recompute(layer, x)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def init_caches(self, batch_size, cache_impl: str = "dense",
                    block_size: int = None, max_context=None):
        import jax.numpy as jnp
        from ..framework.tensor import Tensor as _T
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        dtype = self.model.embed_tokens.weight._value.dtype
        if cache_impl == "paged" and max_context is not None:
            # compiled serving path (see gpt.py): pool sized by the actual
            # generation context; caches hold GQA-repeated heads
            from .kv_cache import PagedKVCache
            return [PagedKVCache(batch_size, max_context, cfg.num_heads,
                                 hd, dtype, block_size=block_size or 64)
                    for _ in range(cfg.num_layers)]
        if cache_impl == "paged":
            block_size = block_size or 16
            from ..ops.pallas_paged import BlockKVCache
            max_blocks = (cfg.max_seq_len + block_size - 1) // block_size
            return [BlockKVCache(
                num_blocks=batch_size * max_blocks + 1,
                block_size=block_size, num_heads=cfg.num_heads,
                head_dim=hd, batch=batch_size,
                max_blocks_per_seq=max_blocks, dtype=dtype)
                for _ in range(cfg.num_layers)]
        if cache_impl == "static":
            # like the paged cache, static caches hold the GQA-repeated
            # heads (attention there is one q head per cached kv head)
            from .kv_cache import StaticKVCache
            return [StaticKVCache(batch_size, cfg.max_seq_len,
                                  cfg.num_heads, hd, dtype)
                    for _ in range(cfg.num_layers)]
        empty = lambda: _T._wrap(jnp.zeros(
            (batch_size, 0, cfg.num_kv_heads, hd), dtype))
        return [(empty(), empty()) for _ in range(cfg.num_layers)]

    def cache_spec(self):
        """What `ServingEngine` caches a layer: a (K, V) pair of pools,
        one row of `head_dim` a query head and token (GQA models cache
        the repeated heads)."""
        from .kv_cache import kv_cache_spec
        cfg = self.cfg
        return kv_cache_spec(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads)

    def forward_with_cache(self, input_ids, caches, pos_offset=0):
        h, new_caches = self.model(input_ids, kv_caches=caches,
                                   pos_offset=pos_offset)
        return self.lm_head(h), new_caches

    def compute_loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(
            _m.reshape(logits, [-1, self.cfg.vocab_size]),
            _m.reshape(labels, [-1]))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> float:
        from ..observability.flops import training_flops_per_token
        return training_flops_per_token(
            self.num_params(), self.cfg.num_layers, self.cfg.hidden_size,
            seq_len or self.cfg.max_seq_len)


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                       num_heads=4, intermediate_size=384, max_seq_len=256,
                       **kw)


def llama2_7b(**kw):
    return LlamaConfig(hidden_size=4096, num_layers=32, num_heads=32,
                       intermediate_size=11008, max_seq_len=4096, **kw)


def llama2_13b(**kw):
    return LlamaConfig(hidden_size=5120, num_layers=40, num_heads=40,
                       intermediate_size=13824, max_seq_len=4096, **kw)
