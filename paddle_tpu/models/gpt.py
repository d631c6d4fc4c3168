"""GPT model family (decoder-only transformer, GPT-2/3 style).

Parity target: the reference ecosystem's GPT pretraining path (Fleet hybrid
GPT in PaddleNLP driven by the fleet APIs surveyed in SURVEY.md §3.4; the
attention fast path replaces `fused_multi_transformer_op.cu` /
`flash_attn_kernel.cu` with the Pallas/SDPA kernel).

TPU-first design:
* pre-LN blocks, bias-full GPT-3 parameterization;
* attention through F.scaled_dot_product_attention (Pallas flash kernel on
  TPU, fused XLA softmax elsewhere);
* optional tensor parallelism: with a live mesh ('mp' axis >1) the QKV/MLP
  weights are laid out column/row-parallel via NamedSharding;
* jax.checkpoint-able blocks for remat (`use_recompute`).

Configs mirror the BASELINE ladder: gpt3_tiny/med for tests, gpt3_1p3b,
gpt3_6p7b for the MFU runs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax

from .. import nn
from ..framework.tensor import Tensor
from ..nn import functional as F
from .generation import GenerationMixin
from ..ops import creation, manipulation as _m
from ..incubate.nn.functional import fused_rotary_position_embedding

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt3_tiny",
           "gpt3_124m", "gpt3_350m", "gpt3_1p3b", "gpt3_6p7b"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = 0  # 0 -> 4*hidden
    dropout: float = 0.0
    use_recompute: bool = False
    # remat every k-th block (1 = all blocks, Megatron "full" granularity;
    # k>1 trades activation memory back for recompute FLOPs — the
    # reference's recompute_granularity/interval knob on GPT configs)
    recompute_interval: int = 1
    # jax.checkpoint_policies member name for selective remat (None =
    # full recompute inside each checkpointed block)
    recompute_policy: str = None
    tensor_parallel: bool = False
    # GPT-MoE: replace the MLP of every `moe_every_n_layers`-th block with
    # a mixture of experts (0 experts = dense); shard ExpertMLP weights
    # over an 'ep' mesh axis for expert parallelism
    moe_num_experts: int = 0
    moe_every_n_layers: int = 2
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.moe_num_experts > 0 and self.moe_every_n_layers < 1:
            raise ValueError(
                "moe_every_n_layers must be >= 1 when moe_num_experts > 0 "
                "(1 = every block is MoE)")


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.hidden = cfg.hidden_size
        from ._common import tp_linear_pair
        self.qkv, self.proj = tp_linear_pair(
            cfg.tensor_parallel, cfg.hidden_size, 3 * cfg.hidden_size,
            row_in=cfg.hidden_size, row_out=cfg.hidden_size)
        self.dropout = cfg.dropout

    def forward(self, x, kv_cache=None):
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv(x)
        # q, k, v are column windows of the result: the values of
        # `reshape(qkv, [b, s, 3, nh, hd])` unbound on axis 2, element for
        # element.  XLA:TPU folds THAT reshape (a new axis between the
        # matmul's output columns) into the dot, as a 3 x nh-window
        # convolution whose kernel wants the weight in the other layout,
        # and every launch then copies every layer's weight first
        # (PERF.md §6, PR 35).
        q, k, v = (_m.reshape(t, [b, s, self.num_heads, self.head_dim])
                   for t in _m.split(qkv, 3, axis=-1))
        if kv_cache is not None and not isinstance(kv_cache, tuple):
            from .kv_cache import PagedKVCache, StaticKVCache
            if isinstance(kv_cache, (StaticKVCache, PagedKVCache)):
                new_cache, out = kv_cache.update_and_attend(
                    q._value, k._value, v._value)
                out_t = Tensor._wrap(out.reshape(
                    b, s, self.num_heads * self.head_dim))
                return self.proj(out_t), new_cache
            # non-tuple, non-static cache = BlockKVCache (dense caches are
            # (k, v) tuples); checked structurally so the pallas import
            # chain is only paid when paged decoding is actually used
            return self._paged_forward(q, k, v, kv_cache, b, s)
        if kv_cache is not None:
            pk, pv = kv_cache
            k = _m.concat([pk, k], axis=1)
            v = _m.concat([pv, v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        k_len = k.shape[1]
        if k_len == s:
            mask, causal = None, True
        elif s == 1:
            mask, causal = None, False  # decode token sees all cache
        else:
            # chunked prefill: offset-aware causal mask (query i at absolute
            # position k_len - s + i may see keys 0..k_len-s+i)
            import jax.numpy as _jnp
            qpos = _jnp.arange(k_len - s, k_len)[:, None]
            kpos = _jnp.arange(k_len)[None, :]
            from ..framework.tensor import Tensor as _T
            mask, causal = _T._wrap(qpos >= kpos), False
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask, dropout_p=self.dropout,
            is_causal=causal, training=self.training)
        out = _m.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.proj(out)
        if new_cache is not None:
            return out, new_cache
        return out

    def _paged_forward(self, q, k, v, cache, b, s):
        """Decode/prefill against a paged block cache: the Pallas
        `paged_attention` kernel replaces concat-and-grow dense caches
        (the reference's block_multihead_attention serving path)."""
        from ..framework.tensor import Tensor as _T
        if s == 1:
            cache.append(k._value[:, 0], v._value[:, 0])
            out = cache.attend(q._value[:, 0])  # [B, nh, hd]
            out_t = _T._wrap(out[:, None].reshape(
                b, 1, self.num_heads * self.head_dim))
        else:  # prefill: dense causal attention + bulk cache insert
            if cache._lens and cache._lens[0] != 0:
                raise NotImplementedError(
                    "chunked prefill against a paged cache: the chunk "
                    "would need the offset-aware mask over cached tokens; "
                    "prefill in one chunk or use cache_impl='dense'")
            cache.append_prefill(k._value, v._value)
            dense = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=False)
            out_t = _m.reshape(dense, [b, s,
                                       self.num_heads * self.head_dim])
        return self.proj(out_t), cache


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        from ._common import tp_linear_pair
        self.fc1, self.fc2 = tp_linear_pair(
            cfg.tensor_parallel, cfg.hidden_size, cfg.intermediate_size)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig, use_moe: bool = False):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        if use_moe:
            from ..incubate.distributed.models.moe import MoELayer
            self.mlp = MoELayer(
                d_model=cfg.hidden_size, num_expert=cfg.moe_num_experts,
                d_hidden=cfg.intermediate_size,
                gate=("gshard" if cfg.moe_top_k == 2 else
                      "switch" if cfg.moe_top_k == 1 else "naive"),
                top_k=cfg.moe_top_k)
        else:
            self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, kv_cache=None):
        if kv_cache is None:
            x = x + self.dropout(self.attn(self.ln1(x)))
        else:
            a, new_cache = self.attn(self.ln1(x), kv_cache)
            x = x + self.dropout(a)
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return x if kv_cache is None else (x, new_cache)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        # GPT-2/3 parameterization: embeddings ~ N(0, 0.02) (the Embedding
        # layer default of N(0, 1) puts the tied-head logits and the
        # initial loss way off scale); passed as weight_attr so init runs
        # before VocabParallelEmbedding shards the table
        from .. import ParamAttr
        from ..nn.initializer import Normal
        emb_attr = lambda: ParamAttr(initializer=Normal(0.0, 0.02))
        if cfg.tensor_parallel:
            from ..distributed.fleet import VocabParallelEmbedding
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                              weight_attr=emb_attr())
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                    weight_attr=emb_attr())
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=emb_attr())
        self.drop = nn.Dropout(cfg.dropout)
        def _is_moe(i):
            return cfg.moe_num_experts > 0 and \
                (i + 1) % cfg.moe_every_n_layers == 0
        self.blocks = nn.LayerList([GPTBlock(cfg, use_moe=_is_moe(i))
                                    for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, kv_caches=None, pos_offset=0):
        b, s = input_ids.shape[0], input_ids.shape[1]
        # arange(s) + offset keeps the program valid for a TRACED offset
        # (compiled decode loops pass the position as a scalar input)
        pos = creation.arange(s, dtype="int32") + pos_offset
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if kv_caches is not None:
            new_caches = []
            for block, cache in zip(self.blocks, kv_caches):
                x, nc = block(x, cache)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        if self.cfg.use_recompute and self.training:
            from ..distributed.fleet import recompute
            from ..incubate.distributed.models.moe import MoELayer
            k = max(1, self.cfg.recompute_interval)
            for i, block in enumerate(self.blocks):
                if isinstance(block.mlp, MoELayer):
                    # the gate's aux loss leaves the block as an attribute,
                    # which cannot cross a jax.checkpoint boundary — MoE
                    # blocks run un-checkpointed (dense blocks still remat)
                    x = block(x)
                elif i % k == 0:
                    x = recompute(block, x,
                                  policy=self.cfg.recompute_policy)
                else:
                    x = block(x)
        else:
            for block in self.blocks:
                x = block(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        # tied output head (reads the embedding weight)
        self._tied = True

    def forward(self, input_ids):
        h = self.gpt(input_ids)
        from ..ops.linalg import matmul
        return matmul(h, self.gpt.wte.weight, transpose_y=True)

    def init_caches(self, batch_size, cache_impl: str = "dense",
                    block_size: int = None, max_context=None):
        import jax.numpy as jnp
        from ..framework.tensor import Tensor as _T
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        dtype = self.gpt.wte.weight._value.dtype
        if cache_impl == "paged" and max_context is not None:
            # compiled serving path: pool sized by the ACTUAL context of
            # this generation, not the max_seq_len rectangle.  Pages of 64
            # keep the decode kernel's [nh, bs, hd] blocks MXU-friendly
            # (the eager BlockKVCache defaults to finer 16-token pages for
            # allocation granularity under continuous batching).
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
            from .kv_cache import StaticKVCache
            return [StaticKVCache(batch_size, cfg.max_seq_len,
                                  cfg.num_heads, hd, dtype)
                    for _ in range(cfg.num_layers)]
        empty = lambda: _T._wrap(jnp.zeros(
            (batch_size, 0, cfg.num_heads, hd), dtype))
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
        h, new_caches = self.gpt(input_ids, kv_caches=caches,
                                 pos_offset=pos_offset)
        from ..ops.linalg import matmul
        return matmul(h, self.gpt.wte.weight, transpose_y=True), new_caches

    @jax.named_scope("forward")
    def compute_loss(self, input_ids, labels):
        logits = self(input_ids)
        loss = F.cross_entropy(
            _m.reshape(logits, [-1, self.cfg.vocab_size]),
            _m.reshape(labels, [-1]))
        if self.cfg.moe_num_experts > 0:
            for block in self.gpt.blocks:
                aux = getattr(block.mlp, "l_aux", None)
                if aux is not None:
                    loss = loss + self.cfg.moe_aux_weight * aux
        return loss

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> float:
        """Train-step FLOPs/token via the shared MFU accounting helper
        (`observability.flops`: 6N + 12*L*H*S)."""
        from ..observability.flops import training_flops_per_token
        return training_flops_per_token(
            self.num_params(), self.cfg.num_layers, self.cfg.hidden_size,
            seq_len or self.cfg.max_seq_len)


def gpt3_tiny(**kw):
    return _preset(dict(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=256), kw)


def _preset(defaults, kw):
    defaults.update(kw)  # caller overrides win (e.g. max_seq_len)
    return GPTConfig(**defaults)


def gpt3_124m(**kw):
    return _preset(dict(hidden_size=768, num_layers=12, num_heads=12,
                        max_seq_len=1024), kw)


def gpt3_350m(**kw):
    return _preset(dict(hidden_size=1024, num_layers=24, num_heads=16,
                        max_seq_len=1024), kw)


def gpt3_1p3b(**kw):
    return _preset(dict(hidden_size=2048, num_layers=24, num_heads=16,
                        max_seq_len=2048), kw)


def gpt3_6p7b(**kw):
    return _preset(dict(hidden_size=4096, num_layers=32, num_heads=32,
                        max_seq_len=2048), kw)
