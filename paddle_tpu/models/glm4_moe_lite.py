"""GLM-4.7-Flash (`model_type: glm4_moe_lite`): multi-head latent attention
(MLA) over EVERY cached token (no index), a DeepSeek-V3 style mixture of
experts held whole, and one multi-token-prediction (MTP) module that
drafts for the model itself.

Block `l`: `x = h + MLA(RMSNorm(h))`, `h' = x + FFN(RMSNorm(x))`; FFN is a
dense SwiGLU for `l < first_k_dense_replace`, `HeldExpertsLayer` (sigmoid
scores, selection bias, top-k, one shared expert, no drops) after.  The
MLA projections and their absorption, the block and the expert layer are
`models/glm_moe_dsa.py`'s (GLM-5 is the same attention under a learned
index); without an index a query attends all rows of the paged latent
pool (`LatentDenseCache`, `ops/pallas_latent.py`).

The MTP module (depth 1, the DeepSeek-V3 form, arXiv:2412.19437 section
2.2): for position i, with `h_i` the model's last hidden state AFTER its
final norm and `t_{i+1}` the next token, `u_i = W_eh [RMSNorm_e(Emb(
t_{i+1})) ; RMSNorm_h(h_i)]`, `z_i = Block(u_i)` (one block of the MoE
kind with its own weights and latent row, RoPE at position i), `logits =
Head(RMSNorm_s(z_i))`, which predicts `t_{i+2}`.  Embedding and head are
the model's.  Over a cache the module's row for position i is kept in
slot i + 1 (`kv_cache.SelfDraft`).

`cache_spec().generation` is `SelfDraft` while `mtp_draft` is set: the
serving engine then runs the last token and the module's draft through
one forward and emits one or two tokens (`inference/serving.py`).
Inference only.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from .generation import GenerationMixin
from .glm_moe_dsa import GlmMoeDsaBlock
from .kv_cache import CacheSpec, LatentDenseCache, PoolRow, SelfDraft

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteForCausalLM",
           "glm4_moe_lite_tiny"]


@dataclass
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64           # the router's width
    n_experts_held: int = 0              # 0 -> all of them
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    mtp_draft: bool = True               # serve with the module drafting
    max_seq_len: int = 202752
    rms_eps: float = 1e-5
    rope_base: float = 1e6
    param_dtype: str = "float32"         # as `GlmMoeDsaConfig.param_dtype`
    index_topk: int = 0                  # no index: attend every row

    def __post_init__(self):
        if self.n_experts_held == 0:
            self.n_experts_held = self.n_routed_experts
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most "
                             f"({self.num_nextn_predict_layers} asked)")


class Glm4MoeLiteMTP(nn.Layer):
    """The multi-token-prediction module: two norms, the projection of
    their concatenation (embedding half first) and one MoE block."""

    def __init__(self, cfg: Glm4MoeLiteConfig):
        super().__init__()
        H = cfg.hidden_size
        self.enorm = nn.RMSNorm(H, cfg.rms_eps)
        self.hnorm = nn.RMSNorm(H, cfg.rms_eps)
        self.eh_proj = nn.Linear(2 * H, H, bias_attr=False)
        self.block = GlmMoeDsaBlock(cfg, cfg.first_k_dense_replace, shift=1)
        self.shared_head_norm = nn.RMSNorm(H, cfg.rms_eps)

    def forward(self, h, emb_next, cache=None):
        """`h` `[B, s, H]` (after the model's final norm), `emb_next` the
        embeddings of the tokens that follow.  Returns `RMSNorm_s(z)`,
        and over a cache view the advanced view too."""
        u = self.eh_proj(Tensor._wrap(jnp.concatenate(
            [self.enorm(emb_next)._value, self.hnorm(h)._value], -1)))
        if cache is None:
            return self.shared_head_norm(self.block(u))
        z, new, _ = self.block(u, cache)
        return self.shared_head_norm(z), new


class Glm4MoeLiteModel(nn.Layer):
    def __init__(self, cfg: Glm4MoeLiteConfig):
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
        """Without caches: the last hidden states (after the final norm).
        Over one view a layer: (hidden states, the advanced views)."""
        x = self.embed_tokens(input_ids)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new = []
        for layer, cache in zip(self.layers, caches):
            x, c, _ = layer(x, cache)
            new.append(c)
        return self.norm(x), new


class Glm4MoeLiteForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: Glm4MoeLiteConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Glm4MoeLiteModel(cfg)
        self.mtp = Glm4MoeLiteMTP(cfg).astype(cfg.param_dtype) \
            if cfg.num_nextn_predict_layers else None
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False).astype(cfg.param_dtype)

    # ------------------------------------------------------ without a cache
    def head(self, h):
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def forward(self, input_ids):
        return self.head(self.model(input_ids))

    def forward_mtp(self, input_ids):
        """(logits `[B, T, V]`, the module's logits `[B, T - 1, V]`: at
        position i from `h_i` and token i + 1, predicting token i + 2)."""
        h = self.model(input_ids)
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        with jax.named_scope("mtp_draft"):
            z = self.mtp(Tensor._wrap(h._value[:, :-1]),
                         self.model.embed_tokens(Tensor._wrap(ids[:, 1:])))
        return self.head(h), self.head(z)

    # --------------------------------------------------------- over a cache
    def forward_hidden(self, input_ids, caches, pos_offset=0):
        """The model's layers over their views (`caches`: one a layer,
        the module's last and passed through): (last hidden states after
        the final norm `[B, s, H]`, the advanced views).  Positions come
        from each view's `seq_lens`."""
        n = self.cfg.num_layers
        h, new = self.model(input_ids, caches[:n])
        return h, new + list(caches[n:])

    def forward_with_cache(self, input_ids, caches, pos_offset=0):
        h, new = self.forward_hidden(input_ids, caches)
        return self.head(h), new

    def draft_hidden(self, h, next_ids, caches):
        """The module over `s` positions: `h` `[B, s, H]` their hidden
        states, `next_ids` `[B, s]` the tokens that follow them, the
        module's view standing where the model's stood BEFORE those
        positions.  Returns (`RMSNorm_s(z)` `[B, s, H]`: `head` of it
        predicts the token after `next_ids`; the advanced views)."""
        n = self.cfg.num_layers
        wrap = lambda a: a if isinstance(a, Tensor) \
            else Tensor._wrap(a)                              # noqa: E731
        with jax.named_scope("mtp_draft"):
            z, new = self.mtp(wrap(h), self.model.embed_tokens(
                wrap(next_ids)), caches[n])
        return z, list(caches[:n]) + [new]

    def cache_spec(self) -> CacheSpec:
        """One latent pool a layer (the module's block is the last layer)
        under one block table, kept in whole tiles of 128 lanes as
        `glm_moe_dsa`'s, each layer's expert-row counts and the
        self-drafter's counts beside it."""
        from ..ops.sparse_mla import padded_width
        cfg = self.cfg
        why = ("the dense latent (MLA) pool, the held-expert layer and the "
               "multi-token-prediction module of glm4_moe_lite have no {} "
               "path")
        drafts = bool(cfg.num_nextn_predict_layers and cfg.mtp_draft)
        return CacheSpec(
            cfg.num_layers + cfg.num_nextn_predict_layers,
            (PoolRow("ckv", trail=(padded_width(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim),)),
             PoolRow("moe_rows", lead=(2, 2, cfg.n_experts_held),
                     dtype=jnp.int32, paged=False),
             PoolRow("mtp", lead=(2,), dtype=jnp.int32, paged=False)),
            LatentDenseCache,
            generation=SelfDraft(cfg.num_nextn_predict_layers)
            if drafts else None,
            unsupported={
                "tp_degree": why.format("tensor-parallel (head-sharded)"),
                "draft_model": why.format("draft-model"),
                "spec_decode": why.format(
                    "n-gram or draft-model speculative") + (
                    ": the model drafts for itself (mtp_draft)"),
                "quant": why.format("weight-quantized")})

    def init_caches(self, batch_size, cache_impl: str = "paged",
                    block_size: int = None, max_context=None):
        if cache_impl != "paged":
            raise ValueError(
                "glm4_moe_lite caches latent rows in paged pools; "
                f"cache_impl={cache_impl!r} is not available")
        bs = block_size or 64
        nb = -(-(max_context or self.cfg.max_seq_len) // bs)
        dtype = self.model.embed_tokens.weight._value.dtype
        tables = (1 + jnp.arange(batch_size * nb, dtype=jnp.int32)
                  ).reshape(batch_size, nb)
        lens = jnp.zeros((batch_size,), jnp.int32)
        return [LatentDenseCache(*pools, tables, lens, bs) for pools in
                self.cache_spec().init_pools(batch_size * nb, bs, dtype)]

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> float:
        """Forward FLOPs of one token through the MODEL (the module's
        work is the price of drafting, not a token's mathematics): 2 x
        the matmul parameters it meets (the experts it is routed to and
        the head; the embedding is a lookup), plus the absorbed attention
        over the whole context."""
        cfg = self.cfg
        ctx = seq_len or cfg.max_seq_len
        per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        n_moe = cfg.num_layers - cfg.first_k_dense_replace
        routed = sum(p.size for n, p in self.named_parameters()
                     if n.startswith("model.") and ".mlp.experts." in n)
        module = sum(p.size for n, p in self.named_parameters()
                     if n.startswith("mtp."))
        met = (self.num_params() - module - routed
               - self.model.embed_tokens.weight.size
               + n_moe * per_expert * cfg.num_experts_per_tok
               * cfg.n_experts_held / cfg.n_routed_experts)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        attn = cfg.num_layers * 2 * ctx * cfg.num_heads \
            * (width + cfg.kv_lora_rank)
        return 2.0 * met + attn


def glm4_moe_lite_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing wide."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
                qk_rope_head_dim=4, v_head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, n_routed_experts=8,
                num_experts_per_tok=2, first_k_dense_replace=1,
                max_seq_len=256)
    base.update(kw)
    return Glm4MoeLiteConfig(**base)
