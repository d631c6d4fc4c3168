"""SDAR (`model_type: sdar_moe`): Qwen3-MoE's decoder block — grouped-
query attention with a per-head RMSNorm of q and k before RoPE, every
layer a mixture of experts routed by a softmax top-k gate — that
generates by diffusion over blocks of `block_length` tokens.

Block: `x = h + Attn(RMSNorm(h))`, `h' = x + MoE(RMSNorm(x))`; RMSNorm
is `nn.RMSNorm`, the experts `HeldExpertsLayer` under `SoftmaxTopKGate`
(no shared expert).  A query at position `t` sees key `s` iff
`s < (t // L + 1) * L`, `L = block_length`: causal over blocks, full
inside one.  The logits at a position are that position's own token
distribution (no shift).

* `forward` (no cache): the whole sequence under the block mask.
* `forward_with_cache` over the paged (K, V) views of `kv_cache.py`
  (a prefill chunk at an offset, a denoising or commit forward of one
  block a sequence): `s` rows are written at `seq_lens` and attended
  under the same mask.  The pools hold the `num_kv_heads` KV heads; the
  `num_heads / num_kv_heads` query heads of a group read their pool head
  and nothing repeats K or V.

Generation (`cache_spec().generation`, which `ServingEngine` reads): the
first `P // L * L` prompt tokens are prefilled; a block starts as the
prompt's tail and `[MASK]` up to `L`, or `L` x `[MASK]`; each of
`denoising_steps` forwards reveals the `L / denoising_steps` masked
positions of highest confidence; one more forward commits the finished
block's K and V.  `benchmark/reference/sdar_moe_ref.py` has the
equations and the sampler in plain `jax.numpy`.

The chip's share: `n_experts_held` of the router's `num_experts`, from
`expert_offset` (all of them by default).  Inference only.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe import (HeldExpertsLayer,
                                              SoftmaxTopKGate)
from ..nn.initializer import Initializer
from ..param_attr import ParamAttr
from .kv_cache import BlockDiffusion, PagedChunkView, PoolRow, kv_cache_spec

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "sdar_moe_tiny"]


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128               # the router's width
    n_experts_held: int = 0              # 0 -> all of them
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_seq_len: int = 32768
    rms_eps: float = 1e-6
    rope_base: float = 1e6
    # how it generates: not in the published config (the family's
    # convention; the benchmark's configuration file lists each under
    # `assumed` with its reason)
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    initializer_range: float = 0.02
    # parameters are created in this dtype, one jitted initialiser a
    # shape: a float32 copy of a layer's 128 stacked experts is 2.4 GB
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.n_experts_held == 0:
            self.n_experts_held = self.num_experts
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must divide "
                f"block_length {self.block_length}")


@functools.lru_cache(maxsize=None)
def _normal(shape, dtype, std):
    return jax.jit(lambda key: (std * jax.random.normal(
        key, shape, jnp.float32)).astype(dtype))


class _JitNormal(Initializer):
    """`N(0, std)` in `dtype`, drawn by one jitted program a shape (the
    eager initialisers run a handful of programs a parameter, each a
    float32 array of its size)."""

    def __init__(self, std: float, dtype):
        self.std, self.dtype = float(std), jnp.dtype(dtype)

    def __call__(self, param, block=None):
        from ..framework import random as _random
        param._value = _normal(tuple(param.shape), self.dtype, self.std)(
            _random.next_key())
        return param


def _rope(x, pos, base):
    """Half-rotation ("neox") RoPE over the whole last axis of `x`
    `[B, s, h, d]` at positions `pos` `[s]` or `[B, s]`; angles and the
    rotation in float32, the result in `x`'s dtype."""
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    if ang.ndim == 3:
        ang = ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


class SdarMoeAttention(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig, attr):
        super().__init__()
        self.cfg = cfg
        H, nh, nkv, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, \
            cfg.head_dim
        lin = lambda i, o: nn.Linear(                          # noqa: E731
            i, o, weight_attr=attr, bias_attr=False)
        self.q_proj = lin(H, nh * hd)
        self.k_proj = lin(H, nkv * hd)
        self.v_proj = lin(H, nkv * hd)
        self.o_proj = lin(nh * hd, H)
        self.q_norm = nn.RMSNorm(hd, cfg.rms_eps)
        self.k_norm = nn.RMSNorm(hd, cfg.rms_eps)

    def forward(self, x, cache=None):
        """Without a cache: the attention's output.  Over a cache view:
        (output, the advanced view)."""
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        nh, nkv, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
            cfg.block_length
        if cache is None:
            pos = jnp.arange(s)
        else:
            pos = cache.seq_lens[:, None] + jnp.arange(
                s, dtype=cache.seq_lens.dtype)
        with jax.named_scope("gqa_proj"):
            q = self.q_norm(Tensor._wrap(
                self.q_proj(x)._value.reshape(b, s, nh, hd)))
            k = self.k_norm(Tensor._wrap(
                self.k_proj(x)._value.reshape(b, s, nkv, hd)))
            v = self.v_proj(x)._value.reshape(b, s, nkv, hd)
            q = _rope(q._value, pos, cfg.rope_base)
            k = _rope(k._value, pos, cfg.rope_base)
        if cache is None:
            with jax.named_scope("gqa_attend"):
                o = self._dense(q, k, v)
            return self.o_proj(Tensor._wrap(o.reshape(b, s, nh * hd)))
        with jax.named_scope("gqa_attend"):
            new, o = cache.update_and_attend(q, k, v, mask_block=L)
        with jax.named_scope("gqa_proj"):
            out = self.o_proj(Tensor._wrap(o.reshape(b, s, nh * hd)))
        return out, new

    def _dense(self, q, k, v):
        """Grouped-query attention over the whole sequence under the
        block mask (small sequences: tests, `forward`)."""
        cfg = self.cfg
        b, s, nh, hd = q.shape
        nkv, L = cfg.num_kv_heads, cfg.block_length
        f32 = jnp.float32
        qg = q.reshape(b, s, nkv, nh // nkv, hd)
        sc = jnp.einsum("bthgd,bkhd->bhgtk", qg, k,
                        preferred_element_type=f32) / math.sqrt(hd)
        t = jnp.arange(s)
        seen = t[None, :] < ((t // L + 1) * L)[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhgtk,bkhd->bthgd", p.astype(v.dtype),
                          v).reshape(b, s, nh, hd)


class SdarMoeBlock(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        init = _JitNormal(cfg.initializer_range, cfg.param_dtype)
        attr = ParamAttr(initializer=init)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.self_attn = SdarMoeAttention(cfg, attr)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_eps)
        self.mlp = HeldExpertsLayer(
            cfg.hidden_size, cfg.moe_intermediate_size,
            SoftmaxTopKGate(cfg.hidden_size, cfg.num_experts,
                            cfg.num_experts_per_tok, cfg.norm_topk_prob,
                            weight_attr=attr),
            n_experts_held=cfg.n_experts_held,
            expert_offset=cfg.expert_offset,
            expert_init=lambda lo, hi: init)

    def forward(self, x, cache=None):
        """Without a cache: the block's output.  Over a cache view:
        (output, the advanced view), the view's state row counting the
        rows each held expert was given: `[tick | chunk] x [rows |
        experts hit] x [held]`, the kind as the engine says it
        (`cache.in_tick`).  An inactive sequence of the batch (an idle
        slot: `cache.active`) is routed to no expert."""
        if cache is None:
            x = x + self.self_attn(self.input_layernorm(x))
            return x + self.mlp(self.post_attention_layernorm(x))
        a, new = self.self_attn(self.input_layernorm(x), cache)
        x = x + a
        y, rows = self.mlp.forward_counted(
            self.post_attention_layernorm(x),
            jnp.repeat(cache.active, a.shape[1]))
        kind = 0 if cache.in_tick else 1
        (moe_rows,) = new.state
        new = new.with_state(moe_rows.at[kind].add(
            jnp.stack([rows, (rows > 0).astype(rows.dtype)])))
        return x + y, new


class SdarMoeModel(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        attr = ParamAttr(initializer=_JitNormal(cfg.initializer_range,
                                                cfg.param_dtype))
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=attr)
        self.layers = nn.LayerList([
            SdarMoeBlock(cfg).astype(cfg.param_dtype)
            for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size,
                               cfg.rms_eps).astype(cfg.param_dtype)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new = []
        for layer, cache in zip(self.layers, caches):
            x, c = layer(x, cache)
            new.append(c)
        return self.norm(x), new


class SdarMoeForCausalLM(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SdarMoeModel(cfg)
        self.lm_head = nn.Linear(
            cfg.hidden_size, cfg.vocab_size, bias_attr=False,
            weight_attr=ParamAttr(initializer=_JitNormal(
                cfg.initializer_range, cfg.param_dtype)))

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def forward_with_cache(self, input_ids, caches, pos_offset=0):
        """Positions come from each view's `seq_lens`; `pos_offset` is
        accepted for the engine's one calling convention."""
        h, new = self.model(input_ids, caches)
        with jax.named_scope("lm_head"):
            return self.lm_head(h), new

    def cache_spec(self):
        """A (K, V) pair of pools a layer over the `num_kv_heads` KV
        heads, the layer's expert-row counts beside them, and how the
        model generates."""
        cfg = self.cfg
        why = (f"block-diffusion generation (a tick denoises and commits "
               f"a block of {cfg.block_length} tokens a sequence) has no "
               "{} path").format
        return kv_cache_spec(
            cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
            state_rows=(PoolRow("moe_rows", lead=(2, 2, cfg.n_experts_held),
                                dtype=jnp.int32, paged=False),),
            unsupported={
                "tp_degree": why("tensor-parallel (head-sharded)"),
                "draft_model": why("draft-model"),
                "spec_decode": why("speculative-verify"),
                "quant": why("weight-quantized")},
            generation=BlockDiffusion(cfg.block_length, cfg.denoising_steps,
                                      cfg.mask_token_id))

    def init_caches(self, batch_size, cache_impl: str = "paged",
                    block_size: int = None, max_context=None):
        if cache_impl != "paged":
            raise ValueError(
                "sdar_moe caches its kv heads in paged pools; "
                f"cache_impl={cache_impl!r} is not available")
        bs = block_size or 64
        nb = -(-(max_context or self.cfg.max_seq_len) // bs)
        dtype = self.model.embed_tokens.weight._value.dtype
        tables = (1 + jnp.arange(batch_size * nb, dtype=jnp.int32)
                  ).reshape(batch_size, nb)
        lens = jnp.zeros((batch_size,), jnp.int32)
        return [PagedChunkView.from_parts(*pools, tables, lens, bs)
                for pools in self.cache_spec().init_pools(
                    batch_size * nb, bs, dtype)]

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> float:
        """Forward FLOPs a token as this share runs it: 2 x the matmul
        parameters a token meets (of the experts, the held ones it is
        routed to: on average `top_k * held / width`), plus the attention
        over a context of `seq_len`."""
        cfg = self.cfg
        ctx = seq_len or cfg.max_seq_len
        per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        routed = cfg.num_layers * cfg.n_experts_held * per_expert
        met = (self.num_params() - routed
               - self.model.embed_tokens.weight.size
               + cfg.num_layers * per_expert * cfg.num_experts_per_tok
               * cfg.n_experts_held / cfg.num_experts)
        attn = cfg.num_layers * 4 * ctx * cfg.num_heads * cfg.head_dim
        return 2.0 * met + attn


def sdar_moe_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing wide."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=8,
                num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
                num_experts=16, num_experts_per_tok=4, max_seq_len=256,
                mask_token_id=255)
    base.update(kw)
    return SdarMoeConfig(**base)
