"""K-EXAONE (`model_type: exaone_moe`): grouped-query attention that looks
back over a window of `sliding_window` positions in three layers of four
(`sliding_window_pattern` "LLLG") and over the whole context in the
fourth, post-norm blocks with q and k normed a head, a dense SwiGLU in the
leading layer and a DeepSeek-V3 style mixture of experts after it, and one
multi-token-prediction (MTP) module that drafts for the model itself.

The layer is EXAONE 4.0's (arXiv:2507.11407; HF `modeling_exaone4.py`),
the feed-forward of `mlp_layer_types` "sparse" the expert layer of
`models/glm_moe_dsa.py`.  `h` is the residual stream (float32), every
RMSNorm has eps `rms_eps`:

* Embedding: `h_0 = E[t]` (no multiplier).
* Attention of layer `l` on `h` as it is (no input norm): `q = RMSNorm_q(h
  W_q)`, `k = RMSNorm_k(h W_k)` a head of `head_dim`, `v = h W_v`; in a
  window layer rotate-half RoPE over all of `head_dim`, theta `rope_base`,
  at absolute positions, and key `s` visible to query `t` iff `t - W < s
  <= t`; in a full layer no RoPE ("global NoPE") and `s <= t`.  Query head
  `j` reads kv head `j // (num_heads / num_kv_heads)`; softmax of `q k /
  sqrt(head_dim)` in float32; `a = concat(heads) W_o`.
* `h <- h + RMSNorm_pa(a)`; `h <- h + RMSNorm_pf(F(h))`: post-norm inside
  the residual.  `F` is a SwiGLU of `intermediate_size`, or
  `HeldExpertsLayer` (sigmoid scores, selection bias, top
  `num_experts_per_tok`, weights normalised over the picked and scaled by
  `routed_scaling_factor`, one shared expert, no drops).
* Logits: `RMSNorm(h_L) W_head` (untied), float32.
* MTP (depth 1, DeepSeek-V3's form, arXiv:2412.19437 section 2.2): for
  position i, with `h_i` the model's last hidden state AFTER its final norm
  and `t_{i+1}` the next token, `u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)]`, `z_i = Block(u_i)` (one block of the MoE kind, full
  attention, its own weights and cache rows), `logits = Head(RMSNorm_s(
  z_i))`, which predicts `t_{i+2}`.  Embedding and head are the model's.
  Over a cache the module's row for position i sits in slot i (the shift
  of `kv_cache.SelfDraft`'s docstring serves the prefix cache, which this
  model refuses).

The chip's share: `n_experts_held` of the router's `num_experts`, from
`expert_offset`; the vocabulary is `vocab_size` rows (a slice is a smaller
vocabulary).

The cache (`cache_spec`): the full layers and the module keep (K, V) in
paged kv-head pools under the block table; each window layer keeps, a
batch slot, a ring of exactly `sliding_window` positions of K and of V
(`kv_cache.WindowRingView`, `ops/window_attention.py`), so its bytes stay
what they are whatever the context.  `benchmark/reference/exaone_moe_ref.py`
has the same equations in plain `jax.numpy`.  Inference only.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe import (HeldExpertsLayer,
                                              SigmoidTopKGate)
from ..param_attr import ParamAttr
from .kv_cache import (CacheSpec, PoolRow, SelfDraft, WindowRingKernelView,
                       WindowRingView)
from .sdar_moe import _JitNormal, _rope

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM", "exaone_moe_tiny"]

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
# the cache's rows (`cache_spec`): (k, v, ring_k, ring_v, moe_rows, mtp);
# a view's `state` is what follows (k, v)
_MOE = 2


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128               # the router's width
    n_experts_held: int = 0              # 0 -> all of them
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 1
    mlp_layer_types: tuple = None        # None: "dense" before
                                         # first_k_dense_replace
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    layer_types: tuple = None            # None: from the pattern
    num_nextn_predict_layers: int = 1
    mtp_layer_types: tuple = (FULL,)
    mtp_draft: bool = True               # serve with the module drafting
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    rope_base: float = 1e6
    initializer_range: float = 0.02
    # parameters are created in this dtype (`falcon_h1`'s way): the held
    # share at published widths is 4.5e9 parameters
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.n_experts_held == 0:
            self.n_experts_held = self.num_experts
        L = self.num_layers
        if self.layer_types is None:
            pat = self.sliding_window_pattern
            self.layer_types = tuple(
                SLIDING if pat[i % len(pat)] == "L" else FULL
                for i in range(L))
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(L))
        # a configuration file lists the published model's 48 entries: the
        # stage runs the first `num_layers`
        self.layer_types = tuple(self.layer_types)[:L]
        self.mlp_layer_types = tuple(self.mlp_layer_types)[:L]
        self.mtp_layer_types = tuple(self.mtp_layer_types)
        if len(self.layer_types) != L or len(self.mlp_layer_types) != L:
            raise ValueError(f"{L} layers need {L} layer types")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing (n_group, topk_group "
                             f"{self.n_group}, {self.topk_group}) has no path")
        if self.num_nextn_predict_layers not in (0, 1) or any(
                t != FULL for t in self.mtp_layer_types):
            raise ValueError("one multi-token-prediction module of full "
                             "attention at most")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("kv heads must divide the query heads")

    def window_of(self, layer: int) -> int:
        """Positions layer `layer` looks back over (0: all); the module's
        layer is `num_layers`."""
        if layer >= self.num_layers:
            return 0
        return self.sliding_window if self.layer_types[layer] == SLIDING \
            else 0


def _param(layer, shape, init, dtype):
    return layer.create_parameter(
        list(shape), attr=ParamAttr(initializer=init), dtype=dtype)


def _mm(x, w):
    """`x W` with `x` in `W`'s dtype, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _ones(layer, n, dtype):
    return _param(layer, (n,), nn.initializer.Constant(1.0), dtype)


class _SwiGLU(nn.Layer):
    """`W_down(SiLU(x W_gate) ⊙ x W_up)` on a Tensor `[.., H]`."""

    def __init__(self, H, width, init, dtype):
        super().__init__()
        self.gate_proj = _param(self, (H, width), init, dtype)
        self.up_proj = _param(self, (H, width), init, dtype)
        self.down_proj = _param(self, (width, H), init, dtype)

    def forward(self, x):
        v = x._value
        y = _mm(jax.nn.silu(_mm(v, self.gate_proj._value))
                * _mm(v, self.up_proj._value), self.down_proj._value)
        return Tensor._wrap(y.astype(v.dtype))


class ExaoneMoeAttention(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig, window: int):
        super().__init__()
        self.cfg, self.window = cfg, window
        H, nh, nkv, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, \
            cfg.head_dim
        dt = cfg.param_dtype
        w = _JitNormal(cfg.initializer_range, dt)
        self.q_proj = _param(self, (H, nh * hd), w, dt)
        self.k_proj = _param(self, (H, nkv * hd), w, dt)
        self.v_proj = _param(self, (H, nkv * hd), w, dt)
        self.o_proj = _param(self, (nh * hd, H), w, dt)
        self.q_norm = _ones(self, hd, dt)
        self.k_norm = _ones(self, hd, dt)

    def forward(self, x, cache=None):
        """`x` `[b, s, H]`.  Returns (output float32, the view advanced,
        or None without a cache)."""
        cfg = self.cfg
        b, s = x.shape[:2]
        nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        act = self.q_proj._value.dtype
        with jax.named_scope("attn_proj"):
            # the barrier keeps each projection's output row-major: without
            # it XLA:TPU lays the chunk program's dots out heads-major, for
            # the attention that follows, and turns W_q, W_k and W_v round
            # at every launch (tests/test_tpu_aot_compile.py)
            q, k, v = jax.lax.optimization_barrier(
                (_mm(x, self.q_proj._value), _mm(x, self.k_proj._value),
                 _mm(x, self.v_proj._value)))
            q = _rms(q.reshape(b, s, nh, hd), self.q_norm._value, cfg.rms_eps)
            k = _rms(k.reshape(b, s, nkv, hd), self.k_norm._value, cfg.rms_eps)
            v = v.astype(act).reshape(b, s, nkv, hd)
            if self.window:
                pos = jnp.arange(s) if cache is None else \
                    cache.seq_lens[:, None] + jnp.arange(
                        s, dtype=cache.seq_lens.dtype)
                q, k = _rope(q, pos, cfg.rope_base), _rope(k, pos,
                                                           cfg.rope_base)
            q, k = q.astype(act), k.astype(act)
        with jax.named_scope("swa_attend" if self.window else "gqa_attend"):
            if cache is None:
                o = self._dense(q, k, v)
            elif self.window:
                cache, o = cache.window_update_and_attend(
                    q, k, v, window=self.window, scale=1.0 / math.sqrt(hd))
            else:
                cache, o = cache.update_and_attend(q, k, v)
        with jax.named_scope("attn_proj"):
            return _mm(o.reshape(b, s, nh * hd), self.o_proj._value), cache

    def _dense(self, q, k, v):
        b, s, nh, hd = q.shape
        nkv = k.shape[2]
        qg = q.reshape(b, s, nkv, nh // nkv, hd)
        sc = jnp.einsum("bthgd,bkhd->bhgtk", qg, k,
                        preferred_element_type=F32) / math.sqrt(hd)
        t = jnp.arange(s)
        seen = t[None, :] <= t[:, None]
        if self.window:
            seen = seen & (t[None, :] > t[:, None] - self.window)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhgtk,bkhd->bthgd", p.astype(v.dtype), v,
                          preferred_element_type=F32
                          ).astype(q.dtype).reshape(b, s, nh, hd)


class ExaoneMoeBlock(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig, layer_idx: int):
        super().__init__()
        self.cfg = cfg
        H, dt = cfg.hidden_size, cfg.param_dtype
        w = _JitNormal(cfg.initializer_range, dt)
        self.self_attn = ExaoneMoeAttention(cfg, cfg.window_of(layer_idx))
        self.post_attention_layernorm = _ones(self, H, dt)
        self.is_moe = layer_idx >= cfg.num_layers \
            or cfg.mlp_layer_types[layer_idx] == "sparse"
        if self.is_moe:
            self.mlp = HeldExpertsLayer(
                H, cfg.moe_intermediate_size,
                SigmoidTopKGate(H, cfg.num_experts, cfg.num_experts_per_tok,
                                cfg.routed_scaling_factor,
                                cfg.norm_topk_prob),
                n_experts_held=cfg.n_experts_held,
                expert_offset=cfg.expert_offset,
                shared=_SwiGLU(H, cfg.moe_intermediate_size
                               * cfg.num_shared_experts, w, dt),
                expert_init=lambda lo, hi: w)
        else:
            self.mlp = _SwiGLU(H, cfg.intermediate_size, w, dt)
        self.post_feedforward_layernorm = _ones(self, H, dt)

    def forward(self, h, cache=None):
        """`h` `[b, s, H]` float32.  Returns (`h'`, the advanced view or
        None).  Over a view an inactive sequence of the batch (an idle slot
        of a tick: `cache.active`) is routed to no expert and counted
        nowhere; the rows a chunk is padded with are not told apart."""
        cfg = self.cfg
        act = self.post_attention_layernorm._value.dtype
        a, new = self.self_attn(h, cache)
        h = h + _rms(a, self.post_attention_layernorm._value, cfg.rms_eps)
        x = Tensor._wrap(h.astype(act))
        if not self.is_moe:
            y = self.mlp(x)
        elif cache is None:
            y = self.mlp(x)
        else:
            y, rows = self.mlp.forward_counted(
                x, jnp.repeat(cache.active, h.shape[1]))
            # rows given to each held expert, by kind of program:
            # [decode step or tick | chunk] x [rows | experts hit] x [held]
            kind = 0 if h.shape[1] == 1 or cache.in_tick else 1
            state = list(new.state)
            state[_MOE] = state[_MOE].at[kind].add(
                jnp.stack([rows, (rows > 0).astype(rows.dtype)]))
            new = new.with_state(*state)
        h = h + _rms(y._value, self.post_feedforward_layernorm._value,
                     cfg.rms_eps)
        return h, new



class ExaoneMoeMTP(nn.Layer):
    """The multi-token-prediction module: two norms, the projection of
    their concatenation (embedding half first), one block of the MoE kind
    with full attention, and the norm before the model's head."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        H, dt = cfg.hidden_size, cfg.param_dtype
        self.enorm = _ones(self, H, dt)
        self.hnorm = _ones(self, H, dt)
        self.eh_proj = _param(self, (2 * H, H), _JitNormal(
            cfg.initializer_range, dt), dt)
        self.block = ExaoneMoeBlock(cfg, cfg.num_layers)
        self.shared_head_norm = _ones(self, H, dt)

    def forward(self, h, emb_next, cache=None):
        """`h` `[b, s, H]` (after the model's final norm), `emb_next` the
        embeddings of the tokens that follow.  Returns (`RMSNorm_s(z)`,
        the advanced view or None)."""
        eps = self.cfg.rms_eps
        u = _mm(jnp.concatenate([_rms(emb_next, self.enorm._value, eps),
                                 _rms(h, self.hnorm._value, eps)], -1),
                self.eh_proj._value)
        z, cache = self.block(u, cache)
        return _rms(z, self.shared_head_norm._value, eps), cache


def _ids(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


class ExaoneMoeForCausalLM(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        H, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
        w = _JitNormal(cfg.initializer_range, dt)
        self.embed_tokens = _param(self, (V, H), w, dt)
        self.lm_head = _param(self, (H, V), w, dt)
        self.layers = nn.LayerList([ExaoneMoeBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm = _ones(self, H, dt)
        self.mtp = ExaoneMoeMTP(cfg) if cfg.num_nextn_predict_layers \
            else None

    def _embed(self, ids):
        return jnp.take(self.embed_tokens._value, _ids(ids), axis=0
                        ).astype(F32)

    def _hidden(self, input_ids, caches=None):
        h = self._embed(input_ids)
        new = []
        for i, layer in enumerate(self.layers):
            h, c = layer(h, None if caches is None else caches[i])
            new.append(c)
        return _rms(h, self.norm._value, self.cfg.rms_eps), new

    def head(self, h):
        """Float32 logits of hidden states after their last norm."""
        h = h._value if isinstance(h, Tensor) else h
        with jax.named_scope("lm_head"):
            return Tensor._wrap(_mm(h, self.lm_head._value))

    # ------------------------------------------------------ without a cache
    def forward(self, input_ids):
        return self.head(self._hidden(input_ids)[0])

    def forward_mtp(self, input_ids):
        """(logits `[B, T, V]`, the module's logits `[B, T - 1, V]`: at
        position i from `h_i` and token i + 1, predicting token i + 2)."""
        h = self._hidden(input_ids)[0]
        ids = _ids(input_ids)
        with jax.named_scope("mtp_draft"):
            z, _ = self.mtp(h[:, :-1], self._embed(ids[:, 1:]))
        return self.head(h), self.head(z)

    # --------------------------------------------------------- over a cache
    def forward_hidden(self, input_ids, caches, pos_offset=0):
        """The model's layers over their views (`caches`: one a layer,
        the module's last, passed through): (last hidden states after the
        final norm `[B, s, H]`, the advanced views).  Positions come from
        each view's `seq_lens`."""
        n = self.cfg.num_layers
        h, new = self._hidden(input_ids, caches[:n])
        return Tensor._wrap(h), new + list(caches[n:])

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
        h = h._value if isinstance(h, Tensor) else h
        with jax.named_scope("mtp_draft"):
            z, new = self.mtp(h, self._embed(next_ids), caches[n])
        return Tensor._wrap(z), list(caches[:n]) + [new]

    def cache_spec(self) -> CacheSpec:
        """(K, V) kv-head pools under the block table for the full layers
        and the module; a ring of `sliding_window` positions of K and of V
        a batch slot for each window layer (`PoolRow.window`); each
        layer's expert-row counts and the self-drafter's counts beside
        them."""
        cfg = self.cfg
        L = cfg.num_layers + cfg.num_nextn_predict_layers
        W, nkv, hd = cfg.sliding_window, cfg.num_kv_heads, cfg.head_dim
        windowed = tuple(i for i in range(L) if cfg.window_of(i))
        full = tuple(i for i in range(L) if not cfg.window_of(i))
        kv = dict(lead=(nkv,), trail=(hd,), layers=full)
        ring = dict(lead=(nkv, W, hd), paged=False, per_slot=True,
                    window=W, layers=windowed)
        drafts = bool(cfg.num_nextn_predict_layers and cfg.mtp_draft)
        why = "exaone_moe's {} has no {} path".format
        return CacheSpec(
            L,
            (PoolRow("k", **kv), PoolRow("v", **kv),
             PoolRow("ring_k", **ring), PoolRow("ring_v", **ring),
             PoolRow("moe_rows", lead=(2, 2, cfg.n_experts_held),
                     dtype=jnp.int32, paged=False),
             PoolRow("mtp", lead=(2,), dtype=jnp.int32, paged=False)),
            WindowRingKernelView, WindowRingView, WindowRingKernelView,
            generation=SelfDraft(cfg.num_nextn_predict_layers)
            if drafts else None,
            unsupported={
                "prefix_cache": (
                    "exaone_moe's window layers keep a ring of the last "
                    f"{W} positions a slot that a shared block cannot give "
                    "back: no snapshot of the ring is kept at a block's "
                    "end, so a prefix hit would start its suffix from the "
                    "wrong window"),
                "tp_degree": why("window ring", "tensor-parallel "
                                 "(head-sharded)"),
                "draft_model": why("window ring", "draft-model") + (
                    ": the model drafts for itself (mtp_draft)"),
                "spec_decode": why("window ring", "n-gram or draft-model "
                                   "speculative") + (
                    ": the model drafts for itself (mtp_draft), one "
                    "position deep"),
                "quant": why("held-expert layer", "weight-quantized")})

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def exaone_moe_tiny(**kw):
    """The CPU tests' size: every mechanism present (a whole LLLG period
    after the dense layer, the module), nothing wide."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
                num_kv_heads=2, head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, sliding_window=8, max_seq_len=256,
                initializer_range=0.1)
    base.update(kw)
    return ExaoneMoeConfig(**base)
