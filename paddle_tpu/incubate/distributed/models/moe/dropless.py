"""Dropless top-k routing and an expert layer that knows its share.

The fixed-capacity gates of `gate.py` drop the tokens an expert has no
slot for.  The DeepSeek-V3 family (`topk_method: noaux_tc`) drops none:
each token scores every expert with a sigmoid, a learned per-expert bias
is added FOR THE CHOICE only, the `top_k` best are taken, and their
un-biased scores, normalised over the picked and scaled, mix the expert
outputs.  `SigmoidTopKGate` is that decision.  The Qwen3-MoE family
routes by `SoftmaxTopKGate`: a softmax over the experts, the `top_k`
largest, renormalised over the picked; no bias, no scaling.

`HeldExpertsLayer` is what expert parallelism asks of a layer: it is told
which experts live here (`expert_offset`, `n_experts_held` of the
router's `num_expert`), routes over ALL of them, and computes the part of
the result its own experts give — the rows routed elsewhere contribute
nothing here (their owners add theirs; on one chip nothing stands in for
them).  `g` is normalised over every picked expert, held or not, so the
shares of all chips add up to the uncut layer.  The experts run as
grouped matmuls over the rows sorted by expert (`ops/pallas_moe.
grouped_matmul`): only the weights of experts that got a row are read.

Inference only: the routing and the grouped matmuls work on raw arrays
and put nothing on the autograd tape.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.nn import Layer
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.ops import pallas_moe as _pm

__all__ = ["SigmoidTopKGate", "SoftmaxTopKGate", "HeldExpertsLayer",
           "publish_expert_rows"]

_M_ROWS = _metrics.counter(
    "moe.local_expert_tokens", "rows (token x routing choice) given to "
    "each expert held here, by layer, expert (its id in the router) and "
    "kind of program (decode | chunk); counted on the device by the "
    "layer, published from ServingEngine.stats()['cache_state']")


def publish_expert_rows(layer_rows, expert_offset: int = 0) -> None:
    """Add device-side counts (`[2 kinds, 2, held]` a layer, as the
    models' blocks accumulate them) to `moe.local_expert_tokens`.  The
    caller passes the growth since its last call."""
    for li, rows in enumerate(layer_rows):
        for ki, kind in enumerate(("decode", "chunk")):
            for e, n in enumerate(rows[ki][0]):
                if n:
                    _M_ROWS.inc(int(n), layer=li, expert=expert_offset + e,
                                kind=kind)


class SigmoidTopKGate(Layer):
    """`s = sigmoid(x W)` in float32; pick the `top_k` largest of
    `s + e_score_correction_bias`; weights `s[picked] / sum s[picked] *
    routed_scaling_factor`.  No capacity, no drop, no auxiliary loss."""

    def __init__(self, d_model: int, num_expert: int, top_k: int,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True):
        super().__init__()
        self.num_expert, self.top_k = num_expert, top_k
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = norm_topk_prob
        self.weight = self.create_parameter([d_model, num_expert])
        self.e_score_correction_bias = self.create_parameter(
            [num_expert], is_bias=True)

    def route(self, x):
        """x: array `[T, d_model]` -> (picked `[T, k]` int32, weights
        `[T, k]` float32)."""
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), self.weight._value.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        bias = self.e_score_correction_bias._value.astype(jnp.float32)
        _, picked = jax.lax.top_k(s + bias, self.top_k)
        g = jnp.take_along_axis(s, picked, axis=-1)
        if self.norm_topk_prob:
            g = g / g.sum(-1, keepdims=True)
        return picked.astype(jnp.int32), g * self.routed_scaling_factor

    def forward(self, x):
        picked, g = self.route(x._value.reshape(-1, x.shape[-1]))
        return Tensor._wrap(picked), Tensor._wrap(g)


class SoftmaxTopKGate(Layer):
    """`p = softmax(x W)` over the experts in float32; pick the `top_k`
    largest; weights `p[picked] / sum p[picked]` (`norm_topk_prob`), or
    `p[picked]` as they are.  No bias, no scaling, no capacity, no drop.
    `weight_attr` places the router's weight (its initialiser)."""

    def __init__(self, d_model: int, num_expert: int, top_k: int,
                 norm_topk_prob: bool = True, weight_attr=None):
        super().__init__()
        self.num_expert, self.top_k = num_expert, top_k
        self.norm_topk_prob = norm_topk_prob
        self.weight = self.create_parameter([d_model, num_expert],
                                            attr=weight_attr)

    def route(self, x):
        """x: array `[T, d_model]` -> (picked `[T, k]` int32, weights
        `[T, k]` float32)."""
        p = jax.nn.softmax(jnp.matmul(
            x.astype(jnp.float32), self.weight._value.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        g, picked = jax.lax.top_k(p, self.top_k)
        if self.norm_topk_prob:
            g = g / g.sum(-1, keepdims=True)
        return picked.astype(jnp.int32), g

    def forward(self, x):
        picked, g = self.route(x._value.reshape(-1, x.shape[-1]))
        return Tensor._wrap(picked), Tensor._wrap(g)


class HeldExpertsLayer(Layer):
    """`y = sum_{picked e held here} g_e E_e(x) + shared(x)`, `E` a
    SwiGLU MLP of width `d_hidden`.  `gate` is the routing decision over
    the router's `gate.num_expert` experts (a Layer with `route(x) ->
    (picked [T, k], weights [T, k])`: `SigmoidTopKGate`,
    `SoftmaxTopKGate`).  `shared` (a Layer, or None) is the part every
    chip computes alike.  `expert_init(lo, hi)` builds the initialiser of
    the stacked expert weights (uniform(lo, hi) if None)."""

    def __init__(self, d_model: int, d_hidden: int, gate: Layer,
                 n_experts_held: int = None, expert_offset: int = 0,
                 shared: Layer = None, expert_init=None):
        super().__init__()
        num_expert = gate.num_expert
        held = num_expert if n_experts_held is None else int(n_experts_held)
        if not 0 <= expert_offset <= expert_offset + held <= num_expert:
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset + held}) are not "
                f"among the router's {num_expert}")
        self.num_expert, self.held, self.offset = num_expert, held, \
            int(expert_offset)
        self.gate = gate
        self.experts = _HeldExperts(held, d_model, d_hidden, expert_init)
        self.shared_experts = shared

    def forward(self, x):
        return self.forward_counted(x)[0]

    def forward_counted(self, x, active=None):
        """(`y`, the rows each held expert was given `[n_experts_held]`
        int32).  `active` (`[T]` bool over the flattened tokens, or None
        for all) marks the real tokens of a padded batch: the others are
        routed to no expert, cost no expert's weights and are not
        counted; they still get the shared part."""
        shape = x.shape
        xt = x._value.reshape(-1, shape[-1])
        with jax.named_scope("moe_route"):
            picked, g = self.gate.route(xt)
        with jax.named_scope("moe_experts"):
            y, rows = self.experts.mix(xt, picked, g, self.num_expert,
                                       self.offset, active)
        out = Tensor._wrap(y.astype(xt.dtype).reshape(shape))
        if self.shared_experts is not None:
            with jax.named_scope("moe_shared"):
                out = out + self.shared_experts(x)
        return out, rows


class _HeldExperts(Layer):
    """The stacked SwiGLU weights of the experts held here."""

    def __init__(self, held: int, d_model: int, d_hidden: int, init=None):
        super().__init__()
        init = init or paddle.nn.initializer.Uniform
        a, b = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_hidden)
        self.gate_proj = self.create_parameter(
            [held, d_model, d_hidden], default_initializer=init(-a, a))
        self.up_proj = self.create_parameter(
            [held, d_model, d_hidden], default_initializer=init(-a, a))
        self.down_proj = self.create_parameter(
            [held, d_hidden, d_model], default_initializer=init(-b, b))

    def mix(self, x, picked, g, num_expert: int, offset: int, active=None):
        """x `[T, M]`, picked / g `[T, k]` over the router's
        `num_expert`, active `[T]` bool or None.  Returns (the held
        experts' weighted part `[T, M]` float32, rows given to each held
        expert `[held]` int32).  The rows of inactive tokens sort behind
        every expert's, into a group of their own that no chip holds (the
        grouped matmul's pad rows join it)."""
        T, k = picked.shape
        held = self.gate_proj.shape[0]
        if active is not None:
            picked = jnp.where(active[:, None], picked, num_expert)
        flat = picked.reshape(-1)
        order = jnp.argsort(flat, stable=True)         # rows by expert
        sizes = jnp.bincount(flat, length=num_expert + 1).astype(jnp.int32)
        rows = jnp.take(x, order // k, axis=0)                   # [T*k, M]
        gm = lambda a, w: _pm.grouped_matmul(                  # noqa: E731
            a, w._value, sizes, offset)
        h = jax.nn.silu(gm(rows, self.gate_proj)) * gm(rows, self.up_proj)
        y = gm(h.astype(x.dtype), self.down_proj)                # float32
        e_sorted = jnp.take(flat, order)
        here = (e_sorted >= offset) & (e_sorted < offset + held)
        w = jnp.where(here, jnp.take(g.reshape(-1), order), 0.0)
        y = jnp.where(here[:, None], y, 0.0) * w[:, None]
        back = jnp.argsort(order)                      # undo the sort
        y = jnp.take(y, back, axis=0).reshape(T, k, -1).sum(1)
        return y, jax.lax.dynamic_slice_in_dim(sizes, offset, held)
