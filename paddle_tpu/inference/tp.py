"""Tensor-parallel serving programs over the device mesh.

Role of the paper's target deployment (GPT-3-class decode on a v5p pod;
SNIPPETS [1]-[3] mesh/NamedSharding patterns): the serving engine's
tick/prefill/decode programs become ``shard_map`` programs over a 'tp'
axis of `distributed/mesh.py`, with weights sharded Megatron-style
(attention heads + FFN/vocab columns) and the paged KV pools sharded
along the HEAD axis.  The host scheduler stays rank-0: block tables,
seq_lens and sampling params are broadcast (replicated inputs), so none
of the scheduler logic changes with the degree.

BIT-PARITY CONTRACT.  TP decode at any degree is bit-identical to
degree 1 because no contraction dimension is ever split:

* every matmul is COLUMN-parallel (output dim sharded) — a local shard
  computes exact column slices of the full matmul, reducing over the
  same elements in the same order;
* attention is per-head independent (heads sharded = batch-like dim);
* activations are re-replicated between matmuls by ``all_gather``
  (deterministic concatenation in device order), never by summing
  partial products (the classic row-parallel all-reduce REORDERS the
  float reduction and loses bitwise parity — on a decode tick the
  gathered activations are tiny, so the extra bytes are noise);
* the vocab-parallel embedding lookup psums one nonzero contribution
  against exact zeros (x + 0.0 == x).

The price is a little more communication volume than an all-reduce
formulation; the win is that greedy streams, the warmup grid and every
parity test are IDENTICAL across degrees — the property the serving
tests pin on a simulated 2-4 device mesh.

Scope: GPT-family models (`models/gpt.py` — pre-LN blocks, fused QKV,
gelu MLP, tied vocab head).  Anything else raises a clear error and
serves at degree 1.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.kv_cache import PagedKVCache

__all__ = ["TPPlan", "build_plan", "shard_plan", "forward_tp",
           "pool_spec", "AXIS"]

AXIS = "tp"


class TPPlan:
    """Host-side description of one model's TP layout: the reshaped
    parameter pytree (qkv as [H, 3, nh, hd] so the head axis is
    shardable), the matching PartitionSpec pytree, and the static dims
    the forward needs."""

    def __init__(self, params: Dict[str, Any], specs: Dict[str, Any],
                 meta: Dict[str, Any]):
        self.params = params
        self.specs = specs
        self.meta = meta


def _leaf(p):
    return p._value if hasattr(p, "_value") else jnp.asarray(p)


def build_plan(model, tp: int) -> TPPlan:
    """Extract + validate the GPT-family parameter layout for degree
    ``tp``.  Raises ValueError for unsupported structures (MoE blocks,
    GQA-free requirement is implicit in the GPT family, dims that do not
    divide the degree)."""
    gpt = getattr(model, "gpt", None)
    cfg = getattr(model, "cfg", None)
    if gpt is None or cfg is None or not hasattr(gpt, "blocks") \
            or not hasattr(gpt, "wte") or not hasattr(gpt, "wpe"):
        raise ValueError(
            "FLAGS_serving_tp_degree > 1 supports GPT-family models "
            f"(got {type(model).__name__}); serve this model at degree 1")
    if getattr(cfg, "moe_num_experts", 0):
        raise ValueError("tensor-parallel serving does not cover MoE "
                         "blocks; serve at degree 1")
    if getattr(cfg, "tensor_parallel", False):
        raise ValueError(
            "model was built with tensor_parallel=True (training-style "
            "mesh sharding); the serving TP path owns its own layout — "
            "build the model with tensor_parallel=False")
    nh, H, V = cfg.num_heads, cfg.hidden_size, cfg.vocab_size
    I = cfg.intermediate_size  # noqa: E741
    for name, dim in (("num_heads", nh), ("intermediate_size", I),
                      ("vocab_size", V)):
        if dim % tp:
            raise ValueError(
                f"serving_tp_degree={tp} must divide {name}={dim}")
    hd = H // nh
    blocks: List[Dict[str, Any]] = []
    specs_blocks: List[Dict[str, Any]] = []
    for blk in gpt.blocks:
        attn, mlp = blk.attn, blk.mlp
        for attr in ("qkv", "proj"):
            if not hasattr(attn, attr):
                raise ValueError("unsupported attention layout for TP "
                                 f"serving: missing attn.{attr}")
        if not hasattr(mlp, "fc1") or not hasattr(mlp, "fc2"):
            raise ValueError("unsupported MLP layout for TP serving "
                             "(expected fc1/fc2)")
        blocks.append({
            "ln1_w": _leaf(blk.ln1.weight), "ln1_b": _leaf(blk.ln1.bias),
            "qkv_w": _leaf(attn.qkv.weight).reshape(H, 3, nh, hd),
            "qkv_b": _leaf(attn.qkv.bias).reshape(3, nh, hd),
            "proj_w": _leaf(attn.proj.weight),
            "proj_b": _leaf(attn.proj.bias),
            "ln2_w": _leaf(blk.ln2.weight), "ln2_b": _leaf(blk.ln2.bias),
            "fc1_w": _leaf(mlp.fc1.weight), "fc1_b": _leaf(mlp.fc1.bias),
            "fc2_w": _leaf(mlp.fc2.weight), "fc2_b": _leaf(mlp.fc2.bias),
        })
        specs_blocks.append({
            "ln1_w": P(), "ln1_b": P(),
            "qkv_w": P(None, None, AXIS, None),
            "qkv_b": P(None, AXIS, None),
            "proj_w": P(None, AXIS), "proj_b": P(AXIS),
            "ln2_w": P(), "ln2_b": P(),
            "fc1_w": P(None, AXIS), "fc1_b": P(AXIS),
            "fc2_w": P(None, AXIS), "fc2_b": P(AXIS),
        })
    params = {"wte": _leaf(gpt.wte.weight), "wpe": _leaf(gpt.wpe.weight),
              "blocks": blocks,
              "lnf_w": _leaf(gpt.ln_f.weight),
              "lnf_b": _leaf(gpt.ln_f.bias)}
    specs = {"wte": P(AXIS, None), "wpe": P(),
             "blocks": specs_blocks, "lnf_w": P(), "lnf_b": P()}
    meta = {"tp": int(tp), "nh": nh, "hd": hd, "H": H, "V": V,
            "V_local": V // tp, "n_layers": cfg.num_layers,
            "ln_eps": [(float(blk.ln1._epsilon), float(blk.ln2._epsilon))
                       for blk in gpt.blocks],
            "lnf_eps": float(gpt.ln_f._epsilon)}
    return TPPlan(params, specs, meta)


def pool_spec():
    """Paged KV pools [nh, num_blocks, bs, hd] shard along the leading
    HEAD axis — each rank holds its heads' blocks of every layer."""
    return P(AXIS)


def shard_plan(plan: TPPlan, mesh) -> Dict[str, Any]:
    """Place the plan's parameters on the mesh with their NamedShardings
    (the TP memory win: each rank holds 1/tp of every sharded matrix);
    returns the device-resident pytree the programs take as input.

    Manual recursion rather than tree_map: PartitionSpec subclasses
    tuple, so a tree_map over the spec tree would recurse INTO the
    specs instead of treating them as leaves."""
    def place(p, s):
        if isinstance(p, dict):
            return {k: place(p[k], s[k]) for k in p}
        if isinstance(p, list):
            return [place(a, b) for a, b in zip(p, s)]
        return jax.device_put(jnp.asarray(p), NamedSharding(mesh, s))
    return place(plan.params, plan.specs)


def _w(leaf):
    """Weight-only quantized leaves (``{"q", "s"}`` pairs installed by
    `inference/quant.quantize_plan`) dequantize IN-TRACE right before
    their matmul — XLA fuses the per-channel scale multiply into the
    contraction, so device weight residency stays the storage format
    (int8 codes or fp8 e4m3fn — `dequantize` is format-agnostic).  The
    scale was computed per channel BEFORE sharding and keeps its
    reduced axis, so each rank's (q, s) shard dequantizes
    bit-identically to a slice of the full dequantized matrix — either
    quant mode composes with the TP bit-parity contract."""
    if isinstance(leaf, dict):
        from ..quantization.weight_only import dequantize
        return dequantize(leaf["q"], leaf["s"])
    return leaf


def _layer_norm(x, w, b, eps):
    # exact mirror of nn/functional/norm.py::_layer_norm_impl over the
    # last axis (the only shape GPT uses) — parity with degree 1 demands
    # the same expression, not an equivalent one
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def forward_tp(meta, params, ids, pools, tables, seq_lens, pos_offset,
               block_size, view_cls=PagedKVCache):
    """One forward over the LOCAL shards — runs inside ``shard_map``.

    ids [B, s] / tables / seq_lens / pos_offset are replicated; params
    and the per-layer (k, v) ``pools`` are this rank's shards.  Returns
    (full [B, s, V] logits — replicated via the final vocab all-gather —
    and the new local pools).  ``view_cls`` selects the cache semantics:
    `PagedKVCache` (decode / from-empty prefill) or `PagedChunkView`
    (prefix-cache suffix prefill)."""
    B, s = ids.shape
    idx = jax.lax.axis_index(AXIS)
    nh, hd, tp = meta["nh"], meta["hd"], meta["tp"]
    nh_l = nh // tp
    Vl = meta["V_local"]
    # vocab-parallel embedding: one rank contributes the row, the psum
    # adds exact zeros elsewhere
    v0 = (idx * Vl).astype(ids.dtype)
    in_range = (ids >= v0) & (ids < v0 + Vl)
    wte = _w(params["wte"])   # also the tied head below
    rows = jnp.take(wte, jnp.clip(ids - v0, 0, Vl - 1), axis=0)
    rows = jnp.where(in_range[..., None], rows, 0)
    pos = jnp.arange(s, dtype=jnp.int32) + pos_offset
    x = jax.lax.psum(rows, AXIS) + jnp.take(params["wpe"], pos, axis=0)

    def gather(h):
        return jax.lax.all_gather(h, AXIS, axis=-1, tiled=True)

    new_pools = []
    for li, blk in enumerate(params["blocks"]):
        eps1, eps2 = meta["ln_eps"][li]
        h = _layer_norm(x, blk["ln1_w"], blk["ln1_b"], eps1)
        qkv = jnp.matmul(h, _w(blk["qkv_w"]).reshape(
            meta["H"], 3 * nh_l * hd)) \
            + blk["qkv_b"].reshape(3 * nh_l * hd)
        # column windows, not a reshape to [B, s, 3, nh_l, hd]: XLA:TPU
        # folds that into the dot and then copies the weight into the
        # other layout every launch (models/gpt.py has the same lines)
        q, k, v = (t.reshape(B, s, nh_l, hd)
                   for t in jnp.split(qkv, 3, axis=-1))
        kp, vp = pools[li]
        view = view_cls.from_parts(kp, vp, tables, seq_lens, block_size)
        new_view, out = view.update_and_attend(q, k, v)
        new_pools.append((new_view.k, new_view.v))
        out = gather(out.reshape(B, s, nh_l * hd))        # heads -> full
        y = gather(jnp.matmul(out, _w(blk["proj_w"])) + blk["proj_b"])
        x = x + y
        h2 = _layer_norm(x, blk["ln2_w"], blk["ln2_b"], eps2)
        a = gather(jax.nn.gelu(
            jnp.matmul(h2, _w(blk["fc1_w"])) + blk["fc1_b"],
            approximate=True))
        x = x + gather(jnp.matmul(a, _w(blk["fc2_w"])) + blk["fc2_b"])
    h = _layer_norm(x, params["lnf_w"], params["lnf_b"], meta["lnf_eps"])
    logits = gather(jnp.matmul(h, jnp.swapaxes(wte, -1, -2)))
    return logits, new_pools
