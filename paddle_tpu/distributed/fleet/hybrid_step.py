"""Hybrid-parallel (dp x mp x pp, + Megatron-SP, + ZeRO) SPMD train step.

This is the TPU-native counterpart of the reference's Fleet hybrid training
path (`fleet/fleet.py:167` + `fleet/meta_parallel/pipeline_parallel.py:458`
forward_backward_pipeline + `fleet/layers/mpu/mp_layers.py` +
`fleet/meta_parallel/sharding/dygraph_sharding_optimizer.py:44`): ONE jitted
SPMD program over a `jax.sharding.Mesh` with axes (pp, dp, mp) that runs

* **PP**  — the microbatch pipeline with `lax.ppermute` moving activations
  over the pp axis (compiles to ICI collective-permute). Only per-microbatch
  *scalars* (the loss) cross stages outside the schedule; activations flow
  strictly neighbor-to-neighbor.
* **TP**  — Megatron column/row-parallel QKV/MLP with explicit `psum` /
  `psum_scatter` over the mp axis (reference `mp_layers.py:334,:541`) and a
  vocab-parallel embedding + parallel softmax cross-entropy
  (reference `mp_layers.py:47,:742`).
* **SP**  — Megatron-style sequence parallelism fused with TP (reference
  `fleet/utils/sequence_parallel_utils.py:85-395`): activations between the
  TP blocks are sharded over the *sequence* dim on the mp axis; entering a
  TP region all-gathers the sequence, leaving it reduce-scatters — so the
  LayerNorm/residual work and memory are 1/mp per rank.
* **DP + ZeRO-1** — batch sharded over dp; gradients all-reduced over dp;
  optimizer (Adam) state sharded over dp (reference
  `dygraph_sharding_optimizer.py:44`): each dp rank updates 1/dp of every
  parameter and all-gathers the result.
* **remat** — each pipeline stage runs under `jax.checkpoint`, bounding
  live activations to one microbatch per stage (the 1F1B memory profile;
  reference `passes/pipeline_scheduler_pass/pipeline_1f1b.py`).

Backward is jax AD *through the whole schedule* — every collective has an
exact transpose (ppermute -> reverse permute, psum_scatter <-> all_gather),
so the backward pipeline and the TP/SP gradient collectives fall out of the
forward description.

The serial functions (`serial_forward`, `serial_train_step`) implement the
identical math without collectives; tests assert loss parity to ~1e-4.
Expert parallelism lives in `paddle_tpu.incubate.moe` (separate module).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


__all__ = [
    "HybridConfig", "init_gpt_params", "stack_for_pipeline",
    "hybrid_param_specs", "init_zero_state", "zero_state_specs",
    "make_hybrid_train_step",
    "make_zero3_train_step", "init_zero3_state", "zero3_unflatten",
    "zero3_train_state", "save_zero3_state", "load_zero3_state",
    "hybrid_train_state", "save_hybrid_state", "load_hybrid_state",
    "serial_train_step", "serial_forward",
]


@dataclass
class HybridConfig:
    vocab_size: int = 128
    hidden_size: int = 64
    num_layers: int = 4
    num_heads: int = 4
    seq_len: int = 32
    intermediate_size: int = 0
    # parallel degrees
    pp: int = 2
    mp: int = 2
    dp: int = 2
    vpp: int = 1  # virtual pipeline chunks per pp rank (interleaved sched)
    n_microbatches: int = 2
    sequence_parallel: bool = True
    # context parallelism (the reference's sep axis, `fleet/base/
    # topology.py` sep dim): activations stay sequence-sharded over the
    # 'cp' mesh axis through the WHOLE block; attention crosses the axis
    # by ring ppermute (`ring_attention_local`) or head all-to-all
    # (`ulysses_attention_local`), labels cross the shard boundary by a
    # one-token ppermute, and the LM loss reduces over cp.
    cp: int = 1
    cp_attention: str = "ring"    # "ring" | "ulysses"
    remat: bool = True
    # MoE / expert parallelism: with moe_num_experts > 0 every block's MLP
    # becomes a top-1 (switch) mixture of experts; experts are sharded over
    # the dp axis and tokens move by a sort-based all_to_all (the TPU-native
    # global_scatter/global_gather, ref moe_utils.py / moe_layer.py:263).
    # moe_capacity = per-destination-rank token capacity (0 = no dropping:
    # capacity equals the local token count, what the parity tests use).
    moe_num_experts: int = 0
    moe_capacity: int = 0
    # ZeRO stage over dp: 1 = all-reduce grads then update a 1/dp slice;
    # 2 = reduce-scatter grads (each rank only ever holds its own grad
    # shard — the SPMD form of sharded gradients,
    # ref group_sharded_stage2.py) — strictly less HBM and comm;
    # 3 = parameters themselves live sharded (the fused ZeRO-3 step of
    # `make_zero3_train_step`: dp-only FSDP, bucketed in-program
    # gathers; `make_hybrid_train_step` treats 3 as 2).
    zero_stage: int = 1
    # optimizer
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        assert self.num_layers % (self.pp * self.vpp) == 0
        assert self.num_heads % self.mp == 0
        assert self.hidden_size % self.num_heads == 0
        assert self.vocab_size % self.mp == 0
        if self.sequence_parallel:
            assert self.seq_len % self.mp == 0
        if self.vpp > 1:
            # the interleaved schedule processes microbatches in blocks of
            # pp (same constraint as Megatron's num_microbatches % pp == 0)
            assert self.n_microbatches % self.pp == 0
        assert self.cp_attention in ("ring", "ulysses")
        if self.cp > 1:
            assert self.mp == 1 and not self.sequence_parallel, \
                "context parallel composes with pp/dp; combine with " \
                "Megatron TP-SP per-config, not both in one block"
            assert self.seq_len % self.cp == 0
            assert self.moe_num_experts == 0
            if self.cp_attention == "ulysses":
                assert self.num_heads % self.cp == 0
        if self.moe_num_experts > 0:
            assert self.moe_num_experts % self.dp == 0, \
                "experts shard over the dp axis"
            assert self.mp == 1 or self.sequence_parallel, \
                "MoE with mp>1 needs sequence_parallel (each mp rank " \
                "must route a disjoint token shard)"

    @property
    def layers_per_stage(self):
        """Layers per model CHUNK (a pp rank owns vpp chunks)."""
        return self.num_layers // (self.pp * self.vpp)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# --------------------------------------------------------------------------
# parameter init (serial layout) and pipeline stacking
# --------------------------------------------------------------------------

def init_gpt_params(key, cfg: HybridConfig) -> Dict[str, Any]:
    """Serial GPT parameter pytree: blocks as stacked [L, ...] leaves."""
    H, I, V, S, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                     cfg.seq_len, cfg.num_layers)
    ks = jax.random.split(key, 8)
    std = 0.02
    dt = cfg.dtype

    def nrm(k, shape, scale=std):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    blocks = {
        "ln1_g": jnp.ones((L, H), dt), "ln1_b": jnp.zeros((L, H), dt),
        "wqkv": nrm(ks[0], (L, H, 3 * H)), "bqkv": jnp.zeros((L, 3 * H), dt),
        "wproj": nrm(ks[1], (L, H, H), std / math.sqrt(2 * L)),
        "bproj": jnp.zeros((L, H), dt),
        "ln2_g": jnp.ones((L, H), dt), "ln2_b": jnp.zeros((L, H), dt),
    }
    if cfg.moe_num_experts > 0:
        E = cfg.moe_num_experts
        blocks.update({
            "wgate": nrm(ks[7], (L, H, E)),
            "wexp1": nrm(ks[2], (L, E, H, I)),
            "wexp2": nrm(ks[3], (L, E, I, H), std / math.sqrt(2 * L)),
        })
    else:
        blocks.update({
            "wfc1": nrm(ks[2], (L, H, I)), "bfc1": jnp.zeros((L, I), dt),
            "wfc2": nrm(ks[3], (L, I, H), std / math.sqrt(2 * L)),
            "bfc2": jnp.zeros((L, H), dt),
        })
    return {
        "blocks": blocks,
        "wte": nrm(ks[4], (V, H)),
        "wpe": nrm(ks[5], (S, H)),
        "lnf_g": jnp.ones((H,), dt), "lnf_b": jnp.zeros((H,), dt),
        "head": nrm(ks[6], (H, V)),
    }


def stack_for_pipeline(params: Dict[str, Any], cfg: HybridConfig):
    """Reshape block leaves [L, ...] -> [pp, vpp, L/(pp*vpp), ...].

    Global chunk g (layers [g*Lc, (g+1)*Lc)) lives on pp rank g % pp at
    chunk slot g // pp — the Megatron interleaved assignment
    (`pipeline_parallel.py:986`); with vpp=1 this is plain contiguous
    stage stacking."""
    out = dict(params)

    def restack(v):
        lc = cfg.layers_per_stage
        # [L, ...] -> [vpp*pp, Lc, ...] (global chunk major) ->
        # [vpp, pp, Lc, ...] -> [pp, vpp, Lc, ...]
        w = v.reshape((cfg.vpp * cfg.pp, lc) + v.shape[1:])
        w = w.reshape((cfg.vpp, cfg.pp, lc) + v.shape[1:])
        return jnp.swapaxes(w, 0, 1)

    out["blocks"] = {k: restack(v) for k, v in params["blocks"].items()}
    return out


def hybrid_param_specs(cfg: HybridConfig) -> Dict[str, Any]:
    """PartitionSpec tree matching `stack_for_pipeline` output.

    TP layout mirrors the reference mp_layers: qkv/fc1 column-parallel
    (out-dim on mp), proj/fc2 row-parallel (in-dim on mp), embedding
    vocab-parallel, LM head column-parallel over vocab.  Block leaves are
    [pp, vpp, Lc, ...]: pp sharded, vpp/Lc replicated locally."""
    blocks = {
        "ln1_g": P("pp"), "ln1_b": P("pp"),
        "wqkv": P("pp", None, None, None, "mp"),
        "bqkv": P("pp", None, None, "mp"),
        "wproj": P("pp", None, None, "mp", None), "bproj": P("pp"),
        "ln2_g": P("pp"), "ln2_b": P("pp"),
    }
    if cfg.moe_num_experts > 0:
        # expert parallelism: the expert dim shards over dp (the reference's
        # EP-in-DP layout); gate replicated, tokens move via all_to_all
        blocks.update({
            "wgate": P("pp"),
            "wexp1": P("pp", None, None, "dp", None, None),
            "wexp2": P("pp", None, None, "dp", None, None),
        })
    else:
        blocks.update({
            "wfc1": P("pp", None, None, None, "mp"),
            "bfc1": P("pp", None, None, "mp"),
            "wfc2": P("pp", None, None, "mp", None), "bfc2": P("pp"),
        })
    return {
        "blocks": blocks,
        "wte": P("mp", None),
        "wpe": P(),
        "lnf_g": P(), "lnf_b": P(),
        "head": P(None, "mp"),
    }


def _spec_axes(spec: P):
    return tuple(a for a in spec if a is not None)


def _flatten_with_specs(tree, specs):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    assert len(leaves) == len(spec_leaves)
    return leaves, spec_leaves, treedef


def _opt_spec(s: P) -> P:
    """Opt-state spec for a param spec: ZeRO shards the flattened state
    over dp — unless the param itself is dp-sharded (expert-parallel
    leaves), where the state follows the param layout positionally."""
    axes = _spec_axes(s)
    return s if "dp" in axes else P(*axes, "dp")


def zero_state_specs(specs: Dict[str, Any]):
    """Opt-state PartitionSpec tree without materializing any state."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_unflatten(
        treedef, [_opt_spec(s) for s in leaves])


def init_zero_state(stacked: Dict[str, Any], specs: Dict[str, Any],
                    mesh: Mesh) -> Tuple[Any, Any, Any]:
    """Adam (m, v) with every leaf flattened and sharded over dp (ZeRO-1).

    For a param leaf with global shape G and spec axes A, the local shard
    has F = prod(G / sizes(A)) elements; the opt leaf's global shape is
    [sizes(A)..., dp*ceil(F/dp)] with spec P(*A, 'dp') — so inside
    shard_map each device holds exactly its own [Fp/dp] slice.
    Returns (m, v, opt_specs) with m/v/opt_specs matching `stacked`'s
    structure."""
    dp = mesh.shape["dp"]
    leaves, spec_leaves, treedef = _flatten_with_specs(stacked, specs)

    def leaf_state(p, spec):
        axes = _spec_axes(spec)
        if "dp" in axes:
            # expert-parallel leaf: state follows the param layout exactly
            return jnp.zeros(p.shape, p.dtype)
        local_shape = list(p.shape)
        for i, a in enumerate(spec):
            if a is not None:
                local_shape[i] //= mesh.shape[a]
        F = int(np.prod(local_shape))
        Fp = dp * ((F + dp - 1) // dp)
        gshape = tuple(mesh.shape[a] for a in axes) + (Fp,)
        return jnp.zeros(gshape, p.dtype)

    m = [leaf_state(p, s) for p, s in zip(leaves, spec_leaves)]
    opt_spec_leaves = [_opt_spec(s) for s in spec_leaves]
    un = jax.tree_util.tree_unflatten
    return (un(treedef, m), un(treedef, [jnp.copy(x) for x in m]),
            un(treedef, opt_spec_leaves))


# --------------------------------------------------------------------------
# model math (shared by serial and SPMD paths)
# --------------------------------------------------------------------------

def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _attention(q, k, v):
    # q,k,v: [B, S, nh, hd] -> [B, S, nh, hd], causal
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attention_cp(q, k, v, cp_axis, mode):
    """Causal attention with the sequence sharded over `cp_axis`: ring
    ppermute hops or Ulysses head-alltoall (SURVEY §5.7; ref
    `fleet/meta_parallel/segment_parallel.py`).  q/k/v [B, s, nh, hd]."""
    from ...incubate.nn.functional.ring_attention import (
        ring_attention_local, ulysses_attention_local)
    fn = ring_attention_local if mode == "ring" else ulysses_attention_local
    tb = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # [B,s,nh,hd]<->[B,nh,s,hd]
    return tb(fn(tb(q), tb(k), tb(v), cp_axis, causal=True))


def _gate_top1(h2, wg):
    """Switch (top-1) router.  h2 [T, H], wg [H, E] -> (expert [T] int32,
    prob [T]); grads flow through the chosen expert's softmax prob."""
    logits = (h2 @ wg).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    a = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    p = jnp.take_along_axis(probs, a[:, None], axis=1)[:, 0]
    return a, p.astype(h2.dtype)


def _moe_ffn_serial(blocks, x, lidx, cfg):
    """Reference-math switch FFN: every token to its argmax expert, no
    capacity dropping, output scaled by the gate prob."""
    B, S, H = x.shape
    h2 = x.reshape(B * S, H)
    a, p = _gate_top1(h2, blocks["wgate"][lidx])
    y = jnp.zeros_like(h2)
    for e in range(cfg.moe_num_experts):
        ye = jax.nn.gelu(h2 @ blocks["wexp1"][lidx, e], approximate=True)
        ye = ye @ blocks["wexp2"][lidx, e]
        y = y + jnp.where((a == e)[:, None], ye, 0.0)
    return (y * p[:, None]).reshape(B, S, H)


def _moe_ffn_dist(blocks, x, lidx, cfg, dp_axis="dp"):
    """Expert-parallel switch FFN inside shard_map: the TPU-native
    global_scatter/global_gather (ref
    `python/paddle/distributed/utils/moe_utils.py`,
    `moe/moe_layer.py:99,:152` MoEScatter/MoEGather).

    Tokens are sorted by destination rank, packed into fixed [DP, C, H]
    lanes (C = per-destination capacity; static shapes are the XLA
    constraint the reference's ragged NCCL alltoall doesn't have), moved
    with `lax.all_to_all`, run through the local expert shard, moved back
    and unsorted.  Dropped tokens (beyond C) contribute zero — their
    residual path passes through.  The sort/scatter indices are integer
    (non-differentiable); gradients ride the gathered values and the gate
    prob, and the all_to_all transposes to the reverse all_to_all."""
    DP = jax.lax.axis_size(dp_axis)
    E = cfg.moe_num_experts
    El = E // DP
    B, S, H = x.shape
    T = B * S
    C = cfg.moe_capacity if cfg.moe_capacity > 0 else T
    h2 = x.reshape(T, H)
    a, p = _gate_top1(h2, blocks["wgate"][lidx])
    dest = a // El                              # destination dp rank [T]
    order = jnp.argsort(dest, stable=True)
    d_s = dest[order]
    # position of each sorted token within its destination lane
    onehot = jax.nn.one_hot(d_s, DP, dtype=jnp.int32)
    pos_s = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), d_s[:, None],
                                axis=1)[:, 0] - 1
    keep = pos_s < C
    # pack tokens + local expert ids ('drop' mode discards over-capacity)
    send_x = jnp.zeros((DP, C, H), x.dtype).at[d_s, pos_s].set(
        jnp.where(keep[:, None], h2[order], 0.0), mode="drop")
    loc_e = a[order] - d_s * El
    send_e = jnp.full((DP, C), El, jnp.int32).at[d_s, pos_s].set(
        jnp.where(keep, loc_e, El), mode="drop")   # El = invalid marker
    recv_x = jax.lax.all_to_all(send_x, dp_axis, 0, 0)   # [DP, C, H]
    recv_e = jax.lax.all_to_all(send_e, dp_axis, 0, 0)
    rx = recv_x.reshape(DP * C, H)
    re = recv_e.reshape(DP * C)
    y = jnp.zeros_like(rx)
    # static loop over the few local experts; masked compute (a sorted
    # segment matmul would avoid the (El-1)x waste — El is small here)
    for e in range(El):
        ye = jax.nn.gelu(rx @ blocks["wexp1"][lidx, e],
                         approximate=True) @ blocks["wexp2"][lidx, e]
        y = y + jnp.where((re == e)[:, None], ye, 0.0)
    back = jax.lax.all_to_all(y.reshape(DP, C, H), dp_axis, 0, 0)
    y_sorted = back[d_s, pos_s] * keep[:, None]
    y_tok = jnp.zeros((T, H), x.dtype).at[order].set(y_sorted)
    return (y_tok * p[:, None]).reshape(B, S, H)


def _block(p, x, lidx, nh_local, *, mp_axis=None, seq_parallel=False,
           cfg=None, dp_axis=None, cp_axis=None):
    """One pre-LN transformer block.  Serial when mp_axis is None.

    With seq_parallel, x enters/leaves sequence-sharded [B, S/mp, H]; the
    TP regions (QKV..proj, FC1..FC2) see the full sequence via all-gather
    in / reduce-scatter out (the AllGatherOp/ReduceScatterOp pair of
    `sequence_parallel_utils.py:85-137`, as plain XLA collectives whose
    transposes give the backward).

    With cfg.moe_num_experts > 0 the MLP is a switch MoE; in the
    distributed path (dp_axis set) it runs on the LOCAL tokens (the
    seq-sharded activations — no mp collectives), expert-parallel over
    dp via all_to_all."""
    take = lambda leaf: p[leaf][lidx]

    def enter_tp(h):  # [B, s, H] -> [B, S, H]
        if seq_parallel:
            return jax.lax.all_gather(h, mp_axis, axis=1, tiled=True)
        return h

    def leave_tp(h):  # row-parallel output: sum partials, re-shard seq
        if seq_parallel:
            return jax.lax.psum_scatter(h, mp_axis, scatter_dimension=1,
                                        tiled=True)
        if mp_axis is not None:
            return jax.lax.psum(h, mp_axis)
        return h

    B = x.shape[0]
    h = _layer_norm(x, take("ln1_g"), take("ln1_b"))
    h = enter_tp(h)
    S = h.shape[1]
    # wqkv's 3H output dim is laid out [nh, 3, hd] (per-head q,k,v
    # contiguous, Megatron-style) so an mp column-shard is whole heads
    qkv = h @ take("wqkv") + take("bqkv")      # [B, S, 3*H/mp]
    qkv = qkv.reshape(B, S, nh_local, 3, -1)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    if cp_axis is not None:
        a = _attention_cp(q, k, v, cp_axis, cfg.cp_attention)
        a = a.reshape(B, S, -1)
    else:
        a = _attention(q, k, v).reshape(B, S, -1)
    a = leave_tp(a @ take("wproj"))
    x = x + a + take("bproj")
    h = _layer_norm(x, take("ln2_g"), take("ln2_b"))
    if cfg is not None and cfg.moe_num_experts > 0:
        # MoE replaces the dense MLP; runs on the local (possibly
        # seq-sharded) tokens — token parallelism over mp, expert
        # parallelism over dp
        if dp_axis is not None:
            return x + _moe_ffn_dist(p, h, lidx, cfg, dp_axis)
        return x + _moe_ffn_serial(p, h, lidx, cfg)
    h = enter_tp(h)
    f = jax.nn.gelu(h @ take("wfc1") + take("bfc1"), approximate=True)
    f = leave_tp(f @ take("wfc2"))
    return x + f + take("bfc2")


def _lm_loss(logits, labels, *, mp_axis=None, vstart=0, sstart=0,
             seq_total=None, seq_axis=None):
    """Causal-LM loss over logits [B, S, V(/mp)]; ignores the last position.

    With mp_axis set this is the parallel softmax cross-entropy of
    `mp_layers.py:742` ParallelCrossEntropy: logits stay vocab-sharded and
    only [B, S] reductions cross the mp axis."""
    logits = logits.astype(jnp.float32)
    # max subtraction is gradient-neutral in logsumexp -> stop_gradient
    # (pmax has no transpose rule, and none is needed)
    mx = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    if mp_axis is not None:
        mx = jax.lax.stop_gradient(jax.lax.pmax(mx, mp_axis))
    se = jnp.sum(jnp.exp(logits - mx), axis=-1)
    if mp_axis is not None:
        se = jax.lax.psum(se, mp_axis)
    logz = jnp.squeeze(mx, -1) + jnp.log(se)          # [B, S]
    Vloc = logits.shape[-1]
    loc = labels - vstart
    in_range = (loc >= 0) & (loc < Vloc)
    tgt = jnp.take_along_axis(
        logits, jnp.clip(loc, 0, Vloc - 1)[..., None], axis=-1)[..., 0]
    tgt = jnp.where(in_range, tgt, 0.0)
    if mp_axis is not None:
        tgt = jax.lax.psum(tgt, mp_axis)
    nll = logz - tgt                                   # [B, S]
    S_tot = seq_total if seq_total is not None else nll.shape[1]
    # ignore the GLOBAL last position (sstart/seq_total place a
    # seq-sharded rank's rows on the global axis)
    mask = (sstart + jnp.arange(nll.shape[1])) < S_tot - 1
    tot = jnp.sum(nll * mask)
    if seq_axis is not None:
        tot = jax.lax.psum(tot, seq_axis)
        return tot / (S_tot - 1) / nll.shape[0]
    return tot / jnp.sum(mask) / nll.shape[0]


# --------------------------------------------------------------------------
# serial reference path
# --------------------------------------------------------------------------

def serial_forward(params, ids, cfg: HybridConfig):
    """ids [B, S] -> mean causal-LM loss (labels = ids shifted left)."""
    S = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:S]
    for l in range(cfg.num_layers):
        x = _block(params["blocks"], x, l, cfg.num_heads, cfg=cfg)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["head"]
    labels = jnp.roll(ids, -1, axis=1)
    return _lm_loss(logits, labels)


def _adam_math(p, g, m, v, step, cfg: HybridConfig):
    m2 = cfg.beta1 * m + (1 - cfg.beta1) * g
    v2 = cfg.beta2 * v + (1 - cfg.beta2) * jnp.square(g)
    mh = m2 / (1 - cfg.beta1 ** step)
    vh = v2 / (1 - cfg.beta2 ** step)
    return p - cfg.learning_rate * mh / (jnp.sqrt(vh) + cfg.eps), m2, v2


def serial_train_step(params, m, v, step, ids, cfg: HybridConfig):
    """One Adam step on the serial model; ids [M, B, S] (same microbatch
    grouping as the pipeline so loss parity is exact)."""
    M = cfg.n_microbatches

    def loss_fn(ps):
        per_mb = jnp.stack([serial_forward(ps, ids[i], cfg)
                            for i in range(M)])
        return jnp.mean(per_mb)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    m_leaves = jax.tree_util.tree_leaves(m)
    v_leaves = jax.tree_util.tree_leaves(v)
    new_p, new_m, new_v = [], [], []
    for p, g, mm, vv in zip(leaves, g_leaves, m_leaves, v_leaves):
        p2, m2, v2 = _adam_math(p, g, mm, vv, step, cfg)
        new_p.append(p2); new_m.append(m2); new_v.append(v2)
    un = jax.tree_util.tree_unflatten
    return (loss, un(treedef, new_p), un(treedef, new_m),
            un(treedef, new_v))


# --------------------------------------------------------------------------
# SPMD hybrid step
# --------------------------------------------------------------------------

def make_hybrid_train_step(mesh: Mesh, cfg: HybridConfig):
    """Build the jitted hybrid train step over mesh axes (pp, dp, mp).

    Returns step(stacked_params, m, v, step_no, ids) -> (loss, params, m, v)
    where ids is [M, B, S] int32 (dp-sharded on B) and step_no is the
    1-based Adam step (float).  All parallelism happens inside ONE shard_map;
    XLA's latency-hiding scheduler overlaps the ppermutes and TP collectives
    with compute."""
    specs = hybrid_param_specs(cfg)
    PP, MP, DP, VPP, CP = cfg.pp, cfg.mp, cfg.dp, cfg.vpp, cfg.cp
    M = cfg.n_microbatches
    nh_local = cfg.num_heads // MP
    Vloc = cfg.vocab_size // MP
    sp = cfg.sequence_parallel

    # opt-state specs (structure-matched to params)
    opt_specs = zero_state_specs(specs)

    spec_leaves = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]

    def device_fn(params, m, v, step_no, ids_local):
        pp_i = jax.lax.axis_index("pp")
        mp_i = jax.lax.axis_index("mp")
        dp_i = jax.lax.axis_index("dp")
        cp_i = jax.lax.axis_index("cp") if CP > 1 else 0
        # drop the unit leading pp dim of the local stage-param shards;
        # block leaves keep their [vpp, Lc, ...] chunk stack
        local = dict(params)
        local["blocks"] = {k: leaf[0]
                           for k, leaf in params["blocks"].items()}

        def embed(ps, ids):  # [B, S] -> [B, S(/mp), H], vocab-parallel
            loc = ids - mp_i * Vloc
            ok = (loc >= 0) & (loc < Vloc)
            e = jnp.where(ok[..., None],
                          jnp.take(ps["wte"], jnp.clip(loc, 0, Vloc - 1),
                                   axis=0), 0.0)
            if sp:
                e = jax.lax.psum_scatter(e, "mp", scatter_dimension=1,
                                         tiled=True)
                s = e.shape[1]
                pos = jax.lax.dynamic_slice_in_dim(
                    ps["wpe"], mp_i * s, s, axis=0)
            else:
                e = jax.lax.psum(e, "mp")
                if CP > 1:   # rows of the cp-sharded sequence
                    pos = jax.lax.dynamic_slice_in_dim(
                        ps["wpe"], cp_i * ids.shape[1], ids.shape[1],
                        axis=0)
                else:
                    pos = ps["wpe"][:ids.shape[1]]
            return e + pos

        def stage(chunk, h):
            for l in range(cfg.layers_per_stage):
                h = _block(chunk, h, l, nh_local, mp_axis="mp",
                           seq_parallel=sp, cfg=cfg, dp_axis="dp",
                           cp_axis="cp" if CP > 1 else None)
            return h

        stage_fn = jax.checkpoint(stage) if cfg.remat else stage

        def head_loss(ps, h, labels):
            h = _layer_norm(h, ps["lnf_g"], ps["lnf_b"])
            if sp:
                h = jax.lax.all_gather(h, "mp", axis=1, tiled=True)
            logits = h @ ps["head"]
            if CP > 1:
                s_loc = labels.shape[1]
                return _lm_loss(logits, labels, mp_axis="mp",
                                vstart=mp_i * Vloc, sstart=cp_i * s_loc,
                                seq_total=s_loc * CP, seq_axis="cp")
            return _lm_loss(logits, labels, mp_axis="mp",
                            vstart=mp_i * Vloc)

        if CP > 1:
            # label of a shard's last token is the NEXT shard's first
            # token (rank CP-1 wraps to rank 0's first = global roll)
            nxt = jax.lax.ppermute(
                ids_local[:, :, :1], "cp",
                [((i + 1) % CP, i) for i in range(CP)])
            labels_all = jnp.concatenate([ids_local[:, :, 1:], nxt],
                                         axis=2)   # [M, b, s]
        else:
            labels_all = jnp.roll(ids_local, -1, axis=2)     # [M, b, S]

        def loss_fn(ps):
            """Interleaved (VPP) pipeline, vpp=1 = plain GPipe schedule.

            Per tick each rank computes ONE chunk.  Rank p at tick t works
            logical step u = t - p; u decomposes (blocks of PP microbatches
            sweeping chunk slots depth-first, `pipeline_parallel.py:986`)
            as b = u // (PP*VPP), j = (u % (PP*VPP)) // PP (chunk slot),
            m = b*PP + u % PP (microbatch).  The ring ppermute delivers
            rank PP-1's slot-j output to rank 0 exactly when rank 0 starts
            slot j+1 of that microbatch — no extra hop for the wrap.

            embed / stage / head run under `lax.cond`, so warm-up/drain
            bubble ticks and non-owner ranks SKIP the compute instead of
            masking it (all ranks of a pp row share the predicate, so the
            mp collectives inside each branch stay consistent)."""
            B, S = ids_local.shape[1], ids_local.shape[2]
            s = S // MP if sp else S
            carry = jnp.zeros((B, s, cfg.hidden_size), cfg.dtype)
            loss_acc = jnp.zeros((), jnp.float32)
            perm = [(i, (i + 1) % PP) for i in range(PP)]
            period = PP * VPP
            for t in range(M * VPP + PP - 1):
                u = t - pp_i                       # traced (per pp row)
                active = (u >= 0) & (u < M * VPP)
                uc = jnp.clip(u, 0, M * VPP - 1)
                jslot = (uc % period) // PP        # chunk slot on this rank
                m = (uc // period) * PP + uc % PP  # microbatch index
                ids_mb = jnp.take(ids_local, m, axis=0)
                h_in = jax.lax.cond(
                    active & (pp_i == 0) & (jslot == 0),
                    lambda: embed(ps, ids_mb), lambda: carry)
                chunk = jax.tree_util.tree_map(
                    lambda leaf: jnp.take(leaf, jslot, axis=0), ps["blocks"])
                if CP > 1:
                    # ring attention's ppermute over cp must execute in the
                    # SAME program order on every rank of the mesh — a
                    # collective permute under a predicate that differs
                    # across pp rows pairs ranks across rows (XLA gives
                    # collective-permute a global rendezvous, unlike the
                    # per-subgroup all_gather/psum/all_to_all the mp/SP
                    # branches use).  Run the stage unconditionally and
                    # select the output; bubble ticks pay compute, never
                    # correctness.
                    h_stage = stage_fn(chunk, h_in)
                    h_out = jnp.where(active, h_stage, h_in)
                else:
                    h_out = jax.lax.cond(
                        active, lambda: stage_fn(chunk, h_in),
                        lambda: h_in)
                lab = jnp.take(labels_all, m, axis=0)
                l = jax.lax.cond(
                    active & (pp_i == PP - 1) & (jslot == VPP - 1),
                    lambda: head_loss(ps, h_out, lab),
                    lambda: jnp.zeros((), jnp.float32))
                loss_acc = loss_acc + l
                carry = jax.lax.ppermute(h_out, "pp", perm)
            total = jax.lax.psum(loss_acc / M, "pp")
            return jax.lax.pmean(total, "dp")

        loss, grads = jax.value_and_grad(loss_fn)(local)

        # restore the stacked layout on block grads
        g_stacked = dict(grads)
        g_stacked["blocks"] = {k: leaf[None]
                               for k, leaf in grads["blocks"].items()}

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(g_stacked)
        m_leaves = jax.tree_util.tree_leaves(m)
        v_leaves = jax.tree_util.tree_leaves(v)

        new_p, new_m, new_v = [], [], []
        for p, g, mm, vv, spec in zip(p_leaves, g_leaves, m_leaves,
                                      v_leaves, spec_leaves):
            axes = _spec_axes(spec)
            # gradients: sum the per-rank contributions over every mesh
            # axis the leaf is NOT sharded on (GSPMD's replica all-reduce,
            # done explicitly).  dp is handled below: ZeRO-2 reduce-
            # scatters it instead of all-reducing.
            replica_axes = ("pp", "mp", "cp") if CP > 1 else ("pp", "mp")
            for ax in replica_axes:
                if ax not in axes:
                    g = jax.lax.psum(g, ax)
            if "dp" in axes:
                # expert-parallel leaf: each dp rank owns its expert shard
                # outright — plain local Adam, no ZeRO slicing/gather
                p2, m2, v2 = _adam_math(p.reshape(-1), g.reshape(-1),
                                        mm.reshape(-1), vv.reshape(-1),
                                        step_no, cfg)
                new_p.append(p2.reshape(p.shape))
                new_m.append(m2.reshape(mm.shape))
                new_v.append(v2.reshape(vv.shape))
                continue
            # ZeRO Adam: update only this dp rank's 1/dp slice, then
            # all-gather the updated parameter.  Stage 1 all-reduces the
            # grad and slices; stage 2 reduce-scatters — the full gradient
            # never materializes on any rank
            shp, F = p.shape, p.size
            k = mm.size                                   # Fp/dp (local)
            flat_p = jnp.pad(p.reshape(-1), (0, DP * k - F))
            flat_g = jnp.pad(g.reshape(-1), (0, DP * k - F))
            psh = jax.lax.dynamic_slice(flat_p, (dp_i * k,), (k,))
            if cfg.zero_stage >= 2:
                gsh = jax.lax.psum_scatter(flat_g, "dp",
                                           scatter_dimension=0, tiled=True)
            else:
                flat_g = jax.lax.psum(flat_g, "dp")
                gsh = jax.lax.dynamic_slice(flat_g, (dp_i * k,), (k,))
            p2sh, m2, v2 = _adam_math(psh, gsh, mm.reshape(-1),
                                      vv.reshape(-1), step_no, cfg)
            p2 = jax.lax.all_gather(p2sh, "dp", tiled=True)
            new_p.append(p2[:F].reshape(shp))
            new_m.append(m2.reshape(mm.shape))
            new_v.append(v2.reshape(vv.shape))

        un = jax.tree_util.tree_unflatten
        return (loss, un(treedef, new_p), un(treedef, new_m),
                un(treedef, new_v))

    # check_vma=False: the updated params ARE dp-replicated (grads are
    # psum'd over dp before the update and shards all-gathered after), but
    # the static varying-axes analysis can't prove it through all_gather
    ids_spec = P(None, "dp", "cp") if CP > 1 else P(None, "dp", None)
    mapped = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(specs, opt_specs, opt_specs, P(), ids_spec),
        out_specs=(P(), specs, opt_specs, opt_specs),
        check_vma=False)
    jitted = jax.jit(mapped)

    import time as _time

    from ... import flags as _pt_flags
    from ...observability import flight_recorder as _flight
    from ...observability import metrics as _metrics
    from ...observability import telemetry as _telemetry
    _hist = _metrics.histogram(
        "train.step_seconds",
        "host wall time to dispatch one train step (labels: mode); on "
        "async accelerators this is enqueue time unless the caller syncs "
        "inside the step — the first sample includes XLA compile")

    # per-token FLOPs of THIS config for the telemetry MFU line, via the
    # shared accounting helper (params estimated from the config shape).
    # The timeline is per-factory — a second config in the same process
    # gets its own FLOPs binding instead of inheriting the first's —
    # and its records still reach the process flight-recorder ring.
    from ...observability.flops import training_flops_per_token
    n_params = (cfg.num_layers * (4 * cfg.hidden_size ** 2
                                  + 2 * cfg.hidden_size
                                  * cfg.intermediate_size)
                + 2 * cfg.vocab_size * cfg.hidden_size
                + cfg.seq_len * cfg.hidden_size)
    dev0 = mesh.devices.flat[0]
    tl = _telemetry.StepTimeline(
        name="train",
        flops_per_token=training_flops_per_token(
            n_params, cfg.num_layers, cfg.hidden_size, cfg.seq_len),
        # MFU only where the peak table has a row: absent on a CPU mesh
        device_kind=dev0.device_kind if dev0.platform == "tpu" else None)
    _step_count = [0]

    def timed_step(*args, **kwargs):
        _step_count[0] += 1
        ids = args[4] if len(args) > 4 else kwargs.get("ids")
        tokens = int(ids.size) if ids is not None else 0
        # periodic watchdog probe: materializing the (tiny, scalar) loss
        # is a host sync, so it runs INSIDE the bracket — on probe steps
        # wall_s is completed-step time (record marked synced), on the
        # others it is enqueue time.  The probe itself is independent of
        # the metrics gate (the annotation no-ops when the registry is
        # off, the check never does).
        probe = _flight.enabled() and _step_count[0] % max(
            int(_pt_flags.get_flag("nan_watchdog_interval")), 1) == 0
        loss = None
        t0 = _time.perf_counter()
        with _flight.guard("hybrid.train_step"), \
                tl.step(tokens=tokens, mode="hybrid") as st:
            out = jitted(*args, **kwargs)
            if probe:
                loss = float(np.asarray(out[0]))
                st.annotate(loss=loss, synced=True)
        _hist.observe(_time.perf_counter() - t0, mode="hybrid")
        if probe:
            _flight.check_finite(loss, site="hybrid.train_step.loss",
                                 step=_step_count[0])
        return out

    timed_step.timeline = tl                 # readout for callers/tests

    timed_step.lower = jitted.lower          # AOT/debug paths still work
    timed_step._jitted = jitted
    return timed_step


# ---------------------------------------------------------------------------
# fused elastic ZeRO-3: stage-3 FSDP over dp, gather/release in-program
# ---------------------------------------------------------------------------
#
# Parameters (and Adam moments) are RESIDENT in the flat ZeRO layout —
# `init_zero_state`'s scheme specialised to a dp-only mesh: each leaf
# flattened to F = prod(shape) elements, zero-padded to
# Fp = dp*ceil(F/dp) (`sharding.flat_shard_layout`, the flattened-leaf
# degenerate case of `_shard_spec_for`), global shape (Fp,), spec
# P('dp').  The train step gathers full parameters INSIDE the compiled
# program — one all_gather per bucket (`sharding.plan_zero3_buckets`,
# sized by FLAGS_zero3_bucket_mb) so XLA's latency-hiding scheduler can
# overlap bucket N+1's gather with bucket N's compute — gradients
# reduce-scatter back to the (Fp/dp,)-per-rank layout, and the fused
# Adam update runs on the 1/dp-resident shards with donated buffers.
# No full parameter ever materializes outside the program, and no eager
# per-layer collective ever runs (lint R014 + the program-count test pin
# this).

def _zero3_leaf_meta(cfg: HybridConfig, dp: int):
    """Per-leaf ``(shape, dtype, F, Fp)`` in tree-flatten order, plus the
    treedef — from `eval_shape` (no parameter materialization)."""
    from .sharding import flat_shard_layout
    tmpl = jax.eval_shape(lambda k: init_gpt_params(k, cfg),
                          jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    metas = [(tuple(l.shape), l.dtype) + flat_shard_layout(l.shape, dp)
             for l in leaves]
    return metas, treedef


def init_zero3_state(params, mesh: Mesh):
    """Enter the flat ZeRO-3 resident layout: every serial leaf is
    flattened, zero-padded to Fp = dp*ceil(F/dp) and device_put with
    spec P('dp'); Adam moments start as matching sharded zeros.
    Returns ``(flat_params, m, v)`` (three trees, `params`' structure,
    every leaf (Fp,))."""
    from jax.sharding import NamedSharding

    from .sharding import flat_shard_layout
    dp = int(mesh.shape["dp"])
    sh = NamedSharding(mesh, P("dp"))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    fp, fm, fv = [], [], []
    for p in leaves:
        F, Fp = flat_shard_layout(p.shape, dp)
        fp.append(jax.device_put(jnp.pad(jnp.ravel(p), (0, Fp - F)), sh))
        fm.append(jax.device_put(jnp.zeros((Fp,), p.dtype), sh))
        fv.append(jax.device_put(jnp.zeros((Fp,), p.dtype), sh))
    un = jax.tree_util.tree_unflatten
    return un(treedef, fp), un(treedef, fm), un(treedef, fv)


def zero3_unflatten(flat_params, cfg: HybridConfig):
    """Flat ZeRO-3 layout -> serial-shaped param tree (pad dropped).
    Parity-test/debug helper — the train step itself never materializes
    full parameters outside its program."""
    tmpl = jax.eval_shape(lambda k: init_gpt_params(k, cfg),
                          jax.random.PRNGKey(0))
    t_leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    leaves = jax.tree_util.tree_leaves(flat_params)
    out = [jnp.asarray(f)[:int(np.prod(t.shape))].reshape(t.shape)
           for f, t in zip(leaves, t_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make_zero3_train_step(mesh: Mesh, cfg: HybridConfig, grain: int = 0):
    """Fused elastic ZeRO-3 train step over a dp-only mesh.

    ``step(flat_params, m, v, step_no, ids) -> (loss, flat_params',
    m', v')`` with every flat leaf (Fp,) P('dp')-sharded and ids
    [M, B, S] sharded P(None, 'dp', None).  ONE compiled program per
    (config, bucket plan, grain): gather, forward/backward,
    reduce-scatter and the fused shard-resident Adam update
    (`optimizer.fused.zero3_shard_update`) all trace into it, with the
    three state trees donated on real accelerators.

    grain=0 — fast path: the bucket gather sits inside the loss closure,
    so gradients reduce-scatter automatically (AD transposes all_gather
    to psum_scatter) and the whole backward stays one fused subgraph.
    The cross-dp `pmean` couples reduction shape to dp, so numerics are
    only tolerance-stable across world sizes.

    grain=G>0 — deterministic-reduction path (the elastic-resume
    contract): the global batch is split into G fixed groups of B/G
    rows, the batch is all-gathered, and EVERY rank differentiates EVERY
    group against the gathered full params, folding the per-group
    gradients in global group order (an ordered left fold, not a psum
    tree) before slicing out its own shard.  The gradient arithmetic
    then contains no trace of dp — bitwise identical HLO at any world
    size — which per-rank group splits cannot give (XLA fuses the
    per-group subgraphs differently in different step programs; ULP
    drift that Adam's first-step sign normalization amplifies).  The
    cost is dp-fold redundant gradient compute: grain mode trades step
    time for the bit-exact 4->2->4 resume the elastic tests pin;
    grain=0 is the perf path."""
    from ... import flags as _pt_flags
    from ...observability import compile_tracker as _ct
    from ...observability import xray as _xray
    from ...optimizer.fused import zero3_shard_update
    from .sharding import plan_zero3_buckets

    dp = int(mesh.shape["dp"])
    assert cfg.zero_stage == 3, "make_zero3_train_step is the stage-3 path"
    assert cfg.pp == 1 and cfg.mp == 1 and cfg.cp == 1, \
        "fused ZeRO-3 is dp-only FSDP; mp/pp belong to make_hybrid_train_step"
    assert cfg.moe_num_experts == 0, "MoE experts already shard over dp"
    assert dp == cfg.dp, f"mesh dp {dp} != cfg.dp {cfg.dp}"
    M = cfg.n_microbatches

    metas, treedef = _zero3_leaf_meta(cfg, dp)
    n_leaves = len(metas)

    # bucket plan is fixed at BUILD time (a new flag value means building
    # a new step — never a silent retrace mid-run)
    bucket_mb = float(_pt_flags.get_flag("zero3_bucket_mb"))
    raw = plan_zero3_buckets(
        [Fp * jnp.dtype(dt).itemsize for (_, dt, _, Fp) in metas],
        bucket_mb)
    buckets = []          # split at dtype changes: buckets concatenate
    for b in raw:
        cur = [b[0]]
        for i in b[1:]:
            if metas[i][1] == metas[cur[-1]][1]:
                cur.append(i)
            else:
                buckets.append(cur)
                cur = [i]
        buckets.append(cur)

    def _gather_full(shards):
        """Per-leaf (Fp/dp,) locals -> serial param tree; ONE all_gather
        per bucket.  Untiled gather ([dp, Kb]) keeps each leaf's shard
        rows contiguous, so the per-leaf extraction is a static window
        slice + reshape — free for XLA to fuse."""
        full = [None] * n_leaves
        for b in buckets:
            conc = (shards[b[0]] if len(b) == 1 else
                    jnp.concatenate([shards[i] for i in b]))
            g = jax.lax.all_gather(conc, "dp", tiled=False)    # [dp, Kb]
            off = 0
            for i in b:
                shape, _, F, Fp = metas[i]
                k = Fp // dp
                full[i] = jax.lax.slice_in_dim(
                    g, off, off + k, axis=1).reshape(dp * k)[:F] \
                    .reshape(shape)
                off += k
        return jax.tree_util.tree_unflatten(treedef, full)

    def device_fn(fp, m, v, step_no, ids_local):
        p_shards = jax.tree_util.tree_leaves(fp)
        m_l = jax.tree_util.tree_leaves(m)
        v_l = jax.tree_util.tree_leaves(v)

        if grain == 0:
            def loss_fn(shards):
                ps = _gather_full(shards)
                per_mb = jnp.stack([serial_forward(ps, ids_local[i], cfg)
                                    for i in range(M)])
                return jax.lax.pmean(jnp.mean(per_mb), "dp")

            loss, g_shards = jax.value_and_grad(loss_fn)(p_shards)
        else:
            # restore global row order: rank blocks of the tiled gather
            # land batch-major, undoing the P(None, 'dp', None) split
            ids_all = jax.lax.all_gather(ids_local, "dp", axis=1,
                                         tiled=True)      # [M, B, S]
            B = ids_all.shape[1]
            assert B % grain == 0, \
                f"global batch {B} must divide by grain {grain}"
            R = B // grain                # rows per group
            # the barrier fences the (dp-shaped) gather off from the
            # grad region: without it XLA fuses the bucket reshapes into
            # the dots and different world sizes compile ULP-different
            # backward arithmetic even on identical values
            ps, ids_all = jax.lax.optimization_barrier(
                (_gather_full(p_shards), ids_all))

            def group_loss(pfull, sub):
                per_mb = jnp.stack([serial_forward(pfull, sub[i], cfg)
                                    for i in range(M)])
                return jnp.mean(per_mb)

            # fori_loop, NOT a python loop or vmap: the body becomes its
            # own HLO computation whose shapes ([M, R, S] rows against
            # full params) carry no trace of dp, so XLA's per-computation
            # fusion/layout passes produce the same arithmetic in the
            # dp=2 and dp=4 programs (unrolled copies fuse with their
            # dp-shaped surroundings and drift; vmap's batched dims
            # change the per-group numerics outright).  The left-fold
            # carry IS the ordered reduction, in global group order.
            def group_body(g, carry):
                loss_acc, gacc = carry
                sub = jax.lax.dynamic_slice_in_dim(ids_all, g * R, R,
                                                   axis=1)
                lg, gg = jax.value_and_grad(group_loss)(ps, sub)
                return (loss_acc + lg,
                        [a + b for a, b in
                         zip(gacc, jax.tree_util.tree_leaves(gg))])

            loss_acc, gacc = jax.lax.fori_loop(
                0, grain, group_body,
                (jnp.zeros((), jnp.float32),
                 [jnp.zeros(shape, dt) for (shape, dt, _, _) in metas]))
            loss = loss_acc / grain
            folded = [a / grain for a in gacc]
            # second fence: everything above is world-size-invariant
            # HLO; dp enters only BELOW, in the shard-window slice —
            # without the barrier the slice fuses upward into the
            # backward and perturbs it per world size
            folded = jax.lax.optimization_barrier(tuple(folded))

            d_i = jax.lax.axis_index("dp")
            g_shards = []
            for i, (shape, dt, F, Fp) in enumerate(metas):
                k = Fp // dp
                flat = jnp.pad(folded[i].reshape(-1).astype(dt),
                               (0, Fp - F))
                g_shards.append(
                    jax.lax.dynamic_slice(flat, (d_i * k,), (k,)))

        new_p, new_m, new_v = zero3_shard_update(
            p_shards, g_shards, m_l, v_l, step_no,
            learning_rate=cfg.learning_rate, beta1=cfg.beta1,
            beta2=cfg.beta2, eps=cfg.eps)
        un = jax.tree_util.tree_unflatten
        return (loss, un(treedef, new_p), un(treedef, new_m),
                un(treedef, new_v))

    flat_specs = jax.tree_util.tree_unflatten(treedef, [P("dp")] * n_leaves)
    # check_vma=False: the loss IS dp-replicated (pmean / ordered fold of
    # an all_gather), but the static analysis can't prove it
    mapped = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(flat_specs, flat_specs, flat_specs, P(),
                  P(None, "dp", None)),
        out_specs=(P(), flat_specs, flat_specs, flat_specs),
        check_vma=False)
    # donation: the old param/moment shards die at the update, so their
    # buffers host the new ones — skip on CPU, where XLA can't honor it
    # and jax warns (same guard as optimizer.fused)
    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    jitted = jax.jit(mapped, donate_argnums=donate)

    sig = (("dp", dp), ("grain", grain), ("buckets", len(buckets)),
           ("bucket_mb", bucket_mb), ("layers", cfg.num_layers),
           ("hidden", cfg.hidden_size))
    step_fn = _ct.wrap_first_call(jitted, "hybrid.zero3_step", sig)
    step_fn.lower = jitted.lower
    step_fn._jitted = jitted
    step_fn.buckets = [tuple(b) for b in buckets]

    def audit(*args, **kwargs):
        """Lower and attach the HLO audit to this program's xray entry,
        so the gather/compute overlap (collective count, flops, bytes)
        shows up in the per-program ledger (`xray.ledger`)."""
        low = jitted.lower(*args, **kwargs)
        _xray.attach_lowered(step_fn._xray_entry, low)
        return low

    step_fn.audit = audit
    return step_fn


def zero3_train_state(flat_params, m, v, step_no,
                      grain: int = 0) -> Dict[str, Any]:
    """Checkpointable tree for the fused ZeRO-3 state: the flat shards
    ride the sharded save path (each process writes only its own
    (Fp/dp,) slices), the Adam step count and reduction grain go into
    the coordinator's extra blob (bit-exact resume is per-grain, so a
    resume can see what the run was trained with)."""
    return {"zero3": {"params": flat_params, "m": m, "v": v},
            "meta": {"step_no": float(step_no), "zero3_grain": int(grain)}}


def save_zero3_state(manager, step: int, flat_params, m, v, step_no,
                     grain: int = 0, wait: bool = False) -> bool:
    """Version the fused ZeRO-3 train state as `step` (atomic commit)."""
    return manager.save(
        step, zero3_train_state(flat_params, m, v, step_no, grain),
        wait=wait)


def load_zero3_state(manager, mesh: Mesh, cfg: HybridConfig, step=None):
    """Elastic resume: reload flat ZeRO-3 state onto THIS mesh's dp
    degree, whatever degree wrote the checkpoint.

    The flat layout makes resharding a trailing-dim resize: a leaf saved
    at dp_old has global shape (Fp_old,), the new mesh needs (Fp_new,) —
    the same F live elements under a different zero pad.  Templates are
    rebuilt at dp_new and ``restore_into(..., resize_trailing=True)``
    truncates or zero-fills the tail.  That is bit-exact because the pad
    region is an invariant 0 of the step: pads start at 0, the ``[:F]``
    slice in the gather gives them zero gradients, and Adam maps a
    (0, 0, 0) triple to (0, 0, 0).

    Returns ``(flat_params, m, v, step_no, grain)``."""
    from jax.sharding import NamedSharding
    dp = int(mesh.shape["dp"])
    metas, treedef = _zero3_leaf_meta(cfg, dp)
    sh = NamedSharding(mesh, P("dp"))

    def templ():
        return jax.tree_util.tree_unflatten(
            treedef, [jax.device_put(jnp.zeros((Fp,), dt), sh)
                      for (_, dt, _, Fp) in metas])

    arrays, extra = manager.restore_into(
        {"zero3": {"params": templ(), "m": templ(), "v": templ()}},
        step=step, resize_trailing=True)
    z = arrays["zero3"]
    meta = extra.get("meta", {})
    return (z["params"], z["m"], z["v"],
            float(meta.get("step_no", 0.0)),
            int(meta.get("zero3_grain", 0)))


# ---------------------------------------------------------------------------
# fault tolerance: versioned save / sharded resume of the hybrid train state
# ---------------------------------------------------------------------------

def hybrid_train_state(params, m, v, step_no) -> Dict[str, Any]:
    """Checkpointable tree for `CheckpointManager.save`: the sharded
    param/optimizer pytrees ride the sharded save path (each process
    writes only its owned shards), the Adam step count goes into the
    coordinator's extra blob."""
    return {"hybrid": {"params": params, "m": m, "v": v},
            "meta": {"step_no": float(step_no)}}


def save_hybrid_state(manager, step: int, params, m, v, step_no,
                      wait: bool = False) -> bool:
    """Version the full hybrid train state as `step` (atomic commit)."""
    return manager.save(step, hybrid_train_state(params, m, v, step_no),
                        wait=wait)


def _shard_tree(tree, specs, mesh: Mesh):
    """device_put every leaf into NamedSharding(mesh, spec) — the layout
    `make_hybrid_train_step` expects its inputs in."""
    from jax.sharding import NamedSharding
    leaves, spec_leaves, treedef = _flatten_with_specs(tree, specs)
    out = [jax.device_put(x, NamedSharding(mesh, s))
           for x, s in zip(leaves, spec_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def load_hybrid_state(manager, mesh: Mesh, cfg: HybridConfig, params, m, v,
                      step=None):
    """Resume: reload (params, m, v, step_no) from the newest complete
    version (or `step`) of `manager`, laid out onto `mesh` per this
    config's param/ZeRO specs.  The template trees supply shapes/dtypes
    only (fresh `init_gpt_params`/`init_zero_state` output is fine) —
    reshard-on-load means the checkpoint may have been written under a
    DIFFERENT mesh/degree.  Returns ``(params, m, v, step_no)``."""
    specs = hybrid_param_specs(cfg)
    opt_specs = zero_state_specs(specs)
    arrays, extra = manager.restore_into(
        {"hybrid": {"params": _shard_tree(params, specs, mesh),
                    "m": _shard_tree(m, opt_specs, mesh),
                    "v": _shard_tree(v, opt_specs, mesh)}}, step=step)
    h = arrays["hybrid"]
    return (h["params"], h["m"], h["v"],
            float(extra.get("meta", {}).get("step_no", 0.0)))


# ---------------------------------------------------------------------------
# schedule accounting (no execution): busy/bubble tick analysis
# ---------------------------------------------------------------------------

def schedule_table(pp: int, vpp: int, n_microbatches: int):
    """Per-rank tick table of the interleaved schedule, computed from the
    SAME index arithmetic as the tick loop in `make_hybrid_train_step`
    (u = t - rank; active iff 0 <= u < M*vpp; chunk slot / microbatch
    decomposition per `pipeline_parallel.py:986`'s block sweep).

    Returns [rank][tick] entries: None for a bubble tick, else
    (chunk_slot, microbatch)."""
    M = n_microbatches
    # mirrors HybridConfig's guard: the block sweep decomposition assumes
    # whole blocks of pp microbatches (phantom microbatch ids otherwise)
    assert M % pp == 0, f"n_microbatches {M} must divide by pp {pp}"
    period = pp * vpp
    T = M * vpp + pp - 1
    table = []
    for p in range(pp):
        row = []
        for t in range(T):
            u = t - p
            if 0 <= u < M * vpp:
                jslot = (u % period) // pp
                mb = (u // period) * pp + u % pp
                row.append((jslot, mb))
            else:
                row.append(None)
        table.append(row)
    return table


def bubble_fraction(pp: int, vpp: int, n_microbatches: int) -> float:
    """Bubble time as a fraction of each rank's BUSY time.  Every tick
    computes one chunk (1/vpp of the rank's layers), so ticks are
    uniform within a schedule; per rank there are pp-1 bubble ticks and
    M*vpp busy ticks -> (pp-1)/(M*vpp), the classic interleaved-schedule
    bubble ratio (GPipe at vpp=1: (pp-1)/M)."""
    table = schedule_table(pp, vpp, n_microbatches)
    bubble = sum(e is None for row in table for e in row)
    busy = sum(e is not None for row in table for e in row)
    # sanity: every (chunk, microbatch) pair computed exactly once/rank
    for row in table:
        work = [e for e in row if e is not None]
        assert len(set(work)) == len(work) == n_microbatches * vpp
    return bubble / busy if busy else 0.0
