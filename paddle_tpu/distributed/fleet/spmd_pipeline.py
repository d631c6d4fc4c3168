"""SPMD pipeline parallelism over the 'pp' mesh axis.

This is the TPU-native replacement for the reference's NCCL-p2p pipeline
runtime (`fleet/meta_parallel/pipeline_parallel.py:458`
forward_backward_pipeline + `pp_utils/p2p_communication.py`): instead of
host-driven send/recv, the whole schedule is ONE SPMD program under
shard_map over 'pp' —

* every stage holds its own stage parameters (stacked pytree sharded on 'pp');
* activations move between stages with `lax.ppermute` (compiles to ICI
  collective-permute);
* the microbatch loop runs all ranks every tick with masking (idle ticks are
  the pipeline bubble);
* backward is jax AD through the schedule — ppermute's transpose is the
  reverse permute, so the backward pipeline falls out for free.

The schedule is GPipe/F-then-B at trace level; XLA's latency-hiding scheduler
overlaps the permutes with compute, which recovers most of 1F1B's overlap on
TPU (the 1F1B memory advantage is instead obtained with jax.checkpoint on the
stage fn).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import mesh as _mesh

__all__ = ["pipeline_forward", "interleaved_pipeline_forward",
           "stack_stage_params", "pp_sharding"]


def stack_stage_params(per_stage_params: list):
    """Stack a list of identical-structure stage param pytrees along axis 0
    (the 'pp'-sharded leading dim)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0),
                                  *per_stage_params)


def pp_sharding(mesh):
    """Sharding for stacked stage params: leading dim on 'pp'."""
    return NamedSharding(mesh, P("pp"))


def pipeline_forward(stage_fn: Callable, params_local: Any, inputs,
                     n_microbatches: int, pp_axis: str = "pp",
                     remat: bool = True):
    """Run the forward pipeline INSIDE shard_map over `pp_axis`.

    stage_fn(params, h) -> h'   (the per-stage computation)
    inputs: [n_microbatches, mb, ...] microbatched activations fed to stage 0
            (same array on every pp rank; only stage 0 reads it).
    Returns [n_microbatches, mb, ...] outputs of the LAST stage (valid on all
    ranks via final broadcast-permute collection).

    Schedule: M + P - 1 ticks; tick t feeds microbatch t into stage 0; stage s
    processes microbatch t - s.  All ranks execute stage_fn every tick.
    """
    P_ = jax.lax.axis_size(pp_axis)
    M = n_microbatches
    idx = jax.lax.axis_index(pp_axis)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    mb_shape = inputs.shape[1:]
    carry0 = jnp.zeros(mb_shape, inputs.dtype)  # activation from prev stage
    outs0 = jnp.zeros((M,) + mb_shape, inputs.dtype)
    perm_fwd = [(i, (i + 1) % P_) for i in range(P_)]
    carry0 = jax.lax.pcast(carry0, (pp_axis,), to="varying")
    outs0 = jax.lax.pcast(outs0, (pp_axis,), to="varying")

    def tick(state, t):
        carry, outs = state
        # stage 0 consumes fresh microbatch t (if any); others the carry
        first_in = inputs[jnp.clip(t, 0, M - 1)]
        h_in = jnp.where(idx == 0, first_in, carry)
        h_out = fn(params_local, h_in)
        # last stage banks its output for microbatch t - (P-1)
        mb_id = t - (P_ - 1)
        valid_out = (idx == P_ - 1) & (0 <= mb_id) & (mb_id < M)
        bank = jnp.clip(mb_id, 0, M - 1)
        outs = jnp.where(valid_out, outs.at[bank].set(h_out), outs)
        # ship activations to the next stage
        carry = jax.lax.ppermute(h_out, pp_axis, perm_fwd)
        return (carry, outs), None

    # scan keeps the traced program size constant in M (one tick body)
    (_, outs), _ = jax.lax.scan(tick, (carry0, outs0),
                                jnp.arange(M + P_ - 1))

    # replicate last-stage outputs to every rank (so loss is SPMD-uniform)
    masked = jnp.where(idx == P_ - 1, outs, jnp.zeros_like(outs))
    outs = jax.lax.psum(masked, pp_axis)
    return outs


def interleaved_pipeline_forward(stage_fn: Callable, chunk_params_local: Any,
                                 inputs, n_microbatches: int,
                                 n_chunks: int, pp_axis: str = "pp",
                                 remat: bool = True):
    """Interleaved / virtual-pipeline (VPP) schedule inside shard_map.

    Parity: `fleet/meta_parallel/pipeline_parallel.py:986`
    (PipelineParallelWithInterleave) — re-designed as one SPMD program.

    Each pp rank owns `n_chunks` (=V) model chunks; global stage
    g = v*P + r lives on rank r, chunk v (the Megatron interleaved
    assignment).  Microbatch m enters the 0th stage at tick
    s_m = (m // P) * P * V + (m % P); activations advance one global stage
    per tick, so every rank computes exactly ONE chunk per tick and the
    bubble shrinks from (P-1)/(M+P-1) stage-units to ~(P-1)/(M*V) chunk
    units — the VPP win, with the p2p rides on ICI collective-permutes.

    chunk_params_local: pytree whose leaves have leading dim V — this
    rank's V chunk parameter sets (from a (V, P, ...) global stack with P
    on the pp axis).
    stage_fn(chunk_params, h) -> h' for ONE chunk.
    inputs: [M, mb, ...]; returns [M, mb, ...] last-global-stage outputs.
    """
    P_ = jax.lax.axis_size(pp_axis)
    M, V = n_microbatches, n_chunks
    idx = jax.lax.axis_index(pp_axis)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    mb_shape = inputs.shape[1:]
    carry0 = jnp.zeros(mb_shape, inputs.dtype)
    outs0 = jnp.zeros((M,) + mb_shape, inputs.dtype)
    perm_fwd = [(i, (i + 1) % P_) for i in range(P_)]
    carry0 = jax.lax.pcast(carry0, (pp_axis,), to="varying")
    outs0 = jax.lax.pcast(outs0, (pp_axis,), to="varying")
    # exact tick count: the last microbatch enters at s_{M-1} =
    # ((M-1)//P)*P*V + (M-1)%P and needs P*V ticks to drain
    total_ticks = ((M - 1) // P_) * P_ * V + (M - 1) % P_ + P_ * V

    def tick(state, t):
        carry, outs = state
        # which (microbatch, global stage) does THIS rank hold right now?
        j = (t - idx) % P_                     # in-round microbatch offset
        k = (t - idx - j) // (P_ * V)          # round index
        m = k * P_ + j
        g = t - (k * P_ * V + j)               # global stage position
        v = jnp.clip(g // P_, 0, V - 1)        # chunk on this rank
        valid = (k >= 0) & (m < M) & (g >= 0) & (g < P_ * V)

        params_v = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, v, axis=0), chunk_params_local)
        fresh = inputs[jnp.clip(m, 0, M - 1)]
        h_in = jnp.where((idx == 0) & (g == 0), fresh, carry)
        h_out = fn(params_v, h_in)
        h_out = jnp.where(valid, h_out, jnp.zeros_like(h_out))

        # last global stage banks its microbatch's output
        is_last = valid & (g == P_ * V - 1)
        bank = jnp.clip(m, 0, M - 1)
        outs = jnp.where(is_last, outs.at[bank].set(h_out), outs)
        carry = jax.lax.ppermute(h_out, pp_axis, perm_fwd)
        return (carry, outs), None

    (_, outs), _ = jax.lax.scan(tick, (carry0, outs0),
                                jnp.arange(total_ticks))
    masked = jnp.where(idx == P_ - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(masked, pp_axis)
