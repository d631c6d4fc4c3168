"""Interleaved (VPP) SPMD pipeline: forward + training parity vs serial.

Mirrors the reference's `test_parallel_dygraph_pipeline_parallel.py`
interleave cases, executed as one shard_map program on the CPU mesh.
"""

import functools

import numpy as np
import pytest

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed.fleet.spmd_pipeline import (
    interleaved_pipeline_forward, pipeline_forward, stack_stage_params)


def make_stages(n_stages, width, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"w": jnp.asarray(rng.randn(width, width).astype(np.float32)
                          / np.sqrt(width)),
         "b": jnp.asarray(rng.randn(width).astype(np.float32) * 0.1)}
        for _ in range(n_stages)]


def stage_fn(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def serial_forward(stages, x):
    for p in stages:
        x = stage_fn(p, x)
    return x


def _mesh(pp):
    return Mesh(np.array(jax.devices()[:pp]), ("pp",))


@pytest.mark.parametrize("pp,vpp,M", [(2, 2, 4), (4, 2, 8), (2, 3, 5)])
def test_interleaved_forward_matches_serial(pp, vpp, M):
    width, mb = 8, 4
    n_stages = pp * vpp
    stages = make_stages(n_stages, width)
    rng = np.random.RandomState(1)
    inputs = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))

    # chunk layout: global stage g = v*pp + r  ->  stack[v, r]
    chunk_stack = stack_stage_params(
        [stack_stage_params([stages[v * pp + r] for r in range(pp)])
         for v in range(vpp)])  # leaves (V, P, ...)
    mesh = _mesh(pp)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(None, "pp"),
                                         chunk_stack),
                  P()),
        out_specs=P())
    def run(params_local, inp):
        # params_local leaves: (V, 1, ...) -> squeeze the pp dim
        local = jax.tree_util.tree_map(lambda l: l[:, 0], params_local)
        return interleaved_pipeline_forward(stage_fn, local, inp, M, vpp,
                                            remat=False)

    got = np.asarray(run(chunk_stack, inputs))
    want = np.stack([np.asarray(serial_forward(stages, inputs[m]))
                     for m in range(M)])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.slow  # 9s measured: full interleaved-vs-serial training parity; schedule-order and stage-mapping tests stay fast
def test_interleaved_training_matches_serial():
    """Grads through the VPP schedule == serial grads; one SGD step."""
    pp, vpp, M, width, mb = 2, 2, 4, 8, 4
    n_stages = pp * vpp
    stages = make_stages(n_stages, width, seed=3)
    rng = np.random.RandomState(4)
    inputs = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))
    target = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))
    mesh = _mesh(pp)

    chunk_stack = stack_stage_params(
        [stack_stage_params([stages[v * pp + r] for r in range(pp)])
         for v in range(vpp)])
    pspec = jax.tree_util.tree_map(lambda _: P(None, "pp"), chunk_stack)

    def loss_pipeline(params_vp, inp, tgt):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(pspec, P(), P()), out_specs=P())
        def run(pl, i, t):
            local = jax.tree_util.tree_map(lambda l: l[:, 0], pl)
            outs = interleaved_pipeline_forward(stage_fn, local, i, M, vpp,
                                                remat=True)
            return jnp.mean((outs - t) ** 2)[None]
        return run(params_vp, inp, tgt)[0]

    def loss_serial(stage_list, inp, tgt):
        outs = jnp.stack([serial_forward(stage_list, inp[m])
                          for m in range(M)])
        return jnp.mean((outs - tgt) ** 2)

    lp, gp = jax.value_and_grad(loss_pipeline)(chunk_stack, inputs, target)
    ls, gs = jax.value_and_grad(loss_serial)(stages, inputs, target)
    np.testing.assert_allclose(float(lp), float(ls), rtol=2e-5)

    # regroup serial grads into the (V, P) stack and compare
    gs_stack = stack_stage_params(
        [stack_stage_params([gs[v * pp + r] for r in range(pp)])
         for v in range(vpp)])
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs_stack)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-6)

    # one SGD step through the pipeline must reduce the pipeline loss
    stepped = jax.tree_util.tree_map(lambda p, g: p - 0.5 * g,
                                     chunk_stack, gp)
    l2 = loss_pipeline(stepped, inputs, target)
    assert float(l2) < float(lp)


def test_host_interleave_class_redirects():
    from paddle_tpu.distributed.fleet import PipelineParallelWithInterleave
    with pytest.raises(NotImplementedError):
        PipelineParallelWithInterleave(None, None, None)


def test_gpipe_and_interleaved_agree():
    """Same model partitioned 4 ways (plain) vs 2 ranks x 2 chunks
    (interleaved) must produce identical outputs."""
    width, M, mb = 8, 4, 2
    stages = make_stages(4, width, seed=9)
    rng = np.random.RandomState(5)
    inputs = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))

    mesh4 = _mesh(4)
    stack4 = stack_stage_params(stages)

    @functools.partial(jax.shard_map, mesh=mesh4,
                       in_specs=(jax.tree_util.tree_map(
                           lambda _: P("pp"), stack4), P()),
                       out_specs=P())
    def run_gpipe(pl, i):
        local = jax.tree_util.tree_map(lambda l: l[0], pl)
        return pipeline_forward(stage_fn, local, i, M, remat=False)

    a = np.asarray(run_gpipe(stack4, inputs))

    pp, vpp = 2, 2
    mesh2 = _mesh(pp)
    chunk_stack = stack_stage_params(
        [stack_stage_params([stages[v * pp + r] for r in range(pp)])
         for v in range(vpp)])

    @functools.partial(jax.shard_map, mesh=mesh2,
                       in_specs=(jax.tree_util.tree_map(
                           lambda _: P(None, "pp"), chunk_stack), P()),
                       out_specs=P())
    def run_vpp(pl, i):
        local = jax.tree_util.tree_map(lambda l: l[:, 0], pl)
        return interleaved_pipeline_forward(stage_fn, local, i, M, vpp,
                                            remat=False)

    b = np.asarray(run_vpp(chunk_stack, inputs))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
