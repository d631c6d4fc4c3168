"""Hybrid-parallel SPMD train step: loss parity vs the serial reference.

The golden-loss parity bar of the reference's distributed CI
(`test/collective/test_communication_api_base.py:26`, hybrid LLM tests in
`test/auto_parallel/hybrid_strategy/`): train the same tiny GPT under
pp x dp x mp (+SP, +ZeRO-1 Adam) and serially, assert identical losses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.distributed.fleet.hybrid_step import (
    HybridConfig, hybrid_param_specs, init_gpt_params, init_zero_state,
    make_hybrid_train_step, serial_train_step, stack_for_pipeline)


# The full hybrid matrix is compile-heavy (20-60s per config on the
# virtual CPU mesh); the representative SP parity config and the schedule
# accounting stay in the fast tier, the rest of the matrix runs with
# -m slow.
def _run_parity(cfg, n_devices, steps=3):
    if cfg.cp > 1:
        shape = (cfg.pp, cfg.dp, cfg.cp, cfg.mp)
        axes = ("pp", "dp", "cp", "mp")
    else:
        shape = (cfg.pp, cfg.dp, cfg.mp)
        axes = ("pp", "dp", "mp")
    devs = np.array(jax.devices()[:n_devices]).reshape(shape)
    mesh = Mesh(devs, axes)
    key = jax.random.key(42)
    params = init_gpt_params(key, cfg)
    stacked = stack_for_pipeline(params, cfg)
    specs = hybrid_param_specs(cfg)
    m, v, _ = init_zero_state(stacked, specs, mesh)
    step = make_hybrid_train_step(mesh, cfg)

    rng = np.random.RandomState(0)
    B = 2 * cfg.dp
    ids = jnp.asarray(
        rng.randint(0, cfg.vocab_size,
                    (cfg.n_microbatches, B, cfg.seq_len)), jnp.int32)

    sp, sm, sv = (params, jax.tree_util.tree_map(jnp.zeros_like, params),
                  jax.tree_util.tree_map(jnp.zeros_like, params))
    serial, hybrid = [], []
    for i in range(steps):
        l, sp, sm, sv = serial_train_step(sp, sm, sv, float(i + 1), ids, cfg)
        serial.append(float(l))
        l2, stacked, m, v = step(stacked, m, v, jnp.float32(i + 1), ids)
        hybrid.append(float(l2))
    np.testing.assert_allclose(hybrid, serial, rtol=2e-4, atol=2e-5)
    assert serial[-1] < serial[0]  # it actually trains


@pytest.mark.slow  # 62s measured: the pp2*dp2*mp2+sp+zero composition drill; each axis keeps its own fast parity test (test_distributed, test_interleaved_pipeline, test_sequence_parallel, test_zero)
def test_hybrid_pp2_dp2_mp2_sp_zero():
    _run_parity(HybridConfig(), 8)


@pytest.mark.slow
def test_hybrid_no_sequence_parallel():
    _run_parity(HybridConfig(sequence_parallel=False), 8)


@pytest.mark.slow
def test_hybrid_no_remat_matches():
    _run_parity(HybridConfig(remat=False), 8)


@pytest.mark.slow
def test_hybrid_pp4_deep_pipeline():
    _run_parity(HybridConfig(num_layers=4, pp=4, dp=2, mp=1,
                             sequence_parallel=False, n_microbatches=3), 8)


@pytest.mark.slow
def test_hybrid_mp_only():
    _run_parity(HybridConfig(pp=1, dp=1, mp=4, n_microbatches=2), 4)


@pytest.mark.slow
def test_hybrid_interleaved_vpp():
    """Megatron interleaved schedule: pp=4 ranks x vpp=2 chunks, with the
    chunk assignment of pipeline_parallel.py:986."""
    _run_parity(HybridConfig(num_layers=8, pp=4, dp=2, mp=1, vpp=2,
                             sequence_parallel=False, n_microbatches=4), 8)


@pytest.mark.slow
def test_hybrid_zero2_reduce_scatter():
    """ZeRO-2: gradients reduce-scattered over dp (never materialized
    whole) — loss parity must be identical to stage 1."""
    _run_parity(HybridConfig(zero_stage=2), 8)


@pytest.mark.slow
def test_hybrid_moe_expert_parallel():
    """Switch-MoE MLP with experts sharded over dp and tokens moved by the
    sort-based all_to_all dispatch (global_scatter/gather equivalent),
    composed with pp x mp x SP + ZeRO-2."""
    _run_parity(HybridConfig(moe_num_experts=4, zero_stage=2), 8)


@pytest.mark.slow
def test_hybrid_moe_with_vpp():
    _run_parity(HybridConfig(num_layers=8, pp=2, dp=2, mp=2, vpp=2,
                             moe_num_experts=4, n_microbatches=2), 8)


def test_schedule_bubble_accounting():
    """Interleaved-schedule tick table: every rank computes each
    (chunk, microbatch) exactly once, bubble ratio matches
    (pp-1)/(M*vpp), and vpp strictly shrinks it (ref
    pipeline_parallel.py:986 interleaved schedule)."""
    from paddle_tpu.distributed.fleet.hybrid_step import (bubble_fraction,
                                                          schedule_table)
    assert bubble_fraction(4, 1, 8) == 3 / 8
    assert bubble_fraction(4, 2, 8) == 3 / 16
    assert bubble_fraction(2, 1, 2) == 1 / 2
    assert bubble_fraction(1, 1, 4) == 0.0
    for pp, vpp, M in ((4, 1, 8), (4, 2, 8), (2, 2, 4), (8, 4, 16)):
        assert bubble_fraction(pp, vpp, M) == (pp - 1) / (M * vpp)
        if vpp > 1:
            assert bubble_fraction(pp, vpp, M) < bubble_fraction(pp, 1, M)
    # the tick a rank receives work must be one after the upstream rank
    # produced it: rank p's first busy tick is t = p (ring latency 1)
    table = schedule_table(4, 2, 8)
    for p, row in enumerate(table):
        first_busy = next(t for t, e in enumerate(row) if e is not None)
        assert first_busy == p
        assert row[first_busy] == (0, 0)  # starts on chunk 0, microbatch 0


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_hybrid_context_parallel(mode):
    """Context parallelism over a 'cp' mesh axis (ref sep dim,
    fleet/base/topology.py): sequence sharded through the whole block,
    attention crossing the axis by ring ppermute or Ulysses head-alltoall,
    composed with pp and dp — loss parity vs serial."""
    _run_parity(HybridConfig(pp=2, dp=2, mp=1, cp=2, cp_attention=mode,
                             sequence_parallel=False), 8)
