"""train_hybrid.py — the job of a four-chip training cell:
`fleet/hybrid_step.make_hybrid_train_step` on a `("pp", "dp", "mp")` mesh,
the path a user trains a model with that one chip's memory cannot hold
(`chip_smoke.py` phase 4a at a published size).

Workload file: the mesh (`pp`, `dp`, `mp`), `sequence_parallel`,
`zero_stage`, `remat`, `n_microbatches`, `learning_rate`, `log_every`,
`n_batches`.  Traffic (`kind: tokens`): `batch` is the global batch of one
microbatch, `seq_len` the sequence.

`correct`: after the window the weights are gathered to the host, one
more step gives the system's loss on a batch, the training state is
dropped, and the plain reference computes the loss of the same batch under
the same weights at full depth on one device; the two agree.  Every loss
finite; the mean of the last five below that of the first five; no
compile request inside the window; block weights split over `mp` and
present on every device; the devices' memory in use balanced.
"""

from __future__ import annotations

import time

# |system - reference| on one step's loss.  The step is float32 with the
# MXU's default pass (bf16 multiplies, float32 sums) and sums partial
# results across the mesh; the reference is float32 "highest" on one
# device.  Averaged over >= 8192 tokens single roundings cancel: against
# the serial step the v5e showed 1e-5..6e-5 (PR 21).  2e-3 leaves room for
# the multiply precision and is far under what a mis-sharded weight moves
# (> 1e-2).
LOSS_TOL = 2e-3


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from paddle_tpu.distributed.fleet import hybrid_step as hs
    from benchmark import peaks, train_window
    from benchmark.reference import gpt_ref
    from benchmark.traffic import openloop

    wl, cfgd, mix = ctx.workload, ctx.config, ctx.traffic
    B, S, M = int(mix["batch"]), int(mix["seq_len"]), int(wl["n_microbatches"])
    pp, dp, mp = int(wl["pp"]), int(wl["dp"]), int(wl["mp"])
    devs = ctx.devices[:pp * dp * mp]
    if len(devs) < pp * dp * mp:
        raise ValueError(f"the mesh needs {pp * dp * mp} devices, the run "
                         f"has {len(devs)}")
    t0 = time.perf_counter()
    cfg = hs.HybridConfig(
        vocab_size=cfgd["vocab_size"], hidden_size=cfgd["hidden_size"],
        num_layers=cfgd["num_layers"], num_heads=cfgd["num_heads"],
        intermediate_size=cfgd["intermediate_size"], seq_len=S,
        pp=pp, dp=dp, mp=mp, n_microbatches=M,
        sequence_parallel=bool(wl["sequence_parallel"]),
        zero_stage=int(wl["zero_stage"]), remat=bool(wl["remat"]),
        learning_rate=float(wl["learning_rate"]))
    mesh = Mesh(np.array(devs).reshape(pp, dp, mp), ("pp", "dp", "mp"))
    specs = hs.hybrid_param_specs(cfg)

    def on_mesh(spec_tree):
        return jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), spec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    # weights and optimizer state are made sharded, each in one jitted
    # call from the seed: made whole on device 0 first (as a small model
    # may be), 1.4B float32 parameters and two moments would not fit it
    stacked = jax.jit(
        lambda k: hs.stack_for_pipeline(hs.init_gpt_params(k, cfg), cfg),
        out_shardings=on_mesh(specs))(jax.random.key(ctx.seed % (2 ** 31)))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(stacked))
    m, v = jax.jit(lambda t: hs.init_zero_state(t, specs, mesh)[:2],
                   out_shardings=(on_mesh(hs.zero_state_specs(specs)),) * 2
                   )(stacked)
    step = hs.make_hybrid_train_step(mesh, cfg)
    n_batches = int(wl["n_batches"])
    xs, _ = openloop.token_batches(mix, ctx.seed, cfg.vocab_size, n_batches,
                                   leading=(M,))
    xs = [jnp.asarray(x) for x in xs]
    t0 = ctx.part("build", t0)
    ctx.say(f"model {cfg.num_layers} x {cfg.hidden_size}, "
            f"{n_params / 1e6:.1f}M parameters (untied head), mesh pp{pp} x "
            f"dp{dp} x mp{mp}, {M} microbatch(es) of B={B} S={S} "
            f"({M * B * S} tokens a step)")

    count = 0

    def one(i):
        nonlocal stacked, m, v, count
        count += 1
        loss, stacked, m, v = step(stacked, m, v, jnp.float32(count), xs[i])
        return loss

    warm = [float(one(0)), float(one(1 % n_batches))]
    ctx.part("warm_up", t0)

    # ---- the window
    w = train_window.run_window(ctx, lambda i: one(i % n_batches),
                                int(wl["log_every"]))
    losses, n, window_s = w["losses"], w["steps"], w["window_s"]
    tokens = M * B * S
    tokens_per_s = n * tokens / window_s
    flops_tok = peaks.training_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, S)
    ctx.say(f"{n} steps in {window_s:.3f} s ({window_s / n * 1e3:.2f} "
            f"ms/step), {tokens_per_s:.1f} tokens/s over {len(devs)} chips; "
            f"{flops_tok / 1e9:.3f} GFLOP a token")
    if not ctx.rehearse:
        ctx.say(f"model FLOP/s utilization "
                f"{100 * peaks.mfu(tokens_per_s, flops_tok, ctx.device['kind'], len(devs)):.2f}"
                f"% of {len(devs)} x {ctx.device['kind']}")
    ctx.say(f"losses warm {[round(x, 4) for x in warm]} first "
            f"{[round(x, 4) for x in losses[:3]]} last "
            f"{[round(x, 4) for x in losses[-3:]]}")

    # ---- correctness, outside the window
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    for name in ("wqkv", "wfc1", "wfc2"):
        a = stacked["blocks"][name]
        on = {s.device for s in a.addressable_shards}
        ctx.check(len(on) == len(devs) and all(
            s.data.size * mp == a.size for s in a.addressable_shards),
            f"block weight {name} {a.shape} is split in {mp} over mp and "
            f"present on all {len(on)} devices")
    if not ctx.rehearse:
        ctx.check(min(used) > 0 and max(used) <= 1.25 * min(used),
                  f"memory in use is balanced over the devices "
                  f"({[round(u / 2**30, 2) for u in used]} GiB)")
    t_ref = time.perf_counter()
    host = jax.device_get(stacked)                   # gathered weights
    i = n % n_batches
    got = float(one(i))
    ids = np.asarray(xs[i]).reshape(-1, S)     # every microbatch: [M*B, S]
    del stacked, m, v
    host["blocks"] = {k: a.reshape((cfg.num_layers,) + a.shape[3:])
                      for k, a in host["blocks"].items()}
    ref_params = gpt_ref.from_hybrid(
        jax.tree_util.tree_map(lambda a: jax.device_put(a, devs[0]), host),
        cfg.num_heads)
    del host
    # a sequence at a time, so one device holds the float32 logits
    ref = float(np.mean([float(gpt_ref.loss(
        ref_params, ids[b:b + 1, :-1], ids[b:b + 1, 1:], cfg.num_heads))
        for b in range(ids.shape[0])]))
    ctx.check(abs(got - ref) <= LOSS_TOL,
              f"a step's loss {got:.5f} equals the reference's {ref:.5f} at "
              f"full depth on the same batch and gathered weights to "
              f"{LOSS_TOL} (|diff| {abs(got - ref):.2e}; took "
              f"{time.perf_counter() - t_ref:.1f} s)")
    train_window.check_losses(ctx, w)
    return {"attempted": n, "failed": 0,
            "metrics": {"train_tokens_per_s": tokens_per_s},
            "counters": {"traced_steps": w["traced_steps"]}}
