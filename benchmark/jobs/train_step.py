"""train_step.py — the job of a one-chip training cell: `GPTForCausalLM`
+ AdamW + amp O1/bf16 under one `jit.to_static` step, the main training
path as a user writes it (`chip_smoke.py` phase 1 at a published size).

Workload file: `learning_rate`, `log_every` (the loop reads the loss back
every so many steps, as a training script that logs does; in between the
host dispatches ahead), `n_batches` (distinct batches made from the seed
and cycled).  Traffic (`kind: tokens`): `batch`, `seq_len` and the
stream's structure.

`correct`: one step's loss equals the plain reference's on the same batch
and the same weights; every loss finite; the mean of the last five below
that of the first five; no compile request inside the window; the fused
optimizer served every update; flash forward and both backward kernels
are Mosaic custom calls, none interpreted.
"""

from __future__ import annotations

import time

# |system - reference| on one step's loss.  The step multiplies in bf16
# (amp O1) and accumulates in float32; the reference is float32
# "highest".  Averaged over B*S >= 2048 tokens the rounding of single
# products cancels: the v5e showed 0.6e-4 .. 2.8e-4 at losses of 5-6 (my
# chip runs, PR 24).  2e-3 is seven times the largest, and far under what
# a wrong mask, a dropped layer or bf16 accumulation would move (> 1e-2).
LOSS_TOL = 2e-3


def run(ctx) -> dict:
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import metrics, xray
    from benchmark import peaks, train_window
    from benchmark.reference import gpt_ref
    from benchmark.traffic import openloop

    wl, cfgd, mix = ctx.workload, ctx.config, ctx.traffic
    B, S = int(mix["batch"]), int(mix["seq_len"])
    t0 = time.perf_counter()
    cfg = GPTConfig(vocab_size=cfgd["vocab_size"],
                    hidden_size=cfgd["hidden_size"],
                    num_layers=cfgd["num_layers"],
                    num_heads=cfgd["num_heads"],
                    max_seq_len=cfgd["max_seq_len"],
                    intermediate_size=cfgd["intermediate_size"])
    if S > cfg.max_seq_len:
        raise ValueError(f"seq_len {S} > the configuration's context "
                         f"{cfg.max_seq_len}")
    paddle.seed(ctx.seed % (2 ** 31))
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=float(wl["learning_rate"]),
                          parameters=model.parameters())

    def train_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    n_batches = int(wl["n_batches"])
    xs, ys = openloop.token_batches(mix, ctx.seed, cfg.vocab_size, n_batches)
    xs = [paddle.to_tensor(x) for x in xs]
    ys = [paddle.to_tensor(y) for y in ys]
    n_params = model.num_params()
    t0 = ctx.part("build", t0)
    ctx.say(f"model {cfg.num_layers} x {cfg.hidden_size}, "
            f"{n_params / 1e6:.1f}M parameters, B={B} S={S} "
            f"({B * S} tokens a step), {n_batches} batches from the seed")

    def fused_counts():
        # a process-wide counter, registered at the first optimizer step
        c = metrics.get("optimizer.fused")
        return {k: c.value(kind=k) if c is not None else 0
                for k in ("hit", "miss", "fallback")}

    fused0 = fused_counts()
    # ---- warm-up: the one shape this cell uses, twice (the first call
    # discovers and compiles, the second proves nothing is built again)
    with xray.capture_kernel_claims() as claims:
        warm = [float(np.asarray(step(xs[0], ys[0])._value))]
    warm.append(float(np.asarray(step(xs[1 % n_batches],
                                      ys[1 % n_batches])._value)))
    ctx.part("warm_up", t0)

    # ---- the window
    w = train_window.run_window(
        ctx, lambda i: step(xs[i % n_batches], ys[i % n_batches])._value,
        int(wl["log_every"]))
    losses, n, window_s = w["losses"], w["steps"], w["window_s"]
    tokens_per_s = n * B * S / window_s
    flops_tok = peaks.training_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, S)
    ctx.say(f"{n} steps in {window_s:.3f} s ({window_s / n * 1e3:.2f} "
            f"ms/step), {tokens_per_s:.1f} tokens/s; "
            f"{flops_tok / 1e9:.3f} GFLOP a token")
    if not ctx.rehearse:
        ctx.say(f"model FLOP/s utilization "
                f"{100 * peaks.mfu(tokens_per_s, flops_tok, ctx.device['kind']):.2f}"
                f"% of {ctx.device['kind']}")
    ctx.say(f"losses warm {[round(x, 4) for x in warm]} first "
            f"{[round(x, 4) for x in losses[:3]]} last "
            f"{[round(x, 4) for x in losses[-3:]]}")

    # ---- correctness, outside the window
    sd = model.state_dict()
    frozen = {k: jax.numpy.copy(v._value) for k, v in sd.items()}
    i = n % n_batches
    t_ref = time.perf_counter()
    got = float(np.asarray(step(xs[i], ys[i])._value))
    ref = float(gpt_ref.loss(gpt_ref.from_state_dict(frozen, cfg.num_layers),
                             xs[i]._value, ys[i]._value, cfg.num_heads))
    ctx.check(abs(got - ref) <= LOSS_TOL,
              f"a step's loss {got:.5f} equals the reference's {ref:.5f} on "
              f"the same batch and weights to {LOSS_TOL} (|diff| "
              f"{abs(got - ref):.2e}; took {time.perf_counter() - t_ref:.1f} s)")
    train_window.check_losses(ctx, w)
    moved = {k: v - fused0[k] for k, v in fused_counts().items()}
    ctx.check(moved["fallback"] == 0 and moved["hit"] + moved["miss"] > 0,
              f"the fused optimizer served the update, no fallback ({moved})")
    if not ctx.rehearse:
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            k = sum(1 for c in claims if c == (name, "custom_call"))
            ctx.check(k >= cfg.num_layers,
                      f"{name}: {k} Mosaic custom-call claims "
                      f"(>= {cfg.num_layers})")
        ctx.check(not any(m == "interpret" for _, m in claims),
                  "no kernel in the step was interpreted")
    counters = {
        "traced_steps": w["traced_steps"],
        "attn_flops_per_step": peaks.causal_attention_train_flops(
            B, cfg.num_heads, S, cfg.hidden_size // cfg.num_heads,
            cfg.num_layers),
        "peak_flops": None if ctx.rehearse
        else peaks.peak_flops(ctx.device["kind"]),
    }
    return {"attempted": n, "failed": 0,
            "metrics": {"train_tokens_per_s": tokens_per_s},
            "counters": counters}
