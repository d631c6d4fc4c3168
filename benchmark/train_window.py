"""train_window.py — the measured window of a training job, shared by
`jobs/train_step.py` and `jobs/train_hybrid.py`: dispatch steps, read the
loss back every `log_every` steps (as a training script that logs does;
in between the host dispatches ahead), stop at the first read-back past
the window's length, and trace the window's last seconds when asked."""

from __future__ import annotations

import math
import time


def run_window(ctx, dispatch, log_every: int) -> dict:
    """`dispatch(n)` enqueues step `n` and returns its loss as a device
    array.  Returns the losses (floats), the step count, the steps inside
    the traced part, the window's length on the host clock (to the last
    loss read back) and the compile requests counted inside it."""
    import jax
    trace_at = ctx.seconds - ctx.trace_seconds if ctx.trace else math.inf
    before = ctx.compiles.count()
    losses, elapsed = [], 0.0
    t_open = ctx.window_opens()

    def run_until(t_end):
        nonlocal elapsed
        while elapsed < t_end:
            for _ in range(log_every):
                with jax.profiler.TraceAnnotation("bench:dispatch"):
                    losses.append(dispatch(len(losses)))
            with jax.profiler.TraceAnnotation("bench:read_loss"):
                losses[-1].block_until_ready()
            elapsed = time.perf_counter() - t_open

    run_until(min(trace_at, ctx.seconds))
    traced_steps = 0
    if ctx.trace:
        n0 = len(losses)
        with ctx.profile():
            run_until(ctx.seconds)
        traced_steps = len(losses) - n0
    in_window = ctx.compiles.since(before)
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "steps": len(losses), "traced_steps": traced_steps,
            "window_s": elapsed, "compiles": in_window}


def check_losses(ctx, w: dict) -> None:
    """The checks every training cell makes of its window."""
    losses, inw = w["losses"], w["compiles"]
    ctx.check(all(math.isfinite(x) for x in losses),
              f"all {len(losses)} losses are finite")
    ctx.check(len(losses) >= 10
              and sum(losses[-5:]) / 5 < sum(losses[:5]) / 5,
              f"mean of the last five losses {sum(losses[-5:]) / 5:.4f} is "
              f"below the mean of the first five {sum(losses[:5]) / 5:.4f}")
    ctx.check(inw["requests"] == 0 and inw["compile_calls"] == 0,
              f"no program was compiled inside the window ({inw})")
