"""Benchmark driver over the observability perf-evidence harness.

Prints ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline: GPT-124M (BASELINE.md rung for single-chip LM training) — a full
train step (fwd + loss + bwd + Adam) captured by `paddle_tpu.jit.to_static`
into one donated XLA program, reported as tokens/sec; `vs_baseline` =
achieved MFU / 0.45 (the BASELINE.json north-star MFU).

Every rung is registered with `paddle_tpu.observability.harness` and emits
one JSON record line on stderr — `{"rung", "ok", "value"|"error"|"reason",
"device", "elapsed_s"}` — no matter what happens inside it.  Backend
probing runs FIRST: with no TPU (or `jax.devices` itself raising), TPU-only
rungs degrade to `ok: false, reason: "backend_unavailable"` and the
CPU-salvageable rungs still measure, so the run always exits 0 with a
schema-valid artifact (BENCH_r05 was a stack trace; this is the fix).

CLI:
    python bench.py                      # full ladder (TPU rungs degrade)
    python bench.py --rungs cpu --smoke  # seconds, CPU-only schema check
    python bench.py --rungs lenet_train  # one rung
    python bench.py --out artifact.json  # also write the full artifact
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_T0 = time.monotonic()
# Wall-clock budget: the driver wraps bench.py in a timeout; every rung's
# JSON line must be out before it fires.  Overridable for local runs.
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1500"))


def remaining_s() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


def enable_compile_cache():
    """Persistent XLA compilation cache, so repeat runs skip XLA.  Where
    it lives is `paddle_tpu.core.compile_cache`'s one rule
    (JAX_COMPILATION_CACHE_DIR, else the flag, else the in-repo
    `.jax_cache`); hit/miss counters land in every rung's metrics
    delta."""
    from paddle_tpu.core import compile_cache as _cc
    _cc.configure()


from paddle_tpu.observability import flight_recorder as _flight  # noqa: E402
from paddle_tpu.observability import harness  # noqa: E402
# the ONE FLOPs/MFU accounting helper — bench, the models'
# flops_per_token and the auto-tuner cost model all read the same table
from paddle_tpu.observability.flops import peak_flops  # noqa: E402,F401


def mfu_or_none(ctx, tokens_per_sec, flops_per_token):
    """MFU against the chip's row of the peak table; None off-TPU — a CPU
    has no row, and a made-up peak would print a made-up utilization."""
    if not ctx.on_tpu:
        return None
    return round(tokens_per_sec * flops_per_token
                 / peak_flops(ctx.device_kind), 4)

# metric keys to diff against the previous round, per rung (higher=better)
_REGRESSION_KEYS = {
    "gpt124m_train": "tokens_per_sec",
    "lenet_train": "jit_imgs_per_sec",
    "resnet50_train": "imgs_per_sec",
    "bert_base_mlm_train": "tokens_per_sec",
    "gpt350m_train": "tokens_per_sec",
    "gpt124m_decode": "paged_tokens_per_sec",
    "telemetry_train": "tokens_per_sec",
    "fused_optimizer": "speedup",
    "fault_tolerance": "save_mb_per_s",
    "request_trace": "trace_overhead_pct",
    "cold_start": "cold_start_warm_speedup",
    "serving_tp": "prefix_hit_speedup",
    "serving_restart": "restart_ttft_speedup",
    "fleet": "goodput_during_restart_ratio",
    "spec_decode": ("spec_decode_speedup", "spec_accept_rate",
                    "quant_weight_ratio"),
    "continuous_batching": ("goodput_under_slo",
                            "long_arrival_tpot_ratio"),
    "analyze": "analyze_files_per_sec",
    "xray": "xray_overhead_pct",
    "fleet_telescope": "fleet_trace_overhead_pct",
    "kernel_coverage": ("paged_prefill_kernel_speedup",
                        "spec_verify_kernel_speedup"),
    "zero3_elastic": ("zero3_step_ratio", "elastic_resume_ok"),
    "elastic_mttr": "elastic_mttr_s",
}

_ENV_PROBE = {}


def _timeit(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def marginal_step_s(run_steps, sync_read, n1=3, n2=13, reps=1):
    """Marginal per-step wall time via work-delta: time(n2 steps) minus
    time(n1 steps), each ending in a forced host read of a small output.
    Robust against async dispatch queues that let `block_until_ready`
    return before remote completion (observed on an earlier launch path).

    A straggler event (late compile-cache write, donation re-layout) can
    make the SHORT window slower than the long one; such non-positive
    deltas are measurement failures and must be DISCARDED — flooring them
    to ~0 and taking min() would report an absurd rate.  Takes the min
    over the positive deltas of `reps` repeats (launch-queue noise is
    strictly additive), widening the window if every rep was poisoned."""
    def timed(n):
        t0 = time.perf_counter()
        run_steps(n)
        np.asarray(sync_read())  # host materialization = full dependency sync
        return time.perf_counter() - t0

    def one(n1, n2):
        return (timed(n1), timed(n2))

    deltas = []
    for _ in range(max(reps, 1)):
        t_a, t_b = one(n1, n2)
        deltas.append((t_b - t_a) / (n2 - n1))
    pos = [d for d in deltas if d > 0]
    if not pos:  # every window was poisoned: widen once and accept
        t_a, t_b = one(n1, 3 * n2)
        pos = [max((t_b - t_a) / (3 * n2 - n1), 1e-9)]
    return min(pos)


def _release_device_memory():
    """Free the previous rung's executables/buffers: each rung must start
    from a clean HBM (compiled programs pin their constants in jax's
    caches; three model families would otherwise accumulate to OOM)."""
    import gc

    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ===================================================================== rungs

@harness.register_rung("gpt124m_train", est_cold_s=300)
def bench_gpt124m(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m

    on_tpu = ctx.on_tpu
    B, S = (4, 1024) if on_tpu else (2, 256)

    paddle.seed(0)
    cfg = gpt3_124m()
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def train_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))

    # warmup/compile
    t0 = time.perf_counter()
    loss = step(ids, labels)
    np.asarray(loss._value)
    compile_s = time.perf_counter() - t0

    def run_steps(n):
        nonlocal loss
        for _ in range(n):
            loss = step(ids, labels)

    # launch queueing adds noise to any single timing;
    # take the best of several marginal measurements over longer windows
    # (noise is strictly additive, so min is the honest sustained rate)
    sync = lambda: model.gpt.ln_f.bias._value  # noqa: E731
    if on_tpu:
        dt = marginal_step_s(run_steps, sync, 5, 30, reps=3)
    else:
        dt = marginal_step_s(run_steps, sync, 1, 3)
    tokens_per_sec = B * S / dt
    fpt = model.flops_per_token(S)
    return {"batch": B, "seq": S, "step_ms": round(dt * 1e3, 2),
            "compile_s": round(compile_s, 1),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "flops_per_token": fpt,
            "mfu": mfu_or_none(ctx, tokens_per_sec, fpt),
            "loss": float(loss.item())}


@harness.register_rung("telemetry_train", est_cold_s=120, smoke=True)
def bench_telemetry_train(ctx):
    """ISSUE 2 acceptance rung: a short compiled GPT train loop driven
    step-by-step under a StepTimeline, so the record carries per-step
    evidence — compute/comm/host fractions, tokens/sec and MFU from the
    shared FLOPs helper — instead of a bare throughput claim.  Each
    step syncs the loss to the host inside the bracket (the timeline
    measures completed steps, not enqueue time)."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny
    from paddle_tpu.observability import telemetry

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    B, S, steps = (4, 1024, 8) if on_tpu else (2, 64, 4)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def train_step(ids, labels):
        loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))

    tl = telemetry.StepTimeline(name="bench.telemetry_train",
                                flops_per_token=model.flops_per_token(S),
                                device_kind=ctx.device_kind
                                if ctx.on_tpu else None)
    for _ in range(steps):
        with tl.step(tokens=B * S) as st:
            loss = step(ids, labels)
            st.annotate(loss=float(np.asarray(loss._value)), synced=True)
    summ = tl.summary()
    return {"batch": B, "seq": S, "steps": steps,
            "tokens_per_sec": summ["tokens_per_sec"],
            "mfu": summ.get("mfu"), "timeline": summ}


@harness.register_rung("fused_optimizer", est_cold_s=120, smoke=True)
def bench_fused_optimizer(ctx):
    """Round-7 tentpole rung: one Adam step with global-norm clip over a
    param-count ladder, FLAGS_fused_optimizer off vs on.  Each cell
    records the marginal per-step wall time and the optimizer-layer
    program dispatches per step (the `dispatch.ops` delta over
    optimizer.fused_step / optimizer.leaf_update / clip.tree / amp.unscale
    — the count the fused path collapses from ~3N+1 to 1)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.observability import metrics as obs_metrics

    _OPT_OPS = ("optimizer.fused_step", "optimizer.leaf_update",
                "clip.tree", "amp.unscale")

    def opt_dispatches():
        c = obs_metrics.get("dispatch.ops")
        return sum(c.value(op=k) for k in _OPT_OPS) if c else 0

    ladder = (8, 64) if ctx.smoke else (8, 64, 256)
    leaf_size = 256 if ctx.smoke else 1024
    rows = []
    for n_leaves in ladder:
        row = {"leaves": n_leaves, "leaf_size": leaf_size}
        rng = np.random.RandomState(0)
        grads_np = [rng.rand(leaf_size).astype(np.float32) * 0.1
                    for _ in range(n_leaves)]
        for fused in (False, True):
            with flag_guard(fused_optimizer=fused):
                paddle.seed(0)
                params = [paddle.Parameter(np.ones(leaf_size, np.float32))
                          for _ in range(n_leaves)]
                grads = [paddle.to_tensor(g) for g in grads_np]
                opt = optimizer.Adam(
                    learning_rate=1e-3, parameters=params,
                    grad_clip=nn.ClipGradByGlobalNorm(1.0))

                def one_step():
                    for p, g in zip(params, grads):
                        p.grad = g
                    opt.step()

                one_step()  # compile/warm the per-tree programs
                base = opt_dispatches()
                one_step()
                dispatches = opt_dispatches() - base
                np.asarray(params[0]._value)
                steps = 3 if ctx.smoke else 20
                best = float("inf")
                for _ in range(2 if ctx.smoke else 3):
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        one_step()
                    np.asarray(params[0]._value)
                    best = min(best, (time.perf_counter() - t0) / steps)
                row["fused" if fused else "per_param"] = {
                    "step_ms": round(best * 1e3, 3),
                    "dispatches_per_step": int(dispatches)}
        row["speedup"] = round(
            row["per_param"]["step_ms"] / max(row["fused"]["step_ms"], 1e-9),
            2)
        rows.append(row)
    return {"ladder": rows,
            "speedup": rows[-1]["speedup"],
            "fused_dispatches_per_step":
                rows[-1]["fused"]["dispatches_per_step"],
            "per_param_dispatches_per_step":
                rows[-1]["per_param"]["dispatches_per_step"]}


@harness.register_rung("fault_tolerance", est_cold_s=90, smoke=True)
def bench_fault_tolerance(ctx):
    """Resilience rung (ISSUE 5): atomic-checkpoint save/restore latency
    and bytes, chaos-truncation detection, and a seconds-scale
    kill-and-resume drill on a tiny hapi model — resume from the
    surviving version must be bit-identical to the uninterrupted run."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.checkpoint import (CheckpointManager,
                                                   latest_complete)
    from paddle_tpu.testing import chaos

    out = {}
    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        # --- raw save/restore latency + bytes on a synthetic pytree
        rng = np.random.RandomState(0)
        n, w = (8, 1 << 16) if ctx.smoke else (16, 1 << 20)
        state = {"model": {f"w{i}": rng.rand(w).astype(np.float32)
                           for i in range(n)}}
        mb = n * w * 4 / 1e6
        mgr = CheckpointManager(os.path.join(root, "raw"), keep_last=2)
        t0 = time.perf_counter()
        mgr.save(1, state)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        loaded = mgr.load()
        restore_s = time.perf_counter() - t0
        roundtrip_ok = all(
            np.array_equal(loaded["model"][k], state["model"][k])
            for k in state["model"])
        mgr.save(2, state)
        # truncate the newest committed version's data file: discovery
        # must skip it and fall back to step 1
        data = os.path.join(mgr.step_path(2), "0_0.distcp")
        chaos.truncate_file(data, os.path.getsize(data) // 2)
        corrupt_skipped = latest_complete(mgr.root) == 1
        out.update(
            payload_mb=round(mb, 2),
            save_s=round(save_s, 4), restore_s=round(restore_s, 4),
            save_mb_per_s=round(mb / max(save_s, 1e-9), 2),
            restore_mb_per_s=round(mb / max(restore_s, 1e-9), 2),
            roundtrip_ok=bool(roundtrip_ok),
            corrupt_skipped=bool(corrupt_skipped))

        # --- tiny-model kill-and-resume drill (in-process "crash": train
        # half the epochs, throw the model away, resume a fresh one)
        rng = np.random.RandomState(1)
        xs = rng.rand(32, 4).astype(np.float32)
        ys = xs.sum(axis=1, keepdims=True).astype(np.float32)

        class _DS(paddle.io.Dataset):
            def __len__(self):
                return len(xs)

            def __getitem__(self, i):
                return xs[i], ys[i]

        def build():
            paddle.seed(11)
            net = nn.Linear(4, 1)
            model = paddle.Model(net)
            model.prepare(optimizer=optimizer.Adam(
                learning_rate=0.05, parameters=net.parameters()),
                loss=nn.MSELoss())
            return model

        def params_of(model):
            return [np.asarray(p._value) for p in model.network.parameters()]

        ref = build()
        ref.fit(_DS(), batch_size=8, epochs=2, verbose=0, shuffle=False)

        ck = CheckpointManager(os.path.join(root, "drill"), save_interval=4)
        crash = build()
        crash.fit(_DS(), batch_size=8, epochs=1, verbose=0, shuffle=False,
                  checkpoint=ck)
        resumed = build()
        resumed.fit(_DS(), batch_size=8, epochs=2, verbose=0, shuffle=False,
                    checkpoint=ck, resume=True)
        out["resume_bitexact"] = bool(all(
            np.array_equal(a, b)
            for a, b in zip(params_of(ref), params_of(resumed))))
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


@harness.register_rung("env_probe", est_cold_s=30, smoke=True)
def bench_env_probe(ctx):
    """Chip/launch-path health, logged in-artifact so every perf number
    can be read against the window it was measured in (an earlier launch
    path had co-tenant windows: the same compiled GPT step measured 35->81 ms
    across an hour with byte-identical numerics; r04's lenet -42% was this
    probe's dispatch floor doubling, not a code change).

    - matmul_tflops: sustained NxN bf16 matmul (healthy ~96 on v5e at
      N=8192; N shrinks off-TPU so the probe stays cheap).
    - tiny_rtt_ms: median round trip of a tiny op + host read.
    - dispatch_floor_ms: per-op cost of a 200-deep chained tiny program —
      the lower bound any latency-bound rung's step time can reach.
    """
    import jax
    import jax.numpy as jnp
    N = 8192 if ctx.on_tpu else (256 if ctx.smoke else 512)
    x = jax.random.normal(jax.random.key(0), (N, N), jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        r = f(x)
        for _ in range(9):
            r = f(r)
        np.asarray(r[:2, :2])
        best = min(best, (time.perf_counter() - t0) / 10)
    tflops = 2 * N ** 3 / best / 1e12

    t = jnp.ones((8, 8), jnp.float32)
    g = jax.jit(lambda a: a + 1)
    np.asarray(g(t))
    ts = sorted(
        _timeit(lambda: np.asarray(g(t))) for _ in range(15))
    rtt = ts[len(ts) // 2]

    depth = 200 if not ctx.smoke else 50
    t0 = time.perf_counter()
    r = t
    for _ in range(depth):
        r = g(r)
    np.asarray(r[:1, :1])
    floor = (time.perf_counter() - t0) / depth

    _ENV_PROBE.update(matmul_tflops=round(tflops, 1),
                      tiny_rtt_ms=round(rtt * 1e3, 2),
                      dispatch_floor_ms=round(floor * 1e3, 3),
                      matmul_n=N)
    return dict(_ENV_PROBE)


@harness.register_rung("dispatch_overhead", est_cold_s=15, smoke=True)
def bench_dispatch(ctx):
    """Eager per-op dispatch overhead: chained small adds vs raw jax."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    ja = jnp.ones((4, 4), jnp.float32)
    n = 100 if ctx.smoke else 300
    # warm
    b = a
    for _ in range(5):
        b = b + a
    b._value.block_until_ready()
    t0 = time.perf_counter()
    b = a
    for _ in range(n):
        b = b + a
    b._value.block_until_ready()
    eager_ops = n / (time.perf_counter() - t0)
    jb = ja
    for _ in range(5):
        jb = jb + ja
    jb.block_until_ready()
    t0 = time.perf_counter()
    jb = ja
    for _ in range(n):
        jb = jb + ja
    jb.block_until_ready()
    raw_ops = n / (time.perf_counter() - t0)
    return {"eager_ops_per_sec": round(eager_ops),
            "raw_jax_ops_per_sec": round(raw_ops),
            "overhead_ratio": round(raw_ops / eager_ops, 2)}


@harness.register_rung("dispatch_overhead_cpu", est_cold_s=60, smoke=True)
def bench_dispatch_cpu(ctx):
    """Framework Python dispatch cost, independent of the accelerator's
    launch path: eager op chain on the LOCAL CPU backend in a subprocess —
    the per-op overhead trend of the dispatch machinery itself (tape
    wiring, AMP hook, cached program lookup), comparable across rounds
    because no accelerator is involved."""
    import subprocess
    chain_n, reps = (100, 2) if ctx.smoke else (400, 5)
    code = rf"""
import os, sys, time
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
x = paddle.to_tensor(np.ones((8, 8), np.float32))
def chain(n):
    y = x
    for _ in range(n):
        y = paddle.add(paddle.multiply(y, x), x)
    return y
np.asarray(chain(50)._value)          # warm caches
best = float("inf")
for _ in range({reps}):
    t0 = time.perf_counter()
    np.asarray(chain({chain_n})._value)
    best = min(best, time.perf_counter() - t0)
print(round(2 * {chain_n} / best, 1))   # 2 ops per iteration
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=180,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    if out.returncode != 0:
        raise RuntimeError(f"subprocess rc={out.returncode}: "
                           f"{out.stderr[-300:]}")
    return {"eager_ops_per_sec": float(out.stdout.strip().splitlines()[-1])}


@harness.register_rung("metrics_overhead", est_cold_s=30, smoke=True)
def bench_metrics_overhead(ctx):
    """Observability cost on the eager hot loop: the same dispatch chain
    with the metrics registry enabled vs disabled (FLAGS_enable_metrics).
    The disabled delta is the acceptance bound (< 2%); the enabled delta
    is the price of per-op counters."""
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.ones((8, 8), np.float32))

    def chain(n):
        y = x
        for _ in range(n):
            y = paddle.add(paddle.multiply(y, x), x)
        return y

    n = 100 if ctx.smoke else 300
    np.asarray(chain(30)._value)  # warm program caches

    def rate():
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(chain(n)._value)
            best = min(best, time.perf_counter() - t0)
        return 2 * n / best

    saved = paddle.get_flags(["enable_metrics"])["enable_metrics"]
    try:
        # interleave on/off windows so drift hits both sides equally
        paddle.set_flags({"enable_metrics": True})
        on1 = rate()
        paddle.set_flags({"enable_metrics": False})
        off1 = rate()
        paddle.set_flags({"enable_metrics": True})
        on2 = rate()
        paddle.set_flags({"enable_metrics": False})
        off2 = rate()
    finally:
        paddle.set_flags({"enable_metrics": saved})
    on, off = max(on1, on2), max(off1, off2)
    return {"ops_per_sec_metrics_on": round(on, 1),
            "ops_per_sec_metrics_off": round(off, 1),
            "enabled_overhead_frac": round(max(0.0, 1 - on / off), 4)}


@harness.register_rung("tuner_memory_validation", requires="tpu",
                       est_cold_s=200)
def bench_tuner_memory_validation(ctx):
    """VERDICT r4 weak #6: calibrate the auto-tuner's analytic HBM model
    against a MEASURED peak on a real config.  Runs the GPT-124M train
    step (same shapes as the headline rung, so the compile is cached),
    reads device.max_memory_allocated(), and logs it against
    cost_model.estimate_memory with this run's true byte widths (AMP O1:
    f32 params+grads, f32 m+v).  The in-artifact ratio is the
    calibration the tuner's memory pruning rests on."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, device, optimizer
    from paddle_tpu.distributed.auto_tuner.cost_model import (
        ModelSpec, estimate_memory)
    from paddle_tpu.distributed.auto_tuner.tuner import Trial
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m

    B, S = 4, 1024
    paddle.seed(0)
    cfg = gpt3_124m()
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def train_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    step(ids, labels)
    device.reset_max_memory_allocated()
    loss = step(ids, labels)
    np.asarray(loss._value)
    measured = float(device.max_memory_allocated())

    spec = ModelSpec(num_layers=cfg.num_layers,
                     hidden_size=cfg.hidden_size,
                     num_heads=cfg.num_heads, vocab_size=cfg.vocab_size,
                     seq_len=S, global_batch_size=B)
    trial = Trial(dp=1, mp=1, pp=1, sharding=1, micro_batch_size=B)
    est = estimate_memory(trial, spec, weight_bytes=4, state_bytes=8,
                          act_bytes=2)
    ratio = measured / est if est else float("inf")
    return {"config": "gpt124m B4 S1024",
            "measured_gb": round(measured / 2 ** 30, 3),
            "estimated_gb": round(est / 2 ** 30, 3),
            "measured_over_estimated": round(ratio, 3),
            "within_2x": bool(0.5 <= ratio <= 2.0)}


@harness.register_rung("lenet_train", est_cold_s=60)
def bench_lenet(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = optimizer.Momentum(learning_rate=0.01,
                             parameters=model.parameters())
    lossf = nn.CrossEntropyLoss()

    def train_step(x, y):
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    B = 256
    x = paddle.to_tensor(rng.rand(B, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (B,)).astype(np.int32))

    def run_eager(n):
        for _ in range(n):
            train_step(x, y)

    sync = lambda: model.parameters()[0]._value  # noqa: E731
    run_eager(2)  # warm vjp/trace caches fully before timing
    np.asarray(sync())
    eager_dt = marginal_step_s(run_eager, sync, 2, 8)

    step = to_static(train_step)
    step(x, y)  # compile
    np.asarray(sync())

    def run_jit(n):
        for _ in range(n):
            step(x, y)

    # three measurement windows a few seconds apart: the step is ONE
    # compiled program whose compute is microseconds, so its wall time sits
    # on the dispatch floor — band the windows so a noisy window is
    # visible in-artifact instead of masquerading as a code regression
    jit_dts = []
    for w in range(3):
        if w:
            time.sleep(3)
        jit_dts.append(marginal_step_s(run_jit, sync, 5, 30))
    jit_dts.sort()
    jit_dt = jit_dts[1]   # median window
    band = [round(B / d, 1) for d in reversed(jit_dts)]  # [min..max] imgs/s
    floor = _ENV_PROBE.get("dispatch_floor_ms", 0.0)
    return {"batch": B,
            "eager_imgs_per_sec": round(B / eager_dt, 1),
            "jit_imgs_per_sec": round(B / jit_dt, 1),
            "jit_imgs_per_sec_band": band,
            "jit_step_ms": round(jit_dt * 1e3, 3),
            "latency_bound": bool(floor and jit_dt * 1e3 < 2.5 * floor)}


@harness.register_rung("gpt124m_decode", est_cold_s=200)
def bench_decode(ctx):
    """Autoregressive decode throughput: GPT-124M greedy generation with
    the static preallocated KV cache (one compiled program for all decode
    steps, `models/kv_cache.py`) vs the paged block cache (Pallas
    kernel).  The concat-and-grow dense cache is excluded on TPU: a new
    shape per token means a fresh XLA compile per decode position —
    the design StaticKVCache exists to replace."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    B, prompt, new = (8, 128, 64) if on_tpu else (2, 16, 8)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, prompt)).astype(np.int32))
    results = {}
    for impl in ("static", "paged"):
        # both impls compile the whole generation (prefill + lax.scan
        # over decode steps) into one program on the first call
        out = model.generate(ids, max_new_tokens=new, cache_impl=impl)
        np.asarray(out._value)
        best = float("inf")
        for _ in range(3 if on_tpu else 1):
            t0 = time.perf_counter()
            out = model.generate(ids, max_new_tokens=new, cache_impl=impl)
            np.asarray(out._value)
            best = min(best, time.perf_counter() - t0)
        results[impl] = B * new / best
    return {"batch": B, "prompt": prompt, "new_tokens": new,
            "static_tokens_per_sec": round(results["static"], 1),
            "paged_tokens_per_sec": round(results["paged"], 1)}


@harness.register_rung("gpt124m_decode_32k_config", requires="tpu",
                       est_cold_s=150)
def bench_decode_longctx(ctx):
    """Paged-KV long-context rung: the SAME model configured for a 32k
    serving context.  The static cache preallocates the full
    [B, max_seq_len] rectangle (~19.3 GB at B=8 — exceeds a v5e's HBM
    and OOMs); the paged pool allocates only the context actually used
    (prompt + new tokens), so serving works.  This is the capability the
    reference's block_multihead_attention paging exists for."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m

    paddle.seed(0)
    cfg = gpt3_124m(max_seq_len=32768)
    model = GPTForCausalLM(cfg)
    model.eval()
    B, prompt, new = 8, 128, 64
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, prompt)).astype(np.int32))
    static_result = "n/a"
    try:
        out = model.generate(ids, max_new_tokens=new, cache_impl="static")
        np.asarray(out._value)
        static_result = "fit"  # unexpected on 16 GB HBM
    except Exception as e:  # noqa: BLE001 - OOM expected
        msg = repr(e)
        oom = any(k in msg for k in (
            "RESOURCE_EXHAUSTED", "Out of memory", "Ran out of memory"))
        import re
        used = re.search(r"Used ([\d.]+[GM]) of ([\d.]+[GM]) hbm", msg)
        static_result = ("OOM " + (f"({used.group(1)} needed, "
                                   f"{used.group(2)} HBM)" if used else "")
                         ).strip() if oom else f"error: {msg[:80]}"
    _release_device_memory()
    out = model.generate(ids, max_new_tokens=new, cache_impl="paged")
    np.asarray(out._value)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new, cache_impl="paged")
        np.asarray(out._value)
        best = min(best, time.perf_counter() - t0)
    tps = B * new / best
    return {"batch": B, "prompt": prompt, "new_tokens": new,
            "static": static_result, "paged_tokens_per_sec": round(tps, 1)}


@harness.register_rung("resnet50_train", est_cold_s=380)
def bench_resnet50(ctx):
    """BASELINE rung 2 (single-chip side of the DDP config): ResNet-50
    jitted train step, synthetic 224x224 batch, imgs/sec."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.vision.models import resnet50

    on_tpu = ctx.on_tpu
    B = 32 if on_tpu else 4  # B=64 exceeded one v5e chip's free HBM (r04)
    paddle.seed(0)
    model = resnet50()
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
    lossf = nn.CrossEntropyLoss()

    def train_step(x, y):
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(B, 3, 224, 224).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 1000, (B,)).astype(np.int32))
    t0 = time.perf_counter()
    step(x, y)
    np.asarray(model.parameters()[0]._value)
    compile_s = time.perf_counter() - t0

    def run(n):
        for _ in range(n):
            step(x, y)

    sync = lambda: model.parameters()[0]._value  # noqa: E731
    dt = marginal_step_s(run, sync, *((3, 13) if on_tpu else (1, 3)),
                         reps=2 if on_tpu else 1)
    return {"batch": B, "imgs_per_sec": round(B / dt, 1),
            "step_ms": round(dt * 1e3, 2), "compile_s": round(compile_s, 1)}


@harness.register_rung("bert_base_mlm_train", est_cold_s=500)
def bench_bert_base(ctx):
    """BASELINE rung 3: BERT-base MLM jitted train step, tokens/sec + MFU."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.bert import BertForMaskedLM, bert_base, bert_tiny

    on_tpu = ctx.on_tpu
    if on_tpu:
        # B=8 fits now that flash attention stopped materializing the
        # [B, nh, S, S] probability tensor (B=16 still exceeds free HBM)
        cfg, B, S = bert_base(), 8, 512
    else:
        cfg, B, S = bert_tiny(), 2, 64
    paddle.seed(0)
    model = BertForMaskedLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def train_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(4, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(np.where(
        rng.rand(B, S) < 0.15,
        rng.randint(4, cfg.vocab_size, (B, S)), -100).astype(np.int32))
    t0 = time.perf_counter()
    loss = step(ids, labels)
    np.asarray(loss._value)
    compile_s = time.perf_counter() - t0

    def run(n):
        for _ in range(n):
            step(ids, labels)

    sync = lambda: model.transform.weight._value  # noqa: E731
    dt = marginal_step_s(run, sync, *((5, 30) if on_tpu else (1, 3)),
                         reps=3 if on_tpu else 1)
    tps = B * S / dt
    return {"batch": B, "seq": S, "tokens_per_sec": round(tps, 1),
            "mfu": mfu_or_none(ctx, tps, model.flops_per_token(S)),
            "step_ms": round(dt * 1e3, 2),
            "compile_s": round(compile_s, 1)}


@harness.register_rung("gpt350m_train", requires="tpu", est_cold_s=450)
def bench_gpt350m(ctx):
    """Medium rung toward BASELINE config 4 (1.3B): GPT-350M
    (hidden 1024 x 24 layers), B=8 S=1024, AMP O1 bf16, selective remat
    (`dots_with_no_batch_dims_saveable`: matmul outputs saved, elementwise
    recomputed — full remat measured 1.5pt MFU lower, no-remat OOMs at
    this batch).  Same step/measurement shape as the 124M headline."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_350m

    B, S = 8, 1024
    paddle.seed(0)
    cfg = gpt3_350m(use_recompute=True,
                    recompute_policy="dots_with_no_batch_dims_saveable")
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def train_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    t0 = time.perf_counter()
    loss = step(ids, labels)
    np.asarray(loss._value)
    compile_s = time.perf_counter() - t0

    def run_steps(n):
        for _ in range(n):
            step(ids, labels)

    sync = lambda: model.gpt.ln_f.bias._value  # noqa: E731
    dt = marginal_step_s(run_steps, sync, 3, 13, reps=3)
    tokens_per_sec = B * S / dt
    fpt = model.flops_per_token(S)
    return {"batch": B, "seq": S, "step_ms": round(dt * 1e3, 2),
            "compile_s": round(compile_s, 1),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "params_m": round(model.num_params() / 1e6, 1),
            "mfu": mfu_or_none(ctx, tokens_per_sec, fpt),
            "loss": float(loss.item())}


@harness.register_rung("ring_attention_8k", est_cold_s=120, smoke=True)
def bench_ring_attention(ctx):
    """Long-context rung (SURVEY §5.7): S=8192 causal attention fwd+bwd.

    Compares the Pallas flash kernel over the full sequence against ONE
    member of an 8-way sequence-parallel ring
    (`ring_attention_chunked`: the busiest causal rank — last S/8
    queries, 8 K/V hops — exactly the per-device program of
    `ring_attention`).  Reports tokens/s (ring member tokens/s is
    per-device; 8 members run concurrently on an 8-chip ring) plus each
    compiled program's XLA temp memory: the member's (S/8, S/8) score
    blocks are the memory shape that lets an 8-ring hold 8x the
    context per chip.  Off-TPU the member runs the exact jnp
    online-softmax fallback at reduced S (interpret-mode scale)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.functional.ring_attention import \
        ring_attention_chunked
    from paddle_tpu.ops import pallas_flash

    on_tpu = ctx.on_tpu
    if on_tpu:
        B, nh, S, hd = 1, 12, 8192, 64
    else:
        B, nh, S, hd = (1, 2, 256, 64) if ctx.smoke else (1, 2, 512, 64)
    R = 8
    key = jax.random.key(0)
    qs = jax.random.normal(key, (B, S, nh, hd), jnp.bfloat16) * 0.1
    ks, vs = qs * 0.7, qs * 1.3
    bhsd = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731

    def loss_flash(q, k, v):
        o = pallas_flash.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * 1e-6)

    def loss_ring(q, k, v):
        o = ring_attention_chunked(q, k, v, n_chunks=R, causal=True,
                                   q_off=S - S // R)
        return jnp.sum(o.astype(jnp.float32) * 1e-6)

    res = {}
    for name, fn, args, toks in (
            ("flash", loss_flash, (qs, ks, vs), B * S),
            ("ring", loss_ring,
             (bhsd(qs)[:, :, -(S // R):], bhsd(ks), bhsd(vs)),
             B * S // R)):
        g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
        lowered = g.lower(*args).compile()
        mem = lowered.memory_analysis()
        temp = getattr(mem, "temp_size_in_bytes", 0)
        r = lowered(*args)
        np.asarray(r[0][0, 0, 0, :2])
        best = float("inf")
        for _ in range(3 if on_tpu else 1):
            t0 = time.perf_counter()
            for _ in range(8):
                r = g(*args)
            np.asarray(r[0][0, 0, 0, :2])
            best = min(best, (time.perf_counter() - t0) / 8)
        res[name] = (toks / best, temp)
    return {"batch": B, "seq": S, "heads": nh, "ring_degree": R,
            "flash_tokens_per_sec": round(res["flash"][0], 1),
            "ring_member_tokens_per_sec": round(res["ring"][0], 1),
            "flash_temp_mb": round(res["flash"][1] / 2**20, 1),
            "ring_member_temp_mb": round(res["ring"][1] / 2**20, 1)}


@harness.register_rung("kernel_coverage", est_cold_s=90, smoke=True)
def bench_kernel_coverage(ctx):
    """The X-ray kernel-gap rung (ISSUE 18): times the paged Pallas
    kernels against the dense linearized-table gather they replace, at
    the TABLE-SLACK shapes where the dense path burns its work — a
    small live pool behind a wide padded block table (continuous
    batching allocates tables for max_context; a short prefix uses a
    few blocks).  Two measurements, one per audited suspect: the
    chunked-prefill chunk and the spec-verify chunk.  The record embeds
    the kernel-coverage audit rows the measurement corresponds to —
    the same two evidence channels (`via`) `xray.kernel_coverage`
    reports after serving warmup — plus the MoE dispatch row from
    `audit_dispatch`, so every BENCH artifact self-evidences WHICH
    executor produced the numbers.  A jax build without Pallas
    degrades to backend_unavailable (the dense path still serves;
    there is just no kernel to measure)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import xray as _xray
    from paddle_tpu.ops import pallas_paged as _pp

    if getattr(_pp, "pltpu", None) is None:
        raise harness.BackendUnavailable(
            "jax.experimental.pallas.tpu unavailable: no Pallas kernel "
            "to measure (the dense reference path still serves)")

    on_tpu = ctx.on_tpu
    bs, nh, hd = 16, 2, 64
    if on_tpu:
        B, max_blocks = 4, 512
        cases = {"paged_prefill": (128, 384), "spec_verify": (8, 504)}
    elif ctx.smoke:
        B, max_blocks = 2, 64
        cases = {"paged_prefill": (32, 48), "spec_verify": (4, 124)}
    else:
        B, max_blocks = 2, 256
        cases = {"paged_prefill": (64, 192), "spec_verify": (8, 248)}

    rng = np.random.RandomState(0)
    out = {"batch": B, "block_size": bs, "max_blocks": max_blocks,
           "heads": nh, "head_dim": hd}
    reps = 8 if on_tpu else 4
    for case, (s, start) in cases.items():
        live = -(-(start + s) // bs)              # blocks holding keys
        npool = live * B + 1                      # block 0 = pad
        k_pool = jnp.asarray(
            rng.standard_normal((nh, npool, bs, hd)), jnp.float32) * 0.3
        v_pool = jnp.asarray(
            rng.standard_normal((nh, npool, bs, hd)), jnp.float32) * 0.3
        tables = np.zeros((B, max_blocks), np.int32)
        for b in range(B):
            tables[b, :live] = 1 + b * live + np.arange(live)
        tables = jnp.asarray(tables)
        starts = jnp.full((B,), start, jnp.int32)
        q = jnp.asarray(
            rng.standard_normal((B, s, nh, hd)), jnp.float32) * 0.3
        fn_kernel = _pp.paged_verify_attention if case == "spec_verify" \
            else _pp.paged_chunk_attention
        entry = _xray.register(
            "serving.prefill_cont" if case == "paged_prefill"
            else "serving.spec_tick",
            (("bench", "kernel_coverage"), ("B", B), ("s", s),
             ("start", start), ("max_blocks", max_blocks)))
        jk = jax.jit(fn_kernel)
        with _xray.capture_kernel_claims() as claims:
            lowered = jk.lower(q, k_pool, v_pool, tables, starts)
        _xray.attach_lowered(entry, lowered, claims)
        jd = jax.jit(_pp.paged_chunk_attention_reference)
        times = {}
        for name, fn in (("kernel", jk), ("dense", jd)):
            r = fn(q, k_pool, v_pool, tables, starts)
            np.asarray(r[0, 0, 0, :2])            # compile + sync
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    r = fn(q, k_pool, v_pool, tables, starts)
                np.asarray(r[0, 0, 0, :2])
                best = min(best, (time.perf_counter() - t0) / reps)
            times[name] = best
        out[f"{case}_chunk"] = s
        out[f"{case}_kernel_ms"] = round(times["kernel"] * 1e3, 3)
        out[f"{case}_dense_ms"] = round(times["dense"] * 1e3, 3)
        out[f"{case}_kernel_speedup"] = round(
            times["dense"] / times["kernel"], 3)

    # MoE dispatch audit row: a representative tiny layer, the ACTIVE
    # data plane per FLAGS_moe_fused_dispatch
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        ExpertMLP, MoELayer, audit_dispatch)
    layer = MoELayer(32, experts=ExpertMLP(4, 32, 64), gate="switch",
                     top_k=1, capacity_factor=2.0)
    audit_dispatch(layer, num_tokens=64)
    suspects = ("suffix/chunked prefill", "spec verify chunk",
                "moe dispatch/combine")
    out["audit"] = [
        {k: r.get(k) for k in ("program", "path", "kernel", "via",
                               "kernels")}
        for r in _xray.kernel_coverage() if r["path"] in suspects]
    return out


@harness.register_rung("zero3_elastic", est_cold_s=150, smoke=True)
def bench_zero3_elastic(ctx):
    """Elastic ZeRO-3 rung (ISSUE 19): the fused one-dispatch stage-3
    step against the naive allgather-on-use loop it replaces, plus the
    elastic-resume drill as a pinned boolean.

    One subprocess on a forced 4-device CPU mesh times
    `make_zero3_train_step` (bucketed in-program gathers, in-program
    reduce-scatter via AD transpose, fused shard optimizer — ONE
    dispatch per step) against a baseline that does what stage 3
    without the fused step has to do: eagerly all-gather every
    parameter leaf (one collective dispatch per leaf), run a jitted
    full-parameter step, eagerly re-shard the gradients and apply the
    shard optimizer as a second program.  `zero3_step_ratio` =
    best-of-reps naive step time / fused step time (regression key;
    it dropping below 1.0 means the fusion stopped paying for itself).
    The same subprocess replays the 4 -> 2 -> 4 reshard-on-resume
    drill through CheckpointManager and reports `elastic_resume_ok`
    (bit-exact params AND moments vs a never-interrupted run) — a
    fast fused step that breaks resume is a regression no ratio
    excuses.  On TPU the rung degrades to backend_unavailable: the
    drill NEEDS a forced multi-device CPU mesh to emulate world-size
    changes inside one host."""
    if ctx.on_tpu:
        raise harness.BackendUnavailable(
            "zero3_elastic drills world-size changes on a forced "
            "multi-device CPU mesh; a latched TPU backend cannot "
            "re-partition itself into 4-then-2 device worlds")
    code = r"""
import dataclasses, json, os, tempfile, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed.checkpoint.manager import CheckpointManager
from paddle_tpu.distributed.fleet import hybrid_step as hs
from paddle_tpu.distributed.fleet.sharding import flat_shard_layout
from paddle_tpu.optimizer.fused import zero3_shard_update

cfg = hs.HybridConfig(vocab_size=128, hidden_size=64, num_layers=4,
                      num_heads=4, seq_len=32, pp=1, mp=1, dp=4,
                      n_microbatches=2, sequence_parallel=False,
                      remat=False, zero_stage=3)
params = hs.init_gpt_params(jax.random.PRNGKey(0), cfg)
ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8, 32), 0, 128)
mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
out = {}

# --- fused one-dispatch step
fp, m, v = hs.init_zero3_state(params, mesh)
step = hs.make_zero3_train_step(mesh, cfg)
out["buckets"] = len(step.buckets)
loss, fp, m, v = step(fp, m, v, jnp.float32(1.0), ids)   # compile
jax.block_until_ready(fp)

def best_of(fn, reps=5, iters=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best

sno = [1.0]
def fused_once():
    sno[0] += 1.0
    _, p2, m2, v2 = step(fp, m, v, jnp.float32(sno[0]), ids)
    jax.block_until_ready(p2)
fused_s = best_of(fused_once)

# --- naive allgather-on-use baseline: one eager collective dispatch
# per leaf to materialize full params, a jitted full-parameter
# grad step, an eager re-shard per leaf, a second program for the
# shard optimizer update
leaves, treedef = jax.tree_util.tree_flatten(params)
repl = NamedSharding(mesh, P())
shard = NamedSharding(mesh, P("dp"))

def full_grad_step(pl, batch):
    ps = jax.tree_util.tree_unflatten(treedef, pl)
    def loss_fn(p):
        per_mb = jnp.stack([hs.serial_forward(p, batch[i], cfg)
                            for i in range(batch.shape[0])])
        return jnp.mean(per_mb)
    return jax.value_and_grad(loss_fn)(ps)
jf = jax.jit(full_grad_step)
ju = jax.jit(zero3_shard_update)

metas = [(tuple(l.shape), l.dtype) + flat_shard_layout(l.shape, 4)
         for l in leaves]

def naive_once(fp_l, m_l, v_l, t):
    # allgather-on-use: leaf-by-leaf eager replication
    full = [jax.device_put(f[:F].reshape(shape), repl)
            for f, (shape, dt, F, Fp) in zip(fp_l, metas)]
    loss, grads = jf(full, ids)
    gl = jax.tree_util.tree_leaves(grads)
    # eager per-leaf re-shard of the gradients back to the flat layout
    g_sh = [jax.device_put(
                jnp.pad(g.reshape(-1), (0, Fp - F)), shard)
            for g, (shape, dt, F, Fp) in zip(gl, metas)]
    kw = dict(learning_rate=cfg.learning_rate, beta1=cfg.beta1,
              beta2=cfg.beta2, eps=cfg.eps)
    p2, m2, v2 = ju(fp_l, g_sh, m_l, v_l, jnp.float32(t), **kw)
    jax.block_until_ready(p2)
    return p2, m2, v2

tl = jax.tree_util.tree_leaves
fp_t, m_t, v_t = hs.init_zero3_state(params, mesh)
fp_l, m_l, v_l = naive_once(tl(fp_t), tl(m_t), tl(v_t), 1.0)  # compile
def naive_step():
    sno[0] += 1.0
    naive_once(fp_l, m_l, v_l, sno[0])
naive_s = best_of(naive_step)

out["fused_step_ms"] = round(fused_s * 1e3, 3)
out["naive_step_ms"] = round(naive_s * 1e3, 3)
out["zero3_step_ratio"] = round(naive_s / max(fused_s, 1e-9), 3)

# --- elastic resume drill: 4 -> 2 -> 4 vs uninterrupted, bit-exact
def run(dp, n, state=None, t0=0, grain=4):
    meshd = Mesh(np.array(jax.devices()[:dp]), ("dp",))
    cfgd = dataclasses.replace(cfg, dp=dp)
    if state is None:
        state = hs.init_zero3_state(params, meshd)
    st = hs.make_zero3_train_step(meshd, cfgd, grain=grain)
    fp0, m0, v0 = state
    for t in range(t0, t0 + n):
        _, fp0, m0, v0 = st(fp0, m0, v0, jnp.float32(t + 1), ids)
    return fp0, m0, v0

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    s4 = run(4, 2)
    hs.save_zero3_state(mgr, 2, *s4, 2.0, grain=4, wait=True)
    fp2, m2, v2, sn, gr = hs.load_zero3_state(mgr, mesh2, cfg)
    s2 = run(2, 1, (fp2, m2, v2), int(sn))
    hs.save_zero3_state(mgr, 3, *s2, 3.0, grain=4, wait=True)
    fp4, m4, v4, sn2, _ = hs.load_zero3_state(mgr, mesh, cfg)
    sR = run(4, 1, (fp4, m4, v4), int(sn2))
    sU = run(4, 4)
    ok = True
    for a, b in zip(sR, sU):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            ok &= bool(np.array_equal(np.asarray(x), np.asarray(y)))
out["elastic_resume_ok"] = bool(ok)
print("RESULT " + json.dumps(out))
"""
    res = _run_result_subprocess("zero3_elastic", code)
    if not res["elastic_resume_ok"]:
        raise RuntimeError("elastic 4->2->4 resume lost bit-exactness")
    return {"zero3_step_ratio": res["zero3_step_ratio"],
            "elastic_resume_ok": bool(res["elastic_resume_ok"]),
            "fused_step_ms": res["fused_step_ms"],
            "naive_step_ms": res["naive_step_ms"],
            "gather_buckets": res["buckets"]}


@harness.register_rung("elastic_mttr", est_cold_s=60, smoke=True)
def bench_elastic_mttr(ctx):
    """Unattended-elastic MTTR rung (ISSUE 20): SIGKILL one node of a
    3-node simulated fleet mid-run and measure seconds from the kill to
    the first post-restart training step — with ZERO operator actions
    (the hard gate: the fleet must recover by itself or the rung
    fails).

    One orchestrating subprocess starts three real launcher processes
    (`python -m paddle_tpu.distributed.launch --nnodes 2:3`, each in
    its own process group) whose workers publish step heartbeats
    through `ProgressReporter`; once all three generation-0 heartbeats
    are moving it SIGKILLs node 2's entire group (launcher AND worker
    — a machine death, not a worker crash) and polls the store:
    `t_detect_s` is kill → surviving launchers publish the bumped
    `restart_generation` (the heartbeat-lease expiry), `elastic_mttr_s`
    is kill → first step heartbeat of the new generation (regression
    key; it growing means detection or re-rendezvous got slower).  The
    drill is pure control-plane (store + launcher + subprocess
    workers, no device mesh) but runs CPU-only like the other
    simulated-fleet rungs."""
    if ctx.on_tpu:
        raise harness.BackendUnavailable(
            "elastic_mttr drills launcher process fleets on the host; "
            "a TPU round measures devices, not process supervision")
    code = r"""
import json, os, signal, socket, subprocess, sys, tempfile, time

repo = os.getcwd()
work = tempfile.mkdtemp(prefix="mttr_")
worker_py = os.path.join(work, "worker.py")
with open(worker_py, "w") as f:
    f.write(
        "import time\n"
        "from paddle_tpu.distributed.fleet.elastic import "
        "ProgressReporter\n"
        "rep = ProgressReporter()\n"
        "for step in range(100000):\n"
        "    rep.publish(step)\n"
        "    time.sleep(0.05)\n")

s = socket.socket(); s.bind(("127.0.0.1", 0))
port = s.getsockname()[1]; s.close()
master = f"127.0.0.1:{port}"

env = dict(os.environ)
env.update({"FLAGS_elastic_lease_interval_s": "0.2",
            "FLAGS_elastic_lease_timeout_s": "1.5",
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", "")})

def launcher(rank):
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--master", master, "--rank", str(rank), "--nnodes", "2:3",
           "--max_restart", "5", "--elastic_timeout", "3",
           "--log_dir", os.path.join(work, f"log{rank}"),
           "--job_id", "mttr", worker_py]
    if rank != 0:
        cmd[6] = "-1"   # auto-rank joiners; only node 0 is explicit
    log = open(os.path.join(work, f"launcher{rank}.log"), "wb")
    return subprocess.Popen(cmd, cwd=repo, env=env,
                            start_new_session=True,
                            stdout=log, stderr=subprocess.STDOUT)

nodes = [launcher(0), launcher(1), launcher(2)]
try:
    from paddle_tpu.distributed.store import TCPStore
    store = TCPStore("127.0.0.1", port, timeout=30.0)

    def moving(gen, ranks, deadline):
        first = {}
        while time.monotonic() < deadline:
            live = 0
            for r in ranks:
                k = f"progress/{gen}/{r}"
                try:
                    if not store.check(k):
                        continue
                    v = store.get(k, timeout=5.0)
                except (OSError, TimeoutError):
                    continue
                if r not in first:
                    first[r] = v
                elif v != first[r]:
                    live += 1
            if live >= len(ranks):
                return True
            time.sleep(0.05)
        return False

    def current_gen():
        try:
            if store.check("restart_generation"):
                return int(store.get("restart_generation", timeout=5.0))
        except (OSError, TimeoutError):
            pass
        return 0

    def logs_tail():
        out = []
        for rank in range(3):
            fn = os.path.join(work, f"launcher{rank}.log")
            if not os.path.isfile(fn):
                continue
            with open(fn, "rb") as f:
                out.append(f"--- launcher{rank}: " + f.read()[-1500:]
                           .decode(errors="replace"))
        return "\n".join(out)

    # wait for a full 3-node world stepping at the CURRENT generation
    # (under load a node can miss generation 0's join window; the
    # late-join scale-up restart admits it a generation later)
    ok3 = False
    base_gen = 0
    deadline = time.monotonic() + 120
    while not ok3 and time.monotonic() < deadline:
        base_gen = max(base_gen, current_gen())
        ok3 = moving(base_gen, [0, 1, 2], time.monotonic() + 6)
    assert ok3, \
        "fleet never reached a 3-node stepping world\n" + logs_tail()
    base_gen = max(base_gen, current_gen())
    victim = nodes[2]
    t_kill = time.monotonic()
    os.killpg(os.getpgid(victim.pid), signal.SIGKILL)

    # detection: a survivor bumps restart_generation past the pre-kill
    # value on lease expiry (a worker-crash bump before the kill must
    # not count as detecting the node death)
    gen, t_detect = None, None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        g = current_gen()
        if g > base_gen:
            gen = g
            t_detect = time.monotonic() - t_kill
            break
        time.sleep(0.02)
    assert gen is not None, \
        "no survivor ever bumped restart_generation\n" + logs_tail()

    # recovery: first post-restart step heartbeat.  Re-read the
    # generation each pass — rendezvous may bump past the first
    # detected value before settling, and progress keys only ever
    # appear under the generation that actually settled.
    t_rec = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        gen = max(gen, current_gen())
        hit = False
        for r in range(2):
            try:
                if store.check(f"progress/{gen}/{r}"):
                    hit = True
                    break
            except (OSError, TimeoutError):
                pass
        if hit:
            t_rec = time.monotonic() - t_kill
            break
        time.sleep(0.02)
    assert t_rec is not None, \
        "fleet never resumed stepping after kill\n" + logs_tail()
    settled = int(store.get(f"world/{gen}", timeout=10.0))
    print("RESULT " + json.dumps({
        "elastic_mttr_s": round(t_rec, 3),
        "t_detect_s": round(t_detect, 3),
        "generation": gen, "settled_nodes": settled,
        "recovered": True, "operator_actions": 0}))
finally:
    for p in nodes:
        try:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        except (OSError, ProcessLookupError):
            pass
"""
    res = _run_result_subprocess("elastic_mttr", code, timeout=300)
    if not res.get("recovered") or res.get("operator_actions", 1) != 0:
        raise RuntimeError(
            "elastic MTTR drill needed operator intervention: "
            f"{res}")
    if res["settled_nodes"] != 2:
        raise RuntimeError(
            f"fleet settled at {res['settled_nodes']} nodes, wanted 2")
    return {"elastic_mttr_s": res["elastic_mttr_s"],
            "t_detect_s": res["t_detect_s"],
            "generation": res["generation"],
            "settled_nodes": res["settled_nodes"],
            "recovered": bool(res["recovered"]),
            "operator_actions": 0}


def _sampled_decode_sweep(model, cfg, on_tpu):
    """Sampled-decode throughput at steps_per_tick in {1, 4} with the
    double-buffered tick overlap off and on (the round-6 serving fast
    path): a mixed greedy+sampled batch runs to completion per cell.
    On-device sampling keeps sampled requests on the full k-step tick,
    so the k=4 cells measure exactly the RTT amortization the old
    host-side sampler forfeited."""
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.inference.serving import Request, ServingEngine

    rng = np.random.RandomState(7)
    plen = 64 if on_tpu else 12
    budget = 64 if on_tpu else 11
    out = {}

    def mk(seed=None):
        ids = rng.randint(1, cfg.vocab_size, (plen,))
        if seed is None:
            return Request(ids, max_new_tokens=budget)
        return Request(ids, max_new_tokens=budget, do_sample=True,
                       temperature=0.9, top_k=40, seed=seed)

    for k in (1, 4):
        for overlap in (False, True):
            with flag_guard(serving_overlap=overlap):
                eng = ServingEngine(model, max_batch=4,
                                    max_context=1024 if on_tpu else 128,
                                    steps_per_tick=k)
                # warm run compiles the prefill bucket and BOTH decode
                # variants (budget spans full ticks + a k=1 tail)
                eng.add_request(mk(seed=1))
                eng.add_request(mk())
                eng.run()
                eng.finished.clear()
                for r in (mk(seed=2), mk(seed=3), mk()):
                    eng.add_request(r)
                t0 = time.perf_counter()
                toks0 = eng.tokens_out
                eng.run()
                dt = time.perf_counter() - t0
                cell = f"k{k}_{'overlap' if overlap else 'sync'}"
                out[cell + "_tokens_per_sec"] = round(
                    (eng.tokens_out - toks0) / dt, 1)
    return out


@harness.register_rung("serving_continuous_batching", est_cold_s=240,
                       smoke=True)
def bench_serving(ctx):
    """Continuous-batching rung: staggered requests (mixed prompt
    lengths and budgets) stream through ONE compiled decode step over the
    paged pool (`inference/serving.py`); reports decode tokens/s at mixed
    occupancy plus the per-step scheduler overhead."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, max_batch=8,
                        max_context=1024 if on_tpu else 128,
                        steps_per_tick=8 if on_tpu else 1)
    rng = np.random.RandomState(0)
    mk = lambda L, n: Request(  # noqa: E731
        rng.randint(1, cfg.vocab_size, (L,)), max_new_tokens=n)
    if ctx.smoke and not on_tpu:
        # schema-validation scale: two short requests, one decode program
        for r in (mk(16, 6), mk(8, 4)):
            eng.add_request(r)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        return {"requests": 2, "decode_steps": eng.steps,
                "tokens_out": eng.tokens_out,
                "tokens_per_sec": round(eng.tokens_out / dt, 1),
                "ms_per_step": round(dt / max(eng.steps, 1) * 1e3, 3),
                "sampled_decode": _sampled_decode_sweep(model, cfg,
                                                        on_tpu),
                "smoke": True}
    # warm every program the timed run will hit: both prefill buckets
    # and both decode variants (the full k-step tick and the k=1 tail)
    # budgets of 34 = 1 prefill token + 4 full ticks + a k=1 tail, so
    # BOTH decode programs compile before the timed region
    eng.add_request(mk(96 if on_tpu else 24, 34))
    eng.add_request(mk(33 if on_tpu else 8, 34))
    eng.run()
    eng.finished.clear()

    reqs = [mk(128 if on_tpu else 24, 96 if on_tpu else 12),
            mk(64 if on_tpu else 12, 64 if on_tpu else 8)]
    for r in reqs:
        eng.add_request(r)
    t0 = time.perf_counter()
    steps0 = eng.steps
    toks0 = eng.tokens_out
    # stagger four more admissions across the first decode steps
    joins = [(3, mk(96 if on_tpu else 16, 80 if on_tpu else 10)),
             (6, mk(32 if on_tpu else 8, 48 if on_tpu else 6)),
             (9, mk(128 if on_tpu else 24, 64 if on_tpu else 8)),
             (12, mk(64 if on_tpu else 12, 72 if on_tpu else 9))]
    n_requests = 2 + len(joins)
    i = 0
    while eng.step() or eng._active_slots() or eng.waiting:
        i += 1
        while joins and joins[0][0] <= i:
            eng.add_request(joins.pop(0)[1])
    dt = time.perf_counter() - t0
    toks = eng.tokens_out - toks0
    steps = eng.steps - steps0
    return {"requests": n_requests, "decode_steps": steps,
            "tokens_out": toks, "tokens_per_sec": round(toks / dt, 1),
            "ms_per_step": round(dt / max(steps, 1) * 1e3, 3),
            "sampled_decode": _sampled_decode_sweep(model, cfg, on_tpu)}


@harness.register_rung("request_trace", est_cold_s=120, smoke=True)
def bench_request_trace(ctx):
    """ISSUE 6 acceptance rung: per-request lifecycle tracing on the
    serving engine.  Records the TTFT/TPOT percentiles the trace
    sketches produce AND the price of producing them — the same request
    workload driven with the metrics gate on vs off, as ticks/sec
    (regression key `trace_overhead_pct`; the acceptance bound is <=2%
    on-gate, exactly 0 work off-gate)."""
    import paddle_tpu as paddle
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny
    from paddle_tpu.observability import metrics as obs_metrics

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, max_batch=4,
                        max_context=1024 if on_tpu else 128,
                        steps_per_tick=4 if on_tpu else 2)
    rng = np.random.RandomState(3)
    plen = 64 if on_tpu else 12
    budget = 48 if on_tpu else 9

    def run_batch(n=4):
        for _ in range(n):
            eng.add_request(Request(rng.randint(1, cfg.vocab_size, (plen,)),
                                    max_new_tokens=budget))
        t0 = time.perf_counter()
        ticks0 = eng.ticks
        eng.run()
        eng.finished.clear()
        return (eng.ticks - ticks0) / (time.perf_counter() - t0)

    run_batch()          # warm the prefill bucket + both tick variants

    def rate():
        return max(run_batch() for _ in range(2 if ctx.smoke else 5))

    with flag_guard(enable_metrics=True):
        # interleave gated/ungated windows so clock drift hits both sides
        obs_metrics.reset()
        on1 = rate()
        paddle.set_flags({"enable_metrics": False})
        off1 = rate()
        paddle.set_flags({"enable_metrics": True})
        on2 = rate()
        paddle.set_flags({"enable_metrics": False})
        off2 = rate()
        paddle.set_flags({"enable_metrics": True})
        ttft = obs_metrics.get("serving.ttft_seconds")
        tpot = obs_metrics.get("serving.tpot_seconds")
        e2e = obs_metrics.get("serving.e2e_seconds")
        n_traced = int(e2e.count()) if e2e else 0
    on, off = max(on1, on2), max(off1, off2)
    q = lambda sk, p: round((sk.quantile(p) or 0.0) * 1e3, 3)  # noqa: E731
    return {"requests_traced": n_traced,
            "ttft_p50_ms": q(ttft, 0.5), "ttft_p99_ms": q(ttft, 0.99),
            "tpot_p50_ms": q(tpot, 0.5), "tpot_p99_ms": q(tpot, 0.99),
            "e2e_p50_ms": q(e2e, 0.5),
            "ticks_per_sec_on": round(on, 1),
            "ticks_per_sec_off": round(off, 1),
            "trace_overhead_pct": round(max(0.0, 1 - on / off) * 100, 2)}


@harness.register_rung("cold_start", est_cold_s=150, smoke=True)
def bench_cold_start(ctx):
    """ISSUE 7 acceptance rung: restart-to-first-token evidence.

    (a) Two subprocesses sharing one fresh cache dir each time a small
    jitted train step from import to first-program-ready: the first is
    the COLD restart (XLA compiles, cache fills), the second the WARM
    one (every compile is a cache hit).  `cold_start_warm_speedup` is
    the regression key — it collapsing toward 1.0 means the persistent
    cache stopped working.  Subprocesses pin JAX_PLATFORMS=cpu: a
    second process cannot share the parent's TPU, and the cache
    machinery under test is platform-independent.

    (b) In-process: a ServingEngine over a 3-bucket pad ladder with
    FLAGS_serving_warmup — records warmup_s/programs and asserts the
    compile tracker saw ZERO events once traffic ran."""
    import json as _json
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    cache_dir = tempfile.mkdtemp(prefix="bench_cold_start_")
    code = r"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit import to_static

paddle.seed(0)
net = nn.Sequential(nn.Linear(64, 128), nn.GELU(), nn.Linear(128, 64))
opt = optimizer.Adam(learning_rate=1e-3, parameters=net.parameters())
lossf = nn.MSELoss()

def train_step(x, y):
    loss = lossf(net(x), y)
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss

step = to_static(train_step)
rng = np.random.RandomState(0)
x = paddle.to_tensor(rng.rand(8, 64).astype(np.float32))
y = paddle.to_tensor(rng.rand(8, 64).astype(np.float32))
t0 = time.perf_counter()
loss = step(x, y)
np.asarray(loss._value)
ready_s = time.perf_counter() - t0
from paddle_tpu.core import compile_cache
rep = compile_cache.cache_report()
print(json.dumps({"first_program_ready_s": round(ready_s, 4),
                  "cache_hits": rep["hits"],
                  "cache_misses": rep["misses"]}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_compilation_cache_dir=cache_dir)

    def restart():
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=240,
                             cwd=repo)
        if out.returncode != 0:
            raise RuntimeError(f"cold_start subprocess rc="
                               f"{out.returncode}: {out.stderr[-300:]}")
        return _json.loads(out.stdout.strip().splitlines()[-1])

    try:
        cold = restart()
        warm = restart()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    speedup = cold["first_program_ready_s"] / max(
        warm["first_program_ready_s"], 1e-9)

    import paddle_tpu as paddle
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny
    from paddle_tpu.observability import compile_tracker as obs_compile

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    ladder = "64,128,256" if on_tpu else "16,32,64"
    with flag_guard(serving_warmup=True, serving_pad_buckets=ladder):
        eng = ServingEngine(model, max_batch=4,
                            max_context=1024 if on_tpu else 128,
                            steps_per_tick=4 if on_tpu else 2)
        rng = np.random.RandomState(9)
        lens = (40, 100, 200) if on_tpu else (12, 24, 48)
        for i, L in enumerate(lens):
            kw = {} if i % 2 == 0 else dict(do_sample=True,
                                            temperature=0.9, top_k=40,
                                            seed=i)
            eng.add_request(Request(rng.randint(1, cfg.vocab_size, (L,)),
                                    max_new_tokens=9, **kw))
        before = obs_compile.total_compiles()   # run() warms first
        eng.run()
        w = eng.stats()["warmup"]
        post = obs_compile.total_compiles() - before - w["programs"]
    return {"cold_first_program_s": cold["first_program_ready_s"],
            "warm_first_program_s": warm["first_program_ready_s"],
            "cold_start_warm_speedup": round(speedup, 2),
            "cold_cache_misses": cold["cache_misses"],
            "warm_cache_hits": warm["cache_hits"],
            "serving_warmup_s": w["warmup_s"],
            "serving_warmup_programs": w["programs"],
            "post_warmup_compiles": int(post)}


def _run_result_subprocess(name: str, code: str, timeout: int = 900):
    """Shared scaffold of the RESULT-line subprocess rungs (serving_tp,
    spec_decode): run ``code`` in a fresh interpreter with the parent's
    JAX_PLATFORMS pin dropped (the child forces its own CPU mesh),
    fail loudly with the stderr tail on a nonzero rc or a missing
    RESULT line, and return the parsed payload."""
    import json as _json
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True,
                          timeout=timeout, cwd=repo)
    if proc.returncode != 0:
        raise RuntimeError(f"{name} subprocess rc={proc.returncode}:"
                           f" {proc.stderr[-400:]}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    if not lines:
        raise RuntimeError(f"{name} subprocess emitted no RESULT line:"
                           f" {proc.stderr[-400:]}")
    return _json.loads(lines[-1][len("RESULT "):])


@harness.register_rung("serving_tp", est_cold_s=120, smoke=True)
def bench_serving_tp(ctx):
    """ISSUE 9 rung: scale-out serving evidence.

    One subprocess on a simulated 4-device CPU mesh (XLA_FLAGS forces
    the device count — the parent process latched its backend long ago)
    sweeps TP degree {1, 2} x prefix-cache {off, on} over a
    shared-system-prompt workload: per degree it records decode
    tokens/sec/CHIP and TTFT p50, asserts the degree-2 streams are
    bit-identical to degree 1, and measures `prefix_hit_speedup` —
    median full-prefill seconds over median suffix-prefill seconds for
    the same requests (regression key; it collapsing toward 1.0 means
    prefix reuse stopped skipping work)."""
    code = r"""
import json, os, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FLAGS_enable_metrics"] = "1"
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

paddle.seed(0)
model = GPTForCausalLM(gpt3_tiny())
model.eval()
rng = np.random.RandomState(0)
sysp = list(rng.randint(1, 1000, (48,)))
suffixes = [[int(t)] for t in rng.randint(1, 1000, (6,))]
out = {}

def drive(eng, n=4, budget=8):
    reqs = []
    t0 = time.perf_counter()
    for i in range(n):
        reqs.append(eng.add_request(
            Request(sysp + suffixes[i % len(suffixes)],
                    max_new_tokens=budget)))
        eng.run()
    dt = time.perf_counter() - t0
    return reqs, dt

for tp in (1, 2):
    eng = ServingEngine(model, max_batch=4, max_context=128,
                        block_size=16, steps_per_tick=2, tp_degree=tp,
                        prefix_cache=True)
    warm, _ = drive(eng, n=2, budget=4)        # compile + register
    toks0 = eng.tokens_out
    reqs, dt = drive(eng)
    toks = eng.tokens_out - toks0
    ttfts = sorted(r.trace["ttft_s"] for r in reqs)
    out[f"tp{tp}"] = {
        "tokens_per_sec_chip": round(toks / dt / tp, 1),
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 3),
        "streams": [list(r.output_ids) for r in reqs]}

# prefix-hit speedup at degree 1: same requests, cache off vs on (both
# pre-warmed so the medians compare compute, not compilation)
on_eng = ServingEngine(model, max_batch=4, max_context=128,
                       block_size=16, tp_degree=1, prefix_cache=True)
off_eng = ServingEngine(model, max_batch=4, max_context=128,
                        block_size=16, tp_degree=1, prefix_cache=False)
drive(on_eng, n=2, budget=2)
drive(off_eng, n=2, budget=2)
hits, misses = [], []
for i in range(5):
    h, _ = drive(on_eng, n=1, budget=2)
    m, _ = drive(off_eng, n=1, budget=2)
    hits.append(h[0].trace["prefill_s"])
    misses.append(m[0].trace["prefill_s"])
out["prefix_hit_speedup"] = round(
    float(np.median(misses)) / max(float(np.median(hits)), 1e-9), 2)
out["prefix_stats"] = on_eng.stats()["prefix_cache"]
out["parity_tp2_vs_tp1"] = out["tp2"].pop("streams") == \
    out["tp1"].pop("streams")
print("RESULT " + json.dumps(out))
"""
    res = _run_result_subprocess("serving_tp", code)
    return {"tokens_per_sec_chip_tp1": res["tp1"]["tokens_per_sec_chip"],
            "tokens_per_sec_chip_tp2": res["tp2"]["tokens_per_sec_chip"],
            "ttft_p50_ms_tp1": res["tp1"]["ttft_p50_ms"],
            "ttft_p50_ms_tp2": res["tp2"]["ttft_p50_ms"],
            "parity_tp2_vs_tp1": bool(res["parity_tp2_vs_tp1"]),
            "prefix_hit_speedup": res["prefix_hit_speedup"],
            "prefix_hits": res["prefix_stats"]["hits"],
            "prefix_blocks_shared": res["prefix_stats"]["blocks_shared"]}


@harness.register_rung("serving_restart", est_cold_s=90, smoke=True)
def bench_serving_restart(ctx):
    """Crash-only serving rung (ISSUE 15): restart-to-first-token.

    One warm engine serves a shared system prompt, drains and exports
    its prefix cache (atomic manifest version under a temp root).  Then
    two fresh engines answer the SAME prompt, both AOT-warmed first so
    TTFT compares prefill COMPUTE, not compilation (the compile half of
    restart is the PR 7 persistent-cache story): a COLD engine (no
    import — full prefill) vs an IMPORT-RESTORED engine (suffix-only
    prefill over the imported KV blocks).  `restart_ttft_speedup` =
    median cold TTFT / median restored TTFT; it collapsing toward 1.0
    means warm restart stopped skipping prefill work.  The rung also
    asserts the restored stream bit-matches the donor's prefix-hit
    stream — a restart that changes tokens is a regression no speedup
    excuses."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import flags as _pflags
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    rng = np.random.RandomState(0)
    # a LONG shared system prompt (the restart-to-first-token
    # scenario): cold prefill pads to the 256 bucket while the
    # restored engine prefills only the one-token suffix — on this
    # tiny CPU model a short prompt would be dispatch-bound and hide
    # the skipped work
    sysp = [int(t) for t in rng.randint(1, 1000, (224,))]
    reps = 5 if ctx.smoke else 9
    root = tempfile.mkdtemp(prefix="bench_restart_")

    def build(import_dir):
        with _pflags.flag_guard(serving_prefix_export_dir=import_dir):
            eng = ServingEngine(model, max_batch=2, max_context=256,
                                block_size=16, prefix_cache=True)
        eng.warmup()
        return eng

    def ttft(eng, suffix, budget=4):
        req = eng.add_request(Request(sysp + suffix,
                                      max_new_tokens=budget))
        eng.run()
        return req, req.trace["ttft_s"]

    try:
        # the engine snapshots its export dir at construction, so the
        # donor is built with it (the dir is empty: nothing to import)
        donor = build(root)
        ttft(donor, [7])                       # registers the prefix
        hit_req, _ = ttft(donor, [8])          # the warm prefix-hit path
        drain = donor.drain()
        export = drain["export"]

        cold_ttfts, restored_ttfts = [], []
        streams_match = True
        for i in range(reps):
            cold = build("")
            _, t_cold = ttft(cold, [8])
            restored = build(root)
            req, t_rest = ttft(restored, [8])
            cold_ttfts.append(t_cold)
            restored_ttfts.append(t_rest)
            streams_match &= req.output_ids == hit_req.output_ids
        imported = restored.stats()["prefix_cache"]["import"]
        speedup = float(np.median(cold_ttfts)) \
            / max(float(np.median(restored_ttfts)), 1e-9)
        return {
            "restart_ttft_speedup": round(speedup, 2),
            "cold_ttft_ms_p50": round(
                float(np.median(cold_ttfts)) * 1e3, 3),
            "restored_ttft_ms_p50": round(
                float(np.median(restored_ttfts)) * 1e3, 3),
            "restored_stream_bitmatch": bool(streams_match),
            "export_blocks": export["blocks"],
            "export_bytes": export["bytes"],
            "export_s": export["export_s"],
            "imported_blocks": imported["blocks"],
            "import_skipped_corrupt": imported["skipped_corrupt"],
            "reps": reps}
    finally:
        shutil.rmtree(root, ignore_errors=True)


@harness.register_rung("fleet", est_cold_s=240, smoke=True)
def bench_fleet(ctx):
    """Replica-fleet rung (ISSUE 16): goodput through a rolling restart.

    Three in-process tiny-model replicas behind the prefix-affinity
    router serve continuous shared-prefix traffic from concurrent
    clients.  Goodput (completed streams per second) is measured over a
    steady window, then across a full zero-downtime rolling restart of
    every replica (cordon -> quiesce -> drain/export -> fresh engine
    warm-imports -> uncordon) under the SAME traffic.
    ``goodput_during_restart_ratio`` = restart-window goodput / steady
    goodput — it collapsing toward 0 means restarts stopped being
    zero-downtime; ``requests_dropped`` must stay 0 (the chaos drill in
    tests/test_fleet.py asserts the same with fault injection on the
    proxy leg)."""
    import shutil
    import tempfile
    import threading
    from http.client import HTTPConnection

    import paddle_tpu as paddle
    from paddle_tpu.inference.fleet import Fleet
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

    def factory(export_dir):
        # one model instance PER replica: concurrent engines must not
        # share a model object (inference/fleet/replica.py) — same
        # seed, identical weights, own copy
        paddle.seed(0)
        m = GPTForCausalLM(gpt3_tiny())
        m.eval()
        return ServingEngine(m, max_batch=2, max_context=64,
                             block_size=16, num_blocks=32,
                             prefix_cache=True,
                             prefix_export_dir=export_dir)

    rng = np.random.RandomState(3)
    prefixes = [list(rng.randint(1, 1000, (16,))) for _ in range(3)]
    steady_s = 2.0 if ctx.smoke else 4.0

    def post(port, ids):
        conn = HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request(
                "POST", "/generate",
                body=json.dumps({"prompt_ids": [int(t) for t in ids],
                                 "max_new_tokens": 2}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            return resp.status == 200 and b"event: done" in body
        finally:
            conn.close()

    root = tempfile.mkdtemp(prefix="bench_fleet_")
    fleet = Fleet.build(factory, 3, root, poll_interval_s=0.1,
                        affinity_tokens=16)
    stop = threading.Event()
    done_ts, dropped = [], []

    def client(k):
        i = 0
        while not stop.is_set():
            ids = prefixes[(k + i) % len(prefixes)] + [i % 997 + 1]
            try:
                ok = post(fleet.router.port, ids)
            except Exception:   # noqa: BLE001 - the gate counts all
                ok = False
            (done_ts if ok else dropped).append(time.perf_counter())
            i += 1

    try:
        # warm wave: register each prefix on its home replica so the
        # steady window measures warmed-cache goodput
        for p in prefixes:
            post(fleet.router.port, p + [1])
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        time.sleep(steady_s)      # warm under load (compiles settle),
        t0 = time.perf_counter()  # THEN open the steady window
        time.sleep(steady_s)
        t1 = time.perf_counter()
        report = fleet.rolling_restart()
        # the drill window is the restart plus enough tail for at
        # least a few client rounds to land: a sub-second restart
        # would otherwise measure an empty window (ratio 0 — a false
        # alarm, not a serving gap); a stalled restart still
        # depresses the whole window
        while time.perf_counter() - t1 < max(1.0, steady_s / 2):
            time.sleep(0.05)
        t2 = time.perf_counter()
        stop.set()
        for t in threads:
            t.join(timeout=120)
        steady = sum(t0 <= t <= t1 for t in done_ts) / (t1 - t0)
        during = sum(t1 < t <= t2 for t in done_ts) / (t2 - t1)
        st = fleet.router.stats()
        return {
            "goodput_during_restart_ratio": round(
                during / max(steady, 1e-9), 3),
            "steady_goodput_rps": round(steady, 3),
            "restart_goodput_rps": round(during, 3),
            "rolling_restart_s": report["rolling_restart_s"],
            "requests_completed": len(done_ts),
            "requests_dropped": len(dropped),
            "affinity_hit_rate": st["affinity_hit_rate"],
            "failovers": st["failovers"],
            "replicas_restarted": sum(
                1 for r in fleet.replicas if r.restarts)}
    finally:
        fleet.close()
        shutil.rmtree(root, ignore_errors=True)


@harness.register_rung("fleet_telescope", est_cold_s=240, smoke=True)
def bench_fleet_telescope(ctx):
    """Fleet-telescope rung (ISSUE 17): what the cross-process tracing
    and metrics federation COST, and proof they see the whole fleet.

    Three in-process tiny-model replicas behind the router (the
    bench_fleet topology, no restart drill) serve shared-prefix
    traffic.  ``fleet_trace_overhead_pct`` compares completed-stream
    throughput with trace propagation ON (router mints ids, records
    plan/proxy spans, forwards the header; engines tag their records)
    vs OFF, measured over adjacent on/off PAIRS with the quietest
    pair's delta winning (co-tenant noise is strictly additive — the
    same min-estimator the xray rung uses).  The telescope facts ride
    along: the federated ``/fleet/metrics`` scrape, the fleet latency
    aggregate, and the multi-process ``fleet_trace`` merge over the
    run's real flight dumps (shared trace ids across processes,
    clock-synced replica rows)."""
    import shutil
    import tempfile
    from http.client import HTTPConnection

    import paddle_tpu as paddle
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.inference.fleet import Fleet
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    from paddle_tpu.observability import tracing as obs_tracing

    def factory(export_dir):
        # one model instance PER replica (inference/fleet/replica.py)
        paddle.seed(0)
        m = GPTForCausalLM(gpt3_tiny())
        m.eval()
        return ServingEngine(m, max_batch=2, max_context=64,
                             block_size=16, num_blocks=32,
                             prefix_cache=True,
                             prefix_export_dir=export_dir)

    rng = np.random.RandomState(7)
    prefixes = [list(rng.randint(1, 1000, (16,))) for _ in range(3)]

    def post(port, ids):
        conn = HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request(
                "POST", "/generate",
                body=json.dumps({"prompt_ids": [int(t) for t in ids],
                                 "max_new_tokens": 2}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            return resp.status == 200 and b"event: done" in body
        finally:
            conn.close()

    root = tempfile.mkdtemp(prefix="bench_fleet_telescope_")
    fleet = Fleet.build(factory, 3, root, poll_interval_s=0.1,
                        affinity_tokens=16, metrics_interval_s=0.2)
    n_reqs = 6 if ctx.smoke else 12
    try:
        for p in prefixes:          # warm wave: compiles + prefix homes
            post(fleet.router.port, p + [1])

        def rate():
            done = 0
            t0 = time.perf_counter()
            for i in range(n_reqs):
                ids = prefixes[i % len(prefixes)] + [i % 997 + 1]
                done += bool(post(fleet.router.port, ids))
            return done / (time.perf_counter() - t0)

        pairs = []
        for _ in range(2 if ctx.smoke else 3):
            with flag_guard(fleet_trace=True):
                on = rate()
            with flag_guard(fleet_trace=False):
                off = rate()
            pairs.append((max(0.0, 1 - on / off) * 100, on, off))
        pct, on, off = min(pairs)

        # federated scrape + fleet latency aggregate
        fleet.router.poll_metrics_all()
        conn = HTTPConnection("127.0.0.1", fleet.router.port, timeout=10)
        conn.request("GET", "/fleet/metrics")
        scrape = conn.getresponse().read().decode()
        conn.close()
        fleet_doc = fleet.router.describe()
        lat = fleet_doc.get("fleet_latency", {})

        # multi-process timeline merge over the run's REAL flight dumps
        dump_paths = fleet.dump_flight(os.path.join(root, "trace"))
        docs = [json.load(open(p)) for p in dump_paths]
        trace = obs_tracing.fleet_trace(docs)
        other = trace["otherData"]
        # a trace id minted at the router must appear in >1 process's
        # records — the single-timeline acceptance fact
        per_proc_ids = [set(obs_tracing._collect_trace_ids(d))
                        for d in docs]
        shared = [t for t in other["trace_ids"]
                  if sum(t in s for s in per_proc_ids) >= 2]
        return {
            "fleet_trace_overhead_pct": round(pct, 2),
            "streams_per_sec_on": round(on, 3),
            "streams_per_sec_off": round(off, 3),
            "overhead_pct_windows": [round(p, 2) for p, _, _ in pairs],
            "fleet_metric_lines": sum(
                1 for ln in scrape.splitlines()
                if ln.startswith("fleet_")),
            "fleet_ttft_p99_ms": round(
                lat.get("ttft", {}).get("p99_s", 0.0) * 1e3, 3),
            "trace_processes": len(other["processes"]),
            "trace_ids_merged": len(other["trace_ids"]),
            "trace_ids_cross_process": len(shared),
            "clock_synced_replicas": sum(
                1 for p in other["processes"]
                if p["clock_offset_s"] != 0.0),
            "trace_events": len(trace["traceEvents"])}
    finally:
        fleet.close()
        shutil.rmtree(root, ignore_errors=True)


@harness.register_rung("spec_decode", est_cold_s=240, smoke=True)
def bench_spec_decode(ctx):
    """ISSUE 10 rung, re-pointed by ISSUE 13 at drafting that PAYS.

    One CPU subprocess measures three things.  (a) The headline: a
    model-free NGRAM arm on a repetitive-suffix workload (the traffic
    shape prompt-lookup drafting exists for) vs the plain engine on the
    SAME workload — `spec_decode_speedup` now keys on this arm, with
    real accepted-token gains, not the old same-weights upper-bound
    harness (that machinery sweep survives as the model-draft cells).
    (b) An accept-rate-vs-k sweep (ngram, fixed k in {2,4,8}) — the
    curve the adaptive-k controller walks.  (c) Quantized serving:
    int8 AND fp8 weight ratios (`quant_weight_ratio`,
    `quant_fp8_weight_ratio`) plus the fp8 max-logit deviation checked
    against its documented 0.25 budget.  Losslessness stays a GATE:
    ngram-arm and model-draft greedy streams must equal their plain
    twins or the rung fails."""
    code = r"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FLAGS_enable_metrics"] = "1"
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference import quant as squant
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

paddle.seed(0)
model = GPTForCausalLM(gpt3_tiny())
model.eval()
paddle.seed(0)
draft = GPTForCausalLM(gpt3_tiny())
draft.eval()
rng = np.random.RandomState(0)
prompts = [rng.randint(1, 1000, (L,)) for L in (12, 24, 40, 18)]
# the ngram arm's workload: prompts whose suffix structure recurs (the
# serving shapes prompt-lookup exists for: quoting, templated output,
# self-repetitive greedy loops) — four distinct periodic prompts
rep_prompts = [np.array(list(rng.randint(1, 1000, (p,))) * (48 // p))
               for p in (3, 4, 6, 8)]
out = {}

def drive(eng, ps, budget=24):
    reqs = [eng.add_request(Request(p, max_new_tokens=budget))
            for p in ps]
    eng.run()
    return reqs

def measure(eng, ps, budget=24):
    # warm pass clears spec_k+1 so every program the steady state uses
    # is compiled before timing; second pass settles caches
    drive(eng, ps, budget=8)
    drive(eng, ps, budget=budget)
    toks0 = eng.tokens_out
    t0 = time.perf_counter()
    reqs = drive(eng, ps, budget=budget)
    dt = time.perf_counter() - t0
    return reqs, round((eng.tokens_out - toks0) / dt, 1)

# --- machinery sweep (model draft = same-weights upper bound) + quant
for spec in (False, True):
    for quant in ("", "int8"):
        eng = ServingEngine(
            model, max_batch=4, max_context=128, block_size=16,
            steps_per_tick=2, quant=quant,
            draft_model=(draft if spec else None), spec_decode=spec,
            spec_k=4)
        reqs, tps = measure(eng, prompts)
        key = f"spec{int(spec)}_quant{int(bool(quant))}"
        rec = {"tokens_per_sec": tps,
               "streams": [list(r.output_ids) for r in reqs]}
        if spec:
            rec["accept_rate"] = eng.stats()["speculative"]["accept_rate"]
        if quant:
            rec["quant_weight_ratio"] = eng.stats()["quant"]["ratio"]
        out[key] = rec

# --- the ngram arm: plain vs host-draft spec on the SAME repetitive
# workload, both at the same steps_per_tick
eng = ServingEngine(model, max_batch=4, max_context=256, block_size=16,
                    steps_per_tick=2)
reqs, tps = measure(eng, rep_prompts, budget=40)
out["rep_plain"] = {"tokens_per_sec": tps,
                    "streams": [list(r.output_ids) for r in reqs]}
eng = ServingEngine(model, max_batch=4, max_context=256, block_size=16,
                    steps_per_tick=2, spec_decode=True,
                    spec_draft="ngram", spec_adaptive=True,
                    spec_k_ladder="2,4,8")
# the adaptive contract: every ladder rung precompiles into the warmup
# grid, so a k step under traffic moves between warmed executables —
# without this, the first measured drive to reach a new rung would
# compile mid-measurement
eng.warmup()
reqs, tps = measure(eng, rep_prompts, budget=40)
st = eng.stats()["speculative"]
out["rep_ngram"] = {"tokens_per_sec": tps,
                    "streams": [list(r.output_ids) for r in reqs],
                    "accept_rate": st["accept_rate"],
                    "k_now": st["k_now"],
                    "k_switches": st["k_switches"],
                    "ineligible_slots": st["ineligible_slots"]}

# --- accept-rate-vs-k: the curve the adaptive controller walks
sweep = {}
for k in (2, 4, 8):
    eng = ServingEngine(model, max_batch=4, max_context=256,
                        block_size=16, steps_per_tick=2,
                        spec_decode=True, spec_draft="ngram", spec_k=k)
    _, tps = measure(eng, rep_prompts, budget=40)
    st = eng.stats()["speculative"]
    sweep[str(k)] = {"accept_rate": st["accept_rate"],
                     "tokens_per_sec": tps}
out["accept_vs_k"] = sweep

# --- fp8: weight ratio + max logit deviation vs the fp weights
eng = ServingEngine(model, max_batch=4, max_context=128, block_size=16,
                    steps_per_tick=2, quant="fp8")
_, tps = measure(eng, prompts)
out["fp8"] = {"tokens_per_sec": tps,
              "quant_weight_ratio": eng.stats()["quant"]["ratio"]}
sd = model.state_dict(); keys = sorted(sd)
snap = squant.snapshot(keys, [sd[k]._value for k in keys], "fp8")
deq = squant.dequant_values(snap.values, snap.axes)
ids = paddle.to_tensor(rng.randint(1, 1000, (2, 16)).astype(np.int32))
ref = np.asarray(model(ids)._value)
orig = {k: sd[k]._value for k in keys}
try:
    for k, v in zip(keys, deq):
        sd[k]._value = v
    got = np.asarray(model(ids)._value)
finally:
    for k in keys:
        sd[k]._value = orig[k]
out["fp8"]["max_logit_dev"] = round(float(np.abs(ref - got).max()), 4)

base = out["spec0_quant0"].pop("streams")
out["parity_spec_vs_plain"] = out["spec1_quant0"].pop("streams") == base
qbase = out["spec0_quant1"].pop("streams")
out["parity_spec_quant"] = out["spec1_quant1"].pop("streams") == qbase
out["parity_ngram_vs_plain"] = \
    out["rep_ngram"].pop("streams") == out["rep_plain"].pop("streams")
print("RESULT " + json.dumps(out))
"""
    res = _run_result_subprocess("spec_decode", code)
    if not (res["parity_spec_vs_plain"] and res["parity_spec_quant"]
            and res["parity_ngram_vs_plain"]):
        # losslessness is the rung's headline claim: a parity break is
        # a FAILED rung, not a recorded curiosity
        raise RuntimeError(
            "spec losslessness parity failed: "
            f"plain={res['parity_spec_vs_plain']} "
            f"quant={res['parity_spec_quant']} "
            f"ngram={res['parity_ngram_vs_plain']}")
    if res["fp8"]["max_logit_dev"] >= 0.25:
        raise RuntimeError(
            "fp8 logit deviation outside the documented 0.25 budget: "
            f"{res['fp8']['max_logit_dev']}")
    plain = res["rep_plain"]["tokens_per_sec"]
    ngram = res["rep_ngram"]["tokens_per_sec"]
    return {"tokens_per_sec_plain": plain,
            "tokens_per_sec_ngram": ngram,
            "tokens_per_sec_model_draft":
                res["spec1_quant0"]["tokens_per_sec"],
            "tokens_per_sec_quant": res["spec0_quant1"]["tokens_per_sec"],
            "tokens_per_sec_fp8": res["fp8"]["tokens_per_sec"],
            "spec_decode_speedup": round(ngram / max(plain, 1e-9), 2),
            "spec_accept_rate": res["rep_ngram"]["accept_rate"],
            "adaptive_k_final": res["rep_ngram"]["k_now"],
            "adaptive_k_switches": res["rep_ngram"]["k_switches"],
            "spec_ineligible_slots": res["rep_ngram"]["ineligible_slots"],
            "accept_vs_k": res["accept_vs_k"],
            "quant_weight_ratio":
                res["spec0_quant1"]["quant_weight_ratio"],
            "quant_fp8_weight_ratio": res["fp8"]["quant_weight_ratio"],
            "fp8_max_logit_dev": res["fp8"]["max_logit_dev"],
            "parity_spec_vs_plain": bool(res["parity_spec_vs_plain"]),
            "parity_spec_quant": bool(res["parity_spec_quant"]),
            "parity_ngram_vs_plain": bool(res["parity_ngram_vs_plain"])}


@harness.register_rung("continuous_batching", est_cold_s=240, smoke=True)
def bench_continuous_batching(ctx):
    """ISSUE 11 rung: continuous-batching evidence, measured CLIENT-side
    (the driver timestamps each request's token arrivals around the
    synchronous step loop, so the numbers need no metric sketches and
    reset per cell).

    (a) Long-prompt-arrival stall: one short stream decodes while one
    long prompt is absorbed; the stream's MAX inter-token gap is the
    stall a monolithic prefill inflicts and chunked prefill bounds.
    `long_arrival_tpot_ratio` (monolithic gap / chunked gap, regression
    key) collapsing toward 1.0 means chunking stopped bounding tails.

    (b) Open-loop Poisson arrivals at 2-3 RPS with mixed prompt
    lengths, chunked vs monolithic: per request TTFT + inter-token
    gaps; a request meets SLO iff TTFT and its max gap clear thresholds
    calibrated from (a) (the gap SLO sits between the two stall
    medians, so it separates exactly the behavior under test).
    `goodput_under_slo` (regression key) is the CHUNKED engine's
    SLO-meeting requests/sec at the highest RPS;
    `goodput_ratio_vs_monolithic` tracks the comparison headline."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    # CPU smoke needs prefill COMPUTE to dominate per-program dispatch
    # (the pools round-trip per program without donation there), or the
    # stall under test hides in fixed floors: a big vocab makes the
    # monolithic prompt's final projection the stall, while pools stay
    # small enough that a decode tick is cheap
    cfg = gpt3_124m() if on_tpu else gpt3_tiny(vocab_size=8192,
                                               max_seq_len=512)
    model = GPTForCausalLM(cfg)
    model.eval()
    scale = 4 if on_tpu else 1
    max_ctx = 512 * scale
    long_len = 448 * scale
    chunk_sz = 32 * scale
    ladder = ",".join(str(v * scale) for v in (32, 64, 512))

    def build(chunk):
        # prefix cache OFF: a repeated long prompt would hit the index
        # and prefill a 1-token suffix, erasing the stall this rung
        # exists to measure (prefix reuse has its own serving_tp rung)
        eng = ServingEngine(model, max_batch=2, max_context=max_ctx,
                            block_size=32 * scale, steps_per_tick=1,
                            prefill_chunk=chunk, pad_buckets=ladder,
                            prefix_cache=False)
        eng.warmup()       # timed windows must measure compute only
        return eng

    engines = {0: build(0), chunk_sz: build(chunk_sz)}

    def drive(eng, arrivals, reqs):
        """Synchronous step loop honoring an open-loop arrival
        schedule; returns per-request (ttft_s, [gap_s...])."""
        recs = [{"t_arr": None, "t_first": None, "t_last": None,
                 "n": 0, "gaps": []} for _ in reqs]
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs) or eng.waiting or eng.prefilling \
                or eng._active_slots():
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now:
                recs[i]["t_arr"] = time.perf_counter()
                eng.add_request(reqs[i])
                i += 1
            if eng.waiting or eng.prefilling or eng._active_slots():
                eng.step()
                t = time.perf_counter()
                for r, rec in zip(reqs, recs):
                    if rec["t_arr"] is None:
                        continue
                    n1 = len(r.output_ids)
                    if n1 > rec["n"]:
                        if rec["t_first"] is None:
                            rec["t_first"] = t
                        else:
                            rec["gaps"].append(
                                (t - rec["t_last"]) / (n1 - rec["n"]))
                        rec["t_last"], rec["n"] = t, n1
            elif i < len(reqs):
                time.sleep(max(0.0, min(
                    0.002, arrivals[i] - (time.perf_counter() - t0))))
        eng.finished.clear()
        wall = time.perf_counter() - t0
        return recs, wall

    # ---- (a) the stall A/B: running stream + one long arrival
    def long_arrival_gap(chunk):
        eng = engines[chunk]
        rng = np.random.RandomState(7)
        stream = Request(rng.randint(1, cfg.vocab_size, (8,)),
                         max_new_tokens=80)
        burst = Request(rng.randint(1, cfg.vocab_size, (long_len,)),
                        max_new_tokens=4)
        # the long prompt must arrive while the stream is MID-decode —
        # same-boundary admission would put the stall before the
        # stream's first token, where no inter-token gap can see it
        recs, _ = drive(eng, [0.0, 0.3], [stream, burst])
        return max(recs[0]["gaps"])

    reps = 3 if ctx.smoke else 5
    gap_mono = float(np.median([long_arrival_gap(0) for _ in range(reps)]))
    gap_chunked = float(np.median(
        [long_arrival_gap(chunk_sz) for _ in range(reps)]))
    ratio = gap_mono / max(gap_chunked, 1e-9)

    # ---- (b) Poisson arrivals; SLO calibrated between the two stalls
    gap_slo = (gap_mono + gap_chunked) / 2.0
    ttft_slo = 2.0          # seconds; queue pathologies, not decode noise
    rps_levels = (2.0, 3.0)
    n_req = 8 if ctx.smoke else 16
    out = {}
    for rps in rps_levels:
        for chunk in (0, chunk_sz):
            rng = np.random.RandomState(int(rps * 10))
            lens = rng.choice([8, 16, 48, long_len], size=n_req,
                              p=[0.3, 0.3, 0.2, 0.2])
            arrivals = np.cumsum(rng.exponential(1.0 / rps, size=n_req))
            reqs = [Request(rng.randint(1, cfg.vocab_size, (int(L),)),
                            max_new_tokens=16) for L in lens]
            recs, wall = drive(engines[chunk], list(arrivals), reqs)
            good = sum(
                1 for rec in recs
                if rec["t_first"] is not None
                and rec["t_first"] - rec["t_arr"] <= ttft_slo
                and (not rec["gaps"] or max(rec["gaps"]) <= gap_slo))
            gaps = sorted(g for rec in recs for g in rec["gaps"])
            p99 = gaps[min(len(gaps) - 1,
                           int(len(gaps) * 0.99))] if gaps else 0.0
            key = f"rps{rps:g}_{'chunked' if chunk else 'mono'}"
            out[key] = {"goodput_rps": round(good / wall, 3),
                        "good": good, "requests": n_req,
                        "tpot_p99_ms": round(p99 * 1e3, 3)}
    top = f"rps{rps_levels[-1]:g}"
    chunked_good = out[f"{top}_chunked"]["goodput_rps"]
    mono_good = out[f"{top}_mono"]["goodput_rps"]
    return {"goodput_under_slo": chunked_good,
            "goodput_monolithic": mono_good,
            "goodput_ratio_vs_monolithic": round(
                chunked_good / max(mono_good, 1e-9), 3),
            "long_arrival_tpot_ratio": round(ratio, 2),
            "long_arrival_gap_mono_ms": round(gap_mono * 1e3, 3),
            "long_arrival_gap_chunked_ms": round(gap_chunked * 1e3, 3),
            "tpot_p99_ms_chunked": out[f"{top}_chunked"]["tpot_p99_ms"],
            "tpot_p99_ms_mono": out[f"{top}_mono"]["tpot_p99_ms"],
            "gap_slo_ms": round(gap_slo * 1e3, 3),
            "prefill_chunk": chunk_sz,
            "levels": out}


@harness.register_rung("analyze", est_cold_s=40, smoke=True)
def bench_analyze(ctx):
    """ISSUE 8/12 rung: graft-lint wall time + per-rule findings over
    the full default tree (package + drivers + tests/ — R010's
    surface).

    The tier-1 ratchet runs the analyzer on every CI pass, so its
    runtime is a build-latency budget: `analyze_files_per_sec` is the
    regression key (collapsing means a rule went quadratic — the
    interprocedural passes R007-R010 are the ones to watch), and the
    findings counts make the ratchet trajectory visible across rounds —
    `findings_new` must be 0 on a committed tree."""
    from paddle_tpu.tooling.analyze import (DEFAULT_BASELINE_PATH,
                                            analyze_paths, load_baseline,
                                            new_findings)
    from paddle_tpu.tooling.analyze.__main__ import default_paths
    from paddle_tpu.tooling.analyze.core import iter_source_files
    from paddle_tpu.tooling.analyze.rules import RULES

    # walk the tree ONCE: the explicit file list goes straight into
    # analyze_paths (file paths short-circuit its own walk), so the
    # timed interval is pure parse+rules — the budget the ratchet pays
    repo = os.path.dirname(os.path.abspath(__file__))
    files = iter_source_files(default_paths())
    n_files = len(files)
    t0 = time.perf_counter()
    findings = analyze_paths(files, root=repo)
    wall = time.perf_counter() - t0
    new = new_findings(findings, load_baseline(DEFAULT_BASELINE_PATH))
    per_rule = {r.id: 0 for r in RULES}
    for f in findings:
        per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
    return {"analyze_wall_s": round(wall, 3),
            "analyze_files": n_files,
            "analyze_files_per_sec": round(n_files / max(wall, 1e-9), 1),
            "rules": len(RULES),
            "findings_total": len(findings),
            "findings_new": len(new),
            "findings_per_rule": per_rule}


@harness.register_rung("xray", est_cold_s=120, smoke=True)
def bench_xray(ctx):
    """ISSUE 14 rung: the engine X-ray ledger's price and its evidence.

    A warmed serving engine drives the same request workload with
    sampling OFF vs ON (FLAGS_xray_sample_interval=8 — the documented
    sampling rate of this rung), interleaved windows so clock drift
    hits both sides; `xray_overhead_pct` (regression key) is the
    acceptance gate (<2 on a quiet box; like trace_overhead_pct the
    schema pin only rejects gross regressions on noisy CI).  The
    record also carries the ledger itself: programs tracked, sampled
    dispatches, the top program by device time with its MFU, and the
    kernel-coverage verdicts for the ROADMAP 5b suspect paths."""
    import paddle_tpu as paddle
    from paddle_tpu.flags import flag_guard
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_124m, gpt3_tiny
    from paddle_tpu.observability import xray as obs_xray

    on_tpu = ctx.on_tpu
    paddle.seed(0)
    cfg = gpt3_124m() if on_tpu else gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    # the ledger is process-global and earlier rungs' engines share
    # some configs: reset so this record's counts/coverage are THIS
    # rung's evidence (warmup below re-registers + re-attaches cost)
    obs_xray.reset()
    # prefix cache ON and ngram spec ON: the grid then includes BOTH
    # ROADMAP 5b suspects — the suffix-prefill (prefill_cont) program
    # and the spec verify chunk — for the kernel-coverage audit
    with flag_guard(serving_pad_buckets="64,128" if on_tpu else "16,32"):
        eng = ServingEngine(model, max_batch=4,
                            max_context=1024 if on_tpu else 128,
                            block_size=64 if on_tpu else 16,
                            steps_per_tick=4 if on_tpu else 2,
                            prefix_cache=True, spec_decode=True,
                            spec_draft="ngram", spec_k=4)
        eng.warmup()           # AOT path attaches cost_analysis + HLO
    rng = np.random.RandomState(5)
    plen = 48 if on_tpu else 12
    budget = 48 if on_tpu else 9

    def run_batch(n=4):
        for _ in range(n):
            eng.add_request(Request(rng.randint(1, cfg.vocab_size,
                                                (plen,)),
                                    max_new_tokens=budget))
        t0 = time.perf_counter()
        toks0 = eng.tokens_out
        eng.run()
        eng.finished.clear()
        return (eng.tokens_out - toks0) / (time.perf_counter() - t0)

    with flag_guard(xray_sample_interval=0):
        run_batch()            # settle caches outside the timed windows

    def rate():
        return max(run_batch() for _ in range(2 if ctx.smoke else 3))

    # co-tenant noise on this box swings single windows +-20%, far
    # above the overhead under test: measure adjacent on/off PAIRS and
    # take the quietest pair's delta (noise is strictly additive — the
    # same min-estimator marginal_step_s uses).  BOTH sides pin the
    # flag: an ambient FLAGS_xray_sample_interval must not sample the
    # baseline and read the gate vacuously clean.
    interval = 8
    pairs = []
    for _ in range(3 if ctx.smoke else 4):
        with flag_guard(xray_sample_interval=interval):
            on = rate()
        with flag_guard(xray_sample_interval=0):
            off = rate()
        pairs.append((max(0.0, 1 - on / off) * 100, on, off))
    pct, on, off = min(pairs)

    rep = obs_xray.report()
    progs = rep["programs"]
    top = progs[0] if progs else {}
    cov = rep["kernel_coverage"]

    def dense(prefix):
        # vacuous truth is not evidence: with no audited rows (AOT
        # warmup fell back) the verdict must be False, not "dense".
        # "kernel" merges both evidence channels — the HLO custom-call
        # scan and trace-time claims (interpret-mode kernels leave no
        # HLO marker), so a CPU build running the paged kernels in
        # interpret mode correctly reads NOT dense (ISSUE 18).
        rows = [c for c in cov if c["program"].startswith(prefix)]
        return bool(rows) and all(not c["kernel"] for c in rows)

    def via(prefix):
        modes = {c["via"] for c in cov
                 if c["program"].startswith(prefix) and c["via"]}
        return sorted(modes)
    return {"sample_interval": interval,
            "tokens_per_sec_on": round(on, 1),
            "tokens_per_sec_off": round(off, 1),
            "xray_overhead_pct": round(pct, 2),
            "overhead_pct_windows": [round(p, 2) for p, _, _ in pairs],
            "programs_tracked": len(progs),
            "sampled_dispatches": sum(p["samples"] for p in progs),
            "programs_with_cost": sum(
                1 for p in progs if p["flops_per_dispatch"]),
            "top_program": top.get("program"),
            "top_program_device_frac": top.get("device_time_frac"),
            "top_program_mfu": top.get("mfu"),
            "kernel_coverage_programs": len(cov),
            "pallas_programs": sum(1 for c in cov if c["pallas"]),
            "suffix_prefill_dense": bool(dense("serving.prefill_cont")),
            "spec_verify_dense": bool(dense("serving.spec_tick")),
            "suffix_prefill_via": via("serving.prefill_cont"),
            "spec_verify_via": via("serving.spec_tick")}


# ====================================================================== main

def _emit(rec):
    print(json.dumps(rec), file=sys.stderr, flush=True)


def _headline(rec):
    """The ONE stdout metric line the driver reads.  Degraded runs still
    print it (value null + why) so the stdout contract always holds."""
    if rec is not None and rec.get("ok"):
        v = rec["value"]
        line = {"metric": "gpt124m_train_tokens_per_sec",
                "value": v["tokens_per_sec"], "unit": "tokens/s",
                "vs_baseline": (round(v["mfu"] / 0.45, 4)
                                if v["mfu"] is not None else None)}
    else:
        why = "rung not selected" if rec is None else (
            rec.get("error") or rec.get("reason") or "failed")
        line = {"metric": "gpt124m_train_tokens_per_sec", "value": None,
                "unit": "tokens/s", "vs_baseline": None, "error": why}
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rungs", default="all",
                   help="'all', 'cpu', 'tpu', or comma-separated rung "
                        f"names from: {', '.join(harness.rung_names())}")
    p.add_argument("--smoke", action="store_true",
                   help="seconds-scale validation: run only smoke-tagged "
                        "rungs at reduced size; others emit skipped "
                        "records")
    p.add_argument("--out", default=None,
                   help="also write the full JSON artifact here")
    args = p.parse_args(argv)

    probe = harness.probe_backend()
    if probe["ok"]:
        try:
            enable_compile_cache()
        except Exception as e:  # noqa: BLE001
            _emit({"rung": "compile_cache", "ok": False, "device": "n/a",
                   "elapsed_s": 0.0, "error": repr(e)[:200]})

    headline_done = False

    def emit(rec):
        nonlocal headline_done
        if not rec.get("ok") and rec.get("error"):
            # rung died: drop a flight-recorder dump next to the JSON
            # record so an rc!=0-style artifact (BENCH_r05) still carries
            # the last-K steps/events/metrics of what ran before it
            base = os.path.splitext(args.out)[0] if args.out \
                else "BENCH_failed"
            dump_path = f"{base}.flight.{rec['rung']}.json"
            try:
                _flight.default_recorder().dump(
                    dump_path, reason=f"rung_failure:{rec['rung']}")
                rec["flight_dump"] = dump_path
            except Exception:  # noqa: BLE001 - evidence is best-effort
                pass
        _emit(rec)
        # headline goes out the moment its rung lands — if the driver
        # caps wall time, the stdout metric line is already committed
        # before the secondary rungs compile
        if rec["rung"] == "gpt124m_train":
            _headline(rec)
            headline_done = True

    records = harness.run(args.rungs, smoke=args.smoke,
                          budget_left=remaining_s, emit=emit, probe=probe,
                          release=_release_device_memory,
                          collect_metrics=True)
    if not headline_done:
        _headline(None)

    regression = harness.regression_check(
        records, keys=_REGRESSION_KEYS, env_probe=_ENV_PROBE or None)
    if regression:
        _emit(dict({"rung": "regression_check", "ok": True,
                    "device": probe.get("device_kind") or "n/a",
                    "elapsed_s": 0.0}, value=regression))

    if args.out:
        artifact = {"schema": harness.SCHEMA,
                    "generated_unix": round(time.time(), 1),
                    "backend": probe, "smoke": bool(args.smoke),
                    "selection": args.rungs, "records": records,
                    "regression": regression}
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
