"""serve_engine.py — the job of a one-chip serving cell: `ServingEngine`
in this process, under an open loop of independent users.

The main thread runs `serve_forever(stop_event)`, the loop behind the
streaming endpoint.  A generator thread calls `add_request` at instants
fixed before the run (`traffic/openloop.py`), whether or not earlier
requests have finished.  Each request's stream queue is a sink that stamps
every token the moment the engine hands it over (`Request._stream_q`, the
hook `observability/http.py` reads tokens from), so a time to first token
runs from the instant the request was *due* to the instant its first token
reached the client's side of the engine.  No HTTP hop, no second process.

The schedule starts `lead_in_s` before the window so that the window opens
on a loaded engine; requests due inside the window are the sample, and the
run drains them before it ends.  A request that failed, was shed or did
not finish within `drain_s` of the window's end counts as missing: its
times read as the window's length.

Workload file: the engine's sizes (`max_batch`, `max_context`,
`block_size`, `steps_per_tick`, `prefill_chunk`) and `rate_rps`, a number
found once by a sweep (see `PERF.md`).  Traffic (`kind: requests`):
lengths, arrival process, `lead_in_s`, `drain_s`.

`correct`: every request due in the window finished with as many tokens as
it asked for; on a seeded sample of them, every served token is the plain
reference's best token at its position to within `GAP_TOL` logits (the
reference runs the full forward over prompt + output in float32, so this
holds prefill and decode through the paged cache to the mathematics); no
compile request inside the window; the paged kernels are Mosaic custom
calls; no tick failed.
"""

from __future__ import annotations

import threading
import time

# How far a served token's logit may fall short of the reference's best
# logit at that position.  Served: bf16 weights, bf16 activations, two
# differently tiled programs (chunked prefill, paged decode).  Reference:
# the same bf16 weights, float32 "highest" throughout.  With N(0, 0.02)
# embeddings the logits have a spread near 1, bf16 keeps 8 bits, and 24
# layers of rounding leave ~1e-2 of noise on a logit: two near-equal top
# logits may swap, a token 1/8 of a logit below the best may not.  A
# wrong block, a stale cache line or a mask off by one moves logits by
# their whole spread.
GAP_TOL = 0.125
CHECK_REQUESTS = 3           # how many finished requests the check samples
CHECK_MAX_TOKENS = 768       # longest prompt + output it takes
CHECK_PAD = 256              # the reference's sequence lengths


class Sink:
    """The request's stream queue: `put` is all the engine calls.  Stamps
    each token's arrival; `None` is the engine's end-of-stream mark."""

    __slots__ = ("times", "closed")

    def __init__(self):
        self.times = []
        self.closed = None

    def put(self, tok) -> None:
        now = time.perf_counter()
        if tok is None:
            self.closed = now
        else:
            self.times.append(now)


def run(ctx) -> dict:
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import xray
    from benchmark import latency
    from benchmark.reference import gpt_ref
    from benchmark.traffic import openloop

    wl, cfgd, mix = ctx.workload, ctx.config, ctx.traffic
    t0 = time.perf_counter()
    cfg = GPTConfig(vocab_size=cfgd["vocab_size"],
                    hidden_size=cfgd["hidden_size"],
                    num_layers=cfgd["num_layers"],
                    num_heads=cfgd["num_heads"],
                    max_seq_len=cfgd["max_seq_len"],
                    intermediate_size=cfgd["intermediate_size"])
    paddle.seed(ctx.seed % (2 ** 31))
    model = GPTForCausalLM(cfg)
    model.eval()
    model.bfloat16()
    xray.reset()
    eng = ServingEngine(model, max_batch=int(wl["max_batch"]),
                        max_context=int(wl["max_context"]),
                        block_size=int(wl["block_size"]),
                        steps_per_tick=int(wl["steps_per_tick"]),
                        prefill_chunk=int(wl["prefill_chunk"]))
    t0 = ctx.part("build", t0)
    info = eng.warmup()
    t0 = ctx.part("warm_up", t0)
    pool_bytes = sum(p.size * p.dtype.itemsize
                     for kv in eng.pools for p in kv)
    ctx.say(f"engine: batch {eng.B}, context {eng.max_context}, "
            f"{eng.num_blocks} blocks of {eng.bs}, pools "
            f"{pool_bytes / 2**30:.2f} GiB {eng.pools[0][0].dtype}, "
            f"{eng.steps_per_tick} steps a tick, chunk {eng.chunk}, ladder "
            f"{list(eng.pad_ladder)}; warm-up {info['programs']} programs "
            f"({info['aot_programs']} AOT) in {info['warmup_s']:.1f} s")

    # ---- the schedule, fixed before the run
    rate = float(wl["rate_rps"])
    lead, drain_s = float(mix["lead_in_s"]), float(mix["drain_s"])
    span = lead + ctx.seconds
    plan = openloop.request_schedule(mix, rate, lead, ctx.seconds, ctx.seed,
                                     cfg.vocab_size)
    reqs = []
    for p in plan:
        r = Request(p["prompt"], max_new_tokens=p["max_new_tokens"])
        r._stream_q = Sink()
        reqs.append(r)
    ctx.say(f"open loop: {rate:g} req/s, {len(plan)} requests over "
            f"{span:g} s ({lead:g} s lead-in + {ctx.seconds:g} s window); "
            f"prompt tokens {sum(len(p['prompt']) for p in plan)}, output "
            f"tokens {sum(p['max_new_tokens'] for p in plan)}")
    ctx.part("schedule", t0)

    stop = threading.Event()
    box = {"sent": [None] * len(plan), "rejected": {}}
    t_sched = time.perf_counter() + 0.25      # the schedule's zero
    t_open, t_close = t_sched + lead, t_sched + span
    sample = [i for i, p in enumerate(plan) if p["due"] >= lead]

    def generator():
        try:
            for i, (p, r) in enumerate(zip(plan, reqs)):
                wait = t_sched + p["due"] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                with jax.profiler.TraceAnnotation("bench:add_request"):
                    try:
                        eng.add_request(r)
                    except ValueError as e:     # refused: counts as failed
                        box["rejected"][i] = str(e)
                box["sent"][i] = time.perf_counter()
            # drain: the sample finishes, or the deadline passes
            t_end = t_close + drain_s
            while time.perf_counter() < t_end and not all(
                    reqs[i]._stream_q.closed is not None
                    or i in box["rejected"] for i in sample):
                time.sleep(0.02)
        except BaseException as e:  # noqa: BLE001 - re-raised by run()
            box["error"] = e
        finally:
            stop.set()

    def tracer():
        try:
            time.sleep(max(0.0, t_close - ctx.trace_seconds
                           - time.perf_counter()))
            with ctx.profile():
                time.sleep(max(0.0, t_close - time.perf_counter()))
        except BaseException as e:  # noqa: BLE001 - re-raised by run()
            box["error"] = e

    def clock():
        # set-up ends and the window opens when its first request is due
        time.sleep(max(0.0, t_open - time.perf_counter()))
        ctx.window_opens()
        box["before"] = ctx.compiles.count()
        box["depth"] = []             # (waiting, running) once a second
        while time.perf_counter() < t_close:
            box["depth"].append((len(eng.waiting),
                                 eng.B - len(eng.free_slots)))
            time.sleep(max(0.0, min(1.0, t_close - time.perf_counter())))
        box["in_window"] = ctx.compiles.since(box["before"])

    threads = [threading.Thread(target=f, daemon=True, name=f.__name__)
               for f in ([generator, clock] + ([tracer] if ctx.trace else []))]
    for t in threads:
        t.start()
    eng.serve_forever(stop)
    for t in threads:
        t.join(drain_s + 30)
    if "error" in box:
        raise box["error"]

    # ---- reduce
    records = []
    for i in sample:
        r, p = reqs[i], plan[i]
        rel = [t - t_sched for t in r._stream_q.times]
        ok = (i not in box["rejected"] and r.done
              and r.outcome in (None, "finished")
              and len(r.output_ids) == p["max_new_tokens"]
              and len(rel) == p["max_new_tokens"])
        admit = getattr(r, "_t_admit", None)
        records.append({
            "due": p["due"], "times": rel, "finished": ok,
            "sent": None if box["sent"][i] is None
            else box["sent"][i] - t_sched,
            "admit": None if admit is None else admit - t_sched})
    summ = latency.summarize(records, ctx.seconds, 90.0)
    delivered = sum(1 for r in reqs for t in r._stream_q.times
                    if t_open <= t < t_close)
    tokens_per_s = delivered / ctx.seconds
    st = eng.stats()
    ctx.say(f"sample: {summ['n']} requests due in the window, "
            f"{summ['failed']} failed; TTFT p50 {summ['ttft_p50_ms']:.1f} "
            f"p90 {summ['ttft_ms']:.1f} ms; TPOT p50 "
            f"{summ['tpot_p50_ms']:.2f} p90 {summ['tpot_ms']:.2f} ms; "
            f"{delivered} tokens in the window = {tokens_per_s:.1f} tokens/s")
    ctx.say(f"generator lag p90 {summ.get('gen_lag_ms', float('nan')):.3f} "
            f"ms; queue wait p90 "
            f"{summ.get('queue_wait_ms', float('nan')):.1f} ms; ticks "
            f"{st['ticks']}, "
            f"prefill chunks {st['prefill_chunks']}, prefix hits "
            f"{st.get('prefix_cache', {}).get('hits')}, sheds "
            f"{st['slo_sheds']}, rejected {len(box['rejected'])}")
    ctx.say(f"(waiting, running) each second of the window: "
            f"{box.get('depth')}")

    # ---- correctness, outside the window
    ctx.check(summ["failed"] == 0,
              f"all {summ['n']} requests due in the window finished with "
              f"the tokens they asked for ({summ['failed']} did not)")
    ctx.check(st["tick_errors"] == 0 and st["poisoned_requests"] == 0,
              "no tick failed and no request was poisoned")
    inw = box.get("in_window", {"requests": -1, "compile_calls": -1})
    ctx.check(inw["requests"] == 0 and inw["compile_calls"] == 0,
              f"no program was compiled inside the window ({inw})")
    if not ctx.rehearse:
        cov = xray.kernel_coverage()
        for prog, kernel in (("serving.tick", "paged_decode"),
                             ("serving.prefill_cont", "paged_chunk_prefill")):
            got = [tuple(c) for row in cov
                   if row["program"].startswith(prog) for c in row["claims"]]
            ctx.check(got and all(c == (kernel, "custom_call") for c in got),
                      f"{prog}*: every kernel claim is ({kernel}, "
                      f"custom_call) [{len(got)} claims]")
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    fit = [i for i, rec in zip(sample, records) if rec["finished"]
           and len(plan[i]["prompt"]) + plan[i]["max_new_tokens"]
           <= CHECK_MAX_TOKENS]
    picked = [fit[j] for j in rng.permutation(len(fit))[:CHECK_REQUESTS]]
    t_ref = time.perf_counter()
    params = gpt_ref.from_state_dict(
        {k: v._value for k, v in model.state_dict().items()}, cfg.num_layers)
    worst, same, total = 0.0, 0, 0
    for i in picked:
        prompt, out = plan[i]["prompt"], reqs[i].output_ids
        L = len(prompt)
        seq = prompt + out[:-1]
        # right-padded to a few fixed lengths (causal: padding cannot
        # reach back), so the reference's programs are found in the cache
        seq = seq + [0] * (-len(seq) % min(CHECK_PAD, cfg.max_seq_len))
        lg = np.asarray(gpt_ref.forward(
            params, np.asarray([seq], np.int32), cfg.num_heads,
            positions=np.arange(L - 1, L - 1 + len(out))))[0]
        gaps = lg.max(-1) - lg[np.arange(len(out)), out]
        worst = max(worst, float(gaps.max()))
        same += int((gaps == 0).sum())
        total += len(out)
    ctx.check(picked and worst <= GAP_TOL,
              f"on {len(picked)} sampled requests ({total} tokens through "
              f"prefill and the paged cache) every served token is the "
              f"reference's best to within {worst:.4f} logits (<= {GAP_TOL}; "
              f"{same} identical; took {time.perf_counter() - t_ref:.1f} s)")
    counters = {"ttft_p90_ms": summ["ttft_ms"],
                "queue_wait_p90_ms": summ.get("queue_wait_ms"),
                "gen_lag_p90_ms": summ.get("gen_lag_ms")}
    return {"attempted": summ["n"],
            "failed": summ["failed"],
            "metrics": {"serve_tpot_p90_ms": summ["tpot_ms"],
                        "serve_tokens_per_s": tokens_per_s},
            "counters": counters}
