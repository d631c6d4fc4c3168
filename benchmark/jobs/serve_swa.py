"""serve_swa.py — the job of a one-chip serving cell whose model attends
over a window of 128 positions in three layers of four and over the whole
context in the fourth, and drafts for itself (`exaone_moe`: K-EXAONE's
block): `ServingEngine` in this process under an open loop, as
`serve_ssm.py` runs it (same `Sink`, `latency`, `openloop`, same threads
and window), with the model built in bfloat16 from the configuration's
keys as one pipeline stage that holds both ends (the embedding, the MTP
module and the head over the vocabulary slice) and its share of experts.

The engine reads from the model's cache spec that each window layer keeps
a ring of the last 128 positions of K and of V a batch slot, and the full
layers and the module paged kv-head pools: short and long prompts share
one queue, the window layers' bytes stay what they are whatever the
context, and the prefix cache is off (a shared block cannot give back a
ring).  Once a second of the window the job samples the engine's cache
counts (`ServingEngine.cache_counts`); `kv_bytes_per_token` is the mean
of `(full_blocks_in_use x the bytes of a block + window_ring_bytes) /
live_context_tokens` over those samples.

`correct`: every request due in the window finished with the tokens it
asked for; no tick failed; no compile inside the window; the kernel claims
of the self-drafted tick and the chunk programs are Mosaic custom calls of
`KERNELS`, as a set; and, on `check_requests` finished requests (the
longest prompt first, then those over one chunk whose final chunk is
shortest), against the plain float32 reference (`reference/exaone_moe_ref
.py`, dense masked attention under the window rule), over prompt +
output, in units of the reference logits' spread (their standard
deviation over the vocabulary) at each position:

(a) every EMITTED token's reference logit is within `GAP_TOL` of the
    reference's best at its position: the timed path (the chunks as the
    engine cut and padded them, the rings carried across them, the
    two-position verify and the drafts it rejected) chose it;
(b) the float32 logits the timed path brought to the host at the prompt's
    last position (the final chunk's last real row;
    `Request.keep_first_logits`) are within `LOGIT_TOL` of the
    reference's.

`control` (a key of the workload file, empty in the cell; `--set
control='"float8"'` or `'"no_window"'`, or both joined by `+`): the
reference with every matmul operand rounded to float8_e4m3, or the
reference whose window layers attend the whole context, stands in for the
program: its greedy tokens and its logits are read through the same
comparison, and the run must come out `correct: false` by the control's
own line.

`witness` (a key of the workload file, empty in the cell; `--set
witness='"bfloat16"'`): the reference with every matmul operand rounded to
bfloat16, the configuration's own precision, read through the same
comparison and printed beside the program's readings, not held to the
limits: how far that precision alone moves (a) and (b) on the same
requests.
"""

from __future__ import annotations

import threading
import time

# Served: bf16 weights, activations, K, V and rings; float32 residual,
# norms, softmax and accumulation.  Reference: the same bf16 weights,
# float32 "highest".  Both limits are in spreads of the reference logits
# (their standard deviation over the vocabulary at the position).  Each
# lies between two readings on the chip (PERF.md section 4; 34 runs of
# the change, two requests each): the largest the change gave over its
# seeds, (a) 0.595 and (b) 0.394, and the smallest of the float8 control's,
# (a) 0.969 and (b) 1.40, which must fail.  (a) has no upper reading three
# times its largest: its tail is the configuration's precision, not the
# cache.  The bfloat16 witness, the reference with its operands rounded as
# the program's are, reads the same tail on the same requests (0.354 and
# 0.114 where the program read 0.354 and 0.114): a token's top 8 of 128
# sigmoid scores from a bfloat16 stream differ from the float32
# reference's for about 1-3% of (token, layer) pairs, and after the
# post-norm such a swap moves that token's logits by tenths of a spread
# (most rows read 0.03).  (b) is the line that catches the float8 control.
GAP_TOL = 0.7          # (a)
LOGIT_TOL = 0.75       # (b)
REF_PAD = 1024         # the reference's sequence lengths are multiples
CONTROLS = ("float8", "no_window")
WITNESSES = ("bfloat16",)
KERNELS = {"serving.mtp_tick": {"paged_chunk_prefill", "moe_grouped_matmul"},
           "serving.prefill_cont": {"paged_chunk_prefill",
                                    "moe_grouped_matmul"}}


def build_model(cfgd: dict, max_context: int, seed: int, draft: bool):
    """The configuration's stage of the model, in its `param_dtype`
    (bfloat16, created a parameter at a time in it; a rehearsal's tiny
    model is float32): `num_experts` held of `router_width`, from
    `expert_offset`, the selection bias drawn from `seed`."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                              ExaoneMoeForCausalLM)
    cfg = ExaoneMoeConfig(
        vocab_size=cfgd["vocab_size"], hidden_size=cfgd["hidden_size"],
        num_layers=cfgd["num_layers"],
        num_heads=cfgd["num_attention_heads"],
        num_kv_heads=cfgd["num_key_value_heads"], head_dim=cfgd["head_dim"],
        intermediate_size=cfgd["intermediate_size"],
        moe_intermediate_size=cfgd["moe_intermediate_size"],
        num_experts=cfgd["router_width"],
        n_experts_held=cfgd["num_experts"],
        expert_offset=int(cfgd.get("expert_offset", 0)),
        num_experts_per_tok=cfgd["num_experts_per_tok"],
        num_shared_experts=cfgd["num_shared_experts"],
        routed_scaling_factor=cfgd["routed_scaling_factor"],
        norm_topk_prob=cfgd["norm_topk_prob"], n_group=cfgd["n_group"],
        topk_group=cfgd["topk_group"],
        first_k_dense_replace=cfgd["first_k_dense_replace"],
        mlp_layer_types=tuple(cfgd["mlp_layer_types"]),
        layer_types=tuple(cfgd["layer_types"]),
        sliding_window=cfgd["sliding_window"],
        sliding_window_pattern=cfgd["sliding_window_pattern"],
        num_nextn_predict_layers=cfgd["num_nextn_predict_layers"],
        mtp_layer_types=tuple(cfgd["mtp_layer_types"]),
        mtp_draft=bool(draft), max_seq_len=max_context,
        rms_eps=cfgd["rms_norm_eps"],
        rope_base=float(cfgd["rope_parameters"]["rope_theta"]),
        initializer_range=cfgd["initializer_range"],
        param_dtype=cfgd["param_dtype"])
    paddle.seed(seed % (2 ** 31))
    model = ExaoneMoeForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed % (2 ** 32))
    for name, p in model.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = jnp.asarray(rng.uniform(-0.1, 0.1, p.shape),
                                   p._value.dtype)
    return model, cfg


def kv_bytes_per_token(samples: list, block_bytes: float):
    """The mean over `samples` (`ServingEngine.cache_counts()` dicts) of
    the cache's bytes a live context position: the blocks the sequences
    hold at `block_bytes` each, and the rings; None with no position
    live."""
    got = [(s["full_blocks_in_use"] * block_bytes + s["window_ring_bytes"])
           / s["live_context_tokens"] for s in samples
           if s["live_context_tokens"]]
    return sum(got) / len(got) if got else None


def run(ctx) -> dict:
    import jax
    import numpy as np
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        publish_expert_rows
    from paddle_tpu.observability import xray
    from benchmark import latency
    from benchmark.jobs.serve_engine import Sink
    from benchmark.traffic import openloop

    wl, cfgd, mix = ctx.workload, ctx.config, ctx.traffic
    t0 = time.perf_counter()
    model, cfg = build_model(cfgd, int(wl["max_context"]), ctx.seed,
                             wl.get("mtp_draft", True))
    xray.reset()
    eng = ServingEngine(model, max_batch=int(wl["max_batch"]),
                        max_context=int(wl["max_context"]),
                        block_size=int(wl["block_size"]),
                        num_blocks=int(wl["num_blocks"]),
                        prefill_chunk=int(wl["prefill_chunk"]),
                        pad_buckets=wl["pad_buckets"])
    drafting = eng.mtp is not None
    t0 = ctx.part("build", t0)
    info = eng.warmup()
    t0 = ctx.part("warm_up", t0)
    paged = eng.cache.held_bytes(eng.pools, lambda row: row.paged)
    block_bytes = paged / (eng.num_blocks + 1)
    rings = eng.cache.held_bytes(eng.pools, lambda row: row.window > 0)
    n_params = model.num_params()
    windowed = sum(1 for i in range(cfg.num_layers) if cfg.window_of(i))
    ctx.say(f"model exaone_moe: {cfg.num_layers} layers x "
            f"{cfg.hidden_size} ({windowed} over a window of "
            f"{cfg.sliding_window}, {cfg.num_layers - windowed} full) + "
            f"{cfg.num_nextn_predict_layers} MTP module, {cfg.num_heads} "
            f"heads over {cfg.num_kv_heads} kv heads of {cfg.head_dim}, "
            f"{cfg.n_experts_held} of {cfg.num_experts} experts, "
            f"vocabulary {cfg.vocab_size}; {n_params / 1e6:.1f}M "
            f"parameters ({n_params * 2 / 1e9:.2f} GB bf16); drafter "
            f"{'on (depth 1)' if drafting else 'off'}")
    ctx.say(f"engine: batch {eng.B}, context {eng.max_context}, "
            f"{eng.num_blocks} blocks of {eng.bs} ({block_bytes:.0f} B a "
            f"block: the full layers' and the module's K and V), paged "
            f"pools {paged / 1e9:.3f} GB, rings {rings / 1e9:.3f} GB "
            f"({rings / eng.B:.0f} B a slot), chunk {eng.chunk}, ladder "
            f"{list(eng.pad_ladder)}; warm-up {info['programs']} programs "
            f"({info['aot_programs']} AOT) in {info['warmup_s']:.1f} s")

    # ---- the schedule, fixed before the run
    rate = float(wl["rate_rps"])
    lead, drain_s = float(mix["lead_in_s"]), float(mix["drain_s"])
    span = lead + ctx.seconds
    plan = openloop.request_schedule(mix, rate, lead, ctx.seconds, ctx.seed,
                                     cfg.vocab_size)
    sample = [i for i, p in enumerate(plan) if p["due"] >= lead]
    kept = set(sample)
    reqs = []
    for i, p in enumerate(plan):
        r = Request(p["prompt"], max_new_tokens=p["max_new_tokens"],
                    keep_first_logits=i in kept)
        r._stream_q = Sink()
        reqs.append(r)
    lens = sorted(len(plan[i]["prompt"]) for i in sample)
    ctx.say(f"open loop: {rate:g} req/s, {len(plan)} requests over "
            f"{span:g} s ({lead:g} s lead-in + {ctx.seconds:g} s window); "
            f"prompt tokens {sum(len(p['prompt']) for p in plan)}, output "
            f"tokens {sum(p['max_new_tokens'] for p in plan)}; the window's "
            f"prompts median {lens[len(lens) // 2] if lens else 0}, longest "
            f"{lens[-3:]}")
    st0 = eng.stats()
    state0 = st0.get("cache_state", {})
    steps0 = st0["steps"]
    ctx.part("schedule", t0)

    stop = threading.Event()
    box = {"sent": [None] * len(plan), "rejected": {}}
    t_sched = time.perf_counter() + 0.25      # the schedule's zero
    t_open, t_close = t_sched + lead, t_sched + span

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
            # the device-side counts as the last harvested tick left them
            # on the host, read before the profiler stops (writing the
            # trace out takes a minute while the engine goes on decoding)
            before = eng.cache_state()
            with ctx.profile():
                time.sleep(max(0.0, t_close - time.perf_counter()))
                box["traced"] = (before, eng.cache_state())
        except BaseException as e:  # noqa: BLE001 - re-raised by run()
            box["error"] = e

    def clock():
        time.sleep(max(0.0, t_open - time.perf_counter()))
        ctx.window_opens()
        box["before"] = ctx.compiles.count()
        box["depth"] = []             # (waiting, running) once a second
        box["cache"] = []             # the cache counts once a second
        while time.perf_counter() < t_close:
            box["depth"].append((len(eng.waiting),
                                 eng.B - len(eng.free_slots)))
            box["cache"].append(eng.cache_counts())
            time.sleep(max(0.0, min(1.0, t_close - time.perf_counter())))
        box["in_window"] = ctx.compiles.since(box["before"])

    threads = [threading.Thread(target=f, daemon=True, name=f.__name__)
               for f in ([generator, clock] + ([tracer] if ctx.trace else []))]
    for t in threads:
        t.start()
    eng.serve_forever(stop)
    for t in threads:
        # the tracer ends when the trace is written, which the reducers
        # need whole (a minute and more on the chip's host)
        t.join(drain_s + 600)
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
    samples = box.get("cache", [])
    per_token = kv_bytes_per_token(samples, block_bytes)
    live = [s["live_context_tokens"] for s in samples]
    ctx.say(f"sample: {summ['n']} requests due in the window, "
            f"{summ['failed']} failed; TTFT p50 {summ['ttft_p50_ms']:.1f} "
            f"p90 {summ['ttft_ms']:.1f} ms; TPOT p50 "
            f"{summ['tpot_p50_ms']:.2f} p90 {summ['tpot_ms']:.2f} ms; "
            f"{delivered} tokens in the window = {tokens_per_s:.1f} tokens/s")
    ctx.say(f"generator lag p90 {summ.get('gen_lag_ms', float('nan')):.3f} "
            f"ms; queue wait p90 "
            f"{summ.get('queue_wait_ms', float('nan')):.1f} ms; ticks "
            f"{st['ticks']}, prefill chunks {st['prefill_chunks']}, sheds "
            f"{st['slo_sheds']}, rejected {len(box['rejected'])}")
    ctx.say(f"cache once a second of the window: live context positions "
            f"{live}; blocks in use "
            f"{[s['full_blocks_in_use'] for s in samples]} of "
            f"{eng.num_blocks}; rings {st['cache']['window_ring_bytes']} B; "
            f"{st['cache']['ring_writes']} ring writes over the run; "
            f"{per_token if per_token is None else round(per_token, 1)} B "
            f"a live position (the full layers alone: "
            f"{block_bytes / eng.bs:.0f})")
    ctx.say(f"(waiting, running) each second of the window: "
            f"{box.get('depth')}")
    counters = {"ttft_p90_ms": summ["ttft_ms"],
                "tpot_p90_ms": summ["tpot_ms"],
                "queue_wait_p90_ms": summ.get("queue_wait_ms"),
                "gen_lag_p90_ms": summ.get("gen_lag_ms"),
                "kv_bytes_per_token": per_token}
    run_steps = max(1, st["steps"] - steps0)
    state = st.get("cache_state", {})
    if "moe_rows" in state:
        grown = list(state["moe_rows"] - state0.get("moe_rows", 0))
        publish_expert_rows(grown, cfg.expert_offset)
        # the reducers read the TRACED window's counts beside its times
        a, b = box.get("traced", ({}, {}))
        if a and b:
            counters["decode_steps"] = b["steps"] - a["steps"]
            counters["moe_rows"] = (b["moe_rows"] - a["moe_rows"]).tolist()
        moe = [g for g in grown if g.sum()]
        ctx.say(f"experts, a forward and MoE block over the run (both "
                f"positions of running slots; idle slots reach none): "
                f"{np.mean([g[0, 0].sum() for g in moe]) / run_steps:.2f} "
                f"rows over {cfg.n_experts_held} experts, "
                f"{np.mean([g[0, 1].sum() for g in moe]) / run_steps:.2f} "
                f"experts hit")
    if drafting:
        sp = st["spec"]
        ctx.say(f"self-drafting: {sp['drafted']} drafts judged, "
                f"{sp['accepted']} accepted ({100 * sp['accept_rate']:.3f}%)"
                f" over {sp['ticks']} ticks (device-side counts)")

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
        for prog, kernels in KERNELS.items():
            got = {tuple(c) for row in cov
                   if row["program"].startswith(prog) for c in row["claims"]}
            ctx.check(got == {(k, "custom_call") for k in kernels},
                      f"{prog}*: its kernel claims are Mosaic custom calls "
                      f"of {sorted(kernels)} {sorted(got)}")
    _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records)
    return {"attempted": summ["n"],
            "failed": summ["failed"],
            "metrics": {"serve_tpot_p90_ms": summ["tpot_ms"],
                        "serve_tokens_per_s": tokens_per_s},
            "counters": counters}


def pick_checked(plan, done, chunk, n, rng):
    """Which finished requests (indices into `plan`) the reference holds
    to: the longest prompt first (it crosses the window and the chunks the
    most times), then the prompts over one chunk whose final chunk is
    shortest (the fewer real rows a chunk has, the more of its last row
    the rings carried into it decide), ties in `rng`'s order, then the
    rest."""
    done = [done[j] for j in rng.permutation(len(done))]
    if not done:
        return []
    first = max(done, key=lambda i: len(plan[i]["prompt"]))
    rest = [i for i in done if i != first]
    over = sorted((i for i in rest if len(plan[i]["prompt"]) > chunk),
                  key=lambda i: (len(plan[i]["prompt"]) - 1) % chunk)
    under = [i for i in rest if len(plan[i]["prompt"]) <= chunk]
    return ([first] + over + under)[:n]


def _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records):
    """Parts (a) and (b) of `correct` (module docstring) on sampled
    finished requests, and the controls asked for."""
    import numpy as np
    from benchmark.reference import exaone_moe_ref as ref
    controls = [c for c in (ctx.workload.get("control") or "").split("+")
                if c]
    if set(controls) - set(CONTROLS):
        raise ValueError(f"control {controls}: not among {CONTROLS}")
    witness = ctx.workload.get("witness") or ""
    if witness and witness not in WITNESSES:
        raise ValueError(f"witness {witness!r}: not among {WITNESSES}")
    t_ref = time.perf_counter()
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    C = eng.chunk
    done = [i for i, rec in zip(sample, records) if rec["finished"]]
    picked = pick_checked(plan, done, C, int(ctx.workload["check_requests"]),
                          rng)
    if not picked:
        ctx.check(False, "no finished request to hold to the reference")
        return
    # what serving held at its peak, before the reference takes the
    # memory of the engine's pools
    serving_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in ctx.devices)
    eng.pools = None
    dims = ref.dims_of(cfgd)
    sd = {k: v._value for k, v in model.state_dict().items()}
    params = ref.from_state_dict(sd, cfg.num_layers)
    import ml_dtypes
    rounded = {"float8": ml_dtypes.float8_e4m3fn,
               "bfloat16": ml_dtypes.bfloat16}

    read = {"gap": [], "logit": []}
    ctrl = {c: {"gap": [], "logit": []}
            for c in controls + ([witness] if witness else [])}
    n_tokens = same = 0
    for i in picked:
        prompt, out = plan[i]["prompt"], list(reqs[i].output_ids)
        L = len(prompt)
        seq = np.asarray(prompt + out, np.int32)
        # the rows that chose the output: the prompt's last position, then
        # each emitted token's
        at = np.arange(L - 1, L - 1 + len(out))
        # padded behind the sequence (causal: nothing before moves), so
        # that one compile serves sequences of like length
        seq = np.concatenate([seq, np.zeros(-len(seq) % REF_PAD, np.int32)])
        lg = np.asarray(ref.forward(params, seq, dims, positions=at))
        spread = lg.std(-1)

        def gap(chosen):
            """How far the chosen tokens' reference logits fall short of
            the reference's best, in spreads."""
            got = lg[np.arange(len(at)), np.asarray(chosen)]
            return float(((lg.max(-1) - got) / spread).max())

        def far(row):
            """How far a row of logits at the prompt's last position lies
            from the reference's, in spreads."""
            return float(np.abs(row - lg[0]).max() / spread[0])

        read["gap"].append(gap(out))
        read["logit"].append(far(reqs[i].first_logits))
        same += int((lg.argmax(-1) == np.asarray(out)).sum())
        n_tokens += len(out)
        for c in ctrl:
            lc = np.asarray(ref.forward(
                params, seq, dims, positions=at,
                no_window=c == "no_window", operand_dtype=rounded.get(c)))
            ctrl[c]["gap"].append(gap(lc.argmax(-1)))
            ctrl[c]["logit"].append(far(lc[0]))
            del lc
        del lg
    took = time.perf_counter() - t_ref
    lens = [(len(plan[i]["prompt"]), len(reqs[i].output_ids)) for i in picked]

    def each(xs):
        return "[" + ", ".join(f"{x:.3g}" for x in xs) + "]"

    ctx.say(f"device memory peak {serving_peak} B while serving, before "
            f"the reference check")
    ctx.check(max(read["gap"]) <= GAP_TOL,
              f"(a) on {len(picked)} sampled requests (prompt, output "
              f"{lens}; every prompt over {cfg.sliding_window} tokens "
              f"crossed its window, those over {C} its chunks) every one "
              f"of the {n_tokens} emitted tokens is the reference's best to "
              f"within {max(read['gap']):.3g} spreads (<= {GAP_TOL}; a "
              f"request {each(read['gap'])}; {same} identical)")
    ctx.check(max(read["logit"]) <= LOGIT_TOL,
              f"(b) the logits the timed path brought to the host at each "
              f"sampled prompt's last position (its final chunk's last real "
              f"row) lie within {max(read['logit']):.3g} spreads of the "
              f"reference's (<= {LOGIT_TOL}; a request "
              f"{each(read['logit'])}); the check took {took:.1f} s")
    for c in controls:
        what = ("the reference with every matmul operand rounded to "
                "float8_e4m3" if c == "float8" else
                "the reference whose window layers attend the whole "
                "context")
        g, lo = ctrl[c]["gap"], ctrl[c]["logit"]
        ctx.check(max(g) <= GAP_TOL and max(lo) <= LOGIT_TOL,
                  f"CONTROL {c}, which must fail: {what}, read as the "
                  f"program is: its greedy tokens fall {max(g):.3g} spreads "
                  f"short of the reference's best (<= {GAP_TOL}; a request "
                  f"{each(g)}), its logits at the prompt's last position lie "
                  f"{max(lo):.3g} spreads off (<= {LOGIT_TOL}; a request "
                  f"{each(lo)})")
    if witness:
        g, lo = ctrl[witness]["gap"], ctrl[witness]["logit"]
        ctx.say(f"WITNESS {witness}, not held to the limits: the reference "
                f"with every matmul operand rounded to {witness}, the "
                f"configuration's own precision, read as the program is: "
                f"(a) {max(g):.3g} spreads (a request {each(g)}), (b) "
                f"{max(lo):.3g} (a request {each(lo)})")
