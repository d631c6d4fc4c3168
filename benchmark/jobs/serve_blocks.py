"""serve_blocks.py — the job of a one-chip serving cell whose model
generates by diffusion over blocks (`sdar_moe`: SDAR's block):
`ServingEngine` in this process under an open loop, as `serve_engine.py`
runs it (same `Sink`, `latency`, `openloop`, same threads and window),
with three differences:

* the model is `SdarMoeForCausalLM`, built directly in bfloat16 from the
  configuration's keys (one jitted initialiser a parameter shape), and
  the engine reads from it how it generates: a tick denoises and commits
  one block of `block_length` tokens a running sequence, so tokens reach
  a client 1 to `block_length` at a time;
* token ids are drawn over the whole vocabulary less the model's `[MASK]`
  id (a prompt that holds it is refused);
* `correct` holds what the timed path produced to the reference's
  mathematics forward by forward, not token by token.

`correct`: every request due in the window finished with the tokens it
asked for; no tick failed; no compile inside the window; every kernel
claim of the block tick and chunk programs is a Mosaic custom call of
`KERNELS`, as a set; and, on `check_requests` finished requests (of at
most `CHECK_MAX_TOKENS` tokens, so that the reference fits), from what
the engine recorded while it served them (`Request.reveal_steps`: for
each token the denoising forward that revealed it, which fixes the state
of the block before every forward):

(a) each revealed token's reference logit lies within `GAP_TOL` of the
    reference's best at that position and forward, and within
    `GAP_MEAN_TOL` on average over the revealed tokens;
(b) each revealed position's reference log-confidence lies within
    `CONF_TOL` of the best masked position's at that forward, and within
    `CONF_MEAN_TOL` on average;
(c) prefill chunks and block commits through the paged kv-head cache
    agree with the reference's full forward over the finished sequence:
    the request is replayed after the window over the engine's own pools
    (its chunk programs, then a twin of the tick's commit forward that
    returns slot 0's logits) and every logit of every sampled block lies
    within `LOGIT_TOL` of the reference's.

(a) and (b) judge the timed program: they read what `serving.block_tick`
revealed at 10-32 live slots inside the window.  (c) does not: the
engine's programs return tokens, not logits, so (c) is a replay after
the window through the engine's own chunk programs and `_commit_probe`,
a jitted twin of the commit forward over the same seam, views and pools,
in slot 0 of an otherwise idle batch.  It holds the cache path (the
kv-head pools, the writes, the kernel under the block mask) to the
reference, not the tick at its timed batch.

The reference is `benchmark/reference/sdar_moe_ref.py`, float32
"highest"; the states of a request are replayed many at once from the
finished sequence's K and V (`block_logits`).  A last block that the
request's budget cut is left out (its dropped tokens are not recorded).

`control` (a key of the workload file, empty in the cell; `--set
control='"float8"'`, `'"by_position"'`, or both joined by `+`) reads what
the limits are there to catch as the program is read, through the same
comparison, so the run must come out `correct: false`: `float8` — the
reference with every matmul operand rounded to float8_e4m3 stands in for
the program (its greedy token at the position it would reveal, its
logits); `by_position` — a sampler that reveals the lowest masked
position, whatever its confidence, stands in for the program's choice.
"""

from __future__ import annotations

import threading
import time

# Served: bf16 weights and activations, bf16 K and V, float32
# accumulation, logits and confidences; reference: the same bf16 weights,
# float32 "highest".  With N(0, 0.02) weights the logits have a spread
# near 0.9.  Each limit lies between two readings (PERF.md section 4): the
# largest the change gave over its seeds, and a control's, which must
# fail: the reference with every matmul operand rounded to float8_e4m3
# for (a) and (c), a sampler that reveals by position for (b).
# (a) and (b) each hold the LARGEST reading over the sampled requests'
# revealed tokens and their MEAN.  The largest readings guard against a
# single forward gone wrong and do not separate the controls by
# themselves: over two requests' few hundred tokens, most of whose gaps
# are 0, float8's largest (a) read 0.016-0.428 in eight control runs
# (once under the limit) and `by_position`'s largest (b) 0.093-0.187
# against the program's 0.030-0.096 (they overlap).  The means do: in
# the four runs that read them the program's lay 2-11 x under its limit
# and the control's 2-27 x over.  float8 also fails by (c) in every run.
GAP_TOL = 0.125       # (a) logits below the reference's best, largest:
#                       change <= 0.079 over 34 runs, float8 0.016-0.428
GAP_MEAN_TOL = 0.005  # (a), mean: change 0.0005-0.0016, float8 0.014-0.134
CONF_TOL = 0.12       # (b) log-confidence below the best masked
#                       position's, largest: change <= 0.096 (0.073 in
#                       the first 18 runs, when this stood at 0.1)
CONF_MEAN_TOL = 0.0055  # (b), mean: change 0.0018-0.0026, by_position
#                         0.0110-0.0144, float8 0.0082-0.0133
LOGIT_TOL = 0.4       # (c) any logit through the cache vs the full
#                       forward: change <= 0.204, float8 0.639-0.879
CHECK_MAX_TOKENS = 1024   # longest prompt + output the check takes
REF_PAD = 256             # the reference's sequence lengths are multiples
STATES_AT_ONCE = 32       # block states a call of `block_logits`
CONTROLS = ("float8", "by_position")
KERNELS = {"serving.block_tick": {"paged_chunk_prefill",
                                  "moe_grouped_matmul"},
           "serving.prefill_cont": {"paged_chunk_prefill",
                                    "moe_grouped_matmul"}}


def build_model(cfgd: dict, traffic: dict, max_context: int, seed: int):
    """The configuration's stage of the model, in its `param_dtype`."""
    import paddle_tpu as paddle
    from paddle_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeForCausalLM
    cfg = SdarMoeConfig(
        vocab_size=cfgd["vocab_size"], hidden_size=cfgd["hidden_size"],
        num_layers=cfgd["num_layers"],
        num_heads=cfgd["num_attention_heads"],
        num_kv_heads=cfgd["num_key_value_heads"], head_dim=cfgd["head_dim"],
        moe_intermediate_size=cfgd["moe_intermediate_size"],
        num_experts=cfgd["num_experts"],
        num_experts_per_tok=cfgd["num_experts_per_tok"],
        norm_topk_prob=cfgd["norm_topk_prob"], max_seq_len=max_context,
        rms_eps=cfgd["rms_norm_eps"], rope_base=float(cfgd["rope_theta"]),
        block_length=int(traffic["block_length"]),
        denoising_steps=int(traffic["denoising_steps"]),
        mask_token_id=min(151669, cfgd["vocab_size"] - 1),
        initializer_range=cfgd["initializer_range"],
        param_dtype=cfgd["param_dtype"])
    paddle.seed(seed % (2 ** 31))
    model = SdarMoeForCausalLM(cfg)
    model.eval()
    return model, cfg


def _commit_probe(eng):
    """A twin of the tick's commit forward that also returns slot 0's
    logits: the same view over the engine's own pools at the tick's
    shapes, the pools threaded and donated as the engine threads them."""
    import jax

    def commit(param_vals, pools, tables, seq_lens, toks):
        logits, pools = eng._forward(param_vals)(
            toks, pools, tables, seq_lens, seq_lens[:, None],
            eng._chunk_view_cls)
        return pools, logits[0].astype("float32")

    donate = () if jax.default_backend() == "cpu" else (1,)
    return jax.jit(commit, donate_argnums=donate)


def _replay(eng, probe, prompt, out):
    """Run a served request again through the engine's own chunk programs
    and the commit probe, in slot 0 of an otherwise idle batch.  Returns
    the logits `[n_blocks * L, V]` of every whole block from the prompt's
    last whole block on."""
    import jax.numpy as jnp
    import numpy as np
    Lb = eng.gen.block_length
    seq = list(prompt) + list(out)
    seq = seq[:len(seq) // Lb * Lb]
    p0 = len(prompt) // Lb * Lb
    need = -(-len(seq) // eng.bs)
    if need > len(eng.free_blocks):       # the prefix index gives way
        eng.prefix.evict(need - len(eng.free_blocks), eng._release_block,
                         lambda b: int(eng.block_rc[b]) == 1)
    blocks = [eng._alloc_block() for _ in range(need)]
    table = np.zeros((eng.B, eng.nb_per_seq), np.int32)
    table[0, :len(blocks)] = blocks
    got = []
    with eng._params_for_call() as param_vals:
        for off in range(0, p0, eng.chunk):
            n = min(eng.chunk, p0 - off)
            L_pad = eng._pad_bucket(n)
            ids = np.zeros((1, L_pad), np.int32)
            ids[0, :n] = seq[off:off + n]
            _, eng.pools = eng._prefill_cont_program(L_pad)(
                param_vals, eng.pools, jnp.asarray(table[:1]),
                jnp.asarray(ids), jnp.int32(n), jnp.int32(off))
        for off in range(p0, len(seq), Lb):
            lens = np.zeros((eng.B,), np.int32)
            toks = np.zeros((eng.B, Lb), np.int32)
            lens[0], toks[0] = off, seq[off:off + Lb]
            eng.pools, lg = probe(param_vals, eng.pools, jnp.asarray(table),
                                  jnp.asarray(lens), jnp.asarray(toks))
            got.append(np.asarray(lg))
    for b in blocks:
        eng._release_block(b)
    return p0, seq, np.concatenate(got)


def block_states(prompt, out, steps, Lb: int, n_steps: int, mask_id: int):
    """The states the engine's blocks went through, from what it recorded:
    `[(start, tokens before forward j [Lb], masked [Lb] bool, revealed by
    forward j [Lb] bool, the finished block [Lb])]`, one a denoising
    forward that revealed something, for every whole block of new tokens
    (a last block the budget cut is left out)."""
    import numpy as np
    P = len(prompt)
    p0 = P // Lb * Lb
    seq = np.asarray(list(prompt) + list(out))
    step_of = np.asarray([-1] * P + list(steps))
    states = []
    for start in range(p0, len(seq) // Lb * Lb, Lb):
        final, when = seq[start:start + Lb], step_of[start:start + Lb]
        for j in range(n_steps):
            masked = when >= j
            if not (when == j).any():
                continue
            states.append((start, np.where(masked, mask_id, final), masked,
                           when == j, final))
    return states


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
    model, cfg = build_model(cfgd, mix, int(wl["max_context"]), ctx.seed)
    xray.reset()
    eng = ServingEngine(model, max_batch=int(wl["max_batch"]),
                        max_context=int(wl["max_context"]),
                        block_size=int(wl["block_size"]),
                        num_blocks=int(wl["num_blocks"]),
                        prefill_chunk=int(wl["prefill_chunk"]),
                        pad_buckets=wl["pad_buckets"], prefix_cache=True)
    t0 = ctx.part("build", t0)
    info = eng.warmup()
    t0 = ctx.part("warm_up", t0)
    pool_bytes = sum(p.size * p.dtype.itemsize
                     for layer in eng.pools for p in layer)
    n_params = model.num_params()
    gen = eng.gen
    ctx.say(f"model sdar_moe: {cfg.num_layers} layers x {cfg.hidden_size}, "
            f"{cfg.num_heads} heads over {cfg.num_kv_heads} kv heads of "
            f"{cfg.head_dim}, {cfg.n_experts_held} of {cfg.num_experts} "
            f"experts of {cfg.moe_intermediate_size}, vocabulary "
            f"{cfg.vocab_size}; {n_params / 1e6:.1f}M parameters "
            f"({n_params * 2 / 2**30:.2f} GiB bf16); blocks of "
            f"{gen.block_length} in {gen.denoising_steps} denoising "
            f"forwards and a commit, [MASK] {gen.mask_token_id}")
    ctx.say(f"engine: batch {eng.B}, context {eng.max_context}, "
            f"{eng.num_blocks} blocks of {eng.bs}, pools "
            f"{pool_bytes / 2**30:.2f} GiB "
            f"({[(r.name, r.shape(eng.num_blocks, eng.bs)) for r in eng.cache.rows]}), "
            f"chunk {eng.chunk}, ladder {list(eng.pad_ladder)}; warm-up "
            f"{info['programs']} programs ({info['aot_programs']} AOT) in "
            f"{info['warmup_s']:.1f} s")

    # ---- the schedule, fixed before the run
    rate = float(wl["rate_rps"])
    lead, drain_s = float(mix["lead_in_s"]), float(mix["drain_s"])
    span = lead + ctx.seconds
    plan = openloop.request_schedule(mix, rate, lead, ctx.seconds, ctx.seed,
                                     cfg.vocab_size)
    reqs = []
    for p in plan:
        # the whole vocabulary less [MASK]
        p["prompt"] = [0 if t == gen.mask_token_id else t
                       for t in p["prompt"]]
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
            # the expert layers' counts as the last harvested tick left
            # them on the host: the traced window's, to within a tick
            before = eng.cache_state()
            with ctx.profile():
                time.sleep(max(0.0, t_close - time.perf_counter()))
                box["traced_rows"] = (before, eng.cache_state())
        except BaseException as e:  # noqa: BLE001 - re-raised by run()
            box["error"] = e

    def clock():
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
    ctx.say(f"sample: {summ['n']} requests due in the window, "
            f"{summ['failed']} failed; TTFT p50 {summ['ttft_p50_ms']:.1f} "
            f"p90 {summ['ttft_ms']:.1f} ms; TPOT p50 "
            f"{summ['tpot_p50_ms']:.2f} p90 {summ['tpot_ms']:.2f} ms; "
            f"{delivered} tokens in the window = {tokens_per_s:.1f} tokens/s")
    ctx.say(f"generator lag p90 {summ.get('gen_lag_ms', float('nan')):.3f} "
            f"ms; queue wait p90 "
            f"{summ.get('queue_wait_ms', float('nan')):.1f} ms; ticks "
            f"{st['ticks']} ({st['steps']} forwards), prefill chunks "
            f"{st['prefill_chunks']}, prefix hits "
            f"{st['prefix_cache']['hits']}, sheds {st['slo_sheds']}, "
            f"rejected {len(box['rejected'])}")
    ctx.say(f"(waiting, running) each second of the window: "
            f"{box.get('depth')}")
    counters = {"ttft_p90_ms": summ["ttft_ms"],
                "tpot_p90_ms": summ["tpot_ms"],
                "tpot_p50_ms": summ["tpot_p50_ms"],
                "queue_wait_p90_ms": summ.get("queue_wait_ms"),
                "gen_lag_p90_ms": summ.get("gen_lag_ms")}
    rows = st.get("cache_state", {}).get("moe_rows")
    if rows is not None:
        publish_expert_rows(list(rows), cfg.expert_offset)
        # the reducers read the TRACED window's counts beside its times
        a, b = box.get("traced_rows", ({}, {}))
        if a and b:
            counters["decode_steps"] = b["steps"] - a["steps"]
            counters["moe_rows"] = (b["moe_rows"] - a["moe_rows"]).tolist()
        n = max(1, st["steps"])
        ctx.say(f"experts, a forward and layer of the block ticks over the "
                f"run (running sequences only): "
                f"{rows[:, 0, 0].sum(-1).mean() / n:.1f} rows over "
                f"{cfg.n_experts_held} experts, "
                f"{rows[:, 0, 1].sum(-1).mean() / n:.1f} experts hit")

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


def _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records):
    """Parts (a), (b) and (c) of `correct` (module docstring) on sampled
    finished requests, and the controls asked for."""
    import numpy as np
    from benchmark.reference import sdar_moe_ref as ref
    t_ref = time.perf_counter()
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    gen = eng.gen
    Lb, n_steps, M = gen.block_length, gen.denoising_steps, \
        gen.mask_token_id
    fit = [i for i, rec in zip(sample, records) if rec["finished"]
           and len(plan[i]["prompt"]) + plan[i]["max_new_tokens"]
           <= CHECK_MAX_TOKENS]
    picked = [fit[j] for j in
              rng.permutation(len(fit))[:int(ctx.workload["check_requests"])]]
    controls = [c for c in (ctx.workload.get("control") or "").split("+")
                if c]
    if set(controls) - set(CONTROLS):
        raise ValueError(f"control {controls}: not among {CONTROLS}")
    # (c)'s replay first: it runs over the engine's pools, whose memory
    # the reference then takes
    probe = _commit_probe(eng)
    replayed = {i: _replay(eng, probe, plan[i]["prompt"],
                           reqs[i].output_ids) for i in picked}
    eng.pools = None
    dims = ref.dims_of(dict(cfgd, expert_offset=cfg.expert_offset), Lb)
    sd = {k: v._value for k, v in model.state_dict().items()}
    params = ref.from_state_dict(sd, cfg.num_layers)
    low = None
    if "float8" in controls:
        import ml_dtypes
        low = ml_dtypes.float8_e4m3fn

    # the largest reading of each kind, and the sums for the means
    worst = {"gap": 0.0, "conf": 0.0, "logit": 0.0, "gap_sum": 0.0,
             "conf_sum": 0.0}
    ctrl = {"gap": 0.0, "conf": 0.0, "logit": 0.0, "pos": 0.0,
            "gap_sum": 0.0, "conf_sum": 0.0, "pos_sum": 0.0}
    n_states = n_tokens = same = 0
    for i in picked:
        prompt, out = plan[i]["prompt"], reqs[i].output_ids
        p0, seq, served = replayed[i]
        ids = np.asarray(seq + [0] * (-len(seq) % REF_PAD), np.int32)
        want = np.arange(p0, len(seq))
        lg, kv = ref.forward(params, ids, dims, positions=want,
                             return_kv=True)
        worst["logit"] = max(worst["logit"],
                             float(np.abs(np.asarray(lg) - served).max()))
        states = block_states(prompt, out, reqs[i].reveal_steps, Lb,
                              n_steps, M)
        if low is not None:
            lq, kvq = ref.forward(params, ids, dims, positions=want,
                                  operand_dtype=low, return_kv=True)
            ctrl["logit"] = max(ctrl["logit"], float(
                np.abs(np.asarray(lg) - np.asarray(lq)).max()))
        for at in range(0, len(states), STATES_AT_ONCE):
            some = states[at:at + STATES_AT_ONCE]
            pad = STATES_AT_ONCE - len(some)
            toks = np.stack([s[1] for s in some] + [some[0][1]] * pad)
            starts = np.asarray([s[0] for s in some] + [some[0][0]] * pad)
            logits = ref.block_logits(params, toks, starts, kv, dims)
            x0, logc = (np.asarray(a) for a in ref.confidence(logits))
            logits = np.asarray(logits)
            if low is not None:
                xq, cq = (np.asarray(a) for a in ref.confidence(
                    ref.block_logits(params, toks, starts, kvq, dims,
                                     operand_dtype=low)))
            for k, (start, _, masked, shown, final) in enumerate(some):
                best = logits[k].max(-1)
                top_c = logc[k][masked].max()
                for pos in np.nonzero(shown)[0]:
                    gap = float(best[pos] - logits[k, pos, final[pos]])
                    short = float(top_c - logc[k, pos])
                    worst["gap"] = max(worst["gap"], gap)
                    worst["conf"] = max(worst["conf"], short)
                    worst["gap_sum"] += gap
                    worst["conf_sum"] += short
                    same += int(gap == 0.0)
                    n_tokens += 1
                # by_position: the lowest masked position stands in
                first = int(np.nonzero(masked)[0][0])
                short = float(top_c - logc[k, first])
                ctrl["pos"] = max(ctrl["pos"], short)
                ctrl["pos_sum"] += short
                if low is not None:
                    # float8: its choice of position, its token there
                    pq = int(np.argmax(np.where(masked, cq[k], -np.inf)))
                    gap = float(best[pq] - logits[k, pq, xq[k, pq]])
                    short = float(top_c - logc[k, pq])
                    ctrl["gap"] = max(ctrl["gap"], gap)
                    ctrl["conf"] = max(ctrl["conf"], short)
                    ctrl["gap_sum"] += gap
                    ctrl["conf_sum"] += short
            n_states += len(some)
    took = time.perf_counter() - t_ref
    what = (f"on {len(picked)} sampled requests ({n_states} denoising "
            f"forwards that revealed {n_tokens} tokens)")
    gap_mean = worst["gap_sum"] / max(n_tokens, 1)
    conf_mean = worst["conf_sum"] / max(n_tokens, 1)
    ctx.check(picked and worst["gap"] <= GAP_TOL
              and gap_mean <= GAP_MEAN_TOL,
              f"(a) {what} every revealed token's reference logit is the "
              f"reference's best at its position and forward to within "
              f"{worst['gap']:.4f} (<= {GAP_TOL}; {same} identical), "
              f"{gap_mean:.5f} on average (<= {GAP_MEAN_TOL})")
    ctx.check(picked and worst["conf"] <= CONF_TOL
              and conf_mean <= CONF_MEAN_TOL,
              f"(b) every revealed position's reference log-confidence is "
              f"the best masked position's to within {worst['conf']:.4f} "
              f"(<= {CONF_TOL}), {conf_mean:.5f} on average "
              f"(<= {CONF_MEAN_TOL})")
    ctx.check(picked and worst["logit"] <= LOGIT_TOL,
              f"(c) prefill chunks and block commits through the kv-head "
              f"cache give the reference's full-forward logits of every "
              f"whole block to within {worst['logit']:.4f} (<= {LOGIT_TOL});"
              f" the check took {took:.1f} s")
    if low is not None:
        c_gap = ctrl["gap_sum"] / max(n_states, 1)
        c_conf = ctrl["conf_sum"] / max(n_states, 1)
        ctx.check(ctrl["gap"] <= GAP_TOL and c_gap <= GAP_MEAN_TOL
                  and ctrl["conf"] <= CONF_TOL and c_conf <= CONF_MEAN_TOL
                  and ctrl["logit"] <= LOGIT_TOL,
                  f"CONTROL float8, which must fail: the reference with "
                  f"every matmul operand rounded to float8_e4m3, read as "
                  f"the program is: (a) {ctrl['gap']:.4f} (<= {GAP_TOL}), "
                  f"{c_gap:.5f} on average (<= {GAP_MEAN_TOL}), "
                  f"(b) {ctrl['conf']:.4f} (<= {CONF_TOL}), {c_conf:.5f} "
                  f"on average (<= {CONF_MEAN_TOL}), (c) "
                  f"{ctrl['logit']:.4f} (<= {LOGIT_TOL})")
    if "by_position" in controls:
        c_pos = ctrl["pos_sum"] / max(n_states, 1)
        ctx.check(ctrl["pos"] <= CONF_TOL and c_pos <= CONF_MEAN_TOL,
                  f"CONTROL by_position, which must fail (b): revealing the "
                  f"lowest masked position of each forward, its reference "
                  f"log-confidence falls {ctrl['pos']:.4f} short of the "
                  f"best masked position's (<= {CONF_TOL}), {c_pos:.5f} on "
                  f"average (<= {CONF_MEAN_TOL})")
