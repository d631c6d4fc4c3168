"""serve_mtp.py — the job of a one-chip serving cell whose model drafts for
itself through its multi-token-prediction module (`glm4_moe_lite`:
GLM-4.7-Flash's block) and whose requests share long prefixes:
`ServingEngine` in this process under an open loop, as `serve_latent.py`
runs it (same `Sink`, `latency`, `openloop`, same threads and window, the
shared prefixes prefilled once in set-up so that in the window they are
prefix-cache hits), with the model built in bfloat16 from the
configuration's keys as one pipeline stage that holds every expert and the
whole vocabulary, and the drafter on (`mtp_draft` of the workload file).

`correct`: every request due in the window finished with the tokens it
asked for; no tick failed; no compile inside the window; every kernel
claim of the tick and chunk programs is a Mosaic custom call and the tick
holds `paged_latent_attention`; and, on `check_requests` finished requests
of the shortest shared prefix (so that one reference compile serves them),
against the plain float32 full forward of `reference/glm4_moe_lite_ref.py`
over prefix + turn + output:

(a) every EMITTED token's reference logit is within `GAP_TOL` of the
    reference's best at its position (largest; the mean is printed);
(b) every DRAFT the timed ticks judged has a reference-module logit
    within `DRAFT_TOL` of the reference module's best at its position,
    the module fed the reference's own `h_i` (a drafter fed a stale or
    shifted hidden state fails here);
(c) the logits of the whole request, REPLAYED after the window through
    the engine's public probe over its own pools (the prefix hit with its
    copied block, the turn's chunks, every verify forward of the timed
    run with the draft it judged, the kernel), and the module's beside
    them, are within `LOGIT_TOL` of the reference's everywhere;
(d) exact integers, on every finished request of the sample: tokens
    emitted = 1 (the prefill's) + forwards + second tokens, and an
    accepted draft equals the token emitted at its position.

(a), (b) and (d) judge what the TIMED path produced: a request keeps the
drafts its forwards judged, their accept flags and emitted counts as it
is served (`Request.draft_log`).  (c) is a replay.

`control` (a key of the workload file, empty in the cell): `float8` — the
reference with every matmul operand rounded to float8_e4m3 stands in for
the program (its greedy tokens, its drafts, its logits); `stale_hidden` —
the reference's module fed `h_{i-1}` drafts in the program's place.  Each
is read through the same comparison and must come out `correct: false` by
its own line.
"""

from __future__ import annotations

import threading
import time

# Served: bf16 weights, activations and latent rows, float32 accumulation;
# reference: the same bf16 weights, float32 "highest".  Logits have a
# spread near 1.  Two readings set each limit (PERF.md section 4; my chip
# runs, PR 34): the largest the change gave over its 17 runs and seeds, and
# what the reference gives with every matmul operand rounded to
# float8_e4m3 (and, for the drafts, with its module fed h_(i-1)), which
# must fail: by (c) for certain, the steadiest of the three readings.
GAP_TOL = 0.08         # (a): program 0.0006-0.0377; float8 0.1017, 0.1315
DRAFT_TOL = 0.06       # (b): program 0.0003-0.0282; float8 0.0825, 0.104;
                       #      stale_hidden 0.871
LOGIT_TOL = 0.14       # (c): program 0.075-0.100 (module 0.057-0.098);
                       #      float8 0.197, 0.184
REF_PAD = 1024         # the reference's sequence lengths are multiples
CONTROLS = ("float8", "stale_hidden")


def build_model(cfgd: dict, max_context: int, seed: int, draft: bool):
    """The configuration's stage of the model, in its `param_dtype`
    (bfloat16, cast a block at a time as it is created; a rehearsal's
    tiny model is float32)."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                 Glm4MoeLiteForCausalLM)
    cfg = Glm4MoeLiteConfig(
        vocab_size=cfgd["vocab_size"], hidden_size=cfgd["hidden_size"],
        num_layers=cfgd["num_layers"],
        num_heads=cfgd["num_attention_heads"],
        q_lora_rank=cfgd["q_lora_rank"], kv_lora_rank=cfgd["kv_lora_rank"],
        qk_nope_head_dim=cfgd["qk_nope_head_dim"],
        qk_rope_head_dim=cfgd["qk_rope_head_dim"],
        v_head_dim=cfgd["v_head_dim"],
        intermediate_size=cfgd["intermediate_size"],
        moe_intermediate_size=cfgd["moe_intermediate_size"],
        n_routed_experts=cfgd["n_routed_experts"],
        n_shared_experts=cfgd["n_shared_experts"],
        num_experts_per_tok=cfgd["num_experts_per_tok"],
        routed_scaling_factor=cfgd["routed_scaling_factor"],
        first_k_dense_replace=cfgd["first_k_dense_replace"],
        num_nextn_predict_layers=cfgd["num_nextn_predict_layers"],
        mtp_draft=bool(draft), max_seq_len=max_context,
        rms_eps=cfgd["rms_norm_eps"], rope_base=float(cfgd["rope_theta"]),
        param_dtype=cfgd["param_dtype"])
    paddle.seed(seed % (2 ** 31))
    model = Glm4MoeLiteForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed % (2 ** 32))
    for name, p in model.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = jnp.asarray(rng.uniform(-0.1, 0.1, p.shape),
                                   p._value.dtype)
    return model, cfg


def _replay(eng, prompt, out, draft_log, n_prefix, ref_logits, ref_module):
    """(c): serve the request again through `eng.probe`, as the engine
    did: the prefix's whole blocks from the prefix cache (the last one
    copied, its last token recomputed), the rest of the prompt as chunks
    of `eng.chunk`, then every verify forward of the timed run over (last
    token, the draft it judged) in slot 0 of an otherwise idle batch.
    `ref_logits(pos)` / `ref_module(pos)` give the reference's rows at
    absolute positions.  Returns the largest |logit - reference| of the
    model's and of the module's rows (compared on the device)."""
    import jax.numpy as jnp
    import numpy as np
    bs, C, L = eng.bs, eng.chunk, len(prompt)
    chain = list(eng.prefix.lookup(prompt).blocks)[:n_prefix // bs]
    if len(chain) != n_prefix // bs:
        raise RuntimeError(f"the prefix cache holds {len(chain)} of the "
                           f"prefix's {n_prefix // bs} blocks")
    start = len(chain) * bs - 1 if chain else 0
    total = L + len(out)
    fresh = eng.take_blocks(-(-(total + 1) // bs) - max(0, len(chain) - 1))
    table = np.zeros((eng.B, eng.nb_per_seq), np.int32)
    if chain:
        eng.copy_block(chain[-1], fresh[0])
        table[0, :len(chain) - 1] = chain[:-1]
        table[0, len(chain) - 1:len(chain) - 1 + len(fresh)] = fresh
    else:
        table[0, :len(fresh)] = fresh
    stream = np.asarray(list(prompt) + list(out), np.int32)
    worst, mworst = 0.0, 0.0

    def far(got, pos, ref_rows):
        return float(jnp.abs(got - ref_rows(np.asarray(pos))).max())

    for off in range(start, L, C):
        n = min(C, L - off)
        ids = np.zeros((1, C), np.int32)
        nxt = np.zeros((1, C), np.int32)
        ids[0, :n] = stream[off:off + n]
        nxt[0, :n] = stream[off + 1:off + n + 1]
        got = eng.probe(ids, table[:1], [off], chunk=True, next_ids=nxt)
        at = np.arange(off, off + n)
        worst = max(worst, far(got["logits"][:n], at, ref_logits))
        if "draft_logits" in got:
            mworst = max(mworst, far(got["draft_logits"][:n], at, ref_module))
    n = L                                # the last token's position
    for d, _, c in draft_log:
        if n + 1 >= total:
            break
        ids = np.zeros((eng.B, 2), np.int32)
        nxt = np.zeros((eng.B, 2), np.int32)
        lens = np.zeros((eng.B,), np.int32)
        ids[0], lens[0] = (stream[n], d), n
        nxt[0, :c] = stream[n + 1:n + 1 + c]
        got = eng.probe(ids, table, lens, next_ids=nxt)
        at = np.arange(n, n + c)
        worst = max(worst, far(got["logits"][:c], at, ref_logits))
        mworst = max(mworst, far(got["draft_logits"][:c], at, ref_module))
        n += c
    eng.give_blocks(fresh)
    return worst, mworst


def run(ctx) -> dict:
    import jax
    import numpy as np
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        publish_expert_rows
    from paddle_tpu.observability import xray
    from benchmark import latency
    from benchmark.jobs.serve_engine import Sink
    from benchmark.jobs.serve_latent import shared_documents
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
                        pad_buckets=wl["pad_buckets"], prefix_cache=True)
    drafting = eng.mtp is not None
    t0 = ctx.part("build", t0)
    info = eng.warmup()
    t0 = ctx.part("warm_up", t0)
    pool_bytes = sum(p.size * p.dtype.itemsize
                     for layer in eng.pools for p in layer)
    n_params = model.num_params()
    ctx.say(f"model glm4_moe_lite: {cfg.num_layers} layers x "
            f"{cfg.hidden_size} + {cfg.num_nextn_predict_layers} MTP module, "
            f"{cfg.n_experts_held} experts, vocabulary {cfg.vocab_size}; "
            f"{n_params / 1e6:.1f}M parameters "
            f"({n_params * 2 / 1e9:.2f} GB bf16); drafter "
            f"{'on (depth 1)' if drafting else 'off'}")
    ctx.say(f"engine: batch {eng.B}, context {eng.max_context}, "
            f"{eng.num_blocks} blocks of {eng.bs}, pools "
            f"{pool_bytes / 1e9:.2f} GB "
            f"({[r.name for r in eng.cache.rows]}), chunk {eng.chunk}, "
            f"ladder {list(eng.pad_ladder)}; warm-up {info['programs']} "
            f"programs ({info['aot_programs']} AOT) in "
            f"{info['warmup_s']:.1f} s")

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
    docs = shared_documents(plan, eng.bs)
    ctx.say(f"open loop: {rate:g} req/s, {len(plan)} requests over "
            f"{span:g} s ({lead:g} s lead-in + {ctx.seconds:g} s window); "
            f"{len(docs)} shared prefixes of {[len(d) for d in docs]} "
            f"tokens; prompt tokens {sum(len(p['prompt']) for p in plan)}, "
            f"output tokens {sum(p['max_new_tokens'] for p in plan)}")
    t0 = ctx.part("schedule", t0)

    # ---- the prefixes, prefilled once: set-up, as a deployment that
    # keeps its agents' prompts warm; the prefix cache holds them after
    for d in docs:
        eng.add_request(Request(d + d[:1], max_new_tokens=1))
    eng.run()
    st0 = eng.stats()
    warm = st0["prefix_cache"]
    ctx.say(f"prefixes prefilled: {st0['prefill_chunks']} chunks, prefix "
            f"cache {warm['entries']} entries; "
            f"{time.perf_counter() - t0:.1f} s")
    state0 = st0.get("cache_state", {})
    steps0, chunks0 = st0["steps"], st0["prefill_chunks"]
    hit0 = warm["hit_tokens"]
    ctx.part("prefixes", t0)

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
            # the device-side counts as the last harvested tick left them
            # on the host: the traced window's, to within a tick.  Both
            # are read before the profiler stops: writing the trace out
            # takes it a minute or more while the engine goes on decoding
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
    pc = st["prefix_cache"]
    asked = sum(len(p["prompt"]) for p in plan)
    hit_pct = 100.0 * (pc["hit_tokens"] - hit0) / max(1, asked)
    ctx.say(f"sample: {summ['n']} requests due in the window, "
            f"{summ['failed']} failed; TTFT p50 {summ['ttft_p50_ms']:.1f} "
            f"p90 {summ['ttft_ms']:.1f} ms; TPOT p50 "
            f"{summ['tpot_p50_ms']:.2f} p90 {summ['tpot_ms']:.2f} ms; "
            f"{delivered} tokens in the window = {tokens_per_s:.1f} tokens/s")
    ctx.say(f"generator lag p90 {summ.get('gen_lag_ms', float('nan')):.3f} "
            f"ms; queue wait p90 "
            f"{summ.get('queue_wait_ms', float('nan')):.1f} ms; ticks "
            f"{st['ticks']}, prefill chunks {st['prefill_chunks'] - chunks0}"
            f", prefix hits {pc['hits']} ({hit_pct:.1f}% of the prompt "
            f"tokens asked came from the cache), sheds {st['slo_sheds']}, "
            f"rejected {len(box['rejected'])}; pool blocks in use at the "
            f"end {eng.num_blocks - len(eng.free_blocks)} of "
            f"{eng.num_blocks}")
    ctx.say(f"(waiting, running) each second of the window: "
            f"{box.get('depth')}")
    counters = {"ttft_p90_ms": summ["ttft_ms"],
                "tpot_p90_ms": summ["tpot_ms"],
                "tpot_p50_ms": summ["tpot_p50_ms"],
                "queue_wait_p90_ms": summ.get("queue_wait_ms"),
                "gen_lag_p90_ms": summ.get("gen_lag_ms"),
                "prefix_hit_token_pct": hit_pct}
    run_steps = max(1, st["steps"] - steps0)
    state = st.get("cache_state", {})
    if "moe_rows" in state:
        grown = list(state["moe_rows"] - state0.get("moe_rows", 0))
        publish_expert_rows(grown, 0)
        # the reducers read the TRACED window's counts beside its times
        a, b = box.get("traced", ({}, {}))
        if a and b:
            counters["decode_steps"] = b["steps"] - a["steps"]
            counters["moe_rows"] = (b["moe_rows"] - a["moe_rows"]).tolist()
            counters["mtp"] = (b["mtp"] - a["mtp"])[-1].tolist()
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
        tick = "serving.mtp_tick" if drafting else "serving.tick"
        for prog in (tick, "serving.prefill_cont"):
            got = [tuple(c) for row in cov
                   if row["program"].startswith(prog) for c in row["claims"]]
            ctx.check(got and all(m == "custom_call" for _, m in got)
                      and (prog != tick or ("paged_latent_attention",
                                            "custom_call") in got),
                      f"{prog}*: every kernel claim is a Mosaic custom call "
                      f"{sorted(set(got))}")
    _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records, docs, drafting)
    return {"attempted": summ["n"],
            "failed": summ["failed"],
            "metrics": {"serve_tpot_p90_ms": summ["tpot_ms"],
                        "serve_tokens_per_s": tokens_per_s},
            "counters": counters}


def _accounts(req) -> bool:
    """(d) for one finished request: exact integers."""
    out, at = req.output_ids, 1
    for d, a, c in req.draft_log:
        if at >= len(out) or a != (d == out[at]) or c not in (1, 1 + a):
            return False
        at += c
    return at == len(out)


def _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records, docs, drafting):
    """Parts (a) to (d) of `correct` (module docstring), on finished
    requests of the shortest shared prefix, and the controls asked for."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import glm4_moe_lite_ref as ref
    t_ref = time.perf_counter()
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    doc = docs[-1] if docs else []
    done = [i for i, rec in zip(sample, records) if rec["finished"]]
    fit = [i for i in done if plan[i]["prompt"][:len(doc)] == doc]
    picked = [fit[j] for j in
              rng.permutation(len(fit))[:int(ctx.workload["check_requests"])]]
    controls = [c for c in (ctx.workload.get("control") or "").split("+")
                if c]
    if set(controls) - set(CONTROLS):
        raise ValueError(f"control {controls}: not among {CONTROLS}")
    if drafting:
        bad = [i for i in done if not _accounts(reqs[i])]
        forwards = sum(len(reqs[i].draft_log) for i in done)
        second = sum(c - 1 for i in done for _, _, c in reqs[i].draft_log)
        ctx.check(done and not bad,
                  f"(d) on all {len(done)} finished requests of the sample "
                  f"the integers add up: {sum(len(reqs[i].output_ids) for i in done)}"
                  f" tokens = {len(done)} from prefill + {forwards} verify "
                  f"forwards + {second} second tokens, and every accepted "
                  f"draft is the token emitted at its position "
                  f"({len(bad)} requests do not)")
    dims = ref.dims_of(dict(cfgd, rope_parameters={
        "rope_theta": cfgd["rope_theta"]}))
    sd = {k: v._value for k, v in model.state_dict().items()}
    params = ref.from_state_dict(sd, cfg.num_layers)
    # the reference first (float32 rows of the sampled requests stay on
    # the device for the replay to be compared with), one request a time
    worst = mean = mworst = 0.0
    same = total = n_drafts = 0
    far = mfar = 0.0
    low = {"gap": 0.0, "draft": 0.0, "logit": 0.0}
    stale_gap = 0.0
    for i in picked:
        prompt, out = plan[i]["prompt"], list(reqs[i].output_ids)
        log = reqs[i].draft_log
        L = len(prompt)
        seq = np.asarray(prompt + out, np.int32)
        padded = np.concatenate([seq, np.zeros(
            (-len(seq)) % min(REF_PAD, cfg.max_seq_len), np.int32)])
        start = (len(doc) // eng.bs) * eng.bs - 1 if doc else 0
        start = max(start, 0)
        want = np.arange(start, len(seq) - 1)
        h = ref.hidden(params, padded, dims)
        lg = ref.logits_of(params, h[want])                 # [n, V] f32
        at = np.arange(L - 1, L - 1 + len(out)) - start     # output rows
        lo = np.asarray(lg[at])
        gaps = lo.max(-1) - lo[np.arange(len(out)), out]
        worst = max(worst, float(gaps.max()))
        mean += float(gaps.sum())
        same += int((gaps == 0).sum())
        total += len(out)
        mlg = None
        if drafting:
            mlg = ref.module_logits(params, h, padded, dims, positions=want)
            # the draft judged by the forward whose last token stands at
            # position n was made at position n - 1
            pos, n = [], L
            for _, _, c in log:
                pos.append(n - 1 - start)
                n += c
            drafts = np.asarray([d for d, _, _ in log])
            md = np.asarray(mlg[np.asarray(pos)])
            mgaps = md.max(-1) - md[np.arange(len(drafts)), drafts]
            mworst = max(mworst, float(mgaps.max()))
            n_drafts += len(drafts)
            if "stale_hidden" in controls:
                ms = np.asarray(ref.module_logits(
                    params, h, padded, dims, positions=want[pos],
                    stale=True))
                stale_gap = max(stale_gap, float(
                    (md.max(-1) - md[np.arange(len(drafts)),
                                     ms.argmax(-1)]).max()))

        def rows(table):
            return lambda p: table[jnp.asarray(p - start)]

        a, b = _replay(eng, prompt, out, log, len(doc), rows(lg),
                       rows(mlg) if drafting else None) if drafting \
            else (0.0, 0.0)
        far, mfar = max(far, a), max(mfar, b)
        if "float8" in controls:
            import ml_dtypes
            f8 = ml_dtypes.float8_e4m3fn
            hq = ref.hidden(params, padded, dims, operand_dtype=f8)
            lq = ref.logits_of(params, hq[want], operand_dtype=f8)
            low["logit"] = max(low["logit"], float(jnp.abs(lq - lg).max()))
            lqo = np.asarray(lq[at])
            low["gap"] = max(low["gap"], float(
                (lo.max(-1) - lo[np.arange(len(out)), lqo.argmax(-1)]).max()))
            if drafting:
                mq = np.asarray(ref.module_logits(
                    params, hq, padded, dims, positions=want[pos],
                    operand_dtype=f8))
                low["draft"] = max(low["draft"], float(
                    (md.max(-1) - md[np.arange(len(drafts)),
                                     mq.argmax(-1)]).max()))
            del hq, lq
        del h, lg, mlg
    took = time.perf_counter() - t_ref
    where = (f"{len(picked)} sampled requests of the {len(doc)}-token "
             f"prefix ({total} tokens through the prefix hit, the turn's "
             f"chunks, the verify forwards and the latent kernel)")
    ctx.check(picked and worst <= GAP_TOL,
              f"(a) on {where} every emitted token is the reference's best "
              f"to within {worst:.4f} logits (<= {GAP_TOL}; mean "
              f"{mean / max(1, total):.5f}; {same} identical)")
    if drafting:
        ctx.check(picked and mworst <= DRAFT_TOL,
                  f"(b) every one of the {n_drafts} drafts the timed ticks "
                  f"judged is the reference module's best, fed the "
                  f"reference's own hidden states, to within {mworst:.4f} "
                  f"logits (<= {DRAFT_TOL})")
        ctx.check(picked and max(far, mfar) <= LOGIT_TOL,
                  f"(c) REPLAYED after the window through the engine's "
                  f"probe over its own pools, every logit of the model "
                  f"is within {far:.4f} of the reference's and of the "
                  f"module within {mfar:.4f} (<= {LOGIT_TOL}); the check "
                  f"took {took:.1f} s")
    if "float8" in controls:
        ctx.check(low["gap"] <= GAP_TOL and low["draft"] <= DRAFT_TOL
                  and low["logit"] <= LOGIT_TOL,
                  f"CONTROL float8, which must fail: the reference with "
                  f"every matmul operand rounded to float8_e4m3, read as "
                  f"the program is: its greedy tokens fall {low['gap']:.4f} "
                  f"logits short of the reference's best (<= {GAP_TOL}), "
                  f"its drafts {low['draft']:.4f} (<= {DRAFT_TOL}), its "
                  f"logits lie {low['logit']:.4f} off (<= {LOGIT_TOL})")
    if "stale_hidden" in controls:
        ctx.check(drafting and stale_gap <= DRAFT_TOL,
                  f"CONTROL stale_hidden, which must fail: the reference's "
                  f"module fed h_(i-1) in h_i's place drafts in the "
                  f"program's place: its drafts fall {stale_gap:.4f} logits "
                  f"short of the reference module's best (<= {DRAFT_TOL})")
