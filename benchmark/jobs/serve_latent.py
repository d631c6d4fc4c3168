"""serve_latent.py — the job of a one-chip serving cell whose model keeps
a latent cache with a sparse index (`glm_moe_dsa`: GLM-5's block) and
whose requests share long documents: `ServingEngine` in this process under
an open loop, as `serve_engine.py` runs it (same `Sink`, `latency`,
`openloop`, same threads and window), with three differences:

* the model is `GlmMoeDsaForCausalLM`, built directly in bfloat16 from the
  configuration's keys as one chip's share (`n_routed_experts` held of
  `router_width`, from `expert_offset`; a slice of the vocabulary);
* each shared document of the schedule is prefilled once during set-up
  (one request a document, one new token), so that in the window a
  request's document is a prefix-cache hit and only its question is
  prefilled — what a deployment that keeps its documents warm sees;
* `correct` also holds the sparse selection to the reference.

`correct`: every request due in the window finished with the tokens it
asked for; no tick failed; no compile inside the window; every kernel
claim of the tick and chunk programs is a Mosaic custom call; and, on
`check_requests` finished requests of the shortest document (so that one
reference compile serves them): (a) every served token's logit is within
`GAP_TOL` of the reference's best at its position, the reference being the
plain float32 full forward over document + question + output, so prefill,
the prefix hit, the latent cache and the selection are all held to the
mathematics; (b) the positions the program selects agree with the
reference's selected sets to at least `SELECT_TOL` on average, and every
query to at least `SELECT_MIN`.  The engine's programs return tokens, not
selections, so (b) replays the sampled request after the window over the
engine's own pools through twins of its two programs that do (`_probes`):
the question as chunks of `prefill_chunk` tokens at their offset through
the chunk view, then every served token as one decode step of the whole
batch through the decode view — the shapes, views and kernels of the
programs the window ran, selections returned beside the pools.

`control` (a key of the workload file, empty in the cell; `--set
control='"float8+topk_half"'`) reads what the limits are there to catch as
the program is read, through the same comparison, so the run must come out
`correct: false`: `float8` — the reference with every matmul operand
rounded to float8_e4m3 stands in for the program (its greedy tokens, its
selections); `topk_half` — the probes select half of `index_topk`.
"""

from __future__ import annotations

import threading
import time

# How far a served token's logit may fall short of the reference's best.
# Served: bf16 weights and activations, bf16 latent rows and index keys,
# float32 accumulation; reference: the same bf16 weights, float32
# "highest".  Logits have a spread near 1.  Two readings set each limit
# (PERF.md section 4): the largest the change gave over its seeds (gap
# 0.026; agreement 0.9925 mean, 0.952 least) and what the reference gives
# with every matmul operand rounded to float8_e4m3, which must fail (gap
# 0.389; agreement 0.892 mean, 0.8125 least).
GAP_TOL = 0.125
# Share of a query's selected positions that are also the reference's.
# bf16 index scores move a few of the 2,048 across the boundary; a
# selection of fewer tokens, or int8 / fp8 index keys, moves many.
SELECT_TOL = 0.97      # mean over the sampled queries and layers
SELECT_MIN = 0.90      # every single query
REF_PAD = 1024         # the reference's sequence lengths are multiples
CONTROLS = ("float8", "topk_half")


def build_model(cfgd: dict, max_context: int, seed: int):
    """The configuration's share of the model, in its `param_dtype`
    (bfloat16, cast a block at a time as it is created; a rehearsal's
    tiny model is float32)."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                               GlmMoeDsaForCausalLM)
    cfg = GlmMoeDsaConfig(
        vocab_size=cfgd["vocab_size"], hidden_size=cfgd["hidden_size"],
        num_layers=cfgd["num_layers"],
        num_heads=cfgd["num_attention_heads"],
        q_lora_rank=cfgd["q_lora_rank"], kv_lora_rank=cfgd["kv_lora_rank"],
        qk_nope_head_dim=cfgd["qk_nope_head_dim"],
        qk_rope_head_dim=cfgd["qk_rope_head_dim"],
        v_head_dim=cfgd["v_head_dim"], index_n_heads=cfgd["index_n_heads"],
        index_head_dim=cfgd["index_head_dim"],
        index_topk=cfgd["index_topk"],
        intermediate_size=cfgd["intermediate_size"],
        moe_intermediate_size=cfgd["moe_intermediate_size"],
        n_routed_experts=cfgd["router_width"],
        n_experts_held=cfgd["n_routed_experts"],
        expert_offset=cfgd["expert_offset"],
        n_shared_experts=cfgd["n_shared_experts"],
        num_experts_per_tok=cfgd["num_experts_per_tok"],
        routed_scaling_factor=cfgd["routed_scaling_factor"],
        first_k_dense_replace=cfgd["first_k_dense_replace"],
        max_seq_len=max_context, rms_eps=cfgd["rms_norm_eps"],
        rope_base=float(cfgd["rope_parameters"]["rope_theta"]),
        param_dtype=cfgd["param_dtype"])
    paddle.seed(seed % (2 ** 31))
    model = GlmMoeDsaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed % (2 ** 32))
    for name, p in model.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = jnp.asarray(rng.uniform(-0.1, 0.1, p.shape),
                                   p._value.dtype)
    return model, cfg


def shared_documents(plan: list, block: int) -> list:
    """The documents the schedule's prompts share, longest first: prompts
    that begin with the same block are one document's, and the document
    is their longest common prefix (`openloop` returns prompts only)."""
    import numpy as np
    groups = {}
    for p in plan:
        groups.setdefault(tuple(p["prompt"][:block]), []).append(p["prompt"])
    docs = []
    for prompts in groups.values():
        if len(prompts) < 2:
            continue             # asked once: it is prefilled when asked
        n = min(len(x) for x in prompts)
        a = np.asarray([x[:n] for x in prompts])
        differ = np.nonzero((a != a[0]).any(axis=0))[0]
        docs.append(prompts[0][:int(differ[0]) if len(differ) else n])
    return sorted(docs, key=len, reverse=True)


def _probes(eng, model):
    """Twins of the engine's chunk and tick programs that also return
    what each layer selected: the same views over the engine's own pools
    at the same shapes (a chunk of `eng.chunk` tokens of one sequence at
    an offset; one token a slot of the whole batch), the pools threaded
    and donated as the engine threads them.  `chunk` returns (pools,
    `[layers, eng.chunk, k]`); `step` (pools, slot 0's `[layers, k]`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.dygraph import no_grad
    from paddle_tpu.framework.tensor import Tensor

    def chunk(param_vals, pools, table_row, suffix, start):
        eng._bind_params(param_vals)
        views = eng._views(pools, table_row, jnp.reshape(start, (1,)),
                           eng._chunk_view_cls)
        with no_grad():
            _, new, sel = model.forward_selecting(Tensor._wrap(suffix),
                                                  views)
        return [c.pools for c in new], jnp.stack([a[0] for a in sel])

    def step(param_vals, pools, tables, seq_lens, last_tok):
        eng._bind_params(param_vals)
        views = eng._views(pools, tables, seq_lens)
        with no_grad():
            _, new, sel = model.forward_selecting(
                Tensor._wrap(last_tok[:, None]), views)
        return [c.pools for c in new], jnp.stack([a[0, 0] for a in sel])

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(chunk, donate_argnums=donate),
            jax.jit(step, donate_argnums=donate))


def _replay(eng, probes, prompt, out, n_doc):
    """Run a served request again through the probes: the document's
    whole blocks come from the prefix cache, as they did when it was
    served; the rest of the prompt runs as chunks, each served token but
    the last as a decode step in slot 0 of an otherwise idle batch.
    Returns (first replayed position, selections `[layers, queries, k]`
    of the queries from there to the last token fed)."""
    import jax.numpy as jnp
    import numpy as np
    chunk, step = probes
    bs, C, L = eng.bs, eng.chunk, len(prompt)
    blocks = list(eng.prefix.lookup(prompt).blocks)[:n_doc // bs]
    start = len(blocks) * bs
    fresh = [eng._alloc_block() for _ in
             range(-(-(L + len(out) - 1) // bs) - len(blocks))]
    table = np.zeros((eng.B, eng.nb_per_seq), np.int32)
    table[0, :len(blocks) + len(fresh)] = blocks + fresh
    sel, steps = [], []
    with eng._params_for_call() as param_vals:
        for off in range(start, L, C):
            n = min(C, L - off)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = prompt[off:off + n]
            eng.pools, got = chunk(param_vals, eng.pools,
                                   jnp.asarray(table[:1]), jnp.asarray(ids),
                                   jnp.int32(off))
            sel.append(np.asarray(got)[:, :n])
        for j, tok in enumerate(out[:-1]):
            lens = np.zeros((eng.B,), np.int32)
            last = np.zeros((eng.B,), np.int32)
            lens[0], last[0] = L + j, tok
            eng.pools, got = step(param_vals, eng.pools, jnp.asarray(table),
                                  jnp.asarray(lens), jnp.asarray(last))
            steps.append(got)
    if steps:
        sel.append(np.stack([np.asarray(g) for g in steps], 1))
    for b in fresh:
        eng._release_block(b)
    return start, np.concatenate(sel, 1)


def _agreement(got, want) -> list:
    """For every query of every layer, the share of the reference's
    selected positions (`want`, -1 = none) that `got` selected too."""
    import numpy as np
    return [np.isin(g[g >= 0], e[e >= 0]).sum() / max(1, (e >= 0).sum())
            for gl, el in zip(got, want)
            for g, e in zip(np.asarray(gl), np.asarray(el))]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        publish_expert_rows
    from paddle_tpu.observability import xray
    from benchmark import latency
    from benchmark.jobs.serve_engine import Sink
    from benchmark.reference import glm_moe_dsa_ref as ref
    from benchmark.traffic import openloop

    wl, cfgd, mix = ctx.workload, ctx.config, ctx.traffic
    t0 = time.perf_counter()
    model, cfg = build_model(cfgd, int(wl["max_context"]), ctx.seed)
    xray.reset()
    eng = ServingEngine(model, max_batch=int(wl["max_batch"]),
                        max_context=int(wl["max_context"]),
                        block_size=int(wl["block_size"]),
                        num_blocks=int(wl["num_blocks"]),
                        steps_per_tick=int(wl["steps_per_tick"]),
                        prefill_chunk=int(wl["prefill_chunk"]),
                        pad_buckets=wl["pad_buckets"], prefix_cache=True)
    t0 = ctx.part("build", t0)
    info = eng.warmup()
    t0 = ctx.part("warm_up", t0)
    pool_bytes = sum(p.size * p.dtype.itemsize
                     for layer in eng.pools for p in layer)
    n_params = model.num_params()
    ctx.say(f"model glm_moe_dsa: {cfg.num_layers} layers x "
            f"{cfg.hidden_size}, {cfg.n_experts_held} of "
            f"{cfg.n_routed_experts} experts from {cfg.expert_offset}, "
            f"vocabulary {cfg.vocab_size}; {n_params / 1e6:.1f}M parameters "
            f"({n_params * 2 / 2**30:.2f} GiB bf16)")
    ctx.say(f"engine: batch {eng.B}, context {eng.max_context}, "
            f"{eng.num_blocks} blocks of {eng.bs}, pools "
            f"{pool_bytes / 2**30:.2f} GiB "
            f"({[r.name for r in eng.cache.rows]}), "
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
    docs = shared_documents(plan, eng.bs)
    ctx.say(f"open loop: {rate:g} req/s, {len(plan)} requests over "
            f"{span:g} s ({lead:g} s lead-in + {ctx.seconds:g} s window); "
            f"{len(docs)} shared documents of {[len(d) for d in docs]} "
            f"tokens; prompt tokens {sum(len(p['prompt']) for p in plan)}, "
            f"output tokens {sum(p['max_new_tokens'] for p in plan)}")
    t0 = ctx.part("schedule", t0)

    # ---- the documents, prefilled once: set-up, as a deployment that
    # keeps its documents warm; the prefix cache holds them after
    for d in docs:
        eng.add_request(Request(d + d[:1], max_new_tokens=1))
    eng.run()
    st0 = eng.stats()
    warm = st0["prefix_cache"]
    ctx.say(f"documents prefilled: {st0['prefill_chunks']} chunks, prefix "
            f"cache {warm['entries']} entries; "
            f"{time.perf_counter() - t0:.1f} s")
    rows0 = st0.get("cache_state", {}).get("moe_rows", 0)
    steps0, chunks0 = st0["steps"], st0["prefill_chunks"]
    hit0 = warm["hit_tokens"]
    ctx.part("documents", t0)

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
            # them on the host: the traced window's, to within a tick.
            # Both are read before the profiler stops: writing the trace
            # out takes it a minute or more here (80-95 s on the chip's
            # host, PERF.md section 6) while the engine goes on decoding
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
        # the generator and the clock end with the window and the drain;
        # the tracer when the trace is written, which the reducers need
        # whole: 90 s were once too few (PERF.md section 6)
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
            f"rejected {len(box['rejected'])}")
    ctx.say(f"(waiting, running) each second of the window: "
            f"{box.get('depth')}")
    counters = {"ttft_p90_ms": summ["ttft_ms"],
                "tpot_p90_ms": summ["tpot_ms"],
                "tpot_p50_ms": summ["tpot_p50_ms"],
                "queue_wait_p90_ms": summ.get("queue_wait_ms"),
                "gen_lag_p90_ms": summ.get("gen_lag_ms"),
                "prefix_hit_token_pct": hit_pct}
    run_steps = max(1, st["steps"] - steps0)
    rows = st.get("cache_state", {}).get("moe_rows")
    if rows is not None:
        grown = list(rows - rows0)                # [layers][2, 2, held]
        publish_expert_rows(grown, cfg.expert_offset)
        # the reducers read the TRACED window's counts beside its times
        a, b = box.get("traced_rows", ({}, {}))
        if a and b:
            counters["decode_steps"] = b["steps"] - a["steps"]
            counters["moe_rows"] = (b["moe_rows"] - a["moe_rows"]).tolist()
        moe = [g for g in grown if g.sum()]
        ctx.say(f"held experts, a decode step and MoE layer over the run "
                f"(running sequences only; idle slots reach no expert): "
                f"{np.mean([g[0, 0].sum() for g in moe]) / run_steps:.2f} "
                f"rows over {cfg.n_experts_held} experts, "
                f"{np.mean([g[0, 1].sum() for g in moe]) / run_steps:.2f} "
                f"experts hit")

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
        for prog in ("serving.tick", "serving.prefill_cont"):
            got = [tuple(c) for row in cov
                   if row["program"].startswith(prog) for c in row["claims"]]
            ctx.check(got and all(m == "custom_call" for _, m in got),
                      f"{prog}*: every kernel claim is a Mosaic custom call "
                      f"{sorted(set(got))}")
    _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records, docs, ref)
    return {"attempted": summ["n"],
            "failed": summ["failed"],
            "metrics": {"serve_tpot_p90_ms": summ["tpot_ms"],
                        "serve_tokens_per_s": tokens_per_s},
            "counters": counters}


def _check_against_reference(ctx, eng, model, cfg, cfgd, plan, reqs, sample,
                             records, docs, ref):
    """Parts (a) and (b) of `correct` (module docstring), on finished
    requests of the shortest shared document, and the controls asked for."""
    import numpy as np
    t_ref = time.perf_counter()
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    doc = docs[-1] if docs else []
    fit = [i for i, rec in zip(sample, records) if rec["finished"]
           and plan[i]["prompt"][:len(doc)] == doc]
    picked = [fit[j] for j in
              rng.permutation(len(fit))[:int(ctx.workload["check_requests"])]]
    controls = [c for c in (ctx.workload.get("control") or "").split("+")
                if c]
    if set(controls) - set(CONTROLS):
        raise ValueError(f"control {controls}: not among {CONTROLS}")
    dims = ref.dims_of(dict(cfgd, expert_offset=cfg.expert_offset))
    topk = cfg.index_topk
    sd = {k: v._value for k, v in model.state_dict().items()}
    params = ref.from_state_dict(sd, cfg.num_layers)
    # (b) first: the probes run over the engine's pools, whose memory the
    # reference then takes
    probes = _probes(eng, model)
    probed = {i: _replay(eng, probes, plan[i]["prompt"], reqs[i].output_ids,
                         len(doc)) for i in picked}
    halved = {}
    if "topk_half" in controls:
        cfg.index_topk = topk // 2        # read when the probes are traced
        try:
            probes = _probes(eng, model)
            halved = {i: _replay(eng, probes, plan[i]["prompt"],
                                 reqs[i].output_ids, len(doc))[1]
                      for i in picked}
        finally:
            cfg.index_topk = topk
    eng.pools = None                  # 2.8 GB the reference needs
    worst, same, total, agree = 0.0, 0, 0, []
    low_gap, low_agree, half_agree = 0.0, [], []
    for i in picked:
        prompt, out = plan[i]["prompt"], np.asarray(reqs[i].output_ids)
        start, sel = probed[i]
        seq = prompt + out[:-1].tolist()
        L = len(prompt)
        want = np.arange(start, len(seq))
        padded = np.asarray(seq + [0] * (-len(seq) % min(
            REF_PAD, cfg.max_seq_len)), np.int32)
        lg, ref_sel = ref.forward(params, padded, dims, positions=want)
        lg = np.asarray(lg)[L - 1 - start:]          # the output positions
        at = np.arange(len(out))
        gaps = lg.max(-1) - lg[at, out]
        worst = max(worst, float(gaps.max()))
        same += int((gaps == 0).sum())
        total += len(out)
        agree += _agreement(sel, ref_sel)
        if "float8" in controls:
            import ml_dtypes
            lq, sq = ref.forward(params, padded, dims, positions=want,
                                 operand_dtype=ml_dtypes.float8_e4m3fn)
            lq = np.asarray(lq)[L - 1 - start:]
            low_gap = max(low_gap, float(
                (lg.max(-1) - lg[at, lq.argmax(-1)]).max()))
            low_agree += _agreement(sq, ref_sel)
        if "topk_half" in controls:
            half_agree += _agreement(halved[i], ref_sel)
    took = time.perf_counter() - t_ref
    ctx.check(picked and worst <= GAP_TOL,
              f"on {len(picked)} sampled requests of the {len(doc)}-token "
              f"document ({total} tokens through the prefix hit, the "
              f"question's chunks and the latent cache) every served token "
              f"is the reference's best to within {worst:.4f} logits "
              f"(<= {GAP_TOL}; {same} identical)")

    def held(shares):
        return bool(shares) and float(np.mean(shares)) >= SELECT_TOL \
            and float(np.min(shares)) >= SELECT_MIN

    def said(shares):
        if not shares:
            return "nothing compared"
        return (f"mean {float(np.mean(shares)):.4f} (>= {SELECT_TOL}), "
                f"least {float(np.min(shares)):.4f} (>= {SELECT_MIN})")

    ctx.check(held(agree),
              f"the program's selections (top {topk}; {len(agree)} queries "
              f"x layers, the question as chunks of {eng.chunk} and every "
              f"served token as a decode step of the batch of {eng.B}, "
              f"replayed over the engine's pools) agree with the "
              f"reference's: {said(agree)}; the check took {took:.1f} s")
    if "float8" in controls:
        ctx.check(low_gap <= GAP_TOL and held(low_agree),
                  f"CONTROL float8, which must fail: the reference with "
                  f"every matmul operand rounded to float8_e4m3, read as "
                  f"the program is: its greedy tokens fall {low_gap:.4f} "
                  f"logits short of the reference's best (<= {GAP_TOL}); "
                  f"its selections agree {said(low_agree)}")
    if "topk_half" in controls:
        ctx.check(held(half_agree),
                  f"CONTROL topk_half, which must fail: the probes with "
                  f"index_topk {topk // 2} in place of {topk}: their "
                  f"selections agree {said(half_agree)}")
