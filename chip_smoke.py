#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, and run right, on the chip?

One process, five phases, the normal entry points at the full width of
GPT-3 124M (hidden 768, 12 layers, 12 heads, vocab 50304; random weights
from a seed):

0. device     — what jax sees; anything but a TPU in the peak table fails.
1. trainer    — GPTForCausalLM + AdamW + amp O1/bf16 under one
                `jit.to_static` step, B=4 S=1024: loss sane and falling,
                no compile after the first step, flash forward and both
                backward kernels compiled by Mosaic, fused optimizer,
                parameters donated.
2. server     — the same model in bf16 behind `ServingEngine` (batch 8,
                context 1024, 4 steps a tick, 256-token prefill chunks):
                `warmup()` over the whole pad ladder, then
                `serve_forever` answering `POST /generate` over loopback
                HTTP, then a closed batch through `run()` (the
                overlapped tick loop) — every stream finishes, paged
                kernels are Mosaic custom calls, nothing compiles after
                warm-up, nothing reads a donated buffer.
3. kernels    — every Pallas kernel the defaults select, against the jnp
                reference beside it, at 124M widths and the 1.3B head
                shape (16 heads of 128), bf16 and float32.
4. four chips — (only with >= 4 devices) the hybrid dp2 x mp2 step with
                sequence parallel + ZeRO against the serial step, the
                fused ZeRO-3 step at dp4, and tensor-parallel serving at
                degree 4; every device must hold its shard.

The first failed check raises and the process exits non-zero: there is
no try/except that turns a failure into a record, and nothing shrinks
because of what `jax.devices()` returns.  The only way to a smaller run
is the explicit `--rehearse-cpu` argument (tiny sizes, interpreted
kernels, a virtual 4-device CPU mesh); it labels every line it prints
and its summary names the CPU as the device, so it cannot be mistaken
for a pass on the chip.

The last line of standard output is one JSON object with exactly two
keys, `{"ok": true, "device": {"platform", "kind", "count"}}`, the
device as jax reports it; it is printed only when every phase passed.
The line before it, `summary: {...}`, is the account: the phases run and
skipped, seconds of set-up/compile vs run per phase, persistent-cache
hits/misses, and `"claim": null` — this script claims no speed.  (A
rehearsal labels both lines, so neither parses as a result.)  The
compile cache lives where
`JAX_COMPILATION_CACHE_DIR` says, else `<checkout>/.jax_cache`
(`paddle_tpu/core/compile_cache.py`).

    python chip_smoke.py                    # on the chip (through the tool)
    python chip_smoke.py --phases 0,4       # a subset, e.g. on four chips
    python chip_smoke.py --rehearse-cpu     # CPU rehearsal, tiny, labelled
"""

from __future__ import annotations

import argparse
import gc
import http.client
import importlib.metadata
import json
import math
import os
import socket
import sys
import threading
import time

ALL_PHASES = (0, 1, 2, 3, 4)
_LABEL = ""          # set to "[CPU-REHEARSAL] " by --rehearse-cpu


def say(msg: str) -> None:
    print(f"{_LABEL}{msg}", flush=True)


class SmokeFailure(AssertionError):
    """A check did not hold.  Never caught: it ends the process."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


# ---------------------------------------------------------------- sizes

def sizes(rehearse: bool) -> dict:
    """Every size the phases use.  The real ones are fixed; the
    rehearsal ones exist only behind the explicit argument."""
    if not rehearse:
        return dict(
            train_B=4, train_S=1024, train_steps=6,
            serve_batch=8, serve_ctx=1024, serve_chunk=256, serve_new=32,
            serve_block=64,
            prompts=(40, 200, 600, 960), shared_prefix=320,
            # (nh, hd, S) per kernel width: 124M and the gpt3_1p3b heads
            widths=((12, 64, 1024), (16, 128, 1024)),
            chunk_s=(256, 1024), ctx=1024,
            moe=((1024, 768, 8, 2), (1024, 2048, 64, 8)),
            hybrid_B=4)
    return dict(
        train_B=2, train_S=64, train_steps=4,
        serve_batch=4, serve_ctx=128, serve_chunk=32, serve_new=6,
        serve_block=16,
        prompts=(10, 30, 60, 100), shared_prefix=48,
        widths=((2, 64, 128),), chunk_s=(32,), ctx=128,
        moe=((24, 64, 4, 2),),
        hybrid_B=4)


def model_config(rehearse: bool):
    from paddle_tpu.models.gpt import gpt3_124m, gpt3_tiny
    if not rehearse:
        return gpt3_124m()
    # 4 heads so tensor-parallel degree 4 divides them
    return gpt3_tiny(vocab_size=512, hidden_size=64, num_heads=4,
                     max_seq_len=128)


# ------------------------------------------------------ compile counting

def compiles() -> dict:
    """Programs built so far, two ways: the repo's compile tracker (one
    event per program a `to_static`/serving wrapper built) and jax's own
    persistent-cache requests (one per XLA compile request, hit or
    miss).  A steady-state window must move neither."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.observability import compile_tracker
    rep = compile_cache.cache_report()
    return {"tracker": compile_tracker.total_compiles(),
            "requests": rep["hits"] + rep["misses"],
            "hits": rep["hits"], "misses": rep["misses"]}


def since(before: dict) -> dict:
    return {k: v - before[k] for k, v in compiles().items()}


# ------------------------------------------------------------- phase 0

def phase0_device(rehearse: bool) -> dict:
    import jax
    import jaxlib
    from paddle_tpu.core import compile_cache, native
    from paddle_tpu.observability import flops

    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    say(f"phase 0: platform={d.platform} device_kind={d.device_kind!r} "
        f"count={len(devs)}")
    say(f"  jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    chosen_by = (compile_cache.ENV_VAR if os.environ.get(compile_cache.ENV_VAR)
                 else "checkout default")
    say(f"  compile cache: {compile_cache.active_dir()} ({chosen_by}), "
        f"entries at start: {compile_cache.cache_report()['entries']}")
    say(f"  native components requested so far: {native.status() or 'none'}")
    if rehearse:
        say("  rehearsal: device checks skipped by --rehearse-cpu")
        return device
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: FAIL phase 0: jax found no accelerator "
                 f"(platform={d.platform!r}, device_kind={d.device_kind!r})")
    peak = flops.peak_flops(d.device_kind)   # raises on an unknown kind
    say(f"  peak table row: {peak:.3g} bf16 FLOP/s per chip")
    return device


# ------------------------------------------------------------- phase 1

def phase1_trainer(sz: dict, rehearse: bool) -> dict:
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.observability import metrics, xray

    t0 = time.perf_counter()
    cfg = model_config(rehearse)
    B, S = sz["train_B"], sz["train_S"]
    say(f"phase 1: trainer, {cfg.num_layers} layers x hidden "
        f"{cfg.hidden_size}, vocab {cfg.vocab_size}, B={B} S={S}")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=3e-4,
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

    probe = model.gpt.ln_f.bias
    with xray.capture_kernel_claims() as claims:
        losses = [float(np.asarray(step(ids, labels)._value))]
    setup_s = time.perf_counter() - t0
    after_first = compiles()
    fused = metrics.get("optimizer.fused")
    ops = metrics.get("dispatch.ops")

    t1 = time.perf_counter()
    old = probe._value
    for _ in range(sz["train_steps"] - 1):
        losses.append(float(np.asarray(step(ids, labels)._value)))
    run_s = time.perf_counter() - t1
    steady = since(after_first)
    say(f"  losses: {[round(l, 4) for l in losses]}")

    check(all(math.isfinite(l) for l in losses), "every loss is finite")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 0.5,
          f"first loss {losses[0]:.3f} is near ln(vocab) = {ln_v:.3f}")
    check(losses[-1] < losses[0],
          f"last loss {losses[-1]:.3f} is below the first")
    check(steady["tracker"] == 0 and steady["requests"] == 0,
          f"no program was built after the first step ({steady})")
    check(fused.value(kind="fallback") == 0
          and fused.value(kind="hit") + fused.value(kind="miss") > 0,
          "the fused optimizer served the update (hits/misses counted, "
          "no fallback)")
    if rehearse:
        say("  rehearsal: flash claims and donation not checked (on a "
            "CPU the XLA softmax is the attention path and donation is "
            "off)")
    else:
        # the whole-step trace claims each kernel once a layer (the
        # eager discovery pass before it traces each op once in all),
        # so >= num_layers claims can only come from the compiled step
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            n = sum(1 for c in claims if c == (name, "custom_call"))
            check(n >= cfg.num_layers,
                  f"{name}: {n} Mosaic custom-call claims in the step "
                  f"(>= {cfg.num_layers} layers)")
        check(not any(m == "interpret" for _, m in claims),
              "no kernel in the step was interpreted")
        check(ops.value(op="sdpa") == 0
              and ops.value(op="flash_attention") > 0,
              "attention dispatched flash_attention, never the XLA sdpa")
        check(old.is_deleted(),
              "the step donated its parameter buffers (the previous "
              "value of a parameter is deleted after a step)")
    del step, opt, model
    gc.collect()
    return {"setup_s": round(setup_s, 2), "run_s": round(run_s, 2),
            "steps": len(losses), "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4)}


# ------------------------------------------------------------- phase 2

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post_generate(port: int, body: dict) -> dict:
    """One POST /generate; returns the terminal SSE event's payload plus
    the streamed tokens.  Raises on anything but a `done` event."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(
                f"POST /generate answered {resp.status}: {resp.read()!r}")
        toks, event = [], None
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                data = json.loads(line[len("data: "):])
                if event == "done":
                    data["streamed"] = toks
                    return data
                if event is not None:
                    raise SmokeFailure(
                        f"stream ended with event {event!r}: {data}")
                toks.append(data["token"])
            elif not line:
                event = None
        raise SmokeFailure("stream closed without a terminal event")
    finally:
        conn.close()


def _wait_ready(port: int, deadline_s: float = 60.0) -> None:
    """Poll GET /healthz until the engine answers 200 (the endpoint
    comes up inside serve_forever, after this thread has started)."""
    t_end = time.monotonic() + deadline_s
    while True:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            conn.request("GET", "/healthz")
            status = conn.getresponse().status
            conn.close()
            if status == 200:
                return
        except OSError:
            status = None
        if time.monotonic() > t_end:
            raise SmokeFailure(
                f"/healthz on port {port} never answered 200 "
                f"(last: {status})")
        time.sleep(0.05)


def _request_bodies(sz: dict, vocab: int) -> tuple:
    """The traffic: one prompt per bucket length, a pair sharing a long
    prefix (sent one after the other so the second finds it cached),
    one sampled, all seeded."""
    import numpy as np
    rng = np.random.RandomState(1)
    new = sz["serve_new"]

    def prompt(n):
        return [int(t) for t in rng.randint(1, vocab, (n,))]

    bodies = [{"prompt_ids": prompt(n), "max_new_tokens": new}
              for n in sz["prompts"]]
    bodies[1].update(do_sample=True, temperature=0.8, top_k=50, seed=7)
    prefix = prompt(sz["shared_prefix"])
    pair = [{"prompt_ids": prefix + prompt(17), "max_new_tokens": new},
            {"prompt_ids": prefix + prompt(23), "max_new_tokens": new}]
    extra = [{"prompt_ids": prompt(sz["prompts"][0] + 5),
              "max_new_tokens": new},
             {"prompt_ids": prompt(sz["prompts"][1] + 9),
              "max_new_tokens": new, "do_sample": True, "top_p": 0.9,
              "seed": 11}]
    return bodies, pair, extra


def _check_stream(done: dict, body: dict, vocab: int, tag: str) -> None:
    out = done["output_ids"]
    if done.get("outcome") != "finished":
        raise SmokeFailure(f"{tag}: outcome {done.get('outcome')!r}")
    if len(out) != body["max_new_tokens"] or out != done["streamed"]:
        raise SmokeFailure(
            f"{tag}: {len(out)} tokens for max_new_tokens="
            f"{body['max_new_tokens']} (streamed {len(done['streamed'])})")
    if not all(0 <= t < vocab for t in out):
        raise SmokeFailure(f"{tag}: token id out of range in {out}")


def _serve_traffic(port: int, sz: dict, vocab: int, box: dict,
                   stop: threading.Event) -> None:
    """Client thread: the four bucket prompts concurrently, then the
    shared-prefix pair in order, then two more concurrently.  Whatever
    it raises is handed to the main thread, which re-raises it."""
    try:
        _wait_ready(port)
        bodies, pair, extra = _request_bodies(sz, vocab)
        results = {}

        def fire(i, body):
            try:
                results[i] = _post_generate(port, body)
            except BaseException as e:  # noqa: BLE001 - forwarded below
                results[i] = e

        def wave(items):
            ts = [threading.Thread(target=fire, args=it, daemon=True)
                  for it in items]
            for t in ts:
                t.start()
            for t in ts:
                t.join(900)
                if t.is_alive():
                    raise SmokeFailure("a /generate request hung")

        wave(list(enumerate(bodies)))
        n = len(bodies)
        for j, body in enumerate(pair):
            fire(n + j, body)
        wave([(n + 2 + j, b) for j, b in enumerate(extra)])
        sent = bodies + pair + extra
        for i, body in enumerate(sent):
            if isinstance(results.get(i), BaseException):
                raise results[i]
            _check_stream(results[i], body, vocab, f"request {i}")
        box["results"] = [(sent[i], results[i]) for i in range(len(sent))]
    except BaseException as e:  # noqa: BLE001 - re-raised by the caller
        box["error"] = e
    finally:
        stop.set()


def _claims_of(coverage: list, prefix: str) -> list:
    return [tuple(c) for row in coverage
            if row["program"].startswith(prefix) for c in row["claims"]]


def _check_serving_kernels(mode: str) -> None:
    from paddle_tpu.observability import xray
    cov = xray.kernel_coverage()
    for prog, kernel in (("serving.tick", "paged_decode"),
                         ("serving.decode", "paged_decode"),
                         ("serving.prefill_cont", "paged_chunk_prefill")):
        got = _claims_of(cov, prog)
        check(got and all(c == (kernel, mode) for c in got),
              f"{prog}*: every kernel claim is ({kernel}, {mode}) "
              f"[{len(got)} claims]")
        if mode == "custom_call":
            rows = [r for r in cov if r["program"].startswith(prog)]
            check(all(r["pallas"] and r["via"] == "custom_call"
                      for r in rows),
                  f"{prog}*: the lowered HLO holds the Mosaic custom call")


def _agrees_with_full_forward(model, body: dict, out: list) -> float:
    """Largest amount by which a served greedy token's logit, in a plain
    full-sequence forward of the same model over prompt + output, falls
    short of that position's best logit.  0 = the same argmax."""
    import numpy as np
    import paddle_tpu as paddle
    seq = body["prompt_ids"] + out
    with paddle.no_grad():
        logits = model(paddle.to_tensor(np.asarray([seq], np.int32)))
    lg = np.asarray(logits._value.astype("float32"))[0]
    L = len(body["prompt_ids"])
    return max(float(lg[L - 1 + j].max() - lg[L - 1 + j, t])
               for j, t in enumerate(out))


def phase2_server(sz: dict, rehearse: bool, tp_degree: int = 1) -> dict:
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.observability import http as obs_http, xray

    t0 = time.perf_counter()
    cfg = model_config(rehearse)
    say(f"phase {'2' if tp_degree == 1 else '4c'}: server, bf16, "
        f"tp_degree={tp_degree}, batch {sz['serve_batch']}, context "
        f"{sz['serve_ctx']}, chunk {sz['serve_chunk']}")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    model.bfloat16()
    xray.reset()
    eng = ServingEngine(model, max_batch=sz["serve_batch"],
                        max_context=sz["serve_ctx"],
                        block_size=sz["serve_block"], steps_per_tick=4,
                        prefill_chunk=sz["serve_chunk"],
                        tp_degree=tp_degree)
    check(str(eng.pools[0][0].dtype) == "bfloat16",
          "the KV pools are bf16 (taken from the parameters)")
    info = eng.warmup()
    say(f"  warm-up: {info['programs']} programs, {info['aot_programs']} "
        f"AOT, ladder {list(eng.pad_ladder)}")
    check(info["aot_programs"] == info["programs"],
          "every warm-up program took the AOT path (no quiet fall back "
          "to a plain first call)")
    check(eng.pad_ladder[-1] == sz["serve_ctx"],
          f"the pad ladder reaches the {sz['serve_ctx']} bucket")
    mode = "interpret" if rehearse else "custom_call"
    _check_serving_kernels(mode)
    setup_s = time.perf_counter() - t0
    warm = compiles()

    # -- HTTP: serve_forever in this (the main) thread, clients beside it
    t1 = time.perf_counter()
    port = _free_port()
    stop, box = threading.Event(), {}
    with flags.flag_guard(serving_http_port=port):
        client = threading.Thread(
            target=_serve_traffic,
            args=(port, sz, cfg.vocab_size, box, stop), daemon=True)
        client.start()
        eng.serve_forever(stop)
        client.join(60)
    check(obs_http.serving_server() is not None,
          f"the streaming endpoint was up on 127.0.0.1:{port}")
    obs_http.stop()
    if "error" in box:
        raise box["error"]
    results = box["results"]
    check(len(results) >= 8,
          f"{len(results)} POST /generate streams ended `finished` with "
          f"{sz['serve_new']} in-range tokens each")
    st = eng.stats()
    check(st["prefix_cache"]["hits"] >= 1,
          f"the shared prefix was found cached "
          f"({st['prefix_cache']['hits']} hit) and its suffix prefilled")
    check(st["prefill_chunks"] > len(results),
          f"prompts were absorbed in chunks ({st['prefill_chunks']})")
    # -- closed batch through run(): the overlapped (double-buffered)
    # tick loop, which keeps pool handles across ticks under donation
    bodies, _, _ = _request_bodies(sz, cfg.vocab_size)
    reqs = [eng.add_request(Request(b["prompt_ids"],
                                    max_new_tokens=sz["serve_new"]))
            for b in bodies]
    eng.run()
    check(all(r.done and len(r.output_ids) == sz["serve_new"]
              for r in reqs),
          f"run() finished {len(reqs)} more requests (overlapped ticks)")
    greedy = [r for (b, r) in results if not b.get("do_sample")]
    check(reqs[0].output_ids == greedy[0]["output_ids"],
          "the same greedy prompt gave the same tokens over HTTP and "
          "through run()")
    run_s = time.perf_counter() - t1
    st = eng.stats()
    steady = since(warm)
    gap = _agrees_with_full_forward(model, *_first_greedy(results))
    # bf16 end to end: the served token must be the full forward's
    # argmax up to bf16 noise in two independently rounded programs.
    # Logits of this random model span ~1; 0.125 is 1/8 of that and
    # ~30 bf16 ulps at magnitude 1 — a wrong cache read lands far out.
    check(gap <= 0.125,
          f"served greedy tokens are the full forward's argmax to "
          f"within {gap:.4f} logits (<= 0.125)")
    check(st["tick_errors"] == 0 and st["poisoned_requests"] == 0,
          "no tick failed and no request was poisoned (no `Array has "
          "been deleted` under donation)")
    check(steady["tracker"] == 0 and steady["requests"] == 0,
          f"nothing was compiled after warm-up ({steady})")
    check(st["free_blocks"] == eng.num_blocks and st["active"] == 0,
          "every block came back to the pool")
    out = {"setup_s": round(setup_s, 2), "run_s": round(run_s, 2),
           "programs": info["programs"], "requests": len(results) + len(reqs),
           "argmax_gap": round(gap, 4)}
    if tp_degree > 1:
        out["pool_shards"] = _check_sharded(
            [p for kv in eng.pools for p in kv], "KV pools", tp_degree)
    del eng, model
    gc.collect()
    return out


def _first_greedy(results):
    for body, done in results:
        if not body.get("do_sample"):
            return body, done["output_ids"]
    raise SmokeFailure("no greedy request in the traffic")


# ------------------------------------------------------------- phase 3
# Tolerances, with reasons.  References run under
# jax.default_matmul_precision("highest") in float32.
#
# * Attention outputs are convex mixes of unit-normal values, so they are
#   O(1) and errors are judged as max|a-b| / max|b|.
# * bf16 inputs: the kernels keep q.k^T and p.v on the MXU in bf16 with
#   float32 accumulation, cast p to bf16 before p.v, and round the output
#   to bf16 (2^-8 = 3.9e-3 relative).  Three roundings of that size
#   through a softmax give ~1e-2; the bound is 2e-2.
# * float32 inputs: the kernels ask the MXU for no particular precision,
#   and its default pass for float32 operands is bf16 multiplies with
#   float32 accumulation — so agreement is bounded by the same operand
#   rounding as bf16, not by float32 epsilon.  Same bound, 2e-2; the
#   observed error is printed so the record shows which it was.
# * Gradients go through one more matmul each: 4e-2.
# * MoE dispatch moves rows: exact (0).  Combine multiplies by a float32
#   weight and accumulates k rows in float32, then rounds to the buffer
#   dtype: 1e-6 relative for float32 buffers, 2^-8 -> 8e-3 for bf16.
TOL_ATTN, TOL_GRAD = 2e-2, 4e-2
TOL_COMBINE = {"float32": 1e-5, "bfloat16": 8e-3}


def _rel_err(a, b) -> float:
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _kernel_case(name: str, err: float, tol: float) -> None:
    if not (math.isfinite(err) and err <= tol):
        raise SmokeFailure(f"{name}: error {err:.3e} exceeds {tol:.1e}")
    say(f"  ok: {name}: {err:.2e} <= {tol:.0e}")


def _flash_cases(nh, hd, S, dtype, rng, rehearse):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import _sdpa_xla_impl
    from paddle_tpu.ops import pallas_flash

    tag = f"nh{nh} hd{hd} S{S} {jnp.dtype(dtype).name}"
    B = 2

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def ref(q, k, v, mask, causal):
        m = None if mask is None else (mask != 0)[:, None, None, :]
        with jax.default_matmul_precision("highest"):
            return _sdpa_xla_impl(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), m, causal=causal, dropout_p=0.0,
                scale=None, key=None)

    for label, nkv, causal, masked in (
            ("causal", nh, True, False),
            ("kv_mask", nh, False, True),
            ("gqa causal", max(1, nh // 4), True, False)):
        q, k, v = rand((B, S, nh, hd)), rand((B, S, nkv, hd)), \
            rand((B, S, nkv, hd))
        g = rand((B, S, nh, hd))
        mask = None
        if masked:
            # ragged key padding: sequence b keeps its first S - 37*(b+1)
            keep = jnp.arange(S)[None, :] < (S - 37 * (jnp.arange(B) + 1)
                                             )[:, None]
            mask = keep.astype(jnp.int32)

        def f_kernel(q, k, v):
            return pallas_flash.flash_attention(q, k, v, causal,
                                                kv_mask=mask)

        out, vjp = jax.vjp(f_kernel, q, k, v)
        want, vjp_ref = jax.vjp(
            lambda q, k, v: ref(q, k, v, mask, causal), q, k, v)
        _kernel_case(f"flash fwd {label} {tag}", _rel_err(out, want),
                     TOL_ATTN)
        for n, a, b in zip(("dq", "dk", "dv"), vjp(g),
                           vjp_ref(g.astype(jnp.float32))):
            _kernel_case(f"flash bwd {n} {label} {tag}", _rel_err(a, b),
                         TOL_GRAD)

    # dropout: no reference can redraw the TPU PRNG's mask, so the three
    # kernels are held to identities that only hold if they drew the
    # SAME mask.  For fixed q, k the output is linear in v: out = A v
    # with A the dropped, rescaled probabilities, so <out(v'), g> must
    # equal <v', dv> for any v' (forward mask == dkv kernel's mask), and
    # sum(dq*q) == sum(dk*k) because both equal sum_ij ds_ij s_ij/scale
    # (dq kernel's mask == dkv kernel's mask).
    rate, seed = 0.1, jnp.int32(1234)
    q, k, v = rand((B, S, nh, hd)), rand((B, S, nh, hd)), \
        rand((B, S, nh, hd))
    g, v2 = rand((B, S, nh, hd)), rand((B, S, nh, hd))

    def f_drop(q, k, v, seed=seed):
        return pallas_flash.flash_attention(q, k, v, True, seed=seed,
                                            dropout_rate=rate)

    out, vjp = jax.vjp(f_drop, q, k, v)
    dq, dk, dv = vjp(g)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    lhs = float(jnp.sum(f32(f_drop(q, k, v2)) * f32(g)))
    rhs = float(jnp.sum(f32(v2) * f32(dv)))
    scale = float(jnp.sqrt(jnp.sum(f32(g) ** 2) * jnp.sum(f32(v2) ** 2)))
    _kernel_case(f"flash dropout fwd/dkv mask identity {tag}",
                 abs(lhs - rhs) / scale, TOL_GRAD)
    a, b = float(jnp.sum(f32(dq) * f32(q))), float(jnp.sum(f32(dk) * f32(k)))
    _kernel_case(f"flash dropout dq/dkv mask identity {tag}",
                 abs(a - b) / (abs(a) + abs(b) + 1e-30), TOL_GRAD)
    if rehearse:
        say("  rehearsal: dropout keep-rate and seed sensitivity not "
            "checked (the TPU interpreter's PRNG is a stub that keeps "
            "everything)")
        return
    ones = jnp.ones_like(v)
    keep_rate = float(jnp.mean(f32(f_drop(q, k, ones))))
    if abs(keep_rate - 1.0) > 0.02:
        raise SmokeFailure(f"dropout rescale {tag}: mean {keep_rate:.4f}")
    same = _rel_err(f_drop(q, k, v), out)
    other = _rel_err(f_drop(q, k, v, jnp.int32(99)), out)
    if same != 0.0 or other < 1e-2:
        raise SmokeFailure(
            f"dropout seeding {tag}: same seed differs by {same:.2e}, "
            f"another seed by {other:.2e}")
    say(f"  ok: flash dropout {tag}: rescale mean {keep_rate:.4f}, same "
        "seed bit-identical, another seed differs")


def _paged_cases(nh, hd, dtype, sz, rng):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_paged as pp

    tag = f"nh{nh} hd{hd} {jnp.dtype(dtype).name}"
    bs, ctx = 64, sz["ctx"]
    nb = ctx // bs
    B = 4
    n_blocks = B * nb

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kc, vc = rand((nh, n_blocks + 1, bs, hd)), rand((nh, n_blocks + 1, bs, hd))
    # every sequence owns a shuffled set of physical blocks
    perm = rng.permutation(n_blocks) + 1
    tables = jnp.asarray(perm.reshape(B, nb), jnp.int32)

    def hi(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*[x.astype(jnp.float32)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x
                        for x in a])

    lens = jnp.asarray([ctx, ctx // 2 + 3, 1, 77][:B], jnp.int32)
    q = rand((B, nh, hd))
    _kernel_case(
        f"paged_attention {tag}",
        _rel_err(pp.paged_attention(q, kc, vc, tables, lens),
                 hi(pp.paged_attention_reference, q, kc, vc, tables, lens)),
        TOL_ATTN)
    for s in sz["chunk_s"]:
        # the engine's shape: one sequence, chunk s at a cached offset
        for start in sorted({0, max(0, ctx - s - 5)}):
            starts = jnp.asarray([start], jnp.int32)
            qc = rand((1, s, nh, hd))
            _kernel_case(
                f"paged_chunk_attention s{s} start{start} {tag}",
                _rel_err(
                    pp.paged_chunk_attention(qc, kc, vc, tables[:1], starts),
                    hi(pp.paged_chunk_attention_reference, qc, kc, vc,
                       tables[:1], starts)),
                TOL_ATTN)
    for k in (4, 5):
        starts = jnp.asarray([ctx - k, 100 % ctx, 0, 63][:B], jnp.int32)
        qv = rand((B, k, nh, hd))
        _kernel_case(
            f"paged_verify_attention k{k} {tag}",
            _rel_err(pp.paged_verify_attention(qv, kc, vc, tables, starts),
                     hi(pp.paged_chunk_attention_reference, qv, kc, vc,
                        tables, starts)),
            TOL_ATTN)


def _moe_cases(T, M, E, k, dtype, rng):
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_moe as pm

    name = jnp.dtype(dtype).name
    tag = f"T{T} M{M} E{E} k{k} {name}"
    C = max(4, int(T * k / E * 1.25))
    eid = jnp.asarray(rng.integers(0, E, (T, k)), jnp.int32)
    # slot = arrival order within the expert; over-capacity choices drop
    flat_e = eid.reshape(-1)
    order = jnp.cumsum(jnp.eye(E, dtype=jnp.int32)[flat_e], axis=0)
    slot = (jnp.take_along_axis(order, flat_e[:, None], axis=1)[:, 0] - 1
            ).reshape(T, k)
    keep = (slot < C).astype(jnp.float32)
    flat, inv = pm.routing_indices(eid, jnp.minimum(slot, C - 1), keep, E, C)
    x = jnp.asarray(rng.standard_normal((T, M)), dtype)
    rows = pm.moe_dispatch(x, inv)
    _kernel_case(f"moe_dispatch {tag}",
                 _rel_err(rows, pm.moe_dispatch_reference(x, inv)), 0.0)
    w = jnp.asarray(rng.random((T, k)), jnp.float32) * keep
    eo = jnp.asarray(rng.standard_normal((E * C, M)), dtype)
    _kernel_case(f"moe_combine {tag}",
                 _rel_err(pm.moe_combine(eo, w, flat),
                          pm.moe_combine_reference(eo, w, flat)),
                 TOL_COMBINE[name])


def phase3_kernels(sz: dict, rehearse: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.observability import xray

    t0 = time.perf_counter()
    say("phase 3: kernels against their references")
    rng = np.random.default_rng(3)
    mode = "interpret" if rehearse else "custom_call"
    with xray.capture_kernel_claims() as claims:
        for dtype in (jnp.bfloat16, jnp.float32):
            for nh, hd, S in sz["widths"]:
                _flash_cases(nh, hd, S, dtype, rng, rehearse)
                _paged_cases(nh, hd, dtype, sz, rng)
            for T, M, E, k in sz["moe"]:
                _moe_cases(T, M, E, k, dtype, rng)
    kernels = sorted({n for n, _ in claims})
    check(set(kernels) >= {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                           "paged_decode", "paged_chunk_prefill",
                           "paged_spec_verify", "moe_fused_dispatch",
                           "moe_fused_combine"},
          f"every default-on kernel ran: {kernels}")
    check(all(m == mode for _, m in claims),
          f"all {len(claims)} kernel traces were {mode}")
    return {"setup_s": 0.0, "run_s": round(time.perf_counter() - t0, 2),
            "kernels": kernels}


# ------------------------------------------------------------- phase 4

def _check_sharded(arrays, what: str, n_dev: int) -> int:
    """Every array has one shard on each of n_dev distinct devices, each
    1/n_dev of the whole.  Returns the bytes one device holds."""
    per_dev = 0
    for a in arrays:
        shards = a.addressable_shards
        devs = {s.device for s in shards}
        if len(devs) != n_dev:
            raise SmokeFailure(
                f"{what}: an array of shape {a.shape} lives on "
                f"{len(devs)} device(s), not {n_dev}")
        if any(s.data.size * n_dev != a.size for s in shards):
            raise SmokeFailure(
                f"{what}: shard sizes {[s.data.shape for s in shards]} "
                f"are not 1/{n_dev} of {a.shape}")
        per_dev += shards[0].data.nbytes
    say(f"  ok: {what}: {len(arrays)} arrays, one 1/{n_dev} shard on each "
        f"of {n_dev} devices ({per_dev / 2**20:.1f} MiB a device)")
    return per_dev


def _check_memory_spread(devices, floor_bytes: int, what: str) -> list:
    """memory_stats() of every device: each must hold at least its
    shard — everything on device 0 would leave the others near zero."""
    used = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_in_use" not in stats:
            say(f"  note: {d} reports no memory_stats (CPU backend); "
                "shard placement above is the evidence")
            return []
        used.append(int(stats["bytes_in_use"]))
    say(f"  {what}: bytes_in_use per device "
        f"{[round(u / 2**20, 1) for u in used]} MiB")
    check(min(used) >= floor_bytes,
          f"{what}: every device holds at least its "
          f"{floor_bytes / 2**20:.1f} MiB shard")
    return used


def phase4_four_chips(sz: dict, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet import hybrid_step as hs

    t0 = time.perf_counter()
    devs = jax.devices()[:4]
    cfg0 = model_config(rehearse)
    B, S = sz["hybrid_B"], (sz["train_S"] if rehearse else 1024)
    base = dict(vocab_size=cfg0.vocab_size, hidden_size=cfg0.hidden_size,
                num_layers=cfg0.num_layers, num_heads=cfg0.num_heads,
                seq_len=S, n_microbatches=1, learning_rate=1e-3)
    say(f"phase 4: four chips, hidden {cfg0.hidden_size} x "
        f"{cfg0.num_layers} layers, B={B} S={S}")
    rng = np.random.RandomState(4)
    ids = jnp.asarray(rng.randint(0, cfg0.vocab_size, (1, B, S)), jnp.int32)
    key = jax.random.key(7)

    # -- serial reference: one step on one device
    cfg_s = hs.HybridConfig(pp=1, dp=1, mp=1, sequence_parallel=False,
                            **base)
    params = hs.init_gpt_params(key, cfg_s)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    # jitted, and only the loss kept: called bare, serial_train_step runs
    # op by op, hundreds of small programs each compiled on the chip
    serial_loss = float(jax.jit(lambda p, ids: hs.serial_train_step(
        p, zeros(p), zeros(p), 1.0, ids, cfg_s)[0])(params, ids))
    say(f"  serial first loss {serial_loss:.5f}")
    # Both programs are float32 with the MXU's default (bf16-multiply)
    # pass; the mesh changes where operands are split and summed, so the
    # losses agree to bf16 operand rounding averaged over B*S tokens,
    # not to float32 epsilon: 1e-3 absolute on a loss near ln(vocab)
    # (the v5e showed 1e-5 .. 6e-5; a mis-sharded weight moves it >1e-2).
    tol = 1e-3

    # -- 4a: hybrid dp2 x mp2, sequence parallel, ZeRO-2
    cfg_h = hs.HybridConfig(pp=1, dp=2, mp=2, sequence_parallel=True,
                            zero_stage=2, **base)
    mesh = Mesh(np.array(devs).reshape(1, 2, 2), ("pp", "dp", "mp"))
    specs = hs.hybrid_param_specs(cfg_h)
    stacked = hs.stack_for_pipeline(params, cfg_h)
    m, v, _ = hs.init_zero_state(stacked, specs, mesh)
    step = hs.make_hybrid_train_step(mesh, cfg_h)
    losses = []
    for i in range(3):
        loss, stacked, m, v = step(stacked, m, v, jnp.float32(i + 1), ids)
        losses.append(float(loss))
    say(f"  hybrid dp2 x mp2 losses {[round(l, 5) for l in losses]}")
    check(abs(losses[0] - serial_loss) <= tol,
          f"hybrid first loss equals the serial step's to {tol} "
          f"(|diff| = {abs(losses[0] - serial_loss):.2e})")
    check(all(map(math.isfinite, losses)) and losses[-1] < losses[0],
          "hybrid loss is finite and falls over three steps")
    mp_sharded = [stacked["blocks"][n] for n in ("wqkv", "wfc1", "wfc2")]
    for a in mp_sharded:
        n_dev = len({s.device for s in a.addressable_shards})
        check(n_dev == 4 and all(
            s.data.size * 2 == a.size for s in a.addressable_shards),
            f"a {a.shape} block weight is split in two over mp and "
            f"present on all {n_dev} devices")
    _check_sharded(jax.tree_util.tree_leaves(m["blocks"]["wqkv"]),
                   "ZeRO moment of wqkv (dp x mp)", 4)
    hybrid_s = time.perf_counter() - t0
    del step, stacked, m, v
    gc.collect()

    # -- 4b: fused ZeRO-3 at dp4
    t1 = time.perf_counter()
    cfg_z = hs.HybridConfig(pp=1, dp=4, mp=1, sequence_parallel=False,
                            zero_stage=3, **base)
    mesh_z = Mesh(np.array(devs), ("dp",))
    fp, fm, fv = hs.init_zero3_state(params, mesh_z)
    del params
    step_z = hs.make_zero3_train_step(mesh_z, cfg_z)
    zl = []
    for i in range(3):
        loss, fp, fm, fv = step_z(fp, fm, fv, jnp.float32(i + 1), ids)
        zl.append(float(loss))
    say(f"  ZeRO-3 dp4 losses {[round(l, 5) for l in zl]}")
    check(abs(zl[0] - serial_loss) <= tol,
          f"ZeRO-3 first loss equals the serial step's to {tol} "
          f"(|diff| = {abs(zl[0] - serial_loss):.2e})")
    check(all(map(math.isfinite, zl)) and zl[-1] < zl[0],
          "ZeRO-3 loss is finite and falls over three steps")
    shard_bytes = 0
    for name, tree in (("parameters", fp), ("first moments", fm),
                       ("second moments", fv)):
        shard_bytes += _check_sharded(jax.tree_util.tree_leaves(tree),
                                      f"ZeRO-3 {name}", 4)
    used = _check_memory_spread(devs, shard_bytes, "ZeRO-3 state")
    zero3_s = time.perf_counter() - t1
    del step_z, fp, fm, fv
    gc.collect()

    # -- 4c: tensor-parallel serving at degree 4, Phase 2's traffic
    server = phase2_server(sz, rehearse, tp_degree=4)
    return {"setup_s": 0.0,
            "run_s": round(time.perf_counter() - t0, 2),
            "hybrid_s": round(hybrid_s, 2), "zero3_s": round(zero3_s, 2),
            "serial_loss": round(serial_loss, 5),
            "hybrid_first_loss": round(losses[0], 5),
            "zero3_first_loss": round(zl[0], 5),
            "zero3_bytes_in_use_mib": [round(u / 2**20, 1) for u in used],
            "tp4_server": server}


# ----------------------------------------------------------------- main

def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--phases", default=",".join(map(str, ALL_PHASES)),
                   help="comma list of phases to run (default: all; "
                        "phase 0 always runs)")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU with interpreted kernels "
                        "and a virtual 4-device mesh; every line is "
                        "labelled and the summary names the CPU")
    return p.parse_args(argv)


def main(argv=None) -> int:
    global _LABEL
    args = parse_args(argv)
    phases = sorted({0} | {int(p) for p in args.phases.split(",") if p})
    if not set(phases) <= set(ALL_PHASES):
        sys.exit(f"chip_smoke: unknown phase in --phases {args.phases!r}")
    rehearse = bool(args.rehearse_cpu)
    if rehearse:
        _LABEL = "[CPU-REHEARSAL] "
        # before jax is imported: pin the CPU and give it four devices
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    t_start = time.perf_counter()
    here = os.path.dirname(os.path.realpath(__file__))
    try:
        import paddle_tpu
    except ImportError as e:
        sys.exit(f"chip_smoke: FAIL: paddle_tpu is not importable from "
                 f"{here}: {e}")
    # the checkout this script sits in is what it vouches for, not a copy
    # that PYTHONPATH or site-packages happens to offer
    if os.path.dirname(os.path.dirname(
            os.path.realpath(paddle_tpu.__file__))) != here:
        sys.exit(f"chip_smoke: FAIL: paddle_tpu was imported from "
                 f"{paddle_tpu.__file__}, not from beside {here}")
    import jax
    from paddle_tpu.core import compile_cache, native

    compile_cache.configure()      # env var, else <checkout>/.jax_cache
    sz = sizes(rehearse)
    report = {"phases": {}, "skipped": {}}

    device = phase0_device(rehearse)
    cache0 = compiles()
    runners = {1: phase1_trainer, 2: phase2_server, 3: phase3_kernels,
               4: phase4_four_chips}
    for ph in ALL_PHASES[1:]:
        if ph not in phases:
            report["skipped"][str(ph)] = "not requested (--phases)"
            say(f"phase {ph}: NOT RUN — not requested (--phases)")
            continue
        if ph == 4 and len(jax.devices()) < 4:
            report["skipped"]["4"] = (
                f"needs 4 devices, jax sees {len(jax.devices())}")
            say(f"phase 4: NOT RUN — {report['skipped']['4']}")
            continue
        before = compiles()
        out = runners[ph](sz, rehearse)
        moved = since(before)
        out["cache"] = {"hits": moved["hits"], "misses": moved["misses"]}
        report["phases"][str(ph)] = out
        say(f"phase {ph}: PASS {json.dumps(out)}")

    total = since(cache0)
    rep = compile_cache.cache_report()
    summary = {
        "ok": True,
        "device": device,
        "phases_run": [0] + [int(p) for p in report["phases"]],
        "phases_skipped": report["skipped"],
        "phases": report["phases"],
        "seconds": {
            "total": round(time.perf_counter() - t_start, 2),
            "setup": round(sum(p["setup_s"]
                               for p in report["phases"].values()), 2),
            "run": round(sum(p["run_s"]
                             for p in report["phases"].values()), 2)},
        "compile_cache": {"dir": rep["dir"], "hits": total["hits"],
                          "misses": total["misses"],
                          "entries": rep["entries"]},
        "native": native.status(),
        "versions": {"jax": jax.__version__,
                     "libtpu": importlib.metadata.version("libtpu")},
        "claim": None,
    }
    if rehearse:
        summary["rehearsal"] = "cpu, tiny sizes, interpreted kernels"
        # labelled like every other rehearsal line, so neither line
        # parses as the result of a chip run
        say(f"summary: {json.dumps(summary)}")
        say(json.dumps({"ok": True, "device": device}))
        return 0
    print(f"summary: {json.dumps(summary)}", flush=True)
    # the result line: these two keys and nothing else (the driver's
    # contract); everything above it is the account
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
