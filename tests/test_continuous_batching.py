"""Continuous batching (ISSUE 11): chunked prefill interleaved with
decode ticks, the SLO-aware per-tick scheduler, and the streaming serve
endpoint.

The headline contracts pinned here:

* chunked prefill streams are BIT-identical to monolithic prefill
  (same `PagedChunkView` writes, same offset causal mask), composing
  with the prefix cache, TP degree 2, spec decode and overlap;
* a running stream keeps receiving tokens while an arriving long
  prompt is absorbed (the bounded inter-token-gap property monolithic
  prefill cannot give);
* SLO-aware shedding rejects the newest lowest-priority arrivals with
  ``reason=slo_shed`` only while the live sketches breach targets AND
  the queue is past the watermark;
* ``POST /generate`` streams tokens as Server-Sent Events, and a
  client disconnect or timeout propagates to slot eviction and block
  release.
"""

import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability import http as obs_http
from paddle_tpu.observability import metrics as obs_metrics


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


def _serve(model, prompts, budgets, chunk, **kw):
    eng = ServingEngine(model, max_batch=2, max_context=64,
                        block_size=16, prefill_chunk=chunk, **kw)
    reqs = [eng.add_request(Request(p, max_new_tokens=b))
            for p, b in zip(prompts, budgets)]
    eng.run()
    assert eng.stats()["free_blocks"] == eng.num_blocks
    assert eng.stats()["reserved"] == 0
    return eng, [list(r.output_ids) for r in reqs]


# ------------------------------------------------------------ bit parity

def test_chunked_equals_monolithic_bit_parity(model):
    """THE tentpole pin: a chunk size that splits both prompts unevenly
    (29 -> 5x5+4, 11 -> 2x5+1) streams token-for-token what monolithic
    prefill streams.  The wider sweep (more chunk sizes x custom
    ladders) is the @slow test below."""
    rng = np.random.RandomState(0)
    prompts = (rng.randint(1, 1000, (29,)), rng.randint(1, 1000, (11,)))
    budgets = (8, 6)
    _, base = _serve(model, prompts, budgets, chunk=0)
    eng, got = _serve(model, prompts, budgets, chunk=5)
    assert got == base
    assert eng.stats()["prefill_chunks"] == 6 + 3


@pytest.mark.slow   # composition pin — full runs cover it (tier-1
                    # budget: ISSUE 11 keeps only the core pins fast)
def test_chunked_prefix_hit_composition(model):
    """A prefix-cache hit under chunking is just a chunked prefill
    starting at the cached offset: streams identical to the monolithic
    engine's, fewer chunks for the hit, hits counted."""
    rng = np.random.RandomState(1)
    sysp = list(rng.randint(1, 1000, (32,)))
    tails = [[int(t)] for t in rng.randint(1, 1000, (3,))]

    def drive(chunk):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, prefill_chunk=chunk,
                            prefix_cache=True)
        outs, chunks = [], []
        for t in tails:
            r = eng.add_request(Request(sysp + t, max_new_tokens=5))
            eng.run()
            outs.append(list(r.output_ids))
            chunks.append(r._prefill_chunks)
        return eng, outs, chunks

    _, base, _ = drive(0)
    eng, got, chunks = drive(8)
    assert got == base
    assert eng.stats()["prefix_cache"]["hits"] >= 2
    # miss absorbed 33 tokens in 5 chunks of 8; a hit starts at the
    # cached offset 32 and needs ONE chunk for the 1-token suffix
    assert chunks[0] == 5 and chunks[1] == 1 and chunks[2] == 1
    assert eng.stats()["free_blocks"] == eng.num_blocks


@pytest.mark.slow   # composition pin — full runs cover it
def test_chunked_overlap_parity(model):
    """Chunk interleaving forces real boundaries while prompts are
    absorbing, but the overlap fast path still runs between them — and
    streams stay identical to the synchronous loop."""
    rng = np.random.RandomState(2)
    prompts = (rng.randint(1, 1000, (20,)), rng.randint(1, 1000, (9,)))
    with flag_guard(serving_overlap=False):
        _, sync = _serve(model, prompts, (9, 7), chunk=8)
    with flag_guard(serving_overlap=True):
        _, ov = _serve(model, prompts, (9, 7), chunk=8)
    assert ov == sync


def test_chunk_overlap_gate_logic(model):
    """Fast twin of the @slow parity drill: `_chunk_overlap_ok` only
    clears NON-FINAL chunks for dispatch behind the chained tick —
    flag off, un-chunked engines, and a pending FINAL chunk (which
    must host-sync the NaN screen and install the shadow row at a
    real boundary) all force `_can_overlap` back to False."""
    eng = ServingEngine(model, max_batch=2, max_context=96,
                        block_size=16, prefill_chunk=8,
                        prefix_cache=False)
    req = Request(np.arange(1, 21), max_new_tokens=2)   # 20 toks, 2+ chunks
    eng.prefilling.append(req)
    assert eng._chunk_overlap_ok()              # 20 - 0 > 8: non-final
    with flag_guard(serving_chunk_overlap=False):
        assert not eng._chunk_overlap_ok()      # flag gates the path
    req._chunk_off = 16
    assert not eng._chunk_overlap_ok()          # 4 left: FINAL chunk
    eng.prefilling.clear()


@pytest.mark.slow  # ~8s measured: two full engine serves (flag off/on)
                   # over a 40-token absorbing prompt; the gate-logic
                   # twin above stays fast
def test_chunk_boundary_overlap_parity_and_counter(model):
    """PR 11 remainder (ISSUE 19 satellite): with
    ``FLAGS_serving_chunk_overlap`` the NON-FINAL chunks of an
    absorbing prompt dispatch BEHIND the chained tick instead of
    forcing a real boundary.  Streams must stay bit-identical either
    way (chunk writes land in the admission's own blocks, disjoint
    from every decoding slot's), and the engine counter proves the
    overlap path actually ran."""
    rng = np.random.RandomState(4)
    prompts = (rng.randint(1, 1000, (6,)), rng.randint(1, 1000, (40,)))
    budgets = (24, 4)
    with flag_guard(serving_overlap=True, serving_chunk_overlap=False):
        eng0, base = _serve(model, prompts, budgets, chunk=8)
    with flag_guard(serving_overlap=True, serving_chunk_overlap=True):
        eng1, got = _serve(model, prompts, budgets, chunk=8)
    assert got == base
    assert eng0.overlap_chunks_total == 0
    assert eng1.overlap_chunks_total > 0
    # chunk count is conserved: overlap moves chunks off the boundary,
    # it never adds or drops any
    assert eng1.stats()["prefill_chunks"] == eng0.stats()["prefill_chunks"]


# ------------------------------------- the bounded inter-token-gap claim

def test_long_arrival_bounds_running_stream(model):
    """Structural pin of the tentpole property (no wall clocks): while
    a 60-token prompt is absorbed, a chunked engine keeps feeding the
    running stream every boundary; the monolithic engine absorbs the
    whole prompt inside ONE boundary, so the stream advances at most
    once in that window."""
    rng = np.random.RandomState(3)
    long_p = rng.randint(1, 1000, (60,))
    short_p = rng.randint(1, 1000, (6,))

    def drive(chunk):
        eng = ServingEngine(model, max_batch=2, max_context=96,
                            block_size=16, prefill_chunk=chunk,
                            prefix_cache=False)
        s = eng.add_request(Request(short_p, max_new_tokens=40))
        eng.step()
        eng.step()
        lr = eng.add_request(Request(long_p, max_new_tokens=3))
        grew = 0
        while not lr.output_ids:
            n0 = len(s.output_ids)
            if not eng.step():
                break
            if len(s.output_ids) > n0:
                grew += 1
        eng.run()
        assert eng.stats()["free_blocks"] == eng.num_blocks
        return grew, lr

    grew_c, lr_c = drive(10)
    assert lr_c._prefill_chunks == 6          # ceil(60 / 10)
    assert grew_c >= 5                        # stream fed between chunks
    grew_m, lr_m = drive(0)
    assert lr_m._prefill_chunks == 0
    assert grew_m <= 1                        # the stall chunking removes


# ----------------------------------------------- scheduler: shed/priority

def test_slo_shed_rejects_newest_lowest_priority(model):
    """With the sketches breaching and the queue past the watermark,
    the scheduler sheds down to the watermark — newest lowest-priority
    victims first — with reason=slo_shed on every surface."""
    obs_metrics.reset()
    with flag_guard(serving_slo_shed=True, serving_ttft_slo_ms=1e-4,
                    serving_shed_queue_depth=2):
        eng = ServingEngine(model, max_batch=1, max_context=64,
                            block_size=16)
        eng.add_request(Request(np.arange(1, 8), max_new_tokens=3))
        eng.run()                     # loads the (breaching) TTFT sketch
        rng = np.random.RandomState(4)
        reqs = [eng.add_request(
            Request(rng.randint(1, 1000, (7,)), max_new_tokens=3,
                    priority=(1 if i == 0 else 0)))
            for i in range(6)]
        eng.run()
    st = eng.stats()
    assert st["slo_sheds"] == 4
    served = [r for r in reqs if r.done]
    shed = [r for r in reqs if r.shed]
    assert len(served) == 2 and len(shed) == 4
    # the priority-1 request and the oldest priority-0 request survive
    assert reqs[0] in served and reqs[1] in served
    for r in shed:
        assert r.trace["outcome"] == "rejected:slo_shed"
        assert not r.output_ids
    snap = obs_metrics.snapshot()
    rej = {dict(s["labels"])["reason"]: s["value"]
           for s in snap["serving.rejections"]["series"]}
    assert rej["slo_shed"] == 4
    assert snap["serving.slo_sheds"]["series"][0]["value"] == 4
    from paddle_tpu.observability.export import render_prometheus
    assert "serving_slo_sheds 4" in render_prometheus()
    assert st["free_blocks"] == eng.num_blocks


def test_no_shed_without_breach_and_priority_order(model):
    """Shedding needs BOTH conditions — a deep queue under HEALTHY
    sketches admits everything — and admission order follows priority
    (FIFO within a priority, the legacy order for all-equal)."""
    with flag_guard(serving_slo_shed=True, serving_ttft_slo_ms=1e9,
                    serving_shed_queue_depth=1):
        eng = ServingEngine(model, max_batch=1, max_context=64,
                            block_size=16)
        lo = eng.add_request(Request(np.arange(1, 8), max_new_tokens=3))
        hi = eng.add_request(Request(np.arange(2, 9), max_new_tokens=3,
                                     priority=5))
        mid = eng.add_request(Request(np.arange(3, 10), max_new_tokens=3,
                                      priority=5))
        eng.run()
    assert eng.stats()["slo_sheds"] == 0
    assert all(r.done for r in (lo, hi, mid))
    assert [r.rid for r in eng.finished] == [hi.rid, mid.rid, lo.rid]


# --------------------------------------------------------- cancellation

def test_cancel_running_and_waiting_releases_everything(model):
    """cancel() on a running request evicts its slot and releases its
    blocks at the next boundary; on a waiting request it drops it from
    the queue.  Nothing leaks either way."""
    eng = ServingEngine(model, max_batch=1, max_context=64, block_size=16)
    running = eng.add_request(Request(np.arange(1, 9), max_new_tokens=30))
    queued = eng.add_request(Request(np.arange(2, 10), max_new_tokens=4))
    eng.step()
    eng.step()
    running.cancel()
    queued.cancel()
    eng.run()
    assert not running.done and len(running.output_ids) < 30
    assert not queued.done and not queued.output_ids
    assert running in eng.finished and queued in eng.finished
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    assert running.trace["outcome"] == "cancelled"


def test_cancel_mid_chunked_prefill_aborts_and_releases(model):
    """A cancel landing while the prompt is still absorbing aborts the
    remaining chunks and releases the shadow-row blocks."""
    eng = ServingEngine(model, max_batch=2, max_context=96, block_size=16,
                        prefill_chunk=8, prefix_cache=False)
    r = eng.add_request(Request(np.arange(1, 61), max_new_tokens=4))
    eng.step()                       # first chunk only (budget 1/tick)
    assert r._prefilling and r._prefill_chunks >= 1
    r.cancel()
    eng.run()
    assert not r.output_ids and not r.done
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    assert st["prefilling"] == 0


# ------------------------------------------------------- observability

def test_chunk_counters_traces_and_flight_records(model):
    """serving.prefill_chunks on /metrics, per-request prefill_chunks
    in the lifecycle trace, chunk events + per-tick chunk counts in the
    flight ring."""
    obs_metrics.reset()
    flight_recorder.default_recorder().clear()
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16,
                        prefill_chunk=8, prefix_cache=False)
    r = eng.add_request(Request(np.arange(1, 21), max_new_tokens=4))
    eng.run()
    assert r.trace["prefill_chunks"] == 3     # ceil(20 / 8)
    snap = obs_metrics.snapshot()
    assert snap["serving.prefill_chunks"]["series"][0]["value"] == 3
    from paddle_tpu.observability.export import render_prometheus
    text = render_prometheus()
    assert "serving_prefill_chunks 3" in text
    rec = flight_recorder.default_recorder()
    chunk_events = [e for e in rec.events()
                    if e.get("kind") == "prefill_chunk"]
    assert len(chunk_events) == 3
    assert chunk_events[-1]["done"] is True
    assert chunk_events[0]["start"] == 0 and chunk_events[0]["tokens"] == 8
    tick_recs = [s for s in rec.steps()
                 if s.get("timeline") == "serving"
                 and s.get("prefill_chunks")]
    assert sum(s["prefill_chunks"] for s in tick_recs) == 3


# ------------------------------------------------------- SSE endpoint

def _sse_events(resp):
    """Parse an SSE byte stream into (event, payload) pairs."""
    event = None
    for raw in resp:
        line = raw.decode().rstrip("\n")
        if line.startswith("event: "):
            event = line[7:]
        elif line.startswith("data: "):
            yield event, json.loads(line[6:])
            event = None


@pytest.mark.slow  # 7s measured (PR 18 re-budget): engine + HTTP server round trip; the chunked-parity and arrival-bound pins stay fast
def test_sse_generate_stream_and_disconnect_cancels(model):
    """POST /generate streams each token as SSE and finishes with a
    `done` event carrying the full output; hanging up mid-stream
    propagates to slot eviction and block release."""
    import http.client

    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16,
                        prefill_chunk=8)
    stop = threading.Event()
    obs_http.attach_engine(eng)
    assert obs_http.current_engine() is eng
    srv = obs_http.MetricsServer(0, "127.0.0.1")
    t = threading.Thread(target=eng.serve_forever, args=(stop,),
                         daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        body = json.dumps({"prompt_ids": list(range(1, 10)),
                           "max_new_tokens": 6})
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "text/event-stream"
        toks, done = [], None
        for event, d in _sse_events(resp):
            if event == "done":
                done = d
                break
            if event is None and "token" in d:
                toks.append(d["token"])
        conn.close()
        assert done["outcome"] == "finished"
        assert done["output_ids"] == toks and len(toks) == 6
        # parity with driving the engine directly
        ref = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16)
        rr = ref.add_request(Request(list(range(1, 10)),
                                     max_new_tokens=6))
        ref.run()
        assert rr.output_ids == toks

        # malformed body -> 400, engine unharmed
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request("POST", "/generate", body="{}",
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
        conn.close()

        # disconnect mid-stream -> cancel -> eviction + block release
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": list(range(1, 9)), "max_new_tokens": 500}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read(40)                 # a few tokens, then hang up
        conn.close()
        deadline = time.time() + 20
        while time.time() < deadline:
            st = eng.stats()
            if st["free_blocks"] == eng.num_blocks and st["active"] == 0 \
                    and st["prefilling"] == 0:
                break
            time.sleep(0.05)
        st = eng.stats()
        assert st["free_blocks"] == eng.num_blocks and st["active"] == 0
    finally:
        stop.set()
        t.join(timeout=10)
        srv.close()
    assert not t.is_alive()


def test_sse_timeout_cancels_and_reports(model):
    """A request whose timeout_s expires gets an `error` SSE event and
    is cancelled.  The engine loop is deliberately NOT running, so the
    request can never produce a token before the deadline — the
    deterministic worst case; the subsequent run() turns the cancel
    into a queue drop with nothing leaked."""
    import http.client

    eng = ServingEngine(model, max_batch=1, max_context=64, block_size=16)
    obs_http.attach_engine(eng)
    srv = obs_http.MetricsServer(0, "127.0.0.1")
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": list(range(1, 9)), "max_new_tokens": 8,
             "timeout_s": 0.3}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        err = next((d for ev, d in _sse_events(resp) if ev == "error"),
                   None)
        conn.close()
        assert err is not None and err["error"] == "timeout"
        assert len(eng.waiting) == 1 and eng.waiting[0].cancelled
        eng.run()            # the boundary drops the cancelled request
        st = eng.stats()
        assert st["free_blocks"] == eng.num_blocks
        assert st["waiting"] == 0 and st["active"] == 0
    finally:
        srv.close()


def test_serving_http_flag_gate():
    """FLAGS_serving_http_port=0 (the default) starts nothing."""
    with flag_guard(serving_http_port=0):
        assert obs_http.start_serving_from_flags() is None


def test_sse_terminal_error_frame_format(model):
    """ISSUE 15 satellite pin: a stream the ENGINE ends (outcome=
    error|poisoned|slo_shed|drained) closes with a terminal
    ``event: error`` frame — exactly ``{"rid", "reason",
    "output_ids"}`` — instead of silently closing; a stream that
    finishes keeps the ``event: done`` frame.  Driven through a drain:
    request A (admitted) finishes in-flight with `done`, request B
    (waiting behind A's slot) is cancelled ``reason=drained``; POST
    /drain answers 202 and /healthz flips to 503 draining."""
    import http.client

    eng = ServingEngine(model, max_batch=1, max_context=64, block_size=16)
    stop = threading.Event()
    obs_http.attach_engine(eng)
    srv = obs_http.MetricsServer(0, "127.0.0.1")
    t = threading.Thread(target=eng.serve_forever, args=(stop,),
                         daemon=True)
    t.start()
    try:
        conn_a = http.client.HTTPConnection("127.0.0.1", srv.port,
                                            timeout=60)
        conn_a.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": [1, 2, 3], "max_new_tokens": 24}),
            headers={"Content-Type": "application/json"})
        resp_a = conn_a.getresponse()
        assert resp_a.status == 200
        events_a = _sse_events(resp_a)
        first = next(d for ev, d in events_a if ev is None)
        assert "token" in first          # A is admitted and streaming
        conn_b = http.client.HTTPConnection("127.0.0.1", srv.port,
                                            timeout=60)
        conn_b.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": [4, 5, 6], "max_new_tokens": 4}),
            headers={"Content-Type": "application/json"})
        resp_b = conn_b.getresponse()
        assert resp_b.status == 200      # enqueued behind A's slot
        conn_d = http.client.HTTPConnection("127.0.0.1", srv.port,
                                            timeout=60)
        conn_d.request("POST", "/drain")
        resp_d = conn_d.getresponse()
        assert resp_d.status == 202
        assert json.loads(resp_d.read())["draining"] is True
        conn_d.close()
        # B never admitted: terminal error frame, format pinned
        ev_b, frame_b = next((e, d) for e, d in _sse_events(resp_b)
                             if e is not None)
        conn_b.close()
        assert ev_b == "error"
        assert frame_b == {"rid": frame_b["rid"], "reason": "drained",
                           "output_ids": []}
        assert set(frame_b) == {"rid", "reason", "output_ids"}
        # A finishes in-flight inside the drain deadline: done frame
        done_a = next(d for ev, d in events_a if ev == "done")
        conn_a.close()
        assert done_a["outcome"] == "finished"
        assert len(done_a["output_ids"]) == 24
        # the drained engine reports 503 draining on /healthz
        conn_h = http.client.HTTPConnection("127.0.0.1", srv.port,
                                            timeout=60)
        conn_h.request("GET", "/healthz")
        resp_h = conn_h.getresponse()
        doc = json.loads(resp_h.read())
        conn_h.close()
        assert resp_h.status == 503 and doc["reason"] == "draining"
        t.join(timeout=30)               # drain() returns the loop
        assert not t.is_alive()
        assert eng.stats()["free_blocks"] == eng.num_blocks
    finally:
        stop.set()
        obs_http.attach_engine(None)
        srv.close()


# ------------------------ serve_forever keeps a tick in flight (ISSUE 33)

_FOREVER: dict = {}


def _forever_engine(model, chunk):
    """One tiny engine a chunk size for the cases below (blocksan armed:
    every harvest reconciles the block ledger), built at first use."""
    if chunk not in _FOREVER:
        with flag_guard(enable_jaxsan=True):
            _FOREVER[chunk] = ServingEngine(
                model, max_batch=4, max_context=96, block_size=16,
                steps_per_tick=2, prefill_chunk=chunk, prefix_cache=False)
    return _FOREVER[chunk]


def _requests(seed, budgets, sampled=False, lengths=(7, 19, 11, 26)):
    rng = np.random.RandomState(seed)
    return [Request(rng.randint(1, 1000, (n,)), max_new_tokens=b,
                    do_sample=sampled and i % 2 == 0, top_k=20,
                    temperature=0.9, seed=100 + i)
            for i, (n, b) in enumerate(zip(lengths, budgets))]


def _reference(eng, seed, budgets, sampled=False):
    """The same requests through the synchronous cycle
    (``serving_overlap=False``): what every chained stream must equal."""
    with flag_guard(serving_overlap=False):
        reqs = [eng.add_request(r)
                for r in _requests(seed, budgets, sampled)]
        eng.run()
    eng.finished.clear()
    return [list(r.output_ids) for r in reqs]


def _until(cond, what, timeout=60.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _served(eng, reqs):
    """Wait for the streams to end AND for the boundary after the last
    harvest, which gives the finished requests' slots and blocks back."""
    _until(lambda: all(r.done for r in reqs)
           and eng.stats()["active"] == 0, "the streams' end")


def _chaining(n, running):
    """The last `n` ticks were each enqueued behind an unharvested one,
    with `running` requests holding a slot."""
    return lambda log: len(log) >= n and all(
        e["launched"] and e["chained"] and len(e["rids"]) == running
        for e in log[-n:])


class _Loop:
    """`eng.serve_forever` on a thread, with a log of its dispatches —
    one entry a `_dispatch_tick` call: did it launch a tick, was the tick
    chained, which requests held a slot after it — and a way to HOLD the
    loop right after a dispatch, so that another thread (the test's: the
    generator of the traffic) acts at a known point: with one tick
    enqueued behind another, both unharvested."""

    def __init__(self, eng):
        self.eng, self.log = eng, []
        self.stop = threading.Event()
        self.error = None
        self.fail = None            # "dispatch" | "harvest": raise once
        self._when = None
        self._held, self._go = threading.Event(), threading.Event()
        self._last = None

    def hold_when(self, pred):
        self._held.clear()
        self._go.clear()
        self._when = pred

    def held(self, timeout=60.0):
        assert self._held.wait(timeout), "the loop never got there"
        return len(self.log)

    def release(self):
        self._go.set()

    def ticks_until(self, n, pred):
        """The ticks launched from log entry `n` to the first entry at
        which `pred(entry)` holds (a boundary acted), that one left out."""
        def first():
            return next((i for i in range(n, len(self.log))
                         if pred(self.log[i])), None)

        _until(lambda: first() is not None, "the boundary")
        return [e for e in self.log[n:first()] if e["launched"]]

    def __enter__(self):
        eng = self.eng
        dispatch_tick, harvest_tick = eng._dispatch_tick, eng._harvest_tick

        def dispatch(boundary=True, chain=None):
            if chain is not None and self.fail == "dispatch":
                self.fail = None
                raise RuntimeError("injected: the chained dispatch raised")
            pend = dispatch_tick(boundary=boundary, chain=chain)
            self._last = pend if pend is not None else self._last
            self.log.append({
                "launched": pend is not None, "chained": chain is not None,
                "draining": eng._draining,
                "rids": {r.rid for r in eng.slot_req if r is not None}})
            if self._when is not None and self._when(self.log):
                self._when = None
                self._held.set()
                self._go.wait(60)
                self._go.clear()
            return pend

        def harvest(pend):
            if self.fail == "harvest" and self._last is not pend:
                self.fail = None     # a tick is chained behind this one
                raise RuntimeError("injected: the harvest raised")
            return harvest_tick(pend)

        eng._dispatch_tick, eng._harvest_tick = dispatch, harvest

        def target():
            try:
                eng.serve_forever(self.stop)
            except BaseException as e:  # noqa: BLE001 - re-raised at exit
                self.error = e

        self.thread = threading.Thread(target=target, daemon=True)
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self._go.set()
        self.thread.join(60)
        del self.eng._dispatch_tick, self.eng._harvest_tick
        assert not self.thread.is_alive()
        if self.error is not None and exc[0] is None:
            raise self.error
        return False


def _settled(eng):
    """Every block and reservation came back, under an armed blocksan."""
    st = eng.stats()
    return (st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
            and st["active"] == 0 and eng._blocksan is not None
            and eng._blocksan.verifies > 0)


def _case_parity(model, chunk, sampled):
    """(1) Streams under the chained `serve_forever`, arrivals from a
    generator thread, equal the synchronous cycle's token for token; the
    chain engaged, and the counter and the spans' totals say so."""
    eng = _forever_engine(model, chunk)
    budgets = (24, 17, 30, 9)
    want = _reference(eng, 11 + chunk, budgets, sampled)
    before = eng.stats()["ticks"]
    obs_metrics.reset()
    with _Loop(eng) as lp:
        reqs = _requests(11 + chunk, budgets, sampled)
        for r in reqs:
            eng.add_request(r)
            time.sleep(0.004)
        _served(eng, reqs)
    assert [list(r.output_ids) for r in reqs] == want
    assert all(r.outcome == "finished" for r in reqs)
    chained = sum(e["launched"] and e["chained"] for e in lp.log)
    launched = sum(e["launched"] for e in lp.log)
    assert launched == eng.stats()["ticks"] - before
    assert 0 < chained < launched        # boundaries still came
    snap = obs_metrics.snapshot()["serving.overlap_dispatches"]
    assert sum(x["value"] for x in snap["series"]) == chained
    assert _settled(eng)


def _case_no_starvation(model, chunk):
    """(2) Three long answers chain; one is cancelled from another thread
    and is gone at the first boundary after the ticks in flight; a
    request added meanwhile gets its slot within one tick of the tick in
    flight at its arrival.  Neither waits for the answers to end."""
    eng = _forever_engine(model, chunk)
    budgets = (60, 60, 60, 12)
    want = _reference(eng, 23 + chunk, budgets)
    with _Loop(eng) as lp:
        *reqs, late = _requests(23 + chunk, budgets)
        lp.hold_when(_chaining(3, running=3))
        for r in reqs:
            eng.add_request(r)
        n = lp.held()                    # two ticks in flight, none waited
        reqs[1].cancel()
        lp.hold_when(_chaining(2, running=2))
        lp.release()
        gone = lp.ticks_until(n, lambda e: reqs[1].rid not in e["rids"])
        assert len(gone) <= 1 and all(e["chained"] for e in gone)
        assert reqs[1].outcome == "cancelled"
        m = lp.held()                    # the other two chain on
        eng.add_request(late)
        lp.release()
        came = lp.ticks_until(m, lambda e: late.rid in e["rids"])
        assert len(came) <= 1 and all(e["chained"] for e in came)
        _served(eng, (reqs[0], reqs[2], late))
    got = [list(r.output_ids) for r in reqs + [late]]
    assert got[0] == want[0] and got[2] == want[2] and got[3] == want[3]
    assert 0 < len(got[1]) < 60 and got[1] == want[1][:len(got[1])]
    assert _settled(eng)


def _case_in_flight(model, what):
    """(3) The loop is stopped, asked to drain, or a tick raises, each
    with one tick enqueued behind another: every token of a dispatched
    tick reaches its request (or the tick's slots are evicted
    ``outcome=error`` with what they had), the ledger reconciles, and
    the loop returns."""
    budgets = (40, 40, 40, 8)
    if what == "drain":                  # a drained engine stays closed
        with flag_guard(enable_jaxsan=True):
            eng = ServingEngine(model, max_batch=3, max_context=64,
                                block_size=16, steps_per_tick=2,
                                prefix_cache=False)
    else:
        eng = _forever_engine(model, 0)
    want = _reference(eng, 37, budgets)
    errors = eng.tick_errors
    with _Loop(eng) as lp:
        *reqs, late = _requests(37, budgets)
        lp.hold_when(_chaining(3, running=3))
        for r in reqs:
            eng.add_request(r)
        n = lp.held()
        slots = [r.slot for r in reqs]
        if what == "stop":
            lp.stop.set()
        elif what == "drain":
            eng.request_drain()
        else:
            lp.fail = what               # "dispatch" or "harvest"
        lp.release()
        if what in ("stop", "drain"):
            lp.thread.join(60)
        else:
            _until(lambda: all(r.outcome for r in reqs),
                   "the error evictions")
        if what == "stop":
            # returned with nothing in flight: what was dispatched was
            # harvested, token for token, and the engine goes on from it
            assert not lp.thread.is_alive() and lp.error is None
            assert len(lp.log) == n      # and no further tick was chained
            for r, slot, ref in zip(reqs, slots, want):
                assert 1 < len(r.output_ids) < 40 and not r.done
                assert len(r.output_ids) == int(eng.tok_pos[slot])
                assert r.output_ids == ref[:len(r.output_ids)]
            eng.run()
            assert [list(r.output_ids) for r in reqs] == want[:3]
        elif what == "drain":
            # the tick in flight was harvested BEFORE drain()'s own
            # steps; the running answers finished inside the deadline
            assert not lp.thread.is_alive() and lp.error is None
            assert not lp.ticks_until(n, lambda e: e["draining"])
            assert eng._drain_info is not None
            assert eng._drain_info["evicted_running"] == 0
            assert [list(r.output_ids) for r in reqs] == want[:3]
            with pytest.raises(ValueError, match="draining"):
                eng.add_request(late)
        else:
            # the ticks in flight are abandoned and exactly their slots
            # evicted with what they had; the loop lives and serves on
            assert eng.tick_errors == errors + 1
            for r, ref in zip(reqs, want):
                assert r.outcome == "error"
                assert 1 < len(r.output_ids) < 40
                assert r.output_ids == ref[:len(r.output_ids)]
            eng.add_request(late)
            _served(eng, [late])
            assert late.outcome == "finished" \
                and list(late.output_ids) == want[3]
    eng.finished.clear()
    assert _settled(eng)


def _case_chunk_behind_chain_raises(model):
    """A prefill chunk riding behind a chained tick raises: its own
    request is struck and tried again, and the two ticks in flight are
    still harvested — the running stream loses no token."""
    from paddle_tpu.testing import chaos
    eng = _forever_engine(model, 8)
    rng = np.random.RandomState(5)
    prompts = (rng.randint(1, 1000, (6,)), rng.randint(1, 1000, (40,)))
    with flag_guard(serving_overlap=False):
        want = [eng.add_request(Request(p, max_new_tokens=b))
                for p, b in zip(prompts, (24, 4))]
        eng.run()
    eng.finished.clear()
    errors, before = eng.tick_errors, eng.overlap_chunks_total
    reqs = [eng.add_request(Request(p, max_new_tokens=b))
            for p, b in zip(prompts, (24, 4))]
    # chunk dispatches: the short prompt's one, the long one's first (at
    # its admission's boundary), then its second — behind a chained tick
    with chaos.fail_at("serving.prefill.dispatch", on_calls=[3],
                       exc=RuntimeError) as f:
        eng.run()
    assert f.fires == 1 and eng.tick_errors == errors + 1
    assert eng.overlap_chunks_total > before      # the retry's rode too
    assert [list(r.output_ids) for r in reqs] \
        == [list(r.output_ids) for r in want]
    assert reqs[1]._strikes == 1 and reqs[1].outcome == "finished"
    eng.finished.clear()
    assert _settled(eng)


_TICK_IN_FLIGHT = {
    "parity-greedy": (_case_parity, 0, False),
    "parity-sampled": (_case_parity, 0, True),
    "parity-greedy-chunked": (_case_parity, 8, False),
    "parity-sampled-chunked": (_case_parity, 8, True),
    "no-starvation": (_case_no_starvation, 0),
    "no-starvation-chunked": (_case_no_starvation, 8),
    "stop-with-a-tick-in-flight": (_case_in_flight, "stop"),
    "drain-with-a-tick-in-flight": (_case_in_flight, "drain"),
    "chained-dispatch-raises": (_case_in_flight, "dispatch"),
    "harvest-raises-with-a-tick-in-flight": (_case_in_flight, "harvest"),
    "chunk-behind-the-chain-raises": (_case_chunk_behind_chain_raises,),
}


@pytest.mark.parametrize("case", list(_TICK_IN_FLIGHT))
def test_serve_forever_keeps_a_tick_in_flight(model, case):
    """ISSUE 33: `serve_forever` drives the cycle `run()` drives — tick
    t+1 is enqueued on tick t's device tokens before t is harvested —
    and everything only a boundary acts on (an arrival, a cancellation,
    a drain request, the stop event) ends the chain within one tick."""
    fn, *args = _TICK_IN_FLIGHT[case]
    fn(model, *args)


# ----------------------------------------------- heavy composition pins

@pytest.mark.slow   # compiles a TP program grid — full runs cover it
def test_chunked_tp2_parity(model):
    """Chunked prefill composes with tensor-parallel serving: degree-2
    chunked streams are bit-identical to degree-1 monolithic."""
    rng = np.random.RandomState(6)
    prompts = (rng.randint(1, 1000, (24,)), rng.randint(1, 1000, (9,)))
    _, base = _serve(model, prompts, (7, 5), chunk=0)
    eng, got = _serve(model, prompts, (7, 5), chunk=8, tp_degree=2)
    assert got == base
    assert eng.stats()["prefill_chunks"] > 0


@pytest.mark.slow   # compiles the spec-tick grid — full runs cover it
def test_chunked_spec_decode_parity():
    """Chunked prefill composes with speculative decoding (the draft
    pools absorb each chunk through the same program): greedy streams
    stay bit-identical to the plain monolithic engine."""
    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    paddle.seed(0)
    draft = GPTForCausalLM(gpt3_tiny())
    draft.eval()
    rng = np.random.RandomState(7)
    prompts = (rng.randint(1, 1000, (22,)), rng.randint(1, 1000, (10,)))
    _, base = _serve(model, prompts, (9, 9), chunk=0)
    eng, got = _serve(model, prompts, (9, 9), chunk=8,
                      draft_model=draft, spec_decode=True, spec_k=3)
    assert got == base
    assert eng.stats()["speculative"]["ticks"] > 0
    assert eng.stats()["prefill_chunks"] > 0


@pytest.mark.slow   # second model family build — full runs cover it
def test_chunked_llama_parity():
    """Chunked prefill is model-agnostic over forward_with_cache: the
    Llama family (RoPE + GQA + RMSNorm) streams identically chunked or
    monolithic."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    rng = np.random.RandomState(9)
    prompts = (rng.randint(1, 500, (21,)), rng.randint(1, 500, (9,)))
    _, base = _serve(m, prompts, (6, 5), chunk=0)
    _, got = _serve(m, prompts, (6, 5), chunk=8)
    assert got == base


@pytest.mark.slow   # many engine builds — full runs cover it
def test_chunked_parity_across_buckets_and_chunk_sizes(model):
    """The wide sweep: custom ladders x chunk sizes x prompts landing
    in every bucket, all bit-identical to monolithic."""
    rng = np.random.RandomState(8)
    prompts = tuple(rng.randint(1, 1000, (L,)) for L in (7, 18, 40, 61))
    budgets = (5, 5, 5, 5)

    def serve(chunk, ladder):
        eng = ServingEngine(model, max_batch=2, max_context=96,
                            block_size=16, prefill_chunk=chunk,
                            pad_buckets=ladder)
        reqs = [eng.add_request(Request(p, max_new_tokens=b))
                for p, b in zip(prompts, budgets)]
        eng.run()
        assert eng.stats()["free_blocks"] == eng.num_blocks
        return [list(r.output_ids) for r in reqs]

    for ladder in ("", "16,48,96"):
        base = serve(0, ladder)
        # 96 >= every prompt: the single-chunk-per-admission edge
        for chunk in (3, 8, 32, 96):
            assert serve(chunk, ladder) == base, (ladder, chunk)
