"""The program's spans on the profiler's clock (ISSUE 26): a tiny
`ServingEngine` under `serve_forever` and a tiny `to_static` step, run
under `jax.profiler.trace` on the CPU and read back with `ProfileData` —
every span of the table is on `/host:CPU` under its exact name with its
attrs, the serve phases of a tick do not overlap, the dispatch spans of
one request share its `rid`, and the flight record's phases and the
compile tracker's seconds are the same spans' durations.  Since ISSUE 33
the loop keeps a tick in flight: a `serve:tick_dispatch` span says
whether it was `chained` behind an unharvested tick.  Since ISSUE 36 a
boundary is accounted for: leaf spans nest in the phases (`LEAVES`), a
`serve:schedule` says `why` it was a boundary, and an idle period is one
`serve:idle` span."""

import collections
import contextlib
import copy
import glob
import threading
import time
import types

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import flags
from paddle_tpu.inference import serving
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.jit import to_static
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import compile_tracker, flight_recorder
from paddle_tpu.observability import metrics as obs_metrics

TRACE_ID = "0123456789abcdef"
PHASES = ("serve:schedule", "serve:tick_dispatch", "serve:harvest_wait",
          "serve:emit", "serve:idle")
NESTED = ("serve:prefill_dispatch", "serve:chunk_dispatch")
STAGES = ("to_static:discover", "to_static:trace_lower",
          "to_static:compile", "to_static:first_run")
# ISSUE 36: leaf -> (the phase it nests in, the attrs a reader takes)
LEAVES = {
    "serve:reap": ("serve:schedule", set()),
    "serve:admit": ("serve:schedule", set()),
    "serve:chunk_stage": ("serve:chunk_dispatch", {"arrays", "bytes"}),
    "serve:first_token": ("serve:chunk_dispatch", set()),
    "serve:tick_stage": ("serve:tick_dispatch", {"arrays", "bytes"}),
    "serve:readback": ("serve:emit", {"arrays", "bytes"}),
}
# `_boundary_reason`'s words, and `_cycle`'s own for a chain that found
# no slot to tick
WHY = ("overlap_off", "block_tick", "stopping", "waiting", "cancelled",
       "chunk_pending", "host_draft", "adapt_k", "host_sampling",
       "finished", "budget_spent", "xray_probe", "kind_switch")
WHY_CYCLE = WHY + ("nothing_to_chain", "idle")
# what the parent of ISSUE 36 (e24fa51) served for the fixtures' requests
# (greedy, `paddle.seed(0)`; its own `run()` at the same checkout)
PARENT_TOKENS = [[1020, 652, 652, 652, 1020], [6, 6, 743, 592],
                 [950, 77, 77],
                 [652, 652, 652, 652, 652, 652, 652, 112, 652]]
PARENT_TOKENS_LEGACY_NGRAM = [[516, 1020, 1020, 1020, 829, 1020],
                              [392, 6, 6, 836, 836]]


def _profile(d, body):
    """Run `body()` under the profiler; the program's spans of the trace
    as `(start, end, name, attrs, thread)`, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                events.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats), ln.name) for e in ln.events
                    if e.name.startswith(("serve:", "to_static:")))
    return sorted(events, key=lambda e: e[:2])


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One profile of both programs; what the tests read from it."""
    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    eng = ServingEngine(model, max_batch=2, max_context=128, block_size=16,
                        steps_per_tick=2, prefill_chunk=16)
    eng.warmup()
    rng = np.random.RandomState(0)
    reqs = [Request(rng.randint(1, 1000, (40,)), max_new_tokens=5,
                    trace_id=TRACE_ID),
            Request(rng.randint(1, 1000, (10,)), max_new_tokens=4),
            Request(rng.randint(1, 1000, (20,)), max_new_tokens=3)]
    # a long answer, sent once the engine is empty: alone in it, nothing
    # waits and nothing finishes, so its ticks chain (1 + 2 + 2 + 2 + 2)
    lone = Request(rng.randint(1, 1000, (12,)), max_new_tokens=9)

    @to_static
    def tiny_step(a):
        return a * 2 + 1

    x = paddle.to_tensor(np.ones((3,), np.float32))
    flight_recorder.default_recorder().clear()
    compile_tracker.reset()
    obs._SPAN_TOTALS.clear()
    stop = threading.Event()
    obs_metrics.reset()

    def client():
        for r in reqs:
            eng.add_request(r)
        while not all(r.done for r in reqs):
            time.sleep(0.005)
        time.sleep(0.02)           # a few naps of the empty engine
        eng.add_request(lone)
        while not lone.done:
            time.sleep(0.005)
        stop.set()

    timing = {}

    def body():
        t = threading.Thread(target=client, daemon=True)
        t.start()
        eng.serve_forever(stop)
        t.join(30)
        t0 = time.perf_counter()
        tiny_step(x)
        timing["first_call_s"] = time.perf_counter() - t0
        tiny_step(x)
        tiny_step(x)

    events = _profile(str(tmp_path_factory.mktemp("trace")), body)
    first_call_s = timing["first_call_s"]
    reqs.append(lone)
    overlap = obs_metrics.snapshot().get("serving.overlap_dispatches")
    bounds = obs_metrics.snapshot().get("serving.boundaries")
    return {"events": events, "reqs": reqs,
            "boundaries": collections.Counter(
                {x["labels"]["why"]: x["value"] for x in bounds["series"]})
            if bounds else {},
            "first_call_s": first_call_s, "totals": obs.span_totals(),
            "overlap_dispatches": sum(x["value"] for x in overlap["series"])
            if overlap else 0,
            "ticks": [r for r in flight_recorder.default_recorder().steps()
                      if r.get("timeline") == "serving"]}


def _named(run, name):
    return [e for e in run["events"] if e[2] == name]


@pytest.mark.parametrize("name", PHASES + NESTED + STAGES
                         + ("to_static:call",) + tuple(LEAVES))
def test_span_is_on_the_host_plane_under_its_name(run, leaf_run, name):
    if name == "serve:readback":
        # a GPT tick brings nothing but its tokens back (the span is
        # skipped); a spec tick its counts and accepts
        assert not _named(run, name)
        run = leaf_run
    got = _named(run, name)
    assert got, sorted({e[2] for e in run["events"]})
    assert all(e[1] > e[0] for e in got)
    # the in-memory totals count the same spans
    assert run["totals"][name]["count"] == len(got)


def test_serve_phases_of_a_tick_do_not_overlap(run):
    loop = [e for e in run["events"] if e[2] in PHASES]
    assert len({e[4] for e in loop}) == 1          # the loop's one thread
    for a, b in zip(loop, loop[1:]):
        assert a[1] <= b[0], (a[2], b[2])
    # a boundary's admissions and chunks lie inside its schedule span
    sched = _named(run, "serve:schedule")
    for e in [x for x in run["events"] if x[2] in NESTED]:
        assert any(s[0] <= e[0] and e[1] <= s[1] for s in sched), e[2]
    # and within a tick the order is schedule, dispatch, wait, emit; the
    # dispatch of the NEXT tick, chained behind it, may lie before its wait
    order = [e[2] for e in loop if e[2] != "serve:idle"
             and not e[3].get("chained")]
    i = order.index("serve:tick_dispatch")
    assert order[i - 1:i + 3] == list(PHASES[:4])


def test_tick_dispatch_says_whether_it_was_chained(run):
    """A tick enqueued behind an unharvested one carries `chained` 1, a
    boundary's tick 0 and a `serve:schedule` before it; the counter
    `serving.overlap_dispatches` counts the same dispatches."""
    loop = [e for e in run["events"] if e[2] in PHASES]
    ticks = _named(run, "serve:tick_dispatch")
    assert {e[3]["chained"] for e in ticks} == {0, 1}
    chained = [e for e in ticks if e[3]["chained"]]
    assert len(chained) == run["overlap_dispatches"] >= 3   # the lone one
    for i, e in enumerate(loop):
        if e[2] != "serve:tick_dispatch":
            continue
        before = loop[i - 1][2]
        if e[3]["chained"]:
            # straight after a dispatch, or after the emit of the tick
            # before the one it chains on: never after a schedule
            assert before in ("serve:tick_dispatch", "serve:emit")
        else:
            assert before == "serve:schedule"
    # every tick is still waited for and emitted once
    assert len(_named(run, "serve:harvest_wait")) == len(ticks) \
        == len(_named(run, "serve:emit"))


def test_span_attrs(run):
    for name, keys in (
            ("serve:schedule", {"waiting", "running"}),
            ("serve:prefill_dispatch", {"rid", "prompt_tokens"}),
            ("serve:chunk_dispatch", {"rid", "q_tokens", "kv_tokens"}),
            ("serve:tick_dispatch", {"steps", "active", "kv_tokens",
                                     "kv_blocks", "chained"}),
            ("serve:emit", {"tokens"}),
            ("to_static:discover", {"fn"}),
            ("to_static:trace_lower", {"fn"}),
            ("to_static:compile", {"fn", "cache_hit"}),
            ("to_static:first_run", {"fn"})):
        for e in _named(run, name):
            assert keys <= set(e[3]), (name, e[3])
    assert {e[3]["fn"] for n in STAGES for e in _named(run, n)} \
        == {"tiny_step"}
    ticks = _named(run, "serve:tick_dispatch")
    assert all(e[3]["steps"] in (1, 2) and 1 <= e[3]["active"] <= 2
               and e[3]["kv_tokens"] >= e[3]["active"] for e in ticks)
    # live blocks at dispatch: at least one a running sequence, and no
    # more than its tokens need (the kernel's walk, against B x columns)
    assert all(e[3]["active"] <= e[3]["kv_blocks"]
               <= e[3]["kv_tokens"] // 16 + e[3]["active"] for e in ticks)  # blocks of 16
    # every token but each request's first comes out of a tick's emit
    reqs = run["reqs"]
    assert sum(e[3]["tokens"] for e in _named(run, "serve:emit")) \
        == sum(len(r.output_ids) - 1 for r in reqs)


def test_dispatch_spans_of_one_request_share_its_rid(run):
    reqs = run["reqs"]
    for r in reqs:
        rid = r.trace_id or r.rid
        admit = [e for e in _named(run, "serve:prefill_dispatch")
                 if e[3]["rid"] == rid]
        chunks = [e for e in _named(run, "serve:chunk_dispatch")
                  if e[3]["rid"] == rid]
        assert len(admit) == 1
        assert admit[0][3]["prompt_tokens"] == len(r.prompt_ids)
        # the chunks cover the prompt, in order, after the admission
        assert sum(e[3]["q_tokens"] for e in chunks) == len(r.prompt_ids)
        assert [e[3]["kv_tokens"] for e in chunks] == sorted(
            e[3]["kv_tokens"] for e in chunks)
        assert chunks[-1][3]["kv_tokens"] == len(r.prompt_ids)
        assert admit[0][0] <= chunks[0][0]
    assert reqs[0].trace_id == TRACE_ID and len(
        [e for e in _named(run, "serve:chunk_dispatch")
         if e[3]["rid"] == TRACE_ID]) == 3           # 40 tokens in 16s
    # the four stamps of a request, always made
    for r in reqs:
        assert r._t_enqueue <= r._t_admit <= r._t_first <= r._t_last


def test_flight_phases_are_the_spans_durations(run):
    """Each phase is timed once: the tick records' phases add up to the
    in-memory totals of the same spans."""
    ticks, tot = run["ticks"], run["totals"]
    assert len(ticks) == tot["serve:tick_dispatch"]["count"]
    for key, name in (("dispatch_ms", "serve:tick_dispatch"),
                      ("harvest_wait_ms", "serve:harvest_wait"),
                      ("emit_ms", "serve:emit")):
        got = sum(r["phases"][key] for r in ticks)
        assert got == pytest.approx(tot[name]["total_s"] * 1e3,
                                    abs=1e-3 * len(ticks)), key
    # (a boundary that ran chunks with no slot decoding yet writes no
    # tick record, so the chunk phase may sum to less than its spans)
    assert 0 < sum(r["phases"]["chunk_prefill_ms"] for r in ticks) \
        <= tot["serve:chunk_dispatch"]["total_s"] * 1e3 + 1e-3 * len(ticks)
    for r in ticks:
        ph = r["phases"]
        assert ph["device_wait_ms"] == ph["harvest_wait_ms"]
        assert ph["host_ms"] == pytest.approx(
            ph["schedule_ms"] + ph["chunk_prefill_ms"] + ph["dispatch_ms"]
            + ph["emit_ms"], abs=1e-3)


def test_capture_stages_add_up_to_the_first_call(run):
    tot = run["totals"]
    four = sum(tot[n]["total_s"] for n in STAGES)
    # contiguous spans: all of the call but its bookkeeping (a tenth of
    # room for a worker descheduled between two of them; the chip's 95 s
    # first call read 99.9%)
    assert 0.9 * run["first_call_s"] <= four <= run["first_call_s"]
    assert compile_tracker.get("tiny_step")["seconds_total"] \
        == pytest.approx(four, rel=1e-6)
    # the stages follow one another without a gap on the timeline
    evs = [e for e in run["events"] if e[2] in STAGES]
    assert [e[2] for e in evs] == list(STAGES)
    for a, b in zip(evs[1:], evs[2:]):
        assert 0 <= b[0] - a[1] < 1e6             # under a millisecond
    assert tot["to_static:call"]["count"] == 2


# ------------------------------------------------ a boundary's account

@pytest.fixture(scope="module")
def leaf_run(tmp_path_factory):
    """A second profile, of the paths the first does not take: legacy
    (whole-prompt) prefill and a spec tick with host-side n-gram drafts,
    whose harvest reads `counts` and `accepts` back."""
    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    eng = ServingEngine(model, max_batch=2, max_context=128, block_size=16,
                        prefill_chunk=0, spec_decode=True,
                        spec_draft="ngram", spec_k=2)
    eng.warmup()
    rng = np.random.RandomState(1)
    reqs = [Request(rng.randint(1, 1000, (L,)), max_new_tokens=n)
            for L, n in ((24, 6), (9, 5))]

    def body():
        for r in reqs:
            eng.add_request(r)
        eng.run()

    before = obs.span_totals()
    events = _profile(str(tmp_path_factory.mktemp("leaf_trace")), body)
    totals = {n: {"count": v["count"] - before.get(n, {"count": 0})["count"]}
              for n, v in obs.span_totals().items()}
    return {"events": events, "reqs": reqs, "totals": totals}


def _inside(child, parents):
    return any(p[0] <= child[0] and child[1] <= p[1] and p[4] == child[4]
               for p in parents)


@pytest.mark.parametrize("name", list(LEAVES))
def test_leaf_nests_in_its_phase_with_its_attrs(run, leaf_run, name):
    parent, keys = LEAVES[name]
    for src in (run, leaf_run):
        if name == "serve:first_token" and src is leaf_run:
            parent = "serve:prefill_dispatch"     # legacy prefill
        for e in _named(src, name):
            assert _inside(e, _named(src, parent)), (name, parent)
            assert keys <= set(e[3]), (name, e[3])
    assert _named(leaf_run if name == "serve:readback" else run, name)


def test_leaves_of_a_tick_and_of_a_chunk(run, leaf_run):
    ticks = _named(run, "serve:tick_dispatch")
    # every tick stages its arguments before the enqueue that is the
    # rest of its dispatch span
    stage = _named(run, "serve:tick_stage")
    assert len(stage) == len(ticks)
    for t, st in zip(ticks, stage):
        assert t[0] <= st[0] <= st[1] <= t[1]
        # tables, lengths, six sampling rows, positions; the last tokens
        # too at a boundary (a chained tick takes them from the device)
        assert st[3]["arrays"] == (8 if t[3]["chained"] else 9)
        assert st[3]["bytes"] > 0
    # a chunk stages its table row, its tokens and two scalars first
    chunks = _named(run, "serve:chunk_dispatch")
    stages = _named(run, "serve:chunk_stage")
    assert len(stages) == len(chunks)
    for c, st in zip(chunks, stages):
        assert c[0] <= st[0] and st[3]["arrays"] == 4
    # one first token a request, from its final chunk's host sync to the
    # end of that chunk's span
    firsts = _named(run, "serve:first_token")
    assert len(firsts) == len(run["reqs"])
    prompts = sorted(len(r.prompt_ids) for r in run["reqs"])
    finals = [c for c in chunks if any(_inside(f, [c]) for f in firsts)]
    assert sorted(c[3]["kv_tokens"] for c in finals) == prompts
    assert all(any(f[1] <= c[1] < f[1] + 1e6 for f in firsts)
               for c in finals)
    # admissions: one span an attempt, so at least one a request
    assert len(_named(run, "serve:admit")) >= len(run["reqs"])
    # a reap a boundary (chunked mode)
    assert len(_named(run, "serve:reap")) \
        == len(_named(run, "serve:schedule"))
    # the spec tick's harvest reads counts and accepts in one stretch
    # (a tick no slot of which has budget for a draft is a plain one)
    reads = _named(leaf_run, "serve:readback")
    assert 1 <= len(reads) <= len(_named(leaf_run, "serve:emit"))
    assert all(e[3]["arrays"] == 2 and e[3]["bytes"] == 2 * 2 * 4
               for e in reads)
    # legacy mode reaps twice a boundary (admit, THEN evict)
    assert len(_named(leaf_run, "serve:reap")) \
        == 2 * len(_named(leaf_run, "serve:schedule"))


def test_token_streams_are_the_parents(run, leaf_run):
    """The spans moved no token: both fixtures serve what the parent of
    ISSUE 36 served for the same requests."""
    assert [list(r.output_ids) for r in run["reqs"]] == PARENT_TOKENS
    assert [list(r.output_ids) for r in leaf_run["reqs"]] \
        == PARENT_TOKENS_LEGACY_NGRAM


def test_every_schedule_says_why_it_was_a_boundary(run, leaf_run):
    sched = _named(run, "serve:schedule")
    whys = [e[3]["why"] for e in sched]
    assert set(whys) <= set(WHY_CYCLE)
    # the empty engine's first boundary followed no tick
    assert whys[0] == "idle"
    # three requests for two slots: one waits while ticks fly, and each
    # answer's end is a boundary
    assert "waiting" in whys
    assert {"finished", "budget_spent"} & set(whys)
    # the counter counts the same boundaries under the same words
    assert run["boundaries"] == collections.Counter(whys)
    # a host draft never chains, and says so
    legacy = [e[3]["why"] for e in _named(leaf_run, "serve:schedule")]
    assert legacy[0] == "idle" and "host_draft" in legacy
    assert set(legacy) <= set(WHY_CYCLE)


class _Recorded(obs.span):
    """`observability.span` that also keeps what it was given: the
    loop's spans without a profiler."""
    log = []

    def set(self, **attrs):
        self.attrs.update(attrs)
        super().set(**attrs)

    def end(self):
        begun = self._t0 is not None
        super().end()
        if begun:
            _Recorded.log.append(self)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(serving, "_span", _Recorded)
    monkeypatch.setattr(_Recorded, "log", [])
    return _Recorded.log


@pytest.fixture(scope="module")
def engine():
    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    eng = ServingEngine(model, max_batch=2, max_context=128, block_size=16,
                        steps_per_tick=2, prefill_chunk=16)
    eng.warmup()
    return eng


def _prompt(n, seed=3):
    return np.random.RandomState(seed).randint(1, 1000, (n,))


def test_an_arrival_behind_a_chained_tick_is_a_waiting_boundary(
        engine, recorded):
    eng = engine
    a = eng.add_request(Request(_prompt(12), max_new_tokens=16))
    pend = eng._cycle(None)                  # boundary: admit, chunk, tick
    assert pend is not None and eng._boundary_reason(pend) is None
    pend = eng._cycle(pend)                  # chains t+1, harvests t
    assert pend is not None and pend.overlapped
    b = eng.add_request(Request(_prompt(9, 4), max_new_tokens=3))
    assert eng._boundary_reason(pend) == "waiting"
    assert eng._cycle(pend) is None          # harvested alone
    pend = eng._cycle(None)                  # the boundary the arrival forced
    sched = [s for s in recorded if s.name == "serve:schedule"]
    assert [s.attrs["why"] for s in sched] == ["idle", "waiting"]
    assert sched[1].attrs["waiting"] == 1
    while pend is not None or eng._has_work():
        pend = eng._cycle(pend)
    assert a.done and b.done
    whys = [s.attrs["why"] for s in recorded if s.name == "serve:schedule"]
    assert set(whys) <= set(WHY_CYCLE) and whys[-1] != "idle"


def test_a_chain_that_finds_no_slot_says_so(engine, recorded,
                                            monkeypatch):
    """`_boundary_reason` allowed the chain and the dispatch found nothing
    to tick: the tick in flight is harvested alone, and the boundary
    that follows does not pass for an empty engine's."""
    eng = engine
    req, pend = _in_flight(eng)
    with monkeypatch.context() as mp:
        mp.setattr(eng, "_dispatch_tick",
                   lambda boundary=True, chain=None: None)
        assert eng._cycle(pend) is None
    recorded.clear()
    pend = eng._cycle(None)
    assert [s.attrs["why"] for s in recorded
            if s.name == "serve:schedule"] == ["nothing_to_chain"]
    while pend is not None or eng._has_work():
        pend = eng._cycle(pend)
    assert req.done and len(req.output_ids) == 12


def test_an_idle_period_is_one_span_over_its_naps(engine, recorded,
                                                  monkeypatch):
    """`serve_forever` on an empty engine: N naps, then an arrival; the
    naps of a period lie under ONE `serve:idle` span (a period longer
    than `_IDLE_SPAN_S` is cut there and reopened)."""
    eng, stop, naps = engine, threading.Event(), []

    class Clock:                  # `time`, counting the loop's naps only
        def __getattr__(self, name):
            return getattr(time, name)

        def sleep(self, s):
            naps.append(s)
            time.sleep(s)

    monkeypatch.setattr(serving, "time", Clock())
    req = Request(_prompt(9, 5), max_new_tokens=2)

    def client():
        time.sleep(0.03)
        eng.add_request(req)
        while not req.done:
            time.sleep(0.002)
        time.sleep(0.03)
        stop.set()

    t = threading.Thread(target=client, daemon=True)
    t.start()
    eng.serve_forever(stop, idle_s=0.001)
    t.join(30)
    assert req.done and not t.is_alive()
    idle = [s for s in recorded if s.name == "serve:idle"]
    # two periods (before the arrival, after the answer), each one span
    # but for those the 0.1 s limit cut
    cut = sum(1 for s in idle if s.seconds >= eng._IDLE_SPAN_S)
    assert 2 <= len(idle) <= 2 + cut
    assert len(naps) >= 20 and sum(s.seconds for s in idle) \
        >= len(naps) * 0.001
    # the first boundary after each period followed no tick
    order = [s.name for s in recorded
             if s.name in ("serve:idle", "serve:schedule")]
    first = order.index("serve:schedule")
    assert order[first - 1] == "serve:idle"
    assert [s for s in recorded if s.name == "serve:schedule"][0] \
        .attrs["why"] == "idle"


def _in_flight(eng):
    """A request decoding alone with a 2-step tick in flight that may
    chain; the tick is harvested by the caller."""
    req = eng.add_request(Request(_prompt(12, 6), max_new_tokens=12))
    pend = eng._cycle(None)
    assert pend is not None and eng._boundary_reason(pend) is None
    return req, pend


# exit -> how to bring the engine there: (word, spec branch?, set-up)
def _exits():
    def attr(obj, name, value):
        return lambda mp, st, eng, req, pend: mp.setattr(
            obj(eng, req, pend), name, value)
    E, R, P = (lambda e, r, p: e), (lambda e, r, p: r), (lambda e, r, p: p)

    def flag(name, value):
        return lambda mp, st, *a: st.enter_context(
            flags.flag_guard(**{name: value}))

    def spent(mp, st, eng, req, pend):
        tok_pos = eng.tok_pos.copy()
        tok_pos[req.slot] = req.max_new_tokens
        mp.setattr(eng, "tok_pos", tok_pos)

    def xray(mp, st, eng, req, pend):
        mp.setattr(serving._xray, "sampling_on", lambda: True)
        mp.setattr(serving._xray, "sample_due", lambda fn: True)

    def both(*fs):
        return lambda *a: [f(*a) for f in fs]

    tail = types.SimpleNamespace(prompt_ids=[0] * 4, _chunk_off=0)
    spec_model = attr(E, "spec_model", True)
    return [
        (None, False, both()),
        (None, True, spec_model),
        ("overlap_off", False, flag("serving_overlap", False)),
        ("block_tick", False, attr(E, "gen", object())),
        ("stopping", False, attr(E, "_drain_requested", True)),
        ("waiting", False, attr(E, "waiting", collections.deque([1]))),
        ("cancelled", False, attr(R, "cancelled", True)),
        ("chunk_pending", False,
         attr(E, "prefilling", collections.deque([tail]))),
        ("host_draft", True, both()),
        ("adapt_k", True,
         both(spec_model, attr(E, "_adapt_step", lambda: 1))),
        ("host_sampling", True,
         both(spec_model, flag("serving_device_sampling", False))),
        ("host_sampling", False, both(attr(P, "device_sampling", False),
                                      attr(R, "do_sample", True))),
        ("kind_switch", False,
         both(attr(E, "spec", True),
              attr(E, "_spec_eligible", lambda active, ds: True))),
        ("finished", True, both(spec_model, attr(R, "done", True))),
        ("finished", False, attr(R, "done", True)),
        ("budget_spent", True, both(spec_model, spent)),
        ("budget_spent", False, spent),
        ("xray_probe", True, both(spec_model, xray)),
        ("xray_probe", False, xray),
    ]


_EXITS = _exits()


@pytest.mark.parametrize(
    "word,spec,arrange", _EXITS,
    ids=[f"{w}-{'spec' if s else 'plain'}" for w, s, _ in _EXITS])
def test_boundary_reason_and_can_overlap_agree(engine, monkeypatch, word,
                                               spec, arrange):
    """Every exit of `_boundary_reason` gives its word, `_can_overlap` is
    its negation, and the vocabulary is the one the spans carry."""
    eng = engine
    req, pend = _in_flight(eng)
    try:
        with monkeypatch.context() as mp, contextlib.ExitStack() as st:
            tick = copy.copy(pend)
            tick.spec = spec
            arrange(mp, st, eng, req, tick)
            assert eng._boundary_reason(tick) == word
            assert eng._can_overlap(tick) is (word is None)
            assert word is None or word in WHY
    finally:
        while pend is not None or eng._has_work():
            pend = eng._cycle(pend)
    assert req.done and len(req.output_ids) == 12
