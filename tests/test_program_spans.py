"""The program's spans on the profiler's clock (ISSUE 26): a tiny
`ServingEngine` under `serve_forever` and a tiny `to_static` step, run
under `jax.profiler.trace` on the CPU and read back with `ProfileData` —
every span of the table is on `/host:CPU` under its exact name with its
attrs, the serve phases of a tick do not overlap, the dispatch spans of
one request share its `rid`, and the flight record's phases and the
compile tracker's seconds are the same spans' durations.  Since ISSUE 33
the loop keeps a tick in flight: a `serve:tick_dispatch` span says
whether it was `chained` behind an unharvested tick."""

import glob
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
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

    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        t = threading.Thread(target=client, daemon=True)
        t.start()
        eng.serve_forever(stop)
        t.join(30)
        t0 = time.perf_counter()
        tiny_step(x)
        first_call_s = time.perf_counter() - t0
        tiny_step(x)
        tiny_step(x)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    events = []                    # (start, end, name, attrs, thread)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                events.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats), ln.name) for e in ln.events
                    if e.name.startswith(("serve:", "to_static:")))
    reqs.append(lone)
    overlap = obs_metrics.snapshot().get("serving.overlap_dispatches")
    return {"events": sorted(events, key=lambda e: e[:2]), "reqs": reqs,
            "first_call_s": first_call_s, "totals": obs.span_totals(),
            "overlap_dispatches": sum(x["value"] for x in overlap["series"])
            if overlap else 0,
            "ticks": [r for r in flight_recorder.default_recorder().steps()
                      if r.get("timeline") == "serving"]}


def _named(run, name):
    return [e for e in run["events"] if e[2] == name]


@pytest.mark.parametrize("name", PHASES + NESTED + STAGES
                         + ("to_static:call",))
def test_span_is_on_the_host_plane_under_its_name(run, name):
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
