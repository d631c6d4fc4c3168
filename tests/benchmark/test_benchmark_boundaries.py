"""`benchmark/reducers/boundaries.py` by hand (ISSUE 36), on traces built
with `xplane.from_events` (times in ns) and hand-made span lists: the lag
between the device's last op and the host's having the tokens, the share
of boundaries an arrival forced, the share of idle time no span of the program covers;
None without a trace or without the spans; and the eight `.serve` metric
files that read a boundary, as the manifest and the files declare them."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import check_manifest, run  # noqa: E402
from benchmark.reducers import boundaries as bd  # noqa: E402
from benchmark.reducers import program_spans as ps  # noqa: E402
from benchmark.reducers import xplane  # noqa: E402

MS = 1_000_000
CELL = "serve-1p3b-chat"
TICK = {"module": "jit_serving_tick"}
NAMES = ("boundary_exposed_ms", "reap_admit_exposed_ms",
         "first_token_exposed_ms", "stage_exposed_ms", "readback_lag_ms",
         "boundary_arrival_pct", "unattributed_idle_pct",
         "boundary_total_exposed_ms")


def _op(s, e):
    return (s * MS, e * MS, "%fusion.1 = f32[] fusion()")


def _tick(s, e):
    return (s * MS, e * MS, "jit_serving_tick(11)")


def _span(s, e, name, **attrs):
    return (s * MS, e * MS, name, attrs)


# A window of 100 ms.  A boundary tick A (dispatched 0-4, on the device
# 6-20), B chained behind it (dispatched 8-10, on the device 20-34) and
# harvested alone; then a boundary: a chunk 44-50 and the boundary tick C
# (dispatched 43-46, queued behind the chunk, on the device 50-64),
# harvested alone; then an empty engine.  The device idles in (0, 6),
# (34, 44) and (64, 100).
OPS = [_op(6, 20), _op(20, 34), _op(44, 50), _op(50, 64)]
MODULES = [_tick(6, 20), _tick(20, 34),
           (44 * MS, 50 * MS, "jit_serving_prefill_cont(5)"), _tick(50, 64)]
SPANS = [
    _span(0, 4, "serve:tick_dispatch", chained=0, steps=4),
    _span(1, 2, "serve:tick_stage", arrays=9, bytes=400),
    _span(8, 10, "serve:tick_dispatch", chained=1, steps=4),
    _span(10, 21, "serve:harvest_wait"),            # A: busy behind it
    _span(21, 22, "serve:emit", tokens=4),
    _span(22, 37, "serve:harvest_wait"),            # B: idle 34-37
    _span(37, 38, "serve:emit", tokens=4),
    _span(39, 43, "serve:schedule", why="waiting", waiting=1, running=1),
    _span(39, 40, "serve:reap"),
    _span(40, 41, "serve:admit"),
    _span(41, 43, "serve:chunk_dispatch", rid=7, q_tokens=9, kv_tokens=9),
    _span(43, 46, "serve:tick_dispatch", chained=0, steps=4),
    _span(46, 67, "serve:harvest_wait"),            # C: idle 64-67
    _span(67, 68, "serve:emit", tokens=4),
    _span(69, 70, "serve:schedule", why="finished", waiting=0, running=1),
    _span(70, 99, "serve:idle"),
]


def _trace(spans=SPANS, ops=OPS, modules=MODULES):
    host = [(0, 100 * MS, xplane.WINDOW)] + [x[:3] for x in spans]
    return xplane.from_events({0: {"ops": ops, "modules": modules}}, host)


@pytest.fixture
def spans(monkeypatch):
    """`program_spans.spans_with_attrs` answers from `SPANS` (the reducers
    read attrs from the trace's own file, which a hand-made trace lacks)."""
    box = {"spans": SPANS}
    monkeypatch.setattr(
        ps, "spans_with_attrs",
        lambda trace, in_window=True: [] if trace is None else box["spans"])
    return box


def test_the_readback_lag_by_hand(spans):
    t = _trace()
    assert ps.idle_gaps_of(t) == [(0, 6 * MS), (34 * MS, 44 * MS),
                                  (64 * MS, 100 * MS)]
    # boundary ticks A and C; B is chained: not a boundary's.
    # A's last op at 20, its harvest ended at 21 with B running: 0; B 34
    # -> 37: 3 ms; C 64 -> 67: 3 ms; over the two boundary ticks
    assert bd.readback_lag_ms(t, {}, TICK) == pytest.approx((0 + 3 + 3) / 2)
    # a host that comes late to its wait (still dispatching the next
    # tick when this one ended) waited only from then on: 36 -> 37
    spans["spans"] = [x if x[:2] != (22 * MS, 37 * MS)
                      else _span(36, 37, "serve:harvest_wait") for x in SPANS]
    assert bd.readback_lag_ms(t, {}, TICK) == pytest.approx((0 + 1 + 3) / 2)
    # a host a whole tick late (A's wait ends after B did): B is still
    # harvested by its own wait, not by A's a second time
    late = {(10 * MS, 21 * MS): _span(10, 36, "serve:harvest_wait"),
            (22 * MS, 37 * MS): _span(36, 37, "serve:harvest_wait")}
    spans["spans"] = [late.get(x[:2], x) for x in SPANS]
    assert bd.readback_lag_ms(t, {}, TICK) \
        == pytest.approx((2 + 1 + 3) / 2)   # A: 34-36 idle in its wait
    spans["spans"] = SPANS
    # the same sums on plain lists
    ticks = [(s, e, a["chained"]) for s, e, n, a in SPANS
             if n == "serve:tick_dispatch"]
    waits = [(s, e) for s, e, n, _ in SPANS if n == "serve:harvest_wait"]
    launches = [(6 * MS, 20 * MS), (20 * MS, 34 * MS), (50 * MS, 64 * MS)]
    assert bd.readback_ns(launches, ticks, waits, ps.idle_gaps_of(t)) \
        == (6 * MS, 2)


def test_a_launch_the_window_cuts_and_a_program_that_chains_all(spans):
    # the window cuts A (it began before): B and C are read, one boundary
    t = _trace(ops=[_op(-5, 20)] + OPS[1:],
               modules=[_tick(-5, 20)] + MODULES[1:])
    assert bd.readback_lag_ms(t, {}, TICK) == pytest.approx(3 + 3)
    # no boundary tick in the window: nothing to take a mean over
    spans["spans"] = [x if x[2] != "serve:tick_dispatch"
                      else x[:3] + (dict(x[3], chained=1),) for x in SPANS]
    assert bd.readback_lag_ms(_trace(), {}, TICK) is None
    # another program's launches are not the tick's
    spans["spans"] = SPANS
    assert bd.readback_lag_ms(_trace(), {}, {"module": "jit_other"}) is None
    # the parent of ISSUE 33 wrote no `chained`: every tick a boundary's
    spans["spans"] = [x if x[2] != "serve:tick_dispatch"
                      else x[:3] + ({"steps": 4},) for x in SPANS]
    assert bd.readback_lag_ms(_trace(), {}, TICK) \
        == pytest.approx((0 + 3 + 3) / 3)


WHYS = {
    "an_arrival_of_two": (["waiting", "finished"], 50.0),
    "idle_is_no_boundary_of_a_tick": (["idle", "waiting", "idle"], 100.0),
    "none_an_arrival": (["finished", "budget_spent", "chunk_pending"], 0.0),
    "only_idle": (["idle", "idle"], None),
    "no_schedule_in_the_window": ([], None),
}


@pytest.mark.parametrize("case", list(WHYS))
def test_boundary_arrival_pct_on_hand_made_whys(spans, case):
    whys, want = WHYS[case]
    spans["spans"] = [_span(i, i + 1, "serve:schedule", why=w)
                      for i, w in enumerate(whys)] \
        + [_span(50, 51, "serve:tick_dispatch", why="waiting")]
    assert bd.why_share_pct(object(), {}, {"why": ["waiting"]}) == want
    assert bd.why_share(whys, ["waiting"]) == (
        None if want is None else want / 100)
    # a set of words: an answer's end, either way
    if case == "none_an_arrival":
        assert bd.why_share_pct(
            object(), {}, {"why": ["finished", "budget_spent"]}) \
            == pytest.approx(200 / 3)


def test_a_parent_without_why_reads_nothing(spans):
    spans["spans"] = [x[:3] + ({k: v for k, v in x[3].items()
                                if k != "why"},) for x in SPANS]
    assert bd.why_share_pct(_trace(), {}, {"why": ["waiting"]}) is None


def test_unattributed_idle_by_hand():
    t = _trace()
    # 52 ms idle.  (0, 6): tick_dispatch covers 0-4, 2 ms bare.  (34, 44):
    # harvest_wait to 37, emit 37-38, schedule 39-43, tick_dispatch from
    # 43: 38-39 bare.  (64, 100): harvest_wait to 67, emit 67-68, schedule
    # 69-70, idle 70-99: 68-69 and 99-100 bare
    assert bd.unattributed_idle_pct(t, {}, {}) \
        == pytest.approx(100 * (2 + 1 + 2) / 52)
    # other threads' spans and jax's own are not the program's names
    host = [(0, 100 * MS, xplane.WINDOW), (0, 100 * MS, "np.asarray"),
            (0, 50 * MS, "serve:idle")]
    t = xplane.from_events({0: {"ops": [_op(60, 80)], "modules": []}}, host)
    assert bd.unattributed_idle_pct(t, {}, {}) \
        == pytest.approx(100 * (10 + 20) / 80)
    # a program with no such span (a train step) has no such account
    t = xplane.from_events({0: {"ops": [_op(60, 80)], "modules": []}},
                           host[:2])
    assert bd.unattributed_idle_pct(t, {}, {}) is None
    # a device that never idles has nothing to attribute
    t = xplane.from_events({0: {"ops": [_op(0, 100)], "modules": []}}, host)
    assert bd.unattributed_idle_pct(t, {}, {}) is None


def test_the_leaves_are_read_by_the_reader_that_was_there(spans):
    """`program_spans.idle_under_spans_ms` as it is, a boundary: the idle
    under the host's phases over the `serve:schedule` spans; a program
    without the leaves (the parent) reads 0 under them."""
    t = _trace()
    per = {"per": "serve:schedule"}
    whole = dict(per, spans=["serve:schedule", "serve:prefill_dispatch",
                             "serve:chunk_dispatch", "serve:tick_dispatch",
                             "serve:emit"])
    # (0, 4) + (37, 38) + (39, 44) + (67, 68) + (69, 70) over 2
    assert ps.idle_under_spans_ms(t, {}, whole) \
        == pytest.approx((4 + 1 + 5 + 1 + 1) / 2)
    # with the waits, all the idle of an engine at work: + (34, 37) and
    # (64, 67), the ends of B's and C's
    total = dict(per, spans=whole["spans"] + ["serve:harvest_wait"])
    assert ps.idle_under_spans_ms(t, {}, total) \
        == pytest.approx((12 + 3 + 3) / 2)
    assert ps.idle_under_spans_ms(
        t, {}, dict(per, spans=["serve:reap", "serve:admit",
                                "serve:prefill_dispatch"])) \
        == pytest.approx(2 / 2)
    assert ps.idle_under_spans_ms(
        t, {}, dict(per, spans=["serve:tick_stage", "serve:chunk_stage",
                                "serve:readback"])) == pytest.approx(1 / 2)
    assert ps.idle_under_spans_ms(
        t, {}, dict(per, spans=["serve:first_token"])) == 0.0
    parent = _trace([x for x in SPANS if x[2] in (
        "serve:schedule", "serve:tick_dispatch", "serve:harvest_wait",
        "serve:emit", "serve:chunk_dispatch", "serve:idle")])
    assert ps.idle_under_spans_ms(
        parent, {}, dict(per, spans=["serve:reap", "serve:admit"])) == 0.0


def test_no_trace_and_no_device_plane_read_as_nothing(spans):
    empty = xplane.from_events({}, [])
    for fn, args in ((bd.readback_lag_ms, TICK),
                     (bd.unattributed_idle_pct, {})):
        assert fn(None, {}, args) is None
        assert fn(empty, {}, args) is None
    assert bd.why_share_pct(None, {}, {"why": ["waiting"]}) is None
    assert bd.why_share([], ["waiting"]) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_declared_alike_in_manifest_and_file(name):
    name += ".serve"
    path = os.path.join(REPO, "BENCHMARK.json")
    with open(path) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert entry == {"name": name,
                     "unit": "%" if name.endswith("_pct.serve") else "ms",
                     "better": "lower", "source": "program_span",
                     "layer": "serving_scheduler",
                     "moves": "serve_tpot_p90_ms", "workloads": [CELL]}
    lm = next(m for m in run.layer_metrics_for("serve_engine", CELL)
              if m["name"] == name)
    mod, _, fn = lm["reducer"].partition(":")
    assert callable(getattr(run.load_module("reducers", mod), fn))
    assert lm["jobs"] == ["serve_engine"] and len(lm["what"]) > 40
    if mod == "program_spans":
        assert fn == "idle_under_spans_ms"
        assert lm["args"]["per"] == "serve:schedule"


def test_the_eight_select_the_chat_cell_and_only_it():
    assert check_manifest.check_file(
        os.path.join(REPO, "BENCHMARK.json")) == []
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert len(manifest["per_layer"]) == 100
    mine = {n + ".serve" for n in NAMES}
    assert [m["name"] for m in manifest["per_layer"][-8:]] \
        == [n + ".serve" for n in NAMES]          # appended, in order
    got = {}
    for cell in manifest["workloads"]:
        wl = run.load_json("workloads", cell["name"] + ".json")
        got[wl["job"]] = [lm["name"] for lm
                          in run.layer_metrics_for(wl["job"], cell["name"])]
    assert mine <= set(got["serve_engine"])
    assert len(got["serve_engine"]) == 14 + 8
    # the other serve jobs' counts stand (their tests hold them to these):
    # the twins of the eight wait for the `benchmark` issue that lifts them
    assert (len(got["serve_latent"]), len(got["serve_blocks"]),
            len(got["serve_mtp"])) == (21, 21, 23)
    for job, names in got.items():
        if job != "serve_engine":
            assert not mine & set(names), job
