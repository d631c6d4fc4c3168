"""`benchmark/reducers/tick_chain.py` by hand (ISSUE 33): the share of a
window's `serve:tick_dispatch` spans that were `chained` behind an
unharvested tick, on hand-made span lists; and the metric that reads
it, as the manifest and its file declare it."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import check_manifest, run  # noqa: E402
from benchmark.reducers import program_spans as ps  # noqa: E402
from benchmark.reducers import tick_chain  # noqa: E402

ARGS = {"span": "serve:tick_dispatch"}


def _tick(t, **attrs):
    return (t, t + 1, "serve:tick_dispatch", attrs)


# a boundary, four ticks chained behind it, a boundary again
CHAIN = [_tick(0, steps=4, chained=0)] \
    + [_tick(10 + i, steps=4, chained=1) for i in range(4)] \
    + [_tick(20, steps=1, chained=0)]

CASES = {
    "a_share": (CHAIN, pytest.approx(100.0 * 4 / 6)),
    "every_tick_a_boundary": ([_tick(i, chained=0) for i in range(3)], 0.0),
    "every_tick_chained": ([_tick(i, chained=1) for i in range(3)], 100.0),
    # the profiler hands stats back as it likes: a string counts too
    "attr_as_text": ([_tick(0, chained="1"), _tick(1, chained="0")], 50.0),
    # a span without the attr is a boundary's: the parent of the PR that
    # added it never chained a tick under serve_forever
    "spans_without_the_attr": (
        [_tick(0, steps=4), _tick(1, steps=4, chained=1)], 50.0),
    "a_program_without_the_attr": ([_tick(i, steps=4) for i in range(5)],
                                   0.0),
    # other spans of the window are not ticks
    "other_spans_do_not_count": (
        CHAIN + [(5, 6, "serve:schedule", {"waiting": 0}),
                 (7, 8, "serve:chunk_dispatch", {"chained": 1})],
        pytest.approx(100.0 * 4 / 6)),
    # nothing to read: the harness leaves the metric out
    "empty_window": ([], None),
    "no_tick_in_the_window": ([(5, 6, "serve:idle", {})], None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tick_chained_pct_on_a_hand_made_span_list(monkeypatch, case):
    spans, want = CASES[case]
    monkeypatch.setattr(ps, "spans_with_attrs", lambda trace: spans)
    got = tick_chain.tick_chained_pct(object(), {}, ARGS)
    assert got == want
    assert got is None or 0.0 <= got <= 100.0


def test_no_trace_reads_as_nothing():
    # an untraced rehearsal hands the readers None for the trace
    assert tick_chain.tick_chained_pct(None, {}, ARGS) is None
    assert tick_chain.chained_share([]) is None


def test_the_metric_is_declared_alike_in_manifest_and_file():
    name, cell = "tick_chained_pct.serve", "serve-1p3b-chat"
    path = os.path.join(REPO, "BENCHMARK.json")
    assert check_manifest.check_file(path) == []
    with open(path) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "serving_scheduler",
                     "moves": "serve_tpot_p90_ms", "workloads": [cell]}
    lm = next(m for m in run.layer_metrics_for("serve_engine", cell)
              if m["name"] == name)
    assert lm["reducer"] == "tick_chain:tick_chained_pct"
    assert lm["args"] == ARGS
    # the other serve jobs' cells do not select it: `.serve_dsa` and
    # `.serve_bd` twins are a `benchmark` issue's (PERF.md section 7)
    for job in ("serve_latent", "serve_blocks"):
        assert name not in [m["name"]
                            for m in run.layer_metrics_for(job, cell)]
