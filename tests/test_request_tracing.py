"""Request lifecycle tracing + recompile blame on the serving engine
(ISSUE 6 tentpole): TTFT/TPOT/e2e/queue-wait sketches, SLO counters,
scheduler-pressure gauges, per-request trace records in the flight ring
and the /requests export ring, compile-tracker blame for shape-driven
recompiles, and the acceptance scrape — a running engine answering
GET /metrics with `serving_ttft_seconds` quantiles and
`compile_seconds_total`."""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import (compile_tracker, export,
                                      flight_recorder, metrics)
from paddle_tpu.observability import http as obs_http


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean():
    metrics.reset()
    compile_tracker.reset()
    export.clear_requests()
    flight_recorder.default_recorder().clear()
    yield
    paddle.set_flags({"enable_metrics": True})
    metrics.reset()
    compile_tracker.reset()
    export.clear_requests()
    obs_http.stop()


def _mk(rng, plen, n):
    return Request(rng.randint(1, 1000, (plen,)), max_new_tokens=n)


def test_ttft_tpot_e2e_traces(model):
    """Every finished request contributes exactly one TTFT/e2e/queue-wait
    observation and per-token TPOT observations; stats() exposes the
    percentiles; the flight ring and export ring carry the records."""
    eng = ServingEngine(model, max_batch=2, max_context=128,
                        block_size=16, steps_per_tick=2)
    rng = np.random.RandomState(0)
    reqs = [eng.add_request(_mk(rng, 10 + i, 6)) for i in range(3)]
    eng.run()
    assert all(r.done for r in reqs)

    assert metrics.get("serving.ttft_seconds").count() == 3
    assert metrics.get("serving.e2e_seconds").count() == 3
    assert metrics.get("serving.queue_wait_seconds").count() == 3
    # 6 tokens per request: 1 from prefill, 5 decode -> 5 TPOT samples
    assert metrics.get("serving.tpot_seconds").count() == 15

    st = eng.stats()
    lat = st["latency"]
    for key in ("ttft", "tpot", "e2e", "queue_wait"):
        assert set(lat[key]) == {"p50", "p90", "p99"}
        assert lat[key]["p50"] <= lat[key]["p99"]
    assert lat["ttft"]["p50"] > 0 and lat["e2e"]["p50"] > 0
    # e2e covers ttft for every request
    assert lat["e2e"]["p99"] >= lat["ttft"]["p50"]

    # per-request records: on the request object, in the export ring,
    # and as kind="request" events in the flight recorder ring
    recs = export.recent_requests()
    assert [r["rid"] for r in recs] == [r.rid for r in reqs]
    for req, rec in zip(reqs, recs):
        assert req.trace["outcome"] == "finished"
        assert rec["tokens_out"] == 6 and rec["ticks"] == 3
        assert rec["ttft_s"] >= rec["queue_wait_s"] >= 0
        assert rec["e2e_s"] >= rec["ttft_s"] > 0
        assert rec["prefill_s"] > 0 and rec["tpot_mean_s"] > 0
        json.dumps(rec)
    flight = [e for e in flight_recorder.default_recorder().events()
              if e["kind"] == "request"]
    assert {e["rid"] for e in flight} == {r.rid for r in reqs}


def test_queue_wait_under_forced_deferral(model):
    """A request deferred on a drained pool (pool_exhausted) accumulates
    its real wait into queue_wait; the pressure gauges see it queued."""
    # pool of 3 blocks: each request reserves 2 worst-case (1 prompt
    # block + 1 growth), so the second MUST wait for the first to
    # finish and free its blocks
    eng = ServingEngine(model, max_batch=2, max_context=64,
                        block_size=16, num_blocks=3)
    rng = np.random.RandomState(1)
    r1 = eng.add_request(_mk(rng, 10, 20))
    r2 = eng.add_request(_mk(rng, 10, 20))
    assert metrics.get("serving.queue_depth").value() == 2
    assert metrics.get("serving.waiting").value() == 2
    eng.step()       # admits r1 only; r2 deferred (pool exhausted)
    assert r2.slot is None
    assert metrics.get("serving.running").value() == 1
    assert metrics.get("serving.waiting").value() == 1
    assert metrics.get("serving.rejections").value(
        reason="pool_exhausted") == 1
    eng.run()
    assert r1.done and r2.done
    # r2 waited for r1's whole decode: queue waits differ by orders
    assert r2.trace["queue_wait_s"] > r1.trace["queue_wait_s"]
    assert r2.trace["queue_wait_s"] > 10 * max(r1.trace["queue_wait_s"],
                                               1e-6)
    st = eng.stats()
    assert st["queue_depth"] == 0 and st["running"] == 0
    assert metrics.get("serving.queue_depth").value() == 0


def test_slo_violation_counters(model):
    """Impossible SLOs (1 ns) make every request/token a violation;
    0-valued flags (the default) count nothing."""
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16)
    rng = np.random.RandomState(2)
    eng.add_request(_mk(rng, 8, 4))
    eng.run()
    slo = metrics.get("serving.slo_violations")
    assert slo.value(metric="ttft") == 0 and slo.value(metric="tpot") == 0
    with flag_guard(serving_ttft_slo_ms=1e-6, serving_tpot_slo_ms=1e-6):
        eng.add_request(_mk(rng, 8, 4))
        eng.run()
    assert slo.value(metric="ttft") == 1
    assert slo.value(metric="tpot") == 3      # every decode token


def test_rejection_trace_records(model):
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16)
    with pytest.raises(ValueError):
        eng.add_request(Request(np.arange(1, 60), max_new_tokens=30))
    recs = export.recent_requests()
    assert recs and recs[-1]["outcome"] == "rejected:over_context"


def test_tracing_off_does_zero_work(model):
    """With the metrics gate off the four request stamps are still made
    (they are four clock reads, and the span primitive is always on), but
    nothing is computed from them: no sketch sample, no trace record, no
    /requests row."""
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16)
    rng = np.random.RandomState(3)
    paddle.set_flags({"enable_metrics": False})
    r = eng.add_request(_mk(rng, 8, 4))
    eng.run()
    paddle.set_flags({"enable_metrics": True})
    assert r.done
    assert r._t_enqueue <= r._t_admit <= r._t_first <= r._t_last
    assert r.trace is None
    assert export.recent_requests() == []
    assert metrics.get("serving.ttft_seconds").count() == 0


@pytest.mark.slow  # 12s measured: forces a shape-change recompile on a second engine; trace schema + ttft/tpot pins stay fast
def test_recompile_blame_names_the_changed_dim(model):
    """Same callable, changed shape: the compile tracker's recompile
    event names exactly what changed (the ISSUE 6 acceptance check)."""
    eng = ServingEngine(model, max_batch=2, max_context=128,
                        block_size=16, steps_per_tick=4)
    rng = np.random.RandomState(4)
    # budget 6 = 1 prefill token + 4-step tick + a k=1 tail, so BOTH
    # tick variants compile
    eng.add_request(_mk(rng, 10, 6))     # pad bucket 16
    eng.run()
    ent = compile_tracker.get("serving.prefill")
    assert ent["compiles"] == 1 and ent["last_cause"] == "first compile"
    eng.add_request(_mk(rng, 20, 6))     # pad bucket 32: recompile
    eng.run()
    ent = compile_tracker.get("serving.prefill")
    assert ent["compiles"] == 2
    assert "L_pad" in ent["last_cause"]
    assert "16 -> 32" in ent["last_cause"]
    # the tick cache compiled the k=4 program and the k=1 tail; blame
    # names the tick-size change
    tick = compile_tracker.get("serving.tick")
    assert tick["compiles"] == 2
    assert "steps_per_tick" in tick["last_cause"]
    rep = compile_tracker.compile_report()
    assert rep["total_compiles"] >= 4
    assert any("L_pad: 16 -> 32" in e["cause"] for e in rep["recompiles"])
    # registry counters feed compile_seconds_total on /metrics
    assert metrics.get("compile.events").value(fn="serving.prefill") == 2
    assert metrics.get("compile.seconds_total").value(
        fn="serving.prefill") > 0
    json.dumps(rep)


def test_jit_recompile_blame_names_shape_change():
    """to_static captures report into the tracker too: a second
    signature for the same function blames the changed arg shape."""
    from paddle_tpu.jit import to_static

    @to_static
    def traced_fn(a):
        return a * 2 + 1

    traced_fn(paddle.to_tensor(np.ones((2, 3), np.float32)))
    traced_fn(paddle.to_tensor(np.ones((2, 3), np.float32)))  # cache hit
    ent = compile_tracker.get("traced_fn")
    assert ent["compiles"] == 1
    traced_fn(paddle.to_tensor(np.ones((4, 3), np.float32)))
    ent = compile_tracker.get("traced_fn")
    assert ent["compiles"] == 2
    assert "arg0.shape" in ent["last_cause"]
    assert "2 -> 4" in ent["last_cause"]


def test_engine_scrape_acceptance(model):
    """ISSUE 6 acceptance: with FLAGS_metrics_port set, a running
    ServingEngine answers GET /metrics in Prometheus text format with
    serving_ttft_seconds quantiles and compile_seconds_total."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16)
    rng = np.random.RandomState(5)
    try:
        with flag_guard(metrics_port=port):
            eng.add_request(_mk(rng, 8, 4))
            eng.run()                     # starts the endpoint
        srv = obs_http.current()
        assert srv is not None and srv.port == port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert 'serving_ttft_seconds{quantile="0.5"}' in body
        assert 'serving_ttft_seconds{quantile="0.99"}' in body
        assert 'serving_tpot_seconds{quantile="0.99"}' in body
        assert "serving_ttft_seconds_count 1" in body
        assert 'compile_seconds_total{fn="serving.prefill"}' in body
        assert "serving_queue_depth 0" in body
        reqs = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/requests", timeout=10).read())
        assert reqs[-1]["outcome"] == "finished"
    finally:
        obs_http.stop()
