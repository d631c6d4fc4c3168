"""Training telemetry (ISSUE 2): the shared FLOPs/MFU helper, the
StepTimeline's per-step records and fractions, the flight recorder's
ring + dumps, the NaN/Inf watchdog (including its verified no-op path),
the profiler chrome-export round trip for spans, and the dump CLI."""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.observability import (flight_recorder as fr, flops,
                                      metrics, telemetry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry_state():
    metrics.reset()
    fr.default_recorder().clear()
    telemetry.default_timeline().reset()
    yield
    paddle.set_flags({"enable_metrics": True, "enable_nan_watchdog": False,
                      "flight_dump_dir": "", "nan_watchdog_interval": 1})
    metrics.reset()
    fr.default_recorder().clear()
    telemetry.default_timeline().reset()


# ------------------------------------------------------------ FLOPs helper

def test_flops_helper_is_the_single_source():
    """The models' flops_per_token must equal the shared helper exactly —
    deduplicating the estimators is how the 40.7%-vs-58% MFU dispute
    becomes impossible to repeat."""
    from paddle_tpu.models.bert import BertForMaskedLM, bert_tiny
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    gpt = GPTForCausalLM(gpt3_tiny())
    assert gpt.flops_per_token(128) == flops.training_flops_per_token(
        gpt.num_params(), gpt.cfg.num_layers, gpt.cfg.hidden_size, 128)
    bert = BertForMaskedLM(bert_tiny())
    assert bert.flops_per_token(64) == flops.training_flops_per_token(
        bert.num_params(), bert.cfg.num_layers, bert.cfg.hidden_size, 64)
    # 6N floor without the attention shape
    assert flops.training_flops_per_token(100) == 600.0


def test_cost_model_uses_shared_flops():
    from paddle_tpu.distributed.auto_tuner.cost_model import (
        Hardware, ModelSpec, estimate_params, estimate_step_time)
    from paddle_tpu.distributed.auto_tuner.tuner import Trial
    spec = ModelSpec(num_layers=4, hidden_size=64, num_heads=4,
                     vocab_size=128, seq_len=32, global_batch_size=8)
    trial = Trial(dp=1, mp=1, pp=1, sharding=1, micro_batch_size=8)
    hw = Hardware(peak_flops=1e12, mfu_ceiling=1.0)
    fpt = flops.training_flops_per_token(
        estimate_params(spec), spec.num_layers, spec.hidden_size,
        spec.seq_len)
    tokens = spec.global_batch_size * spec.seq_len
    assert estimate_step_time(trial, spec, hw) == pytest.approx(
        fpt * tokens / 1e12)


def test_peak_flops_table():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    assert flops.peak_flops("TPU v4") == 275e12
    # a device the table does not hold is an error, never a default
    # peak: there is no CPU row, so no MFU is ever computed on a CPU
    for unknown in ("cpu", "no such device", "", None):
        with pytest.raises(ValueError):
            flops.peak_flops(unknown)
    assert flops.mfu(1000.0, 1e9, peak=2e12) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        flops.mfu(1000.0, 1e9, device_kind="cpu")


# ------------------------------------------------------------- StepTimeline

def test_step_timeline_records_fractions_and_mfu():
    tl = telemetry.StepTimeline(name="t", flops_per_token=1e6,
                                peak_flops=1e12, ici_bandwidth=1e9)
    comm = metrics.counter("collective.bytes")
    for i in range(3):
        with tl.step(tokens=500) as st:
            time.sleep(0.004)
            if i == 2:
                comm.inc(2_000_000, op="all_reduce")  # 2e6 B / 1e9 B/s = 2ms
        st.annotate(loss=0.5 + i)
    recs = tl.records
    assert [r["step"] for r in recs] == [0, 1, 2]
    for r in recs:
        # fractions are rounded to 4 decimals -> sum within rounding
        assert abs(sum(r["fractions"].values()) - 1.0) < 2e-4
        assert r["tokens"] == 500 and r["wall_s"] > 0
        # rounded to 4 decimals too: a slow step (a loaded host) reads an
        # mfu small enough for the rounding to pass rel=1e-3
        assert r["mfu"] == pytest.approx(
            r["tokens_per_sec"] * 1e6 / 1e12, rel=1e-3, abs=5e-5)
    assert recs[2]["comm_bytes"] == 2_000_000
    assert recs[2]["comm_s_est"] > 0
    assert recs[2]["fractions"]["comm"] > recs[0]["fractions"]["comm"]
    assert recs[-1]["loss"] == 2.5
    summ = tl.summary()
    assert summ["schema"] == telemetry.TELEMETRY_SCHEMA
    assert summ["steps"] == 3 and summ["loss_last"] == 2.5
    assert set(summ["fractions"]) == {"compute", "comm", "host"}
    assert summ["mfu"] > 0 and summ["flops_per_token"] == 1e6
    # records also landed in the flight ring
    assert len(fr.default_recorder().steps()) == 3


def test_step_timeline_separates_compile_from_steady():
    """A step that pays a jit compile is charged host time and excluded
    from the steady-state tokens/sec."""
    tl = telemetry.StepTimeline(name="c")
    comp = metrics.histogram("jit.compile_seconds")
    with tl.step(tokens=10):
        comp.observe(5.0, fn="f", stage="compile")  # simulated compile
    with tl.step(tokens=10):
        time.sleep(0.002)
    assert tl.records[0]["compile_s"] == pytest.approx(5.0)
    summ = tl.summary()
    assert summ["steps"] == 2 and summ["steady_steps"] == 1


def test_step_timeline_noop_when_metrics_disabled():
    tl = telemetry.StepTimeline(name="off")
    paddle.set_flags({"enable_metrics": False})
    with tl.step(tokens=5) as st:
        st.tokens = 7          # tolerated, ignored
    st.annotate(loss=1.0)
    assert tl.records == []
    assert fr.default_recorder().steps() == []
    # empty summary is schema-stable (no KeyError for consumers)
    summ = tl.summary()
    assert summ["steps"] == 0 and summ["tokens_per_sec"] == 0.0
    assert set(summ["fractions"]) == {"compute", "comm", "host"}
    paddle.set_flags({"enable_metrics": True})
    with tl.step(tokens=5):
        pass
    assert len(tl.records) == 1


def test_step_annotate_custom_keys_inside_bracket():
    """Custom annotations made inside the bracket must land in the
    sealed record just like post-seal ones."""
    tl = telemetry.StepTimeline(name="ann")
    with tl.step(tokens=1) as st:
        st.annotate(grad_norm=2.5, loss=0.1)
    st.annotate(lr=0.01)
    rec = tl.records[0]
    assert rec["grad_norm"] == 2.5 and rec["loss"] == 0.1
    assert rec["lr"] == 0.01


# ---------------------------------------------------------- flight recorder

def test_flight_recorder_ring_is_bounded_and_dumps(tmp_path):
    rec = fr.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record_step({"step": i})
    rec.record_event("marker", detail="x")
    assert [r["step"] for r in rec.steps()] == [6, 7, 8, 9]
    path = tmp_path / "dump.json"
    doc = rec.dump(str(path), reason="unit")
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == fr.FLIGHT_SCHEMA
    assert on_disk["reason"] == "unit"
    assert [r["step"] for r in on_disk["steps"]] == [6, 7, 8, 9]
    assert on_disk["events"][0]["kind"] == "marker"
    assert doc["first_nonfinite"] is None
    assert "metrics" in on_disk


def test_flight_ring_resizes_via_flag():
    rec = fr.default_recorder()
    for i in range(10):
        rec.record_step({"step": i})
    paddle.set_flags({"flight_recorder_steps": 3})
    try:
        assert [r["step"] for r in rec.steps()] == [7, 8, 9]
        rec.record_step({"step": 10})
        assert [r["step"] for r in rec.steps()] == [8, 9, 10]
    finally:
        paddle.set_flags({"flight_recorder_steps": 64})
    assert rec.capacity == 64


def test_batch_tokens_heuristic():
    from paddle_tpu.hapi.model import _batch_tokens
    ids = np.zeros((4, 16), np.int32)          # [B, S] token ids
    imgs = np.zeros((8, 3, 28, 28), np.float32)
    feats = np.zeros((5, 7), np.float32)       # 2-D but float: rows
    assert _batch_tokens([ids]) == 64
    assert _batch_tokens([imgs]) == 8
    assert _batch_tokens([feats]) == 5
    assert _batch_tokens([]) == 0


def test_check_finite_is_noop_when_flag_off():
    """Verified no-op path: with the watchdog flag off the probe must not
    touch the value at all (no host sync on device arrays)."""

    class Untouchable:
        def __float__(self):
            raise AssertionError("watchdog touched the value while off")

    assert fr.enabled() is False
    assert fr.check_finite(Untouchable(), site="off") is True
    assert fr.default_recorder().first_nonfinite is None


def test_check_finite_trips_and_dumps(tmp_path):
    paddle.set_flags({"enable_nan_watchdog": True,
                      "flight_dump_dir": str(tmp_path)})
    rec = fr.default_recorder()
    rec.record_step({"step": 41, "loss": 1.0})
    assert fr.check_finite(3.0, site="fine", step=41) is True
    assert fr.check_finite(float("inf"), site="train.loss", step=42) is False
    assert rec.first_nonfinite["site"] == "train.loss"
    assert rec.first_nonfinite["step"] == 42
    dump = fr.last_dump_path()
    assert dump and os.path.dirname(dump) == str(tmp_path)
    doc = json.loads(open(dump).read())
    assert doc["first_nonfinite"]["step"] == 42
    assert {"step": 41, "loss": 1.0} in doc["steps"]
    # later trips don't overwrite the FIRST offending site
    fr.check_finite(float("nan"), site="other", step=99)
    assert rec.first_nonfinite["site"] == "train.loss"


def test_nan_watchdog_hapi_fit_dumps_offending_step(tmp_path):
    """ISSUE 2 acceptance: inject a non-finite loss into a tiny hapi fit
    and assert an automatic dump naming the offending step, with the
    last-K step records around it."""
    from paddle_tpu.hapi import Model

    class Blobs(paddle.io.Dataset):
        def __init__(self, n=12):
            rng = np.random.RandomState(0)
            self.x = rng.rand(n, 4).astype(np.float32)
            self.y = (rng.rand(n) * 2).astype(np.int64)

        def __len__(self):
            return len(self.y)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    paddle.set_flags({"enable_nan_watchdog": True,
                      "flight_dump_dir": str(tmp_path)})
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    ce = nn.CrossEntropyLoss()
    calls = {"n": 0}

    def poisoned_loss(out, label):
        calls["n"] += 1
        factor = float("nan") if calls["n"] >= 2 else 1.0
        return ce(out, label) * factor

    m = Model(net)
    # eager mode so the Python-side injection fires per step (a captured
    # program would bake the first factor in)
    m.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                      parameters=net.parameters()),
              loss=poisoned_loss, jit_compile=False)
    m.fit(Blobs(), batch_size=4, epochs=1, verbose=0)

    rec = fr.default_recorder()
    assert rec.first_nonfinite is not None
    assert rec.first_nonfinite["site"].endswith(".loss")
    bad_step = rec.first_nonfinite["step"]
    dump = fr.last_dump_path()
    assert dump and os.path.dirname(dump) == str(tmp_path)
    doc = json.loads(open(dump).read())
    assert doc["first_nonfinite"]["step"] == bad_step
    by_step = {r["step"]: r for r in doc["steps"]
               if r.get("timeline") == "train"}
    # the offending step's record is in the ring with a non-finite loss,
    # preceded by a finite one
    assert bad_step in by_step
    assert not math.isfinite(by_step[bad_step]["loss"])
    assert any(r["loss"] is not None and math.isfinite(r["loss"])
               for s, r in by_step.items() if s < bad_step)
    # hapi brackets include the loss host read -> records are synced
    # (wall_s is completed-step time, not enqueue time)
    assert all(r["synced"] for r in by_step.values())


def test_watchdog_fires_with_metrics_disabled(tmp_path):
    """The watchdog must stay armed when the metrics registry is off —
    the two flags are independent gates (telemetry records are skipped,
    the finite probe is not)."""
    from paddle_tpu.hapi import Model
    paddle.set_flags({"enable_metrics": False, "enable_nan_watchdog": True,
                      "flight_dump_dir": str(tmp_path)})

    def nan_loss(out, label):
        return nn.CrossEntropyLoss()(out, label) * float("nan")

    net = nn.Sequential(nn.Linear(4, 2))
    m = Model(net)
    m.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                      parameters=net.parameters()),
              loss=nan_loss, jit_compile=False)
    m.train_batch([np.ones((4, 4), np.float32)], [np.zeros((4,), np.int64)])
    rec = fr.default_recorder()
    assert rec.first_nonfinite is not None
    assert rec.first_nonfinite["site"] == "hapi.train.loss"
    assert fr.last_dump_path() and \
        os.path.dirname(fr.last_dump_path()) == str(tmp_path)


def test_exception_in_train_step_dumps(tmp_path):
    from paddle_tpu.hapi import Model
    paddle.set_flags({"enable_nan_watchdog": True,
                      "flight_dump_dir": str(tmp_path)})

    def exploding_loss(out, label):
        raise RuntimeError("injected backend death")

    net = nn.Sequential(nn.Linear(4, 2))
    m = Model(net)
    m.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                      parameters=net.parameters()),
              loss=exploding_loss, jit_compile=False)
    x = np.ones((4, 4), np.float32)
    y = np.zeros((4,), np.int64)
    with pytest.raises(RuntimeError, match="injected backend death"):
        m.train_batch([x], [y])
    dump = fr.last_dump_path()
    assert dump and os.path.dirname(dump) == str(tmp_path)
    doc = json.loads(open(dump).read())
    assert doc["reason"].startswith("exception")
    assert any(e["kind"] == "exception" and "injected backend death"
               in e["error"] for e in doc["events"])


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_hybrid_step_feeds_timeline_and_watchdog(tmp_path):
    """The fleet hybrid step records telemetry and its periodic loss
    probe trips on a poisoned parameter tree."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet.hybrid_step import (
        HybridConfig, init_gpt_params, init_zero_state, hybrid_param_specs,
        make_hybrid_train_step, stack_for_pipeline)
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    cfg = HybridConfig(pp=1, mp=1, dp=1, n_microbatches=1, vocab_size=64,
                       hidden_size=32, num_layers=2, num_heads=2,
                       seq_len=16, sequence_parallel=False)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pp", "dp", "mp"))
    params = stack_for_pipeline(init_gpt_params(jax.random.key(0), cfg), cfg)
    specs = hybrid_param_specs(cfg)
    m, v, _ = init_zero_state(params, specs, mesh)
    step = make_hybrid_train_step(mesh, cfg)
    ids = np.zeros((1, 2, 16), np.int32)
    paddle.set_flags({"enable_nan_watchdog": True,
                      "flight_dump_dir": str(tmp_path)})
    loss, params, m, v = step(params, m, v, 1.0, ids)
    assert np.isfinite(float(np.asarray(loss)))
    recs = [r for r in fr.default_recorder().steps()
            if r.get("mode") == "hybrid"]
    assert recs and recs[-1]["tokens"] == ids.size
    # poison the weights -> next step's loss is non-finite -> watchdog
    params["wte"] = params["wte"] * float("nan")
    step(params, m, v, 2.0, ids)
    assert fr.default_recorder().first_nonfinite is not None
    assert fr.default_recorder().first_nonfinite["site"] == \
        "hybrid.train_step.loss"


def test_serving_tick_flight_records_and_deferral_reason():
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    paddle.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    # pool sized so the second request must wait for the first to finish
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=16,
                        num_blocks=4)
    rng = np.random.RandomState(0)
    eng.add_request(Request(rng.randint(1, 100, (16,)), max_new_tokens=30))
    eng.add_request(Request(rng.randint(1, 100, (16,)), max_new_tokens=30))
    eng.run()
    ticks = [r for r in fr.default_recorder().steps()
             if r.get("timeline") == "serving"]
    assert ticks, "serving ticks must land in the flight ring"
    assert all("tokens" in t and "wall_s" in t for t in ticks)
    rej = metrics.get("serving.rejections")
    assert rej.value(reason="pool_exhausted") == 1  # once, not per tick


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_bench_rung_failure_writes_flight_dump(tmp_path):
    """Satellite: a dying rung leaves a flight-recorder dump next to the
    JSON record, so an rc!=0-style artifact still carries evidence."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_flight_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    from paddle_tpu.observability import harness

    @harness.register_rung("_t_dying", smoke=True)
    def dying(ctx):
        fr.default_recorder().record_step({"step": 1, "note": "pre-death"})
        raise ValueError("synthetic rung death")

    try:
        art = tmp_path / "art.json"
        rc = bench.main(["--rungs", "_t_dying", "--out", str(art)])
    finally:
        harness._REGISTRY.pop("_t_dying", None)
    assert rc == 0
    doc = json.loads(art.read_text())
    rec = {r["rung"]: r for r in doc["records"]}["_t_dying"]
    assert rec["ok"] is False and "synthetic rung death" in rec["error"]
    dump_path = rec["flight_dump"]
    assert os.path.dirname(dump_path) == str(tmp_path)
    dump = json.loads(open(dump_path).read())
    assert dump["schema"] == fr.FLIGHT_SCHEMA
    assert dump["reason"] == "rung_failure:_t_dying"
    assert {"step": 1, "note": "pre-death"} in dump["steps"]
    assert any(e["kind"] == "rung_error" and "synthetic rung death"
               in e["error"] for e in dump["events"])


# --------------------------------------------------- profiler round trip

def test_profiler_chrome_export_roundtrip_with_spans(tmp_path):
    """Satellite: observability.span events must land in the exported
    chrome trace with usable timestamps."""
    from paddle_tpu import observability as obs
    from paddle_tpu.profiler import Profiler
    with Profiler() as p:
        with obs.span("telemetry_region"):
            with obs.span("inner_region"):
                time.sleep(0.002)
        path = p.export(str(tmp_path / "trace.json"))
    events = json.loads(open(path).read())["traceEvents"]
    spans = {e["name"]: e for e in events if e["cat"] == "span"}
    assert {"telemetry_region", "inner_region"} <= set(spans)
    for e in spans.values():
        assert e["ph"] == "X" and e["dur"] > 0 and e["ts"] >= 0
    # nesting preserved on the timeline
    outer, inner = spans["telemetry_region"], spans["inner_region"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    # the profiler's record start/stop transitions land in the flight
    # ring, so crash dumps say whether a trace was live
    states = [e["state"] for e in fr.default_recorder().events()
              if e["kind"] == "profiler"]
    assert "record_start" in states and "record_stop" in states


# ------------------------------------------------------------------ CLI

@pytest.mark.slow   # tier-1 budget (R010): three CLI children, ~4s
def test_dump_cli_subprocess(tmp_path):
    """Fast-tier smoke of `python -m paddle_tpu.observability.dump`
    (mirrors the bench --smoke subprocess pattern)."""
    rec = fr.FlightRecorder(capacity=2)
    rec.record_step({"step": 7, "loss": 0.5})
    rec.dump(str(tmp_path / "flight_manual_1.json"), reason="cli-test")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.dump",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["schema"] == fr.FLIGHT_SCHEMA
    assert doc["reason"] == "cli-test"
    assert doc["steps"] == [{"step": 7, "loss": 0.5}]
    # --registry mode prints a metrics snapshot document
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.dump",
         "--registry"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout)["schema"] == "paddle_tpu.metrics/v1"
    # empty dir -> exit 1, stdout stays clean
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.dump",
         "--dir", str(tmp_path / "empty")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert out.returncode == 1 and not out.stdout.strip()
