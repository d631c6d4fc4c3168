"""Self-test for the bench regression classifier (VERDICT r5 #7).

`harness.regression_check` separates CODE regressions from launch-window
artifacts (env_suspect).  Until now its first real firing would have
been its first run ever; these tests synthesize a prior BENCH artifact
plus degraded/healthy env probes on CPU and pin the split it must make.
"""

import json

import numpy as np  # noqa: F401  (suite convention)
import pytest

from paddle_tpu.observability import harness


def _artifact(tmp_path, values, env=None):
    """Write a prior-round artifact in the harness `records` schema."""
    records = [{"rung": name, "ok": True, "device": "cpu",
                "elapsed_s": 1.0, "value": val}
               for name, val in values.items()]
    if env is not None:
        records.append({"rung": "env_probe", "ok": True, "device": "cpu",
                        "elapsed_s": 0.1, "value": env})
    path = tmp_path / "BENCH_r98.json"
    path.write_text(json.dumps({
        "schema": harness.SCHEMA, "records": records}))
    return str(path)


def _records(values):
    return [{"rung": name, "ok": True, "device": "cpu",
             "elapsed_s": 1.0, "value": val}
            for name, val in values.items()]


KEYS = {"gpt124m_train": "tokens_per_sec",
        "serving_decode": "tokens_per_sec"}


def test_healthy_env_drop_is_a_regression(tmp_path):
    """Same dispatch floor and chip throughput, -20% on a rung: that is
    CODE, and the classifier must say so."""
    env = {"dispatch_floor_ms": 1.5, "matmul_tflops": 10.0}
    prev = _artifact(tmp_path, {
        "gpt124m_train": {"tokens_per_sec": 1000.0},
        "serving_decode": {"tokens_per_sec": 500.0, "latency_bound": True},
    }, env=env)
    cur = _records({
        "gpt124m_train": {"tokens_per_sec": 800.0},
        "serving_decode": {"tokens_per_sec": 495.0, "latency_bound": True},
    })
    out = harness.regression_check(cur, previous=prev, keys=KEYS,
                                   env_probe=env)
    assert out["regressed"] == ["gpt124m_train"]
    assert out["env_suspect"] == {}
    # the -1% serving drift is noise, not a finding
    assert "serving_decode" not in out["regressed"]


def test_degraded_dispatch_floor_marks_latency_bound_env_suspect(tmp_path):
    """A latency-bound rung whose drop tracks a worsened dispatch floor
    is a launch-path artifact, not a regression (the round-4/5 lesson)."""
    prev = _artifact(tmp_path, {
        "serving_decode": {"tokens_per_sec": 500.0, "latency_bound": True},
    }, env={"dispatch_floor_ms": 1.5, "matmul_tflops": 10.0})
    cur = _records({
        "serving_decode": {"tokens_per_sec": 330.0, "latency_bound": True},
    })
    out = harness.regression_check(
        cur, previous=prev, keys=KEYS,
        env_probe={"dispatch_floor_ms": 6.0, "matmul_tflops": 10.0})
    assert out["regressed"] == []
    assert "serving_decode" in out["env_suspect"]
    assert "latency-bound" in out["env_suspect"]["serving_decode"]


def test_degraded_chip_window_marks_compute_rung_env_suspect(tmp_path):
    """A compute rung dropping while the probe shows the chip window
    itself degraded (<85% of the prior matmul TFLOP/s) is env-suspect."""
    prev = _artifact(tmp_path, {
        "gpt124m_train": {"tokens_per_sec": 1000.0},
    }, env={"dispatch_floor_ms": 1.5, "matmul_tflops": 10.0})
    cur = _records({"gpt124m_train": {"tokens_per_sec": 700.0}})
    out = harness.regression_check(
        cur, previous=prev, keys=KEYS,
        env_probe={"dispatch_floor_ms": 1.5, "matmul_tflops": 6.0})
    assert out["regressed"] == []
    assert "chip window degraded" in out["env_suspect"]["gpt124m_train"]


def test_no_prior_artifact_returns_none(tmp_path):
    out = harness.regression_check(
        _records({"gpt124m_train": {"tokens_per_sec": 1.0}}),
        previous=str(tmp_path / "missing.json"), keys=KEYS)
    assert out is None


def test_fault_tolerance_rung_schema(tmp_path):
    """Pin the resilience rung's record schema (ISSUE 5): save/restore
    latency + bytes, chaos-truncation detection and the tiny-model
    kill-and-resume drill, run at smoke scale on CPU."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_ft", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_fault_tolerance(ctx)
    rec = {"rung": "fault_tolerance", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("fault_tolerance").smoke
    assert bench._REGRESSION_KEYS["fault_tolerance"] == "save_mb_per_s"
    for key in ("payload_mb", "save_s", "restore_s", "save_mb_per_s",
                "restore_mb_per_s"):
        assert isinstance(val[key], float) and val[key] > 0, key
    # the resilience claims themselves
    assert val["roundtrip_ok"] is True
    assert val["corrupt_skipped"] is True
    assert val["resume_bitexact"] is True


def test_backend_init_failure_degrades_at_rung_start(monkeypatch):
    """ROADMAP housekeeping (BENCH_r05): a PJRT `make_c_api_client`
    failure INSIDE a rung (after a passing probe) must degrade to
    `ok:false reason:backend_unavailable` like probe-gated rungs — not
    surface as a code-bug `error` record (let alone rc=1)."""
    import jax

    def boom():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': INTERNAL: "
            "make_c_api_client failed: could not connect")
    monkeypatch.setattr(jax, "devices", boom)

    @harness.register_rung("_t_backend_init")
    def rung(ctx):
        jax.devices()     # the first backend touch inside the rung

    @harness.register_rung("_t_real_bug")
    def bug_rung(ctx):
        raise RuntimeError("an actual code bug, not the backend")

    try:
        rec = harness.run_rung(harness.get_rung("_t_backend_init"),
                               probe={"ok": True, "platform": "tpu",
                                      "device_kind": "tpu", "n_devices": 1,
                                      "error": None})
        assert rec["ok"] is False
        assert rec["reason"] == "backend_unavailable"
        assert "make_c_api_client" in rec["error"]
        assert harness.validate_record(rec) is None
        # a RuntimeError that is NOT a backend-init fingerprint stays a
        # plain error record (real bugs must not hide as env issues)
        rec = harness.run_rung(harness.get_rung("_t_real_bug"),
                               probe={"ok": True, "platform": "cpu",
                                      "device_kind": "cpu", "n_devices": 1,
                                      "error": None})
        assert rec["ok"] is False and "reason" not in rec
        assert "actual code bug" in rec["error"]
    finally:
        harness._REGISTRY.pop("_t_backend_init", None)
        harness._REGISTRY.pop("_t_real_bug", None)


def test_request_trace_rung_schema():
    """Pin the ISSUE 6 `request_trace` rung's record schema: TTFT/TPOT
    percentiles from the lifecycle sketches plus the tracing-overhead
    split (ticks/s metrics-gate on vs off), regression key
    `trace_overhead_pct`.  Runs the rung at smoke scale on CPU."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_rt", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_request_trace(ctx)
    rec = {"rung": "request_trace", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("request_trace").smoke
    assert bench._REGRESSION_KEYS["request_trace"] == "trace_overhead_pct"
    for key in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                "tpot_p99_ms", "e2e_p50_ms"):
        assert val[key] > 0, key
    assert val["ttft_p99_ms"] >= val["ttft_p50_ms"]
    assert val["requests_traced"] >= 4
    assert val["ticks_per_sec_on"] > 0 and val["ticks_per_sec_off"] > 0
    # the acceptance bound is <=2 on a quiet box; CI containers are
    # noisy, so the schema pin only rejects gross regressions
    assert 0.0 <= val["trace_overhead_pct"] < 25.0


@pytest.mark.slow  # 17s measured: full cold-start rung in-process; joins the other rung-schema drills
def test_cold_start_rung_schema():
    """Pin the ISSUE 7 `cold_start` rung's record schema: two
    subprocesses sharing a cache dir time first-program-ready cold vs
    warm (regression key `cold_start_warm_speedup`), plus the serving
    warmup evidence — programs compiled, warmup seconds, and ZERO
    compile-tracker events once traffic ran.  Smoke scale on CPU."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_cs", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_cold_start(ctx)
    rec = {"rung": "cold_start", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("cold_start").smoke
    assert bench._REGRESSION_KEYS["cold_start"] == "cold_start_warm_speedup"
    assert val["cold_first_program_s"] > 0
    assert val["warm_first_program_s"] > 0
    # the acceptance claim: the warm restart read executables from the
    # shared cache instead of compiling (hit evidence + a real speedup;
    # noisy CI keeps the bound modest — trend rides the regression key)
    assert val["cold_cache_misses"] > 0 and val["warm_cache_hits"] > 0
    assert val["cold_start_warm_speedup"] > 1.0
    # the serving half: a warmed engine compiles NOTHING under traffic
    assert val["serving_warmup_programs"] >= 4
    assert val["serving_warmup_s"] > 0
    assert val["post_warmup_compiles"] == 0


@pytest.mark.slow   # one subprocess compiles the TP program grid — too
                    # heavy for the tier-1 budget; full runs cover it
def test_serving_tp_rung_schema():
    """Pin the ISSUE 9 `serving_tp` rung's record schema: simulated TP
    degree {1, 2} x prefix-cache sweep recording tokens/sec/chip and
    TTFT p50 per degree, the degree-2-vs-1 bit-parity verdict, and the
    `prefix_hit_speedup` regression key (median full-prefill seconds
    over median suffix-prefill seconds)."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_tp", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_serving_tp(ctx)
    rec = {"rung": "serving_tp", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("serving_tp").smoke
    assert bench._REGRESSION_KEYS["serving_tp"] == "prefix_hit_speedup"
    # the two acceptance claims: TP decode is bit-identical across
    # degrees, and a prefix hit really skips prefill work
    assert val["parity_tp2_vs_tp1"] is True
    assert val["prefix_hit_speedup"] > 1.0
    assert val["prefix_hits"] >= 4
    assert val["tokens_per_sec_chip_tp1"] > 0
    assert val["tokens_per_sec_chip_tp2"] > 0
    assert val["ttft_p50_ms_tp1"] > 0 and val["ttft_p50_ms_tp2"] > 0


@pytest.mark.slow   # warms ~a dozen engine grids (donor + cold/restored
                    # per rep) — too heavy for the tier-1 budget
def test_serving_restart_rung_schema():
    """Pin the ISSUE 15 `serving_restart` rung's record schema: one
    donor engine drains + exports its prefix cache, then cold vs
    import-restored engines answer the same shared-system-prompt
    request — `restart_ttft_speedup` (regression key) with the
    restored stream BIT-matching the donor's prefix-hit path."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_restart", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_serving_restart(ctx)
    rec = {"rung": "serving_restart", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("serving_restart").smoke
    assert bench._REGRESSION_KEYS["serving_restart"] == \
        "restart_ttft_speedup"
    # the two acceptance claims: a warm restart really skips prefill
    # work, and it NEVER changes tokens
    assert val["restored_stream_bitmatch"] is True
    assert val["restart_ttft_speedup"] > 1.0
    assert val["imported_blocks"] == val["export_blocks"] > 0
    assert val["import_skipped_corrupt"] == 0
    assert val["cold_ttft_ms_p50"] > val["restored_ttft_ms_p50"] > 0
    assert val["export_bytes"] > 0 and val["export_s"] >= 0


@pytest.mark.slow   # three replicas warm + a live rolling restart —
                    # too heavy for the tier-1 budget; full runs cover it
def test_fleet_rung_schema():
    """Pin the ISSUE 16 `fleet` rung's record schema: 3 in-process
    replicas behind the prefix-affinity router under concurrent
    shared-prefix traffic, a rolling restart mid-run —
    `goodput_during_restart_ratio` (regression key) with zero dropped
    requests and the affinity hit-rate alongside."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_fleet", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_fleet(ctx)
    rec = {"rung": "fleet", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("fleet").smoke
    assert bench._REGRESSION_KEYS["fleet"] == \
        "goodput_during_restart_ratio"
    # the acceptance claims: the fleet keeps serving through the drill
    # (every replica really restarted) and drops NOTHING
    assert val["requests_dropped"] == 0
    assert val["replicas_restarted"] == 3
    assert val["goodput_during_restart_ratio"] > 0
    assert val["steady_goodput_rps"] > 0
    assert val["restart_goodput_rps"] > 0
    assert val["rolling_restart_s"] > 0
    assert val["requests_completed"] > 0
    assert val["affinity_hit_rate"] > 0.9
    assert val["failovers"] >= 0


@pytest.mark.slow   # three replicas warm behind the router — too heavy
                    # for the tier-1 budget; full runs cover it
def test_fleet_telescope_rung_schema():
    """Pin the ISSUE 17 `fleet_telescope` rung's record schema: 3
    in-process replicas behind the router, trace propagation toggled
    over paired windows (`fleet_trace_overhead_pct` is the regression
    key), a federated /fleet/metrics scrape, and the multi-process
    fleet_trace merge over the run's real flight dumps."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_fleet_telescope", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_fleet_telescope(ctx)
    rec = {"rung": "fleet_telescope", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("fleet_telescope").smoke
    assert bench._REGRESSION_KEYS["fleet_telescope"] == \
        "fleet_trace_overhead_pct"
    # the acceptance claims: the telescope sees the whole fleet (one
    # trace id spans >1 process, every process row merged, the
    # federated scrape renders) and costs little
    assert val["trace_processes"] == 4            # router + 3 replicas
    assert val["trace_ids_cross_process"] >= 1
    assert val["trace_ids_merged"] >= 1
    assert val["trace_events"] > 0
    assert val["fleet_metric_lines"] > 0
    assert val["fleet_ttft_p99_ms"] > 0
    assert val["streams_per_sec_on"] > 0
    assert val["streams_per_sec_off"] > 0
    assert val["fleet_trace_overhead_pct"] < 50.0
    assert len(val["overhead_pct_windows"]) >= 2


@pytest.mark.slow   # the subprocess compiles ~nine engine configs —
                    # too heavy for the tier-1 budget; full runs cover it
def test_spec_decode_rung_schema():
    """Pin the `spec_decode` rung's ISSUE 13 schema: the model-draft
    machinery sweep PLUS the ngram arm on the repetitive-suffix
    workload (now the `spec_decode_speedup` headline — acceptance
    demands >= 1.25 with real drafting, not the same-weights 1.0x
    harness), the accept-rate-vs-k curve, adaptive-k evidence, and the
    int8 + fp8 quant ratios, with THREE regression keys wired as a
    tuple (`spec_decode_speedup`, `spec_accept_rate`,
    `quant_weight_ratio`)."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_spec", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_spec_decode(ctx)
    rec = {"rung": "spec_decode", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("spec_decode").smoke
    assert bench._REGRESSION_KEYS["spec_decode"] == (
        "spec_decode_speedup", "spec_accept_rate", "quant_weight_ratio")
    # the acceptance claims: every spec arm is lossless (model draft,
    # model draft x quant, AND the ngram arm), the ngram arm genuinely
    # accepts and PAYS on the repetitive workload, the adaptive
    # controller really moved, and both quant modes shrink the weights
    # with fp8 inside its documented deviation budget
    assert val["parity_spec_vs_plain"] is True
    assert val["parity_spec_quant"] is True
    assert val["parity_ngram_vs_plain"] is True
    assert val["spec_accept_rate"] > 0.5
    assert val["spec_decode_speedup"] >= 1.25
    assert val["adaptive_k_switches"] >= 1
    assert set(val["accept_vs_k"]) == {"2", "4", "8"}
    assert all(v["accept_rate"] > 0 and v["tokens_per_sec"] > 0
               for v in val["accept_vs_k"].values())
    assert val["quant_weight_ratio"] > 2.0
    assert val["quant_fp8_weight_ratio"] > 2.0
    assert val["fp8_max_logit_dev"] < 0.25
    for key in ("tokens_per_sec_plain", "tokens_per_sec_ngram",
                "tokens_per_sec_model_draft", "tokens_per_sec_quant",
                "tokens_per_sec_fp8"):
        assert val[key] > 0, key


@pytest.mark.slow   # two serving engines + open-loop arrival drives —
                    # too heavy for the tier-1 budget; full runs cover it
def test_continuous_batching_rung_schema():
    """Pin the ISSUE 11 `continuous_batching` rung's record schema:
    open-loop Poisson arrivals at 2-3 RPS over chunked vs monolithic
    engines with `goodput_under_slo` as the headline regression key,
    plus the long-prompt-arrival stall A/B — the acceptance claim that
    chunked prefill bounds a running stream's inter-token gap where
    monolithic prefill cannot (`long_arrival_tpot_ratio` strictly
    above 1)."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_cb", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_continuous_batching(ctx)
    rec = {"rung": "continuous_batching", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("continuous_batching").smoke
    assert bench._REGRESSION_KEYS["continuous_batching"] == (
        "goodput_under_slo", "long_arrival_tpot_ratio")
    # the acceptance claim: the monolithic stall strictly exceeds the
    # chunked bound under a long-prompt arrival
    assert val["long_arrival_tpot_ratio"] > 1.0
    assert val["long_arrival_gap_mono_ms"] > \
        val["long_arrival_gap_chunked_ms"]
    assert val["goodput_under_slo"] > 0
    assert val["goodput_monolithic"] > 0
    assert val["goodput_ratio_vs_monolithic"] > 0
    assert val["tpot_p99_ms_chunked"] > 0 and val["tpot_p99_ms_mono"] > 0
    assert val["prefill_chunk"] > 0
    # every cell reports goodput + client-side TPOT p99
    for cell, v in val["levels"].items():
        assert v["requests"] > 0 and v["goodput_rps"] >= 0, cell
        assert "tpot_p99_ms" in v


def test_multi_key_regression_check_labels_secondary_keys(tmp_path):
    """The harness accepts a tuple of regression keys per rung: the
    first labels the rung, later ones report as `<rung>.<key>` — both
    deltas computed against the previous artifact."""
    import json as _json
    prev = tmp_path / "BENCH_r90.json"
    prev.write_text(_json.dumps({
        "schema": harness.SCHEMA,
        "records": [{"rung": "spec_decode", "ok": True, "device": "cpu",
                     "elapsed_s": 1.0,
                     "value": {"spec_decode_speedup": 2.0,
                               "quant_weight_ratio": 4.0}}]}))
    cur = [{"rung": "spec_decode", "ok": True, "device": "cpu",
            "elapsed_s": 1.0,
            "value": {"spec_decode_speedup": 1.0,
                      "quant_weight_ratio": 4.0}}]
    rep = harness.regression_check(
        cur, previous=str(prev),
        keys={"spec_decode": ("spec_decode_speedup",
                              "quant_weight_ratio")})
    assert rep["rel_delta"]["spec_decode"] == -0.5
    assert rep["rel_delta"]["spec_decode.quant_weight_ratio"] == 0.0
    assert "spec_decode" in rep["regressed"]


@pytest.mark.slow  # 6s measured: runs graft-lint over the whole tree; test_static_analysis keeps the fast tier-1 ratchet gate
def test_analyze_rung_schema():
    """Pin the ISSUE 8/12 `analyze` rung's record schema: graft-lint
    wall seconds + per-rule findings over the grown TEN-rule set and
    the full default tree (tests/ included — R010's surface),
    regression key `analyze_files_per_sec` (the analyzer runs in
    tier-1 on every CI pass, so its runtime is a build-latency
    budget).  Smoke on CPU."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_an", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_analyze(ctx)
    rec = {"rung": "analyze", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("analyze").smoke
    assert bench._REGRESSION_KEYS["analyze"] == "analyze_files_per_sec"
    # the 30s acceptance budget, with headroom for noisy CI boxes
    assert 0 < val["analyze_wall_s"] < 30.0
    assert val["analyze_files"] > 100            # really saw the tree
    assert val["analyze_files_per_sec"] > 0
    # a committed tree is clean against its committed baseline
    assert val["findings_new"] == 0
    assert val["findings_total"] >= 0
    assert isinstance(val["findings_per_rule"], dict)
    # every registered rule reports (zero-filled — a rule silently
    # dropping out of the run would otherwise look like a clean rule);
    # R001..R015 as of PR 20
    assert val["rules"] == 15
    assert sorted(val["findings_per_rule"]) == [
        f"R{i:03d}" for i in range(1, 16)]
    # the grown rule set still sees the WHOLE default tree, tests
    # included (the R010 surface) — well over the package alone
    assert val["analyze_files"] > 280


@pytest.mark.slow   # warms a spec+prefix serving grid and drives ~14
                    # measurement windows — too heavy for the tier-1
                    # budget; full runs cover it
def test_xray_rung_schema():
    """Pin the ISSUE 14 `xray` rung's record schema: sampling overhead
    (regression key `xray_overhead_pct`, quietest-pair estimator —
    acceptance <2 on a quiet box, the pin only rejects gross
    regressions on noisy CI) plus the ledger evidence — programs
    tracked with cost, sampled dispatches, the top program by device
    time, and the kernel-coverage verdicts for the ROADMAP 5b suspect
    paths (dense on this CPU build)."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module_xr", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_xray(ctx)
    rec = {"rung": "xray", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert harness.get_rung("xray").smoke
    assert bench._REGRESSION_KEYS["xray"] == "xray_overhead_pct"
    assert 0.0 <= val["xray_overhead_pct"] < 25.0
    assert len(val["overhead_pct_windows"]) >= 3
    assert val["tokens_per_sec_on"] > 0 and val["tokens_per_sec_off"] > 0
    # the ledger evidence: the spec+prefix grid (2 ticks + decode + 1
    # spec rung + 2 prefill + 2 prefill_cont + cow) all tracked, all
    # with cost_analysis, and real samples taken
    assert val["programs_tracked"] >= 9
    assert val["programs_with_cost"] >= 9
    assert val["sampled_dispatches"] > 0
    assert val["top_program"]
    assert val["kernel_coverage_programs"] >= 9
    # the CPU build lowers no Pallas CUSTOM CALLS (interpret mode is
    # traced XLA) — but since ISSUE 18 the suspects run the paged
    # kernels in interpret mode, evidenced by trace-time claims: the
    # rows must read NOT dense, via "interpret"
    assert val["pallas_programs"] == 0
    assert val["suffix_prefill_dense"] is False
    assert val["spec_verify_dense"] is False
    assert val["suffix_prefill_via"] == ["interpret"]
    assert val["spec_verify_via"] == ["interpret"]


def _load_bench(modname):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        modname, os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def _kernel_coverage_record(bench, smoke):
    from types import SimpleNamespace

    from paddle_tpu.observability import xray

    # the audit's entries are process-global: an engine that another test
    # file warmed in this worker with the kernels flagged off would leave
    # its dense rows in this rung's audit
    xray.reset()
    ctx = SimpleNamespace(smoke=smoke, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_kernel_coverage(ctx)
    rec = {"rung": "kernel_coverage", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    return val


def test_kernel_coverage_rung_schema():
    """Pin the ISSUE 18 `kernel_coverage` rung: both regression keys
    present and >= 1.0 on the CPU interpret smoke (the kernels must
    BEAT the dense gather at the table-slack shapes, or the flip is a
    regression dressed as a feature), and the embedded audit rows carry
    kernel=True via=interpret for all three X-ray suspects."""
    bench = _load_bench("bench_module_kc")
    val = _kernel_coverage_record(bench, smoke=True)
    assert harness.get_rung("kernel_coverage").smoke
    assert bench._REGRESSION_KEYS["kernel_coverage"] == (
        "paged_prefill_kernel_speedup", "spec_verify_kernel_speedup")
    for key in bench._REGRESSION_KEYS["kernel_coverage"]:
        assert isinstance(val[key], float)
        assert val[key] >= 1.0, (key, val[key])
    assert val["paged_prefill_kernel_ms"] > 0
    assert val["spec_verify_dense_ms"] > 0
    paths = {r["path"]: r for r in val["audit"]}
    assert set(paths) == {"suffix/chunked prefill", "spec verify chunk",
                          "moe dispatch/combine"}
    for r in paths.values():
        assert r["kernel"] is True and r["via"] == "interpret"
    assert "paged_chunk_prefill" in \
        paths["suffix/chunked prefill"]["kernels"]
    assert "paged_spec_verify" in paths["spec verify chunk"]["kernels"]
    assert "moe_fused_dispatch" in \
        paths["moe dispatch/combine"]["kernels"]


def test_kernel_coverage_degrades_without_pallas(monkeypatch):
    """ISSUE 18 satellite: a jax build without Pallas must degrade the
    kernel rung to `ok:false reason:backend_unavailable` — an
    environment answer, not an rc=1 code bug."""
    bench = _load_bench("bench_module_kc_deg")
    from paddle_tpu.ops import pallas_paged

    monkeypatch.setattr(pallas_paged, "pltpu", None)
    rec = harness.run_rung(harness.get_rung("kernel_coverage"),
                           probe={"ok": True, "platform": "cpu",
                                  "device_kind": "cpu", "n_devices": 1,
                                  "error": None})
    assert rec["ok"] is False
    assert rec["reason"] == "backend_unavailable"
    assert "pallas" in rec["error"].lower()
    assert harness.validate_record(rec) is None
    assert bench is not None   # rung registration came from this load


@pytest.mark.slow  # 4s measured: the non-smoke shapes of the kernel rung
def test_kernel_coverage_rung_heavy():
    """The heavy twin: same pins at the non-smoke CPU shapes (wider
    tables, longer prefixes — the regime the speedup keys are diffed
    at across bench rounds)."""
    bench = _load_bench("bench_module_kc_heavy")
    val = _kernel_coverage_record(bench, smoke=False)
    for key in bench._REGRESSION_KEYS["kernel_coverage"]:
        assert val[key] >= 1.0, (key, val[key])
    assert val["max_blocks"] == 256
    assert {r["path"] for r in val["audit"]} == {
        "suffix/chunked prefill", "spec verify chunk",
        "moe dispatch/combine"}


@pytest.mark.slow  # 5s measured: compiles the fused-optimizer step; joins the other rung-schema drills
def test_fused_optimizer_rung_schema():
    """Pin the round-7 `fused_optimizer` rung's record schema: the
    regression key (`speedup`) and the per-cell dispatch/wall fields the
    acceptance criteria read.  Runs the rung at smoke scale on CPU."""
    import importlib.util
    import os
    from types import SimpleNamespace

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_fused_optimizer(ctx)
    rec = {"rung": "fused_optimizer", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    # the regression key harness diffs across rounds
    assert harness.get_rung("fused_optimizer").smoke
    assert bench._REGRESSION_KEYS["fused_optimizer"] == "speedup"
    assert isinstance(val["speedup"], float)
    assert val["ladder"], "param-count ladder must not be empty"
    for row in val["ladder"]:
        for cell in ("fused", "per_param"):
            assert set(row[cell]) == {"step_ms", "dispatches_per_step"}
            assert row[cell]["step_ms"] > 0
        assert row["per_param"]["dispatches_per_step"] >= row["leaves"]
        # the tentpole claim: ONE program dispatch per fused step
        assert row["fused"]["dispatches_per_step"] <= 3
    assert val["fused_dispatches_per_step"] <= 3


def test_zero3_elastic_regression_keys_and_tpu_degrade():
    """Pin the ISSUE 19 `zero3_elastic` rung's wiring without paying
    for the subprocess drill: both regression keys registered, and the
    TPU path degrades to `ok:false reason:backend_unavailable` (the
    drill NEEDS a forced multi-device CPU mesh — a latched TPU backend
    is an environment answer, not an rc=1 code bug)."""
    bench = _load_bench("bench_module_z3")
    assert bench._REGRESSION_KEYS["zero3_elastic"] == (
        "zero3_step_ratio", "elastic_resume_ok")
    assert harness.get_rung("zero3_elastic").smoke
    rec = harness.run_rung(harness.get_rung("zero3_elastic"),
                           probe={"ok": True, "platform": "tpu",
                                  "device_kind": "TPU v4", "n_devices": 4,
                                  "error": None})
    assert rec["ok"] is False
    assert rec["reason"] == "backend_unavailable"
    assert "mesh" in rec["error"]
    assert harness.validate_record(rec) is None


@pytest.mark.slow  # ~80s measured: the full subprocess rung (fused vs
                   # naive allgather-on-use + the 4->2->4 resume drill)
def test_zero3_elastic_rung_schema():
    """The heavy twin runs the rung for real: the fused one-dispatch
    step must BEAT the naive per-leaf allgather loop (ratio >= 1.0, the
    acceptance floor) and the in-subprocess 4 -> 2 -> 4 reshard drill
    must report bit-exactness."""
    from types import SimpleNamespace

    bench = _load_bench("bench_module_z3_full")
    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_zero3_elastic(ctx)
    rec = {"rung": "zero3_elastic", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert val["zero3_step_ratio"] >= 1.0
    assert val["elastic_resume_ok"] is True
    assert val["fused_step_ms"] > 0 and val["naive_step_ms"] > 0
    assert val["gather_buckets"] >= 1


def test_elastic_mttr_regression_keys_and_tpu_degrade():
    """Pin the ISSUE 20 `elastic_mttr` rung's wiring without paying for
    the 3-launcher fleet: the regression key registered (MTTR growing
    means detection or re-rendezvous got slower), and the TPU path
    degrades to `ok:false reason:backend_unavailable` (the drill
    measures host process supervision, not devices)."""
    bench = _load_bench("bench_module_mttr")
    assert bench._REGRESSION_KEYS["elastic_mttr"] == "elastic_mttr_s"
    assert harness.get_rung("elastic_mttr").smoke
    rec = harness.run_rung(harness.get_rung("elastic_mttr"),
                           probe={"ok": True, "platform": "tpu",
                                  "device_kind": "TPU v4", "n_devices": 4,
                                  "error": None})
    assert rec["ok"] is False
    assert rec["reason"] == "backend_unavailable"
    assert harness.validate_record(rec) is None


@pytest.mark.slow  # ~20s measured: a real 3-launcher fleet, one node
                   # SIGKILLed mid-run
def test_elastic_mttr_rung_schema():
    """The heavy twin runs the kill-a-node drill for real and pins the
    record schema plus the zero-human-intervention hard gate: the fleet
    re-settles at 2 nodes and resumes stepping with operator_actions
    == 0, detection strictly precedes recovery."""
    from types import SimpleNamespace

    bench = _load_bench("bench_module_mttr_full")
    ctx = SimpleNamespace(smoke=True, on_tpu=False, probe={"ok": True},
                          device_kind="cpu")
    val = bench.bench_elastic_mttr(ctx)
    rec = {"rung": "elastic_mttr", "ok": True, "device": "cpu",
           "elapsed_s": 0.1, "value": val}
    assert harness.validate_record(rec) is None
    assert val["recovered"] is True
    assert val["operator_actions"] == 0
    assert val["settled_nodes"] == 2
    assert val["generation"] >= 1
    assert 0 < val["t_detect_s"] < val["elastic_mttr_s"]
