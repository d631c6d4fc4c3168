"""Engine X-ray (ISSUE 14): the per-program execution ledger, sampled
device-time probe, cost_analysis join, HLO kernel-coverage audit,
per-tick phase breakdown, readiness, and the chrome-trace export.

The acceptance story: a warmed CPU-smoke serving run names every grid
program in `dump --xray` with dispatches, sampled device seconds,
cost-analysis FLOPs and MFU; the kernel-coverage table correctly
reports the dense-gather (non-Pallas) status of this build's serving
paths; sampling changes NO streams and forces tick-loop boundaries
(never measuring through the double-buffered chain); and the full
spec+quant+TP2+chunked composition still triggers zero post-warmup
compiles with sampling enabled.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import compile_tracker, dump
from paddle_tpu.observability import flight_recorder as flight
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import xray


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _sampling_off_after():
    yield
    paddle.set_flags({"xray_sample_interval": 0})


# ------------------------------------------------------------- unit layer

def test_key_for_uses_scalar_signature_pairs_only():
    """Ledger keys = compile-tracker name + the blame signature's
    SCALAR pairs; bulky values (the fused step's per-leaf aval tuple)
    are dropped so keys stay readable and bounded."""
    assert xray.key_for("serving.tick",
                        (("steps_per_tick", 2), ("max_batch", 4))) \
        == "serving.tick[steps_per_tick=2,max_batch=4]"
    assert xray.key_for("optimizer.fused_step",
                        (("leaves", 3), ("params", ("f32[4]", "f32[2]")),
                         ("donate", True))) \
        == "optimizer.fused_step[leaves=3,donate=True]"
    assert xray.key_for("plain", None) == "plain"
    long = "x" * 40
    assert xray.key_for("n", (("s", long),)) == "n"   # long strs dropped


def test_dispatch_counts_always_samples_on_interval():
    ent = xray.register("t.xray_unit", (("case", 1),))
    fn = jax.jit(lambda a: a * 2 + 1)
    fn(jnp.ones((4,)))   # compile outside the counted window
    n0 = ent.dispatches
    with flag_guard(xray_sample_interval=2):
        for i in range(4):
            out = xray.dispatch(ent, fn, (jnp.ones((4,)) * i,), {})
    np.testing.assert_allclose(np.asarray(out), np.ones(4) * 7)
    assert ent.dispatches - n0 == 4
    assert ent.samples == 2          # dispatches 2 and 4
    assert ent.sampled_seconds > 0 and ent.min_s <= ent.max_s
    # sampling off: counting continues, sampling stops
    xray.dispatch(ent, fn, (jnp.ones((4,)),), {})
    assert ent.dispatches - n0 == 5 and ent.samples == 2


def test_wrap_first_call_registers_and_never_samples_the_compile():
    fn = compile_tracker.wrap_first_call(
        jax.jit(lambda x: x + 1), "t.xray_wfc", (("v", 7),))
    ent = fn._xray_entry
    assert ent.key == "t.xray_wfc[v=7]"
    with flag_guard(xray_sample_interval=1):
        fn(jnp.ones((2,)))
        # first call = trace + XLA compile: a dispatch, never a sample
        assert ent.dispatches == 1 and ent.samples == 0
        assert xray.sample_due(fn)   # the next dispatch would probe
        fn(jnp.ones((2,)))
        assert ent.dispatches == 2 and ent.samples == 1
    assert not xray.sample_due(fn)   # off: nothing is ever due
    assert not xray.sample_due(None)


def test_attach_lowered_cost_and_custom_call_audit():
    lowered = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8)))
    ent = xray.register("t.xray_cost")
    xray.attach_lowered(ent, lowered)
    assert ent.audited
    assert ent.flops and ent.flops > 0
    assert ent.bytes_accessed and ent.bytes_accessed > 0
    assert ent.pallas is False and ent.custom_calls == 0
    # attach never raises on junk
    xray.attach_lowered(ent, object())
    xray.attach_lowered(None, lowered)


# -------------------------------------------------- the warmed-engine core

def test_warmed_engine_ledger_mfu_coverage_and_dump(model, capsys):
    """THE acceptance core on a fast 3-program grid: after warmup +
    traffic with sampling at interval 1, every warmed program appears
    in the ledger (and `dump --xray`) with dispatches, sampled device
    seconds, cost-analysis FLOPs and achieved FLOP/s (no MFU: this is a
    CPU, which has no peak-table row); the coverage
    table reports the dense (non-Pallas) status of every program on
    this CPU build; sampling triggered ZERO extra compiles (the
    warmup-grid pin extended); and the engine's health flips ready."""
    with flag_guard(serving_warmup=True, serving_pad_buckets="16",
                    xray_sample_interval=1):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, steps_per_tick=1,
                            prefix_cache=False)
        assert eng.ready is False
        assert eng.health() == {"ready": False, "reason": "warmup"}
        eng.warmup()
        before = compile_tracker.total_compiles()
        rng = np.random.RandomState(7)
        r1 = eng.add_request(Request(rng.randint(1, 1000, (10,)),
                                     max_new_tokens=5))
        r2 = eng.add_request(Request(rng.randint(1, 1000, (12,)),
                                     max_new_tokens=5, do_sample=True,
                                     temperature=0.9, seed=3))
        eng.run()
        assert compile_tracker.total_compiles() == before
        assert r1.done and r2.done
    assert eng.ready is True and eng.health()["ready"] is True
    assert eng.health()["warmup"]["programs"] == 3

    rep = xray.report()
    base = "max_batch=2,block_size=16"
    keys = {
        "serving.tick": f"serving.tick[steps_per_tick=1,{base}]",
        "serving.prefill": f"serving.prefill[L_pad=16,{base}]",
        "serving.decode":
            f"serving.decode[variant=host_sampling_k1,{base}]"}
    by_key = {p["program"]: p for p in rep["programs"]}
    by_prefix = {name: by_key[key] for name, key in keys.items()}
    mine = list(by_prefix.values())
    # every warmed grid program is named, with the full evidence row
    for name in ("serving.tick", "serving.prefill", "serving.decode"):
        p = by_prefix[name]
        assert p["dispatches"] > 0, name
        assert p["samples"] > 0, name
        assert p["sampled_device_s"] > 0, name
        assert p["flops_per_dispatch"] > 0, name
        assert p["bytes_per_dispatch"] > 0, name
        # achieved FLOP/s is a count over a clock; MFU needs the chip's
        # peak, and a CPU has no row in the table: absent, not made up
        assert p["mfu"] is None, name
        assert p["achieved_gflops_per_s"] > 0, name
    # fractions are a distribution over the estimated device time
    fracs = [p["device_time_frac"] for p in rep["programs"]
             if p["device_time_frac"]]
    assert 0.99 < sum(fracs) < 1.01
    # the CPU build lowers NO serving path to a Pallas custom call
    cov = {c["program"]: c for c in rep["kernel_coverage"]}
    for name in ("serving.tick", "serving.prefill", "serving.decode"):
        row = cov[by_prefix[name]["program"]]
        assert row["pallas"] is False and row["custom_calls"] == 0
        assert row["path"]     # a human-readable serving-path label
    # stats() exports the same ledger
    st = eng.stats()["xray"]
    assert st["programs_tracked"] == rep["programs_tracked"]
    assert st["total_est_device_s"] > 0
    # /metrics exports the dispatch/device-seconds counters
    disp = obs_metrics.get("xray.program_dispatches_total")
    assert disp.value(program=by_prefix["serving.tick"]["program"]) > 0
    dev = obs_metrics.get("xray.program_device_seconds_total")
    assert dev.value(program=by_prefix["serving.tick"]["program"]) > 0
    # ...and `dump --xray` prints the very same document
    assert dump.main(["--xray"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "paddle_tpu.xray/v1"
    assert {p["program"] for p in doc["programs"]} \
        >= {p["program"] for p in mine}
    assert doc["kernel_coverage"]


def test_sampling_parity_forced_boundaries_and_phases(model):
    """Sampling parity + the overlap contract + the phase breakdown,
    on two engines (tier-1 budget: one shared pair instead of three):
    identical token streams with sampling off vs every-dispatch,
    interval=1 forces EVERY tick to a real boundary
    (overlap_dispatches stays flat — no probe ever times a chained
    dispatch), and the tick flight records carry the ISSUE 14 phases.
    Sparse-interval composition is covered by the @slow composition
    pin (interval=2) and the cold_start spec+quant pin."""
    def drive(interval):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, steps_per_tick=2,
                            prefix_cache=False)
        rng = np.random.RandomState(3)
        with flag_guard(xray_sample_interval=interval,
                        serving_overlap=True):
            reqs = [eng.add_request(
                        Request(rng.randint(1, 1000, (10,)),
                                max_new_tokens=7)),
                    eng.add_request(
                        Request(rng.randint(1, 1000, (12,)),
                                max_new_tokens=7, do_sample=True,
                                seed=5))]
            eng.run()
        return [list(r.output_ids) for r in reqs]

    ov = obs_metrics.get("serving.overlap_dispatches")
    base = drive(0)
    assert ov.total() > 0          # the base run really overlapped
    # the per-tick phase breakdown rides the flight-record tick events
    recs = [r for r in flight.default_recorder().steps()
            if r.get("timeline") == "serving"]
    assert recs
    rec = recs[-1]
    assert rec["t_unix"] > 0
    ph = rec["phases"]
    for key in ("schedule_ms", "chunk_prefill_ms", "dispatch_ms",
                "harvest_wait_ms", "emit_ms", "host_ms",
                "device_wait_ms"):
        assert ph[key] >= 0, key
    assert ph["dispatch_ms"] > 0 and ph["host_ms"] >= ph["dispatch_ms"]
    assert ph["device_wait_ms"] == ph["harvest_wait_ms"]
    ov0 = ov.total()
    assert drive(1) == base        # parity at every-dispatch sampling
    assert ov.total() == ov0       # ...with every boundary forced


# ------------------------------------------------------------ chrome trace

def _flight_doc():
    """A synthetic flight document shaped like a real serving run."""
    t = 1700000000.0
    return {
        "schema": "paddle_tpu.flight/v1", "pid": 42, "reason": "manual",
        "steps": [
            {"timeline": "serving", "step": 3, "t_unix": t + 1.0,
             "wall_s": 0.5, "tokens": 4, "active": 2, "decode_steps": 2,
             "overlap": False,
             "phases": {"schedule_ms": 20.0, "chunk_prefill_ms": 30.0,
                        "dispatch_ms": 100.0, "harvest_wait_ms": 40.0,
                        "emit_ms": 10.0, "host_ms": 160.0,
                        "device_wait_ms": 40.0}},
            {"timeline": "training", "step": 9},       # skipped
            {"timeline": "serving", "step": 4, "wall_s": 0.1},  # no stamp
        ],
        "events": [
            {"kind": "request", "outcome": "finished", "rid": 7,
             "unix_time": t + 1.2, "e2e_s": 0.9, "queue_wait_s": 0.1,
             "prefill_s": 0.2, "ttft_s": 0.3, "prompt_len": 12,
             "tokens_out": 6, "ticks": 3, "prefill_chunks": 2},
            {"kind": "prefill_chunk", "rid": 7, "unix_time": t + 0.5,
             "start": 0, "tokens": 8, "slot": 0, "done": False},
            {"kind": "request", "outcome": "rejected:capacity",
             "rid": 8},                                # skipped
        ]}


def test_chrome_trace_nests_requests_under_the_tick_timeline():
    from paddle_tpu.observability import chrome
    trace = chrome.trace_from_flight(_flight_doc())
    evs = trace["traceEvents"]
    x = [e for e in evs if e["ph"] == "X"]
    names = {e["name"] for e in x}
    assert "tick 3" in names
    # the un-stamped tick and the training record are skipped, never
    # guessed
    assert "tick 4" not in names and "tick 9" not in names
    tick = next(e for e in x if e["name"] == "tick 3")
    phases = [e for e in x if e["cat"] == "phase"]
    assert {p["name"] for p in phases} == {
        "schedule", "chunk_prefill", "dispatch", "harvest_wait", "emit"}
    for p in phases:   # nested inside the tick slice, same row
        assert p["tid"] == tick["tid"]
        assert tick["ts"] <= p["ts"]
        assert p["ts"] + p["dur"] <= tick["ts"] + tick["dur"] + 1
    # request lifecycle: whole span + children on its own row
    req = next(e for e in x if e["name"] == "request 7")
    assert req["tid"] != tick["tid"]
    kids = [e for e in x if e["tid"] == req["tid"] and e is not req]
    assert {k["name"] for k in kids} == {"queue_wait", "prefill",
                                         "decode"}
    for k in kids:
        assert req["ts"] <= k["ts"] <= req["ts"] + req["dur"]
    # ticks and requests share the wall-clock timeline
    assert abs((tick["ts"] + tick["dur"]) - (req["ts"] + req["dur"])) \
        < 0.5 * 1e6
    # the chunk instant landed on the request's row
    chunk = next(e for e in evs if e["ph"] == "i")
    assert chunk["tid"] == req["tid"] and chunk["args"]["tokens"] == 8
    # rows are named for the viewer
    tn = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"ticks", "request 7"} <= tn
    json.dumps(trace)            # chrome JSON must serialize


def test_dump_cli_chrome_roundtrip(tmp_path, capsys):
    """`dump --chrome --path f.json` converts a written flight dump to
    chrome trace JSON on stdout (the PR 2 span round-trip, extended to
    the serving timeline)."""
    rec = flight.FlightRecorder(capacity=8)
    doc = _flight_doc()
    for s in doc["steps"]:
        rec.record_step(s)
    for e in doc["events"]:
        rec.record_event(e.pop("kind"), **e)
    path = tmp_path / "flight_chrome.json"
    rec.dump(str(path))
    assert dump.main(["--chrome", "--path", str(path)]) == 0
    out = capsys.readouterr().out
    trace = json.loads(out)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "tick 3" in names and "request 7" in names
    assert trace["otherData"]["schema"] == "paddle_tpu.chrome_trace/v1"


# -------------------------------------------------- composition (heavy)

@pytest.mark.slow   # warms a TP2 x ngram-spec x chunked grid (~8 shard
                    # map compiles) — tier-1 keeps the 3-program pin fast
def test_composition_spec_quant_tp2_chunked_ledger_pinned(model):
    """ISSUE 14 satellite: ledger correctness under the FULL serving
    composition — ngram spec (adaptive 2-rung ladder) + int8 quant +
    TP2 + chunked prefill + prefix cache, sampling at interval 2.
    Zero post-warmup compiles with sampling enabled; the ledger's
    dispatch counts reconcile exactly against the engine's own
    counters; spec verify and suffix prefill carry sampled MFU and
    their kernel-claim audit rows (via=interpret on this CPU build)."""
    with flag_guard(serving_warmup=True, serving_pad_buckets="16,32",
                    serving_prefill_chunk=8, xray_sample_interval=2):
        # max_batch=3 keeps this engine's ledger keys unique across the
        # process (entries are process-global; other TP2 tests in a
        # full run use max_batch 2/4)
        eng = ServingEngine(model, max_batch=3, max_context=128,
                            block_size=16, steps_per_tick=2,
                            tp_degree=2, spec_decode=True,
                            spec_draft="ngram", spec_adaptive=True,
                            spec_k_ladder="2,4", quant="int8")
        info = eng.warmup()
        before = compile_tracker.total_compiles()
        rng = np.random.RandomState(13)
        pat = list(rng.randint(1, 1000, (4,)))
        reqs = [eng.add_request(Request(np.array(pat * 10),
                                        max_new_tokens=20)),
                eng.add_request(Request(rng.randint(1, 1000, (24,)),
                                        max_new_tokens=8)),
                eng.add_request(Request(rng.randint(1, 1000, (40,)),
                                        max_new_tokens=8,
                                        do_sample=True, seed=2))]
        eng.run()
        assert compile_tracker.total_compiles() == before
        assert all(r.done for r in reqs)
        assert eng.spec_ticks > 0 and eng.prefill_chunks_total > 0

        rep = xray.report()
        tp = [p for p in rep["programs"]
              if p["program"].endswith("max_batch=3,block_size=16,tp=2]")]
        spec = [p for p in tp
                if p["program"].startswith("serving.spec_tick")]
        cont = [p for p in tp
                if p["program"].startswith("serving.prefill_cont")]
        # counts pinned against the engine's own accounting: one ledger
        # dispatch per spec tick + the per-rung warmup validation run;
        # one per prefill chunk + the per-bucket validation run
        assert sum(p["dispatches"] for p in spec) \
            == eng.spec_ticks + len(eng.spec_ladder)
        assert sum(p["dispatches"] for p in cont) \
            == eng.prefill_chunks_total + len(eng.pad_ladder)
        assert info["programs"] == len(tp)
        # the hot programs were sampled (MFU itself is absent on CPU)
        hot = max(spec, key=lambda p: p["dispatches"])
        assert hot["samples"] > 0 and hot["achieved_gflops_per_s"] > 0
        assert hot["mfu"] is None
        # both ROADMAP 5b suspects now run the paged Pallas kernels
        # (ISSUE 18): no custom call on this CPU build (interpret mode
        # is traced XLA), but the trace-time claims channel flips the
        # rows to kernel=True via=interpret — and the dense-gather
        # note is gone
        cov = {c["program"]: c for c in rep["kernel_coverage"]}
        for p in spec:
            row = cov[p["program"]]
            assert row["pallas"] is False
            assert row["kernel"] is True and row["via"] == "interpret"
            assert "paged_spec_verify" in row["kernels"]
            assert "note" not in row
        for p in cont:
            row = cov[p["program"]]
            assert row["pallas"] is False
            assert row["kernel"] is True and row["via"] == "interpret"
            assert "paged_chunk_prefill" in row["kernels"]
            assert "note" not in row
        assert cov[hot["program"]]["path"] == "spec verify chunk"
