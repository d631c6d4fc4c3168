"""CPU tests of the benchmark's own code at tiny sizes: the traffic
generator, the latency arithmetic, the peak table and FLOP functions, the
trace reducer on a small recorded trace, and the plain reference against
the models it stands beside."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import latency, peaks  # noqa: E402
from benchmark.reducers import xplane  # noqa: E402
from benchmark.traffic import openloop  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MIX = {"kind": "requests", "arrival_cv": 1.0,
       "prompt_tokens": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
       "output_tokens": {"median": 96, "sigma": 0.7, "min": 16, "max": 512}}
BIG_SEED = 3_000_000_011          # the driver's seeds pass 2**31


# ------------------------------------------------------------- traffic

def test_request_schedule_is_deterministic_in_the_seed():
    a = openloop.request_schedule(MIX, 4.0, 6.0, 30.0, BIG_SEED, 50304)
    b = openloop.request_schedule(MIX, 4.0, 6.0, 30.0, BIG_SEED, 50304)
    c = openloop.request_schedule(MIX, 4.0, 6.0, 30.0, BIG_SEED + 1, 50304)
    assert a == b
    assert a != c
    assert len(a) == 24 + 120


def test_request_lengths_stay_in_their_clips_and_arrivals_in_the_span():
    plan = openloop.request_schedule(MIX, 4.0, 6.0, 30.0, 7, 50304)
    assert all(32 <= len(p["prompt"]) <= 1024 for p in plan)
    assert all(16 <= p["max_new_tokens"] <= 512 for p in plan)
    assert all(1 <= t < 50304 for p in plan for t in p["prompt"])
    due = [p["due"] for p in plan]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 36.0
    assert sum(1 for t in due if t < 6.0) == 24        # the lead-in's own


def test_every_seed_puts_the_same_work_into_the_window():
    """The same count and the same set of sizes and gaps in the window,
    whatever the seed; only the order (and the token ids) differ."""
    def window(seed):
        plan = openloop.request_schedule(MIX, 2.7, 6.0, 40.0, seed, 50304)
        return [p for p in plan if p["due"] >= 6.0]
    a, b = window(1), window(BIG_SEED)
    assert len(a) == len(b) == 108
    sizes = lambda w: sorted(                                  # noqa: E731
        (len(p["prompt"]), p["max_new_tokens"]) for p in w)
    assert sizes(a) == sizes(b)
    assert [len(p["prompt"]) for p in a] != [len(p["prompt"]) for p in b]
    gaps = lambda w: sorted(np.round(np.diff(                   # noqa: E731
        [6.0] + [p["due"] for p in w]), 9))
    assert gaps(a) == gaps(b)


def test_shared_prefixes_are_prepended():
    mix = dict(MIX, shared_prefixes={"count": 2, "median": 64, "sigma": 0.1,
                                     "min": 48, "max": 80})
    plan = openloop.request_schedule(mix, 4.0, 0.0, 10.0, 3, 1000)
    heads = {tuple(p["prompt"][:48]) for p in plan}
    assert len(heads) == 2


def test_token_batches_shapes_labels_and_seed():
    mix = {"kind": "tokens", "batch": 2, "seq_len": 16}
    x, y = openloop.token_batches(mix, BIG_SEED, 512, 3)
    assert x.shape == y.shape == (3, 2, 16) and x.dtype == np.int32
    assert (x[..., 1:] == y[..., :-1]).all()      # labels = next token
    assert 0 <= x.min() and x.max() < 512
    x2, _ = openloop.token_batches(mix, BIG_SEED, 512, 3)
    x3, _ = openloop.token_batches(mix, BIG_SEED + 1, 512, 3)
    assert (x == x2).all() and (x != x3).any()
    xm, _ = openloop.token_batches(mix, 1, 512, 3, leading=(1,))
    assert xm.shape == (3, 1, 2, 16)


# ------------------------------------------------------------- latency

def test_percentile_is_nearest_rank():
    vs = list(range(1, 11))                       # 1..10
    assert latency.percentile(vs, 90) == 9
    assert latency.percentile(vs, 50) == 5
    assert latency.percentile(vs, 100) == 10
    assert latency.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        latency.percentile([], 90)


def test_ttft_and_tpot_by_hand():
    # due at 1.0 s, tokens at 1.2, 1.2, 1.2, 1.2 (one tick), 1.6 s
    times = [1.2, 1.2, 1.2, 1.2, 1.6]
    assert latency.ttft_ms(1.0, times, 30.0) == pytest.approx(200.0)
    assert latency.tpot_ms(times, True, 30.0) == pytest.approx(100.0)
    # a missing request counts as the window's length
    assert latency.ttft_ms(1.0, [], 30.0) == 30000.0
    assert latency.tpot_ms(times, False, 30.0) == 30000.0
    assert latency.tpot_ms([1.2], True, 30.0) == 30000.0


def test_summarize_counts_a_missing_request_as_the_window():
    ok = {"due": 0.0, "times": [0.1, 0.2, 0.3], "finished": True,
          "sent": 0.001, "admit": 0.05}
    recs = [dict(ok) for _ in range(9)] + [
        {"due": 0.0, "times": [], "finished": False, "sent": 0.002,
         "admit": None}]
    s = latency.summarize(recs, 30.0, 90.0)
    assert s["n"] == 10 and s["failed"] == 1
    assert s["ttft_ms"] == pytest.approx(100.0)       # 9 of 10 at 100 ms
    assert latency.summarize(recs, 30.0, 100.0)["ttft_ms"] == 30000.0
    assert s["tpot_ms"] == pytest.approx(100.0)
    assert s["gen_lag_ms"] == pytest.approx(1.0)
    assert s["queue_wait_ms"] == pytest.approx(50.0)


# --------------------------------------------------------------- peaks

def test_peaks_raise_on_an_unknown_device_kind():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    assert peaks.peak_bytes_per_s("TPU v5 lite") == 819e9
    for kind in ("cpu", "", None, "NVIDIA H100"):
        with pytest.raises(ValueError):
            peaks.peak_flops(kind)
        with pytest.raises(ValueError):
            peaks.peak_bytes_per_s(kind)


def test_flop_functions_equal_the_programs_today():
    from paddle_tpu.observability import flops
    for args in ((355e6,), (355e6, 24, 1024, 2048), (1.3e9, 24, 2048, 0)):
        assert peaks.training_flops_per_token(*args) == \
            flops.training_flops_per_token(*args)
    for kind in ("TPU v5 lite", "TPU v5e", "TPU v5p", "TPU v4",
                 "TPU v6 lite"):
        assert peaks.peak_flops(kind) == flops.peak_flops(kind)
    assert peaks.mfu(1000.0, 1e9, "TPU v5 lite") == \
        flops.mfu(1000.0, 1e9, "TPU v5 lite")
    assert peaks.mfu(4000.0, 1e9, "TPU v5 lite", chips=4) == \
        pytest.approx(peaks.mfu(1000.0, 1e9, "TPU v5 lite"))


def test_param_count_and_attention_flops():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    cfg = gpt3_tiny(vocab_size=512, hidden_size=64, num_heads=4,
                    max_seq_len=128)
    assert peaks.gpt_param_count(512, 64, cfg.num_layers, 128) == \
        GPTForCausalLM(cfg).num_params()
    # 6 matmuls of 2*B*nh*S*S*hd/2 a layer
    assert peaks.causal_attention_train_flops(1, 16, 2048, 64, 24) == \
        6 * 24 * 2 * 16 * 2048 * 2048 * 64 / 2


# ------------------------------------------------------------- reducer

@pytest.fixture(scope="module")
def recorded():
    """0.2 s (three steps) of train-350m-2k on a TPU v5 lite, cut from
    PR 24's first traced run; HLO names shortened, stats dropped."""
    return xplane.load(os.path.join(
        DATA, "train_350m_3steps.xplane.pb.gz"))


def test_reducer_on_the_recorded_trace(recorded):
    tr = recorded
    assert tr.window_s == pytest.approx(0.2)
    assert sorted(tr.devices) == [0]
    assert xplane.busy_s(tr) == pytest.approx(0.193232568, rel=1e-6)
    assert xplane.device_idle_pct(tr, {}, {}) == \
        pytest.approx(3.383716, rel=1e-5)
    pat = {"pattern": 'custom_call_target="tpu_custom_call"'}
    assert xplane._matching_s(tr, pat["pattern"]) == \
        pytest.approx(0.063491144, rel=1e-6)
    assert xplane.matching_time_pct(tr, {}, pat) == \
        pytest.approx(100 * 0.063491144 / 0.193232568, rel=1e-6)
    counters = {"traced_steps": 3, "peak_flops": 197e12,
                "attn_flops_per_step":
                    peaks.causal_attention_train_flops(1, 16, 2048, 64, 24)}
    assert xplane.step_device_ms(tr, counters, {}) == \
        pytest.approx(64.410856, rel=1e-6)
    assert xplane.attn_kernel_roofline_pct(tr, counters, pat) == \
        pytest.approx(14.834203, rel=1e-5)
    assert xplane.collective_exposed_pct(tr, counters, {}) is None
    top = xplane.top_ops(tr)
    assert [k for k, _ in top[:3]] == [
        "fusion", "custom-call:tpu_custom_call", "copy-done"]
    assert sum(s for _, s in top) <= xplane.busy_s(tr) * (1 + 1e-9)
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps["bench:dispatch"] == pytest.approx(0.006682576, rel=1e-5)
    assert sum(gaps.values()) == pytest.approx(
        tr.window_s - xplane.busy_s(tr), rel=1e-4)   # top ten names only
    assert xplane.module_times(tr)["jit_functional"] == \
        pytest.approx(0.193356215, rel=1e-6)


def test_readers_return_nothing_without_a_device_plane():
    empty = xplane.from_events({}, [(0.0, 10.0, xplane.WINDOW)])
    for fn, args in ((xplane.device_idle_pct, {}),
                     (xplane.step_device_ms, {}),
                     (xplane.matching_time_pct, {"pattern": "x"}),
                     (xplane.attn_kernel_roofline_pct, {"pattern": "x"}),
                     (xplane.collective_exposed_pct, {})):
        assert fn(empty, {"traced_steps": 3}, args) is None
        assert fn(None, {}, args) is None


def _op(name, code):
    return f"%{name} = f32[8]{{0:T(8)}} {code}(f32[8]{{0:T(8)}} %x)"


def test_busy_self_time_gaps_and_exposed_collectives_by_hand():
    # times in ns; the window is 0..1000
    ops = [
        (0, 400, _op("while.1", "while")),            # holds the next two
        (0, 100, _op("fusion.1", "fusion")),
        (100, 400, _op("all-gather.1", "all-gather")),
        (300, 500, _op("fusion.2", "fusion")),        # hides 300..400
        (700, 900, _op("all-reduce.1", "all-reduce")),    # fully exposed
        (900, 1000, "%k = f32[] custom-call(), "
                    'custom_call_target="tpu_custom_call"'),
    ]
    host = [(0, 1000, xplane.WINDOW), (480, 720, "bench:read_loss"),
            (0, 1000, "thread_main")]
    tr = xplane.from_events({0: {"ops": ops, "modules": []}}, host)
    assert tr.window == (0, 1000)
    assert xplane.busy_s(tr) == pytest.approx(800e-9)       # idle 500..700
    assert xplane.device_idle_pct(tr, {}, {}) == pytest.approx(20.0)
    own = dict(xplane.top_ops(tr))
    assert own["while"] == pytest.approx(0.0)
    assert own["fusion"] == pytest.approx(300e-9)
    assert own["all-gather"] == pytest.approx(300e-9)
    # all-gather 100..400 is alone for 100..300; all-reduce for 700..900
    assert xplane.collective_exposed_pct(tr, {}, {}) == pytest.approx(40.0)
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps == {"bench:read_loss": pytest.approx(200e-9)}
    assert xplane.op_kind(ops[-1][2]) == "custom-call:tpu_custom_call"


def test_opcode_of_a_tuple_typed_op():
    name = ("%while.4 = (s32[]{:T(128)}, bf16[12,129,64,64]{3,0,2,1:T(8,128)"
            "(2,1)S(1)}) while((s32[]{:T(128)}, bf16[12,129,64,64]) %t), "
            "condition=%c, body=%b")
    assert xplane.op_kind(name) == "while"


# ----------------------------------------------------------- reference

def test_reference_agrees_with_the_model_on_the_cpu():
    """Float32 on both sides here, so the two agree to rounding."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    from benchmark.reference import gpt_ref
    cfg = gpt3_tiny(vocab_size=512, hidden_size=64, num_heads=4,
                    max_seq_len=128)
    paddle.seed(5)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = np.random.RandomState(0).randint(0, 512, (2, 48)).astype(np.int32)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(ids))._value)
    params = gpt_ref.from_state_dict(
        {k: v._value for k, v in model.state_dict().items()}, cfg.num_layers)
    got = np.asarray(gpt_ref.forward(params, ids, cfg.num_heads))
    assert np.abs(got - want).max() < 2e-4
    last = np.asarray(gpt_ref.forward(params, ids, cfg.num_heads,
                                      positions=np.array([47])))
    assert np.abs(last[:, 0] - want[:, 47]).max() < 2e-4


def test_reference_agrees_with_the_hybrid_steps_serial_forward():
    import jax
    from paddle_tpu.distributed.fleet import hybrid_step as hs
    from benchmark.reference import gpt_ref
    cfg = hs.HybridConfig(vocab_size=512, hidden_size=64, num_layers=2,
                          num_heads=4, seq_len=32, pp=1, dp=1, mp=1,
                          n_microbatches=1, sequence_parallel=False)
    params = hs.init_gpt_params(jax.random.key(3), cfg)
    ids = np.random.RandomState(1).randint(0, 512, (2, 32)).astype(np.int32)
    want = float(hs.serial_forward(params, ids, cfg))
    got = float(gpt_ref.loss(gpt_ref.from_hybrid(params, cfg.num_heads),
                             ids[:, :-1], ids[:, 1:], cfg.num_heads))
    assert abs(got - want) < 1e-4
