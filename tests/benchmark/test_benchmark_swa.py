"""`benchmark/reducers/swa.py` by hand and on a small synthetic trace, the
window cell's files, and the helpers of its job that need no engine."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import check_manifest, run  # noqa: E402
from benchmark.reducers import mtp  # noqa: E402
from benchmark.reducers import program_spans as ps  # noqa: E402
from benchmark.reducers import swa, xplane  # noqa: E402

CELL = "serve-kexaone-mixed"
NAME = "k-exaone-236b-ep8-pp12"
CFG = run.load_json("configs", NAME + ".json")
MS = 1_000_000
TICK = {"steps": 1, "active": 48, "kv_tokens": 48 * 3000, "state_rows": 48}
NAMES = {
    "swa_attend_ms", "swa_roofline_pct", "full_attend_ms",
    "full_decode_roofline_pct", "kv_bytes_per_token", "moe_experts_ms",
    "moe_experts_roofline_pct", "mtp_draft_ms", "decode_step_device_ms",
    "chunk_device_ms", "device_idle_pct", "serve_mfu_pct",
    "weights_read_roofline_pct"}


def test_the_layers_and_bytes_are_the_issues():
    # layers 0-4 are L L L G L: four window layers; the full layer 3 and
    # the module attend the whole context
    assert swa.windowed_layers(CFG) == 4 and swa.full_layers(CFG) == 2
    assert swa.kv_position_bytes(CFG) == 2 * 8 * 128 * 2 == 4096
    # a block of 64 in the two full layers: 512 KiB
    assert 64 * swa.full_layers(CFG) * swa.kv_position_bytes(CFG) \
        == 524_288
    # a slot's rings: 4 layers x 128 positions x 4 KiB = 2 MiB
    assert swa.windowed_layers(CFG) * CFG["sliding_window"] \
        * swa.kv_position_bytes(CFG) == 2 * 2 ** 20
    # a verify forward over 48 slots: each ring read, two positions
    # written, in 4 layers; the full layers read 3,000 + 2 positions
    assert swa.ring_moved_bytes([TICK], CFG) == 48 * 4 * 4096 * 130
    assert swa.full_kv_bytes([TICK], CFG) == (48 * 3000 + 96) * 2 * 4096


def test_weight_bytes_by_hand_on_the_real_widths():
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024
    assert swa.attention_params(CFG) == attn == 113_246_208
    assert swa.expert_params(CFG) == 37_748_736
    assert swa.moe_layers(CFG) == 5                  # 4 + the module's
    dense = (6 * attn + 3 * 6144 * 18432) * 2 \
        + 5 * (6144 * 128 * 4 + 37_748_736 * 2) + 2 * 6144 * 6144 * 2 \
        + 2 * 6144 * 19200 * 2
    assert swa.dense_weight_bytes(CFG) == dense
    assert swa.forward_weight_bytes(7.5, CFG) == dense \
        + 7.5 * 37_748_736 * 2
    # the issue's 4,543M parameters (9.09 GB): the head's second read
    # stands for the embedding, the 16 experts of each of the 5 expert
    # layers join, and the router's float32 is counted as bf16 there
    whole = swa.dense_weight_bytes(CFG) + 5 * 16 * 37_748_736 * 2 \
        - 5 * 6144 * 128 * 2
    assert whole == pytest.approx(9.086e9, rel=1e-3)


def test_window_pairs_and_flops_by_hand():
    assert swa.window_pairs(1, 1, 128) == 1
    assert swa.window_pairs(1, 5000, 128) == 128
    assert swa.window_pairs(512, 512, 128) == 128 * 129 // 2 + 384 * 128
    assert swa.window_pairs(2, 130, 128) == 256
    # a token through the stage: 5 attention projections, the dense MLP,
    # 4 expert layers (router, shared, 8 x 16/128 = 1 held expert)
    per = 5 * 113_246_208 + 3 * 6144 * 18432 \
        + 4 * (6144 * 128 + 2 * 37_748_736)
    head = 6144 * 19200
    assert swa.token_flops(1, 0, 0, CFG) == 2.0 * (per + head)
    assert swa.token_flops(1, 0, 0, CFG, head_tokens=0) == 2.0 * per
    # a pair: 64 heads x 128 x (score + value) in one full layer
    assert swa.token_flops(0, 1, 0, CFG) == 4.0 * 64 * 128
    assert swa.token_flops(0, 0, 1, CFG) == 4.0 * 64 * 128 * 4
    assert swa.window_flops([TICK], 48, [], CFG) == swa.token_flops(
        48, 48 * 3000, 48 * 128, CFG)
    chunk = {"q_tokens": 512, "kv_tokens": 1024}
    assert swa.window_flops([], 0, [chunk], CFG) == swa.token_flops(
        512, 512 * 512 + 512 * 513 // 2, swa.window_pairs(512, 1024, 128),
        CFG, head_tokens=0)


def test_kv_bytes_per_token_is_the_mean_of_the_samples():
    from benchmark.jobs.serve_swa import kv_bytes_per_token
    ring = 64 * 2 * 2 ** 20
    samples = [{"full_blocks_in_use": 100, "window_ring_bytes": ring,
                "live_context_tokens": 6400},
               {"full_blocks_in_use": 0, "window_ring_bytes": ring,
                "live_context_tokens": 0},
               {"full_blocks_in_use": 3000, "window_ring_bytes": ring,
                "live_context_tokens": 190_000}]
    got = kv_bytes_per_token(samples, 524_288)
    want = ((100 * 524_288 + ring) / 6400
            + (3000 * 524_288 + ring) / 190_000) / 2
    assert got == pytest.approx(want)
    # a cache of whole blocks reads 8,192 B a position plus the rings'
    # share: the acceptance's reckoning
    full = {"full_blocks_in_use": 3000, "window_ring_bytes": ring,
            "live_context_tokens": 3000 * 64}
    assert kv_bytes_per_token([full], 524_288) == pytest.approx(
        2 * 4096 + ring / (3000 * 64))
    assert kv_bytes_per_token([samples[1]], 524_288) is None


CALL = ' = bf16[8,4,64,128]{3,2,1,0} custom-call(%a), ' \
    'custom_call_target="tpu_custom_call"'


def _trace():
    """A window of 100 ms: two launches of the tick, 20 ms each (of which
    the window layers take 2, the paged kernel 4, the experts 5), and a
    chunk of 30."""
    ops = [(0, 9 * MS, "%fusion.1 = f32[] fusion()"),
           (9 * MS, 11 * MS, "%fusion.2 = f32[] fusion()"),
           (11 * MS, 15 * MS, "%paged_chunk_prefill.3" + CALL),
           (15 * MS, 20 * MS, "%fusion.5 = f32[] fusion()"),
           (40 * MS, 49 * MS, "%fusion.1 = f32[] fusion()"),
           (49 * MS, 51 * MS, "%fusion.2 = f32[] fusion()"),
           (51 * MS, 55 * MS, "%paged_chunk_prefill.3" + CALL),
           (55 * MS, 60 * MS, "%fusion.5 = f32[] fusion()"),
           (62 * MS, 92 * MS, "%fusion.9 = f32[] fusion()")]
    modules = [(0, 20 * MS, "jit_serving_mtp_tick(3)"),
               (40 * MS, 60 * MS, "jit_serving_mtp_tick(3)"),
               (62 * MS, 92 * MS, "jit_serving_prefill_cont(5)")]
    host = [(-1 * MS, 101 * MS, xplane.WINDOW)]
    return xplane.from_events({0: {"ops": ops, "modules": modules}}, host)


def test_readers_on_a_small_synthetic_trace(monkeypatch, tmp_path):
    t = _trace()
    tick = dict(TICK, chained=1)
    spans = [(-2 * MS, -1 * MS, "serve:tick_dispatch", tick),
             (30 * MS, 31 * MS, "serve:tick_dispatch", tick),
             (22 * MS, 23 * MS, "serve:emit", {"tokens": 48}),
             (61 * MS, 62 * MS, "serve:emit", {"tokens": 40}),
             (60 * MS, 61 * MS, "serve:chunk_dispatch",
              {"q_tokens": 512, "kv_tokens": 1024})]
    inside = [x for x in spans if x[0] >= 0]
    monkeypatch.setattr(ps, "spans_with_attrs",
                        lambda trace, in_window=True:
                        inside if in_window else spans)
    monkeypatch.setattr(ps, "_peaks", lambda: (197e12, 819e9))
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(ps, "_newest_pb", lambda: str(pb))
    monkeypatch.setattr(ps, "_op_scopes", lambda path, mtime: {
        "%fusion.1 = f32[] fusion()": "jit(mtp_tick)/mtp_verify/attn_proj/dot",
        "%fusion.2 = f32[] fusion()":
            "jit(mtp_tick)/mtp_verify/swa_attend/dot",
        "%paged_chunk_prefill.3" + CALL:
            "jit(mtp_tick)/mtp_verify/gqa_attend/pallas_call",
        "%fusion.5 = f32[] fusion()":
            "jit(mtp_tick)/mtp_verify/moe_experts/gmm",
        "%fusion.9 = f32[] fusion()": "jit(prefill)/moe_experts/gmm"})
    args = {"config": NAME}
    # one verify forward's 48 slots over both launches' 4 ms of window
    # layers
    got = swa.swa_roofline_pct(t, {}, args)
    assert got == pytest.approx(
        100 * swa.ring_moved_bytes([tick], CFG) / 819e9 / 0.004)
    assert 0 < got < 100
    got = swa.full_decode_roofline_pct(
        t, {}, dict(args, pattern="paged_chunk_prefill"))
    assert got == pytest.approx(
        100 * swa.full_kv_bytes([tick], CFG) / 819e9 / 0.008)
    assert 0 < got < 100
    # the scopes a verify forward (one span in the window)
    for scope, ms in (("swa_attend", 4.0), ("gqa_attend", 8.0),
                      ("moe_experts", 10.0)):
        assert mtp.scope_ms_per_forward(t, {}, {"scope": scope}) == ms
    counters = {"moe_rows": [[[[3] * 16, [1] * 16]]] * 5,
                "decode_steps": 2}
    hits = mtp._hits_per_forward(counters)
    assert hits == 5 * 16 / 2
    per = ps.device_ms_per_step(
        t, {}, {"module": swa.MODULE, "span": "serve:tick_dispatch"})
    assert per == 20.0
    w = swa.weights_read_roofline_pct(t, counters, args)
    assert w == pytest.approx(100 * (
        swa.forward_weight_bytes(hits, CFG)
        + swa.ring_moved_bytes([tick], CFG)
        + swa.full_kv_bytes([tick], CFG)) / 819e9 / 0.020)
    assert 0 < w < 100
    mfu = swa.serve_mfu_pct(t, {}, args)
    assert mfu == pytest.approx(100 * swa.window_flops(
        [tick], 88, [spans[-1][3]], CFG) / (197e12 * t.window_s))
    assert 0 < mfu < 100


def test_readers_return_nothing_without_a_trace_or_the_spans():
    """What the parent gives (no such program, scopes or counters) and an
    untraced rehearsal: every reader returns None and raises nothing."""
    empty = xplane.from_events({}, [])
    for fn, args in (
            (swa.swa_roofline_pct, {"config": NAME}),
            (swa.full_decode_roofline_pct,
             {"config": NAME, "pattern": "paged_chunk_prefill"}),
            (swa.weights_read_roofline_pct, {"config": NAME}),
            (swa.serve_mfu_pct, {"config": NAME})):
        for trace in (None, empty):
            assert fn(trace, {}, args) is None


def test_the_held_entries_are_accepted_in_a_copy_of_the_manifest():
    """The cell's entries in `BENCHMARK.json` (its configuration, the cell,
    its place on `serve_tokens_per_s`'s list and one entry a metric file)
    are accepted against the files as they stand, at the end of their
    lists, with room left under the 128 per-layer entries a manifest may
    hold for the one `test_a_new_cell_and_metric_are_files_only` adds."""
    assert check_manifest.check_file(os.path.join(REPO, "BENCHMARK.json")) \
        == []
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert check_manifest.check(manifest, REPO) == []
    assert len(manifest["per_layer"]) < 128
    assert manifest["configs"][-1]["name"] == NAME
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-len(NAMES):]] == \
        sorted(n + ".serve_swa" for n in NAMES)
    lists = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert CELL in lists["serve_tokens_per_s"]
    assert CELL not in lists["serve_tpot_p90_ms"]
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "mixed-longtail-32k", "chips": 1,
                    "why": cell["why"]}
    cfg = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert cfg["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert cfg["source"].startswith(CFG["source"] + " ; cut: ")
    ours = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].endswith(".serve_swa")}
    assert set(ours) == {n + ".serve_swa" for n in NAMES}
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"


def test_the_jobs_metric_files_are_exactly_the_13_named():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["job"] == "serve_swa"
    files = {lm["name"]: lm for lm in run.layer_metrics_for(wl["job"], CELL)}
    assert set(files) == {n + ".serve_swa" for n in NAMES}
    for lm in files.values():
        mod, _, fn = lm["reducer"].partition(":")
        assert callable(getattr(run.load_module("reducers", mod), fn))
        assert lm["jobs"] == ["serve_swa"]
        if "config" in lm["args"]:
            assert lm["args"]["config"] == NAME
    # no accepted cell's job picks up the new files
    for other in ("serve-1p3b-chat", "serve-glm5-docqa", "serve-sdar-chat",
                  "serve-glm47f-agent", "serve-falconh1-chat"):
        job = run.load_json("workloads", other + ".json")["job"]
        assert not [lm["name"] for lm in run.layer_metrics_for(job, other)
                    if lm["name"].endswith(".serve_swa")]


@pytest.mark.parametrize("name,old", [
    ("device_idle_pct", "serve"),
    ("chunk_device_ms", "serve"), ("mtp_draft_ms", "serve_mtp"),
    ("moe_experts_ms", "serve_mtp"), ("decode_step_device_ms", "serve_mtp")])
def test_a_general_metric_is_redeclared_for_the_job_as_it_is(name, old):
    was = run.load_json("layer_metrics", f"{name}.{old}.json")
    new = run.load_json("layer_metrics", name + ".serve_swa.json")
    for key in ("layer", "unit", "better", "source", "reducer"):
        assert new[key] == was[key], key
    if name != "chunk_device_ms":
        assert new["args"] == was["args"]
    assert new["jobs"] == ["serve_swa"]


def test_the_cell_file_asks_for_no_control_and_the_engine_the_issue_gave():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["control"] == "" and wl["witness"] == "" \
        and wl["mtp_draft"] is True
    # 0.6 x the swept knee of 2.4 req/s, the rule's rate once 0.8 x spread
    # over 3%
    assert wl["rate_rps"] == 0.6 * 2.4
    assert {k: wl[k] for k in ("max_batch", "max_context", "block_size",
                               "prefill_chunk", "pad_buckets")} == {
        "max_batch": 64, "max_context": 33280, "block_size": 64,
        "prefill_chunk": 512, "pad_buckets": [128, 256, 512]}
    mix = run.load_json("traffic", "mixed-longtail-32k.json")
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 1.0,
                                    "min": 256, "max": 32768}
    assert mix["output_tokens"] == {"median": 128, "sigma": 0.6, "min": 32,
                                    "max": 384}
    assert mix["arrival_cv"] == 1.0
    job = run.load_module("jobs", "serve_swa")
    for c in job.CONTROLS:
        assert c in wl["control_note"]
    for c in job.WITNESSES:
        assert c in wl["witness_note"]
    # the limits lie between the program's largest readings and the float8
    # control's smallest on the chip (PERF.md section 4): (a) 0.595 against
    # 0.969, (b) 0.394 against 1.40
    assert 0.595 < job.GAP_TOL < 0.969
    assert 0.394 < job.LOGIT_TOL < 1.40


@pytest.mark.parametrize("key,value", [
    ("control", "fp8"), ("control", "float8+window"),
    ("witness", "float16"), ("witness", "bfloat16+float8")])
def test_an_unknown_control_or_witness_is_refused(key, value):
    job = run.load_module("jobs", "serve_swa")

    class Ctx:
        seed = 1
        workload = {"check_requests": 1, key: value}
    with pytest.raises(ValueError, match=key):
        job._check_against_reference(Ctx(), None, None, None, {}, [], [],
                                     [], [])


def test_the_longest_prompt_is_checked_first():
    import numpy as np
    job = run.load_module("jobs", "serve_swa")
    plan = [{"prompt": [1] * n} for n in (300, 20000, 1030, 600, 513)]
    got = job.pick_checked(plan, [0, 1, 2, 3, 4], 512, 3,
                           np.random.RandomState(0))
    # then those over a chunk whose final chunk is shortest: 513 (1 row),
    # 1030 (6 rows), 600 (88 rows)
    assert got == [1, 4, 2]
    assert job.pick_checked(plan, [], 512, 2, np.random.RandomState(0)) == []


def test_the_configuration_holds_the_catalogs_numbers_and_states_the_cut():
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_key_value_heads": 8,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "sliding_window": 128,
        "sliding_window_pattern": "LLLG", "num_nextn_predict_layers": 1,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "num_hidden_layers": 48, "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "mtp_layer_types": ["full_attention"], "model_type": "exaone_moe",
        "tie_word_embeddings": False}
    assert {k: CFG[k] for k in published} == published
    assert len(CFG["layer_types"]) == len(CFG["mlp_layer_types"]) == 48
    assert CFG["layer_types"][3::4] == ["full_attention"] * 12
    assert CFG["mlp_layer_types"][:2] == ["dense", "sparse"]
    assert CFG["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert CFG["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert (CFG["num_layers"], CFG["num_experts"], CFG["vocab_size"],
            CFG["router_width"]) == (5, 16, 19200, 128)
    assert CFG["published"] == {"num_layers": 48, "num_experts": 128,
                                "vocab_size": 153600}
    assert "8 chips share each layer" in CFG["deployment"]
    assert "12 pipeline stages" in CFG["deployment"]
    assert {"norms", "qk_norm", "rope", "mtp_form", "selection_bias",
            "initialisers"} <= set(CFG["assumed"])
    assert CFG["departures"] == {} and CFG["param_dtype"] == "bfloat16"


def test_the_job_builds_the_stage_the_configuration_states():
    import numpy as np
    from benchmark.jobs.serve_swa import build_model
    cfgd = run.with_rehearsal(CFG, True)
    model, cfg = build_model(cfgd, 96, 3000000011, True)
    assert (cfg.num_layers, cfg.num_experts, cfg.n_experts_held,
            cfg.expert_offset, cfg.sliding_window) == (5, 8, 2, 2, 8)
    assert [cfg.window_of(i) for i in range(6)] == [8, 8, 8, 0, 8, 0]
    spec = model.cache_spec()
    assert [r.name for r in spec.rows] == ["k", "v", "ring_k", "ring_v",
                                           "moe_rows", "mtp"]
    assert spec.rows[0].layers == (3, 5) and spec.rows[2].layers == (
        0, 1, 2, 4)
    assert set(spec.unsupported) == {"prefix_cache", "spec_decode",
                                     "draft_model", "tp_degree", "quant"}
    bias = np.asarray(model.state_dict()[
        "layers.1.mlp.gate.e_score_correction_bias"]._value)
    assert np.abs(bias).max() <= 0.1 and bias.std() > 0.01
    again, _ = build_model(cfgd, 96, 3000000011, True)
    head = np.asarray(model.state_dict()["lm_head"]._value)
    assert (np.asarray(again.state_dict()["lm_head"]._value) == head).all()
