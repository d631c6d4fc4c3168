"""`benchmark/reducers/mtp.py` by hand and on a small synthetic trace, the
self-drafting cell's files, and the helpers of its job that need no
engine."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import check_manifest, run  # noqa: E402
from benchmark.reducers import mtp  # noqa: E402
from benchmark.reducers import program_spans as ps  # noqa: E402
from benchmark.reducers import sparse_mla, xplane  # noqa: E402

CELL = "serve-glm47f-agent"
NAME = "glm47-flash-pp8"
CFG = run.load_json("configs", NAME + ".json")
MS = 1_000_000
TICK = {"steps": 1, "active": 16, "kv_tokens": 16 * 12500}
NAMES = {
    "mtp_accept_pct", "tokens_per_forward", "mtp_draft_ms", "mla_attend_ms",
    "mla_dense_roofline_pct", "moe_experts_ms", "moe_tokens_per_expert",
    "moe_experts_roofline_pct", "weights_read_roofline_pct", "serve_mfu_pct",
    "decode_step_device_ms", "chunk_device_ms", "device_idle_pct",
    "copy_time_pct", "host_exposed_ms_per_tick",
    "harvest_exposed_ms_per_tick", "tick_chained_pct",
    "prefix_hit_token_pct", "queue_wait_p90_ms", "ttft_p90_ms",
    "tpot_p50_ms", "tpot_p90_ms", "gen_lag_p90_ms"}


@pytest.mark.parametrize("drafted,accepted,want", [
    (200, 0, 0.0), (200, 200, 100.0), (200, 50, 25.0), (0, 0, None)])
def test_acceptance_of_none_all_and_some(drafted, accepted, want):
    assert mtp.accept_pct(drafted, accepted) == want
    got = mtp.mtp_accept_pct(None, {"mtp": [drafted, accepted]}, {})
    assert got == want
    # tokens a forward: one, plus one for each accepted draft
    ticks = [dict(TICK, active=drafted)]
    if drafted:
        assert mtp.tokens_per_forward(ticks, drafted + accepted) == \
            1 + accepted / drafted
    assert mtp.tokens_per_forward([], 5) is None


def test_latent_row_bytes_are_1152_a_row_a_block_a_forward():
    assert (512 + 64) * 2 == 1152 and mtp.layers_of(CFG) == 7
    one = {"steps": 1, "active": 1, "kv_tokens": 998}
    assert mtp.latent_row_bytes([one], CFG) == 1000 * 1152 * 7
    # the issue's reckoning: 16 contexts of 12.5k are 1.6 GB of rows (its
    # 1.8 GB counts the 640 lanes kept), 2 ms at 819 GB/s
    assert mtp.latent_row_bytes([TICK], CFG) == pytest.approx(1.61e9,
                                                              rel=0.01)


def test_weight_bytes_by_hand_on_the_real_widths():
    attn = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
            + 20 * 256 * 2048)
    assert mtp.attention_params(CFG) == attn == 21_757_952
    expert = 3 * 2048 * 1536
    assert mtp.expert_params(CFG) == expert == 9_437_184
    head = 2048 * 154880
    dense = (7 * attn + 3 * 2048 * 10240 + 6 * (2048 * 64 + expert)
             + 2 * 2048 * 2048 + 2 * head) * 2
    assert mtp.dense_weight_bytes(CFG) == dense
    # every expert of every block hit: all weights but the embedding, the
    # head a second time: the issue's 8.45 GB + 0.63
    whole = mtp.forward_weight_bytes(6 * 64, CFG)
    assert whole == dense + 6 * 64 * expert * 2
    assert whole == pytest.approx(8.45e9 + head * 2, rel=0.01)


def test_token_flops_by_hand_on_the_real_widths():
    H = 2048
    per = (6 * mtp.attention_params(CFG) + 3 * H * 10240
           + 5 * (H * 64 + 5 * mtp.expert_params(CFG)))
    assert mtp.token_flops(1, 0, CFG) == 2.0 * (per + H * 154880)
    assert mtp.token_flops(1, 0, CFG, head_tokens=0) == 2.0 * per
    # a (query, row) pair: 20 heads x (576 for the score + 512 for the
    # value) MACs a layer
    assert mtp.token_flops(0, 1, CFG) == 2.0 * 20 * 1088 * 6
    # 16 tokens emitted at a mean context of 12,500; a chunk of 256 after
    # 12,288 rows (no head)
    assert mtp.window_flops([TICK], 16, [], CFG) == mtp.token_flops(
        16, 16 * 12500, CFG)
    chunk = {"q_tokens": 256, "kv_tokens": 12288 + 256}
    assert mtp.window_flops([], 0, [chunk], CFG) == mtp.token_flops(
        256, 256 * 12288 + 256 * 257 // 2, CFG, head_tokens=0)


CALL = ' = f32[24,48,512]{2,1,0} custom-call(%a), ' \
    'custom_call_target="tpu_custom_call"'


def _trace():
    """A window of 100 ms: two launches of the tick, 20 ms each, of which
    the latent kernel takes 4; a chunk of 30."""
    ops = [(0, 16 * MS, "%fusion.1 = f32[] fusion()"),
           (16 * MS, 20 * MS, "%paged_latent_attention.3" + CALL),
           (40 * MS, 56 * MS, "%fusion.2 = f32[] fusion()"),
           (56 * MS, 60 * MS, "%paged_latent_attention.3" + CALL),
           (60 * MS, 90 * MS, "%fusion.9 = f32[] fusion()")]
    modules = [(0, 20 * MS, "jit_serving_mtp_tick(3)"),
               (40 * MS, 60 * MS, "jit_serving_mtp_tick(3)"),
               (60 * MS, 90 * MS, "jit_serving_prefill_cont(5)")]
    host = [(-1 * MS, 101 * MS, xplane.WINDOW)]
    return xplane.from_events({0: {"ops": ops, "modules": modules}}, host)


def test_readers_on_a_small_synthetic_trace(monkeypatch, tmp_path):
    t = _trace()
    tick = {"steps": 1, "active": 16, "kv_tokens": 200000, "chained": 1}
    spans = [(-2 * MS, -1 * MS, "serve:tick_dispatch", tick),
             (30 * MS, 31 * MS, "serve:tick_dispatch", tick),
             (22 * MS, 23 * MS, "serve:emit", {"tokens": 16}),
             (62 * MS, 63 * MS, "serve:emit", {"tokens": 20}),
             (58 * MS, 59 * MS, "serve:chunk_dispatch",
              {"q_tokens": 256, "kv_tokens": 12544})]
    inside = [x for x in spans if x[0] >= 0]
    monkeypatch.setattr(ps, "spans_with_attrs",
                        lambda trace, in_window=True:
                        inside if in_window else spans)
    monkeypatch.setattr(ps, "_peaks", lambda: (197e12, 819e9))
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(ps, "_newest_pb", lambda: str(pb))
    monkeypatch.setattr(ps, "_op_scopes", lambda path, mtime: {
        "%fusion.1 = f32[] fusion()":
            "jit(mtp_tick)/mtp_verify/moe_experts/dot",
        "%fusion.2 = f32[] fusion()":
            "jit(mtp_tick)/mtp_draft/moe_experts/dot",
        "%paged_latent_attention.3" + CALL:
            "jit(mtp_tick)/mtp_verify/mla_attend/pallas_call"})
    # one tick span in the window, 36 tokens emitted over its 16 slots
    assert mtp.tokens_per_forward_in_window(t, {}, {}) == 36 / 16
    # scopes: own time over the window's forwards (one span in it)
    assert mtp.scope_ms_per_forward(t, {}, {"scope": "moe_experts"}) == 32.0
    assert mtp.scope_ms_per_forward(t, {}, {"scope": "mtp_draft"}) == 16.0
    assert mtp.scope_ms_per_forward(t, {}, {"scope": "mla_attend"}) == 8.0
    assert mtp.scope_ms_per_forward(t, {}, {"scope": "dsa_index"}) is None
    # the latent kernel: one tick's rows over both launches' 8 ms
    args = {"config": NAME, "pattern": "paged_latent_attention"}
    least = mtp.latent_row_bytes([tick], CFG) / 819e9
    got = mtp.mla_dense_roofline_pct(t, {}, args)
    assert got == pytest.approx(100 * least / 0.008)
    # device-side counts: 2 forwards, 56 experts hit a block and forward
    rows = [[[[0] * 64, [0] * 64], [[0] * 64] * 2]] + [
        [[[2] * 64, [1] * 56 + [0] * 8], [[0] * 64] * 2]] * 6
    c = {"moe_rows": rows, "decode_steps": 2, "mtp": [32, 8]}
    assert mtp._hits_per_forward(c) == 6 * 56 / 2
    assert mtp.mtp_accept_pct(t, c, {}) == 25.0
    assert sparse_mla.moe_tokens_per_expert(t, c, {}) == pytest.approx(1.0)
    per = ps.device_ms_per_step(
        t, {}, {"module": mtp.MODULE, "span": "serve:tick_dispatch"})
    assert per == 20.0
    w = mtp.weights_read_roofline_pct(t, c, {"config": NAME})
    assert w == pytest.approx(
        100 * mtp.forward_weight_bytes(168, CFG) / 819e9 / 0.020)
    assert 0 < w < 100
    e = mtp.moe_experts_roofline_pct(t, c, {"config": NAME})
    assert e == pytest.approx(
        100 * 168 * mtp.expert_params(CFG) * 2 / 819e9 / 0.032)
    mfu = mtp.serve_mfu_pct(t, c, {"config": NAME})
    assert mfu == pytest.approx(100 * mtp.window_flops(
        [tick], 36, [spans[-1][3]], CFG) / (197e12 * t.window_s))
    assert 0 < mfu < 100


def test_a_roofline_cannot_pass_100_on_what_the_counts_say_ran():
    """Bytes and FLOPs are counted from the spans and the device-side
    counts, never from `max_batch`: at the byte floor of what ran, a
    share reads exactly 100."""
    hits = 300.0
    floor_ms = mtp.forward_weight_bytes(hits, CFG) / 819e9 * 1e3
    assert floor_ms == pytest.approx(9.6, rel=0.05)
    # a launch at its floor reads 100; one no faster than the chip can
    # read cannot read more
    for took in (floor_ms, 2 * floor_ms, 17.0, 24.0):
        share = 100.0 * floor_ms / took
        assert share <= 100.0
    # fewer experts hit, fewer bytes: the share does not stay up
    assert mtp.forward_weight_bytes(100, CFG) < mtp.forward_weight_bytes(
        300, CFG) < mtp.forward_weight_bytes(6 * 64, CFG)


def test_readers_return_nothing_without_a_trace_or_the_spans():
    """What the parent of the PR that added the spans gives, and an
    untraced rehearsal: every reader returns None and raises nothing."""
    empty = xplane.from_events({}, [])
    counters = {"moe_rows": [[[[8] * 4, [1] * 4], [[0] * 4] * 2]],
                "decode_steps": 5}
    for fn, args in (
            (mtp.tokens_per_forward_in_window, {}),
            (mtp.scope_ms_per_forward, {"scope": "mtp_draft"}),
            (mtp.mla_dense_roofline_pct,
             {"config": NAME, "pattern": "paged_latent_attention"}),
            (mtp.moe_experts_roofline_pct, {"config": NAME}),
            (mtp.weights_read_roofline_pct, {"config": NAME}),
            (mtp.serve_mfu_pct, {"config": NAME})):
        for trace in (None, empty):
            assert fn(trace, {}, args) is None
            assert fn(trace, counters, args) is None
    assert mtp.mtp_accept_pct(None, {}, {}) is None
    assert sparse_mla.moe_tokens_per_expert(None, {}, {}) is None
    assert sparse_mla.moe_tokens_per_expert(None, counters, {}) is not None


def test_the_manifest_is_accepted_with_the_cell_in_it():
    assert check_manifest.check_file(os.path.join(REPO, "BENCHMARK.json")) \
        == []
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert CELL in lists["serve_tokens_per_s"]
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "agent-shared-12k", "chips": 1,
                    "why": cell["why"]}
    cfg = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert cfg["reduced"] == ["num_layers"]
    assert cfg["source"].startswith(CFG["source"] + " ; cut: ")
    ours = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].endswith(".serve_mtp")}
    assert set(ours) == {n + ".serve_mtp" for n in NAMES}
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"


def test_the_jobs_metric_files_are_exactly_the_23_named():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["job"] == "serve_mtp"
    files = {lm["name"]: lm for lm in run.layer_metrics_for(wl["job"], CELL)}
    assert set(files) == {n + ".serve_mtp" for n in NAMES}
    assert len(files) == 23
    for lm in files.values():
        mod, _, fn = lm["reducer"].partition(":")
        assert callable(getattr(run.load_module("reducers", mod), fn))
        assert lm["jobs"] == ["serve_mtp"]
    # no accepted cell's job is picked up by the new files, nor the new
    # job by theirs
    for other in ("serve-1p3b-chat", "serve-glm5-docqa", "serve-sdar-chat"):
        job = run.load_json("workloads", other + ".json")["job"]
        assert not [lm["name"] for lm in run.layer_metrics_for(job, other)
                    if lm["name"].endswith(".serve_mtp")]


@pytest.mark.parametrize("name,old", [
    ("host_exposed_ms_per_tick", "serve"), ("chunk_device_ms", "serve"),
    ("ttft_p90_ms", "serve"), ("queue_wait_p90_ms", "serve"),
    ("gen_lag_p90_ms", "serve"), ("harvest_exposed_ms_per_tick", "serve"),
    ("copy_time_pct", "serve"), ("device_idle_pct", "serve"),
    ("tick_chained_pct", "serve"), ("prefix_hit_token_pct", "serve_dsa"),
    ("tpot_p50_ms", "serve_dsa"), ("tpot_p90_ms", "serve_dsa")])
def test_a_general_serve_metric_is_redeclared_for_the_job_as_it_is(name,
                                                                   old):
    was = run.load_json("layer_metrics", f"{name}.{old}.json")
    new = run.load_json("layer_metrics", name + ".serve_mtp.json")
    for key in ("layer", "unit", "better", "source", "reducer", "args"):
        assert new[key] == was[key], key
    assert new["jobs"] == ["serve_mtp"]


def test_the_cell_file_asks_for_no_control_and_the_engine_the_issue_gave():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["control"] == "" and wl["mtp_draft"] is True
    assert {k: wl[k] for k in ("max_batch", "max_context", "block_size",
                               "num_blocks", "prefill_chunk",
                               "pad_buckets")} == {
        "max_batch": 24, "max_context": 18432, "block_size": 64,
        "num_blocks": 4096, "prefill_chunk": 512,
        "pad_buckets": [128, 256, 512]}
    mix = run.load_json("traffic", "agent-shared-12k.json")
    assert mix["shared_prefixes"] == {"count": 8, "median": 12288,
                                      "sigma": 0.25, "min": 8192,
                                      "max": 16384}
    assert mix["prompt_tokens"] == {"median": 256, "sigma": 0.7,
                                    "min": 64, "max": 1024}
    assert mix["output_tokens"] == {"median": 96, "sigma": 0.5,
                                    "min": 32, "max": 256}
    assert (mix["arrival_cv"], mix["lead_in_s"], mix["drain_s"]) == (
        1.0, 6.0, 60.0)
    job = run.load_module("jobs", "serve_mtp")
    for c in job.CONTROLS:
        assert c in wl["control_note"]


@pytest.mark.parametrize("control", ["fp8", "float8+stale"])
def test_an_unknown_control_is_refused(control):
    job = run.load_module("jobs", "serve_mtp")

    class Ctx:
        seed = 1
        workload = {"check_requests": 1, "control": control}
    with pytest.raises(ValueError, match="control"):
        job._check_against_reference(Ctx(), None, None, None, {}, [], [],
                                     [], [], [], False)


def test_the_configuration_holds_the_catalogs_numbers_and_states_the_cut():
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_layers"] and CFG["num_layers"] == 6
    assert CFG["published"] == {"num_layers": 47}
    assert "8 pipeline stages" in CFG["deployment"]
    assert {"mtp_form", "rope", "softmax_scale", "selection_bias",
            "initialisers"} <= set(CFG["assumed"])
    assert CFG["departures"] == {} and CFG["param_dtype"] == "bfloat16"


def test_the_accounts_of_a_request_are_exact_integers():
    job = run.load_module("jobs", "serve_mtp")

    class Req:
        output_ids = [5, 6, 7, 8, 9]
        draft_log = [(6, True, 2), (1, False, 1), (9, True, 1)]
    assert job._accounts(Req) is True
    Req.draft_log = [(6, True, 2), (1, False, 1)]          # a token short
    assert job._accounts(Req) is False
    Req.draft_log = [(6, True, 2), (8, False, 1), (9, True, 1)]
    assert job._accounts(Req) is False      # 8 was the token: not "rejected"
    Req.draft_log = [(6, False, 2), (1, False, 1), (9, True, 1)]
    assert job._accounts(Req) is False      # two tokens need an accepted one


def test_the_job_builds_the_stage_the_configuration_states():
    import numpy as np
    from benchmark.jobs.serve_mtp import build_model
    cfgd = run.with_rehearsal(CFG, True)
    model, cfg = build_model(cfgd, 96, 3000000011, True)
    assert (cfg.n_routed_experts, cfg.n_experts_held, cfg.num_layers) == (
        8, 8, 3)
    spec = model.cache_spec()
    assert spec.num_layers == 4 and spec.generation.depth == 1
    assert [r.name for r in spec.rows] == ["ckv", "moe_rows", "mtp"]
    sd = model.state_dict()
    assert tuple(sd["mtp.eh_proj.weight"].shape) == (128, 64)
    assert tuple(sd["mtp.block.mlp.experts.gate_proj"].shape) == (8, 64, 32)
    w = np.asarray(sd["lm_head.weight"]._value)
    again, _ = build_model(cfgd, 96, 3000000011, False)
    assert (np.asarray(again.state_dict()["lm_head.weight"]._value)
            == w).all()
    assert again.cache_spec().generation is None
