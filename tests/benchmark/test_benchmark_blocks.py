"""`benchmark/reducers/blocks.py` by hand, the block-diffusion cell's
files, and the helpers of its job that need no engine."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.reducers import blocks as bl  # noqa: E402
from benchmark.reducers import xplane  # noqa: E402

CELL = "serve-sdar-chat"
CFG = run.load_json("configs", "sdar-30b-a3b-pp8.json")
TICK = {"steps": 5, "active": 32, "kv_tokens": 32 * 500, "block_len": 4}


def test_forwards_per_token_is_five_quarters_for_whole_blocks():
    assert bl.forwards_per_token([TICK], 32 * 4) == 1.25
    # a first block that holds 3 prompt tokens hands over one: 5 / 1
    first = dict(TICK, active=1)
    assert bl.forwards_per_token([first], 1) == 5.0
    assert bl.forwards_per_token([TICK, first], 32 * 4 + 1) == \
        pytest.approx(165 / 129)
    assert bl.forwards_per_token([], 0) is None


def test_kv_read_bytes_are_2048_a_token_a_layer_a_forward():
    # 4 kv heads x 128 x 2 B for K and as much for V: nothing is repeated
    # to the 32 query heads (that would be 16,384)
    assert 2 * 4 * 128 * 2 == 2048
    one = {"steps": 1, "active": 1, "kv_tokens": 996, "block_len": 4}
    assert bl.kv_read_bytes([one], CFG) == 1000 * 2048 * 6
    assert bl.kv_read_bytes([TICK], CFG) == 5 * (16000 + 128) * 2048 * 6


def test_weight_bytes_by_hand_on_the_real_widths():
    expert = 3 * 2048 * 768 * 2
    assert bl.expert_weight_bytes(1, CFG) == expert == 9_437_184
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512          # q, o; k, v
    layer = attn + 2048 * 128                        # + the router
    assert bl.dense_weight_bytes(CFG, head=False) == 6 * layer * 2
    head = 2048 * 151936 * 2
    assert bl.dense_weight_bytes(CFG) == 6 * layer * 2 + head
    # a forward with every expert of every layer hit: the issue's 8.1 GB
    whole = bl.dense_weight_bytes(CFG) + 6 * 128 * expert
    assert whole == pytest.approx(8.1e9, rel=0.01)
    assert whole / 819e9 == pytest.approx(9.9e-3, rel=0.01)
    # a launch of 5 forwards: 4 heads, and the commit's last layer's
    # experts are not read
    launch = bl.launch_weight_bytes(5, 128, CFG)
    assert launch == 5 * 6 * layer * 2 + 4 * head + 29 * 128 * expert


def test_forward_flops_by_hand_on_the_real_widths():
    H = 2048
    per_row = 6 * (2 * H * 4096 + 2 * H * 512 + H * 128 + 8 * 3 * H * 768)
    assert bl.forward_flops(1, 0, CFG) == 2.0 * (per_row + H * 151936)
    assert bl.forward_flops(1, 0, CFG, head_rows=0) == 2.0 * per_row
    # a (query, key) pair costs the 32 heads 128 MACs for the score and
    # 128 for the value, a layer
    assert bl.forward_flops(0, 1, CFG) == 4.0 * 32 * 128 * 6
    # a tick: 5 forwards of 128 rows, 4 of them through the head, each
    # row scoring its slot's 500 committed tokens and the block
    rows = 32 * 4
    want = bl.forward_flops(5 * rows, 5 * 4 * (16000 + rows), CFG,
                            head_rows=4 * rows)
    assert bl.window_flops([TICK], [], CFG) == want
    chunk = {"q_tokens": 512, "kv_tokens": 1024}
    assert bl.window_flops([], [chunk], CFG) == bl.forward_flops(
        512, 512 * 512 + 512 * 513 // 2, CFG, head_rows=0)
    # the matmuls of a full tick's forward: 0.85 ms at 197 TFLOP/s
    assert bl.forward_flops(rows, 0, CFG) / 197e12 == \
        pytest.approx(0.85e-3, rel=0.1)


def test_counter_readers_by_hand():
    # two layers, 10 forwards: [tick | chunk] x [rows | hits] x [held = 2]
    rows = [[[[30, 10], [9, 7]], [[5, 5], [1, 1]]],
            [[[20, 20], [10, 4]], [[0, 0], [0, 0]]]]
    c = {"moe_rows": rows, "decode_steps": 10}
    assert bl._hits_per_layer_forward(c) == (9 + 7 + 10 + 4) / 20
    assert bl._hits_per_layer_forward({}) is None
    # the accepted reader of the same layout reads the tick's rows
    from benchmark.reducers import sparse_mla
    assert sparse_mla.moe_tokens_per_expert(None, c, {}) == \
        pytest.approx((20 + 20) / 2 / 10)


def test_readers_return_nothing_without_a_trace_or_the_spans():
    """What the parent of the PR that added the spans gives, and an
    untraced rehearsal: every reader returns None and raises nothing."""
    empty = xplane.from_events({}, [])
    counters = {"moe_rows": [[[[8] * 4, [1] * 4], [[0] * 4] * 2]],
                "decode_steps": 5}
    for fn, args in (
            (bl.forwards_per_token_in_window, {}),
            (bl.scope_ms_per_forward, {"scope": "bd_reveal"}),
            (bl.moe_experts_roofline_pct, {"config": "sdar-30b-a3b-pp8"}),
            (bl.paged_block_roofline_pct, {"config": "sdar-30b-a3b-pp8",
                                           "pattern": "paged_chunk_prefill"}),
            (bl.weights_read_roofline_pct, {"config": "sdar-30b-a3b-pp8"}),
            (bl.serve_mfu_pct, {"config": "sdar-30b-a3b-pp8"})):
        for trace in (None, empty):
            assert fn(trace, {}, args) is None
            assert fn(trace, counters, args) is None


def test_the_cells_metric_files_select_it_and_name_readers_that_exist():
    wl = run.load_json("workloads", CELL + ".json")
    names = {lm["name"]: lm for lm in run.layer_metrics_for(wl["job"], CELL)}
    assert len(names) == 21 and all(n.endswith(".serve_bd") for n in names)
    for lm in names.values():
        mod, _, fn = lm["reducer"].partition(":")
        assert callable(getattr(run.load_module("reducers", mod), fn))
    assert {lm["layer"] for lm in names.values()} == {
        "block_schedule", "moe_routing", "paged_kernels", "device",
        "serving_programs", "serving_scheduler", "traffic_generator"}
    # no accepted cell's job is picked up by the new files, nor the new
    # job by theirs
    for other in ("serve-1p3b-chat", "serve-glm5-docqa"):
        job = run.load_json("workloads", other + ".json")["job"]
        assert not [lm["name"] for lm in run.layer_metrics_for(job, other)
                    if lm["name"].endswith(".serve_bd")]


@pytest.mark.parametrize("name", ["host_exposed_ms_per_tick",
                                  "chunk_device_ms", "ttft_p90_ms",
                                  "queue_wait_p90_ms", "gen_lag_p90_ms",
                                  "harvest_exposed_ms_per_tick",
                                  "copy_time_pct", "paged_kernel_time_pct"])
def test_a_general_serve_metric_is_redeclared_for_the_job_as_it_is(name):
    old = run.load_json("layer_metrics", name + ".serve.json")
    new = run.load_json("layer_metrics", name + ".serve_bd.json")
    for key in ("layer", "unit", "better", "source", "reducer", "args"):
        assert new[key] == old[key], key
    assert new["jobs"] == ["serve_blocks"] and old["jobs"] == ["serve_engine"]


def test_the_cell_reports_tokens_per_s_and_every_file_moves_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert CELL in lists["serve_tokens_per_s"]
    wl = run.load_json("workloads", CELL + ".json")
    files = run.layer_metrics_for(wl["job"], CELL)
    # TPOT spread by 9.4% at the issue's rate: read as a layer metric
    assert CELL not in lists["serve_tpot_p90_ms"]
    assert {lm["moves"] for lm in files} == {"serve_tokens_per_s"}
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell == {"name": CELL, "config": "sdar-30b-a3b-pp8",
                    "traffic": "chat-blocks-poisson", "chips": 1,
                    "why": cell["why"]}


def test_the_cell_file_asks_for_no_control_and_the_engine_the_issue_gave():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["control"] == ""
    assert wl["rate_rps"] == 3.6          # 0.8 of the knee, 4.5 req/s
    assert {k: wl[k] for k in ("max_batch", "max_context", "block_size",
                               "num_blocks", "prefill_chunk",
                               "pad_buckets")} == {
        "max_batch": 32, "max_context": 4096, "block_size": 64,
        "num_blocks": 2560, "prefill_chunk": 512,
        "pad_buckets": [128, 256, 512]}
    mix = run.load_json("traffic", "chat-blocks-poisson.json")
    assert mix["prompt_tokens"] == {"median": 384, "sigma": 0.8,
                                    "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.6,
                                    "min": 64, "max": 1024}
    assert (mix["arrival_cv"], mix["lead_in_s"], mix["block_length"],
            mix["denoising_steps"]) == (1.0, 6.0, 4, 4)


@pytest.mark.parametrize("control", ["fp8", "float8+reversed"])
def test_an_unknown_control_is_refused(control):
    job = run.load_module("jobs", "serve_blocks")

    class Eng:
        class gen:
            block_length, denoising_steps, mask_token_id = 4, 4, 255

    class Ctx:
        seed = 1
        workload = {"check_requests": 1, "control": control}
    with pytest.raises(ValueError, match="control"):
        job._check_against_reference(Ctx(), Eng(), None, None, {}, [], [],
                                     [], [])


def test_the_configuration_holds_the_catalogs_numbers_and_states_the_cut():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_layers"] and CFG["num_layers"] == 6
    assert CFG["published"] == {"num_layers": 48}
    assert 48 % CFG["num_layers"] == 0
    assert "8 pipeline stages" in CFG["deployment"]
    assert {"block_length", "denoising_steps", "noise_schedule",
            "mask_token_id", "qk_norm", "logit_shift", "prefill_mask",
            "commit_forward"} <= set(CFG["assumed"])
    assert CFG["departures"] == {}


def test_block_states_are_what_the_recorded_steps_fix():
    job = run.load_module("jobs", "serve_blocks")
    M = 99
    # a prompt of 6 (tail of 2), 5 new tokens: the first block reveals 2
    # (forwards 0 and 1), the second 3 of its 4 (the budget cut it)
    states = job.block_states([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11],
                              [1, 0, 2, 0, 3], 4, 4, M)
    assert [s[0] for s in states] == [4, 4]      # the cut block is left out
    start, toks, masked, shown, final = states[0]
    assert toks.tolist() == [5, 6, M, M] and masked.tolist() == [
        False, False, True, True]
    assert shown.tolist() == [False, False, False, True]
    assert final.tolist() == [5, 6, 7, 8]
    _, toks, masked, shown, _ = states[1]
    assert toks.tolist() == [5, 6, M, 8] and shown.tolist() == [
        False, False, True, False]
    # a whole second block: one state a forward, the masks shrinking
    states = job.block_states([1, 2, 3, 4], [5, 6, 7, 8], [2, 0, 3, 1],
                              4, 4, M)
    assert [s[1].tolist() for s in states] == [
        [M, M, M, M], [M, 6, M, M], [M, 6, M, 8], [5, 6, M, 8]]
    assert [int(np.nonzero(s[3])[0][0]) for s in states] == [1, 3, 0, 2]


def test_the_job_builds_the_stage_the_configuration_states():
    from benchmark.jobs.serve_blocks import build_model
    cfgd = run.with_rehearsal(CFG, True)
    mix = run.load_json("traffic", "chat-blocks-poisson.json")
    model, cfg = build_model(cfgd, mix, 96, 3000000011)
    assert (cfg.num_experts, cfg.n_experts_held, cfg.num_kv_heads) == (
        16, 16, 2)
    gen = model.cache_spec().generation
    assert (gen.block_length, gen.denoising_steps, gen.mask_token_id) == (
        4, 4, 255)
    sd = model.state_dict()
    assert tuple(sd["model.layers.1.mlp.experts.gate_proj"].shape) == (
        16, 64, 32)
    # N(0, initializer_range) from the seed, the same again
    w = np.asarray(sd["lm_head.weight"]._value)
    assert abs(w.std() - cfgd["initializer_range"]) < 0.01
    again, _ = build_model(cfgd, mix, 96, 3000000011)
    assert (np.asarray(again.state_dict()["lm_head.weight"]._value)
            == w).all()
