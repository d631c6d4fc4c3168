"""`benchmark/reducers/sparse_mla.py` by hand, the new cell's files, and
the helpers of its job that need no engine."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.reducers import sparse_mla as sm  # noqa: E402
from benchmark.reducers import xplane  # noqa: E402

CELL = "serve-glm5-docqa"
CFG = run.load_json("configs", "glm5-ep16.json")


def test_tick_contexts_count_the_tokens_written_inside_a_tick():
    # one tick of 4 steps over 2 slots holding 100 tokens: steps see
    # 102, 104, 106, 108 cached tokens (each slot's own new token counts)
    assert sm.tick_contexts([{"steps": 4, "active": 2,
                              "kv_tokens": 100}]) == 102 + 104 + 106 + 108


def test_index_key_bytes_are_256_a_token_a_layer_a_step():
    ticks = [{"steps": 1, "active": 1, "kv_tokens": 999}]
    assert sm.index_key_bytes(ticks, CFG) == 1000 * 128 * 2 * 5
    assert 128 * 2 == 256


def test_selected_row_bytes_are_1152_a_row_a_layer_a_step():
    ticks = [{"steps": 4, "active": 2, "kv_tokens": 50000,
              "selected_tokens": 2 * 2048}]
    assert sm.selected_row_bytes(ticks, CFG) == 4 * 4096 * 1152 * 5
    assert (512 + 64) * 2 == 1152


def test_expert_weight_bytes_are_three_matrices_an_expert_hit():
    assert sm.expert_weight_bytes(1, CFG) == 3 * 6144 * 2048 * 2
    assert sm.expert_weight_bytes(6.5, CFG) == 6.5 * 75497472


def test_step_flops_by_hand_on_the_real_widths():
    H = 6144
    attn = (H * 2048 + 2048 * 64 * 256 + H * 576 + 512 * 64 * 448
            + 64 * 256 * H)
    assert attn == 165_019_648                     # the issue's 165.0M
    index = 2048 * 32 * 128 + H * 128 + H * 32     # 9.4M
    expert = 3 * H * 2048                          # 37.7M
    per_token = (5 * (attn + index) + 3 * H * 12288
                 + 4 * (H * 256 + expert + expert * 8 * 16 / 256)
                 + H * 19360)
    one = sm.step_flops(1, 0, 0, CFG)
    assert one == pytest.approx(2.0 * per_token)
    # a context token costs the indexer 32 x 128 MACs a layer; a selected
    # row costs the 64 heads 576 (score) + 512 (value) MACs a layer
    assert sm.step_flops(0, 1, 0, CFG) == 2.0 * 5 * 32 * 128
    assert sm.step_flops(0, 0, 1, CFG) == 2.0 * 5 * 64 * (576 + 512)


def test_counter_readers_by_hand():
    # two MoE layers and the dense one (all zero), 10 decode steps
    rows = [[[[0, 0], [0, 0]], [[0, 0], [0, 0]]],
            [[[30, 10], [2, 1]], [[0, 0], [0, 0]]],
            [[[20, 20], [2, 2]], [[0, 0], [0, 0]]]]
    c = {"moe_rows": rows, "decode_steps": 10}
    # mean over MoE layers of (rows / experts) a step: (20 + 20) / 2 / 10
    assert sm.moe_tokens_per_expert(None, c, {}) == pytest.approx(2.0)
    assert sm.moe_tokens_per_expert(None, {}, {}) is None


def test_readers_return_nothing_without_a_trace_or_the_spans():
    empty = xplane.from_events({}, [])
    for fn, args in ((sm.scope_ms_per_decode_step, {"scope": "dsa_index"}),
                     (sm.dsa_index_roofline_pct, {"config": "glm5-ep16"}),
                     (sm.mla_sparse_roofline_pct, {"config": "glm5-ep16"}),
                     (sm.moe_experts_roofline_pct, {"config": "glm5-ep16"}),
                     (sm.serve_mfu_pct, {"config": "glm5-ep16"})):
        assert fn(None, {}, args) is None
        assert fn(empty, {}, args) is None


def test_the_cells_metric_files_select_it_and_name_readers_that_exist():
    wl = run.load_json("workloads", CELL + ".json")
    names = {lm["name"]: lm for lm in run.layer_metrics_for(wl["job"], CELL)}
    assert len(names) == 21 and all(n.endswith(".serve_dsa") for n in names)
    for lm in names.values():
        mod, _, fn = lm["reducer"].partition(":")
        assert callable(getattr(run.load_module("reducers", mod), fn))
    # and none of the accepted cells' files picks the new job up
    assert not [n for n in names if not n.endswith(".serve_dsa")]


@pytest.mark.parametrize("name", ["copy_time_pct",
                                  "host_exposed_ms_per_tick",
                                  "harvest_exposed_ms_per_tick"])
def test_a_general_serve_metric_is_redeclared_for_the_job_as_it_is(name):
    """The same reader over the same arguments as the accepted `.serve`
    file; the job it selects differs, and the end-to-end metric it is
    tied to is the one this job's cell reports."""
    old = run.load_json("layer_metrics", name + ".serve.json")
    new = run.load_json("layer_metrics", name + ".serve_dsa.json")
    for key in ("layer", "unit", "better", "source", "reducer", "args"):
        assert new[key] == old[key], key
    assert new["jobs"] == ["serve_latent"] and old["jobs"] == ["serve_engine"]


def test_the_cell_is_held_to_tokens_per_s_and_reads_tpot_as_a_layer_metric():
    """TPOT p90 spread too widely over this cell's seeds to be held to a
    bound (PERF.md section 2): the cell is not on its list, every metric
    of the job is tied to the end-to-end metric the cell does report, and
    the TPOT percentiles are read as per-layer metrics from the job's own
    counters."""
    import json
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert CELL not in lists["serve_tpot_p90_ms"]
    assert CELL in lists["serve_tokens_per_s"]
    wl = run.load_json("workloads", CELL + ".json")
    files = run.layer_metrics_for(wl["job"], CELL)
    assert {lm["moves"] for lm in files} == {"serve_tokens_per_s"}
    by_name = {lm["name"]: lm for lm in files}
    for q in ("p90", "p50"):
        lm = by_name[f"tpot_{q}_ms.serve_dsa"]
        assert lm["reducer"] == "engine_counts:counter"
        assert lm["args"] == {"key": f"tpot_{q}_ms"}


def test_agreement_is_the_share_of_the_references_selection():
    job = run.load_module("jobs", "serve_latent")
    want = [np.asarray([[0, 1, 2, 3], [5, 6, -1, -1]])]      # one layer
    assert job._agreement(want, want) == [1.0, 1.0]
    got = [np.asarray([[0, 1, 9, 8], [6, -1, -1, -1]])]
    assert job._agreement(got, want) == [0.5, 0.5]
    # a selection of half as many, all of them the reference's: a half
    assert job._agreement([np.asarray([[2, 0], [5, -1]])], want) \
        == [0.5, 0.5]


@pytest.mark.parametrize("control", ["fp8", "float8+half"])
def test_an_unknown_control_is_refused(control):
    job = run.load_module("jobs", "serve_latent")

    class Ctx:
        seed = 1
        workload = {"check_requests": 1, "control": control}
    with pytest.raises(ValueError, match="control"):
        job._check_against_reference(Ctx(), None, None, None, {}, [], [], [],
                                     [], [], None)


def test_the_cell_file_asks_for_no_control():
    wl = run.load_json("workloads", CELL + ".json")
    assert wl["control"] == "" and wl["check_requests"] == 1
    assert set(wl["control_note"].split("'\"")[1].split("\"'")[0]
               .split("+")) == set(
        run.load_module("jobs", "serve_latent").CONTROLS)


def test_the_configuration_holds_the_catalogs_numbers_and_states_the_cut():
    published = {"hidden_size": 6144, "num_attention_heads": 64,
                 "q_lora_rank": 2048, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
                 "v_head_dim": 256, "index_n_heads": 32,
                 "index_head_dim": 128, "index_topk": 2048,
                 "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
                 "intermediate_size": 12288, "num_hidden_layers": 78,
                 "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05}
    assert {k: CFG[k] for k in published} == published
    assert sorted(CFG["reduced"]) == sorted(
        ["num_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size", "num_nextn_predict_layers"])
    assert set(CFG["published"]) == set(CFG["reduced"])
    assert CFG["router_width"] == CFG["published"]["n_routed_experts"] == 256
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert "16 chips share each layer" in CFG["deployment"]
    assert set(CFG["departures"]) == {"mtp", "indexer_fp8_hadamard"}
    assert CFG["assumed"]


def test_shared_documents_are_the_longest_common_prefixes():
    from benchmark.jobs.serve_latent import shared_documents
    from benchmark.traffic import openloop
    mix = run.with_rehearsal(
        run.load_json("traffic", "docqa-shared-24k.json"), True)
    plan = openloop.request_schedule(mix, 6.0, 1.0, 4.0, 3000000011, 256)
    docs = shared_documents(plan, 8)
    assert 1 <= len(docs) <= mix["shared_prefixes"]["count"]
    lo, hi = (mix["shared_prefixes"][k] for k in ("min", "max"))
    for d in docs:
        assert lo <= len(d) <= hi + 2        # a chance equal token or two
        assert sum(p["prompt"][:len(d)] == d for p in plan) >= 2
    assert [len(d) for d in docs] == sorted(map(len, docs), reverse=True)


def test_the_job_builds_the_share_the_configuration_states():
    from benchmark.jobs.serve_latent import build_model
    cfgd = run.with_rehearsal(CFG, True)
    model, cfg = build_model(cfgd, 96, 3000000011)
    assert (cfg.n_routed_experts, cfg.n_experts_held, cfg.expert_offset) \
        == (8, 2, 4)
    sd = model.state_dict()
    assert sd["model.layers.1.mlp.experts.gate_proj"].shape[0] == 2
    assert sd["model.layers.1.mlp.gate.weight"].shape == [64, 8] \
        or tuple(sd["model.layers.1.mlp.gate.weight"].shape) == (64, 8)
    assert "model.layers.0.mlp.gate_proj.weight" in sd      # the dense one
    bias = sd["model.layers.1.mlp.gate.e_score_correction_bias"]._value
    assert float(abs(bias).max()) > 0


def test_own_times_keep_the_ops_inside_the_modules_launches(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    ops = [(0, 10, "a"), (2, 6, "b"),            # b nests in a: a owns 6
           (20, 30, "outside"),                  # under another module
           (40, 50, "c")]
    mods = [(0, 12, "jit_serving_tick(1)"), (18, 32, "jit_other(2)"),
            (39, 51, "jit_serving_tick(3)")]
    trace = xplane.from_events({0: {"ops": ops, "modules": mods}},
                               [(0, 60, xplane.WINDOW)])
    got = dict(sm._own_times(trace, "jit_serving_tick", str(path)))
    assert got == {"a": 6, "b": 4, "c": 10}
