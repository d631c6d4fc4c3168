"""The manifest check that failed PR 22 before any run, as a tier-1 test:
`benchmark/check_manifest.py` passes the real `BENCHMARK.json`, refuses bad
twins with a message naming the field, and the harness finds a new cell
and a new per-layer metric that a later PR adds as files only."""

import copy
import importlib.util
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import check_manifest  # noqa: E402


def _real():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


def test_real_manifest_passes():
    assert check_manifest.check_file(
        os.path.join(REPO, "BENCHMARK.json")) == []
    m = _real()
    assert m["command"][-1] == "benchmark/run.py"
    assert sorted(m["paths"]) == ["benchmark", "tests/benchmark"]


def _layer_with_space(m):
    m["per_layer"][0]["layer"] = "traffic generator"


def _unit_of_17(m):
    m["end_to_end"][0]["unit"] = "tokens_per_second"       # 17 characters


def _moves_a_cell_lacks(m):
    # a serve-only metric said to move a train cell's layer metric
    x = _by_name(m["per_layer"], "device_idle_pct.train")
    x["moves"] = "serve_tpot_p90_ms"


def _second_four_chip_cell(m):
    for c in m["workloads"]:
        c["chips"] = 4


def _missing_workload_file(m):
    c = copy.deepcopy(m["workloads"][0])
    c.update(name="train-350m-nofile", traffic="tokens-none")
    m["workloads"].append(c)
    for x in m["end_to_end"] + m["per_layer"]:
        if m["workloads"][0]["name"] in x.get("workloads", []):
            x["workloads"].append(c["name"])


def _bound_too_wide(m):
    m["end_to_end"][0]["bound"] = 0.2


def _width_reduced(m):
    m["configs"][0]["reduced"] = ["hidden_size"]


def _no_setup(m):
    m["end_to_end"] = [x for x in m["end_to_end"] if x["name"] != "setup_s"]


def _extra_key_on_metric(m):
    m["per_layer"][0]["why"] = "because"


def _command_outside_paths(m):
    m["command"] = ["python3", "bench.py"]


def _run_seconds_too_long(m):
    m["run_seconds"] = 52


@pytest.mark.parametrize("spoil, field", [
    (_layer_with_space, "layer"),
    (_unit_of_17, "unit"),
    (_moves_a_cell_lacks, "moves"),
    (_second_four_chip_cell, "chips"),
    (_missing_workload_file, "workload file"),
    (_bound_too_wide, "bound"),
    (_width_reduced, "reduced"),
    (_no_setup, "setup_s"),
    (_extra_key_on_metric, "keys"),
    (_command_outside_paths, "command"),
    (_run_seconds_too_long, "run_seconds"),
])
def test_bad_twin_is_refused_with_the_field_named(spoil, field):
    m = _real()
    spoil(m)
    errs = check_manifest.check(m, REPO)
    assert errs, f"a manifest spoiled by {spoil.__name__} was accepted"
    assert any(field in e for e in errs), errs


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_new_cell_and_metric_are_files_only(tmp_path):
    """A later PR may add files and manifest entries and edit nothing: in
    a copy of the benchmark, a new cell (workload + traffic files), a new
    per-layer metric and a new reducer module are found by listing."""
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "benchmark").mkdir(parents=True)
    m = _real()
    old = m["workloads"][0]
    wl = json.loads((bench / "workloads" / (old["name"] + ".json"))
                    .read_text())
    wl.update(name="train-350m-new", traffic="tokens-new")
    (bench / "workloads" / "train-350m-new.json").write_text(json.dumps(wl))
    shutil.copy(bench / "traffic" / (old["traffic"] + ".json"),
                bench / "traffic" / "tokens-new.json")
    (bench / "reducers" / "fresh.py").write_text(
        "def answer(trace, counters, args):\n    return 42.0\n")
    (bench / "layer_metrics" / "fresh_count.json").write_text(json.dumps({
        "layer": "model_step", "unit": "1", "better": "higher",
        "source": "program_counter", "moves": "train_tokens_per_s",
        "jobs": [wl["job"]], "workloads": ["train-350m-new"],
        "reducer": "fresh:answer", "args": {}}))
    m["workloads"].append(dict(old, name="train-350m-new",
                               traffic="tokens-new"))
    for x in m["end_to_end"] + m["per_layer"]:
        if old["name"] in x.get("workloads", []):
            x["workloads"].append("train-350m-new")
    m["per_layer"].append({
        "name": "fresh_count", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "model_step",
        "moves": "train_tokens_per_s", "workloads": ["train-350m-new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cm = _load(str(bench / "check_manifest.py"), "copied_check_manifest")
    assert cm.check_file(str(tmp_path / "BENCHMARK.json")) == []
    run = _load(str(bench / "run.py"), "copied_run")
    found = run.layer_metrics_for(wl["job"], "train-350m-new")
    assert "fresh_count" in [lm["name"] for lm in found]
    assert "fresh_count" not in [
        lm["name"] for lm in run.layer_metrics_for(wl["job"], old["name"])]
    lm = next(x for x in found if x["name"] == "fresh_count")
    mod, _, fn = lm["reducer"].partition(":")
    assert getattr(run.load_module("reducers", mod), fn)(None, {}, {}) == 42.0
    assert run.load_module("jobs", wl["job"]).run
