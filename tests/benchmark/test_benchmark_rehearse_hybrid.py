"""The hybrid job's CPU rehearsal.  `train-1p3b-hybrid4` is not in the
manifest yet (no four-chip machine could be had in PR 24; see PERF.md §7),
so `run.py --workload` cannot name it: this test builds the job's context
from the cell's files the way `run.main` does and runs the job at the
sizes of their `rehearse` blocks on four virtual CPU devices.  It keeps
the files a later PR will add the cell from working."""

import os
import sys
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def test_hybrid_job_rehearses_from_its_files(capsys):
    import jax
    from benchmark import run
    cell = "train-1p3b-hybrid4"
    workload = run.with_rehearsal(
        run.load_json("workloads", cell + ".json"), True)
    config = run.with_rehearsal(
        run.load_json("configs", workload["config"] + ".json"), True)
    traffic = run.with_rehearsal(
        run.load_json("traffic", workload["traffic"] + ".json"), True)
    args = SimpleNamespace(seed=3000000011, seconds=2.0, trace=0,
                           rehearse_cpu=True)
    ctx = run.Context(args, cell, workload, config, traffic,
                      run.CompileCounter())
    ctx.devices = jax.devices()[:workload["chips"]]
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 4}
    result = run.load_module("jobs", workload["job"]).run(ctx)
    out = capsys.readouterr().out
    assert ctx.checks and all(ok for ok, _ in ctx.checks), out
    assert "equals the reference's" in out        # the full-depth check ran
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert result["metrics"]["train_tokens_per_s"] > 0
    assert ctx.setup_s is not None
    # every metric file that selects this job names a reader that exists
    names = [lm["name"] for lm in run.layer_metrics_for(workload["job"], cell)]
    assert "collective_exposed_pct.train" in names
