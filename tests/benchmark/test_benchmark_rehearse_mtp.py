"""The self-drafting serve job's CPU rehearsal; a file of its own, as the
other rehearsals (see `benchmark_rehearsal.py`)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark_rehearsal import check_rehearsal  # noqa: E402

CELL = "serve-glm47f-agent"


def test_rehearsal_ends_with_the_contracts_line(capsys):
    check_rehearsal(CELL, capsys)


@pytest.mark.parametrize("control", ["float8", "stale_hidden"])
def test_a_control_comes_out_not_correct(capsys, control):
    """What the limits are there to catch, read through the job's own
    comparison, fails it: the run's last line says `correct: false`, by
    the control's check alone."""
    from benchmark import run
    assert run.main(["--workload", CELL, "--seed", "3000000012",
                     "--seconds", "2", "--trace", "0", "--rehearse-cpu",
                     "--set", f'control="{control}"']) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    failed = [ln for ln in lines if "check FAIL" in ln]
    assert len(failed) == 1 and f"CONTROL {control}" in failed[0]


def test_a_traced_rehearsal_reports_the_jobs_own_counters(capsys):
    """The drafter's device-side counts and the expert layers' (taken
    before the profiler stops), the spans' tokens a forward and the
    latency percentiles reach the line of a `--trace 1` run."""
    from benchmark import run
    assert run.main(["--workload", CELL, "--seed", "3000000013",
                     "--seconds", "4", "--trace", "1", "--rehearse-cpu"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True
    assert {"mtp_accept_pct.serve_mtp", "tokens_per_forward.serve_mtp",
            "moe_tokens_per_expert.serve_mtp", "tick_chained_pct.serve_mtp",
            "prefix_hit_token_pct.serve_mtp", "tpot_p90_ms.serve_mtp",
            "tpot_p50_ms.serve_mtp", "ttft_p90_ms.serve_mtp",
            "queue_wait_p90_ms.serve_mtp", "gen_lag_p90_ms.serve_mtp"} \
        <= set(last["metrics"])
    said = next(ln for ln in out if "values on the CPU" in ln)
    tpf = float(said.split("'tokens_per_forward.serve_mtp': ")[1]
                .split(",")[0].rstrip("}"))
    assert 0.5 < tpf <= 2.0
