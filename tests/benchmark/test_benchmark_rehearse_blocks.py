"""The block-diffusion serve job's CPU rehearsal; a file of its own, as the
other rehearsals (see `benchmark_rehearsal.py`)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark_rehearsal import check_rehearsal  # noqa: E402

CELL = "serve-sdar-chat"


# ~25 s of compiling on the CPU: out of the tier-1 selection for the reason
# `test_benchmark_rehearse_serve.py` gives.  tests/test_sdar_moe.py drives
# the same engine path in tier-1.
@pytest.mark.slow
def test_rehearsal_ends_with_the_contracts_line(capsys):
    check_rehearsal(CELL, capsys)


@pytest.mark.slow
@pytest.mark.parametrize("control,parts", [("float8", "(a)"),
                                           ("by_position", "(b)")])
def test_a_control_comes_out_not_correct(capsys, control, parts):
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
    assert parts in failed[0]


@pytest.mark.slow
def test_a_traced_rehearsal_reports_the_jobs_own_counters(capsys):
    """The block schedule's count from the spans, the expert layers'
    counts (taken before the profiler stops) and the latency percentiles
    reach the line of a `--trace 1` run."""
    from benchmark import run
    assert run.main(["--workload", CELL, "--seed", "3000000013",
                     "--seconds", "4", "--trace", "1", "--rehearse-cpu"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True
    assert {"forwards_per_token.serve_bd", "moe_tokens_per_expert.serve_bd",
            "tpot_p90_ms.serve_bd", "tpot_p50_ms.serve_bd",
            "ttft_p90_ms.serve_bd", "queue_wait_p90_ms.serve_bd",
            "gen_lag_p90_ms.serve_bd"} <= set(last["metrics"])
    said = next(ln for ln in out if "values on the CPU" in ln)
    fpt = float(said.split("'forwards_per_token.serve_bd': ")[1]
                .split(",")[0].rstrip("}"))
    assert 1.25 <= fpt < 2.5
