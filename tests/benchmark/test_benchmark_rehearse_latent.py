"""The latent-cache serve job's CPU rehearsal; a file of its own, as the
other rehearsals (see `benchmark_rehearsal.py`)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark_rehearsal import check_rehearsal  # noqa: E402


# ~30 s of compiling on the CPU: out of the tier-1 selection for the reason
# `test_benchmark_rehearse_serve.py` gives.  tests/test_glm_moe_dsa.py
# drives the same engine path in tier-1.
@pytest.mark.slow
def test_rehearsal_ends_with_the_contracts_line(capsys):
    check_rehearsal("serve-glm5-docqa", capsys)


@pytest.mark.slow
def test_the_controls_come_out_not_correct(capsys):
    """What the limits are there to catch, read through the job's own
    comparison, fails it: the run's last line says `correct: false`, by
    the controls' checks alone."""
    import json
    from benchmark import run
    assert run.main(["--workload", "serve-glm5-docqa", "--seed", "3000000012",
                     "--seconds", "2", "--trace", "0", "--rehearse-cpu",
                     "--set", 'control="float8+topk_half"']) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    failed = [ln for ln in lines if "check FAIL" in ln]
    assert len(failed) == 2 and all("CONTROL" in ln for ln in failed)


@pytest.mark.slow
def test_a_traced_rehearsal_reports_the_jobs_own_counters(capsys):
    """The expert layers' counts (taken before the profiler stops) and the
    TPOT percentiles reach the line of a `--trace 1` run."""
    import json
    from benchmark import run
    assert run.main(["--workload", "serve-glm5-docqa", "--seed", "3000000013",
                     "--seconds", "4", "--trace", "1", "--rehearse-cpu"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert {"moe_tokens_per_expert.serve_dsa", "tpot_p90_ms.serve_dsa",
            "tpot_p50_ms.serve_dsa", "prefix_hit_token_pct.serve_dsa"} \
        <= set(last["metrics"])
