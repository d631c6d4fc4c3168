"""The window-attention serve job's CPU rehearsal; a file of its own, as
the other rehearsals (see `benchmark_rehearsal.py`)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchmark_rehearsal  # noqa: E402
from benchmark import run  # noqa: E402

CELL = "serve-kexaone-mixed"


def test_rehearsal_ends_with_the_contracts_line(capsys):
    benchmark_rehearsal.check_rehearsal(CELL, capsys)


@pytest.mark.parametrize("control", ["float8", "no_window"])
def test_a_control_comes_out_not_correct(capsys, control):
    """What the limits are there to catch, read through the job's own
    comparison, fails it: the run's last line says `correct: false`, by
    the control's check alone."""
    assert run.main(["--workload", CELL, "--seed", "3000000012",
                     "--seconds", "2", "--trace", "0", "--rehearse-cpu",
                     "--set", f'control="{control}"']) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    failed = [ln for ln in lines if "check FAIL" in ln]
    assert len(failed) == 1 and f"CONTROL {control}" in failed[0]


def test_a_traced_rehearsal_reports_the_jobs_own_counters(capsys):
    """The cache's bytes a live position reach the line of a `--trace 1`
    run where a once-a-second sample found a position live (the
    rehearsal's requests last a few ticks); the rings' bytes are what the
    rehearsal's sizes give (4 window layers x 3 slots x 8 positions x 2 kv
    heads x 16 x (K, V) x 4 B).  The bfloat16 witness is read beside the
    program's readings and held to no limit."""
    assert run.main(["--workload", CELL, "--seed", "3000000013",
                     "--seconds", "4", "--trace", "1", "--rehearse-cpu",
                     "--set", 'witness="bfloat16"']) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True
    said = next(ln for ln in out if "cache once a second" in ln)
    if "kv_bytes_per_token.serve_swa" not in last["metrics"]:
        assert "None B a live position" in said
    assert f"rings {4 * 3 * 8 * 2 * 16 * 2 * 4} B" in said
    assert [ln for ln in out if "WITNESS bfloat16, not held" in ln]
    assert not [ln for ln in out if "check FAIL" in ln]
