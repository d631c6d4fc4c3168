"""The serve job's CPU rehearsal; a file of its own so the three rehearsals
spread over the workers (see `benchmark_rehearsal.py`)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark_rehearsal import check_rehearsal  # noqa: E402


# 13-18 s of compiling on the CPU: kept out of the tier-1 selection, whose
# timing-sensitive tests (test_bench_regression's overhead ratios) fail when
# heavy neighbours share the cores.  The hybrid job's rehearsal, the
# lightest, stays in tier-1 and drives the same harness.
@pytest.mark.slow
def test_rehearsal_ends_with_the_contracts_line(capsys):
    check_rehearsal("serve-1p3b-chat", capsys)
