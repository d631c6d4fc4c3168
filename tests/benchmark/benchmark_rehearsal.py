"""What each rehearsal test checks: `benchmark/run.py --rehearse-cpu` for
one cell, in this process, is the whole harness end to end at tiny sizes on
the CPU.  The three tests live in a file each so that they spread over the
workers; this is their shared body."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_rehearsal(cell: str, capsys) -> None:
    from benchmark import run
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert run.main(["--workload", cell, "--seed", "3000000011",
                     "--seconds", "2", "--trace", "0",
                     "--rehearse-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    # the account is labelled; the last line is the contract's object
    assert all(ln.startswith("[CPU-REHEARSAL] ") for ln in lines[:-1])
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0, \
        [ln for ln in lines if "check FAIL" in ln]
    assert last["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(last["device"])
    assert last["device"]["platform"] == "cpu"
    promised = {m["name"] for m in manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) == promised and "setup_s" in promised
    for m in last["metrics"].values():
        # a CPU number is never written under a device metric's name
        assert set(m) == {"value", "unit"} and m["value"] is None
