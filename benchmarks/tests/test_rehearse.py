"""``run.py --rehearse`` end to end, once for each job: the control flow of
a cell on the CPU at tiny sizes.  It may never print a result line."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.common import ROOT


def _rehearse(cell, trace, seconds):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    # never a line the driver could read as a result
    assert all(line.startswith("REHEARSAL ") for line in lines)
    return [json.loads(line[len("REHEARSAL "):]) for line in lines]


@pytest.mark.parametrize("cell,trace,seconds", [
    ("mistral7b_l2.steady", 0, 2), ("mistral7b_l2.save_mem", 1, 2),
    # the whole resume has to end inside the window, or it counts as failed
    ("gpt2m.kill_resume", 0, 60),
])
def test_rehearsal(cell, trace, seconds):
    records = _rehearse(cell, trace, seconds)
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert last["would_print"]
