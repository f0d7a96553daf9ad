"""``run.py --rehearse`` of the expert-parallel cell on four virtual
devices (as ``test_rehearse.py`` walks the other cells): traced, so the
program's routing counter has to come out among the metrics."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_rehearsal_of_the_four_chip_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "olmoe1b7b_ep4.steady", "--seed", "3200000017",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert all(line.startswith("REHEARSAL ") for line in lines)
    records = [json.loads(line[len("REHEARSAL "):]) for line in lines]
    state = next(r for r in records if r["phase"] == "state")
    assert state["mesh"]["ep"] == 4
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"moe_load_max_over_mean", "moe_rows_held_over_live",
            "moe_chip_rows_max_over_mean"} <= set(last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "low_margin_share",
        "compiles_in_window", "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    routing = [json.loads(line) for line in proc.stderr.splitlines()
               if line.startswith('{"phase": "moe_routing"')]
    assert routing and routing[-1]["load_max_over_mean"] >= 1.0
    assert len(routing[-1]["records"][0]["by_layer"]) == 2
