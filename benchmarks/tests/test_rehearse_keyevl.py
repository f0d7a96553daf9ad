"""``run.py --rehearse`` of the sparse-attention cell (as ``test_rehearse.py``
walks the other cells): traced, so the program's counters have to come out
among the metrics, at a size where the selection bites (``seq`` 64, the
tiny ``topk`` 16)."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_keyevl_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "keyevl2_30b_1of8.steady", "--seed", "3300000017",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "XLA_FLAGS": ""},     # one device, as the cell has
    )
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert all(line.startswith("REHEARSAL ") for line in lines)
    records = [json.loads(line[len("REHEARSAL "):]) for line in lines]
    state = next(r for r in records if r["phase"] == "state")
    assert state["batch"] == 2 and state["seq"] == 64
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"index_low_margin_share", "moe_share_rows_over_expected",
            "moe_load_max_over_mean", "moe_rows_held_over_live", "step_ms",
            "host_step_ms"} <= set(last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "low_margin_share", "index_loss_rel_err.layer0",
        "index_loss_rel_err.layer1", "compiles_in_window",
        "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    # which attention ran, and which share of the expert layer
    assert "attention.path impl=indexed_sparse seq=64" in proc.stderr
    assert "select=threshold_by_counting" in proc.stderr
    assert "held=2 first_expert=0" in proc.stderr
    selection = [json.loads(line) for line in proc.stderr.splitlines()
                 if line.startswith('{"phase": "index_selection"')]
    assert selection and len(selection[-1]["records"][0]["index_loss"]) == 2
    rows = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith('{"phase": "moe_rows"')]
    # a ratio, the passes' extent over the rows this chip's experts took
    assert 1.0 <= rows[-1]["rows_held_over_live"] < 8.0
