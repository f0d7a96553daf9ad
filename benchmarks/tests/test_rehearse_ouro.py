"""``run.py --rehearse`` of the Ouro cell (as ``test_rehearse.py`` walks the
other cells): traced, so the program's counter has to come out among the
metrics, at the tiny sizes (two layers, four loop steps)."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_ouro_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "ouro2b6_l8.steady", "--seed", "6100000017",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        # one device, as the cell has; the counters' cadence short enough
        # for a CPU's few steps
        env={**os.environ, "XLA_FLAGS": "", "DLROVER_TPU_DIGEST_EVERY": "2"},
    )
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert all(line.startswith("REHEARSAL ") for line in lines)
    records = [json.loads(line[len("REHEARSAL "):]) for line in lines]
    state = next(r for r in records if r["phase"] == "state")
    assert state["batch"] == 2 and state["seq"] == 64
    # a weight once: 2 layers' worth, whatever the loop steps
    assert state["params"] == 115_329
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"loop_exit_entropy", "step_ms", "host_step_ms",
            "shard_batch_ms"} <= set(last["would_print"])
    # no device time on a CPU
    assert not {"loop_remat_ms_per_step", "loop_exit_ms_per_step",
                "loop_head_ms_per_step", "full_attn_ms_per_step",
                "full_attn_roofline_pct"} & set(last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "exit_token_max_abs_err",
        "exit_p_max_abs_err", "objective_rel_err", "compiles_in_window",
        "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    exits = [json.loads(line) for line in proc.stderr.splitlines()
             if line.startswith('{"phase": "loop_exits"')][-1]
    assert exits["read_at"].startswith("window step")
    assert len(exits["loop_ce_by_step"]) == 4
    assert 0.1 < exits["loop_exit_entropy"][0] < 1.3863
    seen = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith('{"phase": "reference_exits"')][-1]
    assert len(seen["exit_mass_reference"]) == 4
    assert abs(sum(seen["exit_mass_reference"]) - 1.0) < 1e-5
