"""``run.py --rehearse`` of the EvaByte cell (as ``test_rehearse.py`` walks
the other cells): traced, so the program's counter has to come out among the
metrics, at a size where the summaries bite (``seq`` 64, four windows of 16,
chunks of 4)."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_evabyte_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "evabyte_l4.steady", "--seed", "3500000017",
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
    assert {"eva_summary_mass_share", "step_ms", "host_step_ms",
            "shard_batch_ms"} <= set(last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "multi_byte_rel_err", "compiles_in_window", "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    # which attention ran, and on what
    assert ("attention.path impl=eva seq=64 window=16 chunk=4 windows=4 "
            "summaries_max=12 heads=4 head_dim=16 exact=jnp") in proc.stderr
    counters = [json.loads(line) for line in proc.stderr.splitlines()
                if line.startswith('{"phase": "eva_attention"')]
    record = counters[-1]["records"][0]
    assert len(record["summary_mass_share"]) == 2       # a value a layer
    assert len(record["pool_weight_max"]) == 2
    assert len(record["multi_byte_loss"]) == 1
    assert 0.1 < counters[-1]["eva_summary_mass_share"] < 0.9
