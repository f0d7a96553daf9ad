"""``run.py --rehearse`` of the Phi-4-mini-flash cell (as ``test_rehearse.py``
walks the other cells): traced, so the program's counters have to come out
among the metrics, at a size with every kind of layer (eight: two periods of
a Mamba and a window layer, the pair that hands on, a gated memory unit and
a cross layer)."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_phi4flash_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "phi4miniflash_l8.steady", "--seed",
         "5700000017", "--seconds", "2", "--trace", "1", "--rehearse"],
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
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"ssm_decay_p50", "step_ms", "host_step_ms",
            "shard_batch_ms"} <= set(last["would_print"])
    # no device time on a CPU
    assert not {"ssm_scan_ms_per_step", "diff_attn_ms_per_step",
                "gmu_ms_per_step", "ssm_core_step_share_pct",
                "ssm_scan_roofline_pct", "diff_attn_roofline_pct"} & set(
                    last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "compiles_in_window", "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    # which mixers ran, on what
    assert ("attention.path impl=mamba seq=64 channels=128 state=8 conv=4 "
            "dt_rank=4 state_dtype=float32 core=jnp chunk=64") in proc.stderr
    assert ("attention.path impl=differential seq=64 heads=4 head_dim=16 "
            "exact=reference window=16") in proc.stderr
    scans = [json.loads(line) for line in proc.stderr.splitlines()
             if line.startswith('{"phase": "ssm_scan"')][-1]["records"]
    assert len(scans[0]["decay_p50"]) == 3      # a value a Mamba layer
    assert len(scans[0]["diff_lambda"]) == 4    # and a differential layer
    assert scans[0]["memory_readers"] == [2]
    seen = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith('{"phase": "reference_phi4flash"')][-1]
    assert len(seen["layers"]) == 8
