"""``run.py --rehearse`` of the SDAR cell (as ``test_rehearse.py`` walks the
other cells): traced, so the program's counters have to come out among the
metrics, at a size with sixteen blocks of four a sequence and a share of
the experts."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_sdar_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "sdar_30b_1of8.steady", "--seed", "4100000017",
         "--seconds", "4", "--trace", "1", "--rehearse"],
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
    window = next(r for r in records if r["phase"] == "window")
    assert window["tokens_per_step"] == 128         # DATA tokens, not rows
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"bd_masked_share", "moe_load_max_over_mean",
            "moe_rows_held_over_live", "moe_share_rows_over_expected",
            "step_ms", "host_step_ms", "shard_batch_ms"} <= set(
                last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "objective_rel_err", "masked_median_abs_err", "low_margin_share",
        "compiles_in_window",
        "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    # which attention ran, and on what: one line a compiled program
    assert ("attention.path impl=block_diffusion seq=64 rows=128 block=4 "
            "query_block=64 pairs=4352 heads=4 head_dim=16 exact=jnp"
            ) in proc.stderr
    # the routed block sees the rows of both copies
    assert "moe.path impl=ragged_dot experts=8 top_k=3 ep=1 tokens=256" in (
        proc.stderr)
    assert "held=2 first_expert=0" in proc.stderr
    counters = [json.loads(line) for line in proc.stderr.splitlines()
                if line.startswith('{"phase": "block_diffusion"')]
    assert 0.35 < counters[-1]["bd_masked_share"] < 0.65
    record = counters[-1]["records"][0]
    assert len(record["masked_share"]) == 1 and record["weight_max"][0] >= 1
    # the noise differs by step
    shares = [r["masked_share"][0] for r in counters[-1]["records"]]
    assert len(set(shares)) > 1
