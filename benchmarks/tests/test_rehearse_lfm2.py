"""``run.py --rehearse`` of the LFM2 cell (as ``test_rehearse.py`` walks the
other cells): traced, so the program's counters have to come out among the
metrics, at a size with the dense ``conv`` layer, a period of (softmax,
three ``conv`` layers) and a share of the experts."""

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT


def test_lfm2_rehearsal_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "lfm2_24b_1of8.steady", "--seed", "6600000017",
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
    last = records[-1]
    assert last["phase"] == "result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"gconv_past_tap_share", "moe_bias_abs_max",
            "moe_load_max_over_mean", "moe_rows_held_over_live",
            "moe_share_rows_over_expected", "step_ms", "host_step_ms",
            "shard_batch_ms"} <= set(last["would_print"])
    # no device time on a CPU
    assert not {"gconv_ms_per_step", "gconv_roofline_pct"} & set(
        last["would_print"])
    # every number ``correct`` compared, beside its limit, ends standard error
    checks = [line for line in proc.stderr.splitlines()
              if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "token_max_abs_err", "token_median_abs_err", "mean_abs_err",
        "low_margin_share", "compiles_in_window", "non_finite_losses"}
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    # which mixer ran, on what, and what the routed block is
    assert ("attention.path impl=short_conv seq=64 channels=64 conv=3 "
            "core=jnp") in proc.stderr
    assert "moe.path impl=ragged_dot experts=16 top_k=3" in proc.stderr
    assert "held=4 first_expert=0" in proc.stderr
    moved = [json.loads(line) for line in proc.stderr.splitlines()
             if line.startswith('{"phase": "moe_bias"')][-1]["records"]
    assert len(moved[0]["bias_abs_max"]) == 4       # a value a routed layer
    # the load moves the bias between two records
    assert moved[0]["bias_abs_max"] != moved[-1]["bias_abs_max"]
    taps = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith('{"phase": "gconv_taps"')][-1]["records"]
    assert len(taps[0]["past_tap_share"]) == 4      # a value a conv layer
    assert all(0.3 < v < 0.8 for v in taps[0]["past_tap_share"])
    load = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith('{"phase": "reference_lfm2"')][-1]
    assert len(load["share_rows_over_expected_by_layer"]) == 4
