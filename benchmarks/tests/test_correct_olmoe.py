"""What ``correct`` must tell apart in the expert-parallel cell, through the
harness's own comparison (``jobs_shared.reference_check``) at the ``TINY``
sizes on the CPU, on the state ``program.make_state`` gives: the system is
correct; the control (the reference in the program's place with its
parameters rounded through float8, the precision below the configuration's
bfloat16) and two faults of the expert layer are not.  The readings on the
chips at the cell's own size are under ``TOKEN_ATOL`` in
``families/olmoe.py`` (``tests/precision_olmoe.py`` takes them).  The last
test drives a whole rehearsal run with the expert layer broken underneath
and sees ``correct`` come out false."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, ROOT, read_json
from benchmarks.jobs_shared import reference_check


@pytest.fixture(scope="module")
def made():
    config = read_json(HERE, "configs", "olmoe1b7b_ep4.json")
    config["run"]["mesh"] = {"ep": 1}
    family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, 3200000019, family)
    state = program.make_state(trainer, family, config, True, 3200000019, pool)
    return config, family, model, trainer, state, pool


def _planted(family, config, what):
    def float8(params, ids, labels):
        return family.reference_token_losses(
            params, ids, labels, config, True,
            round_through=jnp.float8_e4m3fn)

    def weakest_expert_dropped(params, ids, labels):
        m = family.sizes(config, True)
        return family.reference_forward(
            params, ids, labels,
            {**m, "num_experts_per_tok": m["num_experts_per_tok"] - 1})[0]

    def one_chips_experts_missing(params, ids, labels):
        mlp = params["layers"]["layer"]["mlp"]
        local = mlp["down_proj"].shape[1] // 4
        missing = {**mlp, "down_proj": mlp["down_proj"].at[:, :local].set(0)}
        return family.reference_token_losses(
            {**params, "layers": {"layer": {
                **params["layers"]["layer"], "mlp": missing}}},
            ids, labels, config, True)

    return locals()[what]


def test_the_system_is_correct(made):
    config, family, model, trainer, state, pool = made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 2


@pytest.mark.parametrize("what", [
    "float8", "one_chips_experts_missing", "weakest_expert_dropped"])
def test_the_control_and_the_faults_are_not(made, what):
    config, family, model, trainer, state, pool = made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool,
        stand_in=_planted(family, config, what))
    assert not ok, detail
    # by the steady number, not by one token's swing
    assert detail["token_median_abs_err"] > detail["median_atol"], detail


BROKEN = """
import sys
from dlrover_tpu.models import moe
whole = moe.local_experts
def renormalised(x, top_i, top_w, *rest):
    return whole(x, top_i, top_w / top_w.sum(axis=-1, keepdims=True), *rest)
moe.local_experts = renormalised
sys.path.insert(0, {root!r})
from benchmarks import run
sys.exit(run.main(["--workload", "olmoe1b7b_ep4.steady", "--seed",
                   "3200000021", "--seconds", "2", "--trace", "0",
                   "--rehearse"]))
"""


def test_a_run_with_the_expert_layer_broken_is_not_correct():
    """The harness's look for a chip skipped (``--rehearse``), the rest of
    the run as it is, and underneath an expert layer that renormalises the
    kept router weights: the result says not correct, and the check lines
    say by which number."""
    proc = subprocess.run(
        [sys.executable, "-c", BROKEN.format(root=ROOT)],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("REHEARSAL ")]
    last = json.loads(lines[-1][len("REHEARSAL "):])
    assert last["phase"] == "result" and last["correct"] is False, (
        proc.stdout[-2000:] + proc.stderr[-2000:])
    over = {}
    for line in proc.stderr.splitlines():
        if line.startswith("check "):
            _, name, value, _, limit = line.split()
            over[name.rstrip(":")] = float(value) > float(limit)
    assert over["token_median_abs_err"] and over["token_max_abs_err"], over
    assert not over["compiles_in_window"]
