"""What ``correct`` must tell apart in the SDAR cell, through the harness's
own comparison (``jobs_shared.reference_check``) at the ``TINY`` sizes on
the CPU, on the state ``program.make_state`` gives: the system is correct;
the control (the reference in the program's place with its parameters
rounded through float8, the precision below the configuration's bfloat16)
and the seven planted faults of ``families/sdar.py::FAULTS`` are not.  The
readings on the chip at the cell's own size are under ``FAULTS`` in
``families/sdar.py`` (``tests/precision_sdar.py`` takes them).  The last
test drives a whole rehearsal run with the mask broken underneath and sees
``correct`` come out false."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, ROOT, load_module, read_json
from benchmarks.jobs_shared import reference_check

PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "sdar").FAULTS}}
SEED = 4100000019


@pytest.fixture(scope="module")
def sdar_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "sdar_30b_1of8.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_sdar_state_is_the_rule_of_the_file(sdar_made):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by factors read from the configuration file; the mask token's
    row by a factor of its own."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = sdar_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    m = family.sizes(config, True)
    factors = config["run"]["state"]
    layer = ("layers", "layer")
    assert set(rule) == {
        ("embed_tokens",), layer + ("attn", "q_norm", "scale"),
        *(layer + ("mlp", leaf)
          for leaf in ("gate_proj", "up_proj", "down_proj"))}
    rows = rule[("embed_tokens",)]
    assert rows.shape == (m["vocab_size"], 1)
    assert m["mask_token_id"] == m["vocab_size"] - 1
    assert rows[m["mask_token_id"], 0] == np.float32(
        factors["mask_row_scale"])
    assert set(np.delete(rows[:, 0], m["mask_token_id"])) == {
        float(factors["embed_scale"])}
    assert rule[layer + ("mlp", "up_proj")] == float(factors["expert_scale"])
    assert rule[layer + ("attn", "q_norm", "scale")] == float(
        factors["q_scale"])
    seen = set()

    def held_to_the_rule(path, got, before):
        keys = tuple(k.key for k in path)
        seen.add(keys)
        np.testing.assert_allclose(
            got, np.asarray(before) * rule.get(keys, 1.0), rtol=1e-6,
            err_msg=str(keys))

    jax.tree_util.tree_map_with_path(
        held_to_the_rule, nn.meta.unbox(state.params),
        nn.meta.unbox(plain.params))
    assert set(rule) <= seen
    no_rule = {**config, "run": {
        k: v for k, v in config["run"].items() if k != "state"}}
    assert family.state_rule(no_rule, True) == {}
    # absent factors: the table's for the mask's row, none on the q norm
    bare = {**config, "run": {**config["run"], "state": {"embed_scale": 7}}}
    bare_rule = family.state_rule(bare, True)
    assert set(bare_rule[("embed_tokens",)][:, 0]) == {7.0}
    assert layer + ("attn", "q_norm", "scale") not in bare_rule
    # and Keye's factor on the experts: the square root of the number held
    assert bare_rule[layer + ("mlp", "up_proj")] == m["num_experts"] ** 0.5


def test_sdar_system_is_correct(sdar_made, capfd):
    config, family, model, trainer, state, pool = sdar_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 2
    err = capfd.readouterr().err
    assert '"phase": "reference_objective"' in err
    assert "check objective_rel_err:" in err


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_sdar_control_and_faults_are_not(sdar_made, what):
    config, family, model, trainer, state, pool = sdar_made
    by_tokens = what != "objective_unweighted"
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool,
        # the token limits alone have to catch all but one
        stand_in=family.stand_in(
            config, True, hold_objective=not by_tokens, **PLANTED[what]))
    assert not ok, detail
    if not by_tokens:
        # it moves no logit: the objective's own limit catches it
        assert detail["loss_system"] != detail["loss_system"]     # NaN
        return
    over = [name for name, limit in (
        ("token_max_abs_err", "token_atol"),
        ("token_median_abs_err", "median_atol"),
        ("mean_abs_err", "mean_atol")) if detail[name] > detail[limit]]
    assert over, detail


@pytest.mark.parametrize("limit", ["OBJECTIVE_RTOL", "MASKED_MEDIAN_ATOL"])
def test_sdar_objective_off_its_limit_fails_the_comparison(sdar_made,
                                                          monkeypatch, limit):
    """The harness compares token losses only: an objective further from
    the reference's than its limit, or its terms over the masked tokens
    further than theirs, turns every loss to NaN."""
    config, family, model, trainer, state, pool = sdar_made
    monkeypatch.setattr(family, limit, -1.0)
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert not ok and detail["loss_reference"] != detail["loss_reference"]


BROKEN = """
import sys
import jax.numpy as jnp
from dlrover_tpu.ops import attention
whole = attention.block_diffusion_keep
def leaky(first, last, block, noisy):
    # noisy queries allowed their OWN block's clean keys
    keep = whole(first, last, block, noisy)
    if not noisy:
        return keep
    r = jnp.arange(first, last)[:, None] // block
    c = jnp.arange(last)[None, :] // block
    return keep.at[0, :, :last].set(c <= r)
attention.block_diffusion_keep = leaky
sys.path.insert(0, {root!r})
from benchmarks import run
sys.exit(run.main(["--workload", "sdar_30b_1of8.steady", "--seed",
                   "4100000021", "--seconds", "2", "--trace", "0",
                   "--rehearse"]))
"""


def test_sdar_run_with_the_mask_leaking_is_not_correct():
    """The harness's look for a chip skipped (``--rehearse``), the rest of
    the run as it is, and underneath a mask that lets a noisy row see the
    clean tokens it has to predict: the result says not correct, and the
    check lines say by which numbers."""
    proc = subprocess.run(
        [sys.executable, "-c", BROKEN.format(root=ROOT)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "XLA_FLAGS": ""})     # one device, as the cell has
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("REHEARSAL ")]
    last = json.loads(lines[-1][len("REHEARSAL "):])
    assert last["phase"] == "result" and last["correct"] is False, (
        proc.stdout[-2000:] + proc.stderr[-2000:])
    over = {}
    for line in proc.stderr.splitlines():
        if line.startswith("check "):
            _, name, value, _, limit = line.split()
            over[name.rstrip(":")] = not float(value) <= float(limit)
    assert over["token_max_abs_err"] or over["token_median_abs_err"], over
    assert not over["compiles_in_window"]
