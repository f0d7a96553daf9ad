"""What ``correct`` must tell apart in the EvaByte cell, through the
harness's own comparison (``jobs_shared.reference_check``) at the ``TINY``
sizes on the CPU, on the state ``program.make_state`` gives: the system is
correct; the control (the reference in the program's place with its
parameters rounded through float8, the precision below the configuration's
bfloat16) and the five planted faults of ``families/evabyte.py::FAULTS`` are
not.  The readings on the chip at the cell's own size are under
``TOKEN_ATOL`` in ``families/evabyte.py`` (``tests/precision_evabyte.py``
takes them).  The last test drives a whole rehearsal run with the pooling
broken underneath and sees ``correct`` come out false."""

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
              load_module("families", "evabyte").FAULTS}}
SEED = 3500000019


@pytest.fixture(scope="module")
def evabyte_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "evabyte_l4.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_evabyte_state_is_the_rule_of_the_file(evabyte_made):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by factors read from the configuration file."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = evabyte_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    scale = float(config["run"]["state"]["pool_scale"])
    assert scale != 1.0 and rule == {
        ("layers", "layer", "attn", name): scale
        for name in ("adaptive_mu_k", "adaptive_phi")}
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


def test_evabyte_system_is_correct(evabyte_made):
    config, family, model, trainer, state, pool = evabyte_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert "low_margin_share_by_layer" not in detail


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_evabyte_control_and_faults_are_not(evabyte_made, what):
    config, family, model, trainer, state, pool = evabyte_made
    m = family.sizes(config, True)

    def stand_in(params, ids, labels):
        return family.reference(params, ids, labels, m, **PLANTED[what])[0]

    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool, stand_in=stand_in)
    assert not ok, detail
    # by the steady number, not by one token's swing
    assert detail["token_median_abs_err"] > detail["median_atol"], detail


BROKEN = """
import sys
import jax.numpy as jnp
from dlrover_tpu.ops import attention
learned = attention.eva_pool
def plain_mean(k, v, mu, phi, chunk):
    return learned(k, v, jnp.zeros_like(mu), jnp.zeros_like(phi), chunk)
attention.eva_pool = plain_mean
sys.path.insert(0, {root!r})
from benchmarks import run
sys.exit(run.main(["--workload", "evabyte_l4.steady", "--seed",
                   "3500000021", "--seconds", "2", "--trace", "0",
                   "--rehearse"]))
"""


def test_evabyte_run_with_the_pooling_broken_is_not_correct():
    """The harness's look for a chip skipped (``--rehearse``), the rest of
    the run as it is, and underneath a pooling that takes the plain mean of
    a chunk: the result says not correct, and the check lines say by which
    numbers."""
    proc = subprocess.run(
        [sys.executable, "-c", BROKEN.format(root=ROOT)],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
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
    assert over["token_median_abs_err"] and over["token_max_abs_err"], over
    assert not over["compiles_in_window"]
