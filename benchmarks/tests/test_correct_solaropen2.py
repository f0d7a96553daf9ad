"""What ``correct`` must tell apart in the Solar-Open2 cell, through the
harness's own comparison (``jobs_shared.reference_check``) at the ``TINY``
sizes on the CPU, on the state ``program.make_state`` gives: the system is
correct; the control (the reference in the program's place with its
parameters rounded through float8, the precision below the configuration's
bfloat16) and the seven planted faults of ``families/solaropen2.py::FAULTS``
are not.  The readings on the chip at the cell's own size are under
``TOKEN_ATOL`` in ``families/solaropen2.py`` (``tests/precision_solaropen2.py``
takes them).  The last test drives a whole rehearsal run with the decay
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
              load_module("families", "solaropen2").FAULTS}}
SEED = 4100000019


@pytest.fixture(scope="module")
def solar_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "solaropen2_250b_1of32.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_solar_state_is_the_rule_of_the_file(solar_made):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by factors read from the configuration file."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = solar_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    held = family.sizes(config, True)["n_routed_experts"] ** 0.5
    factors = config["run"]["state"]
    assert rule == {
        ("embed_tokens",): float(factors["embed_scale"]),
        ("layers", "gqa_0", "layer", "attn", "q_proj", "kernel"): float(
            factors["q_scale"]),
        **{("layers", run, "layer", "mlp", "router", "kernel"): float(
            factors["router_scale"]) for run in ("gqa_0", "kda_1")},
        **{("layers", run, "layer", "mlp", leaf): held
           for run in ("gqa_0", "kda_1")
           for leaf in ("gate_proj", "up_proj", "down_proj")}}
    assert all(factor != 1.0 for factor in rule.values())
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


def test_solar_system_is_correct(solar_made):
    config, family, model, trainer, state, pool = solar_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 4


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_solar_control_and_faults_are_not(solar_made, what):
    config, family, model, trainer, state, pool = solar_made
    m = family.sizes(config, True)

    def stand_in(params, ids, labels):
        return family.reference(params, ids, labels, m, **PLANTED[what])[0]

    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool, stand_in=stand_in)
    assert not ok, detail
    over = [name for name, limit in (
        ("token_max_abs_err", "token_atol"),
        ("token_median_abs_err", "median_atol"),
        ("mean_abs_err", "mean_atol")) if detail[name] > detail[limit]]
    assert over, detail


BROKEN = """
import sys
import jax.numpy as jnp
from dlrover_tpu.models import llama
from dlrover_tpu.ops import linear_attention
whole = linear_attention.kda
def no_decay(q, k, v, g, beta, chunk=64):
    return whole(q, k, v, jnp.zeros_like(g), beta, chunk)
linear_attention.kda = no_decay
sys.path.insert(0, {root!r})
from benchmarks import run
sys.exit(run.main(["--workload", "solaropen2_250b_1of32.steady", "--seed",
                   "4100000021", "--seconds", "2", "--trace", "0",
                   "--rehearse"]))
"""


def test_solar_run_with_the_decay_broken_is_not_correct():
    """The harness's look for a chip skipped (``--rehearse``), the rest of
    the run as it is, and underneath a delta rule that never forgets: the
    result says not correct, and the check lines say by which numbers."""
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
    assert over["token_median_abs_err"] and over["token_max_abs_err"], over
    assert not over["compiles_in_window"]
