"""What ``correct`` must tell apart in the Ouro cell, through the harness's
own comparison (``jobs_shared.reference_check`` and ``compare_losses``) at
the ``TINY`` sizes on the CPU, on the state ``program.make_state`` gives:
the system is correct; the control (the reference in the program's place
with its parameters rounded through float8, the precision below the
configuration's bfloat16) and the five planted faults of
``families/ouro.py::FAULTS`` are not, three by the result's token losses
and two (they move no logit) by the exits' own limits.  The readings on the
chip at the cell's own size are under ``TOKEN_ATOL`` in ``families/ouro.py``
(``tests/precision_ouro.py`` takes them)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, load_module, read_json
from benchmarks.jobs_shared import compare_losses, reference_check

PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "ouro").FAULTS}}
#: what moves no logit: the exit distribution's and the objective's own
#: limits have to catch it
BY_EXITS = ("last_exit_gated", "entropy_sign")
SEED = 6100000019


@pytest.fixture(scope="module")
def ouro_made():
    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "ouro2b6_l8.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    batch = trainer.shard_batch({k: v[:1] for k, v in pool[0].items()})
    params = nn.meta.unbox(state.params)
    want = jax.jit(lambda p, i, t: family.reference_token_losses(
        p, i, t, config, True))(params, batch["input_ids"], batch["labels"])
    return config, family, model, trainer, state, pool, batch, params, want


def test_ouro_system_is_correct(ouro_made, capfd):
    config, family, model, trainer, state, pool = ouro_made[:6]
    assert not hasattr(family, "condition")     # ``create_state``'s state
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["tokens"] == 64 and "low_margin_share_max" not in detail
    err = capfd.readouterr().err
    assert '"phase": "reference_exits"' in err
    for name in ("exit_token_max_abs_err", "exit_p_max_abs_err",
                 "objective_rel_err"):
        assert f"check {name}:" in err
    # the program's counters, for the reader of ``loop_exit_entropy``
    assert 0.1 < family.SEEN["loop_exit_entropy"][0] < 1.3863
    assert len(family.SEEN["loop_ce_by_step"]) == 4


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_ouro_control_and_faults_are_not(ouro_made, what):
    config, family, *_, batch, params, want = ouro_made
    by_tokens = what not in BY_EXITS
    got = jax.jit(family.stand_in(
        config, True, hold_exits=not by_tokens, **PLANTED[what]))(
            params, batch["input_ids"], batch["labels"])
    ok, detail = compare_losses(family, got, want)
    assert not ok, detail
    if by_tokens:       # the harness's own limits catch it
        assert (detail["token_max_abs_err"] > family.TOKEN_ATOL
                or detail["token_median_abs_err"] > family.MEDIAN_ATOL
                or detail["mean_abs_err"] > family.MEAN_ATOL), detail
    else:               # NaN: the exits' limits turned every loss
        assert detail["loss_system"] != detail["loss_system"]


@pytest.mark.parametrize("limit", [
    "EXIT_TOKEN_ATOL", "EXIT_P_ATOL", "OBJECTIVE_RTOL"])
def test_ouro_exits_off_a_limit_fail_the_comparison(ouro_made, monkeypatch,
                                                   limit):
    """The harness compares the result's token losses only: an exit's
    losses, the exit distribution or the objective further from the
    reference's than its limit turns every loss to NaN."""
    config, family, *_, batch, params, want = ouro_made
    monkeypatch.setattr(family, limit, -1.0)
    losses = jax.jit(lambda p, i, t: family.reference_token_losses(
        p, i, t, config, True))(params, batch["input_ids"], batch["labels"])
    assert bool(jnp.isnan(losses).all()) and not bool(jnp.isnan(want).any())
