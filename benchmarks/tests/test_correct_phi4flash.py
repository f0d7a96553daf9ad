"""What ``correct`` must tell apart in the Phi-4-mini-flash cell, through
the harness's own comparison (``jobs_shared.reference_check``) at the
``TINY`` sizes on the CPU, on the state ``program.make_state`` gives: the
system is correct; the control (the reference in the program's place with
its parameters rounded through float8, the precision below the
configuration's bfloat16) and the planted faults of
``families/phi4flash.py::FAULTS`` are not, each limit at a tenth of the
chip's (``TINY_LIMITS``).  The control that puts the scan's
state through bfloat16 (``LOWER_PRECISION``) is the chip's to show: at
sixty-four positions the state has summed too little for its rounding to
pass a limit.  The readings on the chip at the cell's own size are in
PERF.md section 6 (``tests/precision_phi4flash.py`` takes them)."""

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, load_module, read_json
from benchmarks.jobs_shared import reference_check

#: the limits are fitted to bfloat16 at 16,384 positions; in float32 at
#: sixty-four the system reads 3e-5 / 4e-6 / 3e-7 and a mild fault (the
#: wrong layer's ``lambda_init``: 0.22 / 0.030 / 3.6e-4) lies under them:
#: the tiny walk of the harness's comparison holds every limit at a tenth
TINY_LIMITS = 0.1
PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "phi4flash").FAULTS}}
SEED = 5700000019


@pytest.fixture(scope="module")
def phi_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "phi4miniflash_l8.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    for limit in ("TOKEN_ATOL", "MEDIAN_ATOL", "MEAN_ATOL"):
        setattr(family, limit, TINY_LIMITS * getattr(family, limit))
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_phi4flash_state_is_the_rule_of_the_file(phi_made):
    """``condition`` changes the leaves ``state_rule`` names and no other,
    by numbers read from the configuration file."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = phi_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    numbers = config["run"]["state"]
    mamba = [("layers", "mamba_0", "layer"), ("memory", "mamba_0", "layer")]
    own = [("layers", "swa_1", "layer"), ("memory", "gqa_1", "layer")]
    want = {
        **{layer + ("attn", "x_proj", "kernel"): (
            "times", numbers["x_proj_scale"]) for layer in mamba},
        **{layer + ("attn", "dt_proj", "bias"): (
            "add", numbers["dt_bias_add"]) for layer in mamba},
        **{layer + ("attn", name, "bias"): (
            "fill", numbers["attn_bias_spread"])
           for layer in own
           for name in ("q_proj", "k_proj", "v_proj", "o_proj")},
        **{("cross", "xattn_1", "layer", "attn", name, "bias"): (
            "fill", numbers["attn_bias_spread"])
           for name in ("q_proj", "o_proj")},
        ("cross", "gmu_0", "layer", "attn", "out_proj", "kernel"): (
            "times", numbers["gmu_out_scale"]),
        ("cross", "xattn_1", "layer", "attn", "o_proj", "kernel"): (
            "times", numbers["cross_out_scale"])}
    assert rule == want and len(rule) == 16
    before = nn.meta.unbox(plain.params)
    seen = set()

    def held_to_the_rule(path, got):
        keys = tuple(k.key for k in path)
        was = before
        for key in keys:
            was = was[key]
        how, value = rule.get(keys, ("times", 1.0))
        seen.add(keys)
        if how == "fill":        # a unit normal a bias, from its kernel
            assert not np.any(np.asarray(was))
            assert 0.5 * value < float(np.std(got)) < 2 * value, keys
            return
        np.testing.assert_allclose(
            got, np.asarray(was) * value if how == "times"
            else np.asarray(was) + value, rtol=1e-6, err_msg=str(keys))

    jax.tree_util.tree_map_with_path(
        held_to_the_rule, nn.meta.unbox(state.params))
    assert set(rule) <= seen
    no_rule = {**config, "run": {
        k: v for k, v in config["run"].items() if k != "state"}}
    assert family.state_rule(no_rule, True) == {}


def test_phi4flash_system_is_correct(phi_made):
    config, family, model, trainer, state, pool = phi_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_phi4flash_control_and_faults_are_not(phi_made, what):
    config, family, model, trainer, state, pool = phi_made
    m = family.sizes(config, True)

    def stand_in(params, ids, labels):
        return family.reference(params, ids, labels, m, **PLANTED[what])[0]

    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool, stand_in=stand_in)
    assert not ok, detail
    over = [name for name, limit in (
        ("token_max_abs_err", "token_atol"),
        ("token_median_abs_err", "median_atol"),
        # a reading that is no number (``delta`` without its softplus
        # grows the state without bound) is outside every limit
        ("mean_abs_err", "mean_atol")) if not detail[name] <= detail[limit]]
    assert over, detail
