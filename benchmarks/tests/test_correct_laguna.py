"""What ``correct`` must tell apart in the Laguna cell, through the
harness's own comparison (``jobs_shared.reference_check``) at the ``TINY``
sizes on the CPU, on the state ``program.make_state`` gives: the system is
correct; the control (the reference in the program's place with its
parameters rounded through float8, the precision below the configuration's
bfloat16) and the planted faults of ``families/laguna.py::FAULTS`` are not,
but the one that IS the precision below (the scores through bfloat16),
which float32 arithmetic at sixty-four positions cannot show.  The readings
on the chip at the cell's own size are in PERF.md section 6
(``tests/precision_laguna.py`` takes them)."""

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, load_module, read_json
from benchmarks.jobs_shared import reference_check

PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "laguna").FAULTS
              if fault != "bfloat16_scores"}}
SEED = 5100000019


@pytest.fixture(scope="module")
def laguna_state():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "laguna_xs2_33b_1of8.json")
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_laguna_state_is_the_rule_of_the_file(laguna_state):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by factors read from the configuration file."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = laguna_state
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    held = family.sizes(config, True)["num_experts"] ** 0.5
    factors = config["run"]["state"]
    scale = lambda key: float(factors.get(key, 1.0))  # noqa: E731
    dense = ("prefix", "gqa_dense_0", "layer")
    window, full = ("layers", "swa_0", "layer"), ("layers", "gqa_1", "layer")
    want = {
        ("embed_tokens",): scale("embed_scale"),
        **{layer + ("attn", "q_proj", "kernel"): scale(key)
           for layer, key in ((dense, "q_scale"), (full, "q_scale"),
                              (window, "window_q_scale"))},
        **{layer + ("attn", "head_gate_proj", "kernel"): scale("gate_scale")
           for layer in (dense, window, full)},
        **{layer + ("attn", "o_proj", "kernel"): scale("attn_out_scale")
           for layer in (dense, window, full)},
        **{layer + ("mlp", "router", "kernel"): scale("router_scale")
           for layer in (window, full)},
        **{layer + ("mlp", leaf): held for layer in (window, full)
           for leaf in ("gate_proj", "up_proj")},
        **{layer + ("mlp", "down_proj"): held * scale("expert_out_scale")
           for layer in (window, full)}}
    assert rule == {k: v for k, v in want.items() if v != 1.0}
    assert len(rule) >= 8
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


def test_laguna_system_is_correct(laguna_state):
    config, family, model, trainer, state, pool = laguna_state
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 4


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_laguna_control_and_faults_are_not(laguna_state, what):
    config, family, model, trainer, state, pool = laguna_state
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
