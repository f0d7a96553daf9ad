"""What ``correct`` must tell apart in the Nemotron-3-Super cell, through
the harness's own comparison (``jobs_shared.reference_check``) at the
``TINY`` sizes on the CPU, on the state ``program.make_state`` gives: the
system is correct; the control (the reference in the program's place with
its parameters rounded through float8, the precision below the
configuration's bfloat16) and the planted faults of
``families/nemotronh.py::FAULTS`` are not.  The two controls that put ONE
part through bfloat16 (``LOWER_PRECISION``) are the chip's to show: float32
arithmetic at sixty-four positions decides nothing about them.  The
readings on the chip at the cell's own size are in PERF.md section 6
(``tests/precision_nemotronh.py`` takes them)."""

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, load_module, read_json
from benchmarks.jobs_shared import reference_check

#: at the tiny size in float32 a bias of the file's spread moves the weights
#: by less than the limits: the tiny state draws it wider
SPREAD = 0.3
PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "nemotronh").FAULTS}}
SEED = 6400000019


@pytest.fixture(scope="module")
def nemotron_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "nemotron3super_120b_1of32.json")
    config = {**config, "run": {**config["run"], "state": {
        **config["run"]["state"], "bias_spread": SPREAD}}}
    # one chip's cell: one device of however many the test session has
    with pytest.MonkeyPatch.context() as patch:
        whole = mesh.build_mesh
        patch.setattr(mesh, "build_mesh", lambda cfg: whole(
            cfg, devices=jax.devices()[:1]))
        family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    return config, family, model, trainer, state, pool


def test_nemotron_state_is_the_rule_of_the_file(nemotron_made):
    """``condition`` multiplies (or moves) the leaves ``state_rule`` names
    and no other, by numbers read from the configuration file, and draws the
    bias of every routed layer; the model's shell and the reference read
    the buffers it made (Ling-3.0's shell, by import)."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = nemotron_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    held = family.sizes(config, True)["n_routed_experts"] ** 0.5
    factors = config["run"]["state"]
    scale = lambda key: float(factors.get(key, 1.0))  # noqa: E731
    routed, mamba, attn = (
        ("layers", "ffn_0", "layer"), ("layers", "mamba2_alone_1", "layer"),
        ("suffix", "gqa_alone_0", "layer"))
    want = {
        ("embed_tokens",): scale("embed_scale"),
        routed + ("mlp", "up_proj"): held,
        routed + ("mlp", "down_proj"): held * scale("expert_out_scale"),
        routed + ("mlp", "latent_up", "kernel"): scale("latent_out_scale"),
        routed + ("mlp", "shared_expert", "down_proj", "kernel"):
            scale("shared_out_scale"),
        mamba + ("attn", "out_proj", "kernel"): scale("mamba_out_scale"),
        mamba + ("attn", "conv_weight"): scale("conv_scale"),
        mamba + ("attn", "dt_bias"): ("add", float(factors["dt_bias_add"])),
        attn + ("attn", "o_proj", "kernel"): scale("attn_out_scale"),
        attn + ("attn", "q_proj", "kernel"): scale("q_scale")}
    assert rule == {k: v for k, v in want.items() if v != 1.0}
    assert len(rule) >= 6
    seen = set()

    def held_to_the_rule(path, got, before):
        keys = tuple(k.key for k in path)
        seen.add(keys)
        how = rule.get(keys, 1.0)
        before = np.asarray(before)
        np.testing.assert_allclose(
            got, before + how[1] if isinstance(how, tuple) else before * how,
            rtol=1e-6, err_msg=str(keys))

    jax.tree_util.tree_map_with_path(
        held_to_the_rule, nn.meta.unbox(state.params),
        nn.meta.unbox(plain.params))
    assert set(rule) <= seen
    (bias,) = jax.tree.leaves(state.buffers)
    assert bias.shape == (2, 1, 8)
    assert 0.5 * SPREAD < float(bias.std()) < 2 * SPREAD
    assert all(not np.any(np.asarray(b)) for b in jax.tree.leaves(plain.buffers))
    assert family._ling._STATE["buffers"] is state.buffers
    no_rule = {**config, "run": {
        k: v for k, v in config["run"].items() if k != "state"}}
    assert family.state_rule(no_rule, True) == {}


def test_nemotron_system_is_correct(nemotron_made):
    config, family, model, trainer, state, pool = nemotron_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 2


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_nemotron_control_and_faults_are_not(nemotron_made, what):
    config, family, model, trainer, state, pool = nemotron_made
    m = family.sizes(config, True)

    def stand_in(params, ids, labels):
        return family.reference(
            params, state.buffers, ids, labels, m, **PLANTED[what])[0]

    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool, stand_in=stand_in)
    assert not ok, detail
    over = [name for name, limit in (
        ("token_max_abs_err", "token_atol"),
        ("token_median_abs_err", "median_atol"),
        ("mean_abs_err", "mean_atol")) if detail[name] > detail[limit]]
    assert over, detail
