"""What ``correct`` must tell apart in the LFM2 cell, through the harness's
own comparison (``jobs_shared.reference_check``) at the ``TINY`` sizes on
the CPU, on the state ``program.make_state`` gives: the system is correct;
the control (the reference in the program's place with its parameters
rounded through float8, the precision below the configuration's bfloat16)
and the planted faults of ``families/lfm2.py::FAULTS`` (the ``B`` gate left
out, the taps shifted by one position, SiLU put on the taps, the bias left
out of the choice, the q/k norm left out, the weights not renormalised) are
not.  The control that puts the gates and taps through bfloat16
(``LOWER_PRECISION``) is the chip's to show.  The readings on the chip at
the cell's own size are in PERF.md section 6 (``tests/precision_lfm2.py``
takes them)."""

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
              load_module("families", "lfm2").FAULTS}}
SEED = 6600000019
#: the file's limits stand between bfloat16's readings on the chip (a median
#: of 0.030) and the control's there; here both sides are float32 and the
#: system reads 5e-7, so the same comparison is held six times tighter (a
#: median of 0.01, still twenty thousand times the system's reading): at the
#: chip's own limits the control (0.027 at sixty-four positions), the bias
#: left out (0.034) and the weights not renormalised (0.048) would pass
TIGHTER = 6.0


@pytest.fixture(scope="module")
def lfm2_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "lfm2_24b_1of8.json")
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
        for limit in ("TOKEN_ATOL", "MEDIAN_ATOL", "MEAN_ATOL"):
            patch.setattr(family, limit, getattr(family, limit) / TIGHTER)
        yield config, family, model, trainer, state, pool


def test_lfm2_state_is_the_rule_of_the_file(lfm2_made):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by numbers read from the configuration file, and draws the bias
    of every routed layer; the model's shell and the reference read the
    buffers it made (Ling-3.0's shell, by import)."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = lfm2_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    held = family.sizes(config, True)["num_experts"] ** 0.5
    factors = config["run"]["state"]
    scale = lambda key: float(factors.get(key, 1.0))  # noqa: E731
    want = {("embed_tokens",): scale("embed_scale"),
            ("prefix", "conv_dense_0", "layer", "attn", "out_proj", "kernel"):
                scale("conv_out_scale"),
            ("prefix", "conv_dense_0", "layer", "attn", "conv_weight"):
                scale("tap_scale")}
    for run, kind in (("gqa_0", "gqa"), ("conv_1", "conv")):
        layer = ("layers", run, "layer")
        want.update({
            layer + ("mlp", "gate_proj"): held,
            layer + ("mlp", "up_proj"): held,
            layer + ("mlp", "down_proj"): held * scale("expert_out_scale")})
        want.update({
            layer + ("attn", "out_proj", "kernel"): scale("conv_out_scale"),
            layer + ("attn", "conv_weight"): scale("tap_scale")}
            if kind == "conv" else {
                layer + ("attn", "o_proj", "kernel"): scale("attn_out_scale"),
                layer + ("attn", "q_norm", "scale"): scale("qk_norm_scale"),
                layer + ("attn", "k_norm", "scale"): scale("qk_norm_scale")})
    assert rule == {k: v for k, v in want.items() if v != 1.0}
    assert len(rule) >= 6
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
    biases = jax.tree.leaves(state.buffers)
    assert sorted(b.shape for b in biases) == [(1, 1, 16), (1, 3, 16)]
    assert all(0.5 * SPREAD < float(b.std()) < 2 * SPREAD for b in biases)
    assert all(not np.any(np.asarray(b)) for b in jax.tree.leaves(plain.buffers))
    assert family._ling._STATE["buffers"] is state.buffers
    no_rule = {**config, "run": {
        k: v for k, v in config["run"].items() if k != "state"}}
    assert family.state_rule(no_rule, True) == {}


def test_lfm2_system_is_correct(lfm2_made):
    config, family, model, trainer, state, pool = lfm2_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 4


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_lfm2_control_and_faults_are_not(lfm2_made, what):
    config, family, model, trainer, state, pool = lfm2_made
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
