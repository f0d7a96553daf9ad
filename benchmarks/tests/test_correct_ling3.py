"""What ``correct`` must tell apart in the Ling-3.0 cell, through the
harness's own comparison (``jobs_shared.reference_check``) at the ``TINY``
sizes on the CPU, on the state ``program.make_state`` gives: the system is
correct; the control (the reference in the program's place with its
parameters rounded through float8, the precision below the configuration's
bfloat16) and the planted faults of ``families/ling3.py::FAULTS`` are not.
The readings on the chip at the cell's own size are in PERF.md section 6
(``tests/precision_ling3.py`` takes them).  The last test drives a whole
rehearsal run with the groups broken underneath and sees ``correct`` come
out false."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks import program
from benchmarks.common import HERE, ROOT, load_module, read_json
from benchmarks.jobs_shared import reference_check

#: at the tiny size in float32 a bias of the file's spread moves the weights
#: by less than the limits: the tiny state draws it wider
SPREAD = 0.3
PLANTED = {"float8": {"round_through": jnp.float8_e4m3fn},
           **{fault: {"fault": fault} for fault in
              load_module("families", "ling3").FAULTS}}
SEED = 4800000019


@pytest.fixture(scope="module")
def ling_made():
    import jax

    from dlrover_tpu.parallel import mesh

    config = read_json(HERE, "configs", "ling3flashvl_125b_1of32.json")
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


def test_ling_state_is_the_rule_of_the_file(ling_made):
    """``condition`` multiplies the leaves ``state_rule`` names and no
    other, by factors read from the configuration file, and draws the bias
    of every routed layer, the same on every call for one state."""
    import flax.linen as nn
    import jax
    import numpy as np

    config, family, model, trainer, state, pool = ling_made
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    rule = family.state_rule(config, True)
    held = family.sizes(config, True)["num_experts"] ** 0.5
    factors = config["run"]["state"]
    routed = (("layers", "kda_0", "layer"), ("layers", "mla_1", "layer"))
    mla = routed[1] + ("attn",)
    scale = lambda key: float(factors.get(key, 1.0))  # noqa: E731
    want = {
        ("embed_tokens",): scale("embed_scale"),
        mla + ("q_proj", "kernel"): scale("q_scale"),
        mla + ("kv_a_proj", "kernel"): scale("latent_scale"),
        mla + ("gate_proj", "kernel"): scale("gate_scale"),
        mla + ("o_proj", "kernel"): scale("mla_out_scale"),
        **{layer + ("mlp", "router", "kernel"): scale("router_scale")
           for layer in routed},
        **{layer + ("mlp", leaf): held for layer in routed
           for leaf in ("gate_proj", "up_proj")},
        **{layer + ("mlp", "down_proj"): held * scale("expert_out_scale")
           for layer in routed},
        **{layer + ("attn", *leaf): scale(key)
           for layer in (("prefix", "kda_dense_0", "layer"), routed[0])
           for leaf, key in ((("f_proj", "kernel"), "decay_scale"),
                             (("dt_bias",), "dt_bias_scale"))}}
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
    bias = jax.tree.leaves(state.buffers)
    assert [b.shape for b in bias] == [(1, 2, 16), (1, 1, 16)]
    assert all(0.5 * SPREAD < float(b.std()) < 2 * SPREAD for b in bias)
    assert all(not np.any(np.asarray(b)) for b in jax.tree.leaves(plain.buffers))
    again = family.drawn_bias(
        nn.meta.unbox(plain.params), plain.buffers, SPREAD)
    for a, b in zip(jax.tree.leaves(again), bias):     # eager against jitted
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert family._STATE["buffers"] is state.buffers
    no_rule = {**config, "run": {
        k: v for k, v in config["run"].items() if k != "state"}}
    assert family.state_rule(no_rule, True) == {}


def test_ling_system_is_correct(ling_made):
    config, family, model, trainer, state, pool = ling_made
    ok, detail = reference_check(
        config, True, family, model, trainer, state, pool)
    assert ok, detail
    assert detail["token_median_abs_err"] <= detail["median_atol"]
    assert len(detail["low_margin_share_by_layer"]) == 3


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_ling_control_and_faults_are_not(ling_made, what):
    config, family, model, trainer, state, pool = ling_made
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


BROKEN = """
import sys
import jax
from dlrover_tpu.models import moe
def no_groups(self, scores):
    cfg = self.config
    bias = self.variable("buffers", "selection_bias", jax.numpy.zeros,
                         (cfg.num_experts,), jax.numpy.float32).value
    top_i = jax.lax.top_k(scores + bias, cfg.top_k)[1]
    return jax.numpy.take_along_axis(scores, top_i, axis=-1), top_i
moe._choose = no_groups
sys.path.insert(0, {root!r})
from benchmarks import run
sys.exit(run.main(["--workload", "ling3flashvl_125b_1of32.steady", "--seed",
                   "4800000021", "--seconds", "2", "--trace", "0",
                   "--rehearse"]))
"""


def test_ling_run_with_the_groups_broken_is_not_correct():
    """The harness's look for a chip skipped (``--rehearse``), the rest of
    the run as it is, and underneath a router that takes the eight best of
    all 512: the result says not correct, and the check lines say by which
    numbers."""
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
    assert over["token_median_abs_err"] or over["token_max_abs_err"], over
    assert not over["compiles_in_window"]
