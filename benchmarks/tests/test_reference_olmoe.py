"""The OLMoE family's plain reference against the system's model at the
tiny size on the CPU, in float32 on both sides (as ``test_reference.py``
does for the other families), and what the comparison must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "olmoe")


def _model_and_inputs(**changes):
    model = family.build({}, True, 32)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, 33)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _params(model, inputs):
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained scales are 1 and the router starts near uniform: move every
    # leaf, or a reference that forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _model_and_inputs()
    params = _params(model, inputs)
    got = _system_losses(model, params, inputs, labels)
    want = np.asarray(
        family.reference_token_losses(params, inputs, labels, {}, True))
    assert got.shape == want.shape == (2, 32)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    # the low-margin tokens are counted and reported, layer by layer
    assert '"phase": "reference_margin"' in capfd.readouterr().err


def test_the_copy_is_the_repositorys_reference():
    """The issue asked for the reference twice, in the repository for its
    tests and here for the benchmark: the two give the same losses."""
    from dlrover_tpu.models import olmoe_reference

    model, inputs, labels = _model_and_inputs()
    params = _params(model, inputs)
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    want = olmoe_reference.forward(
        params, inputs, labels, family.sizes({}, True))["token_losses"]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("what", ["no_qk_norm", "renormalised_top_k",
                                  "one_expert_fewer"])
def test_a_departure_from_the_layer_is_far_outside_float32_agreement(what):
    """Each is a hundred times the 1e-4 of the test above at this size; on
    the chip at the published widths the readings are in PERF.md."""
    model, inputs, labels = _model_and_inputs()
    params = _params(model, inputs)
    want = np.asarray(
        family.reference_token_losses(params, inputs, labels, {}, True))
    if what == "no_qk_norm":
        wrong, _, _ = _model_and_inputs(qk_norm=False)
        got = _system_losses(wrong, params, inputs, labels)
    elif what == "one_expert_fewer":       # a token's last expert dropped
        wrong, _, _ = _model_and_inputs(top_k=model.config.top_k - 1)
        got = _system_losses(wrong, params, inputs, labels)
    else:
        from dlrover_tpu.models import moe

        def renormalised(x, top_i, top_w, *rest):
            top_w = top_w / top_w.sum(axis=-1, keepdims=True)
            return local_experts(x, top_i, top_w, *rest)

        local_experts, moe.local_experts = moe.local_experts, renormalised
        try:
            got = _system_losses(model, params, inputs, labels)
        finally:
            moe.local_experts = local_experts
    assert np.abs(got - want).max() > 1e-2


def test_too_many_low_margin_tokens_fail_the_comparison():
    """A router that has collapsed towards ties: the reference says so
    with NaN, it does not compare token by token."""
    model, inputs, labels = _model_and_inputs()
    params = _params(model, inputs)
    router = params["layers"]["layer"]["mlp"]["router"]
    router["kernel"] = router["kernel"] * 1e-4
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isnan(np.asarray(got)).all()
