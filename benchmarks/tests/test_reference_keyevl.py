"""The Keye-VL-2.0 family's plain reference against the system's model at
the tiny size on the CPU, in float32 on both sides (as
``test_reference_olmoe.py`` does for its family), and what the comparison
must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "keyevl")
SEQ = 64


def _keyevl_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _keyevl_params(model, inputs):
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained scales are 1, biases 0 and the router near uniform: move
    # every leaf, or a reference that forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _keyevl_system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_keyevl_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _keyevl_model_and_inputs()
    params = _keyevl_params(model, inputs)
    got = _keyevl_system_losses(model, params, inputs, labels)
    want = np.asarray(
        family.reference_token_losses(params, inputs, labels, {}, True))
    assert got.shape == want.shape == (2, SEQ)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    # L_I layer by layer beside its limit, and the low-margin shares
    err = capfd.readouterr().err
    assert '"phase": "reference_index"' in err
    assert "check index_loss_rel_err.layer1:" in err


def test_keyevl_copy_is_the_repositorys_reference():
    """The reference twice, in the repository for its tests and here for
    the benchmark: the two give the same losses and the same ``L_I`` (to
    float32's resolution at a loss of 8, 1e-6: the repository's works a head
    at a time, the benchmark's every head of a block at once)."""
    from dlrover_tpu.models import keye_reference

    model, inputs, labels = _keyevl_model_and_inputs()
    params = _keyevl_params(model, inputs)
    m = family.sizes({}, True)
    got = family.reference(params, inputs, labels, m)
    want = keye_reference.forward(params, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=5e-6)
    np.testing.assert_allclose(got[1], want["index_loss"], rtol=0, atol=1e-6)
    np.testing.assert_allclose(got[2], want["index_low_margin"], atol=1e-6)
    np.testing.assert_allclose(got[3], want["router_low_margin"], atol=1e-6)
    # the selection bites at this size: 48 of 64 queries a sequence
    assert family.kept_pairs(SEQ, 16) < family.causal_pairs(SEQ)
    assert float(np.min(got[1])) > 1e-3


@pytest.mark.parametrize("what", [
    "no_head_norm", "weights_not_renormalised", "every_key_kept",
    "nearest_keys_kept", "absent_experts_added"])
def test_keyevl_departure_is_far_outside_float32_agreement(what):
    """Each is a hundred times the 1e-4 of the test above at this size; on
    the chip at the published widths the readings are in PERF.md."""
    model, inputs, labels = _keyevl_model_and_inputs()
    params = _keyevl_params(model, inputs)
    m = family.sizes({}, True)
    want = np.asarray(family.reference(params, inputs, labels, m)[0])
    if what == "nearest_keys_kept":
        got = family.reference(params, inputs, labels, m, nearest=True)[0]
    elif what == "absent_experts_added":
        got = family.reference(params, inputs, labels, m, absent=True)[0]
    else:
        changed = {"no_head_norm": {"qk_norm": False},
                   "weights_not_renormalised": {"norm_topk_prob": False},
                   "every_key_kept": {"index_topk": SEQ}}[what]
        wrong, _, _ = _keyevl_model_and_inputs(**changed)
        got = _keyevl_system_losses(wrong, params, inputs, labels)
    assert np.abs(np.asarray(got) - want).max() > 1e-2


def test_keyevl_index_loss_that_disagrees_fails_the_comparison(monkeypatch):
    """The harness compares token losses; the family holds ``L_I`` itself:
    a layer further off than ``INDEX_LOSS_RTOL`` turns the losses to NaN."""
    model, inputs, labels = _keyevl_model_and_inputs()
    params = _keyevl_params(model, inputs)
    sound = family.reference_forward(params, inputs, labels, {}, True)[0]
    assert np.isfinite(np.asarray(sound)).all()
    whole = family.system_index_loss
    monkeypatch.setattr(
        family, "system_index_loss",
        lambda *a: whole(*a) * jnp.asarray([1.0, 1 + 2 * family.INDEX_LOSS_RTOL]))
    got = family.reference_forward(params, inputs, labels, {}, True)[0]
    assert np.isnan(np.asarray(got)).all()


def test_keyevl_too_many_low_margin_tokens_fail_the_comparison():
    model, inputs, labels = _keyevl_model_and_inputs()
    params = _keyevl_params(model, inputs)
    router = params["layers"]["layer"]["mlp"]["router"]
    router["kernel"] = router["kernel"] * 1e-4
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isnan(np.asarray(got)).all()
