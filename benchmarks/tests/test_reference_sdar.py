"""The SDAR family's plain reference against the system's model at the tiny
size on the CPU, in float32 on both sides (as ``test_reference_keyevl.py``
does for its family), and what the comparison must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "sdar")
SEQ = 64


def _sdar_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _sdar_params(model, inputs):
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained norm scales are 1: move every leaf, or a reference that
    # forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _sdar_system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_sdar_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _sdar_model_and_inputs()
    params = _sdar_params(model, inputs)
    got = _sdar_system_losses(model, params, inputs, labels)
    losses, low = family.reference_forward(params, inputs, labels, {}, True)
    assert got.shape == np.asarray(losses).shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, losses, rtol=0, atol=1e-4)
    assert low.shape == (2,)                    # a share a layer
    err = capfd.readouterr().err
    assert '"phase": "reference_objective"' in err
    report = next(__import__("json").loads(line) for line in err.splitlines()
                  if line.startswith('{"phase": "reference_objective"'))
    assert report["objective_rel_err"] < 1e-5
    assert 0.3 < report["bd_masked_share"] < 0.7
    assert report["bd_weight_max"] >= 1.0


def test_sdar_copy_is_the_repositorys_reference():
    """The reference twice, in the repository for its tests and here for
    the benchmark (token losses at the harness's labels, the planted
    faults): the two give the same logits' losses, the same objective and
    the same counters on the same noise."""
    from dlrover_tpu.models import sdar_reference

    model, inputs, labels = _sdar_model_and_inputs()
    params = _sdar_params(model, inputs)
    m = family.sizes({}, True)
    assert (m["block_length"], m["mask_token_id"]) == (4, 255)
    noisy, weights = family.draw_noise(inputs, {}, True)
    losses, objective, low, terms = family.reference(
        params, noisy, inputs, weights, labels, m)
    want = sdar_reference.forward(params, noisy, inputs, weights, m)
    logp = jax.nn.log_softmax(want["logits"], -1)
    at_labels = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(losses, at_labels, rtol=0, atol=5e-6)
    np.testing.assert_allclose(objective, want["nelbo"], rtol=1e-6)
    np.testing.assert_allclose(terms, want["token_nll"], rtol=0, atol=5e-6)
    np.testing.assert_allclose(low, want["router_low_margin"], atol=1e-6)
    # the noise is the program's own on its default key, and it bites:
    # masks where the weights are, about half the tokens
    np.testing.assert_array_equal(
        np.asarray(noisy)[np.asarray(weights) > 0], m["mask_token_id"])
    assert 0.3 < float(np.mean(np.asarray(weights) > 0)) < 0.7
    # the two masks are one function
    rows = jnp.arange(2 * SEQ)
    np.testing.assert_array_equal(
        family._allowed(rows[:, None], rows[None, :], SEQ, 4),
        sdar_reference.allowed(rows[:, None], rows[None, :], SEQ, 4))


@pytest.mark.parametrize("what", list(family.FAULTS) + [
    "another_block_in_the_program", "another_query_block_is_not"])
def test_sdar_departure_is_far_outside_float32_agreement(what, monkeypatch):
    """Each is a hundred times the 1e-4 of the test above at this size (the
    block of queries worked at a time alone changes nothing: the
    mathematics does not depend on it; the objective's weight moves no
    logit, and is caught by the objective's own number); on the chip at the
    published widths the readings are in PERF.md."""
    model, inputs, labels = _sdar_model_and_inputs()
    params = _sdar_params(model, inputs)
    m = family.sizes({}, True)
    noisy, weights = family.draw_noise(inputs, {}, True)
    want, objective, _, _ = family.reference(
        params, noisy, inputs, weights, labels, m)
    want = np.asarray(want)
    if what in family.FAULTS:
        got, theirs, _, _ = family.reference(
            params, noisy, inputs, weights, labels, m, fault=what)
        if what == "objective_unweighted":
            assert np.abs(np.asarray(got) - want).max() < 1e-6
            assert abs(float(theirs) / float(objective) - 1) > 0.1
        else:
            assert np.abs(np.asarray(got) - want).max() > 1e-2
        return
    if what == "another_query_block_is_not":
        from dlrover_tpu.ops import attention

        whole = attention.block_diffusion_attention
        monkeypatch.setattr(
            attention, "block_diffusion_attention",
            lambda q, k, v, block, query_block=512: whole(q, k, v, block, 16))
        got = _sdar_system_losses(model, params, inputs, labels)
        assert np.abs(got - want).max() < 1e-4
        return
    wrong, _, _ = _sdar_model_and_inputs(block_diffusion=8)
    got = _sdar_system_losses(wrong, params, inputs, labels)
    assert np.abs(got - want).max() > 1e-2


def test_sdar_low_margin_share_over_its_limit_fails_the_comparison(monkeypatch):
    """A routed family's losses are NaN where too many rows of a layer
    cannot be told apart: a comparison token by token says nothing then."""
    model, inputs, labels = _sdar_model_and_inputs()
    params = _sdar_params(model, inputs)
    sound = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isfinite(np.asarray(sound)).all()
    monkeypatch.setattr(family, "LOW_MARGIN_SHARE_MAX", -1.0)
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isnan(np.asarray(got)).all()
