"""The Solar-Open2 family's plain reference against the system's model at
the tiny size on the CPU, in float32 on both sides (as
``test_reference_keyevl.py`` does for its family), and what the comparison
must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "solaropen2")
SEQ = 64


def _solar_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _solar_params(model, inputs):
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained norm scales are 1 and the decay's vectors small: move every
    # leaf, or a reference that forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _solar_system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_solar_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _solar_model_and_inputs()
    params = _solar_params(model, inputs)
    got = _solar_system_losses(model, params, inputs, labels)
    losses, low = family.reference_forward(params, inputs, labels, {}, True)
    assert got.shape == np.asarray(losses).shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, losses, rtol=0, atol=1e-4)
    assert low.shape == (4,)                    # a share a layer
    err = capfd.readouterr().err
    assert '"phase": "reference_kda"' in err
    assert '"kda_beta_over_one_share_by_layer"' in err


def test_solar_copy_is_the_repositorys_reference():
    """The reference twice, in the repository for its tests and here for
    the benchmark (scans over periods and runs, the planted faults): the
    two give the same losses and the same counters."""
    from dlrover_tpu.models import solar_open2_reference

    model, inputs, labels = _solar_model_and_inputs()
    params = _solar_params(model, inputs)
    m = family.sizes({}, True)
    assert m["layer_pattern"] == ("gqa", "kda", "kda", "kda")
    got = family.reference(params, inputs, labels, m)
    want = solar_open2_reference.forward(params, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=5e-6)
    np.testing.assert_allclose(got[1], want["router_low_margin"], atol=1e-6)
    np.testing.assert_allclose(got[2], want["beta_over_one_share"], atol=1e-6)
    np.testing.assert_allclose(got[3], want["decay_half_life"], rtol=1e-4)
    # the mechanism bites at this size: betas on both sides of 1, a state
    # that forgets within the sequence
    assert 0.2 < float(np.min(got[2])) and float(np.max(got[2])) < 0.8
    assert 0.5 < float(np.min(got[3])) and float(np.max(got[3])) < SEQ
    assert family.runs(m["layer_pattern"]) == [
        ("gqa_0", "gqa", 1), ("kda_1", "kda", 3)]


@pytest.mark.parametrize("what", list(family.FAULTS) + [
    "rope_in_the_program", "another_chunk_is_not"])
def test_solar_departure_is_far_outside_float32_agreement(what):
    """Each is a hundred times the 1e-4 of the test above at this size (the
    chunk size alone changes nothing: the mathematics does not depend on
    it); on the chip at the published widths the readings are in PERF.md."""
    model, inputs, labels = _solar_model_and_inputs()
    params = _solar_params(model, inputs)
    m = family.sizes({}, True)
    want = np.asarray(family.reference(params, inputs, labels, m)[0])
    if what in family.FAULTS:
        got = family.reference(params, inputs, labels, m, fault=what)[0]
        assert np.abs(np.asarray(got) - want).max() > 1e-2
        return
    changed = {"rope_in_the_program": {"use_rope": True},
               "another_chunk_is_not": {"kda_chunk": 16}}[what]
    wrong, _, _ = _solar_model_and_inputs(**changed)
    got = _solar_system_losses(wrong, params, inputs, labels)
    err = np.abs(got - want).max()
    assert (err < 1e-4) if what == "another_chunk_is_not" else (err > 1e-2)


def test_solar_low_margin_share_over_its_limit_fails_the_comparison(monkeypatch):
    """A routed family's losses are NaN where too many tokens of a layer
    cannot be told apart: a comparison token by token says nothing then."""
    model, inputs, labels = _solar_model_and_inputs()
    params = _solar_params(model, inputs)
    sound = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isfinite(np.asarray(sound)).all()
    monkeypatch.setattr(family, "LOW_MARGIN_SHARE_MAX", -1.0)
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isnan(np.asarray(got)).all()
