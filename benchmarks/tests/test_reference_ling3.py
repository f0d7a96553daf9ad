"""The Ling-3.0 family's plain reference against the system's model at the
tiny size on the CPU, in float32 on both sides (as
``test_reference_solaropen2.py`` does for its family), and what the
comparison must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "ling3")
SEQ = 64


def _ling_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)._model
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _ling_state(model, inputs):
    """(parameters, buffers), every leaf moved: untrained norm scales are 1,
    the decay's vectors small and the bias 0, and a reference that forgot
    one would pass."""
    made = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs))

    def moved(tree, by, seed):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf + by * jax.random.normal(k, leaf.shape, leaf.dtype)
            for leaf, k in zip(leaves, keys)])

    return moved(made["params"], 0.1, 2), moved(made["buffers"], 0.05, 3)


def _ling_system_losses(model, params, buffers, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply(
            {"params": params, "buffers": buffers}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_ling_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _ling_model_and_inputs()
    params, buffers = _ling_state(model, inputs)
    got = _ling_system_losses(model, params, buffers, inputs, labels)
    losses, low = family.reference_forward(
        params, inputs, labels, {}, True, buffers=buffers)
    assert got.shape == np.asarray(losses).shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, losses, rtol=0, atol=1e-4)
    assert low.shape == (3,)                    # a share a routed layer
    err = capfd.readouterr().err
    assert '"phase": "reference_ling3"' in err
    assert '"group_dropped_share_by_layer"' in err


def test_ling_copy_is_the_repositorys_reference():
    """The reference twice, in the repository for its tests and here for
    the benchmark (scans over periods and runs, the planted faults): the
    two give the same losses and the same loads."""
    from dlrover_tpu.models import ling3_reference

    model, inputs, labels = _ling_model_and_inputs()
    params, buffers = _ling_state(model, inputs)
    m = family.sizes({}, True)
    assert m["layer_prefix"] == ("kda:dense",)
    assert m["layer_pattern"] == ("kda", "kda", "mla")
    got = family.reference(params, buffers, inputs, labels, m)
    want = ling3_reference.forward(params, buffers, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[3], want["rows"])
    assert got[3].shape == (3, 16) and int(got[3][0].sum()) == 2 * SEQ * 4
    # the mechanisms bite at this size: the groups drop an expert of most
    # tokens, few choices hang on rounding, a state that forgets
    assert float(np.min(got[2])) > 0.3
    assert float(np.max(got[1])) < family.LOW_MARGIN_SHARE_MAX
    assert got[4].shape == (3,)                 # a half life a kda layer
    assert family.runs(m["layer_prefix"] + m["layer_pattern"]) == [
        ("kda_dense_0", "kda:dense", 1), ("kda_1", "kda", 2),
        ("mla_2", "mla", 1)]
    assert [path for path, _, _ in family.stacks(m)] == [
        ("prefix", "kda_dense_0", "layer"), ("layers", "kda_0", "layer"),
        ("layers", "mla_1", "layer")]


@pytest.mark.parametrize("what", list(family.FAULTS) + [
    "rope_theta_in_the_program", "another_chunk_is_not"])
def test_ling_departure_is_far_outside_float32_agreement(what):
    """Each is a hundred times the 1e-4 of the test above at this size (the
    chunk size alone changes nothing); on the chip at the published widths
    the readings are in PERF.md."""
    model, inputs, labels = _ling_model_and_inputs()
    params, buffers = _ling_state(model, inputs)
    m = family.sizes({}, True)
    want = np.asarray(family.reference(params, buffers, inputs, labels, m)[0])
    if what in family.FAULTS:
        got = family.reference(
            params, buffers, inputs, labels, m, fault=what)[0]
        assert np.abs(np.asarray(got) - want).max() > 1e-2
        return
    changed = {"rope_theta_in_the_program": {"rope_theta": 100.0},
               "another_chunk_is_not": {"kda_chunk": 16}}[what]
    wrong, _, _ = _ling_model_and_inputs(**changed)
    got = _ling_system_losses(wrong, params, buffers, inputs, labels)
    err = np.abs(got - want).max()
    assert (err < 1e-4) if what == "another_chunk_is_not" else (err > 1e-2)


def test_ling_low_margin_share_over_its_limit_fails_the_comparison(monkeypatch):
    """A routed family's losses are NaN where too many tokens of a layer
    cannot be told apart: a comparison token by token says nothing then."""
    model, inputs, labels = _ling_model_and_inputs()
    params, buffers = _ling_state(model, inputs)
    sound = family.reference_token_losses(
        params, inputs, labels, {}, True, buffers=buffers)
    assert np.isfinite(np.asarray(sound)).all()
    monkeypatch.setattr(family, "LOW_MARGIN_SHARE_MAX", -1.0)
    got = family.reference_token_losses(
        params, inputs, labels, {}, True, buffers=buffers)
    assert np.isnan(np.asarray(got)).all()


def test_ling_reference_without_a_state_says_so(monkeypatch):
    """The harness hands the reference parameters alone: before
    ``condition`` has made a state there is no bias to read."""
    model, inputs, labels = _ling_model_and_inputs()
    params, _ = _ling_state(model, inputs)
    monkeypatch.setitem(family._STATE, "buffers", None)
    with pytest.raises(RuntimeError, match="no selection bias"):
        family.reference_forward(params, inputs, labels, {}, True)
