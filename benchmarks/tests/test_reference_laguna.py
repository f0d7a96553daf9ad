"""The Laguna family's plain reference against the system's model at the
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

family = load_module("families", "laguna")
SEQ = 64


def _laguna_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _laguna_params(model, inputs):
    """Every leaf moved: untrained norm scales are 1 and a reference that
    forgot one would pass."""
    made = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs))["params"]
    leaves, treedef = jax.tree.flatten(made)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _laguna_system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


@pytest.fixture(scope="module")
def laguna_made():
    model, inputs, labels = _laguna_model_and_inputs()
    params = _laguna_params(model, inputs)
    m = family.sizes({}, True)
    want = jax.jit(lambda p: family.reference(p, inputs, labels, m))(params)
    return model, inputs, labels, params, m, jax.device_get(want)


def test_laguna_reference_agrees_with_the_model_in_float32(laguna_made, capfd):
    model, inputs, labels, params, m, _ = laguna_made
    got = _laguna_system_losses(model, params, inputs, labels)
    losses, low = family.reference_forward(params, inputs, labels, {}, True)
    assert got.shape == np.asarray(losses).shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, losses, rtol=0, atol=1e-4)
    assert low.shape == (4,)                    # a share a routed layer
    err = capfd.readouterr().err
    assert '"phase": "reference_laguna"' in err
    assert '"share_rows_over_expected_by_layer"' in err


def test_laguna_copy_is_the_repositorys_reference(laguna_made):
    """The reference twice, in the repository for its tests (an ``[S, S]``
    mask, no scan) and here for the benchmark (blocks of queries, scans over
    periods and runs, the planted faults): the two give the same losses and
    the same loads."""
    from dlrover_tpu.models import laguna_reference

    model, inputs, labels, params, m, got = laguna_made
    assert m["layer_prefix"] == ("gqa:dense",)
    assert m["layer_pattern"] == ("swa", "swa", "swa", "gqa")
    assert m["heads"] == {"gqa": 6, "swa": 8}
    # blocks shorter than the sequence, so that the blocked walk is walked
    assert m["sliding_window"] < SEQ
    m = {**m, "query_block": 16, "window_query_block": 16}
    got = family.reference(params, inputs, labels, m)
    want = laguna_reference.forward(params, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[2], want["rows"])
    assert got[2].shape == (4, 16) and int(got[2][0].sum()) == 2 * SEQ * 4
    assert float(np.max(got[1])) < family.LOW_MARGIN_SHARE_MAX
    assert family.runs(m["layer_prefix"] + m["layer_pattern"]) == [
        ("gqa_dense_0", "gqa:dense", 1), ("swa_1", "swa", 3),
        ("gqa_2", "gqa", 1)]
    assert [path for path, _, _ in family.stacks(m)] == [
        ("prefix", "gqa_dense_0", "layer"), ("layers", "swa_0", "layer"),
        ("layers", "gqa_1", "layer")]


@pytest.mark.parametrize("what", list(family.FAULTS) + [
    "window_in_the_program", "rope_theta_in_the_program"])
def test_laguna_departure_is_far_outside_float32_agreement(laguna_made, what):
    """Each is a hundred times the 1e-4 of the test above at this size but
    the scores rounded to bfloat16, which is the precision below; on the
    chip at the published widths the readings are in PERF.md."""
    model, inputs, labels, params, m, want = laguna_made
    want = np.asarray(want[0])
    if what in family.FAULTS:
        got = jax.jit(lambda p: family.reference(
            p, inputs, labels, m, fault=what)[0])(params)
        floor = 1e-3 if what == "bfloat16_scores" else 1e-2
        assert np.abs(np.asarray(got) - want).max() > floor
        return
    changed = {"window_in_the_program": {"sliding_window": 15},
               "rope_theta_in_the_program": {"rope_theta": 100.0}}[what]
    wrong, _, _ = _laguna_model_and_inputs(**changed)
    got = _laguna_system_losses(wrong, params, inputs, labels)
    assert np.abs(got - want).max() > 1e-2


def test_laguna_low_margin_share_over_its_limit_fails_the_comparison(
        laguna_made, monkeypatch):
    """A routed family's losses are NaN where too many tokens of a layer
    cannot be told apart: a comparison token by token says nothing then."""
    model, inputs, labels, params, m, _ = laguna_made
    sound = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isfinite(np.asarray(sound)).all()
    monkeypatch.setattr(family, "LOW_MARGIN_SHARE_MAX", -1.0)
    got = family.reference_token_losses(params, inputs, labels, {}, True)
    assert np.isnan(np.asarray(got)).all()


def test_laguna_stack_is_read_from_the_three_lists():
    """``layer_types``, ``mlp_layer_types`` and
    ``num_attention_heads_per_layer`` stand in the file whole; the stack is
    their first ``num_hidden_layers`` entries, the leading dense layers once
    and the rest in whole periods."""
    from benchmarks.common import HERE, read_json

    config = read_json(HERE, "configs", "laguna_xs2_33b_1of8.json")
    m = family.sizes(config, False)
    assert (m["layer_prefix"], m["layer_pattern"]) == (
        ("gqa:dense",), ("swa", "swa", "swa", "gqa"))
    assert m["heads"] == {"gqa": 48, "swa": 64}
    assert m["experts_total"] == 256 and m["first_expert"] == 0
    # nine periods; the published 40 end in three window layers more, a
    # part of a period, which a pattern of whole periods cannot name
    deep = family.sizes({**config, "num_hidden_layers": 37}, False)
    assert deep["layer_pattern"] == m["layer_pattern"]
    assert family.layer_counts(deep) == {"gqa:dense": 1, "swa": 27, "gqa": 9}
    assert len(family.sizes(
        {**config, "num_hidden_layers": 40}, False)["layer_pattern"]) == 39
    with pytest.raises(ValueError, match="fewer than"):
        family.sizes({**config, "num_hidden_layers": 41}, False)
    with pytest.raises(ValueError, match="unlike head counts"):
        family.sizes({**config, "num_attention_heads_per_layer": [
            48, 64, 32, 64, 48]}, False)
