"""The EvaByte family's plain reference against the system's model at the
tiny size on the CPU, in float32 on both sides (as
``test_reference_keyevl.py`` does for its family), and what the comparison
must catch."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "evabyte")
SEQ = 64


def _evabyte_model_and_inputs(**changes):
    model = family.build({}, True, SEQ)
    model = type(model)(dataclasses.replace(
        model.config, dtype=jnp.float32, **changes))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    return model, ids[:, :-1], ids[:, 1:]


def _evabyte_params(model, inputs):
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained norm offsets are 0 and the pooling vectors small: move every
    # leaf, or a reference that forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _evabyte_system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_evabyte_reference_agrees_with_the_model_in_float32(capfd):
    model, inputs, labels = _evabyte_model_and_inputs()
    params = _evabyte_params(model, inputs)
    got = _evabyte_system_losses(model, params, inputs, labels)
    want = np.asarray(
        family.reference_forward(params, inputs, labels, {}, True)[0])
    assert got.shape == want.shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    # the further heads' loss beside its limit, and the two counters
    err = capfd.readouterr().err
    assert '"phase": "reference_eva"' in err
    assert "check multi_byte_rel_err:" in err


def test_evabyte_copy_is_the_repositorys_reference():
    """The reference twice, in the repository for its tests and here for
    the benchmark (a scan over the layers, the planted faults): the two
    give the same losses, the same further heads' term and the same
    counters."""
    from dlrover_tpu.models import evabyte_reference

    model, inputs, labels = _evabyte_model_and_inputs()
    params = _evabyte_params(model, inputs)
    m = family.sizes({}, True)
    got = family.reference(params, inputs, labels, m)
    want = evabyte_reference.forward(params, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=5e-6)
    np.testing.assert_allclose(got[1], want["multi_byte"], rtol=1e-6)
    np.testing.assert_allclose(got[2], want["summary_mass_share"], rtol=1e-5)
    np.testing.assert_allclose(got[3], want["pool_weight_max"], rtol=1e-5)
    # the mechanism bites at this size: 3 of 4 windows see summaries, and
    # the pooling is far from a plain mean of 4
    assert family.summary_pairs(SEQ, 16, 4) == 16 * 4 * 6
    assert 0.2 < float(np.min(got[2])) and float(np.min(got[3])) > 0.3


@pytest.mark.parametrize("what", list(family.FAULTS) + [
    "no_unit_offset", "one_window", "another_chunk"])
def test_evabyte_departure_is_far_outside_float32_agreement(what):
    """Each is a hundred times the 1e-4 of the test above at this size; on
    the chip at the published widths the readings are in PERF.md."""
    model, inputs, labels = _evabyte_model_and_inputs()
    params = _evabyte_params(model, inputs)
    m = family.sizes({}, True)
    want = np.asarray(family.reference(params, inputs, labels, m)[0])
    if what in family.FAULTS:
        got = family.reference(params, inputs, labels, m, fault=what)[0]
    else:
        changed = {"no_unit_offset": {"norm_unit_offset": False},
                   "one_window": {"eva_window": SEQ},
                   "another_chunk": {"eva_chunk": 8}}[what]
        wrong, _, _ = _evabyte_model_and_inputs(**changed)
        got = _evabyte_system_losses(wrong, params, inputs, labels)
    assert np.abs(np.asarray(got) - want).max() > 1e-2


def test_evabyte_further_heads_that_disagree_fail_the_comparison(monkeypatch):
    """The harness compares token losses; the family holds the seven further
    heads' loss itself: further off than ``MULTI_BYTE_RTOL`` turns the
    losses to NaN."""
    model, inputs, labels = _evabyte_model_and_inputs()
    params = _evabyte_params(model, inputs)
    sound = family.reference_forward(params, inputs, labels, {}, True)
    assert np.isfinite(np.asarray(sound[0])).all() and sound[1].size == 0
    whole = family.system_multi_byte_loss
    monkeypatch.setattr(
        family, "system_multi_byte_loss",
        lambda *a: whole(*a) * (1 + 2 * family.MULTI_BYTE_RTOL))
    got = family.reference_forward(params, inputs, labels, {}, True)[0]
    assert np.isnan(np.asarray(got)).all()
