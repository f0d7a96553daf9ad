"""The Phi-4-mini-flash family's plain reference against the system's model
at the tiny size on the CPU, in float32 on both sides (as
``test_reference_kanana2.py`` does for its family), against the repository's
copy, and what a departure in the program does to the comparison.  The
planted faults go through the harness's own comparison in
``test_correct_phi4flash.py``."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "phi4flash")
SEQ = 64


@pytest.fixture(scope="module")
def phi_tiny():
    """(model, parameters, inputs, labels, the system's losses), every leaf
    moved: untrained norm scales are 1 and the biases 0, and a reference
    that forgot one would pass."""
    model = family.build({}, True, SEQ)
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    made = nn.meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(1), inputs))
    leaves, treedef = jax.tree.flatten(made["params"])
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    return model, params, inputs, labels, _system_losses(
        model, params, inputs, labels)


def _system_losses(model, params, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)(
            {"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_phi4flash_reference_agrees_with_the_model_in_float32(
        phi_tiny, capfd):
    model, params, inputs, labels, got = phi_tiny
    losses = jax.jit(lambda p, i, l: family.reference_token_losses(
        p, i, l, {}, True))(params, inputs, labels)
    np.testing.assert_allclose(got, np.asarray(losses), atol=2e-4)
    jax.effects_barrier()
    said = [line for line in capfd.readouterr().err.splitlines()
            if line.startswith('{"phase": "reference_phi4flash"')]
    assert len(said) == 1 and '"decay_p50"' in said[0]


def test_phi4flash_copy_is_the_repositorys_reference(phi_tiny):
    """The benchmark's copy and ``dlrover_tpu/models/phi4flash_reference.py``
    compute the same losses from the same tree."""
    from dlrover_tpu.models import phi4flash_reference

    model, params, inputs, labels, got = phi_tiny
    m = family.sizes({}, True)
    ours = jax.jit(lambda p: family.reference(p, inputs, labels, m)[0])(params)
    theirs = jax.jit(lambda p: phi4flash_reference.forward(
        p, inputs, labels, m)["token_losses"])(params)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-5)
    assert phi4flash_reference.kinds_of(m) == family.kinds_of(m)


@pytest.mark.parametrize("changes,same", [
    ({}, True),
    ({"sliding_window": 15}, False),      # a position fewer in the band
    ({"mamba_conv": 3}, None),            # another tree: refused below
    ({"norm": "rms"}, False),             # the mean and the bias left out
    ({"attention_impl": "reference"}, True),
])
def test_phi4flash_departure_in_the_program(phi_tiny, changes, same):
    """The comparison sees a program that departs from the equations: a
    window a position short moves the losses; a change of the tree cannot
    take the parameters at all."""
    model, params, inputs, labels, got = phi_tiny
    other = type(model)(dataclasses.replace(model.config, **changes))
    if same is None:
        with pytest.raises(Exception):
            _system_losses(other, params, inputs, labels)
        return
    again = _system_losses(other, params, inputs, labels)
    assert bool(np.abs(again - got).max() < 1e-4) is same


@pytest.mark.parametrize("fault", family.FAULTS + family.LOWER_PRECISION)
def test_phi4flash_every_planted_reading_moves_the_losses(phi_tiny, fault):
    """At the tiny size in float32: each fault's losses are another
    function of the same parameters."""
    model, params, inputs, labels, got = phi_tiny
    m = family.sizes({}, True)
    losses = jax.jit(lambda p: family.reference(
        p, inputs, labels, m, fault=fault)[0])(params)
    # (``delta`` without its softplus goes negative and the state grows
    # without bound: no number at all)
    assert not np.abs(np.asarray(losses) - got).max() <= 1e-3
