"""Each family's plain reference against the system's model at tiny sizes
on the CPU, in float32 on both sides: the same mathematics to 1e-4, so what
is left on the chip is the precision of the arithmetic."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as nn

from benchmarks.common import load_module


@pytest.mark.parametrize("name", ["llama", "gpt"])
def test_reference_agrees_with_the_model_in_float32(name):
    family = load_module("families", name)
    model = family.build({}, True, 32)
    # the same model computing in float32: only the mathematics is compared
    model = type(model)(dataclasses.replace(model.config, dtype=jnp.float32))
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, 33)), jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), inputs)["params"])
    # untrained biases and scales are 0 and 1: move them, or a reference that
    # forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    got = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    want = family.reference_token_losses(params, inputs, labels, {}, True)
    assert got.shape == want.shape == (2, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-4)
