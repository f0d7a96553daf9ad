"""The latent-attention core's kernels (``ops/pallas/latent_attention.py``)
on the chip at the two cells' shapes: one sequence of 16,384 positions at
Kanana-2's 32 heads and at Ling-3.0's 8, bfloat16 operands, the table's
blocks.  The backward pass is ONE call whose two dQ accumulators live in
HBM between key blocks: its five gradients against the float32
``jax.numpy`` core where that core's ``[H, S, S]`` fits, and at the cells'
own length twice in one process, because a block of an accumulator left
from the first run, or fetched before the step before had written it,
shows on the second."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import _latent_reference
from dlrover_tpu.ops.pallas import latent_attention as kernels

NAMES = ("q_nope", "q_pe", "k_nope", "k_pe", "v")
# of the float32 core's largest entry, bfloat16 operands through the
# kernels (probabilities and dS rounded to bfloat16 for their products);
# the shared key's gradient sums 8 or 32 heads' roundings
TOLERANCE = 1e-2


def _operands(seq, heads, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((1, seq, heads, 128), (1, seq, heads, 64), (1, seq, heads, 128),
              (1, seq, 64), (1, seq, heads, 128), (1, seq, heads, 128))
    return tuple(jax.random.normal(k, shape).astype(jnp.bfloat16)
                 for k, shape in zip(ks, shapes))


def _grads(core, ops, weight):
    def loss(*operands):
        return (core(*operands).astype(jnp.float32)
                * weight.astype(jnp.float32)).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*ops)


@pytest.mark.parametrize("heads", [32, 8], ids=["kanana2", "ling3"])
def test_five_gradients_against_the_float32_core(tpu_backend, heads):
    seq = 2048
    *ops, weight = _operands(seq, heads, seed=21)
    blocks = kernels.blocks_for(seq)
    got = _grads(lambda *a: kernels.latent_attention_kernels(*a, *blocks),
                 ops, weight)
    with jax.default_matmul_precision("highest"):
        want = _grads(_latent_reference,
                      [x.astype(jnp.float32) for x in ops], weight)
    errors = {}
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16, name
        errors[name] = float(jnp.abs(g.astype(jnp.float32) - w).max()
                             / jnp.abs(w).max())
    print("rel err of the largest entry:", errors)
    assert max(errors.values()) < TOLERANCE, errors


@pytest.mark.parametrize("heads", [32, 8], ids=["kanana2", "ling3"])
def test_a_second_backward_sees_nothing_of_the_first(tpu_backend, heads):
    """Nothing zero-fills the accumulators: key block 0 adds to zeros and
    every later key block to what the one before wrote."""
    seq = 16384
    *ops, weight = _operands(seq, heads, seed=22)
    blocks = kernels.blocks_for(seq)
    assert blocks == kernels.BLOCKS
    _, kept = jax.jit(
        lambda *a: kernels._latent_fwd(*a, *blocks, False))(*ops)
    backward = jax.jit(lambda kept, grad: kernels._latent_bwd(
        *blocks, False, kept, grad))
    first = backward(kept, weight)
    # other gradients pass through the same HBM between the two
    jax.block_until_ready(backward(kept, -weight))
    second = backward(kept, weight)
    for name, a, b in zip(NAMES, first, second):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)
