"""The chunked gated delta rule (``ops/linear_attention.py::kda``) against
the recurrence a token at a time, on the CPU in float32: the forward pass
and the gradients of all five operands, over chunk sizes, a strong decay
(the hazard: ``exp(G_i - G_j)`` factorised overflows float32 inside one
chunk), betas near 0 and near 2, lengths of several chunks and lengths that
are no multiple of the chunk."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.linear_attention import SUB, kda, kda_recurrent
from shared_memo import shared_memo

OPERANDS = ("q", "k", "v", "g", "beta")


def _operands(seq, g_min=-1.0, beta_logit=0.0, heads=2, dim=8, seed=0,
              batch=2):
    """As the model hands them over: q and k of unit length (q times
    ``dim^-1/2``), a log decay in ``[g_min, 0]``, beta in (0, 2)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, seq, heads, dim)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = g_min * jax.random.uniform(keys[3], shape)
    beta = 2.0 * jax.nn.sigmoid(
        beta_logit + jax.random.normal(keys[4], shape[:3]))
    return q, k, v, g, beta


def _chunked(chunk):
    return functools.partial(kda, chunk=chunk)


@shared_memo
def _gradients(chunk, seq=128, **operands):
    """The gradients of all five operands, one program: the chunked rule's
    at ``chunk``, the recurrence's at ``chunk`` None."""
    fn = _chunked(chunk) if chunk else kda_recurrent
    return jax.jit(jax.grad(_weighted(fn), argnums=range(5)))(
        *_operands(seq, **operands))


def _weighted(fn):
    """A scalar of the output that weighs every element differently, so
    that a gradient which swaps two positions or channels does not pass."""
    def loss(*operands):
        out = fn(*operands)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return jnp.sum(out * weights)
    return loss


@pytest.mark.parametrize("seq,chunk", [
    (64, 16), (128, 64), (96, 96), (192, 64), (48, 8), (80, 32), (100, 32),
    (7, 64)])
def test_chunked_equals_the_recurrence(seq, chunk):
    operands = _operands(seq)
    want = jax.jit(kda_recurrent)(*operands)
    got = jax.jit(_chunked(chunk))(*operands)
    assert got.shape == want.shape == operands[2].shape
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 64, 128])
@pytest.mark.parametrize("operand", OPERANDS)
def test_gradient_of_each_operand(chunk, operand):
    """Chunks of 16 (one sub-block a chunk), 64 (four) and one chunk the
    whole sequence, a length of several chunks but for the last."""
    at = OPERANDS.index(operand)
    want, got = _gradients(None, seed=1)[at], _gradients(chunk, seed=1)[at]
    assert float(jnp.abs(want).max()) > 1e-3        # the operand matters
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-5)


@pytest.mark.parametrize("g_min", [-5.0, -20.0, -80.0])
@pytest.mark.parametrize("chunk", [16, 64])
def test_a_strong_decay_neither_overflows_nor_is_clamped(g_min, chunk):
    """``g`` down to -20 a token is -1280 over a chunk of 64: ``exp(1280)``
    is no float32.  Forward and all five gradients stay finite and equal
    the recurrence's, which clamps nothing."""
    operands = _operands(128, g_min=g_min, seed=2)
    np.testing.assert_allclose(
        jax.jit(_chunked(chunk))(*operands), jax.jit(kda_recurrent)(*operands),
        rtol=0, atol=2e-5)
    want = _gradients(None, g_min=g_min, seed=2)
    got = _gradients(chunk, g_min=g_min, seed=2)
    for name, g, w in zip(OPERANDS, got, want):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4, err_msg=name)


def test_the_factorised_form_would_overflow_here():
    """What the sub-blocks are for: at this decay ``exp(-G_j)`` alone is
    infinite in float32 before a chunk of 64 ends."""
    g = _operands(128, g_min=-20.0, seed=2)[3]
    running = jnp.cumsum(g[:, :64], axis=1)
    assert bool(jnp.isinf(jnp.exp(-running)).any())
    assert 64 % SUB == 0


@pytest.mark.parametrize("beta_logit,low,high", [
    (-8.0, 0.0, 0.02), (8.0, 1.98, 2.0)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_beta_near_its_ends(beta_logit, low, high, chunk):
    """Near 0 the state hardly moves; near 2 ``I - beta k k^T`` reflects
    (an eigenvalue of -1): the triangular system is then far from the
    identity."""
    operands = _operands(128, beta_logit=beta_logit, seed=3)
    beta = operands[4]
    assert low <= float(beta.min()) and float(beta.max()) <= high
    np.testing.assert_allclose(
        jax.jit(_chunked(chunk))(*operands), jax.jit(kda_recurrent)(*operands),
        rtol=0, atol=5e-5)
    want = _gradients(None, beta_logit=beta_logit, seed=3)
    got = _gradients(chunk, beta_logit=beta_logit, seed=3)
    for name, g, w in zip(OPERANDS, got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-4, err_msg=name)


def test_no_decay_and_beta_one_is_the_plain_delta_rule():
    """``g = 0`` and ``beta = 1``: the newest value stored under a key is
    read back exactly by that key."""
    q, k, v, g, beta = _operands(32, seed=4, heads=1, batch=1)
    out = jax.jit(_chunked(16))(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    np.testing.assert_allclose(out, v, rtol=0, atol=1e-5)


def test_the_state_is_carried_in_float32_beside_bfloat16_operands():
    """bfloat16 operands give a bfloat16 result close to the float32 one:
    the decay, its running sums, the solve and the state between chunks do
    not take the operands' dtype."""
    operands = _operands(256, seed=5, dim=16)
    want = jax.jit(kda_recurrent)(*operands)
    low = tuple(t.astype(jnp.bfloat16) for t in operands[:3]) + operands[3:]
    chunked = jax.jit(_chunked(64))
    got = chunked(*low)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(err.max()) < 0.06 and float(err.mean()) < 0.006
    text = chunked.lower(*low).as_text()
    # the solve is float32's, and the scan's carry is a float32 state
    assert "_solve_triangular" in text and "x16x16xf32>" in text


def test_what_stands_in_the_compiled_step():
    """One scan over the chunks carries the state; nothing of the extent
    ``seq x seq`` is built."""
    operands = _operands(512, dim=8)
    text = jax.jit(lambda *o: kda(*o, chunk=64)).lower(*operands).as_text()
    assert "512x512" not in text and "stablehlo.while" in text
