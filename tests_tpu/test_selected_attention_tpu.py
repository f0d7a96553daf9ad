"""The selected attention's Pallas kernels on the REAL TPU (compiled by
Mosaic, not interpreted) against the ``jax.numpy`` body, at the shapes of
``keyevl2_30b_1of8.steady``: a block of 512 queries, 32 heads on 4 kv heads
of 128, bfloat16; the index scores' kernels
(``ops/pallas/index_scores.py``) against theirs, 16 index heads of 64; and
``indexed_sparse_attention`` whole at S 8192."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops.pallas.tuning import index_tiling, selected_tiling

HEADS, KV_HEADS, HEAD_DIM, BLOCK, TOPK = 32, 4, 128, 512, 2048
J, C = 16, 64


def _operands(seq, first, last, seed):
    """One block's operands (``first`` None: the whole sequence's), with
    q and k normalised a head as the model's are."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    queries = slice(None) if first is None else slice(first, last)

    def unit(x):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)

    def draw(key, *shape):
        return jax.random.normal(key, (1, seq) + shape, jnp.float32)

    q = unit(draw(ks[0], HEADS, HEAD_DIM))[:, queries]
    k = unit(draw(ks[1], KV_HEADS, HEAD_DIM))
    v = draw(ks[2], KV_HEADS, HEAD_DIM)
    index_q = draw(ks[3], J, C)[:, queries]
    index_k = draw(ks[4], C)
    index_w = draw(ks[5], J)[:, queries]
    return tuple(x.astype(jnp.bfloat16)
                 for x in (q, k, v, index_q, index_k, index_w))


def _close(got, want, atol, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    np.testing.assert_allclose(got, want, atol=atol, rtol=0, err_msg=what)


@pytest.mark.parametrize("last", [2560, 8192], ids=["early", "late"])
def test_a_block_forward_and_backward(tpu_backend, last):
    first = last - BLOCK
    q, k, v, index_q, index_k, index_w = _operands(last, first, last, last)
    weight = (J * C) ** -0.5
    index = (index_q, index_k, index_w.astype(jnp.float32) * weight)
    causal = (jnp.arange(first, last)[:, None] >= jnp.arange(last))[None]
    keep, _ = jax.jit(functools.partial(ops.select_top_keys, topk=TOPK))(
        ops._index_scores(*index), causal)
    weights = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)

    def run(attend):
        def loss(q, k, v, *index):
            out, kl = attend(q, k, v, ops._index_scores(*index), keep)
            return (out.astype(jnp.float32) * weights).sum() + kl, (out, kl)

        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(q, k, v, *index)

    (_, (out, kl)), grads = run(functools.partial(
        ops._attend_selected_kernels,
        tiling=selected_tiling(BLOCK, HEAD_DIM)))
    (_, (want_out, want_kl)), want_grads = run(ops._attend_selected)
    # bfloat16 operands and results, float32 accumulation on both sides:
    # they differ by the rounding of outputs and of the probabilities
    _close(out, want_out, 2e-2, "out")
    assert abs(float(kl) - float(want_kl)) <= 2e-3 * abs(float(want_kl))
    for name, got, want in zip(
            ("q", "k", "v", "index_q", "index_k", "index_w"),
            grads, want_grads):
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        _close(got, want, 4e-2 * scale, f"gradient of {name}")


@pytest.mark.parametrize("last", [512, 2560, 8192],
                         ids=["first", "early", "late"])
def test_the_index_scores_and_their_gradient(tpu_backend, last):
    """The kernels against the ``jax.numpy`` body at Keye's widths: the
    same bfloat16 operands to the matrix unit and float32 after it on both
    sides, so ``I`` differs by the order of the sum over the heads; the
    gradients besides by the rounding of ``dI w`` to bfloat16, which the
    ``jax.numpy`` body's products do too."""
    *_, index_q, index_k, index_w = _operands(last, last - BLOCK, last, last)
    index = (index_q, index_k,
             index_w.astype(jnp.float32) * (J * C) ** -0.5)
    weights = jax.random.normal(
        jax.random.PRNGKey(2), (1, BLOCK, last), jnp.float32)

    def run(scores):
        def loss(*index):
            out = scores(*index)
            return (out * weights).sum(), out

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*index)

    (_, got), grads = run(functools.partial(
        ops._index_scores_kernels, tiling=index_tiling(BLOCK, C)))
    (_, want), want_grads = run(ops._index_scores)
    assert got.dtype == jnp.float32
    _close(got, want, 1e-5 * float(jnp.abs(want).max()), "I")
    for name, a, b in zip(("q_I", "k_I", "w"), grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        _close(a, b, 2e-2 * scale, f"gradient of {name}")


def test_the_whole_sequence_by_index_kernels_and_jnp(tpu_backend):
    """``indexed_sparse_attention`` at S 8192 with the index scores from
    the kernels and from ``jax.numpy``, the attention through its kernels
    on both sides.  The selection hangs on the scores' last bits, so a few
    kept keys differ at the margin: the output and the loss agree to the
    operands' rounding, the low-margin share to a hundredth."""
    operands = _operands(8192, None, None, 11)
    assert ops.index_scores_path(jax.default_backend(), BLOCK, J, C) == "pallas"

    def run():
        return jax.jit(functools.partial(
            ops.indexed_sparse_attention, topk=TOPK, block=BLOCK))(*operands)

    out, index_loss, low = run()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ops, "index_scores_path", lambda *a: "jnp")
        want_out, want_loss, want_low = run()
    differ = np.abs(np.asarray(out, np.float32)
                    - np.asarray(want_out, np.float32))
    assert float(np.median(differ)) <= 1e-3
    assert float(differ.max()) <= 0.1
    assert abs(float(index_loss) - float(want_loss)) <= 5e-3 * float(want_loss)
    assert abs(float(low) - float(want_low)) <= 0.01


def test_the_whole_sequence(tpu_backend):
    operands = _operands(8192, None, None, 7)
    assert ops.selected_attend_path(
        jax.default_backend(), BLOCK, HEAD_DIM, HEADS, KV_HEADS) == "pallas"
    def run():  # a new function each call: traced anew
        return jax.jit(functools.partial(
            ops.indexed_sparse_attention, topk=TOPK, block=BLOCK))(*operands)

    out, index_loss, low = run()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ops, "selected_attend_path", lambda *a: "jnp")
        want_out, want_loss, want_low = run()
    _close(out, want_out, 2e-2, "out")
    assert abs(float(index_loss) - float(want_loss)) <= 2e-3 * float(want_loss)
    # the selection is the jax.numpy code on both sides
    assert float(low) == float(want_low)
