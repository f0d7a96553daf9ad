"""The selected attention's Pallas kernels on the REAL TPU (compiled by
Mosaic, not interpreted) against the ``jax.numpy`` body, at the shapes of
``keyevl2_30b_1of8.steady``: a block of 512 queries, 32 heads on 4 kv heads
of 128, bfloat16; and ``indexed_sparse_attention`` whole at S 8192."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops.pallas.tuning import selected_tiling

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
            out, kl = attend(q, k, v, *index, keep)
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
