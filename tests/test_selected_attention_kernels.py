"""The selected attention's Pallas kernels
(``ops/pallas/selected_attention.py``) in the interpreter on the CPU,
against the ``jax.numpy`` body they stand in for
(``ops/attention.py::_attend_selected``): one block of queries, float32, so
the two agree to rounding; and which of the two ``indexed_sparse_attention``
takes, with the ``attention.path`` event that says so."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops.pallas import selected_attention as kernels
from dlrover_tpu.ops.pallas.tuning import selected_tiling
from shared_memo import shared_memo

TILE = 128   # keys a kernel tile in these cases: every block has several
J, C = 2, 16


def _causal(first, queries, keys):
    return (jnp.arange(first, first + queries)[:, None]
            >= jnp.arange(keys))[None]


def _selected(topk, levels=0):
    """A mask from ``select_top_keys`` on random index scores, ``levels``
    distinct values where ties are planted."""
    def make(key, batch, first, queries, keys):
        scores = jax.random.normal(key, (batch, queries, keys))
        if levels:
            scores = jnp.round(scores * levels) / levels
        keep, _ = ops.select_top_keys(
            scores, _causal(first, queries, keys), topk)
        return keep
    return make


def _causal_only(key, batch, first, queries, keys):
    return jnp.broadcast_to(_causal(first, queries, keys),
                            (batch, queries, keys))


def _an_empty_tile(key, batch, first, queries, keys):
    """No key of the FIRST tile kept by any query, none of the second by
    the first half of the queries: rows that meet a tile with nothing in
    it before and after their first kept key."""
    keep = jax.random.bernoulli(key, 0.2, (batch, queries, keys))
    keep = keep.at[:, :, :TILE].set(False).at[:, :, 2 * TILE + 3].set(True)
    return keep.at[:, : queries // 2, TILE: 2 * TILE].set(False)


CASES = {
    # name: (heads, kv heads, first query, queries, keys, mask, tiles of the
    # kernel of the heads' mean over tiles of the other two)
    "gqa_8_2_selects": (8, 2, 256, 128, 384, _selected(96), 3),
    "mha_4_4_selects": (4, 4, 256, 128, 384, _selected(96), 1),
    "first_block_causal": (8, 2, 0, 128, 128, _causal_only, 2),
    "keys_under_topk_causal": (4, 2, 128, 128, 256, _causal_only, 1),
    "a_tile_with_no_kept_key": (4, 2, 256, 128, 384, _an_empty_tile, 2),
    "planted_ties": (4, 2, 256, 128, 384, _selected(96, levels=2), 1),
    "one_kv_head": (4, 1, 128, 128, 256, _selected(64), 2),
}
QUANTITIES = ("out", "kl", "q", "k", "v", "index_q", "index_k", "index_w")


@shared_memo
def _both(case):
    """quantity -> (kernels, jax.numpy) for one case: the outputs and the
    gradients of a loss that weighs every output element differently."""
    heads, kv_heads, first, queries, keys, mask, mean_tiles = CASES[case]
    batch, head_dim = 2, kernels.KERNEL_HEAD_DIM
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 8)
    operands = (
        jax.random.normal(ks[0], (batch, queries, heads, head_dim)),
        jax.random.normal(ks[1], (batch, keys, kv_heads, head_dim)),
        jax.random.normal(ks[2], (batch, keys, kv_heads, head_dim)),
        jax.random.normal(ks[3], (batch, queries, J, C)),
        jax.random.normal(ks[4], (batch, keys, C)),
        jax.random.normal(ks[5], (batch, queries, J)),
    )
    keep = mask(ks[6], batch, first, queries, keys)
    assert bool(keep.any(-1).all()), "a query with no key at all"
    weights = jax.random.normal(ks[7], operands[0].shape)

    def run(attend):
        def loss(q, k, v, *index):
            out, kl = attend(q, k, v, ops._index_scores(*index), keep)
            return (out * weights).sum() + 3.0 * kl, (out, kl)

        (_, (out, kl)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(*operands)
        return dict(zip(QUANTITIES, (out, kl) + grads))

    got = run(functools.partial(
        ops._attend_selected_kernels, tiling=(TILE, TILE * mean_tiles), interpret=True))
    want = run(ops._attend_selected)
    return {name: (got[name], want[name]) for name in QUANTITIES}


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_against_the_jnp_body(case, quantity):
    got, want = _both(case)[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "nothing to compare"
    np.testing.assert_allclose(got, want, atol=2e-5 * max(scale, 1.0), rtol=0)


def test_the_target_is_the_mean_over_heads_and_carries_no_gradient():
    heads, kv_heads, first, queries, keys, mask, _ = CASES["gqa_8_2_selects"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, queries, heads, 128))
    k = jax.random.normal(ks[1], (1, keys, kv_heads, 128))
    v = jax.random.normal(ks[2], (1, keys, kv_heads, 128))
    keep = mask(ks[3], 1, first, queries, keys)
    _, target = kernels.selected_attention(q, k, v, keep, (TILE, 2 * TILE), True)
    _, want = ops._dense_selected(q, k, v, keep)
    np.testing.assert_allclose(target, want, atol=1e-6)
    np.testing.assert_allclose(target.sum(-1), 1.0, atol=1e-5)
    assert not bool(jnp.any(jnp.where(keep, 0.0, target)))
    grads = jax.grad(
        lambda *x: kernels.selected_attention(
            *x, keep, (TILE, 2 * TILE), True)[1].sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert all(not bool(jnp.any(g)) for g in grads)


@pytest.mark.parametrize("keys, block_kv, tile", [
    (512, 1024, 512), (1536, 1024, 768), (2560, 1024, 640),
    (5632, 1024, 512), (8192, 1024, 1024), (8192, 2048, 2048),
    (384, 128, 128)])
def test_a_tile_divides_the_keys(keys, block_kv, tile):
    assert kernels.kv_tile(keys, block_kv) == tile


@pytest.mark.parametrize("backend, block, head_dim, heads, kv_heads, path", [
    ("tpu", 512, 128, 32, 4, "pallas"),
    ("tpu", 128, 128, 4, 4, "pallas"),
    ("cpu", 512, 128, 32, 4, "jnp"),
    ("tpu", 512, 64, 32, 4, "jnp"),     # a head is not a 128-lane block
    ("tpu", 8, 128, 4, 2, "jnp"),       # the tests' tiny blocks
    ("tpu", 512, 128, 6, 4, "jnp"),
])
def test_the_path_follows_backend_and_shape(backend, block, head_dim, heads,
                                            kv_heads, path):
    assert ops.selected_attend_path(
        backend, block, head_dim, heads, kv_heads) == path


def _whole(seq, heads, kv_heads, head_dim):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    return (jax.random.normal(ks[0], (1, seq, heads, head_dim)),
            jax.random.normal(ks[1], (1, seq, kv_heads, head_dim)),
            jax.random.normal(ks[2], (1, seq, kv_heads, head_dim)),
            jax.random.normal(ks[3], (1, seq, J, C)),
            jax.random.normal(ks[4], (1, seq, C)),
            jax.random.normal(ks[5], (1, seq, J)))


def _records(monkeypatch):
    records = []
    monkeypatch.setattr(
        ops.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    return records


def test_on_the_cpu_the_event_says_jnp(monkeypatch):
    records = _records(monkeypatch)
    ops.indexed_sparse_attention(*_whole(256, 4, 2, 128), topk=96, block=128)
    assert records == [("attention.path", dict(
        impl="indexed_sparse", seq=256, head_dim=128, heads=4, topk=96,
        index_heads=J, index_dim=C, block=128,
        select="threshold_by_counting", attend="jnp", index="jnp"))]


def test_on_a_tpu_the_whole_sequence_goes_through_the_kernels(monkeypatch):
    """``indexed_sparse_attention`` as a TPU backend would run it (the
    kernels in the interpreter), against the ``jax.numpy`` path: blocks
    under ``topk`` and blocks that select, outputs and gradients."""
    operands = _whole(512, 4, 2, 128)

    def run():
        def loss(*xs):
            out, index_loss, low = ops.indexed_sparse_attention(
                *xs, topk=160, block=128)
            return jnp.sin(out).sum() + 5.0 * index_loss, (out, index_loss, low)

        return jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True)(*operands)

    (_, want), want_grads = run()
    records = _records(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        ops, "_attend_selected_kernels",
        functools.partial(ops._attend_selected_kernels, interpret=True))
    (_, got), got_grads = run()
    assert records[0][1]["attend"] == "pallas"
    assert records[0][1]["index"] == "jnp"   # 2 index heads of 16: no kernel
    assert (records[0][1]["block_kv"], records[0][1]["mean_block_kv"]
            ) == selected_tiling(128, 128)
    for a, b in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(a, b, atol=3e-5 * max(
            1.0, float(jnp.abs(b).max())), rtol=0)
    assert float(got[2]) == float(want[2])
