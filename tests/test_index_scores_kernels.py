"""The index scores' Pallas kernels (``ops/pallas/index_scores.py``) in the
interpreter on the CPU, against the ``jax.numpy`` body they stand in for
(``ops/attention.py::_index_scores``) and ``jax.grad`` through it: one block
of queries, float32, so the two agree to rounding; which of the two
``indexed_sparse_attention`` takes, with the ``attention.path`` event that
says so; and the whole attention through both."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops.pallas import index_scores as kernels
from dlrover_tpu.ops.pallas.tuning import index_tiling, selected_tiling
from shared_memo import shared_memo

TILE = 128   # keys a kernel tile in these cases


def _normal(key, shape):
    return jax.random.normal(key, shape)


def _a_tile_all_negative(operands):
    """Every product of the SECOND tile's keys negative, whatever the
    head: positive queries, negative keys there.  The relu leaves nothing
    of the tile, forward or backward."""
    index_q, index_k, index_w = operands
    index_q = jnp.abs(index_q)
    index_k = index_k.at[:, TILE: 2 * TILE].set(
        -jnp.abs(index_k[:, TILE: 2 * TILE]))
    return index_q, index_k, index_w


def _as_bfloat16(operands):
    """The operands as the model gives them."""
    index_q, index_k, index_w = operands
    return (index_q.astype(jnp.bfloat16), index_k.astype(jnp.bfloat16),
            index_w)


CASES = {
    # name: (batch, queries, index heads, their size, keys, column blocks
    # a turn of the loop, what is planted, tolerance over the largest value)
    "16_heads_of_64_one_tile": (2, 128, 16, 64, 128, 1, None, 2e-6),
    "16_heads_of_64_keys_512": (2, 128, 16, 64, 512, 1, None, 2e-6),
    "16_heads_of_64_keys_2560": (1, 128, 16, 64, 2560, 2, None, 2e-6),
    "4_heads_of_32_one_column_block": (2, 128, 4, 32, 384, 1, None, 2e-6),
    "8_heads_of_32_unrolled": (2, 256, 8, 32, 256, 2, None, 2e-6),
    "a_tile_with_every_product_negative": (
        2, 128, 16, 64, 384, 4, _a_tile_all_negative, 2e-6),
    "bfloat16_operands": (2, 128, 16, 64, 384, 1, _as_bfloat16, 2e-2),
}
QUANTITIES = ("I", "dq_I", "dk_I", "dw")


@shared_memo
def _both(case):
    """quantity -> (kernels, jax.numpy): the scores and the gradients of a
    loss that weighs every score differently; ``w`` of either sign."""
    batch, queries, heads, dim, keys, unroll, plant, _ = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    operands = (_normal(ks[0], (batch, queries, heads, dim)),
                _normal(ks[1], (batch, keys, dim)),
                _normal(ks[2], (batch, queries, heads)))
    assert bool((operands[2] < 0).any()) and bool((operands[2] > 0).any())
    if plant:
        operands = plant(operands)
    weights = _normal(ks[3], (batch, queries, keys))

    def run(scores):
        def loss(*xs):
            out = scores(*xs)
            return (out * weights).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*operands)
        return dict(zip(QUANTITIES, (out,) + grads))

    got = run(functools.partial(
        kernels.index_scores, tiling=(TILE, unroll), interpret=True))
    want = run(ops._index_scores)
    return {name: (got[name], want[name]) for name in QUANTITIES}


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_against_the_jnp_body(case, quantity):
    got, want = _both(case)[quantity]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    scale = float(np.abs(want).max())
    assert scale > 0, "nothing to compare"
    np.testing.assert_allclose(
        got, want, atol=CASES[case][-1] * max(scale, 1.0), rtol=0)


def test_a_tile_with_every_product_negative_scores_nothing():
    scores, _ = _both("a_tile_with_every_product_negative")["I"]
    dk, _ = _both("a_tile_with_every_product_negative")["dk_I"]
    assert not bool(jnp.any(scores[:, :, TILE: 2 * TILE]))
    assert not bool(jnp.any(dk[:, TILE: 2 * TILE]))
    assert bool(jnp.any(scores[:, :, :TILE]))


def test_column_blocks_must_divide_by_the_turn():
    q, k, w = (jnp.zeros(s) for s in ((1, 128, 6, 64), (1, 128, 64), (1, 128, 6)))
    with pytest.raises(ValueError, match="column blocks"):
        kernels.index_scores(q, k, w, (TILE, 2), True)


@pytest.mark.parametrize("backend, block, heads, dim, path", [
    ("tpu", 512, 16, 64, "pallas"),
    ("tpu", 128, 4, 32, "pallas"),
    ("tpu", 128, 1, 128, "pallas"),
    ("cpu", 512, 16, 64, "jnp"),
    ("gpu", 512, 16, 64, "jnp"),
    ("tpu", 512, 2, 16, "jnp"),      # the heads do not fill a column block
    ("tpu", 512, 16, 48, "jnp"),     # a head does not divide the lanes
    ("tpu", 512, 3, 64, "jnp"),      # an odd head: half a column block
    ("tpu", 16, 16, 64, "jnp"),      # the tests' tiny blocks
])
def test_the_path_follows_backend_and_shape(backend, block, heads, dim, path):
    assert ops.index_scores_path(backend, block, heads, dim) == path


@pytest.mark.parametrize("table, want", [
    ({"index_q512_c64_kv": {"block_kv": 4096, "unroll": 2}}, (4096, 2)),
    ({"index_q512_c64_kv": {"block_kv": 1024}}, (1024, 1)),
    ({"index_q512_c64_kv": {"block_kv": "wide"}}, (512, 1)),
    ({"index_q512_c64_kv": {"block_kv": 1024, "unroll": 0}}, (512, 1)),
    ({}, None),
])
def test_the_tile_comes_from_the_table(tmp_path, monkeypatch, table, want):
    """A user's table over the shipped one; a malformed entry reads as the
    untuned default, and the shipped entry is what the cell runs."""
    import json

    from dlrover_tpu.ops.pallas import tuning

    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv("DLROVER_TPU_FA_TUNING", str(path))
    tuning._load_one.cache_clear()
    try:
        shipped = tuning._load_one(tuning._SHIPPED)["index_q512_c64_kv"]
        assert index_tiling(512, 64) == (
            want or (shipped["block_kv"], shipped["unroll"]))
        assert index_tiling(256, 32) == (512, 1)   # no entry: the default
    finally:
        tuning._load_one.cache_clear()


J, C = 4, 32   # the least indexer the kernels take: one column block


def _whole(seq, heads=4, kv_heads=2, head_dim=128, index=(J, C)):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    return (jax.random.normal(ks[0], (1, seq, heads, head_dim)),
            jax.random.normal(ks[1], (1, seq, kv_heads, head_dim)),
            jax.random.normal(ks[2], (1, seq, kv_heads, head_dim)),
            jax.random.normal(ks[3], (1, seq) + index),
            jax.random.normal(ks[4], (1, seq, index[1])),
            jax.random.normal(ks[5], (1, seq, index[0])))


def _records(monkeypatch):
    records = []
    monkeypatch.setattr(
        ops.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    return records


def _as_on_a_tpu(monkeypatch):
    """The backend patched, both sets of kernels in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("_attend_selected_kernels", "_index_scores_kernels"):
        monkeypatch.setattr(
            ops, name, functools.partial(getattr(ops, name), interpret=True))


def test_on_the_cpu_the_event_says_jnp(monkeypatch):
    records = _records(monkeypatch)
    ops.indexed_sparse_attention(*_whole(256), topk=96, block=128)
    assert records == [("attention.path", dict(
        impl="indexed_sparse", seq=256, head_dim=128, heads=4, topk=96,
        index_heads=J, index_dim=C, block=128,
        select="threshold_by_counting", attend="jnp", index="jnp"))]


def test_on_a_tpu_the_event_says_pallas_and_the_tile(monkeypatch):
    records = _records(monkeypatch)
    _as_on_a_tpu(monkeypatch)
    ops.indexed_sparse_attention(*_whole(256), topk=96, block=128)
    (name, attrs), (kept_name, kept) = records
    assert name == "attention.path"
    # beside it, what a rematerialised layer keeps of the attention's
    # kernels (tests/test_remat_kept.py); the index kernels name nothing
    assert kept_name == "remat.kept" and kept["core"] == "indexed_sparse"
    assert attrs["index"] == "pallas" and attrs["attend"] == "pallas"
    assert attrs["index_block_kv"] == index_tiling(128, C)[0]
    assert (attrs["block_kv"], attrs["mean_block_kv"]) == selected_tiling(
        128, 128)


def test_on_a_tpu_at_other_heads_the_event_says_jnp(monkeypatch):
    """An indexer the kernels do not take beside an attention they do."""
    records = _records(monkeypatch)
    _as_on_a_tpu(monkeypatch)
    ops.indexed_sparse_attention(
        *_whole(256, index=(2, 16)), topk=96, block=128)
    attrs = records[0][1]
    assert attrs["index"] == "jnp" and attrs["attend"] == "pallas"
    assert "index_block_kv" not in attrs


WHOLE = ("out", "index_loss", "low_margin_share", "q", "k", "v", "index_q",
         "index_k", "index_w")


@shared_memo
def _whole_both():
    """quantity -> (kernels, jax.numpy) of ``indexed_sparse_attention`` at
    S 512 by blocks of 128, ``topk`` 160: a block under ``topk`` and three
    that select; the loss weighs the indexer's loss in."""
    operands = _whole(512)

    def run():
        def loss(*xs):
            out, index_loss, low = ops.indexed_sparse_attention(
                *xs, topk=160, block=128)
            return jnp.sin(out).sum() + 5.0 * index_loss, (out, index_loss, low)

        (_, aux), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(*operands)
        return dict(zip(WHOLE, aux + grads))

    want = run()
    with pytest.MonkeyPatch.context() as patch:
        records = _records(patch)
        _as_on_a_tpu(patch)
        got = run()
    assert records[0][1]["index"] == "pallas"
    return {name: (got[name], want[name]) for name in WHOLE}


@pytest.mark.parametrize("quantity", WHOLE)
def test_the_whole_attention_through_kernels_and_jnp(quantity):
    got, want = _whole_both()[quantity]
    if quantity == "low_margin_share":   # the same scores, the same search
        assert float(got) == float(want)
        return
    assert float(jnp.abs(want).max()) > 0, "nothing to compare"
    np.testing.assert_allclose(got, want, atol=3e-5 * max(
        1.0, float(jnp.abs(want).max())), rtol=0)


@pytest.mark.parametrize("on_a_tpu", [False, True], ids=["jnp", "kernels"])
def test_the_loss_reaches_the_indexer_and_nothing_else(monkeypatch, on_a_tpu):
    """The indexer's loss is taught from the attention's probabilities under
    ``stop_gradient``: its gradient reaches ``q_I``, ``k_I`` and ``w`` (the
    indexer's three projections) and is exactly zero at q, k and v."""
    if on_a_tpu:
        _as_on_a_tpu(monkeypatch)

    def index_loss(*xs):
        return ops.indexed_sparse_attention(*xs, topk=160, block=128)[1]

    grads = jax.jit(jax.grad(index_loss, argnums=tuple(range(6))))(
        *_whole(512))
    for name, grad in zip(WHOLE[3:], grads):
        if name.startswith("index_"):
            assert bool(jnp.any(grad)), name
            assert bool(jnp.all(jnp.isfinite(grad))), name
        else:
            assert not bool(jnp.any(grad)), name


def test_a_pass_computes_a_blocks_scores_once(monkeypatch):
    """Selection and loss read the same ``I``: one call a block in the
    traced forward, on either path."""
    calls = []
    real = ops._index_scores

    def counted(*xs):
        calls.append(xs[1].shape[1])
        return real(*xs)

    monkeypatch.setattr(ops, "_index_scores", counted)
    ops._attend_block.clear_cache()
    try:
        jax.make_jaxpr(functools.partial(
            ops.indexed_sparse_attention, topk=160, block=128))(*_whole(512))
    finally:
        ops._attend_block.clear_cache()
    assert calls == [128, 256, 384, 512]
