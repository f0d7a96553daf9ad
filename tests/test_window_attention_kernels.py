"""The FA2 kernels under a causal window (``ops/pallas/flash_attention.py``
with ``window``): forward, dQ and dK/dV in the interpreter against the
reference core under the same band, over windows under, at and over a
block, not a multiple of one, at and over the sequence, unequal blocks,
groups of 6 and 8 and head sizes 64 and 128.  Every case through the
STREAMED kernels, called at the kernel level (the path wide bands take);
every case the rule sends to the band kernels (one visit a query block)
through those too (a window of one position, of a block, a fetch of two
blocks, an odd head count among them), with the band's own cases: rows
taken a part of the block at a time, the first block's clamped fetch;
the two paths against each other; the rule from shapes; the streamed
axes' lengths; and ``window=None`` lowering to the kernels as they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import (
    attention_path, band_mask, causal_attention, flash_attention,
    reference_attention)
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.ops.pallas.flash_attention import (
    ONE_VISIT, STREAMED, band_path, band_pairs, band_record, band_steps,
    band_tiles, band_vmem_bytes, kernel_takes, pallas_flash_attention)
from dlrover_tpu.ops.pallas.tuning import tuned_blocks
from shared_memo import shared_memo

# (seq, window, block_q, block_kv, heads, kv_heads, head_dim)
CASES = {
    "under_a_block": (256, 24, 64, 64, 2, 1, 64),
    "a_block": (256, 64, 64, 64, 2, 2, 64),
    "over_a_block_no_multiple": (256, 100, 64, 64, 2, 1, 128),
    "two_blocks_and_one": (256, 129, 64, 64, 2, 2, 64),
    "the_sequence": (128, 128, 64, 64, 2, 1, 64),
    "over_the_sequence": (128, 500, 64, 64, 2, 2, 64),
    "one_position": (128, 1, 64, 64, 2, 2, 64),
    "wide_q_blocks": (256, 40, 128, 64, 2, 1, 64),
    "wide_kv_blocks": (256, 70, 64, 128, 2, 1, 128),
    "groups_of_6_d128": (128, 48, 64, 64, 6, 1, 128),
    "groups_of_8_d64": (128, 48, 64, 64, 8, 1, 64),
    "groups_of_6_two_kv_heads_d64": (128, 70, 64, 64, 12, 2, 64),
    "odd_heads_d64": (128, 30, 64, 64, 3, 3, 64),
}


# what only the band kernels have: ``block_kv`` is the rows a query block
# takes at a time and the granule of the fetch beside its own
BAND_ONLY = {
    # two query blocks: the first one's fetch of earlier keys is clamped
    # at block 0 and masked whole, the second's is block 0 itself
    "one_over_a_block": (128, 65, 64, 64, 2, 2, 64),
    # the cell's: groups of 8, a head a block, 8 rows (a quarter) at a
    # time against 8 + 32 keys
    "groups_of_8_d128": (128, 32, 32, 8, 8, 1, 128),
    # groups of 2 across a block's two heads, half a block at a time
    "two_heads_a_block_d64": (128, 33, 64, 32, 4, 2, 64),
}
ALL_CASES = {**CASES, **BAND_ONLY}


def _takes_one_visit(case):
    seq, window, block_q, block_kv, _, _, head_dim = ALL_CASES[case]
    return band_path(seq, block_q, block_kv, window, head_dim) == ONE_VISIT


BAND_CASES = [case for case in ALL_CASES if _takes_one_visit(case)]
WHICH = ["out", "dq", "dk", "dv"]


def _qkv(seq, heads, kv_heads, head_dim):
    keys = jax.random.split(jax.random.PRNGKey(seq + heads + head_dim), 4)
    q = jax.random.normal(keys[0], (1, seq, heads, head_dim), jnp.float32)
    k = jax.random.normal(keys[1], (1, seq, kv_heads, head_dim), jnp.float32)
    v = jax.random.normal(keys[2], (1, seq, kv_heads, head_dim), jnp.float32)
    # heads of different sizes: a neighbour's lanes would show
    v = v * (1.0 + jnp.arange(kv_heads))[None, None, :, None]
    weight = jax.random.normal(keys[3], (1, seq, heads, head_dim), jnp.float32)
    return q, k, v, weight


def _through(core, q, k, v, weight):
    """``(out, dq, dk, dv)`` of ``sum(core(q, k, v) * weight)``."""
    def loss(q_, k_, v_):
        out = core(q_, k_, v_)
        return jnp.sum(out * weight), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


def _streamed(q, k, v, weight, block_q, block_kv, window):
    """The streamed kernels themselves, whatever the rule says of the
    shape: the forward with its LSE, then dQ and dK/dV of ``weight``."""
    out, lse = fa._streamed_forward(
        q, k, v, True, block_q, block_kv, True, True, window)
    return (out,) + fa._streamed_backward(
        q, k, v, out, lse, weight, True, block_q, block_kv, True, window)


@shared_memo
def _reference(case):
    """``(out, dq, dk, dv)`` of a case through the reference core."""
    seq, window, _, _, heads, kv_heads, head_dim = ALL_CASES[case]
    operands = _qkv(seq, heads, kv_heads, head_dim)
    return jax.jit(lambda: _through(
        lambda q_, k_, v_: reference_attention(
            q_, k_, v_, window=window), *operands))()


@shared_memo
def _streamed_kernels(case):
    """``(out, dq, dk, dv)`` of a case through the streamed kernels."""
    seq, window, block_q, block_kv, heads, kv_heads, head_dim = CASES[case]
    operands = _qkv(seq, heads, kv_heads, head_dim)
    return jax.jit(lambda: _streamed(*operands, block_q, block_kv, window))()


@shared_memo
def _band(case):
    """``(out, dq, dk, dv)`` of a case through the public entry, which the
    rule sends to the band kernels."""
    seq, window, block_q, block_kv, heads, kv_heads, head_dim = ALL_CASES[case]
    operands = _qkv(seq, heads, kv_heads, head_dim)
    return jax.jit(lambda: _through(
        lambda q_, k_, v_: pallas_flash_attention(
            q_, k_, v_, True, block_q, block_kv, True, window), *operands))()


def _assert_close(got, want, tol=2e-3):
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    # one position: the softmax of one score has no gradient to q and k,
    # and the kernel's is the rounding of dp - delta
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=tol, atol=tol)


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", list(CASES))
def test_windowed_kernels_match_the_reference(case, which):
    at = WHICH.index(which)
    _assert_close(_streamed_kernels(case)[at], _reference(case)[at])


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", BAND_CASES)
def test_band_kernels_match_the_reference(case, which):
    at = WHICH.index(which)
    _assert_close(_band(case)[at], _reference(case)[at])


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", [c for c in BAND_CASES if c in CASES])
def test_band_kernels_match_the_streamed_ones(case, which):
    """The same operands through both paths: a plain softmax over all of a
    row's scores is what the online form reaches step by step, so the two
    differ by float32 rounding, a hundredth of the reference's tolerance."""
    at = WHICH.index(which)
    _assert_close(_band(case)[at], _streamed_kernels(case)[at], tol=2e-5)


def test_which_cases_take_one_visit():
    """Nine of the thirteen first cases and every case of the band's own;
    the others: a band over the sequence, and key blocks that do not
    tile the query block."""
    assert set(BAND_CASES) == set(ALL_CASES) - {
        "the_sequence", "over_the_sequence", "wide_kv_blocks",
        "groups_of_6_two_kv_heads_d64"}


@pytest.mark.parametrize(
    "seq, block_q, block_kv, window, head_dim, path",
    [(16384, 512, 128, 512, 128, ONE_VISIT),     # the Laguna cell's
     (16384, 512, 512, 512, 128, ONE_VISIT),
     (16384, 1024, 512, 512, 128, ONE_VISIT),
     (16384, 512, 512, 513, 128, ONE_VISIT),     # back 512 still
     (16384, 512, 512, 1, 128, ONE_VISIT),       # the block's own keys
     (16384, 512, 1024, 512, 128, STREAMED),     # 1024 does not tile 512
     (16384, 512, 384, 512, 128, STREAMED),
     (32768, 512, 512, 4096, 128, STREAMED),     # Mistral's: 54 MiB
     (32768, 1024, 1024, 4096, 128, STREAMED),
     (512, 512, 512, 512, 128, STREAMED),        # a band over the sequence
     (1024, 512, 512, 514, 128, STREAMED),       # back 1024: 1536 keys
     (1024, 512, 512, 100000, 128, STREAMED),
     (1024, 512, 128, 100, 64, ONE_VISIT)],      # two heads a block
)
def test_the_rule_is_a_function_of_shapes(seq, block_q, block_kv, window,
                                          head_dim, path):
    assert band_path(seq, block_q, block_kv, window, head_dim) == path
    back, fetch = band_tiles(block_q, block_kv, window)
    assert window - 1 <= back < window - 1 + block_kv
    assert back % block_kv == 0 and back % fetch == 0 == block_q % fetch
    if path == ONE_VISIT:
        assert block_q + back <= seq
        assert band_vmem_bytes(block_q, block_kv, window,
                               head_dim) <= fa.BAND_VMEM_LIMIT_BYTES


def test_the_budgets_arithmetic_at_the_cells_shape():
    """The numbers beside ``BAND_VMEM_LIMIT_BYTES``: 21 MiB at whole blocks
    of 512, 19.5 as shipped, under the limit with half as much again; the
    count falls with the rows taken at a time and rises with the
    window."""
    MiB = 1024 * 1024
    assert band_vmem_bytes(512, 512, 512, 128) == 21 * MiB
    assert band_vmem_bytes(*tuned_blocks(16384, 128, 512), 512,
                           128) == 19.5 * MiB
    assert 1.5 * 21 * MiB <= fa.BAND_VMEM_LIMIT_BYTES
    assert band_vmem_bytes(512, 512, 1024, 128) > band_vmem_bytes(
        512, 512, 512, 128) > band_vmem_bytes(512, 256, 512, 128)


def test_a_band_one_position_past_the_budget_is_streamed(monkeypatch):
    """A window of 33 on blocks of 32 holds 32 keys beside its own, one of
    34 holds 64: with the budget at the first one's count the first still
    takes the band kernels and the second the streamed ones (a grid of
    four axes) and agrees with the reference through the public entry."""
    q, k, v, weight = _qkv(128, 2, 1, 64)
    monkeypatch.setattr(fa, "BAND_VMEM_LIMIT_BYTES",
                        band_vmem_bytes(32, 32, 33, 64))
    assert band_path(128, 32, 32, 33, 64) == ONE_VISIT
    assert band_path(128, 32, 32, 34, 64) == STREAMED

    def chosen(window):
        return _through(lambda q_, k_, v_: pallas_flash_attention(
            q_, k_, v_, True, 32, 32, True, window), q, k, v, weight)

    assert [len(grid) for grid in _pallas_grids(
        lambda: chosen(33))] == [3, 3, 4]
    assert [len(grid) for grid in _pallas_grids(
        lambda: chosen(34))] == [4, 4, 4]
    for a, b in zip(*jax.jit(lambda: (chosen(34), _through(
            lambda q_, k_, v_: reference_attention(q_, k_, v_, window=34),
            q, k, v, weight)))()):
        _assert_close(np.asarray(a), np.asarray(b))


def test_the_reference_band_is_the_rule_written_out():
    """Query ``t`` sees ``t - window < s <= t``: itself and ``window - 1``
    before it."""
    mask = np.asarray(band_mask(8, 3))[0, 0]
    for t in range(8):
        for s in range(8):
            assert mask[t, s] == (t - 3 < s <= t), (t, s)
    q, k, v, _ = _qkv(16, 2, 2, 8)
    banded = reference_attention(q, k, v, window=4)
    causal = jnp.tril(jnp.ones((16, 16), bool))[None, None]
    np.testing.assert_allclose(
        banded, reference_attention(q, k, v, causal & band_mask(16, 4)))
    np.testing.assert_array_equal(
        banded, reference_attention(q, k, v, causal, window=4))
    # a window over the sequence is the causal attention
    np.testing.assert_array_equal(
        reference_attention(q, k, v, causal, window=16),
        reference_attention(q, k, v, causal))


@pytest.mark.parametrize(
    "seq, block_q, block_kv, window, steps, ratio",
    [(16384, 512, 512, 512, (2, 2), 1.99994),
     (16384, 256, 256, 512, (3, 3), 1.49995),
     (16384, 128, 128, 512, (5, 5), 1.24996),
     (16384, 1024, 512, 512, (3, 2), None),
     (16384, 512, 1024, 512, (2, 3), None),
     (256, 64, 64, 1, (1, 1), None),
     (256, 64, 64, 256, (4, 4), None),
     (256, 64, 64, 1000, (4, 4), None)],
)
def test_the_streamed_axis_spans_the_band_alone(seq, block_q, block_kv,
                                                window, steps, ratio):
    assert band_steps(seq, block_q, block_kv, window) == steps
    multiplied, allowed = band_pairs(seq, block_q, block_kv, window)
    w = min(window, seq)
    assert allowed == seq * w - w * (w - 1) // 2
    assert multiplied >= allowed
    if ratio is not None:
        assert multiplied / allowed == pytest.approx(ratio, abs=2e-5)


def _pallas_grids(fn, *args):
    """The grids of the Pallas calls in ``fn``'s jaxpr, in order."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def test_grids_shrink_under_a_window_and_not_without():
    q, k, v, weight = _qkv(512, 6, 1, 128)

    def streamed(window):
        return _pallas_grids(
            lambda *a: _streamed(*a, 64, 64, window), q, k, v, weight)

    def chosen(window):
        def loss(q_, k_, v_):
            return pallas_flash_attention(
                q_, k_, v_, True, 64, 64, True, window).sum()
        return _pallas_grids(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    # forward, dQ, dK/dV: 8 blocks each way, groups of 6
    assert chosen(None) == streamed(None) == [
        (1, 6, 8, 8), (1, 6, 8, 8), (1, 1, 8, 6 * 8)]
    assert streamed(64) == [(1, 6, 8, 2), (1, 6, 8, 2), (1, 1, 8, 6 * 2)]
    assert streamed(65) == streamed(64)
    assert streamed(66) == [(1, 6, 8, 3), (1, 6, 8, 3), (1, 1, 8, 6 * 3)]
    # one visit: no streamed axis in forward and dQ, the group's in dK/dV
    assert chosen(64) == chosen(66) == chosen(300) == [
        (1, 6, 8), (1, 6, 8), (1, 1, 8, 6)]
    # a band over the sequence streams
    assert chosen(500) == streamed(500) == [
        (1, 6, 8, 8), (1, 6, 8, 8), (1, 1, 8, 6 * 8)]


def test_no_window_is_the_program_it_was():
    """``window=None`` traces the causal kernels' own jaxpr: the same text
    whether the argument is passed or left out, and none of the band's
    arithmetic in it."""
    q, k, v, _ = _qkv(128, 2, 1, 64)

    def text(*extra):
        def loss(q_, k_, v_):
            return pallas_flash_attention(
                q_, k_, v_, True, 64, 64, True, *extra).sum()
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    assert text() == text(None)
    assert text() != text(32)
    # the text PR 51's tree traces (``git archive aad878f``, this file's
    # operands, jax 0.9.0): neither the band kernels nor the split into
    # ``_streamed_forward`` and ``_streamed_backward`` changed a letter
    import hashlib
    assert hashlib.sha256(text().encode()).hexdigest()[:16] == (
        "b9c18598017c432d")


def test_a_window_needs_causal_attention_and_a_position():
    q, k, v, _ = _qkv(128, 2, 2, 64)
    with pytest.raises(ValueError, match="window"):
        pallas_flash_attention(q, k, v, False, 64, 64, True, 32)
    with pytest.raises(ValueError, match="window"):
        pallas_flash_attention(q, k, v, True, 64, 64, True, 0)


def test_the_path_takes_the_window():
    assert kernel_takes(16384, 128, 64, 8, 512)
    assert kernel_takes(16384, 128, 64, 8, 100000)
    assert not kernel_takes(16384, 128, 64, 8, 0)
    assert attention_path("tpu", 16384, 128, 64, 8, 512) == "flash"
    assert attention_path("cpu", 16384, 128, 64, 8, 512) == "reference"
    assert attention_path("tpu", 100, 128, 64, 8, 512) == "reference"


def test_windowed_blocks_are_keyed_apart():
    """The table's causal entries answer no windowed call and the other way
    round; the shipped windowed entry is the cell's."""
    assert tuned_blocks(1024, 64) == (1024, 1024)
    assert tuned_blocks(1024, 64, 512) != (1024, 1024)
    causal = tuned_blocks(16384, 128)
    assert tuned_blocks(16384, 128, 512) == tuple(
        fa_entry("s16384_d128_w512"))
    assert tuned_blocks(16384, 128) == causal


def fa_entry(key):
    import json
    import os

    with open(os.path.join(os.path.dirname(fa.__file__),
                           "fa_tuned.json")) as f:
        entry = json.load(f)[key]
    return entry["block_q"], entry["block_kv"]


def test_the_record_of_a_windowed_call(monkeypatch):
    """``attention.path`` of a windowed call says the window, the blocks,
    which kernels run, the key blocks a query block visits and the pairs
    multiplied and allowed, the kernels' own; a causal call's record has
    none of them."""
    q, k, v, _ = _qkv(256, 2, 1, 64)
    notes = []
    monkeypatch.setattr(
        trace, "note_trace_time", lambda name, **attrs: notes.append(
            (name, attrs)))
    out = flash_attention(q, k, v, block_q=64, block_kv=32, interpret=True,
                          window=70)
    np.testing.assert_allclose(
        out, reference_attention(q, k, v, window=70), rtol=2e-3, atol=2e-3)
    (name, attrs), = notes
    assert name == "attention.path" and attrs["impl"] == "flash"
    assert attrs["window"] == 70 and attrs["blocks"] == (64, 32)
    assert attrs["band"] == "one_visit" and attrs["kv_blocks_visited"] == 1
    assert attrs["pairs_allowed"] == 256 * 70 - 70 * 69 // 2
    # 32 rows at a time against 32 + 96 keys, the first blocks' too
    assert attrs["pairs_multiplied"] == 256 * (32 + 96)
    notes.clear()
    # a band over the sequence: the streamed kernels' numbers (the record
    # is made while the call is traced: nothing need run)
    jax.eval_shape(lambda: flash_attention(
        q, k, v, block_q=64, block_kv=64, interpret=True, window=250))
    attrs = notes[0][1]
    assert attrs["band"] == "streamed" and attrs["kv_blocks_visited"] == 4
    assert attrs["pairs_multiplied"] == (1 + 2 + 3 + 4) * 64 * 64
    assert attrs == {**attrs, **band_record(256, 64, 64, 250, 64)}
    notes.clear()
    jax.eval_shape(lambda: flash_attention(
        q, k, v, block_q=64, block_kv=64, interpret=True))
    assert not {"window", "band", "pairs_allowed"} & set(notes[0][1])


def test_causal_attention_chooses_with_the_window(monkeypatch):
    """On a TPU at a shape the kernel takes the windowed kernel runs; off
    it the reference core under the same band."""
    q, k, v, _ = _qkv(128, 2, 1, 64)
    mask = jnp.tril(jnp.ones((128, 128), bool))[None, None]
    want = reference_attention(q, k, v, mask, window=40)
    np.testing.assert_array_equal(
        causal_attention(q, k, v, mask, window=40), want)
    real = attention.flash_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention, "flash_attention",
        lambda *a, **kw: real(*a, block_q=64, block_kv=64, interpret=True,
                              **kw))
    got = causal_attention(q, k, v, mask, window=40)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    assert not np.array_equal(got, want)
