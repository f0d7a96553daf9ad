"""The FA2 kernels under a causal window (``ops/pallas/flash_attention.py``
with ``window``): forward, dQ and dK/dV in the interpreter against the
reference core under the same band, over windows under, at and over a
block, not a multiple of one, at and over the sequence, unequal blocks,
groups of 6 and 8 and head sizes 64 and 128; the streamed axes' lengths;
and ``window=None`` lowering to the kernels as they were."""

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
    band_pairs, band_steps, kernel_takes, pallas_flash_attention)
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


def _qkv(seq, heads, kv_heads, head_dim):
    keys = jax.random.split(jax.random.PRNGKey(seq + heads + head_dim), 4)
    q = jax.random.normal(keys[0], (1, seq, heads, head_dim), jnp.float32)
    k = jax.random.normal(keys[1], (1, seq, kv_heads, head_dim), jnp.float32)
    v = jax.random.normal(keys[2], (1, seq, kv_heads, head_dim), jnp.float32)
    # heads of different sizes: a neighbour's lanes would show
    v = v * (1.0 + jnp.arange(kv_heads))[None, None, :, None]
    weight = jax.random.normal(keys[3], (1, seq, heads, head_dim), jnp.float32)
    return q, k, v, weight


@shared_memo
def _both(case):
    """``{"kernel": (out, dq, dk, dv), "reference": (...)}`` of a case."""
    seq, window, block_q, block_kv, heads, kv_heads, head_dim = CASES[case]
    q, k, v, weight = _qkv(seq, heads, kv_heads, head_dim)

    def through(core):
        def loss(q_, k_, v_):
            out = core(q_, k_, v_)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    return {
        "kernel": jax.jit(lambda: through(
            lambda q_, k_, v_: pallas_flash_attention(
                q_, k_, v_, True, block_q, block_kv, True, window)))(),
        "reference": jax.jit(lambda: through(
            lambda q_, k_, v_: reference_attention(
                q_, k_, v_, window=window)))(),
    }


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(CASES))
def test_windowed_kernels_match_the_reference(case, which):
    both = _both(case)
    at = ["out", "dq", "dk", "dv"].index(which)
    got, want = both["kernel"][at], both["reference"][at]
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    # one position: the softmax of one score has no gradient to q and k,
    # and the kernel's is the rounding of dp - delta
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=2e-3, atol=2e-3)


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
    q, k, v, _ = _qkv(512, 6, 1, 128)

    def grids(window):
        def loss(q_, k_, v_):
            return pallas_flash_attention(
                q_, k_, v_, True, 64, 64, True, window).sum()
        return _pallas_grids(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    # forward, dQ, dK/dV: 8 blocks each way, groups of 6
    assert grids(None) == [(1, 6, 8, 8), (1, 6, 8, 8), (1, 1, 8, 6 * 8)]
    assert grids(64) == [(1, 6, 8, 2), (1, 6, 8, 2), (1, 1, 8, 6 * 2)]
    assert grids(65) == grids(64)
    assert grids(66) == [(1, 6, 8, 3), (1, 6, 8, 3), (1, 1, 8, 6 * 3)]


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
    the key blocks a query block visits and the pairs multiplied and
    allowed; a causal call's record has none of them."""
    q, k, v, _ = _qkv(256, 2, 1, 64)
    notes = []
    monkeypatch.setattr(
        trace, "note_trace_time", lambda name, **attrs: notes.append(
            (name, attrs)))
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True,
                          window=70)
    np.testing.assert_allclose(
        out, reference_attention(q, k, v, window=70), rtol=2e-3, atol=2e-3)
    (name, attrs), = notes
    assert name == "attention.path" and attrs["impl"] == "flash"
    assert attrs["window"] == 70 and attrs["blocks"] == (64, 64)
    assert attrs["kv_blocks_visited"] == 3
    assert attrs["pairs_allowed"] == 256 * 70 - 70 * 69 // 2
    assert attrs["pairs_multiplied"] == (1 + 2 + 3 + 3) * 64 * 64
    notes.clear()
    flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    assert "window" not in notes[0][1] and "pairs_allowed" not in notes[0][1]


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
