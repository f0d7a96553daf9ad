"""The differential core's Pallas kernels (``ops/pallas/
differential_attention.py``) in the interpreter on the CPU against
``differential_attention(impl="reference")`` in float32: ``out`` and the
gradients of q, k, v and ``lam``, whole and under a window (under a tile,
over one and, at 512 in tiles of 512, at one; the edge to the position), over keys and values handed from
elsewhere (the cross layer), a group of two query pairs a key pair and a
sequence of several tiles; that the backward pass is ONE call of eight
products; the walk's static counts, the path's refusals and its records."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.pallas import differential_attention as kernels
from shared_memo import shared_memo

D = kernels.HEAD_DIM

#: (seq, heads, kv heads, window, (query tile, key tile, sub-tile)).  Tiles
#: of 128 at 384 positions: three query tiles by three key tiles, on the
#: diagonal (cut, by sub-tiles of 64 with the dead one skipped), under it
#: (no mask) and above it (not walked)
CASES = {
    "whole": (384, 4, 2, None, (128, 128, 64)),
    "window_under_a_tile": (256, 4, 2, 100, (128, 128, 64)),
    "window_over_a_tile": (384, 2, 2, 200, (128, 128, 64)),
    "window_511_of_512": (1024, 2, 2, 511, (512, 512, 128)),
    "window_512": (1024, 2, 2, 512, (512, 512, 128)),
    "window_513_of_512": (1024, 2, 2, 513, (512, 512, 128)),
    "query_tiles_wider_than_key_tiles": (512, 2, 2, 200, (256, 128, 128)),
}
WHICH = ["out", "q", "k", "v", "lam"]


def _operands(seq, heads, kv_heads, dtype=jnp.float32):
    """q; k and v as another layer would hand them (the cross layer reads
    the memory's: nothing ties them to q); lam; the loss's weights."""
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    q, k, v = (jax.random.normal(key, (1, seq, n, D)).astype(dtype)
               for key, n in zip(ks, (heads, kv_heads, kv_heads)))
    # pairs of different sizes: a neighbour's block would show
    v = v * (1.0 + jnp.arange(kv_heads, dtype=dtype) // 2)[None, None, :,
                                                            None]
    return q, k, v, jnp.float32(0.55), jax.random.normal(
        ks[3], (1, seq, heads // 2, 2 * D))


@shared_memo
def _computed(case, which):
    """``(out, dq, dk, dv, dlam)`` of one case through the kernels at its
    tiles or through the float32 reference core (for the windows one off
    512: the reference AT 512)."""
    seq, heads, kv_heads, window, tiles = CASES[case]
    *ops, w = _operands(seq, heads, kv_heads)
    if which == "kernels":
        def core(q, k, v, lam):
            return kernels.differential_attention_kernels(
                q, k, v, lam, window, tiles, True)
    else:
        causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]

        def core(q, k, v, lam):
            return attention.differential_attention(
                q, k, v, lam, causal, 512 if "_of_512" in case else window)

    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(core, *ops)
        return (out,) + pull(w)


# (a case's five quantities stand apart in the collection order, so the
# workers of a run start on different cases and few wait for a neighbour's)
@pytest.mark.parametrize(
    "case", [case for case in CASES if "_of_512" not in case])
@pytest.mark.parametrize("which", WHICH)
def test_out_and_every_gradient_against_the_float32_core(case, which):
    n = WHICH.index(which)
    got, want = _computed(case, "kernels")[n], _computed(case, "jnp")[n]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("case", ["window_511_of_512", "window_513_of_512"])
@pytest.mark.parametrize("which", WHICH)
def test_a_window_one_position_off_reads_apart(case, which):
    """The edge is exact: the kernels at 511 and at 513 differ from the
    reference at 512 where the kernels at 512 do not (the case above)."""
    n = WHICH.index(which)
    got, want = _computed(case, "kernels")[n], _computed(case, "jnp")[n]
    at = _computed("window_512", "kernels")[n]
    far = float(np.abs(got - want).max())
    assert far > 1e3 * max(float(np.abs(at - want).max()), 1e-7)
    if which == "out":
        # the first rows see all their keys under either window
        np.testing.assert_allclose(got[:, :511], want[:, :511], atol=1e-5)


def test_the_backward_of_a_call_is_one_call_of_eight_products():
    seq, heads, kv_heads, window, tiles = CASES["whole"]
    *ops, w = _operands(seq, heads, kv_heads)
    _, pull = jax.vjp(lambda *a: kernels.differential_attention_kernels(
        *a, window, tiles, True), *ops)
    text = str(jax.make_jaxpr(pull)(w))
    assert text.count("pallas_call") == 1
    # the kernel's body holds a branch a kind of tile: the uncut one (one
    # visit of the whole tile) has the eight products a pair of tiles
    walk = kernels.Walk(seq, *tiles, window)
    assert walk.cut == [0] and walk.any_full
    visits = len(walk.visits(0)) + 1
    kernel = text[text.index("pallas_call"):]
    assert kernel.count("dot_general") == 8 * visits
    # the forward: four a visit
    fwd = str(jax.make_jaxpr(
        lambda *a: kernels.differential_attention_kernels(
            *a, window, tiles, True))(*ops))
    assert fwd.count("pallas_call") == 1
    assert fwd[fwd.index("pallas_call"):].count("dot_general") == 4 * visits


def test_bfloat16_operands_as_the_step_hands_them():
    seq, heads, kv_heads, window, tiles = CASES["window_under_a_tile"]
    q, k, v, lam, _ = _operands(seq, heads, kv_heads, jnp.bfloat16)
    got = attention.differential_attention(
        q, k, v, lam, None, window, "flash", interpret=True)
    causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    want = attention.differential_attention(q, k, v, lam, causal, window)
    assert got.dtype == jnp.float32
    assert got.shape == (1, seq, heads // 2, 2 * D)
    np.testing.assert_allclose(got, want, atol=5e-2)


@pytest.mark.parametrize("seq, window, tiles, cut, live, walked", [
    # the diagonal alone is cut; 16 x 17 / 2 live tiles of 256 steps
    (16384, None, (1024, 1024, 256), [0], 136, 256),
    # a band of 512 in tiles of 512: its own tile and the one before, both
    # cut, 63 live of 64 steps
    (16384, 512, (512, 512, 128), [0, 512], 63, 64),
    (16384, 600, (512, 512, 128), [0, 512, 1024], 93, 96),
    # a window over the sequence cuts nothing
    (512, 512, (128, 128, 64), [0], 10, 16),
])
def test_the_walk_counts(seq, window, tiles, cut, live, walked):
    walk = kernels.Walk(seq, *tiles, window)
    assert walk.cut == cut
    assert (walk.tiles_live, walk.tiles_walked) == (live, walked)
    # a cut tile's visits multiply every allowed pair and no dead sub-tile
    for distance in walk.cut:
        rows_seen = set()
        for rows, keys, masked in walk.visits(distance):
            assert rows.stop - rows.start == walk.sub
            assert rows.start not in rows_seen
            rows_seen.add(rows.start)
            ahead = (distance + np.arange(rows.start, rows.stop)[:, None]
                     - np.arange(walk.tile_kv)[None, :])
            keep = (ahead >= 0) & (ahead < (walk.window or seq))
            assert not keep[:, :keys.start].any()
            assert not keep[:, keys.stop:].any()
            assert masked == (not keep[:, keys].all())


def test_the_shipped_tiles_divide_the_length():
    assert kernels.tiles_for(16384) == kernels.WHOLE_TILES
    assert kernels.tiles_for(16384, 512) == kernels.WINDOW_TILES
    assert kernels.tiles_for(2048, 4096) == kernels.tiles_for(2048)
    for seq, window in ((384, None), (1280, 512), (128, 64)):
        tile_q, tile_kv, sub = kernels.tiles_for(seq, window)
        assert seq % tile_q == 0 == seq % tile_kv
        assert tile_q % sub == 0 == tile_kv % sub and sub % 128 == 0


@pytest.mark.parametrize("shape, takes", [
    ((16384, 64, 40, 20), True),
    ((16384, 128, 40, 20), False),      # a head of another size
    ((16384, 64, 40, 10), True),        # a group of four pairs
    ((16384, 64, 6, 4), False),         # three pairs on two
    ((100, 64, 4, 2), False),           # no multiple of 128
    ((1024, 64, 3, 1), False),          # no pairs
])
def test_the_shapes_the_kernels_take(shape, takes):
    assert kernels.kernels_take(*shape) is takes


def test_off_the_chip_flash_raises_and_never_turns_into_the_reference():
    q, k, v, lam, _ = _operands(256, 4, 2)
    with pytest.raises(RuntimeError, match="TPU backend"):
        attention.differential_attention(q, k, v, lam, impl="flash")
    wide = jnp.zeros((1, 256, 4, 128))
    with pytest.raises(ValueError, match="pairs of heads of 64"):
        attention.differential_attention(
            wide, wide, wide, lam, impl="flash", interpret=True)


def test_the_records_of_both_paths(monkeypatch):
    records = []
    monkeypatch.setattr(
        attention.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []
    monkeypatch.setattr(
        kernels, "differential_attention_kernels",
        lambda *a: called.append(a[4:]) or a[0])
    q, k, v, lam, _ = _operands(1024, 4, 2)
    monkeypatch.setattr(kernels, "WINDOW_TILES", (512, 512, 128))
    attention.differential_attention(q, k, v, lam, window=512, impl="flash")
    attention.differential_attention(q, k, v, lam, window=512)
    path, (kept_name, kept), reference = records
    assert path == ("attention.path", dict(
        impl="differential", core="pallas", seq=1024, heads=4, head_dim=64,
        window=512, maps=2, scores_over=64, value=128, backward_products=8,
        tiles=(512, 512, 128), tiles_live=3, tiles_walked=4))
    assert kept_name == "remat.kept" and kept["core"] == "diff"
    assert kept["names"] == "attn_out,attn_lse"
    assert kept["attn_out_bytes"] == 2 * 1024 * 2 * 128 * 4     # O1, O2
    assert kept["attn_lse_bytes"] == 4 * 1024 * 4           # a row a head
    assert called == [(512, (512, 512, 128), False)]
    assert reference == ("attention.path", dict(
        impl="differential", seq=1024, heads=4, head_dim=64, window=512,
        exact="reference"))
