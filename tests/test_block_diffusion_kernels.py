"""The block-diffusion attention's Pallas kernels (``ops/pallas/
block_diffusion_attention.py``) in the interpreter on the CPU against the
rule written out (``test_sdar._dense`` under ``_table``): ``out``, the LSE
and the gradients of q, k and v from ONE call a pass over ``[noisy copy ;
clean copy]``; the walk over live tiles alone; the pairs the record says a
head's forward multiplies; the path the code chooses and its record."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.pallas import block_diffusion_attention as kernels
from shared_memo import shared_memo
from test_sdar import _dense, _table

D = 128
#: case -> (batch, positions, heads, kv heads, block length, tile, sub[,
#: the keys of the forward's wide tiles: the file's, which no case reaches])
CASES = {
    # a GQA group of 8 (SDAR's) and of 1, two query tiles a half: a FULL
    # tile under each half's cut one, a key tile's second and third visit
    "a_group_of_8": (1, 256, 8, 1, 4, 128, 128),
    "a_group_of_1": (2, 256, 2, 2, 4, 128, 128),
    # one query tile a half: nothing FULL, every key tile visited afresh
    "one_tile": (1, 128, 2, 1, 4, 128, 128),
    "four_tiles": (1, 512, 3, 1, 4, 128, 128),
    # a block of the sequence a tile: the clean cut allows every pair, the
    # noisy cut none (its rows see their own noisy block alone: ONE softmax
    # over both parts wipes what the dead part left)
    "a_block_a_tile": (1, 256, 2, 1, 128, 128, 128),
    # cut tiles of 2 x 2 sub-tiles: the one above the diagonal is skipped,
    # and of a noisy tile's own keys the two off it
    "dead_sub_tiles": (1, 512, 2, 1, 4, 256, 128),
    # the forward takes the clean keys under a query tile's own two tiles
    # at a time as far as whole pairs lie there: of the third query tile a
    # wide tile, of the fourth a wide one and a FULL one
    "wide_tiles": (1, 512, 2, 1, 4, 128, 128, 256),
}
WHICH = ["out", "lse", "q", "k", "v"]


def _operands(case):
    batch, seq, heads, kv_heads = CASES[case][:4]
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    q, k, v, w = (jax.random.normal(key, (batch, 2 * seq, n, D))
                  for key, n in zip(ks, (heads, kv_heads, kv_heads, heads)))
    # heads of different sizes, and noisy keys that outweigh the clean ones
    # for some rows and not for others: both parts of a noisy row's softmax
    # carry weight
    q = q * (1.0 + jnp.arange(heads))[None, None, :, None] / heads
    return q, k.at[:, :seq].multiply(1.5), v, w


@shared_memo
def _computed(case, which):
    """``(out, lse, dq, dk, dv)`` of one case through the kernels at its
    tiles or through the rule written out."""
    _, seq, _, _, block, tile, sub, *wide = CASES[case]
    q, k, v, w = _operands(case)
    table = _table(seq, block)
    with jax.default_matmul_precision("highest"), \
            pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "WIDE_KEYS", *wide or [kernels.WIDE_KEYS])
        if which == "kernels":
            def core(*ops):
                return kernels.block_diffusion_kernels(
                    *ops, block, tile, sub, True)

            lse = jax.jit(lambda *ops: kernels._bd_fwd(
                *ops, block, tile, sub, True)[1][-1])(q, k, v)
        else:
            def core(*ops):
                return _dense(*ops, table)

            groups = q.shape[2] // k.shape[2]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, jnp.repeat(k, groups, axis=2)) * D ** -0.5
            lse = jax.nn.logsumexp(
                jnp.where(table, scores, -jnp.inf), axis=-1)
        out = jax.jit(core)(q, k, v)
        grads = jax.jit(jax.grad(
            lambda *ops: (core(*ops) * w).sum(), argnums=(0, 1, 2)))(q, k, v)
    return (out, lse) + grads


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_against_the_rule_written_out(case, which):
    n = WHICH.index(which)
    got, want = _computed(case, "kernels")[n], _computed(case, "rule")[n]
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=1e-5,
                               atol=1e-5)
    seq = CASES[case][1]
    if which in ("k", "v"):     # both copies' keys are taught
        assert np.abs(got[:, :seq]).max() > 0 < np.abs(got[:, seq:]).max()


def _primitives(jaxpr, found=None):
    """``(the kernels called by name, every other primitive)`` of ``jaxpr``
    and the jaxprs inside it, a kernel's own body left out."""
    found = found or (collections.Counter(), collections.Counter())
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[0][eqn.params["jaxpr"].debug_info.func_name] += 1
            continue
        found[1][eqn.primitive.name] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _primitives(inner, found)
    return found


def test_a_pass_is_one_call_over_the_whole_arrays():
    q, k, v, _ = _operands("four_tiles")

    def core(*ops):
        return kernels.block_diffusion_kernels(*ops, 4, 128, 128, True)

    out, pull = jax.vjp(core, q, k, v)
    calls, around = _primitives(jax.make_jaxpr(core)(q, k, v).jaxpr)
    assert calls == {"_fwd_kernel": 1}
    # nothing is joined or stacked, and the one slice is lane 0 of the LSE
    assert not around["concatenate"] and around["slice"] <= 1
    calls, around = _primitives(jax.make_jaxpr(pull)(out).jaxpr)
    assert calls == {"_bwd_kernel": 1}
    assert not around["concatenate"] and not around["slice"]


@pytest.mark.parametrize("seq,tile,sub,block", [
    (512, 128, 128, 4), (512, 256, 128, 4), (1024, 512, 256, 4),
    (8192, 512, 256, 4), (8192, 512, 128, 4), (256, 256, 256, 8)])
def test_the_walk_visits_the_live_tiles_and_sub_tiles_alone(seq, tile, sub,
                                                            block):
    """Every tile of the ``[2S, 2S]`` table that holds an allowed pair is a
    step of the walk, once, and no other; a query tile's steps stand next to
    each other; and ``pairs_multiplied`` is the area of the sub-tiles that
    hold an allowed pair."""
    table = kernels.walk(seq, tile)
    q_at, kv_at, wide_at, kind, first, last, fresh = table
    assert not wide_at.any()
    n = seq // tile
    at = np.arange(2 * seq)
    noisy, blk = at < seq, at % seq // block
    allowed = np.where(
        noisy[:, None],
        np.where(noisy[None, :], blk[:, None] == blk[None, :],
                 blk[None, :] < blk[:, None]),
        ~noisy[None, :] & (blk[None, :] <= blk[:, None]))

    def live(size):
        return allowed.reshape(
            2 * seq // size, size, 2 * seq // size, size).any(axis=(1, 3))

    steps = list(zip(q_at, kv_at))
    assert len(set(steps)) == len(steps)
    assert set(steps) == set(zip(*np.nonzero(live(tile))))
    assert len(steps) == n * (n + 1) + n
    # a query tile's steps are one run, opened and closed once
    assert first.sum() == last.sum() == 2 * n == len(set(q_at))
    assert (np.diff(q_at) != 0).sum() == 2 * n - 1
    assert fresh.sum() == len(set(kv_at)) == 2 * n
    assert collections.Counter(kind.tolist()) == +collections.Counter({
        kernels.FULL: n * (n - 1), kernels.CLEAN_CUT: n,
        kernels.NOISY_CUT: n, kernels.OWN: n})
    multiplied = kernels.pairs_multiplied(seq, tile, sub)
    assert multiplied == live(sub).sum() * sub * sub
    assert allowed.sum() == attention.block_diffusion_pairs(seq, block)
    if (seq, tile) == (8192, 512):      # the cell's: x1.062 and x1.031
        assert multiplied == {256: 71303168, 128: 69206016}[sub]


@pytest.mark.parametrize("seq,tile,wide", [
    (8192, 1024, 2048), (8192, 512, 2048), (1024, 128, 256), (512, 128, 512)])
def test_wide_tiles_cover_the_full_tiles_they_stand_for(seq, tile, wide):
    """The forward's walk with wide tiles visits the keys the walk without
    visits, a query tile at a time and in the same order; a step reads
    narrow keys or wide ones, and the block index of the operand it does
    not read stands where the last step that read it left it."""
    narrow, walked = kernels.walk(seq, tile), kernels.walk(seq, tile, wide)
    q_at, kv_at, wide_at, kind = walked[:4]
    per = wide // tile

    def keys(table):
        found = collections.defaultdict(list)
        for q, j, w, k in table[:4].T:
            found[q] += (range(w * per, (w + 1) * per)
                         if k == kernels.WIDE else [j])
        return found

    assert keys(walked) == keys(narrow)
    is_wide = kind == kernels.WIDE
    n = seq // tile
    assert is_wide.sum() == 2 * sum(i // per for i in range(n))
    assert walked.shape[1] == narrow.shape[1] - is_wide.sum() * (per - 1)
    for at, reads in ((kv_at, ~is_wide), (wide_at, is_wide)):
        moved = np.nonzero(np.diff(at))[0] + 1
        assert reads[moved].all()
    assert kernels.wide_tile(seq, tile) == (
        wide if wide == kernels.WIDE_KEYS else 0)
    np.testing.assert_array_equal(walked[kernels.FIRST:kernels.FRESH].sum(1),
                                  [2 * n, 2 * n])


def test_sub_tiles_are_whole_blocks_or_the_tile():
    assert kernels.sub_tile(512, 4) == kernels.SUB == 256
    assert kernels.tile_for(jnp.bfloat16) == kernels.TILE == 1024
    assert kernels.tile_for(jnp.float32) == 512
    assert kernels.sub_tile(128, 4) == 128      # SUB does not tile it
    assert kernels.sub_tile(512, 512) == 512    # a block straddles SUB
    with pytest.raises(ValueError, match="sub-tiles of 128"):
        x = jnp.zeros((1, 512, 1, D))
        kernels.block_diffusion_kernels(x, x, x, 256, 256, 128, True)


def test_the_path_and_its_record(monkeypatch):
    path = attention.block_diffusion_path
    assert path("tpu", 8192, 512, D, 32, 4) == "pallas"
    assert path("tpu", 8192, 512, D, 32, 5) == "jnp"
    assert path("tpu", 8192, 192, D, 32, 4) == "jnp"
    assert path("gpu", 8192, 512, D, 32, 4) == "jnp"
    records = []
    monkeypatch.setattr(
        attention.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    q, k, v, _ = _operands("dead_sub_tiles")
    monkeypatch.setattr(kernels, "TILE", 512)   # float32 operands: half
    monkeypatch.setattr(kernels, "SUB", 128)
    got = attention.block_diffusion_attention(q, k, v, 4, interpret=True)
    np.testing.assert_allclose(
        got, _computed("dead_sub_tiles", "kernels")[0], rtol=0, atol=1e-5)
    (name, record), (kept_name, kept_record) = records
    assert name == "attention.path" and record == dict(
        impl="block_diffusion", seq=512, rows=1024, block=4, query_block=256,
        pairs=attention.block_diffusion_pairs(512, 4), heads=2, head_dim=D,
        exact="pallas", sub=128, calls=1,
        # two cut tiles of three sub-tiles and one of two a query tile
        # pair, two FULL tiles
        pairs_multiplied=(2 * 2 * 3 + 2 * 2 + 2 * 4) * 128 * 128)
    assert kept_name == "remat.kept" and kept_record["core"] == (
        "block_diffusion")
    assert kept_record["bytes_per_layer"] == (1024 * 2 * D + 2 * 1024) * 4
    # off the chip, and nobody asking for the interpreter: jax.numpy
    del records[:]
    attention.block_diffusion_attention(q, k, v, 4)
    assert [r["exact"] for _, r in records] == ["jnp"]
