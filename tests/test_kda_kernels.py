"""The gated delta rule's Pallas kernels (``ops/pallas/kda.py``) in the
interpreter on the CPU, at heads of 128 and chunks of 64, float32 so that
they agree to rounding with the recurrence a token at a time
(``kda_recurrent``) and with the ``jax.numpy`` body they stand in for:
the forward pass and the gradient of every operand, a strong decay, betas
near their ends, bfloat16 operands, two sequences in a batch; which of the
two bodies ``kda`` takes, and the ``attention.path`` event that says so."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import linear_attention
from dlrover_tpu.ops.linear_attention import kda, kda_path, kda_recurrent
from dlrover_tpu.ops.pallas import kda as kernels
from dlrover_tpu.ops.pallas import tuning
from test_kda import OPERANDS, _operands, _weighted
from shared_memo import shared_memo

DIM, CHUNK = 128, 64
CASES = {
    # name: (chunks, tile, g_min, beta_logit)
    "two_chunks": (2, (1, 1, 1), -1.0, 0.0),
    "five_chunks": (5, (1, 2, 1), -1.0, 0.0),
    "four_chunks_two_a_step": (4, (2, 2, 2), -1.0, 0.0),
    "g_min_5": (2, (1, 1, 2), -5.0, 0.0),
    "g_min_20": (2, (2, 1, 1), -20.0, 0.0),
    "beta_near_0": (2, (1, 1, 1), -1.0, -8.0),
    "beta_near_2": (2, (2, 2, 1), -1.0, 8.0),
    # the last: ``test_a_strong_decay_is_not_clamped`` reads it next
    "g_min_80": (2, (1, 2, 2), -80.0, 0.0),
}
BODIES = {"the_recurrence": kda_recurrent,
          "the_jnp_body": linear_attention._kda_chunked}


def _through_kernels(tile):
    return functools.partial(kernels.kda_kernels, tile=tile, interpret=True)


def _jitted(fn):
    """A new compiled function each time: what a test patches (the
    backend, the kernels' entry) is read when this one is traced."""
    return jax.jit(lambda *operands: fn(*operands))


@shared_memo
def _all(case):
    """body -> (output, the five gradients of a loss that weighs every
    element differently), the kernels among them; a body is one compiled
    program."""
    chunks, tile, g_min, beta_logit = CASES[case]
    operands = _operands(chunks * CHUNK, g_min=g_min, beta_logit=beta_logit,
                         dim=DIM, seed=len(case))
    bodies = dict(BODIES, the_kernels=_through_kernels(tile))
    bodies["the_jnp_body"] = functools.partial(
        bodies["the_jnp_body"], chunk=CHUNK)
    return {name: jax.jit(lambda *o, fn=fn: (fn(*o), jax.grad(
        _weighted(fn), argnums=range(5))(*o)))(*operands)
        for name, fn in bodies.items()}


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    """A module's fixture and not a ``parametrize``, so that every test of
    one case stands next to the others in the collection and one worker
    computes ``_all(case)`` once."""
    return request.param


@pytest.mark.parametrize("body", list(BODIES))
def test_forward(case, body):
    got, want = _all(case)["the_kernels"][0], _all(case)[body][0]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("operand", OPERANDS)
@pytest.mark.parametrize("body", list(BODIES))
def test_gradient_of_each_operand(case, body, operand):
    at = OPERANDS.index(operand)
    got, want = _all(case)["the_kernels"][1][at], _all(case)[body][1][at]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(want).max()) > 1e-4        # the operand matters
    # the jnp body is itself up to 5e-4 from the recurrence at a strong
    # decay or a beta near 2 (tests/test_kda.py allows it 1e-4 and 2e-4 at
    # heads of 8)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-4 if body == "the_recurrence" else 5e-4)


def test_a_strong_decay_is_not_clamped():
    """At ``g`` down to -80 a token the recurrence's output is reproduced,
    not a clamped decay's: the first chunk's positions see nothing of one
    another beyond rounding, and the kernels say the same."""
    got = _all("g_min_80")["the_kernels"][0]
    want = _all("g_min_80")["the_recurrence"][0]
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_the_levels_cover_every_pair_once():
    """The diagonal and the six levels of halves: every pair ``j <= i`` of
    a chunk under exactly one mask, in both copies of a stacked pair but
    for the diagonal, which is the first copy's alone."""
    masks = [np.asarray(m) for m in kernels._masks(down=2)]
    total = sum(m.astype(int) for m in masks)
    lower = np.tril(np.ones((CHUNK, CHUNK), int))
    np.testing.assert_array_equal(total[:CHUNK], lower)
    np.testing.assert_array_equal(total[CHUNK:], lower - np.eye(CHUNK, dtype=int))


def test_no_part_of_an_exponent_is_positive():
    """The sums the exponents are made of take ``g`` with weights 0 and 1
    only, so with ``g <= 0`` no factor's exponent is above 0; a level's
    two parts add up to ``G_i - G_j``."""
    sums = kernels._decay_sums()
    assert set(np.unique(sums)) == {0.0, 1.0}
    g = -np.random.default_rng(0).uniform(0, 80, (CHUNK, 3)).astype(np.float64)
    running = np.cumsum(g, axis=0)
    parts = (sums.astype(np.float64) @ g).reshape(-1, CHUNK, 3)
    np.testing.assert_allclose(parts[0], running)
    np.testing.assert_allclose(parts[1], running[-1] - running)
    for half, part in zip(kernels.LEVELS, parts[2:]):
        i, j = 2 * half - 1, 0       # the first block's last row, first key
        np.testing.assert_allclose(part[i] + part[j], running[i] - running[j])
    assert (parts <= 0).all()


def test_bfloat16_operands_beside_a_float32_state():
    """bfloat16 q, k, v give a bfloat16 result as close to the float32
    recurrence as the ``jax.numpy`` body's; the state in the kernel's
    scratch and the solve's inverse are float32."""
    operands = _operands(4 * CHUNK, seed=5, dim=DIM)
    want = jax.jit(kda_recurrent)(*operands)
    low = tuple(t.astype(jnp.bfloat16) for t in operands[:3]) + operands[3:]
    through = jax.jit(_through_kernels((2, 2, 2)))
    got = through(*low)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    plain = jnp.abs(_jitted(kda)(*low).astype(jnp.float32) - want)
    assert float(err.max()) < 1.5 * float(plain.max()) < 0.02
    assert float(err.mean()) < 1.2 * float(plain.mean())
    grads = jax.jit(jax.grad(
        _weighted(_through_kernels((2, 2, 2))), argnums=range(5)))(*low)
    assert [g.dtype for g in grads] == [t.dtype for t in low]
    text = through.lower(*low).as_text()
    assert "2x128x128xf32" in text          # the state of two heads a step


def test_the_state_is_zeroed_at_each_sequences_first_chunk():
    """Two sequences in a batch: the second's output is what it is alone,
    whatever state the first left in the scratch."""
    q, k, v, g, beta = _operands(3 * CHUNK, seed=7, dim=DIM)
    alone = _through_kernels((1, 2, 2))(
        *(t[1:] for t in (q, k, v, g, beta)))
    v = v.at[0].multiply(100.0)             # a large state to leave behind
    both = _through_kernels((1, 2, 2))(q, k, v, g, beta)
    np.testing.assert_array_equal(both[1:], alone)


@pytest.mark.parametrize("backend,seq,chunk,head_dim,path", [
    ("tpu", 8192, 64, 128, "pallas"),
    ("tpu", 128, 64, 128, "pallas"),
    ("cpu", 8192, 64, 128, "jnp"),
    ("gpu", 8192, 64, 128, "jnp"),
    ("tpu", 8192, 64, 64, "jnp"),       # another head
    ("tpu", 8192, 64, 16, "jnp"),       # the tests' small heads
    ("tpu", 8192, 32, 128, "jnp"),      # another chunk
    ("tpu", 32, 32, 128, "jnp"),        # a sequence shorter than a chunk
    ("tpu", 8200, 64, 128, "jnp"),      # a ragged length
])
def test_the_path_follows_backend_and_shape(backend, seq, chunk, head_dim,
                                            path):
    assert kda_path(backend, seq, chunk, head_dim) == path


@pytest.mark.parametrize("table, want", [
    ({"kda_c64_d128": {"chunks": 4, "heads": 2, "state_heads": 8}},
     (4, 2, 8)),
    ({"kda_c64_d128": {"chunks": 4, "heads": 2}}, (4, 2, 2)),
    ({"kda_c64_d128": {"chunks": 4}}, (1, 1, 1)),
    ({"kda_c64_d128": {"chunks": "many", "heads": 2}}, (1, 1, 1)),
    ({"kda_c64_d128": {"chunks": 0, "heads": 2}}, (1, 1, 1)),
    ({}, None),
])
def test_the_tile_comes_from_the_table(tmp_path, monkeypatch, table, want):
    """A user's table over the shipped one; a malformed entry reads as one
    of each, and the shipped entry is what the cell runs."""
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv("DLROVER_TPU_FA_TUNING", str(path))
    tuning._load_one.cache_clear()
    try:
        shipped = tuning._load_one(tuning._SHIPPED)["kda_c64_d128"]
        assert tuning.kda_tiling(64, 128) == (
            want or (shipped["chunks"], shipped["heads"],
                     shipped["state_heads"]))
        assert tuning.kda_tiling(32, 64) == (1, 1, 1)   # no entry
    finally:
        tuning._load_one.cache_clear()


def _as_on_a_tpu(monkeypatch):
    """The backend patched, the kernels in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        linear_attention, "_kda_kernels",
        functools.partial(linear_attention._kda_kernels, interpret=True))


@pytest.mark.parametrize("on_a_tpu", [False, True], ids=["cpu", "as_on_a_tpu"])
def test_kda_takes_the_path_and_the_result_is_the_same(monkeypatch, on_a_tpu):
    """One entry, ``kda``: on a TPU at the kernels' shapes it goes through
    them, elsewhere through ``jax.numpy``; a ragged length and a small head
    stay ``jax.numpy`` on a TPU too."""
    through = []
    monkeypatch.setattr(
        kernels, "_chunk_forward",
        lambda *a, _real=kernels._chunk_forward: (
            through.append(1), _real(*a))[1])
    if on_a_tpu:
        _as_on_a_tpu(monkeypatch)
    operands = _operands(2 * CHUNK, dim=DIM, seed=9, batch=1)
    np.testing.assert_allclose(
        _jitted(kda)(*operands), _jitted(kda_recurrent)(*operands), rtol=0,
        atol=2e-5)
    assert bool(through) == on_a_tpu
    del through[:]
    ragged = _operands(2 * CHUNK + 8, dim=DIM, seed=9, batch=1)
    small = _operands(2 * CHUNK, dim=16, seed=9, batch=1)
    for operands in (ragged, small):
        np.testing.assert_allclose(
            _jitted(kda)(*operands), _jitted(kda_recurrent)(*operands),
            rtol=0, atol=2e-5)
    assert not through


@pytest.mark.parametrize("on_a_tpu", [False, True], ids=["cpu", "as_on_a_tpu"])
@pytest.mark.parametrize("head_dim", [128, 16])
def test_the_event_says_which_core_and_the_tile(monkeypatch, on_a_tpu,
                                                head_dim):
    """``DeltaAttention``'s ``attention.path`` line, one a trace:
    ``core=pallas`` with the tile it took and ``sub`` on a TPU at heads of
    128, ``core=jnp`` and no tile off the chip and at the tests' heads."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.models.llama import LlamaConfig

    records = []
    monkeypatch.setattr(
        llama.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    if on_a_tpu:
        _as_on_a_tpu(monkeypatch)
    cfg = LlamaConfig.tiny(
        num_layers=1, layer_pattern=("kda",), kda_heads=2,
        kda_head_dim=head_dim, dtype=jnp.float32, max_seq_len=128)
    layer = llama.DeltaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, cfg.hidden_size))
    params = _jitted(layer.init)(jax.random.PRNGKey(1), x, None, None)
    del records[:]
    out, _ = _jitted(functools.partial(layer.apply, mutable=["stats"]))(
        params, x, None, None)
    assert bool(jnp.isfinite(out).all())
    (name, attrs), *kept_records = records
    assert name == "attention.path"
    core = dict(core="jnp")
    # the kernels' core says beside it what a rematerialised layer keeps
    # (tests/test_remat_kept.py holds the bytes to the residuals)
    assert [name for name, _ in kept_records] == [
        "remat.kept"] * (on_a_tpu and head_dim == 128)
    if on_a_tpu and head_dim == 128:
        chunks, heads, state_heads = tuning.kda_tiling(64, 128)
        core = dict(core="pallas", chunks_per_step=chunks,
                    heads_per_turn=heads, state_heads_per_step=state_heads,
                    sub=1)
    assert attrs == dict(
        impl="kda", seq=128, heads=2, head_dim=head_dim, chunk=64, conv=4,
        state_dtype="float32", **core)
    assert list(attrs)[-len(core):] == list(core)   # the old fields first
