"""The latent-attention core's Pallas kernels (``ops/pallas/
latent_attention.py``) in the interpreter on the CPU against the
``jax.numpy`` body and against the scores written out: forward, the
gradients of all five operands (the backward pass is ONE call: both parts
of dQ gather over the key blocks in float32 accumulators in HBM), the path
the code chooses from what it can observe, and its record."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.pallas import latent_attention as kernels
from shared_memo import shared_memo

B, S, H, D, R = 1, 384, 2, 128, 64


def _operands(seed=0, dtype=jnp.float32, batch=B, seq=S, heads=H):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = (batch, seq, heads, D)
    shapes = (wide, (batch, seq, heads, R), wide, (batch, seq, R), wide, wide)
    return tuple(jax.random.normal(k, shape).astype(dtype)
                 for k, shape in zip(ks, shapes))


def _written_out(q_nope, q_pe, k_nope, k_pe, v):
    """A head's key written whole, ``[k_nope | k_pe]``, and plain causal
    softmax attention over ``192``: what the two products stand for."""
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], q_pe.shape)], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    keep = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _both(fn):
    *ops, w = _operands()

    def loss(*operands):
        return (fn(*operands) * w).sum()

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*ops)


@shared_memo
def _computed(which):
    """``(loss, the five gradients)`` of one body."""
    if which == "kernels":
        # tiles of 128 x 128 at 384 positions: three q blocks by three kv
        # blocks, on the diagonal, below it and (skipped) above it
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(kernels, "BLOCKS", (128, 128))
            return _both(lambda *a: attention.latent_attention(
                *a, interpret=True))
    return _both({"jnp": attention._latent_reference,
                  "written_out": _written_out}[which])


@pytest.mark.parametrize("against", ["jnp", "written_out"])
def test_forward_and_every_gradient(against):
    got, want = _computed("kernels"), _computed(against)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, g, w in zip(("q_nope", "q_pe", "k_nope", "k_pe", "v"),
                          got[1], want[1]):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-5, err_msg=name)
    # the shared rotary key's gradient sums over the heads
    assert got[1][3].shape == (B, S, R)


# the ONE backward call: (batch, seq, heads, block_q, block_kv).  dQ gathers
# over four key blocks at least, so key block 0's add to zeros, the revisit
# of a block a later key block fetches, and the masked steps a key block's
# sweep starts with all run; three heads and two sequences, so a
# neighbour's block of either accumulator would show.
ONE_CALL_CASES = {
    "four_key_blocks": (2, 512, 3, 128, 128),
    "wide_q_blocks": (1, 512, 2, 256, 128),
    "wide_kv_blocks": (1, 512, 2, 128, 256),
}
WHICH = ["loss", "q_nope", "q_pe", "k_nope", "k_pe", "v"]


@shared_memo
def _one_call(case, which):
    """``(loss, the five gradients)`` of one case through the kernels at
    its blocks or through the float32 ``jax.numpy`` core."""
    batch, seq, heads, block_q, block_kv = ONE_CALL_CASES[case]
    *ops, w = _operands(seq + heads, batch=batch, seq=seq, heads=heads)
    # heads of different sizes
    ops[4] = ops[4] * (1.0 + jnp.arange(heads))[None, None, :, None]
    core = {"jnp": attention._latent_reference,
            "kernels": lambda *a: kernels.latent_attention_kernels(
                *a, block_q, block_kv, True)}[which]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda *a: (core(*a) * w).sum(), argnums=(0, 1, 2, 3, 4)))(*ops)
    return (loss,) + grads


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", list(ONE_CALL_CASES))
def test_the_one_backward_call_against_the_float32_core(case, which):
    n = WHICH.index(which)
    got, want = _one_call(case, "kernels")[n], _one_call(case, "jnp")[n]
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=1e-5,
                               atol=1e-5)


def test_the_backward_of_a_call_is_one_pallas_call():
    *ops, w = _operands()
    _, pull = jax.vjp(lambda *a: kernels.latent_attention_kernels(
        *a, 128, 128, True), *ops)
    assert str(jax.make_jaxpr(pull)(w)).count("pallas_call") == 1


def test_bfloat16_operands_as_the_step_hands_them(monkeypatch):
    monkeypatch.setattr(kernels, "BLOCKS", (128, 128))
    *ops, _ = _operands(dtype=jnp.bfloat16)
    got = attention.latent_attention(*ops, interpret=True)
    want = attention._latent_reference(*ops)
    assert got.dtype == jnp.bfloat16 and got.shape == (B, S, H, D)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


def test_blocks_divide_the_length():
    assert kernels.blocks_for(16384) == kernels.BLOCKS
    assert kernels.blocks_for(384) == (384, 384)
    assert kernels.blocks_for(1280) == (640, 640)
    assert all(1280 % b == 0 and b % 128 == 0
               for b in kernels.blocks_for(1280))


@pytest.mark.parametrize("backend, seq, dims, want", [
    ("tpu", 16384, (128, 64, 128), "pallas"),
    ("tpu", 8192, (128, 64, 128), "pallas"),
    ("cpu", 16384, (128, 64, 128), "reference"),
    ("tpu", 100, (128, 64, 128), "reference"),      # no multiple of 128
    ("tpu", 1024, (16, 8, 16), "reference"),        # a test's tiny heads
    ("tpu", 1024, (128, 64, 64), "reference"),      # values of another width
])
def test_the_path_from_what_the_code_can_observe(backend, seq, dims, want):
    assert attention.latent_attention_path(backend, seq, 8, *dims) == want


def _records(monkeypatch):
    records = []
    monkeypatch.setattr(
        attention.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    return records


def test_on_the_cpu_the_event_says_reference(monkeypatch):
    records = _records(monkeypatch)
    *ops, _ = _operands()
    attention.latent_attention(*ops)
    assert records == [("attention.path", dict(
        impl="latent", seq=S, heads=H, qk="128+64", v=128, blocks=None,
        exact="reference"))]


def test_on_a_tpu_the_event_says_pallas_and_its_blocks(monkeypatch):
    """Backend and shape alone send the call through the kernels, and a
    rematerialised layer is told what they keep."""
    records = _records(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []
    monkeypatch.setattr(
        kernels, "latent_attention_kernels",
        lambda *a: called.append(a[5:]) or a[4])
    *ops, _ = _operands()
    attention.latent_attention(*ops)
    event, (kept_name, kept) = records
    assert event == ("attention.path", dict(
        impl="latent", seq=S, heads=H, qk="128+64", v=128,
        blocks=(384, 384), exact="pallas", backward="one_call"))
    assert kept_name == "remat.kept" and kept["core"] == "latent"
    assert kept["names"] == "attn_out,attn_lse"
    assert kept["attn_lse_bytes"] == B * H * S * 4
    assert called == [(384, 384, False)]


def test_the_record_says_the_backward_is_one_call(monkeypatch):
    """Through the kernels themselves (the interpreter), as a step's trace
    makes it; the ``jax.numpy`` body's record says nothing of a backward."""
    records = _records(monkeypatch)
    *ops, _ = _operands()
    attention.latent_attention(*ops, interpret=True, rope="pairs")
    attention.latent_attention(*ops)
    (_, on_kernels), _, (_, on_jnp) = records
    assert on_kernels["exact"] == "pallas"
    assert on_kernels["backward"] == "one_call"
    assert on_kernels["rope"] == "pairs"
    assert on_jnp["exact"] == "reference" and "backward" not in on_jnp
