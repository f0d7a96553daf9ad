"""The latent-attention core alone at one cell's shape, on the chip: the
Pallas kernels of ``ops/pallas/latent_attention.py`` at each candidate
tile.

For each, the forward pass, the backward pass alone (the ONE call on the
residuals handed over) and forward + backward (the layer's ``remat``
keeps ``out`` and the LSE, so no second forward) in milliseconds from the
host's clock around a read-back, over bfloat16 operands as the step hands
them over; and how far ``out``, the LSE, the summed output and the five
gradients are from the ``jax.numpy`` body on a shorter sequence of float32
operands.  One JSON line a candidate::

    python3 scripts/latent_alone.py --blocks "1024,1024;512,1024;512,512"

A tile is ``block_q,block_kv``.  ``--rehearse``: the interpreter on the CPU
at a tiny shape, to walk the script before it costs chip time.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIDTHS = (128, 64, 128)      # nope, rope, v


def operands(batch, seq, heads, seed, dtype):
    import jax

    nope, rope, wide = WIDTHS
    shapes = ((batch, seq, heads, nope), (batch, seq, heads, rope),
              (batch, seq, heads, nope), (batch, seq, rope),
              (batch, seq, heads, wide))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return tuple(jax.random.normal(k, s).astype(dtype)
                 for k, s in zip(keys, shapes))


def reference_lse(q_nope, q_pe, k_nope, k_pe):
    """``[B, H, S]``: the causal scores' log-sum-exp, float32, written out
    as ``ops/attention.py::_latent_reference`` scores."""
    import jax
    import jax.numpy as jnp

    S = q_nope.shape[1]
    scale = (q_nope.shape[-1] + q_pe.shape[-1]) ** -0.5
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe)) * scale
    keep = jnp.tril(jnp.ones((S, S), dtype=bool))
    return jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shape", default="1,16384,8")
    parser.add_argument("--blocks", default="1024,1024")
    parser.add_argument("--turns", type=int, default=10)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.attention import _latent_reference
    from dlrover_tpu.ops.pallas import latent_attention as kernels

    batch, seq, heads = (int(n) for n in args.shape.split(","))
    if args.rehearse:
        batch, seq, heads = 1, 256, 2
    device = jax.devices()[0]
    for tile in args.blocks.split(";"):
        block_q, block_kv = (int(n) for n in tile.split(","))
        if args.rehearse:
            block_q = block_kv = 128

        settings = (block_q, block_kv, args.rehearse)

        def core(*ops):
            return kernels.latent_attention_kernels(*ops, *settings)

        def loss(fn):
            return lambda *ops: fn(*ops).astype(jnp.float32).sum()

        # the custom gradient's two rules: (out, residuals), and the five
        # gradients from the residuals and out's cotangent
        residuals = jax.jit(lambda *ops: kernels._latent_fwd(*ops, *settings))
        backward = jax.jit(lambda kept, grad: kernels._latent_bwd(
            *settings, kept, grad))
        forward = jax.jit(core)
        both = jax.jit(jax.grad(loss(core), argnums=(0, 1, 2, 3, 4)))
        ops = operands(batch, seq, heads, 0, jnp.bfloat16)
        out, kept = residuals(*ops)
        line = {"blocks": [block_q, block_kv], "shape": [batch, seq, heads],
                "device": device.device_kind}
        for name, fn, handed in (
                ("forward_ms", forward, ops),
                ("backward_ms", backward, (kept, jnp.ones_like(out))),
                ("forward_backward_ms", both, ops)):
            jax.block_until_ready(fn(*handed))
            t0 = time.perf_counter()
            for _ in range(args.turns):
                got = fn(*handed)
            jax.block_until_ready(got)
            line[name] = round(1e3 * (time.perf_counter() - t0) / args.turns, 3)
        # against the jax.numpy body, float32, where its [H, S, S] fits
        short = operands(1, min(seq, 2048), heads, 1, jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.value_and_grad(
                loss(_latent_reference), argnums=(0, 1, 2, 3, 4)))(*short)
            got = jax.jit(jax.value_and_grad(
                loss(core), argnums=(0, 1, 2, 3, 4)))(*short)
        line["max_abs_err"] = {
            name: float(np.abs(np.asarray(g) - np.asarray(w)).max())
            for name, g, w in zip(
                ("q_nope", "q_pe", "k_nope", "k_pe", "v"), got[1], want[1])}
        line["loss_rel_err"] = float(abs(got[0] - want[0]) / abs(want[0]))
        # ``out`` and the LSE themselves (PERF.md section 7: a change that
        # touches the kernels brings its reading of both)
        with jax.default_matmul_precision("highest"):
            out, kept = residuals(*short)
            want_out = jax.jit(_latent_reference)(*short)
            want_lse = jax.jit(reference_lse)(*short[:4])
        line["out_max_abs_err"] = float(jnp.abs(out - want_out).max())
        line["lse_max_abs_err"] = float(jnp.abs(kept[-1] - want_lse).max())
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
