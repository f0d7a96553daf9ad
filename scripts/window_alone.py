"""The FA2 kernels under a causal window alone at one cell's shape, on the
chip: ``ops/pallas/flash_attention.py`` with ``window`` at each candidate
pair of blocks, beside the causal kernels at the shape of the same model's
full layers.

For each, the forward pass and forward + backward (dQ and dK/dV both: the
gradients of q, k and v) in milliseconds from the host's clock around a
read-back, over bfloat16 operands as the step hands them over; the key
blocks a query block visits and the pairs multiplied over the pairs
allowed; and how far the output and the three gradients are from the
reference core under the same band on a shorter sequence of float32
operands.  One JSON line a candidate::

    python3 scripts/window_alone.py --blocks "512,512;256,256;128,128"

A candidate is ``block_q,block_kv``.  ``--full`` times the causal kernels
at ``--full-heads`` query heads instead (no window).  ``--rehearse``: the
interpreter on the CPU at a tiny shape, to walk the script before it costs
chip time.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(batch, seq, heads, kv_heads, head_dim, seed, dtype):
    import jax

    shapes = ((batch, seq, heads, head_dim),) + (
        (batch, seq, kv_heads, head_dim),) * 2 + (
            (batch, seq, heads, head_dim),)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return tuple(jax.random.normal(k, s).astype(dtype)
                 for k, s in zip(keys, shapes))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shape", default="1,16384,64,8,128",
                        help="batch,seq,heads,kv heads,head size")
    parser.add_argument("--window", type=int, default=512)
    parser.add_argument("--blocks", default="512,512")
    parser.add_argument("--full", action="store_true")
    parser.add_argument("--full-heads", type=int, default=48)
    parser.add_argument("--turns", type=int, default=5)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.attention import reference_attention
    from dlrover_tpu.ops.pallas.flash_attention import (
        band_pairs, band_steps, pallas_flash_attention)

    batch, seq, heads, kv_heads, head_dim = (
        int(n) for n in args.shape.split(","))
    window = None if args.full else args.window
    if args.full:
        heads = args.full_heads
    if args.rehearse:
        batch, seq, heads, kv_heads, head_dim = 1, 256, 4, 2, 64
        window = None if args.full else 48
    device = jax.devices()[0]

    def core(blocks, interpret):
        def run(q, k, v):
            return pallas_flash_attention(
                q, k, v, True, blocks[0], blocks[1], interpret, window)
        return run

    def both(run, weight):
        def loss(q, k, v):
            return jnp.sum(run(q, k, v).astype(jnp.float32) * weight)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def timed(fn, *operands_):
        out = fn(*operands_)
        jax.device_get(jax.tree.leaves(out)[0].ravel()[0])      # compile
        t0 = time.perf_counter()
        for _ in range(args.turns):
            out = fn(*operands_)
        jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
        return 1e3 * (time.perf_counter() - t0) / args.turns

    q, k, v, weight = operands(
        batch, seq, heads, kv_heads, head_dim, 0, jnp.bfloat16)
    short = min(seq, 256 if args.rehearse else 2048)
    small = operands(1, short, heads, kv_heads, head_dim, 1, jnp.float32)
    want = both(lambda q_, k_, v_: reference_attention(
        q_, k_, v_, jnp.tril(jnp.ones((short, short), bool))[None, None],
        window), small[3])(*small[:3])
    for candidate in args.blocks.split(";"):
        blocks = tuple(int(n) for n in candidate.split(","))
        if args.rehearse:
            blocks = tuple(min(b, 64) for b in blocks)
        line = {"blocks": blocks, "window": window,
                "shape": [batch, seq, heads, kv_heads, head_dim],
                "device_kind": device.device_kind}
        if window is not None:
            multiplied, allowed = band_pairs(seq, *blocks, window)
            line.update(kv_blocks_visited=band_steps(seq, *blocks, window)[0],
                        pairs_multiplied_over_allowed=multiplied / allowed)
        try:
            run = core(blocks, args.rehearse)
            line["forward_ms"] = timed(jax.jit(run), q, k, v)
            line["forward_backward_ms"] = timed(both(run, weight), q, k, v)
            got = both(run, small[3])(*small[:3])
            line["rel_err_loss_dq_dk_dv"] = [
                float(jnp.abs(a - b).max() / jnp.abs(b).max())
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        except Exception as e:  # noqa: BLE001 - a tile the chip refuses
            line["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
