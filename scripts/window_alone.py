"""The FA2 kernels under a causal window alone at one cell's shape, on the
chip: ``ops/pallas/flash_attention.py`` with ``window`` at each candidate
pair of blocks, the band kernels (one visit a query block) beside the
streamed ones, beside the causal kernels at the shape of the same model's
full layers.

For each, the forward pass and forward + backward (dQ and dK/dV both: the
gradients of q, k and v) in milliseconds from the host's clock around a
read-back, over bfloat16 operands as the step hands them over; the key
blocks a query block visits and the pairs multiplied over the pairs
allowed; and how far the output and the three gradients are from the
reference core under the same band on a shorter sequence of float32
operands.  One JSON line a candidate::

    python3 scripts/window_alone.py --blocks "512,128;512,512;512,512,streamed"

A candidate is ``block_q,block_kv`` (the kernels the shapes choose,
``flash_attention.py::band_path``: in one visit ``block_kv`` is the rows a
query block takes at a time and the granule of the band's fetch) or
``block_q,block_kv,streamed`` (the streamed windowed kernels whatever the
rule says); a line names the path it ran (``band``), and its visits and
pairs are ``band_record``'s, the record's own.  ``--full`` times the causal kernels
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
    from dlrover_tpu.ops.pallas import flash_attention as fa

    batch, seq, heads, kv_heads, head_dim = (
        int(n) for n in args.shape.split(","))
    window = None if args.full else args.window
    if args.full:
        heads = args.full_heads
    if args.rehearse:
        batch, seq, heads, kv_heads, head_dim = 1, 256, 4, 2, 64
        window = None if args.full else 48
    device = jax.devices()[0]

    def core(blocks, streamed, interpret):
        if not streamed:  # what the shapes choose
            return lambda q, k, v: fa.pallas_flash_attention(
                q, k, v, True, *blocks, interpret, window)

        @jax.custom_vjp
        def run(q, k, v):
            return fa._streamed_forward(
                q, k, v, True, *blocks, interpret, False, window)

        def forward(q, k, v):
            out, lse = fa._streamed_forward(
                q, k, v, True, *blocks, interpret, True, window)
            return out, (q, k, v, out, lse)

        run.defvjp(forward, lambda kept, grad: fa._streamed_backward(
            *kept, grad, True, *blocks, interpret, window))
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
        *blocks, streamed = (candidate.split(",") + [""])[:3]
        blocks = tuple(int(n) for n in blocks)
        if args.rehearse:
            blocks = tuple(min(b, 64) for b in blocks)
        line = {"blocks": blocks, "window": window,
                "shape": [batch, seq, heads, kv_heads, head_dim],
                "device_kind": device.device_kind}
        if window is not None:
            record = fa.band_record(seq, *blocks, window, head_dim)
            if streamed:
                multiplied, _ = fa.band_pairs(seq, *blocks, window)
                record.update(
                    band=fa.STREAMED, pairs_multiplied=multiplied,
                    kv_blocks_visited=fa.band_steps(seq, *blocks, window)[0])
            line.update(
                band=record["band"],
                kv_blocks_visited=record["kv_blocks_visited"],
                pairs_multiplied_over_allowed=record["pairs_multiplied"]
                / record["pairs_allowed"])
        try:
            run = core(blocks, bool(streamed), args.rehearse)
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
