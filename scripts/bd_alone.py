"""The attention under the block-diffusion mask alone at the cell's shape,
on the chip: the kernels of ``ops/pallas/block_diffusion_attention.py``
(ONE call a pass over the whole arrays) at each candidate tile, beside the
path they replaced (``old``: the mask-operand kernels once a block of 512
queries and half, its mask from ``iota``, the noisy half's keys joined,
32 outputs concatenated; kept in this script alone, for the comparison).

For each, a layer's forward pass, its backward pass alone (from the
residuals handed over) and both, in milliseconds from the host's clock
around a read-back, over bfloat16 operands as the step hands them over;
the pairs a head's forward multiplies; and how far ``out`` and dq, dk, dv
are from the rule written out in ``jax.numpy`` on a shorter sequence of
float32 operands.  One JSON line a candidate::

    python3 scripts/bd_alone.py --tiles "old;512,256;512,128;1024,256"

A tile is ``tile,sub`` (``ops/pallas/block_diffusion_attention.py``'s
``TILE`` and ``SUB``).  ``--rehearse``: the interpreter on the CPU at a
tiny shape, to walk the script before it costs chip time.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(shape, seed, dtype):
    import jax

    batch, rows, heads, kv_heads = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(key, (batch, rows, n, 128)).astype(dtype)
        for key, n in zip(keys, (heads, kv_heads, kv_heads)))


def rule(q, k, v, block):
    """The four lines of the rule over dense ``[H, 2S, 2S]`` scores."""
    import jax
    import jax.numpy as jnp

    rows = q.shape[1]
    seq, groups = rows // 2, q.shape[2] // k.shape[2]
    at = jnp.arange(rows)
    noisy, b = at < seq, at % seq // block
    r_noisy, c_noisy = noisy[:, None], noisy[None, :]
    b_r, b_c = b[:, None], b[None, :]
    keep = jnp.where(
        r_noisy, jnp.where(c_noisy, b_r == b_c, b_c < b_r),
        ~c_noisy & (b_c <= b_r))
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def old_path(q, k, v, block, query_block, block_kv, interpret):
    """What ``block_diffusion_attention`` ran on a TPU until PR 58."""
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import block_diffusion_keep
    from dlrover_tpu.ops.pallas.selected_attention import masked_attention

    seq = q.shape[1] // 2
    noisy, clean = [], []
    for first in range(0, seq, query_block):
        last = first + query_block
        seen = slice(seq, seq + last)

        def attend(queries, keys, values, is_noisy):
            keep = jnp.broadcast_to(
                block_diffusion_keep(first, last, block, is_noisy),
                (q.shape[0], query_block, keys.shape[1]))
            return masked_attention(
                queries, keys, values, keep, None, block_kv, interpret)[0]

        clean.append(attend(q[:, seq + first: seq + last], k[:, seen],
                            v[:, seen], False))
        joined = [jnp.concatenate([t[:, seen], t[:, first:last]], axis=1)
                  for t in (k, v)]
        noisy.append(attend(q[:, first:last], *joined, True))
    return jnp.concatenate(noisy + clean, axis=1)


def _loss(fn):
    import jax.numpy as jnp

    return lambda *ops: fn(*ops).astype(jnp.float32).sum()


def timed(core, ops, turns):
    """Milliseconds a pass: forward, backward alone, both."""
    import jax
    import jax.numpy as jnp

    # a pass at a time: the pull-back is a pytree of its residuals
    forward = jax.jit(core)
    residuals = jax.jit(lambda *ops: jax.vjp(core, *ops))
    backward = jax.jit(lambda pull, grad: pull(grad))
    both = jax.jit(jax.grad(_loss(core), argnums=(0, 1, 2)))
    out, pull = residuals(*ops)
    found = {}
    for name, fn, handed in (
            ("forward_ms", forward, ops),
            ("backward_ms", backward, (pull, jnp.ones_like(out))),
            ("forward_backward_ms", both, ops)):
        jax.block_until_ready(fn(*handed))
        t0 = time.perf_counter()
        for _ in range(turns):
            got = fn(*handed)
        jax.block_until_ready(got)
        found[name] = round(1e3 * (time.perf_counter() - t0) / turns, 3)
    return found


def against_the_rule(core, few, block):
    """How far ``out``, the summed output and dq, dk, dv are from the rule
    written out, float32 operands at the highest precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    written_out = functools.partial(rule, block=block)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            _loss(written_out), argnums=(0, 1, 2)))(*few)
        got = jax.jit(jax.value_and_grad(
            _loss(core), argnums=(0, 1, 2)))(*few)
        out_err = jnp.abs(
            jax.jit(core)(*few) - jax.jit(written_out)(*few)).max()
    return {
        "out_max_abs_err": float(out_err),
        "max_abs_err": {
            name: float(np.abs(np.asarray(g) - np.asarray(w)).max())
            for name, g, w in zip(("q", "k", "v"), got[1], want[1])},
        "loss_rel_err": float(abs(got[0] - want[0]) / abs(want[0]))}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shape", default="1,16384,32,4",
                        help="batch, rows (both copies), heads, kv heads")
    parser.add_argument("--block", type=int, default=4)
    parser.add_argument("--tiles", default="old;512,256")
    parser.add_argument("--turns", type=int, default=10)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.pallas import block_diffusion_attention as kernels
    from dlrover_tpu.ops.pallas.tuning import selected_tiling

    shape = tuple(int(n) for n in args.shape.split(","))
    # long enough for the forward's wide tiles, few enough heads for the
    # rule's dense scores
    short = (1, min(shape[1], 8192), min(shape[2], 4), 1)
    if args.rehearse:
        shape = short = (1, 512, 2, 1)
    device = jax.devices()[0]
    for candidate in args.tiles.split(";"):
        if candidate == "old":
            tile = 128 if args.rehearse else 512
            core = functools.partial(
                old_path, block=args.block, query_block=tile,
                block_kv=selected_tiling(tile, 128)[0],
                interpret=args.rehearse)
            line = {"path": "old", "query_block": tile,
                    "pairs_multiplied":
                        (shape[1] // 2) * (shape[1] // 2 + 2 * tile)}
        else:
            tile, sub, *more = (int(n) for n in candidate.split(","))
            if args.rehearse:
                tile, sub = 256, 128
            if more:
                kernels.WIDE_KEYS = more[0]

            def core(q, k, v, tile=tile, sub=sub):
                return kernels.block_diffusion_kernels(
                    q, k, v, args.block, tile, sub, args.rehearse)

            line = {"path": "one_call", "tile": tile, "sub": sub,
                    "wide": kernels.wide_tile(shape[1] // 2, tile),
                    "pairs_multiplied": kernels.pairs_multiplied(
                        shape[1] // 2, tile, sub)}

        line.update(shape=list(shape), block=args.block,
                    device=device.device_kind)
        try:    # a tile the chip's fast memory does not take says so
            line.update(timed(core, operands(shape, 0, jnp.bfloat16),
                              args.turns))
            line.update(against_the_rule(
                core, operands(short, 1, jnp.float32), args.block))
        except Exception as e:
            line["failed"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
