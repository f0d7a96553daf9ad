"""The differential core alone at the cell's shape, on the chip: the kernels
of ``ops/pallas/differential_attention.py`` (both maps of a head pair in one
visit, ONE backward call of eight products) at each candidate tiling,
beside the path they replaced (``old``: ONE call of the FA2 kernels at head
size 128 over q and k padded with 64 columns of zeros and ``V`` written
twice, the halves subtracted outside; kept in this script alone, for the
comparison), whole and under the cell's window.

For each, a layer's forward pass, its backward pass alone (from the
residuals handed over) and both, in milliseconds from the host's clock
around a read-back, over bfloat16 operands as the step hands them over;
and how far ``out`` and dq, dk, dv, dlam are from the rule written out in
float32 (``differential_attention(impl="reference")`` over the same
operands at the highest precision) on a shorter sequence.  A window's
candidates are also run one position short and one long against the rule
at the window itself: the cell's ``correct`` cannot see a window off by one
(PERF.md section 4), so ``window_off_by_one`` here is what holds it.  One
JSON line a candidate::

    python3 scripts/diff_alone.py --windows "none;512" \\
        --tiles "old;1024,1024,256;512,512,128"

A tiling is ``query tile,key tile,sub-tile`` (``WHOLE_TILES`` and
``WINDOW_TILES`` of the kernels' file; ``shipped``: what ``tiles_for``
gives).  ``--rehearse``: the interpreter on the CPU at a tiny shape, to
walk the script before it costs chip time.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(shape, seed, dtype):
    """``(q, k, v, lam, weights of the loss)``."""
    import jax
    import jax.numpy as jnp

    batch, seq, heads, kv_heads = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (batch, seq, n, 64)).astype(dtype)
               for key, n in zip(keys, (heads, kv_heads, kv_heads)))
    weights = jax.random.normal(keys[3], (batch, seq, heads // 2, 128))
    return q, k, v, jnp.float32(0.6), weights


def old_path(q, k, v, lam, window, interpret):
    """What ``differential_attention(impl="flash")`` ran until PR 60."""
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import flash_attention

    B, S, H, D = q.shape
    G = k.shape[2]

    def padded(t, factor=None):
        if factor is not None:
            t = (t.astype(jnp.float32) * factor).astype(t.dtype)
        return jnp.pad(t, ((0, 0),) * 3 + ((0, D),))

    wide = v.reshape(B, S, G // 2, 2 * D)
    out = flash_attention(
        jnp.concatenate([padded(q[:, :, 0::2], 2.0 ** 0.5),
                         padded(q[:, :, 1::2], 2.0 ** 0.5)], axis=2),
        jnp.concatenate([padded(k[:, :, 0::2]), padded(k[:, :, 1::2])],
                        axis=2),
        jnp.concatenate([wide, wide], axis=2),
        causal=True, window=window, interpret=interpret)
    return (out[:, :, : H // 2].astype(jnp.float32)
            - lam * out[:, :, H // 2:].astype(jnp.float32))


def rule(q, k, v, lam, window):
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import differential_attention

    S = q.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
    return differential_attention(
        *(t.astype(jnp.float32) for t in (q, k, v)), lam, causal, window)


def timed(core, ops, weights, turns):
    """Milliseconds a pass: forward, backward alone, both."""
    import jax

    # a pass at a time: the pull-back is a pytree of its residuals
    forward = jax.jit(core)
    residuals = jax.jit(lambda *ops: jax.vjp(core, *ops))
    backward = jax.jit(lambda pull, grad: pull(grad))
    both = jax.jit(jax.grad(
        lambda *ops: (core(*ops) * weights).sum(), argnums=(0, 1, 2, 3)))
    _, pull = residuals(*ops)
    found = {}
    for name, fn, handed in (
            ("forward_ms", forward, ops),
            ("backward_ms", backward, (pull, weights)),
            ("forward_backward_ms", both, ops)):
        jax.block_until_ready(fn(*handed))
        t0 = time.perf_counter()
        for _ in range(turns):
            got = fn(*handed)
        jax.block_until_ready(got)
        found[name] = round(1e3 * (time.perf_counter() - t0) / turns, 3)
    return found


def against_the_rule(core, few, weights, window):
    """How far ``out`` and dq, dk, dv, dlam of ``core`` are from the rule at
    ``window``, over the same operands, largest absolute difference."""
    import jax
    import numpy as np

    def both(fn):
        out, pull = jax.vjp(fn, *few)
        return (out,) + pull(weights)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: both(functools.partial(rule, window=window)))()
    got = jax.jit(lambda: both(core))()     # as the step runs it
    return {name: float(np.abs(np.asarray(g, np.float32)
                               - np.asarray(w, np.float32)).max())
            for name, g, w in zip(("out", "dq", "dk", "dv", "dlam"),
                                  got, want)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shape", default="1,16384,40,20",
                        help="batch, positions, heads, kv heads")
    parser.add_argument("--windows", default="none;512")
    parser.add_argument("--tiles", default="old;shipped")
    parser.add_argument("--turns", type=int, default=10)
    parser.add_argument("--short", type=int, default=2048,
                        help="positions of the comparison with the rule")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.pallas import differential_attention as kernels

    shape = tuple(int(n) for n in args.shape.split(","))
    short = (1, min(shape[1], args.short)) + shape[2:]
    if args.rehearse:
        shape = short = (1, 512, 4, 2)
    device = jax.devices()[0]
    for window in args.windows.split(";"):
        window = None if window == "none" else int(window)
        if args.rehearse and window is not None:
            window = 128
        for candidate in args.tiles.split(";"):
            if candidate == "old":
                def core(q, k, v, lam, window=window):
                    return old_path(q, k, v, lam, window, args.rehearse)

                line = {"path": "old"}
            else:
                tiles = (kernels.tiles_for(shape[1], window)
                         if candidate == "shipped"
                         else tuple(int(n) for n in candidate.split(",")))
                if args.rehearse:
                    tiles = (128, 128, 64)

                def core(q, k, v, lam, window=window, tiles=tiles):
                    # (a shorter sequence takes the tiles that divide it)
                    fit = tuple(min(n, q.shape[1]) for n in tiles)
                    return kernels.differential_attention_kernels(
                        q, k, v, lam, window, fit, args.rehearse)

                walk = kernels.Walk(shape[1], *tiles, window)
                line = {"path": "kernels", "tiles": list(tiles),
                        "tiles_live": walk.tiles_live,
                        "tiles_walked": walk.tiles_walked}
            line.update(shape=list(shape), window=window,
                        device=device.device_kind)
            try:    # a tile the chip's fast memory does not take says so
                *ops, weights = operands(shape, 0, jnp.bfloat16)
                line.update(timed(core, ops, weights, args.turns))
                *few, weights = operands(short, 1, jnp.bfloat16)
                line["max_abs_err"] = against_the_rule(
                    core, few, weights, window)
                if window is not None:
                    line["window_off_by_one"] = {
                        str(other): against_the_rule(
                            functools.partial(core, window=other), few,
                            weights, window)["out"]
                        for other in (window - 1, window + 1)}
            except Exception as e:
                line["failed"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
