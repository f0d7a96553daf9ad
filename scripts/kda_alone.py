"""The gated delta rule alone at one cell's shape, on the chip: the
``jax.numpy`` body beside the Pallas kernels at each candidate tile.

For each, a layer's forward pass and forward + rematerialised forward +
backward (``jax.checkpoint`` around ``kda``, as the layer's ``remat``), in
milliseconds from the host's clock around a read-back; the kernels' own
device time by instruction name from one traced turn; and how far the
output and the five gradients are from the ``jax.numpy`` body on float32
operands.  One JSON line a candidate::

    python3 scripts/kda_alone.py --tiles "1,1,1;2,2,8"

A tile is ``chunks,heads,state_heads`` as ``ops/pallas/tuning.py::kda_tiling`` has it.
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPERANDS = ("q", "k", "v", "g", "beta")


def operands(batch, seq, heads, dim, seed):
    """As the model hands them over: q and k of unit length (q times
    ``dim^-1/2``) in bfloat16, a log decay with a half life of a few
    tokens, beta in (0, 2)."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, seq, heads, dim)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -0.15 * jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    return q, k, v, g, beta


def kernel_ms(trace_dir):
    """instruction name without its number -> ms of the custom calls the
    first chip ran."""
    from benchmarks import trace as trace_mod

    loaded = trace_mod.load(trace_mod.find_xplane(trace_dir))
    found = {}
    if loaded.device_ops:
        for name, start, end in loaded.device_ops[min(loaded.device_ops)]:
            if "tpu_custom_call" in name:
                kind = name.split(" = ")[0].lstrip("%")
                found[kind] = found.get(kind, 0.0) + (end - start) * 1e3
    return {kind: round(ms, 3) for kind, ms in found.items()}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--tiles", default="2,2,4")
    parser.add_argument("--shape", default="1,8192,8,128")
    parser.add_argument("--turns", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="the kernels in the interpreter (the CPU)")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import linear_attention
    from dlrover_tpu.ops.pallas.kda import kda_kernels

    full = operands(*(int(n) for n in args.shape.split(",")), args.seed)
    low = tuple(t.astype(jnp.bfloat16) for t in full[:3]) + full[3:]
    weights = jnp.cos(jnp.arange(full[2].size, dtype=jnp.float32)).reshape(
        full[2].shape)

    def plain(*o):
        return linear_attention._kda_chunked(*o, 64)

    def passes(fn):
        def loss(*o):
            return jnp.sum(jax.checkpoint(fn)(*o).astype(jnp.float32)
                           * weights)
        return jax.jit(fn), jax.jit(jax.grad(loss, argnums=range(5)))

    def timed(fn, *o):
        jax.block_until_ready(fn(*o))
        t0 = time.perf_counter()
        for _ in range(args.turns):
            out = fn(*o)
        jax.tree.map(lambda t: t.block_until_ready(), out)
        return (time.perf_counter() - t0) / args.turns * 1e3

    want_out, want_grads = (fn(*full) for fn in passes(plain))
    candidates = [("jnp", plain)] + [
        (tile, lambda *o, _t=tuple(int(n) for n in tile.split(",")):
         kda_kernels(*o, tile=_t, interpret=args.rehearse))
        for tile in args.tiles.split(";") if tile]
    for name, fn in candidates:
        forward, backward = passes(fn)
        t0 = time.perf_counter()
        out, grads = forward(*low), backward(*low)
        line = dict(body=name, first_call_s=round(time.perf_counter() - t0, 2))
        line["forward_ms"] = round(timed(forward, *low), 3)
        line["all_passes_ms"] = round(timed(backward, *low), 3)
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(backward(*low))
            line["kernels_ms"] = kernel_ms(trace_dir)
        err = jnp.abs(out.astype(jnp.float32) - want_out)
        line["out_err_max_mean"] = [float(err.max()), float(err.mean())]
        line["grad_err_over_max"] = {
            n: float(jnp.abs(g.astype(jnp.float32) - w).max()
                     / jnp.abs(w).max())
            for n, g, w in zip(OPERANDS, grads, want_grads)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
