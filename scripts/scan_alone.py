"""The selective scan alone at the Phi-4-mini-flash cell's shape, on the
chip: the Pallas kernels of ``ops/pallas/selective_scan.py``.

The forward pass, the backward pass alone (on the residuals handed over: the
layer's ``remat`` keeps ``y`` and the chunks' starts, so no second forward)
and forward + backward in milliseconds from the host's clock around a
read-back, over float32 operands as the layer hands them over; and how far
``y`` and the five gradients are from the ``jax.numpy`` body on a shorter
sequence.  One JSON line::

    python3 scripts/scan_alone.py [--shape 1,16384,5120,16] [--turns 10]

``--rehearse``: the interpreter on the CPU at a tiny shape, to walk the
script before it costs chip time.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(batch, seq, channels, state, seed=0):
    """(drive, delta, A, B, C) as a conditioned Mamba layer hands them:
    ``delta`` near 0.07, ``A`` from -1 to -16."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    delta = jax.nn.softplus(
        jax.random.normal(keys[0], (batch, seq, channels)) - 2.6)
    return (delta * jax.random.normal(keys[1], (batch, seq, channels)), delta,
            -jnp.broadcast_to(
                jnp.arange(1.0, state + 1), (channels, state)),
            jax.random.normal(keys[2], (batch, seq, state)),
            jax.random.normal(keys[3], (batch, seq, state)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shape", default="1,16384,5120,16")
    parser.add_argument("--turns", type=int, default=10)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.shape, args.turns = "1,128,256,8", 1
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import selective_scan as scan
    from dlrover_tpu.ops.pallas.selective_scan import scan_kernels

    kernels = jax.jit(
        lambda *o: scan_kernels(*o, interpret=args.rehearse))
    shape = tuple(int(n) for n in args.shape.split(","))
    ops = operands(*shape)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.turns):
            out = fn(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.turns

    both = jax.jit(jax.grad(
        lambda *o: jnp.sum(kernels(*o)), argnums=tuple(range(5))))
    y, pull = jax.vjp(kernels, *ops)
    line = {"device": jax.devices()[0].device_kind, "shape": shape,
            "forward_ms": timed(kernels, *ops),
            "backward_ms": timed(jax.jit(pull), jnp.ones_like(y)),
            "forward_backward_ms": timed(both, *ops)}
    # against the ``jax.numpy`` body, where its chunk's history fits
    short = tuple(t[:, :256] if t.ndim == 3 else t for t in ops)
    weights = jax.random.normal(jax.random.PRNGKey(7), short[0].shape)

    def through(body):
        return jax.jit(jax.value_and_grad(
            lambda *o: jnp.sum(body(*o) * weights),
            argnums=tuple(range(5))))(*short)

    (got, got_grads), (want, want_grads) = through(kernels), through(
        lambda *o: scan._scan_chunked(*o, scan.CHUNK))
    line["summed_rel_err"] = float(abs(got - want) / abs(want))
    for name, g, w in zip(("drive", "delta", "A", "B", "C"), got_grads,
                          want_grads):
        line[f"d{name}_max_err_over_max"] = float(
            jnp.abs(g - w).max() / jnp.abs(w).max())
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
