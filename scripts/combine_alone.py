"""The expert layer's two sums by token alone at the routed cells' shapes,
on the chip: every candidate body of ``models/moe.py::_sum_by_token``.

A pass over ``X`` sorted rows serves ``T x k`` slots.  The forward sum
takes the rows times their weights (``_down_and_sum``), the backward sum
the rows' cotangents as they are (``_rows_of_bwd``); both return ``[T, D]``
float32 from bfloat16 rows, and the callers cast to bfloat16.  Bodies:

``slots``       gather every token's ``k`` slots, ``einsum`` over ``k``:
                the layer's one body until PR 50, and since then its body
                where the rows are many beside the slots
``slots_mul``   the same gather, multiply and ``sum(axis=1)``
``shift``       rows into token order, ``log2 k`` shifted adds, gather ``T``
``window``      rows into token order, cast and weighted, one pass over
                ``k`` shifted slices of the float32 rows
``window_narrow``  the same pass over the rows as they are, cast and weight
                inside it: the layer's body where the rows are few
``scatter``     scatter-add of the weighted rows at their tokens
``sorted_scatter``  the same in token order, indices declared sorted

The two the layer kept are ``models/moe.py::_sum_by_token`` itself, held to
one body; the others live here alone.

One JSON line a shape, extent and pass: milliseconds a call from the host's
clock around a read-back, each body's largest difference from ``slots``::

    python3 scripts/combine_alone.py [--shapes ling:all,solar,olmoe:all]

``cell:all`` runs every extent of the cell's ladder, ``cell:2`` the first
two, ``cell`` the first.

``--rehearse``: the CPU at a tiny shape.  ``--described``: compile every
body at the real shapes for a described v5e and count its instructions by
opcode (no chip, no times); ``--hlo DIR`` writes the compiled texts there.
"""

import argparse
import collections
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: cell -> tokens of a pass, top_k, width, experts, experts held
SHAPES = {
    "ling": (16384, 8, 2560, 512, 16),
    "solar": (8192, 8, 4096, 320, 10),
    "sdar": (16384, 8, 2048, 128, 16),
    "keye": (8192, 8, 2048, 128, 16),
    "olmoe": (8192, 8, 2048, 64, 16),
}


def bodies():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def gathered(rows, slot):
        if len(rows) < slot.size:
            return rows.at[slot].get(mode="fill", fill_value=0)
        return rows[slot]

    def slots_mul(rows, picked, slot, live, weights=None):
        mine = gathered(rows, slot).astype(jnp.float32)
        if weights is not None:
            mine = mine * weights.astype(jnp.float32)[..., None]
        return mine.sum(axis=1)

    def of_the_layer(body):
        """``moe._sum_by_token`` as the layer runs it, held to one of its
        two bodies whatever the shapes."""
        def run(*operands):
            rule = moe._combine_body
            moe._combine_body = lambda extent, slots: body
            try:
                return moe._sum_by_token(*operands)
            finally:
                moe._combine_body = rule
        return run

    def by_row(weights, picked):
        return weights.reshape(-1)[picked].astype(jnp.float32)

    def in_token_order(rows, picked, slot, live, weights):
        """``(token of each row, the rows weighted in float32)``, sorted by
        assignment, the rows that hold none behind every token."""
        tokens, fan = slot.shape
        operands = (jnp.where(live[:, 0], picked, tokens * fan),
                    jnp.arange(len(rows), dtype=jnp.int32))
        if weights is not None:
            operands += (by_row(weights, picked),)
        key, at, *scale = jax.lax.sort(operands, num_keys=1)
        total = rows[at].astype(jnp.float32)
        if scale:
            total = total * scale[0][:, None]
        return key // fan, total

    def first_rows(total, slot, live):
        """A token's sum from the first row of its run, zero where it has
        none."""
        count = (slot < live.sum()).sum(axis=1)
        first = jnp.where(count > 0, jnp.cumsum(count) - count, len(total))
        return total.at[first].get(mode="fill", fill_value=0)

    def shift(rows, picked, slot, live, weights=None):
        token, total = in_token_order(rows, picked, slot, live, weights)
        step = 1
        while step < slot.shape[1]:
            same = (token[step:] == token[:-step])[:, None]
            total = total + jnp.pad(
                jnp.where(same, total[step:], 0), ((0, step), (0, 0)))
            step *= 2
        return first_rows(total, slot, live)

    def window(rows, picked, slot, live, weights=None):
        token, total = in_token_order(rows, picked, slot, live, weights)
        fan, extent = slot.shape[1], len(rows)
        later = jnp.pad(total, ((0, fan - 1), (0, 0)))
        theirs = jnp.pad(token, (0, fan - 1), constant_values=-1)
        for j in range(1, fan):
            total = total + jnp.where(
                (theirs[j:j + extent] == token)[:, None],
                later[j:j + extent], 0)
        return first_rows(total, slot, live)

    def scatter(rows, picked, slot, live, weights=None):
        tokens, fan = slot.shape
        scaled = rows.astype(jnp.float32)
        if weights is not None:
            scaled = scaled * by_row(weights, picked)[:, None]
        at = jnp.where(live[:, 0], picked // fan, tokens)
        return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[at].add(
            scaled, mode="drop")

    def sorted_scatter(rows, picked, slot, live, weights=None):
        token, total = in_token_order(rows, picked, slot, live, weights)
        return jnp.zeros((slot.shape[0], rows.shape[1]), jnp.float32).at[
            token].add(total, mode="drop", indices_are_sorted=True)

    return dict(slots=of_the_layer("slots"), slots_mul=slots_mul, shift=shift,
                window=window, window_narrow=of_the_layer("rows"),
                scatter=scatter, sorted_scatter=sorted_scatter)


def routing(seed, tokens, fan, experts, held):
    """``(order, inverse, sizes, weights)`` as ``local_experts`` sorts a
    uniform choice of ``fan`` of ``experts`` a token, the first ``held``
    here."""
    import jax
    import jax.numpy as jnp

    k_choice, k_weight = jax.random.split(jax.random.PRNGKey(seed))
    top_i = jax.lax.top_k(
        jax.random.uniform(k_choice, (tokens, experts)), fan)[1]
    mine = top_i < held
    key = jnp.where(mine, top_i, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = (key[:, None] == jnp.arange(held)).sum(axis=0, dtype=jnp.int32)
    weights = jnp.where(mine, jax.random.uniform(
        k_weight, top_i.shape, minval=0.05, maxval=0.5), 0)
    return order, jnp.argsort(order), sizes, weights.astype(jnp.bfloat16)


def opcodes(text):
    """Instructions of the entry computation by opcode, fusions by kind."""
    entry = text[text.index("ENTRY"):]
    found = collections.Counter()
    for line in entry.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m and m.group(1) not in ("parameter", "get-tuple-element",
                                    "tuple", "bitcast", "constant"):
            kind = re.search(r"kind=(\w+)", line)
            found[m.group(1) + (":" + kind.group(1) if kind else "")] += 1
    return dict(found)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--bodies", default="")
    parser.add_argument("--turns", type=int, default=20)
    parser.add_argument("--seed", type=int, default=50)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--described", action="store_true")
    parser.add_argument("--hlo", default="")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    if args.described:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.moe import ladder

    candidates = bodies()
    names = args.bodies.split(",") if args.bodies else list(candidates)
    place = None
    if args.described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    device = "described v5e" if place else jax.devices()[0].device_kind
    for cell in args.shapes.split(","):
        cell, _, rungs = cell.partition(":")
        tokens, fan, width, experts, held = SHAPES[cell]
        if args.rehearse:
            tokens, width = 256, 128
        order, inverse, sizes, weights = routing(
            args.seed, tokens, fan, experts, held)
        extents = ladder(tokens * fan, held, experts)
        rows_of = {e: jax.random.normal(
            jax.random.PRNGKey(args.seed + 1), (e, width)).astype(jnp.bfloat16)
            for e in (extents if rungs == "all" else extents[:int(rungs or 1)])}
        for extent, rows in rows_of.items():
            picked = order[:extent]
            slot = inverse.reshape(weights.shape)
            live = (jnp.arange(extent) < sizes.sum())[:, None]
            # what the callers hand over: the rows behind the last group 0
            rows = jnp.where(live, rows, 0)
            for which, given in (("forward", weights), ("backward", None)):
                line = {"cell": cell, "pass": which, "tokens": tokens,
                        "k": fan, "width": width, "extent": extent,
                        "rows_over_slots": round(extent / (tokens * fan), 4),
                        "live": int(sizes.sum()), "device": device,
                        "ms": {}, "max_abs_diff": {}}
                want = None
                for name in names:
                    operands = (rows, picked, slot, live) + (
                        () if given is None else (given,))
                    fn = jax.jit(
                        lambda *a, body=candidates[name]: body(*a).astype(
                            jnp.bfloat16))
                    if place:
                        compiled = fn.lower(*(jax.ShapeDtypeStruct(
                            a.shape, a.dtype, sharding=place)
                            for a in operands)).compile()
                        line["ms"][name] = "not measured"
                        line.setdefault("opcodes", {})[name] = opcodes(
                            compiled.as_text())
                        line.setdefault("temp_mib", {})[name] = round(
                            compiled.memory_analysis().temp_size_in_bytes
                            / 2 ** 20, 1)
                    else:
                        compiled = fn.lower(*operands).compile()
                        got = jax.block_until_ready(compiled(*operands))
                        t0 = time.perf_counter()
                        for _ in range(args.turns):
                            out = compiled(*operands)
                        jax.block_until_ready(out)
                        line["ms"][name] = round(
                            1e3 * (time.perf_counter() - t0) / args.turns, 3)
                        got = np.asarray(got.astype(jnp.float32))
                        if want is None:
                            want = got
                            line["abs_max"] = float(np.abs(want).max())
                        line["max_abs_diff"][name] = float(
                            np.abs(got - want).max())
                    if args.hlo:
                        os.makedirs(args.hlo, exist_ok=True)
                        with open(os.path.join(
                                args.hlo, f"{cell}_{extent}_{which}_{name}.txt"),
                                "w") as f:
                            f.write(compiled.as_text())
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
