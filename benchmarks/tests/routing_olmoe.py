#!/usr/bin/env python3
"""How a state of the OLMoE family routes the cell's own batch, before any
step, for each of a few values of ``run.state.embed_scale``: the count
``families/olmoe.py::condition``'s number was chosen from (PERF.md, section
6, PR 32).  One chip: the parameters are the program's initialiser's from
the cell's key (``model.init``, which is all ``Trainer.create_state`` makes
of them) with the rest of ``families/olmoe.py::state_rule`` applied (the
experts' matrices), held in the bfloat16 the step multiplies in; the forward pass is
the family's plain reference at the chip's default precision, one source
rank's sequences at a time.  A count of routed rows, no time.

    python3 benchmarks/tests/routing_olmoe.py [config] [--scales=1,50,..] [seed ...]

One JSON line a seed and scale, layer by layer: the largest expert's rows
over the mean (``moe_load_max_over_mean`` reads the same of the program's
counter), the hottest chip's rows over the chips' mean
(``chip_rows_max_over_mean``), and the fullest pass (one source rank's rows
on one chip) over the rows expected there: the ladder's first extent holds
1.25 times the expected rows, so a pass over 1.25 climbs."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def programs(config, family, rehearse):
    """(init, routed): the parameters from a key, in bfloat16, and the rows
    each expert gets of some sequences, [layers, experts]."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from benchmarks import program

    m = family.sizes(config, rehearse)
    k, experts = int(m["num_experts_per_tok"]), int(m["num_experts"])
    eps = float(m["rms_norm_eps"])
    model = family.build({**config, "run": {
        **config["run"], "attention_impl": "reference"}}, rehearse,
        program.sizes(config, rehearse)[1])

    rule = {path: factor for path, factor in
            family.state_rule(config, rehearse).items()
            if path != ("embed_tokens",)}       # the table's is ``scale`` below

    @jax.jit
    def init(key, ids):
        params = nn.meta.unbox(model.init(key, ids)["params"])
        return jax.tree_util.tree_map_with_path(
            lambda path, t: (t * rule.get(tuple(k.key for k in path), 1.0)
                             ).astype(jnp.bfloat16), params)

    @jax.jit
    def routed(params, ids, scale):
        f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731

        def layer(x, p):
            p = jax.tree.map(f32, p)
            x = x + family._attention(family._rms_norm(
                x, p["input_norm"]["scale"], eps), p["attn"], m)
            h = family._rms_norm(x, p["post_attn_norm"]["scale"], eps)
            logits = jnp.matmul(h, p["mlp"]["router"]["kernel"],
                                precision="highest")
            picked = jax.lax.top_k(logits, k)[1]
            rows = jnp.zeros(experts, jnp.int32).at[picked.ravel()].add(1)
            return x + family._experts(h, p["mlp"], m)[0], rows

        x = f32(params["embed_tokens"])[ids] * scale
        return jax.lax.scan(layer, x, params["layers"]["layer"])[1]

    return init, routed


def main(argv):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common, program

    rehearse = "--rehearse" in argv      # the TINY sizes, on the CPU
    args = [a for a in argv[1:] if not a.startswith("--")]
    scales = [1.0, 25.0, 50.0, 100.0, 200.0]
    for a in argv[1:]:
        if a.startswith("--scales"):
            scales = [float(s) for s in a.split("=", 1)[1].split(",")]
    name = args[0] if args and not args[0].isdigit() else "olmoe1b7b_ep4"
    seeds = [int(s) for s in args if s.isdigit()] or [3200000001, 3200000002]
    config = common.read_json(common.HERE, "configs", name + ".json")
    family = common.load_module("families", config["family"])
    batch, _ = program.sizes(config, rehearse)
    ranks = int(np.prod(list(config["run"]["mesh"].values())))
    init, routed = programs(config, family, rehearse)

    per_rank = batch // ranks
    for seed in seeds:
        t0 = time.time()
        pool = program.make_pool(config, rehearse, seed, family)
        ids = jnp.asarray(pool[0]["input_ids"])
        params = init(program.make_key(seed), ids[:1])
        for scale in scales:
            rows = np.stack([np.asarray(routed(
                params, ids[r * per_rank:(r + 1) * per_rank], scale))
                for r in range(ranks)])          # [rank, layer, expert]
            chip = rows.reshape(ranks, rows.shape[1], ranks, -1).sum(-1)
            expert_all, chip_all = rows.sum(0), chip.sum(0)
            print(json.dumps({
                "seed": seed, "embed_scale": scale,
                "load_max_over_mean": np.round(
                    expert_all.max(-1) / expert_all.mean(-1), 3).tolist(),
                "chip_rows_max_over_mean": np.round(
                    chip_all.max(-1) / chip_all.mean(-1), 3).tolist(),
                "fullest_pass_over_expected": np.round(
                    chip.max((0, 2)) / chip.mean((0, 2)), 3).tolist(),
                "seconds": round(time.time() - t0, 1),
            }), flush=True)
        del params


if __name__ == "__main__":
    main(sys.argv)
