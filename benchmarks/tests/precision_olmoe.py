#!/usr/bin/env python3
"""The readings the OLMoE family's tolerances stand between, on the chip
at the published widths (``TOKEN_ATOL`` and ``MEAN_ATOL`` in
``families/olmoe.py``), and the routing the cell starts from:

* over a few seeds, how far the system's token losses (bfloat16 matmuls,
  FA2 kernel, sorted dispatch over ``ep``) lie from the float32 reference;
* how far the reference lies from itself when its parameters are rounded to
  float8 (e4m3), the nearest precision below the bfloat16 the configuration
  states, which has to come out as not correct;
* the largest expert's rows over the mean in each layer before any step.

    python3 benchmarks/tests/precision_olmoe.py [config] [seed ...]

One JSON line a seed.  Needs the chips the configuration's mesh names."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    import dlrover_tpu.trainer as trainer_pkg
    from benchmarks import common, program

    trainer_pkg.init()
    name = argv[1] if len(argv) > 1 else "olmoe1b7b_ep4"
    seeds = [int(s) for s in argv[2:]] or [3000000019, 2200000011, 7]
    config = common.read_json(common.HERE, "configs", name + ".json")
    family, model, trainer = program.make_trainer(config, False)
    n = int(np.prod([dict(trainer.mesh.shape).get(a, 1)
                     for a in trainer.data_axes]))

    def losses(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    @jax.jit
    def system(params, ids, labels):
        logits, sown = model.apply({"params": params}, ids, mutable=["stats"])
        load = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            sown["stats"]) if "load_max_over_mean" in jax.tree_util.keystr(path)]
        return losses(logits, labels), load[0].ravel()

    @jax.jit
    def reference(params, ids, labels):
        return family.reference_token_losses(params, ids, labels, config)

    @jax.jit
    def float8_reference(params, ids, labels):
        return family.reference_token_losses(
            params, ids, labels, config, round_through=jnp.float8_e4m3fn)

    for seed in seeds:
        t0 = time.time()
        pool = program.make_pool(config, False, seed, family)
        state = trainer.create_state(program.make_key(seed),
                                     pool[0]["input_ids"])
        batch = trainer.shard_batch({k: v[:n] for k, v in pool[0].items()})
        ids, labels = batch["input_ids"], batch["labels"]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got, load = system(state.params, ids, labels)
            got = np.asarray(got, np.float64)
        with trainer.mesh:
            params = nn.meta.unbox(state.params)
            want = np.asarray(reference(params, ids, labels), np.float64)
            low = np.asarray(float8_reference(params, ids, labels),
                             np.float64)
        err, err8 = np.abs(got - want), np.abs(low - want)
        print(json.dumps({
            "seed": seed, "sequences": n, "tokens": int(got.size),
            "system_token_max_abs_err": float(err.max()),
            "system_token_err_p999": float(np.quantile(err, 0.999)),
            "system_token_err_median": float(np.median(err)),
            "system_mean_abs_err": float(abs(got.mean() - want.mean())),
            "float8_token_max_abs_err": float(err8.max()),
            "float8_token_err_median": float(np.median(err8)),
            "float8_mean_abs_err": float(abs(low.mean() - want.mean())),
            "token_atol": family.TOKEN_ATOL, "mean_atol": family.MEAN_ATOL,
            # the routing before any step, layer by layer: what the
            # untrained weights make of uniform random tokens
            "load_max_over_mean_at_init": np.asarray(load).tolist(),
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
