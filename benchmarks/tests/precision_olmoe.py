#!/usr/bin/env python3
"""The readings the OLMoE family's limits stand between (``TOKEN_ATOL``,
``MEDIAN_ATOL`` and ``MEAN_ATOL`` in ``families/olmoe.py``), on the chips at
the published widths and the cell's own size, on the state the cell starts
from (``program.make_state``).  For each seed, every set of token losses
below goes through the harness's own comparison with the float32 reference
(``jobs_shared.compare_losses``, what ``reference_check`` decides ``correct``
by), and is printed with each number beside its limit and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls, FA2 kernel,
  sorted dispatch over ``ep``), which has to come out correct;
* ``float8``, the control: the reference put in the program's place with its
  parameters rounded through float8 (e4m3), the nearest precision below the
  bfloat16 the configuration states, which has to come out NOT correct;
* with ``--departures``, a fault of the expert layer planted the same way,
  which has to come out NOT correct or ``correct`` does not cover the layer
  the cell is there for: one chip's experts missing (their down projections
  zero: the exchange between chips left out); with ``--departures=both``
  also every token's weakest expert dropped (7 of 8);
* the share of each layer's tokens whose router margin is under
  ``LOW_MARGIN``, and from the program's counters on the cell's whole batch
  before any step, layer by layer: the largest expert's rows over the mean,
  the hottest chip's rows over the chips' mean, the extents held over the
  rows in use.

    python3 benchmarks/tests/precision_olmoe.py [config] [--departures[=both]] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed.  Needs the chips the configuration's mesh names.
After ``--budget-seconds`` no further seed is started.  ``--rehearse``: the
TINY sizes on virtual CPU devices (set XLA_FLAGS for as many as the mesh
has), to walk the tool before it costs chip time."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv):
    t_start = time.time()
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    import dlrover_tpu.trainer as trainer_pkg
    from benchmarks import common, program
    from benchmarks.jobs_shared import compare_losses

    trainer_pkg.init()
    args = [a for a in argv[1:] if not a.startswith("--")]
    name = args[0] if args and not args[0].isdigit() else "olmoe1b7b_ep4"
    seeds = [int(s) for s in args if s.isdigit()] or [
        3000000019, 2200000011, 7]
    budget = float(next((a.split("=")[1] for a in argv
                         if a.startswith("--budget-seconds=")), "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(common.HERE, "configs", name + ".json")
    family, model, trainer = program.make_trainer(config, rehearse)
    n = int(np.prod([dict(trainer.mesh.shape).get(a, 1)
                     for a in trainer.data_axes]))

    @jax.jit
    def system(params, ids, labels):
        logp = jax.nn.log_softmax(
            model.apply({"params": params}, ids).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    @jax.jit
    def routing(params, ids):
        return program.stats_by_name(model.apply(
            {"params": params}, ids, mutable=["stats"])[1]["stats"])

    @jax.jit
    def reference(params, ids, labels):
        return family.reference_forward(params, ids, labels, config, rehearse)

    @jax.jit
    def float8(params, ids, labels):
        return family.reference_token_losses(
            params, ids, labels, config, rehearse,
            round_through=jnp.float8_e4m3fn)

    @jax.jit
    def weakest_expert_dropped(params, ids, labels):
        m = family.sizes(config, rehearse)
        fewer = {**m, "num_experts_per_tok": m["num_experts_per_tok"] - 1}
        return family.reference_forward(params, ids, labels, fewer)[0]

    @jax.jit
    def one_chips_experts_missing(params, ids, labels):
        mlp = params["layers"]["layer"]["mlp"]
        local = mlp["down_proj"].shape[1] // dict(trainer.mesh.shape)["ep"]
        missing = {**mlp, "down_proj": mlp["down_proj"].at[:, :local].set(0)}
        return family.reference_token_losses(
            {**params, "layers": {"layer": {
                **params["layers"]["layer"], "mlp": missing}}},
            ids, labels, config, rehearse)

    planted = [float8]
    if "--departures" in argv or "--departures=both" in argv:
        planted.append(one_chips_experts_missing)
    if "--departures=both" in argv:
        planted.append(weakest_expert_dropped)

    def verdict(got, want):
        ok, detail = compare_losses(family, got, want)
        err = np.abs(np.asarray(got, np.float64) - want)
        return {"correct": ok, "token_err_p999": float(np.quantile(err, 0.999)),
                **{k: v for k, v in detail.items()
                   if k.endswith("_err") or k.endswith("_atol")}}

    @jax.jit
    def conversions(t):
        """Relative rms distance of a leaf from itself through float8: by
        this backend's own conversion, and by the reference's arithmetic."""
        by = {"convert": jnp.asarray(jnp.asarray(t, jnp.float8_e4m3fn), t.dtype),
              "arithmetic": family._round_through(t, jnp.float8_e4m3fn)}
        return {k: jnp.sqrt(jnp.mean(jnp.square(v - t)) / jnp.mean(jnp.square(t)))
                for k, v in by.items()}

    for seed in seeds:
        if time.time() - t_start > budget:
            break
        t0 = time.time()
        pool = program.make_pool(config, rehearse, seed, family)
        state = program.make_state(trainer, family, config, rehearse, seed, pool)
        batch = trainer.shard_batch({k: v[:n] for k, v in pool[0].items()})
        ids, labels = batch["input_ids"], batch["labels"]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got = system(state.params, ids, labels)
            sown = routing(state.params,
                           trainer.shard_batch(pool[0])["input_ids"])
        with trainer.mesh:
            params = nn.meta.unbox(state.params)
            want, low = reference(params, ids, labels)
            want = np.asarray(want, np.float64)
            line = {"seed": seed, "sequences": n, "tokens": int(want.size),
                    "system": verdict(got, want)}
            if seed == seeds[0]:
                line["float8_rel_rms_of_lm_head"] = {
                    k: float(v) for k, v in
                    conversions(params["lm_head"]["kernel"]).items()}
            for fn in planted:
                line[fn.__name__] = verdict(fn(params, ids, labels), want)
        print(json.dumps({
            **line,
            "low_margin_share_by_layer": [float(v) for v in low],
            "low_margin_share_max": family.LOW_MARGIN_SHARE_MAX,
            # the routing of the cell's batch before any step, layer by
            # layer: what the state makes of uniform random tokens
            **{name + "_at_init": np.asarray(value, np.float64).tolist()
               for name, value in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
