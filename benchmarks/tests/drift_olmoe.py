#!/usr/bin/env python3
"""Whether a routed cell's state keeps routing evenly while it trains: the
cell's own ``Trainer`` steps on the cell's own batches from the state
``program.make_state`` gives, for each of a few values of
``run.state.embed_scale``, with what the model sows into ``stats`` read
every few steps (largest expert's rows over the mean, hottest chip's rows
over the chips' mean, extents held over rows in use: worst layer, and layer
by layer for the first) and the time of every step between two read-backs.
Needs the chips the configuration's mesh names; one compile, the scale is
no part of the step.

    python3 benchmarks/tests/drift_olmoe.py [config] [--scales=100,400] [--steps=100] [--every=10] [seed ...]

One JSON line a seed and scale.  ``--rehearse``: the TINY sizes on virtual
CPU devices (set XLA_FLAGS for as many as the mesh has)."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def option(argv, name, default):
    for a in argv:
        if a.startswith(f"--{name}="):
            return a.split("=", 1)[1]
    return default


def main(argv):
    import jax
    import numpy as np

    import dlrover_tpu.trainer as trainer_pkg
    from benchmarks import common, program

    trainer_pkg.init()
    rehearse = "--rehearse" in argv
    args = [a for a in argv[1:] if not a.startswith("--")]
    scales = [float(s) for s in option(argv, "scales", "100").split(",")]
    steps, every = int(option(argv, "steps", 100)), int(option(argv, "every", 10))
    name = args[0] if args and not args[0].isdigit() else "olmoe1b7b_ep4"
    seeds = [int(s) for s in args if s.isdigit()] or [3200000051]
    config = common.read_json(common.HERE, "configs", name + ".json")
    family, _, trainer = program.make_trainer(config, rehearse)

    def worst(stats, name):
        return [float(v) for v in np.asarray(
            jax.device_get(program.stats_by_name(stats)[name]))]

    for seed in seeds:
        pool = program.make_pool(config, rehearse, seed, family)
        for scale in scales:
            cfg = {**config, "run": {**config["run"], "state": {
                **config["run"]["state"], "embed_scale": scale}}}
            state = program.make_state(trainer, family, cfg, rehearse, seed, pool)
            records, step_ms, t_last = [], [], None
            for step in range(1, steps + 1):
                batch = trainer.shard_batch(pool[(step - 1) % len(pool)])
                state, metrics = trainer.train_step(state, batch)
                loss = float(jax.device_get(metrics["loss"]))
                now = time.perf_counter()
                if t_last is not None:
                    step_ms.append(round(1e3 * (now - t_last), 2))
                t_last = now
                if step == 1 or step % every == 0:
                    load = worst(metrics["stats"], "load_max_over_mean")
                    records.append({
                        "step": step, "loss": round(loss, 4),
                        "load_by_layer": [round(v, 3) for v in load],
                        "chip_rows": round(max(worst(
                            metrics["stats"], "chip_rows_max_over_mean")), 4),
                        "held": max(worst(
                            metrics["stats"], "rows_held_over_live"))})
                    t_last = None     # the reads above are not a step's time
            print(json.dumps({"seed": seed, "embed_scale": scale,
                              "records": records, "step_ms": step_ms}),
                  flush=True)
            del state


if __name__ == "__main__":
    main(sys.argv)
