#!/usr/bin/env python3
"""Whether the sparse-attention cell's state keeps routing evenly while it
trains (as ``drift_olmoe.py`` asks of its cell): the cell's own ``Trainer``
steps on the cell's own batches from the state ``program.make_state``
gives, for each of a few values of one key of ``run.state``, with what the
model sows into ``stats`` read every few steps, layer by layer (the largest
expert's rows over the mean, this chip's rows over a fair share, the
passes' extent over those rows, ``L_I``) and the time of every step between
two read-backs.  Needs one chip; one compile, the state's rule is no part
of the step.

    python3 benchmarks/tests/drift_keyevl.py [--key=embed_scale] [--values=50,100] [--steps=60] [--every=10] [seed ...]

One JSON line a seed and value.  ``--rehearse``: the TINY sizes on the CPU."""

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
    key = option(argv, "key", "embed_scale")
    config = common.read_json(common.HERE, "configs", "keyevl2_30b_1of8.json")
    values = [float(v) for v in option(
        argv, "values", str(config["run"]["state"][key])).split(",")]
    steps, every = int(option(argv, "steps", 60)), int(option(argv, "every", 10))
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [3300000051]
    family, _, trainer = program.make_trainer(config, rehearse)

    def by_layer(stats, name):
        return [round(float(v), 3) for v in np.asarray(
            jax.device_get(program.stats_by_name(stats)[name]))]

    for seed in seeds:
        pool = program.make_pool(config, rehearse, seed, family)
        for value in values:
            cfg = {**config, "run": {**config["run"], "state": {
                **config["run"]["state"], key: value}}}
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
                    records.append({
                        "step": step, "loss": round(loss, 4),
                        **{name: by_layer(metrics["stats"], name) for name in (
                            "load_max_over_mean", "share_rows_over_expected",
                            "rows_held_over_live", "index_loss")}})
                    t_last = None     # the reads above are not a step's time
            print(json.dumps({"seed": seed, key: value, "records": records,
                              "step_ms": step_ms}), flush=True)
            del state


if __name__ == "__main__":
    main(sys.argv)
