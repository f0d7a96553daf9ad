#!/usr/bin/env python3
"""The readings the Solar-Open2 family's limits stand between
(``TOKEN_ATOL``, ``MEDIAN_ATOL`` and ``MEAN_ATOL`` in
``families/solaropen2.py``), on the chip at the published widths and the
cell's own size, on the state the cell starts from
(``program.make_state``).  For each seed, every set of token losses goes
through the harness's own comparison with the float32 reference
(``jobs_shared.compare_losses``) and is printed with each number beside its
limit and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls; the decay, its
  running sums, the triangular solve, the state between chunks and the
  router's scores in float32), which has to come out correct, with the
  counters that say the mechanism decides something on this state
  (``kda_beta_over_one_share``, ``kda_decay_half_life``, the share's rows);
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), which has to come out NOT
  correct;
* each planted fault of ``families/solaropen2.py::FAULTS`` (no decay; beta
  in (0, 1); no convolution; rotary positions on the softmax layer; no
  output gates; no shared expert; a softmax router), NOT correct.

    python3 benchmarks/tests/precision_solaropen2.py [--key=embed_scale --values=1,100] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed (and a value of ``--key``, a key of ``run.state``: how
the state's rule was chosen).  Needs one chip.  ``--rehearse``: the TINY
sizes on the CPU, to walk the tool before it costs chip time."""

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
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [4100000101]
    option = {a.split("=")[0]: a.split("=")[1] for a in argv if "=" in a}
    budget = float(option.get("--budget-seconds", "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(
        common.HERE, "configs", "solaropen2_250b_1of32.json")
    key = option.get("--key", "embed_scale")
    values = [float(v) for v in option.get(
        "--values", str(config["run"]["state"].get(key, 1.0))).split(",")]
    family, model, trainer = program.make_trainer(config, rehearse)
    m = family.sizes(config, rehearse)

    @jax.jit
    def system(params, ids, labels):
        logits, sown = model.apply({"params": params}, ids, mutable=["stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return (-jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0],
                program.stats_by_name(sown["stats"]))

    planted = {"float8": {"round_through": jnp.float8_e4m3fn},
               **{fault: {"fault": fault} for fault in family.FAULTS}}
    reference = jax.jit(
        lambda p, i, l, **kw: family.reference(p, i, l, m, **kw),
        static_argnames=("round_through", "fault"))

    def verdict(got, want, low=None):
        ok, detail = compare_losses(family, got, want)
        err = np.abs(np.asarray(got, np.float64) - want)
        out = {"correct": ok, "token_err_p999": float(np.quantile(err, 0.999)),
               **{k: v for k, v in detail.items()
                  if k.endswith("_err") or k.endswith("_atol")}}
        if low is not None:
            out["low_margin_share_max"] = float(np.max(low))
            out["correct"] = bool(ok and out["low_margin_share_max"]
                                  <= family.LOW_MARGIN_SHARE_MAX)
        return out

    for seed, value in ((s, v) for s in seeds for v in values):
        if time.time() - t_start > budget:
            break
        t0 = time.time()
        pool = program.make_pool(config, rehearse, seed, family)
        cfg = {**config, "run": {**config["run"], "state": {
            **config["run"]["state"], key: value}}}
        state = program.make_state(trainer, family, cfg, rehearse, seed, pool)
        batch = trainer.shard_batch({k: v[:1] for k, v in pool[0].items()})
        ids, labels = batch["input_ids"], batch["labels"]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got, sown = system(state.params, ids, labels)
        params = nn.meta.unbox(state.params)
        want, low, over_one, life = reference(params, ids, labels)
        want = np.asarray(want, np.float64)
        line = {"seed": seed, key: value, "tokens": int(want.size),
                "system": verdict(got, want, np.asarray(low))}
        for name, kw in planted.items():
            losses = reference(params, ids, labels, **kw)[0]
            line[name] = verdict(losses, want)
        print(json.dumps({
            **line,
            "router_low_margin_share_reference": [float(v) for v in low],
            "beta_over_one_share_reference": [float(v) for v in over_one],
            "decay_half_life_reference": [float(v) for v in life],
            **{name + "_system": np.asarray(value_, np.float64).tolist()
               for name, value_ in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
