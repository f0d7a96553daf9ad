#!/usr/bin/env python3
"""The readings the Ouro family's limits stand between (``TOKEN_ATOL``,
``MEAN_ATOL``, ``EXIT_TOKEN_ATOL``, ``EXIT_P_ATOL`` and ``OBJECTIVE_RTOL`` in
``families/ouro.py``), on the chip at the published widths and the cell's
own size, on the state the cell starts from (``program.make_state``).  For
each seed, every set of token losses goes through the harness's own
comparison with the float32 reference (``jobs_shared.compare_losses``) and
is printed with each number beside its limit, the exits' errors beside
theirs, and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls, FA2, the
  softmax, the gates and the objective in float32), which has to come out
  correct, with its counters (the exit distribution's entropy, the last
  exit's mass, every exit's mean cross entropy);
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), the nearest precision below the
  configuration's bfloat16, which has to come out NOT correct;
* each planted fault of ``families/ouro.py::FAULTS``, NOT correct.

    python3 benchmarks/tests/precision_ouro.py [--system-only] [--faults=a,b] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed.  Needs one chip.  ``--rehearse``: the TINY sizes on
the CPU, to walk the tool before it costs chip time."""

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
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [6100000101]
    option = {a.split("=")[0]: a.split("=")[1] for a in argv if "=" in a}
    budget = float(option.get("--budget-seconds", "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(common.HERE, "configs", "ouro2b6_l8.json")
    family, model, trainer = program.make_trainer(config, rehearse)
    m = family.sizes(config, rehearse)

    @jax.jit
    def system(params, ids, labels):
        logits = model.apply({"params": params}, ids).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    exits = jax.jit(lambda p, i: family.system_exits(p, i, config, rehearse))
    faults = option.get("--faults", ",".join(family.FAULTS)).split(",")
    planted = {} if "--system-only" in argv else {
        "float8": {"round_through": jnp.float8_e4m3fn},
        **{fault: {"fault": fault} for fault in faults if fault}}
    reference = jax.jit(
        lambda p, i, t, w, **kw: family.reference(p, i, t, w, m, **kw),
        static_argnames=("round_through", "fault"))
    errors_of = jax.jit(family.exit_errors)

    def verdict(losses, theirs, want, weights):
        ok, detail = compare_losses(family, losses, want["ce"][-1])
        err = np.abs(np.asarray(losses, np.float64)
                     - np.asarray(want["ce"][-1], np.float64))
        errors = errors_of(theirs, want, weights)
        held = bool(family.exits_agree(errors))
        return {"correct": bool(ok and held), "by_tokens": ok,
                "by_exits": held,
                **{k: float(v) for k, v in errors.items()},
                "token_err_p999": float(np.quantile(err, 0.999)),
                "token_median_abs_err": float(np.median(err)),
                **{k: v for k, v in detail.items()
                   if k.endswith("_err") or k.endswith("_atol")}}

    for seed in seeds:
        if time.time() - t_start > budget:
            break
        t0 = time.time()
        pool = program.make_pool(config, rehearse, seed, family)
        state = program.make_state(trainer, family, config, rehearse, seed,
                                   pool)
        batch = trainer.shard_batch({k: v[:1] for k, v in pool[0].items()})
        ids, labels = batch["input_ids"], batch["labels"]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got = system(state.params, ids, labels)
        params = nn.meta.unbox(state.params)
        targets, weights = family.model_targets(ids, labels)
        got_exits = exits(params, ids)
        want = reference(params, ids, targets, weights)
        line = {"seed": seed, "tokens": int(got.size),
                "objective_reference": float(want["objective"]),
                "exit_entropy_reference": float(want["entropy"]),
                "limits": {"exit_token": family.EXIT_TOKEN_ATOL,
                           "exit_p": family.EXIT_P_ATOL,
                           "objective": family.OBJECTIVE_RTOL},
                "system": verdict(got, got_exits, want, weights),
                "counters": {k: np.asarray(got_exits[k], np.float64).tolist()
                             for k in ("loop_exit_entropy",
                                       "loop_exit_mass_last",
                                       "loop_ce_by_step")}}
        print(json.dumps({**line, "seconds": round(time.time() - t0, 1)}),
              flush=True)
        for name, kw in planted.items():
            if time.time() - t_start > budget:
                break
            t1 = time.time()
            theirs = reference(params, ids, targets, weights, **kw)
            print(json.dumps({
                "seed": seed, name: verdict(
                    theirs["ce"][-1], theirs, want, weights),
                "seconds": round(time.time() - t1, 1)}), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
