#!/usr/bin/env python3
"""The readings the EvaByte family's limits stand between (``TOKEN_ATOL``,
``MEDIAN_ATOL``, ``MEAN_ATOL`` and ``MULTI_BYTE_RTOL`` in
``families/evabyte.py``), on the chip at the published widths and the cell's
own size, on the state the cell starts from (``program.make_state``).  For
each seed, every set of token losses goes through the harness's own
comparison with the float32 reference (``jobs_shared.compare_losses``) and
is printed with each number beside its limit and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls, the windows'
  scores and the pooling weights in float32), which has to come out correct,
  with the further heads' loss beside the reference's and the two counters
  that say the mechanism decides something on this state
  (``eva_summary_mass_share``, ``eva_pool_weight_max``);
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), which has to come out NOT
  correct;
* each planted fault of ``families/evabyte.py::FAULTS`` (a plain mean in
  place of the learned pooling; no summaries; the summary set off by one
  window either way; two softmaxes averaged), NOT correct.

    python3 benchmarks/tests/precision_evabyte.py [--key=pool_scale --values=1,2] [--budget-seconds=N] [--rehearse] [seed ...]

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
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [3500000101]
    option = {a.split("=")[0]: a.split("=")[1] for a in argv if "=" in a}
    budget = float(option.get("--budget-seconds", "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(common.HERE, "configs", "evabyte_l4.json")
    key = option.get("--key", "pool_scale")
    values = [float(v) for v in option.get(
        "--values", str(config["run"]["state"][key])).split(",")]
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

    def verdict(got, want):
        ok, detail = compare_losses(family, got, want)
        err = np.abs(np.asarray(got, np.float64) - want)
        return {"correct": ok, "token_err_p999": float(np.quantile(err, 0.999)),
                **{k: v for k, v in detail.items()
                   if k.endswith("_err") or k.endswith("_atol")}}

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
        want, multi_byte, share, largest = reference(params, ids, labels)
        want = np.asarray(want, np.float64)
        multi_byte = float(multi_byte)
        sown = {k: np.asarray(v, np.float64) for k, v in sown.items()}
        rel = abs(float(sown["multi_byte_loss"][0]) - multi_byte) / multi_byte
        line = {"seed": seed, key: value, "tokens": int(want.size),
                "system": {**verdict(got, want), "multi_byte_rel_err": rel,
                           "multi_byte_rtol": family.MULTI_BYTE_RTOL}}
        line["system"]["correct"] = bool(
            line["system"]["correct"] and rel <= family.MULTI_BYTE_RTOL)
        for name, kw in planted.items():
            losses, its_multi_byte, _, _ = reference(params, ids, labels, **kw)
            line[name] = {**verdict(losses, want), "multi_byte_rel_err": abs(
                float(its_multi_byte) - multi_byte) / multi_byte}
        print(json.dumps({
            **line, "multi_byte_loss_reference": multi_byte,
            "summary_mass_share_reference": [float(v) for v in share],
            "pool_weight_max_reference": [float(v) for v in largest],
            **{name + "_system": value.tolist()
               for name, value in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
