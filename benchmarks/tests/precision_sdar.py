#!/usr/bin/env python3
"""The readings the SDAR family's limits stand between (``TOKEN_ATOL``,
``MEDIAN_ATOL``, ``MEAN_ATOL`` and ``OBJECTIVE_RTOL`` in
``families/sdar.py``), on the chip at the published widths and the cell's
own size, on the state the cell starts from (``program.make_state``).  For
each seed, every set of token losses goes through the harness's own
comparison with the float32 reference (``jobs_shared.compare_losses``) and
is printed with each number beside its limit, the objective's error beside
its own, and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls, the attention
  under the block-diffusion mask through the mask-operand kernels, the
  router's scores, the softmax and the objective in float32), which has to
  come out correct, with the counters that say where the routed rows went
  (the share's rows over a fair share and the ladder's extent over the rows
  in use, layer by layer: the hazard of a quarter of the rows being one
  token) and what the noise masked;
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), which has to come out NOT
  correct;
* each planted fault of ``families/sdar.py::FAULTS``, NOT correct.

    python3 benchmarks/tests/precision_sdar.py [--rules='[{"q_scale": 4}, {"expert_scale": 2}]'] [--system-only] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed (and a rule of ``--rules``, each a set of keys laid over
the file's ``run.state``: how the state's rule was chosen).  ``--system-only``: no control and no fault
(the routing's counters over many seeds).  Needs one chip.  ``--rehearse``:
the TINY sizes on the CPU, to walk the tool before it costs chip time."""

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
    config = common.read_json(common.HERE, "configs", "sdar_30b_1of8.json")
    rules = json.loads(option.get("--rules", "[{}]"))
    family, model, trainer = program.make_trainer(config, rehearse)
    m = family.sizes(config, rehearse)

    @jax.jit
    def system(params, ids, labels):
        logits, sown = model.apply(
            {"params": params}, ids, mutable=["stats", "losses"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

        def taken(at):
            return -jnp.take_along_axis(logp, at[..., None], axis=-1)[..., 0]

        return (taken(labels), sown["losses"]["nelbo"][0], taken(ids),
                program.stats_by_name(sown["stats"]))

    planted = {} if "--system-only" in argv else {
        "float8": {"round_through": jnp.float8_e4m3fn},
        **{fault: {"fault": fault} for fault in family.FAULTS}}
    reference = jax.jit(
        lambda p, n, i, w, l, **kw: family.reference(p, n, i, w, l, m, **kw),
        static_argnames=("round_through", "fault"))
    noise = jax.jit(lambda i: family.draw_noise(i, config, rehearse))

    def verdict(got, objective, terms, want, want_objective, want_terms,
                weights, low=None):
        ok, detail = compare_losses(family, got, want)
        err = np.abs(np.asarray(got, np.float64) - want)
        rel = abs(float(objective) - want_objective) / want_objective
        masked = float(family.masked_median_abs_err(terms, want_terms, weights))
        out = {"correct": bool(ok and rel <= family.OBJECTIVE_RTOL
                               and masked <= family.MASKED_MEDIAN_ATOL),
               "by_tokens": ok, "objective_rel_err": rel,
               "objective_rtol": family.OBJECTIVE_RTOL,
               "masked_median_abs_err": masked,
               "masked_median_atol": family.MASKED_MEDIAN_ATOL,
               "token_err_p999": float(np.quantile(err, 0.999)),
               **{k: v for k, v in detail.items()
                  if k.endswith("_err") or k.endswith("_atol")}}
        if low is not None:
            out["low_margin_share_max"] = float(np.max(low))
            out["correct"] = bool(out["correct"] and out[
                "low_margin_share_max"] <= family.LOW_MARGIN_SHARE_MAX)
        return out

    for seed, rule in ((s, r) for s in seeds for r in rules):
        if time.time() - t_start > budget:
            break
        t0 = time.time()
        pool = program.make_pool(config, rehearse, seed, family)
        cfg = {**config, "run": {**config["run"], "state": {
            **config["run"]["state"], **rule}}}
        state = program.make_state(trainer, family, cfg, rehearse, seed, pool)
        batch = trainer.shard_batch({k: v[:1] for k, v in pool[0].items()})
        ids, labels = batch["input_ids"], batch["labels"]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got, objective, terms, sown = system(state.params, ids, labels)
        params = nn.meta.unbox(state.params)
        noisy, weights = noise(ids)
        want, want_objective, low, want_terms = reference(
            params, noisy, ids, weights, labels)
        want = np.asarray(want, np.float64)
        want_objective = float(want_objective)
        line = {"seed": seed, "rule": rule, "tokens": int(want.size),
                "objective_reference": want_objective,
                "system": verdict(got, objective, terms, want, want_objective,
                                  want_terms, weights, np.asarray(low))}
        for name, kw in planted.items():
            losses, theirs, _, their_terms = reference(
                params, noisy, ids, weights, labels, **kw)
            line[name] = verdict(losses, theirs, their_terms, want,
                                 want_objective, want_terms, weights)
        print(json.dumps({
            **line,
            "router_low_margin_share_reference": [float(v) for v in low],
            **{name + "_system": np.asarray(value_, np.float64).tolist()
               for name, value_ in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
