#!/usr/bin/env python3
"""The readings the Keye-VL-2.0 family's limits stand between
(``TOKEN_ATOL``, ``MEDIAN_ATOL``, ``MEAN_ATOL`` and ``INDEX_LOSS_RTOL`` in
``families/keyevl.py``), on the chip at the published widths and the cell's
own size, on the state the cell starts from (``program.make_state``).  For
each seed, every set of token losses goes through the harness's own
comparison with the float32 reference (``jobs_shared.compare_losses``) and
is printed with each number beside its limit and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls, the selection
  by threshold, the sorted dispatch of the share), which has to come out
  correct, with ``L_I`` layer by layer beside the reference's;
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), which has to come out NOT
  correct;
* ``nearest``: a fault of the selection, the nearest 2048 keys in place of
  the highest 2048, NOT correct;
* with ``--absent``, the absent chips' experts added in, NOT correct;
* the shares of queries and tokens whose selection and routing hang on
  rounding, and the share's rows layer by layer before any step.

    python3 benchmarks/tests/precision_keyevl.py [--absent] [--key=embed_scale --values=50,100] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed (and a value of ``--key``, a key of ``run.state``: how
the state's rule was chosen).  Needs one chip.  ``--rehearse``: the TINY sizes on
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
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [3300000101]
    budget = float(next((a.split("=")[1] for a in argv
                         if a.startswith("--budget-seconds=")), "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(common.HERE, "configs", "keyevl2_30b_1of8.json")
    option = {a.split("=")[0]: a.split("=")[1] for a in argv if "=" in a}
    key = option.get("--key", "embed_scale")
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
               "nearest": {"nearest": True}}
    if "--absent" in argv:
        planted["absent"] = {"absent": True}
    reference = jax.jit(
        lambda p, i, l, **kw: family.reference(p, i, l, m, **kw),
        static_argnames=("round_through", "nearest", "absent"))

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
        want, index_loss, index_low, router_low = reference(params, ids, labels)
        want = np.asarray(want, np.float64)
        index_loss = np.asarray(index_loss, np.float64)
        sown = {k: np.asarray(v, np.float64) for k, v in sown.items()}
        rel = np.abs(sown["index_loss"] - index_loss) / index_loss
        line = {"seed": seed, key: value, "tokens": int(want.size),
                "system": {**verdict(got, want),
                           "index_loss": sown["index_loss"].tolist(),
                           "index_loss_rel_err": rel.tolist(),
                           "index_loss_rtol": family.INDEX_LOSS_RTOL}}
        line["system"]["correct"] = bool(
            line["system"]["correct"] and (rel <= family.INDEX_LOSS_RTOL).all())
        for name, kw in planted.items():
            losses, its_index_loss, _, _ = reference(params, ids, labels, **kw)
            line[name] = {**verdict(losses, want), "index_loss_rel_err": (
                np.abs(np.asarray(its_index_loss) - index_loss)
                / index_loss).tolist()}
        print(json.dumps({
            **line, "index_loss_reference": index_loss.tolist(),
            "index_low_margin_share_by_layer": [float(v) for v in index_low],
            "router_low_margin_share_by_layer": [float(v) for v in router_low],
            **{name + "_at_init": value.tolist()
               for name, value in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
