#!/usr/bin/env python3
"""The readings the LFM2 family's limits stand between (``TOKEN_ATOL``,
``MEDIAN_ATOL`` and ``MEAN_ATOL`` in ``families/lfm2.py``), on the chip
at the published widths and the cell's own size, on the state the cell
starts from (``program.make_state``: the correction bias drawn, in the
state's buffers).  For each seed, every set of token losses goes through the
harness's own comparison with the float32 reference
(``jobs_shared.compare_losses``) and is printed with each number beside its
limit and the verdict:

* ``system``: the program's forward pass (bfloat16 matmuls; the softmax,
  the router's scores and the gates and taps of a ``conv`` layer in float32
  on bfloat16 operands; the FA2 kernels), which has to come out correct,
  with the counters that say the routing stays even and the taps hear
  earlier positions (the share's rows, the hottest expert's load,
  ``bias_abs_max``, ``gconv_past_tap_share``);
* ``float8``, the control: the reference in the program's place with its
  parameters rounded through float8 (e4m3), the nearest precision below the
  configuration's bfloat16, which has to come out NOT correct; and the same
  forward pass with ONE part at a precision below the one the program
  states for it (``families/lfm2.py::LOWER_PRECISION``: the two gates'
  products and the taps' sum through bfloat16, the published code's own
  arithmetic);
* each planted fault of ``families/lfm2.py::FAULTS`` (the ``B`` gate left
  out; the taps shifted by one position; SiLU put on the taps; the bias left
  out of the choice; the q/k norm left out; the weights not renormalised),
  NOT correct.

    python3 benchmarks/tests/precision_lfm2.py [--rules='[{"qk_norm_scale": 2}, ..]'] [--system-only] [--faults=a,b] [--seq=N] [--budget-seconds=N] [--rehearse] [seed ...]

One JSON line a seed and rule (keys of ``run.state`` put over the file's:
how the state's rule was chosen).  Needs one chip.  ``--rehearse``: the
TINY sizes on the CPU, to walk the tool before it costs chip time."""

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
    seeds = [int(a) for a in argv[1:] if a.isdigit()] or [6600000101]
    option = {a.split("=", 1)[0]: a.split("=", 1)[1] for a in argv if "=" in a}
    budget = float(option.get("--budget-seconds", "inf"))
    rehearse = "--rehearse" in argv
    config = common.read_json(
        common.HERE, "configs", "lfm2_24b_1of8.json")
    if "--seq" in option:
        config = {**config, "run": {**config["run"],
                                    "seq": int(option["--seq"])}}
    rules = json.loads(option.get("--rules", "[{}]"))
    family, model, trainer = program.make_trainer(config, rehearse)
    m = family.sizes(config, rehearse)

    @jax.jit
    def system(params, buffers, ids, labels):
        logits, sown = model.apply(
            {"params": params, "buffers": buffers}, ids, mutable=["stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return (-jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0],
                program.stats_by_name(sown["stats"]))

    faults = [f for f in option.get("--faults", "").split(",") if f] or list(
        family.LOWER_PRECISION + family.FAULTS)
    planted = {"float8": {"round_through": jnp.float8_e4m3fn},
               **{fault: {"fault": fault} for fault in faults}}
    if "--system-only" in argv:
        planted = {}
    reference = jax.jit(
        lambda p, b, i, l, **kw: family.reference(p, b, i, l, m, **kw),
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
            got, sown = system(state.params, state.buffers, ids, labels)
        params = nn.meta.unbox(state.params)
        buffers = nn.meta.unbox(state.buffers)
        want, low, rows, past = reference(params, buffers, ids, labels)
        want = np.asarray(want, np.float64)
        line = {"seed": seed, "rule": rule, "tokens": int(want.size),
                "system": verdict(got, want, np.asarray(low))}
        for name, kw in planted.items():
            losses = reference(params, buffers, ids, labels, **kw)[0]
            line[name] = verdict(losses, want)
        rows = np.asarray(rows, np.float64)
        first, held = m["first_expert"], m["num_experts"]
        print(json.dumps({
            **line,
            "choice_low_margin_share_reference": [float(v) for v in low],
            "gconv_past_tap_share_reference": [float(v) for v in past],
            "load_max_over_mean_reference": [
                float(r.max() / r.mean()) for r in rows],
            "share_rows_over_expected_reference": [
                float(r[first: first + held].sum() * r.size / (held * r.sum()))
                for r in rows],
            **{name + "_system": np.asarray(value_, np.float64).tolist()
               for name, value_ in sorted(sown.items())},
            "seconds": round(time.time() - t0, 1),
        }), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv)
