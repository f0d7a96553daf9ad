"""The routing's balance, from the program's own counter: the largest
expert's rows over the mean, the worst layer of the worst step among the
``trainer.model_stats`` spans the window's steps left in the recorder (one
every ``DLROVER_TPU_DIGEST_EVERY`` steps, read from a step that had ended).
1 is uniform; the number of experts is one expert taking everything.  The
records go to standard error layer by layer, so that a run shows whether
the imbalance was there when the window opened or grew inside it, and
where in the stack it sits."""

import json
import sys

from benchmarks import program_spans

def read(observed):
    records = program_spans.model_stats(observed, "load_max_over_mean")
    if not records:
        return None
    worst = max(max(layers) for _, layers in records)
    print(json.dumps({"phase": "moe_routing", "load_max_over_mean": worst,
                      "records": [{"step": step, "by_layer": layers}
                                  for step, layers in records]}),
          file=sys.stderr, flush=True)
    return worst
