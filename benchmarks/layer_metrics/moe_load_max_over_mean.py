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

STATS = "trainer.model_stats"


def read(observed):
    window = program_spans.select(observed)
    if not window:
        return None
    first = window.steps[0].start_ns
    spans = [s for s in program_spans.ring()
             if s.name == STATS and s.start_ns >= first
             and s.attrs.get("load_max_over_mean")]
    if not spans:
        return None
    worst = max(max(s.attrs["load_max_over_mean"]) for s in spans)
    print(json.dumps({"phase": "moe_routing", "load_max_over_mean": worst,
                      "records": [{"step": s.attrs.get("step"),
                                   "by_layer": s.attrs["load_max_over_mean"]}
                                  for s in spans]}),
          file=sys.stderr, flush=True)
    return worst
