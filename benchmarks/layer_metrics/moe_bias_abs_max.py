"""The largest entry, in absolute value, of any routed layer's selection
bias, from the program's own counter (``bias_abs_max`` in the
``trainer.model_stats`` spans the window's steps left in the recorder: the
largest layer of the LAST record).  The load moves every entry by the
update rate a step, so from a bias of spread ``s`` it reads near ``3 s +
rate x steps``; a bias that no step moves reads the same in every record
(standard error has them all)."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "bias_abs_max")
    if not records:
        return None
    print(json.dumps({
        "phase": "moe_bias",
        "records": [{"step": step, "bias_abs_max": layers}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return max(records[-1][1])
