"""The median of the Mamba-2 scan's decay ``exp(d_t A)`` over heads and
positions, from the program's own counter (``ssd_decay_p50`` in the
``trainer.model_stats`` spans the window's steps left in the recorder: a
value a Mamba-2 layer, over every head at 1024 positions of the sequence;
of the LAST record the layer farthest from 1/2).  The guard that the state
the benchmark makes keeps the recurrence where a position hears the ones
before it: near 1 the state only sums, near 0 it forgets at once and a
fault in the scan shows in no loss.  Beside it on standard error, layer by
layer, every record."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "ssd_decay_p50")
    if not records:
        return None
    print(json.dumps({
        "phase": "ssd_scan",
        "records": [{"step": step, "decay_p50": layers}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return max(records[-1][1], key=lambda median: abs(median - 0.5))
