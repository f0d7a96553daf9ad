"""The median of the selective scan's decay ``exp(delta A)``, from the
program's own counter (``ssm_decay_p50`` in the ``trainer.model_stats``
spans the window's steps left in the recorder: a value a Mamba layer, over
16 positions of the sequence, every eighth channel, all its state columns; the LAST
record's layers' mean).  The guard that the state the benchmark makes keeps
the recurrence where a position hears the ones before it: near 1 the state
only sums, near 0 it forgets at once and a fault in the scan shows in no
loss.  Beside it on standard error, layer by layer, every record, with
``lambda`` of each differential layer and the number of layers that read the
memory."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "ssm_decay_p50")
    if not records:
        return None
    lam = dict(program_spans.model_stats(observed, "diff_lambda"))
    readers = dict(program_spans.model_stats(observed, "memory_readers"))
    print(json.dumps({
        "phase": "ssm_scan",
        "records": [{"step": step, "decay_p50": layers,
                     "diff_lambda": lam.get(step),
                     "memory_readers": readers.get(step)}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    last = records[-1][1]
    return sum(last) / len(last)
