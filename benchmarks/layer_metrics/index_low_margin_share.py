"""The share of a layer's queries whose selection hangs on rounding, from
the program's own counter: the ``topk``-th and the next index score closer
than ``ops/attention.py::INDEX_LOW_MARGIN`` (``index_low_margin_share`` in
the ``trainer.model_stats`` spans the window's steps left in the recorder:
the worst layer of the worst record).  Beside it on standard error, layer
by layer, the indexer's loss ``L_I`` of the same steps."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "index_low_margin_share")
    if not records:
        return None
    losses = dict(program_spans.model_stats(observed, "index_loss"))
    worst = max(max(layers) for _, layers in records)
    print(json.dumps({
        "phase": "index_selection", "index_low_margin_share": worst,
        "records": [{"step": step, "low_margin_share": layers,
                     "index_loss": losses.get(step)}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return worst
