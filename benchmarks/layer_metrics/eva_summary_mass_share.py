"""The share of a query's softmax mass that lies on chunk summaries, from
the program's own counter: the mean over the queries past the first window
(``eva_summary_mass_share`` in the ``trainer.model_stats`` spans the window's
steps left in the recorder: the layer where the summaries decide least, of
the record where they decide least).  Beside it on standard error, layer by
layer, the largest pooling weight (``eva_pool_weight_max``: 1 / chunk is a
plain mean) and the further heads' loss of the same steps."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "eva_summary_mass_share")
    if not records:
        return None
    weights = dict(program_spans.model_stats(observed, "eva_pool_weight_max"))
    multi = dict(program_spans.model_stats(observed, "multi_byte_loss"))
    least = min(min(layers) for _, layers in records)
    print(json.dumps({
        "phase": "eva_attention", "eva_summary_mass_share": least,
        "records": [{"step": step, "summary_mass_share": layers,
                     "pool_weight_max": weights.get(step),
                     "multi_byte_loss": multi.get(step)}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return least
