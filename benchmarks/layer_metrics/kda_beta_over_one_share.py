"""The share of a delta-rule layer's betas above 1, from the program's own
counter (``kda_beta_over_one_share`` in the ``trainer.model_stats`` spans
the window's steps left in the recorder: the layer where it is largest, of
the record where it is largest).  Above 1 the update ``I - beta k k^T`` has a
negative eigenvalue: 0 would say that ``kda_allow_neg_eigval`` decides
nothing on this state.  Beside it on standard error, layer by layer, how far
the state remembers (``kda_decay_half_life``: the median channel's half life
in tokens)."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "kda_beta_over_one_share")
    if not records:
        return None
    life = dict(program_spans.model_stats(observed, "kda_decay_half_life"))
    largest = max(max(layers) for _, layers in records)
    print(json.dumps({
        "phase": "kda_attention", "kda_beta_over_one_share": largest,
        "records": [{"step": step, "beta_over_one_share": layers,
                     "decay_half_life": life.get(step)}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return largest
