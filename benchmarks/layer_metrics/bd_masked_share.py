"""The share of a step's data tokens that its noise masked, from the
program's own counter (``bd_masked_share`` in the ``trainer.model_stats``
spans the window's steps left in the recorder: the mean over the records).
A GAUGE, not a quantity to raise: under the linear schedule with ``t``
uniform a block it sits at 0.5; far from it the noise is not what the
configuration states (0: nothing is masked and the objective is empty).
Beside it on standard error, record by record, the largest ``1/t`` of the
step's noise (``bd_weight_max``: what makes a loss spike)."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "bd_masked_share")
    if not records:
        return None
    weights = dict(program_spans.model_stats(observed, "bd_weight_max"))
    shares = [value for _, values in records for value in values]
    mean = sum(shares) / len(shares)
    print(json.dumps({
        "phase": "block_diffusion", "bd_masked_share": mean,
        "records": [{"step": step, "masked_share": values,
                     "weight_max": weights.get(step)}
                    for step, values in records]}), file=sys.stderr, flush=True)
    return mean
