"""The share of tokens whose ``k`` best experts by score-plus-bias are not
all inside the groups the router kept, from the program's own counter
(``group_dropped_share`` in the ``trainer.model_stats`` spans the window's
steps left in the recorder: the layer where it is SMALLEST, of the record
where it is smallest).  0 would say that the choice by groups decides
nothing on this state."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "group_dropped_share")
    if not records:
        return None
    smallest = min(min(layers) for _, layers in records)
    print(json.dumps({
        "phase": "moe_groups", "group_dropped_share": smallest,
        "records": [{"step": step, "group_dropped_share": layers}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return smallest
