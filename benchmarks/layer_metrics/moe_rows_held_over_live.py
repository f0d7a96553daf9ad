"""How far the expert layer's passes over its sorted rows follow the rows
in use, from the program's own counter: the extents the chips' passes ran
at, summed over chips and source ranks, over the rows the routing put
there (``rows_held_over_live`` in the ``trainer.model_stats`` spans the
window's steps left in the recorder, read as ``moe_load_max_over_mean``
reads its name: the worst layer of the worst record).  1 is no row moved
or masked that no expert sees; with every pass at the worst case, every
assignment of a source rank on this chip, it is the number of ``ep``
ranks.  Beside it on standard error, layer by layer: the hottest chip's
live rows over the chips' mean (``chip_rows_max_over_mean``), which is
what chooses the extent on the chip the others wait for."""

import json
import sys

from benchmarks import program_spans

def read(observed):
    records = program_spans.model_stats(observed, "rows_held_over_live")
    if not records:
        return None
    chips = dict(program_spans.model_stats(observed, "chip_rows_max_over_mean"))
    worst = max(max(layers) for _, layers in records)
    print(json.dumps({
        "phase": "moe_rows", "rows_held_over_live": worst,
        "records": [{"step": step, "held_over_live": layers,
                     "chip_rows_max_over_mean": chips.get(step)}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return worst
