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

STATS = "trainer.model_stats"


def read(observed):
    window = program_spans.select(observed)
    if not window:
        return None
    first = window.steps[0].start_ns
    spans = [s for s in program_spans.ring()
             if s.name == STATS and s.start_ns >= first
             and s.attrs.get("rows_held_over_live")]
    if not spans:
        return None
    worst = max(max(s.attrs["rows_held_over_live"]) for s in spans)
    print(json.dumps({
        "phase": "moe_rows", "rows_held_over_live": worst,
        "records": [{"step": s.attrs.get("step"),
                     "held_over_live": s.attrs["rows_held_over_live"],
                     "chip_rows_max_over_mean":
                         s.attrs.get("chip_rows_max_over_mean")}
                    for s in spans]}), file=sys.stderr, flush=True)
    return worst
