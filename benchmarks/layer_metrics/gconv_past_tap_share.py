"""How much of a ``conv`` layer's taps' result comes from EARLIER positions,
from the program's own counter (``gconv_past_tap_share`` in the
``trainer.model_stats`` spans the window's steps left in the recorder: a
value a ``conv`` layer, ``mean |w_0 v_{t-2} + w_1 v_{t-1}|`` over that plus
``mean |w_2 v_t|``, every channel at 1,024 positions of the sequence; of the
LAST record the layer farthest from 1/2).  The guard that the state the
benchmark makes keeps the convolution where a position hears the two before
it: near 0 the layer hears the present position alone and a fault in the
shift shows in no loss; near 1 it hears everything but.  Beside it on
standard error, layer by layer, every record."""

import json
import sys

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "gconv_past_tap_share")
    if not records:
        return None
    print(json.dumps({
        "phase": "gconv_taps",
        "records": [{"step": step, "past_tap_share": layers}
                    for step, layers in records]}), file=sys.stderr, flush=True)
    return max(records[-1][1], key=lambda share: abs(share - 0.5))
