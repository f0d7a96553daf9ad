"""Of the worst chip's idle seconds in the traced window, the share in gaps
whose middle lies under a span of the program (``benchmarks/
program_spans.py``, which also puts the two clocks on one origin)."""

from benchmarks import program_spans


def read(observed):
    return program_spans.idle_attributed_pct(observed)
