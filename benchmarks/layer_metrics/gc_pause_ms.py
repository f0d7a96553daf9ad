"""The collector's pauses on the stepping thread over the window (``gc_ns``
of its ``trainer.step`` spans, summed)."""

from benchmarks import step_ledger


def read(observed):
    return step_ledger.metric(observed, step_ledger.gc_pause_ms)
