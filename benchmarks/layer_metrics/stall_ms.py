"""What the window lost to stalls: over the intervals beyond twice the median
one, the sum of interval less median.  0 in a clean window; the save's cost
in a window that holds one."""

from benchmarks import step_ledger


def read(observed):
    return step_ledger.metric(observed, step_ledger.stall_ms)
