"""The window's longest interval between two steps over its median one: 1.0x
in a clean window.  The whole window, from the program's recorder; this
reader also prints the window's ``phase: step_ledger`` line on standard error
(every interval, the ticks with their parts, the stalls explained)."""

from benchmarks import step_ledger


def read(observed):
    led = step_ledger.read(observed)
    if led is None:
        return None
    step_ledger.report(led)
    return step_ledger.step_max_over_median(led)
