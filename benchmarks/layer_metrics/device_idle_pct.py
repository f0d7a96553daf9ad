"""1 - the union of the device's operation intervals over the traced
window, on the worst chip, in per cent (``benchmarks/trace.py``)."""


def read(observed):
    return (observed.get("trace") or {}).get("idle_pct_worst_chip")
