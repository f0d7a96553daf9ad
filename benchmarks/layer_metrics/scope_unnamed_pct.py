"""Device self time of the instructions no scope of the program claims, after one hop of inheritance, over the busy time (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.unnamed_pct(observed)
