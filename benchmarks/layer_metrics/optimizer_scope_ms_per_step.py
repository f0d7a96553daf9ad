"""Device self time a step under the program's scopes of kind optimizer and grad_sync: the low-precision parameter view, the gradient norm, clipping, the update (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "optimizer_scope_ms_per_step")
