"""Device self time a step under the program's scopes of kind head_loss: the output head, the cross entropy and the sown loss terms (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "head_loss_scope_ms_per_step")
