"""Device self time a step under the program's scopes of kind embed, norm and other: the token table, the norms, and what carries a path with no kind (the residual adds) (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "rest_scope_ms_per_step")
