"""Device self time a step under the program's scopes of kind mlp and moe: the dense feed-forward or the routed block (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "ffn_scope_ms_per_step")
