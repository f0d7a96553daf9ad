"""Device self time a step under the program's scopes of kind attn.proj: the q/k/v/o projections, q/k norms, RoPE, an indexer's projections (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "attn_proj_scope_ms_per_step")
