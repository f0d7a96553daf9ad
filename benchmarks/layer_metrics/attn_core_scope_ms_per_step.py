"""Device self time a step under the program's scopes of kind attn.core: from q, k, v to the attention's output, kernels, layout copies, selection and pooling included (``benchmarks/device_scopes.py``)."""

from benchmarks import device_scopes


def read(observed):
    return device_scopes.ms_per_step(observed, "attn_core_scope_ms_per_step")
