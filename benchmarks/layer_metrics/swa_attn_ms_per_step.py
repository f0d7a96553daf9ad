"""Device self time a step of the window layers' softmax core (a causal
band of ``sliding_window`` positions: the FA2 kernels with ``window``, or
whatever implements it), all passes, on the first chip: the program's
scopes of kind ``attn.core`` with the sub-scope ``window``
(``models/llama.py::Attention`` of kind ``swa``), from
``benchmarks/device_scopes.py``'s table.  By scope, not by shape.  Nothing
to read where the program has no such scope (an older commit, another
family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "window")
