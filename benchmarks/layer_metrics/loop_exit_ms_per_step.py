"""Device self time a step of a looped stack's exits beside their heads: the
gate after every loop step, the exit distribution, the weighting of the
exits' losses and the entropy, all passes, on the first chip: the program's
scopes of kind ``head_loss`` with the sub-scope ``exit``
(``models/llama.py::_looped_stack``), from ``benchmarks/device_scopes.py``'s
table.  Nothing to read where the program has no such scope (an older
commit, a model without an exit gate)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "head_loss", "exit")
