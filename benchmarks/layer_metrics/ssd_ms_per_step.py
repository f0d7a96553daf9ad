"""Device self time a step of the Mamba-2 layers' scan, all passes, on the
first chip: the program's scopes of kind ``attn.core`` with the sub-scope
``ssd`` (``ops/ssd.py::ssd``: the scores ``C B^T`` a group, the decay mask a
head, the two products, the state's carry between chunks and the skip ``D
x``), from ``benchmarks/device_scopes.py``'s table.  By scope, not by shape:
whatever implements the scan is read the same.  Nothing to read where the
program has no such scope (an older commit, another family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "ssd")
