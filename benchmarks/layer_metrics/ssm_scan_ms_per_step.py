"""Device self time a step of the state-space layers' selective scan, all
passes, on the first chip: the program's scopes of kind ``attn.core`` with
the sub-scope ``scan`` (``ops/selective_scan.py::selective_scan``: the
drive ``delta a``, the recurrence, the skip ``D a``; the Pallas kernels of
``ops/pallas/selective_scan.py`` on a TPU with what ``jax.numpy`` does
around them: the lane broadcast of ``B`` and ``C``, the lanes' sum of their
gradients), from ``benchmarks/device_scopes.py``'s table.  By scope, not by
shape: whatever implements the scan is read the same.  Nothing to read
where the program has no such scope (an older commit, another family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "scan")
