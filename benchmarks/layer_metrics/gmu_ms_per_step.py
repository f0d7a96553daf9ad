"""Device self time a step of the gated memory units' core (``Y * silu(h
W_in)`` with the handed scan output ``Y``: elementwise, no scan, no
convolution), all passes, on the first chip: the program's scopes of kind
``attn.core`` with the sub-scope ``gmu``
(``models/llama.py::GatedMemoryUnit``), from
``benchmarks/device_scopes.py``'s table.  The unit's two projections are
``attn.proj``'s; the program keeps the product an operation of its own
(two barriers), or the compiler fuses it into a projection and it reads
under the projection's name; its gradient is fused into the memory's
(``attn.core`` / ``handed``) and is not in this number.  Nothing to read where the program has no such scope (an
older commit, another family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "gmu")
