"""Device self time a step of the full-attention layers' softmax core in a
model that also has window layers (every causal pair: the FA2 kernels as
the dense cells run them, here at groups of six query heads a key head),
all passes, on the first chip: the program's scopes of kind ``attn.core``
OUTSIDE every sub-scope, from ``benchmarks/device_scopes.py``'s table.
Nothing to read where the family names no full layers beside window layers
(``full_shape``) or the program has no scopes."""

from benchmarks.common import load_module


def read(observed):
    if not hasattr(observed.get("family"), "full_shape"):
        return None
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "")
