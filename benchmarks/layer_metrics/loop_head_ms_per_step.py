"""Device self time a step of a looped stack's heads: the output projection
after every loop step and its cross entropy by blocks of rows, forward,
rematerialised and backward, on the first chip: the program's scopes of
kind ``head_loss`` OUTSIDE the sub-scope ``exit``, from
``benchmarks/device_scopes.py``'s table.  Four heads where a plain model has
one: by the family's count 20% of the cell's matmul work.  Nothing to read
where the family is no looped one or the program has no scopes."""

from benchmarks.common import load_module


def read(observed):
    if not hasattr(observed.get("family"), "layer_applications"):
        return None
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "head_loss", "")
