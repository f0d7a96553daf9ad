"""The latent-attention core's share of the step: the device self time a
step under ``attn.core`` / ``latent`` (``mla_attn_ms_per_step``: forward,
rematerialised and backward passes of every latent layer) over the device's
busy time a step, both from ``benchmarks/device_scopes.py``'s table of the
traced steps.  The number that says whether the mechanism does most of the
work in a cell: some 60 where every layer is latent at 32 heads, 5.6 where
one layer of seven is at 8.  By scope, not by shape; nothing to read where
the program has no such scope (an older commit, another family)."""

from benchmarks import device_scopes
from benchmarks.common import load_module


def read(observed):
    took_ms = load_module(
        "layer_metrics", "mla_attn_ms_per_step").read(observed)
    if not took_ms:
        return None
    # a table stands where the core's time was read
    return 100.0 * took_ms / device_scopes.table_of(observed)["busy_ms"]
