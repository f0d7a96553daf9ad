"""Device self time a step of the scan between chunks alone (sub-scope
``state`` of ``attn.core``, all passes): the part of a delta-rule layer that
is a chain, one step a chunk of 64 positions, 128 a layer and pass at the
cell's 8192 positions (``kda_ms_per_step.ms_of``)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "kda_ms_per_step").ms_of(
        observed, ("state",))
