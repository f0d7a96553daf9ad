"""Device self time a step of the routed block's choice (sub-scope ``route``
of kind ``moe``, all passes): the router's matmul, the scores, where the
model has them the bias and the groups (their best two, the groups kept),
the top-k, the weights' normalisation and the routing's counters
(``mla_attn_ms_per_step.ms_of``)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "moe", "route")
