"""Device time of the selection alone in one step, on the first chip: the
threshold searches (``sparse_attn_ms_per_step.select_loop``: for each block
of queries whose rows have more keys than they may keep, the search for the
``topk``-th largest score by counting and the search among the keys that
equal it), in the forward pass and the layer's rematerialised forward."""

from benchmarks.common import load_module


def read(observed):
    sparse = load_module("layer_metrics", "sparse_attn_ms_per_step")
    return sparse.union_ms_per_step(observed, sparse.select_loop)
