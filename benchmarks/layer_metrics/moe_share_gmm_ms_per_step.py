"""Device time of the grouped matmuls (``%ragged-dot...``) of one chip's
share of the expert layer in one step, steps counted as
``moe_share_ms_per_step`` counts them."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "moe_share_ms_per_step").ms_per_step(
        observed, gmm_only=True)
