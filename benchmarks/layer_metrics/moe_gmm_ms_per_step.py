"""Device time of the grouped matmuls (``%ragged-dot...``: forward,
recomputed forward and both gradients of the gate, up and down matmuls of
every layer, with their metadata kernels) in one step, on the first
chip."""

from benchmarks.common import load_module


def read(observed):
    moe = load_module("layer_metrics", "moe_ms_per_step")
    if not moe.shapes_of(observed):
        return None
    return moe.ms_per_step(observed, moe.GMM.match)
