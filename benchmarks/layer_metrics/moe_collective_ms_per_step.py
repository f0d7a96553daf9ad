"""Device time of the expert layer's collectives in one step, on the first
chip: the all-gathers of the tokens over ``ep`` and the reduce-scatters of
the results, forward and backward (``moe_ms_per_step.collective``)."""

from benchmarks.common import load_module


def read(observed):
    moe = load_module("layer_metrics", "moe_ms_per_step")
    if not moe.shapes_of(observed):
        return None
    return moe.ms_per_step(observed, moe.collective)
