"""Device self time a step of the two projections around routed experts
that work in a latent (sub-scope ``latent`` of kind ``moe``, all passes):
``h W_down`` before the sort and ``r W_up`` after the weighted sum, whole on
every chip (``mla_attn_ms_per_step.ms_of``).  Nothing to read where the
experts work at the model's own width."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "moe", "latent")
