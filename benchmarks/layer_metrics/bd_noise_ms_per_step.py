"""Device self time a step of the sampling of block diffusion's noise (the
rates a block, the masks a token, the noisy copy and the NELBO's weights:
sub-scope ``noise`` of kind ``embed``, all passes;
``bd_attn_ms_per_step.ms_of``)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "bd_attn_ms_per_step").ms_of(
        observed, "embed", ("noise",))
