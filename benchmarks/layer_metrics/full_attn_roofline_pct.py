"""The full-attention layers' softmax core's share of its roofline, in a
model that also has window layers: the least time the chip could take for
the work the model asks of one step (every causal pair at the full layers'
head count: ``families/laguna.py::full_step_flops``, ``full_step_bytes``)
over ``full_attn_ms_per_step``; ``swa_attn_roofline_pct``'s rule."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "swa_attn_roofline_pct").share(
        observed, "full")
