"""The differential core's share of its roofline: the least time the chip
could take for the work THE MODEL asks of one step (an allowed pair of
positions and pair of heads: two maps' scores over ``head_dim`` and two
maps' products with one value of ``2 head_dim`` forward, twice that
backward; the band's pairs in a window layer, every causal pair in a whole
and in a cross layer: ``families/phi4flash.py::diff_step_flops``; q, k, v
and o moved once a pass: ``diff_step_bytes``; the larger of operations over
the bf16 peak and bytes over the HBM peak) over ``diff_attn_ms_per_step``.
Defined by the model and the shapes: a core that scores over ``2 head_dim``
lanes of which half hold zeros, multiplies the masked part of a block, or
runs its forward pass twice under ``remat`` reads lower for it, and no
implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    # ``diff_attn_ms_per_step`` against the family's ``diff_shape``,
    # ``diff_step_flops`` and ``diff_step_bytes``
    return load_module("layer_metrics", "swa_attn_roofline_pct").share(
        observed, "diff")
