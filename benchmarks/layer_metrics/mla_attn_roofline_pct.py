"""The latent-attention core's share of its roofline: the least time the
chip could take for the work THE MODEL asks of one step (a causal pair of
positions and head: the scores over ``128 + 64``, the values over 128,
forward; the scores again, their gradient's two products and the values'
two, backward: ``families/ling3.py::mla_step_flops``, at the model's
widths, nothing padded; q, k_nope, the one k_pe, v and o moved once a
pass: ``mla_step_bytes``; the larger of operations over the bf16 peak and
bytes over the HBM peak) over ``mla_attn_ms_per_step``.  Defined by the
model and the shapes: it reads the same work whatever implements it, so no
implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "mla_shape") and load_module(
        "layer_metrics", "mla_attn_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.mla_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(family.mla_step_flops(shape) / peaks["bf16_flops_per_s"],
                family.mla_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
