"""The block-diffusion attention's share of its roofline: the least time the
chip could take for the work THE MODEL asks of one step (two products
forward and four backward over the ``S^2 + L S`` pairs a head and layer the
mask allows, no recomputation, no masked-out pair:
``families/sdar.py::bd_attn_step_flops``; q, k, v and the output moved once
a pass: ``bd_attn_step_bytes``; the larger of operations over the bf16 peak
and bytes over the HBM peak) over ``bd_attn_ms_per_step``.  Defined by the
model and the shapes: it reads the same work whatever implements it, so no
implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "bd_attn_shape") and load_module(
        "layer_metrics", "bd_attn_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.bd_attn_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(family.bd_attn_step_flops(shape) / peaks["bf16_flops_per_s"],
                family.bd_attn_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
