"""The attention's share of its roofline: the least time the chip could
take for the work THE MODEL asks of one step (two products forward and four
backward over the exact and the summary pairs, the pooling, no recomputation
and no masked-out pair counted: ``families/evabyte.py::eva_attn_step_flops``;
the operands read once: ``eva_attn_step_bytes``; the larger of operations
over the bf16 peak and bytes over the HBM peak) over ``eva_attn_ms_per_step``.
Defined by the model and the shapes: it reads the same work whatever
implements it, so no implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks = observed.get("peaks")
    eva = load_module("layer_metrics", "eva_attn_ms_per_step")
    took_ms = peaks and eva.read(observed)
    if not took_ms:
        return None
    family, shape = observed["family"], eva.shape_of(observed)
    least = max(
        family.eva_attn_step_flops(shape) / peaks["bf16_flops_per_s"],
        family.eva_attn_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
