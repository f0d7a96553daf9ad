"""The sparse attention's share of its roofline: the least time the chip
could take for the work THE MODEL asks of one step (index scores over the
causal pairs, attention over the pairs kept, two products forward and four
backward, no recomputation and no masked-out pair counted:
``families/keyevl.py::sparse_attn_step_flops``; the operands read once:
``sparse_attn_step_bytes``; the larger of operations over the bf16 peak and
bytes over the HBM peak) over ``sparse_attn_ms_per_step``.  Defined by the
model and the shapes: it reads the same work whatever implements it, so no
implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks = observed.get("peaks")
    sparse = load_module("layer_metrics", "sparse_attn_ms_per_step")
    took_ms = peaks and sparse.read(observed)
    if not took_ms:
        return None
    family, shape = observed["family"], sparse.shape_of(observed)
    least = max(
        family.sparse_attn_step_flops(shape) / peaks["bf16_flops_per_s"],
        family.sparse_attn_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
