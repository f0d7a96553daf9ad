"""The grouped matmuls' share of their roofline: the least time the chip
could take for one step's grouped matmuls (operations and bytes by
``families/olmoe.py::gmm_step_flops`` and ``gmm_step_bytes``, from rows =
tokens x experts a token / chips; forward and backward, the recomputed
forward not counted; the larger of operations over the bf16 peak and
bytes over the HBM peak) over the time they took a step."""

from benchmarks.common import load_module


def read(observed):
    peaks = observed.get("peaks")
    moe = load_module("layer_metrics", "moe_ms_per_step")
    shapes = moe.shapes_of(observed) if peaks else None
    took_ms = shapes and moe.ms_per_step(observed, moe.GMM.match)
    if not took_ms:
        return None
    family = observed["family"]
    least = max(
        family.gmm_step_flops(shapes["gmm"]) / peaks["bf16_flops_per_s"],
        family.gmm_step_bytes(shapes["gmm"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
