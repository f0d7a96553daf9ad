"""The grouped matmuls' share of their roofline in a cell that holds one
chip's share of the expert layer: operations and bytes by
``families/keyevl.py::gmm_step_flops`` and ``gmm_step_bytes`` from the rows
expected under even routing (tokens x experts a token x held / all), over
``moe_share_gmm_ms_per_step``."""

from benchmarks.common import load_module


def read(observed):
    peaks = observed.get("peaks")
    share = load_module("layer_metrics", "moe_share_ms_per_step")
    marks = share.marks_of(observed) if peaks else None
    took_ms = marks and share.ms_per_step(observed, gmm_only=True)
    if not took_ms:
        return None
    family = observed["family"]
    least = max(
        family.gmm_step_flops(marks["gmm"]) / peaks["bf16_flops_per_s"],
        family.gmm_step_bytes(marks["gmm"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
