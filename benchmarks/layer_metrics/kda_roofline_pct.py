"""The delta-rule layers' share of their roofline: the least time the chip
could take for the work THE MODEL asks of one step (per token and head the
decay, the rank-one correction and update and the read-out of a ``head_dim x
head_dim`` state, forward and backward, no recomputation:
``families/solaropen2.py::kda_step_flops``; q, k, v, the log decay, beta and
the read-out moved once a pass: ``kda_step_bytes``; the larger of operations
over the bf16 peak and bytes over the HBM peak) over ``kda_ms_per_step``.
Defined by the model and the shapes: it reads the same work whatever
implements it, so no implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "kda_shape") and load_module(
        "layer_metrics", "kda_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.kda_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(family.kda_step_flops(shape) / peaks["bf16_flops_per_s"],
                family.kda_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
