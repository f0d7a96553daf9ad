"""The Mamba-2 scan's share of its roofline: the least time the chip could
take for the work THE MODEL asks of one step (an update of one entry of a
head's ``[P, n]`` state, ``families/nemotronh.py::SSD_FORWARD_OPS``
operations forward and twice that backward: ``ssd_step_flops``; ``x``,
``B``, ``C`` and the step size read and ``y`` written once a pass, never the
state's history: ``ssd_step_bytes``; the larger of operations over the bf16
peak and bytes over the HBM peak) over ``ssd_ms_per_step``.  The count is
the recurrence's, not the chunked form's scores, masks and second product:
whatever body runs does at least this work, so none can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "ssd_shape") and load_module(
        "layer_metrics", "ssd_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.ssd_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(family.ssd_step_flops(shape) / peaks["bf16_flops_per_s"],
                family.ssd_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
