"""The selective scan's share of its roofline: the least time the chip
could take for the work THE MODEL asks of one step (an update of one state
entry, ``families/phi4flash.py::SCAN_FORWARD_OPS`` operations forward and
twice that backward: ``scan_step_flops``; ``a``, ``delta``, ``z``, ``B`` and
``C`` read and ``Y`` written once a pass, never the state's history:
``scan_step_bytes``; the larger of operations over the bf16 peak and bytes
over the HBM peak) over ``ssm_scan_ms_per_step``.  The scan is elementwise
and sequential: it is bound by the vector unit, for which
``benchmarks/peaks.json`` has no peak, so against the matrix unit's and the
memory's it reads single digits (as ``kda_roofline_pct`` does), and no
implementation can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "scan_shape") and load_module(
        "layer_metrics", "ssm_scan_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.scan_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(family.scan_step_flops(shape) / peaks["bf16_flops_per_s"],
                family.scan_step_bytes(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)
