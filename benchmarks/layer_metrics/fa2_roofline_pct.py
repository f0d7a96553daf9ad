"""The FA2 kernels' share of their roofline: the least time the chip could
take for the calls the trace holds (operations and bytes from
``benchmarks/flops.py``; the larger of operations over the bf16 peak and
bytes over the HBM peak, call by call) over the time they took."""

from benchmarks.common import load_module
from benchmarks.flops import fa2_call_least_seconds


def read(observed):
    if observed.get("peaks") is None:
        return None
    kernels = load_module("layer_metrics", "fa2_ms_per_step")
    shape = kernels.shape_of(observed)
    if not shape:
        return None
    found = kernels.kernel_events(observed)
    if not found:
        return None
    least = sum(n * fa2_call_least_seconds(kind, shape, observed["peaks"])[0]
                for kind, (n, _) in found.items())
    took = sum(s for _, s in found.values())
    return 100.0 * least / took
