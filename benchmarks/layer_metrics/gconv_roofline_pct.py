"""The gated short convolution's share of its roofline: the least time the
chip could take for the bytes THE MODEL asks one step's cores to move
(``families/lfm2.py::gconv_step_bytes``: ``B``, ``C`` and ``u`` read and the
result written once forward; backward the three, and the result's gradient,
read and three gradients written once; bfloat16, never ``v`` or ``c``, no
second forward pass) over the HBM peak, over ``gconv_ms_per_step``.  **The
bound is bytes alone**: the core is some ten elementwise operations a loaded
value, the vector unit's work, and ``peaks.json`` has the bfloat16 matrix
rate and the HBM rate and no vector rate.  Whatever body runs moves at least
these bytes, so none can pass 100%."""

from benchmarks.common import load_module


def read(observed):
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, "gconv_shape") and load_module(
        "layer_metrics", "gconv_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = family.gconv_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = family.gconv_step_bytes(shape) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (took_ms * 1e-3)
