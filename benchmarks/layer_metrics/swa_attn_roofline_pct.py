"""The window layers' softmax core's share of its roofline: the least time
the chip could take for the work THE MODEL asks of one step (the pairs the
band allows, ``S W - W (W - 1) / 2`` a head, a multiply-add over the head
size for the score and one for the value forward, twice that backward:
``families/laguna.py::swa_step_flops``; q, k, v and o moved once a pass:
``swa_step_bytes``; the larger of operations over the bf16 peak and bytes
over the HBM peak) over ``swa_attn_ms_per_step``.  Defined by the model and
the shapes: a kernel that multiplies the masked part of a block, or scores
twice in its backward pass, reads lower for it, and no implementation can
pass 100%."""

from benchmarks.common import load_module


def share(observed, kind):
    """``<kind>_attn_ms_per_step`` against the family's ``<kind>_shape``,
    ``<kind>_step_flops`` and ``<kind>_step_bytes``."""
    peaks, family = observed.get("peaks"), observed.get("family")
    took_ms = peaks and hasattr(family, kind + "_shape") and load_module(
        "layer_metrics", kind + "_attn_ms_per_step").read(observed)
    if not took_ms:
        return None
    shape = getattr(family, kind + "_shape")(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])
    least = max(
        getattr(family, kind + "_step_flops")(shape)
        / peaks["bf16_flops_per_s"],
        getattr(family, kind + "_step_bytes")(shape)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms * 1e-3)


def read(observed):
    return share(observed, "swa")
