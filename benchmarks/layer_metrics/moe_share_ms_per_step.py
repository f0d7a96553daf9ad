"""Device time inside one chip's share of the expert layer in one step, on
the first chip (``moe_ms_per_step`` counts its steps from the FA2 kernels,
which a cell without them does not run; this reader counts them from the
sparse attention's threshold searches).  An operation is the share's by
what its text holds: the grouped matmuls (``%ragged-dot...``) and the
router's ``%top_k``; an operand or a result of a shape only the share has:
the sorted assignments (``[65536]`` index vectors), the sorted rows at one
of the ladder's extents (``[10240,`` in the cell), the held experts' weights
(``[16,2048,768]``, ``[16,768,2048]``); and not what carries a block of
queries by its keys, which is the attention's whatever else it holds."""

from benchmarks.common import load_module


def marks_of(observed):
    family = observed["family"]
    if not hasattr(family, "gmm_shape") or not hasattr(
            family, "sparse_attn_shape"):
        return None
    gmm = family.gmm_shape(
        observed["config"], observed["batch"] * observed["seq"])
    e, h, w = gmm["experts"], gmm["hidden"], gmm["width"]
    return {"gmm": gmm, "marks": [f"[{extent}," for extent in gmm["extents"]]
            + [f"[{gmm['extents'][-1]}]", f"[{e},{h},{w}]", f"[{e},{w},{h}]"]}


def is_share_op(op_text, marks, sparse, shape):
    if sparse.is_sparse_attn_op(op_text, shape):
        return False
    if op_text.startswith(("%ragged-dot", "%top_k")):
        return True
    return not op_text.startswith("%while") and any(
        mark in op_text for mark in marks["marks"])


def ms_per_step(observed, gmm_only=False):
    sparse = load_module("layer_metrics", "sparse_attn_ms_per_step")
    marks = marks_of(observed)
    if not marks:
        return None
    if gmm_only:
        return sparse.union_ms_per_step(
            observed, lambda text, shape: text.startswith("%ragged-dot"))
    return sparse.union_ms_per_step(
        observed, lambda text, shape: is_share_op(text, marks, sparse, shape))


def read(observed):
    return ms_per_step(observed)
