"""Device time inside the attention over windows and chunk summaries
(``ops/attention.py::eva_attention``) in one step, on the first chip: the
pooling of every chunk's keys and values, a window's scores over ``[its own
keys ; the summaries before it]``, their softmax and the weighted sum,
forward, rematerialised forward and backward; the union of the intervals of
the operations the trace lets one tell are its own, over the whole steps
traced.

The trace names a device operation by its whole HLO text, operands' types
included, and carries no scope, so an operation is the attention's by the
shapes its text holds (``is_eva_attn_op``):

* a block of scores, probabilities or masks: the last two dimensions are
  one window of queries by ``window + j x window / chunk`` keys and
  summaries (``,2048,2944]`` for the last window of the cell);
* the keys and values a window attends to, its own with the summaries
  before it: ``window + j x window / chunk`` rows (``j`` from 1: 2176 to
  2944 in the cell) by heads by head_dim (with the heads apart: the output
  head's ``[4096, 2560]`` has 2560 columns too);
* the pooling (``is_pool_op``): chunks by positions, ``seq / chunk`` and
  ``chunk`` among the dimensions of an array as large as ``k`` or as the
  pooling weights.

What it runs on shapes that others share is left out: the projections of
q, k, v and the output (the compiler fuses the windows' gradients, ``[batch,
window, heads, head_dim]``, straight into the projections' backward matmuls:
a window's rows alone therefore mark nothing), RoPE over the whole sequence,
the slicing of a window's rows.  **The steps traced** are whole periods of the layer
loop: from the first start of the outermost ``%while`` inside the traced
window to its last, every operation of a step once in each.  Nothing to
read where the family has no such attention or the trace holds fewer than
two runs of the loop."""

import re

DIMS = re.compile(r"\[((?:\d+,)*\d+)\]")


def shape_of(observed):
    family = observed["family"]
    if not hasattr(family, "eva_attn_shape"):
        return None
    return family.eva_attn_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])


def _arrays(op_text):
    """The dimensions of every array the text names, 1s dropped."""
    return [[int(d) for d in dims.split(",") if d != "1"]
            for dims in DIMS.findall(op_text)]


def is_pool_op(op_text, shape):
    """Chunks by the positions of a chunk, in an array of k's size or of
    the pooling weights'."""
    if op_text.startswith(("%while", "%conditional")):
        return False
    rows = shape["batch"] * shape["seq"] * shape["heads"]
    chunks, chunk = shape["seq"] // shape["chunk"], shape["chunk"]
    for dims in _arrays(op_text):
        size = 1
        for d in dims:
            size *= d
        if chunks in dims and chunk in dims and size in (
                rows, rows * shape["head_dim"]):
            return True
    return False


def is_window_op(op_text, shape):
    """A window's scores, or the keys and values it attends to."""
    if op_text.startswith(("%while", "%conditional")):
        return False
    window, per_window = shape["window"], shape["window"] // shape["chunk"]
    widths = {window + j * per_window for j in range(shape["windows"])}
    for dims in _arrays(op_text):
        if len(dims) >= 2 and dims[-2] == window and dims[-1] in widths:
            return True
        if shape["heads"] in dims and shape["head_dim"] in dims and any(
                d in widths and d != window for d in dims):
            return True
    return False


def is_eva_attn_op(op_text, shape):
    return is_window_op(op_text, shape) or is_pool_op(op_text, shape)


def whole_steps(ops):
    """(start, end, steps): from the first start of the outermost loop (the
    ``%while`` with the fewest runs, at least two) to its last."""
    starts = {}
    for name, start, _ in ops:
        if name.startswith("%while"):
            starts.setdefault(name.partition(" = ")[0], []).append(start)
    runs = [sorted(s) for s in starts.values() if len(s) >= 2]
    if not runs:
        return None
    outermost = min(runs, key=len)
    return outermost[0], outermost[-1], len(outermost) - 1


def union_ms_per_step(observed, keep):
    """Union of the intervals of the operations ``keep(text, shape)`` takes
    inside the whole steps traced, in ms a step; ``None`` where there is
    nothing to read."""
    from benchmarks import trace as trace_mod

    loaded = observed.get("trace_loaded")
    shape = shape_of(observed)
    if not shape or loaded is None or not loaded.device_ops:
        return None
    lo, hi = trace_mod.window_of(loaded)
    ops = [op for op in loaded.device_ops[min(loaded.device_ops)]
           if op[1] >= lo and op[2] <= hi]
    steps = whole_steps(ops)
    if not steps:
        return None
    mine = [(start, end) for name, start, end in ops if keep(name, shape)]
    if not mine:
        return None
    covered = trace_mod.clip(trace_mod.union(mine), steps[:2])
    return 1e3 * trace_mod.total(covered) / steps[2]


def read(observed):
    return union_ms_per_step(observed, is_eva_attn_op)
