"""Device time inside the expert layer in one step, on the first chip: the
union of the intervals of the operations the trace lets one tell are the
expert layer's, over the steps traced.

The trace names a device operation by its whole HLO text and carries no
scope, so an operation is the expert layer's by what its text holds:

* the grouped matmuls (``%ragged-dot...``: the compiler's own kernel for
  ``jax.lax.ragged_dot``, and its metadata kernel) and the router's
  ``%top_k``;
* its collectives (see ``collective``);
* any operation with an operand or a result of a shape only the expert
  layer has: the sorted rows of one source rank (tokens a chip x experts a
  token, ``[65536,`` in the cell), the gathered tokens (``[4,8192,``), one
  chip's expert weights (``[16,2048,1024]``, ``[16,1024,2048]``);
* the loop over the source ranks (a ``%while`` that carries the gathered
  tokens and not the layer stack's activations), whose interval covers
  what its body runs.

What the layer runs on shapes that others share (the router's matmul and
softmax on ``[2,4096,64]``) is left out: some 1% of it by operations.
The steps traced are counted as ``fa2_ms_per_step`` counts them, from the
forward FA2 kernels.  Nothing to read where the family has no grouped
matmul or the trace holds none."""

import re

from benchmarks.common import load_module

GMM = re.compile(r"^%ragged-dot")
COLLECTIVE = re.compile(r"^%(all-gather|reduce[_-]scatter)")


def collective(op_text):
    """An all-gather or a reduce-scatter (whole, or the start or the end
    of an asynchronous one).  Under a mesh whose only split axis is ``ep``
    the program's other collectives are the gradients' all-reduces, so
    these are the expert layer's: the tokens gathered over ``ep`` and the
    results scattered back, forward and backward."""
    return bool(COLLECTIVE.match(op_text))


def shapes_of(observed):
    """The shapes that mark the expert layer, as they stand in HLO text,
    or ``None`` where the family has no grouped matmul."""
    family = observed["family"]
    if not hasattr(family, "gmm_shape"):
        return None
    chips = observed["chips"]
    tokens_chip = observed["batch"] // chips * observed["seq"]
    gmm = family.gmm_shape(observed["config"], tokens_chip * chips, chips)
    m = family.sizes(observed["config"], False)
    e, h, w = gmm["experts"], gmm["hidden"], gmm["width"]
    return {
        "marks": [f"[{tokens_chip * m['num_experts_per_tok']},",
                  f"[{chips},{tokens_chip},",
                  f"[{e},{h},{w}]", f"[{e},{w},{h}]"],
        "loop": f"[{chips},{tokens_chip},{h}]",
        "stack": f"[{m['num_hidden_layers']},{observed['batch'] // chips},"
                 f"{observed['seq']},{h}]",
        "gmm": gmm,
    }


def is_expert_op(op_text, shapes):
    if op_text.startswith("%while"):
        return shapes["loop"] in op_text and shapes["stack"] not in op_text
    if (GMM.match(op_text) or collective(op_text)
            or op_text.startswith("%top_k")):
        return True
    return any(mark in op_text for mark in shapes["marks"])


def window_ops(observed):
    """(operations of the first chip inside the traced window, steps
    traced) or ``None``."""
    from benchmarks import trace as trace_mod

    loaded = observed.get("trace_loaded")
    if loaded is None or not loaded.device_ops:
        return None
    fa2 = load_module("layer_metrics", "fa2_ms_per_step")
    shape = fa2.shape_of(observed)
    found = fa2.kernel_events(observed) if shape else None
    if not found:
        return None
    steps = found["fwd"][0] / shape["calls_per_step"]["fwd"]
    lo, hi = trace_mod.window_of(loaded)
    ops = [op for op in loaded.device_ops[min(loaded.device_ops)]
           if op[1] >= lo and op[2] <= hi]
    return ops, steps


def ms_per_step(observed, keep):
    """Sum of the durations of the window's operations that ``keep``
    takes, in ms a step; ``None`` where there is none."""
    got = window_ops(observed)
    if not got:
        return None
    ops, steps = got
    seconds = [end - start for name, start, end in ops if keep(name)]
    return 1e3 * sum(seconds) / steps if seconds else None


def read(observed):
    from benchmarks import trace as trace_mod

    shapes = shapes_of(observed)
    got = window_ops(observed) if shapes else None
    if not got:
        return None
    ops, steps = got
    mine = [(start, end) for name, start, end in ops
            if is_expert_op(name, shapes)]
    if not mine:
        return None
    return 1e3 * trace_mod.total(trace_mod.union(mine)) / steps
