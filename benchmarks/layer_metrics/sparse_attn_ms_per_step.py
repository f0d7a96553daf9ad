"""Device time inside the sparse attention in one step, on the first chip:
index scores, the selection, the attention over it and the indexer's loss,
forward, rematerialised forward and backward; the union of the intervals of
the operations the trace lets one tell are its own, over the steps traced.

The trace names a device operation by its whole HLO text and carries no
scope, so an operation is the sparse attention's by what its text holds:

* the threshold searches of the selection (``select_loop``): a ``%while``
  that carries the block's keys as ``u32[batch,block,keys]``, two a search;
* any operation with an operand or a result whose last two dimensions are
  one block of queries by that block's keys (``,512,4096]``: scores,
  probabilities, masks, index scores and their gradients; no other layer
  of the model works on a block of queries).

What it runs on shapes that others share is left out: the projections of
q, k, v and of the indexer, the norms and RoPE over the whole sequence, the
gathering of the blocks' outputs.  The steps traced are counted from the
threshold searches, ``select_loops_per_step`` of them a step by the
family's count (``families/keyevl.py::sparse_attn_shape``), so a step cut by
the window's edge counts by its part.  Nothing to read where the family has
no such attention or the trace holds no search."""

import re

DIMS = re.compile(r"\[((?:\d+,)*\d+)\]")


def shape_of(observed):
    family = observed["family"]
    if not hasattr(family, "sparse_attn_shape"):
        return None
    return family.sparse_attn_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])


def select_loop(op_text, shape):
    """One loop of a threshold search."""
    if not op_text.startswith("%while"):
        return False
    mark = f"u32[{shape['batch']},{shape['block']},"
    return mark in op_text


def block_by_keys(op_text, shape):
    """An operand or a result of ``[..., block, keys]`` with ``keys`` a
    whole number of blocks up to the sequence.  Never a loop or a branch,
    whose interval covers whatever its body runs."""
    if op_text.startswith(("%while", "%conditional")):
        return False
    block, seq = shape["block"], shape["seq"]
    for dims in DIMS.findall(op_text):
        dims = dims.split(",")
        if len(dims) >= 2 and int(dims[-2]) == block:
            keys = int(dims[-1])
            if keys % block == 0 and block <= keys <= seq:
                return True
    return False


def is_sparse_attn_op(op_text, shape):
    return select_loop(op_text, shape) or block_by_keys(op_text, shape)


def window_ops(observed):
    """(the sparse attention's shape, operations of the first chip inside
    the traced window, steps traced) or ``None``."""
    from benchmarks import trace as trace_mod

    loaded = observed.get("trace_loaded")
    shape = shape_of(observed)
    if not shape or loaded is None or not loaded.device_ops:
        return None
    lo, hi = trace_mod.window_of(loaded)
    ops = [op for op in loaded.device_ops[min(loaded.device_ops)]
           if op[1] >= lo and op[2] <= hi]
    loops = sum(1 for name, _, _ in ops if select_loop(name, shape))
    if not loops:
        return None
    return shape, ops, loops / shape["select_loops_per_step"]


def union_ms_per_step(observed, keep):
    """Union of the intervals of the window's operations that ``keep(text,
    shape)`` takes, in ms a step; ``None`` where there is none."""
    from benchmarks import trace as trace_mod

    got = window_ops(observed)
    if not got:
        return None
    shape, ops, steps = got
    mine = [(start, end) for name, start, end in ops if keep(name, shape)]
    if not mine:
        return None
    return 1e3 * trace_mod.total(trace_mod.union(mine)) / steps


def read(observed):
    return union_ms_per_step(observed, is_sparse_attn_op)
