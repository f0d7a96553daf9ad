"""Device time of the FA2 forward, dQ and dK/dV kernels in one step: the
sum of their events' durations in the traced window over the steps traced
(forward events over forward calls a step, so a step cut by the window's
edge counts by its part).  Nothing to read where the family has no kernel
or the trace does not name it."""

import re

#: The trace names a device operation by its whole HLO text.  The three
#: Pallas kernels are the ``tpu_custom_call``s of the attention module
#: (``attn._attend``); the program gives them no name of their own, so which
#: is which is read from the result: the forward returns (out, float32
#: statistics), dQ one array, dK/dV two arrays of the inputs' type.  Stable
#: kernel names are on PERF.md's list for the tracing issue.
FA2_CALL = re.compile(r"^%[\w.]*_attend[\w.]* = (.*) custom-call\(.*"
                      r'custom_call_target="tpu_custom_call"')
TUPLE_OF_TWO = re.compile(r"^\((\w+)\[[\d,]*\](?:\{[^{}]*\})?, "
                          r"(\w+)\[[\d,]*\](?:\{[^{}]*\})?\)$")


def kind_of(op_text):
    """``fwd``, ``dq``, ``dkv`` or ``None`` for one device operation."""
    call = FA2_CALL.match(op_text)
    if not call:
        return None
    result = call.group(1)
    two = TUPLE_OF_TWO.match(result)
    if two:
        return "fwd" if two.group(2) == "f32" != two.group(1) else "dkv"
    return None if result.startswith("(") else "dq"


def kernel_events(observed):
    """kind -> (events, seconds) on the first chip inside the traced
    window, or ``None`` unless all three kernels are found."""
    from benchmarks import trace as trace_mod

    loaded = observed.get("trace_loaded")
    if loaded is None or not loaded.device_ops:
        return None
    lo, hi = trace_mod.window_of(loaded)
    found = {"fwd": [0, 0.0], "dq": [0, 0.0], "dkv": [0, 0.0]}
    for name, start, end in loaded.device_ops[min(loaded.device_ops)]:
        kind = kind_of(name) if start >= lo and end <= hi else None
        if kind:
            found[kind][0] += 1
            found[kind][1] += end - start
    return found if all(n for n, _ in found.values()) else None


def shape_of(observed):
    """The kernels' call shape on one chip, or ``None`` where the family
    has no kernel."""
    return observed["family"].fa2_shape(
        observed["config"], observed["batch"] // observed["chips"],
        observed["seq"])


def read(observed):
    shape = shape_of(observed)
    found = kernel_events(observed) if shape else None
    if not found:
        return None
    steps = found["fwd"][0] / shape["calls_per_step"]["fwd"]
    return 1e3 * sum(s for _, s in found.values()) / steps
