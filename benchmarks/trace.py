"""From a profiler trace to numbers: device busy and idle, time by device
operation, idle gaps by what the host was doing.  Two stages, so that the
second is checked on plain lists: ``load`` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData`` (nothing else) into ``Trace``; the rest is
arithmetic on intervals.

A TPU trace has one plane a chip, ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event for each operation the chip ran (kernels included), and
``XLA Modules`` one for each run of a jitted program.  Host threads are lines
of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
there under their own names (the harness's all start with ``bench.``).
"""

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]  # start, end in seconds


@dataclasses.dataclass
class Trace:
    #: chip number -> [(name, start_s, end_s)] of its device operations
    device_ops: Dict[int, List[Tuple[str, float, float]]]
    #: [(name, start_s, end_s)] of the harness's spans on the host
    host_spans: List[Tuple[str, float, float]]
    #: names of every plane and line met, for a trace that reads empty
    seen: Dict[str, List[str]]


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_spans, seen = {}, [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        seen[plane.name] = [line.name for line in lines]
        chip = DEVICE_PLANE.match(plane.name)
        for line in lines:
            if chip and line.name == OPS_LINE:
                device_ops[int(chip.group(1))] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                ]
            elif plane.name == HOST_PLANE:
                host_spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return Trace(device_ops, sorted(host_spans, key=lambda s: s[1]), seen)


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """What ``window`` holds beside the (disjoint, sorted) ``busy``."""
    out, at = [], window[0]
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def window_of(trace: Trace, name=SPAN_PREFIX + "window") -> Interval:
    """The traced window: the harness's own span of that name where the
    trace holds it and the device's events lie inside it (one clock);
    else from the first device operation to the end of the last."""
    ops = [op for chip in trace.device_ops.values() for op in chip]
    spans = [s for s in trace.host_spans if s[0] == name]
    if spans:
        lo, hi = spans[0][1], spans[-1][2]
        inside = [op for op in ops if op[2] > lo and op[1] < hi]
        if not ops or len(inside) >= 0.5 * len(ops):
            return lo, hi
    if not ops:
        return 0.0, 0.0
    return min(op[1] for op in ops), max(op[2] for op in ops)


def busy_by_chip(trace: Trace, window: Interval) -> Dict[int, float]:
    return {
        chip: total(clip(union([(s, e) for _, s, e in ops]), window))
        for chip, ops in trace.device_ops.items()
    }


_LAYOUT = re.compile(r"\{[^{}]*\}")


def label(op_text: str, limit=120) -> str:
    """A device operation's name is its whole HLO text.  For a table: the
    name, its result types without layouts, and the kind of operation."""
    name, _, rest = op_text.partition(" = ")
    rest = _LAYOUT.sub("", rest)
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):          # up to the operands' bracket
        depth += ch == "("
        depth -= ch == ")"
        if ch == "(" and depth == 1 and i and rest[i - 1].isalnum():
            end = i
            break
    return (name + " -> " + rest[:end])[:limit] if rest else name[:limit]


def op_seconds(trace: Trace, window: Interval, chip=None) -> Dict[str, float]:
    """Seconds by operation inside the window, on one chip (the lowest-
    numbered unless named).  A ``while`` is left out: it is the loop over
    the layers, and the operations of its body are listed themselves."""
    if not trace.device_ops:
        return {}
    chip = min(trace.device_ops) if chip is None else chip
    out = {}
    for name, start, end in trace.device_ops[chip]:
        s, e = max(start, window[0]), min(end, window[1])
        if e > s and not name.startswith("%while"):
            key = label(name)
            out[key] = out.get(key, 0.0) + (e - s)
    return out


def idle_by_host_span(trace: Trace, window: Interval, chip=None):
    """Idle seconds of one chip by the innermost harness span that was
    open on the host at the middle of each gap (``(no span)`` where none
    was): what the host was doing while the chip waited."""
    if not trace.device_ops:
        return {}
    chip = min(trace.device_ops) if chip is None else chip
    busy = clip(union([(s, e) for _, s, e in trace.device_ops[chip]]), window)
    spans = [s for s in trace.host_spans if s[0] != SPAN_PREFIX + "window"]
    out = {}
    for start, end in gaps(busy, window):
        mid = 0.5 * (start + end)
        open_now = [s for s in spans if s[1] <= mid < s[2]]
        name = (min(open_now, key=lambda s: s[2] - s[1])[0]
                if open_now else "(no span)")
        out[name] = out.get(name, 0.0) + (end - start)
    return out


def top(table: Dict[str, float], n=10):
    return [[name, seconds] for name, seconds in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Trace) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace.  ``busy_s`` is averaged over the chips, the idle share is the
    worst chip's."""
    window = window_of(trace)
    window_s = window[1] - window[0]
    busy = busy_by_chip(trace, window)
    out = {"window_s": window_s, "chips_traced": len(busy),
           "planes": trace.seen}
    if not busy or window_s <= 0:
        return out
    out["busy_s"] = sum(busy.values()) / len(busy)
    out["busy_by_chip"] = busy
    out["idle_pct_worst_chip"] = 100.0 * (1 - min(busy.values()) / window_s)
    worst = min(busy, key=busy.get)
    out["device_ops"] = top(op_seconds(trace, window, worst))
    out["idle_gaps"] = top(idle_by_host_span(trace, window, worst))
    return out
