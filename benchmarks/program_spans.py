"""What the program's own span recorder holds about a window, for the
per-layer readers under ``layer_metrics/``.

The stepping job runs in this process, so the program's flight recorder
(``dlrover_tpu.observability.flight_recorder``) still holds the finished
spans when a reader is called: ``trace.SpanTuple``s with ``name``,
``start_ns``/``end_ns`` (``time.time_ns()``), ``tid``, ``span_id``,
``parent_span_id`` and ``attrs``.  A program without them (an older
commit) leaves every reader with ``None``; nothing here raises for it.

The window's steps are the last ``attempted`` (less the save) ``trainer.step``
spans: the job calls ``train_step`` for nothing else after the window.  Its
save is the last ``flash.save`` that began among them, with its children and
the ``flash.stage`` whose parent it is.

The profiler's xplane counts from the start of its session and the
recorder from the epoch: one clock, two origins.  ``clock_offset_ns``
finds the distance by nesting: every ``trainer.step`` lies inside one
``bench.train_step`` of ``trace_loaded.host_spans``.
"""

import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple

STEP = "trainer.step"
DISPATCH = "trainer.step.dispatch"
SHARD_BATCH = "trainer.shard_batch"
SAVE = "flash.save"
STAGE = "flash.stage"
HARNESS_STEP = "bench.train_step"
#: room for the two clock reads not being the same instant
NESTING_SLACK_NS = 2000


def ring() -> list:
    """The recorder's finished spans, oldest first; ``[]`` where the
    program has no such recorder or holds records of another shape."""
    try:
        from dlrover_tpu.observability import flight_recorder

        spans = list(flight_recorder.recorder().spans)
    except Exception:  # noqa: BLE001 - a program without the recorder
        return []
    return [s for s in spans
            if isinstance(s, tuple) and hasattr(s, "start_ns")]


def model_stats(observed, name):
    """What the model sowed under ``name`` in the window's
    ``trainer.model_stats`` records, oldest first: ``[(step, [a value a
    layer])]``; ``[]`` where the window or the name is not there (a dense
    model sows nothing)."""
    window = select(observed)
    if not window:
        return []
    first = window.steps[0].start_ns
    return [(s.attrs.get("step"), s.attrs[name]) for s in ring()
            if s.name == "trainer.model_stats" and s.start_ns >= first
            and s.attrs.get(name)]


@dataclasses.dataclass
class Window:
    steps: list                      # trainer.step, oldest first
    dispatch: Dict[str, object]      # step's span_id -> its dispatch
    shard_batches: list
    save: Optional[object] = None
    save_parts: Dict[str, object] = dataclasses.field(default_factory=dict)
    stage: Optional[object] = None


def dur_ns(span) -> int:
    return span.end_ns - span.start_ns


def select(observed, spans=None) -> Optional[Window]:
    spans = ring() if spans is None else spans
    steps = [s for s in spans if s.name == STEP]
    has_save = "save_blocked_ms" in observed.get("values", {})
    n = int(observed.get("attempted", 0)) - (1 if has_save else 0)
    steps = steps[-n:] if n > 0 else []
    if not steps:
        return None
    tid, first = steps[0].tid, steps[0].start_ns
    ids = {s.span_id for s in steps}
    window = Window(
        steps=steps,
        dispatch={s.parent_span_id: s for s in spans
                  if s.name == DISPATCH and s.parent_span_id in ids},
        shard_batches=[s for s in spans if s.name == SHARD_BATCH
                       and s.tid == tid][-len(steps):],
    )
    saves = [s for s in spans if s.name == SAVE and s.start_ns >= first]
    if saves:
        window.save = save = saves[-1]
        window.save_parts = {
            s.name: s for s in spans if s.parent_span_id == save.span_id
            and s.name.startswith(SAVE + ".")}
        stages = [s for s in spans if s.name == STAGE
                  and s.parent_span_id == save.span_id]
        window.stage = stages[-1] if stages else None
    return window


def median(values: List[float]) -> Optional[float]:
    ordered = sorted(values)
    if not ordered:
        return None
    mid = len(ordered) // 2
    return (ordered[mid] if len(ordered) % 2
            else 0.5 * (ordered[mid - 1] + ordered[mid]))


def median_ms(spans) -> Optional[float]:
    value = median([dur_ns(s) for s in spans])
    return None if value is None else value * 1e-6


def save_part_ms(observed, part) -> Optional[float]:
    window = select(observed)
    span = window and window.save_parts.get(f"{SAVE}.{part}")
    return dur_ns(span) * 1e-6 if span else None


def stage_attr(observed, key) -> Optional[float]:
    window = select(observed)
    if not window or window.stage is None:
        return None
    return window.stage.attrs.get(key)


def busy_pct(window: Window) -> Optional[float]:
    """The stepping thread's time under ``trainer.*`` and ``flash.save``
    over the time from the first of the window's spans to the end of the
    last: the union, so a span inside another counts once."""
    tid = window.steps[0].tid
    mine = list(window.steps) + list(window.shard_batches)
    if window.save is not None and window.save.tid == tid:
        mine.append(window.save)
    lo = min(s.start_ns for s in mine)
    hi = max(s.end_ns for s in mine)
    busy, at = 0, lo
    for start, end in sorted((s.start_ns, s.end_ns) for s in mine):
        if end > at:
            busy += end - max(start, at)
            at = end
    return 100.0 * busy / (hi - lo) if hi > lo else None


def unaccounted_pct(stage) -> Optional[float]:
    """What of the stage's time none of its counters holds.  Compiling is
    inside ``slice_s``: a slice program compiles in the call that first
    dispatches it."""
    total = dur_ns(stage) * 1e-9
    if total <= 0:
        return None
    seen = sum(float(stage.attrs.get(k, 0.0)) for k in (
        "lock_wait_s", "pace_sleep_s", "slice_s", "d2h_wait_s", "shm_copy_s"))
    return 100.0 * (total - seen) / total


def clock_offset_ns(ring_steps, harness_steps,
                    anchor: Optional[Tuple[int, int]] = None):
    """``(offset_ns, slack_ns, matched)`` with ``recorder = xplane +
    offset``, or ``None``.  ``harness_steps``: ``(start_s, end_s)`` of the
    traced ``bench.train_step`` spans in order; they are consecutive steps,
    so they match ``ring_steps[j:j+n]`` for one ``j``, and each ring step has
    to lie inside its harness span.  That bounds the offset from both
    sides; a ``j`` whose bounds cross is not the one.  ``anchor`` (bounds
    from another nesting, the save's) narrows the choice; several ``j``
    that all fit leave it undecided."""
    n, m = len(harness_steps), len(ring_steps)
    if not n or m < n:
        return None
    starts = [int(round(s * 1e9)) for s, _ in harness_steps]
    ends = [int(round(e * 1e9)) for _, e in harness_steps]
    fits = []
    for j in range(m - n + 1):
        lo = max(ring_steps[j + i].end_ns - ends[i] for i in range(n))
        hi = min(ring_steps[j + i].start_ns - starts[i] for i in range(n))
        if anchor is not None:
            lo, hi = max(lo, anchor[0]), min(hi, anchor[1])
        if lo <= hi + NESTING_SLACK_NS:
            fits.append((lo, hi))
    if len(fits) != 1:
        return None
    lo, hi = fits[0]
    return (lo + hi) // 2, hi - lo, n


def offset_for(observed, spans=None):
    """The offset for this run's trace, from every ``trainer.step`` the
    ring still holds and, where the window has a save, the nesting of
    ``flash.save`` inside ``bench.save_checkpoint``."""
    loaded = observed.get("trace_loaded")
    if loaded is None:
        return None
    spans = ring() if spans is None else spans
    harness = [(s, e) for name, s, e in loaded.host_spans
               if name == HARNESS_STEP]
    anchor = None
    window = select(observed, spans)
    outer = [(s, e) for name, s, e in loaded.host_spans
             if name == "bench.save_checkpoint"]
    if window and window.save is not None and len(outer) == 1:
        anchor = (window.save.end_ns - int(round(outer[0][1] * 1e9)),
                  window.save.start_ns - int(round(outer[0][0] * 1e9)))
    return clock_offset_ns([s for s in spans if s.name == STEP], harness,
                           anchor)


def idle_attributed_pct(observed, spans=None) -> Optional[float]:
    """Of the worst chip's idle seconds in the traced window, the share in
    gaps whose middle lies under a span of the program, on any thread."""
    from benchmarks import trace as trace_mod

    loaded = observed.get("trace_loaded")
    if loaded is None:
        return None
    spans = ring() if spans is None else spans
    found = offset_for(observed, spans)
    if found is None:
        return None
    offset, slack, matched = found
    window = trace_mod.window_of(loaded)
    busy = trace_mod.busy_by_chip(loaded, window)
    # a trace with no device plane (a rehearsal on the CPU) is one gap
    ops = trace_mod.clip(trace_mod.union(
        [(s, e) for _, s, e in loaded.device_ops[min(busy, key=busy.get)]]
    ), window) if busy else []
    under = trace_mod.union([((s.start_ns - offset) * 1e-9,
                              (s.end_ns - offset) * 1e-9) for s in spans])
    idle = attributed = 0.0
    for start, end in trace_mod.gaps(ops, window):
        idle += end - start
        mid = 0.5 * (start + end)
        if any(lo <= mid < hi for lo, hi in under):
            attributed += end - start
    stage = getattr(select(observed, spans), "stage", None)
    print(json.dumps({
        "phase": "program_spans", "clock_offset_ns": offset,
        "offset_slack_ns": slack, "steps_matched": matched,
        "idle_s": idle, "idle_attributed_s": attributed,
        "stage": stage.attrs if stage is not None else None,
    }, default=str), file=sys.stderr, flush=True)
    return 100.0 * attributed / idle if idle > 0 else None
