"""Always-on in-process flight recorder: bounded rings of recent evidence.

When a hang/straggler/overload diagnostician fires, the question is
always "what was every process doing *just before* this" — and by the
time a human attaches, that evidence is gone.  The flight recorder keeps
it resident: four bounded ring buffers per process, appended on the
paths that already exist (finished trace spans, training events, chaos
faults, per-step timings, warning-level log lines), cheap enough to stay
on for the whole job.  :func:`snapshot` freezes the rings plus
all-thread Python stacks and the live metrics registry into one JSON
document — the unit the incident engine (``observability/incidents.py``)
collects from every process and merges into an incident report.

Design constraints, in order:

1. **Always on, bounded, lock-light.**  Appends are single
   ``deque.append`` calls on ``maxlen`` deques — atomic under CPython,
   no lock, O(1), nothing ever blocks.  Capacities come from the
   ``DLROVER_TPU_RECORDER_*`` knobs; total resident size is a few MB.
   The span ring holds finished spans as tuples
   (``trace.SpanTuple``) and is rendered to records only when
   :func:`snapshot` is asked; beside it ``span_totals`` keeps, for each
   span name, a count, the summed and the longest duration, which no
   eviction touches.
   The totals counters are intentionally unlocked (a lost increment
   under a race is an off-by-one in an informational field, never
   corruption).
2. **Overhead budgeted and measured.**  :func:`measure_overhead` times
   the real append path (acceptance: < 1% of step time;
   ``tests/test_flight_recorder.py`` holds the per-append cost).
3. **Feeds are one-directional.**  ``trace._export`` pushes finished
   spans, ``training_event.emitter`` pushes BEGIN/END/INSTANT
   events, the chaos engine pushes fired faults, ``Trainer.train_step``
   pushes step durations — all via the module-level helpers here, all
   guarded so a broken recorder can never break training.

``DLROVER_TPU_RECORDER=0`` turns every append into a flag check (the
flag is read when the rings are built; :meth:`FlightRecorder.reset`
re-reads it).

With the process's recorder goes one ``gc.callbacks`` hook: the garbage
collector's pauses summed by the thread that ran them
(:func:`gc_pause_ns`), and every pause of a millisecond or more as a span
``runtime.gc`` in the span ring.  :func:`explain` reads the span ring the
other way round: what one thread did in one interval, as a partition.
"""

import gc
import json
import logging
import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Union

from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger


#: span names the aggregate will hold (request types and bucket numbers
#: ride in names; a runaway name scheme must not grow it without bound)
_MAX_SPAN_NAMES = 1024


def enabled() -> bool:
    return envs.get_bool("DLROVER_TPU_RECORDER")


def all_thread_stacks() -> Dict[str, List[str]]:
    """Formatted Python stacks of every live thread, keyed
    ``"<thread name>:<ident>"`` — the ``sys._current_frames`` analogue
    of a ``faulthandler`` dump, but structured and capturable without a
    file descriptor.  Needs no cooperation from a stuck thread, which
    is the whole point: the thread wedged inside a collective cannot
    report itself."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: Dict[str, List[str]] = {}
    for ident, frame in sys._current_frames().items():
        key = f"{names.get(ident, '?')}:{ident}"
        out[key] = traceback.format_stack(frame)
    return out


class _RingLogHandler(logging.Handler):
    """Warning-and-up log lines into the recorder's log ring (INFO from
    the chatty heartbeat/tuner loops would evict the lines that
    matter)."""

    def __init__(self, recorder: "FlightRecorder"):
        super().__init__(level=logging.WARNING)
        self._recorder = recorder

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self._recorder.record_log(self.format(record))
        except Exception:  # noqa: BLE001 - logging must never recurse/raise
            pass


class FlightRecorder:
    """The per-process ring set.  One instance per process (see
    :func:`recorder`); tests may build private ones."""

    def __init__(self, attach_log_handler: bool = True):
        self._build_rings()
        self._log_handler: Optional[_RingLogHandler] = None
        if attach_log_handler:
            self._log_handler = _RingLogHandler(self)
            self._log_handler.setFormatter(
                logging.Formatter("%(asctime)s %(levelname)s %(message)s")
            )
            logger.addHandler(self._log_handler)

    def _build_rings(self) -> None:
        self._on = enabled()
        #: span name -> [count, total ns, longest ns]; never evicted
        self.span_totals: Dict[str, List[int]] = {}
        self.spans: deque = deque(
            maxlen=max(1, envs.get_int("DLROVER_TPU_RECORDER_SPANS"))
        )
        self.events: deque = deque(
            maxlen=max(1, envs.get_int("DLROVER_TPU_RECORDER_EVENTS"))
        )
        # (ts, step, dur_s)
        self.steps: deque = deque(
            maxlen=max(1, envs.get_int("DLROVER_TPU_RECORDER_STEPS"))
        )
        self.logs: deque = deque(
            maxlen=max(1, envs.get_int("DLROVER_TPU_RECORDER_LOG_LINES"))
        )
        self._t0 = time.time()
        # approximate totals (unlocked by design; see module docstring)
        self.total_spans = 0
        self.total_events = 0
        self.total_steps = 0

    def reset(self) -> None:
        """Drop everything and re-read capacities (tests, per-scenario
        drill isolation)."""
        self._build_rings()

    # -- appends (the hot path) --------------------------------------------

    def record_span(self, span: Union[tuple, Dict[str, Any]]) -> None:
        """A finished span: a ``trace.SpanTuple``, or a SPAN record
        already rendered (``trace.record_of`` shape)."""
        if not self._on:
            return
        self.spans.append(span)
        self.total_spans += 1
        if type(span) is not dict:
            dur = span.end_ns - span.start_ns
            agg = self.span_totals.get(span.name)
            if agg is not None:
                agg[0] += 1
                agg[1] += dur
                if dur > agg[2]:
                    agg[2] = dur
            elif len(self.span_totals) < _MAX_SPAN_NAMES:
                self.span_totals[span.name] = [1, dur, dur]

    def record_event(self, record: Dict[str, Any]) -> None:
        """A training event (BEGIN/END/INSTANT) or a chaos-fault record."""
        if not self._on:
            return
        self.events.append(record)
        self.total_events += 1

    def record_step(self, step: int, dur_s: float) -> None:
        if not self._on:
            return
        self.steps.append((round(time.time(), 6), int(step), float(dur_s)))
        self.total_steps += 1

    def record_log(self, line: str) -> None:
        if not self._on:
            return
        self.logs.append(line)

    # -- derived views ------------------------------------------------------

    def step_digest(self) -> Dict[str, float]:
        """Compact step-time summary of the ring — the per-rank digest
        heartbeats carry to the master's straggler screens.  Empty when
        no steps were recorded."""
        samples = list(self.steps)
        if not samples:
            return {}
        durs = sorted(d for _, _, d in samples)
        return {
            "last_step": float(samples[-1][1]),
            "step_p50_s": round(durs[len(durs) // 2], 6),
            "step_max_s": round(durs[-1], 6),
            "steps": float(len(durs)),
            "ts": round(samples[-1][0], 6),
        }

    def span_records(self) -> List[Dict[str, Any]]:
        """The span ring as SPAN records, oldest first."""
        from dlrover_tpu.observability import trace

        return [s if type(s) is dict else trace.record_of(s)
                for s in list(self.spans)]

    def snapshot(self, stacks: bool = True) -> Dict[str, Any]:
        """Freeze the rings + live-thread stacks + open spans + metrics
        into one JSON-serializable document (the incident dump unit)."""
        snap: Dict[str, Any] = {
            "role": envs.get_str("DLROVER_TPU_ROLE", default="proc"),
            "pid": os.getpid(),
            "ts": round(time.time(), 6),
            "uptime_s": round(time.time() - self._t0, 3),
            "totals": {
                "spans": self.total_spans,
                "events": self.total_events,
                "steps": self.total_steps,
            },
            "spans": self.span_records(),
            "span_totals": {
                name: {"count": c, "total_s": round(total * 1e-9, 6),
                       "max_s": round(longest * 1e-9, 6)}
                for name, (c, total, longest) in list(
                    self.span_totals.items())
            },
            "events": list(self.events),
            "steps": [list(s) for s in self.steps],
            "logs": list(self.logs),
            "step_digest": self.step_digest(),
        }
        try:
            from dlrover_tpu.observability import trace

            # the stuck operation is exactly the span that never
            # finished — it is NOT in the spans ring, only here
            snap["open_spans"] = trace.open_spans()
        except Exception:  # noqa: BLE001 - snapshot is best-effort
            snap["open_spans"] = []
        try:
            from dlrover_tpu.observability import metrics

            snap["metrics"] = metrics.registry().snapshot()
        except Exception:  # noqa: BLE001
            snap["metrics"] = {}
        if stacks:
            snap["stacks"] = all_thread_stacks()
        return snap


def dump(dir_path: str, tag: str,
         snapshot: Optional[Dict[str, Any]] = None) -> str:
    """Write a snapshot into ``dir_path/dump_<tag>.json`` (atomic
    tmp+rename) and return the path."""
    snap = snapshot if snapshot is not None else recorder().snapshot()
    os.makedirs(dir_path, exist_ok=True)
    path = os.path.join(dir_path, f"dump_{tag}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f, sort_keys=True)
    os.replace(tmp, path)
    return path


def measure_overhead(samples: int = 20000) -> float:
    """Seconds per ``record_event`` append, measured on the real path
    with the recorder enabled (a private instance so the measurement
    does not pollute the process rings)."""
    rec = FlightRecorder(attach_log_handler=False)
    record = {"ts": 0.0, "name": "overhead-probe", "type": "INSTANT"}
    t0 = time.perf_counter()
    for _ in range(samples):
        rec.record_event(record)
    return (time.perf_counter() - t0) / max(1, samples)


_RECORDER: Optional[FlightRecorder] = None
_RECORDER_MU = threading.Lock()


def recorder() -> FlightRecorder:
    """The process singleton every feed writes to."""
    global _RECORDER
    if _RECORDER is None:
        with _RECORDER_MU:
            if _RECORDER is None:
                _RECORDER = FlightRecorder()
                if _on_gc not in gc.callbacks:
                    gc.callbacks.append(_on_gc)
    return _RECORDER


# -- the collector's pauses ----------------------------------------------------

#: a pause at least this long is a ``runtime.gc`` span of its own
GC_SPAN_MIN_NS = 1_000_000

_gc_started: Dict[int, int] = {}  # thread -> when its collection began
_gc_paused: Dict[int, int] = {}  # thread -> its pauses so far, summed


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """The ``gc.callbacks`` hook.  A collection runs on the thread whose
    allocation set it off and holds the interpreter until it is done: two
    clock reads and two dictionary writes a collection."""
    try:
        tid = threading.get_ident()
        if phase == "start":
            _gc_started[tid] = time.time_ns()
            return
        start_ns = _gc_started.pop(tid, None)
        if start_ns is None:
            return
        end_ns = time.time_ns()
        _gc_paused[tid] = _gc_paused.get(tid, 0) + end_ns - start_ns
        if end_ns - start_ns >= GC_SPAN_MIN_NS:
            from dlrover_tpu.observability import trace

            recorder().record_span(trace.SpanTuple(
                "runtime.gc", start_ns, end_ns, tid,
                # a root of its own: an incident's timeline holds every
                # span of the ring to a connected tree
                threading.current_thread().name, trace.new_trace_id(),
                trace.new_span_id(), "", trace.INTERNAL, "ok", "",
                {"generation": info.get("generation"),
                 "collected": info.get("collected")}, [],
            ))
    except Exception:  # noqa: BLE001 - never raise into an allocation
        pass


def gc_pause_ns(tid: Optional[int] = None) -> int:
    """The collector's pauses on thread ``tid`` (the caller's where none
    is named) since the hook was installed, summed, in nanoseconds."""
    return _gc_paused.get(threading.get_ident() if tid is None else tid, 0)


# -- what a thread did in an interval ------------------------------------------


def explain(start_ns: int, end_ns: int, tid: int,
            spans: Optional[Iterable[tuple]] = None) -> Dict[str, Any]:
    """The interval ``[start_ns, end_ns)`` of thread ``tid`` by the
    finished spans of the ring (or of ``spans``), as a partition by self
    time: an instant belongs to the innermost span of that thread open at
    it, and what no span covers is ``outside_spans_ns``, so ``parts_ns``
    (by span name) and ``outside_spans_ns`` sum to ``interval_ns``.  Spans
    of other threads that overlap the interval are listed beside it
    (``others_ns``: the nanoseconds of overlap by ``<name>@<thread>``; read
    from the ring, also those still open, as a stage behind the steps is)
    and summed into nothing."""
    mine: List[tuple] = []
    others: Dict[str, int] = {}

    def beside(name: str, thread: str, overlap_ns: int) -> None:
        key = f"{name}@{thread}"
        others[key] = others.get(key, 0) + overlap_ns

    if spans is None:
        spans = list(recorder().spans)
        from dlrover_tpu.observability import trace

        for sp in trace.open_spans():
            opened_ns = int(sp["start_ts"] * 1e9)
            if sp["tid"] != tid and opened_ns < end_ns:
                beside(sp["name"], sp["thread"],
                       end_ns - max(opened_ns, start_ns))
    for s in spans:
        if type(s) is dict or s.end_ns <= start_ns or s.start_ns >= end_ns:
            continue
        lo, hi = max(s.start_ns, start_ns), min(s.end_ns, end_ns)
        if s.tid == tid:
            mine.append((lo, hi, s.name))
        else:
            beside(s.name, s.thread, hi - lo)
    parts: Dict[str, int] = {}
    outside = 0
    at, open_now = start_ns, []  # a thread's spans nest: a stack of (name, end)

    def credit(until: int) -> None:
        nonlocal at, outside
        if until > at:
            if open_now:
                name = open_now[-1][0]
                parts[name] = parts.get(name, 0) + until - at
            else:
                outside += until - at
            at = until

    for lo, hi, name in sorted(mine, key=lambda m: (m[0], -m[1])):
        while open_now and open_now[-1][1] <= lo:
            credit(open_now[-1][1])
            open_now.pop()
        credit(lo)
        open_now.append((name, hi))
    while open_now:
        credit(open_now[-1][1])
        open_now.pop()
    credit(end_ns)
    return {
        "interval_ns": end_ns - start_ns,
        "parts_ns": parts,
        "outside_spans_ns": outside,
        "others_ns": others,
    }


# -- feed helpers (called from trace/emitter/chaos/trainer; every caller
# wraps in try/except so instrumentation can never break the host) ----------


def on_span(span: Union[tuple, Dict[str, Any]]) -> None:
    recorder().record_span(span)


def on_event(record: Dict[str, Any]) -> None:
    recorder().record_event(record)


def on_step(step: int, dur_s: float) -> None:
    recorder().record_step(step, dur_s)
