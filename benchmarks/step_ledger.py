"""The window's steps one by one, from the program's own recorder (PR 53):
which were slow, what the stepping thread did in them, what the
every-twentieth-step tick cost by its parts.  For the six readers under
``layer_metrics/`` that share it (``step_max_over_median``, ``stall_ms``,
``stall_program_ms``, ``host_tick_ms``, ``host_run_delay_pct``,
``gc_pause_ms``) and for the one ``phase: step_ledger`` line a traced run
prints on standard error.

An *interval* is the time between the closes of two consecutive
``trainer.step`` spans of the window (``program_spans.select``): the WHOLE
window, not the seconds the profiler's session covers.  The span that closes
an interval carries what its thread did in it (``interval_cpu_ns``,
``run_delay_ns``, ``nvcsw``, ``nivcsw``, ``majflt``, ``gc_ns``;
``dlrover_tpu/trainer/step_account.py``), and the program's
``flight_recorder.explain`` partitions it by the thread's spans.  A program
without that account (an older commit) leaves every reader with ``None``.

A *stall* is an interval beyond twice the window's median one; what it lost
is the interval less the median.  One interval of a traced run is the
harness's own: the one in which it stops the profiler (0.6-1.1 s of writing
the trace out, on the stepping thread).  It is found by the trace's
``bench.window`` span on the recorder's clock (``program_spans.offset_for``),
left out of the stalls and the longest, and shown apart in the line
(``profiler_stop``); where the clocks cannot be matched nothing is left out.
"""

import dataclasses
import json
import sys
from typing import List, Optional

from benchmarks import program_spans

TICK = "trainer.step.tick"
SLOW_STEP = "trainer.slow_step"
GC = "runtime.gc"
#: on every ``trainer.step`` but a thread's first where the program accounts
ACCOUNTED = "gc_ns"
TICK_PARTS = ("poll_s", "memscope_s", "digests_s", "write_s", "stats_read_s")
STALL_OVER_MEDIAN = 2.0


@dataclasses.dataclass
class Ledger:
    window: program_spans.Window
    spans: list                  # the ring from the window's first step on
    closing: list                # the step that closes each interval
    intervals_ns: List[int]
    median_ns: float
    explain: object              # the program's ``flight_recorder.explain``
    harness: Optional[int] = None   # the interval that holds the profiler's stop

    @property
    def tid(self):
        return self.closing[0].tid

    def bounds(self, i):
        end = self.closing[i].end_ns
        return end - self.intervals_ns[i], end

    def own(self) -> List[int]:
        """The intervals but the harness's."""
        return [i for i in range(len(self.intervals_ns)) if i != self.harness]

    def stalled(self) -> List[int]:
        return [i for i in self.own()
                if self.intervals_ns[i] > STALL_OVER_MEDIAN * self.median_ns]

    def explained(self, i) -> dict:
        start, end = self.bounds(i)
        return self.explain(start, end, self.tid, self.spans)


def read(observed, spans=None) -> Optional[Ledger]:
    """The window's ledger, or ``None``: no window, under three steps, or a
    program whose steps carry no account.  Made once a run: the harness
    hands every reader the same ``observed``."""
    if spans is None:
        if "step_ledger" not in observed:
            observed["step_ledger"] = read(observed, program_spans.ring())
        return observed["step_ledger"]
    window = program_spans.select(observed, spans)
    if not window or len(window.steps) < 3:
        return None
    if ACCOUNTED not in window.steps[-1].attrs:
        return None
    try:
        from dlrover_tpu.observability.flight_recorder import explain
    except ImportError:
        return None
    steps = window.steps
    first = steps[0].start_ns
    intervals = [b.end_ns - a.end_ns for a, b in zip(steps, steps[1:])]
    return Ledger(
        window=window,
        spans=[s for s in spans if s.end_ns >= first],
        closing=steps[1:], intervals_ns=intervals,
        median_ns=program_spans.median(intervals), explain=explain,
        harness=profiler_stop(observed, spans, steps))


def profiler_stop(observed, spans, steps) -> Optional[int]:
    """The interval in which the harness stopped the profiler: the one that
    holds the end of the trace's ``bench.window`` span, brought onto the
    recorder's clock.  ``None``: an untraced run, or clocks that cannot be
    matched."""
    loaded = observed.get("trace_loaded")
    traced = [e for name, _, e in getattr(loaded, "host_spans", ())
              if name == "bench.window"]
    found = program_spans.offset_for(observed, spans) if traced else None
    if found is None:
        return None
    at = int(round(traced[-1] * 1e9)) + found[0]
    for i, (a, b) in enumerate(zip(steps, steps[1:])):
        if a.end_ns <= at < b.end_ns:
            return i
    return None


def step_max_over_median(led: Ledger) -> float:
    return max(led.intervals_ns[i] for i in led.own()) / led.median_ns


def stall_ms(led: Ledger) -> float:
    return sum(led.intervals_ns[i] - led.median_ns
               for i in led.stalled()) * 1e-6


def program_ns(found: dict, gc_ns: int) -> int:
    """What of an explained interval a change to the program can shorten:
    the time under the stepping thread's program spans, and the collector's
    pauses wherever they fell (those of a millisecond are spans themselves:
    counted once)."""
    under = sum(ns for name, ns in found["parts_ns"].items() if name != GC)
    return under + gc_ns


def stall_program_ms(led: Ledger) -> float:
    """Over the stalls, the program's time beyond what a calm interval
    holds of it, at most what the stall lost."""
    stalled = led.stalled()
    if not stalled:
        return 0.0
    at = {i: program_ns(led.explained(i), led.closing[i].attrs[ACCOUNTED])
          for i in led.own()}
    calm = program_spans.median(
        [ns for i, ns in at.items() if i not in stalled] or [0])
    return sum(
        min(led.intervals_ns[i] - led.median_ns, max(0.0, at[i] - calm))
        for i in stalled) * 1e-6


def ticks(led: Ledger) -> list:
    ids = {s.span_id for s in led.window.steps}
    return [s for s in led.spans
            if s.name == TICK and s.parent_span_id in ids]


def host_tick_ms(led: Ledger) -> Optional[float]:
    return program_spans.median_ms(ticks(led))


def accounted(led: Ledger, key) -> Optional[int]:
    """The window's sum of one of the account's counters, or ``None`` where
    the platform lacks its source."""
    values = [s.attrs[key] for s in led.closing if key in s.attrs]
    return sum(values) if values else None


def host_run_delay_pct(led: Ledger) -> Optional[float]:
    delayed = accounted(led, "run_delay_ns")
    return None if delayed is None else 100.0 * delayed / sum(led.intervals_ns)


def gc_pause_ms(led: Ledger) -> Optional[float]:
    paused = accounted(led, ACCOUNTED)
    return None if paused is None else paused * 1e-6


def slow_step_records(led: Ledger) -> list:
    """Every ``trainer.slow_step`` record the program made in the window,
    whole, from the recorder's event ring."""
    try:
        from dlrover_tpu.observability import flight_recorder

        events = list(flight_recorder.recorder().events)
    except Exception:  # noqa: BLE001 - a program without the recorder
        return []
    since = led.window.steps[0].start_ns * 1e-9
    return [e.get("content") for e in events
            if e.get("name") == SLOW_STEP and e.get("ts", 0) >= since]


def _ms(ns) -> float:
    return round(ns * 1e-6, 3)


def interval_record(led: Ledger, i) -> dict:
    """One interval as the operator's line has it, from this side."""
    found, step = led.explained(i), led.closing[i]
    parts = sum(found["parts_ns"].values()) + found["outside_spans_ns"]
    return {
        "step": step.attrs.get("step"), "window_step": i + 1,
        "interval_ms": _ms(found["interval_ns"]),
        "parts_ms": {k: _ms(v) for k, v in sorted(
            found["parts_ns"].items(), key=lambda kv: -kv[1])},
        "outside_spans_ms": _ms(found["outside_spans_ns"]),
        "parts_sum_over_interval": parts / found["interval_ns"],
        "others_ms": {k: _ms(v) for k, v in found["others_ns"].items()},
        **{k: step.attrs[k] for k in (
            "interval_cpu_ns", "run_delay_ns", "gc_ns", "nvcsw", "nivcsw",
            "majflt") if k in step.attrs},
        "next_interval_ms": (_ms(led.intervals_ns[i + 1])
                             if i + 1 < len(led.intervals_ns) else None),
    }


def report(led: Ledger) -> dict:
    """The ``phase: step_ledger`` line: every interval of the window, the
    ticks with their parts, the stalls (and the longest interval, stalled
    or not) explained, the program's own slow-step records."""
    found_ticks = ticks(led)
    longest = max(led.own(), key=led.intervals_ns.__getitem__)
    line = {
        "phase": "step_ledger",
        "steps": len(led.window.steps),
        "intervals_ms": [_ms(ns) for ns in led.intervals_ns],
        "median_ms": _ms(led.median_ns),
        "ticks": [{"step": t.attrs.get("step"),
                   "ms": _ms(program_spans.dur_ns(t)),
                   **{k: v for k, v in t.attrs.items() if k != "step"}}
                  for t in found_ticks],
        "tick_max_ms": max(
            (_ms(program_spans.dur_ns(t)) for t in found_ticks), default=None),
        "tick_parts_median_ms": {
            part: 1e3 * program_spans.median(
                [t.attrs[part] for t in found_ticks if part in t.attrs])
            for part in TICK_PARTS
            if any(part in t.attrs for t in found_ticks)},
        "stalls": [interval_record(led, i) for i in led.stalled()],
        "longest": interval_record(led, longest),
        "profiler_stop": (None if led.harness is None
                          else interval_record(led, led.harness)),
        "slow_steps": slow_step_records(led),
        "totals": {key: accounted(led, key) for key in (
            "interval_cpu_ns", "run_delay_ns", "gc_ns", "nvcsw", "nivcsw",
            "majflt")},
    }
    print(json.dumps(line, default=str), file=sys.stderr, flush=True)
    return line


def metric(observed, compute):
    """A reader's body: ``compute`` of the window's ledger, ``None`` where
    there is none."""
    led = read(observed)
    return None if led is None else compute(led)
