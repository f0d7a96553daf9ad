"""What happened to the stepping thread between two steps, and the record of
a slow one.

``Trainer.train_step`` closes one ``trainer.step`` span a step.  The time from
one close to the next (an *interval*) holds the loop's wait for a loss, the
next batch's way onto the mesh and the step's own bookkeeping, and most of it
lies outside every span of the program.  :class:`StepAccount` reads, at each
close, what the operating system and the interpreter say the thread did since
the close before, and leaves it on the span:

``interval_cpu_ns``  the thread's CPU time (``time.thread_time_ns``); where
                     the clock ticks too coarsely to say anything of one
                     step, read one step in twenty and at a slow one's
                     close, over ``cpu_intervals`` intervals
``run_delay_ns``     time runnable and not run: the second field of
                     ``/proc/thread-self/schedstat``, one ``pread`` of a
                     descriptor kept open
``nvcsw``, ``nivcsw``, ``majflt``
                     voluntary and involuntary context switches and major
                     page faults (``getrusage(RUSAGE_THREAD)``), for as
                     long as the kernel is seen to count them
``gc_ns``            the collector's pauses on the thread
                     (``flight_recorder.gc_pause_ns``)

A source the platform lacks leaves its attribute out; nothing here raises
into a step.  (The sandboxed kernel of the benchmark's machines, gVisor, has
no ``schedstat``, ticks its CPU clocks every 10 ms, counts no switches or
faults, and charges a thread that has just woken about 70 us a system call:
see :class:`StepAccount`.)  An interval over
twice the step clock's calm baseline and 50 ms is a *slow step*: one step
later (so that the record holds the interval after it
too) it is explained from the span ring (``flight_recorder.explain``) as one
``trainer.slow_step`` event and one WARNING line, ending in one word that
says whose the time was (:func:`verdict`).

:func:`host_pressure` is the slower reading the every-twentieth-step tick
makes: whether the container was throttled and the machine under pressure.
"""

import os
import threading
import time
from typing import Any, Dict, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import flight_recorder
from dlrover_tpu.training_event.emitter import TrainerEvents

try:
    import resource
except ImportError:  # a platform without it: no switches, no faults
    resource = None

_SCHEDSTAT = "/proc/thread-self/schedstat"
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
_thread_time_ns = getattr(time, "thread_time_ns", None)

#: an interval over this many calm baselines and this much is a slow step (a
#: fixed rule).  The room on top: a loop that waits for its last step before
#: it goes on (a log line, an evaluation, the benchmark's warm-up) makes an
#: interval of exactly two steps, and steps of a few milliseconds differ by
#: their own length of themselves; neither is anybody's line.
SLOW_OVER_BASELINE = 2.0
SLOW_ROOM_S = 0.05
#: steps of a thread for which every source is asked every step
#: (``StepAccount``)
PROBATION = 8


def verdict(interval_ns: int, parts_ns: Dict[str, int], cpu_ns: int,
            run_delay_ns: int, gc_ns: int) -> str:
    """One word for a slow interval, by what holds over half of it:
    ``gc`` (the collector's pauses), ``program:<span>`` (one span of the
    program, by self time), ``runnable_not_run`` (the thread waited for a
    CPU), ``caller_cpu`` (outside the program's spans, computing: the
    loop's own code), else ``waiting`` (outside the spans, asleep: the
    loss came late).  Waiting for a CPU and computing hold it together: a
    thread that did one or the other for over half the interval was not
    asleep (a busy loop on a loaded host computes for half its time and
    stands in the run queue for the other half, and neither alone holds
    half), and the word is the larger one's."""
    half = interval_ns / 2
    if gc_ns > half:
        return "gc"
    program = {k: v for k, v in parts_ns.items() if k != "runtime.gc"}
    name = max(program, key=program.get, default=None)
    if name is not None and program[name] > half:
        return f"program:{name}"
    caller_cpu_ns = max(0, cpu_ns - sum(program.values()))
    if run_delay_ns + caller_cpu_ns > half:
        return ("runnable_not_run" if run_delay_ns > caller_cpu_ns
                else "caller_cpu")
    return "waiting"


class StepAccount:
    """One a ``Trainer``; every call comes from the stepping thread.

    The thread's CPU clock and ``getrusage`` are system calls.  On a plain
    kernel they cost 0.3-0.5 us; on the sandboxed one of the benchmark's
    machines a call made by a thread that has just woken costs about 70 us
    (``host_step_self_ms`` 0.07 -> 0.15 at one a step; PERF.md, PR 53) and
    says little: no switch is counted, and the clock ticks every 10 ms.  So
    each source is asked every step for :data:`PROBATION` steps of a thread
    and after that only if it has said something a step at a time: the
    counters if any switch was counted, the clock if it has ticked finer
    than a millisecond.  A clock that has not is read where it still tells:
    on the tick (one step in twenty) and at the close of an interval that is
    slow by the wall clock; ``interval_cpu_ns`` then covers the
    ``cpu_intervals`` intervals since the last reading."""

    def __init__(self, step_clock, events):
        self._clock = step_clock
        self._events = events
        self._tid: Optional[int] = None
        self._fd: Optional[int] = None
        self._slow: Optional[Dict[str, Any]] = None  # waits for its sequel
        self._forget()

    def _forget(self) -> None:
        self._last: Dict[str, int] = {}   # the sources' last readings
        self._last_close_ns = 0
        self._closes = 0                  # of this thread, since forgotten
        self._cpu_read_at = 0             # the close of the last CPU reading
        self._calm_cpu_ns = 0.0           # CPU a calm interval takes
        self._probation = PROBATION
        self._cpu_every_step = _thread_time_ns is not None
        self._cpu_ticks_fine = False
        self._rusage = _RUSAGE_THREAD is not None
        self._switches_counted = False

    def reset(self) -> None:
        """Forget the last close: the next interval holds a compilation."""
        self._forget()
        self._slow = None

    def _open(self, tid: int) -> None:
        """The thread's ``schedstat`` kept open: ``thread-self`` resolves
        when the file is opened, so another stepping thread opens anew."""
        if self._fd is not None:
            os.close(self._fd)
        self._tid, self._fd = tid, None
        self._forget()
        try:
            self._fd = os.open(_SCHEDSTAT, os.O_RDONLY)
        except OSError:
            pass

    def _read(self, cpu: bool) -> Dict[str, int]:
        out = {"gc_ns": flight_recorder.gc_pause_ns(self._tid)}
        if cpu:
            out["interval_cpu_ns"] = _thread_time_ns()
        if self._rusage:
            usage = resource.getrusage(_RUSAGE_THREAD)
            out["nvcsw"] = usage.ru_nvcsw
            out["nivcsw"] = usage.ru_nivcsw
            out["majflt"] = usage.ru_majflt
            if usage.ru_nvcsw or usage.ru_nivcsw:
                self._switches_counted = True
        if self._fd is not None:
            try:
                out["run_delay_ns"] = int(
                    os.pread(self._fd, 128, 0).split()[1])
            except (OSError, IndexError, ValueError):
                pass
        return out

    def close(self, span, step: int, tick: bool = False) -> None:
        """At the end of ``trainer.step`` number ``step``, inside it;
        ``tick``: the every-twentieth-step work ran in this step."""
        try:
            tid = threading.get_ident()
            if tid != self._tid:
                self._open(tid)
            now_ns = time.time_ns()
            start_ns, self._last_close_ns = self._last_close_ns, now_ns
            interval_ns = now_ns - start_ns
            baseline = self._clock.baseline()
            is_slow = bool(start_ns and baseline and interval_ns > 1e9 * (
                SLOW_OVER_BASELINE * baseline + SLOW_ROOM_S))
            read = self._read(self._cpu_every_step or (
                _thread_time_ns is not None and (tick or is_slow)))
            took = {k: v - self._last[k] for k, v in read.items()
                    if k in self._last}
            self._last.update(read)
            self._closes += 1
            if "interval_cpu_ns" in read:
                covers = self._closes - self._cpu_read_at
                self._cpu_read_at = self._closes
                if "interval_cpu_ns" in took:
                    self._cpu_of_this_interval(took, covers, is_slow)
            if not start_ns:
                return
            if self._probation:
                self._on_probation(took)
            span.set_attrs(took)
            slow, self._slow = self._slow, None
            if slow is not None:
                self._report(slow, interval_ns)
            if is_slow:
                self._slow = {"step": step, "start_ns": start_ns,
                              "end_ns": now_ns, "tid": tid,
                              "baseline_s": baseline, **took}
        except Exception as e:  # noqa: BLE001 - never break a training step
            logger.debug("step account failed: %s", e)

    def _cpu_of_this_interval(self, took: Dict[str, int], covers: int,
                              is_slow: bool) -> None:
        """A CPU reading that covers several intervals (``cpu_intervals``)
        at the close of a slow one: what the calm ones before it took, by
        the last calm reading's rate, is taken off."""
        if covers > 1:
            took["cpu_intervals"] = covers
        if is_slow:
            took["interval_cpu_ns"] = max(0, int(
                took["interval_cpu_ns"] - (covers - 1) * self._calm_cpu_ns))
        else:
            self._calm_cpu_ns = took["interval_cpu_ns"] / covers

    def _on_probation(self, took: Dict[str, int]) -> None:
        if took.get("interval_cpu_ns", 0) % 1_000_000:
            self._cpu_ticks_fine = True
        self._probation -= 1
        if not self._probation:
            self._cpu_every_step = self._cpu_every_step and self._cpu_ticks_fine
            self._rusage = self._rusage and self._switches_counted

    def _report(self, slow: Dict[str, Any], next_interval_ns: int) -> None:
        found = flight_recorder.explain(
            slow["start_ns"], slow["end_ns"], slow["tid"])
        interval_ns = found["interval_ns"]

        def ms(ns):
            return round(ns * 1e-6, 3)

        record = {
            "step": slow["step"],
            "interval_ms": ms(interval_ns),
            "baseline_ms": round(slow["baseline_s"] * 1e3, 3),
            "parts_ms": {name: ms(ns) for name, ns in sorted(
                found["parts_ns"].items(), key=lambda kv: -kv[1])},
            "outside_spans_ms": ms(found["outside_spans_ns"]),
            "others_ms": {key: ms(ns)
                          for key, ns in found["others_ns"].items()},
        }
        for key, name in (("interval_cpu_ns", "cpu_ms"),
                          ("run_delay_ns", "run_delay_ms"),
                          ("gc_ns", "gc_ms")):
            if key in slow:
                record[name] = ms(slow[key])
        for key in ("cpu_intervals", "nvcsw", "nivcsw", "majflt"):
            if key in slow:
                record[key] = slow[key]
        record["next_interval_ms"] = ms(next_interval_ns)
        record["word"] = verdict(
            interval_ns, found["parts_ns"], slow.get("interval_cpu_ns", 0),
            slow.get("run_delay_ns", 0), slow["gc_ns"])
        self._events.instant(TrainerEvents.SLOW_STEP, record)
        logger.warning("%s %s", TrainerEvents.SLOW_STEP, " ".join(
            f"{k}={v}" for k, v in record.items()))


_CPU_STAT = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat")
_PRESSURE = "/proc/pressure/"


def host_pressure() -> Dict[str, int]:
    """Cumulative counters of the container and the machine, where
    readable: ``nr_throttled`` and ``throttled_us`` of the cgroup's
    ``cpu.stat`` (version 2's ``throttled_usec``, version 1's
    ``throttled_time`` in nanoseconds) and the ``some`` totals of
    ``/proc/pressure/{cpu,io,memory}`` in microseconds
    (``pressure_<resource>_us``).  Two ticks' difference says whether the
    steps between them were throttled or squeezed."""
    out: Dict[str, int] = {}
    for path in _CPU_STAT:
        try:
            with open(path) as f:
                stat = dict(line.split() for line in f)
        except (OSError, ValueError):
            continue
        if "nr_throttled" in stat:
            out["nr_throttled"] = int(stat["nr_throttled"])
        if "throttled_usec" in stat:
            out["throttled_us"] = int(stat["throttled_usec"])
        elif "throttled_time" in stat:
            out["throttled_us"] = int(stat["throttled_time"]) // 1000
        break
    for what in ("cpu", "io", "memory"):
        try:
            with open(_PRESSURE + what) as f:
                some = f.readline().split()
            out[f"pressure_{what}_us"] = int(some[-1].partition("=")[2])
        except (OSError, IndexError, ValueError):
            continue
    return out
