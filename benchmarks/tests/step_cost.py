#!/usr/bin/env python3
"""What the step's own accounting costs the stepping thread (PR 53), beside
``clock_check.py``'s cost of a span.  One process, no device needed (run it
through the chip tool for the numbers PERF.md quotes: the chip's host is not
the builder's box):

    python3 benchmarks/tests/step_cost.py

1. One hot-path span: a per-step one (``trainer.step`` with its ``step``),
   which reads no CPU clock, and one that does (``trainer.step.tick``), with
   the two reads that give it ``cpu_ns`` and without them.
2. What ``trainer.step`` gains at its close (``StepAccount.close``: one read
   of the thread's CPU clock, one ``pread`` of its ``schedstat`` where there
   is one, one ``getrusage`` while the kernel is seen to count switches, the
   collector's sum, the step clock's baseline, the attributes), alone and by
   its parts.  In a process of JAX's size a sandboxed kernel's calls cost
   several times what they cost here: the traced cell's
   ``host_step_self_ms`` is the judge.
3. One tick's reading of the container's and the machine's counters
   (``host_pressure``), and one ``explain`` over a full ring.

Prints one JSON object, microseconds a call.
"""

import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def per_call_us(fn, n):
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return 1e6 * (time.perf_counter() - t0) / n


def main():
    from dlrover_tpu.observability import flight_recorder, trace
    from dlrover_tpu.trainer import step_account
    from dlrover_tpu.utils.step_clock import StepClock

    def one_span(name="trainer.step"):
        with trace.span(name, attrs={"step": 1}):
            pass

    def one_tick():
        one_span("trainer.step.tick")

    out = {"per_step_span_us": per_call_us(one_span, 100_000),
           "span_with_cpu_ns_us": per_call_us(one_tick, 100_000)}
    clock_read, trace._thread_time_ns = trace._thread_time_ns, None
    try:
        out["span_without_cpu_ns_us"] = per_call_us(one_tick, 100_000)
    finally:
        trace._thread_time_ns = clock_read

    class Events:
        def instant(self, name, content):
            pass

    clock = StepClock()
    for _ in range(40):
        clock.record(1e6)     # a baseline no interval here is twice of
    account = step_account.StepAccount(clock, Events())
    with trace.span("trainer.step", attrs={"step": 1}) as span:
        account.close(span, 1)
        out["close_us"] = per_call_us(lambda: account.close(span, 2), 100_000)
    out["parts_us"] = {
        "time_ns": per_call_us(time.time_ns, 100_000),
        "thread_time_ns": per_call_us(time.thread_time_ns, 100_000),
        "getrusage_thread": per_call_us(
            lambda: resource.getrusage(resource.RUSAGE_THREAD), 100_000),
        "gc_pause_ns": per_call_us(flight_recorder.gc_pause_ns, 100_000),
        "baseline": per_call_us(clock.baseline, 100_000),
    }
    try:
        fd = os.open(step_account._SCHEDSTAT, os.O_RDONLY)
    except OSError as e:      # a kernel without it: the account leaves it out
        out["parts_us"]["pread_schedstat"] = f"absent: {e}"
    else:
        out["parts_us"]["pread_schedstat"] = per_call_us(
            lambda: int(os.pread(fd, 128, 0).split()[1]), 100_000)
        os.close(fd)
    # how fine the thread's CPU clock ticks: the smallest step it takes
    # while this thread spins for 50 ms
    seen, until = set(), time.perf_counter() + 0.05
    while time.perf_counter() < until:
        seen.add(time.thread_time_ns())
    ticks = sorted(seen)
    out["thread_clock"] = {
        "info": str(time.get_clock_info("thread_time")),
        "distinct_readings_in_50_ms": len(ticks),
        "smallest_step_ns": min(
            (b - a for a, b in zip(ticks, ticks[1:])), default=None)}
    out["host_pressure_us"] = per_call_us(step_account.host_pressure, 2_000)
    out["host_pressure"] = step_account.host_pressure()
    ring = flight_recorder.recorder().spans
    while len(ring) < ring.maxlen:
        one_span()
    first, last = ring[0], ring[-1]
    out["explain_full_ring_us"] = per_call_us(
        lambda: flight_recorder.explain(
            first.start_ns, last.end_ns, first.tid), 20)
    out["ring_spans"] = len(ring)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
