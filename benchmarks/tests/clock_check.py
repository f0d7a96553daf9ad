#!/usr/bin/env python3
"""Is the program's span recorder on the profiler's clock, and what does a
span cost?  One process, on whatever device JAX finds (run it through the
chip tool for the numbers PERF.md quotes):

    python3 benchmarks/tests/clock_check.py

1. The cost of one ``trace.span`` with no profiler session (the state the
   benchmark's untraced runs and a deployment are in), alone and nested.
2. Under a session: the same cost, and every bridged span read from both
   sides.  The recorder stamps ``time.time_ns()``; the xplane counts from
   the start of its session.  The distance between the two stamps of one
   span is that origin; how far it moves from span to span is how well the
   clocks agree.
3. One jitted matmul under a span, to see that the device's operations lie
   inside the host span that waited for them: device and host planes on one
   timeline.

Prints one JSON object.
"""

import glob
import json
import os
import statistics
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def per_span_us(trace, n):
    t0 = time.perf_counter()
    for i in range(n):
        with trace.span("trainer.step", attrs={"step": i}):
            pass
    alone = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for i in range(n // 2):
        with trace.span("trainer.step", attrs={"step": i}):
            with trace.span("trainer.step.dispatch", attrs={"compiled": False}):
                pass
    nested = (time.perf_counter() - t0) / n
    return {"alone_us": 1e6 * alone, "nested_us": 1e6 * nested}


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from dlrover_tpu.observability import flight_recorder, trace

    device = jax.devices()[0]
    out = {"platform": device.platform, "kind": device.device_kind}
    matmul = jax.jit(lambda a: a @ a)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    matmul(x).block_until_ready()

    out["no_session"] = per_span_us(trace, 200_000)
    out["ring_spans"] = len(flight_recorder.recorder().spans)

    folder = tempfile.mkdtemp(prefix="clockcheck_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    before = time.time_ns()
    jax.profiler.start_trace(folder, profiler_options=options)
    after = time.time_ns()
    out["under_session"] = per_span_us(trace, 20_000)
    for i in range(1000):
        with trace.span("clock.probe", attrs={"i": i}):
            pass

    def elsewhere():
        for i in range(200):
            with trace.span("clock.other", attrs={"i": i}):
                pass

    thread = threading.Thread(target=elsewhere, name="clock-other")
    thread.start()
    thread.join()
    with trace.span("clock.device"):
        matmul(x).block_until_ready()
    jax.profiler.stop_trace()

    path = sorted(glob.glob(os.path.join(
        folder, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out["xplane_bytes"] = os.path.getsize(path)
    seen, device_ops = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/host:CPU" and e.name.startswith("clock."):
                    seen.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.duration_ns), line.name))
                elif (plane.name.startswith("/device:")
                      and line.name == "XLA Ops"):
                    device_ops.append((int(e.start_ns),
                                       int(e.start_ns + e.duration_ns)))
    ring = {}
    for t in flight_recorder.recorder().spans:
        if t.name.startswith("clock."):
            ring.setdefault(t.name, []).append(t)
    origins = []
    for name, spans in ring.items():
        xs = sorted(seen.get(name, []))
        out[f"{name}.in_ring_in_xplane"] = [len(spans), len(xs)]
        if len(xs) != len(spans):
            continue
        origins += [t.start_ns - start for t, (start, _, _) in zip(spans, xs)]
        out[f"{name}.xplane_line"] = xs[0][2]
        out[f"{name}.ring_thread"] = spans[0].thread
        out[f"{name}.dur_ring_minus_xplane_ns_median"] = statistics.median(
            (t.end_ns - t.start_ns) - dur for t, (_, dur, _) in zip(spans, xs))
    if origins:
        mid = int(statistics.median(origins))
        out["origin_ns"] = {
            "median": mid, "min_minus_median": min(origins) - mid,
            "max_minus_median": max(origins) - mid,
            "p05_minus_median": int(statistics.quantiles(
                origins, n=20)[0]) - mid,
            "p95_minus_median": int(statistics.quantiles(
                origins, n=20)[-1]) - mid,
            "spans": len(origins),
        }
        out["start_trace_ns"] = {"before_minus_origin": before - mid,
                                 "after_minus_origin": after - mid}
        dev = ring.get("clock.device")
        if dev and device_ops:
            lo, hi = dev[0].start_ns - mid, dev[0].end_ns - mid
            inside = [op for op in device_ops if lo <= op[0] and op[1] <= hi]
            last = max(device_ops, key=lambda op: op[1])
            out["device_ops"] = {
                "traced": len(device_ops), "inside_clock.device": len(inside),
                "span_ns": hi - lo,
                "span_end_minus_last_op_end_ns": hi - last[1],
                # each operation against the span's start: where the
                # device's plane lies on the host's timeline
                "op_start_end_minus_span_start_ns": [
                    [op[0] - lo, op[1] - lo] for op in sorted(device_ops)[-8:]],
            }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
