"""Device-event timing on the REAL TPU chip.

Validates what the CPU tier can't: the profiler exposes true
``/device:TPU`` lanes, the collector lands per-op device timings in the
native timer, the daemon's ``/metrics`` endpoint exposes them under the
xpu_timer-compatible names, and the sampling overhead stays within the
reference's <=0.5% budget (``xpu_timer/README.md:21``) at the default
cadence.
"""

import urllib.request

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.timer.core import ExecutionTimer
from dlrover_tpu.timer.device_events import (
    DeviceEventCollector,
    measure_overhead,
)


@pytest.fixture(scope="module")
def timer():
    t = ExecutionTimer(metrics_port=0, allow_build=True)
    yield t
    t.shutdown()


def _step_fn():
    @jax.jit
    def step(x):
        return (x @ x.T).astype(jnp.float32).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()  # compile
    return lambda: step(x).block_until_ready()


class TestDeviceLanes:
    def test_device_events_reach_metrics_endpoint(self, timer):
        """A profiled window must surface device-lane ops, and the
        native /metrics endpoint must expose XPU_TIMER_* aggregates."""
        collector = DeviceEventCollector(
            timer, every_n_steps=1, device_only=True
        )
        run = _step_fn()
        with collector.window():
            run()
        assert collector.events_recorded > 0, (
            "no /device:TPU lane events captured"
        )
        port = timer.metrics_port
        if not port:
            pytest.skip("native metrics server unavailable (py fallback)")
        with urllib.request.urlopen(
            f"http://localhost:{port}/metrics", timeout=10
        ) as resp:
            body = resp.read().decode()
        assert "XPU_TIMER_" in body

    def test_collective_timings_exposed(self, timer):
        """psum on the chip -> XPU_TIMER_COLL_* series in the timer
        (single chip: XLA may elide the physical collective, so accept
        either the collective name or the kernel it folded into —
        but the capture pipeline itself must produce events)."""
        mesh = jax.sharding.Mesh(jax.devices(), ("dp",))
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        @jax.jit
        @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
        def allreduce(x):
            return jax.lax.psum(x, "dp")

        x = jnp.ones((len(jax.devices()), 256))
        allreduce(x).block_until_ready()
        collector = DeviceEventCollector(
            timer, every_n_steps=1, device_only=True
        )
        with collector.window():
            allreduce(x).block_until_ready()
        assert collector.events_recorded > 0

    def test_sampling_overhead_within_budget(self):
        """At the default 1-in-200 cadence the overhead must hold the
        reference's 0.5% claim; measured at 1-in-50 here to keep the
        test short, then scaled: overhead(200) ~= overhead(50) / 4."""
        run = _step_fn()
        report = measure_overhead(run, steps=100, every_n_steps=50)
        scaled_pct = report["overhead_pct"] / 4.0
        assert scaled_pct <= 0.5, report
