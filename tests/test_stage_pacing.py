"""Auto-paced checkpoint staging: step clock, pacer control law, and
chunked device->host transfers into the shm segment.

Counterpart of VERDICT r02 item 4: the manual ``DLROVER_TPU_STAGE_PACE``
knob became closed-loop control keeping step inflation bounded.
"""

import uuid

import numpy as np
import pytest

from dlrover_tpu.common.multi_process import SharedMemoryBuffer
from dlrover_tpu.trainer.flash_checkpoint import snapshot
from dlrover_tpu.trainer.flash_checkpoint.snapshot import (
    _MAX_CHUNK,
    _MIN_CHUNK,
    StagePacer,
    extract_host_shards,
)
from dlrover_tpu.utils.step_clock import StepClock


def _read_all(shm):
    """(meta, {path: the leaf assembled from its shards}) of a segment."""
    meta = snapshot.read_snapshot_meta(shm)
    assert meta is not None
    out = {}
    for leaf in meta["leaves"]:
        pieces = snapshot.ShardIndexMap(leaf["dtype"], leaf["gshape"])
        for sm in leaf["shards"]:
            pieces.add(
                sm["index"],
                snapshot.read_shard_bytes(shm, meta, sm, leaf["dtype"]),
            )
        out[leaf["path"]] = pieces.read(
            tuple(slice(0, d) for d in leaf["gshape"])
        )
    return meta, out


class TestStepClock:
    def test_baseline_needs_two_samples(self):
        clock = StepClock()
        assert clock.baseline() is None
        clock.record(0.1)
        assert clock.baseline() is None
        clock.record(0.2)
        assert clock.baseline() == pytest.approx(0.2)

    def test_staging_steps_excluded_from_calm_baseline(self):
        clock = StepClock()
        clock.record(0.1)
        clock.record(0.1)
        clock.staging_started()
        for _ in range(10):
            clock.record(5.0)  # inflated steps during staging
        clock.staging_finished()
        assert clock.baseline() == pytest.approx(0.1)

    def test_steps_since_and_idle(self):
        import time

        clock = StepClock()
        assert clock.idle()  # nothing recorded yet
        mark = time.monotonic()
        clock.record(0.05)
        clock.record(0.07)
        assert sorted(clock.steps_since(mark)) == [0.05, 0.07]
        assert clock.steps_since(time.monotonic()) == []
        assert not clock.idle()  # just recorded
        assert clock.idle(now=time.monotonic() + 60)

    def test_reset_clears_history(self):
        clock = StepClock()
        clock.record(0.1)
        clock.record(0.1)
        clock.reset()
        assert clock.baseline() is None
        assert clock.idle()


class TestStagePacer:
    def _clock_with_baseline(self, step_s=0.1, n=4):
        clock = StepClock()
        for _ in range(n):
            clock.record(step_s)
        return clock

    def test_calibrates_chunk_from_bandwidth_and_baseline(self):
        clock = self._clock_with_baseline(step_s=0.1)
        pacer = StagePacer(factor=1.5, clock=clock)
        # 100 MB/s observed, 0.1s steps, factor 1.5 -> slack 0.05s*0.6
        pacer.note_transfer(100 << 20, 1.0)
        expect = (100 << 20) * 0.05 * 0.6
        assert pacer.chunk_bytes == pytest.approx(expect, rel=0.01)

    def test_inflated_steps_shrink_chunk(self):
        clock = self._clock_with_baseline(step_s=0.1)
        pacer = StagePacer(factor=1.5, clock=clock)
        pacer.note_transfer(32 << 20, 1.0)
        before = pacer.chunk_bytes
        clock.staging_started()
        clock.record(1.0)  # 10x inflation
        pacer._adjust()
        assert pacer.chunk_bytes <= max(_MIN_CHUNK, before // 2)

    def test_at_min_chunk_inflation_raises_sleep(self):
        clock = self._clock_with_baseline(step_s=0.1)
        pacer = StagePacer(factor=1.5, clock=clock)
        pacer.chunk_bytes = _MIN_CHUNK
        clock.record(1.0)
        pacer._adjust()
        assert pacer.sleep_ratio > 0

    def test_calm_steps_recover_throughput(self):
        clock = self._clock_with_baseline(step_s=0.1)
        pacer = StagePacer(factor=1.5, clock=clock)
        pacer.sleep_ratio = 2.0
        chunk = pacer.chunk_bytes
        clock.record(0.1)  # no inflation observed
        pacer._adjust()
        assert pacer.sleep_ratio < 2.0
        clock.record(0.1)
        pacer.sleep_ratio = 0.0
        pacer._adjust()
        assert pacer.chunk_bytes >= chunk

    def test_idle_training_goes_full_speed(self):
        clock = StepClock()  # never recorded -> idle
        pacer = StagePacer(factor=1.5, clock=clock)
        pacer.sleep_ratio = 4.0
        before = pacer.chunk_bytes
        pacer.gate()
        assert pacer.sleep_ratio == 0.0
        assert pacer.chunk_bytes == min(_MAX_CHUNK, before * 2)

    def test_manual_pace_env_still_honored(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_STAGE_PACE", "0.5")
        clock = self._clock_with_baseline()
        pacer = StagePacer(clock=clock)
        assert pacer.manual_pace == 0.5
        pacer.note_transfer(1 << 20, 0.01)
        pacer.gate()  # sleeps 0.005s; must not adjust/crash


class TestPacerConvergence:
    """Inflation-bounding under a deterministic clock: BENCH_r05
    observed 2.08x median staged-step inflation against the 1.5x
    ``DLROVER_TPU_STAGE_FACTOR`` target on the CPU fallback path.  This
    simulates the closed loop with virtual time — each train step waits
    behind exactly one in-flight chunk (the chunking contract) — and
    asserts the control law converges the MEDIAN staged-step inflation
    under the factor."""

    def _virtual_time(self, monkeypatch):
        import time as _time

        vtime = [0.0]
        monkeypatch.setattr(_time, "monotonic", lambda: vtime[0])
        monkeypatch.setattr(
            _time, "sleep",
            lambda s: vtime.__setitem__(0, vtime[0] + s),
        )
        return vtime

    def _simulate(self, monkeypatch, base, bw, chunks=40):
        """Returns the staged-step durations observed while a pacer
        stages through a link of ``bw`` bytes/s against a training loop
        with calm step time ``base``."""
        monkeypatch.delenv("DLROVER_TPU_STAGE_PACE", raising=False)
        vtime = self._virtual_time(monkeypatch)
        clock = StepClock()
        for _ in range(4):
            vtime[0] += base
            clock.record(base)
        pacer = StagePacer(clock=clock)  # factor from the env var
        clock.staging_started()
        staged = []
        for _ in range(chunks):
            pacer.gate()
            chunk_s = pacer.chunk_bytes / bw
            vtime[0] += chunk_s
            pacer.note_transfer(pacer.chunk_bytes, chunk_s)
            # one train step completes per chunk, waiting behind it
            duration = base + chunk_s
            vtime[0] += base
            clock.record(duration)
            staged.append(duration)
        clock.staging_finished()
        return staged

    def test_converges_median_inflation_under_env_factor(
        self, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_STAGE_FACTOR", "1.5")
        base = 0.1
        staged = self._simulate(monkeypatch, base=base, bw=100e6)
        # the pre-calibration default chunk (8 MiB at 100 MB/s) blows
        # the bound — the loop must have something to converge FROM
        assert staged[0] > 1.5 * base
        tail = sorted(staged[-10:])
        median = tail[len(tail) // 2]
        assert median <= 1.5 * base * 1.05, (
            f"median staged step {median:.3f}s exceeds "
            f"{1.5 * base:.3f}s bound (staged={staged[-10:]})"
        )

    def test_converges_for_tighter_factor(self, monkeypatch):
        # 1.2x bound, fast link: the calibrated chunk stays above the
        # 1 MiB floor, so the bound is reachable by chunk sizing alone
        # (below the floor the pacer escalates duty-cycle sleeps, which
        # this one-wait-per-step model deliberately does not credit)
        monkeypatch.setenv("DLROVER_TPU_STAGE_FACTOR", "1.2")
        base = 0.05
        staged = self._simulate(
            monkeypatch, base=base, bw=400e6, chunks=60
        )
        tail = sorted(staged[-10:])
        median = tail[len(tail) // 2]
        assert median <= 1.2 * base * 1.05


class TestChunkedTransfer:
    """The stager's writer, chunk size pinned: what ``stream_snapshot``
    lands in shm, read back, is the host copy of the array, and is what
    ``extract_host_shards`` + ``write_snapshot`` land."""

    @pytest.fixture(autouse=True)
    def _small_shards_are_chunked(self, monkeypatch):
        # a shard under 2 x _MIN_CHUNK goes in one transfer whatever the
        # chunk size: lower the floor so that these shapes really stream
        monkeypatch.setattr(snapshot, "_MIN_CHUNK", 1 << 10)
        monkeypatch.delenv("DLROVER_TPU_STAGE_PACE", raising=False)

    def _land_both(self, state, chunk_bytes, monkeypatch):
        """(meta, {path: array}) read back from the streamed segment,
        the same from the packed one, and the stream's counters."""
        monkeypatch.setenv(
            "DLROVER_TPU_STREAM_CHUNK_BYTES", str(chunk_bytes))
        tag = uuid.uuid4().hex[:8]
        streamed = SharedMemoryBuffer(f"pace_s_{tag}")
        packed = SharedMemoryBuffer(f"pace_p_{tag}")
        try:
            counters = snapshot.stream_snapshot(
                streamed, 2, snapshot.plan_shards(state),
                pacer=StagePacer(factor=1.5, clock=StepClock()),
            )
            snapshot.write_snapshot(
                packed, 2, extract_host_shards(state))
            return _read_all(streamed), _read_all(packed), counters
        finally:
            streamed.unlink()
            packed.unlink()

    @pytest.mark.parametrize(
        "shape,chunks",
        # rows of 1200, 4096 and 22572 bytes in chunks of 64 KiB
        [((1024, 300), 19), ((300, 1024), 19), ((7, 513, 11), 4),
         ((33,), 1)],
    )
    def test_matches_plain_copy(self, shape, chunks, monkeypatch):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        host = rng.standard_normal(shape).astype(np.float32)
        (meta_s, got), (meta_p, ref), counters = self._land_both(
            {"x": jnp.asarray(host)}, 64 * 1024, monkeypatch)
        np.testing.assert_array_equal(got["x"], host)
        np.testing.assert_array_equal(got["x"], ref["x"])
        assert meta_s == meta_p
        assert counters.bytes == host.nbytes
        assert counters.host_copies == counters.chunks == chunks

    def test_small_array_single_transfer(self, monkeypatch):
        import jax.numpy as jnp

        (_, got), _, counters = self._land_both(
            {"x": jnp.ones((8, 8), jnp.float32)}, 1 << 20, monkeypatch)
        np.testing.assert_array_equal(got["x"], np.ones((8, 8), np.float32))
        assert counters.chunks == 1

    def test_bfloat16_roundtrip(self, monkeypatch):
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        host = rng.standard_normal((512, 700)).astype(np.float32)
        arr = jnp.asarray(host, jnp.bfloat16)
        (meta_s, got), (meta_p, ref), counters = self._land_both(
            {"x": arr}, 128 * 1024, monkeypatch)
        np.testing.assert_array_equal(got["x"], np.asarray(arr))
        np.testing.assert_array_equal(got["x"], ref["x"])
        assert meta_s == meta_p and counters.chunks > 1

    def test_streamed_state_equals_packed(self, monkeypatch):
        import jax.numpy as jnp

        state = {
            "w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "b": jnp.ones((7,), jnp.bfloat16),
            "step": np.int64(3),
        }
        (meta_s, got), (meta_p, ref), _ = self._land_both(
            state, 4096, monkeypatch)
        assert meta_s == meta_p
        assert set(got) == set(ref) == {"w", "b", "step"}
        for path in ref:
            np.testing.assert_array_equal(got[path], ref[path])
        np.testing.assert_array_equal(got["w"], np.asarray(state["w"]))
