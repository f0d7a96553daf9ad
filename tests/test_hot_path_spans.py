"""The span recorder on the hot path: what a span holds, where it goes,
what it costs to have it off, the bridge to ``jax.profiler``, and the
spans the trainer step and the flash checkpoint layer open."""

import glob
import json
import subprocess
import sys
import threading
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.observability import flight_recorder, goodput, trace
from dlrover_tpu.trainer.flash_checkpoint import snapshot


@pytest.fixture
def rec(monkeypatch):
    """A private recorder and a sink of one's own: the process's are
    shared with every other suite of the run."""
    recorder = flight_recorder.FlightRecorder(attach_log_handler=False)
    monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
    exported = []
    trace.set_span_sink(exported.append)
    trace.seed_ids(4321)
    recorder.exported = exported
    yield recorder
    trace.set_span_sink(None)
    trace.seed_ids(0)


def _named(recorder, name):
    return [t for t in recorder.spans if t.name == name]


class TestSpanSemantics:
    def test_parent_child_thread_and_integer_clock(self, rec):
        before = time.time_ns()
        with trace.span("trainer.step", attrs={"step": 7}) as outer:
            with trace.span("trainer.step.dispatch") as inner:
                pass
        after = time.time_ns()
        child, parent = rec.spans
        assert (child.name, parent.name) == (
            "trainer.step.dispatch", "trainer.step")
        assert child.parent_span_id == parent.span_id == outer.span_id
        assert child.trace_id == parent.trace_id and inner.span_id
        for t in (child, parent):
            assert isinstance(t.start_ns, int) and isinstance(t.end_ns, int)
            assert t.tid == threading.get_ident()
            assert t.thread == threading.current_thread().name
        assert before <= parent.start_ns <= child.start_ns
        assert child.end_ns <= parent.end_ns <= after
        # the spans a step opens read no CPU clock (``PER_STEP_SPANS``)
        assert parent.attrs == {"step": 7} and child.attrs == {}

    @pytest.mark.parametrize("name,timed", [
        ("trainer.step", False), ("trainer.step.dispatch", False),
        ("trainer.shard_batch", False),
        ("trainer.step.tick", True), ("trainer.model_stats", True),
        ("flash.save", True), ("flash.save.device_copy", True),
        ("flash.stage", True), ("flash.stage.shard", True),
        ("flash.persist", False), ("rpc.get/Req", False),
    ])
    def test_which_spans_say_how_long_their_thread_computed(
            self, rec, name, timed):
        """A hot-path span that is not opened every step carries ``cpu_ns``:
        small where its thread slept, near its length where it spun."""
        with trace.span(name):
            time.sleep(0.02)
        with trace.span(name):
            # 20 ms by the thread's own CPU clock: a loaded machine makes
            # the span longer, not the computing shorter
            until = time.thread_time() + 0.02
            while time.thread_time() < until:
                pass
        slept, spun = rec.spans
        assert ("cpu_ns" in slept.attrs) == timed == ("cpu_ns" in spun.attrs)
        if timed:
            assert 0 <= slept.attrs["cpu_ns"] < 10_000_000
            assert 19_000_000 <= spun.attrs["cpu_ns"] <= (
                spun.end_ns - spun.start_ns + 1000)

    def test_record_keeps_seconds_for_the_timeline(self, rec):
        with trace.span("flash.save", attrs={"step": 1}):
            time.sleep(0.002)
        (t,) = rec.spans
        record = trace.record_of(t)
        assert record["type"] == "SPAN" and record["name"] == "flash.save"
        assert record["ts"] == pytest.approx(t.start_ns * 1e-9, abs=1e-6)
        assert record["dur"] == pytest.approx(
            (t.end_ns - t.start_ns) * 1e-9, abs=1e-6)
        assert record["tid"] == t.tid and record["thread"] == t.thread
        snap = rec.snapshot(stacks=False)
        assert snap["spans"] == [record]
        assert snap["span_totals"]["flash.save"]["count"] == 1
        json.dumps(snap)

    def test_ring_is_bounded_and_totals_survive_eviction(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_RECORDER_SPANS", "8")
        recorder = flight_recorder.FlightRecorder(attach_log_handler=False)
        monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
        for i in range(50):
            with trace.span("trainer.step", attrs={"step": i}):
                pass
        assert len(recorder.spans) == 8
        assert [t.attrs["step"] for t in recorder.spans] == list(range(42, 50))
        count, total_ns, longest_ns = recorder.span_totals["trainer.step"]
        assert count == 50 and 0 < longest_ns <= total_ns

    def test_default_ring_holds_a_window_of_the_fastest_cell(self):
        from dlrover_tpu.common import envs

        # 51 s at 134 ms a step, three spans a step, one save's spans; since
        # PR 53 one ``trainer.step.tick`` and one ``trainer.model_stats``
        # every twentieth step and a ``runtime.gc`` for a pause of a
        # millisecond (counted as one a step: none was seen on the chip)
        steps = 381
        assert envs.knob("DLROVER_TPU_RECORDER_SPANS").default >= (
            steps * 3 + 2 * (steps // 20 + 1) + steps + 200)

    def test_switched_off_is_a_noop(self, rec, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_TRACE", "0")
        trace.seed_ids(4321)  # the switch is read once; this re-reads it
        with trace.span("trainer.step") as sp:
            assert sp is trace.NOOP_SPAN and sp.context() is None
            sp.set_attrs({"k": 1})
            assert trace.current_span() is None
        assert not rec.spans and not rec.span_totals and not rec.exported
        assert trace.NOOP_SPAN.attrs == {}

    def test_context_carried_to_another_thread(self, rec):
        seen = {}

        def stager(ctx):
            with trace.span("flash.stage", parent=ctx):
                seen["tid"] = threading.get_ident()

        with trace.span("flash.save") as save:
            t = threading.Thread(
                target=stager, args=(save.context(),), name="stg")
            t.start()
            t.join()
        (stage,) = _named(rec, "flash.stage")
        assert stage.parent_span_id == save.span_id
        assert stage.trace_id == save.trace_id
        assert stage.tid == seen["tid"] != threading.get_ident()
        assert stage.thread == "stg"

    def test_a_process_without_jax_never_imports_it(self):
        code = (
            "import sys\n"
            "from dlrover_tpu.observability import trace, flight_recorder\n"
            "with trace.span('trainer.step'):\n"
            "    with trace.span('rpc.get/X'):\n"
            "        pass\n"
            "assert len(flight_recorder.recorder().spans) == 2\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]


class TestWhereASpanGoes:
    @pytest.mark.parametrize("name,exported", [
        ("trainer.step", False), ("trainer.step.dispatch", False),
        ("trainer.shard_batch", False), ("flash.save", False),
        ("flash.save.device_copy", False), ("flash.stage", False),
        ("flash.stage.shard", False),
        ("flash.persist", True), ("flash.restore", True),
        ("rpc.get/Req", True), ("kv.wait", True), ("rdzv.join", True),
    ])
    def test_hot_path_spans_stay_in_memory(self, rec, name, exported):
        with trace.span(name):
            pass
        assert [t.name for t in rec.spans] == [name]  # the ring: always
        assert [r["name"] for r in rec.exported] == ([name] * exported)

    @pytest.mark.parametrize("name,claim", [
        ("flash.stage", "ckpt_background"),
        ("flash.stage.shard", "ckpt_background"),
        ("flash.persist", "ckpt_background"),
        ("flash.save", "ckpt_blocking"),
        ("flash.save.device_copy", "ckpt_blocking"),
        ("flash.restore", "ckpt_blocking"),
        ("trainer.step", ""), ("trainer.shard_batch", ""),
    ])
    def test_goodput_claim_of_each_name(self, name, claim):
        assert goodput.span_phase(name) == claim

    def test_a_stage_behind_the_steps_books_no_stall(self, monkeypatch):
        ledger = goodput.GoodputLedger(res_s=0.01)
        monkeypatch.setattr(goodput, "_LEDGER", ledger)
        monkeypatch.setattr(goodput, "enabled", lambda: True)
        recorder = flight_recorder.FlightRecorder(attach_log_handler=False)
        monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
        t0 = time.time()
        with trace.span("flash.stage"):
            time.sleep(0.2)
        ledger.charge_interval("compute", t0, time.time())
        phases = ledger.summary()["phases"]
        assert phases["compute"] >= 0.15
        assert phases["ckpt_stall"] <= 0.03

    def test_flash_spans_feed_an_attached_timer(self, rec, monkeypatch):
        fed = []

        class Timer:
            KIND_CKPT = 3

            def now_ns(self):
                return 10_000_000_000

            def record(self, name, start_ns, dur_ns, kind):
                fed.append((name, start_ns, dur_ns, kind))

        monkeypatch.setattr(trace, "_TIMER", Timer())
        with trace.span("flash.stage"):
            pass
        with trace.span("trainer.step"):
            pass
        ((name, start_ns, dur_ns, kind),) = fed
        (stage,) = _named(rec, "flash.stage")
        assert (name, kind) == ("flash.stage", 3)
        assert dur_ns == stage.end_ns - stage.start_ns
        assert start_ns == 10_000_000_000 - dur_ns  # the timer's clock

    def test_an_open_flash_span_is_the_timers_stuck_span(self):
        from dlrover_tpu.timer.core import ExecutionTimer

        timer = ExecutionTimer(metrics_port=-1, hang_timeout_secs=600)
        try:
            with trace.span("flash.restore.agreement"):
                time.sleep(0.01)
                stuck = timer.stuck_span()
            assert stuck[0] == "flash.restore.agreement" and stuck[1] > 0
            assert not [s for s in timer.current_spans()
                        if s[0].startswith("flash.")]
        finally:
            timer.shutdown()


class TestProfilerBridge:
    def test_a_session_holds_the_span_under_its_name(self, rec, tmp_path):
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with trace.span("trainer.step", attrs={"step": 1}):
                with trace.span("trainer.step.dispatch"):
                    jnp.ones((8, 8)).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("trainer."):
                        found[event.name] = (event.start_ns, event.duration_ns)
        assert set(found) == {"trainer.step", "trainer.step.dispatch"}
        (step,) = _named(rec, "trainer.step")
        (dispatch,) = _named(rec, "trainer.step.dispatch")
        # one clock, two origins: the distance between the two spans is the
        # same on both sides, and so, nearly, is each span's length
        gap_ring = dispatch.start_ns - step.start_ns
        gap_xplane = found["trainer.step.dispatch"][0] - found["trainer.step"][0]
        assert abs(gap_ring - gap_xplane) < 1_000_000
        assert abs((step.end_ns - step.start_ns)
                   - found["trainer.step"][1]) < 1_000_000

    def test_without_a_session_nothing_is_written(self, rec, tmp_path):
        with trace.span("trainer.step"):
            pass
        assert _named(rec, "trainer.step")
        assert not list(tmp_path.iterdir())


def _scope():
    return f"t{uuid.uuid4().hex[:8]}"


def _state():
    return {
        # over twice the smallest chunk, or it goes as one transfer
        "w": jnp.arange(1 << 20, dtype=jnp.float32).reshape(1024, 1024),
        "b": jnp.arange(4096, dtype=jnp.bfloat16),
        "step": jnp.asarray(3, jnp.int32),
    }


class TestFlashCheckpointSpans:
    @pytest.fixture
    def engine(self, tmp_path, monkeypatch):
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            CheckpointEngine,
        )

        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")
        monkeypatch.setenv("DLROVER_TPU_STREAM_CHUNK_BYTES", str(256 << 10))
        eng = CheckpointEngine(
            str(tmp_path), process_id=0, num_processes=1, scope=_scope())
        yield eng
        eng.close()
        eng.unlink_memory()

    def test_async_save_yields_save_parts_and_a_stage(self, rec, engine):
        state = _state()
        assert engine.save_to_memory_async(5, state) >= 0
        assert engine._flush_async(60)
        (save,) = _named(rec, "flash.save")
        assert save.tid == threading.get_ident()
        assert save.attrs["step"] == 5 and save.attrs["async"] is True
        assert save.attrs["storage"] is False
        assert save.attrs["outcome"] == "async"
        nbytes = sum(x.size * x.dtype.itemsize for x in state.values())
        assert save.attrs["bytes"] == nbytes
        parts = {t.name: t for t in rec.spans
                 if t.parent_span_id == save.span_id and t.tid == save.tid}
        assert set(parts) == {"flash.save.slot_wait", "flash.save.device_copy",
                              "flash.save.submit"}
        for part in parts.values():
            # two clocks: a microsecond of room
            assert 0 <= part.attrs.pop("cpu_ns") <= (
                part.end_ns - part.start_ns + 1000)
        assert parts["flash.save.slot_wait"].attrs == {"live_copies": 0}
        assert parts["flash.save.device_copy"].attrs == {"leaves": 3}
        assert parts["flash.save.submit"].attrs == {"result": True}
        for part in parts.values():
            assert save.start_ns <= part.start_ns <= part.end_ns <= save.end_ns
        (stage,) = _named(rec, "flash.stage")
        assert stage.parent_span_id == save.span_id
        assert stage.trace_id == save.trace_id
        assert stage.tid != save.tid and stage.thread == "ckpt-stager"
        attrs = stage.attrs
        assert attrs["step"] == 5 and attrs["bytes"] == nbytes
        # what the streaming path makes of the same state at this chunk size
        shm = __import__(
            "dlrover_tpu.common.multi_process", fromlist=["x"]
        ).SharedMemoryBuffer(f"cmp_{_scope()}")
        try:
            alone = snapshot.stream_snapshot(
                shm, 5, snapshot.plan_shards(state), chunk_bytes=256 << 10)
        finally:
            shm.unlink()
        assert attrs["chunks"] == alone.chunks == attrs["host_copies"]
        assert attrs["chunks"] == 16 + 2
        assert attrs["chunk_bytes_max"] == 256 << 10
        assert (attrs["chunk_bytes_min"] <= attrs["chunk_bytes_median"]
                <= attrs["chunk_bytes_max"])
        for key in ("lock_wait_s", "pace_sleep_s", "slice_s", "compile_s",
                    "d2h_wait_s", "shm_copy_s"):
            assert attrs[key] >= 0, key
        assert attrs["compiles"] >= 0
        assert set(attrs["pacer"]) == {
            "best_bw", "baseline_step_s", "chunk_bytes", "sleep_ratio"}
        took = (stage.end_ns - stage.start_ns) * 1e-9
        assert sum(attrs[k] for k in (
            "lock_wait_s", "pace_sleep_s", "slice_s", "d2h_wait_s",
            "shm_copy_s")) <= took
        shards = [t for t in _named(rec, "flash.stage.shard")
                  if t.parent_span_id == stage.span_id]
        assert len(shards) == 3
        assert sum(t.attrs["bytes"] for t in shards) == nbytes
        assert sum(t.attrs["chunks"] for t in shards) == attrs["chunks"]
        assert {t.attrs["path"] for t in shards} == {"w", "b", "step"}
        assert not rec.exported  # none of it is serialised span by span

    def test_the_save_instant_is_unchanged(self, rec, engine):
        engine.save_to_memory_async(6, _state())
        assert engine._flush_async(60)
        (event,) = [e for e in rec.events
                    if e.get("name") == "trainer.ckpt.save"]
        assert set(event["content"]) == {
            "step", "blocked_s", "storage", "async"}
        assert event["content"]["step"] == 6
        assert event["content"]["async"] is True
        assert event["content"]["storage"] is False
        (save,) = _named(rec, "flash.save")
        assert event["span_id"] == save.span_id  # stamped inside the span

    def test_sync_save_has_one_save_and_its_stage(self, rec, engine):
        state = _state()
        assert engine.save_to_memory(2, state) >= 0
        (save,) = _named(rec, "flash.save")
        (stage,) = _named(rec, "flash.stage")
        assert save.attrs["async"] is False
        assert save.attrs["outcome"] == "sync"
        assert stage.parent_span_id == save.span_id and stage.tid == save.tid
        nbytes = sum(x.size * x.dtype.itemsize for x in state.values())
        assert stage.attrs["bytes"] == save.attrs["bytes"] == nbytes
        assert stage.attrs["chunks"] == 3 == stage.attrs["host_copies"]
        assert stage.attrs["lock_wait_s"] >= 0

    def test_a_small_state_is_one_save_not_two(self, rec, tmp_path,
                                                 monkeypatch):
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            CheckpointEngine,
        )

        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", str(1 << 30))
        eng = CheckpointEngine(
            str(tmp_path), process_id=0, num_processes=1, scope=_scope())
        try:
            assert eng.save_to_memory_async(9, _state()) >= 0
        finally:
            eng.close()
            eng.unlink_memory()
        (save,) = _named(rec, "flash.save")
        assert save.attrs["async"] is True and save.attrs["outcome"] == "sync"
        assert len(_named(rec, "flash.stage")) == 1


class TestStageCounters:
    def test_paced_stream_counts_chunks_and_sleeps(self, monkeypatch):
        from dlrover_tpu.common.multi_process import SharedMemoryBuffer

        monkeypatch.setenv("DLROVER_TPU_STAGE_PACE", "0.5")
        monkeypatch.delenv("DLROVER_TPU_STREAM_CHUNK_BYTES", raising=False)
        pacer = snapshot.StagePacer()
        pacer.chunk_bytes = 64 << 10
        pacer._calibrated = True
        counters = snapshot.StageCounters()
        state = {"w": jnp.ones((2048, 1024), jnp.float32)}
        shm = SharedMemoryBuffer(f"cnt_{_scope()}")
        try:
            snapshot.stream_snapshot(
                shm, 3, snapshot.plan_shards(state), pacer=pacer,
                counters=counters)
            meta = snapshot.read_snapshot_meta(shm)
            (shard,) = meta["leaves"][0]["shards"]
            np.testing.assert_array_equal(
                snapshot.read_shard_bytes(shm, meta, shard, "float32"),
                np.ones((2048, 1024), np.float32))
        finally:
            shm.unlink()
        assert counters.bytes == 8 << 20 and counters.chunks == 128
        assert counters.host_copies == counters.chunks  # one a chunk
        assert counters.pace_sleep_s == pytest.approx(pacer.slept_s)
        assert pacer.slept_s > 0 and counters.d2h_wait_s > 0
        attrs = counters.as_attrs()
        assert attrs["chunk_bytes_median"] == 64 << 10
        assert pacer.summary()["chunk_bytes"] == 64 << 10


class TestTrainerSpans:
    def test_a_tiny_trainer_yields_the_three_spans(self, rec):
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer.train import Trainer

        cfg = LlamaConfig.tiny()
        trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-2),
                          build_mesh(MeshConfig(dp=8)))
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(8, 17))
        host = {"input_ids": np.asarray(ids[:, :-1], np.int32),
                "labels": np.asarray(ids[:, 1:], np.int32)}
        state = trainer.create_state(jax.random.PRNGKey(0), host["input_ids"])
        for _ in range(3):
            state, _ = trainer.train_step(state, trainer.shard_batch(host))
        steps = _named(rec, "trainer.step")
        assert [t.attrs["step"] for t in steps] == [1, 2, 3]
        batches = _named(rec, "trainer.shard_batch")
        assert [t.attrs["bytes"] for t in batches] == [2 * 8 * 16 * 4] * 3
        dispatches = _named(rec, "trainer.step.dispatch")
        assert [t.parent_span_id for t in dispatches] == [
            t.span_id for t in steps]
        assert [t.attrs["compiled"] for t in dispatches] == [
            True, False, False]
        for step, dispatch in zip(steps, dispatches):
            assert step.start_ns <= dispatch.start_ns
            assert dispatch.end_ns <= step.end_ns
        # the compile observatory's two spans of the first call are exported
        # as before; of the step's own, none
        assert not [r for r in rec.exported
                    if r["name"].startswith("trainer.")]
        assert rec.span_totals["trainer.step"][0] == 3
