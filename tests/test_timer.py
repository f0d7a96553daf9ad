"""Native execution-timer tests: recording, Prometheus export, hang
watchdog, timeline dump."""

import json
import time
import urllib.request

import pytest

from dlrover_tpu.timer.core import ExecutionTimer


@pytest.fixture(scope="module")
def timer():
    t = ExecutionTimer(metrics_port=0, hang_timeout_secs=2.0, allow_build=True)
    yield t
    t.shutdown()


class TestExecutionTimer:
    def test_native_library_loaded(self, timer):
        # the toolchain is present in this environment; the native core
        # must build and load (fallback would hide a build regression)
        assert timer.native

    def test_record_and_metrics_export(self, timer):
        t0 = timer.now_ns()
        timer.record("matmul_fwd", t0, 5_000_000, timer.KIND_SPAN)
        timer.record("matmul_fwd", t0, 7_000_000, timer.KIND_SPAN)
        timer.set_gauge("custom_gauge", 42.5)
        assert timer.metrics_port > 0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{timer.metrics_port}/metrics", timeout=10
        ).read().decode()
        assert 'XPU_TIMER_KERNEL_COUNT{name="matmul_fwd"} 2' in body
        assert 'XPU_TIMER_KERNEL_MAX_MS{name="matmul_fwd"} 7.0' in body
        assert "custom_gauge 42.5" in body
        assert "XPU_TIMER_COMMON_HANG 0" in body

    def test_span_context_manager(self, timer):
        with timer.span("span_x"):
            time.sleep(0.01)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{timer.metrics_port}/metrics", timeout=10
        ).read().decode()
        assert 'XPU_TIMER_KERNEL_COUNT{name="span_x"} 1' in body

    def test_hang_watchdog_fires_and_clears(self, timer):
        timer.kick()
        assert not timer.hang_detected()
        time.sleep(2.6)  # exceed the 2s watchdog without activity
        assert timer.hang_detected()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{timer.metrics_port}/metrics", timeout=10
        ).read().decode()
        assert "XPU_TIMER_COMMON_HANG 1" in body
        timer.kick()  # activity clears the hang
        assert not timer.hang_detected()

    def test_timeline_dump_chrome_trace(self, timer, tmp_path):
        t0 = timer.now_ns()
        timer.record("step", t0, 1_000_000, timer.KIND_STEP)
        path = str(tmp_path / "timeline.json")
        assert timer.dump_timeline(path)
        trace = json.load(open(path))
        names = {e["name"] for e in trace["traceEvents"]}
        assert "step" in names
        step_event = next(
            e for e in trace["traceEvents"] if e["name"] == "step"
        )
        assert step_event["ph"] == "X"
        assert step_event["dur"] == pytest.approx(1000.0, rel=0.01)

    def test_step_helpers(self, timer):
        timer.step_start()
        time.sleep(0.005)
        timer.step_end(step=12)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{timer.metrics_port}/metrics", timeout=10
        ).read().decode()
        assert "XPU_TIMER_GLOBAL_STEP 12" in body
        assert 'XPU_TIMER_KERNEL_COUNT{name="train_step"}' in body


class TestHangDiagnostics:
    """The VERDICT #3 drill: an injected stuck collective must produce
    'stuck in <span> for Ns' + a stack file + a job-level verdict."""

    def test_inflight_span_tracking(self, timer):
        assert timer.stuck_span() is None or timer.stuck_span()[1] < 60
        with timer.span("outer_op"):
            spans = timer.current_spans()
            assert [s[0] for s in spans if s[0] == "outer_op"]
        assert all(s[0] != "outer_op" for s in timer.current_spans())

    def test_stuck_collective_drill(self, tmp_path):
        import threading

        from dlrover_tpu.agent.monitor import WorkerMonitor

        t = ExecutionTimer(metrics_port=0, hang_timeout_secs=0.3)
        t.record("warmup", t.now_ns(), 1000, t.KIND_STEP)  # instrumented
        release = threading.Event()

        def stuck_worker():
            with t.span("fake_psum_collective", t.KIND_COLLECTIVE):
                release.wait(30)

        th = threading.Thread(target=stuck_worker, daemon=True)
        th.start()
        time.sleep(0.8)  # exceed the watchdog window with the span open

        class FakeClient:
            def __init__(self):
                self.hangs = []

            def report_hang(self, **kw):
                self.hangs.append(kw)
                return True

            def report_resource_stats(self, **kw):
                return True

        client = FakeClient()
        mon = WorkerMonitor(
            client=client, timer=t, artifact_dir=str(tmp_path)
        )
        try:
            assert t.hang_detected()
            mon._report_once()
            assert len(client.hangs) == 1
            detail = client.hangs[0]["detail"]
            assert "fake_psum_collective" in detail
            assert "stuck in span" in detail
            stack_files = list(tmp_path.glob("hang_stacks_*.txt"))
            assert stack_files, "no stack dump written"
            content = stack_files[0].read_text()
            assert "fake_psum_collective" in content
            assert "stuck_worker" in content  # the hung thread's frame
            timeline_files = list(tmp_path.glob("hang_timeline_*.json"))
            assert timeline_files, "no timeline written"
            json.loads(timeline_files[0].read_text())
            # repeated polls while still hung must not re-report
            mon._report_once()
            assert len(client.hangs) == 1
        finally:
            release.set()
            th.join(5)
            t.shutdown()

    def test_master_hang_verdict_names_first_stalled_node(self):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager

        actions = []
        mgr = DiagnosisManager(sink=actions.append)

        class Report:
            def __init__(self, node_id, last_active_ts, detail):
                self.hung = True
                self.node_id = node_id
                self.last_active_ts = last_active_ts
                self.detail = detail

        now = time.time()
        # node 2 stalled first; nodes 0/1 wedged later waiting on it
        mgr.report_hang(Report(0, now - 30, "stuck in span 'psum' for 30s"))
        mgr.report_hang(
            Report(2, now - 300, "stuck in span 'ckpt_replica_exchange'")
        )
        mgr.report_hang(Report(1, now - 40, "stuck in span 'psum' for 40s"))
        verdict = mgr.hang_verdict()
        assert verdict["culprit"] == 2
        assert sorted(verdict["hung_nodes"]) == [0, 1, 2]
        assert "node 2 stalled first" in verdict["summary"]
        assert "ckpt_replica_exchange" in verdict["summary"]
        # one incident -> ONE restart action despite three reports
        assert len(actions) == 1
        # recovery clears the node from the verdict
        recovered = Report(2, now, "")
        recovered.hung = False
        mgr.report_hang(recovered)
        assert 2 not in mgr.hang_verdict()["hung_nodes"]

    def test_ckpt_spans_recorded(self, tmp_path):
        """save_to_memory's spans (``flash.save`` and, around the
        device->host copy and the shm write, ``flash.stage``) feed the
        process timer as KIND_CKPT records when they close."""
        import uuid

        import jax

        from dlrover_tpu.timer.core import get_timer
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            CheckpointEngine,
        )

        t = get_timer()
        eng = CheckpointEngine(
            str(tmp_path), process_id=0, num_processes=1,
            scope=f"t{uuid.uuid4().hex[:8]}",
        )
        try:
            state = {"w": jax.numpy.arange(8, dtype=jax.numpy.float32)}
            eng.save_to_memory(1, state)
            tl = tmp_path / "tl.json"
            assert t.dump_timeline(str(tl))
            names = {
                e["name"] for e in json.loads(tl.read_text())["traceEvents"]
            }
            assert "flash.save" in names
            assert "flash.stage" in names
        finally:
            eng.close() if hasattr(eng, "close") else None


class TestTrainerIntegration:
    def test_trainer_records_steps(self):
        import jax
        import numpy as np
        import optax

        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer.train import Trainer

        timer = ExecutionTimer(metrics_port=-1, hang_timeout_secs=600, allow_build=True)
        mesh = build_mesh(MeshConfig(dp=8))
        cfg = LlamaConfig.tiny()
        trainer = Trainer(
            LlamaForCausalLM(cfg), optax.adamw(1e-2), mesh, timer=timer
        )
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 17))
        batch = {
            "input_ids": np.asarray(ids[:, :-1], np.int32),
            "labels": np.asarray(ids[:, 1:], np.int32),
        }
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        for _ in range(3):
            state, _ = trainer.train_step(state, batch)
        # between-call timing records n-1 steps
        assert not timer.hang_detected()


class TestHangFixRegressions:
    def test_nested_spans_keep_outer_inflight(self):
        t = ExecutionTimer(metrics_port=0, hang_timeout_secs=60)
        try:
            with t.span("outer"):
                with t.span("inner"):
                    names = [s[0] for s in t.current_spans()]
                    assert "outer" in names and "inner" in names
                # inner closed: outer must STILL be tracked
                names = [s[0] for s in t.current_spans()]
                assert "outer" in names and "inner" not in names
            assert not t.current_spans()
        finally:
            t.shutdown()

    def test_monitor_reports_recovery(self, tmp_path):
        from dlrover_tpu.agent.monitor import WorkerMonitor

        t = ExecutionTimer(metrics_port=0, hang_timeout_secs=0.2)
        t.record("warmup", t.now_ns(), 1000, t.KIND_STEP)

        class FakeClient:
            def __init__(self):
                self.hangs = []

            def report_hang(self, **kw):
                self.hangs.append(kw)
                return True

            def report_resource_stats(self, **kw):
                return True

        client = FakeClient()
        mon = WorkerMonitor(client=client, timer=t,
                            artifact_dir=str(tmp_path))
        try:
            time.sleep(0.5)
            mon._report_once()  # hang
            assert client.hangs[-1]["hung"] is True
            t.kick()  # activity resumes
            mon._report_once()  # recovery
            assert client.hangs[-1]["hung"] is False
            assert client.hangs[-1]["detail"] == "recovered"
            assert len(client.hangs) == 2
        finally:
            t.shutdown()
