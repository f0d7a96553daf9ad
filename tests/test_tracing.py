"""Distributed tracing + RED metrics + timeline assembler tests.

Covers the PR-5 observability stack end to end: context propagation
through a REAL servicer round-trip (client span -> per-attempt child ->
server span -> kv server span), retry/breaker/chaos span events, the
Prometheus RED page on the master dashboard, and the merged Perfetto
timeline (3-process synthetic run: connected span trees, flow arrows
across pids, byte-stable output for a fixed seed)."""

import json
import threading
import urllib.request

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.observability import metrics, timeline, trace


@pytest.fixture(autouse=True)
def _isolate():
    """Every test sees a fresh registry, sink, id stream, and a
    disarmed chaos engine."""
    records = []
    trace.set_span_sink(records.append)
    trace.seed_ids(1234)
    metrics.registry().reset()
    yield records
    trace.set_span_sink(None)
    trace.seed_ids(0)
    chaos.clear()
    metrics.registry().reset()


def _client_and_servicer():
    from dlrover_tpu.agent.master_client import LocalMasterClient
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    return LocalMasterClient(servicer, node_id=3), servicer


class TestTraceContext:
    def test_span_nesting_and_parentage(self, _isolate):
        with trace.span("outer") as outer:
            assert trace.current_span() is outer
            with trace.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_span_id == outer.span_id
            assert trace.current_span() is outer
        assert trace.current_span() is None
        names = [r["name"] for r in _isolate]
        assert names == ["inner", "outer"]  # children export first

    def test_traceparent_roundtrip(self):
        with trace.span("op") as sp:
            header = trace.current_traceparent()
            ctx = trace.parse_traceparent(header)
            assert ctx is not None
            assert ctx.trace_id == sp.trace_id
            assert ctx.span_id == sp.span_id
            assert ctx.sampled

    @pytest.mark.parametrize("bad", [
        "", "junk", "00-short-abc-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
        "00-" + "g" * 32 + "-" + "1" * 16 + "-01",  # non-hex
    ])
    def test_invalid_traceparent_rejected(self, bad):
        assert trace.parse_traceparent(bad) is None

    def test_server_span_adopts_remote_context(self, _isolate):
        remote = trace.TraceContext(trace_id="ab" * 16, span_id="cd" * 8)
        with trace.server_span("srv", remote.traceparent()) as sp:
            assert sp.trace_id == remote.trace_id
            assert sp.parent_span_id == remote.span_id
            assert sp.kind == trace.SERVER

    def test_server_span_without_header_is_root(self):
        with trace.server_span("srv", "") as sp:
            assert sp.parent_span_id == ""
            assert len(sp.trace_id) == 32

    def test_exception_marks_span_error(self, _isolate):
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("nope")
        record = _isolate[-1]
        assert record["status"] == "error"
        assert "nope" in record["error"]

    def test_seeded_ids_deterministic(self):
        trace.seed_ids(42)
        a = (trace.new_trace_id(), trace.new_span_id())
        trace.seed_ids(42)
        b = (trace.new_trace_id(), trace.new_span_id())
        assert a == b

    def test_disabled_tracing_is_noop(self, _isolate, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_TRACE", "0")
        trace.seed_ids(1234)  # the switch is read once; this re-reads it
        with trace.span("x") as sp:
            assert sp is trace.NOOP_SPAN
            assert trace.current_traceparent() == ""
        assert _isolate == []

    def test_threads_do_not_share_context(self):
        seen = {}

        def worker():
            seen["span"] = trace.current_span()

        with trace.span("main_only"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["span"] is None

    def test_event_cap_bounds_span_growth(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_TRACE_MAX_EVENTS", "5")
        with trace.span("storm") as sp:
            for i in range(50):
                sp.add_event("retry", n=i)
        assert len(sp.events) == 5


class TestMetricsRegistry:
    def test_counter_and_gauge_render(self):
        reg = metrics.registry()
        reg.counter_inc("c_total", help="a counter", kind="x")
        reg.counter_inc("c_total", kind="x")
        reg.gauge_set("g", 2.5)
        page = reg.render()
        assert '# TYPE c_total counter' in page
        assert 'c_total{kind="x"} 2' in page
        assert "g 2.5" in page

    def test_histogram_buckets_cumulative(self):
        reg = metrics.registry()
        for v in (0.003, 0.003, 0.2, 99.0):
            reg.observe("h_seconds", v, m="a")
        page = reg.render()
        assert 'h_seconds_bucket{m="a",le="0.005"} 2' in page
        assert 'h_seconds_bucket{m="a",le="0.25"} 3' in page
        assert 'h_seconds_bucket{m="a",le="+Inf"} 4' in page
        assert 'h_seconds_count{m="a"} 4' in page
        stats = reg.histogram_stats("h_seconds", m="a")
        assert stats["count"] == 4

    def test_cardinality_guard_drops_series(self):
        reg = metrics.MetricsRegistry(max_series=3)
        for i in range(10):
            reg.counter_inc("c", key=str(i))
        page = reg.render()
        assert "dlrover_tpu_metrics_dropped_series_total 7" in page
        # admitted series keep counting
        reg.counter_inc("c", key="0")
        assert reg.counter_value("c", key="0") == 2

    def test_snapshot_shape(self):
        reg = metrics.registry()
        metrics.observe_rpc("X", True, 0.01)
        snap = reg.snapshot()
        assert "dlrover_tpu_rpc_requests_total" in snap["counters"]
        hist = snap["histograms"]["dlrover_tpu_rpc_duration_seconds"]
        only = next(iter(hist.values()))
        assert only["count"] == 1 and only["avg"] > 0


class TestServicerRoundTrip:
    """Acceptance: a real servicer round-trip produces linked client/
    server spans AND per-RPC RED histograms."""

    def test_client_server_span_chain(self, _isolate):
        client, _ = _client_and_servicer()
        assert client.kv_store_set("k", b"v")
        assert client.kv_store_get("k") == b"v"
        by_name = {}
        for record in _isolate:
            by_name.setdefault(record["name"], []).append(record)
        attempt = by_name["rpc.attempt/KVStoreGetRequest"][0]
        logical = by_name["rpc.get/KVStoreGetRequest"][0]
        server = by_name["master.get/KVStoreGetRequest"][0]
        kv_client = by_name["kv.get"][0]
        kv_server = by_name["kv_server.get"][0]
        # one trace end to end
        assert (
            kv_client["trace_id"] == logical["trace_id"]
            == attempt["trace_id"] == server["trace_id"]
            == kv_server["trace_id"]
        )
        # kv.get -> rpc.get -> rpc.attempt -> master.get -> kv_server.get
        assert logical["parent_span_id"] == kv_client["span_id"]
        assert attempt["parent_span_id"] == logical["span_id"]
        assert server["parent_span_id"] == attempt["span_id"]
        assert kv_server["parent_span_id"] == server["span_id"]
        assert server["kind"] == trace.SERVER
        forest = timeline.span_forest(_isolate)
        assert all(t["connected"] for t in forest.values())

    def test_red_metrics_from_round_trip(self, _isolate):
        client, _ = _client_and_servicer()
        client.kv_store_set("k", b"v")
        client.kv_store_get("k")
        client.barrier("b", notify=True)
        reg = metrics.registry()
        for method in (
            "KVStoreGetRequest", "KeyValuePair", "SyncBarrierRequest"
        ):
            assert reg.counter_value(
                "dlrover_tpu_rpc_requests_total",
                method=method, code="ok", transport="master",
            ) >= 1, method
            assert reg.histogram_stats(
                "dlrover_tpu_rpc_duration_seconds",
                method=method, transport="master",
            )["count"] >= 1, method
        page = reg.render()
        assert 'dlrover_tpu_rpc_duration_seconds_bucket' in page

    def test_server_error_counted_as_error(self, _isolate):
        client, servicer = _client_and_servicer()
        # unknown rendezvous name -> dispatch raises -> error code
        client.join_rendezvous(0, 0, rdzv_name="nope")
        assert metrics.registry().counter_value(
            "dlrover_tpu_rpc_requests_total",
            method="JoinRendezvousRequest", code="error",
            transport="master",
        ) == 1

    def test_envelope_carries_traceparent(self):
        from dlrover_tpu.common import comm

        client, _ = _client_and_servicer()
        captured = {}
        original = client._servicer.get

        def spy(envelope):
            captured["trace_ctx"] = envelope.trace_ctx
            return original(envelope)

        client._servicer.get = spy
        client.kv_store_get("k")
        ctx = trace.parse_traceparent(captured["trace_ctx"])
        assert ctx is not None and ctx.sampled


class TestRetryAndChaosAttribution:
    def test_retry_events_land_on_call_span(self, _isolate):
        client, _ = _client_and_servicer()
        chaos.configure(chaos.ChaosPlan(
            name="t", seed=7,
            faults=[chaos.FaultSpec(
                point="master_client.transport", kind=chaos.EXCEPTION,
                on_calls=[0], times=1,
            )],
        ))
        assert client.kv_store_get("k") == b""  # recovered on retry
        logical = next(
            r for r in _isolate if r["name"] == "rpc.get/KVStoreGetRequest"
        )
        events = [e["name"] for e in logical["events"]]
        assert "retry.attempt_failed" in events
        failed_attempt = next(
            r for r in _isolate
            if r["name"] == "rpc.attempt/KVStoreGetRequest"
            and r["status"] == "error"
        )
        assert any(
            e["name"] == "chaos.fault" for e in failed_attempt["events"]
        )
        assert metrics.registry().counter_value(
            "dlrover_tpu_retry_total",
            policy="master_rpc[worker:3]", outcome="attempt_failed",
        ) == 1

    def test_chaos_record_carries_span_ids(self, _isolate):
        client, _ = _client_and_servicer()
        chaos.configure(chaos.ChaosPlan(
            name="t", seed=7,
            faults=[chaos.FaultSpec(
                point="kv_server.get", kind=chaos.DROP, times=1,
            )],
        ))
        client.kv_store_get("k")
        record = chaos.trace()[0]
        assert record["span_id"] and record["trace_id"]
        owner = next(
            r for r in _isolate if r["span_id"] == record["span_id"]
        )
        assert owner["name"] == "kv_server.get"
        assert metrics.registry().counter_value(
            "dlrover_tpu_chaos_faults_total",
            point="kv_server.get", kind="drop",
        ) == 1

    def test_chaos_record_empty_ids_without_span(self):
        chaos.configure(chaos.ChaosPlan(
            name="t", seed=7,
            faults=[chaos.FaultSpec(point="bare.point", times=1)],
        ))
        with pytest.raises(chaos.ChaosError):
            chaos.point("bare.point")
        record = chaos.trace()[0]
        assert record["span_id"] == "" and record["trace_id"] == ""


class TestEmitterStamping:
    def test_events_stamped_with_live_span(self):
        from dlrover_tpu.training_event.emitter import (
            MemoryExporter, Process,
        )

        exporter = MemoryExporter()
        process = Process("tester", exporter)
        with trace.span("op") as sp:
            process.instant("inside", {"a": 1})
        process.instant("outside")
        inside, outside = exporter.events
        assert inside["trace_id"] == sp.trace_id
        assert inside["span_id"] == sp.span_id
        assert outside["trace_id"] == "" and outside["span_id"] == ""


class TestDashboardMetricsEndpoint:
    def test_metrics_endpoint_serves_prometheus_text(self, _isolate):
        from dlrover_tpu.master.dashboard import DashboardServer
        from dlrover_tpu.master.local_master import LocalJobMaster

        client, _ = _client_and_servicer()
        client.kv_store_set("k", b"v")
        master = LocalJobMaster(node_num=1)
        server = DashboardServer(master, port=0)
        server.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=10
            ).read().decode()
        finally:
            server.stop()
        assert "dlrover_tpu_rpc_requests_total" in body
        assert "dlrover_tpu_rpc_duration_seconds_bucket" in body
        assert "dlrover_tpu_goodput" in body
        assert "dlrover_tpu_global_step" in body


class TestTimelineAssembler:
    """Satellite: merge a synthetic 3-process run and assert the span
    forest, cross-pid flow arrows, and seed-stable output."""

    def _synthetic_run(self, tmp_path):
        """agent + master + trainer processes sharing one trace, plus a
        timer chrome trace and a chaos trace attributed to the agent's
        attempt span."""
        trace.seed_ids(99)
        trace_id = trace.new_trace_id()
        root, attempt, server, kv = (trace.new_span_id() for _ in range(4))

        def span_record(name, span_id, parent, ts, dur, target, pid,
                        events=()):
            return {
                "ts": ts, "dur": dur, "name": name, "type": "SPAN",
                "kind": "internal", "trace_id": trace_id,
                "span_id": span_id, "parent_span_id": parent,
                "status": "ok", "attrs": {}, "events": list(events),
                "target": target, "pid": pid,
            }

        agent = [
            span_record("rpc.get/X", root, "", 100.0, 1.0, "agent", 11),
            span_record(
                "rpc.attempt/X", attempt, root, 100.1, 0.8, "agent", 11,
                events=[{
                    "ts": 100.2, "name": "chaos.fault",
                    "attrs": {"point": "master_client.transport",
                              "kind": "delay", "seq": 0},
                }],
            ),
            {
                "ts": 100.05, "target": "agent", "pid": 11,
                "name": "agent.worker.start", "type": "INSTANT",
                "span": "", "content": {},
                "trace_id": trace_id, "span_id": root,
                "parent_span_id": "",
            },
        ]
        master = [
            span_record(
                "master.get/X", server, attempt, 100.3, 0.4, "master", 22
            ),
            span_record(
                "kv_server.get", kv, server, 100.35, 0.1, "master", 22
            ),
        ]
        trainer = [
            {
                "ts": 100.0, "target": "trainer", "pid": 33,
                "name": "trainer.step", "type": "BEGIN", "span": "s1",
                "content": {"step": 1},
                "trace_id": "", "span_id": "", "parent_span_id": "",
            },
            {
                "ts": 101.5, "target": "trainer", "pid": 33,
                "name": "trainer.step", "type": "END", "span": "s1",
                "content": {}, "trace_id": "", "span_id": "",
                "parent_span_id": "",
            },
        ]
        paths = {}
        for label, records in (
            ("agent", agent), ("master", master), ("trainer", trainer)
        ):
            path = tmp_path / f"events_{label}.jsonl"
            path.write_text(
                "\n".join(json.dumps(r) for r in records) + "\n"
            )
            paths[label] = str(path)
        timer_path = tmp_path / "timer.json"
        timer_path.write_text(json.dumps({
            "traceEvents": [{
                "name": "train_step", "ph": "X", "ts": 100.0e6,
                "dur": 0.5e6, "pid": 0, "tid": 1, "cat": "tpu",
            }]
        }))
        chaos_path = tmp_path / "chaos.jsonl"
        chaos_path.write_text(json.dumps({
            "seq": 0, "point": "master_client.transport", "kind": "delay",
            "call": 0, "trace_id": trace_id, "span_id": attempt,
        }) + "\n" + json.dumps({
            "seq": 1, "point": "orphan.point", "kind": "drop", "call": 3,
            "trace_id": "", "span_id": "",
        }) + "\n")
        return paths, str(timer_path), str(chaos_path), {
            "trace_id": trace_id, "attempt": attempt, "server": server,
        }

    def test_merged_timeline_connected_with_flows(self, tmp_path):
        paths, timer_path, chaos_path, ids = self._synthetic_run(tmp_path)
        merged = timeline.assemble(
            event_files=paths.values(), timer_files=[timer_path],
            chaos_files=[chaos_path],
        )
        summary = merged["summary"]
        # one connected span tree for the trace
        forest = summary["span_forest"][ids["trace_id"]]
        assert forest["connected"] and forest["spans"] == 4
        assert forest["orphans"] == []
        # flow arrows cross the agent->master pid boundary
        events = merged["traceEvents"]
        starts = [e for e in events if e.get("ph") == "s"]
        finishes = [e for e in events if e.get("ph") == "f"]
        assert summary["flows"] >= 1
        assert any(e["id"] == ids["server"] for e in starts)
        assert any(e["id"] == ids["server"] for e in finishes)
        flow_s = next(e for e in starts if e["id"] == ids["server"])
        flow_f = next(e for e in finishes if e["id"] == ids["server"])
        assert flow_s["pid"] != flow_f["pid"]
        # lanes: agent, master, trainer (+ timer + chaos)
        lane_names = {
            e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert {"agent:11", "master:22", "trainer:33"} <= lane_names
        # attributed chaos fault sits in the agent lane at the span
        # event's timestamp; the orphan goes to the chaos lane
        chaos_events = [e for e in events if e.get("cat") == "chaos"]
        assert summary["chaos_attributed"] == 1
        attributed = next(
            e for e in chaos_events
            if e["args"]["span_id"] == ids["attempt"]
        )
        assert attributed["ts"] == pytest.approx(100.2e6)
        assert any(
            e["args"]["point"] == "orphan.point" for e in chaos_events
        )
        # trainer BEGIN/END became one slice
        assert any(
            e.get("name") == "trainer.step" and e.get("ph") == "X"
            and e.get("dur") == pytest.approx(1.5e6)
            for e in events
        )

    def test_output_stable_for_fixed_seed(self, tmp_path, capsys):
        paths, timer_path, chaos_path, _ = self._synthetic_run(tmp_path)
        out_a = tmp_path / "a.json"
        out_b = tmp_path / "b.json"
        argv = [
            "--events", *paths.values(), "--timer", timer_path,
            "--chaos", chaos_path,
        ]
        assert timeline.main(argv + ["-o", str(out_a)]) == 0
        assert timeline.main(argv + ["-o", str(out_b)]) == 0
        assert out_a.read_bytes() == out_b.read_bytes()

    def test_cli_requires_inputs(self):
        with pytest.raises(SystemExit):
            timeline.main(["-o", "/tmp/x.json"])


class TestTraceSmoke:
    def test_smoke_green(self, tmp_path):
        from dlrover_tpu.observability import trace_smoke

        result = trace_smoke.run_smoke(str(tmp_path))
        assert result["ok"], result["checks"]


class TestDaemonFoldsMasterPage:
    def test_extra_target_relabeled(self, _isolate):
        from dlrover_tpu.master.dashboard import DashboardServer
        from dlrover_tpu.master.local_master import LocalJobMaster
        from dlrover_tpu.timer.daemon import TimerDaemon

        client, _ = _client_and_servicer()
        client.kv_store_set("k", b"v")
        dashboard = DashboardServer(LocalJobMaster(node_num=1), port=0)
        dashboard.start()
        daemon = TimerDaemon(
            [], port=0,
            extra_targets={
                "master": f"http://127.0.0.1:{dashboard.port}/metrics"
            },
        )
        # stop() blocks unless the serve loop is running
        daemon.start()
        try:
            page = daemon.metrics_page()
        finally:
            daemon.stop()
            dashboard.stop()
        assert 'XPU_TIMER_WORKER_UP{worker="master"} 1' in page
        assert 'worker="master"' in page
        assert "dlrover_tpu_rpc_requests_total" in page
