"""Comm observatory tests: FabricModel, MeshProbe (synthetic + real
mesh), BucketScope per-bucket attribution, the digest -> agent ->
time-series -> slow-link-sentinel -> incident pipeline, and the
dashboard /comm view."""

import json
import os
import time
import urllib.request
from types import SimpleNamespace

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.master.timeseries import TimeSeriesStore
from dlrover_tpu.observability import commscope
from dlrover_tpu.observability.sentinel import SlowLinkDiagnostician


@pytest.fixture(autouse=True)
def _clean():
    chaos.clear()
    commscope.reset_scope()
    yield
    chaos.clear()
    commscope.reset_scope()


def _env(monkeypatch, **overrides):
    for key, value in overrides.items():
        monkeypatch.setenv(key, value)


# ---------------------------------------------------------------------------
# FabricModel
# ---------------------------------------------------------------------------


class TestFabricModel:
    def test_update_and_snapshot(self):
        model = commscope.FabricModel(alpha=1.0)
        model.update("dp", 4, 0.001, 2.5)
        snap = model.snapshot()
        assert snap["dp"]["world"] == 4
        assert snap["dp"]["lat_us"] == pytest.approx(1000.0)
        assert snap["dp"]["gbps"] == pytest.approx(2.5)
        assert snap["dp"]["samples"] == 1

    def test_ewma_smoothing(self):
        model = commscope.FabricModel(alpha=0.5)
        model.update("dp", 2, 0.001, 1.0)
        model.update("dp", 2, 0.003, 3.0)
        entry = model.get("dp")
        assert entry["lat_us"] == pytest.approx(2000.0)
        assert entry["gbps"] == pytest.approx(2.0)

    def test_digest_keys_roundtrip(self):
        model = commscope.FabricModel(alpha=1.0)
        model.update("dp", 2, 0.002, 1.5)
        model.update("fsdp", 4, 0.0001, 9.0)
        digest = model.digest()
        assert digest["fxl_dp"] == pytest.approx(2000.0)
        assert digest["fxb_fsdp"] == pytest.approx(9.0)
        assert commscope.digest_axes(digest) == ["dp", "fsdp"]

    def test_invalid_alpha_falls_back(self):
        model = commscope.FabricModel(alpha=7.0)
        model.update("dp", 2, 0.001, 1.0)
        assert model.get("dp") is not None


# ---------------------------------------------------------------------------
# MeshProbe (synthetic runner — no devices)
# ---------------------------------------------------------------------------


class TestMeshProbe:
    def test_probe_feeds_model_per_axis(self):
        model = commscope.FabricModel(alpha=1.0)
        probe = commscope.MeshProbe(
            {"dp": 2, "fsdp": 4}, runner=lambda a, k: None, reps=2
        )
        out = probe.probe_once(model)
        assert sorted(out) == ["dp", "fsdp"]
        assert model.get("dp")["world"] == 2
        assert model.get("fsdp")["world"] == 4
        assert probe.probes_done == 1

    def test_trivial_axes_are_skipped(self):
        probe = commscope.MeshProbe(
            {"dp": 1, "tp": 1, "cp": 2}, runner=lambda a, k: None
        )
        assert sorted(probe.axes) == ["cp"]

    def test_probe_defaults_to_process_scope_fabric(self):
        probe = commscope.MeshProbe(
            {"dp": 2}, runner=lambda a, k: None, reps=1
        )
        probe.probe_once()
        assert commscope.scope().fabric.get("dp") is not None

    def test_injected_axis_delay_prices_one_axis(self):
        chaos.configure(chaos.ChaosPlan(
            name="t", seed=3,
            faults=[chaos.FaultSpec(
                point="comm.axis_delay.dp", kind=chaos.DELAY,
                # 0.15 s against a 0.5 ms runner: the 10x margin below
                # then survives a 0.5 ms sleep stretching to 15 ms on a
                # host busy with five other test workers (at 0.03 s it
                # failed there: host-clock noise, no JAX involved)
                delay_s=0.15,
            )],
        ))
        model = commscope.FabricModel(alpha=1.0)
        probe = commscope.MeshProbe(
            {"dp": 2, "fsdp": 2},
            runner=lambda a, k: time.sleep(0.0005), reps=2,
        )
        probe.probe_once(model)
        snap = model.snapshot()
        assert snap["dp"]["lat_us"] > 10 * snap["fsdp"]["lat_us"]
        delays = [r for r in chaos.trace() if r["kind"] == chaos.DELAY]
        assert delays and all(
            r["point"] == "comm.axis_delay.dp" for r in delays
        )

    def test_probe_spans_reach_flight_recorder(self):
        from dlrover_tpu.observability import flight_recorder

        flight_recorder.recorder().reset()
        probe = commscope.MeshProbe(
            {"dp": 2}, runner=lambda a, k: None, reps=1
        )
        probe.probe_once(commscope.FabricModel(alpha=1.0))
        spans = flight_recorder.recorder().snapshot(stacks=False)["spans"]
        names = [s.get("name") for s in spans]
        assert "comm.probe.dp" in names
        attrs = next(
            s["attrs"] for s in spans if s["name"] == "comm.probe.dp"
        )
        assert "lat_us" in attrs and "gbps" in attrs

    def test_probe_gauges_recorded(self):
        from dlrover_tpu.observability import metrics as obs_metrics

        probe = commscope.MeshProbe(
            {"ep": 2}, runner=lambda a, k: None, reps=1
        )
        probe.probe_once(commscope.FabricModel(alpha=1.0))
        assert obs_metrics.registry().gauge_value(
            "dlrover_tpu_comm_probe_latency_us", axis="ep"
        ) is not None


# ---------------------------------------------------------------------------
# Real-mesh probe + per-bucket attribution (virtual CPU devices)
# ---------------------------------------------------------------------------


def _tiny_bucketed_trainer(n_devices=4):
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel.collectives import GradSyncPolicy
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.train import Trainer

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    import jax

    mesh = build_mesh(
        MeshConfig(dp=n_devices), devices=jax.devices()[:n_devices]
    )
    trainer = Trainer(
        model, optax.adamw(1e-2), mesh,
        grad_sync=GradSyncPolicy(mode="int8_sharded", bucket_mb=1.0),
    )
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(n_devices, 17))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    return trainer, state, batch


class TestRealMeshProbe:
    def test_for_mesh_probes_active_axes(self):
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        probe = commscope.MeshProbe.for_mesh(
            mesh, bw_bytes=1 << 14, reps=1
        )
        assert sorted(probe.axes) == ["dp", "fsdp"]
        model = commscope.FabricModel(alpha=1.0)
        out = probe.probe_once(model)
        assert out["dp"]["lat_s"] > 0
        assert out["fsdp"]["gbps"] > 0

    def test_bandwidth_accounting_uses_actual_payload(self):
        # the probe floors its psum payload at 256 elems; the GB/s
        # accounting must price the ACTUAL bytes, not the raw knob
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
        probe = commscope.MeshProbe.for_mesh(
            mesh, bw_bytes=100, reps=1
        )
        probe.probe_once(commscope.FabricModel(alpha=1.0))
        assert probe._bw_bytes == 4 * 256  # noqa: SLF001

    def test_for_mesh_none_when_all_axes_trivial(self):
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        assert commscope.MeshProbe.for_mesh(mesh) is None


class TestBucketScope:
    def test_measure_emits_attributed_rows(self):
        trainer, state, batch = _tiny_bucketed_trainer()
        scope = commscope.BucketScope.for_trainer(trainer)
        assert scope is not None
        rows = scope.measure(reps=1)
        assert rows, "bucketed trainer must yield at least one bucket"
        for row in rows:
            assert row["axis"] == "dp"
            assert row["transport"] == "all_to_all"  # quantized bucket
            assert row["wire_bytes"] > 0
            assert row["chain_ms"] > 0
            assert row["gbps"] > 0
            assert row["leaves"] >= 1

    def test_bucket_spans_carry_transport_and_bytes(self):
        from dlrover_tpu.observability import flight_recorder

        trainer, state, batch = _tiny_bucketed_trainer()
        scope = commscope.BucketScope.for_trainer(trainer)
        flight_recorder.recorder().reset()
        scope.measure(reps=1)
        spans = flight_recorder.recorder().snapshot(stacks=False)["spans"]
        bucket_spans = [
            s for s in spans
            if str(s.get("name", "")).startswith("comm.bucket")
        ]
        assert bucket_spans
        attrs = bucket_spans[0]["attrs"]
        for key in ("axis", "transport", "wire_bytes", "gbps", "chain_ms"):
            assert key in attrs, attrs

    def test_for_trainer_none_on_exact_policy(self):
        import jax
        import optax

        from dlrover_tpu.models.llama import (
            LlamaConfig,
            LlamaForCausalLM,
        )
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer.train import Trainer

        mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
        trainer = Trainer(
            LlamaForCausalLM(LlamaConfig.tiny()), optax.adamw(1e-2),
            mesh, grad_sync="exact",
        )
        assert commscope.BucketScope.for_trainer(trainer) is None


# ---------------------------------------------------------------------------
# CommScope: the exposed_comm sub-account
# ---------------------------------------------------------------------------


class TestCommScope:
    def test_exposed_breakdown_books_by_transport_axis(self):
        scope = commscope.reset_scope()
        scope.attribute_exposed("dp", "psum_scatter", 0.4)
        scope.attribute_exposed("dp", "psum_scatter", 0.1)
        scope.attribute_exposed("dp", "ring", 0.5)
        breakdown = scope.exposed_breakdown()
        assert breakdown["total_s"] == pytest.approx(1.0)
        assert breakdown["by"]["psum_scatter/dp"] == pytest.approx(0.5)
        assert breakdown["share"]["ring/dp"] == pytest.approx(0.5)

    def test_exposed_charges_goodput_ledger(self, monkeypatch):
        from dlrover_tpu.observability import goodput

        _env(monkeypatch, DLROVER_TPU_GOODPUT_RES_S="0.05")
        ledger = goodput.reset_ledger()
        try:
            scope = commscope.reset_scope()
            scope.attribute_exposed("dp", "ring", 0.3)
            summary = ledger.summary()
            assert summary["phases"]["exposed_comm"] > 0
        finally:
            goodput.reset_ledger()

    def test_exposed_counter_recorded(self):
        from dlrover_tpu.observability import metrics as obs_metrics

        scope = commscope.reset_scope()
        scope.attribute_exposed("cp", "ring_pallas", 0.25)
        total = obs_metrics.registry().counter_total(
            "dlrover_tpu_comm_exposed_seconds_total"
        )
        assert total >= 0.25

    def test_nonpositive_duration_ignored(self):
        scope = commscope.reset_scope()
        scope.attribute_exposed("dp", "ring", 0.0)
        scope.attribute_exposed("dp", "ring", -1.0)
        assert scope.exposed_breakdown()["total_s"] == 0.0

    def test_summary_shape(self):
        scope = commscope.reset_scope()
        scope.fabric.update("dp", 2, 0.001, 1.0)
        scope.attribute_exposed("dp", "ring", 0.2)
        summary = scope.summary()
        assert "dp" in summary["fabric"]
        assert summary["exposed_comm"]["total_s"] > 0


# ---------------------------------------------------------------------------
# Master time-series: comm series + worst-case rollups
# ---------------------------------------------------------------------------


def _fx(lat_dp, bw_dp, lat_fsdp=2.0, bw_fsdp=3.0):
    return {
        "fxl_dp": lat_dp, "fxb_dp": bw_dp,
        "fxl_fsdp": lat_fsdp, "fxb_fsdp": bw_fsdp,
    }


class TestTimeSeriesCommFeeds:
    def test_node_and_job_series_recorded(self):
        store = TimeSeriesStore()
        now = time.time()
        store.record_digest(0, _fx(5.0, 2.0), ts=now - 2)
        store.record_digest(0, _fx(6.0, 2.1), ts=now - 1)
        names = store.names()
        assert "node0.comm.dp.lat_us" in names
        assert "node0.comm.fsdp.gbps" in names
        assert "job.comm.dp.lat_us" in names
        assert store.latest("job.comm.dp.lat_us") == pytest.approx(6.0)

    def test_job_rollup_is_worst_case_across_nodes(self):
        store = TimeSeriesStore()
        now = time.time()
        store.record_digest(0, _fx(5.0, 4.0), ts=now - 2)
        store.record_digest(1, _fx(900.0, 0.5), ts=now - 1)
        # job latency = max across fresh nodes, bandwidth = min
        assert store.latest("job.comm.dp.lat_us") == pytest.approx(900.0)
        assert store.latest("job.comm.dp.gbps") == pytest.approx(0.5)

    def test_stale_node_leaves_rollup(self):
        store = TimeSeriesStore()
        now = time.time()
        from dlrover_tpu.master.timeseries import FRESH_S

        store.record_digest(1, _fx(900.0, 0.5), ts=now - FRESH_S - 60)
        store.record_digest(0, _fx(5.0, 4.0), ts=now)
        assert store.latest("job.comm.dp.lat_us") == pytest.approx(5.0)

    def test_comm_nodes_latest_view(self):
        store = TimeSeriesStore()
        now = time.time()
        store.record_digest(3, _fx(7.0, 1.5), ts=now)
        nodes = store.comm_nodes()
        assert nodes[3]["axes"]["dp"]["lat_us"] == pytest.approx(7.0)
        assert nodes[3]["axes"]["dp"]["gbps"] == pytest.approx(1.5)

    def test_evict_node_forgets_comm_baseline(self):
        store = TimeSeriesStore()
        store.record_digest(2, _fx(7.0, 1.5), ts=time.time())
        store.evict_node(2)
        assert 2 not in store.comm_nodes()

    def test_digest_without_fx_keys_unchanged(self):
        store = TimeSeriesStore()
        store.record_digest(0, {"step_p50_s": 0.5}, ts=time.time())
        assert not [
            n for n in store.names() if ".comm." in n
        ]


# ---------------------------------------------------------------------------
# Agent digest forwarding (worst-rank merge)
# ---------------------------------------------------------------------------


class TestAgentDigestForwarding:
    def test_collect_digest_merges_fx_worst_case(
        self, tmp_path, monkeypatch
    ):
        from dlrover_tpu.agent.elastic_agent import (
            ElasticAgent,
            ElasticLaunchConfig,
        )
        from dlrover_tpu.agent.master_client import LocalMasterClient
        from dlrover_tpu.master.servicer import MasterServicer

        base = str(tmp_path / "runtime_metrics.json")
        _env(monkeypatch, DLROVER_TPU_RUNTIME_METRICS_PATH=base)
        now = time.time()
        # two ranks: the node is as healthy as its slowest link, so
        # lat merges MAX and bandwidth merges MIN
        for rank, (lat, bw) in enumerate([(5.0, 4.0), (950.0, 0.25)]):
            with open(f"{base}.rank{rank}", "w") as f:
                json.dump({
                    "ts": now, "step_p50_s": 0.1, "last_step": 7,
                    "fxl_dp": lat, "fxb_dp": bw,
                }, f)
        client = LocalMasterClient(MasterServicer(), node_id=0)
        agent = ElasticAgent(client, ElasticLaunchConfig())
        digest = agent._collect_digest()  # noqa: SLF001
        assert digest["fxl_dp"] == pytest.approx(950.0)
        assert digest["fxb_dp"] == pytest.approx(0.25)

    def test_stale_rank_file_not_forwarded(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent.elastic_agent import (
            ElasticAgent,
            ElasticLaunchConfig,
        )
        from dlrover_tpu.agent.master_client import LocalMasterClient
        from dlrover_tpu.master.metric_context import DIGEST_FRESH_S
        from dlrover_tpu.master.servicer import MasterServicer

        base = str(tmp_path / "runtime_metrics.json")
        _env(monkeypatch, DLROVER_TPU_RUNTIME_METRICS_PATH=base)
        with open(f"{base}.rank0", "w") as f:
            json.dump({
                "ts": time.time() - DIGEST_FRESH_S - 60,
                "fxl_dp": 900.0, "fxb_dp": 0.1,
            }, f)
        client = LocalMasterClient(MasterServicer(), node_id=0)
        agent = ElasticAgent(client, ElasticLaunchConfig())
        digest = agent._collect_digest()  # noqa: SLF001
        assert "fxl_dp" not in digest


# ---------------------------------------------------------------------------
# SlowLinkDiagnostician
# ---------------------------------------------------------------------------


def _feed_rounds(store, n, node=0, degrade_from=None,
                 degraded_lat=9000.0):
    base = time.time() - n - 2
    for i in range(n):
        lat = (
            degraded_lat
            if degrade_from is not None and i >= degrade_from else 2.0
        )
        store.record_digest(node, _fx(lat, 3.0), ts=base + i)


class TestSlowLinkDiagnostician:
    def _manager(self, store, tmp_path, monkeypatch):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager
        from dlrover_tpu.observability.incidents import IncidentManager

        _env(
            monkeypatch,
            DLROVER_TPU_SENTINEL_MIN_SAMPLES="2",
            DLROVER_TPU_SENTINEL_CONSECUTIVE="1",
            DLROVER_TPU_INCIDENT_DIR=str(tmp_path / "incidents"),
            DLROVER_TPU_INCIDENT_COOLDOWN_S="0",
            DLROVER_TPU_INCIDENT_GRACE_S="0",
        )
        diagnosis = DiagnosisManager()
        incidents = IncidentManager()
        diagnosis.register(SlowLinkDiagnostician(store, res_s=1.0))
        diagnosis.set_incident_manager(incidents)
        return diagnosis, incidents

    def test_breach_opens_comm_incident_naming_axis(
        self, tmp_path, monkeypatch
    ):
        store = TimeSeriesStore()
        _feed_rounds(store, 10, degrade_from=5)
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        actions = diagnosis.diagnose_once()
        assert any(a.action_type == "event" for a in actions)
        opened = incidents.list_incidents()
        assert opened and opened[0]["kind"] == "slow_link"
        final = incidents.finalize(
            opened[0]["incident_id"], force=True
        )
        assert final["phase"] == "comm"
        assert "'dp'" in final["detail"]

    def test_culprit_is_worst_node_on_axis(self, tmp_path, monkeypatch):
        store = TimeSeriesStore()
        n = 10
        base = time.time() - n - 2
        for i in range(n):
            lat1 = 9000.0 if i >= 5 else 2.0
            store.record_digest(0, _fx(2.0, 3.0), ts=base + i)
            store.record_digest(1, _fx(lat1, 3.0), ts=base + i)
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        diagnosis.diagnose_once()
        opened = incidents.list_incidents()
        final = incidents.finalize(
            opened[0]["incident_id"], force=True
        )
        assert final["culprit_node"] == 1

    def test_quiet_fabric_never_fires(self, tmp_path, monkeypatch):
        store = TimeSeriesStore()
        _feed_rounds(store, 10)
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        assert diagnosis.diagnose_once() == []
        assert incidents.list_incidents() == []

    def test_each_bucket_consumed_once(self, tmp_path, monkeypatch):
        store = TimeSeriesStore()
        _feed_rounds(store, 10, degrade_from=5)
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        diagnosis.diagnose_once()
        # no new buckets -> no re-fire on the same evidence
        assert diagnosis.diagnose_once() == []

    def test_severity_prefers_degraded_axis(self):
        # a big latency breach must outvote a coincidental small one
        big = {"value": 9000.0, "baseline": 2.0}
        small = {"value": 2.6, "baseline": 2.0}
        assert (
            SlowLinkDiagnostician._severity(big)
            > SlowLinkDiagnostician._severity(small)
        )

    def test_concurrent_breaches_both_reported(
        self, tmp_path, monkeypatch
    ):
        # two axes degrade in the same window: the most severe breach
        # fires first, but the other's detector already re-baselined —
        # it must queue and fire on the NEXT round, not vanish
        store = TimeSeriesStore()
        n = 10
        base = time.time() - n - 2
        for i in range(n):
            lat_dp = 9000.0 if i >= 5 else 2.0
            lat_fsdp = 4000.0 if i >= 5 else 2.0
            store.record_digest(0, {
                "fxl_dp": lat_dp, "fxb_dp": 3.0,
                "fxl_fsdp": lat_fsdp, "fxb_fsdp": 3.0,
            }, ts=base + i)
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        first = diagnosis.diagnose_once()
        assert first and "'dp'" in first[0].reason
        second = diagnosis.diagnose_once()
        assert second and "'fsdp'" in second[0].reason

    def test_culprit_ignores_evicted_node(self):
        # an evicted (scaled-out) node's series rings outlive it; the
        # culprit scan must read the evictable per-node latest view,
        # never the rings
        store = TimeSeriesStore()
        now = time.time()
        store.record_digest(7, _fx(99999.0, 0.01), ts=now - 1)
        store.evict_node(7)
        store.record_digest(0, _fx(9000.0, 3.0), ts=now)
        assert "node7.comm.dp.lat_us" in store.names()  # ring survives
        diagnostician = SlowLinkDiagnostician(store, res_s=1.0)
        assert diagnostician._culprit("dp", "lat_us") == 0  # noqa: SLF001
        assert diagnostician._culprit("dp", "gbps") == 0  # noqa: SLF001

    def test_culprit_ignores_stale_node(self):
        from dlrover_tpu.master.metric_context import DIGEST_FRESH_S

        store = TimeSeriesStore()
        now = time.time()
        store.record_digest(
            7, _fx(99999.0, 0.01), ts=now - DIGEST_FRESH_S - 30
        )
        store.record_digest(0, _fx(9000.0, 3.0), ts=now)
        diagnostician = SlowLinkDiagnostician(store, res_s=1.0)
        assert diagnostician._culprit("dp", "lat_us") == 0  # noqa: SLF001

    def test_abs_floor_suppresses_noise(self, tmp_path, monkeypatch):
        # sub-floor jitter (default floor 50µs) on a quiet fabric must
        # not open incidents
        store = TimeSeriesStore()
        n = 10
        base = time.time() - n - 2
        for i in range(n):
            store.record_digest(
                0, _fx(2.0 + (i % 3) * 0.5, 3.0), ts=base + i
            )
        diagnosis, incidents = self._manager(
            store, tmp_path, monkeypatch
        )
        assert diagnosis.diagnose_once() == []


# ---------------------------------------------------------------------------
# Incident classification from chaos evidence alone
# ---------------------------------------------------------------------------


class TestCommIncidentClassification:
    def test_axis_delay_point_maps_to_comm_phase(self):
        from dlrover_tpu.observability.incidents import classify

        verdict = classify(chaos_records=[
            {"point": "comm.axis_delay.dp", "kind": "delay", "seq": 0},
        ])
        assert verdict["phase"] == "comm"
        assert verdict["chaos"]["point"] == "comm.axis_delay.dp"

    def test_stuck_probe_span_maps_to_comm_phase(self):
        from dlrover_tpu.observability.incidents import classify

        verdict = classify(dumps={
            "node_2": {"open_spans": [
                {"name": "comm.probe.dp", "open_for_s": 42.0},
            ]},
        })
        assert verdict["phase"] == "comm"
        assert verdict["culprit_node"] == 2
        assert verdict["stuck_op"] == "comm.probe.dp"


# ---------------------------------------------------------------------------
# Dashboard /comm
# ---------------------------------------------------------------------------


class _FakeMaster:
    def __init__(self, servicer, incident_manager=None):
        from dlrover_tpu.master.job_context import get_job_context
        from dlrover_tpu.master.perf_monitor import PerfMonitor

        self.servicer = servicer
        self.perf_monitor = PerfMonitor()
        self._job_context = get_job_context()
        self.rdzv_managers = {}
        self.stats_reporter = SimpleNamespace(records=lambda: [])
        if incident_manager is not None:
            self.incident_manager = incident_manager


class TestDashboardComm:
    @pytest.fixture
    def dash(self):
        from dlrover_tpu.master.dashboard import DashboardServer
        from dlrover_tpu.master.servicer import MasterServicer

        servicer = MasterServicer()
        server = DashboardServer(_FakeMaster(servicer), port=0)
        server.start()
        yield servicer, server
        server.stop()

    def _get(self, port, path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/{path}", timeout=5
        ) as resp:
            return resp.status, resp.read()

    def test_comm_endpoint_reports_axes_and_nodes(self, dash):
        servicer, server = dash
        now = time.time()
        servicer.timeseries.record_digest(0, _fx(5.0, 4.0), ts=now - 1)
        servicer.timeseries.record_digest(1, _fx(800.0, 0.5), ts=now)
        status, body = self._get(server.port, "comm")
        assert status == 200
        payload = json.loads(body)
        assert payload["axes"]["dp"]["lat_us"] == pytest.approx(800.0)
        assert payload["axes"]["dp"]["gbps"] == pytest.approx(0.5)
        assert payload["nodes"]["1"]["axes"]["dp"]["lat_us"] == (
            pytest.approx(800.0)
        )

    def test_comm_endpoint_empty_store(self, dash):
        _, server = dash
        status, body = self._get(server.port, "comm")
        assert status == 200
        payload = json.loads(body)
        assert payload["axes"] == {}

    def test_page_links_comm_view(self, dash):
        _, server = dash
        status, body = self._get(server.port, "")
        page = body.decode()
        assert "fabric" in page
        assert "href=comm" in page


# ---------------------------------------------------------------------------
# Trainer integration: probe cadence + digest keys
# ---------------------------------------------------------------------------


class TestTrainerIntegration:
    def test_trainer_builds_probe_for_active_mesh(self):
        trainer, state, batch = _tiny_bucketed_trainer(2)
        assert trainer._comm_probe is not None  # noqa: SLF001
        assert "dp" in trainer._comm_probe.axes  # noqa: SLF001

    def test_probe_cadence_feeds_scope_and_digest(
        self, tmp_path, monkeypatch
    ):
        _env(
            monkeypatch,
            DLROVER_TPU_COMM_PROBE_EVERY="2",
            DLROVER_TPU_COMM_PROBE_BW_BYTES=str(1 << 12),
            DLROVER_TPU_COMM_PROBE_REPS="1",
            DLROVER_TPU_COMM_BUCKET_PROBE="0",
            DLROVER_TPU_DIGEST_EVERY="2",
            DLROVER_TPU_RUNTIME_METRICS_PATH=str(
                tmp_path / "runtime_metrics.json"
            ),
        )
        commscope.reset_scope()
        trainer, state, batch = _tiny_bucketed_trainer(2)
        sharded = trainer.shard_batch(batch)
        # first dispatch is the compile; digest steps count from the
        # second — 6 steps => digest steps 1..5, file drops at 2 and 4,
        # the probe fires at digest step 2, so the step-4 file carries
        # the fabric keys
        for _ in range(6):
            state, _ = trainer.train_step(state, sharded)
        assert commscope.scope().fabric.get("dp") is not None
        rank_files = list(tmp_path.glob("runtime_metrics.json.rank*"))
        assert rank_files
        with open(rank_files[0]) as f:
            digest = json.load(f)
        assert "fxl_dp" in digest

    def test_probe_disabled_by_knob(self, monkeypatch):
        _env(monkeypatch, DLROVER_TPU_COMM_PROBE_EVERY="0")
        trainer, state, batch = _tiny_bucketed_trainer(2)
        assert trainer._comm_probe is None  # noqa: SLF001
