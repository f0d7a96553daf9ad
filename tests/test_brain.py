"""Brain v2: fleet state, arbiters, priced cost model, closed loop,
HTTP fleet surface, optimizer edge cases, resource-optimizer bridge."""

import json
import time
import urllib.request

import pytest

from dlrover_tpu.brain import optimizers
from dlrover_tpu.brain.arbiters import (
    ArbiterConfig,
    run_arbiters,
)
from dlrover_tpu.brain.fleet_arbiter import FleetArbiter
from dlrover_tpu.brain.fleet_state import (
    FleetState,
    FleetView,
    JobHandle,
    JobSnapshot,
)
from dlrover_tpu.common.constants import NodeStatus, NodeType
from dlrover_tpu.common.node import Node
from dlrover_tpu.master.job_context import JobContext
from dlrover_tpu.master.timeseries import TimeSeriesStore


# ---------------------------------------------------------------------------
# optimizer plugin edge cases (satellite: deterministic degenerate
# histories)
# ---------------------------------------------------------------------------


class TestThroughputRegressionEdgeCases:
    def test_single_point_returns_best_observed(self):
        assert optimizers.throughput_regression([(4, 10.0)], 1, 16) == 4

    def test_single_distinct_count_many_samples(self):
        points = [(4, 10.0), (4, 12.0), (4, 8.0)]
        assert optimizers.throughput_regression(points, 1, 16) == 4

    def test_all_equal_speeds_returns_best_observed(self):
        # b == 0 exactly: per-node efficiency is best at the NARROWEST
        points = [(2, 10.0), (4, 10.0), (8, 10.0)]
        assert optimizers.throughput_regression(points, 1, 16) == 2

    def test_negative_exponent_returns_best_observed(self):
        # speed FALLS with n: extrapolation has nothing good to say
        points = [(2, 10.0), (4, 5.0)]
        assert optimizers.throughput_regression(points, 1, 16) == 2

    def test_empty_history_is_none(self):
        assert optimizers.throughput_regression([], 1, 16) is None

    def test_degenerate_respects_eligibility(self):
        # the best-observed fallback still honors min/max/unit
        points = [(3, 10.0), (3, 12.0)]
        assert optimizers.throughput_regression(
            points, 1, 16, node_unit=2
        ) is None

    def test_healthy_fit_still_extrapolates(self):
        points = [(1, 100.0), (2, 198.0), (4, 390.0)]
        assert optimizers.throughput_regression(points, 1, 16) == 16


class TestEfficiencyFloorWalk:
    def test_accepts_paying_steps(self):
        # 2->4 retains 16/4=4 vs 10/2=5 -> 80% >= 70%: accepted;
        # 4->8 retains 18/8=2.25 vs 4 -> 56% < 70%: rejected
        points = [(2, 10.0), (4, 16.0), (8, 18.0)]
        assert optimizers.efficiency_floor_walk(points, 1, 16) == 4

    def test_rejects_first_bad_step_and_everything_wider(self):
        # 2->4 fails the floor, so the (paying) 4->8 step is never
        # reached — the walk judges consecutive accepted steps
        points = [(2, 10.0), (4, 6.0), (8, 11.0)]
        assert optimizers.efficiency_floor_walk(points, 1, 16) == 2

    def test_single_point(self):
        assert optimizers.efficiency_floor_walk([(4, 8.0)], 1, 16) == 4

    def test_empty(self):
        assert optimizers.efficiency_floor_walk([], 1, 16) is None

    def test_run_optimizer_passes_floor_through(self):
        points = [(2, 10.0), (4, 15.0)]
        # eff ratio = 0.75: accepted at floor 0.7, rejected at 0.8
        assert optimizers.run_optimizer(
            "efficiency_floor", points, 1, 16, efficiency_floor=0.7
        ) == 4
        assert optimizers.run_optimizer(
            "efficiency_floor", points, 1, 16, efficiency_floor=0.8
        ) == 2

    def test_unknown_kwargs_ignored_by_all_plugins(self):
        for name in optimizers.list_optimizers():
            optimizers.run_optimizer(
                name, [(2, 10.0), (4, 15.0)], 1, 16,
                efficiency_floor=0.7,
            )


class TestArbiterRegistry:
    def test_standard_arbiters_registered(self):
        names = optimizers.list_arbiters()
        for name in ("goodput_marginal", "priority_preempt",
                     "incident_cost"):
            assert name in names

    def test_unknown_arbiter_skipped(self):
        view = FleetView(
            ts=time.time(), snapshots={}, free_nodes=0, capacity=0,
            history=lambda j: [],
        )
        assert run_arbiters(["nonsense"], view) == []


# ---------------------------------------------------------------------------
# resource-optimizer bridge (satellite: one shared registry)
# ---------------------------------------------------------------------------


class TestResourceOptimizerBridge:
    def _opt(self, samples, current, **kwargs):
        from dlrover_tpu.master.perf_monitor import PerfMonitor
        from dlrover_tpu.master.resource_optimizer import (
            SliceResourceOptimizer,
        )

        pm = PerfMonitor()
        pm.set_worker_num(current)
        opt = SliceResourceOptimizer(pm, **kwargs)
        opt._samples.update(samples)
        opt.phase = "sampling"
        return opt

    def test_revert_sets_stable_and_stops_exploring(self):
        opt = self._opt({2: 10.0, 4: 10.5}, 4, min_nodes=2,
                        max_nodes=8, node_unit=2)
        assert opt.propose_node_count() == 2
        assert opt.phase == "stable"
        # once stable, no more exploration probes
        opt._perf_monitor.set_worker_num(2)
        assert opt.propose_node_count() is None

    def test_paying_scale_up_keeps_exploring(self):
        opt = self._opt({2: 10.0, 4: 16.0}, 4, min_nodes=2,
                        max_nodes=8, node_unit=2)
        assert opt.propose_node_count() == 6

    def test_pluggable_optimizer_name(self):
        # the regression plugin extrapolates past observed counts
        opt = self._opt({2: 100.0, 4: 196.0}, 4, min_nodes=2,
                        max_nodes=8, node_unit=2,
                        optimizer_name="throughput_regression")
        assert opt.propose_node_count() == 8


# ---------------------------------------------------------------------------
# fleet state
# ---------------------------------------------------------------------------


def _make_ctx(node_ids):
    ctx = JobContext()
    for node_id in node_ids:
        ctx.update_job_node(
            Node(NodeType.WORKER, node_id, status=NodeStatus.RUNNING)
        )
    return ctx


def _fed_store(goodput=0.9, idle=0.0, n_points=8, now=None):
    now = time.time() if now is None else now
    store = TimeSeriesStore()
    for i in range(n_points):
        ts = now - (n_points - i) * 10
        store.add("job.goodput", goodput, ts)
        if idle:
            store.add("job.share.idle_unknown", idle, ts)
    return store


class TestFleetState:
    def test_snapshot_reads_store_and_context(self):
        handle = JobHandle(
            "j", timeseries=_fed_store(goodput=0.8, idle=0.3),
            job_context=_make_ctx([0, 1, 2]), priority=2,
            min_nodes=1, max_nodes=8,
        )
        snap = handle.snapshot()
        assert snap.node_count == 3
        assert snap.alive_nodes == (0, 1, 2)
        assert snap.goodput == pytest.approx(0.8)
        assert snap.idle_share() == pytest.approx(0.3)
        assert snap.speed == pytest.approx(0.8 * 3)
        assert len(snap.goodput_series) > 0

    def test_refresh_feeds_history_and_free_pool(self):
        state = FleetState(capacity=8)
        state.register_job(JobHandle(
            "j", timeseries=_fed_store(), job_context=_make_ctx([0, 1]),
        ))
        view = state.refresh()
        assert view.capacity == 8
        assert view.free_nodes == 6
        points = view.history("j")
        assert points and points[0][0] == 2

    def test_refresh_survives_broken_handle(self):
        state = FleetState(capacity=4)

        class Broken(JobHandle):
            def snapshot(self):
                raise RuntimeError("sick job")

        state.register_job(Broken("bad"))
        state.register_job(JobHandle(
            "ok", timeseries=_fed_store(),
            job_context=_make_ctx([0]),
        ))
        view = state.refresh()
        assert set(view.snapshots) == {"ok"}

    def test_open_incidents_filters(self):
        import tempfile

        from dlrover_tpu.observability.incidents import IncidentManager

        with tempfile.TemporaryDirectory() as tmp:
            manager = IncidentManager(root=tmp)
            slow = manager.open("slow_link", broadcast=False)
            manager.open("hang", broadcast=False)  # not a degradation
            handle = JobHandle("j", incident_manager=manager)
            kinds = [i["kind"] for i in handle.open_incidents()]
            assert kinds == ["slow_link"]
            # a decided incident stops surfacing
            manager.annotate(slow, "brain_decision",
                             {"action": "ride_out"})
            assert handle.open_incidents() == []

    def test_fleet_goodput(self):
        view = FleetView(
            ts=0.0,
            snapshots={
                "a": JobSnapshot("a", node_count=4, goodput=0.5),
                "b": JobSnapshot("b", node_count=4, goodput=1.0),
            },
            free_nodes=8, capacity=16, history=lambda j: [],
        )
        assert view.fleet_goodput() == pytest.approx(
            (0.5 * 4 + 1.0 * 4) / 16
        )


# ---------------------------------------------------------------------------
# arbiters over synthetic views
# ---------------------------------------------------------------------------


def _view(snapshots, free, capacity, history=None, ts=None):
    return FleetView(
        ts=time.time() if ts is None else ts,
        snapshots={s.job: s for s in snapshots},
        free_nodes=free, capacity=capacity,
        history=history or (lambda j: []),
    )


def _cfg(**kw):
    base = dict(
        optimizer="efficiency_floor", marginal_floor=0.7,
        idle_shrink_share=0.5, grow_min_goodput=0.6,
        cooldown_s=0.0, rideout_horizon_s=600.0, restart_cost_s=120.0,
    )
    base.update(kw)
    return ArbiterConfig(**base)


class TestGoodputMarginal:
    def test_grows_unexplored_healthy_job(self):
        snap = JobSnapshot("j", node_count=2, min_nodes=2, max_nodes=8,
                           goodput=0.9)
        decisions = run_arbiters(
            ["goodput_marginal"],
            _view([snap], free=4, capacity=8,
                  history=lambda j: [(2, 1.8)]),
            _cfg(),
        )
        assert [d.kind for d in decisions] == ["grow"]
        assert decisions[0].target_nodes == 3

    def test_no_grow_without_free_nodes(self):
        snap = JobSnapshot("j", node_count=2, min_nodes=2, max_nodes=8,
                           goodput=0.9)
        assert run_arbiters(
            ["goodput_marginal"],
            _view([snap], free=0, capacity=2,
                  history=lambda j: [(2, 1.8)]),
            _cfg(),
        ) == []

    def test_no_probe_when_goodput_unhealthy(self):
        snap = JobSnapshot("j", node_count=2, min_nodes=2, max_nodes=8,
                           goodput=0.3)
        assert run_arbiters(
            ["goodput_marginal"],
            _view([snap], free=4, capacity=8,
                  history=lambda j: [(2, 0.6)]),
            _cfg(),
        ) == []

    def test_no_probe_when_input_bound(self):
        """A healthy-goodput job blocked on its input pipeline must not
        be handed more accelerators — wider just starves faster."""
        snap = JobSnapshot("j", node_count=2, min_nodes=2, max_nodes=8,
                           goodput=0.9,
                           shares={"input_starved": 0.5},
                           data_backlog=37.0)
        assert run_arbiters(
            ["goodput_marginal"],
            _view([snap], free=4, capacity=8,
                  history=lambda j: [(2, 1.8)]),
            _cfg(),
        ) == []

    def test_shrinks_idle_job(self):
        snap = JobSnapshot(
            "j", node_count=4, min_nodes=2, max_nodes=8, goodput=0.3,
            shares={"idle_unknown": 0.7},
        )
        decisions = run_arbiters(
            ["goodput_marginal"], _view([snap], free=0, capacity=4),
            _cfg(),
        )
        assert [d.kind for d in decisions] == ["shrink"]
        assert decisions[0].target_nodes == 3

    def test_shrinks_when_history_says_wide_does_not_pay(self):
        snap = JobSnapshot("j", node_count=8, min_nodes=2, max_nodes=8,
                           goodput=0.9)
        decisions = run_arbiters(
            ["goodput_marginal"],
            _view([snap], free=0, capacity=8,
                  history=lambda j: [(4, 4.0), (8, 4.4)]),
            _cfg(),
        )
        assert [d.kind for d in decisions] == ["shrink"]
        assert decisions[0].target_nodes == 4

    def test_cooldown_blocks_back_to_back_scaling(self):
        snap = JobSnapshot("j", node_count=2, min_nodes=2, max_nodes=8,
                           goodput=0.9)
        state = {}
        view = _view([snap], free=4, capacity=8,
                     history=lambda j: [(2, 1.8)], ts=1000.0)
        first = run_arbiters(
            ["goodput_marginal"], view, _cfg(cooldown_s=60.0), state
        )
        assert len(first) == 1
        again = run_arbiters(
            ["goodput_marginal"], view, _cfg(cooldown_s=60.0), state
        )
        assert again == []


class TestPriorityPreempt:
    def test_admits_arrival_from_free_pool(self):
        arrival = JobSnapshot("new", node_count=0, min_nodes=4,
                              max_nodes=8, priority=5)
        decisions = run_arbiters(
            ["priority_preempt"],
            _view([arrival], free=6, capacity=8), _cfg(),
        )
        assert [d.kind for d in decisions] == ["grow"]
        assert decisions[0].target_nodes == 4

    def test_preempts_lower_priority_least_goodput_lost(self):
        arrival = JobSnapshot("new", node_count=0, min_nodes=4,
                              max_nodes=8, priority=5)
        cheap = JobSnapshot("cheap", node_count=6, min_nodes=2,
                            priority=0, goodput=0.2,
                            alive_nodes=(0, 1, 2, 3, 4, 5))
        costly = JobSnapshot("costly", node_count=6, min_nodes=2,
                             priority=0, goodput=0.9,
                             alive_nodes=(0, 1, 2, 3, 4, 5))
        decisions = run_arbiters(
            ["priority_preempt"],
            _view([arrival, cheap, costly], free=0, capacity=12),
            _cfg(),
        )
        assert [d.kind for d in decisions] == ["preempt"]
        assert decisions[0].victims == {"cheap": 4}

    def test_never_preempts_equal_or_higher_priority(self):
        arrival = JobSnapshot("new", node_count=0, min_nodes=4,
                              priority=1)
        peer = JobSnapshot("peer", node_count=8, min_nodes=2,
                           priority=1, goodput=0.1)
        assert run_arbiters(
            ["priority_preempt"],
            _view([arrival, peer], free=0, capacity=8), _cfg(),
        ) == []

    def test_victims_keep_their_minimum(self):
        arrival = JobSnapshot("new", node_count=0, min_nodes=6,
                              priority=5)
        victim = JobSnapshot("v", node_count=4, min_nodes=2,
                             priority=0, goodput=0.5)
        # only 2 sheddable + 0 free < 6 needed: unsatisfiable, no
        # partial preemption
        assert run_arbiters(
            ["priority_preempt"],
            _view([arrival, victim], free=0, capacity=4), _cfg(),
        ) == []


class TestIncidentCost:
    def _incident_snap(self, degradation, opened_ts=500.0,
                       restart_price=30.0):
        series = []
        for i in range(20):
            ts = 300.0 + i * 10
            healthy = 0.9
            value = healthy - (degradation if ts >= opened_ts else 0.0)
            series.append({"ts": ts, "mean": value})
        return JobSnapshot(
            "j", node_count=4, goodput=0.9 - degradation,
            goodput_series=series,
            restart_price_s=restart_price,
            incidents=[{"incident_id": "inc-1", "kind": "slow_link",
                        "opened_ts": opened_ts}],
        )

    def test_restart_when_degradation_expensive(self):
        snap = self._incident_snap(degradation=0.5)
        decisions = run_arbiters(
            ["incident_cost"], _view([snap], 0, 4), _cfg(),
        )
        assert [d.kind for d in decisions] == ["restart"]
        cost = decisions[0].cost
        assert cost["cost_restart_gps"] < cost["cost_rideout_gps"]
        assert cost["restart_s"] == 30.0

    def test_rideout_when_degradation_cheap(self):
        snap = self._incident_snap(degradation=0.02)
        decisions = run_arbiters(
            ["incident_cost"], _view([snap], 0, 4), _cfg(),
        )
        assert [d.kind for d in decisions] == ["ride_out"]
        cost = decisions[0].cost
        assert cost["cost_rideout_gps"] <= cost["cost_restart_gps"]

    def test_each_incident_decided_once(self):
        snap = self._incident_snap(degradation=0.5)
        state = {}
        view = _view([snap], 0, 4)
        assert len(run_arbiters(
            ["incident_cost"], view, _cfg(), state
        )) == 1
        assert run_arbiters(
            ["incident_cost"], view, _cfg(), state
        ) == []

    def test_fallback_restart_price_from_config(self):
        snap = self._incident_snap(degradation=0.5,
                                   restart_price=None)
        decisions = run_arbiters(
            ["incident_cost"], _view([snap], 0, 4),
            _cfg(restart_cost_s=77.0),
        )
        assert decisions[0].cost["restart_s"] == 77.0


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------


class TestFleetArbiterLoop:
    def test_tick_grows_and_shrinks_and_issues_actions(self):
        arb = FleetArbiter(capacity=16)
        now = time.time()
        scales_a, scales_b = [], []
        arb.register_job(JobHandle(
            "grower", timeseries=_fed_store(goodput=0.9, now=now),
            job_context=_make_ctx([0, 1]), min_nodes=2, max_nodes=8,
            scaler=scales_a.append,
        ))
        arb.register_job(JobHandle(
            "idler", timeseries=_fed_store(goodput=0.2, idle=0.7,
                                           now=now),
            job_context=_make_ctx([0, 1, 2, 3]), min_nodes=1,
            max_nodes=8, scaler=scales_b.append,
        ))
        decisions = arb.tick(now=now)
        kinds = {d.job: d.kind for d in decisions}
        assert kinds == {"grower": "grow", "idler": "shrink"}
        assert scales_a == [3]
        assert scales_b == [3]
        # ScalePlan broadcasts are tracked deliveries
        pending = arb.tracker.pending()
        assert {p["job"] for p in pending} == {"grower", "idler"}
        snap = arb.snapshot()
        assert snap["ticks"] == 1
        assert snap["jobs"]["grower"]["nodes"] == 2
        assert len(snap["decisions"]) == 2

    def test_restart_and_rideout_annotate_incidents(self):
        import tempfile

        from dlrover_tpu.observability.incidents import IncidentManager

        arb = FleetArbiter(capacity=8)
        now = time.time()
        with tempfile.TemporaryDirectory() as tmp:
            manager = IncidentManager(root=tmp)
            store = TimeSeriesStore()
            opened = now - 60
            for i in range(20):
                ts = now - 200 + i * 10
                store.add(
                    "job.goodput",
                    0.9 if ts < opened else 0.3, ts,
                )
            incident_id = manager.open(
                "slow_link", broadcast=False, opened_ts=opened
            )
            arb.register_job(JobHandle(
                "j", timeseries=store, job_context=_make_ctx([0, 1]),
                incident_manager=manager, min_nodes=2, max_nodes=2,
            ))
            decisions = arb.tick(now=now)
            restart = [d for d in decisions if d.kind == "restart"]
            assert len(restart) == 1
            meta = manager.get(incident_id)
            decision = meta["annotations"]["brain_decision"]
            assert decision["action"] == "restart"
            assert decision["cost"]["cost_restart_gps"] < \
                decision["cost"]["cost_rideout_gps"]
            # the restart order is a tracked broadcast on the channel
            actions = manager._job_context  # not used; channel below
            del actions
            queued = [
                p for p in arb.tracker.pending()
                if p["type"] == "restart_worker"
            ]
            assert len(queued) == 1

    def test_demote_job_issues_tracked_broadcast(self):
        arb = FleetArbiter(capacity=4)
        ctx = _make_ctx([0])
        arb.register_job(JobHandle("j", job_context=ctx))
        action_id = arb.demote_job("j", axis="slice", reason="slow")
        assert action_id is not None
        queued = ctx.next_actions(0)
        assert queued and queued[0]["action"] == "brain_demote"
        assert queued[0]["extra"]["brain"]["id"] == action_id


# ---------------------------------------------------------------------------
# HTTP fleet surface + reporter
# ---------------------------------------------------------------------------


class TestFleetHttpSurface:
    def test_register_report_decide_pull_ack(self):
        from dlrover_tpu.brain.client import BrainClient, FleetReporter
        from dlrover_tpu.brain.service import BrainService

        svc = BrainService(port=0, fleet=True, capacity=8)
        svc.start()
        try:
            client = BrainClient(f"localhost:{svc.port}")
            ctx = _make_ctx([0, 1])
            ctx.job_name = "remote"
            reporter = FleetReporter(
                client, "remote",
                timeseries=_fed_store(goodput=0.9),
                job_context=ctx, min_nodes=2, max_nodes=8,
            )
            assert reporter.sync_once() == 0  # registered + reported
            svc.arbiter.tick()
            applied = reporter.sync_once()
            assert applied >= 1  # the grow's ScalePlan notice arrived
            delivered = ctx.next_actions(0)
            brain_ids = [
                ((a.get("extra") or {}).get("brain") or {}).get("id")
                for a in delivered
            ]
            assert any(brain_ids)
            # agent ack -> reporter buffer -> next pull -> tracker
            reporter.on_ack("remote", 0,
                            [i for i in brain_ids if i])
            reporter.sync_once()
            assert svc.arbiter.tracker.pending() == []
            # /fleet/status serves the arbiter snapshot
            with urllib.request.urlopen(
                f"http://localhost:{svc.port}/fleet/status", timeout=5
            ) as r:
                status = json.loads(r.read())
            assert "remote" in status["jobs"]
        finally:
            svc.stop()

    def test_report_unregistered_job_is_error(self):
        from dlrover_tpu.brain.client import BrainClient
        from dlrover_tpu.brain.service import BrainService

        svc = BrainService(port=0, fleet=True, capacity=4)
        svc.start()
        try:
            client = BrainClient(f"localhost:{svc.port}")
            assert not client.fleet_report("ghost", {"node_count": 1})
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# dashboard /brain
# ---------------------------------------------------------------------------


class TestDashboardBrain:
    def test_brain_view_over_http(self):
        from dlrover_tpu.master.dashboard import DashboardServer

        class FakeMaster:
            pass

        master = FakeMaster()
        master.brain = FleetArbiter(capacity=4)
        master.brain.register_job(JobHandle(
            "j", timeseries=_fed_store(), job_context=_make_ctx([0]),
        ))
        master.brain.tick()
        dash = DashboardServer(master, port=0)
        dash.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/brain", timeout=5
            ) as r:
                body = json.loads(r.read())
            assert body["enabled"] is True
            assert body["role"] == "arbiter"
            assert "j" in body["jobs"]
        finally:
            dash.stop()

    def test_brain_view_disabled_without_arbiter(self):
        from dlrover_tpu.master.dashboard import DashboardServer

        class FakeMaster:
            pass

        dash = DashboardServer(FakeMaster(), port=0)
        dash.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/brain", timeout=5
            ) as r:
                body = json.loads(r.read())
            assert body == {"enabled": False}
        finally:
            dash.stop()


# ---------------------------------------------------------------------------
# the bench (short) + gate column
# ---------------------------------------------------------------------------


class TestBrainBench:
    def test_brain_beats_static_with_both_drill_verdicts(self):
        from dlrover_tpu.diagnosis import brain_bench

        result = brain_bench.run_bench(ticks=320, seed=0, capacity=16)
        assert brain_bench.assert_bench(result) == []
        assert result["fleet_goodput_gain"] > 1.0
        drill = result["drill"]
        assert drill["ride_out"]["restarts"] == 0
        assert drill["restart"]["restarts"] >= 1
