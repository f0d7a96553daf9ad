"""Brain v2 action channel under agent churn: tracked delivery over
the REAL servicer — re-target or expire, never silently dropped
(``test_control_plane.py``-style fixtures)."""

import time

import pytest

from dlrover_tpu.agent.master_client import LocalMasterClient
from dlrover_tpu.brain.actions import (
    ActionTracker,
    BrainActionType,
    DemoteAction,
    PreemptAction,
    RestartAction,
    RideOutAction,
    ScalePlanAction,
)
from dlrover_tpu.brain.fleet_arbiter import FleetArbiter
from dlrover_tpu.brain.fleet_state import JobHandle
from dlrover_tpu.common.constants import NodeStatus, NodeType
from dlrover_tpu.master.job_context import JobContext
from dlrover_tpu.common.node import Node
from dlrover_tpu.master.servicer import MasterServicer


def _ctx(node_ids, job="churnjob"):
    ctx = JobContext()
    ctx.job_name = job
    for node_id in node_ids:
        ctx.update_job_node(
            Node(NodeType.WORKER, node_id, status=NodeStatus.RUNNING)
        )
    return ctx


def _kill(ctx, node_id):
    ctx.job_node(NodeType.WORKER, node_id).update_status(
        NodeStatus.FAILED
    )


class TestActionTaxonomy:
    def test_delivered_dicts_carry_brain_envelope(self):
        for action in (
            ScalePlanAction("j", 4, 2, reason="r"),
            PreemptAction("j", 3, beneficiary="b"),
            DemoteAction("j", axis="slice"),
            RestartAction("j", incident_id="inc"),
        ):
            wire = action.to_dict()
            assert wire["extra"]["brain"]["id"] == action.id
            assert wire["extra"]["brain"]["job"] == "j"
            assert wire["action"] == action.action_type

    def test_restart_uses_the_agents_existing_verb(self):
        assert RestartAction("j").to_dict()["action"] == \
            "restart_worker"

    def test_scale_plan_restarts_workers_only_on_shrink(self):
        grow = ScalePlanAction("j", 6, 4)
        shrink = ScalePlanAction("j", 2, 4)
        assert grow.to_dict()["extra"]["restart_workers"] is False
        assert shrink.to_dict()["extra"]["restart_workers"] is True

    def test_rideout_is_recorded_not_delivered(self):
        tracker = ActionTracker(ack_timeout_s=0.0)
        delivered = []
        tracker.issue(
            RideOutAction("j", incident_id="inc"),
            lambda n, a: delivered.append(a),
        )
        assert delivered == []
        assert tracker.pending() == []
        assert tracker.log()[-1]["outcome"] == "recorded"


class TestTrackerLifecycle:
    def test_targeted_ack_only_from_target(self):
        tracker = ActionTracker(ack_timeout_s=60.0)
        ctx = _ctx([0, 1])
        action = PreemptAction("j", 1)
        tracker.issue(action, ctx.enqueue_action)
        assert tracker.ack("j", 0, [action.id]) == 0  # wrong node
        assert tracker.ack("other", 1, [action.id]) == 0  # wrong job
        assert tracker.ack("j", 1, [action.id]) == 1
        assert tracker.pending() == []

    def test_broadcast_ack_from_any_node(self):
        tracker = ActionTracker(ack_timeout_s=60.0)
        ctx = _ctx([0, 1])
        action = DemoteAction("j")
        tracker.issue(action, ctx.enqueue_action)
        assert tracker.ack("j", 1, [action.id]) == 1

    @staticmethod
    def _targeted(job, node_id, **kwargs):
        """A targeted NON-preempt delivery (preempts have their own
        dead-target semantics — the death IS the preemption)."""
        action = DemoteAction(job, axis="slice", **kwargs)
        action.node_id = node_id
        return action

    def test_dead_target_retargets_to_survivor(self):
        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0, 1])
        action = self._targeted("j", 1)
        alive = lambda: [  # noqa: E731 - churn-aware view
            n.id for n in ctx.job_nodes_by_type(NodeType.WORKER)
            .values() if n.status == NodeStatus.RUNNING
        ]
        tracker.issue(action, ctx.enqueue_action, alive)
        # node 1 dies BEFORE draining its queue
        _kill(ctx, 1)
        outcomes = tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["retargeted"]
        assert action.node_id == 0
        # the re-issued dict is on the survivor's queue
        queued = ctx.next_actions(0)
        assert any(
            (a.get("extra") or {}).get("brain", {}).get("id")
            == action.id for a in queued
        )
        assert tracker.ack("j", 0, [action.id]) == 1

    def test_dead_preempt_target_is_obsolete_not_retargeted(self):
        """The preempt's goal was to free that node — its death
        achieved it; re-targeting would reclaim an extra healthy
        node."""
        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0, 1])
        action = PreemptAction("j", 1)
        tracker.issue(action, ctx.enqueue_action, lambda: [0])
        outcomes = tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["obsolete"]
        assert tracker.pending() == []
        # node 0 never received a surprise preempt
        ctx.next_actions(1)  # drain the original delivery
        assert not any(
            a.get("action") == "brain_preempt"
            for a in ctx.next_actions(0)
        )

    def test_alive_target_is_not_retargeted_early(self):
        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0, 1])
        action = self._targeted("j", 1)
        tracker.issue(action, ctx.enqueue_action, lambda: [0, 1])
        assert tracker.watch() == []  # just slow, not dead
        assert action.node_id == 1

    def test_no_survivor_waits_for_expiry(self):
        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0])
        action = self._targeted("j", 0, expiry_secs=3600.0)
        tracker.issue(action, ctx.enqueue_action, lambda: [])
        assert tracker.watch() == []  # nowhere to go yet
        assert len(tracker.pending()) == 1

    def test_expiry_is_loud_never_silent(self):
        from dlrover_tpu.observability import metrics as obs_metrics

        def expired_total():
            snap = obs_metrics.registry().snapshot()
            return sum(
                v for labels, v in snap.get("counters", {}).get(
                    "dlrover_tpu_brain_actions_total", {}
                ).items() if 'outcome="expired"' in labels
            )

        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0])
        before = expired_total()
        action = PreemptAction("j", 0, expiry_secs=0.0)
        tracker.issue(action, ctx.enqueue_action, lambda: [0])
        time.sleep(0.01)
        outcomes = tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["expired"]
        assert tracker.pending() == []
        assert expired_total() == before + 1
        assert tracker.log()[-1]["outcome"] == "expired"

    def test_broadcast_rebroadcasts_after_ack_timeout(self):
        tracker = ActionTracker(ack_timeout_s=0.0)
        ctx = _ctx([0])
        action = DemoteAction("j", expiry_secs=3600.0)
        tracker.issue(action, ctx.enqueue_action, lambda: [0])
        ctx.next_actions(0)  # first delivery lost with the node
        outcomes = tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["retargeted"]
        queued = ctx.next_actions(0)
        assert any(
            (a.get("extra") or {}).get("brain", {}).get("id")
            == action.id for a in queued
        )


class TestChannelOverRealServicer:
    """The wire: JobContext queue -> HeartbeatResponse -> agent client
    -> BrainActionAck report -> arbiter tracker."""

    def _fixture(self):
        JobContext.reset()
        ctx = JobContext.singleton_instance()
        ctx.job_name = "wirejob"
        for node_id in (0, 1):
            ctx.update_job_node(Node(
                NodeType.WORKER, node_id, status=NodeStatus.RUNNING
            ))
        arbiter = FleetArbiter(
            capacity=4, tracker=ActionTracker(ack_timeout_s=0.0)
        )
        handle = JobHandle("wirejob", job_context=ctx, min_nodes=1,
                           max_nodes=4)
        arbiter.register_job(handle)
        servicer = MasterServicer()
        servicer.set_brain(arbiter)
        return ctx, arbiter, handle, servicer

    def teardown_method(self):
        JobContext.reset()

    def test_delivery_ack_roundtrip(self):
        ctx, arbiter, handle, servicer = self._fixture()
        action = PreemptAction("wirejob", 0, reason="wire")
        arbiter.tracker.issue(
            action, handle.enqueue, handle.alive_nodes
        )
        client = LocalMasterClient(servicer, 0, NodeType.WORKER)
        delivered = client.report_heart_beat()
        ids = [
            ((a.get("extra") or {}).get("brain") or {}).get("id")
            for a in delivered
        ]
        assert action.id in ids
        assert len(arbiter.tracker.pending()) == 1
        assert client.report_brain_ack([action.id])
        assert arbiter.tracker.pending() == []

    def test_ack_defaults_job_from_the_masters_context(self):
        ctx, arbiter, handle, servicer = self._fixture()
        action = DemoteAction("wirejob")
        arbiter.tracker.issue(
            action, handle.enqueue, handle.alive_nodes
        )
        client = LocalMasterClient(servicer, 1, NodeType.WORKER)
        client.report_heart_beat()
        # the agent does not know its job name; the servicer fills it
        assert client.report_brain_ack([action.id], job="")
        assert arbiter.tracker.pending() == []

    def test_die_mid_delivery_retarget_end_to_end(self):
        ctx, arbiter, handle, servicer = self._fixture()
        action = DemoteAction("wirejob", reason="churn e2e")
        action.node_id = 1  # targeted delivery
        arbiter.tracker.issue(
            action, handle.enqueue, handle.alive_nodes
        )
        # node 1's heartbeat pops the action... and the node dies
        # before acting on it (the reply is lost with the process)
        doomed = LocalMasterClient(servicer, 1, NodeType.WORKER)
        delivered = doomed.report_heart_beat()
        assert any(
            ((a.get("extra") or {}).get("brain") or {}).get("id")
            == action.id for a in delivered
        )
        _kill(ctx, 1)
        outcomes = arbiter.tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["retargeted"]
        assert action.node_id == 0
        survivor = LocalMasterClient(servicer, 0, NodeType.WORKER)
        redelivered = survivor.report_heart_beat()
        assert any(
            ((a.get("extra") or {}).get("brain") or {}).get("id")
            == action.id for a in redelivered
        )
        assert survivor.report_brain_ack([action.id])
        assert arbiter.tracker.pending() == []

    def test_preempt_die_mid_delivery_obsolete_end_to_end(self):
        ctx, arbiter, handle, servicer = self._fixture()
        action = PreemptAction("wirejob", 1, reason="preempt churn")
        arbiter.tracker.issue(
            action, handle.enqueue, handle.alive_nodes
        )
        doomed = LocalMasterClient(servicer, 1, NodeType.WORKER)
        doomed.report_heart_beat()
        _kill(ctx, 1)
        outcomes = arbiter.tracker.watch()
        assert [o["outcome"] for o in outcomes] == ["obsolete"]
        assert arbiter.tracker.pending() == []
        # the survivor's heartbeat carries no surprise preempt
        survivor = LocalMasterClient(servicer, 0, NodeType.WORKER)
        assert not any(
            a.get("action") == "brain_preempt"
            for a in survivor.report_heart_beat()
        )

    def test_ack_without_brain_attached_is_harmless(self):
        JobContext.reset()
        servicer = MasterServicer()
        client = LocalMasterClient(servicer, 0, NodeType.WORKER)
        assert client.report_brain_ack(["ghost-id"])


class TestAgentSideHandling:
    """The agent's verbs: acks flushed, demote staged, preempt/scale
    semantics — on a minimally-constructed agent."""

    def _agent(self):
        from dlrover_tpu.agent.elastic_agent import ElasticAgent

        agent = ElasticAgent.__new__(ElasticAgent)

        class SpyClient:
            def __init__(self):
                self.acked = []
                self.fail = False

            def report_brain_ack(self, ids, job=""):
                if self.fail:
                    raise RuntimeError("master down")
                self.acked.extend(ids)
                return True

        agent._client = SpyClient()
        return agent

    def test_flush_brain_acks(self):
        agent = self._agent()
        acks = ["a", "b"]
        agent._flush_brain_acks(acks)
        assert agent._client.acked == ["a", "b"]
        assert acks == []  # cleared

    def test_flush_survives_a_dead_master(self):
        agent = self._agent()
        agent._client.fail = True
        acks = ["a"]
        agent._flush_brain_acks(acks)  # must not raise
        assert acks == []

    def test_handle_brain_demote_stages_for_the_trainer(self, tmp_path,
                                                       monkeypatch):
        from dlrover_tpu.parallel import hierarchy

        monkeypatch.setenv(
            "DLROVER_TPU_RUNTIME_METRICS_PATH",
            str(tmp_path / "runtime_metrics.json"),
        )
        # an agent's process holds no trainer: a Trainer an earlier test
        # of this worker left uncollected would take the demotion
        # in-process, and nothing would be staged
        monkeypatch.setattr(hierarchy, "_DEMOTION_TARGET", None)
        agent = self._agent()
        agent._handle_brain_demote(
            {"action": "brain_demote", "reason": "slow slice link"}
        )

        class Holder:
            applied = 0

            def apply_dcn_demotion(self):
                self.applied += 1
                return "int4"

        holder = Holder()
        seq = hierarchy.poll_staged_demotion(holder, 0)
        assert seq == 1
        assert holder.applied == 1

    def test_demote_applies_in_process_when_target_registered(self):
        from dlrover_tpu.parallel import hierarchy

        class Holder:
            applied = 0

            def apply_dcn_demotion(self):
                self.applied += 1
                return "int4"

        holder = Holder()
        hierarchy.register_demotion_target(holder)
        try:
            agent = self._agent()
            agent._handle_brain_demote({"action": "brain_demote"})
            assert holder.applied == 1
        finally:
            hierarchy.register_demotion_target(None)


class TestSlowLinkChannelDemotion:
    """r18 follow-up closed: a slow-DCN-link breach on a master with
    NO co-resident trainer queues brain_demote on the action channel."""

    def test_breach_enqueues_brain_demote_broadcast(self):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import register_sentinels
        from dlrover_tpu.parallel import hierarchy

        hierarchy.register_demotion_target(None)  # no trainer here
        store = TimeSeriesStore()
        ctx = _ctx([0, 1], job="slicejob")
        manager = DiagnosisManager(
            sink=lambda action: ctx.enqueue_action(
                action.node_id, action.to_dict()
            )
        )
        sentinels = register_sentinels(manager, store, job_context=ctx)
        slow = [s for s in sentinels if s.name == "slow_link"][0]
        now = time.time()
        # healthy slice-axis latency, then a sustained degradation
        for i in range(12):
            store.add("job.comm.slice.lat_us", 80.0,
                      now - 400 + i * 10)
        for i in range(6):
            store.add("job.comm.slice.lat_us", 5000.0,
                      now - 280 + i * 10)
        obs = slow.observe()
        assert obs.observed
        assert obs.extra["dcn_demoted_to"] == "action_channel"
        queued = ctx.next_actions(0)
        demotes = [
            a for a in queued if a.get("action") == "brain_demote"
        ]
        assert len(demotes) == 1
        assert demotes[0]["extra"]["axis"] == "slice"
        # broadcast: the other node receives it too
        assert any(
            a.get("action") == "brain_demote"
            for a in ctx.next_actions(1)
        )

    def test_in_process_target_still_wins(self):
        from dlrover_tpu.parallel import hierarchy

        class Holder:
            applied = 0

            def apply_dcn_demotion(self):
                self.applied += 1
                return "int4"

        holder = Holder()
        hierarchy.register_demotion_target(holder)
        try:
            sink_calls = []
            hook = hierarchy.DcnDemotionHook(
                action_sink=lambda axis, reason: sink_calls.append(axis)
            )
            assert hook("slice", "lat_us", {}) == "int4"
            assert holder.applied == 1
            assert sink_calls == []  # channel not used
        finally:
            hierarchy.register_demotion_target(None)
