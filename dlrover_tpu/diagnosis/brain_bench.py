"""Multi-job fleet bench: Brain-on vs static allocation.

The Brain's value claim is fleet-level: under a churning, bursty
multi-job workload, closing the loop (grow/shrink from goodput
telemetry, preempt for priority arrivals, priced restart-vs-ride-out
after incidents) beats a static allocation on AGGREGATE fleet goodput.
This bench measures exactly that, twice over the same seeded scenario:

* **static** — every job keeps its initial allocation; arrivals are
  admitted only from the free pool; incidents ride out forever.
* **brain** — a real :class:`~dlrover_tpu.brain.fleet_arbiter.
  FleetArbiter` closes the loop over the jobs' REAL ingestion objects:
  each simulated job owns a real ``TimeSeriesStore`` (fed through
  ``record_digest`` — the same differentiation path heartbeat digests
  take), a real ``JobContext`` (whose action queues the simulated
  agents drain exactly like ``ElasticAgent._monitor_workers``), and a
  real ``IncidentManager`` (whose annotations confirm every priced
  restart/ride-out verdict).

The simulation prices what production pays: per-node efficiency decays
with world size (``n**(beta-1)``), every scale change costs a
rendezvous window, restarts cost each job its measured
``rendezvous_restart`` price, input-bound jobs idle, and injected
incidents (a persistent ``slow_link``, a decaying ``cache_cold``)
degrade goodput until cured or ridden out.  Timestamps are synthetic
1s-spaced and anchored in the past (the r16/r17 drill pattern), so a
400-tick fleet day runs in seconds, deterministically.

Output (``--json-out``; nothing is written without it): per-mode fleet
goodput, the ``fleet_goodput_gain`` headline, the decision log, and the restart-vs-ride-out DRILL (one incident resolved
by ride-out with the incident engine confirming no restart, one by a
Brain-ordered restart, each chosen by the priced cost model).

CLI::

    JAX_PLATFORMS=cpu python -m dlrover_tpu.diagnosis.brain_bench
    python -m dlrover_tpu.diagnosis.brain_bench --smoke   # CI gate
"""

import argparse
import dataclasses
import json
import os
import random
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.constants import NodeStatus, NodeType
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.node import Node
# scoped env-knob override shared with the sibling drills
from dlrover_tpu.diagnosis.chaos_drill import _env

#: sim cadences (ticks are synthetic seconds)
DIGEST_TICKS = 5     # nodes write their digest every N ticks
BRAIN_TICKS = 10     # arbiter tick cadence
DETECT_LAG = 5       # degradation start -> incident open (sentinel lag)
RECONFIG_TICKS = 3   # rendezvous window a scale change costs


@dataclasses.dataclass
class JobSpec:
    name: str
    priority: int = 0
    min_nodes: int = 2
    max_nodes: int = 8
    node_unit: int = 1
    start_nodes: int = 2
    arrive_tick: int = 0
    depart_tick: int = -1  # -1 = stays to the end
    #: aggregate speed(n) = n**beta -> per-node efficiency n**(beta-1)
    beta: float = 0.9
    base_goodput: float = 0.9
    #: node-equivalents of input demand; None = compute-bound (busy 1.0)
    demand: Optional[float] = None
    #: ledger price of one rendezvous restart, sim seconds
    restart_s: float = 30.0
    model_params: int = 1_000_000_000


@dataclasses.dataclass
class IncidentSpec:
    job: str
    kind: str        # slow_link | cache_cold | ... (degradation kinds)
    tick: int
    degradation: float  # goodput fraction lost at full effect
    decay_ticks: int = 0  # 0 = persistent until cured by restart
    restart_cures: bool = True


def default_scenario(capacity: int = 16) -> Dict[str, Any]:
    """The churning bursty fleet the acceptance criteria describe:
    a well-scaling job with room to grow, an input-bound idler, a
    low-priority victim, a high-priority burst arrival, a late
    priority churn — plus one persistent and one decaying incident so
    the cost model must pick differently."""
    specs = [
        JobSpec("scaler", priority=1, min_nodes=2, max_nodes=8,
                start_nodes=2, beta=0.92, base_goodput=0.9,
                restart_s=25.0, model_params=7_000_000_000),
        JobSpec("idler", priority=0, min_nodes=2, max_nodes=6,
                start_nodes=4, beta=0.85, base_goodput=0.9,
                demand=1.2, model_params=1_000_000_000),
        JobSpec("victim", priority=0, min_nodes=2, max_nodes=8,
                start_nodes=8, beta=0.8, base_goodput=0.75,
                model_params=2_000_000_000),
        JobSpec("burst", priority=5, min_nodes=4, max_nodes=6,
                start_nodes=0, arrive_tick=100,
                beta=0.9, base_goodput=0.9,
                model_params=3_000_000_000),
    ]
    incidents = [
        # persistent link degradation on the scaler: restart (replace
        # the flaky node) is priced cheaper than riding it out
        IncidentSpec("scaler", "slow_link", tick=150,
                     degradation=0.5, decay_ticks=0,
                     restart_cures=True),
        # transient cold cache on the victim: decays on its own, so
        # the cost model must choose ride-out
        IncidentSpec("victim", "cache_cold", tick=200,
                     degradation=0.06, decay_ticks=120,
                     restart_cures=True),
    ]
    churn = [
        # late priority churn: the idler becomes important (exercises
        # snapshot churn; preemption already happened for the burst)
        {"tick": 280, "job": "idler", "priority": 3},
    ]
    return {"capacity": capacity, "specs": specs,
            "incidents": incidents, "churn": churn}


class SimJob:
    """One simulated job over the REAL ingestion objects."""

    def __init__(self, spec: JobSpec, incident_root: str,
                 rng: random.Random):
        from dlrover_tpu.master.job_context import JobContext
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.incidents import IncidentManager

        self.spec = spec
        self.rng = rng
        self.store = TimeSeriesStore()
        self.ctx = JobContext()
        self.ctx.job_name = spec.name
        self.incidents = IncidentManager(
            root=os.path.join(incident_root, spec.name),
            job_context=self.ctx,
        )
        self.nodes: List[int] = []
        self._next_node_id = 0
        self.target = spec.start_nodes
        self.restart_remaining = 0
        self.restarts = 0
        self.restart_ticks_total = 0
        self.departed = False
        #: nodes released by preempt deliveries since the last pool
        #: collection (the fleet credits them back each tick)
        self.pending_released = 0
        #: kind -> {"start": tick, "spec": IncidentSpec}
        self.effects: Dict[str, Dict[str, Any]] = {}
        # per-node cumulative ledger counters (the digest payload)
        self._gp: Dict[int, Dict[str, float]] = {}
        self.goodput_now = 0.0
        self.productive = 0.0  # Σ goodput * nodes over ticks

    # -- membership ---------------------------------------------------------

    def _add_node(self) -> None:
        node_id = self._next_node_id
        self._next_node_id += 1
        self.nodes.append(node_id)
        self.ctx.update_job_node(
            Node(NodeType.WORKER, node_id, status=NodeStatus.RUNNING)
        )
        self._gp[node_id] = {
            "compute": 0.0, "exposed_comm": 0.0,
            "rendezvous_restart": 0.0, "idle_unknown": 0.0,
            "wall": 0.0,
        }

    def _drop_node(self, node_id: int) -> None:
        if node_id in self.nodes:
            self.nodes.remove(node_id)
        self.ctx.remove_job_node(NodeType.WORKER, node_id)
        self.store.evict_node(node_id)
        self._gp.pop(node_id, None)

    def release_all(self) -> int:
        released = len(self.nodes)
        for node_id in list(self.nodes):
            self._drop_node(node_id)
        self.target = 0
        return released

    def set_target(self, target: int) -> None:
        self.target = max(0, int(target))

    # -- incident effects ---------------------------------------------------

    def degradation(self, tick: int) -> float:
        total = 0.0
        for effect in self.effects.values():
            spec: IncidentSpec = effect["spec"]
            age = tick - effect["start"]
            if spec.decay_ticks > 0:
                total += max(
                    0.0,
                    spec.degradation * (1.0 - age / spec.decay_ticks),
                )
            else:
                total += spec.degradation
        return min(0.9, total)

    def restart(self, tick: int) -> None:
        """A restart_worker delivery: pay the rendezvous window, cure
        the curable effects."""
        self.restart_remaining = max(
            self.restart_remaining, int(self.spec.restart_s)
        )
        self.restarts += 1
        for kind in [
            k for k, e in self.effects.items()
            if e["spec"].restart_cures
        ]:
            self.effects.pop(kind, None)

    # -- one sim tick -------------------------------------------------------

    def drain_actions(self, arbiter, tick: int) -> None:
        """Simulated-agent action loop: drain each node's queue the
        way ``ElasticAgent._monitor_workers`` does, ack brain ids."""
        restart_requested = False
        for node_id in list(self.nodes):
            acks: List[str] = []
            for action in self.ctx.next_actions(node_id):
                verb = action.get("action")
                extra = action.get("extra") or {}
                brain_id = (extra.get("brain") or {}).get("id", "")
                if brain_id:
                    acks.append(brain_id)
                if verb == "restart_worker":
                    restart_requested = True
                elif verb == "brain_preempt":
                    self._drop_node(node_id)
                    self.pending_released += 1
                    self.target = min(self.target, len(self.nodes))
                elif verb == "brain_scale_plan":
                    if extra.get("restart_workers"):
                        self.restart_remaining = max(
                            self.restart_remaining, RECONFIG_TICKS
                        )
                # flight_dump / brain_demote / events: no sim effect
            if acks and arbiter is not None:
                arbiter.on_ack(self.spec.name, node_id, acks)
        if restart_requested:
            self.restart(tick)

    def reconfigure(self, pool: int) -> int:
        """Move toward the target node count; returns the new pool."""
        if self.departed:
            return pool
        changed = False
        while len(self.nodes) > self.target:
            self._drop_node(self.nodes[-1])
            pool += 1
            changed = True
        while len(self.nodes) < self.target and pool > 0:
            self._add_node()
            pool -= 1
            changed = True
        if changed and self.nodes:
            # any world change pays a rendezvous window
            self.restart_remaining = max(
                self.restart_remaining, RECONFIG_TICKS
            )
        return pool

    def tick(self, tick: int, ts: float) -> None:
        n = len(self.nodes)
        if n == 0:
            self.goodput_now = 0.0
            return
        restarting = self.restart_remaining > 0
        if restarting:
            self.restart_remaining -= 1
            self.restart_ticks_total += 1
        eff = n ** (self.spec.beta - 1.0)
        busy = 1.0
        if self.spec.demand is not None:
            busy = min(1.0, self.spec.demand / n)
        degradation = self.degradation(tick)
        jitter = self.rng.uniform(-0.01, 0.01)
        compute = 0.0 if restarting else max(
            0.0, min(
                1.0,
                busy * eff * (1.0 - degradation)
                * self.spec.base_goodput + jitter,
            )
        )
        comm = 0.0 if restarting else max(0.0, busy - compute)
        idle = max(0.0, 1.0 - busy) if not restarting else 0.0
        rdzv = 1.0 if restarting else 0.0
        self.goodput_now = compute
        self.productive += compute * n
        for node_id in self.nodes:
            gp = self._gp[node_id]
            gp["compute"] += compute
            gp["exposed_comm"] += comm
            gp["idle_unknown"] += idle
            gp["rendezvous_restart"] += rdzv
            gp["wall"] += 1.0
            if tick % DIGEST_TICKS == 0:
                digest = {
                    f"gp_{k}": v for k, v in gp.items() if k != "wall"
                }
                digest["gp_wall"] = gp["wall"]
                digest["gp_seq"] = ts
                self.store.record_digest(node_id, digest, ts=ts)


class FleetSim:
    """One full scenario run in one mode."""

    def __init__(self, scenario: Dict[str, Any], brain_on: bool,
                 ticks: int = 400, seed: int = 0,
                 incident_root: Optional[str] = None):
        self.capacity = int(scenario["capacity"])
        self.specs: List[JobSpec] = list(scenario["specs"])
        self.incident_specs: List[IncidentSpec] = list(
            scenario["incidents"]
        )
        self.churn: List[Dict[str, Any]] = list(
            scenario.get("churn") or []
        )
        self.brain_on = brain_on
        self.ticks = int(ticks)
        self.seed = int(seed)
        self.t0 = time.time() - self.ticks - 120.0
        self.jobs: Dict[str, SimJob] = {}
        self.pool = self.capacity
        self.arbiter = None
        self._incident_root = incident_root or tempfile.mkdtemp(
            prefix="brain_bench_incidents_"
        )
        self.decisions: List[Dict[str, Any]] = []

    def _handle(self, job: SimJob):
        from dlrover_tpu.brain.fleet_state import JobHandle

        spec = job.spec
        return JobHandle(
            spec.name,
            timeseries=job.store,
            job_context=job.ctx,
            incident_manager=job.incidents,
            priority=spec.priority,
            min_nodes=spec.min_nodes,
            max_nodes=spec.max_nodes,
            node_unit=spec.node_unit,
            model_params=spec.model_params,
            scaler=job.set_target,
            restart_price_fn=lambda: job.spec.restart_s,
        )

    def _arrive(self, spec: JobSpec, tick: int) -> None:
        rng = random.Random(
            (self.seed * 1_000_003 + hash(spec.name)) & 0xFFFFFFFF
        )
        job = SimJob(spec, self._incident_root, rng)
        self.jobs[spec.name] = job
        if self.brain_on:
            job.target = spec.start_nodes
            self.arbiter.register_job(self._handle(job))
        else:
            # static admission: first-come, free pool only
            grant = min(
                spec.start_nodes or spec.min_nodes, self.pool
            )
            if spec.start_nodes == 0 and grant < spec.min_nodes:
                grant = 0  # arrival can't start below its minimum
            job.target = grant
        logger.info(
            "brain_bench t=%d: job %s arrives (priority %d)", tick,
            spec.name, spec.priority,
        )

    def run(self) -> Dict[str, Any]:
        if self.brain_on:
            from dlrover_tpu.brain.fleet_arbiter import FleetArbiter

            self.arbiter = FleetArbiter(capacity=self.capacity)
        capacity_seconds = 0.0
        productive = 0.0
        weighted = 0.0
        weighted_capacity = 0.0
        for tick in range(self.ticks):
            ts = self.t0 + tick
            # arrivals / departures / priority churn
            for spec in self.specs:
                if spec.arrive_tick == tick:
                    self._arrive(spec, tick)
                if spec.depart_tick == tick and spec.name in self.jobs:
                    job = self.jobs[spec.name]
                    job.departed = True
                    self.pool += job.release_all()
                    if self.brain_on:
                        self.arbiter.deregister_job(spec.name)
            for event in self.churn:
                if event["tick"] == tick:
                    spec_map = {s.name: s for s in self.specs}
                    spec_map[event["job"]].priority = event["priority"]
                    if self.brain_on:
                        handle = self.arbiter.state.handle(
                            event["job"]
                        )
                        if handle is not None:
                            handle.priority = event["priority"]
            # incident activations (degradation starts now; the
            # "sentinel" opens the incident DETECT_LAG later)
            for ispec in self.incident_specs:
                job = self.jobs.get(ispec.job)
                if job is None or job.departed:
                    continue
                if ispec.tick == tick:
                    job.effects[ispec.kind] = {
                        "start": tick, "spec": ispec,
                    }
                if ispec.tick + DETECT_LAG == tick:
                    job.incidents.open(
                        ispec.kind,
                        detail=(
                            f"simulated {ispec.kind} on {ispec.job} "
                            f"(degradation {ispec.degradation})"
                        ),
                        culprit=job.nodes[0] if job.nodes else -1,
                        broadcast=False,
                        opened_ts=ts,
                    )
            # job ticks: actions -> reconfigure -> produce
            for name in sorted(self.jobs):
                job = self.jobs[name]
                if job.departed:
                    continue
                job.drain_actions(self.arbiter, tick)
                self.pool += job.pending_released
                job.pending_released = 0
            for name in sorted(self.jobs):
                job = self.jobs[name]
                if job.departed:
                    continue
                self.pool = job.reconfigure(self.pool)
            for name in sorted(self.jobs):
                job = self.jobs[name]
                if job.departed:
                    continue
                job.tick(tick, ts)
                weight = 1.0 + job.spec.priority
                productive += job.goodput_now * len(job.nodes)
                weighted += (
                    job.goodput_now * len(job.nodes) * weight
                )
            capacity_seconds += self.capacity
            weighted_capacity += self.capacity
            # the closed loop
            if self.brain_on and tick % BRAIN_TICKS == 0 and tick > 0:
                for decision in self.arbiter.tick(now=ts):
                    self.decisions.append(decision.to_dict())
        fleet_goodput = (
            productive / capacity_seconds if capacity_seconds else 0.0
        )
        weighted_goodput = (
            weighted / weighted_capacity if weighted_capacity else 0.0
        )
        return {
            "mode": "brain" if self.brain_on else "static",
            "fleet_goodput": round(fleet_goodput, 6),
            "weighted_goodput": round(weighted_goodput, 6),
            "jobs": {
                name: {
                    "final_nodes": len(job.nodes),
                    "restarts": job.restarts,
                    "restart_ticks": job.restart_ticks_total,
                    "productive_node_s": round(job.productive, 1),
                    "incidents": [
                        {
                            "incident_id": e.get("incident_id"),
                            "kind": e.get("kind"),
                            "brain_decision": (
                                e.get("annotations") or {}
                            ).get("brain_decision"),
                        }
                        for e in job.incidents.list_incidents()
                    ],
                }
                for name, job in sorted(self.jobs.items())
            },
            "decisions": self.decisions,
            "decision_counts": _count_decisions(self.decisions),
        }


def _count_decisions(decisions: List[Dict[str, Any]]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for decision in decisions:
        counts[decision.get("kind", "?")] = counts.get(
            decision.get("kind", "?"), 0
        ) + 1
    return counts


def _drill_verdicts(brain_result: Dict[str, Any]) -> Dict[str, Any]:
    """The restart-vs-ride-out drill: find the two arbitrated
    incidents and report what the incident engine confirms."""
    out: Dict[str, Any] = {"ride_out": None, "restart": None}
    for name, job in brain_result["jobs"].items():
        for incident in job["incidents"]:
            decision = incident.get("brain_decision")
            if not decision:
                continue
            entry = {
                "job": name,
                "incident_id": incident.get("incident_id"),
                "kind": incident.get("kind"),
                "cost": decision.get("cost"),
                "restarts": job["restarts"],
            }
            if decision.get("action") == "ride_out":
                out["ride_out"] = entry
            elif decision.get("action") == "restart":
                out["restart"] = entry
    return out


def run_bench(ticks: int = 400, seed: int = 0,
              capacity: int = 16) -> Dict[str, Any]:
    """Both modes over one scenario; the comparison is the headline."""
    overrides = {
        # sim seconds drive the arbiter's cooldown/horizon windows
        "DLROVER_TPU_BRAIN_COOLDOWN_S": "30",
        "DLROVER_TPU_BRAIN_RIDEOUT_HORIZON_S": "300",
        "DLROVER_TPU_INCIDENT_COOLDOWN_S": "1",
        # the bench asserts tracked-delivery on its own cadence
        "DLROVER_TPU_BRAIN_ACK_TIMEOUT_S": "3600",
    }
    with _env(**overrides):
        static = FleetSim(
            default_scenario(capacity), brain_on=False, ticks=ticks,
            seed=seed,
        ).run()
        brain = FleetSim(
            default_scenario(capacity), brain_on=True, ticks=ticks,
            seed=seed,
        ).run()
    gain = (
        brain["fleet_goodput"] / static["fleet_goodput"]
        if static["fleet_goodput"] > 0 else None
    )
    weighted_gain = (
        brain["weighted_goodput"] / static["weighted_goodput"]
        if static["weighted_goodput"] > 0 else None
    )
    return {
        "ticks": ticks,
        "seed": seed,
        "capacity": capacity,
        "modes": {"static": static, "brain": brain},
        "fleet_goodput_gain": round(gain, 4) if gain else None,
        "weighted_goodput_gain": (
            round(weighted_gain, 4) if weighted_gain else None
        ),
        "drill": _drill_verdicts(brain),
        "ts": round(time.time(), 1),
    }


def assert_bench(result: Dict[str, Any]) -> List[str]:
    """The acceptance assertions (shared by the smoke gate and
    tests)."""
    problems: List[str] = []
    gain = result.get("fleet_goodput_gain")
    if not gain or gain <= 1.0:
        problems.append(
            f"Brain-on did not beat static allocation: gain={gain}"
        )
    brain = result["modes"]["brain"]
    counts = brain["decision_counts"]
    if not counts.get("grow"):
        problems.append("no grow decision")
    if not counts.get("preempt"):
        problems.append("no preempt decision")
    drill = result["drill"]
    ride = drill.get("ride_out")
    restart = drill.get("restart")
    if not ride:
        problems.append("no incident resolved by ride-out")
    else:
        if ride["restarts"] != 0:
            problems.append(
                f"ride-out job {ride['job']} restarted "
                f"{ride['restarts']} time(s) — not a ride-out"
            )
        cost = ride.get("cost") or {}
        if not (
            cost.get("cost_rideout_gps", 0)
            <= cost.get("cost_restart_gps", 0)
        ):
            problems.append(
                f"ride-out not chosen by price: {cost}"
            )
    if not restart:
        problems.append("no incident resolved by Brain-ordered restart")
    else:
        if restart["restarts"] < 1:
            problems.append(
                f"restart-decided job {restart['job']} never restarted"
            )
        cost = restart.get("cost") or {}
        if not (
            cost.get("cost_restart_gps", 1e9)
            < cost.get("cost_rideout_gps", 0)
        ):
            problems.append(
                f"restart not chosen by price: {cost}"
            )
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--ticks", type=int, default=400)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--capacity", type=int, default=16)
    parser.add_argument("--json-out", default="")
    parser.add_argument(
        "--smoke", action="store_true",
        help="CI gate: assert the acceptance criteria, nonzero exit "
        "on violation",
    )
    args = parser.parse_args(argv)
    result = run_bench(
        ticks=args.ticks, seed=args.seed, capacity=args.capacity
    )
    problems = assert_bench(result)
    result["assertions"] = {
        "ok": not problems, "problems": problems,
    }
    payload = json.dumps(result, indent=2, default=str)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(payload)
    print(payload)
    if args.smoke and problems:
        print("BRAIN BENCH VIOLATIONS:", *problems, sep="\n  ",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
