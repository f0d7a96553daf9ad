"""Simulated-fleet load harness for the master control plane.

The master is one process coordinating every agent in a job; its scale
story is coordination throughput, not gradient math — and unlike
TPU-kernel perf, it is fully benchmarkable on CPU.  This harness drives
1k–10k lightweight agent clients through the REAL
:class:`MasterServicer` (in-process by default; ``--transport
http|grpc`` exercises the real wire) running the same call sequence a
real agent runs: rendezvous join + world wait, kv set/get/wait,
counter barriers, heartbeats, and shard lease/complete.

Two modes, same workload, same convergence:

* ``poll`` — the legacy client behavior (``DLROVER_TPU_LONGPOLL=0``):
  kv waits probe every 0.5s, rendezvous and shard waits every 1s, no
  envelope batching.
* ``longpoll`` — the r11 protocol: server-side Condition long-polls
  (kv/rendezvous/shard), batched shard leases + completions, and
  coalesced envelopes (heartbeat bursts, barrier add+wait) in one
  BatchRequest.

The report carries per-RPC p50/p99 client latency, total transport RPC
count (the ≥10x-reduction headline), rendezvous convergence time,
shards/s, admission-control overloads, coalesced waits, peak thread
count, and RED-registry snapshots taken before/after each mode.

CLI::

    JAX_PLATFORMS=cpu python -m dlrover_tpu.diagnosis.fleet_bench \
        --agents 1000 --mode both
    python -m dlrover_tpu.diagnosis.fleet_bench --smoke   # CI gate
    python -m dlrover_tpu.diagnosis.fleet_bench --agents 10000 \
        --workload storm                                  # overload run

``--workload full`` (default) runs one thread per agent through the
whole rendezvous+barrier sequence; ``--workload storm`` replays many
short agent *sessions* over a bounded thread pool — the 10k-client
shape, where admission control (not thread count) must bound p99.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import random
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeType, RendezvousName
from dlrover_tpu.common.log import logger
# scoped env-knob override shared with the sibling drill
from dlrover_tpu.diagnosis.chaos_drill import _env
from dlrover_tpu.observability import metrics as obs_metrics

_DATASET = "fleet_ds"


@dataclasses.dataclass
class FleetConfig:
    agents: int = 200
    mode: str = "longpoll"  # poll | longpoll
    transport: str = "local"  # local | http | grpc
    workload: str = "full"  # full | storm
    seed: int = 0
    # full-workload shape
    stagger_s: float = 1.0  # join arrival spread
    barriers: int = 2
    barrier_delay_s: float = 1.5  # per-phase "compute" arrival spread
    heartbeats: int = 2
    shards_per_agent: int = 2
    shard_batch: int = 8
    straggler_s: float = 2.0  # last agent's slow shard (tail wait)
    rdzv_timeout_s: float = 120.0
    wait_timeout_s: float = 120.0
    # storm-workload shape
    fanout: int = 256  # concurrent driver threads
    # timeouts
    agent_deadline_s: float = 300.0
    # multi-slice topology (r18): agents split into this many pod
    # slices (DCN domains); each joins with its slice_id and node_unit
    # = agents//slices, so the master must seal a slice-contiguous
    # world with whole-slice truncation
    slices: int = 1

    def hosts_per_slice(self) -> int:
        return max(1, self.agents // max(1, self.slices))

    def slice_of(self, agent: int) -> int:
        return agent // self.hosts_per_slice() if self.slices > 1 else 0


#: the headline >=500-agent workload shape: wait-dominated coordination,
#: the regime the control plane actually lives in at fleet scale.
#: One shape for every caller of the CLI preset below, so that "1k
#: headline" results stay comparable.
HEADLINE_SHAPE = dict(
    stagger_s=10.0, barriers=5, barrier_delay_s=20.0,
    heartbeats=6, shards_per_agent=2, straggler_s=10.0,
)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


class _Recorder:
    """Thread-safe per-RPC sample sink + per-agent outcomes.

    Latency is bucketed into *service* RPCs (answered as fast as the
    master can) and *wait* RPCs (long-polls that block by design —
    their duration is coordination time, not service time).  The
    harness marks wait sections explicitly via :meth:`waiting`, so the
    p99 SLO is asserted over what the master can actually control."""

    def __init__(self):
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.durations_ms: List[float] = []
        self.wait_durations_ms: List[float] = []
        self.rpc_total = 0
        self.rpc_failures = 0
        self.by_method: Dict[str, int] = {}
        self.agent_errors: List[str] = []
        self.convergence_s: List[float] = []
        self.shards_done = 0
        self.baseline_threads = threading.active_count()
        self.peak_threads = 0

    @contextlib.contextmanager
    def waiting(self):
        """RPCs issued inside this block are expected to long-poll."""
        self._tls.wait = True
        try:
            yield
        finally:
            self._tls.wait = False

    def on_rpc(self, method: str, dur_s: float, ok: bool) -> None:
        is_wait = getattr(self._tls, "wait", False)
        with self._mu:
            self.rpc_total += 1
            if is_wait:
                self.wait_durations_ms.append(dur_s * 1000.0)
            else:
                self.durations_ms.append(dur_s * 1000.0)
            self.by_method[method] = self.by_method.get(method, 0) + 1
            if not ok:
                self.rpc_failures += 1

    def agent_error(self, agent: int, err: str) -> None:
        with self._mu:
            self.agent_errors.append(f"agent{agent}: {err[:200]}")

    def converged(self, dur_s: float) -> None:
        with self._mu:
            self.convergence_s.append(dur_s)

    def shards(self, n: int) -> None:
        with self._mu:
            self.shards_done += n

    def sample_threads(self) -> None:
        with self._mu:
            self.peak_threads = max(
                self.peak_threads, threading.active_count()
            )

    @staticmethod
    def _pcts(data: List[float]) -> Tuple[float, float]:
        if not data:
            return 0.0, 0.0
        data = sorted(data)
        p50 = data[len(data) // 2]
        p99 = data[min(len(data) - 1, int(len(data) * 0.99))]
        return round(p50, 3), round(p99, 3)

    def percentiles(self) -> Tuple[float, float, float, float]:
        """(service p50, service p99, wait p50, wait p99) in ms."""
        with self._mu:
            service = list(self.durations_ms)
            wait = list(self.wait_durations_ms)
        return self._pcts(service) + self._pcts(wait)




# ---------------------------------------------------------------------------
# master + transports
# ---------------------------------------------------------------------------


class _Master:
    """A real MasterServicer plus (optionally) a real wire transport."""

    def __init__(self, transport: str):
        from dlrover_tpu.master.rdzv_manager import (
            ElasticTrainingRendezvousManager,
        )
        from dlrover_tpu.master.servicer import MasterServicer

        self.rdzv = ElasticTrainingRendezvousManager()
        self.servicer = MasterServicer(
            rdzv_managers={self.rdzv.name: self.rdzv}
        )
        self.transport = transport
        self._server = None
        self.addr = ""
        if transport == "http":
            from dlrover_tpu.master.master_service import HttpMasterServer

            self._server = HttpMasterServer(0, self.servicer)
            self._server.start()
            self.addr = f"127.0.0.1:{self._server.port}"
        elif transport == "grpc":
            from dlrover_tpu.master.master_service import GrpcMasterServer

            self._server = GrpcMasterServer(0, self.servicer)
            self._server.start()
            self.addr = f"127.0.0.1:{self._server.port}"

    def client(self, node_id: int, recorder: _Recorder):
        from dlrover_tpu.agent.master_client import (
            GrpcMasterClient,
            HttpMasterClient,
            LocalMasterClient,
        )

        if self.transport == "http":
            client = HttpMasterClient(self.addr, node_id, NodeType.WORKER)
        elif self.transport == "grpc":
            client = GrpcMasterClient(self.addr, node_id, NodeType.WORKER)
        else:
            client = LocalMasterClient(
                self.servicer, node_id, NodeType.WORKER
            )
        client.on_rpc = recorder.on_rpc
        return client

    def stop(self):
        if self._server is not None:
            self._server.stop()


# ---------------------------------------------------------------------------
# the full agent workload (one thread per agent)
# ---------------------------------------------------------------------------


def _wait_counter(client, key: str, target: int, cfg: FleetConfig,
                  rec: _Recorder, batched_add: bool) -> None:
    """Counter barrier: arrive (+1) and wait for everyone.

    longpoll mode coalesces arrive+wait into ONE BatchRequest envelope
    whose wait item blocks server-side; poll mode is the legacy
    add-then-poll loop (kv_store_wait's own fallback path)."""
    if batched_add:
        with rec.waiting():
            replies = client.batch([
                comm.KVStoreAddRequest(key=key, amount=1),
                comm.KVStoreWaitRequest(
                    key=key, timeout=cfg.wait_timeout_s, min_value=target
                ),
            ])
            got = replies[1]
            if isinstance(got, comm.KeyValuePair) and got.value:
                return
            # chunk expired inside the envelope (stragglers beyond the
            # clamp): finish the wait with the plain long-poll primitive
            value = client.kv_store_wait(
                key, timeout=cfg.wait_timeout_s, min_value=target
            )
    else:
        client.kv_store_add(key, 1)
        value = client.kv_store_wait(
            key, timeout=cfg.wait_timeout_s, min_value=target
        )
    if not value:
        raise TimeoutError(f"barrier {key} timed out")


def _shard_loop(agent: int, client, cfg: FleetConfig,
                rec: _Recorder) -> None:
    """Lease and complete shards until the shared dataset drains."""
    straggler = agent == cfg.agents - 1 and cfg.straggler_s > 0
    deadline = time.time() + cfg.agent_deadline_s
    if cfg.mode == "longpoll":
        while time.time() < deadline:
            with rec.waiting():
                out = client.get_task_batch(
                    _DATASET, count=cfg.shard_batch,
                    wait_timeout=min(10.0, cfg.wait_timeout_s),
                )
            if out is None:  # pragma: no cover - same-version harness
                raise RuntimeError("master rejected batch protocol")
            tasks, finished = out
            if tasks:
                if straggler:
                    time.sleep(cfg.straggler_s)
                    straggler = False
                client.report_task_results(
                    _DATASET, [t.task_id for t in tasks]
                )
                rec.shards(len(tasks))
            elif finished:
                return
        raise TimeoutError("shard loop timed out")
    while time.time() < deadline:
        task = client.get_task(_DATASET)
        if task.task_id >= 0:
            if straggler:
                time.sleep(cfg.straggler_s)
                straggler = False
            client.report_task_result(_DATASET, task.task_id)
            rec.shards(1)
        elif task.task_type == "wait":
            time.sleep(1.0)
        else:
            return
    raise TimeoutError("shard loop timed out")


def _agent_full(agent: int, master: _Master, cfg: FleetConfig,
                rec: _Recorder) -> None:
    rng = random.Random(cfg.seed * 100003 + agent)
    client = master.client(agent, rec)
    try:
        time.sleep(rng.uniform(0.0, cfg.stagger_s))
        t0 = time.time()
        client.join_rendezvous(
            node_rank=agent, rdzv_name=RendezvousName.TRAINING,
            slice_id=cfg.slice_of(agent),
            node_unit=cfg.hosts_per_slice() if cfg.slices > 1 else 1,
        )
        if cfg.mode == "longpoll":
            with rec.waiting():
                world = client.wait_comm_world(
                    RendezvousName.TRAINING, timeout=cfg.rdzv_timeout_s
                )
        else:
            world = comm.CommWorld()
            deadline = time.time() + cfg.rdzv_timeout_s
            while time.time() < deadline:  # the legacy agent loop
                world = client.get_comm_world(RendezvousName.TRAINING)
                if world.world:
                    break
                time.sleep(1.0)
        if not world.world:
            raise TimeoutError("rendezvous timed out")
        rec.converged(time.time() - t0)

        for b in range(cfg.barriers):
            # designed per-phase compute: arrivals spread over the delay
            time.sleep(rng.uniform(0.0, cfg.barrier_delay_s))
            _wait_counter(
                client, f"fleet/barrier/{b}", cfg.agents, cfg, rec,
                batched_add=cfg.mode == "longpoll",
            )

        if cfg.mode == "longpoll":
            # a heartbeat burst coalesces into one envelope
            payloads: List[Any] = []
            for h in range(cfg.heartbeats):
                payloads.append(
                    comm.HeartBeat(node_id=agent, timestamp=time.time())
                )
                payloads.append(comm.ResourceStats(
                    cpu_percent=50.0, memory_mb=1024, step=h,
                ))
            client.batch(payloads)
        else:
            for h in range(cfg.heartbeats):
                client.report_heart_beat()
                client.report_resource_stats(
                    cpu_percent=50.0, memory_mb=1024, step=h
                )

        _shard_loop(agent, client, cfg, rec)

        _wait_counter(
            client, "fleet/exit", cfg.agents, cfg, rec,
            batched_add=cfg.mode == "longpoll",
        )
    except Exception as e:  # noqa: BLE001 - recorded, not fatal
        rec.agent_error(agent, f"{type(e).__name__}: {e}")
    finally:
        close = getattr(client, "close", None)
        if close is not None:
            close()


# ---------------------------------------------------------------------------
# the storm workload (many short sessions over a bounded pool)
# ---------------------------------------------------------------------------


def _storm_session(session: int, master: _Master, cfg: FleetConfig,
                   rec: _Recorder) -> None:
    client = master.client(session, rec)
    try:
        key = f"storm/{session % 64}"
        if cfg.mode == "longpoll":
            replies = client.batch([
                comm.KeyValuePair(key=key, value=b"x"),
                comm.KVStoreGetRequest(key=key),
                comm.HeartBeat(node_id=session, timestamp=time.time()),
                comm.ResourceStats(cpu_percent=10.0, memory_mb=256),
            ])
            if not replies:
                raise RuntimeError("empty batch reply")
            out = client.get_task_batch(_DATASET, count=cfg.shard_batch)
            if out is not None and out[0]:
                client.report_task_results(
                    _DATASET, [t.task_id for t in out[0]]
                )
                rec.shards(len(out[0]))
        else:
            client.kv_store_set(key, b"x")
            client.kv_store_get(key)
            client.report_heart_beat()
            client.report_resource_stats(cpu_percent=10.0, memory_mb=256)
            task = client.get_task(_DATASET)
            if task.task_id >= 0:
                client.report_task_result(_DATASET, task.task_id)
                rec.shards(1)
    except Exception as e:  # noqa: BLE001
        rec.agent_error(session, f"{type(e).__name__}: {e}")
    finally:
        close = getattr(client, "close", None)
        if close is not None:
            close()


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------


def _red_slice() -> Dict[str, Any]:
    """The control-plane subset of the RED snapshot (the fleet report
    keeps the attributable counters)."""
    snap = obs_metrics.registry().snapshot()
    keep = (
        "dlrover_tpu_rpc_requests_total",
        "dlrover_tpu_servicer_overload_total",
        "dlrover_tpu_longpoll_coalesced_total",
        "dlrover_tpu_retry_total",
    )
    out: Dict[str, Any] = {}
    for table in ("counters", "gauges"):
        for name, series in snap.get(table, {}).items():
            if name in keep:
                out[name] = series
    return out


def _counter_total(snap: Dict[str, Any], name: str,
                   needle: str = "") -> float:
    return sum(
        v for labels, v in snap.get(name, {}).items() if needle in labels
    )


def run_mode(cfg: FleetConfig) -> Dict[str, Any]:
    """One fleet pass in one mode; returns its metrics dict."""
    if cfg.slices > 1 and cfg.agents % cfg.slices:
        # a remainder would assign trailing agents an out-of-range
        # slice index — a phantom partial slice that can only fail the
        # multi-slice verification; demand a clean split up front
        raise ValueError(
            f"agents={cfg.agents} not divisible into {cfg.slices} "
            "slices"
        )
    rec = _Recorder()
    master = _Master(cfg.transport)
    master.rdzv.update_rdzv_params(
        cfg.agents, cfg.agents, waiting_timeout=2.0,
        node_unit=cfg.hosts_per_slice() if cfg.slices > 1 else 1,
    )
    master.servicer.task_manager.new_dataset(
        batch_size=1,
        dataset_size=cfg.agents * cfg.shards_per_agent,
        dataset_name=_DATASET,
        num_epochs=1,
        num_minibatches_per_shard=1,
    )
    red_before = _red_slice()
    stop_sampling = threading.Event()

    def _sampler():
        while not stop_sampling.is_set():
            rec.sample_threads()
            stop_sampling.wait(0.2)

    sampler = threading.Thread(
        target=_sampler, daemon=True, name="fleet-sampler"
    )
    env = {"DLROVER_TPU_LONGPOLL": "1" if cfg.mode == "longpoll" else "0"}
    t0 = time.time()
    old_stack = threading.stack_size()
    try:
        with _env(**env):
            # thousands of mostly-blocked threads: shrink stacks so the
            # fleet fits comfortably in one process
            try:
                threading.stack_size(512 * 1024)
            except (ValueError, RuntimeError):
                pass
            sampler.start()
            if cfg.workload == "storm":
                _run_storm(master, cfg, rec)
            else:
                threads = [
                    threading.Thread(
                        target=_agent_full, args=(i, master, cfg, rec),
                        name=f"fleet-agent-{i}", daemon=True,
                    )
                    for i in range(cfg.agents)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(cfg.agent_deadline_s)
    finally:
        try:
            threading.stack_size(old_stack)
        except (ValueError, RuntimeError):
            pass
        stop_sampling.set()
        slice_report = None
        if cfg.slices > 1:
            try:
                slice_report = _slice_report(master, cfg)
            except Exception as e:  # noqa: BLE001 - report, not fatal
                slice_report = {"error": f"{type(e).__name__}: {e}"}
        master.stop()
    wall = time.time() - t0
    red_after = _red_slice()
    p50, p99, wait_p50, wait_p99 = rec.percentiles()
    lease_p50 = lease_p99 = peak_backlog = None
    try:
        telemetry = getattr(master.servicer, "shard_telemetry", None)
        if telemetry is not None:
            telemetry.flush()
            data = telemetry.summary()
            lease_p50 = data.get("lease_p50_ms")
            lease_p99 = data.get("lease_p99_ms")
            peak_backlog = data.get("peak_backlog")
    except Exception:  # noqa: BLE001 - telemetry is a report, not the bench
        pass
    overloads = (
        _counter_total(red_after, "dlrover_tpu_servicer_overload_total")
        - _counter_total(red_before, "dlrover_tpu_servicer_overload_total")
    )
    coalesced = (
        _counter_total(red_after, "dlrover_tpu_longpoll_coalesced_total")
        - _counter_total(red_before, "dlrover_tpu_longpoll_coalesced_total")
    )
    server_errors = (
        _counter_total(
            red_after, "dlrover_tpu_rpc_requests_total", 'code="error"'
        )
        - _counter_total(
            red_before, "dlrover_tpu_rpc_requests_total", 'code="error"'
        )
    )
    return {
        "mode": cfg.mode,
        "wall_s": round(wall, 3),
        "rpc_total": rec.rpc_total,
        "rpc_per_agent": round(rec.rpc_total / max(1, cfg.agents), 2),
        "rpc_transport_failures": rec.rpc_failures,
        "server_error_responses": server_errors,
        "agent_errors": rec.agent_errors[:20],
        "agent_error_count": len(rec.agent_errors),
        "p50_ms": p50,
        "p99_ms": p99,
        "wait_p50_ms": wait_p50,
        "wait_p99_ms": wait_p99,
        "rdzv_convergence_s": round(max(rec.convergence_s), 3)
        if rec.convergence_s else None,
        "shards_done": rec.shards_done,
        "shards_per_s": round(rec.shards_done / wall, 1) if wall else 0.0,
        "lease_p50_ms": lease_p50,
        "lease_p99_ms": lease_p99,
        "peak_backlog": peak_backlog,
        "overload_responses": overloads,
        "coalesced_waits": coalesced,
        "peak_threads": rec.peak_threads,
        "peak_thread_growth": max(0, rec.peak_threads - rec.baseline_threads),
        "rpc_by_method": dict(
            sorted(rec.by_method.items(), key=lambda kv: -kv[1])[:12]
        ),
        "slices": slice_report,
        "red_before": red_before,
        "red_after": red_after,
    }


def _slice_report(master: "_Master", cfg: FleetConfig) -> Dict[str, Any]:
    """Verify the sealed world's multi-slice topology: every slice
    present at full strength, each slice's world ranks CONTIGUOUS (the
    SliceContiguousSorter invariant the two-level mesh layout rides),
    and every member's NodeMeta carrying the slice_id it joined with."""
    groups = master.rdzv.slice_groups()
    world = master.rdzv._latest_rdzv_nodes  # noqa: SLF001 - bench
    contiguous = all(
        ranks == list(range(ranks[0], ranks[0] + len(ranks)))
        for ranks in groups.values() if ranks
    )
    ids_consistent = all(
        cfg.slice_of(meta.node_id) == meta.slice_id
        for meta in world.values()
    )
    return {
        "count": len(groups),
        "expected": cfg.slices,
        "group_sizes": {s: len(r) for s, r in sorted(groups.items())},
        "hosts_per_slice": cfg.hosts_per_slice(),
        "contiguous_ranks": contiguous,
        "slice_ids_consistent": ids_consistent,
        "ok": (
            len(groups) == cfg.slices
            and contiguous
            and ids_consistent
            and all(
                len(r) == cfg.hosts_per_slice() for r in groups.values()
            )
        ),
    }


def _run_storm(master: _Master, cfg: FleetConfig, rec: _Recorder) -> None:
    """Replay cfg.agents short sessions over cfg.fanout driver threads."""
    counter = {"next": 0}
    mu = threading.Lock()

    def _driver():
        while True:
            with mu:
                session = counter["next"]
                if session >= cfg.agents:
                    return
                counter["next"] = session + 1
            _storm_session(session, master, cfg, rec)

    drivers = [
        threading.Thread(target=_driver, daemon=True, name=f"storm-{d}")
        for d in range(min(cfg.fanout, cfg.agents))
    ]
    for d in drivers:
        d.start()
    for d in drivers:
        d.join(cfg.agent_deadline_s)


def run_fleet(cfg: FleetConfig, modes: Optional[List[str]] = None
              ) -> Dict[str, Any]:
    """Run the workload in the requested modes (same shape, same
    convergence) and fold in the poll/longpoll comparison."""
    modes = modes or ["poll", "longpoll"]
    result: Dict[str, Any] = {
        "agents": cfg.agents,
        "transport": cfg.transport,
        "workload": cfg.workload,
        "seed": cfg.seed,
        "shape": {
            "stagger_s": cfg.stagger_s,
            "barriers": cfg.barriers,
            "barrier_delay_s": cfg.barrier_delay_s,
            "heartbeats": cfg.heartbeats,
            "shards_per_agent": cfg.shards_per_agent,
            "shard_batch": cfg.shard_batch,
            "straggler_s": cfg.straggler_s,
            "fanout": cfg.fanout,
        },
        "modes": {},
    }
    for mode in modes:
        run_cfg = dataclasses.replace(cfg, mode=mode)
        logger.info(
            "fleet_bench: %d agents, %s workload, %s transport, %s mode",
            cfg.agents, cfg.workload, cfg.transport, mode,
        )
        result["modes"][mode] = run_mode(run_cfg)
    poll = result["modes"].get("poll")
    lp = result["modes"].get("longpoll")
    if poll and lp and lp["rpc_total"]:
        result["rpc_reduction"] = round(
            poll["rpc_total"] / lp["rpc_total"], 2
        )
    return result


# ---------------------------------------------------------------------------
# CLI + SLO gate
# ---------------------------------------------------------------------------


def _assert_slo(result: Dict[str, Any], min_reduction: float,
                p99_ms: float) -> List[str]:
    """The CI smoke's SLOs, asserted from the harness report."""
    violations = []
    for mode, stats in result["modes"].items():
        slices = stats.get("slices")
        if slices is not None and not slices.get("ok"):
            violations.append(
                f"{mode}: multi-slice rendezvous verification failed: "
                f"{slices}"
            )
        if stats["agent_error_count"]:
            violations.append(
                f"{mode}: {stats['agent_error_count']} agent errors "
                f"(first: {stats['agent_errors'][:1]})"
            )
        if stats["server_error_responses"]:
            violations.append(
                f"{mode}: {stats['server_error_responses']} server "
                "error responses"
            )
        if stats["rpc_transport_failures"]:
            violations.append(
                f"{mode}: {stats['rpc_transport_failures']} transport "
                "failures"
            )
    lp = result["modes"].get("longpoll")
    if lp and p99_ms and lp["p99_ms"] > p99_ms:
        violations.append(
            f"longpoll p99 {lp['p99_ms']}ms > SLO {p99_ms}ms"
        )
    reduction = result.get("rpc_reduction", 0)
    if min_reduction and reduction and reduction < min_reduction:
        violations.append(
            f"rpc_reduction {reduction}x < required {min_reduction}x"
        )
    return violations


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--agents", type=int, default=1000)
    parser.add_argument("--mode", default="both",
                        choices=["poll", "longpoll", "both"])
    parser.add_argument("--transport", default="local",
                        choices=["local", "http", "grpc"])
    parser.add_argument("--workload", default="full",
                        choices=["full", "storm"])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--stagger-s", type=float, default=None)
    parser.add_argument("--barriers", type=int, default=None)
    parser.add_argument("--barrier-delay-s", type=float, default=None)
    parser.add_argument("--heartbeats", type=int, default=None)
    parser.add_argument("--shards-per-agent", type=int, default=None)
    parser.add_argument("--straggler-s", type=float, default=None)
    parser.add_argument("--fanout", type=int, default=None)
    parser.add_argument(
        "--slices", type=int, default=1,
        help="split the agents into this many pod slices (DCN "
        "domains): each joins with its slice_id, the master must seal "
        "a slice-contiguous world (verified in the report)",
    )
    parser.add_argument("--json-out", default="")
    parser.add_argument(
        "--smoke", action="store_true",
        help="CI gate: 200 agents, small delays, SLO-asserted exit code",
    )
    parser.add_argument("--assert-reduction", type=float, default=0.0)
    parser.add_argument("--assert-p99-ms", type=float, default=0.0)
    args = parser.parse_args(argv)

    cfg = FleetConfig(
        agents=args.agents, transport=args.transport,
        workload=args.workload, seed=args.seed,
        slices=max(1, args.slices),
    )
    if args.smoke:
        cfg = dataclasses.replace(
            cfg, agents=200, stagger_s=1.0, barriers=2,
            barrier_delay_s=1.5, heartbeats=2, shards_per_agent=2,
            straggler_s=2.0, agent_deadline_s=120.0,
        )
        args.assert_reduction = args.assert_reduction or 2.0
        args.assert_p99_ms = args.assert_p99_ms or 500.0
    elif args.workload == "full" and args.agents >= 500:
        cfg = dataclasses.replace(cfg, **HEADLINE_SHAPE)
    for name in ("stagger_s", "barriers", "barrier_delay_s", "heartbeats",
                 "shards_per_agent", "straggler_s", "fanout"):
        value = getattr(args, name)
        if value is not None:
            cfg = dataclasses.replace(cfg, **{name: value})
    if cfg.slices > 1 and cfg.agents % cfg.slices:
        # validated on the FINAL shape: presets (--smoke's agents=200)
        # override the parsed agent count
        parser.error(
            f"agents={cfg.agents} must divide evenly into "
            f"--slices {cfg.slices}"
        )

    modes = ["poll", "longpoll"] if args.mode == "both" else [args.mode]
    result = run_fleet(cfg, modes)
    violations = _assert_slo(
        result, args.assert_reduction, args.assert_p99_ms
    )
    result["slo_violations"] = violations
    payload = json.dumps(result, indent=2, default=str)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(payload)
    print(payload)
    if violations:
        print("FLEET SLO VIOLATIONS:", *violations, sep="\n  ",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
