"""UnifiedPrimeMaster: multi-role job supervision with gang scheduling
and role-aware failover.

Counterpart of reference ``dlrover/python/unified/controller/manager.py``
(PrimeManager: create placement groups, schedule the execution graph,
role-aware restart/failover) + ``controller/schedule/scheduler.py``.  The
reference schedules Ray actors; on TPU the runtime is supervised OS
processes, so this master

- builds an :class:`~dlrover_tpu.unified.graph.ExecutionGraph` from the
  job spec's roles,
- runs ONE shared job master (rendezvous + KV + diagnosis) that every
  role can reach via ``DLROVER_TPU_MASTER_ADDR``,
- launches the ELASTIC role through the elastic agent stack (one agent
  per node — the same path ``tpurun`` uses) and SIMPLE roles as plain
  supervised processes with role/rank env wiring,
- enforces GANG start (all processes of a collocation group spawn
  together) and gang restart (a member failure restarts the whole group
  when its policy says so — reference node-group failover),
- applies per-role failover policy via :meth:`ExecutionGraph.on_failure`
  within per-role restart budgets,
- tears down daemon (service) roles once every gating role finished,
  and persists its view to the state backend on every transition.

The single-role :class:`~dlrover_tpu.unified.prime_master.PrimeMaster`
remains the thin path for plain elastic jobs; this class is the
multi-role superset the builder API submits to.
"""

import os
import subprocess
import sys
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.unified.graph import (
    ExecutionGraph,
    FailoverAction,
    RoleKind,
    RoleSpec,
    Vertex,
)
from dlrover_tpu.unified.prime_master import (
    _Supervised,
    _await_serving,
    _terminate_fleet,
)
from dlrover_tpu.unified.state import (
    FileStateBackend,
    JobPhase,
    JobStateBackend,
)


@dataclass
class UnifiedJobSpec:
    """A multi-role job: roles + gangs + job-wide env."""

    name: str = ""
    roles: Dict[str, RoleSpec] = field(default_factory=dict)
    env: Dict[str, str] = field(default_factory=dict)

    def validate(self):
        if not self.name:
            raise ValueError("job needs a name")
        if not self.roles:
            raise ValueError("job needs at least one role")
        for role in self.roles.values():
            if not role.entrypoint:
                raise ValueError(f"role {role.name!r} needs an entrypoint")
        if all(self.roles[r].daemon for r in self.roles):
            raise ValueError(
                "all roles are daemon services; nothing gates completion"
            )


class UnifiedPrimeMaster:
    """Supervise a :class:`UnifiedJobSpec` to completion."""

    def __init__(
        self,
        spec: UnifiedJobSpec,
        state_backend: Optional[JobStateBackend] = None,
        poll_secs: float = 1.0,
    ):
        spec.validate()
        self.spec = spec
        self.name = spec.name
        self.graph = ExecutionGraph(spec.roles)
        self._backend = state_backend or FileStateBackend()
        self._poll_secs = poll_secs
        self.phase = JobPhase.INIT
        self.exit_code: Optional[int] = None
        self.master: Optional[_Supervised] = None
        self.master_port: Optional[int] = None
        self.master_restarts = 0
        self.MASTER_RESTART_BUDGET = 3
        self._procs: Dict[str, _Supervised] = {}  # vertex name -> process
        self._stopped = threading.Event()
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        # vertices adopted after a driver restart whose exit codes are
        # unreapable (not our children): their deaths must not read as
        # failures, and a job that finishes on them ends STOPPED, not
        # SUCCEEDED (same liveness-only contract as PrimeMaster.attach)
        self._unreaped: set = set()

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def create(
        cls,
        spec: UnifiedJobSpec,
        state_backend: Optional[JobStateBackend] = None,
        poll_secs: float = 1.0,
    ) -> "UnifiedPrimeMaster":
        backend = state_backend or FileStateBackend()
        existing = backend.load(spec.name)
        if existing and existing.get("phase") not in JobPhase.terminal():
            # the shared master counts too: it runs with --hold and
            # never exits by itself, so a survivor here means the old
            # job's fabric is still serving its port
            survivors = [existing.get("master") or {}] + list(
                existing.get("procs", {}).values()
            )
            for proc in survivors:
                if proc and _Supervised.from_state(proc).alive():
                    raise RuntimeError(
                        f"job {spec.name!r} is already running "
                        f"(pid {proc['pid']} alive)"
                    )
        prime = cls(spec, backend, poll_secs)
        prime.start()
        return prime

    @classmethod
    def attach(
        cls,
        name: str,
        state_backend: Optional[JobStateBackend] = None,
        poll_secs: float = 1.0,
    ) -> "UnifiedPrimeMaster":
        """Driver self-recovery: adopt a multi-role job from persisted
        state (same contract as PrimeMaster.attach — no duplicate
        spawns; supervision resumes over the live pids)."""
        backend = state_backend or FileStateBackend()
        state = backend.load(name)
        if state is None:
            raise KeyError(f"no persisted state for job {name!r}")
        spec_state = state.get("spec") or {}
        known = set(RoleSpec.__dataclass_fields__)
        roles = {
            n: RoleSpec(**{k: v for k, v in r.items() if k in known})
            for n, r in (spec_state.get("roles") or {}).items()
        }
        spec = UnifiedJobSpec(
            name=name, roles=roles, env=spec_state.get("env") or {}
        )
        prime = cls(spec, backend, poll_secs)
        prime.phase = state["phase"]
        prime.master_port = state.get("master_port")
        prime.master_restarts = state.get("master_restarts", 0)
        prime.exit_code = state.get("exit_code")
        if state.get("master"):
            prime.master = _Supervised.from_state(state["master"])
        for vertex_name, proc_state in (state.get("procs") or {}).items():
            prime._procs[vertex_name] = _Supervised.from_state(proc_state)
        prime.graph.load_state(state.get("graph") or [])
        prime._unreaped = set(state.get("unreaped") or [])
        if prime.phase in JobPhase.terminal():
            for vertex in prime.graph.vertices:
                proc = prime._procs.get(vertex.name)
                vertex.running = bool(proc is not None and proc.alive())
            prime._done.set()
            return prime
        for vertex in prime.graph.vertices:
            proc = prime._procs.get(vertex.name)
            if proc is not None and proc.alive():
                vertex.running = True
                continue
            vertex.running = False
            if vertex.exit_code is not None:
                continue
            if proc is not None:
                # died while the driver was down: the code is
                # unreapable — liveness-only completion, never a hang
                # (a skipped not-running vertex would gate job_result
                # forever) and never a fabricated failure
                vertex.exit_code = 0
                prime._unreaped.add(vertex.name)
            else:
                # persisted before this vertex ever spawned (PREPARED
                # window): we own the job now — launch it
                prime._spawn_vertex(vertex)
        logger.info(
            "recovered multi-role job %s: phase=%s roles=%s",
            name, prime.phase, sorted(spec.roles),
        )
        prime._thread = threading.Thread(
            target=prime._monitor, daemon=True,
            name=f"unified-master-{name}",
        )
        prime._thread.start()
        return prime

    def start(self):
        self._spawn_shared_master()
        self.phase = JobPhase.PREPARED
        self._persist()
        # gang start: spawn groups atomically — every member of a gang
        # is launched before any other group, so collocated roles come
        # up together (reference placement-group gang scheduling)
        for gang_vertices in self._spawn_order():
            for vertex in gang_vertices:
                self._spawn_vertex(vertex)
        self.phase = JobPhase.RUNNING
        self._persist()
        self._thread = threading.Thread(
            target=self._monitor, daemon=True,
            name=f"unified-master-{self.name}",
        )
        self._thread.start()

    def _spawn_order(self) -> List[List[Vertex]]:
        """Vertices grouped by gang (ungrouped roles = their own group),
        gangs first so collocated fleets claim resources atomically."""
        seen = set()
        order: List[List[Vertex]] = []
        for gang, members in self.graph.gangs.items():
            order.append(members)
            seen.update(v.name for v in members)
        for v in self.graph.vertices:
            if v.name not in seen:
                order.append([v])
        return order

    # -- process spawning --------------------------------------------------

    def _repo(self) -> str:
        return os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))

    def _env(self, extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
        env = dict(os.environ)
        env["PYTHONPATH"] = self._repo() + os.pathsep + env.get(
            "PYTHONPATH", ""
        )
        env["DLROVER_TPU_JOB_NAME"] = self.name
        env.pop("DLROVER_TPU_MASTER_ADDR", None)
        env.update(self.spec.env)
        if extra:
            env.update(extra)
        return env

    def _spawn_shared_master(self):
        """One job master for the whole multi-role job: the elastic
        role's rendezvous/diagnosis brain AND the KV/sync fabric simple
        roles coordinate through."""
        import tempfile

        node_num = max(
            (r.total for r in self.spec.roles.values()
             if r.kind == RoleKind.ELASTIC),
            default=1,
        )
        fd, port_file = tempfile.mkstemp(prefix="dlunified_port_")
        os.close(fd)
        os.unlink(port_file)
        cmd = [
            sys.executable, "-m", "dlrover_tpu.master.main",
            "--platform", "local", "--job_name", self.name,
            "--node_num", str(node_num),
            "--port", "0", "--port_file", port_file,
            # multi-role: other roles still need the KV/sync fabric
            # after the elastic fleet finishes; we terminate the master
            # ourselves at teardown
            "--hold",
        ]
        self.master = _Supervised(
            subprocess.Popen(cmd, env=self._env(), cwd=self._repo())
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            if os.path.exists(port_file):
                content = open(port_file).read().strip()
                if content:
                    self.master_port = int(content)
                    os.unlink(port_file)
                    return
            if not self.master.alive():
                raise RuntimeError("shared job master failed to start")
            time.sleep(0.2)
        self.master.terminate()
        raise TimeoutError("shared job master did not start")

    def _recover_master(self) -> bool:
        """Respawn a dead shared master on its ORIGINAL port (clients
        reconnect; the KV store is rebuilt by the roles' next writes).
        Same bind-race-tolerant loop as PrimeMaster._recover_master.
        False when the budget is exhausted — the job is then FAILED and
        torn down."""
        node_num = max(
            (r.total for r in self.spec.roles.values()
             if r.kind == RoleKind.ELASTIC),
            default=1,
        )
        from dlrover_tpu.common.retry import respawn_policy

        policy = respawn_policy(name=f"shared-master-respawn[{self.name}]")
        gaps = policy.sleeps()
        while self.master_restarts < self.MASTER_RESTART_BUDGET:
            if self._stopped.is_set():
                return False
            self.master_restarts += 1
            logger.warning(
                "job %s: shared master died; restart %d/%d on port %s",
                self.name, self.master_restarts,
                self.MASTER_RESTART_BUDGET, self.master_port,
            )
            cmd = [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--platform", "local", "--job_name", self.name,
                "--node_num", str(node_num),
                "--port", str(self.master_port), "--hold",
            ]
            self.master = _Supervised(
                subprocess.Popen(cmd, env=self._env(), cwd=self._repo())
            )
            if _await_serving(
                self.master, self.master_port, self._stopped, timeout=60.0
            ):
                self._persist()
                return True
            self.master.terminate()
            # the restart budget (not the policy's attempt count) bounds
            # this loop; once the policy's schedule is exhausted keep
            # sleeping at its cap
            time.sleep(next(gaps, policy.max_s))
        logger.error(
            "job %s: shared master unrecoverable; failing the job",
            self.name,
        )
        self.phase = JobPhase.FAILED
        self.exit_code = self.exit_code or 1
        self._teardown_fleet()
        self._persist()
        return False

    def _spawn_vertex(self, vertex: Vertex):
        spec = self.spec.roles[vertex.role]
        if spec.kind == RoleKind.ELASTIC:
            proc = self._spawn_elastic_agent(spec, vertex.rank)
        else:
            proc = self._spawn_simple(spec, vertex.rank)
        self._procs[vertex.name] = proc
        vertex.running = True
        vertex.exit_code = None
        logger.info(
            "job %s: spawned %s (pid %d)", self.name, vertex.name, proc.pid
        )

    def _spawn_elastic_agent(self, spec: RoleSpec, rank: int) -> _Supervised:
        env = self._env({
            "DLROVER_TPU_NODE_ID": str(rank),
            "DLROVER_TPU_ROLE": spec.name,
            "DLROVER_TPU_ROLE_RANK": str(rank),
            "DLROVER_TPU_ROLE_WORLD": str(spec.total),
            **spec.env,
        })
        cmd = [
            sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
            f"--nnodes={spec.min_nodes or spec.total}:{spec.total}",
            f"--node-rank={rank}",
            f"--nproc_per_node={spec.nproc_per_node}",
            f"--node-unit={spec.node_unit}",
            f"--master-addr=localhost:{self.master_port}",
        ]
        if spec.network_check:
            cmd.append("--network-check")
        if spec.platform:
            cmd.append(f"--platform={spec.platform}")
        cmd.append(spec.entrypoint)
        cmd.extend(spec.args)
        return _Supervised(
            subprocess.Popen(cmd, env=env, cwd=self._repo())
        )

    def _spawn_simple(self, spec: RoleSpec, rank: int) -> _Supervised:
        """A plain role process: gets the shared master's address (KV
        store, sync, reporting) and its role/rank identity via env —
        the reference wires ActorInfo through Ray; we wire it through
        the environment (reference api/runtime/worker.py current_worker).
        """
        env = self._env({
            "DLROVER_TPU_MASTER_ADDR": f"localhost:{self.master_port}",
            "DLROVER_TPU_ROLE": spec.name,
            "DLROVER_TPU_ROLE_RANK": str(rank),
            "DLROVER_TPU_ROLE_WORLD": str(spec.total),
            "DLROVER_TPU_NODE_ID": str(rank),
            **spec.env,
        })
        if spec.platform:
            # both knobs: JAX_PLATFORMS for plain jax processes, and
            # DLROVER_TPU_PLATFORM for roles calling runtime.init()
            # (which pins through jax.config, see its docstring)
            env["JAX_PLATFORMS"] = spec.platform
            env["DLROVER_TPU_PLATFORM"] = spec.platform
        cmd = [sys.executable, spec.entrypoint, *spec.args]
        return _Supervised(
            subprocess.Popen(cmd, env=env, cwd=self._repo())
        )

    # -- supervision -------------------------------------------------------

    def _monitor(self):
        try:
            while not self._stopped.wait(self._poll_secs):
                with self._lock:
                    if self.phase in JobPhase.terminal():
                        break
                    if self._tick():
                        break
        except Exception:  # noqa: BLE001 - wait() must never hang forever
            logger.exception(
                "job %s: unified supervisor failed; marking FAILED",
                self.name,
            )
            with self._lock:
                if self.phase not in JobPhase.terminal():
                    self.phase = JobPhase.FAILED
                    self.exit_code = self.exit_code or 1
                # the fleet must die with the verdict: the --hold master
                # never exits by itself and role processes would leak
                self._teardown_fleet()
                try:
                    self._persist()
                except OSError:
                    pass
        finally:
            self._done.set()

    def _tick(self) -> bool:
        """One supervision pass; True when the job reached a terminal
        phase."""
        # the shared master is the KV/rendezvous fabric every role
        # depends on: a dead master must be recovered (same port, so
        # clients reconnect) before role failures cascade into it
        if self.master is not None and not self.master.alive():
            if not self._recover_master():
                return True
        changed = False
        for vertex in self.graph.vertices:
            proc = self._procs.get(vertex.name)
            if proc is None or not vertex.running:
                continue
            if proc.alive():
                continue
            vertex.running = False
            if proc.exit_code is not None:
                vertex.exit_code = proc.exit_code
            elif proc.popen is None:
                # adopted pid: the real code is unreapable — record a
                # liveness-only completion, never a fabricated failure
                vertex.exit_code = 0
                self._unreaped.add(vertex.name)
            else:
                vertex.exit_code = 1
            changed = True
            if vertex.failed:
                self._handle_failure(vertex)
                if self.phase in JobPhase.terminal():
                    self._persist()
                    return True
            else:
                logger.info("job %s: %s succeeded", self.name, vertex.name)
        result = self.graph.job_result()
        if result is not None:
            self.exit_code = result
            if result == 0 and self._unreaped:
                # finished on adopted processes: liveness-only view
                self.phase = JobPhase.STOPPED
            else:
                self.phase = (
                    JobPhase.SUCCEEDED if result == 0 else JobPhase.FAILED
                )
            logger.info(
                "job %s finished: exit=%s; stopping %d daemon/service "
                "process(es)", self.name, result,
                sum(1 for v in self.graph.vertices
                    if self.spec.roles[v.role].daemon),
            )
            self._teardown_fleet()
            self._persist()
            return True
        if changed:
            self._persist()
        return False

    def _handle_failure(self, vertex: Vertex):
        action = self.graph.on_failure(vertex)
        if action == FailoverAction.IGNORE:
            return
        if action == FailoverAction.FAIL_JOB:
            logger.error(
                "job %s: %s failed (exit %s); failing the job",
                self.name, vertex.name, vertex.exit_code,
            )
            self.phase = JobPhase.FAILED
            self.exit_code = vertex.exit_code or 1
            self._teardown_fleet()
            return
        members = (
            self.graph.gang_of(vertex)
            if action == FailoverAction.RESTART_GANG else [vertex]
        )
        # gang restart: stop survivors first so the group re-enters
        # together (a half-restarted gang would rendezvous against a
        # stale peer set)
        live = [
            self._procs[m.name] for m in members
            if m.name in self._procs and m.name != vertex.name
        ]
        if live:
            _terminate_fleet(live, grace_secs=5.0)
        for m in members:
            m.restart_count += 1
            m.running = False
        logger.warning(
            "job %s: %s failed (exit %s); %s restart %s",
            self.name, vertex.name, vertex.exit_code,
            "gang" if len(members) > 1 else "vertex",
            [m.name for m in members],
        )
        for m in members:
            self._spawn_vertex(m)

    def _teardown_fleet(self):
        procs = [
            self._procs[v.name] for v in self.graph.vertices
            if v.name in self._procs
        ]
        _terminate_fleet(procs + [self.master])

    # -- state / user API --------------------------------------------------

    def _persist(self):
        self._backend.save(
            self.name,
            {
                "spec": {
                    "name": self.spec.name,
                    "env": self.spec.env,
                    "roles": {
                        n: asdict(r) for n, r in self.spec.roles.items()
                    },
                },
                "phase": self.phase,
                "master_port": self.master_port,
                "master_restarts": self.master_restarts,
                "exit_code": self.exit_code,
                "master": self.master.to_state() if self.master else None,
                "procs": {
                    name: p.to_state() for name, p in self._procs.items()
                },
                "graph": self.graph.to_state(),
                "unreaped": sorted(self._unreaped),
                "updated": time.time(),
            },
        )

    def status(self) -> Dict:
        with self._lock:
            return {
                "name": self.name,
                "phase": self.phase,
                "master_port": self.master_port,
                "exit_code": self.exit_code,
                "roles": {
                    role: {
                        "alive": [
                            v.rank for v in self.graph.role_vertices(role)
                            if v.name in self._procs
                            and self._procs[v.name].alive()
                        ],
                        "restarts": sum(
                            v.restart_count
                            for v in self.graph.role_vertices(role)
                        ),
                        "failures": sum(
                            v.total_failures
                            for v in self.graph.role_vertices(role)
                        ),
                    }
                    for role in self.spec.roles
                },
            }

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        self._done.wait(timeout)
        return self.exit_code

    def stop(self):
        self._stopped.set()
        with self._lock:
            if self.phase not in JobPhase.terminal():
                self.phase = JobPhase.STOPPED
            self._teardown_fleet()
            self._persist()
        self._done.set()
