"""Typed registry of every ``DLROVER_TPU_*`` environment knob.

One owner for the repo's env surface: each knob is registered once with
a name, type, default, and doc string.  Call sites read through the
typed accessors (:func:`get_str` / :func:`get_int` / :func:`get_float` /
:func:`get_bool`), which

* read ``os.environ`` **at call time** (tests that monkeypatch env keep
  working; no import-order freezing),
* fall back to the registered default — or a per-call ``default=``
  override for the handful of sites whose default is computed (e.g.
  ``NODE_ID`` defaulting to ``NODE_RANK``),
* survive malformed values by logging and returning the default (a typo
  in a knob must never crash a trainer at step 40k), and
* raise ``KeyError`` for unregistered names — registering here (and
  regenerating ``docs/envs.md``) is the price of a new knob.

``graftlint`` (``python -m dlrover_tpu.analysis``) enforces the
contract: GL301 flags raw ``os.getenv``/``os.environ`` reads of
registered-prefix knobs anywhere outside this module, GL302 flags knob
names missing from this registry.  ``docs/envs.md`` is generated from
here (``python -m dlrover_tpu.analysis --gen-env-docs docs/envs.md``).

Writes/injection (building a child-process env dict, ``os.environ[k] =
v`` at bootstrap) intentionally stay raw — the registry owns *reads*.
"""

import dataclasses
import os
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.constants import ConfigPath, NodeEnv, RendezvousEnv

_MISSING = object()

_TYPES = ("str", "int", "float", "bool")


@dataclasses.dataclass(frozen=True)
class EnvKnob:
    name: str
    type: str  # one of _TYPES
    default: Any
    doc: str


_REGISTRY: Dict[str, EnvKnob] = {}


def register(name: str, type_: str, default: Any, doc: str) -> EnvKnob:
    if type_ not in _TYPES:
        raise ValueError(f"knob {name}: bad type {type_!r}")
    if name in _REGISTRY:
        raise ValueError(f"knob {name} registered twice")
    knob = EnvKnob(name=name, type=type_, default=default, doc=doc)
    _REGISTRY[name] = knob
    return knob


def knob(name: str) -> EnvKnob:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"env knob {name!r} is not registered; add it to "
            "dlrover_tpu/common/envs.py (name, type, default, doc) and "
            "regenerate docs/envs.md"
        ) from None


def all_knobs() -> List[EnvKnob]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def all_knob_names() -> List[str]:
    return sorted(_REGISTRY)


def is_set(name: str) -> bool:
    knob(name)  # unregistered names are a programming error even here
    return name in os.environ


def raw(name: str) -> Optional[str]:
    """The raw string value, or None when unset.  For the rare site that
    needs set-vs-unset semantics beyond the typed default."""
    knob(name)
    return os.environ.get(name)


def _complain(name: str, value: str, type_: str, fallback: Any):
    # lazy import: log.py reads DLROVER_TPU_LOG_LEVEL through this module
    from dlrover_tpu.common.log import logger

    logger.warning(
        "env %s=%r is not a valid %s; using %r", name, value, type_,
        fallback,
    )


def _resolve_default(k: EnvKnob, default: Any) -> Any:
    return k.default if default is _MISSING else default


def get_str(name: str, default: Any = _MISSING) -> str:
    k = knob(name)
    assert k.type == "str", f"{name} is registered as {k.type}, not str"
    value = os.environ.get(name)
    if value is None:
        return _resolve_default(k, default)
    return value


def get_int(name: str, default: Any = _MISSING) -> int:
    k = knob(name)
    assert k.type == "int", f"{name} is registered as {k.type}, not int"
    fallback = _resolve_default(k, default)
    value = os.environ.get(name)
    if value is None:
        return fallback
    try:
        # int(float(...)) accepts the "1e8"-style byte sizes operators
        # actually type for the *_BYTES knobs
        return int(float(value))
    except (TypeError, ValueError):
        _complain(name, value, "int", fallback)
        return fallback


def get_float(name: str, default: Any = _MISSING) -> float:
    k = knob(name)
    assert k.type == "float", f"{name} is registered as {k.type}, not float"
    fallback = _resolve_default(k, default)
    value = os.environ.get(name)
    if value is None:
        return fallback
    try:
        return float(value)
    except (TypeError, ValueError):
        _complain(name, value, "float", fallback)
        return fallback


_TRUE_WORDS = ("1", "true", "yes", "on")
_FALSE_WORDS = ("0", "false", "no", "off", "")


def get_bool(name: str, default: Any = _MISSING) -> bool:
    k = knob(name)
    assert k.type == "bool", f"{name} is registered as {k.type}, not bool"
    fallback = _resolve_default(k, default)
    value = os.environ.get(name)
    if value is None:
        return bool(fallback)
    word = value.strip().lower()
    if word in _TRUE_WORDS:
        return True
    if word in _FALSE_WORDS:
        return False
    _complain(name, value, "bool", fallback)
    return bool(fallback)


def get(name: str, default: Any = _MISSING) -> Any:
    """Type-dispatched read for generic consumers (docs, dashboards)."""
    k = knob(name)
    return {
        "str": get_str,
        "int": get_int,
        "float": get_float,
        "bool": get_bool,
    }[k.type](name, default)


def render_markdown() -> str:
    """docs/envs.md content: the full knob catalog, generated — never
    hand-edit the file."""
    lines = [
        "# Environment knobs",
        "",
        "<!-- GENERATED from dlrover_tpu/common/envs.py — do not edit.",
        "     Regenerate: python -m dlrover_tpu.analysis --gen-env-docs"
        " docs/envs.md -->",
        "",
        "Every `DLROVER_TPU_*` knob is registered in"
        " `dlrover_tpu/common/envs.py` with a type, default, and doc;"
        " code reads knobs through the typed accessors there"
        " (`envs.get_str/int/float/bool`).  `graftlint` rule GL301 flags"
        " raw `os.getenv` reads of these knobs, GL302 flags unregistered"
        " knob names.",
        "",
        f"{len(_REGISTRY)} knobs.",
        "",
        "| Name | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    for k in all_knobs():
        default = f"`{k.default!r}`"
        doc = k.doc.replace("|", "\\|")
        lines.append(f"| `{k.name}` | {k.type} | {default} | {doc} |")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The catalog.  Grouped by subsystem; keep defaults in lock-step with
# any call-site override comments.
# ---------------------------------------------------------------------------

# -- node / job identity (injected by the agent & schedulers) ---------------
register(NodeEnv.MASTER_ADDR, "str", "",
         "host:port of the job master; empty = standalone/local mode")
register(NodeEnv.MASTER_SERVICE_TYPE, "str", "grpc",
         "master transport: grpc or http")
register("DLROVER_TPU_MASTER_PORT", "int", 0,
         "master listen port; 0 picks a free port")
register("DLROVER_TPU_POD_IP", "str", "",
         "this pod's IP (k8s downward API); used to advertise the master")
register(NodeEnv.NODE_ID, "int", 0,
         "stable node id assigned by the master (falls back to NODE_RANK)")
register(NodeEnv.NODE_RANK, "int", 0,
         "rank of this node in the current rendezvous world")
register(NodeEnv.NODE_TYPE, "str", "worker",
         "node role: worker (TPU jobs are worker-only), master, ...")
register(NodeEnv.NODE_NUM, "int", 1,
         "requested number of nodes in the job")
register("DLROVER_TPU_NODE_UNIT", "int", 1,
         "scale plans move in units of this many hosts (TPU slices are "
         "all-or-nothing)")
register(NodeEnv.JOB_NAME, "str", "",
         "job name; namespaces shared-memory/IPC object names")
register("DLROVER_TPU_NAMESPACE", "str", "default",
         "kubernetes namespace for pods/watchers")
register("DLROVER_TPU_PLATFORM", "str", "",
         "platform hint for workers: local, k8s, tpu_vm, ray; empty = "
         "auto")
register("DLROVER_TPU_ROLE", "str", "worker",
         "unified-API role name of this process")
register("DLROVER_TPU_ROLE_RANK", "int", 0,
         "rank within this role's world (unified API)")
register("DLROVER_TPU_ROLE_WORLD", "int", 1,
         "size of this role's world (unified API)")
register(NodeEnv.GRPC_ENABLED, "bool", False,
         "reserved: force-enable grpc transport on workers")
register(NodeEnv.MONITOR_ENABLED, "bool", True,
         "start the in-process WorkerMonitor reporting thread")
register(NodeEnv.COORDINATOR_ADDR, "str", "",
         "jax.distributed coordinator address (host:port)")
register(NodeEnv.PROCESS_ID, "int", 0,
         "jax.distributed process id of this worker")
register(NodeEnv.NUM_PROCESSES, "int", 1,
         "jax.distributed world size")
register(NodeEnv.LOCAL_DEVICE_COUNT, "int", 0,
         "reserved: local device count override for virtual-device runs")
register("DLROVER_TPU_LOCAL_RANK", "int", 0,
         "rank of this process on its host")
register("DLROVER_TPU_RESTART_COUNT", "int", 0,
         "how many times the agent restarted the worker process")
register("DLROVER_TPU_RDZV_ROUND", "int", 0,
         "rendezvous round the worker was launched under")

# -- rendezvous / elasticity / health ---------------------------------------
register(RendezvousEnv.TIMEOUT, "int", 600,
         "rendezvous completion timeout (s)")
register(RendezvousEnv.MIN_NODES, "int", 0,
         "reserved: explicit rendezvous min nodes")
register(RendezvousEnv.MAX_NODES, "int", 0,
         "reserved: explicit rendezvous max nodes")
register("DLROVER_TPU_RDZV_WAITING_TIMEOUT", "float", 30.0,
         "how long the master waits for more nodes before sealing a "
         "smaller world (s)")
register("DLROVER_TPU_MIN_NODES", "int", 0,
         "elastic lower bound; 0 derives from node_num/node_unit")
register("DLROVER_TPU_MAX_NODES", "int", 0,
         "elastic upper bound; 0 derives from node_num")
register("DLROVER_TPU_NETWORK_CHECK", "bool", False,
         "run the pre-training network/node check rendezvous")
register("DLROVER_TPU_PRE_CHECK", "bool", True,
         "run master-side pre-checks before scheduling")
register("DLROVER_TPU_RELAUNCH_ALWAYS", "bool", False,
         "relaunch workers on any exit reason (not just the positive "
         "taxonomy)")
register("DLROVER_TPU_AUTO_SCALE", "bool", False,
         "let the master's auto-scaler act on optimizer plans")
register("DLROVER_TPU_EXCLUDE_STRAGGLER", "bool", False,
         "opt-in: relaunch nodes the device evidence marks as stragglers")
register("DLROVER_TPU_STRAGGLER_RATIO", "float", 1.6,
         "elapsed > avg*ratio marks a straggler")
register("DLROVER_TPU_HEARTBEAT_TIMEOUT", "int", 180,
         "agent heartbeat silence that marks a node NO_HEARTBEAT (s)")
register("DLROVER_TPU_HANG_DOWNTIME", "int", 300,
         "no step progress for this long => hang verdict (s)")
register("DLROVER_TPU_HANG_DETECTION", "int", 1,
         "hang detector mode: 0=off, 1=step-watermark, 2=timer-metrics")
register("DLROVER_TPU_STALL_THRESHOLD", "float", 15.0,
         "step-report gap counted as downtime by the perf monitor (s)")

# -- cluster / scheduler -----------------------------------------------------
register("DLROVER_TPU_ACCELERATOR", "str", "v5e",
         "TPU generation hint (v4/v5e/v5p); k8s scaler uses the "
         "node-selector accelerator name instead")
register("DLROVER_TPU_TOPOLOGY", "str", "",
         "TPU slice topology (e.g. 2x4) for the k8s node selector")
register("DLROVER_TPU_CHIPS_PER_HOST", "int", 4,
         "TPU chips per host for capacity planning")
register("DLROVER_TPU_WORKER_COMMAND", "str", "",
         "JSON list of argv strings the scheduler launches as the worker")
register("DLROVER_TPU_WORKER_IMAGE", "str", "dlrover-tpu:latest",
         "container image for scheduled workers")
register("DLROVER_TPU_BRAIN_ADDR", "str", "",
         "brain (resource optimizer service) address; empty = local "
         "heuristics")

# -- brain v2 (fleet arbiter) -----------------------------------------------
register("DLROVER_TPU_BRAIN_TICK_S", "float", 30.0,
         "fleet-arbiter loop cadence (seconds between ticks)")
register("DLROVER_TPU_BRAIN_ARBITERS", "str", "",
         "comma-separated arbiter chain from the brain registry; "
         "empty = incident_cost,priority_preempt,goodput_marginal")
register("DLROVER_TPU_BRAIN_OPTIMIZER", "str", "efficiency_floor",
         "optimizer plugin the goodput_marginal arbiter judges "
         "scaling curves with (brain/optimizers.py registry)")
register("DLROVER_TPU_BRAIN_COOLDOWN_S", "float", 120.0,
         "minimum seconds between scale decisions for one job (lets "
         "a resize land and produce fresh goodput before re-judging)")
register("DLROVER_TPU_BRAIN_IDLE_SHRINK_SHARE", "float", 0.5,
         "idle+overload ledger share at which the arbiter shrinks a "
         "job by one node unit")
register("DLROVER_TPU_BRAIN_GROW_MIN_GOODPUT", "float", 0.6,
         "minimum current goodput before the arbiter probes one node "
         "unit wider at an unobserved count")
register("DLROVER_TPU_BRAIN_INPUT_BOUND_SHARE", "float", 0.30,
         "input_starved ledger share at which the arbiter judges a job "
         "input-bound and stops probing it wider (more compute cannot "
         "help a starved pipeline; the backlog signal must recover "
         "first)")
register("DLROVER_TPU_BRAIN_MARGINAL_FLOOR", "float", 0.7,
         "per-node efficiency a wider count must retain for the "
         "marginal nodes to be judged as paying (efficiency_floor "
         "plugin semantics)")
register("DLROVER_TPU_BRAIN_RIDEOUT_HORIZON_S", "float", 600.0,
         "horizon over which the cost model prices riding out an "
         "incident's measured goodput degradation")
register("DLROVER_TPU_BRAIN_RESTART_COST_S", "float", 120.0,
         "fallback rendezvous-restart price (seconds) when the job's "
         "ledger has not observed one")
register("DLROVER_TPU_BRAIN_ACK_TIMEOUT_S", "float", 60.0,
         "un-acked brain action age before the tracker re-targets a "
         "delivery whose node died")
register("DLROVER_TPU_BRAIN_ACTION_EXPIRY_S", "float", 600.0,
         "brain action lifetime; past this an un-acked action expires "
         "LOUDLY (logged + counted), never silently")

# -- paths / logging / observability ----------------------------------------
register("DLROVER_TPU_JOB_STATE_DIR", "str", "/tmp/dlrover_tpu/jobs",
         "unified-API job state root")
register("DLROVER_TPU_SOCKET_DIR", "str", "/tmp/dlrover_tpu/sockets",
         "unix-socket dir for agent<->worker shared objects")
register("DLROVER_TPU_LOG_LEVEL", "str", "INFO",
         "logging level for the dlrover_tpu logger")
register("DLROVER_TPU_LOG_DIR", "str", "/tmp/dlrover_tpu/hang",
         "where hang artifacts (stacks, timer dumps) are written")
register("DLROVER_TPU_EVENT_FILE", "str", "",
         "training-event JSONL path; empty = per-pid file under "
         "/tmp/dlrover_tpu/events")
register("DLROVER_TPU_DEVICE_METRICS_URL", "str", "",
         "Prometheus text endpoint with libtpu runtime metrics "
         "(tpu-info's source); empty = HBM-only sampling")
register("DLROVER_TPU_DEVICE_PROFILE_EVERY", "int", 200,
         "profile one step in N for device-lane timing; 0 disables")
register("DLROVER_TPU_TIMER_PORT", "int", 0,
         "native timer metrics port; 0 = disabled")
register("DLROVER_TPU_TIMER_HANG_SECS", "float", 300.0,
         "native timer watchdog: seconds without activity = hang")
register("DLROVER_TPU_TIMER_DAEMON_PORT", "int", 0,
         "master-side timer-daemon scrape port; 0 = disabled")
register("DLROVER_TPU_PY_TRACE", "str", "",
         "comma-separated module prefixes to py-trace into timer spans")
register("DLROVER_TPU_FA_TUNING", "str", "",
         "flash-attention tuning table path override")
register("DLROVER_TPU_COMPILE_CACHE", "str", "",
         "persistent XLA compile-cache dir when JAX_COMPILATION_CACHE_DIR "
         "is unset (empty = .cache/xla in the checkout; CPU backends "
         "cache only when a dir is named); 'off' disables")
register("DLROVER_TPU_FASTCOPY_LIB", "str", "",
         "explicit libfastcopy.so path; empty = search defaults")
register(ConfigPath.ENV_PARAL_CONFIG, "str", ConfigPath.PARAL_CONFIG,
         "where the agent drops the auto-parallelism config for workers")
register(ConfigPath.ENV_RUNTIME_METRICS, "str", ConfigPath.RUNTIME_METRICS,
         "where workers drop runtime metrics for the agent/tuner")
register("DLROVER_TPU_RPC_GAP_LEASE_S", "float", 45.0,
         "role-RPC: skip a claimed-but-never-filled request seq after "
         "this long")

# -- flash checkpoint --------------------------------------------------------
register("DLROVER_TPU_STREAM_CHUNK_BYTES", "int", 0,
         "fixed streaming chunk size; 0 = adaptive pacer")
register("DLROVER_TPU_STAGE_PACE", "float", 0.0,
         "manual staging duty-cycle override (sleep = pace x transfer "
         "time); 0 = adaptive")
register("DLROVER_TPU_STAGE_FACTOR", "float", 1.5,
         "adaptive pacer: allowed step-inflation factor during staging")
register("DLROVER_TPU_CKPT_LOCK_TIMEOUT_S", "float", 600.0,
         "checkpoint buffer-lock acquisition bound (must outlast an "
         "in-flight stream)")
register("DLROVER_TPU_ASYNC_MIN_BYTES", "int", 128 << 20,
         "states at or below this take the synchronous save path")
register("DLROVER_TPU_SNAPSHOT_DTYPE", "str", "",
         "snapshot precision policy: '' exact, 'bf16' halves copy HBM "
         "and D2H traffic (not bit-exact)")
register("DLROVER_TPU_VERIFY_CRC", "str", "lazy",
         "per-chunk CRC verification on restore: eager, lazy, or off")
register("DLROVER_TPU_PERSIST_WRITERS", "int", 4,
         "parallel pwrite workers for the posix persist path")
register("DLROVER_TPU_PERSIST_CHUNK_BYTES", "int", 64 << 20,
         "persist write-chunk size")
register("DLROVER_TPU_PERSIST_LOCK_WAIT_S", "float", 900.0,
         "agent saver: SharedLock wait bound before abandoning a persist")
register("DLROVER_TPU_REPLICA_CHUNK_BYTES", "int", 64 << 20,
         "ICI replica-exchange chunk size")
register("DLROVER_CKPT_SLOT_WAIT_S", "float", 120.0,
         "legacy name: how long an async save waits for the single "
         "transient-HBM-copy slot before falling back to sync")
register("DLROVER_TPU_DIST_PERSIST", "bool", False,
         "route flash-checkpoint storage saves through the distributed "
         "two-phase commit (owned shards only + master-sealed manifest) "
         "instead of the legacy per-proc done-file protocol")
register("DLROVER_TPU_DIST_DIFF", "bool", True,
         "differential distributed saves: shards whose CRC matches the "
         "last committed write chain back to the older step file "
         "instead of re-writing")
register("DLROVER_TPU_DIST_MANIFEST_KEEP", "int", 4,
         "sealed manifests the coordinator retains; shard files no "
         "retained manifest references are garbage-collected at seal")
register("DLROVER_TPU_DIST_COMMIT_TIMEOUT_S", "float", 600.0,
         "how long a host waits for the coordinator to seal a step "
         "(phase-2) before reporting the save un-sealed")
register("DLROVER_TPU_DIST_SEAL_POLL_S", "float", 0.2,
         "seal-status poll interval while waiting for a phase-2 commit")
register("DLROVER_TPU_PEER_RESTORE", "bool", False,
         "checkpoint-free fast recovery: a replaced host pulls its lost "
         "shards from surviving peers' shm snapshots before touching "
         "storage (ladder: peer shm -> manifest ranged reads -> full "
         "storage restore, bit-exact at every rung)")
register("DLROVER_TPU_PEER_SERVE_PORT", "int", 0,
         "agent-side peer serve endpoint port (0 = ephemeral)")
register("DLROVER_TPU_PEER_BIND_HOST", "str", "",
         "interface the peer serve endpoint listens on (empty = the "
         "advertised host; the endpoint serves the full training "
         "state unauthenticated, so widen to 0.0.0.0 only on a "
         "trusted fabric)")
register("DLROVER_TPU_PEER_FETCH_TIMEOUT_S", "float", 30.0,
         "per-request timeout for peer shard/meta/cache fetches")
register("DLROVER_TPU_PEER_FETCH_CHUNK_BYTES", "int", 64 << 20,
         "ranged peer shard reads: bytes per HTTP request")
register("DLROVER_TPU_PEER_CACHE_PREWARM", "bool", True,
         "prewarm the persistent compile cache from a peer (or the "
         "shared cache dir) before first dispatch on a recovery, so "
         "the cache_cold sentinel never fires on a replacement host")
register("DLROVER_TPU_MTTR_BUDGET_S", "float", 60.0,
         "recovery MTTR budget: the MTTR sentinel opens a classified "
         "incident when a recovery's wall clock exceeds this; 0 "
         "disables the sentinel")

# -- retry / deadline policy (common/retry.py) ------------------------------
register("DLROVER_TPU_RETRY_JITTER", "bool", True,
         "jittered retry backoff (equal jitter on the master transport, "
         "full elsewhere; off restores the deterministic schedule; "
         "tests only — synchronized retries herd on a "
         "recovering master)")
register("DLROVER_TPU_RETRY_CB_THRESHOLD", "int", 0,
         "circuit breaker: consecutive exhausted retry budgets that "
         "open the breaker; 0 disables")
register("DLROVER_TPU_RETRY_CB_COOLDOWN_S", "float", 30.0,
         "circuit breaker: fail-fast window before the half-open probe")
register("DLROVER_TPU_RPC_RETRY_ATTEMPTS", "int", 8,
         "agent->master transport: attempts per RPC (rides out a master "
         "restart-on-same-port)")
register("DLROVER_TPU_RPC_RETRY_BASE_S", "float", 0.5,
         "agent->master transport: first backoff gap")
register("DLROVER_TPU_RPC_RETRY_MAX_S", "float", 8.0,
         "agent->master transport: backoff gap cap")
register("DLROVER_TPU_RPC_RETRY_DEADLINE_S", "float", 60.0,
         "agent->master transport: overall wall deadline per RPC "
         "(attempt timeouts included); 0 = attempts-only")
register("DLROVER_TPU_ROLE_RPC_RETRY_ATTEMPTS", "int", 2,
         "cross-role RPC call(): attempts (stale-reply after master "
         "recovery retries once)")
register("DLROVER_TPU_ROLE_RPC_RETRY_BASE_S", "float", 0.2,
         "cross-role RPC call(): first backoff gap")
register("DLROVER_TPU_ROLE_RPC_RETRY_DEADLINE_S", "float", 0.0,
         "cross-role RPC call(): overall wall deadline; 0 = attempts-only")
register("DLROVER_TPU_DRILL_RETRY_ATTEMPTS", "int", 3,
         "goodput/chaos drills: whole-drill attempts")
register("DLROVER_TPU_DRILL_RETRY_BASE_S", "float", 15.0,
         "goodput/chaos drills: gap between drill attempts")
register("DLROVER_TPU_RESPAWN_RETRY_ATTEMPTS", "int", 3,
         "supervisor respawn loops (prime/shared master): bind-and-serve "
         "attempts per recovery")

# -- control-plane scale-out: long-poll + admission control ------------------
register("DLROVER_TPU_LONGPOLL", "bool", True,
         "client long-poll: kv/rendezvous/shard waits block server-side "
         "on the store Condition instead of sleep-polling (off = legacy "
         "0.5-1s client poll loops)")
register("DLROVER_TPU_LONGPOLL_MAX_S", "float", 30.0,
         "ceiling on one blocking wait chunk, enforced server-side and "
         "used as the client's re-issue interval — bounds how long a "
         "dead client can pin a master wait slot")
register("DLROVER_TPU_SERVICER_MAX_INFLIGHT", "int", 256,
         "admission control: max concurrently-served ordinary requests "
         "(the work pool); 0 = unlimited")
register("DLROVER_TPU_SERVICER_MAX_WAITERS", "int", 4096,
         "admission control: max concurrently-blocked long-poll "
         "requests (the wait pool); 0 = unlimited")
register("DLROVER_TPU_SERVICER_QUEUE_TIMEOUT_S", "float", 0.5,
         "admission control: how long an over-cap request may queue for "
         "a slot before it is refused with OVERLOADED + retry-after")
register("DLROVER_TPU_SERVICER_RETRY_AFTER_S", "float", 0.25,
         "admission control: base retry-after hint on an overload "
         "response (scaled up with queue depth)")
register("DLROVER_TPU_SHARD_LEASE_BATCH", "int", 1,
         "shard leases fetched per TaskBatchRequest envelope (>1 "
         "prefetches client-side; trades dispatch granularity for RPCs)")
register("DLROVER_TPU_SHARD_WAIT_S", "float", 10.0,
         "long-poll chunk while waiting for a dispatchable shard "
         "(replaces the 1s sleep-poll in fetch_shard)")
register("DLROVER_TPU_DATASCOPE", "bool", True,
         "data-pipeline observatory (datascope): master-side shard "
         "lease/backlog telemetry + agent-side data.fetch/data.consume "
         "spans; off = every hook is a no-op")
register("DLROVER_TPU_DATA_FLUSH_S", "float", 1.0,
         "datascope: min seconds between shard-telemetry flushes into "
         "the master time-series store (throttles the per-lease hook)")
register("DLROVER_TPU_DATA_WINDOW", "int", 512,
         "datascope: per-dataset bounded sample window for lease/"
         "completion latency percentiles")
register("DLROVER_TPU_DATA_STARVED_MIN_S", "float", 0.05,
         "datascope: a fetch_shard blocking wait shorter than this is "
         "never charged to the input_starved goodput phase (prefetch "
         "micro-waits overlapped by compute cost nothing)")
register("DLROVER_TPU_DATA_STARVED_SHARE", "float", 0.10,
         "data-starvation sentinel: job.share.input_starved floor — "
         "below it the detector never fires (idle jobs aren't starved)")
register("DLROVER_TPU_DATA_P99_MIN_MS", "float", 50.0,
         "shard-latency sentinel: job.data.lease_p99_ms floor — p99 "
         "regressions under this absolute latency never fire")
register("DLROVER_TPU_MASTER_GRPC_WORKERS", "int", 0,
         "gRPC master service thread-pool size; 0 = auto "
         "(MAX_WAITERS + MAX_INFLIGHT + headroom, so blocked long-polls "
         "can never starve ordinary RPCs of a pool thread — each "
         "long-poll occupies one worker for up to its chunk)")

# -- chaos injection (dlrover_tpu/chaos) ------------------------------------
register("DLROVER_TPU_CHAOS", "bool", False,
         "arm the chaos-injection engine from the env (tests/drills "
         "ONLY; graftlint GL501 forbids force-enabling in production "
         "code, and the default MUST stay off)")
register("DLROVER_TPU_CHAOS_SPEC", "str", "",
         "chaos plan: inline JSON ('{...}') or a path to a plan file")
register("DLROVER_TPU_CHAOS_SEED", "int", 0,
         "chaos: seed override — the same seed replays the same fault "
         "trace")
register("DLROVER_TPU_CHAOS_TRACE_FILE", "str", "",
         "chaos: JSONL file fired faults are appended to (drills read "
         "it back to assert replay determinism)")

# -- distributed tracing + RED metrics (dlrover_tpu/observability) ----------
register("DLROVER_TPU_TRACE", "bool", True,
         "master switch for control-plane distributed tracing: spans "
         "around every master RPC / kv op / role RPC, exported as SPAN "
         "records into the per-process event stream")
register("DLROVER_TPU_TRACE_SEED", "int", 0,
         "tracing: nonzero seeds the trace/span id stream (single-"
         "process drills and golden-output tests); 0 = entropy")
register("DLROVER_TPU_TRACE_FILE", "str", "",
         "tracing: write SPAN records to this JSONL file instead of "
         "the per-process training-event file")
register("DLROVER_TPU_TRACE_SAMPLE", "float", 1.0,
         "tracing: head-sampling probability for new root traces "
         "(child spans inherit the root's decision)")
register("DLROVER_TPU_TRACE_MAX_EVENTS", "int", 256,
         "tracing: max events attached to one span — a retry storm "
         "must not grow a span without bound")
register("DLROVER_TPU_METRICS_MAX_SERIES", "int", 4096,
         "RED metrics: max live label combinations per process; "
         "excess series are dropped and counted")

# -- flight recorder + incident engine (dlrover_tpu/observability) ----------
register("DLROVER_TPU_RECORDER", "bool", True,
         "always-on in-process flight recorder: bounded rings of recent "
         "spans/events/step timings/log tail, snapshotted into incident "
         "dumps (0 turns every append into a flag check)")
register("DLROVER_TPU_RECORDER_SPANS", "int", 2048,
         "flight recorder: finished-span ring capacity (tuples; the "
         "default holds a minute of steps at 7 a second with their "
         "three spans each, and a save's, in about 1.3 MB)")
register("DLROVER_TPU_RECORDER_EVENTS", "int", 1024,
         "flight recorder: training-event/chaos-fault ring capacity")
register("DLROVER_TPU_RECORDER_STEPS", "int", 512,
         "flight recorder: per-step timing ring capacity")
register("DLROVER_TPU_RECORDER_LOG_LINES", "int", 200,
         "flight recorder: warning-level log-tail ring capacity")
register("DLROVER_TPU_INCIDENT_DIR", "str", "/tmp/dlrover_tpu/incidents",
         "incident engine: root directory for per-incident dump/"
         "timeline/INCIDENT.json artifacts")
register("DLROVER_TPU_INCIDENT_COOLDOWN_S", "float", 300.0,
         "incident engine: repeat detections of one kind within this "
         "window join the existing incident instead of opening a new one")
register("DLROVER_TPU_INCIDENT_GRACE_S", "float", 60.0,
         "incident engine: how long finalize waits for agent dumps "
         "before merging with whatever arrived; must exceed the "
         "heartbeat interval (~15s) + an agent monitor tick, or dumps "
         "riding the next heartbeat are sealed out of the verdict")
register("DLROVER_TPU_INCIDENT_MAX", "int", 16,
         "incident engine: incidents kept on disk; older ones are "
         "evicted with their directories")
register("DLROVER_TPU_STRAGGLER_STEP_RATIO", "float", 1.5,
         "step-time straggler screen: a node whose heartbeat-digest p50 "
         "step time exceeds ratio x the job median is a laggard")
register("DLROVER_TPU_CKPT_STALL_S", "float", 600.0,
         "checkpoint-stall diagnostician: a node whose saver has been "
         "busy on one persist longer than this is stalled")
register("DLROVER_TPU_OVERLOAD_STORM_RATE", "float", 50.0,
         "overload-storm diagnostician: sustained admission refusals/s "
         "(from the r11 RED counters) that open an incident")
register("DLROVER_TPU_DIGEST_EVERY", "int", 20,
         "trainer: write the per-rank step-time digest file (read into "
         "agent heartbeats) every N steps; 0 disables the file")

# -- goodput ledger / time-series store / regression sentinel ----------------
register("DLROVER_TPU_GOODPUT_LEDGER", "bool", True,
         "goodput ledger: attribute every second of each process's wall "
         "clock to one phase (compute/exposed_comm/ckpt_stall/"
         "rendezvous_restart/overload_rideout/compile/idle_unknown) "
         "from the existing span/step/ride-out streams; 0 turns every "
         "feed into a flag check")
register("DLROVER_TPU_GOODPUT_RES_S", "float", 1.0,
         "goodput ledger: wall-clock slot resolution in seconds (drills "
         "lower it so sub-second stalls are attributable)")
register("DLROVER_TPU_GOODPUT_WINDOW", "int", 7200,
         "goodput ledger: live slots kept before the oldest fold into "
         "cumulative per-phase totals (bounds memory; the summary stays "
         "full-job)")
register("DLROVER_TPU_TS_POINTS", "int", 600,
         "master time-series store: points kept per series per "
         "resolution ring (1s/10s/5m rings -> 10min/100min/~50h of "
         "history at the default)")
register("DLROVER_TPU_SENTINEL_ALPHA", "float", 0.25,
         "perf-regression sentinel: EWMA smoothing factor for the "
         "baseline and deviation estimates")
register("DLROVER_TPU_SENTINEL_MAD_K", "float", 4.0,
         "perf-regression sentinel: a sample breaching baseline by more "
         "than k x the EWMA absolute deviation counts toward a "
         "regression")
register("DLROVER_TPU_SENTINEL_MIN_SAMPLES", "int", 8,
         "perf-regression sentinel: baseline samples required before "
         "breaches can fire (a cold detector never alerts)")
register("DLROVER_TPU_SENTINEL_CONSECUTIVE", "int", 2,
         "perf-regression sentinel: consecutive breaching samples "
         "required before a detector fires (one noisy sample must not "
         "open an incident)")

# -- comm observatory (fabric probes + per-bucket attribution) ---------------
register("DLROVER_TPU_COMM_PROBE_EVERY", "int", 200,
         "comm observatory: run the active mesh probe (timed "
         "ppermute/psum micro-collectives per mesh axis feeding the "
         "FabricModel) every N trainer steps; 0 disables probing")
register("DLROVER_TPU_COMM_PROBE_LAT_BYTES", "int", 64,
         "comm observatory: payload bytes of the latency probe's "
         "ppermute ring hop (small = pure per-message latency)")
register("DLROVER_TPU_COMM_PROBE_BW_BYTES", "int", 1048576,
         "comm observatory: payload bytes of the bandwidth probe's "
         "psum (large enough to amortize dispatch; ~1MB default)")
register("DLROVER_TPU_COMM_PROBE_REPS", "int", 4,
         "comm observatory: timed repetitions per probe op (the "
         "measured value is the per-rep mean)")
register("DLROVER_TPU_COMM_EWMA_ALPHA", "float", 0.5,
         "comm observatory: FabricModel EWMA smoothing for probe "
         "latency/bandwidth estimates (1.0 = last sample wins)")
register("DLROVER_TPU_COMM_BUCKET_PROBE", "bool", True,
         "comm observatory: also time each grad-sync bucket's chain "
         "(one sync-only program per bucket, comm.bucket<i> spans with "
         "transport/axis/wire-bytes/GB/s) on the probe cadence")
register("DLROVER_TPU_COMM_SLOWLINK_MIN_LAT_US", "float", 50.0,
         "slow-link sentinel: absolute probe-latency move (µs) a "
         "breach must clear — keeps sub-noise jitter on a quiet fabric "
         "from opening incidents")

# -- memory observatory (per-subsystem byte attribution + OOM forecast) ------
register("DLROVER_TPU_MEM_SCOPE", "bool", True,
         "memory observatory: sample per-chip device memory + host "
         "RSS/shm on the digest cadence and attribute bytes to owning "
         "subsystems; 0 turns every hook into a flag check")
register("DLROVER_TPU_MEM_CPU_LIMIT_B", "float", 0.0,
         "memory observatory: synthetic per-device bytes_limit for "
         "backends that report none (CPU); 0 = unknown (headroom "
         "series absent, fit checks refuse)")
register("DLROVER_TPU_MEM_HEADROOM_FLOOR", "float", 0.05,
         "mem-pressure sentinel: absolute headroom floor as a fraction "
         "of the per-chip limit — below it a mem_pressure incident "
         "opens regardless of slope")
register("DLROVER_TPU_MEM_LEAK_SLOPE_B_S", "float", 1048576.0,
         "mem-pressure sentinel: minimum EWMA in-use byte slope (B/s) "
         "that counts as a leak — sub-slope drift is noise")
register("DLROVER_TPU_MEM_FORECAST_S", "float", 600.0,
         "mem-pressure sentinel: open the hbm_leak incident when the "
         "EWMA slope projects the chip hitting its limit within this "
         "many seconds")
register("DLROVER_TPU_MEM_EWMA_ALPHA", "float", 0.5,
         "mem-pressure sentinel: EWMA smoothing for the per-node "
         "in-use byte slope estimate (1.0 = last delta wins)")
register("DLROVER_TPU_MEM_FIT_MARGIN", "float", 0.08,
         "fit_report: safety margin subtracted from the measured "
         "per-chip limit before judging a proposed layout")
register("DLROVER_TPU_MEM_CHAOS_INFLATE_B", "float", 268435456.0,
         "chaos mem.pressure point: synthetic bytes ADDED to the "
         "reported in-use figure per fired fault (cumulative — the "
         "injected leak slope); inert unless a chaos plan arms the "
         "point")

# -- compile observatory (per-function recompile attribution) ----------------
register("DLROVER_TPU_JITSCOPE", "bool", True,
         "compile observatory: attribute XLA compile work to watched "
         "jit call sites (function name, measured compile seconds, "
         "trigger classification, persistent-cache hit/miss) via the "
         "jax.monitoring streams; 0 turns every hook into a flag check")
register("DLROVER_TPU_JITSCOPE_EVENTS", "int", 256,
         "compile observatory: compile events kept in the per-process "
         "ring (each also lands in the flight-recorder span ring)")
register("DLROVER_TPU_JITSCOPE_STALL_MS", "float", 500.0,
         "dispatch-stall probe: a watched call blocking the host "
         "longer than this while compile work landed in its window "
         "emits a jitscope.dispatch_stall span (and the daemon poller "
         "drops a stall_detected event while it is STILL blocked); "
         "0 disables stall detection")
register("DLROVER_TPU_COMPILE_CACHE_MIN_S", "float", 1.0,
         "persistent compile cache: minimum compile seconds before an "
         "executable is written to the cache dir "
         "(jax_persistent_cache_min_compile_time_secs; drills lower "
         "it to 0 so tiny programs round-trip)")
register("DLROVER_TPU_COMPILE_STORM_MIN_S", "float", 5.0,
         "recompile-storm sentinel: absolute compile seconds per "
         "rollup window a breach must clear — routine sub-second "
         "retraces on a quiet job must not open incidents")
register("DLROVER_TPU_CACHE_COLD_RATIO", "float", 0.5,
         "cache-cold sentinel: a node that expected a warm persistent "
         "cache (restart / non-empty cache dir at boot) whose recent "
         "hit ratio sits below this floor opens a cache_cold incident")

# -- fault injection / drills / bench ---------------------------------------
register("DLROVER_TPU_GRAD_BUCKET_MB", "float", 4.0,
         "grad-sync bucket target (MB of fp32 gradient per bucket) for "
         "the overlapped bucketed dp sync; 0 = r6 per-leaf collectives. "
         "GradSyncPolicy(bucket_mb=...) overrides per trainer")
register("DLROVER_TPU_GRAD_TRANSPORT", "str", "auto",
         "exact-bucket reduce-scatter transport: auto (lax.psum_scatter)"
         " | all_to_all | ring | ring_pallas | ring_rdma (each ring tier"
         " falls back when its preconditions fail; quantized buckets "
         "always use the codec all_to_all)")
register("DLROVER_TPU_GRAD_HI_FRAC", "float", 0.125,
         "blockwise grad-sync mode: fraction of blocks per chunk "
         "(picked by max-abs grad statistics) that ship an int8 "
         "refinement over the int4 base")
register("DLROVER_TPU_GRAD_RING_RDMA", "bool", False,
         "enable the prototype Pallas RDMA ring reduce-scatter kernel "
         "on TPU for transport=ring_rdma (off = jax-level ring)")
register("DLROVER_TPU_GRAD_HIERARCHICAL", "bool", True,
         "topology-aware grad sync: on a mesh with an active slice "
         "axis, decompose the dp sync into quantized reduce-scatter "
         "over ICI within the slice -> one aggregated (more "
         "aggressively quantized) exchange over DCN across slices -> "
         "intra-slice all-gather; off = the flat combined-axis "
         "collectives.  GradSyncPolicy(hierarchical=...) overrides")
register("DLROVER_TPU_GRAD_DCN_FORMAT", "str", "int4",
         "hierarchical grad sync: wire codec of the cross-slice DCN "
         "leg (exact | int8 | int4 | blockwise) — the EQuARX "
         "observation that cross-fabric exchanges tolerate heavier "
         "quantization than intra-fabric ones.  Only applies to "
         "quantized base modes (exact modes keep an exact DCN leg); "
         "GradSyncPolicy(dcn_format=...) overrides")
register("DLROVER_TPU_SLICE_COUNT", "int", 0,
         "two-level mesh: number of pod slices (DCN domains) the "
         "device set splits into — parallel.mesh.build_mesh builds the "
         "explicit slice mesh (build_slice_mesh) when set, falling "
         "back to a flat mesh with a warning on incompatible configs; "
         "0/1 = flat single-slice mesh")
register("DLROVER_TPU_SLICE_ID", "int", 0,
         "this host's pod-slice index (DCN domain), carried into the "
         "rendezvous world so the master keeps slices contiguous and "
         "groups nodes per slice")
register("DLROVER_TPU_SLICE_SIM", "bool", False,
         "simulate the DCN slice boundary on a CPU mesh: every "
         "cross-slice exchange pays a host-side toll (bytes / "
         "DLROVER_TPU_SLICE_SIM_GBPS + DLROVER_TPU_SLICE_SIM_LAT_US, "
         "plus any armed comm.axis_delay.slice chaos DELAY) so "
         "hierarchical-vs-flat wall times are measurable pre-hardware")
register("DLROVER_TPU_SLICE_SIM_GBPS", "float", 0.5,
         "simulated DCN link bandwidth (GB/s) the slice-boundary toll "
         "prices bytes against")
register("DLROVER_TPU_SLICE_SIM_LAT_US", "float", 200.0,
         "simulated DCN per-exchange latency (µs) added to every "
         "tolled cross-slice collective")
register("DLROVER_TPU_GRAD_STRIPE", "float", 0.0,
         "dual-fabric striping: fraction of each hierarchical bucket's "
         "columns routed over the DCN leg CONCURRENTLY with the ICI "
         "reduce-scatter of the rest (FlexLink) — 0 = pure "
         "hierarchical; the fabric tuner overrides per bucket when "
         "DLROVER_TPU_TUNER_APPLY is on.  GradSyncPolicy(stripe=...) "
         "overrides")
register("DLROVER_TPU_TUNER", "bool", True,
         "per-bucket fabric auto-tuner: price every transport tier and "
         "stripe fraction against the measured FabricModel snapshot on "
         "each probe round and record the winning plan in "
         "grad_sync_summary() / span attrs (compute + record only; "
         "hot-path swaps additionally need DLROVER_TPU_TUNER_APPLY)")
register("DLROVER_TPU_TUNER_APPLY", "bool", False,
         "fabric auto-tuner: stage the winning plan under the demotion "
         "lock and swap it into the live bucketed grad sync at the "
         "next train_step (the r18 demotion pattern); off = decisions "
         "are recorded but the static policy keeps the hot path")
register("DLROVER_TPU_TUNER_MIN_GAIN", "float", 0.1,
         "fabric auto-tuner hysteresis: a new plan must price at least "
         "this fraction faster than the live plan before a swap is "
         "staged (suppresses plan flapping on noisy probes)")
register("DLROVER_TPU_TUNER_STRIPE_MAX", "float", 0.5,
         "fabric auto-tuner: ceiling on the per-bucket DCN stripe "
         "fraction the tuner may pick (the DCN leg also carries the "
         "hierarchical stage-2 exchange, so striping past ~half the "
         "bucket starves it)")
register("DLROVER_TPU_TUNER_HBM_GBPS", "float", 0.0,
         "fabric auto-tuner: HBM bandwidth (GB/s) used to price the "
         "quantize round-trip that the fused ring_pallas_q tier "
         "avoids; 0 = ignore the HBM term (CPU simulation)")
register("DLROVER_TPU_TUNER_SEED_FILE", "str", "BENCH_comm.json",
         "fabric auto-tuner cold start: bench artifact whose fabric "
         "section seeds the tuner before the first live probe fires "
         "(resolved against the cwd; missing file = static ladder "
         "until the first probe)")
register("DLROVER_TPU_BENCH_LEGS", "str", "all",
         "grad_sync_bench leg selection: 'all' or a comma list of "
         "modes/comm/hierarchy/tuner/rdma — a partial run refreshes "
         "only the named legs of BENCH_grad_overlap.json and keeps "
         "the prior file's other sections (re-prove one leg's "
         "evidence without paying the full matrix; comm needs modes)")
register("DLROVER_TPU_HIER_DEMOTION", "bool", True,
         "auto-demotion hook: allow a SlowLinkDiagnostician breach on "
         "the DCN axis to demote the hierarchical policy's DCN leg to "
         "a heavier quantization tier (int8 -> int4, blockwise -> "
         "int4); each demotion is logged and counted in "
         "dlrover_tpu_hier_dcn_demotions_total")
register(NodeEnv.MOCK_ERR_RANK, "str", "",
         "fault injection: the single node rank that fails node-check; "
         "empty = off")
register("DLROVER_TPU_MOCK_SLOW_NODE", "str", "",
         "fault injection: the single node rank that runs node-check "
         "slowly; empty = off")
register("DLROVER_TPU_MOCK_SLOW_SECS", "float", 5.0,
         "fault injection: how slow a mocked-slow node-check is (s)")
register("DLROVER_TPU_DRILL_CRASH_STEPS", "str", "",
         "goodput drill: comma list of steps to crash at")
register("DLROVER_TPU_CRASH_AT_STEP", "int", -1,
         "example trainers: simulate a hard crash at this step; -1 off")
register("DLROVER_TPU_TOTAL_STEPS", "int", 0,
         "example trainers: total steps to run; 0 = per-example default")
register("DLROVER_TPU_RESHARD_FIT_GATE", "bool", True,
         "live reshard (r22): refuse transition plans the r17 measured "
         "fit report says do not fit the surviving per-chip HBM; "
         "unknown verdicts (no registered state plan, no measured "
         "limit) pass with a warning")
register("DLROVER_TPU_RESHARD_DONOR_DIR", "str", "",
         "live reshard (r22): sealed r13 distributed-checkpoint dir "
         "used as the byte-range partial-read donor for shards no "
         "surviving member holds; empty = survivors-only (plans "
         "needing departed-only state are refused)")
register("DLROVER_TPU_RESHARD_LIVE", "bool", False,
         "Brain fleet arbiter: order scale plans as LIVE in-place "
         "reshards (parallel/reshard.py) instead of worker restarts — "
         "the agent stages the mesh transition on the training process "
         "and no rendezvous/restart window is paid")
register("DLROVER_TPU_BENCH_MIN_CORES", "int", 2,
         "grad_sync_bench: minimum host CPU cores for the "
         "SLICE_SIM-executing legs (hierarchy flat leg, tuner) — below "
         "this the leg is skipped with a logged reason instead of "
         "deadlocking a 1-core host's serialized device transfers")
