"""Worker-process bootstrap: env -> jax.distributed -> global mesh.

The TPU-native analogue of torch's ``init_process_group`` bootstrapping in
the reference's worker scripts: ``tpurun`` (elastic_run.py) exports the
coordinator address / process id / process count chosen by the master
rendezvous, and the training script calls :func:`init` once before any JAX
computation.
"""

import dataclasses
import os
from typing import Optional

from dlrover_tpu.common import envs
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger


@dataclasses.dataclass
class WorkerContext:
    node_rank: int = 0
    local_rank: int = 0
    process_id: int = 0
    num_processes: int = 1
    num_nodes: int = 1
    restart_count: int = 0
    rdzv_round: int = 0
    master_addr: str = ""
    coordinator_addr: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


_worker_ctx: Optional[WorkerContext] = None


def worker_context() -> WorkerContext:
    global _worker_ctx
    if _worker_ctx is None:
        _worker_ctx = WorkerContext(
            node_rank=envs.get_int(NodeEnv.NODE_RANK),
            local_rank=envs.get_int("DLROVER_TPU_LOCAL_RANK"),
            process_id=envs.get_int(NodeEnv.PROCESS_ID),
            num_processes=envs.get_int(NodeEnv.NUM_PROCESSES),
            num_nodes=envs.get_int(NodeEnv.NODE_NUM),
            restart_count=envs.get_int("DLROVER_TPU_RESTART_COUNT"),
            rdzv_round=envs.get_int("DLROVER_TPU_RDZV_ROUND"),
            master_addr=envs.get_str(NodeEnv.MASTER_ADDR),
            coordinator_addr=envs.get_str(NodeEnv.COORDINATOR_ADDR),
        )
    return _worker_ctx


def init(platform: Optional[str] = None) -> WorkerContext:
    """Initialize JAX for this worker from the tpurun environment.

    - forces the requested platform (``DLROVER_TPU_PLATFORM``; "cpu" uses
      gloo collectives for multi-process virtual-device testing),
    - calls ``jax.distributed.initialize`` with the coordinator the agent
      published via the master KV store,
    - returns the :class:`WorkerContext`.

    Must be called before any JAX backend use.
    """
    ctx = worker_context()
    platform = platform or envs.get_str("DLROVER_TPU_PLATFORM")
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if ctx.is_distributed and ctx.coordinator_addr:
        if platform == "cpu":
            # gloo only when a distributed client will exist: recent
            # jaxlib requires one (make_gloo_tcp_collectives rejects
            # distributed_client=None), so a worker that rendezvoused
            # into a 1-process world must keep the default in-process
            # CPU collectives or its backend init TypeErrors
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator_addr,
            num_processes=ctx.num_processes,
            process_id=ctx.process_id,
        )
        logger.info(
            "jax.distributed initialized: process %d/%d coordinator=%s",
            ctx.process_id, ctx.num_processes, ctx.coordinator_addr,
        )
    _setup_compile_cache(jax)
    try:
        # the compile observatory's jax.monitoring listeners must be
        # live before the first dispatch or the first (usually biggest)
        # compile of the job goes unattributed
        from dlrover_tpu.observability import jitscope

        jitscope.install()
    except Exception as e:  # noqa: BLE001 - observability must not
        logger.warning("jitscope install failed: %s", e)  # break boot
    if monitoring_enabled():
        _start_monitor()
    return ctx


#: persistent-cache boot state the compile observatory reads: whether
#: the cache is enabled, where it lives, why it is off, how many
#: executables it held at boot (nonzero = a warm restart is EXPECTED to
#: hit), and whether this process is itself a restart.
_cache_status: dict = {
    "enabled": False, "dir": "", "reason": "not-initialized",
    "entries_at_boot": 0, "restart": False,
}


def compile_cache_info() -> dict:
    """The persistent compile cache's boot state (a copy)."""
    return dict(_cache_status)


def _count_cache_entries(cache_dir: str) -> int:
    try:
        return sum(
            1 for name in os.listdir(cache_dir) if name.endswith("-cache")
        )
    except OSError:
        return 0


def _note_cache_disabled(reason: str, cache_dir: str = "") -> None:
    """A fleet-wide cold cache must be VISIBLE, not a line in a log
    nobody tails: count it and drop a flight-recorder event so the
    dashboard and every incident dump carry it."""
    _cache_status.update(
        enabled=False, dir=cache_dir, reason=reason,
    )
    try:
        from dlrover_tpu.observability import metrics as obs_metrics

        obs_metrics.registry().counter_inc(
            "dlrover_tpu_compile_cache_disabled_total",
            help=obs_metrics._help(
                "dlrover_tpu_compile_cache_disabled_total"
            ),
            reason=reason.split(":", 1)[0][:40],
        )
    except Exception:  # noqa: BLE001 - telemetry must not break boot
        pass
    try:
        from dlrover_tpu.observability import flight_recorder
        import time as _time

        flight_recorder.on_event({
            "ts": round(_time.time(), 6),
            "type": "INSTANT",
            "name": "compile_cache.disabled",
            "content": {"reason": reason, "dir": cache_dir},
        })
    except Exception:  # noqa: BLE001 - telemetry must not break boot
        pass


#: the env var JAX itself reads into ``jax_compilation_cache_dir``
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


#: the fixed in-checkout cache directory (``.cache/xla`` beside
#: ``pyproject.toml``).  The path is part of the cache's key, so it is
#: never made from a temporary name, a pid or the time.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".cache", "xla",
)


def compile_cache_dir() -> str:
    """The persistent compile cache every process of this job shares:
    ``JAX_COMPILATION_CACHE_DIR`` where the machine sets it (then JAX
    configures itself and this code sets no other directory), else the
    explicit ``DLROVER_TPU_COMPILE_CACHE``, else the fixed in-checkout
    default.  Empty string = switched off (``DLROVER_TPU_COMPILE_CACHE=
    off``).  JAX-free: the agent and the drills call it too."""
    explicit = envs.get_str("DLROVER_TPU_COMPILE_CACHE")
    if explicit.lower() == "off":
        return ""
    return (
        os.environ.get(JAX_CACHE_ENV, "")
        or explicit
        or _DEFAULT_CACHE_DIR
    )


def _setup_compile_cache(jax):
    """Persistent XLA compile cache: restart-based elasticity re-traces
    the train step on every membership change, and a warm cache turns
    that recompile into a disk read (SURVEY §7 hard-part (a)); the dir
    survives worker restarts because the host owns it.

    Which directory: :func:`compile_cache_dir`.  Default on for
    accelerator backends only — XLA:CPU AOT entries bake in host CPU
    features and reloading them can SIGILL on a different machine, so
    CPU requires an explicit env opt-in (either variable).  Gated on the
    RESOLVED backend (not the requested platform string): runs after the
    platform config is final, before any compile.

    The outcome is recorded in :func:`compile_cache_info` either way —
    the compile observatory classifies warm-restart misses against it,
    and a cache that could NOT be enabled emits a metric + flight-
    recorder event (a fleet-wide cold cache is an incident precursor,
    not a log line).
    """
    _cache_status["restart"] = bool(worker_context().restart_count > 0)
    cache_dir = compile_cache_dir()
    if not cache_dir:
        jax.config.update("jax_enable_compilation_cache", False)
        _cache_status.update(
            enabled=False, dir="", reason="env-off",
        )
        return
    from_jax_env = bool(os.environ.get(JAX_CACHE_ENV))
    if not from_jax_env and not envs.get_str("DLROVER_TPU_COMPILE_CACHE"):
        try:
            if jax.default_backend() == "cpu":
                _cache_status.update(
                    enabled=False, dir="", reason="cpu-default-off",
                )
                return
        except Exception:  # noqa: BLE001 - no backend: no cache
            _note_cache_disabled("no-backend")
            return
    try:
        os.makedirs(cache_dir, exist_ok=True)
        _prewarm_cache_from_peers(cache_dir)
        entries = _count_cache_entries(cache_dir)
        if not from_jax_env:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            envs.get_float("DLROVER_TPU_COMPILE_CACHE_MIN_S"),
        )
        _cache_status.update(
            enabled=True, dir=cache_dir, reason="",
            entries_at_boot=entries,
        )
    except Exception as e:  # noqa: BLE001 - cache is an optimization
        logger.warning("compile cache disabled: %s", e)
        _note_cache_disabled(f"config-error: {e}", cache_dir)


def _prewarm_cache_from_peers(cache_dir: str) -> None:
    """Peer-restore cache prewarm: BEFORE the boot count above, pull
    the compile-cache entries surviving hosts hold — a replacement
    host's recovery must hit a warm cache (``entries_at_boot > 0``)
    instead of firing the ``cache_cold`` sentinel and paying a compile
    the fleet already paid.  No-op unless peer restore is on and a
    master client was registered with the peer-restore context."""
    if not (
        envs.get_bool("DLROVER_TPU_PEER_RESTORE")
        and envs.get_bool("DLROVER_TPU_PEER_CACHE_PREWARM")
    ):
        return
    try:
        from dlrover_tpu.trainer.flash_checkpoint import peer_restore

        got = peer_restore.prewarm_from_context(cache_dir)
        if got.get("fetched"):
            logger.info(
                "compile cache prewarmed: %d entr(ies), %d bytes from "
                "peer %d", got["fetched"], got.get("bytes", 0),
                got.get("donor", -1),
            )
    except Exception as e:  # noqa: BLE001 - prewarm is an optimization
        logger.warning("compile-cache prewarm failed: %s", e)


def monitoring_enabled() -> bool:
    """One gate for the monitor thread AND the trainer's timer feed."""
    return bool(
        envs.get_str(NodeEnv.MASTER_ADDR)
        and envs.get_bool(NodeEnv.MONITOR_ENABLED)
    )


_monitor = None


def _start_monitor():
    """Resource/hang monitoring thread + native timer (best-effort)."""
    global _monitor
    if _monitor is not None:
        return
    try:
        from dlrover_tpu.agent.monitor import WorkerMonitor
        from dlrover_tpu.timer import get_timer

        _monitor = WorkerMonitor(timer=get_timer())
        _monitor.start()
    except Exception as e:  # noqa: BLE001 - monitoring must not break boot
        logger.warning("worker monitor not started: %s", e)
