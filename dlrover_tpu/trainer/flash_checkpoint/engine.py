"""Checkpoint engine: the training-process side of Flash Checkpoint.

TPU-native counterpart of reference
``dlrover/trainer/torch/flash_checkpoint/engine.py`` (``CheckpointEngine:
175``, ``save_state_dict_to_memory:365``, ``get_state_dict_from_memory:
406``).  One engine covers DDP/FSDP/TP uniformly: shards are extracted from
the arrays' *actual* sharding, so "which framework" never matters — the
mesh is the single source of truth.

Save path: device->host copy of this process's replica-0 shards into shm
(the only blocking cost), then an event to the agent's async saver which
persists shm to storage off the training path.  Load path: shm fast path
when the sharding still matches (restart on the same mesh: seconds), else
reassembly from storage with arbitrary resharding via global shard indices.

Async snapshots (``save_to_memory_async`` / ``save_to_storage_async``)
cut the blocking cost to the *dispatch* of an on-device copy: JAX arrays
are immutable and a device executes its queue in order, so a copy enqueued
before the next (donated) train step reads the pre-donation values, and
the device->host staging + shm write then run in a background thread while
the device keeps training.  The reference cannot make this move — torch
optimizers mutate parameters in place, so its blocking floor is the full
pinned-memory copy (engine.py:365 save_state_dict_to_memory) — which is
exactly why this is the TPU-first design rather than a port.
"""

import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common.constants import CheckpointConstant, NodeEnv
from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import (
    SharedLock,
    SharedMemoryBuffer,
    SharedQueue,
)
from dlrover_tpu.common.storage import get_checkpoint_storage
from dlrover_tpu.observability import trace
from dlrover_tpu.training_event.emitter import (
    TrainerEvents,
    get_default_emitter,
)
from dlrover_tpu.trainer.flash_checkpoint import snapshot
from dlrover_tpu.trainer.flash_checkpoint.snapshot import ShardIndexMap

CKPT_EVENT_QUEUE = "ckpt_events"
CKPT_LOCK = "ckpt_lock"
CKPT_PROGRESS = "ckpt_progress"


def default_scope() -> str:
    """Per-job scope for shm/socket names.  Derived from the job name or
    the master address so two unrelated jobs on one host never collide
    (a stale snapshot from job A must not 'resume' into job B)."""
    name = envs.get_str(NodeEnv.JOB_NAME)
    if name:
        return name
    master = envs.get_str(NodeEnv.MASTER_ADDR)
    if master:
        import hashlib

        return "job" + hashlib.md5(master.encode()).hexdigest()[:8]
    return "job"


def shm_name(process_id: int, scope: str = "") -> str:
    scope = scope or default_scope()
    return f"dlrover_tpu_ckpt_{scope}_{process_id}"


class _DeviceCopy:
    """Holds the transient on-device state copy of one async snapshot.

    Freeing is observable (``on_free``) and idempotent, so the engine can
    account how many extra state copies are live in HBM and refuse to
    dispatch a second concurrent one — the documented worst case is ONE
    transient extra copy, and that promise is enforced here rather than
    hoped for."""

    def __init__(self, snap, on_free, ctx=None):
        self._snap = snap
        self._on_free = on_free
        self._freed = False
        #: the trace context of the ``flash.save`` that made the copy:
        #: it rides the queue item to the stager thread, whose
        #: ``flash.stage`` span is that save's child
        self.ctx = ctx

    def take(self):
        snap, self._snap = self._snap, None
        return snap

    def free(self):
        self._snap = None
        if not self._freed:
            self._freed = True
            self._on_free()


class _SnapshotStager:
    """One background thread staging queued device-copies into shm.

    Mailbox of depth 1 with latest-wins for memory snapshots: a newer
    snapshot makes a *queued* (not yet started) older one pointless, so
    it is superseded rather than either dropping the new one or stalling
    the training thread.  A queued STORAGE snapshot is never superseded
    (it carries a durability promise): a newer memory snapshot arriving
    behind it gets ``"busy"`` back — the engine then saves synchronously,
    so the fresher state is never dropped — and a second storage snapshot
    waits (bounded) for the queued one to be taken.  A storage snapshot
    MAY supersede a queued memory one — it writes the same shm with a
    same-or-newer step, so the memory snapshot's purpose is subsumed.

    Invariant across every path: a newer snapshot never loses to an
    older one; the recovery point (shm step) tracks the latest completed
    save.
    """

    def __init__(self, stage_fn):
        self._stage = stage_fn
        self._cond = threading.Condition()
        self._pending = None  # (step, box, extras, persist)
        self._busy = False
        self._stopped = False
        self._thread: Optional[threading.Thread] = None

    def drop_queued_memory(self) -> bool:
        """Free a queued (not yet started) MEMORY snapshot, releasing its
        on-device copy.  Used by the engine when a newer memory save needs
        the HBM slot: the queued older snapshot is pointless once a newer
        one is about to be dispatched.  A queued STORAGE snapshot is never
        dropped (durability promise).  Returns True if something was
        dropped."""
        with self._cond:
            if self._pending is not None and not self._pending[3]:
                logger.info(
                    "queued memory snapshot step=%d dropped for a newer "
                    "save", self._pending[0],
                )
                self._pending[1].free()
                self._pending = None
                self._cond.notify_all()
                return True
        return False

    def submit(self, step, box, extras, persist, wait_timeout: float = 60.0):
        """Queue a staging item.  Returns True when queued, False when the
        stager is stopped, and ``"busy"`` when a queued storage snapshot
        would not drain within ``wait_timeout`` — the caller must then
        fall back to a synchronous save rather than blocking the training
        thread unboundedly (the engine's contract is dispatch-only
        blocking)."""
        with self._cond:
            if self._stopped:
                return False
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="ckpt-stager", daemon=True
                )
                self._thread.start()
            if self._pending is not None and self._pending[3]:
                if not persist:
                    # never displace a durability promise — but never
                    # drop the fresher snapshot either: report busy so
                    # the engine takes the synchronous save path and the
                    # recovery point still advances
                    logger.info(
                        "memory snapshot step=%d: storage snapshot "
                        "step=%d queued; deferring to sync path",
                        step, self._pending[0],
                    )
                    return "busy"
                deadline = time.time() + wait_timeout
                while (
                    self._pending is not None
                    and self._pending[3]
                    and not self._stopped
                ):
                    left = deadline - time.time()
                    if left <= 0:
                        return "busy"
                    self._cond.wait(min(left, 1.0))
                if self._stopped:
                    return False
            if self._pending is not None:
                logger.info(
                    "async snapshot step=%d superseded by step=%d",
                    self._pending[0], step,
                )
                self._pending[1].free()
            self._pending = (step, box, extras, persist)
            self._cond.notify_all()
            return True

    def flush(self, timeout: float = 600.0) -> bool:
        """Wait until nothing is queued and nothing is staging."""
        deadline = time.time() + timeout
        with self._cond:
            while self._pending is not None or self._busy:
                left = deadline - time.time()
                if left <= 0:
                    return False
                self._cond.wait(left)
        return True

    def stop(self, timeout: float = 60.0) -> bool:
        """Drain and stop.  Returns False if the stager thread is still
        running (stuck staging) — the caller must then NOT tear down
        resources the thread touches (shm)."""
        deadline = time.time() + timeout
        drained = self.flush(max(0.0, deadline - time.time()))
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
            thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(max(0.1, deadline - time.time()))
            if thread.is_alive():
                return False
        return drained

    def _run(self):
        while True:
            with self._cond:
                while self._pending is None and not self._stopped:
                    self._cond.wait()
                if self._pending is None:
                    return  # stopped and drained
                item, self._pending = self._pending, None
                self._busy = True
                # a submitter may be waiting for a queued storage
                # snapshot to be taken
                self._cond.notify_all()
            step, box, extras, persist = item
            # drop the tuple ref NOW: holding it through staging would
            # keep the on-device copy alive long after the stage body
            # freed its own reference post-extract
            item = None
            try:
                self._stage(step, box, extras, persist)
            except Exception:  # noqa: BLE001 - must not kill the trainer
                logger.exception("async snapshot step=%d failed", step)
            finally:
                # safety net (normally a no-op: the stage body frees the
                # copy right after device->host extraction)
                box.free()
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()


def tracker_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, CheckpointConstant.TRACKER_FILE)


def read_tracker(ckpt_dir: str, storage=None) -> Optional[int]:
    storage = storage or get_checkpoint_storage(path=ckpt_dir)
    try:
        content = storage.read(tracker_path(ckpt_dir))
        return int(content.strip()) if content else None
    except (OSError, ValueError):
        return None


class CheckpointEngine:
    def __init__(
        self,
        checkpoint_dir: str,
        process_id: Optional[int] = None,
        num_processes: Optional[int] = None,
        scope: str = "",
        replica: bool = False,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.process_id = (
            process_id
            if process_id is not None
            else envs.get_int(NodeEnv.PROCESS_ID)
        )
        self.num_processes = (
            num_processes
            if num_processes is not None
            else envs.get_int(NodeEnv.NUM_PROCESSES)
        )
        self._scope = scope or default_scope()
        self._shm = SharedMemoryBuffer(shm_name(self.process_id, self._scope))
        # memory observatory: the snapshot segment is this process's
        # dominant /dev/shm footprint — register a live byte provider
        # so every mem sample prices the staging buffer (memscope reads
        # it at sample time; a torn-down segment reads as 0)
        try:
            from dlrover_tpu.observability import memscope

            # reads the MAPPED size only (0 until the engine maps the
            # segment): a sample must never attach/remap a segment the
            # engine released — pricing is passive
            memscope.scope().register_host_provider(
                f"ckpt_shm:{self._shm.name}",
                lambda: float(self._shm.size),
            )
        except Exception:  # noqa: BLE001 - telemetry must not break
            pass  # engine construction
        # Each engine OWNS the lock guarding its snapshot buffer (one
        # writer per shm; a job-global lock would make concurrent
        # processes starve each other's snapshots).  The lock dies with
        # this process, so a crashed mid-save worker can never leave it
        # held.  The agent owns the event queue.
        self._lock_name = f"{CKPT_LOCK}_{self._scope}_{self.process_id}"
        self._lock = SharedLock(self._lock_name, create=True)
        # The SharedLock serializes this process against the AGENT's
        # saver, but it is idempotent per client id — and every thread
        # of this engine is one client, so it cannot serialize the
        # background stager against the training thread (a sync save
        # "re-acquiring" mid-stream would interleave two writers on the
        # same buffer and could even release the stager's hold).  This
        # in-process mutex is the thread-vs-thread half of the buffer
        # lock; writers take it FIRST, then the SharedLock (the
        # _buffer_write_lock helper encodes the protocol once).
        self._shm_mu = threading.Lock()
        # guards the durability watermarks (_persist_requested /
        # _last_storage_step): they are check-then-written from both the
        # training thread and the stager thread
        self._persist_mu = threading.Lock()
        queue_name = f"{CKPT_EVENT_QUEUE}_{self._scope}"
        queue_probe = SharedQueue(queue_name, create=False)
        agent_side = queue_probe.is_available()
        self._queue = (
            queue_probe if agent_side else SharedQueue(queue_name, create=True)
        )
        from dlrover_tpu.common.multi_process import SharedDict

        self._progress = SharedDict(
            f"{CKPT_PROGRESS}_{self._scope}", create=False
        )
        self._local_saver = None
        if not agent_side:
            # no agent: persist synchronously from a background thread pool
            from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

            self._local_saver = AsyncCheckpointSaver(
                scope=self._scope, queue=self._queue
            )
            self._local_saver.start()
        self.latest_memory_step = -1
        self._last_storage_step = -1
        # highest step an ASYNC storage save was requested for; compared
        # against _last_storage_step (advanced only once the persist
        # event is truly enqueued) so the exit barrier can detect a
        # dropped persist instead of reporting success on a stale target
        self._persist_requested = -1
        self.last_extras: Dict = {}
        self._registered = False
        self._register_mu = threading.Lock()
        self._stager = _SnapshotStager(self._stage_snapshot)
        # live transient on-device state copies (async snapshots).  The
        # engine's HBM contract is AT MOST ONE: jobs are sized against
        # "one transient extra copy", so a second concurrent copy is an
        # OOM in the training step — refuse it instead of dispatching it.
        self._live_copies = 0
        self._copy_cv = threading.Condition()
        # How long an async save waits for the HBM copy slot before
        # falling back to the synchronous path.  The slot frees as soon
        # as the stager finishes device->host extraction, so this bounds
        # trainer blocking at (remaining extraction time); the sync
        # fallback after it guarantees the recovery point still advances.
        self._slot_wait_s = envs.get_float("DLROVER_CKPT_SLOT_WAIT_S")
        # Buffer-lock acquisition bound for the stager and blocking
        # saves.  The default must outlast a legitimate in-flight
        # STREAM, not just a memcpy: the streaming stager holds the
        # buffer for the whole paced D2H (minutes for a multi-GB state
        # on a slow device->host link), and a blocking
        # storage save that gives up sooner would break its durability
        # promise against a lock that frees moments later.  Env-tunable
        # (also lets tests exercise the timeout reconciliation without
        # waiting minutes).
        self._lock_timeout_s = envs.get_float(
            "DLROVER_TPU_CKPT_LOCK_TIMEOUT_S"
        )
        # States at or below this many local bytes take the SYNCHRONOUS
        # save path even when async was requested: a small state stages
        # in milliseconds, so the async machinery buys nothing while
        # opening a crash window (save returned, snapshot not yet in
        # shm).  The reference's memory save is synchronous-into-shm for
        # exactly this durability reason (flash_checkpoint blog); async
        # device-copy staging is our TPU answer for the multi-GB states
        # where a blocking D2H would stall training for minutes.
        self._async_min_bytes = envs.get_int("DLROVER_TPU_ASYNC_MIN_BYTES")
        # Opt-in snapshot precision policy: "bf16" casts fp32 leaves in
        # the transient device copy, HALVING both the copy's HBM cost
        # (lifting the single-chip async-save envelope from 2*state to
        # 1.5*state plus transients — docs/design.md has the numbers)
        # and the D2H staging traffic.  Restore casts back up
        # automatically (_assemble matches the abstract dtype), so
        # resume works unchanged — at bf16 master precision for the
        # snapshot, which is NOT bit-exact: the last ~16 mantissa bits
        # of fp32 masters are dropped.  Leave empty for exact snapshots.
        self._snapshot_dtype = envs.get_str(
            "DLROVER_TPU_SNAPSHOT_DTYPE"
        ).lower()
        if self._snapshot_dtype in ("bfloat16",):
            self._snapshot_dtype = "bf16"  # accept the dtype's own name
        elif self._snapshot_dtype not in ("", "bf16"):
            # a misspelled knob must not silently size the job against
            # the halved-copy envelope it never gets
            logger.warning(
                "unrecognized DLROVER_TPU_SNAPSHOT_DTYPE=%r (supported: "
                "bf16); snapshots stay at full precision",
                self._snapshot_dtype,
            )
            self._snapshot_dtype = ""
        self._events = get_default_emitter("trainer")
        # Distributed persist (opt-in): storage saves route through the
        # two-phase master-sealed commit — each host's saver writes only
        # the shards it OWNS (replica-group dedup) and reports a
        # manifest instead of running the legacy done-file protocol.
        # The ownership map is computed here (the saver never sees the
        # shardings) and rides the save event.
        self._dist_persist = envs.get_bool("DLROVER_TPU_DIST_PERSIST")
        self._dist_owned: Optional[Dict] = None
        # URL checkpoint dirs (gs://...) get the fsspec backend
        self._storage = get_checkpoint_storage(path=checkpoint_dir)
        self._replica = None
        if replica and self.num_processes > 1:
            from dlrover_tpu.trainer.flash_checkpoint.replica import (
                CkptReplicaManager,
            )

            self._replica = CkptReplicaManager(
                self._shm.name, self.process_id, self.num_processes
            )

    # -- save --------------------------------------------------------------

    @contextmanager
    def _buffer_write_lock(self, timeout: Optional[float]):
        """The two-level buffer-lock protocol, encoded ONCE: thread
        mutex first (stager vs training thread), SharedLock second
        (worker vs agent saver), released in reverse order; the
        SharedLock is never touched unless the mutex is held (a
        same-client "re-acquire" is idempotent and its release would
        strip the stager's cross-process hold mid-stream).

        ``timeout=None`` means non-blocking.  The two acquires share ONE
        deadline — a caller never blocks past the configured bound even
        when both a stream (mutex) and the saver (SharedLock) contend.
        Yields True iff BOTH are held; on False nothing is held."""
        if timeout is None:
            mu_ok = self._shm_mu.acquire(blocking=False)
        else:
            deadline = time.monotonic() + timeout
            mu_ok = self._shm_mu.acquire(timeout=timeout)
        acquired = False
        if mu_ok:
            got = False
            try:
                if timeout is None:
                    got = self._lock.acquire(blocking=False)
                else:
                    left = max(0.05, deadline - time.monotonic())
                    got = self._lock.acquire(timeout=left)
            finally:
                if not got:
                    self._shm_mu.release()
            acquired = got
        try:
            yield acquired
        finally:
            if acquired:
                self._lock.release()
                self._shm_mu.release()

    def save_to_memory(
        self,
        step: int,
        state: Any,
        extras: Optional[Dict] = None,
        block_on_busy: bool = False,
    ) -> float:
        """Blocking device->host snapshot into shm; returns blocked secs.

        When the async saver still holds the buffer (persisting the
        previous snapshot), a plain memory save is *skipped* rather than
        stalling the training loop (reference save_state_dict_to_memory
        behavior); storage saves pass ``block_on_busy=True`` because the
        caller explicitly asked for durability."""
        from dlrover_tpu.observability import metrics as obs_metrics

        t0, blocked = time.monotonic(), -1.0
        try:
            with self._save_span(
                step, {"async": False, "storage": bool(block_on_busy)}
            ) as sp:
                blocked = self._save_to_memory_traced(
                    step, state, extras, block_on_busy, sp
                )
            return blocked
        finally:
            # a skipped non-blocking save is normal contention, not an
            # error (mirrors the ERROR-vs-INFO log split below); only a
            # durability-requested save that could not write counts
            obs_metrics.observe_ckpt_phase(
                "save_memory", time.monotonic() - t0,
                ok=blocked >= 0 or not block_on_busy,
            )

    @contextmanager
    def _save_span(self, step: int, attrs: Dict):
        """``flash.save``: one span a save call.  A synchronous save
        made from inside an asynchronous one (small state, a fallback)
        is that call's, not a second save."""
        live = trace.current_span()
        if live is not None and live.name == "flash.save":
            yield live
            return
        with trace.span(
            "flash.save", attrs={"step": int(step), **attrs}
        ) as sp:
            yield sp

    def _save_to_memory_traced(
        self,
        step: int,
        state: Any,
        extras: Optional[Dict],
        block_on_busy: bool,
        save_span,
    ) -> float:
        from dlrover_tpu import chaos

        chaos.point("flash.save", step=step)  # exception/delay kinds
        t0 = time.time()
        if "outcome" not in save_span.attrs:
            save_span.set_attr("outcome", "sync")
        if not block_on_busy:
            # cheap skip probe: an in-process stager mid-stream, or the
            # agent's saver reading the buffer, must not stall a plain
            # memory save
            with self._buffer_write_lock(None) as free:
                pass
            if not free:
                logger.info(
                    "skip memory snapshot step=%d: stager/saver holds "
                    "the buffer", step,
                )
                save_span.set_attr("outcome", "skipped")
                self._replicate()
                return 0.0
        self._ensure_registered()
        counters = snapshot.StageCounters()
        written = False
        with trace.span("flash.stage", attrs={"step": int(step)}) as sp:
            leaves = snapshot.extract_host_shards(state, counters=counters)
            # Re-acquire for the write.  A plain memory save must never
            # stall the training loop, so it skips if the stager or
            # saver won the buffer between the probe above and here;
            # only explicit storage saves block (bounded).
            t_lock = time.perf_counter()
            with self._buffer_write_lock(
                self._lock_timeout_s if block_on_busy else None
            ) as held:
                lock_wait_s = time.perf_counter() - t_lock
                if held:
                    snapshot.write_snapshot(
                        self._shm, step, leaves, extras, counters=counters
                    )
                    written = True
            sp.set_attrs({
                **counters.as_attrs(), "lock_wait_s": round(lock_wait_s, 6),
            })
        save_span.set_attr("bytes", counters.bytes)
        if not written:
            # writing anyway would tear the snapshot the saver is reading
            logger.log(
                logging.ERROR if block_on_busy else logging.INFO,
                "could not acquire ckpt buffer for step %d; snapshot skipped",
                step,
            )
            save_span.set_attr("outcome", "skipped")
            self._replicate()
            return -1.0
        self.latest_memory_step = step
        self._replicate()
        if envs.get_bool("DLROVER_TPU_PEER_RESTORE"):
            # advertise the committed shm step to the master's broker
            # so a future replacement knows this host can donate it
            from dlrover_tpu.trainer.flash_checkpoint import peer_restore

            peer_restore.maybe_announce(
                step, scope=self._scope, process_id=self.process_id,
                num_processes=self.num_processes,
            )
        blocked = time.time() - t0
        logger.info(
            "flash-ckpt memory snapshot step=%d blocked %.3fs", step, blocked
        )
        self._events.instant(
            TrainerEvents.CKPT_SAVE,
            {"step": int(step), "blocked_s": round(blocked, 4),
             "storage": bool(block_on_busy)},
        )
        return blocked

    def _note_dist_ownership(self, state: Any) -> None:
        """Refresh the ownership map a distributed-persist save event
        carries.  Ownership depends only on the shardings (not values),
        so the map stays valid when the saver relabels the event to a
        newer shm step of the same mesh."""
        if not self._dist_persist:
            return
        try:
            from dlrover_tpu.trainer.flash_checkpoint import distributed

            self._dist_owned = distributed.owned_event_map(
                state, self.process_id, self.num_processes
            )
        except Exception as e:  # noqa: BLE001 - fall back to legacy
            logger.warning(
                "distributed persist: ownership planning failed (%s); "
                "this save falls back to the legacy persist protocol", e,
            )
            self._dist_owned = None

    def save_to_storage(
        self, step: int, state: Any, extras: Optional[Dict] = None
    ) -> float:
        """Snapshot to shm + async persist event; returns blocked secs."""
        self._note_dist_ownership(state)
        # record the durability promise BEFORE attempting the write
        # (mirroring the async path): if the save is dropped below, the
        # exit barrier must see requested > persisted and report the
        # loss instead of succeeding against a stale target
        with self._persist_mu:
            self._persist_requested = max(self._persist_requested, int(step))
        blocked = self.save_to_memory(step, state, extras, block_on_busy=True)
        if blocked < 0:
            # the snapshot was not written (buffer-lock timeout — e.g. a
            # stream held it past DLROVER_TPU_CKPT_LOCK_TIMEOUT_S): an
            # event now would persist stale data under this step's name.
            # Reconcile the durability intent the same way the async drop
            # does — persist whatever committed snapshot shm holds, or
            # clear the request loudly — instead of surfacing the loss
            # only at the exit barrier.
            self._reconcile_dropped_stage(step, persist=True)
            return blocked
        self._queue.put(self._save_event(step), timeout=60)
        with self._persist_mu:
            self._last_storage_step = max(self._last_storage_step, int(step))
        return blocked

    # -- async save --------------------------------------------------------

    def save_to_memory_async(
        self, step: int, state: Any, extras: Optional[Dict] = None
    ) -> float:
        """Snapshot with ~dispatch-only blocking (see module docstring).

        Enqueues an on-device copy of ``state`` — ordered before any later
        step that donates/overwrites the source buffers — and returns; a
        background thread stages the copy to host shm.  Falls back to the
        sync path when replicas are enabled (the replica exchange is a
        collective and must not run off the main thread) or when the
        device copy cannot be dispatched (e.g. HBM too tight for a
        transient second copy of the state).  Never skips: if a previous
        copy is still staging, a queued older memory snapshot is
        superseded, else this call waits (bounded) for the HBM slot, else
        it saves synchronously — the recovery point always advances to
        this step."""
        if self._replica is not None:
            return self.save_to_memory(step, state, extras)
        return self._async_save(step, state, extras, persist=False)

    def save_to_storage_async(
        self, step: int, state: Any, extras: Optional[Dict] = None
    ) -> float:
        """Storage save with ~dispatch-only blocking: the persist event is
        enqueued by the background thread AFTER the shm write, preserving
        the snapshot-before-event commit order.  ``_last_storage_step``
        (the exit-barrier target) is also advanced by the stager, only
        once the event is actually enqueued — a failed staging must not
        leave the barrier waiting on a step that will never persist."""
        if self._replica is not None:
            return self.save_to_storage(step, state, extras)
        self._note_dist_ownership(state)
        return self._async_save(step, state, extras, persist=True)

    def _on_copy_freed(self):
        with self._copy_cv:
            self._live_copies -= 1
            self._copy_cv.notify_all()

    @staticmethod
    def _local_state_nbytes(state) -> int:
        """Host-local bytes the staging would move (addressable shards
        only; metadata-only walk, no device sync)."""
        import math

        import jax

        total = 0
        for a in jax.tree.leaves(state):
            if hasattr(a, "addressable_shards"):
                for s in a.addressable_shards:
                    total += (
                        math.prod(s.data.shape) * s.data.dtype.itemsize
                        if s.data.shape else s.data.dtype.itemsize
                    )
        return total

    def _async_save(self, step, state, extras, persist: bool) -> float:
        nbytes = self._local_state_nbytes(state)
        with self._save_span(step, {
            "async": True, "storage": persist, "bytes": nbytes,
        }) as sp:
            return self._async_save_traced(
                step, state, extras, persist, nbytes, sp
            )

    def _async_save_traced(
        self, step, state, extras, persist: bool, nbytes: int, save_span
    ) -> float:
        import jax
        import jax.numpy as jnp

        def sync_save(outcome: str, block_on_busy: bool = False) -> float:
            save_span.set_attr("outcome", outcome)
            if persist:
                return self.save_to_storage(step, state, extras)
            return self.save_to_memory(
                step, state, extras, block_on_busy=block_on_busy
            )

        t0 = time.time()
        if nbytes <= self._async_min_bytes:
            # small state: sync staging is ~free and leaves no window
            # where a crash right after save() loses the snapshot
            return sync_save("sync")
        # HBM accounting: never dispatch a second on-device state copy
        # while one is still live (queued or staging pre-extraction).  A
        # newer snapshot must NEVER lose to an older in-flight one — the
        # recovery point has to track the latest save — so when the slot
        # is held we (1) supersede a merely-QUEUED older memory copy,
        # which frees its HBM slot immediately, then (2) wait bounded for
        # the slot (it frees as soon as the stager finishes device->host
        # extraction, well before the shm write), and (3) as a last
        # resort take the synchronous save path.  Skipping is not an
        # option: under slow staging (real-TPU D2H) saves can arrive
        # faster than staging drains, and a skip would age the recovery
        # point without bound.
        sync_fallback = False
        with trace.span(
            "flash.save.slot_wait", attrs={"live_copies": self._live_copies}
        ):
            # Not under _copy_cv: freeing the queued copy runs
            # _on_copy_freed, which locks _copy_cv from under the
            # stager's own lock — taking the two locks here in the
            # opposite order would deadlock against the stager thread's
            # box.free().  Storage saves supersede a queued memory item
            # too: its purpose is subsumed by the same-or-newer shm
            # write, and freeing it hands us the slot instantly instead
            # of waiting out its paced stream.
            if self._live_copies > 0:
                self._stager.drop_queued_memory()
            with self._copy_cv:
                if self._live_copies > 0:
                    deadline = t0 + self._slot_wait_s
                    while self._live_copies > 0:
                        left = deadline - time.time()
                        if left <= 0:
                            break
                        self._copy_cv.wait(left)
                    sync_fallback = self._live_copies > 0
                if not sync_fallback:
                    self._live_copies += 1
        if sync_fallback:
            # NOT under the cv: the sync save takes minutes and the
            # stager must still be able to report its copy freed
            logger.warning(
                "async %s save step=%d: previous device copy still "
                "live after %.0fs; sync fallback",
                "storage" if persist else "memory", step, self._slot_wait_s,
            )
            self._events.instant(
                TrainerEvents.CKPT_SYNC_FALLBACK,
                {"step": int(step), "storage": persist},
            )
            # block_on_busy: the fallback exists to GUARANTEE the
            # recovery point advances; a skippable save here would
            # re-open the silent-staleness hole
            return sync_save("sync_fallback", block_on_busy=True)
        cast_to = None
        if self._snapshot_dtype == "bf16":
            cast_to = jnp.bfloat16

        def _snapshot_copy(a):
            if not hasattr(a, "addressable_shards"):
                return a
            if cast_to is not None and a.dtype == jnp.float32:
                # astype IS the copy (new buffers, enqueued before any
                # later donation), at half the HBM and half the D2H
                return a.astype(cast_to)
            return jnp.copy(a)

        try:
            with trace.span("flash.save.device_copy") as sp:
                snap = jax.tree.map(_snapshot_copy, state)
                sp.set_attr("leaves", len(jax.tree.leaves(snap)))
        except Exception as e:  # noqa: BLE001 - HBM pressure, backend quirks
            self._on_copy_freed()
            logger.warning(
                "on-device snapshot copy failed (%s); sync fallback", e
            )
            self._events.instant(
                TrainerEvents.CKPT_SYNC_FALLBACK,
                {"step": int(step), "storage": persist,
                 "reason": "device-copy-failed"},
            )
            return sync_save("sync_fallback")
        with trace.span("flash.save.submit") as sp:
            box = _DeviceCopy(
                snap, self._on_copy_freed, ctx=save_span.context()
            )
            del snap
            if persist:
                with self._persist_mu:
                    self._persist_requested = max(
                        self._persist_requested, int(step)
                    )
            submitted = self._stager.submit(int(step), box, extras, persist)
            sp.set_attr("result", submitted)
        if submitted is not True:
            box.free()
            if submitted == "busy":
                # queued storage snapshot refused to drain / blocks a
                # fresher memory snapshot: keep the promise synchronously
                # instead of dropping the newer state or blocking the
                # training thread for unbounded minutes
                logger.warning(
                    "async %s save step=%d: stager busy; sync fallback",
                    "storage" if persist else "memory", step,
                )
                return sync_save("sync_fallback", block_on_busy=True)
            # stager stopped (engine closing): same contract as the sync
            # path's skip — the caller must not believe this step is safe
            logger.warning(
                "async snapshot step=%d dropped: stager stopped", step
            )
            save_span.set_attr("outcome", "dropped")
            return -1.0
        save_span.set_attr("outcome", "async")
        blocked = time.time() - t0
        self._events.instant(
            TrainerEvents.CKPT_SAVE,
            {"step": int(step), "blocked_s": round(blocked, 4),
             "storage": persist, "async": True},
        )
        return blocked

    def _stage_snapshot(self, step, box, extras, persist: bool):
        """Stager thread body: stage the device copy into shm, maybe
        emit the persist event.

        The shm layout is precomputed from abstract shapes, the buffer
        lock is taken for the WHOLE stream (shm is mid-rewrite the entire
        time — the seqlock generation additionally marks it dirty for
        lock-free readers), and each paced D2H chunk lands directly at
        its final offset, releasing its share of the on-device copy as
        it goes.

        All of it is one ``flash.stage`` span, child of the ``flash.save``
        that submitted it (``box.ctx``), which carries at its close what the
        stage counted: where the seconds between the call and the
        landing went."""
        from dlrover_tpu.observability import jitscope

        if jitscope.enabled():
            jitscope.install()
        compiled0 = jitscope._thread_counters()
        counters = snapshot.StageCounters()
        pacer = snapshot.StagePacer()
        with trace.span(
            "flash.stage", attrs={"step": int(step)}, parent=box.ctx
        ) as sp:
            try:
                self._stage_snapshot_traced(
                    step, box, extras, persist, counters, pacer, sp
                )
            finally:
                compile_s, hits, misses = (
                    b - a for a, b in
                    zip(compiled0, jitscope._thread_counters())
                )
                sp.set_attrs({
                    **counters.as_attrs(),
                    "compile_s": round(compile_s, 6),
                    "compiles": hits + misses,
                    "pacer": pacer.summary(),
                })

    def _stage_snapshot_traced(
        self, step, box, extras, persist, counters, pacer, sp
    ):
        self._ensure_registered()
        snap = box.take()
        # plan only (no transfer): refs move into the leaves list so
        # streaming can release them shard by shard
        leaves = snapshot.plan_shards(snap)
        del snap
        persist_step = step if persist else None
        staged = False
        t_lock = time.perf_counter()
        with self._buffer_write_lock(self._lock_timeout_s) as held:
            sp.set_attr(
                "lock_wait_s", round(time.perf_counter() - t_lock, 6)
            )
            if held:
                try:
                    meta = snapshot.read_snapshot_meta(self._shm)
                    if meta and meta["step"] > step:
                        # a newer snapshot already landed (e.g. a sync-
                        # fallback save raced ahead of this stager item);
                        # overwriting would regress the recovery point.
                        # A persist item keeps its durability promise by
                        # persisting the NEWER content: the saver re-
                        # reads shm meta and relabels to the step it
                        # finds, so the event just points it at the shm.
                        if persist:
                            persist_step = int(meta["step"])
                        logger.info(
                            "async snapshot step=%d obsolete (shm at "
                            "%d)%s", step, meta["step"],
                            "; persisting the newer snapshot"
                            if persist else "",
                        )
                        step = int(meta["step"])
                    elif not (meta and meta["step"] == step):
                        pacer.clock.staging_started()
                        try:
                            snapshot.stream_snapshot(
                                self._shm, step, leaves, extras,
                                pacer=pacer, counters=counters,
                            )
                        finally:
                            pacer.clock.staging_finished()
                    staged = True
                finally:
                    box.free()
        if not staged:
            box.free()
            self._reconcile_dropped_stage(step, persist)
            return
        self.latest_memory_step = max(self.latest_memory_step, step)
        if envs.get_bool("DLROVER_TPU_PEER_RESTORE"):
            from dlrover_tpu.trainer.flash_checkpoint import peer_restore

            peer_restore.maybe_announce(
                step, scope=self._scope, process_id=self.process_id,
                num_processes=self.num_processes,
            )
        if persist_step is not None:
            self._queue.put(self._save_event(persist_step), timeout=60)
            # only now is the persist in flight; the exit barrier may
            # safely wait on it
            with self._persist_mu:
                self._last_storage_step = max(
                    self._last_storage_step, persist_step
                )
        logger.info(
            "flash-ckpt async snapshot step=%d staged (training not "
            "blocked)", step,
        )

    def _reconcile_dropped_stage(self, step: int, persist: bool):
        """A staging item was dropped on the buffer-lock timeout.  For a
        memory snapshot that only ages the recovery point; for
        ``persist=True`` it breaks a durability promise.  Reconcile the
        STORAGE side — persist whatever committed snapshot the shm
        currently holds, so the freshest recoverable state still reaches
        disk — without masking the failure: unless the shm snapshot is
        at or beyond the requested step (promise met by newer content),
        ``_persist_requested`` keeps the broken target and the exit
        barrier reports False fast instead of waiting on a persist that
        was never enqueued."""
        logger.error(
            "snapshot step=%d: buffer busy after %.0fs; staging dropped",
            step, self._lock_timeout_s,
        )
        if not persist:
            return
        # lock-free peek is safe here: read_snapshot_meta refuses torn
        # (odd-generation) snapshots, and the event's saver re-validates
        # under the lock before persisting any bytes
        meta = snapshot.read_snapshot_meta(self._shm)
        got = int(meta["step"]) if meta is not None else -1
        with self._persist_mu:
            already_durable = got <= self._last_storage_step
        if meta is not None and not already_durable:
            # fallback persist: the newest committed snapshot still
            # reaches storage even though it may be older than promised
            self._queue.put(self._save_event(got), timeout=60)
            with self._persist_mu:
                self._last_storage_step = max(self._last_storage_step, got)
        if got >= step:
            # a newer snapshot raced ahead and is (being) persisted: the
            # durability promise for ``step`` is met by newer content
            return
        logger.error(
            "durability promise for step %d is BROKEN (buffer-lock "
            "timeout dropped the staging); %s — the exit barrier will "
            "report this failure", step,
            f"persisted the older shm snapshot at step {got} as a "
            "fallback" if got >= 0 else
            "no committed shm snapshot existed to persist in its place",
        )

    def _flush_async(self, timeout: float = 600.0) -> bool:
        """Wait for queued/in-flight background staging to finish."""
        return self._stager.flush(timeout)

    def _save_event(self, step: int) -> Dict:
        event = {
            "type": "save",
            "step": int(step),
            "shm": self._shm.name,
            "lock": self._lock_name,
            "ckpt_dir": self.checkpoint_dir,
            "process_id": self.process_id,
            "num_processes": self.num_processes,
        }
        if self._dist_persist and self._dist_owned is not None:
            event["dist"] = True
            event["owned"] = self._dist_owned
        return event

    def _ensure_registered(self):
        """Tell the agent-side saver about our shm so save-on-failure can
        persist snapshots that never saw a storage event.  Thread-safe:
        called from both the training thread and the async stager."""
        with self._register_mu:
            if self._registered:
                return
            self._queue.put(
                {
                    "type": "register",
                    "shm": self._shm.name,
                    "lock": self._lock_name,
                    "ckpt_dir": self.checkpoint_dir,
                    "process_id": self.process_id,
                    "num_processes": self.num_processes,
                    "step": -1,
                    # save-on-failure must speak the same commit
                    # protocol the dir uses; with no ownership map the
                    # saver persists every local shard (safe: extra
                    # bytes, correct manifest)
                    "dist": self._dist_persist,
                },
                timeout=30,
            )
            self._registered = True

    # -- load --------------------------------------------------------------

    def load_from_storage(
        self, abstract_state: Any, shardings: Any
    ) -> Tuple[Optional[Any], int]:
        """Restore (state, step) from STORAGE only, bypassing the shm
        fast path.  For readers whose source of truth is the on-disk
        step set — e.g. a TensorHandoff consumer, where a same-named shm
        segment on this host (the producer's, or a stale one from a dead
        run) may hold data that is not the announced version."""
        return self._load_from_storage(abstract_state, shardings)

    def storage_leaves_to_host(
        self,
        paths: List[str],
        step: Optional[int] = None,
        transform=None,
    ) -> Optional[Tuple[int, Dict[str, np.ndarray]]]:
        """(step, {path: full ndarray}) for ``paths`` — assembled on the
        HOST, no device arrays.  For leaves that must be transformed
        before they can live on the current mesh (the dp-shaped
        error-feedback stacks in ``Trainer.load_state``): materializing
        them replicated on every device first would cost dp_old
        full-gradient-sized copies of HBM per device.

        ``step`` pins the read to exactly that step (the one a
        COLLECTIVE load already agreed on — scanning for an alternative
        here could silently diverge processes); without it the newest
        readable step wins.  ``transform`` is applied per leaf right
        after its read, so a reducing transform (e.g. summing a
        ``(dp_old, *leaf)`` stack) bounds peak host RAM to one leaf's
        stack instead of the whole tree's.

        Paths absent from the step are OMITTED from the result rather
        than failing the whole read (a dp shrink can make new leaves
        shardable, so the caller may legitimately request EF paths the
        old checkpoint never stored); only a step carrying none of the
        requested paths (or unreadable) yields None."""

        def try_step(cand: int):
            step_dir = os.path.join(self.checkpoint_dir, str(cand))
            try:
                loaded = self._index_maps_from_storage(step_dir)
            except (ValueError, OSError, KeyError):
                return None
            if loaded is None:
                return None
            maps, _ = loaded
            present = [p for p in paths if p in maps]
            if not present:
                return None
            out = {}
            try:
                for p in present:
                    arr = maps[p].read(
                        tuple(slice(0, d) for d in maps[p].gshape)
                    )
                    out[p] = transform(arr) if transform else arr
            except (ValueError, OSError):
                return None
            return out

        if step is not None:
            out = try_step(step)
            return (step, out) if out is not None else None
        for cand in self._storage_step_candidates():
            out = try_step(cand)
            if out is not None:
                return cand, out
        return None

    def _storage_step_candidates(self) -> List[int]:
        """Storage steps newest-first, the tracked step first."""
        candidates: List[int] = []
        tracked = read_tracker(self.checkpoint_dir, self._storage)
        if tracked is not None:
            candidates.append(tracked)
        for name in self._storage.listdir(self.checkpoint_dir):
            if name.isdigit() and int(name) not in candidates:
                candidates.append(int(name))
        candidates.sort(reverse=True)
        if tracked is not None and candidates and candidates[0] != tracked:
            candidates.remove(tracked)
            candidates.insert(0, tracked)
        return candidates

    def load(
        self, abstract_state: Any, shardings: Any
    ) -> Tuple[Optional[Any], int]:
        """Restore (state, step): shm fast path, storage fallback.

        ``abstract_state``: pytree of ShapeDtypeStruct; ``shardings``: same
        tree of NamedSharding (the target layout — may differ from the one
        saved; storage restore reshards).

        Multi-process: the memory-vs-storage-vs-fresh choice is agreed
        COLLECTIVELY (allgather of each process's feasible step) — a mixed
        restore would silently diverge the replicas."""
        from dlrover_tpu.observability import metrics as obs_metrics

        t0, step_out = time.monotonic(), -1
        try:
            with trace.span("flash.restore") as sp:
                state, step_out = self._load_traced(
                    abstract_state, shardings
                )
                sp.set_attr("step", int(step_out))
            return state, step_out
        finally:
            obs_metrics.observe_ckpt_phase(
                "restore", time.monotonic() - t0, ok=step_out >= 0
            )

    def _load_traced(
        self, abstract_state: Any, shardings: Any
    ) -> Tuple[Optional[Any], int]:
        from dlrover_tpu import chaos

        chaos.point("flash.restore")  # exception/delay kinds
        # a restore must see the latest snapshot, not race the stager
        self._flush_async()
        # extras must always describe the checkpoint actually restored:
        # a memory candidate may set them and then LOSE the collective
        # agreement (falling back to an older storage step), so reset
        # first and let the winning path re-populate.
        self.last_extras = {}
        load_span = self._events.duration(TrainerEvents.CKPT_LOAD).begin()
        mem_step, maps, extras = self._memory_candidate(
            abstract_state, shardings
        )
        agreed_mem = self._agree_on_step(mem_step)
        if agreed_mem < 0 and self._replica is not None:
            # a replaced host has an empty shm but its successor holds a
            # replica: one collective exchange restores it, then the
            # memory agreement is retried (same collective count on every
            # process — the agreement result above was identical job-wide)
            if self._replica.restore_from_peers():
                self._shm.close()
                self._shm = SharedMemoryBuffer(self._shm.name)
            mem_step, maps, extras = self._memory_candidate(
                abstract_state, shardings
            )
            agreed_mem = self._agree_on_step(mem_step)
        if agreed_mem < 0 and envs.get_bool("DLROVER_TPU_PEER_RESTORE"):
            # checkpoint-free fast path: pull the lost shards from
            # surviving peers' shm into OUR shm, then retry the memory
            # candidate.  The agreement above was collective and its
            # verdict identical job-wide, so every process enters this
            # branch together (survivors skip the fetch — their shm
            # already holds the brokered step) and the re-agreement
            # below keeps the collective count symmetric.
            from dlrover_tpu.trainer.flash_checkpoint import peer_restore

            try:
                peer_restore.try_engine_recover(
                    self, abstract_state, shardings
                )
            except Exception as e:  # noqa: BLE001 - the fast path must
                # never make a recovery WORSE than the storage restore
                logger.warning("peer restore failed (%s); using storage", e)
            mem_step, maps, extras = self._memory_candidate(
                abstract_state, shardings
            )
            agreed_mem = self._agree_on_step(mem_step)
        if agreed_mem >= 0 and agreed_mem == mem_step and maps is not None:
            state = self._assemble(abstract_state, shardings, maps)
            self.last_extras = extras
            logger.info("restored step %d from shared memory", agreed_mem)
            load_span.end(step=agreed_mem, source="memory")
            return state, agreed_mem
        state, step = self._load_from_storage(abstract_state, shardings)
        load_span.end(
            step=step, source="storage" if step >= 0 else "fresh"
        )
        return state, step

    def _agree_on_step(self, step: int) -> int:
        """All processes must report the same non-negative step."""
        if self.num_processes <= 1:
            return step
        try:
            from jax.experimental import multihost_utils

            with trace.span("flash.restore.agreement"):
                steps = np.asarray(
                    multihost_utils.process_allgather(
                        np.asarray([step], dtype=np.int64)
                    )
                ).reshape(-1)
        except Exception as e:  # noqa: BLE001 - agreement must not crash
            logger.warning("restore agreement failed (%s); using storage", e)
            return -1
        if (steps == steps[0]).all() and steps[0] >= 0:
            return int(steps[0])
        if steps.max() >= 0:
            logger.info(
                "processes disagree on memory snapshot (%s); using storage",
                steps.tolist(),
            )
        return -1

    def _memory_candidate(self, abstract_state, shardings):
        """(step, maps, extras) if this process's shm fully covers its
        addressable shards under the target sharding, else (-1, None, {}).

        Pure read: ``last_extras`` is assigned only in ``load()`` once a
        candidate actually WINS the collective agreement — a losing
        candidate's extras must never leak into the restored state."""
        with self._buffer_write_lock(60) as _held:
            # _held may be False when a stager stream is mid-flight or
            # the saver is persisting: read lock-free anyway and let the
            # seqlock generation check reject a torn read
            loaded = self._index_maps_from_shm()
        if loaded is None:
            return -1, None, {}
        maps, step, extras = loaded
        if not self._covers_all(abstract_state, shardings, maps):
            return -1, None, {}
        return step, maps, extras or {}

    def _index_maps_from_shm(self) -> Optional[Tuple[Dict, int, Dict]]:
        # seqlock read: the generation must be even (committed) before
        # the read and UNCHANGED after it.  With the streaming stager
        # the shm is mid-rewrite for whole staging windows; a reader
        # that raced one (e.g. a load whose lock acquire timed out)
        # must detect the torn read instead of assembling garbage.
        gen0 = snapshot.read_generation(self._shm)
        meta = snapshot.read_snapshot_meta(self._shm)
        if meta is None:
            return None
        maps: Dict[str, ShardIndexMap] = {}
        for leaf in meta["leaves"]:
            m = ShardIndexMap(leaf["dtype"], leaf["gshape"])
            for shard_meta in leaf["shards"]:
                data = snapshot.read_shard_bytes(
                    self._shm, meta, shard_meta, leaf["dtype"]
                )
                m.add(shard_meta["index"], data)
            maps[leaf["path"]] = m
        if snapshot.read_generation(self._shm) != gen0:
            logger.warning(
                "shm snapshot generation moved during read; discarding "
                "the torn memory candidate"
            )
            return None
        return maps, meta["step"], meta.get("extras", {})

    def _try_dist_restore(self, abstract_state, shardings, floor: int):
        """Restore from a sealed distributed commit when one exists and
        is at least as new as the best legacy candidate (``floor``).
        Returns (state, step) or (None, -1) to fall through.  No
        collective agreement is needed — the sealed COMMITTED pointer
        is job-global, so every process picks the same step — but the
        dist-vs-legacy DECISION is also deterministic (same storage
        reads on every process)."""
        from dlrover_tpu.trainer.flash_checkpoint import distributed

        try:
            dist_step = distributed.read_committed_step(
                self.checkpoint_dir, self._storage
            )
        except Exception:  # noqa: BLE001 - probe must not kill restore
            dist_step = -1
        probe = dist_step if 0 <= floor <= dist_step else -1
        if self.num_processes > 1:
            # the dist-vs-legacy CHOICE must be collective: a shared-FS
            # visibility race on the COMMITTED pointer could otherwise
            # send some processes down this branch (0 collectives) and
            # others into the legacy loop (1 allgather) — a deadlock,
            # then silent divergence.  This allgather runs on EVERY
            # process unconditionally, keeping collective counts equal.
            probe = self._agree_on_step(probe)
        if probe < 0:
            return None, -1
        dist_step = probe
        try:
            engine = distributed.DistributedCheckpointEngine(
                self.checkpoint_dir,
                process_id=self.process_id,
                num_processes=self.num_processes,
                storage=self._storage,
            )
            state, step = engine.load(
                abstract_state, shardings, step=dist_step
            )
        except (OSError, ValueError, KeyError) as e:
            if self.num_processes > 1:
                # the agreement already happened: a unilateral fallback
                # would diverge the replicas (same contract as the
                # legacy assembly failure below) — fail loudly
                raise
            logger.error(
                "distributed restore of sealed step %d failed (%s); "
                "falling back to legacy step candidates", dist_step, e,
            )
            return None, -1
        if state is not None:
            self.last_extras = engine.last_extras
            logger.info(
                "restored step %d from a distributed commit "
                "(read %.1f/%.1f MB)", step,
                engine.last_read_stats.get("bytes_read", 0) / 1e6,
                engine.last_read_stats.get("bytes_total", 0) / 1e6,
            )
        return state, step

    def _load_from_storage(self, abstract_state, shardings):
        # tracked step first, then older committed steps as fallbacks if
        # the tracked one is unreadable (partially deleted / corrupted)
        candidates = self._storage_step_candidates()
        # a sealed distributed commit at-or-past the best legacy step
        # wins: with DLROVER_TPU_DIST_PERSIST the shards/manifests/
        # COMMITTED layout is the ONLY place new saves land, and a
        # legacy-only scan would silently resume from a stale pre-flip
        # step (or from scratch)
        state, step = self._try_dist_restore(
            abstract_state, shardings,
            floor=candidates[0] if candidates else 0,
        )
        if state is not None:
            return state, step
        excluded: set = set()
        while True:
            # find MY newest fully-readable step, then agree collectively
            # in a single allgather (a fixed collective count per load()
            # — variable counts across processes would deadlock the
            # agreement itself; the retry loop below only re-enters for
            # single-process engines, where agreement is local)
            best_step, best_maps, best_extras = -1, None, {}
            for step in candidates:
                if step in excluded:
                    continue
                step_dir = os.path.join(self.checkpoint_dir, str(step))
                try:
                    loaded = self._index_maps_from_storage(step_dir)
                except (ValueError, OSError, KeyError) as e:
                    logger.warning(
                        "checkpoint step %d unreadable (%s)", step, e
                    )
                    continue
                if loaded is None:
                    continue
                maps, extras = loaded
                if self._covers_all(abstract_state, shardings, maps):
                    best_step, best_maps, best_extras = step, maps, extras
                    break
            agreed = self._agree_on_step(best_step)
            if agreed < 0 or agreed != best_step or best_maps is None:
                # disagreement (shared-FS race / one-host corruption):
                # every process starts fresh rather than silently
                # diverging
                if best_step >= 0 or agreed >= 0:
                    logger.warning(
                        "storage restore not agreed (mine=%d agreed=%d); "
                        "starting fresh", best_step, agreed,
                    )
                self.last_extras = {}
                return None, -1
            self.last_extras = best_extras
            try:
                state = self._assemble(abstract_state, shardings, best_maps)
            except (OSError, ValueError) as e:
                # lazy reads surfaced corruption (CRC mismatch, vanished
                # range) only at assembly.  Single-process: fall back to
                # the next older candidate.  Multi-process: the agreement
                # already happened, so a unilateral fallback would
                # diverge the replicas — fail loudly instead (or run
                # DLROVER_TPU_VERIFY_CRC=eager to reject corrupt steps
                # at probe time, before the agreement).
                if self.num_processes > 1:
                    raise
                logger.error(
                    "checkpoint step %d failed integrity checks at "
                    "assembly (%s); trying an older step", agreed, e,
                )
                excluded.add(agreed)
                self.last_extras = {}
                continue
            logger.info("restored step %d from storage", agreed)
            return state, agreed

    def _covers_all(self, abstract_state, shardings, maps) -> bool:
        import jax

        flat_abs = jax.tree_util.tree_flatten_with_path(abstract_state)[0]
        flat_shard = jax.tree_util.tree_flatten(shardings)[0]
        for (key_path, abs_leaf), sharding in zip(flat_abs, flat_shard):
            path = snapshot._path_str(key_path)
            index_map = maps.get(path)
            if index_map is None:
                return False
            if tuple(index_map.gshape) != tuple(abs_leaf.shape):
                # a GLOBAL-shape mismatch is a different tensor, not a
                # resharding: stored shards of a larger global (e.g. a
                # dp-shaped error-feedback stack saved at a higher dp
                # degree) may well cover a smaller target's slices, and
                # assembling that corner would be silent corruption
                return False
            for index in sharding.addressable_devices_indices_map(
                tuple(abs_leaf.shape)
            ).values():
                if not index_map.covers(index):
                    return False
        return True

    def _verify_chunks(self, bin_path: str, chunks: List[Dict]):
        """Check recorded per-chunk CRC32s against the stored payload
        (eager mode: whole payload at probe time, BEFORE the collective
        agreement, so a corrupt candidate loses on every process
        together).  A mismatch raises OSError — rejecting the candidate
        at probe time."""
        import zlib

        for chunk in chunks:
            off, n = int(chunk["offset"]), int(chunk["nbytes"])
            data = self._storage.read_range(bin_path, off, n)
            if data is None or len(data) != n:
                raise OSError(f"chunk vanished: {bin_path}@{off}+{n}")
            crc = zlib.crc32(memoryview(np.ascontiguousarray(data)))
            if crc != int(chunk["crc32"]):
                raise OSError(
                    f"chunk checksum mismatch: {bin_path}@{off}+{n} "
                    f"(stored {chunk['crc32']:#010x}, got {crc:#010x})"
                )

    def _index_maps_from_storage(self, step_dir: str):
        import json

        metas = [
            f for f in self._storage.listdir(step_dir)
            if f.startswith("meta_") and f.endswith(".json")
        ]
        if not metas:
            return None
        crc_mode = envs.get_str("DLROVER_TPU_VERIFY_CRC").lower()
        maps: Dict[str, ShardIndexMap] = {}
        extras: Dict = {}
        for meta_file in metas:
            raw = self._storage.read(os.path.join(step_dir, meta_file))
            if raw is None:
                raise OSError(f"meta file vanished: {meta_file}")
            meta = json.loads(raw)
            if meta.get("extras"):
                extras = meta["extras"]
            bin_path = os.path.join(step_dir, meta["bin_file"])
            # payload reads are lazy (ranged, post-agreement), so validate
            # the blob NOW while falling back to an older candidate is
            # still possible: missing or TRUNCATED (killed writer /
            # partial upload) payloads must lose at probe time, not crash
            # the restore after the collective agreement
            blob_size = self._storage.size(bin_path)
            if blob_size is None:
                raise OSError(f"shard payload missing: {bin_path}")
            needed = max(
                (
                    int(s["offset"]) + int(s["nbytes"])
                    for leaf in meta["leaves"]
                    for s in leaf["shards"]
                ),
                default=0,
            )
            if blob_size < needed:
                raise OSError(
                    f"shard payload truncated: {bin_path} has "
                    f"{blob_size} bytes, needs {needed}"
                )
            # CRC32s (persist format 2).  "eager" verifies the recorded
            # writer chunks over the whole payload at probe time —
            # corruption then rejects the candidate BEFORE the
            # collective agreement, so the restore falls back to an
            # older step on every process; "lazy" (default) verifies
            # each shard's OWN recorded CRC against exactly the bytes
            # its ranged read fetches — zero read amplification, the
            # ranged-GET economics stay intact.  Metas without CRCs
            # (pre-round-7 checkpoints) load unverified as before.
            chunk_list = meta.get("chunks") or []
            if chunk_list and crc_mode == "eager":
                self._verify_chunks(bin_path, chunk_list)
            lazy_verify = crc_mode == "lazy"
            for leaf in meta["leaves"]:
                m = maps.setdefault(
                    leaf["path"], ShardIndexMap(leaf["dtype"], leaf["gshape"])
                )
                for shard_meta in leaf["shards"]:
                    # lazy ranged read: only shards the target sharding
                    # actually assembles get fetched (a multi-host
                    # restore must not pull every host's full blob)
                    def load(
                        _path=bin_path,
                        _start=shard_meta["offset"],
                        _nbytes=shard_meta["nbytes"],
                        _dtype=leaf["dtype"],
                        _shape=tuple(shard_meta["shape"]),
                        _crc=(
                            shard_meta.get("crc32")
                            if lazy_verify else None
                        ),
                    ):
                        buf = self._storage.read_range(
                            _path, _start, _nbytes
                        )
                        if buf is None:
                            raise OSError(
                                f"shard payload vanished: {_path}"
                            )
                        if _crc is not None:
                            import zlib

                            got = zlib.crc32(memoryview(
                                np.ascontiguousarray(buf)
                            ))
                            if got != int(_crc):
                                raise OSError(
                                    "shard checksum mismatch: "
                                    f"{_path}@{_start}+{_nbytes} (stored "
                                    f"{int(_crc):#010x}, got {got:#010x})"
                                )
                        return (
                            np.asarray(buf)
                            .view(np.dtype(_dtype))
                            .reshape(_shape)
                        )

                    m.add_lazy(shard_meta["index"], load)
        return maps, extras

    def _assemble(self, abstract_state, shardings, maps: Dict):
        import jax

        flat_abs = jax.tree_util.tree_flatten_with_path(abstract_state)
        flat_shard = jax.tree_util.tree_flatten(shardings)[0]
        leaves = []
        for ((key_path, abs_leaf), sharding) in zip(flat_abs[0], flat_shard):
            path = snapshot._path_str(key_path)
            index_map = maps.get(path)
            if index_map is None:
                raise ValueError(f"checkpoint missing leaf {path}")

            def cb(index, _m=index_map, _dtype=abs_leaf.dtype):
                return _m.read(index).astype(_dtype, copy=False)

            arr = jax.make_array_from_callback(
                tuple(abs_leaf.shape), sharding, cb
            )
            leaves.append(arr)
        return jax.tree_util.tree_unflatten(flat_abs[1], leaves)

    # -- misc --------------------------------------------------------------

    def _replicate(self):
        if self._replica is not None:
            # NOT best-effort: backup() is a collective, and a process
            # that silently skips it desynchronizes collective counts and
            # wedges every peer at the next exchange.  Failing loudly
            # turns a job-wide hang into a restartable worker crash.
            self._replica.backup()

    def latest_step(self) -> int:
        """Max of shm step and storage tracker."""
        self._flush_async()
        mem = -1
        meta = snapshot.read_snapshot_meta(self._shm)
        if meta:
            mem = meta["step"]
        disk = read_tracker(self.checkpoint_dir, self._storage)
        return max(mem, disk if disk is not None else -1)

    def wait_saving_complete(self, timeout: float = 600.0) -> bool:
        """Block until the async saver persisted this process's latest
        storage save (exit barrier).  Uses the saver's progress dict — a
        merely-empty queue still has in-flight persists."""
        deadline = time.time() + timeout
        # an async storage save only enqueues its persist event once the
        # stager finishes; the barrier must wait for that first
        if not self._flush_async(timeout):
            # still staging: a timeout, not a loss — don't misreport a
            # merely-slow persist as dropped
            logger.warning(
                "exit barrier timed out waiting for snapshot staging"
            )
            return False
        with self._persist_mu:
            requested = self._persist_requested
            target = self._last_storage_step
        if target < requested:
            # the stager is idle yet a requested persist never made it to
            # the event queue (lock timeout / staging failure): that
            # checkpoint is gone and will never appear — report failure
            # now instead of succeeding against a stale target
            logger.error(
                "async storage save step=%d was dropped (persisted "
                "through step %d)", requested, target,
            )
            return False
        while time.time() < deadline:
            if self._local_saver is not None:
                if self._queue.empty() and self._local_saver.idle():
                    if not self._dist_persist or target < 0:
                        return True
                    # distributed commit: idle is not durable — the
                    # step counts only once the coordinator sealed it
                    # (the saver advances its watermark on seal)
                    if self._local_saver.persisted_step(
                        self.process_id
                    ) >= target:
                        return True
            else:
                try:
                    done = self._progress.get(str(self.process_id))
                except Exception:  # noqa: BLE001 - agent may be gone
                    done = None
                if target < 0 or (done is not None and done >= target):
                    return True
            time.sleep(0.5)
        return False

    def close(self):
        stopped = self._stager.stop(timeout=60)
        if self._local_saver is not None:
            self._local_saver.stop()
        try:
            from dlrover_tpu.observability import memscope

            memscope.scope().deregister_host_provider(
                f"ckpt_shm:{self._shm.name}"
            )
        except Exception:  # noqa: BLE001 - telemetry only
            pass
        if stopped:
            self._shm.close()
        else:
            # the stager thread may still be writing the buffer; leaking
            # the mapping beats a use-after-close crash in that thread
            logger.warning(
                "stager still staging at close(); leaving shm mapped"
            )

    def unlink_memory(self):
        """Drop the shm snapshot (call after a clean job completion —
        leaving it would make a future unrelated run 'resume')."""
        self._shm.unlink()
        try:
            from dlrover_tpu.observability import memscope

            memscope.scope().deregister_host_provider(
                f"ckpt_shm:{self._shm.name}"
            )
        except Exception:  # noqa: BLE001 - telemetry only
            pass
