"""Worker-side runtime for multi-role unified jobs.

Counterpart of reference ``dlrover/python/unified/api/runtime/worker.py``
(``current_worker()``: the ActorInfo Ray injects) and ``api/runtime/
queue.py`` (cross-role data queues over the Ray object store).  On TPU
the identity rides the environment set by :class:`~dlrover_tpu.unified.
multi_role.UnifiedPrimeMaster`, and cross-role signalling rides the
shared job master's KV store — a control-plane channel for SMALL
payloads (steps, paths, verdicts, json blobs).  Bulk tensor handoff
between roles goes through the checkpoint storage (save on one role,
lazy ranged restore on the other), which is the TPU-native equivalent
of the reference's object-store queues.
"""

import json
import time
from dataclasses import dataclass
from typing import Any, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.common import envs


@dataclass(frozen=True)
class RoleInfo:
    role: str
    rank: int
    world: int
    job_name: str

    @property
    def is_leader(self) -> bool:
        return self.rank == 0


def current_role() -> RoleInfo:
    """This process's role identity (reference current_worker())."""
    return RoleInfo(
        role=envs.get_str("DLROVER_TPU_ROLE"),
        rank=envs.get_int("DLROVER_TPU_ROLE_RANK"),
        world=envs.get_int("DLROVER_TPU_ROLE_WORLD"),
        job_name=envs.get_str("DLROVER_TPU_JOB_NAME"),
    )


def init() -> RoleInfo:
    """Initialize a SIMPLE-role process: apply the role's platform pin
    and return its identity.  The counterpart of ``trainer.init()`` for
    non-elastic roles.

    The pin goes through ``jax.config`` so a role is held to its
    platform whatever ``JAX_PLATFORMS`` the launching shell had
    (``JAX_PLATFORMS=cpu`` alone works too): a cpu-pinned service role
    must never claim the chip its trainer peers need.  Call before the
    first jax use."""
    platform = envs.get_str("DLROVER_TPU_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    return current_role()


class RoleChannel:
    """Named cross-role mailbox over the job master's KV store.

    ``put`` overwrites the slot; ``get`` reads it; ``next`` blocks until
    the slot's sequence number advances past what this consumer already
    saw — a 1-deep latest-wins stream, which is exactly the hand-off
    shape trainer->evaluator pipelines need (evaluate the NEWEST
    checkpoint, skip superseded ones).  Values are JSON (no pickle on
    the wire, same rule as the rest of the control plane).
    """

    def __init__(self, name: str, client=None):
        if client is None:
            from dlrover_tpu.agent.master_client import MasterClient

            client = MasterClient.singleton_instance()
        if client is None:
            raise RuntimeError(
                "RoleChannel needs a master (DLROVER_TPU_MASTER_ADDR); "
                "run under the unified master or tpurun"
            )
        self._client = client
        self._key = f"unified/channel/{name}"
        self._seen_seq = 0
        self._epoch = None

    def put(self, value: Any) -> int:
        """Publish; returns the sequence number the server assigned.
        Seq assignment and slot write happen in ONE server-side critical
        section (kv_store.put_indexed), so concurrent producers can
        never regress the slot to an older payload."""
        return self._client.kv_store_put_indexed(
            self._key, json.dumps(value).encode()
        )

    def _read_slot(self):
        """(seq, value) of the slot, or (0, None) when empty.  Also
        tracks the store epoch (master/kv_store.py KV_EPOCH_KEY): a
        changed epoch means the KV store restarted, so the consumer
        watermark is reset BEFORE the seq comparison — this closes the
        race where post-recovery publishes push the fresh counter back
        to exactly the old watermark between polls (seq-only regression
        detection below stays as a fallback for epoch-less stores)."""
        from dlrover_tpu.master.kv_store import KV_EPOCH_KEY

        getter = getattr(self._client, "kv_store_multi_get", None)
        if getter is not None:
            kvs = getter([self._key, KV_EPOCH_KEY])
            raw = kvs.get(self._key, b"")
            epoch = kvs.get(KV_EPOCH_KEY, b"")
        else:
            raw = self._client.kv_store_get(self._key)
            epoch = b""
        if epoch:
            if self._epoch is not None and epoch != self._epoch:
                logger.warning(
                    "RoleChannel %s: KV epoch changed (master "
                    "recovered); resetting consumer watermark from %d",
                    self._key, self._seen_seq,
                )
                self._seen_seq = 0
            self._epoch = epoch
        if not raw or b"|" not in raw:
            return 0, None
        seq_bytes, payload = raw.split(b"|", 1)
        return int(seq_bytes), json.loads(payload.decode())

    def get(self) -> Optional[Any]:
        """Latest value, or None if nothing was ever published."""
        return self._read_slot()[1]

    def next(self, timeout: float = 120.0,
             poll_secs: float = 0.5) -> Optional[Any]:
        """Block until a value NEWER than the last one this consumer
        returned arrives; None on timeout."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            seq, value = self._read_slot()  # graftlint: disable=GL103 (deadline-bounded poll: the slot read is a point KV get from the master, not a barrier; each consumer polls independently and a timeout returns None)
            if seq > self._seen_seq:
                self._seen_seq = seq
                return value
            if seq < self._seen_seq:
                # The per-key counter regressed: the KV store lives in
                # the master process, so a master recovery re-seeds it
                # at zero while this consumer's watermark survives.
                # (A transport failure raises out of _read_slot instead
                # of reading low — a regression is always a reset.)
                # Adopt the new watermark; a non-empty slot is a fresh
                # post-recovery publish — deliver it, never drop it.
                logger.warning(
                    "RoleChannel %s: seq regressed %d -> %d (master "
                    "recovered); resetting consumer watermark",
                    self._key, self._seen_seq, seq,
                )
                self._seen_seq = seq
                if seq > 0:
                    return value
            time.sleep(poll_secs)
        logger.info("RoleChannel %s: no newer value within %.0fs",
                    self._key, timeout)
        return None
