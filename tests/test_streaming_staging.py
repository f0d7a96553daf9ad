"""Streaming zero-copy flash-checkpoint data path (round 7).

Covers the streaming stager (layout precompute -> paced D2H chunks
landing at final shm offsets, seqlock generation commit), its zero-copy
invariant (at most ONE host-side copy per shard chunk, instrumented so
it can't silently regress), the torn-snapshot fault path, the
lock-timeout persist reconciliation, the parallel chunked CRC persist
format and its verification on restore, and the atomic tracker write.
"""

import json
import os
import struct
import threading
import time
import uuid
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common.multi_process import SharedLock, SharedMemoryBuffer
from dlrover_tpu.common.storage import (
    FsspecStorage,
    PosixDiskStorage,
    chunk_spans,
)
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.flash_checkpoint import (
    Checkpointer,
    StorageType,
    snapshot,
)
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    CheckpointEngine,
    _DeviceCopy,
    read_tracker,
    tracker_path,
)


def _scope():
    return f"st{uuid.uuid4().hex[:8]}"


def _sharded_state():
    """Mixed state: fsdp/tp-sharded fp32, a bf16 leaf (extension dtype:
    no buffer protocol), and a host scalar."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    from jax.sharding import NamedSharding, PartitionSpec as P

    w = jax.device_put(
        jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32),
        NamedSharding(mesh, P("fsdp", "tp")),
    )
    m = jax.device_put(
        (jnp.arange(48 * 16, dtype=jnp.float32) / 7.0)
        .astype(jnp.bfloat16).reshape(48, 16),
        NamedSharding(mesh, P("fsdp")),
    )
    return {"w": w, "m": m, "step": np.int64(3)}


def _read_all(shm):
    meta = snapshot.read_snapshot_meta(shm)
    assert meta is not None
    out = {}
    for leaf in meta["leaves"]:
        m = snapshot.ShardIndexMap(leaf["dtype"], leaf["gshape"])
        for sm in leaf["shards"]:
            m.add(
                sm["index"],
                snapshot.read_shard_bytes(shm, meta, sm, leaf["dtype"]),
            )
        out[leaf["path"]] = m.read(
            tuple(slice(0, d) for d in leaf["gshape"])
        )
    return meta, out


class TestStreamSnapshot:
    def test_layout_and_payload_match_two_phase(self):
        """The streaming writer must produce a byte-identical snapshot
        (same meta, same payload bytes) as the two-phase path — readers
        can never tell which path staged it."""
        state = _sharded_state()
        shm_a = SharedMemoryBuffer(f"tp_{_scope()}")
        shm_b = SharedMemoryBuffer(f"strm_{_scope()}")
        try:
            leaves = snapshot.extract_host_shards(state)
            snapshot.write_snapshot(shm_a, 11, leaves, {"tag": "x"})
            snapshot.stream_snapshot(
                shm_b, 11, snapshot.plan_shards(state), {"tag": "x"},
                chunk_bytes=1 << 12,
            )
            meta_a, data_a = _read_all(shm_a)
            meta_b, data_b = _read_all(shm_b)
            assert meta_a == meta_b
            assert set(data_a) == set(data_b)
            for path in data_a:
                np.testing.assert_array_equal(data_a[path], data_b[path])
        finally:
            shm_a.unlink()
            shm_b.unlink()

    def test_stream_roundtrip_bit_exact(self):
        state = _sharded_state()
        shm = SharedMemoryBuffer(f"rt_{_scope()}")
        try:
            snapshot.stream_snapshot(
                shm, 4, snapshot.plan_shards(state), chunk_bytes=1 << 12
            )
            meta, data = _read_all(shm)
            assert meta["step"] == 4
            np.testing.assert_array_equal(
                data["w"], np.asarray(state["w"])
            )
            np.testing.assert_array_equal(
                data["m"], np.asarray(state["m"]).view(np.uint16)
                .view(data["m"].dtype)
            )
            gen = snapshot.read_generation(shm)
            assert gen is not None and gen % 2 == 0
        finally:
            shm.unlink()

    def test_zero_copy_invariant_one_host_copy_per_chunk(self):
        """Tier-1 guard for the zero-copy claim: the streaming path
        performs exactly ONE host-side copy per shard chunk (the landing
        memcpy into shm); any reintroduced intermediate host buffer
        shows up as copies > chunks."""
        state = _sharded_state()
        shm = SharedMemoryBuffer(f"zc_{_scope()}")
        try:
            # tiny chunks: every shard streams in several chunks
            counts = snapshot.stream_snapshot(
                shm, 1, snapshot.plan_shards(state), chunk_bytes=1 << 10
            )
        finally:
            shm.unlink()
        assert counts.chunks > len(jax.tree.leaves(state))
        assert counts.host_copies == counts.chunks, (
            "streaming must cost exactly one host-side copy per chunk, "
            f"got {counts.host_copies} copies over {counts.chunks} "
            "chunks"
        )
        assert counts.bytes == counts.host_copy_bytes == sum(
            counts.chunk_sizes)

    def test_coarse_leading_dim_still_chunks(self):
        """A (1, big) shard must not stream as one giant unpaced
        transfer: the chunker flattens on device so the pacing bound
        holds for every shape (review finding)."""
        # 4MB in ONE row: above the 2*_MIN_CHUNK single-transfer floor,
        # yet unchunkable along axis 0 without the device flatten
        arr = jnp.arange(1 << 20, dtype=jnp.float32).reshape(1, 1 << 20)
        state = {"w": arr}
        shm = SharedMemoryBuffer(f"coarse_{_scope()}")
        try:
            counts = snapshot.stream_snapshot(
                shm, 1, snapshot.plan_shards(state), chunk_bytes=1 << 18
            )
            meta, data = _read_all(shm)
            np.testing.assert_array_equal(data["w"], np.asarray(arr))
        finally:
            shm.unlink()
        assert counts.chunks >= 8, (
            f"coarse leading dim must still chunk, got {counts.chunks}"
        )
        assert counts.host_copies == counts.chunks

    def test_release_shards_drops_device_refs(self):
        state = _sharded_state()
        leaves = snapshot.plan_shards(state)
        shm = SharedMemoryBuffer(f"rel_{_scope()}")
        try:
            snapshot.stream_snapshot(shm, 2, leaves, release_shards=True)
            for leaf in leaves:
                for shard in leaf["shards"]:
                    assert shard["data"] is None
        finally:
            shm.unlink()

    def test_fault_mid_stream_leaves_dirty_generation(self):
        """Killing the stager mid-stream must leave a torn snapshot that
        readers detect (seqlock), and a later complete write recovers."""
        state = {"w": np.arange(1 << 14, dtype=np.float32)}
        shm = SharedMemoryBuffer(f"fault_{_scope()}")

        def fault(chunk_idx):
            if chunk_idx >= 2:
                raise RuntimeError("injected kill")

        try:
            snapshot.set_stream_fault(fault)
            with pytest.raises(RuntimeError):
                snapshot.stream_snapshot(
                    shm, 9, snapshot.plan_shards(state),
                    chunk_bytes=1 << 12,
                )
            snapshot.set_stream_fault(None)
            assert snapshot.is_torn(shm)
            assert snapshot.read_snapshot_meta(shm) is None
            # recovery: a complete two-phase write re-commits the buffer
            snapshot.write_snapshot(
                shm, 10, snapshot.extract_host_shards(state)
            )
            assert not snapshot.is_torn(shm)
            meta, data = _read_all(shm)
            assert meta["step"] == 10
            np.testing.assert_array_equal(data["w"], state["w"])
        finally:
            snapshot.set_stream_fault(None)
            shm.unlink()

    def test_zeroed_length_word_still_reads_as_no_snapshot(self):
        """The legacy invalidation (meta length word zeroed) keeps
        working alongside the generation seqlock."""
        state = {"w": np.arange(64, dtype=np.float32)}
        shm = SharedMemoryBuffer(f"len_{_scope()}")
        try:
            snapshot.stream_snapshot(shm, 3, snapshot.plan_shards(state))
            assert snapshot.read_snapshot_meta(shm)["step"] == 3
            shm.buf[0:snapshot._HEADER] = struct.pack(">Q", 0)
            assert snapshot.read_snapshot_meta(shm) is None
        finally:
            shm.unlink()


class TestStreamingEngine:
    @pytest.fixture(autouse=True)
    def _force_async(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")

    def _trainer_state(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        import optax

        from dlrover_tpu.trainer.train import Trainer

        trainer = Trainer(model, optax.adamw(1e-2), mesh)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 17))
        batch = {
            "input_ids": np.asarray(ids[:, :-1], np.int32),
            "labels": np.asarray(ids[:, 1:], np.int32),
        }
        state = trainer.create_state(
            jax.random.PRNGKey(0), batch["input_ids"]
        )
        return trainer, state

    def test_streaming_async_save_roundtrips(self, tmp_path):
        trainer, state = self._trainer_state()
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            blocked = ckpt.save_checkpoint(7, state, StorageType.MEMORY)
            assert blocked >= 0
            assert ckpt.engine._flush_async(timeout=60)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state),
                trainer.state_shardings,
            )
            assert step == 7
            for a, b in zip(
                jax.tree.leaves(state), jax.tree.leaves(restored)
            ):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                )
        finally:
            ckpt.close()


class TestLockTimeoutPersistReconcile:
    """Satellite: a persist=True staging item dropped on the buffer-lock
    timeout must not silently break its durability promise."""

    def _engine(self, tmp_path, monkeypatch) -> CheckpointEngine:
        monkeypatch.setenv("DLROVER_TPU_CKPT_LOCK_TIMEOUT_S", "0.5")
        return CheckpointEngine(str(tmp_path), scope=_scope())

    def test_fallback_persists_current_snapshot_and_barrier_fails(
        self, tmp_path, monkeypatch
    ):
        """The drop must queue a persist of the committed shm snapshot
        (freshest recoverable state still reaches disk) while the exit
        barrier honestly reports the broken step-5 promise."""
        eng = self._engine(tmp_path, monkeypatch)
        other = None
        try:
            state = {"w": np.arange(256, dtype=np.float32)}
            assert eng.save_to_memory(2, state) >= 0  # committed shm @2
            # the agent side holds the buffer past the stager's timeout
            other = SharedLock(eng._lock_name, create=False)
            assert other.acquire(timeout=5)
            eng._persist_requested = 5
            box = _DeviceCopy({"w": state["w"] + 1}, lambda: None)
            eng._stage_snapshot(5, box, None, persist=True)
            # the step-2 fallback persist is in flight...
            assert eng._last_storage_step == 2
            other.release()
            other = None
            # ...and commits, but the barrier reports the broken promise
            deadline = time.time() + 60
            while read_tracker(str(tmp_path)) != 2:
                assert time.time() < deadline
                time.sleep(0.2)
            assert eng.wait_saving_complete(timeout=10) is False
        finally:
            if other is not None:
                other.release()
            eng._shm.unlink()
            eng.close()

    def test_no_snapshot_drop_fails_barrier_fast(
        self, tmp_path, monkeypatch
    ):
        eng = self._engine(tmp_path, monkeypatch)
        other = None
        try:
            other = SharedLock(eng._lock_name, create=False)
            assert other.acquire(timeout=5)
            eng._persist_requested = 5
            box = _DeviceCopy(
                {"w": np.arange(16, dtype=np.float32)}, lambda: None
            )
            eng._stage_snapshot(5, box, None, persist=True)
            other.release()
            other = None
            # nothing persistable existed: the barrier fails FAST (no
            # waiting on a persist that never happened) and the promise
            # is reported broken, not silently cleared
            t0 = time.time()
            assert eng.wait_saving_complete(timeout=30) is False
            assert time.time() - t0 < 10
            assert read_tracker(str(tmp_path)) is None
        finally:
            if other is not None:
                other.release()
            eng._shm.unlink()
            eng.close()

    def test_newer_shm_snapshot_keeps_promise(self, tmp_path, monkeypatch):
        """If the shm already holds a snapshot AT OR BEYOND the dropped
        step (a sync save raced ahead), the promise is met by newer
        content and the barrier succeeds."""
        eng = self._engine(tmp_path, monkeypatch)
        other = None
        try:
            state = {"w": np.arange(256, dtype=np.float32)}
            assert eng.save_to_memory(7, state) >= 0  # committed shm @7
            other = SharedLock(eng._lock_name, create=False)
            assert other.acquire(timeout=5)
            eng._persist_requested = 5
            box = _DeviceCopy({"w": state["w"] + 1}, lambda: None)
            eng._stage_snapshot(5, box, None, persist=True)
            assert eng._last_storage_step == 7
            other.release()
            other = None
            assert eng.wait_saving_complete(timeout=60)
            assert read_tracker(str(tmp_path)) == 7
        finally:
            if other is not None:
                other.release()
            eng._shm.unlink()
            eng.close()

    def test_sync_storage_drop_fails_barrier(self, tmp_path, monkeypatch):
        """A DROPPED synchronous save_to_storage must also register its
        durability promise so the exit barrier reports the loss (review
        finding: only the async path recorded _persist_requested)."""
        eng = self._engine(tmp_path, monkeypatch)
        other = None
        try:
            other = SharedLock(eng._lock_name, create=False)
            assert other.acquire(timeout=5)
            blocked = eng.save_to_storage(
                4, {"w": np.arange(16, dtype=np.float32)}
            )
            assert blocked < 0  # buffer busy: the save was dropped
            other.release()
            other = None
            assert eng.wait_saving_complete(timeout=10) is False
        finally:
            if other is not None:
                other.release()
            eng._shm.unlink()
            eng.close()

    def test_memory_drop_does_not_touch_persist_state(
        self, tmp_path, monkeypatch
    ):
        eng = self._engine(tmp_path, monkeypatch)
        other = None
        try:
            other = SharedLock(eng._lock_name, create=False)
            assert other.acquire(timeout=5)
            box = _DeviceCopy(
                {"w": np.arange(16, dtype=np.float32)}, lambda: None
            )
            eng._stage_snapshot(3, box, None, persist=False)
            assert eng._last_storage_step == -1
            assert eng._persist_requested == -1
        finally:
            if other is not None:
                other.release()
            eng._shm.unlink()
            eng.close()


class TestCrcPersist:
    def _save_steps(self, tmp_path, steps):
        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        states = {}
        try:
            for step in steps:
                arr = jax.device_put(
                    jnp.arange(4096, dtype=jnp.float32) + step * 1000,
                    NamedSharding(mesh, P("dp")),
                )
                state = {"w": arr}
                states[step] = np.asarray(arr)
                ckpt.save_checkpoint(step, state, StorageType.DISK)
                assert ckpt.wait_latest_checkpoint(timeout=120)
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()
        return states

    def _abstract(self):
        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        abstract = {
            "w": jax.ShapeDtypeStruct((4096,), jnp.float32)
        }
        shardings = {"w": NamedSharding(mesh, P("dp"))}
        return abstract, shardings

    def test_disk_meta_records_verifiable_chunks(self, tmp_path):
        self._save_steps(tmp_path, [1])
        meta = json.loads(
            (tmp_path / "1" / "meta_0.json").read_text()
        )
        chunks = meta["chunks"]
        assert chunks, "persist format 2 must record chunk CRCs"
        payload = (tmp_path / "1" / meta["bin_file"]).read_bytes()
        assert sum(c["nbytes"] for c in chunks) == len(payload)
        assert meta["payload_bytes"] == len(payload)
        for c in chunks:
            got = zlib.crc32(
                payload[c["offset"] : c["offset"] + c["nbytes"]]
            )
            assert got == c["crc32"]
        # every shard entry carries its own CRC (lazy restore verifies
        # exactly the ranges it fetches, no chunk amplification)
        for leaf in meta["leaves"]:
            for s in leaf["shards"]:
                got = zlib.crc32(
                    payload[s["offset"] : s["offset"] + s["nbytes"]]
                )
                assert got == s["crc32"]

    @pytest.mark.parametrize("mode", ["lazy", "eager"])
    def test_corrupted_chunk_falls_back_to_older_step(
        self, tmp_path, monkeypatch, mode
    ):
        monkeypatch.setenv("DLROVER_TPU_VERIFY_CRC", mode)
        states = self._save_steps(tmp_path, [1, 2])
        # flip one payload byte of the NEWEST step
        bin_path = tmp_path / "2" / "shards_0.bin"
        blob = bytearray(bin_path.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        bin_path.write_bytes(bytes(blob))
        abstract, shardings = self._abstract()
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            restored, step = ckpt.load_checkpoint(abstract, shardings)
            assert step == 1, (
                f"corrupted step 2 must be rejected ({mode}); got {step}"
            )
            np.testing.assert_array_equal(
                np.asarray(restored["w"]), states[1]
            )
        finally:
            ckpt.close()

    def test_intact_checkpoint_restores_under_both_modes(
        self, tmp_path, monkeypatch
    ):
        states = self._save_steps(tmp_path, [4])
        abstract, shardings = self._abstract()
        for mode in ("lazy", "eager"):
            monkeypatch.setenv("DLROVER_TPU_VERIFY_CRC", mode)
            ckpt = Checkpointer(str(tmp_path), scope=_scope())
            try:
                restored, step = ckpt.load_checkpoint(abstract, shardings)
                assert step == 4
                np.testing.assert_array_equal(
                    np.asarray(restored["w"]), states[4]
                )
            finally:
                ckpt.close()


class TestTrackerAtomic:
    def test_corrupt_tracker_falls_back_to_directory_scan(self, tmp_path):
        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        arr = jax.device_put(
            jnp.arange(512, dtype=jnp.float32),
            NamedSharding(mesh, P("dp")),
        )
        try:
            ckpt.save_checkpoint(3, {"w": arr}, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()
        # torn tracker: binary garbage a crashed writer could leave
        with open(tracker_path(str(tmp_path)), "wb") as f:
            f.write(b"\x00\xffgarbage\x13")
        assert read_tracker(str(tmp_path)) is None
        abstract = {"w": jax.ShapeDtypeStruct((512,), jnp.float32)}
        shardings = {"w": NamedSharding(mesh, P("dp"))}
        ckpt2 = Checkpointer(str(tmp_path), scope=_scope())
        try:
            restored, step = ckpt2.load_checkpoint(abstract, shardings)
            assert step == 3, "directory scan must recover the step"
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(512, dtype=np.float32),
            )
        finally:
            ckpt2.close()

    def test_write_atomic_replaces_without_droppings(self, tmp_path):
        storage = PosixDiskStorage()
        path = str(tmp_path / "tracker")
        storage.write_atomic("1", path)
        storage.write_atomic("2", path)
        assert (tmp_path / "tracker").read_text() == "2"
        leftovers = [
            f for f in os.listdir(tmp_path) if f.startswith("tracker.")
        ]
        assert leftovers == []

    def test_fsspec_write_atomic(self):
        pytest.importorskip("fsspec")
        storage = FsspecStorage()
        path = f"memory://atomic_{uuid.uuid4().hex[:8]}/tracker"
        storage.write_atomic("7", path)
        assert storage.read(path) == "7"


class TestWriteChunks:
    def _payload(self, nbytes, seed=0):
        return np.random.default_rng(seed).integers(
            0, 255, size=nbytes, dtype=np.uint8
        ).tobytes()

    @pytest.mark.parametrize("writers", [1, 4])
    @pytest.mark.parametrize("nbytes", [0, 1, 1 << 16, (1 << 16) + 37])
    def test_posix_content_and_crc(self, tmp_path, writers, nbytes):
        storage = PosixDiskStorage()
        payload = self._payload(nbytes)
        path = str(tmp_path / f"b_{writers}_{nbytes}.bin")
        records = storage.write_chunks(
            payload, path, chunk_bytes=1 << 12, writers=writers
        )
        with open(path, "rb") as f:
            assert f.read() == payload
        assert len(records) == len(chunk_spans(nbytes, 1 << 12))
        for r in records:
            assert r["crc32"] == zlib.crc32(
                payload[r["offset"] : r["offset"] + r["nbytes"]]
            )

    def test_pool_matches_single_writer(self, tmp_path):
        storage = PosixDiskStorage()
        payload = self._payload((1 << 20) + 11, seed=3)
        rec1 = storage.write_chunks(
            payload, str(tmp_path / "one.bin"), chunk_bytes=1 << 14,
            writers=1,
        )
        rec4 = storage.write_chunks(
            payload, str(tmp_path / "four.bin"), chunk_bytes=1 << 14,
            writers=4,
        )
        assert rec1 == rec4
        assert (tmp_path / "one.bin").read_bytes() == (
            tmp_path / "four.bin"
        ).read_bytes()

    def test_fsspec_sequential_fallback(self):
        pytest.importorskip("fsspec")
        storage = FsspecStorage()
        payload = self._payload(1 << 14, seed=5)
        path = f"memory://chunks_{uuid.uuid4().hex[:8]}/b.bin"
        records = storage.write_chunks(
            payload, path, chunk_bytes=1 << 12, writers=4
        )
        assert storage.read(path, mode="rb") == payload
        for r in records:
            assert r["crc32"] == zlib.crc32(
                payload[r["offset"] : r["offset"] + r["nbytes"]]
            )


class TestSaveOnFailureTorn:
    def test_torn_shm_not_persisted(self, tmp_path):
        """save_shm_on_failure must refuse a dirty-generation snapshot
        (stager killed mid-stream) and leave restore to the storage
        candidates."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        scope = _scope()
        saver = AsyncCheckpointSaver(scope=scope)
        saver.start()
        shm_name_ = f"dlrover_tpu_ckpt_{scope}_0"
        shm = SharedMemoryBuffer(shm_name_)
        try:
            state = {"w": np.arange(1 << 14, dtype=np.float32)}

            def fault(chunk_idx):
                if chunk_idx >= 1:
                    raise RuntimeError("injected kill")

            snapshot.set_stream_fault(fault)
            with pytest.raises(RuntimeError):
                snapshot.stream_snapshot(
                    shm, 6, snapshot.plan_shards(state),
                    chunk_bytes=1 << 12,
                )
            snapshot.set_stream_fault(None)
            saver._tracked[0] = {
                "type": "register",
                "shm": shm_name_,
                "lock": "",
                "ckpt_dir": str(tmp_path),
                "process_id": 0,
                "num_processes": 1,
                "step": -1,
            }
            assert saver.save_shm_on_failure() == []
            assert read_tracker(str(tmp_path)) is None
            # a committed snapshot IS persisted
            snapshot.write_snapshot(
                shm, 8, snapshot.extract_host_shards(state)
            )
            assert saver.save_shm_on_failure() == [8]
        finally:
            snapshot.set_stream_fault(None)
            shm.unlink()
            saver.stop()


class TestChaosRestoreFaults:
    """Restore-under-fault coverage driven through chaos injection
    points (``dlrover_tpu.chaos``) instead of monkeypatching internals
    or flipping disk bytes by hand — the same faults the recovery drill
    scripts, exercised at test granularity."""

    @pytest.fixture(autouse=True)
    def _disarm(self):
        from dlrover_tpu import chaos

        chaos.clear()
        yield
        chaos.clear()

    def test_chaos_torn_stream_restores_from_storage(self, tmp_path):
        """A chaos exception mid-stream leaves torn shm; load must fall
        back to the persisted step, bit-exact."""
        from dlrover_tpu import chaos

        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("dp"))
        committed = {
            "w": jax.device_put(
                jnp.arange(4096, dtype=jnp.float32) + 1000.0, sharding
            )
        }
        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        try:
            ckpt.save_checkpoint(3, committed, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            chaos.inject(chaos.FaultSpec(
                point="snapshot.stream_chunk", after=1, times=1,
            ))
            newer = {
                "w": jax.device_put(
                    jnp.arange(4096, dtype=jnp.float32) + 9000.0,
                    sharding,
                )
            }
            with pytest.raises(chaos.ChaosError):
                snapshot.stream_snapshot(
                    ckpt.engine._shm, 9, snapshot.plan_shards(newer),
                    chunk_bytes=1 << 12,
                )
            assert snapshot.is_torn(ckpt.engine._shm)
            chaos.clear()
            abstract = {"w": jax.ShapeDtypeStruct((4096,), jnp.float32)}
            restored, step = ckpt.load_checkpoint(
                abstract, {"w": sharding}
            )
            assert step == 3
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(4096, dtype=np.float32) + 1000.0,
            )
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()

    @pytest.mark.parametrize("mode", ["lazy", "eager"])
    def test_chaos_torn_persist_chunk_rejected_on_restore(
        self, tmp_path, monkeypatch, mode
    ):
        """A chaos torn-write corrupts a persisted chunk ON DISK (the
        CRC record still describes the intended bytes); restore must
        refuse the corrupt step and fall back."""
        from dlrover_tpu import chaos

        monkeypatch.setenv("DLROVER_TPU_VERIFY_CRC", mode)
        monkeypatch.setenv("DLROVER_TPU_PERSIST_WRITERS", "1")
        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("dp"))

        def _state(tag):
            return {
                "w": jax.device_put(
                    jnp.arange(4096, dtype=jnp.float32) + tag * 1000,
                    sharding,
                )
            }

        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        try:
            ckpt.save_checkpoint(1, _state(1), StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            # corrupt the NEXT persist's first chunk
            chaos.inject(chaos.FaultSpec(
                point="storage.write_chunk", kind=chaos.TORN_WRITE,
                times=1,
            ))
            ckpt.save_checkpoint(2, _state(2), StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            torn = [
                r for r in chaos.trace()
                if r["kind"] == chaos.TORN_WRITE
            ]
            assert len(torn) == 1, chaos.trace()
            chaos.clear()
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()
        # replacement host (fresh shm scope): storage-only restore
        abstract = {"w": jax.ShapeDtypeStruct((4096,), jnp.float32)}
        ckpt2 = Checkpointer(str(tmp_path), scope=_scope())
        try:
            restored, step = ckpt2.load_checkpoint(
                abstract, {"w": sharding}
            )
            assert step == 1, (
                f"chaos-corrupted step 2 must be rejected ({mode}); "
                f"got {step}"
            )
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(4096, dtype=np.float32) + 1000,
            )
        finally:
            ckpt2.close()

    def test_chaos_dropped_chunked_write_leaves_nothing_on_disk(
        self, tmp_path
    ):
        """A drop fault on storage.write must be HONORED by the chunked
        posix path too: trace says dropped => disk says nothing landed
        (a vacuous drill would otherwise pass on a lie)."""
        from dlrover_tpu import chaos

        chaos.inject(chaos.FaultSpec(
            point="storage.write", kind=chaos.DROP, times=1,
        ))
        storage = PosixDiskStorage()
        path = str(tmp_path / "dropped.bin")
        records = storage.write_chunks(
            b"x" * 8192, path, chunk_bytes=1 << 12, writers=2
        )
        assert len(records) == 2  # intended-bytes records still returned
        assert not os.path.exists(path)
        # the fault budget is spent: the next write lands
        chaos.clear()
        storage.write_chunks(b"y" * 64, path, chunk_bytes=32)
        assert os.path.getsize(path) == 64

    def test_chaos_torn_chunked_write_detectable_by_crc(self, tmp_path):
        """A torn-write fault on the chunked path leaves a full-size
        file whose tail bytes never landed — the CRC records must
        disagree with the disk content."""
        from dlrover_tpu import chaos

        chaos.inject(chaos.FaultSpec(
            point="storage.write", kind=chaos.TORN_WRITE, times=1,
        ))
        storage = PosixDiskStorage()
        path = str(tmp_path / "torn.bin")
        payload = bytes(range(256)) * 32  # 8KB
        records = storage.write_chunks(
            payload, path, chunk_bytes=1 << 12, writers=1
        )
        assert os.path.getsize(path) == len(payload)  # size looks fine
        blob = open(path, "rb").read()
        mismatched = [
            r for r in records
            if zlib.crc32(blob[r["offset"] : r["offset"] + r["nbytes"]])
            != r["crc32"]
        ]
        assert mismatched, "torn tail must be CRC-detectable"

    def test_chaos_storage_stall_does_not_break_commit(self, tmp_path):
        """Delay faults on storage writes slow the persist but the
        commit protocol still lands and restores exactly."""
        from dlrover_tpu import chaos

        chaos.inject(chaos.FaultSpec(
            point="storage.write", kind=chaos.DELAY, delay_s=0.2,
            times=2,
        ))
        mesh = build_mesh(MeshConfig(dp=8))
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("dp"))
        state = {
            "w": jax.device_put(
                jnp.arange(4096, dtype=jnp.float32) + 7.0, sharding
            )
        }
        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        try:
            ckpt.save_checkpoint(5, state, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            assert read_tracker(str(tmp_path)) == 5
            delays = [
                r for r in chaos.trace() if r["kind"] == chaos.DELAY
            ]
            assert len(delays) == 2
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()
