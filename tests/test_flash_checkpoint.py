"""Flash Checkpoint tests: shm roundtrip, async persist + commit protocol,
reshard-on-restore, save-on-failure."""

import os
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
from dlrover_tpu.trainer.flash_checkpoint import snapshot
from dlrover_tpu.trainer.flash_checkpoint.engine import read_tracker
from dlrover_tpu.common.multi_process import SharedMemoryBuffer
from dlrover_tpu.trainer.train import Trainer


def _scope():
    return f"t{uuid.uuid4().hex[:8]}"


def _make_trainer(mesh_cfg):
    mesh = build_mesh(mesh_cfg)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    trainer = Trainer(model, optax.adamw(1e-2), mesh)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, 17))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    return trainer, state, batch


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestSnapshot:
    def test_extract_and_shm_roundtrip(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("fsdp", "tp")),
        )
        state = {"w": arr, "step": jnp.ones((), jnp.int32)}
        leaves = snapshot.extract_host_shards(state)
        paths = {l["path"] for l in leaves}
        assert paths == {"w", "step"}
        w_leaf = next(l for l in leaves if l["path"] == "w")
        # fsdp=2 x tp=2 shards, replica-0 only (dp replicas excluded)
        assert len(w_leaf["shards"]) == 4

        shm = SharedMemoryBuffer(f"snap_{_scope()}")
        try:
            snapshot.write_snapshot(shm, 7, leaves)
            meta = snapshot.read_snapshot_meta(shm)
            assert meta["step"] == 7
            m = snapshot.ShardIndexMap(
                w_leaf["dtype"], w_leaf["gshape"]
            )
            for sm in next(
                l for l in meta["leaves"] if l["path"] == "w"
            )["shards"]:
                m.add(
                    sm["index"],
                    snapshot.read_shard_bytes(shm, meta, sm, "float32"),
                )
            full = m.read((slice(0, 8), slice(0, 8)))
            np.testing.assert_array_equal(
                full, np.arange(64, dtype=np.float32).reshape(8, 8)
            )
            # arbitrary sub-slice crossing shard boundaries
            sub = m.read((slice(2, 6), slice(3, 7)))
            np.testing.assert_array_equal(
                sub, np.arange(64, dtype=np.float32).reshape(8, 8)[2:6, 3:7]
            )
        finally:
            shm.unlink()

    def test_uncovered_slice_raises(self):
        m = snapshot.ShardIndexMap("float32", [4, 4])
        m.add([[0, 2], [0, 4]], np.zeros((2, 4), np.float32))
        with pytest.raises(ValueError):
            m.read((slice(0, 4), slice(0, 4)))


class TestCheckpointer:
    # fast tier on purpose: the flagship save/restore correctness smoke
    # must run in the default `pytest tests/` invocation (advisor r3) —
    # the full matrix (reshard, overwrite, pipelines) stays slow-tier
    def test_memory_roundtrip(self, tmp_path):
        trainer, state, batch = _make_trainer(MeshConfig(dp=2, fsdp=2, tp=2))
        state, _ = trainer.train_step(state, batch)
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            blocked = ckpt.save_checkpoint(5, state, StorageType.MEMORY)
            assert blocked < 30
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state), trainer.state_shardings
            )
            assert step == 5
            _trees_equal(state, restored)
        finally:
            ckpt.close()

    def test_disk_roundtrip_and_commit(self, tmp_path):
        trainer, state, batch = _make_trainer(MeshConfig(dp=4, fsdp=2))
        state, _ = trainer.train_step(state, batch)
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            ckpt.save_checkpoint(3, state, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            assert read_tracker(str(tmp_path)) == 3
            step_dir = tmp_path / "3"
            assert step_dir.is_dir()
            assert (step_dir / ".done" / "0").exists()
            assert not (tmp_path / "tmp_3").exists()
        finally:
            ckpt.close()

    @pytest.mark.slow
    def test_restore_with_different_mesh(self, tmp_path):
        """FSDP state saved on one mesh restores resharded on another."""
        scope = _scope()
        trainer, state, batch = _make_trainer(MeshConfig(dp=2, fsdp=4))
        state, _ = trainer.train_step(state, batch)
        ckpt = Checkpointer(str(tmp_path), scope=scope)
        try:
            ckpt.save_checkpoint(9, state, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
        finally:
            ckpt.close()
        # wipe shm so the fast path can't serve; then a NEW mesh shape
        from dlrover_tpu.trainer.flash_checkpoint.engine import shm_name

        shm = SharedMemoryBuffer(shm_name(0, scope))
        shm.unlink()

        trainer2, state2, _ = _make_trainer(MeshConfig(dp=8, fsdp=1))
        ckpt2 = Checkpointer(str(tmp_path), scope=_scope())
        try:
            restored, step = ckpt2.load_checkpoint(
                jax.eval_shape(lambda s: s, state2), trainer2.state_shardings
            )
            assert step == 9
            _trees_equal(state, restored)
        finally:
            ckpt2.close()

    def test_no_checkpoint_returns_none(self, tmp_path):
        trainer, state, _ = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state), trainer.state_shardings
            )
            assert restored is None and step == -1
        finally:
            ckpt.close()

    @pytest.mark.slow
    def test_memory_save_overwrites(self, tmp_path):
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            ckpt.save_checkpoint(1, state, StorageType.MEMORY)
            state2, _ = trainer.train_step(state, batch)
            ckpt.save_checkpoint(2, state2, StorageType.MEMORY)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state2), trainer.state_shardings
            )
            assert step == 2
            _trees_equal(state2, restored)
        finally:
            ckpt.close()


class TestSaveOnFailure:
    def test_agent_persists_unsaved_snapshot(self, tmp_path):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        scope = _scope()
        saver = AsyncCheckpointSaver(scope=scope)
        saver.start()
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=scope)
        try:
            # memory-only save: nothing on disk yet
            ckpt.save_checkpoint(4, state, StorageType.MEMORY)
            time.sleep(1.0)  # let the register event drain
            assert read_tracker(str(tmp_path)) is None
            # "worker died": agent persists the shm snapshot
            saved = saver.save_shm_on_failure()
            assert saved == [4]
            deadline = time.time() + 60
            while read_tracker(str(tmp_path)) != 4:
                assert time.time() < deadline
                time.sleep(0.5)
        finally:
            ckpt.close()
            saver.stop()


class TestAsyncSnapshot:
    """The dispatch-only-blocking save path (engine module docstring)."""

    @pytest.fixture(autouse=True)
    def _force_async(self, monkeypatch):
        # tiny test states would auto-select the sync path (small-state
        # threshold); force the async machinery under test
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")

    # fast tier on purpose: donation safety is the async path's core
    # correctness promise; it must run in the default invocation
    def test_async_save_is_donation_safe(self, tmp_path):
        """A donated train step right after the save overwrites the
        source buffers; the snapshot must hold the PRE-step values
        because its on-device copy was enqueued first."""
        trainer, state, batch = _make_trainer(MeshConfig(dp=2, fsdp=2, tp=2))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            before = jax.tree.map(lambda a: np.asarray(a), state)
            blocked = ckpt.save_checkpoint(1, state, StorageType.MEMORY)
            assert blocked >= 0
            # trainer's jit step donates argnums=(0,): state buffers die
            state2, _ = trainer.train_step(state, batch)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state2), trainer.state_shardings
            )
            assert step == 1
            _trees_equal(before, restored)
        finally:
            ckpt.close()

    @pytest.mark.slow
    def test_latest_async_save_wins(self, tmp_path):
        """Back-to-back async memory saves: the newest step must be the
        one a later restore sees (superseded-or-staged, never dropped)."""
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            states = [state]
            for step in range(1, 5):
                ckpt.save_checkpoint(step, states[-1], StorageType.MEMORY)
                s, _ = trainer.train_step(states[-1], batch)
                states.append(s)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, states[-1]),
                trainer.state_shardings,
            )
            assert step == 4
        finally:
            ckpt.close()

    def test_async_storage_save_commits(self, tmp_path):
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            ckpt.save_checkpoint(2, state, StorageType.MEMORY)
            blocked = ckpt.save_checkpoint(3, state, StorageType.DISK)
            assert blocked >= 0
            assert ckpt.wait_latest_checkpoint(timeout=120)
            assert read_tracker(str(tmp_path)) == 3
            assert (tmp_path / "3" / ".done" / "0").exists()
        finally:
            ckpt.close()

    def test_sync_opt_out(self, tmp_path):
        """async_snapshot=False restores the fully-blocking contract
        (for HBM-tight jobs that can't afford the transient copy)."""
        trainer, state, _ = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(
            str(tmp_path), scope=_scope(), async_snapshot=False
        )
        try:
            ckpt.save_checkpoint(7, state, StorageType.MEMORY)
            # no flush needed: the sync path wrote shm before returning
            from dlrover_tpu.trainer.flash_checkpoint import snapshot as snap
            meta = snap.read_snapshot_meta(ckpt.engine._shm)
            assert meta is not None and meta["step"] == 7
        finally:
            ckpt.close()


class TestSnapshotStager:
    """Mailbox semantics (review findings): storage snapshots are never
    displaced, and a stuck stager is reported by stop()."""

    def _stager(self, stage_fn):
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            _SnapshotStager,
        )

        return _SnapshotStager(stage_fn)

    def _box(self, freed=None):
        """A device-copy box; records into ``freed`` when released."""
        from dlrover_tpu.trainer.flash_checkpoint.engine import _DeviceCopy

        sink = freed if freed is not None else []
        return _DeviceCopy(object(), lambda: sink.append(True))

    def test_storage_item_never_superseded_by_memory(self):
        import threading

        gate = threading.Event()
        staged = []

        def stage(step, box, extras, persist):
            gate.wait(10)
            staged.append((step, persist))

        s = self._stager(stage)
        s.submit(1, self._box(), None, False)
        s.submit(2, self._box(), None, True)  # storage: durability promise
        # a memory snapshot must NOT displace queued storage; if step 2 is
        # still queued the stager reports busy so the engine saves sync
        r3 = s.submit(3, self._box(), None, False)
        assert r3 in (True, "busy")
        gate.set()
        assert s.flush(10)
        assert (2, True) in staged
        assert s.stop()

    def test_superseded_pending_copy_is_freed(self):
        """A queued memory snapshot displaced by a newer one must release
        its on-device copy immediately — the HBM accounting that bounds
        async snapshots to ONE transient extra state copy."""
        import threading

        gate = threading.Event()

        def stage(step, box, extras, persist):
            gate.wait(10)

        s = self._stager(stage)
        # filler occupies the worker so later submits stay queued
        s.submit(0, self._box(), None, False)
        deadline = time.time() + 5
        while not s._busy:
            assert time.time() < deadline
            time.sleep(0.01)
        freed = []
        s.submit(1, self._box(freed), None, False)
        assert not freed
        s.submit(2, self._box(), None, False)  # supersedes step 1
        assert freed == [True]
        gate.set()
        assert s.flush(10)
        assert s.stop()

    def test_second_storage_save_waits_not_displaces(self):
        """Pin the wait branch: while a storage item is QUEUED (not just
        in flight), a second storage submit must wait for it to be taken
        rather than displacing it."""
        import threading

        gate = threading.Event()
        staged = []

        def stage(step, box, extras, persist):
            gate.wait(10)
            staged.append(step)

        s = self._stager(stage)
        # filler goes in-flight (blocked on the gate)...
        s.submit(0, self._box(), None, False)
        deadline = time.time() + 5
        while not s._busy:
            assert time.time() < deadline
            time.sleep(0.01)
        # ...so this storage item stays QUEUED in the mailbox
        s.submit(1, self._box(), None, True)
        done = []
        t = threading.Thread(
            target=lambda: done.append(
                s.submit(2, self._box(), None, True)
            )
        )
        t.start()
        time.sleep(0.3)
        # the guard must be holding submit(2) while step 1 is queued
        assert not done
        assert s._pending is not None and s._pending[0] == 1
        gate.set()
        t.join(10)
        assert done == [True]
        assert s.flush(10)
        assert 1 in staged and 2 in staged  # neither storage item lost
        assert s.stop()

    def test_recovery_point_tracks_latest_under_slow_staging(
        self, tmp_path, monkeypatch
    ):
        """Saves arriving faster than staging drains must never age the
        recovery point (round-3 regression: async memory saves were
        skipped while a previous device copy was still staging, so the
        shm snapshot stayed at an old step without bound).  With an
        artificially slow stager and saves every 50 ms, the shm step
        must end at the LATEST saved step."""
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        real_stream = snapshot.stream_snapshot
        slowed = []

        def slow_stream(*args, **kwargs):  # the stager's writer alone
            slowed.append(args[1])
            time.sleep(0.4)
            return real_stream(*args, **kwargs)

        monkeypatch.setattr(snapshot, "stream_snapshot", slow_stream)
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            last = 0
            for step in range(1, 11):
                blocked = ckpt.save_checkpoint(
                    step, state, StorageType.MEMORY
                )
                assert blocked >= 0  # never dropped
                last = step
                time.sleep(0.05)
            assert ckpt.engine._flush_async(timeout=60)
            meta = snapshot.read_snapshot_meta(ckpt.engine._shm)
            assert meta is not None and meta["step"] == last
            assert slowed and slowed[-1] == last
        finally:
            ckpt.close()

    def test_stop_reports_stuck_stager(self):
        import threading

        release = threading.Event()
        s = self._stager(lambda *a: release.wait(30))
        s.submit(1, self._box(), None, False)
        time.sleep(0.3)  # let the item go in-flight
        assert s.stop(timeout=1.0) is False
        release.set()

    def test_barrier_detects_dropped_persist(self, tmp_path):
        """If a requested async storage save never reached the event
        queue, the exit barrier must report failure, not succeed against
        a stale target."""
        trainer, state, _ = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            ckpt.engine._persist_requested = 5  # as if step-5 was dropped
            assert ckpt.wait_latest_checkpoint(timeout=5) is False
        finally:
            ckpt.close()


class TestTornSnapshot:
    def test_interrupted_write_reads_as_no_snapshot(self):
        """Kill-anywhere safety: until the final header commit, the shm
        must read as empty — a torn payload with valid-looking metadata
        would be persisted by save-on-failure and restored as garbage."""
        import struct

        from dlrover_tpu.trainer.flash_checkpoint.snapshot import (
            _HEADER,
            read_snapshot_meta,
            write_snapshot,
        )

        shm = SharedMemoryBuffer(f"torn_{_scope()}")
        try:
            leaves = [{
                "path": "w",
                "dtype": "float32",
                "gshape": [4],
                "shards": [{
                    "index": [[0, 4]],
                    "data": np.arange(4, dtype=np.float32),
                }],
            }]
            write_snapshot(shm, 3, leaves)
            assert read_snapshot_meta(shm)["step"] == 3
            # simulate a crash mid-write: header zeroed (as the writer
            # does first), payload half-garbled
            shm.buf[0:_HEADER] = struct.pack(">Q", 0)
            assert read_snapshot_meta(shm) is None
        finally:
            shm.unlink()

    def test_chaos_torn_shm_full_state_falls_back_to_disk(self, tmp_path):
        """End-to-end restore-under-fault on a REAL trainer state: a
        chaos fault tears the shm stream of a newer step; load must
        restore the older DISK commit bit-exactly (never the torn shm,
        never a fresh state).  Chaos points replace the old
        monkeypatching — the same spec works on a live job."""
        from dlrover_tpu import chaos

        trainer, state, batch = _make_trainer(MeshConfig(dp=4, fsdp=2))
        state, _ = trainer.train_step(state, batch)
        ckpt = Checkpointer(str(tmp_path), scope=_scope(),
                            async_snapshot=False)
        try:
            ckpt.save_checkpoint(4, state, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            # host-side expectation BEFORE the next train_step: the step
            # donates its input state, deleting those arrays
            expected = jax.tree.map(
                lambda a: np.asarray(a).copy(), state
            )
            abstract = jax.eval_shape(lambda s: s, state)
            newer, _ = trainer.train_step(state, batch)
            chaos.inject(chaos.FaultSpec(
                point="snapshot.stream_chunk", after=2, times=1,
            ))
            try:
                with pytest.raises(chaos.ChaosError):
                    snapshot.stream_snapshot(
                        ckpt.engine._shm, 8,
                        snapshot.plan_shards(newer), chunk_bytes=1 << 12,
                    )
            finally:
                chaos.clear()
            assert snapshot.is_torn(ckpt.engine._shm)
            restored, step = ckpt.load_checkpoint(
                abstract, trainer.state_shardings
            )
            assert step == 4
            _trees_equal(expected, restored)
        finally:
            ckpt.engine.unlink_memory()
            ckpt.close()


class TestSnapshotDtypePolicy:
    """Opt-in bf16 snapshot precision (DLROVER_TPU_SNAPSHOT_DTYPE):
    halves the transient copy and staging traffic; restore casts back
    to the state's dtypes automatically (engine._assemble)."""

    def test_bf16_snapshot_roundtrips_with_cast_up(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")
        monkeypatch.setenv("DLROVER_TPU_SNAPSHOT_DTYPE", "bf16")
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            blocked = ckpt.save_checkpoint(3, state, StorageType.MEMORY)
            assert blocked >= 0
            assert ckpt.engine._flush_async(timeout=60)
            # the stored snapshot is bf16 for fp32 leaves...
            meta = snapshot.read_snapshot_meta(ckpt.engine._shm)
            stored = {
                leaf["path"]: leaf["dtype"] for leaf in meta["leaves"]
            }
            import jax.numpy as jnp

            fp32_paths = [
                snapshot._path_str(kp)
                for kp, leaf in jax.tree_util.tree_flatten_with_path(
                    state
                )[0]
                if leaf.dtype == jnp.float32
            ]
            assert fp32_paths and all(
                stored[p] == "bfloat16" for p in fp32_paths
            )
            # ...and restores at the state's own dtypes, bf16-close
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state),
                trainer.state_shardings,
            )
            assert step == 3
            for a, b in zip(
                jax.tree.leaves(state), jax.tree.leaves(restored)
            ):
                assert a.dtype == b.dtype
                np.testing.assert_allclose(
                    np.asarray(a, np.float32),
                    np.asarray(b, np.float32),
                    rtol=1e-2, atol=1e-2,
                )
        finally:
            ckpt.close()

    def test_default_stays_exact(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "0")
        monkeypatch.delenv("DLROVER_TPU_SNAPSHOT_DTYPE", raising=False)
        trainer, state, batch = _make_trainer(MeshConfig(dp=8))
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            ckpt.save_checkpoint(4, state, StorageType.MEMORY)
            assert ckpt.engine._flush_async(timeout=60)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state),
                trainer.state_shardings,
            )
            assert step == 4
            _trees_equal(state, restored)  # bitwise
        finally:
            ckpt.close()


class TestBf16MomentState:
    def test_bf16_moment_optimizer_state_roundtrips(self, tmp_path):
        """The bench recipe (bf16 Adam moments) must checkpoint: bf16
        leaves ride the shm pipe via the uint16 view (ml_dtypes arrays
        have no buffer protocol — this crashed the stager before)."""
        from dlrover_tpu.trainer.optim import create_optimizer

        mesh = build_mesh(MeshConfig(dp=8))
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        opt = create_optimizer(
            peak_lr=1e-2, warmup_steps=2, total_steps=100,
            moment_dtype=jnp.bfloat16,
        )
        trainer = Trainer(model, opt, mesh)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 17))
        batch = {
            "input_ids": np.asarray(ids[:, :-1], np.int32),
            "labels": np.asarray(ids[:, 1:], np.int32),
        }
        state = trainer.create_state(
            jax.random.PRNGKey(0), batch["input_ids"]
        )
        state, _ = trainer.train_step(state, batch)  # non-zero moments
        assert any(
            leaf.dtype == jnp.bfloat16
            for leaf in jax.tree.leaves(state.opt_state)
            if hasattr(leaf, "dtype")
        ), "recipe must actually produce bf16 moments"
        ckpt = Checkpointer(str(tmp_path), scope=_scope())
        try:
            blocked = ckpt.save_checkpoint(1, state, StorageType.MEMORY)
            assert blocked >= 0
            assert ckpt.engine._flush_async(timeout=60)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state),
                trainer.state_shardings,
            )
            assert step == 1
            _trees_equal(state, restored)  # bitwise, incl. bf16 leaves
        finally:
            ckpt.close()
