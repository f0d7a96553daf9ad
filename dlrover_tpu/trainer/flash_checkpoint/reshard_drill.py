"""Elastic-reshard drill: save on one mesh, restore onto another.

The single-engine resharding restore is THE differentiator of this
checkpoint design (reference ships per-framework engines and a separate
universal-checkpoint conversion step — ``dlrover/python/elastic_agent/
torch/ckpt_saver.py:1394``; here the shard index maps make any-mesh ->
any-mesh restore a plain load).  This drill proves it end to end and
times it: create state on mesh A (dp1/fsdp2/tp2/cp2), train a step, save
to storage, restore onto mesh B (dp2/fsdp4), assert bit-level loss
continuity, then train one more step on the new mesh.

Used by the driver-facing ``__graft_entry__.dryrun_multichip`` (the
"reshard OK" leg).
"""

import contextlib
import json
import os
import shutil
import sys
import tempfile
import uuid
from typing import Dict, Optional


@contextlib.contextmanager
def _ledger_phases(out: Dict):
    """The r15 goodput ledger as the drill's stopwatch: reset it with
    fine buckets, run the leg, hand back the accrued per-phase seconds
    — the SAME account the production goodput report prints, so the
    drill's restart-vs-live comparison is apples-to-apples with the
    ledger the live path is priced into (no ad-hoc wall clocks)."""
    from dlrover_tpu.observability import goodput

    overrides = {"DLROVER_TPU_GOODPUT_RES_S": "0.005"}
    saved = {key: os.environ.get(key) for key in overrides}
    os.environ.update(overrides)
    try:
        goodput.reset_ledger()
        yield
        out.update(goodput.ledger().summary()["phases"])
    finally:
        for key, old in saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        goodput.reset_ledger()


def run_reshard_drill(
    n_devices: int = 8, ckpt_dir: Optional[str] = None
) -> Dict:
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        StorageType,
    )
    from dlrover_tpu.trainer.train import Trainer, cross_entropy_loss

    assert n_devices % 8 == 0 or n_devices >= 8, (
        f"reshard drill wants >=8 devices, got {n_devices}"
    )
    devices = jax.devices()[:8]
    tag = uuid.uuid4().hex[:8]
    own_dir = ckpt_dir is None
    if own_dir:
        ckpt_dir = tempfile.mkdtemp(prefix="dlrover_tpu_reshard_")

    cfg = LlamaConfig.tiny(num_kv_heads=4)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, 65))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    init_rng = jax.random.PRNGKey(0)

    def eval_loss(trainer, state):
        with trainer.mesh:
            logits = model.apply(
                {"params": state.params}, batch["input_ids"]
            )
            return float(
                jax.device_get(
                    cross_entropy_loss(logits, batch["labels"], None)
                )
            )

    try:
        # -- mesh A: train one step, save ------------------------------
        mesh_a = build_mesh(
            MeshConfig(dp=1, fsdp=2, tp=2, cp=2), devices=devices
        )
        trainer_a = Trainer(model, optax.adamw(1e-2), mesh_a)
        state = trainer_a.create_state(init_rng, batch["input_ids"])
        state, _ = trainer_a.train_step(state, batch)
        loss_before = eval_loss(trainer_a, state)
        # sync snapshot: the drill times the save itself, and the driver
        # gate must not depend on background-thread scheduling
        ckpt_a = Checkpointer(
            ckpt_dir, scope=f"rsa{tag}", async_snapshot=False
        )
        save_phases: Dict = {}
        with _ledger_phases(save_phases):
            ckpt_a.save_checkpoint(1, state, StorageType.DISK)
            ok = ckpt_a.wait_latest_checkpoint(timeout=300)
        save_s = save_phases.get("ckpt_stall", 0.0)
        assert ok, "reshard drill: save did not persist"
        ckpt_a.close()

        # -- torn-shm leg: a stager killed mid-stream leaves a dirty-
        # generation snapshot in mesh B's shm; the restore must detect
        # it and fall back to storage instead of assembling garbage ----
        from dlrover_tpu.common.multi_process import SharedMemoryBuffer
        from dlrover_tpu.trainer.flash_checkpoint import snapshot
        from dlrover_tpu.trainer.flash_checkpoint.engine import shm_name

        torn_shm = SharedMemoryBuffer(shm_name(0, f"rsb{tag}"))
        stub = {"junk": np.arange(1 << 16, dtype=np.float32)}

        def _fault(chunk_idx):
            if chunk_idx >= 1:
                raise RuntimeError("injected mid-stream kill")

        snapshot.set_stream_fault(_fault)
        try:
            snapshot.stream_snapshot(
                torn_shm, 99, snapshot.plan_shards(stub),
                chunk_bytes=1 << 14,
            )
            raise AssertionError("stream fault injection did not fire")
        except RuntimeError:
            pass
        finally:
            snapshot.set_stream_fault(None)
        assert snapshot.is_torn(torn_shm), "fault must leave a dirty gen"
        assert snapshot.read_snapshot_meta(torn_shm) is None, (
            "torn snapshot must read as no-snapshot"
        )

        # -- mesh B: restore with a different layout -------------------
        mesh_b = build_mesh(MeshConfig(dp=2, fsdp=4), devices=devices)
        trainer_b = Trainer(model, optax.adamw(1e-2), mesh_b)
        abstract = trainer_b.abstract_state(init_rng, batch["input_ids"])
        shardings = trainer_b.state_sharding_for(
            init_rng, batch["input_ids"]
        )
        # fresh scope: shm still holds mesh A's snapshot; the drill must
        # exercise the STORAGE reshard path
        ckpt_b = Checkpointer(ckpt_dir, scope=f"rsb{tag}")
        restore_phases: Dict = {}
        with _ledger_phases(restore_phases):
            state_b, step = ckpt_b.load_checkpoint(abstract, shardings)
        restore_s = restore_phases.get("ckpt_stall", 0.0)
        assert state_b is not None and step == 1, (
            f"reshard restore failed (step={step})"
        )
        trainer_b.state_shardings = shardings
        loss_after = eval_loss(trainer_b, state_b)
        assert abs(loss_after - loss_before) <= 1e-4 * max(
            1.0, abs(loss_before)
        ), f"loss discontinuity across reshard: {loss_before} -> {loss_after}"
        # training continues on the new mesh
        state_b, metrics = trainer_b.train_step(state_b, batch)
        next_loss = float(jax.device_get(metrics["loss"]))
        assert np.isfinite(next_loss), "post-reshard step diverged"
        ckpt_b.engine.unlink_memory()
        ckpt_b.close()
        result = {
            "save_s": round(save_s, 3),
            "restore_reshard_s": round(restore_s, 3),
            "loss_before": round(loss_before, 6),
            "loss_after": round(loss_after, 6),
            "post_reshard_step_loss": round(next_loss, 6),
            "mesh_a": "dp1/fsdp2/tp2/cp2",
            "mesh_b": "dp2/fsdp4",
            # mesh B's shm held a deliberately torn (dirty-generation)
            # snapshot; the step==1 assertion above proves the restore
            # fell back to storage instead of trusting it
            "torn_shm_fallback": True,
            "timing_source": "goodput_ledger",
        }
        try:
            result["grad_sync_reshard"] = run_grad_sync_reshard_leg(
                devices, batch, tag
            )
        except Exception as e:  # noqa: BLE001 - the primary reshard leg
            # is a driver gate; the grad-sync leg reports its own
            # failure instead of voiding that evidence
            result["grad_sync_reshard"] = {"error": str(e)[:300]}
        gs = result.get("grad_sync_reshard") or {}
        if "live_reshard_s" in gs:
            # the live transition's ledger price and its edge over the
            # restart path, both from the SAME ledger account
            result["live_reshard_s"] = gs["live_reshard_s"]
            result["reshard_speedup_vs_restart"] = (
                gs["reshard_speedup_vs_restart"]
            )
        return result
    finally:
        if own_dir:
            shutil.rmtree(ckpt_dir, ignore_errors=True)


def run_grad_sync_reshard_leg(devices, batch, tag: str) -> Dict:
    """Second drill leg: the int8_sharded grad-sync state survives a
    dp-degree change.  dp4 trains under the quantized policy (dp-sharded
    Adam moments + error-feedback stacks in the TrainState), saves, and
    dp2 restores via ``Trainer.load_state`` — moments reshard through
    the generic global-index path, the EF stacks are redistributed
    (``sum(old)/dp_new``; the total pending quantization error is the
    invariant).  Asserts loss continuity and the EF-sum invariant, then
    trains one more step on the new degree."""
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        StorageType,
    )
    from dlrover_tpu.trainer.train import Trainer, cross_entropy_loss

    cfg = LlamaConfig.tiny(num_kv_heads=4)
    model = LlamaForCausalLM(cfg)
    init_rng = jax.random.PRNGKey(0)
    ckpt_dir = tempfile.mkdtemp(prefix="dlrover_tpu_gs_reshard_")

    def eval_loss(trainer, state):
        with trainer.mesh:
            logits = model.apply(
                {"params": state.params}, batch["input_ids"]
            )
            return float(
                jax.device_get(
                    cross_entropy_loss(logits, batch["labels"], None)
                )
            )

    def ef_total(state):
        return {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in state.ef_residual.items()
        }

    try:
        mesh_c = build_mesh(MeshConfig(dp=4), devices=devices[:4])
        trainer_c = Trainer(
            model, optax.adamw(1e-2), mesh_c, grad_sync="int8_sharded"
        )
        state = trainer_c.create_state(init_rng, batch["input_ids"])
        batch_c = trainer_c.shard_batch(batch)
        for _ in range(2):
            state, _ = trainer_c.train_step(state, batch_c)
        loss_before = eval_loss(trainer_c, state)
        ef_before = ef_total(state)
        ckpt_c = Checkpointer(
            ckpt_dir, scope=f"gsa{tag}", async_snapshot=False
        )
        ckpt_c.save_checkpoint(2, state, StorageType.DISK)
        assert ckpt_c.wait_latest_checkpoint(timeout=300), (
            "grad-sync reshard leg: save did not persist"
        )
        ckpt_c.close()

        from dlrover_tpu.observability import trace

        ckpt_d = Checkpointer(ckpt_dir, scope=f"gsb{tag}")
        # the restart path, ledger-priced end to end: a respawned
        # worker rebuilds the trainer at the new degree and restores
        # from storage.  The outer rdzv.restore span claims every
        # bucket the inner ckpt spans don't, so the sum of phases is
        # the whole transition — the same accounting the live leg gets
        # from its reshard.live span (apples-to-apples).
        restore_phases: Dict = {}
        with _ledger_phases(restore_phases):
            with trace.span("rdzv.restore"):
                mesh_d = build_mesh(MeshConfig(dp=2), devices=devices[:2])
                trainer_d = Trainer(
                    model, optax.adamw(1e-2), mesh_d,
                    grad_sync="int8_sharded",
                )
                state_d, step = trainer_d.load_state(
                    ckpt_d, init_rng, batch["input_ids"]
                )
        restore_s = sum(restore_phases.values())
        assert state_d is not None and step == 2, (
            f"grad-sync reshard restore failed (step={step})"
        )
        loss_after = eval_loss(trainer_d, state_d)
        assert abs(loss_after - loss_before) <= 1e-4 * max(
            1.0, abs(loss_before)
        ), (
            "loss discontinuity across grad-sync reshard: "
            f"{loss_before} -> {loss_after}"
        )
        ef_after = ef_total(state_d)
        for k, total in ef_before.items():
            np.testing.assert_allclose(
                ef_after[k], total, rtol=1e-5, atol=1e-7,
                err_msg=f"EF total not preserved for {k}",
            )

        # -- live leg (r22): the SAME dp4 -> dp2 transition in place on
        # the still-running dp4 trainer, priced by the SAME ledger the
        # restart restore was — the apples-to-apples speedup.
        # Bit-exactness against the restart-restored state is the
        # correctness gate.
        live_phases: Dict = {}
        with _ledger_phases(live_phases):
            state_live, live_report = trainer_c.live_reshard(
                state, {"dp": 2}, sample_input=batch["input_ids"],
                reason="reshard drill live leg",
            )
        assert live_phases.get("live_reshard", 0.0) > 0.0, (
            f"live transition unpriced: {live_phases}"
        )
        live_s = sum(live_phases.values())
        assert live_phases.get("rendezvous_restart", 0.0) == 0.0, (
            f"live transition restarted something: {live_phases}"
        )
        assert live_report["donor_bytes_read"] == 0, (
            "all-survivor shrink must not touch the donor manifest"
        )
        for live_leaf, restart_leaf in zip(
            jax.tree_util.tree_leaves(state_live),
            jax.tree_util.tree_leaves(state_d),
        ):
            assert np.array_equal(
                np.asarray(live_leaf), np.asarray(restart_leaf)
            ), "live reshard diverged from the restart path"

        batch_d = trainer_d.shard_batch(batch)
        state_d, metrics = trainer_d.train_step(state_d, batch_d)
        next_loss = float(jax.device_get(metrics["loss"]))
        assert np.isfinite(next_loss), "post-reshard grad-sync step diverged"
        ckpt_d.engine.unlink_memory()
        ckpt_d.close()
        return {
            "mode": "int8_sharded",
            "dp_from": 4,
            "dp_to": 2,
            "restore_s": round(restore_s, 3),
            "live_reshard_s": round(live_s, 3),
            "reshard_speedup_vs_restart": (
                round(restore_s / live_s, 1) if live_s else None
            ),
            "live_bit_exact_vs_restart": True,
            "loss_before": round(loss_before, 6),
            "loss_after": round(loss_after, 6),
            "post_reshard_step_loss": round(next_loss, 6),
            "ef_total_preserved": True,
        }
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main() -> int:
    """Subprocess entry: force an 8-virtual-device CPU backend and print
    one JSON line."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ.setdefault("DLROVER_TPU_JOB_NAME", f"rs{uuid.uuid4().hex[:6]}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    result = run_reshard_drill(8)
    print("RESHARD_DRILL " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
