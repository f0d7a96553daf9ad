"""Elastic Llama training with Flash Checkpoint: the survival demo.

Run::

    tpurun --standalone --nproc_per_node=1 --platform=cpu \
        examples/train_llama_ckpt.py /tmp/ckpt_dir

Saves to host memory every 2 steps and to disk every 10; on restart
(crash, preemption, rescale) it resumes from the freshest snapshot —
memory if the mesh is unchanged (sub-second), disk with resharding
otherwise.  Set DLROVER_TPU_CRASH_AT_STEP=N to simulate a hard crash.
"""

import os
import sys

import dlrover_tpu.trainer as trainer_pkg


def main() -> int:
    ctx = trainer_pkg.init()
    ckpt_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/dlrover_tpu_ckpt"

    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
    from dlrover_tpu.trainer.train import Trainer

    total_steps = int(os.getenv("DLROVER_TPU_TOTAL_STEPS", "20"))
    crash_at = int(os.getenv("DLROVER_TPU_CRASH_AT_STEP", "-1"))

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    mesh = build_mesh(MeshConfig(dp=jax.device_count()))
    trainer = Trainer(model, optax.adamw(1e-2), mesh)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, 33))
    global_batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    # every process feeds its slice of the global batch; shard_batch turns
    # host-local numpy into global jax Arrays on the mesh's data axes
    per_proc = global_batch["input_ids"].shape[0] // ctx.num_processes
    lo = ctx.process_id * per_proc
    host_batch = {k: v[lo : lo + per_proc] for k, v in global_batch.items()}
    batch = None  # created after the trainer knows its shardings

    init_rng = jax.random.PRNGKey(0)
    sample = global_batch["input_ids"]
    ckpt = Checkpointer(ckpt_dir)
    state, start_step = ckpt.load_checkpoint(
        trainer.abstract_state(init_rng, sample),
        trainer.state_sharding_for(init_rng, sample),
    )
    if state is None:
        state = trainer.create_state(init_rng, sample)
        start_step = 0
        print("starting fresh", flush=True)
    else:
        trainer.state_shardings = trainer.state_sharding_for(init_rng, sample)
        print(f"resumed from step {start_step}", flush=True)
    batch = trainer.shard_batch(host_batch)

    import time

    from dlrover_tpu.utils.timing import hard_block

    metrics = None
    first_resumed_step = ctx.restart_count > 0
    for step in range(start_step + 1, total_steps + 1):
        state, metrics = trainer.train_step(state, batch)
        if first_resumed_step:
            # recovery marker: the step is only claimed done once the
            # device finished it (a reader of the log takes the
            # crash_ts -> resume_ts span as the recovery time)
            hard_block(metrics["loss"])
            print(
                f"resume_ts={time.time():.3f} step={step}", flush=True
            )
            first_resumed_step = False
        if step == crash_at and ctx.restart_count == 0:
            print(f"simulating crash at step {step}", flush=True)
            print(f"crash_ts={time.time():.3f}", flush=True)
            os._exit(17)
        # DISK implies the same shm snapshot, so never pair both at one
        # step (the second save would just re-stage identical state)
        if step % 10 == 0:
            ckpt.save_checkpoint(step, state, StorageType.DISK)
        elif step % 2 == 0:
            ckpt.save_checkpoint(step, state, StorageType.MEMORY)
    if not ckpt.wait_latest_checkpoint(timeout=300):
        print("WARNING: final checkpoint persist did not complete",
              flush=True)
    if metrics is not None:
        loss = float(jax.device_get(metrics["loss"]))
        print(f"done at step {total_steps}, loss={loss:.4f}", flush=True)
    else:
        print(f"done at step {total_steps} (already complete)", flush=True)
    ckpt.engine.unlink_memory()  # clean completion: drop the shm snapshot
    ckpt.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
