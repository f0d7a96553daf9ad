#!/usr/bin/env python3
"""Record the small trace the reduction is checked on: three training
steps of a one-layer model with the FA2 kernel, on the chip.

    python3 benchmarks/tests/record_trace.py <out_dir>

Writes ``tiny_step.xplane.pb`` there; the file kept under
``benchmarks/tests/data/`` came from this script (my chip run, PR 24)."""

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.train import Trainer

    cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                      num_layers=1, num_heads=2, num_kv_heads=1, head_dim=128,
                      max_seq_len=256, attention_impl="flash")
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-3),
                      build_mesh(MeshConfig(dp=1)))
    ids = np.random.default_rng(0).integers(0, 512, size=(2, 257))
    batch = {"input_ids": np.asarray(ids[:, :-1], np.int32),
             "labels": np.asarray(ids[:, 1:], np.int32)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    for _ in range(3):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        jax.block_until_ready(metrics["loss"])
    scratch = tempfile.mkdtemp(prefix="rectrace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(scratch, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, metrics = trainer.train_step(
                    state, trainer.shard_batch(batch))
            with jax.profiler.TraceAnnotation("bench.read_back"):
                float(jax.device_get(metrics["loss"]))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        scratch, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(found, os.path.join(out_dir, "tiny_step.xplane.pb"))
    shutil.rmtree(scratch, ignore_errors=True)
    print("recorded", os.path.getsize(found), "bytes", jnp.float32(0))


if __name__ == "__main__":
    main(sys.argv[1])
