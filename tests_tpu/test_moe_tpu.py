"""The routed feed-forward block on the REAL TPU.

The sorted dispatch is gathers through a permutation and
``jax.lax.ragged_dot``, which the TPU compiler lowers to its own grouped
kernel: patterns the CPU accepts whatever the chip's compiler makes of
them.  This is the on-hardware proof that the block compiles and
trains, on one chip (``ep=1``: no collective)."""

import jax
import numpy as np
import optax

from dlrover_tpu.models.llama import LlamaForCausalLM
from dlrover_tpu.models.moe import MoELlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer


def test_moe_trains_on_device(tpu_backend):
    cfg = MoELlamaConfig.tiny_moe(num_experts=4, qk_norm=True)
    model = LlamaForCausalLM(cfg)
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(model, optax.adamw(1e-2), mesh)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(4, 65))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"moe loss did not drop on TPU: {losses}"
    mlp = jax.device_get(metrics["stats"])["layers"]["layer"]["mlp"]
    assert np.all(mlp["load_max_over_mean"][0] >= 1.0)
