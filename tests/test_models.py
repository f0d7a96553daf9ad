"""Model + parallel-layer tests on the 8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import (
    DEFAULT_LOGICAL_RULES,
    spec_for_logical_axes,
)
from dlrover_tpu.trainer.train import Trainer, cross_entropy_loss


def _batch(rng, batch, seq, vocab):
    ids = rng.integers(0, vocab, size=(batch, seq + 1))
    return {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }


class TestMesh:
    def test_infer_axis(self):
        cfg = MeshConfig(dp=-1, fsdp=2, tp=2)
        assert cfg.axis_sizes(8) == (2, 2, 2, 1, 1, 1)

    def test_bad_shape(self):
        with pytest.raises(ValueError):
            MeshConfig(dp=3, fsdp=1, tp=1).axis_sizes(8)

    def test_build_mesh(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        assert mesh.shape == {
            "dp": 2, "fsdp": 2, "tp": 2, "cp": 1, "ep": 1, "pp": 1,
        }

    def test_spec_mapping(self):
        # "embed"->fsdp is dropped (fsdp already used by batch), then trimmed
        spec = spec_for_logical_axes(("batch", "seq", "embed"))
        assert spec == jax.sharding.PartitionSpec(("dp", "fsdp", "ep"), "cp")
        # an already-used mesh axis drops the whole later mapping
        spec = spec_for_logical_axes(("embed", "batch"))
        assert spec == jax.sharding.PartitionSpec("fsdp")


class TestLlama:
    def test_forward_shapes_and_dtype(self):
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        ids = jnp.zeros((2, 16), jnp.int32)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
        logits = jax.jit(model.apply)(variables, ids)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Changing a future token must not change past logits."""
        cfg = LlamaConfig.tiny(remat=False, scan_layers=False)
        model = LlamaForCausalLM(cfg)
        rng = jax.random.PRNGKey(1)
        ids = jax.random.randint(rng, (1, 12), 0, cfg.vocab_size)
        variables = jax.jit(model.init)(rng, ids)
        apply = jax.jit(model.apply)
        base = apply(variables, ids)
        changed = ids.at[0, 8].set((ids[0, 8] + 1) % cfg.vocab_size)
        out = apply(variables, changed)
        np.testing.assert_allclose(
            np.asarray(base[0, :8], np.float32),
            np.asarray(out[0, :8], np.float32),
            rtol=2e-3, atol=2e-3,
        )
        assert not np.allclose(
            np.asarray(base[0, 8:]), np.asarray(out[0, 8:]), atol=1e-4
        )

    def test_gqa_heads(self):
        cfg = LlamaConfig.tiny(num_heads=4, num_kv_heads=2)
        model = LlamaForCausalLM(cfg)
        ids = jnp.zeros((1, 8), jnp.int32)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
        logits = jax.jit(model.apply)(variables, ids)
        assert logits.shape[-1] == cfg.vocab_size


class TestShardedTraining:
    def _train(self, mesh_cfg, steps=6, grad_accum=1):
        mesh = build_mesh(mesh_cfg)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        trainer = Trainer(
            model, optax.adamw(1e-2), mesh, grad_accum_steps=grad_accum
        )
        rng = np.random.default_rng(0)
        sample = _batch(rng, 8, 16, cfg.vocab_size)
        state = trainer.create_state(
            jax.random.PRNGKey(0), sample["input_ids"]
        )
        batch = sample  # overfit one batch; loss must drop
        losses = []
        for _ in range(steps):
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses, state, trainer

    def test_dp_fsdp_tp_training(self):
        losses, state, trainer = self._train(MeshConfig(dp=2, fsdp=2, tp=2))
        assert losses[-1] < losses[0]
        assert int(state.step) == 6
        # params are actually sharded: at least one param leaf not replicated
        sharded = [
            leaf.sharding
            for leaf in jax.tree.leaves(state.params)
            if hasattr(leaf, "sharding")
        ]
        assert any(
            s.spec != jax.sharding.PartitionSpec() for s in sharded
        )

    def test_pure_dp_training(self):
        losses, _, _ = self._train(MeshConfig(dp=8, fsdp=1, tp=1))
        assert losses[-1] < losses[0]

    def test_grad_accum_matches_global_batch(self):
        losses, _, trainer = self._train(
            MeshConfig(dp=4, fsdp=2), grad_accum=2
        )
        assert losses[-1] < losses[0]
        # elastic re-adjustment: shrink world -> accumulate more
        accum = trainer.adjust_accum_for_world(
            global_batch=64, per_device_batch=1
        )
        assert accum == 8

    def test_cp_axis_shards_sequence(self):
        losses, _, _ = self._train(MeshConfig(dp=2, fsdp=1, tp=2, cp=2))
        assert losses[-1] < losses[0]


class TestGPT:
    def test_forward_and_train(self):
        mesh = build_mesh(MeshConfig(dp=4, fsdp=2))
        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        trainer = Trainer(model, optax.adamw(1e-2), mesh)
        rng = np.random.default_rng(0)
        batch = _batch(rng, 8, 32, cfg.vocab_size)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        l0 = None
        for _ in range(5):
            state, m = trainer.train_step(state, batch)
            l0 = l0 or float(m["loss"])
        assert float(m["loss"]) < l0

    def test_loss_fn_masking(self):
        logits = jnp.zeros((1, 4, 10))
        labels = jnp.array([[1, 2, 3, 4]])
        mask = jnp.array([[1.0, 1.0, 0.0, 0.0]])
        full = cross_entropy_loss(logits, labels)
        masked = cross_entropy_loss(logits, labels, mask)
        assert full == pytest.approx(np.log(10), rel=1e-5)
        assert masked == pytest.approx(np.log(10), rel=1e-5)


class TestViT:
    def test_forward_shapes(self):
        from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification

        cfg = ViTConfig.tiny()
        model = ViTForImageClassification(cfg)
        images = jnp.ones((2, cfg.image_size, cfg.image_size, 3))
        params = jax.jit(model.init)(jax.random.PRNGKey(0), images)["params"]
        logits = jax.jit(model.apply)({"params": params}, images)
        assert logits.shape == (2, cfg.num_classes)
        assert logits.dtype == jnp.float32

    def test_sharded_training_loss_drops(self):
        from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification

        mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        cfg = ViTConfig.tiny()
        model = ViTForImageClassification(cfg)

        def vit_loss(params, batch):
            logits = model.apply({"params": params}, batch["images"])
            return model.loss(logits, batch["labels"])

        trainer = Trainer(model, optax.adamw(3e-3), mesh, loss_fn=vit_loss)
        rng = np.random.default_rng(0)
        batch = {
            "images": rng.normal(
                size=(8, cfg.image_size, cfg.image_size, 3)
            ).astype(np.float32),
            "labels": rng.integers(0, cfg.num_classes, 8).astype(np.int32),
        }
        state = trainer.create_state(jax.random.PRNGKey(0), batch["images"])
        losses = []
        for _ in range(6):
            state, metrics = trainer.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        # the shared rules table actually shards vision params too
        specs = [
            leaf.sharding.spec
            for leaf in jax.tree.leaves(state.params)
            if hasattr(leaf, "sharding")
        ]
        assert any(spec != jax.sharding.PartitionSpec() for spec in specs)

    def test_cp_mesh_state_creation(self):
        """pos_embed length is odd (num_patches+1): it must be replicated
        over cp, not partitioned on the 'seq' rule."""
        from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification

        mesh = build_mesh(MeshConfig(dp=2, cp=2, tp=2))
        cfg = ViTConfig.tiny()
        model = ViTForImageClassification(cfg)
        trainer = Trainer(model, optax.adamw(1e-2), mesh)
        images = jnp.ones((4, cfg.image_size, cfg.image_size, 3))
        state = trainer.create_state(jax.random.PRNGKey(0), images)
        assert int(state.step) == 0

    def test_unscanned_matches_layer_count(self):
        from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification

        cfg = ViTConfig.tiny(scan_layers=False, remat=False)
        model = ViTForImageClassification(cfg)
        images = jnp.ones((1, cfg.image_size, cfg.image_size, 3))
        params = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), images)["params"]
        blocks = [k for k in params if k.startswith("encoder_")]
        assert len(blocks) == cfg.num_layers
