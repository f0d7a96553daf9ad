"""Remaining model families on the REAL TPU: GPT (scan+remat) and ViT.

The scan-over-layers + remat combination and the conv patch-embed are the
compilation risks the CPU tier can't vouch for; one train step each on
hardware settles it.
"""

import jax
import numpy as np
import optax

from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer


def _train_losses(trainer, state, batch, steps=3):
    losses = []
    for _ in range(steps):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    return losses


def test_gpt_scan_remat_trains_on_device(tpu_backend):
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig.tiny(scan_layers=True, remat=True)
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=1))
    trainer = Trainer(model, optax.adamw(1e-2), mesh)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(4, cfg.block_size + 1))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    losses = _train_losses(trainer, state, batch)
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"gpt loss did not drop on TPU: {losses}"


def test_gpt_head_64_trains_through_the_kernel(tpu_backend, monkeypatch):
    """A GPT whose shape the FA2 kernel takes (4 heads of 64, S 256)
    reaches it with no option set: the compiled step holds the kernel's
    custom calls and no [B, H, S, S] result, and three steps give the
    losses of the reference path."""
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.ops import attention

    cfg = GPTConfig(vocab_size=512, n_embd=256, n_layer=2, n_head=4,
                    block_size=256, scan_layers=True, remat=True)
    mesh = build_mesh(MeshConfig(dp=1))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(4, cfg.block_size + 1))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }

    def compiled_text_and_losses():
        trainer = Trainer(GPT(cfg), optax.adamw(1e-2), mesh)
        state = trainer.create_state(
            jax.random.PRNGKey(0), batch["input_ids"]
        )
        text = trainer.lower_train_step(
            state, trainer.shard_batch(batch)
        ).compile().as_text()
        return text, _train_losses(trainer, state, batch)

    text, losses = compiled_text_and_losses()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    assert "[4,4,256,256]" not in text
    monkeypatch.setattr(
        attention, "attention_path", lambda *shape: "reference"
    )
    ref_text, ref_losses = compiled_text_and_losses()
    assert "tpu_custom_call" not in ref_text
    assert "[4,4,256,256]" in ref_text
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    # bf16 kernel noise only, as the Llama flash-against-reference test
    np.testing.assert_allclose(losses, ref_losses, rtol=0.05)


def test_vit_trains_on_device(tpu_backend):
    from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification

    cfg = ViTConfig.tiny()
    model = ViTForImageClassification(cfg)
    mesh = build_mesh(MeshConfig(dp=1))

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
    losses = _train_losses(trainer, state, batch)
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"vit loss did not drop on TPU: {losses}"


def test_flash_attention_long_sequence(tpu_backend):
    """Long-context kernel health: S=4096, d=128 — the tuned-table
    nearest-shape borrow path plus a 16x-larger grid than the unit
    shapes."""
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4096, 4, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 4096, 4, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 4096, 4, 128), jnp.bfloat16)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v
    )
    out = np.asarray(jax.device_get(out), np.float32)
    assert out.shape == (1, 4096, 4, 128)
    assert np.isfinite(out).all()
    # causal row 0 attends only to itself: output == v[0]
    np.testing.assert_allclose(
        out[0, 0], np.asarray(jax.device_get(v), np.float32)[0, 0],
        atol=2e-2, rtol=0,
    )
