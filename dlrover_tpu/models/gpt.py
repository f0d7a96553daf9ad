"""GPT-2 family (nanoGPT-class), TPU-first.

Parity target: the reference's canonical demo job is nanoGPT trained via
``dlrover-run`` (``examples/pytorch/nanogpt/train.py`` in the reference);
this is its mesh-native equivalent, sharing the logical-axis vocabulary of
the Llama family so the same sharding rules apply.  Attention is fused
where it can be, as nanoGPT's is: ``ops.attention.causal_attention`` takes
the FA2 kernel on a TPU at a shape the kernel runs and the reference core
elsewhere; the configuration has no field for it.
"""

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    block_size: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # scan_layers stacks params under one 'h' subtree (layers axis) — a
    # DIFFERENT checkpoint layout from the unrolled h_{i} form; restore
    # pre-scan checkpoints with scan_layers=False
    scan_layers: bool = True  # one trace for any depth (compile time)
    remat: bool = True  # recompute activations (HBM for FLOPs)

    @classmethod
    def gpt2(cls, **kw):
        return cls(**kw)

    @classmethod
    def gpt2_xl(cls, **kw):
        """1.5B — the reference Flash-Checkpoint benchmark size."""
        return cls(n_embd=1600, n_layer=48, n_head=25, **kw)

    @classmethod
    def gpt2_large(cls, **kw):
        return cls(n_embd=1280, n_layer=36, n_head=20, **kw)

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(vocab_size=256, n_embd=64, n_layer=2, n_head=4,
                        block_size=64)
        defaults.update(kw)
        return cls(**defaults)


class Block(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.n_embd // cfg.n_head
        ln = partial(nn.LayerNorm, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        dense = partial(
            nn.DenseGeneral, dtype=cfg.dtype, param_dtype=cfg.param_dtype
        )

        h = ln(name="ln_1")(x)
        # the scopes give the block's two halves the kinds the Llama code's
        # modules have (the kind table of ``observability/trace.py``);
        # module names are parameter names and stay
        with jax.named_scope("attn"):
            qkv = dense(
                features=(3, cfg.n_head, head_dim),
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02),
                    ("embed", None, "heads", "head_dim")
                ),
                name="attn_qkv",
            )(h)
            # the split is the kernels' layout copy: they take q, k and v
            # as arrays of their own
            with jax.named_scope("attn.core"):
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                att = self._attend(q, k, v, mask)
            att = dense(
                features=cfg.n_embd,
                axis=(-2, -1),
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("heads", "head_dim", "embed")
                ),
                name="attn_proj",
            )(att)
            att = nn.Dropout(cfg.dropout)(att, deterministic=deterministic)
        x = x + att

        h = ln(name="ln_2")(x)
        with jax.named_scope("mlp"):
            h = dense(
                features=4 * cfg.n_embd,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", "mlp")
                ),
                name="mlp_fc",
            )(h)
            h = nn.gelu(h)
            h = dense(
                features=cfg.n_embd,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("mlp", "embed")
                ),
                name="mlp_proj",
            )(h)
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = x + h
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))

    def _attend(self, q, k, v, mask):
        # a method of this name, as in models/llama.py: the device trace
        # names the kernel's custom calls after it.  ``mask`` is the
        # reference path's; the kernel's causal mask is positional
        from dlrover_tpu.ops.attention import causal_attention

        return causal_attention(q, k, v, mask)


class _ScannedBlock(nn.Module):
    """Block wrapped for nn.scan (carry=x, per-layer params)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool = True):
        x = Block(self.config, name="block")(x, mask, deterministic)
        return x, None


class GPT(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        B, S = input_ids.shape
        wte = self.param(
            "wte",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.n_embd),
            cfg.param_dtype,
        )
        wpe = self.param(
            "wpe",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, "embed")
            ),
            (cfg.block_size, cfg.n_embd),
            cfg.param_dtype,
        )
        with jax.named_scope("embed"):
            x = (wte.astype(cfg.dtype)[input_ids]
                 + wpe.astype(cfg.dtype)[None, :S])
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None, None, :, :]
        if cfg.scan_layers:
            block_cls = _ScannedBlock
            if cfg.remat:
                block_cls = nn.remat(
                    block_cls,
                    prevent_cse=False,
                    static_argnums=(3,),  # deterministic
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
            x, _ = nn.scan(
                block_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,  # mask/deterministic shared
                length=cfg.n_layer,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="h")(x, mask, deterministic)
        else:
            # plain Block keeps the legacy h_{i}/... checkpoint layout
            plain = Block
            if cfg.remat:
                plain = nn.remat(
                    Block,
                    prevent_cse=True,
                    static_argnums=(3,),
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
            for i in range(cfg.n_layer):
                x = plain(cfg, name=f"h_{i}")(x, mask, deterministic)
        x = nn.LayerNorm(
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="ln_f"
        )(x)
        # weight-tied lm head, fp32 logits
        with jax.named_scope("head_loss"):
            logits = jnp.einsum(
                "bsd,vd->bsv", x.astype(jnp.float32), wte.astype(jnp.float32)
            )
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))
