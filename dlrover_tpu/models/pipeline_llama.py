"""Pipeline-parallel Llama: GPipe stages over the ``pp`` mesh axis.

Reuses ``LlamaForCausalLM``'s parameters unchanged (``scan_layers=True``
gives every decoder-layer weight a leading ``num_layers`` dim), so a
checkpoint trained one way restores into the other: the pipeline is a
different *schedule* over the same pytree, which is exactly how the
reference treats Megatron TP/PP regrouping in its distributed checkpoint
logic (``dlrover/python/elastic_agent/torch/ckpt_saver.py``).

Embedding, final norm and LM head run replicated on every pp rank
(cheap, and keeps the pipeline body homogeneous); only the decoder-layer
stack is staged.  Composes with data parallelism (each ``dp`` shard
pipelines its own microbatches); tp/fsdp inside a stage is future work.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    DecoderLayer,
    LlamaConfig,
    LlamaForCausalLM,
    RMSNorm,
)
from dlrover_tpu.ops.pallas.kept import LAYER_POLICY
from dlrover_tpu.parallel.pipeline import pipeline_apply, stage_params
from dlrover_tpu.parallel.sharding import unbox_params


class PipelinedLlama:
    """Function-style wrapper: same params as ``LlamaForCausalLM``,
    pipelined execution over ``mesh.shape['pp']`` stages."""

    def __init__(
        self,
        config: LlamaConfig,
        mesh,
        num_microbatches: int = 4,
    ):
        if not config.scan_layers:
            raise ValueError(
                "PipelinedLlama needs scan_layers=True (stacked per-layer "
                "params are what gets split into stages)"
            )
        self.config = config
        self.mesh = mesh
        self.num_stages = mesh.shape["pp"]
        if config.num_layers % self.num_stages:
            raise ValueError(
                f"{config.num_layers} layers not divisible by "
                f"{self.num_stages} pipeline stages"
            )
        self.num_microbatches = num_microbatches
        self.inner = LlamaForCausalLM(config)

    def init(self, rng, input_ids):
        return self.inner.init(rng, input_ids)

    def num_params(self) -> int:
        return self.inner.num_params()

    def _stage_fn(self):
        cfg = self.config

        def body(h, lp):
            B, S, _ = h.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None, None, :, :]
            out = DecoderLayer(cfg).apply({"params": lp}, h, positions, mask)
            return out, None

        if cfg.remat:
            # the scanned stack's own policy (``models/llama.py::
            # _layer_class``): the same object, so the two cannot drift
            body = jax.checkpoint(body, policy=LAYER_POLICY)

        def stage(sp, x):
            h, _ = jax.lax.scan(body, x, sp)
            return h

        return stage

    def apply(self, variables, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``variables``: the flax dict from ``init`` (boxed or unboxed)."""
        cfg = self.config
        params = variables.get("params", variables)
        params = unbox_params(params)

        x = params["embed_tokens"].astype(cfg.dtype)[input_ids]
        staged = stage_params(
            params["layers"]["layer"], self.num_stages
        )
        piped = pipeline_apply(
            self._stage_fn(), self.mesh, self.num_microbatches
        )
        x = piped(staged, x)

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype).apply(
            {"params": params["final_norm"]}, x
        )
        # same head semantics as LlamaForCausalLM's LMHead: compute-dtype
        # operands on the MXU with fp32 accumulation (models/llama.py) —
        # the stage-parity tests compare against that model bit-for-bit
        logits = jax.lax.dot_general(
            x.astype(cfg.dtype),
            params["lm_head"]["kernel"].astype(cfg.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return logits
