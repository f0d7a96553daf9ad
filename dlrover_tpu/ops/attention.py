"""Attention kernels: one reference core, a TPU flash path on top.

The reference math lives in exactly one place so numerics policy (fp32
logits, mask fill value, fp32 softmax) can never diverge between model
families.  ``flash_attention`` is the Pallas TPU kernel
(ops/pallas/flash_attention.py) and nothing else: it never turns into the
reference.  Off the chip it raises unless the caller asks for the Pallas
interpreter by argument; under a mesh of several devices it runs the kernel
per shard through ``shard_map`` (a Mosaic kernel cannot be partitioned
automatically).  ``causal_attention`` is the one place that chooses between
the two for a model that has no opinion (the GPT family): from the backend
and the shape, once, while the step is traced.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.observability import trace


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain attention; q,k,v: [B, S, H, D] (k/v heads may be fewer: GQA).

    fp32 logits + softmax regardless of input dtype; mask is broadcastable
    to [B, H, Sq, Sk] with True = attend.
    """
    if k.shape[2] != q.shape[2]:
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_shard_specs(mesh):
    """(q_spec, kv_spec) for the per-shard kernel call on ``mesh``, from
    the logical rules table (``ring_attention_sharded`` does the same):
    batch over the data axes, heads over ``tp``.  The sequence stays whole
    on every shard — the kernel's causal mask is positional."""
    import flax.linen as nn

    from dlrover_tpu.parallel.sharding import spec_on_mesh

    rules = list(nn.get_logical_axis_rules()) or None
    return tuple(
        spec_on_mesh(mesh, ("batch", None, heads_axis, None), rules)
        for heads_axis in ("heads", "kv_heads")
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention: the Pallas TPU kernel, never the reference.

    Block sizes default to the autotuned table (``ops/pallas/tuning.py``)
    for this (seq_len, head_dim); pass explicit values to override.
    ``interpret=True`` runs the kernel in the Pallas interpreter (tests off
    the chip); without it a backend that is not a TPU is an error.  Under
    an active mesh of more than one device, outside any ``shard_map``, the
    call is wrapped in one.
    """
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "flash_attention needs a TPU backend (found "
            f"{jax.default_backend()!r}); use attention_impl='reference' "
            "off the chip, or pass interpret=True"
        )
    from dlrover_tpu.ops.pallas.flash_attention import (
        heads_per_block,
        pallas_flash_attention,
    )
    from dlrover_tpu.ops.pallas.tuning import tuned_blocks
    from dlrover_tpu.ops.ring_attention import active_mesh

    seq_len, heads, head_dim = q.shape[1:]
    if not block_q or not block_kv:
        tuned_q, tuned_kv = tuned_blocks(seq_len, head_dim)
        block_q = block_q or tuned_q
        block_kv = block_kv or tuned_kv
    # ``layout``: the kernels read and write [B, S, H*D] in column blocks
    # of 128 lanes, ``heads_per_block`` heads in each
    trace.note_trace_time(
        "attention.path", impl="flash", seq=seq_len, head_dim=head_dim,
        heads=heads, blocks=(block_q, block_kv), layout="bsd",
        heads_per_block=heads_per_block(head_dim),
    )

    def kernel(q_, k_, v_):
        return pallas_flash_attention(
            q_, k_, v_, causal, block_q, block_kv, interpret
        )

    mesh = active_mesh()
    if (
        mesh is None
        or mesh.size == 1
        # already per-shard: the enclosing shard_map owns the mesh axes
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return kernel(q, k, v)
    from dlrover_tpu.parallel.collectives import shard_map_unchecked

    def per_shard(q_, k_, v_):
        # a device trace names a kernel by the innermost scope around it,
        # which the shard_map would make "shard_map": keep the name the
        # unsharded call has from its module (``attn._attend``)
        with jax.named_scope("shard._attend"):
            return kernel(q_, k_, v_)

    q_spec, kv_spec = _flash_shard_specs(mesh)
    return shard_map_unchecked(
        per_shard, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )(q, k, v)


def attention_path(backend: str, seq_len: int, head_dim: int, heads: int,
                   kv_heads: int) -> str:
    """``"flash"`` or ``"reference"``: the kernel wherever it can run, from
    what the code can observe and nothing else."""
    from dlrover_tpu.ops.pallas.flash_attention import kernel_takes

    if backend == "tpu" and kernel_takes(seq_len, head_dim, heads, kv_heads):
        return "flash"
    return "reference"


def causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Causal self-attention, q/k/v: [B, S, H, D]: the FA2 kernel on a TPU
    at a shape it takes (no [B, H, S, S] tensor in HBM, forward or
    backward), the reference core with the caller's causal ``mask``
    everywhere else.  Both compute float32 scores and softmax from the
    operands as given and accumulate in float32."""
    seq_len, heads, head_dim = q.shape[1:]
    if attention_path(jax.default_backend(), seq_len, head_dim, heads,
                      k.shape[2]) == "flash":
        return flash_attention(q, k, v, causal=True)  # writes the record
    trace.note_trace_time("attention.path", impl="reference", seq=seq_len,
                          head_dim=head_dim, heads=heads, blocks=None)
    return reference_attention(q, k, v, mask)
