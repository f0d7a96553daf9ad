"""Ring attention over the cp mesh axis: long-context sequence parallelism.

The new-capability op the reference lacks in-tree (SURVEY.md §2.8: SP/CP/
ring attention live outside DLRover; here they are first-class).  The
sequence dimension is sharded over the ``cp`` axis; each device computes
attention of its local queries against the key/value chunk it currently
holds, accumulates with the flash-style online softmax, and passes the
chunk around the ring with ``lax.ppermute`` — KV memory per device stays
O(S/cp) and the collective rides the ICI ring.  GQA K/V stay UNEXPANDED on
the wire (heads are repeated per-step, after the permute), and the final
rotation is peeled off (N-1 permutes for N chunks).

``ring_attention`` is the per-shard computation (call it inside
``shard_map``); ``ring_attention_sharded`` wraps it for mesh-level use with
PartitionSpecs derived from the logical-axis rules table.  Causal masking
is exact across chunks via global position offsets.  Only causal (or
no-mask) attention is supported — arbitrary padding masks are not threaded
through the ring.
"""

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "cp",
    causal: bool = True,
) -> jnp.ndarray:
    """Per-shard ring attention; q,k,v: [B, S_local, H, D] (seq sharded
    over ``axis_name``; k/v may have fewer (GQA) heads)."""
    groups = q.shape[2] // k.shape[2]
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, S_local, H, D = q.shape
    scale = D ** -0.5

    q32 = q.astype(jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def accumulate(acc, m, l, k_cur, v_cur, ring_step):
        """One online-softmax update of q against the held KV chunk."""
        if groups > 1:
            k_cur = jnp.repeat(k_cur, groups, axis=2)
            v_cur = jnp.repeat(v_cur, groups, axis=2)
        src = (my_idx - ring_step) % axis_size
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale  # [B, H, S_local, S_local]
        if causal:
            q_pos = my_idx * S_local + lax.broadcasted_iota(
                jnp.int32, (S_local, S_local), 0
            )
            k_pos = src * S_local + lax.broadcasted_iota(
                jnp.int32, (S_local, S_local), 1
            )
            s = jnp.where(
                (q_pos >= k_pos)[None, None, :, :], s, NEG_INF
            )
        m_cur = jnp.max(s, axis=-1)  # [B, H, S_local]
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
        return acc_new, m_new, l_new

    def scan_body(carry, ring_step):
        acc, m, l, k_cur, v_cur = carry
        acc, m, l = accumulate(acc, m, l, k_cur, v_cur, ring_step)
        # the UNEXPANDED chunk travels the ring (groups x less ICI traffic)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_next, v_next), None

    # carry init derived from q so it inherits q's varying manual axes
    # (fresh constants would be "unvarying" and shard_map's scan rejects a
    # carry whose variance changes between input and output)
    acc0 = jnp.zeros_like(q32)
    m0 = jnp.swapaxes(q32[..., 0] * 0.0, 1, 2) + NEG_INF  # [B, H, S_local]
    l0 = jnp.swapaxes(q32[..., 0] * 0.0, 1, 2)

    # peel the final chunk: N-1 rotations suffice for N chunks
    (acc, m, l, k_last, v_last), _ = lax.scan(
        scan_body, (acc0, m0, l0, k, v), jnp.arange(max(0, axis_size - 1))
    )
    acc, m, l = accumulate(acc, m, l, k_last, v_last, axis_size - 1)

    l_t = l.transpose(0, 2, 1)[..., None]  # [B, S_local, H, 1]
    safe_l = jnp.where(l_t == 0.0, 1.0, l_t)
    return (acc / safe_l).astype(q.dtype)


def ring_attention_sharded(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    axis_name: str = "cp",
    rules: Optional[Sequence[Tuple[str, object]]] = None,
):
    """Mesh-level ring attention.  PartitionSpecs come from the logical
    rules table (q: batch/seq/heads/head_dim, kv: batch/seq/kv_heads/
    head_dim) so a strategy change in the table never touches this code."""
    from jax import shard_map
    from dlrover_tpu.parallel.sharding import spec_for_logical_axes

    q_spec = spec_for_logical_axes(
        ("batch", "seq", "heads", "head_dim"), rules
    )
    kv_spec = spec_for_logical_axes(
        ("batch", "seq", "kv_heads", "head_dim"), rules
    )
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name, causal),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )
    return fn(q, k, v)


def active_mesh():
    """The mesh of the enclosing ``with mesh:`` context (how modules find
    the mesh without threading it through their signatures)."""
    try:
        from jax._src.mesh import thread_resources

        mesh = thread_resources.env.physical_mesh
        if not getattr(mesh, "empty", True) and mesh.axis_names:
            return mesh
    except Exception:  # noqa: BLE001 - internal API best-effort
        pass
    return None
