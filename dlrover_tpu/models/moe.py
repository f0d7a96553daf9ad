"""Mixture-of-experts feed-forward block for the Llama decoder: dropless
top-k routing, experts sharded over the ``ep`` mesh axis.

``MoELlamaConfig.feed_forward`` hands ``models/llama.py``'s one decoder this
block in place of the dense SwiGLU; everything else (attention, norms,
``remat``, the scanned layer stack, the head) is the dense model's code.

Routing (OLMoE, arXiv:2409.02060): ``softmax(x W_r)`` in float32 over all
experts, the ``top_k`` largest kept with their softmax weights as they are
(no renormalisation).  No token is ever dropped and every shape is static:
the ``tokens x top_k`` assignments are sorted by expert, one grouped matmul
(``jax.lax.ragged_dot``, which the TPU compiler turns into its own grouped
kernel and skips the rows no group holds) runs over the sorted rows, and
the results go back weighted.  The buffer of sorted rows is sized for the
worst case, every assignment on this chip's experts.

Expert parallelism: ``ep`` ranks hold ``num_experts / ep`` experts each and
are data ranks for everything else.  Inside a ``shard_map`` the tokens of
an ``ep`` group are all-gathered, each rank runs its own experts over the
rows routed to them, one source rank's tokens at a time, and a
reduce-scatter sums the partial results back to the rank that owns the
token.  With 8 of 64 experts a token and 16 a rank, a
token has an expert on a given rank with probability 0.91: an all-to-all
would move about the same bytes and need a bound on what a rank receives.
With ``ep == 1`` the same code runs with no collective.

Each layer sows two loss terms into the ``losses`` collection, already
weighted and divided by the number of layers (``Trainer``'s default loss
adds whatever a model sows there): the load-balancing loss
``E * sum_e f_e P_e`` (``f_e``: share of the assignments that went to expert
``e``, ``P_e``: its mean router probability; 1 at uniform routing) and the
router z-loss ``mean(logsumexp(logits)^2)``.  Into ``stats`` it sows the
largest expert's rows over the mean, counted from the groups the matmul was
given.  There is no count of dropped rows: the buffers hold the worst case,
so none can be, and ``tests/test_moe.py`` holds a forced routing to the
reference's result.
"""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.observability import trace


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig(LlamaConfig):
    """``intermediate_size`` is the width of one expert."""

    num_experts: int = 8
    top_k: int = 2
    load_balance_coef: float = 0.01
    router_z_coef: float = 0.001

    def feed_forward(self):
        return MoEMLP

    def feed_forward_params(self) -> int:
        return self.num_experts * super().feed_forward_params() + (
            self.hidden_size * self.num_experts
        )

    @classmethod
    def tiny_moe(cls, **kw) -> "MoELlamaConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=128, num_experts=4, top_k=2,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def olmoe_1b_7b(cls, **kw) -> "MoELlamaConfig":
        """allenai/OLMoE-1B-7B-0125 (``config.json``).  The two loss
        coefficients are the paper's (section 3, "auxiliary losses")."""
        defaults = dict(
            vocab_size=50304, hidden_size=2048, intermediate_size=1024,
            num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
            max_seq_len=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
            qk_norm=True, num_experts=64, top_k=8,
        )
        defaults.update(kw)
        return cls(**defaults)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take(x, perm, inv_perm, fan):
    """``x[perm // fan]``, where ``perm`` is a permutation of
    ``range(fan * len(x))`` and ``inv_perm`` its inverse: each row of ``x``
    feeds ``fan`` rows of the result.  The transpose is a gather through
    ``inv_perm`` and a sum over the ``fan`` copies, where autodiff would
    scatter-add."""
    return x[perm // fan]


def _take_fwd(x, perm, inv_perm, fan):
    return x[perm // fan], inv_perm


def _take_bwd(fan, inv_perm, g):
    copies = g[inv_perm].reshape(-1, fan, g.shape[-1])
    return copies.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_take.defvjp(_take_fwd, _take_bwd)


def local_experts(x, top_i, top_w, gate_w, up_w, down_w, first_expert):
    """What the experts ``[first_expert, first_expert + len(gate_w))`` add
    to the layer's result for tokens ``x`` [T, D] routed by ``top_i`` and
    weighted by ``top_w`` (both [T, k]): ``([T, D] float32, rows each of
    these experts processed)``."""
    tokens, k = top_i.shape
    n_local = gate_w.shape[0]
    local = top_i - first_expert
    mine = (local >= 0) & (local < n_local)
    # assignments of other ranks' experts sort behind every group
    key = jnp.where(mine, local, n_local).reshape(-1)
    order = jnp.argsort(key, stable=True)
    slots = jnp.arange(tokens * k, dtype=order.dtype)
    inverse = jnp.zeros_like(order).at[order].set(slots, unique_indices=True)
    sizes = (key[:, None] == jnp.arange(n_local)).sum(axis=0, dtype=jnp.int32)
    # the grouped matmul leaves the rows behind the last group undefined:
    # they are masked on the way in (so no gradient comes back through
    # them) and on the way out
    live = (slots < sizes.sum())[:, None]
    rows = jnp.where(live, _take(x, order, inverse, k), 0)
    grouped = functools.partial(
        jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=x.dtype
    )
    hidden = nn.silu(grouped(rows, gate_w)) * grouped(rows, up_w)
    out = jnp.where(live, grouped(hidden, down_w), 0)
    out = _take(out, inverse, order, 1).reshape(tokens, k, -1)
    weights = jnp.where(mine, top_w, 0).astype(x.dtype)
    return jnp.einsum("tkd,tk->td", out, weights,
                      preferred_element_type=jnp.float32), sizes


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts, expert-sharded over ``ep``."""

    config: MoELlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, D = x.shape
        E, k = cfg.num_experts, cfg.top_k
        with jax.named_scope("moe"):
            logits = nn.DenseGeneral(
                features=E, use_bias=False,
                # routing decisions in float32: on a TPU a float32 matmul
                # at the default precision multiplies in bfloat16
                dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", None)
                ),
                name="router",
            )(x)
            probs = jax.nn.softmax(logits, axis=-1)
            top_w, top_i = jax.lax.top_k(probs, k)

            def expert_weight(name, shape, axes):
                return self.param(
                    name,
                    nn.with_logical_partitioning(
                        nn.initializers.lecun_normal(), axes
                    ),
                    shape, cfg.param_dtype,
                ).astype(cfg.dtype)

            F = cfg.intermediate_size
            gate_w = expert_weight("gate_proj", (E, D, F),
                                   ("expert", "embed", "mlp"))
            up_w = expert_weight("up_proj", (E, D, F),
                                 ("expert", "embed", "mlp"))
            down_w = expert_weight("down_proj", (E, F, D),
                                   ("expert", "mlp", "embed"))
            mixed, rows = self._experts(
                x.astype(cfg.dtype), top_i, top_w, gate_w, up_w, down_w
            )
            assigned = rows.astype(jnp.float32) / (B * S * k)
            self.sow(
                "losses", "load_balance",
                (cfg.load_balance_coef / cfg.num_layers) * E
                * jnp.sum(assigned * probs.mean(axis=(0, 1))),
            )
            self.sow(
                "losses", "router_z",
                (cfg.router_z_coef / cfg.num_layers)
                * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
            )
            self.sow("stats", "load_max_over_mean", rows.max() / rows.mean())
        return nn.with_logical_constraint(mixed, ("batch", "seq", "embed"))

    def _experts(self, x, top_i, top_w, gate_w, up_w, down_w):
        """``(the experts' weighted results [B, S, D], rows each of the E
        experts processed over the whole batch)``."""
        from dlrover_tpu.ops.ring_attention import active_mesh

        cfg = self.config
        D, k = x.shape[-1], cfg.top_k
        mesh = active_mesh()
        sharded = (
            mesh is not None and mesh.size > 1
            # already per-shard: the enclosing shard_map owns the mesh
            # axes, and its parameters are whole on every shard
            and not jax.sharding.get_abstract_mesh().manual_axes
        )
        ep = int(mesh.shape.get("ep", 1)) if sharded else 1
        if cfg.num_experts % ep:
            raise ValueError(
                f"{cfg.num_experts} experts do not split over ep={ep}"
            )

        x_spec = w_spec = None
        others = ()
        if sharded:
            from dlrover_tpu.parallel.sharding import spec_on_mesh

            rules = list(nn.get_logical_axis_rules()) or None
            x_spec = spec_on_mesh(mesh, ("batch", "seq", None), rules)
            w_spec = spec_on_mesh(mesh, ("expert", None, None), rules)
            # the axes that split tokens beside ``ep``: each holds other
            # tokens, so the rows an expert processed add up over them
            others = tuple(
                a for axes in x_spec if axes
                for a in (axes if isinstance(axes, tuple) else (axes,))
                if a != "ep"
            )

        def per_shard(x, top_i, top_w, gate_w, up_w, down_w):
            tokens = (x.reshape(-1, D), top_i.reshape(-1, k),
                      top_w.reshape(-1, k))
            if ep == 1:
                out, rows = local_experts(*tokens, gate_w, up_w, down_w, 0)
                out = out.astype(cfg.dtype)
            else:
                first = jax.lax.axis_index("ep") * gate_w.shape[0]

                # one rank's tokens at a time: the buffer of sorted rows
                # is sized for the worst case, and only one is alive (the
                # backward pass recomputes each in its turn)
                @jax.checkpoint
                def one_rank(its_tokens):
                    out, rows = local_experts(
                        *its_tokens, gate_w, up_w, down_w, first)
                    return out.astype(cfg.dtype), rows

                out, rows = jax.lax.map(one_rank, [
                    jax.lax.all_gather(t, "ep", axis=0) for t in tokens])
                out = jax.lax.psum_scatter(out, "ep", scatter_dimension=0)
                rows = jax.lax.all_gather(
                    rows.sum(axis=0), "ep", axis=0, tiled=True)
            if others:
                rows = jax.lax.psum(rows, others)
            return out.reshape(x.shape), rows

        if sharded:
            from jax.sharding import PartitionSpec

            from dlrover_tpu.parallel.collectives import shard_map_unchecked

            per_shard = shard_map_unchecked(
                per_shard, mesh=mesh,
                in_specs=(x_spec, x_spec, x_spec, w_spec, w_spec, w_spec),
                out_specs=(x_spec, PartitionSpec()),
            )
        out, rows = per_shard(x, top_i, top_w, gate_w, up_w, down_w)
        trace.note_trace_time(
            "moe.path", impl="ragged_dot", experts=cfg.num_experts,
            top_k=k, ep=ep, tokens=x.shape[0] * x.shape[1],
            rows=x.shape[0] * x.shape[1] * k, layers=cfg.num_layers,
        )
        return out, rows
