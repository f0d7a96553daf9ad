"""Plain reference of the OLMoE decoder (arXiv:2409.02060; Hugging Face
``modeling_olmoe.py``): forward pass, total loss and, through ``jax.grad``,
gradients, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No
kernel, no sort, no sharding, no remat: every head and every expert is
looped over plainly (the loops over layers and experts are
``jax.lax.scan``s of the plain body, so that the benchmark's copy compiles
in seconds at 64 experts).  The tests hold ``models/moe.py`` to it.

It reads the program's parameter tree (unboxed, layers stacked on the
leading axis) and the published ``config.json`` keys.  Departures from the
published code, each on purpose:

* the load-balancing loss is the paper's ``N_E * sum_i f_i P_i`` per layer,
  with ``f_i`` the share of the ``tokens x k`` assignments, averaged over
  the layers (1 at uniform routing), as the training code (OLMo with
  megablocks) computes it; ``modeling_olmoe.py`` pools the layers before
  the product and does not divide by ``k``;
* the router z-loss (the paper's, absent from ``modeling_olmoe.py``):
  ``mean(logsumexp(router logits)^2)``, averaged over the layers;
* ``modeling_olmoe.py`` computes the router logits in the model's dtype and
  the softmax in float32; here both are float32.

Routing is discontinuous: where the ``k``-th and ``k+1``-th router weights
of a token nearly tie, arithmetic of lower precision can pick the other
expert.  ``forward`` routes by its own logits and reports, layer by layer,
the share of tokens whose margin (in logits) is under ``LOW_MARGIN``.
"""

import jax
import jax.numpy as jnp

#: a router-logit margin that bfloat16 arithmetic upstream can cross
LOW_MARGIN = 1e-2


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, p, m):
    """Causal multi-head attention with q and k RMS-normalised over their
    whole projected width, one head at a time."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    flat = q.shape[:2] + (-1,)
    q = rms_norm(q.reshape(flat), p["q_norm"]["scale"], eps).reshape(q.shape)
    k = rms_norm(k.reshape(flat), p["k_norm"]["scale"], eps).reshape(k.shape)
    q, k = rope(q, theta), rope(k, theta)
    groups = q.shape[2] // k.shape[2]
    seq = h.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    out = jnp.zeros_like(h)
    for head in range(q.shape[2]):
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, head],
                            k[:, :, head // groups]) * q.shape[-1] ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        mixed = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1),
                           v[:, :, head // groups])
        out = out + mixed @ p["o_proj"]["kernel"][head]
    return out


def experts(h, p, m):
    """``(result, load-balancing term, z term, low-margin share)`` of one
    expert layer: every expert computes every token, one expert after the
    other, and a token keeps the ``k`` largest router weights as they
    are."""
    k, n = int(m["num_experts_per_tok"]), int(m["num_experts"])
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    largest = jax.lax.top_k(logits, k + 1)[0]
    chosen = logits >= largest[..., k - 1: k]
    gates = jnp.where(chosen, probs, 0.0)

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates, -1, 0)))
    token_axes = tuple(range(h.ndim - 1))
    assigned = chosen.astype(jnp.float32).mean(axis=token_axes) / k
    balance = n * jnp.sum(assigned * probs.mean(axis=token_axes))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, balance, z, low


def forward(params, input_ids, labels, m):
    """``m``: the published keys (``num_experts``, ``num_experts_per_tok``,
    ``rms_norm_eps``, ``rope_theta``).  Returns the loss of every token
    [B, S], the two router terms averaged over the layers, and each layer's
    low-margin share."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    eps = float(m["rms_norm_eps"])

    def layer(x, p):
        p = jax.tree.map(f32, p)
        x = x + attention(
            rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m)
        out, *terms = experts(
            rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m)
        return x + out, terms

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, (balance, z, low) = jax.lax.scan(
            layer, x, params["layers"]["layer"])
        x = rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    token_losses = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return {"token_losses": token_losses, "load_balance": jnp.mean(balance),
            "router_z": jnp.mean(z), "low_margin_share": low}


def total_loss(params, input_ids, labels, m, load_balance_coef=0.01,
               router_z_coef=0.001):
    out = forward(params, input_ids, labels, m)
    return (out["token_losses"].mean()
            + load_balance_coef * out["load_balance"]
            + router_z_coef * out["router_z"])
