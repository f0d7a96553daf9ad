"""Plain reference of EvaByte as the program runs it: a byte-level decoder
whose attention is EVA (arXiv:2302.04542) as EvaByte's public model code
simplifies it, with eight prediction heads and a float32 residual stream.
Forward pass, every loss term and, through ``jax.grad``, gradients, in
float32 ``jax.numpy`` at ``highest`` matmul precision.  No kernel, no scan,
no remat: the layers are looped over plainly, and the attention works a
window of queries at a time so that a long sequence fits.  The tests hold
``ops/attention.py::eva_attention`` and ``models/llama.py`` to it; it shares
no function with either.

One layer, per head (``d`` the head size, ``c`` = ``chunk_size``, ``W`` =
``window_size``; ``mu``, ``phi`` in R^d the head's learned
``adaptive_mu_k`` and ``adaptive_phi``), positions ``0..S-1``, ``x`` the
float32 residual stream:

1. ``h = RMSNorm(x) (1 + g)``; ``q = h W_q``, ``k = h W_k``, ``v = h W_v``,
   a key head a query head, RoPE (halves convention) on q and k.
2. Chunk ``j`` is positions ``c j .. c j + c - 1``; its summary is a pooled
   key ``k~_j = sum_m a_m k_m`` with ``a = softmax_m(mu . k_m)`` and a pooled
   value ``v~_j = sum_m b_m v_m`` with ``b = softmax_m(phi . k_m)``, ``m``
   over the chunk's positions, the logits unscaled.
3. Query ``t`` of window ``w = floor(t / W)`` attends to ``E_t = {m : W w
   <= m <= t}``, its own window's keys under the causal mask, and to ``C_t
   = {j : j < (W / c) w}``, the summaries of every chunk of every EARLIER
   window and none of its own, under ONE softmax: ``o_t = (sum_E exp(s q_t
   . k_m) v_m + sum_C exp(s q_t . k~_j) v~_j) / Z_t`` with ``s = d^-0.5``
   and ``Z_t`` the sum of both kinds of weight.
4. ``x = x + o W_o``; ``x = x + W_down(silu(W_gate h') * W_up h')`` with
   ``h' = RMSNorm(x)(1 + g')``.

After the last layer ``logits = RMSNorm(x)(1 + g_f) W_head`` with ``W_head``
``[hidden, num_pred_heads x vocab]``; block ``i`` at position ``t`` predicts
byte ``t + 1 + i``.  Block 0's targets are ``labels``; **a departure, the
program's alike**: blocks 1 on take theirs from ``input_ids`` shifted (the
model sees no labels), so each goes without the one target that lies in
``labels`` alone, and its mean is over the ``S - 1 - i`` positions that have
one.  The loss is the unweighted sum of the blocks' means
(arXiv:2404.19737, equation 2).

``m`` carries the published key names (``rms_norm_eps``, ``rope_theta``,
``window_size``, ``chunk_size``, ``num_pred_heads``).  The parameter tree
is the program's (unboxed, layers stacked on the leading axis).
"""

import jax
import jax.numpy as jnp


def rms_norm(x, g, eps):
    """``norm_add_unit_offset``: the learned ``g`` starts at 0."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, mu, phi, chunk):
    """Step 2: ``(k~, v~ [B, S / chunk, H, D], the largest weight of each
    pooling [2, B, S / chunk, H])``."""
    B, S, H, D = k.shape
    k = k.reshape(B, S // chunk, chunk, H, D)
    v = v.reshape(B, S // chunk, chunk, H, D)
    a = jax.nn.softmax(jnp.sum(k * mu, axis=-1), axis=2)
    b = jax.nn.softmax(jnp.sum(k * phi, axis=-1), axis=2)
    pooled_k = jnp.sum(a[..., None] * k, axis=2)
    pooled_v = jnp.sum(b[..., None] * v, axis=2)
    return pooled_k, pooled_v, jnp.stack([a.max(axis=2), b.max(axis=2)])


def attention(h, p, m):
    """``(o W_o [B, S, hidden], the mean over the queries past the first
    window of the softmax mass on summaries, the mean largest pooling
    weight)``: steps 1 to 3, a window of queries at a time, the two kinds
    of weight exponentiated against their common maximum and summed into
    one ``Z``."""
    theta, window = float(m["rope_theta"]), int(m["window_size"])
    chunk = int(m["chunk_size"])
    q = rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]), theta)
    k = rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]), theta)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    B, S, H, D = q.shape
    window = min(window, S)
    if S % window or window % chunk:
        raise ValueError(f"seq {S}, window {window}, chunk {chunk}")
    pooled_k, pooled_v, largest = summaries(
        k, v, p["adaptive_mu_k"], p["adaptive_phi"], chunk)
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    outs, mass = [], 0.0
    for w in range(S // window):
        own = slice(w * window, (w + 1) * window)
        earlier = slice(0, w * (window // chunk))
        exact = jnp.einsum("bqhd,bkhd->bhqk", q[:, own], k[:, own]) * D ** -0.5
        exact = jnp.where(causal, exact, -jnp.inf)
        pooled = jnp.einsum(
            "bqhd,bjhd->bhqj", q[:, own], pooled_k[:, earlier]) * D ** -0.5
        top = jnp.maximum(exact.max(-1), pooled.max(-1, initial=-jnp.inf))
        on_keys = jnp.exp(exact - top[..., None])
        on_summaries = jnp.exp(pooled - top[..., None])
        z = on_keys.sum(-1) + on_summaries.sum(-1)
        out = (jnp.einsum("bhqk,bkhd->bqhd", on_keys, v[:, own])
               + jnp.einsum("bhqj,bjhd->bqhd", on_summaries,
                            pooled_v[:, earlier]))
        outs.append(out / jnp.moveaxis(z, 1, 2)[..., None])
        mass = mass + jnp.sum(on_summaries.sum(-1) / z)
    out = jnp.concatenate(outs, axis=1)
    share = mass / max(B * H * (S - window), 1)
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"]), share,
            largest.mean())


def later_heads_loss(logits, input_ids):
    """The sum over blocks 1 on of the mean cross entropy of block ``i`` at
    position ``t`` on ``input_ids[t + 1 + i]`` (``logits`` [B, S, heads,
    vocab])."""
    S = input_ids.shape[1]
    total = 0.0
    for i in range(1, min(logits.shape[2], S - 1)):
        logp = jax.nn.log_softmax(logits[:, : S - 1 - i, i], axis=-1)
        total = total - jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1 + i:, None], axis=-1))
    return total


def forward(params, input_ids, labels, m):
    """``token_losses`` [B, S] (block 0 on ``labels``), ``multi_byte`` (the
    other blocks' summed means), ``summary_mass_share`` and
    ``pool_weight_max`` (a value a layer), and ``loss``: what the program's
    training step minimises, the mean token loss plus ``multi_byte``."""
    eps = float(m["rms_norm_eps"])
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    stack = params["layers"]["layer"]
    shares, weights = [], []
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            p = jax.tree.map(lambda t: f32(t[i]), stack)
            mixed, share, weight = attention(
                rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m)
            x = x + mixed
            h = rms_norm(x, p["post_attn_norm"]["scale"], eps)
            gate = h @ p["mlp"]["gate_proj"]["kernel"]
            up = h @ p["mlp"]["up_proj"]["kernel"]
            x = x + (jax.nn.silu(gate) * up) @ p["mlp"]["down_proj"]["kernel"]
            shares.append(share)
            weights.append(weight)
        x = rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
    logits = logits.reshape(logits.shape[:2] + (int(m["num_pred_heads"]), -1))
    logp = jax.nn.log_softmax(logits[:, :, 0], axis=-1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    multi_byte = later_heads_loss(logits, input_ids)
    return {"token_losses": token, "multi_byte": multi_byte,
            "summary_mass_share": jnp.stack(shares),
            "pool_weight_max": jnp.stack(weights),
            "loss": token.mean() + multi_byte}
