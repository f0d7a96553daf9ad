"""Plain reference of SDAR-30B-A3B's training step as the program runs it
(``model_type`` ``sdar_moe``; SDAR, arXiv:2510.06303; the objective is
block diffusion as BD3-LMs state it, arXiv:2503.09573): a Qwen3-MoE decoder
(GQA, q and k RMS-normalised over each head, RoPE, top-k of renormalised
softmax router weights, no shared expert) run on a noisy and a clean copy
of a sequence at once under the block-diffusion mask, and the NELBO over
the masked tokens.  Forward pass, objective and, through ``jax.grad``,
gradients, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No
kernel, no sort of assignments, no sharding, no remat, and nothing of
``ops/attention.py::block_diffusion_attention``: the mask is the four lines
below as a function of (row, column) over all ``2S`` keys, applied a block
of query rows at a time so that a long sequence fits, and every held expert
is looped over plainly.  The tests hold ``models/llama.py`` (its
``block_diffusion`` path), the attention and ``models/moe.py`` to it; it
shares no function with any of them.

**The noise is data here**, as weights are: the caller draws it
(``models/llama.py::noise_blocks``) and hands over ``noisy_ids`` and the
NELBO's ``weights = m / t`` [B, S].

Rows ``r = 0..2S-1``: the first ``S`` are the noisy copy, the last ``S`` the
clean one; row ``r`` has position ``r mod S`` and block ``b(r) = (r mod S)
// L``.  The mask (query row ``r``, key row ``c``):

* noisy ``r``, noisy ``c``: allowed iff ``b(r) = b(c)`` (a block sees itself,
  both ways);
* noisy ``r``, clean ``c``: allowed iff ``b(c) < b(r)`` (every EARLIER block,
  clean);
* clean ``r``, clean ``c``: allowed iff ``b(c) <= b(r)`` (causal by block);
* clean ``r``, noisy ``c``: never.

One layer, ``h = RMSNorm(x)``, ``g(i)`` the kv head of query head ``i``:

1. ``q = h W_q``, ``k = h W_k``, ``v = h W_v``; q and k RMS-normalised over
   the ``head_dim`` of each head (one learned scale for q, one for k), then
   RoPE (halves convention) at the row's position.
2. ``o[r, i] = sum_c softmax_c(q[r, i] . k[c, g(i)] / sqrt(head_dim) + M(r,
   c)) v[c, g(i)]``, then ``W_o``.
3. Experts: ``s = softmax(h2 W_r)`` over all ``experts_total`` in float32,
   top-k, the kept weights divided by their sum; the result is the sum over
   the kept experts **that are held here** (``[first_expert, first_expert +
   held)``) of ``s_e down_e(silu(gate_e h2) * up_e h2)``.  The
   load-balancing loss ``E sum_e f_e P_e`` over all ``E`` experts and all
   ``2S`` rows, averaged over the layers.

The objective: ``L = (1 / (B S)) sum_i weights_i CE(logits of NOISY row i,
clean token i)`` (no shift; the clean half yields no logits) plus the
load-balancing loss times ``router_aux_loss_coef``.

``m`` carries the published key names (``num_experts_per_tok``,
``rms_norm_eps``, ``rope_theta``) plus ``experts_total``, ``first_expert``,
``router_aux_loss_coef``, ``block_length`` and ``query_block``.  The
parameter tree is the program's (unboxed, layers stacked on the leading
axis); the heads and the held experts are read from it.
"""

import jax
import jax.numpy as jnp

#: a router-logit margin that bfloat16 arithmetic upstream can cross
LOW_MARGIN = 1e-2


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding on [B, R, H, D] at ``positions`` [R], halves
    convention (the published ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def allowed(r, c, seq, block):
    """The mask's four lines: whether query row ``r`` may see key row ``c``
    (arrays that broadcast against each other)."""
    r_noisy, c_noisy = r < seq, c < seq
    b_r, b_c = (r % seq) // block, (c % seq) // block
    return jnp.where(
        r_noisy,
        jnp.where(c_noisy, b_r == b_c, b_c < b_r),
        ~c_noisy & (b_c <= b_r))


def attention(h, p, m):
    """Steps 1 and 2 on the ``2S`` rows ``h`` [B, 2S, E], a block of query
    rows at a time against every key, every head of it at once."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    B, rows = h.shape[:2]
    seq, L = rows // 2, int(m["block_length"])
    positions = jnp.arange(rows) % seq
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), positions, theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), positions, theta)
    heads, dim = q.shape[2:]
    n = min(int(m["query_block"]), rows)
    while rows % n:
        n -= 1
    # query head i reads kv head i // groups: [B, R, kv heads, groups, D]
    q = q.reshape(B, rows, k.shape[2], heads // k.shape[2], dim)

    def one_block(first):
        mine = jax.lax.dynamic_slice_in_dim(q, first, n, 1)
        scores = jnp.einsum("bqngd,bknd->bqngk", mine, k) * dim ** -0.5
        keep = allowed(first + jnp.arange(n)[:, None],
                       jnp.arange(rows)[None, :], seq, L)
        probs = jax.nn.softmax(
            jnp.where(keep[None, :, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqngk,bknd->bqngd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, rows, n))
    out = jnp.moveaxis(out, 0, 1).reshape(B, rows, heads, dim)
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def experts(h, p, m, whole=False):
    """``(result [B, R, E], load-balancing loss of the layer, share of rows
    with a low router margin)``: step 3, every held expert computes every
    row, one expert after the other.  ``whole``: the tree holds every expert
    (the uncut layer the shares must add up to)."""
    k, total = int(m["num_experts_per_tok"]), int(m["experts_total"])
    first = 0 if whole else int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    largest = jax.lax.top_k(logits, k + 1)[0]
    kept = logits >= largest[..., k - 1: k]
    gates = jnp.where(kept, probs, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True)
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    assigned = kept.astype(jnp.float32).mean(axis=(0, 1)) / k
    balance = total * jnp.sum(assigned * probs.mean(axis=(0, 1)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, balance, low


def forward(params, noisy_ids, clean_ids, weights, m):
    """``logits`` [B, S, V] of the noisy half, ``token_nll`` [B, S] (each
    noisy row's cross entropy against the clean token at its position),
    ``nelbo`` (the objective's first term), ``load_balance`` (a value a
    layer, unweighted), ``router_low_margin`` (a share a layer) and
    ``loss``: what the program's training step minimises."""
    eps = float(m["rms_norm_eps"])
    seq = clean_ids.shape[1]

    def layer(x, p):
        p = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), p)
        x = x + attention(rms_norm(x, p["input_norm"]["scale"], eps),
                          p["attn"], m)
        out, balance, low = experts(
            rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m)
        return x + out, (balance, low)

    with jax.default_matmul_precision("highest"):
        rows = jnp.concatenate([noisy_ids, clean_ids], axis=1)
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[rows]
        x, (balance, low) = jax.lax.scan(layer, x, params["layers"]["layer"])
        x = rms_norm(x[:, :seq], jnp.asarray(
            params["final_norm"]["scale"], jnp.float32), eps)
        logits = x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    token = -jnp.take_along_axis(logp, clean_ids[..., None], axis=-1)[..., 0]
    nelbo = jnp.mean(weights * token)
    loss = nelbo + float(m["router_aux_loss_coef"]) * balance.mean()
    return {"logits": logits, "token_nll": token, "nelbo": nelbo,
            "load_balance": balance, "router_low_margin": low, "loss": loss}
