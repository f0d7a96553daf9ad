"""Plain reference of Keye-VL-2.0's language model as the program runs it:
a Qwen3-MoE decoder (GQA, q and k RMS-normalised over each head, RoPE,
top-k of renormalised softmax router weights, no shared expert) whose
attention keeps, for each query, the ``topk`` keys a learned indexer scores
highest (``sa_config``; DeepSeek-V3.2-Exp's "lightning indexer").  Forward
pass, every loss term and, through ``jax.grad``, gradients, in float32
``jax.numpy`` at ``highest`` matmul precision.  No kernel, no sort of
assignments, no threshold search, no sharding, no remat: the selection is
``jax.lax.top_k`` on the whole row, every head and every expert is looped
over plainly, queries in blocks so that a long sequence fits.  The tests
hold ``ops/attention.py::indexed_sparse_attention`` and ``models/moe.py``
to it; it shares no function with either.

One layer, ``h = RMSNorm(x)``, positions ``0..S-1``, ``g(i)`` the kv head
of query head ``i``:

1. ``q = h W_q``, ``k = h W_k``, ``v = h W_v``; q and k RMS-normalised over
   the ``head_dim`` of each head (one learned scale for q, one for k), then
   RoPE (halves convention).
2. The indexer reads ``stop_gradient(h)``: ``qI = h W_qI`` [S, J, C],
   ``kI = LayerNorm(h W_kI)`` [S, C] (one key head), ``w = h W_w`` [S, J],
   RoPE on qI and kI, and in float32 ``I[t, s] = sum_j w[t, j] relu(qI[t, j]
   . kI[s]) C^-0.5 J^-0.5`` for ``s <= t``.
3. ``S_t``: the positions of the ``topk`` largest ``I[t, s]`` over ``s <= t``
   (all while ``t < topk``), ties to the earlier position.  No gradient.
4. ``o[t, i] = sum_{s in S_t} softmax_{s in S_t}(q[t, i] . k[s, g(i)] /
   sqrt(head_dim)) v[s, g(i)]``, then ``W_o``.
5. The indexer's loss (the sparse training stage of the DeepSeek-V3.2-Exp
   report): ``p[t, s]`` the probabilities of step 4 averaged over the heads,
   under ``stop_gradient``; ``L_I = mean_t KL(p[t, S_t] || softmax_{s in S_t}
   I[t, s])``, averaged over the layers, coefficient 1.
6. Experts: ``r = softmax(h2 W_r)`` over all ``experts_total`` in float32,
   top-k, the kept weights divided by their sum; the result is the sum over
   the kept experts **that are held here** (``[first_expert, first_expert +
   num_experts)``) of ``r_e down_e(silu(gate_e h2) * up_e h2)``: one chip's
   share of the layer, what the absent experts would add left out.  The
   load-balancing loss ``E sum_e f_e P_e`` over all ``E = experts_total``
   experts, averaged over the layers.

``m`` carries the published key names (``num_attention_heads``,
``num_experts_per_tok``, ``rms_norm_eps``, ``rope_theta``, ``sa_config``)
plus ``experts_total``, ``first_expert``, ``router_aux_loss_coef`` and
``query_block``.  The parameter tree is the program's (unboxed, layers
stacked on the leading axis); ``num_experts`` held experts is read from it.
"""

import jax
import jax.numpy as jnp

#: a router-logit margin that bfloat16 arithmetic upstream can cross
LOW_MARGIN = 1e-2
#: the same for the index scores of the ``topk``-th and the next key
INDEX_LOW_MARGIN = 1e-3


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def select(index_scores, first, topk, nearest=False):
    """``(keep [B, q, S] bool, low [B, q] bool)`` for the queries ``first,
    first + 1, ...``: steps 3's set by ``jax.lax.top_k`` on the whole row.
    ``nearest`` plants a fault for the tests and the chip's control: the
    ``topk`` nearest keys in place of the ``topk`` highest."""
    B, n, S = index_scores.shape
    t = first + jnp.arange(n)[:, None]
    causal = jnp.arange(S)[None, :] <= t
    if nearest:
        return jnp.broadcast_to(causal & (jnp.arange(S) > t - topk),
                                index_scores.shape), jnp.zeros((B, n), bool)
    if topk >= S:
        return jnp.broadcast_to(causal, index_scores.shape), jnp.zeros(
            (B, n), bool)
    masked = jnp.where(causal, index_scores, -jnp.inf)
    top, at = jax.lax.top_k(masked, topk + 1)
    rows = jnp.arange(n)[None, :, None]
    keep = jnp.zeros(index_scores.shape, bool).at[
        jnp.arange(B)[:, None, None], rows, at[..., :topk]].set(
            top[..., :topk] > -jnp.inf)
    low = (top[..., topk] > -jnp.inf) & (
        top[..., topk - 1] - top[..., topk] < INDEX_LOW_MARGIN)
    return keep, low


def attention(h, p, m, nearest=False):
    """``(o W_o [B, S, D], L_I of this layer, share of queries whose
    selection has a low margin)``; steps 1 to 5, a block of queries and one
    head at a time."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    topk = int(m["sa_config"]["topk"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    hi = jax.lax.stop_gradient(h)
    q_i = rope(jnp.einsum("bse,ejc->bsjc", hi, p["index_q_proj"]["kernel"]),
               theta)
    k_i = layer_norm(hi @ p["index_k_proj"]["kernel"],
                     p["index_k_norm"]["scale"], p["index_k_norm"]["bias"])
    k_i = rope(k_i[:, :, None], theta)[:, :, 0]
    w = hi @ p["index_w_proj"]["kernel"]
    heads, dim = q.shape[2:]
    groups = heads // k.shape[2]
    B, S = h.shape[:2]
    block = min(int(m.get("query_block", 512)), S)

    def one_block(first):
        rows = lambda t: jax.lax.dynamic_slice_in_dim(t, first, block, 1)  # noqa: E731
        dots = jnp.einsum("bqjc,bkc->bqjk", rows(q_i), k_i)
        index = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(dots), rows(w)) * (
            q_i.shape[-1] ** -0.5 * q_i.shape[-2] ** -0.5)
        keep, low = select(jax.lax.stop_gradient(index), first, topk, nearest)
        mixed = jnp.zeros((B, block, h.shape[-1]), jnp.float32)
        mean_probs = jnp.zeros(index.shape, jnp.float32)
        for head in range(heads):
            scores = jnp.einsum("bqd,bkd->bqk", rows(q)[:, :, head],
                                k[:, :, head // groups]) * dim ** -0.5
            probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            mixed = mixed + jnp.einsum(
                "bqk,bkd->bqd", probs, v[:, :, head // groups]
            ) @ p["o_proj"]["kernel"][head]
            mean_probs = mean_probs + probs / heads
        target = jax.lax.stop_gradient(mean_probs)
        log_index = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), -1)
        kl = jnp.where(keep & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - log_index), 0.0)
        return mixed, kl.sum(), low.sum()

    mixed, kl, low = jax.lax.map(one_block, jnp.arange(0, S, block))
    mixed = jnp.moveaxis(mixed, 0, 1).reshape(B, S, -1)
    return mixed, kl.sum() / (B * S), low.sum() / (B * S)


def experts(h, p, m, whole=False):
    """``(result [B, S, D], load-balancing loss of the layer, share of tokens
    with a low router margin)``: step 6, every held expert computes every
    token, one expert after the other.  ``whole``: the tree holds every
    expert (the uncut layer the shares must add up to)."""
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


def forward(params, input_ids, labels, m, nearest=False):
    """``token_losses`` [B, S], ``index_loss`` and ``load_balance`` (a value
    a layer, unweighted), ``index_low_margin`` and ``router_low_margin`` (a
    share a layer), and ``loss``: what the program's training step
    minimises, the mean token loss plus ``L_I`` (coefficient 1) and the
    load-balancing loss times ``router_aux_loss_coef``, both averaged over
    the layers."""
    eps = float(m["rms_norm_eps"])

    def layer(x, p):
        p = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), p)
        mixed, index_loss, index_low = attention(
            rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m, nearest)
        x = x + mixed
        out, balance, router_low = experts(
            rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m)
        return x + out, (index_loss, balance, index_low, router_low)

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[input_ids]
        x, (index_loss, balance, index_low, router_low) = jax.lax.scan(
            layer, x, params["layers"]["layer"])
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32), -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = token.mean() + index_loss.mean() + float(
        m["router_aux_loss_coef"]) * balance.mean()
    return {"token_losses": token, "index_loss": index_loss,
            "load_balance": balance, "index_low_margin": index_low,
            "router_low_margin": router_low, "loss": loss}
