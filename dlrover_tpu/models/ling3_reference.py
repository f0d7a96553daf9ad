"""Plain reference of Ling-3.0-flash's language model (``inclusionAI/
Ling-3.0-flash-VL``, the text decoder) as the program runs it: leading dense
layers, then periods of gated delta-rule layers (Kimi Delta Attention,
arXiv:2510.26692) to one latent-attention layer (MLA, arXiv:2405.04434),
the period's layers followed by sigmoid-routed experts chosen by groups
under a selection bias the load moves (arXiv:2412.19437) beside one shared
expert.  Forward pass, loss, through ``jax.grad`` gradients, and the bias's
update, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No
kernel, no chunks, no sort of assignments, no sharding, no remat: the delta
rule runs token by token, the softmax is dense, every held expert is looped
over plainly.  The tests hold ``models/llama.py``, ``models/moe.py``,
``ops/attention.py::latent_attention`` and the trainer's step to it; it
shares no function with them.

``h = RMSNorm(x)``, ``x <- x + attn(h)``, ``x <- x + ffn(RMSNorm(x))``.

**A ``kda`` layer**, per head, ``d`` keys and values a head, ``u`` any of
q, k, v:

1. ``u~_t = SiLU(sum_{i=0..3} c_u[i] (h W_u)_{t-3+i})``: a causal depthwise
   convolution of 4 taps a channel, zeros before the start.
2. ``q_t = q~_t / |q~_t| d^-1/2``, ``k_t = k~_t / |k~_t|`` (1e-6 under the
   root), ``v_t = v~_t``.
3. ``g_t = lb sigmoid(exp(A_log) (h W_f + dt_bias))`` in R^d, ``lb`` =
   ``kda_lower_bound`` (-5): in ``(lb, 0)``; ``W_f`` full rank, hidden ->
   heads x d.  ``beta_t = sigmoid(h w_beta)`` (no factor 2).
4. ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
   ``S_0 = 0``; ``o_t = S_t^T q_t``.
5. ``attn(h)_t = W_o [RMSNorm_d(o_t) * sigmoid(h W_g)]``: the norm over each
   head with one learned scale, ``W_g`` full rank.  No positions.

**An ``mla`` layer**: ``q = h W_q``, a head ``[q_nope | q_pe]``; ``[c | k_pe]
= h W_kva``; ``c <- RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` a head; RoPE
(``rope_theta``, halves convention) on ``q_pe`` a head and on the ONE
``k_pe``; ``s = (q_nope k_nope^T + q_pe k_pe^T) / sqrt(nope + rope)``,
causal softmax, ``o = softmax(s) v``; ``attn(h) = W_o [o_h * sigmoid(h
w_gate)_h]``, one gate a head.

**The routed block**: ``s = sigmoid(h W_r)`` over all ``experts_total``
experts; ``c = s + b``; the experts in ``n_group`` runs of consecutive
columns, a group's score the sum of its two largest ``c``, the
``topk_group`` best groups kept, the ``num_experts_per_tok`` largest ``c``
inside them chosen; ``w_e = s_e / sum_chosen s * routed_scaling_factor``
(the bias is in the choice, never in ``w``); ``ffn(h) = sum_{e chosen, held
here} w_e SwiGLU_e(h) + SwiGLU_shared(h)``: the held experts are
``[first_expert, first_expert + held)``.  After a step, ``b_e +=
bias_update_rate * sign(mean(n) - n_e)``, ``n_e`` the tokens the step
routed to expert ``e`` (``bias_update``).  No balance loss.

**A dense layer** has a SwiGLU of the dense width in place of the block.

``m`` carries the published key names plus ``layer_prefix``,
``layer_pattern`` (entries ``"kda"``, ``"mla"``, ``"kda:dense"``),
``experts_total``, ``first_expert`` and ``bias_update_rate``.  The parameter
tree is the program's (unboxed): under ``prefix`` and ``layers`` one entry
a run of equal layers, its leaves stacked ``[run, ...]`` and ``[periods,
run, ...]``; the buffers' tree has the same paths down to
``mlp/selection_bias``.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """[B, S, H, D] at positions ``0..S-1``: the first half of the columns
    paired with the second."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(x, taps):
    n, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i: i + S] * taps[i] for i in range(n)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """Step 4, a token at a time."""
    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        decayed = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)
        state = decayed + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    B, _, H, D = q.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((B, H, D, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def delta_attention(h, p, m):
    project = lambda name: jnp.einsum(  # noqa: E731
        "bse,ehd->bshd", h, p[name]["kernel"])
    q = short_conv(project("q_proj"), p["q_conv"])
    k = short_conv(project("k_proj"), p["k_conv"])
    v = short_conv(project("v_proj"), p["v_conv"])
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = float(m["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (project("f_proj") + p["dt_bias"]))
    beta = jax.nn.sigmoid(h @ p["beta_proj"]["kernel"])
    out = delta_rule(q, k, v, g, beta)
    out = rms_norm(out, p["o_norm"]["scale"], float(m["rms_norm_eps"]))
    out = out * jax.nn.sigmoid(project("g_proj"))
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def latent_attention(h, p, m):
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    nope, rank = int(m["qk_nope_head_dim"]), int(m["kv_lora_rank"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    down = h @ p["kv_a_proj"]["kernel"]
    latent = rms_norm(down[..., :rank], p["kv_a_norm"]["scale"], eps)
    up = jnp.einsum("bsr,rhd->bshd", latent, p["kv_b_proj"]["kernel"])
    q_pe = rope(q[..., nope:], theta)
    k_pe = rope(down[..., None, rank:], theta)[:, :, 0]
    S = q.shape[1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], up[..., :nope])
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe)) * (
                  q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, up[..., nope:])
    out = out * jax.nn.sigmoid(h @ p["gate_proj"]["kernel"])[..., None]
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def swiglu(h, p):
    gate_w, up_w, down_w = (p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def choose(scores, bias, m):
    """``(chosen [.., E] bool, kept groups [.., n_group] bool)``."""
    k, n_group = int(m["num_experts_per_tok"]), int(m["n_group"])
    c = scores + bias
    grouped = c.reshape(*c.shape[:-1], n_group, -1)
    best_two = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
    kept = best_two >= jnp.sort(best_two, axis=-1)[
        ..., -int(m["topk_group"])][..., None]
    inside = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(c.shape)
    return inside >= jnp.sort(inside, axis=-1)[..., -k][..., None], kept


def experts(h, p, bias, m):
    """``(ffn(h), rows each of the router's experts took [E])``."""
    first = int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen, _ = choose(scores, bias, m)
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m["routed_scaling_factor"])
    out = swiglu(h, p["shared_expert"])
    for e in range(p["gate_proj"].shape[0]):
        out = out + gates[..., first + e, None] * (
            jax.nn.silu(h @ p["gate_proj"][e]) * (h @ p["up_proj"][e])
        ) @ p["down_proj"][e]
    return out, chosen.sum(axis=tuple(range(chosen.ndim - 1)))


def layers_of(params, buffers, m):
    """``[(entry, the layer's parameters, its buffers or None)]`` in the
    stack's order, float32."""
    def runs(entries):
        out = []
        for entry in entries:
            if out and out[-1][1] == entry:
                out[-1][2] += 1
            else:
                out.append([f"{entry.replace(':', '_')}_{len(out)}", entry, 1])
        return out

    def at(tree, *index):
        return jax.tree.map(
            lambda t: jnp.asarray(t, jnp.float32)[index], tree)

    out = []
    for name, entry, length in runs(m["layer_prefix"]):
        for i in range(length):
            out.append((entry, at(params["prefix"][name]["layer"], i), None))
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    for period in range(periods):
        for name, entry, length in runs(m["layer_pattern"]):
            for i in range(length):
                held = buffers["layers"].get(name)
                out.append((
                    entry, at(params["layers"][name]["layer"], period, i),
                    held and at(held["layer"], period, i)))
    return out


def forward(params, buffers, input_ids, labels, m):
    """``token_losses`` [B, S], ``loss`` (their mean: what the program's
    step minimises, no further term) and ``rows`` [routed layers, E]: the
    tokens each layer's router sent to each expert."""
    eps = float(m["rms_norm_eps"])
    rows = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[input_ids]
        for entry, p, b in layers_of(params, buffers, m):
            kind, _, ffn = entry.partition(":")
            h = rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + (delta_attention if kind == "kda" else latent_attention)(
                h, p["attn"], m)
            h = rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn:
                x = x + swiglu(h, p["mlp"])
            else:
                out, n = experts(h, p["mlp"], b["mlp"]["selection_bias"], m)
                x = x + out
                rows.append(n)
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32), -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return {"token_losses": token, "loss": token.mean(),
            "rows": jnp.stack(rows)}


def bias_update(bias, rows, rate):
    """``b_e + rate * sign(mean(n) - n_e)`` of one layer."""
    rows = rows.astype(jnp.float32)
    return bias + rate * jnp.sign(rows.mean() - rows)
