"""Plain reference of Laguna-XS.2 (``poolside/Laguna-XS.2``, ``model_type``
``laguna``) as the program runs it: a leading full-attention layer with a
dense SwiGLU, then periods of three sliding-window layers to one
full-attention layer, the period's layers followed by sigmoid-routed experts
beside one shared expert.  Forward pass, loss and, through ``jax.grad``,
gradients, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No
kernel, no blocks of queries, no sort of assignments, no sharding, no remat,
no scan over layers: an ``[S, S]`` mask a layer, the softmax dense, every
held expert looped over plainly.  The tests hold ``models/llama.py``,
``models/moe.py`` and ``ops/attention.py`` to it; it shares no function with
them.

``h = RMSNorm(x)``, ``x <- x + attn(h)``, ``x <- x + ffn(RMSNorm(x))``; no
bias anywhere, the head untied.

**A window layer** (``sliding_attention``, the pattern's ``swa``): ``q = h
W_q`` (``num_attention_heads_per_layer`` heads of ``head_dim``), ``k, v = h
W_k, h W_v`` (``num_key_value_heads``; a key head serves ``heads / kv`` query
heads).  RoPE on all columns of every q and k head, base
``rope_parameters.sliding_attention.rope_theta``, halves convention (column
``i`` of the first half pairs with column ``i`` of the second).  Scores ``q_t
. k_s / sqrt(head_dim)`` for ``t - sliding_window < s <= t``: a query sees
itself and the ``sliding_window - 1`` positions before it; softmax; ``o_t =
sum_s p_ts v_s``.

**A full layer** (``full_attention``, ``gqa``): its own head count.  RoPE on
the FIRST ``partial_rotary_factor * head_dim`` columns of each head, the
rest unrotated, under YaRN as ``transformers``' ``_compute_yarn_parameters``
has it: over the pairs ``i`` of the ``r`` rotary columns, ``f_i =
theta^(-2i/r)``; ``dim(n) = r ln(original / (2 pi n)) / (2 ln theta)``;
``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``; ``ramp_i =
clip((i - low) / (high - low), 0, 1)``; the frequency is ``f_i (1 - ramp_i)
+ (f_i / factor) ramp_i``; cos and sin both times ``attention_factor``.
Causal, every earlier key, scores over ``sqrt(head_dim)``.

**The gate** (``gating``), both kinds: ``o_h * sigmoid(h w_g)_h``, ``w_g``
hidden x heads, on the attention's output before the output projection.

**The routed block**: ``s = sigmoid(h W_r)`` over all ``experts_total``
columns; the ``num_experts_per_tok`` largest chosen; ``w = s_chosen /
sum(s_chosen) * moe_routed_scaling_factor`` on the experts' OUTPUTS; ``ffn(h)
= SwiGLU_shared(h) + sum_{e chosen, held here} w_e SwiGLU_e(h)``: the held
experts are ``[first_expert, first_expert + held)``.  No groups, no bias, no
auxiliary loss.  **A dense layer** has a SwiGLU of ``intermediate_size`` in
place of the block.

``m`` carries the published key names plus ``layer_prefix``,
``layer_pattern`` (entries ``"gqa"``, ``"swa"``, ``"gqa:dense"``), ``heads``
(``{"gqa": .., "swa": ..}``), ``experts_total`` and ``first_expert``.  The
parameter tree is the program's (unboxed): under ``prefix`` and ``layers``
one entry a run of equal layers, its leaves stacked ``[run, ...]`` and
``[periods, run, ...]``.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def frequencies(rope, head_dim):
    """``(inverse frequencies [r / 2], factor on cos and sin, r)`` of one
    entry of ``rope_parameters``."""
    r = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    pairs = jnp.arange(r // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * pairs / r)
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0, r
    original = float(rope["original_max_position_embeddings"])

    def dim(turns):
        return r * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rope["beta_slow"]))), r - 1)
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    factor = float(rope["factor"])
    attention = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return freq * (1 - ramp) + freq / factor * ramp, float(attention), r


def rope(x, rule, head_dim):
    """[B, S, H, D] at positions ``0..S-1``: the first ``r`` columns turned,
    their first half paired with their second; the rest as they are."""
    freq, factor, r = frequencies(rule, head_dim)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = factor * jnp.cos(angles)[None, :, None, :]
    sin = factor * jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(h, p, m, kind):
    rule = m["rope_parameters"][
        "sliding_attention" if kind == "swa" else "full_attention"]
    d = int(m["head_dim"])
    q = rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]), rule, d)
    k = rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]), rule, d)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    groups = q.shape[2] // k.shape[2]       # query head i reads key head i // groups
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    S = q.shape[1]
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = ahead >= 0
    if kind == "swa":
        seen &= ahead < int(m["sliding_window"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = out * jax.nn.sigmoid(h @ p["head_gate_proj"]["kernel"])[..., None]
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def swiglu(h, p):
    gate_w, up_w, down_w = (p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def experts(h, p, m):
    """``(ffn(h), rows each of the router's experts took [E])``."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen = scores >= jnp.sort(scores, axis=-1)[..., -k][..., None]
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m["moe_routed_scaling_factor"])
    out = swiglu(h, p["shared_expert"])
    for e in range(p["gate_proj"].shape[0]):
        out = out + gates[..., first + e, None] * (
            jax.nn.silu(h @ p["gate_proj"][e]) * (h @ p["up_proj"][e])
        ) @ p["down_proj"][e]
    return out, chosen.sum(axis=tuple(range(chosen.ndim - 1)))


def layers_of(params, m):
    """``[(entry, the layer's parameters)]`` in the stack's order, float32."""
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
            out.append((entry, at(params["prefix"][name]["layer"], i)))
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    for period in range(periods):
        for name, entry, length in runs(m["layer_pattern"]):
            for i in range(length):
                out.append(
                    (entry, at(params["layers"][name]["layer"], period, i)))
    return out


def forward(params, input_ids, labels, m):
    """``logits`` [B, S, V], ``token_losses`` [B, S], ``loss`` (their mean:
    what the program's step minimises, no further term) and ``rows``
    [routed layers, E]: the tokens each layer's router sent to each
    expert."""
    eps = float(m["rms_norm_eps"])
    rows = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[input_ids]
        for entry, p in layers_of(params, m):
            kind, _, ffn = entry.partition(":")
            h = rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + attention(h, p["attn"], m, kind)
            h = rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn:
                x = x + swiglu(h, p["mlp"])
            else:
                out, n = experts(h, p["mlp"], m)
                x = x + out
                rows.append(n)
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logits = x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return {"logits": logits, "token_losses": token, "loss": token.mean(),
            "rows": jnp.stack(rows)}
