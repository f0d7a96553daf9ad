"""Plain reference of Kanana-2-30B-A3B's language model (``kakaocorp/
kanana-2-30b-a3b-instruct-2601``, ``model_type`` ``deepseek_v3``) as the
program runs it: latent attention (MLA, arXiv:2405.04434, without a query
bottleneck) in EVERY layer, rotary by interleaved pairs, a leading dense
layer and then sigmoid-routed experts chosen under a selection bias the load
moves (arXiv:2412.19437) beside two shared experts.  Forward pass, loss,
through ``jax.grad`` gradients, and the bias's update, in float32
``jax.numpy`` at ``highest`` matmul precision.  No kernel, no scan, no sort
of assignments, no sharding, no remat: the softmax is dense, every held
expert is looped over plainly.  The tests hold ``models/llama.py``,
``models/moe.py``, ``ops/attention.py::latent_attention`` and the trainer's
step to it; it shares no function with them.

``h = RMSNorm(x)``, ``x <- x + MLA(h)``, ``x <- x + FFN(RMSNorm(x))``;
the final norm, an untied head, the loss of every token.

**MLA** (every layer; ``q_lora_rank`` null): ``q = h W_q``, a head ``[q_nope
(128) | q_pe (64)]``; ``[c (512) | k_pe (64)] = h W_kva``; ``c <-
RMSNorm(c)``; ``[k_nope (128) | v (128)] = c W_kvb`` a head; RoPE on ``q_pe``
a head and on the ONE ``k_pe`` every head shares, **by neighbouring pairs**
(``rope_interleave`` true): with ``f_i = rope_theta^(-2i/64)`` and ``p`` the
position, ``(x[2i], x[2i+1]) -> (x[2i] cos(p f_i) - x[2i+1] sin(p f_i),
x[2i+1] cos(p f_i) + x[2i] sin(p f_i))``, each result left where its operand
stood; ``s = (q_nope k_nope^T + q_pe k_pe^T) / sqrt(128 + 64)``, causal
softmax, ``o = softmax(s) v``; ``MLA(h) = W_o o``.  No gate.

**The routed block** (layers 1 on): ``s = sigmoid(h W_r)`` over all
``experts_total`` experts, in float32; the choice is the
``num_experts_per_tok`` largest of ``s + b`` (``topk_method`` ``noaux_tc``
with ``n_group`` 1 and ``topk_group`` 1: the one group is always kept, the
choice by groups is the plain top-k); ``w_e = s_e / (sum_chosen s + 1e-20) *
routed_scaling_factor`` (the bias is in the choice, never in ``w``);
``FFN(h) = sum_{e chosen, held here} w_e SwiGLU_e(h) + SwiGLU_shared(h)``,
the shared SwiGLU of width ``n_shared_experts * moe_intermediate_size``
(two shared experts are one SwiGLU of 1536: their gate and up columns side
by side, their down rows stacked); the held experts are ``[first_expert,
first_expert + held)`` and what the others would add is left out.  After a
step ``b_e += bias_update_rate * sign(mean(n) - n_e)``, ``n_e`` the tokens
the step routed to expert ``e`` (``bias_update``).  No balance loss.

**Layer 0** has a SwiGLU of ``intermediate_size`` in place of the block.

Departures from the published description: none in the mathematics.  The
update of ``b`` is DeepSeek-V3's (the config names no rate: ``assumed``);
Hugging Face's ``apply_rotary_pos_emb_interleave`` moves the rotated pairs
to the halves' layout, which changes no score (both operands are permuted
alike); here they stay in place.

``m`` carries the published key names plus ``layer_prefix``,
``layer_pattern`` (entries ``"mla"``, ``"mla:dense"``), ``experts_total``,
``first_expert`` and ``bias_update_rate``.  The parameter tree is the
program's (unboxed): under ``prefix`` and ``layers`` one entry a run of
equal layers, its leaves stacked ``[run, ...]`` and ``[periods, run, ...]``;
the buffers' tree has the same paths down to ``mlp/selection_bias``.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope_pairs(x, theta):
    """[B, S, H, D] at positions ``0..S-1``: column ``2i`` turned with
    column ``2i + 1`` by the angle ``p theta^(-2i/D)``, in place."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(h, p, m):
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    nope, rank = int(m["qk_nope_head_dim"]), int(m["kv_lora_rank"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    down = h @ p["kv_a_proj"]["kernel"]
    latent = rms_norm(down[..., :rank], p["kv_a_norm"]["scale"], eps)
    up = jnp.einsum("bsr,rhd->bshd", latent, p["kv_b_proj"]["kernel"])
    q_pe = rope_pairs(q[..., nope:], theta)
    k_pe = rope_pairs(down[..., None, rank:], theta)[:, :, 0]
    S = q.shape[1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], up[..., :nope])
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe)) * (
                  q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, up[..., nope:])
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def swiglu(h, p):
    gate_w, up_w, down_w = (p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def choose(scores, bias, m):
    """[.., E] bool: the ``num_experts_per_tok`` largest of ``scores +
    bias``."""
    c = scores + bias
    k = int(m["num_experts_per_tok"])
    return c >= jnp.sort(c, axis=-1)[..., -k][..., None]


def experts(h, p, bias, m):
    """``(ffn(h), rows each of the router's experts took [E])``."""
    first = int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen = choose(scores, bias, m)
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20) * float(
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
            h = rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + latent_attention(h, p["attn"], m)
            h = rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if entry.endswith(":dense"):
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


# --------------------------------------------------------------------------
# a ``deepseek_v3``-layout checkpoint's names -> the program's tree
# (docs/migration.md has the table; ``from_checkpoint_names`` is the table
# as code, checked at the tiny size in ``tests/test_kanana2.py``)
# --------------------------------------------------------------------------

def from_checkpoint_names(named, like_params, like_buffers, m):
    """``(params, buffers)`` in the program's tree (the shapes of
    ``like_*``) from ``{checkpoint name: array}``: the name map of
    docs/migration.md.  The rotary columns of ``q_proj`` and
    ``kv_a_proj_with_mqa`` are used AS STORED: the program turns pairs
    (``mla_rope_interleave``)."""
    heads = int(m["num_attention_heads"])

    def layer(i, entry, like_p):
        at = f"model.layers.{i}."
        get = lambda name: jnp.asarray(named[at + name])  # noqa: E731
        hidden = like_p["attn"]["q_proj"]["kernel"].shape[0]
        rank = like_p["attn"]["kv_b_proj"]["kernel"].shape[0]
        attn = {
            "q_proj": {"kernel": get("self_attn.q_proj.weight").T.reshape(
                hidden, heads, -1)},
            "kv_a_proj": {"kernel": get(
                "self_attn.kv_a_proj_with_mqa.weight").T},
            "kv_a_norm": {"scale": get("self_attn.kv_a_layernorm.weight")},
            "kv_b_proj": {"kernel": get("self_attn.kv_b_proj.weight")
                          .T.reshape(rank, heads, -1)},
            "o_proj": {"kernel": get("self_attn.o_proj.weight").T.reshape(
                heads, -1, hidden)}}
        swiglu_of = lambda at_: {  # noqa: E731
            name: {"kernel": get(f"{at_}.{name}.weight").T}
            for name in ("gate_proj", "up_proj", "down_proj")}
        if entry.endswith(":dense"):
            mlp, bias = swiglu_of("mlp"), None
        else:
            first = int(m["first_expert"])
            held = like_p["mlp"]["gate_proj"].shape[0]
            mlp = {"router": {"kernel": get("mlp.gate.weight").T},
                   "shared_expert": swiglu_of("mlp.shared_experts"),
                   **{name: jnp.stack([
                       get(f"mlp.experts.{first + e}.{name}.weight").T
                       for e in range(held)])
                      for name in ("gate_proj", "up_proj", "down_proj")}}
            bias = {"mlp": {"selection_bias": get(
                "mlp.gate.e_score_correction_bias")}}
        return {"input_norm": {"scale": get("input_layernorm.weight")},
                "post_attn_norm": {"scale": get(
                    "post_attention_layernorm.weight")},
                "attn": attn, "mlp": mlp}, bias

    like = layers_of(like_params, like_buffers, m)
    made = [layer(i, entry, p) for i, (entry, p, _) in enumerate(like)]
    n_prefix = len(m["layer_prefix"])
    # one kind all the way down: one run in the prefix ``[run, ...]``, and
    # a period of ONE layer ``[periods, 1, ...]``
    (prefix_name,) = like_params["prefix"]
    (run_name,) = like_params["layers"]

    def stacked(trees, period_axis=False):
        return jax.tree.map(
            lambda *t: jnp.stack(t)[:, None] if period_axis else jnp.stack(t),
            *trees)

    params = {
        "embed_tokens": jnp.asarray(named["model.embed_tokens.weight"]),
        "final_norm": {"scale": jnp.asarray(named["model.norm.weight"])},
        "lm_head": {"kernel": jnp.asarray(named["lm_head.weight"]).T},
        "prefix": {prefix_name: {"layer": stacked(
            [p for p, _ in made[:n_prefix]])}},
        "layers": {run_name: {"layer": stacked(
            [p for p, _ in made[n_prefix:]], True)}}}
    buffers = {"layers": {run_name: {"layer": stacked(
        [b for _, b in made[n_prefix:]], True)}}}
    return params, buffers
