"""Plain reference of LFM2-24B-A2B's language model (``LiquidAI/LFM2-24B-
A2B``, ``model_type`` ``lfm2_moe``; the layers as ``transformers``'
``modeling_lfm2_moe.py`` / ``modeling_lfm2.py`` state them:
``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2MoeSparseMoeBlock``; the
selection bias DeepSeek-V3's, arXiv:2412.19437 section 2.1.2) as the program
runs it.  Forward pass, loss, through ``jax.grad`` gradients, and the bias's
update, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No kernel,
no scan over layers, no sort of assignments, no sharding, no remat: a Python
loop over layers, the convolution three shifted multiplies (``short_conv``)
or a literal loop over positions (``short_conv_by_position``), the softmax
dense, every held expert looped over plainly.  The tests hold
``models/llama.py``, ``models/moe.py``, ``ops/short_conv.py`` and the
trainer's step to it; it shares no function with them.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``, each with its own ``g``; no bias
anywhere.  Every layer ``l``::

    h = x + Mixer_l( N_op(x) )          operator_norm; Mixer_l by ``layer_types``
    y = h + FFN_l( N_ffn(h) )           ffn_norm; dense in the leading layers, routed after
    logits = N_f(x_L) E^T               embedding_norm, then the embedding table transposed

**conv** mixer (entry ``conv``; ``Lfm2ShortConv.slow_forward``)::

    [B | C | u] = n W_in                W_in hidden x 3 hidden, three runs of ``hidden`` columns in that order
    v = B * u                           elementwise
    c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t      causal, depthwise, ``conv_L_cache`` taps, zeros before the start, NO activation
    out = (C * c) W_out                 W_out hidden x hidden

**full_attention** mixer (entry ``gqa``; ``Lfm2Attention``)::

    q, k, v = n W_q, n W_k, n W_v       query heads in groups over the key-value heads
    q, k = N_q(q), N_k(k)               over the ``head_dim`` of a head, one learned scale each, BEFORE RoPE
    q, k = RoPE(q), RoPE(k)             the whole head, base ``rope_theta``, halves convention
    out = softmax_causal(q k^T / sqrt(head_dim)) v W_o

**Dense feed-forward** (``:dense``): ``(silu(n W_1) * (n W_3)) W_2``.
**Routed feed-forward** (``Lfm2MoeSparseMoeBlock``)::

    s = sigmoid( n W_r )                float32, over all E experts
    chosen = top_k( s + b )             b the ``expert_bias``: in the choice alone
    w_e = s_e / (sum_{chosen} s + 1e-6) norm_topk_prob; times routed_scaling_factor (1)
    out = sum_{e in chosen, held here} w_e (silu(n G_e) * (n U_e)) D_e

The held experts are ``[first_expert, first_expert + held)`` and what the
others would add is left out.  After a step ``b_e += bias_update_rate *
sign(mean(n) - n_e)``, ``n_e`` the tokens the step routed to expert ``e``
over all ``E`` columns (``bias_update``).  No balance loss, no shared expert.

Departures from the published code, each a reading the configuration file
lists under ``assumed``: ``head_dim`` = hidden / heads (the config gives
null); the head tied to the embedding table (``Lfm2MoeConfig``'s default);
the order ``[B | C | u]`` of ``W_in``'s columns and no activation on the taps
(``slow_forward``; the CUDA path's ``causal_conv1d_fn(activation=None)``);
``q_layernorm`` / ``k_layernorm`` before RoPE; the ``+ 1e-6`` under the
weights; the bias's rate and sign rule, which the config does not name.
The published code multiplies ``B * u`` and ``C * c`` in the activations'
dtype; here and in the program they are float32 on the operands handed.

``m`` carries the published key names (``num_attention_heads``,
``num_key_value_heads``, ``norm_eps``, ``rope_theta``, ``conv_L_cache``,
``num_experts_per_tok``, ``routed_scaling_factor``) plus ``layer_prefix``,
``layer_pattern`` (entries ``"conv"``, ``"gqa"``, each also ``":dense"``),
``first_expert`` and ``bias_update_rate``.  The parameter tree is the
program's (unboxed): under ``prefix`` one entry a run of equal layers, its
leaves stacked ``[run, ...]``, under ``layers`` ``[periods, run, ...]``; the
buffers' tree has the same paths down to ``mlp/selection_bias``.
"""

import jax
import jax.numpy as jnp

#: what the published code adds to the sum of the chosen scores
NORM_TOPK_EPS = 1e-6


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def conv_taps(v, weight):
    """``c_t = sum_i w_i v_{t - (taps - 1) + i}`` by shifted multiplies: v
    ``[B, S, channels]``, ``weight`` ``[taps, channels]``."""
    taps, S = weight.shape[0], v.shape[1]
    out = jnp.zeros_like(v)
    for i in range(taps):
        back = taps - 1 - i         # tap i weighs the position ``back`` before
        out = out + weight[i] * jnp.concatenate(
            [jnp.zeros_like(v[:, :back]), v[:, : S - back]], axis=1)
    return out


def conv_taps_by_position(v, weight):
    """The same a position at a time, as a decoding step would: the
    ``taps - 1`` earlier values carried, zeros at the start."""
    taps = weight.shape[0]

    def step(earlier, v_t):
        window = jnp.concatenate([earlier, v_t[:, None]], axis=1)
        return window[:, 1:], jnp.einsum("btc,tc->bc", window, weight)

    first = jnp.zeros((v.shape[0], taps - 1, v.shape[2]), v.dtype)
    _, out = jax.lax.scan(step, first, jnp.moveaxis(v, 1, 0))
    return jnp.moveaxis(out, 0, 1)


def short_conv(h, p, m, conv=conv_taps):
    """``(mixer(h), the share of the convolution's result that the earlier
    taps make)``: the second is the program's ``gconv_past_tap_share`` over
    every position."""
    wide = h.shape[-1]
    all_three = h @ p["in_proj"]["kernel"]
    B, C, u = (all_three[..., :wide], all_three[..., wide: 2 * wide],
               all_three[..., 2 * wide:])
    v, w = B * u, p["conv_weight"]
    c = conv(v, w)
    present = jnp.abs(w[-1] * v).mean()
    past = jnp.abs(c - w[-1] * v).mean()
    return (C * c) @ p["out_proj"]["kernel"], past / (past + present)


def rope(x, theta):
    """[B, S, H, D] at positions ``0..S-1``: column ``i`` turned with ``i +
    D/2`` by ``p theta^(-2i/D)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, m):
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    S, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd,hde->bqe", probs, v, p["o_proj"]["kernel"])


def swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def choose(scores, bias, m):
    """[.., E] bool: the ``num_experts_per_tok`` largest of ``scores +
    bias``."""
    c = scores + bias
    k = int(m["num_experts_per_tok"])
    return c >= jnp.sort(c, axis=-1)[..., -k][..., None]


def routed(h, p, bias, m):
    """``(ffn(h), rows each of the router's experts took [E])``."""
    first = int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen = choose(scores, bias, m)
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + NORM_TOPK_EPS)
    gates = gates * float(m.get("routed_scaling_factor", 1.0))
    out = jnp.zeros_like(h)
    for e in range(p["gate_proj"].shape[0]):
        out = out + gates[..., first + e, None] * swiglu(
            h, p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e])
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
    for name, entry, length in runs(m.get("layer_prefix", ())):
        for i in range(length):
            held = buffers.get("prefix", {}).get(name)
            out.append((entry, at(params["prefix"][name]["layer"], i),
                        held and at(held["layer"], i)))
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    for period in range(periods):
        for name, entry, length in runs(m["layer_pattern"]):
            for i in range(length):
                held = buffers.get("layers", {}).get(name)
                out.append((
                    entry, at(params["layers"][name]["layer"], period, i),
                    held and at(held["layer"], period, i)))
    return out


def forward(params, buffers, input_ids, labels, m, conv=conv_taps):
    """``token_losses`` [B, S], ``loss`` (their mean: what the program's
    step minimises, no further term), ``rows`` [routed layers, E]: the
    tokens each layer's router sent to each expert, and ``past_tap_share``
    [conv layers]."""
    eps = float(m["norm_eps"])
    rows, shares = [], []
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params["embed_tokens"], jnp.float32)
        x = table[input_ids]
        for entry, p, b in layers_of(params, buffers, m):
            kind, _, ffn = entry.partition(":")
            n = rms_norm(x, p["input_norm"]["scale"], eps)
            if kind == "conv":
                mixed, share = short_conv(n, p["attn"], m, conv)
                shares.append(share)
            else:
                mixed = attention(n, p["attn"], m)
            x = x + mixed
            n = rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn == "dense":
                out = swiglu(n, *(p["mlp"][name]["kernel"] for name in (
                    "gate_proj", "up_proj", "down_proj")))
            else:
                out, took = routed(
                    n, p["mlp"], b["mlp"]["selection_bias"], m)
                rows.append(took)
            x = x + out
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logp = jax.nn.log_softmax(x @ table.T, -1)      # the tied head
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return {"token_losses": token, "loss": token.mean(),
            "rows": jnp.stack(rows), "past_tap_share": jnp.stack(shares)}


def bias_update(bias, rows, rate):
    """``b_e + rate * sign(mean(n) - n_e)`` of one layer."""
    rows = rows.astype(jnp.float32)
    return bias + rate * jnp.sign(rows.mean() - rows)
