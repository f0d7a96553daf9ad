"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B's language model
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``model_type``
``nemotron_h``; Nemotron-H arXiv:2504.03624, Mamba-2 arXiv:2405.21060, the
sigmoid router with its correction bias DeepSeek-V3's, arXiv:2412.19437) as
the program runs it: a stack of layers that are EACH a mixer or a
feed-forward alone.  Forward pass, loss, through ``jax.grad`` gradients, and
the bias's update, in float32 ``jax.numpy`` at ``highest`` matmul precision.
No kernel, no chunk, no sort of assignments, no sharding, no remat: the scan
walks a token at a time, the softmax is dense, every held expert is looped
over plainly.  The tests hold ``models/llama.py``, ``models/moe.py``,
``ops/ssd.py`` and the trainer's step to it; it shares no function with them.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``, each with its own ``g``.  A layer
``l`` has ONE branch (``modeling_nemotron_h.py::NemotronHBlock``)::

    x <- x + Mixer_l( N_l(x) )        Mixer_l one of Mamba2, Attn, LatentMoE by the pattern
    logits = N_f(x_L) W_head          untied; plain next-token cross entropy

**Mamba2** (entry ``mamba2:alone``; ``H`` heads of ``P``, ``G`` groups, state
``n``, inner ``H P``, conv width ``H P + 2 G n``; no bias but the
convolution's)::

    [z | u | dt] = h W_in                       W_in hidden x (H P + (H P + 2 G n) + H)
    u = silu( conv4(u) + b_c )                  causal, depthwise, 4 taps, over x, B, C together
    [x | B | C] = u                             x [S, H, P];  B, C [S, G, n]: head h reads group h // (H / G)
    d_t = softplus(dt_t + dt_bias)              [S, H]
    a_t = d_t * A,   A = -exp(A_log)            one number a head: a_t <= 0
    S_t = exp(a_t) S_{t-1} + d_t x_t B_t^T      S in R^{P x n} a head, S_0 = 0
    y_t = S_t C_t + D x_t                       D one number a head
    y = GroupRMSNorm( y * silu(z) )             gate first, then RMS over each group's H P / G channels, one learned scale of H P
    out = y W_out                               H P x hidden

**Attn** (entry ``gqa:alone``): ``q, k, v = h W_q, h W_k, h W_v`` (query
heads in groups over the key-value heads, no bias), **no positional
signal**, causal softmax at ``head_dim^-1/2``, ``o W_o``.

**LatentMoE** (entry ``ffn``; ``modeling_nemotron_h.py::NemotronHMOE`` with a
latent)::

    s = sigmoid( h W_r )                        W_r hidden x E, on the FULL h
    chosen = top_k( s + b )                     b the correction bias: in the choice alone; n_group 1 is no group
    w_e = factor * s_e / sum_{chosen} s         norm_topk_prob, then routed_scaling_factor
    c = h W_down                                hidden -> latent
    r = sum_{e in chosen, held here} w_e relu(c W1_e)^2 W2_e      W1_e latent x I, W2_e I x latent
    out = r W_up  +  relu(h V1)^2 V2            W_up latent -> hidden; the shared expert on the FULL h

The held experts are ``[first_expert, first_expert + held)`` and what the
others would add is left out.  After a step ``b_e += bias_update_rate *
sign(mean(n) - n_e)``, ``n_e`` the tokens the step routed to expert ``e``
over all ``E`` columns (``bias_update``).  No balance loss.

Departures from the published description: none in the mathematics that is
here.  The multi-token-prediction module (``num_nextn_predict_layers`` 1)
is not here and nothing stands in for it.  The readings the configuration
file lists under ``assumed`` (no rotary embedding in the attention layers,
the gate before the group norm, no bias on the latent projections and the
shared expert, the bias's rate and sign rule, ``dt`` unclamped) are the
ones above.

``m`` carries the published key names (``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``layer_norm_epsilon``)
plus ``layer_pattern``, ``layer_suffix`` (entries ``"ffn"``,
``"mamba2:alone"``, ``"gqa:alone"``), ``first_expert`` and
``bias_update_rate``.  The parameter tree is the program's (unboxed): under
``layers`` one entry a run of equal layers, its leaves stacked ``[periods,
run, ...]``, under ``suffix`` ``[run, ...]``; the buffers' tree has the same
paths down to ``mlp/selection_bias``.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def relu2(t):
    return jnp.square(jax.nn.relu(t))


def mamba2(h, p, m):
    H, P, G, n = (int(m[key]) for key in (
        "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    inner, taps = H * P, int(m["conv_kernel"])
    B, S, _ = h.shape
    both = h @ p["in_proj"]["kernel"]
    z, u, dt = (both[..., :inner], both[..., inner: 2 * inner + 2 * G * n],
                both[..., 2 * inner + 2 * G * n:])
    lead = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_bias"] + sum(
        lead[:, i: i + S] * p["conv_weight"][i] for i in range(taps)))
    x = u[..., :inner].reshape(B, S, H, P)
    # a head reads its group's B and C
    Bm, Cm = (jnp.repeat(t.reshape(B, S, G, n), H // G, axis=2) for t in (
        u[..., inner: inner + G * n], u[..., inner + G * n:]))
    d = jax.nn.softplus(dt + p["dt_bias"])
    a = d * -jnp.exp(p["A_log"])

    def step(state, at):
        x_t, d_t, a_t, b_t, c_t = at
        state = jnp.exp(a_t)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", d_t[..., None] * x_t, b_t)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, n), jnp.float32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, d, a, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
    gated = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(
        B, S, G, inner // G)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        + float(m["layer_norm_epsilon"]))
    out = normed.reshape(B, S, inner) * p["norm_scale"]
    return out @ p["out_proj"]["kernel"], jnp.median(jnp.exp(a))


def attention(h, p, m):
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    S, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd,hde->bqe", probs, v, p["o_proj"]["kernel"])


def choose(scores, bias, m):
    """[.., E] bool: the ``num_experts_per_tok`` largest of ``scores +
    bias``."""
    c = scores + bias
    k = int(m["num_experts_per_tok"])
    return c >= jnp.sort(c, axis=-1)[..., -k][..., None]


def latent_moe(h, p, bias, m):
    """``(ffn(h), rows each of the router's experts took [E])``."""
    first = int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen = choose(scores, bias, m)
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m["routed_scaling_factor"])
    c = h @ p["latent_down"]["kernel"]
    r = jnp.zeros_like(c)
    for e in range(p["up_proj"].shape[0]):
        r = r + gates[..., first + e, None] * (
            relu2(c @ p["up_proj"][e]) @ p["down_proj"][e])
    shared = p["shared_expert"]
    out = r @ p["latent_up"]["kernel"] + relu2(
        h @ shared["up_proj"]["kernel"]) @ shared["down_proj"]["kernel"]
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
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    for period in range(periods):
        for name, entry, length in runs(m["layer_pattern"]):
            for i in range(length):
                held = buffers.get("layers", {}).get(name)
                out.append((
                    entry, at(params["layers"][name]["layer"], period, i),
                    held and at(held["layer"], period, i)))
    for name, entry, length in runs(m.get("layer_suffix", ())):
        for i in range(length):
            held = buffers.get("suffix", {}).get(name)
            out.append((entry, at(params["suffix"][name]["layer"], i),
                        held and at(held["layer"], i)))
    return out


def forward(params, buffers, input_ids, labels, m):
    """``token_losses`` [B, S], ``loss`` (their mean: what the program's
    step minimises, no further term), ``rows`` [routed layers, E]: the
    tokens each layer's router sent to each expert, and ``decay_p50``
    [Mamba-2 layers]: the median of ``exp(a_t)`` over heads and
    positions."""
    eps = float(m["layer_norm_epsilon"])
    rows, decay = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[input_ids]
        for entry, p, b in layers_of(params, buffers, m):
            h = rms_norm(x, p["input_norm"]["scale"], eps)
            if entry == "ffn":
                out, n = latent_moe(
                    h, p["mlp"], b["mlp"]["selection_bias"], m)
                rows.append(n)
            elif entry == "mamba2:alone":
                out, median = mamba2(h, p["attn"], m)
                decay.append(median)
            else:
                out = attention(h, p["attn"], m)
            x = x + out
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32), -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return {"token_losses": token, "loss": token.mean(),
            "rows": jnp.stack(rows), "decay_p50": jnp.stack(decay)}


def bias_update(bias, rows, rate):
    """``b_e + rate * sign(mean(n) - n_e)`` of one layer."""
    rows = rows.astype(jnp.float32)
    return bias + rate * jnp.sign(rows.mean() - rows)
