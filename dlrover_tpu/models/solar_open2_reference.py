"""Plain reference of Solar-Open2 (``model_type`` ``solar_open2``) as the
program runs it: a decoder whose layers follow a pattern, three gated
delta-rule layers (Kimi Delta Attention, arXiv:2510.26692; flash-linear-
attention's ``fla/layers/kda.py``) to one softmax GQA layer without
positions and with an output gate, every layer followed by top-k of
sigmoid-routed experts beside one shared expert.  Forward pass, every loss
term and, through ``jax.grad``, gradients, in float32 ``jax.numpy`` at
``highest`` matmul precision.  No kernel, no chunks, no sort of assignments,
no sharding, no remat: the delta rule runs token by token (``jax.lax.scan``
over positions), attention a block of queries at a time so that nothing
``[S, S]`` is whole, every held expert is looped over plainly.  The tests
hold ``ops/linear_attention.py::kda``, ``models/llama.py`` and
``models/moe.py`` to it; it shares no function with them.

``h = RMSNorm(x)``, ``x <- x + attn(h)``, ``x <- x + ffn(RMSNorm(x))``.

**A ``kda`` layer**, per head, ``d = 128`` keys and values a head, ``u`` any
of q, k, v:

1. ``u~_t = SiLU(sum_{i=0..3} c_u[i] (h W_u)_{t-3+i})``: a causal depthwise
   convolution of 4 taps a channel, zeros before the start.
2. ``q_t = q~_t / |q~_t| d^-1/2``, ``k_t = k~_t / |k~_t|`` (``|.|`` with
   fla's 1e-6 under the root), ``v_t = v~_t``.
3. ``g_t = -exp(A_log) softplus((h W_f1) W_f2 + dt_bias)`` in R^d,
   ``alpha_t = exp(g_t)``; ``beta_t = 2 sigmoid(h w_beta)``
   (``kda_allow_neg_eigval``: the 2).
4. ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
   ``S_0 = 0``; ``o_t = S_t^T q_t``.
5. ``attn(h)_t = W_o [RMSNorm_d(o_t) * sigmoid((h W_g1) W_g2)]``: the norm
   over each head with one learned scale, the gate of rank d.

**A ``gqa`` layer**: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``, no rotary
embedding, no q/k norm, ``a = softmax(q k^T d^-1/2 + causal) v``, ``attn(h) =
W_o [a * sigmoid(h W_gate)]``, the gate elementwise (arXiv:2505.06708).

**The expert block**: ``s = sigmoid(h W_r)`` over all ``experts_total``
experts in float32, the top ``num_experts_per_tok`` by ``s``, ``w_e = s_e /
sum_top s`` times ``routed_scaling_factor``; ``ffn(h) = sum_{e kept, held
here} w_e SwiGLU_e(h) + SwiGLU_shared(h)``: the held experts are
``[first_expert, first_expert + held)``, what the absent ones would add is
left out, the shared expert is whole and counted once.  The load-balancing
loss ``E sum_e f_e P_e`` with ``P_e`` the mean of ``s_e / sum_e s_e``, over
all ``E`` experts, averaged over the layers.

**Departures**: the selection bias of DeepSeek-V3's router (a buffer whose
update rule the published configuration does not give) is not built; there
is no grouped selection (the configuration has no ``n_group``).

``m`` carries the published key names (``rms_norm_eps``,
``num_experts_per_tok``, ``routed_scaling_factor``) plus ``layer_pattern``,
``experts_total``, ``first_expert``, ``router_aux_loss_coef`` and
``query_block``.  The parameter tree is the program's (unboxed): under
``layers`` one entry a run of equal layers of the period, ``<kind>_<n>``,
its leaves stacked ``[periods, run length, ...]``; heads, head size, taps and
held experts are read from it.
"""

import jax
import jax.numpy as jnp

#: a router-logit margin that bfloat16 arithmetic upstream can cross
LOW_MARGIN = 1e-2


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def short_conv(x, taps):
    """Step 1: ``x`` [B, S, H, D], ``taps`` [n, H, D]; tap ``i`` weighs
    position ``t - (n - 1) + i``."""
    n, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i: i + S] * taps[i] for i in range(n)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """Step 4, a token at a time: q, k, g [B, S, H, D], v [B, S, H, D'],
    beta [B, S, H] -> o [B, S, H, D']."""
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
    """``(attn(h), share of betas over 1, the median channel's half life in
    tokens)`` of a ``kda`` layer."""
    project = lambda name: jnp.einsum(  # noqa: E731
        "bse,ehd->bshd", h, p[name]["kernel"])
    q = short_conv(project("q_proj"), p["q_conv"])
    k = short_conv(project("k_proj"), p["k_conv"])
    v = short_conv(project("v_proj"), p["v_conv"])
    d = q.shape[-1]
    q, k = unit(q) * d ** -0.5, unit(k)
    low = lambda name: jnp.einsum(  # noqa: E731
        "bsr,rhd->bshd", h @ p[name + "_down"]["kernel"],
        p[name + "_up"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        low("f") + p["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(h @ p["beta_proj"]["kernel"])
    out = delta_rule(q, k, v, g, beta)
    out = rms_norm(out, p["o_norm"]["scale"], float(m["rms_norm_eps"]))
    out = out * jax.nn.sigmoid(low("g"))
    half_life = jnp.median(jnp.log(2.0) / -jnp.mean(g, axis=(0, 1)))
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"]),
            jnp.mean(beta > 1.0), half_life)


def gated_attention(h, p, m):
    """``attn(h)`` of a ``gqa`` layer: a block of ``query_block`` queries at
    a time against every key, the causal mask by position."""
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    gate = jax.nn.sigmoid(jnp.einsum("bse,ehd->bshd", h,
                                     p["gate_proj"]["kernel"]))
    B, S, heads, d = q.shape
    block = min(int(m["query_block"]), S)
    # query head i reads kv head i // groups: [B, S, kv heads, groups, D]
    q = q.reshape(B, S, k.shape[2], heads // k.shape[2], d)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        scores = jnp.einsum("bqngd,bknd->bqngk", rows, k) * d ** -0.5
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, :, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqngk,bknd->bqngd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads, d)
    return jnp.einsum("bshd,hde->bse", out * gate, p["o_proj"]["kernel"])


def swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def experts(h, p, m, whole=False):
    """``(ffn(h) [B, S, D], load-balancing loss of the layer, share of tokens
    with a low router margin)``: every held expert computes every token, one
    expert after the other; the shared expert once.  ``whole``: the tree
    holds every expert (the uncut layer the shares must add up to)."""
    k, total = int(m["num_experts_per_tok"]), int(m["experts_total"])
    first = 0 if whole else int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    scores = jax.nn.sigmoid(logits)
    largest = jax.lax.top_k(logits, k + 1)[0]
    kept = logits >= largest[..., k - 1: k]
    gates = jnp.where(kept, scores, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m.get("routed_scaling_factor", 1.0))
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    out = out + swiglu(h, p["shared_expert"])
    assigned = kept.astype(jnp.float32).mean(axis=(0, 1)) / k
    share = scores / scores.sum(axis=-1, keepdims=True)
    balance = total * jnp.sum(assigned * share.mean(axis=(0, 1)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, balance, low


def layers_of(params, pattern):
    """The layers' parameters in the stack's order, ``[(kind, tree)]``, from
    the program's tree (a run of equal layers stacked ``[periods, run
    length, ...]`` under ``<kind>_<run>``)."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][1] == kind:
            runs[-1][2] += 1
        else:
            runs.append([f"{kind}_{len(runs)}", kind, 1])
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    return [(kind, jax.tree.map(
        lambda t: jnp.asarray(t[period, i], jnp.float32),
        params["layers"][name]["layer"]))
        for period in range(periods)
        for name, kind, length in runs for i in range(length)]


def forward(params, input_ids, labels, m):
    """``token_losses`` [B, S], ``load_balance`` (a value a layer,
    unweighted), ``router_low_margin`` (a share a layer),
    ``beta_over_one_share`` and ``decay_half_life`` (a value a ``kda``
    layer), and ``loss``: what the program's training step minimises, the
    mean token loss plus the load-balancing loss times
    ``router_aux_loss_coef`` averaged over the layers."""
    eps = float(m["rms_norm_eps"])
    balance, router_low, over_one, half_life = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed_tokens"], jnp.float32)[input_ids]
        for kind, p in layers_of(params, m["layer_pattern"]):
            h = rms_norm(x, p["input_norm"]["scale"], eps)
            if kind == "kda":
                mixed, share, life = delta_attention(h, p["attn"], m)
                over_one.append(share)
                half_life.append(life)
            else:
                mixed = gated_attention(h, p["attn"], m)
            x = x + mixed
            out, layer_balance, low = experts(
                rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m)
            x = x + out
            balance.append(layer_balance)
            router_low.append(low)
        x = rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                    jnp.float32), eps)
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32), -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    balance = jnp.stack(balance)
    loss = token.mean() + float(m["router_aux_loss_coef"]) * balance.mean()
    return {"token_losses": token, "load_balance": balance,
            "router_low_margin": jnp.stack(router_low),
            "beta_over_one_share": jnp.stack(over_one),
            "decay_half_life": jnp.stack(half_life), "loss": loss}
