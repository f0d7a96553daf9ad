"""Plain reference of Ouro's training step as the program runs it
(``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): a stack of softmax layers with sandwich norms,
run ``total_ut_steps`` times over the SAME weights with the final norm
inside the loop, a head and an exit gate read after every loop step, and
the expected loss over the exit distribution less an entropy term.  Forward
pass, every exit's token losses, the exit distribution, the objective and,
through ``jax.grad`` of these plain functions, the gradients, in float32
``jax.numpy`` at ``highest`` matmul precision: Python loops over loop steps
and layers, no scan over either, no remat, no kernel, attention a block of
queries at a time and the head a block of rows at a time so that 16,384
rows fit.  The tests hold ``models/llama.py`` (``loop_steps``,
``sandwich_norm``, ``exit_gate``) to it; it shares no function with it.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``, each with its own ``g``.  One
layer ``l``, as the published model code (``modeling_ouro.py::
OuroDecoderLayer``) has it:

    x <- x + N2_l( Attn_l( N1_l(x) ) )       N1 input_layernorm, N2 input_layernorm_2
    x <- x + N4_l( SwiGLU_l( N3_l(x) ) )     N3 post_attention_layernorm, N4 post_attention_layernorm_2

``Attn``: q, k, v, o projections without bias, ``num_attention_heads``
heads of ``head_dim`` (``num_key_value_heads`` key heads, each read by a
group of query heads), RoPE (halves convention, base ``rope_theta``) on q
and k, causal softmax at scale ``head_dim^-1/2``, no window.  ``SwiGLU(h) =
(silu(h W_g) * (h W_u)) W_d``.  The loop, ``T = total_ut_steps``, ``L`` layers:

    h_0 = E[ids]
    h_t = N_f( Layers_{1..L}( h_{t-1} ) )    t = 1..T, the SAME weights every t;
                                             the final norm INSIDE the loop
    z_t = h_t W_head                         logits of exit t
    lam_t = sigmoid( h_t w_g + b_g )         the exit gate, one scalar a token
    p_t = lam_t * prod_{j<t} (1 - lam_j)  (t < T);   p_T = prod_{j<T} (1 - lam_j)

The model's RESULT is ``z_T`` (at ``early_exit_threshold`` 1 the published
forward never leaves early).  The training objective (the paper's
entropy-regularised first stage; a uniform prior over the exits, so the KL
term is ``ln T - H``), a token ``i`` with target ``y_i`` and weight ``w_i``:

    CE_t,i = -log softmax(z_t,i)[y_i]
    loss = sum_i w_i [ sum_t p_t,i CE_t,i  -  beta * H(p_.,i) ] / sum_i w_i
    H(p) = -sum_t p_t log p_t

Departures from the published code, each on purpose:

* the published forward returns ``z_T`` (and, generating, leaves at the
  first exit whose cumulated mass passes the threshold); the objective over
  all exits is the paper's training recipe, not a function of
  ``modeling_ouro.py``.  ``beta`` and the uniform prior are the paper's
  first stage as remembered (the configuration file's ``assumed``).  Not
  here: the second stage, which trains the gate alone on a frozen model,
  and generation with early exit;
* targets and weights are the CALLER'S.  The program's model sees no labels
  and takes ``y_i = ids[i + 1]``, ``w_i = 1`` but for a sequence's last
  position, which has no target and weight 0: the tests hand over the same;
* the published attention multiplies all queries at once and keeps a cache
  entry a loop step and layer (index ``t * L + l``); here a block of
  ``query_block`` queries against every key, no cache: the same numbers;
* the parameter tree is the program's (unboxed; a layer's arrays stacked on
  the leading axis under ``layers/layer``; ``attn_out_norm`` is the
  published ``input_layernorm_2``, ``mlp_out_norm``
  ``post_attention_layernorm_2``, ``exit_gate`` ``early_exit_gate``:
  ``docs/migration.md``).

``m`` carries the published key names (``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``rms_norm_eps``, ``rope_theta``, ``total_ut_steps``) plus
``exit_entropy_weight`` (``beta``), ``query_block`` and ``head_rows`` (the
rows a block of the attention and of the head).
"""

import jax
import jax.numpy as jnp

#: what ``forward`` and ``objective`` can plant, each a wrong reading of the
#: equations above that a comparison has to catch.  ``three_loop_steps``:
#: ``T - 1`` loop steps for ``T``; ``final_norm_outside``: the loop carries
#: the stream unnormed and the final norm stands before the exits alone;
#: ``no_mlp_out_norm``: ``N4`` left out; ``last_exit_gated``: ``p_T = lam_T
#: prod_{j<T} (1 - lam_j)`` in place of the mass that is left;
#: ``entropy_sign``: ``+ beta H``
FAULTS = ("three_loop_steps", "final_norm_outside", "no_mlp_out_norm",
          "last_exit_gated", "entropy_sign")


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotary embedding on [B, S, H, D] at positions ``0..S-1``, halves
    convention (the published ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(total, block):
    """The largest size of at most ``block`` that divides ``total``."""
    n = min(int(block), total)
    while total % n:
        n -= 1
    return n


def attention(h, p, m):
    """Causal softmax attention of ``h`` [B, S, E]: a block of query rows at
    a time against every key, every head of it at once."""
    theta = float(m["rope_theta"])
    B, S = h.shape[:2]
    q = rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]), theta)
    k = rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]), theta)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    heads, dim = q.shape[2:]
    n = _blocks(S, m["query_block"])
    # query head i reads key head i // groups: [B, S, key heads, groups, D]
    q = q.reshape(B, S, k.shape[2], heads // k.shape[2], dim)
    o_proj = p["o_proj"]["kernel"].reshape(q.shape[2:] + (-1,))

    def one_block(first):
        mine = jax.lax.dynamic_slice_in_dim(q, first, n, 1)
        scores = jnp.einsum("bqngd,bknd->bqngk", mine, k) * dim ** -0.5
        seen = (jnp.arange(S)[None, :] <= first + jnp.arange(n)[:, None])
        probs = jax.nn.softmax(jnp.where(
            seen[None, :, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqngd,ngde->bqe", jnp.einsum(
            "bqngk,bknd->bqngd", probs, v), o_proj)

    mixed = jax.lax.map(one_block, jnp.arange(0, S, n))
    return jnp.moveaxis(mixed, 0, 1).reshape(B, S, -1)


def swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def layer(x, p, m, fault=None):
    """One layer with its four norms."""
    eps = float(m["rms_norm_eps"])
    mixed = attention(rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m)
    x = x + rms_norm(mixed, p["attn_out_norm"]["scale"], eps)
    out = swiglu(rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"])
    if fault != "no_mlp_out_norm":
        out = rms_norm(out, p["mlp_out_norm"]["scale"], eps)
    return x + out


def forward(table, copies, input_ids, m, fault=None):
    """``[h_1, .., h_T]``, the stream every exit reads, each [B, S, E]:
    loop step ``t`` through the layers and the final norm of ``copies[t]``
    (float32 trees with ``layers`` and ``final_norm``; the model's ONE tree
    ``T`` times over), from the rows of ``table``."""
    eps = float(m["rms_norm_eps"])
    x = table[input_ids]
    streams = []
    for own in copies:
        for i in range(int(m["num_hidden_layers"])):
            x = layer(x, jax.tree.map(
                lambda t: t[i], own["layers"]["layer"]), m, fault)
        normed = rms_norm(x, own["final_norm"]["scale"], eps)
        if fault != "final_norm_outside":
            x = normed
        streams.append(normed)
    return streams


def head_losses(h, kernel, targets, m):
    """``-log softmax(h kernel)[targets]`` [B, S], a block of rows at a
    time: the logits of 16,384 rows over 49,152 columns are 3.2 GB."""
    B, S, E = h.shape
    n = _blocks(S, m["head_rows"])

    def one_block(first):
        logits = jax.lax.dynamic_slice_in_dim(h, first, n, 1) @ kernel
        at = jax.lax.dynamic_slice_in_dim(targets, first, n, 1)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), at[..., None], axis=-1)[..., 0]

    losses = jax.lax.map(one_block, jnp.arange(0, S, n))
    return jnp.moveaxis(losses, 0, 1).reshape(B, S)


def exit_probabilities(lam, fault=None):
    """``p`` [T, B, S] from the gates ``lam`` [T, B, S], as the equations
    have it: products, plainly."""
    p, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(lam[-1] * left if fault == "last_exit_gated" else left)
    return jnp.stack(p)


def objective(ce, p, weights, beta, fault=None):
    """(the objective; the weighted mean entropy of ``p`` in nats)."""
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    sign = 1.0 if fault == "entropy_sign" else -1.0
    per_token = jnp.sum(p * ce, axis=0) + sign * beta * entropy
    total = jnp.sum(weights)
    return (jnp.sum(weights * per_token) / total,
            jnp.sum(weights * entropy) / total)


def exits(table, copies, input_ids, targets, m, fault=None):
    """(every exit's token losses, every exit's gate) [T, B, S] each, exit
    ``t`` read by the head and the gate of ``copies[t]``."""
    with jax.default_matmul_precision("highest"):
        streams = forward(table, copies, input_ids, m, fault)
        ce = jnp.stack([
            head_losses(h, own["lm_head"]["kernel"], targets, m)
            for h, own in zip(streams, copies)])
        lam = jnp.stack([
            jax.nn.sigmoid((h @ own["exit_gate"]["kernel"])[..., 0]
                           + own["exit_gate"]["bias"][0])
            for h, own in zip(streams, copies)])
    return ce, lam


def reference(params, input_ids, targets, weights, m, fault=None, cast=None):
    """``{"ce": every exit's token losses [T, B, S] (the last is the
    model's result's, z_T's), "p": the exit distribution [T, B, S],
    "objective", "entropy"}`` from the program's parameter tree (unboxed).
    ``cast``: what every parameter goes through first (float32; a control
    rounds through a narrower type)."""
    params = jax.tree.map(
        cast or (lambda t: jnp.asarray(t, jnp.float32)), params)
    steps = int(m["total_ut_steps"]) - (fault == "three_loop_steps")
    ce, lam = exits(params["embed_tokens"], [params] * steps, input_ids,
                    targets, m, fault)
    p = exit_probabilities(lam, fault)
    loss, entropy = objective(
        ce, p, weights, float(m["exit_entropy_weight"]), fault)
    return {"ce": ce, "p": p, "objective": loss, "entropy": entropy}


def unrolled_gradients(params, input_ids, targets, weights, m):
    """The objective's gradient with the tie taken apart: ``T`` separate
    copies of the weights, loop step ``t`` reading copy ``t`` (its layers,
    its final norm, its head and its gate), the copies' gradients summed
    afterwards: what the looped model's one gradient a weight has to
    equal."""
    params = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), params)
    table = params.pop("embed_tokens")
    copies = [params] * int(m["total_ut_steps"])

    def loss_of(table, copies):
        ce, lam = exits(table, copies, input_ids, targets, m)
        return objective(ce, exit_probabilities(lam), weights,
                         float(m["exit_entropy_weight"]))[0]

    d_table, d_copies = jax.grad(loss_of, argnums=(0, 1))(table, copies)
    return {"embed_tokens": d_table,
            **jax.tree.map(lambda *g: sum(g), *d_copies)}
