"""Plain reference of Phi-4-mini-flash-reasoning (``microsoft/
Phi-4-mini-flash-reasoning``, ``model_type`` ``phi4flash``: SambaY with
differential attention, arXiv:2507.06607) as the program runs it.  Forward
pass, loss and, through ``jax.grad``, gradients, in float32 ``jax.numpy`` at
``highest`` matmul precision.  No kernel, no chunk of the recurrence, no
scan over layers, no sharding, no remat: a ``lax.scan`` over positions that
carries the ``[d_inner, N]`` state, the two softmax maps of a layer
materialised a block of queries at a time against every key.  The tests
hold ``models/llama.py``, ``ops/selective_scan.py`` and
``ops/attention.py::differential_attention`` to it; it shares no function
with them.

**The layout, from the configuration alone** (``kinds_of``; ``L =
num_hidden_layers``): layer ``i`` is a Mamba layer where ``i % mb_per_layer
== 0``, else attention, under the window ``sliding_window`` where ``i < L/2``
and ``i`` is odd, else whole; layer ``L/2`` (Mamba) hands on its scan output
``Y``, layer ``L/2 + 1`` (whole attention) its keys and values; from ``L/2 +
2`` an even layer is a gated memory unit over ``Y`` and an odd one cross
attention over those keys and values.

**Every layer**: ``x <- x + mixer(LN1(x))``; ``x <- x + W2 (silu(g) * u)``
with ``[g | u] = LN2(x) W1``; LayerNorm with bias (``layer_norm_eps``); a
final LayerNorm; logits ``x E^T``, ``E`` the embedding table (tied).

**Mamba-1 mixer**: ``[a | z] = h W_in``; ``a = silu(conv(a) + b)``, causal,
depthwise, ``mamba_d_conv`` taps (tap ``i`` weighs position ``t - (taps - 1)
+ i``); ``[r | B_t | C_t] = a W_x``; ``delta = softplus(r W_dt + b_dt)``; ``A =
-exp(A_log)``; ``s_t = exp(delta_t A) * s_{t-1} + (delta_t a_t) B_t^T``, ``s_0
= 0``; ``Y_t = s_t C_t + D * a_t``; ``out = (Y * silu(z)) W_out``.

**Gated memory unit**: ``out = (Y * silu(h W_in)) W_out`` with layer
``L/2``'s ``Y``, before its gate.

**Differential attention**: query heads ``(2j, 2j+1)`` are ``q1_j, q2_j``,
key heads ``(2m, 2m+1)`` ``k1_m, k2_m``, value heads ``(2m, 2m+1)`` side by
side ``V_m``; query pair ``j`` reads key pair ``j // (pairs of queries / pairs
of keys)``; ``A1 = softmax(q1 k1^T / sqrt(head_dim))``, ``A2`` likewise, under
the causal mask (and the window: a query sees itself and the ``window - 1``
before it); ``O_j = (A1 - lambda A2) V_m``; ``O_j <- RMSNorm(O_j) * (1 -
lambda_init)`` (a learned scale, eps 1e-5); ``lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` at
layer ``i``; the ``O_j`` side by side times ``W_o``; biases on q, k, v and
``W_o``.  No rotary, no position of any kind.  A cross layer has ``W_q`` and
``W_o`` alone and reads layer ``L/2 + 1``'s keys and values, causal.

``m`` carries the published key names (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``mb_per_layer``, ``num_hidden_layers``, ``layer_norm_eps``) and the
family's defaults ``mamba_d_state``, ``mamba_d_conv``, ``mamba_dt_rank``,
with ``query_block`` (queries a block of the attention; absent: all).  The
parameter tree is the program's (unboxed): ``layers/<run>/layer`` stacked
``[periods, 1, ...]``, ``memory/<run>/layer`` as they are,
``cross/<run>/layer`` ``[periods, 1, ...]``.
"""

import math

import jax
import jax.numpy as jnp

SUB_NORM_EPS = 1e-5


def kinds_of(m):
    """``[(kind, window or None)]`` of every layer, by the configuration's
    rule: kinds ``mamba``, ``attn``, ``gmu``, ``cross``."""
    L, per = int(m["num_hidden_layers"]), int(m["mb_per_layer"])
    out = []
    for i in range(L):
        if i >= L // 2 + 2:
            out.append(("cross" if i % 2 else "gmu", None))
        elif i % per == 0:
            out.append(("mamba", None))
        else:
            out.append(("attn", int(m["sliding_window"])
                        if i < L // 2 and i % 2 else None))
    return out


def layer_norm(x, p, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def mamba(h, p, m):
    """``(the mixer's output, Y)``."""
    N, rank = int(m["mamba_d_state"]), int(m["mamba_dt_rank"])
    taps = int(m["mamba_d_conv"])
    both = h @ p["in_proj"]["kernel"]
    inner = both.shape[-1] // 2
    a, z = both[..., :inner], both[..., inner:]
    S = a.shape[1]
    lead = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0)))
    a = jax.nn.silu(p["conv_bias"] + sum(
        lead[:, i: i + S] * p["conv_weight"][i] for i in range(taps)))
    steer = a @ p["x_proj"]["kernel"]
    delta = jax.nn.softplus(
        steer[..., :rank] @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"])
    A = -jnp.exp(p["A_log"])

    def step(state, at):
        a_t, delta_t, b_t, c_t = at
        state = jnp.exp(delta_t[..., None] * A) * state + (
            delta_t * a_t)[..., None] * b_t[:, None, :]
        return state, jnp.einsum("bcn,bn->bc", state, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((a.shape[0], inner, N), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (
            a, delta, steer[..., rank: rank + N], steer[..., rank + N:])))
    y = jnp.moveaxis(y, 0, 1) + p["D"] * a
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], y


def gated_memory(h, p, y):
    return (y * jax.nn.silu(h @ p["in_proj"]["kernel"])) @ (
        p["out_proj"]["kernel"])


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def differential_attention(h, p, m, i, window, handed=None):
    """``(the layer's output, (K, V) as projected)``; ``handed``: a cross
    layer's keys and values."""
    def project(name):
        return jnp.einsum("bse,ehd->bshd", h, p[name]["kernel"]) + (
            p[name]["bias"])

    q = project("q_proj")
    k, v = handed if handed is not None else (
        project("k_proj"), project("v_proj"))
    B, S, heads, D = q.shape
    groups = heads // k.shape[2]
    # a query pair's key pair, a key head a query head
    k1, k2 = (jnp.repeat(k[:, :, half::2], groups, axis=2) for half in (0, 1))
    wide = jnp.repeat(
        v.reshape(B, S, k.shape[2] // 2, 2 * D), groups, axis=2)
    first = lambda_init(i)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + first)
    block = min(int(m.get("query_block") or S), S)

    def one_block(start):
        rows = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, start, block, 1)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(S)[None, :]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)

        def attend(q_half, k_half):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", rows(q_half), k_half) * D ** -0.5
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

        probs = attend(q[:, :, 0::2], k1) - lam * attend(q[:, :, 1::2], k2)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, wide)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads // 2, 2 * D)
    var = jnp.mean(jnp.square(out), axis=-1, keepdims=True)
    out = out * jax.lax.rsqrt(var + SUB_NORM_EPS) * p["sub_norm"]["scale"]
    out = out * (1.0 - first)
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])
            + p["o_proj"]["bias"]), (k, v)


def layers_of(params, m):
    """The layers' parameters in the stack's order, float32."""
    def at(tree, *index):
        return jax.tree.map(
            lambda t: jnp.asarray(t, jnp.float32)[index], tree)

    L, per = int(m["num_hidden_layers"]), int(m["mb_per_layer"])
    # the period's runs as the program names them (no two neighbours of a
    # period are one kind)
    names = [("mamba" if kind == "mamba" else "swa" if window else "gqa")
             + f"_{j}" for j, (kind, window) in enumerate(kinds_of(m)[:per])]
    out = []
    for period in range(L // 2 // per):
        for name in names:
            out.append(at(params["layers"][name]["layer"], period, 0))
    out += [at(params["memory"]["mamba_0"]["layer"]),
            at(params["memory"]["gqa_1"]["layer"])]
    for period in range((L - L // 2 - 2) // 2):
        for name in ("gmu_0", "xattn_1"):
            out.append(at(params["cross"][name]["layer"], period, 0))
    return out


def forward(params, input_ids, labels, m, head=None):
    """``logits`` [B, S, V], ``token_losses`` [B, S] and ``loss`` (their
    mean: what the program's step minimises, no further term).  ``head``: a
    table for the output head in place of the embedding's (how a test sees
    the two uses of the tied table apart)."""
    eps = float(m["layer_norm_eps"])
    half = int(m["num_hidden_layers"]) // 2
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params["embed_tokens"], jnp.float32)
        x = table[input_ids]
        y = handed = None
        for i, ((kind, window), p) in enumerate(
                zip(kinds_of(m), layers_of(params, m))):
            h = layer_norm(x, p["input_norm"], eps)
            if kind == "mamba":
                out, scanned = mamba(h, p["attn"], m)
                y = scanned if i == half else y
            elif kind == "gmu":
                out = gated_memory(h, p["attn"], y)
            else:
                out, projected = differential_attention(
                    h, p["attn"], m, i, window,
                    handed if kind == "cross" else None)
                handed = projected if i == half + 1 else handed
            x = x + out
            x = x + swiglu(layer_norm(x, p["post_attn_norm"], eps), p["mlp"])
        x = layer_norm(x, jax.tree.map(
            lambda t: jnp.asarray(t, jnp.float32), params["final_norm"]), eps)
        logits = x @ (table if head is None else jnp.asarray(
            head, jnp.float32)).T
        logp = jax.nn.log_softmax(logits, -1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return {"logits": logits, "token_losses": token, "loss": token.mean()}
