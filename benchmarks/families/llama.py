"""The Llama *code* of the program (``models/llama.py``: GQA, RoPE, SwiGLU,
RMSNorm, untied head, FA2 kernel) driven from a configuration file, its
count of operations, and its plain reference.

The configuration file carries the published keys of a Hugging Face
``config.json`` of this architecture (Mistral-7B's, not a Llama's: see
``configs/``); ``build`` maps them onto ``LlamaConfig``."""

import jax
import jax.numpy as jnp

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    head_dim = src.get("head_dim") or (
        src["hidden_size"] // src["num_attention_heads"])
    return {**src, "head_dim": head_dim}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    m = sizes(config, rehearse)
    window = m.get("sliding_window")
    if window is not None and seq > window:
        raise ValueError(
            f"seq {seq} is longer than the sliding window {window}: the "
            "program's attention is full causal, which is the published "
            "layer only up to the window")
    cfg = LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        # the kernel, or (rehearsal, on the CPU) the jnp path: never a
        # silent change of path, "flash" raises off the chip
        attention_impl="reference" if rehearse else config["run"]["attention_impl"],
    )
    return LlamaForCausalLM(cfg)


def matmul_params(config, rehearse=False):
    """Parameters that take part in a matmul: the four attention
    projections, the three of the MLP, the output head.  Not the
    embedding table (a lookup) and not the norms."""
    m = sizes(config, rehearse)
    h, d = m["hidden_size"], m["head_dim"]
    attn = h * d * (2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    mlp = 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + mlp) + h * m["vocab_size"]


def flops_per_token(config, seq, rehearse=False):
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    return train_flops_per_token(
        matmul_params(config, rehearse), m["num_hidden_layers"],
        m["num_attention_heads"] * m["head_dim"], seq)


def fa2_shape(config, batch_per_chip, seq):
    """Shape of one call of the FA2 kernels on one chip, and how often a
    step calls each: with ``remat`` the forward runs again in the backward
    pass, so twice a layer."""
    m = sizes(config, False)
    layers = m["num_hidden_layers"]
    return {"batch": batch_per_chip, "seq": seq,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "causal": True,
            "calls_per_step": {"fwd": 2 * layers, "dq": layers, "dkv": layers}}


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no scan, no remat
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of each token and on their
#: mean.  The system multiplies in bfloat16 (8 bits of mantissa) with
#: float32 accumulation, as the configuration states; the reference is
#: float32 throughout at ``highest`` matmul precision.  Measured on the
#: chip at the Mistral-7B widths (my chip run, PR 24): see PERF.md.  A wrong
#: mask, RoPE convention, norm epsilon, GQA grouping or a float16/8-bit
#: matmul moves a token's loss by 1e-1 and more.
TOKEN_ATOL = 1.5e-1
MEAN_ATOL = 2e-3


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def reference_token_losses(params, input_ids, labels, config, rehearse=False):
    """Loss of every token, [B, S] float32, from the same parameter tree
    (unboxed, layers stacked on the leading axis by the program's scan)."""
    m = sizes(config, rehearse)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        seq = x.shape[1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        stack = params["layers"]["layer"]
        for i in range(m["num_hidden_layers"]):
            p = jax.tree.map(lambda t: f32(t[i]), stack)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            q = jnp.einsum("bse,ehd->bshd", h, p["attn"]["q_proj"]["kernel"])
            k = jnp.einsum("bse,ehd->bshd", h, p["attn"]["k_proj"]["kernel"])
            v = jnp.einsum("bse,ehd->bshd", h, p["attn"]["v_proj"]["kernel"])
            q, k = _rope(q, theta), _rope(k, theta)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * m["head_dim"] ** -0.5
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + jnp.einsum("bshd,hde->bse", att,
                               p["attn"]["o_proj"]["kernel"])
            h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
            gate = h @ p["mlp"]["gate_proj"]["kernel"]
            up = h @ p["mlp"]["up_proj"]["kernel"]
            x = x + (jax.nn.silu(gate) * up) @ p["mlp"]["down_proj"]["kernel"]
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
        logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
