"""The GPT-2 code of the program (``models/gpt.py``: LayerNorm, GELU
(tanh), learned positions, biases, tied head; the attention is the one
``ops.attention.causal_attention`` chooses, the FA2 kernel on a TPU) driven
from a configuration file, its count of operations, and its plain
reference."""

import jax
import jax.numpy as jnp

TINY = {"vocab_size": 256, "n_embd": 64, "n_layer": 2, "n_head": 4,
        "n_positions": 64, "layer_norm_epsilon": 1e-6}


def sizes(config, rehearse):
    return dict(TINY if rehearse else config)


def build(config, rehearse, seq):
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    m = sizes(config, rehearse)
    if seq > m["n_positions"]:
        raise ValueError(f"seq {seq} exceeds n_positions {m['n_positions']}")
    return GPT(GPTConfig(
        vocab_size=m["vocab_size"], n_embd=m["n_embd"], n_layer=m["n_layer"],
        n_head=m["n_head"], block_size=m["n_positions"],
    ))


def matmul_params(config, rehearse=False):
    """Per layer qkv (3E^2), projection (E^2) and the MLP (8E^2); the tied
    head is a matmul over the whole table.  Not the token lookup, the
    positions, the norms or the biases."""
    m = sizes(config, rehearse)
    e = m["n_embd"]
    return m["n_layer"] * 12 * e * e + e * m["vocab_size"]


def flops_per_token(config, seq, rehearse=False):
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    return train_flops_per_token(
        matmul_params(config, rehearse), m["n_layer"], m["n_embd"], seq)


def fa2_shape(config, batch_per_chip, seq):
    """Shape of one call of the FA2 kernels, which ``ops.attention.
    causal_attention`` gives this family on a TPU at head size 64 or 128
    and S a multiple of 128 (PR 26), and how often a step calls each: with
    ``remat`` the forward runs again in the backward pass.  MHA: as many
    kv heads as heads."""
    m = sizes(config, False)
    layers, heads = m["n_layer"], m["n_head"]
    return {"batch": batch_per_chip, "seq": seq, "heads": heads,
            "kv_heads": heads, "head_dim": m["n_embd"] // heads,
            "causal": True,
            "calls_per_step": {"fwd": 2 * layers, "dq": layers, "dkv": layers}}


#: as in ``families/llama.py``; the GPT code multiplies in bfloat16 and takes
#: its logits from a float32 einsum at the TPU's default precision.
TOKEN_ATOL = 1.5e-1
MEAN_ATOL = 2e-3


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(
        jnp.sqrt(2 / jnp.pi) * (x + 0.044715 * x ** 3)))


def reference_token_losses(params, input_ids, labels, config, rehearse=False):
    m = sizes(config, rehearse)
    eps = float(m["layer_norm_epsilon"])
    head_dim = m["n_embd"] // m["n_head"]
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        wte = f32(params["wte"])
        seq = input_ids.shape[1]
        x = wte[input_ids] + f32(params["wpe"])[None, :seq]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        stack = params["h"]["block"]
        for i in range(m["n_layer"]):
            p = jax.tree.map(lambda t: f32(t[i]), stack)
            h = _layer_norm(x, p["ln_1"], eps)
            qkv = (jnp.einsum("bse,ethd->bsthd", h, p["attn_qkv"]["kernel"])
                   + p["attn_qkv"]["bias"])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + (jnp.einsum("bshd,hde->bse", att, p["attn_proj"]["kernel"])
                     + p["attn_proj"]["bias"])
            h = _layer_norm(x, p["ln_2"], eps)
            h = _gelu_tanh(h @ p["mlp_fc"]["kernel"] + p["mlp_fc"]["bias"])
            x = x + h @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
        x = _layer_norm(x, jax.tree.map(f32, params["ln_f"]), eps)
        logits = x @ wte.T
        logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
