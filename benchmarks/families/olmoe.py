"""OLMoE (arXiv:2409.02060; ``model_type`` ``olmoe``) through the program's
one decoder (``models/llama.py``) with the routed feed-forward block of
``models/moe.py``: MHA with q and k RMS-normalised over their whole width,
RoPE, a router over all experts that keeps the top k softmax weights as
they are, dropless, experts sharded over ``ep``.  Built from a configuration
file, with its count of operations and its plain reference (the benchmark's
copy of ``dlrover_tpu/models/olmoe_reference.py``)."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "num_experts": 8, "num_experts_per_tok": 2,
        "max_position_embeddings": 128}

#: published keys the program has no path for: only these values run
ONLY = {"attention_bias": False, "clip_qkv": None, "norm_topk_prob": False,
        "rope_scaling": None, "tie_word_embeddings": False,
        "hidden_act": "silu"}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    head_dim = src.get("head_dim") or (
        src["hidden_size"] // src["num_attention_heads"])
    return {**src, "head_dim": head_dim}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"qk_norm", "load_balance_coef", "router_z_coef"} <= fields:
        raise RuntimeError(
            "this checkout's models/moe.py has no QK-norm and no dropless "
            "router with its loss terms: it cannot run OLMoE")
    m = sizes(config, rehearse)
    for key, only in ONLY.items():
        if not rehearse and config.get(key, only) != only:
            raise ValueError(f"{key}={config[key]!r}: the program runs "
                             f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    assumed = config.get("assumed", {})
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]), qk_norm=True,
        num_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
        load_balance_coef=float(assumed.get("router_aux_loss_coef", 0.01)),
        router_z_coef=float(assumed.get("router_z_loss_coef", 0.001)),
        # the kernel, or (rehearsal, on the CPU) the jnp path: never a
        # silent change of path, "flash" raises off the chip
        attention_impl="reference" if rehearse else config["run"]["attention_impl"],
        # a rehearsal compares 128 tokens, whose bfloat16 mean is noise of
        # 1e-3: it walks the harness in float32, and ``MEAN_ATOL`` is for
        # the 16,384 tokens of a run on the chips
        **({"dtype": jnp.float32} if rehearse else {}),
    )
    return LlamaForCausalLM(cfg)


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with: the four attention projections,
    the router, its ``num_experts_per_tok`` experts (three matrices each)
    and the output head.  Not the other experts, the embedding table (a
    lookup) or the norms."""
    m = sizes(config, rehearse)
    h = m["hidden_size"]
    attn = h * m["head_dim"] * (
        2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    experts = m["num_experts_per_tok"] * 3 * h * m["intermediate_size"]
    layer = attn + h * m["num_experts"] + experts
    return m["num_hidden_layers"] * layer + h * m["vocab_size"]


def flops_per_token(config, seq, rehearse=False):
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    return train_flops_per_token(
        matmul_params(config, rehearse), m["num_hidden_layers"],
        m["num_attention_heads"] * m["head_dim"], seq)


def fa2_shape(config, batch_per_chip, seq):
    """As ``families/llama.py``: with ``remat`` the forward kernel runs
    twice a layer."""
    m = sizes(config, False)
    layers = m["num_hidden_layers"]
    return {"batch": batch_per_chip, "seq": seq,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "causal": True,
            "calls_per_step": {"fwd": 2 * layers, "dq": layers, "dkv": layers}}


def gmm_shape(config, tokens_per_step, chips):
    """The grouped matmuls of one chip in one step, for
    ``moe_gmm_roofline_pct``: ``rows`` go through each layer's experts on
    this chip (tokens x experts a token / chips: every assignment is
    processed once, somewhere), through three weight matrices of
    ``experts`` local experts."""
    m = sizes(config, False)
    return {"rows": tokens_per_step * m["num_experts_per_tok"] // chips,
            "experts": m["num_experts"] // chips,
            "hidden": m["hidden_size"], "width": m["intermediate_size"],
            "layers": m["num_hidden_layers"]}


def gmm_step_flops(shape):
    """Operations the mathematics needs for the grouped matmuls of one chip
    in one step: three matmuls a row forward (gate, up, down), and twice
    that backward (the gradient of the rows and of the weights).  The
    forward pass that ``remat`` repeats is not counted."""
    per_layer = 3 * 2 * shape["rows"] * shape["hidden"] * shape["width"]
    return 3 * shape["layers"] * per_layer


def gmm_step_bytes(shape, itemsize=2):
    """Least bytes those matmuls move to and from HBM, each operand read
    once and each result written once.  Forward: the rows in (twice: gate
    and up read them) and the two hidden results out, the hidden product
    in and the result out, the three weight matrices of every local expert
    in.  Backward: twice the forward's traffic (each matmul has two
    gradients, each reading one operand and the result's gradient and
    writing one array of an operand's size)."""
    rows, h, w = shape["rows"], shape["hidden"], shape["width"]
    activations = (2 * rows * h + 2 * rows * w) + (rows * w + rows * h)
    weights = 3 * shape["experts"] * h * w
    return 3 * shape["layers"] * (activations + weights) * itemsize


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no sort, no sharding, no
# remat; every head and every expert looped over plainly
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of each token and on their
#: mean.  The system multiplies in bfloat16 with float32 accumulation, as
#: the configuration states (the router in float32 at the highest
#: precision); the reference is float32 throughout.  On top of the rounding
#: a dense model shows, routing is discontinuous: where the k-th and k+1-th
#: router logits of a token lie closer than the bfloat16 error of the
#: hidden state that feeds the router, the system can keep the other
#: expert.  The two experts' weights are then nearly equal, so the token's
#: result moves by one expert's weighted output (some 3% of the weight
#: mass) and not by a whole block.  The reference routes by its own
#: logits; it counts the tokens whose margin is under ``LOW_MARGIN`` in each
#: layer, prints the shares (``phase: reference_margin``), and no token
#: leaves the comparison.  A share over ``LOW_MARGIN_SHARE_MAX`` means the
#: router has collapsed towards ties and a comparison token by token says
#: nothing: the reference then returns NaN and the run is not correct.
#: Each limit stands between two readings on the chip at the published
#: widths, four sequences of 4096 (``tests/precision_olmoe.py`` and the
#: cell's own check; PERF.md, section 4): what the system gives over its
#: seeds, and what the reference gives against itself with its parameters
#: rounded through float8 (e4m3), the nearest precision below the bfloat16
#: the configuration states, which has to come out as not correct.  That
#: probe rounds the parameters only, reference against reference (the
#: system never multiplies below bfloat16): it is the mildest reading below
#: bfloat16, and rounding the activations too could only lie further off.
#:
#:   worst token  system 0.0238-0.0352 (19 seeds)  float8 0.328-0.441 (8)
#:   mean         system 1.4e-5-1.1e-4 (19 seeds)  float8 8e-6-1.0e-3 (8)
#:
#: The token limit is the one a lower precision cannot pass: three times
#: the system's worst, a third of float8's best.  The mean's error is the
#: average of 16,384 token errors of either sign (median 0.0046 for the
#: system, 0.059 for float8): noise of standard deviation 6e-5 for the
#: system whatever the seed, and of some 6e-4 for float8, of whose eight
#: seeds two read under the limit (8e-6, 1.9e-4) and six over it.  No
#: value separates two such ranges; 2.5e-4 is four of the system's
#: deviations (a sound run in some 30,000 fails it; at 2e-4 one in a
#: thousand, in a cell every later PR runs a dozen times), and what it
#: holds is a bias: 2.5e-4 on every token is already over it.
TOKEN_ATOL = 1e-1
MEAN_ATOL = 2.5e-4
LOW_MARGIN = 1e-2
LOW_MARGIN_SHARE_MAX = 0.25


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


def _attention(h, p, m):
    """Causal attention, q and k RMS-normalised over their whole projected
    width before the split into heads; one head at a time, so that the
    scores of one head are all that is held at S 4096."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    flat = q.shape[:2] + (-1,)
    q = _rms_norm(q.reshape(flat), p["q_norm"]["scale"], eps).reshape(q.shape)
    k = _rms_norm(k.reshape(flat), p["k_norm"]["scale"], eps).reshape(k.shape)
    q, k = _rope(q, theta), _rope(k, theta)
    groups = q.shape[2] // k.shape[2]
    seq = h.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    out = jnp.zeros_like(h)
    for head in range(q.shape[2]):
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, head],
                            k[:, :, head // groups]) * q.shape[-1] ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        mixed = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1),
                           v[:, :, head // groups])
        out = out + mixed @ p["o_proj"]["kernel"][head]
    return out


def _experts(h, p, m):
    """(result, share of tokens with a low margin): every expert computes
    every token, one expert after the other; a token keeps its k largest
    router weights as they are."""
    k = int(m["num_experts_per_tok"])
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    largest = jax.lax.top_k(logits, k + 1)[0]
    gates = jnp.where(logits >= largest[..., k - 1: k], probs, 0.0)

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates, -1, 0)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, low


def _report_margin(shares):
    print(json.dumps({"phase": "reference_margin", "low_margin": LOW_MARGIN,
                      "share_by_layer": [float(s) for s in shares],
                      "share_max": LOW_MARGIN_SHARE_MAX}),
          file=sys.stderr, flush=True)


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           round_through=None):
    """Loss of every token, [B, S] float32, from the same parameter tree
    (unboxed, layers stacked on the leading axis by the program's scan).
    The loops over the layers and over the experts are ``jax.lax.scan``s of
    the plain body, so that the program compiles in seconds and holds one
    layer's float32 weights at a time beside the training state.
    ``round_through`` (``precision_olmoe.py``): a dtype every parameter is
    rounded through before it is used, for the reading below bfloat16."""
    m = sizes(config, rehearse)
    eps = float(m["rms_norm_eps"])

    def f32(t):
        if round_through is not None:
            t = jnp.asarray(t, round_through)
        return jnp.asarray(t, jnp.float32)

    def layer(x, p):
        p = jax.tree.map(f32, p)
        x = x + _attention(
            _rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m)
        out, low = _experts(
            _rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m)
        return x + out, low

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, low = jax.lax.scan(layer, x, params["layers"]["layer"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    jax.debug.callback(_report_margin, low)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
