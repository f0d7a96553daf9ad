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


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}: the whole rule, read from the configuration file
    (none where the file names no ``run.state``)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    experts = float(sizes(config, rehearse)["num_experts"]) ** 0.5
    mlp = ("layers", "layer", "mlp")
    return {("embed_tokens",): float(config["run"]["state"]["embed_scale"]),
            mlp + ("gate_proj",): experts, mlp + ("up_proj",): experts,
            mlp + ("down_proj",): experts}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors.  Same tree, shardings and dtypes; one multiply a leaf on
    the device, no forward pass, no look at a batch.

    The embedding table, times ``run.state.embed_scale``: the initialiser
    makes the table at rms 0.02, and every branch of the decoder reads the
    residual stream through an RMSNorm.  An untrained attention layer adds
    the running mean of the values before a token (rms 0.1-0.9), the same
    vector for a sequence's neighbouring positions, so from the first layer
    on the router of ``create_state``'s state sees a sequence's slowly moving
    context and not the token: a layer's tokens go to a few experts, which
    ones differs by seed and by step, and the step's time with them
    (PERF.md, section 6, PR 32).  Scaling the table up is scaling every
    branch down by as much (each reads the stream normalised): the router
    then sees the token's own vector first, and uniform random tokens
    spread evenly over the experts, which is where the load-balancing loss
    holds a mixture in training.

    Each expert's three matrices, times the square root of the number of
    experts: the initialiser draws the stacked ``[experts, in, out]`` arrays
    with the expert axis counted into the fan-in, so every matrix of
    ``create_state``'s experts is that factor smaller than a matrix of its
    own shape would be drawn, and the routed branch, three matrices deep,
    adds rms 0.0002 to a stream of 0.02: no comparison of the model's
    output could see the expert layer at all (PR 32's review: one chip's
    experts missing moved the worst token's loss by 0.0007).  At the
    factor the branch adds rms 0.10 a layer beside attention's 0.12-0.20,
    in a stream of 2.0 (CPU, float32, the published widths, 2 sequences of
    1024): the layer the cell is there for counts in ``correct`` as much as
    attention does.  The measured loads and readings are in the
    configuration file's notes and under ``TOKEN_ATOL`` below."""
    rule = state_rule(config, rehearse)

    def scaled(path, leaf):
        factor = rule.get(tuple(k.key for k in path[:-1]))   # [-1]: ``value``
        if factor is None:
            return leaf
        return jax.jit(lambda t: (t * factor).astype(t.dtype),
                       donate_argnums=0, out_shardings=leaf.sharding)(leaf)

    return state.replace(
        params=jax.tree_util.tree_map_with_path(scaled, state.params))


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

#: |system - reference| allowed on the loss of the worst token, of the median
#: token, and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (the router in float32 at the
#: highest precision); the reference is float32 throughout.  On top of the
#: rounding a dense model shows, routing is discontinuous: where the k-th and
#: k+1-th router logits of a token lie closer than the bfloat16 error of the
#: hidden state that feeds the router, the system can keep the other expert.
#: The two experts' weights are then nearly equal, so the token's result
#: moves by one expert's weighted output and not by a whole block.  The
#: reference routes by its own logits; it counts the tokens whose margin is
#: under ``LOW_MARGIN`` in each layer, prints the shares (``phase:
#: reference_margin``), and no token leaves the comparison.  A share over
#: ``LOW_MARGIN_SHARE_MAX`` means the router has collapsed towards ties and a
#: comparison token by token says nothing: the run is not correct.
#:
#: Each limit stands between readings on the chips at the published widths
#: and the cell's own size, four sequences of 4096, on the state
#: ``condition`` gives (``tests/precision_olmoe.py``, every set of losses
#: through ``jobs_shared.compare_losses``; my chip runs, PR 32's review
#: round): what the system gives, and what the control gives, the reference
#: put in the program's place with its parameters rounded through float8
#: (e4m3, ``_round_through``), the nearest precision below the bfloat16 the
#: configuration states, which has to come out as not correct.  The control
#: rounds the parameters only (the system never multiplies below bfloat16):
#: the mildest reading below bfloat16, rounding the activations too could
#: only lie further off.  Beside them a fault of the layer the cell is there
#: for, planted the same way: one chip's experts missing.
#:
#:                 system, 6 seeds     float8, 3 seeds    a chip's experts missing, 3
#:   worst token   0.0619-0.0870       0.189-0.205        0.370-0.444
#:   median token  0.00498-0.00510     0.0307-0.0314      0.0394-0.0408
#:   mean          3.6e-5-1.13e-4      1.2e-5-3.3e-4      4.3e-4-5.5e-4
#:
#: (all the seeds the review round's 40 chip-minutes held: three in the
#: probe, and the system's other three are the cell's own runs; on the CPU at
#: 2 sequences of 1024, float32 reference against the program's bfloat16
#: without the kernel, three seeds read 0.050-0.065 / 0.0051-0.0052,
#: 0.141-0.165 / 0.031-0.032, 0.236-0.341 / 0.040, and every token's weakest
#: expert dropped 0.088-0.098 / 0.016; PERF.md section 6 has every reading.)
#: The median is the number that holds the cell: steady to 2% from seed to
#: seed, the control 6.0 times and the fault 7.7 times the system's largest,
#: so ``MEDIAN_ATOL`` 0.01 stands 2.0 times over the system's largest and
#: 3.1 times under the control's smallest.  The worst token swings with the
#: routing (one flip in one layer moves a token by some 0.03; deviation 0.009
#: over the six seeds): the control's smallest is 2.2 times the system's
#: largest, under the three times a limit wants, and the control need not
#: fail it: ``TOKEN_ATOL`` 0.15 is there for one token or one row gone
#: wrong, which no median sees.  It lies 1.7 times over the system's largest
#: (seven deviations: a check draws thirty new seeds, and one false "not
#: correct" refuses a sound PR) and 1.26 times under the control's smallest.
#: The chip runs of the review round were made at 0.12 and the constant was
#: raised when the sixth seed read 0.087; every reading is under both.  The
#: mean's error is the average of 16,384 token errors of either sign, noise
#: of some 6e-5 for system and control alike: no value separates them and
#: none did (PR 27's review round); 2.5e-4 stays, four of those deviations,
#: and what it holds is a bias: the missing chip is over it on every seed.
#:
#: How the limits got here.  PR 27 set 0.1 and 2.5e-4 on ``create_state``'s
#: state (system 0.0238-0.0352, float8 by the backend's own conversion
#: 0.328-0.441).  PR 32's first round scaled the table by 100 and read the
#: system at 0.0192-0.0219 with a median of 0.0034, the control at
#: 0.0719-0.1357 with a median of 0.0036, and one chip's experts missing at
#: 0.0006: the comparison saw table, norm and head, not the branches.  Two
#: causes, both found in the review round.  The routed branch added rms
#: 0.0002 to a stream of 2.0, because the initialiser draws each expert's
#: matrices 8 times too small (``condition``).  And that control was no
#: float8: a conversion to float8 and back is removed by the chip's
#: compiler where the two meet (0.0 relative rms on the head's kernel
#: through ``astype``, 0.0316 through ``_round_through``, same call; the
#: CPU keeps it, and read a median of 0.028 where the chips read 0.0036),
#: so its median was the system's.  ``_round_through`` rounds in float32
#: arithmetic, bit for bit the CPU's conversion, and cannot be optimised
#: away.
TOKEN_ATOL = 0.15
MEDIAN_ATOL = 1e-2
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


def _round_through(t, dtype):
    """``t`` rounded to the nearest value of a narrower float ``dtype`` (ties
    to even, its subnormals kept, no overflow expected), in float32
    arithmetic and bit for bit what ``t.astype(dtype).astype(float32)`` gives
    on the CPU: the control's rounding then does not hang on how a backend
    implements a conversion to a type its chip has no unit for."""
    info = jnp.finfo(dtype)
    _, exponent = jnp.frexp(t)          # |t| in [2**(exponent-1), 2**exponent)
    k = jnp.maximum(exponent - 1, int(info.minexp)) - int(info.nmant)

    def two_to(n):
        return jax.lax.bitcast_convert_type(
            ((n + 127) << 23).astype(jnp.int32), jnp.float32)

    return jnp.round(t * two_to(-k)) * two_to(k)


def _report_margin(shares):
    print(json.dumps({"phase": "reference_margin", "low_margin": LOW_MARGIN,
                      "share_by_layer": [float(s) for s in shares],
                      "share_max": LOW_MARGIN_SHARE_MAX}),
          file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      round_through=None):
    """(loss of every token, [B, S] float32; share of each layer's tokens
    with a router margin under ``LOW_MARGIN``, [layers]) from the same
    parameter tree (unboxed, layers stacked on the leading axis by the
    program's scan).  The loops over the layers and over the experts are
    ``jax.lax.scan``s of the plain body, so that the program compiles in
    seconds and holds one layer's float32 weights at a time beside the
    training state.  ``round_through`` (``precision_olmoe.py``): a dtype
    every parameter is rounded through before it is used, for the reading
    below bfloat16.  The shares also go to standard error."""
    m = sizes(config, rehearse)
    eps = float(m["rms_norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if round_through is None else _round_through(t, round_through)

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
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           round_through=None):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, round_through)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
