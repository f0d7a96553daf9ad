"""Solar-Open2 (``model_type`` ``solar_open2``) through the program's one
decoder (``models/llama.py``): a pattern of layers, one softmax GQA layer
without positions and with an output gate (FA2 on the chip) to three gated
delta-rule layers (Kimi Delta Attention, ``ops/linear_attention.py::kda``),
each followed by ``models/moe.py``'s routed block with sigmoid scores,
renormalised top-k weights and a shared expert, told which experts of the
layer this chip holds.  Built from a configuration file, with its counts of
operations and bytes and its plain reference (the benchmark's copy of
``dlrover_tpu/models/solar_open2_reference.py``, which states the layers
equation by equation).

In the file ``n_routed_experts`` is the experts HELD HERE (``reduced``) and
``published.n_routed_experts`` the router's width; ``run.first_expert``
says which.  The heads and the vocabulary in the file are this chip's
share too."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 32,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-5,
        "n_routed_experts": 2, "n_shared_experts": 1,
        "num_experts_per_tok": 3, "routed_scaling_factor": 1,
        "gqa_interval": 3, "gqa_layers": [0],
        "max_position_embeddings": 128, "published": {"n_routed_experts": 8},
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 2, "num_kv_heads": None}}

#: published keys the program has one path for: only these values run
ONLY = {"use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "norm_topk_prob": True,
        "first_k_dense_replace": 0, "tie_word_embeddings": False}

#: positions a chunk of the program's chunked delta rule
KDA_CHUNK = 64


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    assumed = config.get("assumed", {})
    period = int(src["gqa_interval"]) + 1
    return {**src, "experts_total": int(src["published"]["n_routed_experts"]),
            "first_expert": first,
            "router_aux_loss_coef": float(
                assumed.get("router_aux_loss_coef", 0.001)),
            # one period of the stack, from ``gqa_layers``
            "layer_pattern": tuple(
                "gqa" if i in src["gqa_layers"] else "kda"
                for i in range(period)),
            "query_block": 512}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"layer_pattern", "kda_heads", "attn_gate", "router_scores",
            "shared_experts", "experts_held"} <= fields:
        raise RuntimeError(
            "this checkout's models have no layer pattern, no gated "
            "delta-rule layer, no gated attention without positions, no "
            "sigmoid router and no shared expert: it cannot run Solar-Open2")
    m = sizes(config, rehearse)
    kda = m["linear_attn_config"]
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    if m["num_hidden_layers"] % len(m["layer_pattern"]):
        raise ValueError("num_hidden_layers is no whole number of periods")
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["rms_norm_eps"]),
        layer_pattern=m["layer_pattern"], use_rope=False, attn_gate=True,
        kda_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        kda_conv=kda["short_conv_kernel_size"], kda_chunk=KDA_CHUNK,
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        shared_experts=m["n_shared_experts"],
        experts_held=m["n_routed_experts"], first_expert=m["first_expert"],
        load_balance_coef=m["router_aux_loss_coef"], router_z_coef=0.0,
        # the kernel, or (rehearsal, on the CPU) the jnp path: never a
        # silent change of path, "flash" raises off the chip.  A rehearsal
        # compares a few hundred tokens, whose bfloat16 mean is noise: it
        # walks the harness in float32
        **({"dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return LlamaForCausalLM(cfg)


def runs(pattern):
    """One period as the program stacks it: ``[(name, kind, length)]``, a
    run of equal layers under ``<kind>_<run>``."""
    out = []
    for kind in pattern:
        if out and out[-1][1] == kind:
            out[-1][2] += 1
        else:
            out.append([f"{kind}_{len(out)}", kind, 1])
    return [tuple(run) for run in out]


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}: the whole rule, read from the configuration file
    (none where the file names no ``run.state``): the embedding table times
    ``embed_scale``; each held expert's three matrices times the square root
    of the number held; every router times ``router_scale``; a softmax
    layer's query projection times ``q_scale`` (a key that is absent is
    1)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    scale = lambda key: float(state.get(key, 1.0))  # noqa: E731
    held = float(m["n_routed_experts"]) ** 0.5
    rule = {("embed_tokens",): scale("embed_scale")}
    for name, kind, _ in runs(m["layer_pattern"]):
        layer = ("layers", name, "layer")
        rule.update({
            layer + ("mlp", "gate_proj"): held,
            layer + ("mlp", "up_proj"): held,
            layer + ("mlp", "down_proj"): held,
            layer + ("mlp", "router", "kernel"): scale("router_scale")})
        if kind == "gqa":
            rule[layer + ("attn", "q_proj", "kernel")] = scale("q_scale")
    return {path: factor for path, factor in rule.items() if factor != 1.0}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors; same tree, shardings and dtypes, one multiply a leaf on
    the device, no forward pass, no look at a batch.  As
    ``families/keyevl.py::condition``, for the same two reasons: the
    embedding table times ``run.state.embed_scale`` lets the router see the
    token's own vector, so uniform random tokens spread evenly over the 320
    experts and this chip's ten stay on the ladder's first extent; each held
    expert's three matrices times the square root of the number held undoes
    the initialiser's count of the expert axis into the fan-in.  And two
    factors that make mechanisms an untrained state leaves idle decide
    something (as ``families/evabyte.py``'s ``pool_scale``): every router
    times ``router_scale`` (unit-spread logits give sigmoid and softmax
    weights too alike for a loss to show) and the softmax layer's query
    projection times ``q_scale`` (unit-spread scores over thousands of keys
    are an average of the values, with or without positions).  The shared
    expert and the delta-rule layers are ``create_state``'s; the readings
    are under ``TOKEN_ATOL`` below."""
    rule = state_rule(config, rehearse)

    def scaled(path, leaf):
        factor = rule.get(tuple(k.key for k in path[:-1]))   # [-1]: ``value``
        if factor is None:
            return leaf
        return jax.jit(lambda t: (t * factor).astype(t.dtype),
                       donate_argnums=0, out_shardings=leaf.sharding)(leaf)

    return state.replace(
        params=jax.tree_util.tree_map_with_path(scaled, state.params))


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_counts(m):
    """(softmax layers, delta-rule layers) of the stack."""
    periods = m["num_hidden_layers"] // len(m["layer_pattern"])
    gqa = m["layer_pattern"].count("gqa")
    return periods * gqa, periods * (len(m["layer_pattern"]) - gqa)


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: a softmax layer's q,
    k, v, o and gate projections; a delta-rule layer's q, k, v, o, its two
    low-rank pairs and beta; in every layer the router, the shared expert
    and of the routed experts what a token's ``num_experts_per_tok``
    assignments meet here under even routing (``k * held / all`` experts: a
    quarter of one, at 8 a token and 10 of 320 held); the output head.  Not
    the embedding table, the norms, the convolutions' taps or the decay's
    vectors."""
    m = sizes(config, rehearse)
    h, kda = m["hidden_size"], m["linear_attn_config"]
    gqa = h * m["head_dim"] * (
        3 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    width = kda["num_heads"] * kda["head_dim"]
    delta = 4 * h * width + 2 * (h + width) * kda["head_dim"] + (
        h * kda["num_heads"])
    expert = 3 * h * m["moe_intermediate_size"]
    met = m["num_experts_per_tok"] * m["n_routed_experts"] / m["experts_total"]
    ffn = h * m["experts_total"] + (m["n_shared_experts"] + met) * expert
    n_gqa, n_kda = layer_counts(m)
    return (n_gqa * (gqa + ffn) + n_kda * (delta + ffn)
            + h * m["vocab_size"])


def kda_shape(config, batch, seq, rehearse=False):
    """The shapes the delta-rule layers of one chip work on in one step."""
    m = sizes(config, rehearse)
    kda = m["linear_attn_config"]
    return {"batch": batch, "seq": seq, "heads": kda["num_heads"],
            "head_dim": kda["head_dim"], "layers": layer_counts(m)[1]}


def kda_step_flops(shape):
    """Operations the model asks of one step's delta rule, whatever computes
    them: per token and head, on a state of ``head_dim x head_dim``, the
    decay (one multiply an element), the correction's ``S^T k`` (a
    multiply-add an element), the rank-one update and the read-out ``S^T
    q`` (the same each): 7 operations an element forward, twice that
    backward.  No recomputation counted, and nothing a chunked form adds
    (its triangular solve, its products inside a chunk)."""
    state = shape["head_dim"] * shape["head_dim"]
    return 3 * 7 * state * (
        shape["layers"] * shape["batch"] * shape["seq"] * shape["heads"])


def kda_step_bytes(shape, itemsize=2):
    """Least bytes it moves to and from HBM, each operand read once and each
    result written once: forward q, k, v in the compute dtype, the log decay
    a channel and beta a head in float32 in, the read-out out; backward
    those and the read-out's gradient in, the five gradients out.  The
    state never needs leave the chip."""
    rows = shape["batch"] * shape["seq"] * shape["heads"]
    wide = rows * shape["head_dim"]
    operands = 3 * wide * itemsize + wide * 4 + rows * 4
    forward = operands + wide * itemsize
    backward = (operands + wide * itemsize) + operands
    return shape["layers"] * (forward + backward)


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params``, the softmax
    layers' causal attention (``benchmarks/flops.py``) and the delta rule
    as the model asks for it (``kda_step_flops``: the recurrence, not the
    chunked form's extra products)."""
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    softmax = train_flops_per_token(
        matmul_params(config, rehearse), layer_counts(m)[0],
        m["num_attention_heads"] * m["head_dim"], seq)
    return softmax + kda_step_flops(
        kda_shape(config, 1, seq, rehearse)) / seq


def fa2_shape(config, batch_per_chip, seq):
    """Shape of one call of the FA2 kernels on one chip, and how often a
    step calls each: the softmax layers alone.  With ``remat`` the forward
    runs again in the backward pass, but not where a layer sits in loops of
    one turn (one period, a run of one): the compiler unrolls those and
    finds the rematerialised forward in the forward (my chip run and the
    described-chip compile, PR 41: three calls a step, not four)."""
    m = sizes(config, False)
    periods = m["num_hidden_layers"] // len(m["layer_pattern"])
    forward = layers = 0
    for _, kind, length in runs(m["layer_pattern"]):
        if kind == "gqa":
            layers += periods * length
            forward += periods * length * (
                2 if periods > 1 or length > 1 else 1)
    return {"batch": batch_per_chip, "seq": seq,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "causal": True,
            "calls_per_step": {"fwd": forward, "dq": layers, "dkv": layers}}


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no chunks, no sort of
# assignments, no sharding, no remat; the delta rule token by token, the
# attention a block of queries at a time, every held expert looped over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores, the decay, its
#: running sums, the triangular solve and the state between chunks in
#: float32); the reference is float32 throughout.  Beside the rounding a
#: dense model shows, one choice is discontinuous: a router margin under
#: the bfloat16 error of the hidden state flips an expert (``LOW_MARGIN``).
#: Each limit stands between readings on the chip at the published widths
#: and the cell's own size (one sequence of 8192, four layers), on the
#: state ``condition`` gives (``tests/precision_solaropen2.py``, each set of
#: losses through ``jobs_shared.compare_losses``; my chip runs, PR 41;
#: PERF.md section 6 has them by value of the state's factors):
#:
#:                  system            float8 control   the mildest faults
#:   worst token    0.030-0.050       0.19-0.25        0.13-0.15 (beta in (0, 1)), 0.19-0.21 (softmax router at
#:                                                     router_scale 3), 0.29 (rotary positions at q_scale 4)
#:   median token   0.0038-0.0040     0.034-0.036      0.023-0.025 (beta in (0, 1)), 0.040 (rotary positions)
#:   mean           7.6e-7-1.2e-4     2.8e-4-1.5e-3    4.1e-5-3.4e-4
#:
#: (two seeds, one factor of the state swept at a time around ``embed_scale``
#: 300, then sixteen seeds of the final state, two through the tool and
#: fourteen runs of the cell; the other four faults read 0.57-0.80 /
#: 0.095-0.134.  On the state
#: the rule gave first, ``embed_scale`` 100 and no other factor, five seeds of
#: the tool and five runs of the cell read the system at 0.072-0.160 /
#: 0.0051-0.0054 / 5e-5-2.4e-4 and the control at 0.32-0.34 / 0.053-0.054.)
#: The median is the number that holds the cell: steady to 5% from seed to
#: seed, the control 8.5 times and the mildest median fault 5.8 times the
#: system's largest, so ``MEDIAN_ATOL`` 0.01 stands 2.5 times over the one
#: and 2.3 times under the other.  ``TOKEN_ATOL`` 0.1 is there for one token
#: or one row gone wrong, which no median sees, and for the router: a softmax
#: router moves no median (a token meets a quarter of an expert here) but a
#: worst token to 0.19 and more; 0.1 is 2.0 times over the system's largest
#: and 1.9 times under that fault's smallest.  The mean is the average of
#: 8192 token errors, which cancel: ``MEAN_ATOL`` 3e-4 is there for a bias,
#: 2.4 times over the system's largest; the control reads on both sides of it.
#:
#: **Why the state's three factors** (``condition``; two seeds each, the
#: other factors at their final values but for the one swept).
#: ``embed_scale``: at 100 the rows this chip's ten experts take read
#: 0.83-1.14 of a fair share by layer and seed at set-up and 1.248 in a run
#: of the cell (the ladder's first extent ends at 1.25), at 300 0.97-1.04, at
#: 1000 0.97-1.02 but every layer's part then drowns in the stream (the
#: control's median 0.031, no decay's 0.032: one number for everything).
#: ``q_scale``: at 1 rotary positions planted on the softmax layer read a
#: median of 0.0023, BELOW the system's 0.0039 (an untrained head's scores
#: are N(0, 1) over thousands of keys: an average of the values with or
#: without positions); at 4 0.040 and a worst token of 0.29, at 8 0.065 and
#: 0.38-0.46; the system's readings do not move.  ``router_scale``: at 1 a
#: softmax router in the reference reads worst 0.065-0.067, median 0.0010
#: (unit-spread logits: the kept experts' sigmoid weights are within 6% of
#: even and their softmax weights within a factor of two, on a branch that
#: holds a quarter of an expert a token); at 3 worst 0.19-0.21, and the
#: low-margin share falls from 0.17 to 0.06.  Scaling the held experts'
#: down projection instead moved the system as far as the fault (worst
#: 0.13-0.15 against 0.25 at 4): not taken.
TOKEN_ATOL = 0.1
MEDIAN_ATOL = 1e-2
MEAN_ATOL = 3e-4
LOW_MARGIN = 1e-2
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above
FAULTS = ("no_decay", "beta_below_one", "no_conv", "rope_on_gqa", "no_gate",
          "no_shared_expert", "softmax_router")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention: only the planted
    fault ``rope_on_gqa`` calls it."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _short_conv(x, taps, fault):
    """SiLU of the causal depthwise convolution: tap ``i`` weighs position
    ``t - (n - 1) + i``.  ``no_conv``: the position's own value alone."""
    if fault == "no_conv":
        return jax.nn.silu(x)
    n, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i: i + S] * taps[i] for i in range(n)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, g, beta):
    """``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``, ``o_t =
    S_t^T q_t``, a token at a time."""
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


def _delta_attention(h, p, m, fault):
    """(attn(h), share of betas over 1, the median channel's half life)."""
    project = lambda name: jnp.einsum(  # noqa: E731
        "bse,ehd->bshd", h, p[name]["kernel"])
    q = _short_conv(project("q_proj"), p["q_conv"], fault)
    k = _short_conv(project("k_proj"), p["k_conv"], fault)
    v = _short_conv(project("v_proj"), p["v_conv"], fault)
    q, k = _unit(q) * q.shape[-1] ** -0.5, _unit(k)
    low = lambda name: jnp.einsum(  # noqa: E731
        "bsr,rhd->bshd", h @ p[name + "_down"]["kernel"],
        p[name + "_up"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        low("f") + p["dt_bias"])
    beta = jax.nn.sigmoid(h @ p["beta_proj"]["kernel"])
    if fault != "beta_below_one":
        beta = 2.0 * beta
    out = _delta_rule(q, k, v, jnp.zeros_like(g) if fault == "no_decay" else g,
                      beta)
    out = _rms_norm(out, p["o_norm"]["scale"], float(m["rms_norm_eps"]))
    if fault != "no_gate":
        out = out * jax.nn.sigmoid(low("g"))
    half_life = jnp.median(jnp.log(2.0) / -jnp.mean(g, axis=(0, 1)))
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"]),
            jnp.mean(beta > 1.0), half_life)


def _gated_attention(h, p, m, fault):
    """Softmax GQA without positions, a block of queries at a time against
    every key, the output gated elementwise before the output projection."""
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    if fault == "rope_on_gqa":
        q, k = _rope(q, 10000.0), _rope(k, 10000.0)
    gate = jax.nn.sigmoid(jnp.einsum(
        "bse,ehd->bshd", h, p["gate_proj"]["kernel"]))
    if fault == "no_gate":
        gate = jnp.ones_like(gate)
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


def _experts(h, p, m, fault):
    """(ffn(h), share of tokens with a low router margin): every held expert
    computes every token, one after the other; a token's k kept scores are
    divided by their sum; the experts that are not here add nothing; the
    shared expert once."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    scores = (jax.nn.softmax(logits, axis=-1) if fault == "softmax_router"
              else jax.nn.sigmoid(logits))
    largest = jax.lax.top_k(logits, k + 1)[0]
    gates = jnp.where(logits >= largest[..., k - 1: k], scores, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m["routed_scaling_factor"])
    here = p["gate_proj"].shape[0]

    def swiglu(gate_w, up_w, down_w):
        return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        return out + gate[..., None] * swiglu(gate_w, up_w, down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    if fault != "no_shared_expert":
        shared = p["shared_expert"]
        out = out + swiglu(*(shared[name]["kernel"] for name in (
            "gate_proj", "up_proj", "down_proj")))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, low


def reference(params, input_ids, labels, m, round_through=None, fault=None):
    """(loss of every token [B, S], share of each layer's tokens with a low
    router margin, share of betas over 1 and the half life in tokens a
    delta-rule layer) from the program's parameter tree (unboxed, a run of
    equal layers stacked ``[periods, run, ...]`` under ``<kind>_<run>``), as
    ``m = sizes(config, rehearse)`` reads the file.  The loops over periods
    and over a run are ``jax.lax.scan``s of the plain body: one layer's
    temporaries at a time beside the training state.  ``fault``: one of
    ``FAULTS``."""
    eps = float(m["rms_norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        if round_through is None:
            return t
        # rounding in float32 arithmetic: the chip's compiler removes a
        # conversion there and back (``families/olmoe.py::_round_through``)
        return load_module("families", "olmoe")._round_through(t, round_through)

    def layer(kind):
        def body(x, p):
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            if kind == "kda":
                mixed, over_one, life = _delta_attention(h, p["attn"], m, fault)
                seen = (over_one, life)
            else:
                mixed, seen = _gated_attention(h, p["attn"], m, fault), ()
            x = x + mixed
            out, low = _experts(
                _rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m,
                fault)
            return x + out, (low, seen)
        return body

    def period(x, p):
        seen = {}
        for name, kind, _ in runs(m["layer_pattern"]):
            x, seen[name] = jax.lax.scan(layer(kind), x, p[name]["layer"])
        return x, seen

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, seen = jax.lax.scan(period, x, params["layers"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # [periods, run] a run -> the stack's order
    names = [name for name, _, _ in runs(m["layer_pattern"])]
    low = jnp.concatenate([seen[n][0] for n in names], axis=1).ravel()
    kda_runs = [seen[n][1] for n in names if seen[n][1]]
    over_one = jnp.concatenate([s[0] for s in kda_runs], axis=1).ravel()
    life = jnp.concatenate([s[1] for s in kda_runs], axis=1).ravel()
    return losses, low, over_one, life


def _report(router_low, over_one, life):
    print(json.dumps({
        "phase": "reference_kda",
        "router_low_margin": LOW_MARGIN,
        "router_low_margin_share_by_layer": [float(v) for v in router_low],
        "router_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        "kda_beta_over_one_share_by_layer": [float(v) for v in over_one],
        "kda_decay_half_life_by_layer": [float(v) for v in life]}),
        file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each layer's tokens with a low router margin,
    which it holds to ``LOW_MARGIN_SHARE_MAX``).  The two counters that say
    the mechanism decides something on this state go to standard error."""
    losses, low, over_one, life = reference(
        params, input_ids, labels, sizes(config, rehearse), **planted)
    jax.debug.callback(_report, low, over_one, life)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
