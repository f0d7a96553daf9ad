"""Ling-3.0-flash-VL's language model (``inclusionAI/Ling-3.0-flash-VL``)
through the program's one decoder (``models/llama.py``): a leading dense
layer and then periods of five gated delta-rule layers (Kimi Delta
Attention with full-rank gate projections, a bounded decay and beta in (0,
1); ``ops/linear_attention.py::kda``) to one latent-attention layer (MLA:
scores in two products, one rotary key every head shares, a gate a head;
``ops/attention.py::latent_attention``), each of the period's layers
followed by ``models/moe.py``'s routed block: sigmoid scores, the choice by
groups under a selection bias the load moves, renormalised weights times
2.5, a shared expert; told which experts of the layer this chip holds.
Built from a configuration file, with its counts of operations and bytes
and its plain reference (the benchmark's copy of
``dlrover_tpu/models/ling3_reference.py``, which states the layers equation
by equation).

In the file ``num_experts`` is the experts HELD HERE (``reduced``) and
``published.num_experts`` the router's width; ``run.first_expert`` says
which.  The heads and the vocabulary in the file are this chip's share too.

**The selection bias is no parameter**: it lives in the trainer's state
beside the parameters (``TrainState.buffers``).  The harness's forward
check hands a model its parameters alone (``jobs_shared.reference_check``,
which this PR may not edit), so ``build`` returns the program's model in a
shell that adds the state's buffers where a caller gave none, and
``condition``, which makes the state a cell starts from, tells the shell
and the reference which they are (``_STATE``)."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
        "num_hidden_layers": 4, "first_k_dense_replace": 1,
        "layer_group_size": 3, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
        "rope_theta": 6000000, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 4,
        "num_experts_per_tok": 4, "routed_scaling_factor": 2.5, "n_group": 4,
        "topk_group": 2, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "max_position_embeddings": 128, "published": {"num_experts": 16}}

#: published keys the program has one path for: only these values run
ONLY = {"use_nGPT": False, "value_norm": False, "up_proj_norm": False,
        "scale_router_input": False, "q_lora_rank": None,
        "use_mla_nope": False, "no_kda_lora": True, "use_kda_lora": False,
        "kda_safe_gate": True, "norm_topk_prob": True,
        "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
        "linear_silu": True}

#: positions a chunk of the program's chunked delta rule
KDA_CHUNK = 64

#: what ``condition`` last made: ``{"buffers": the state's}``, for the
#: model's shell and the reference (the module's text)
_STATE = {"buffers": None}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    assumed = config.get("assumed", {})
    group = int(src["layer_group_size"])
    dense = int(src["first_k_dense_replace"])
    if (int(src["num_hidden_layers"]) - dense) % group:
        raise ValueError("num_hidden_layers less the dense prefix is no "
                         "whole number of periods")
    return {**src, "experts_total": int(src["published"]["num_experts"]),
            "first_expert": first,
            "bias_update_rate": float(assumed.get("bias_update_rate", 0.001)),
            # the dense prefix (KDA layers: layers 0 and 1 of the published
            # stack are no multiple of ``layer_group_size`` less one), then
            # one period: MLA where ``(i + 1) % layer_group_size == 0``
            "layer_prefix": ("kda:dense",) * dense,
            "layer_pattern": ("kda",) * (group - 1) + ("mla",),
            "query_block": 512}


class _WithStateBuffers:
    """The program's model, with ``apply`` given the buffers of the state
    ``condition`` made where the caller names none (the harness's forward
    check; ``Trainer``'s step names its own).  Everything else is the
    model's."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, variables, *args, **kwargs):
        if "buffers" not in variables and _STATE["buffers"] is not None:
            variables = {**variables, "buffers": _STATE["buffers"]}
        return self._model.apply(variables, *args, **kwargs)


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"layer_prefix", "mla_kv_rank", "kda_full_rank_gates", "n_group",
            "selection_bias", "experts_held"} <= fields:
        raise RuntimeError(
            "this checkout's models have no latent attention, no leading "
            "dense layers, no grouped selection and no selection bias: it "
            "cannot run Ling-3.0")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    _STATE["buffers"] = None
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["rms_norm_eps"]),
        rope_theta=float(m["rope_theta"]),
        layer_prefix=m["layer_prefix"], layer_pattern=m["layer_pattern"],
        kda_heads=m["num_attention_heads"], kda_head_dim=m["head_dim"],
        kda_conv=m["short_conv_kernel_size"], kda_chunk=KDA_CHUNK,
        kda_full_rank_gates=True,
        kda_decay_lower_bound=float(m["kda_lower_bound"]),
        kda_neg_eigval=False,
        mla_kv_rank=m["kv_lora_rank"], mla_nope_dim=m["qk_nope_head_dim"],
        mla_rope_dim=m["qk_rope_head_dim"], mla_v_dim=m["v_head_dim"],
        mla_head_gate=True,
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        shared_experts=1,
        shared_intermediate_size=m["moe_shared_expert_intermediate_size"],
        experts_held=m["num_experts"], first_expert=m["first_expert"],
        n_group=m["n_group"], topk_group=m["topk_group"],
        selection_bias=True, bias_update_rate=m["bias_update_rate"],
        # the published strategy is loss-free: no term in the objective
        load_balance_coef=0.0, router_z_coef=0.0,
        # a rehearsal compares a few hundred tokens, whose bfloat16 mean is
        # noise: it walks the harness in float32
        **({"dtype": jnp.float32} if rehearse else {}),
    )
    return _WithStateBuffers(LlamaForCausalLM(cfg))


def runs(entries):
    """Entries as the program stacks them: ``[(name, entry, length)]``, a
    run of equal layers under ``<kind>_<run>`` (``<kind>_dense_<run>``)."""
    out = []
    for entry in entries:
        if out and out[-1][1] == entry:
            out[-1][2] += 1
        else:
            out.append([f"{entry.replace(':', '_')}_{len(out)}", entry, 1])
    return [tuple(run) for run in out]


def stacks(m):
    """``[(path of the stack in the tree, name, entry)]`` of every run, the
    prefix's first."""
    return ([(("prefix", name, "layer"), name, entry)
             for name, entry, _ in runs(m["layer_prefix"])]
            + [(("layers", name, "layer"), name, entry)
               for name, entry, _ in runs(m["layer_pattern"])])


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}, read from the configuration file (none where the
    file names no ``run.state``): the embedding table times ``embed_scale``;
    each held expert's three matrices times the square root of the number
    held; every router times ``router_scale``; the latent-attention layer's
    query projection times ``q_scale``; a delta-rule layer's decay
    projection times ``decay_scale`` and its ``dt_bias`` times
    ``dt_bias_scale`` (a key that is absent is 1)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    scale = lambda key: float(state.get(key, 1.0))  # noqa: E731
    held = float(m["num_experts"]) ** 0.5
    rule = {("embed_tokens",): scale("embed_scale")}
    for layer, _, entry in stacks(m):
        kind, _, ffn = entry.partition(":")
        if not ffn:
            rule.update({
                layer + ("mlp", "gate_proj"): held,
                layer + ("mlp", "up_proj"): held,
                layer + ("mlp", "down_proj"): held * scale("expert_out_scale"),
                layer + ("mlp", "router", "kernel"): scale("router_scale")})
        if kind == "mla":
            attn = layer + ("attn",)
            rule.update({
                attn + ("q_proj", "kernel"): scale("q_scale"),
                attn + ("kv_a_proj", "kernel"): scale("latent_scale"),
                attn + ("gate_proj", "kernel"): scale("gate_scale"),
                attn + ("o_proj", "kernel"): scale("mla_out_scale")})
        else:
            rule[layer + ("attn", "f_proj", "kernel")] = scale("decay_scale")
            rule[layer + ("attn", "dt_bias")] = scale("dt_bias_scale")
    return {path: factor for path, factor in rule.items() if factor != 1.0}


def drawn_bias(params, like, spread):
    """The selection bias a cell starts from: normal draws times ``spread``
    in the shape of ``like``, from a key made of the state's own first
    embedding weight's bits: a function of the seed (the weights are) that
    needs no seed handed in, the same on every call for one state."""
    bits = jax.lax.bitcast_convert_type(
        jnp.asarray(params["embed_tokens"], jnp.float32)[0, 0], jnp.uint32)
    key = jax.random.fold_in(jax.random.PRNGKey(48), bits)
    leaves, tree = jax.tree.flatten(like)
    return jax.tree.unflatten(tree, [
        spread * jax.random.normal(k, leaf.shape, jnp.float32)
        for k, leaf in zip(jax.random.split(key, len(leaves)), leaves)])


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors (``families/solaropen2.py::condition``: same tree,
    shardings and dtypes, one multiply a leaf on the device, no forward
    pass, no look at a batch), and with the selection bias of every routed
    layer drawn with spread ``run.state.bias_spread`` (``drawn_bias``; the
    initialiser's 0 where the file names none): at 0 a fault in what the
    bias does is invisible.  The readings are under ``TOKEN_ATOL`` below."""
    import flax.linen as nn

    rule = state_rule(config, rehearse)

    def scaled(path, leaf):
        factor = rule.get(tuple(k.key for k in path[:-1]))   # [-1]: ``value``
        if factor is None:
            return leaf
        return jax.jit(lambda t: (t * factor).astype(t.dtype),
                       donate_argnums=0, out_shardings=leaf.sharding)(leaf)

    spread = float(config["run"].get("state", {}).get("bias_spread", 0.0))
    buffers = state.buffers
    if spread:
        buffers = jax.jit(lambda params: drawn_bias(
            params, buffers, spread))(nn.meta.unbox(state.params))
    state = state.replace(
        params=jax.tree_util.tree_map_with_path(scaled, state.params),
        buffers=buffers)
    _STATE["buffers"] = state.buffers
    return state


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_counts(m):
    """(dense delta-rule layers, routed delta-rule layers, latent-attention
    layers) of the stack."""
    periods = (m["num_hidden_layers"] - len(m["layer_prefix"])) // len(
        m["layer_pattern"])
    return (len(m["layer_prefix"]),
            periods * m["layer_pattern"].count("kda"),
            periods * m["layer_pattern"].count("mla"))


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: a delta-rule
    layer's q, k, v, o, its two full-rank gate projections and beta; the
    latent-attention layer's query, down, up, output and gate projections;
    a dense layer's SwiGLU; in a routed layer the router, the shared expert
    and of the routed experts what a token's ``num_experts_per_tok``
    assignments meet here under even routing (``k * held / all`` experts: a
    quarter of one, at 8 a token and 16 of 512 held); the output head.  Not
    the embedding table, the norms, the convolutions' taps or the decay's
    vectors."""
    m = sizes(config, rehearse)
    h, heads = m["hidden_size"], m["num_attention_heads"]
    delta = 6 * h * heads * m["head_dim"] + h * heads
    nope, rope, wide = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"])
    latent = (h * heads * (nope + rope) + h * (m["kv_lora_rank"] + rope)
              + m["kv_lora_rank"] * heads * (nope + wide)
              + heads * wide * h + h * heads)
    met = m["num_experts_per_tok"] * m["num_experts"] / m["experts_total"]
    routed = (h * m["experts_total"]
              + 3 * h * m["moe_shared_expert_intermediate_size"]
              + met * 3 * h * m["moe_intermediate_size"])
    dense = 3 * h * m["intermediate_size"]
    n_dense, n_kda, n_mla = layer_counts(m)
    return (n_dense * (delta + dense) + n_kda * (delta + routed)
            + n_mla * (latent + routed) + h * m["vocab_size"])


def kda_shape(config, batch, seq, rehearse=False):
    """The shapes the delta-rule layers of one chip work on in one step."""
    m = sizes(config, rehearse)
    n_dense, n_kda, _ = layer_counts(m)
    return {"batch": batch, "seq": seq, "heads": m["num_attention_heads"],
            "head_dim": m["head_dim"], "layers": n_dense + n_kda}


def kda_step_flops(shape):
    """Operations the model asks of one step's delta rule, whatever computes
    them: ``families/solaropen2.py::kda_step_flops``, the same rule (7
    operations an element of a head's state and token forward, twice that
    backward)."""
    return load_module("families", "solaropen2").kda_step_flops(shape)


def kda_step_bytes(shape, itemsize=2):
    """Least bytes it moves to and from HBM, each operand read once and each
    result written once a pass: ``families/solaropen2.py::kda_step_bytes``."""
    return load_module("families", "solaropen2").kda_step_bytes(
        shape, itemsize)


def mla_shape(config, batch, seq, rehearse=False):
    """The shapes the latent-attention layers' core works on in one step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["num_attention_heads"],
            "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
            "v": m["v_head_dim"], "layers": layer_counts(m)[2]}


def mla_step_flops(shape):
    """Operations the model asks of one step's latent-attention core,
    whatever computes them: a causal pair of positions and head costs a
    multiply-add over the scores' ``nope + rope`` and over the values'
    ``v`` forward; backward the scores again, their gradient's two products
    (``nope + rope`` each) and the two products over ``v``.  The model's
    widths, unpadded; no rematerialised forward counted."""
    pairs = shape["seq"] * (shape["seq"] + 1) // 2
    qk, v = shape["nope"] + shape["rope"], shape["v"]
    per_pair = 2 * (qk + v) + 2 * (3 * qk + 2 * v)
    return per_pair * pairs * shape["heads"] * shape["batch"] * shape["layers"]


def mla_step_bytes(shape, itemsize=2):
    """Least bytes the core moves to and from HBM: q (both parts), k_nope,
    the one k_pe, v and o once forward; backward those and o's gradient in,
    the gradients of q, k_nope, k_pe and v out."""
    rows = shape["batch"] * shape["seq"]
    heads, qk, v = shape["heads"], shape["nope"] + shape["rope"], shape["v"]
    q, k_nope, k_pe, wide = (rows * heads * qk, rows * heads * shape["nope"],
                             rows * shape["rope"], rows * heads * v)
    forward = q + k_nope + k_pe + 2 * wide
    backward = (q + k_nope + k_pe + 3 * wide) + (q + k_nope + k_pe + wide)
    return shape["layers"] * itemsize * (forward + backward)


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params``, the latent
    attention's core (``mla_step_flops``) and the delta rule as the model
    asks for it (``kda_step_flops``)."""
    return (6 * matmul_params(config, rehearse)
            + mla_step_flops(mla_shape(config, 1, seq, rehearse)) / seq
            + kda_step_flops(kda_shape(config, 1, seq, rehearse)) / seq)


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no chunks, no sort of
# assignments, no sharding, no remat; the delta rule token by token, the
# latent attention a block of queries at a time against every key (each
# head's key written out whole: [k_nope | k_pe]), every held expert looped
# over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores, the decay, its
#: running sums, the state between chunks and the softmax in float32); the
#: reference is float32 throughout.  Beside the rounding a dense model
#: shows, one choice is discontinuous: a margin of the choice under the
#: bfloat16 error of the hidden state flips an expert (``LOW_MARGIN``), and
#: a flip here weighs 2.5 / 8 of an expert's whole result
#: (``routed_scaling_factor``): the system's worst tokens are flips.  Each
#: limit stands between readings on the chip at the published widths and
#: the cell's own size (one sequence of 16,384, seven layers), on the state
#: ``condition`` gives (``tests/precision_ling3.py``, each set of losses
#: through ``jobs_shared.compare_losses``; my chip runs, PR 48: four seeds
#: ..104-..107 through the tool, and the system again in nine runs of the
#: cell; PERF.md section 6 has them by value of the state's factors):
#:
#:                  system            float8 control   the mildest faults
#:   worst token    0.287-0.395       0.43-0.53        0.17-0.43 (the bias in the weights: NOT caught),
#:                                                     0.28-0.40 (scores over sqrt(128))
#:   median token   0.00614-0.00642   0.0432-0.0436    0.0174-0.0193 (the bias left out of the choice),
#:                                                     0.0228-0.0236 (scores over sqrt(128)),
#:                                                     0.0230-0.0245 (no groups)
#:   mean           8e-6-2.9e-4       2.3e-4-1.5e-3    9e-6-5.8e-4
#:
#: (the other six faults read a median of 0.059-0.30.)  **The median holds
#: the cell**: steady to 3% from seed to seed, the control 6.7 times and the
#: mildest fault it catches 2.7 times the system's largest, so
#: ``MEDIAN_ATOL`` 0.011 stands 1.7 times over the one and 1.6 times under
#: the other.  **The worst token cannot tell a routing fault from the
#: system**: a fault of the choice is more flips of the size the system's
#: own few have (no groups 0.46-0.59, the bias out of the choice 0.45-0.57
#: beside the system's 0.29-0.40), so ``TOKEN_ATOL`` 0.7 is there for a
#: token or a row gone wrong (1.8 times the system's largest; the norm of the
#: latent left out reads 2.0-2.6, Solar's decay 0.93-1.12) and the faults of
#: the choice are caught by the median, which they move through every later
#: layer's mixing of tokens.  ``MEAN_ATOL`` 8e-4 is there for a bias, 2.8
#: times over the system's largest; the control reads on both sides of it.
#: **One planted fault of the ten is not caught**: the bias added to the
#: weights before they are divided by their sum moves a token's loss by a
#: median of 0.0006 at a spread of 0.01 (the division takes most of it
#: back), and a spread that shows it (0.03: worst token 0.108 beside the
#: system's 0.105 on the state then) crowds the experts the bias favours
#: (``load_max_over_mean`` 4-5) and moves this chip's rows by seed.
#:
#: **Why the state's factors** (``condition``; PERF.md section 6 has the
#: sweeps, one seed each, thirteen rules).  On ``create_state``'s own with
#: the table times 300 and a bias of spread 0.01 the system reads 0.105-0.131
#: / 0.0052-0.0054 and six faults decide nothing: the latent's norm
#: (``c`` has unit scale already: median 0.0046, BELOW the system's),
#: the gate's granularity (0.0042), the scores' scale (0.0071), no groups
#: (0.0058), the bias out of the choice (0.0044) or in the weights
#: (0.0002).  ``latent_scale`` 3 (the down-projection: the norm then has
#: something to take back, 0.0046 -> 0.13) and ``gate_scale`` 3 (gates that
#: open and shut, 0.012 -> 0.036) cost the system nothing;
#: ``mla_out_scale`` 3 lets the one latent layer of seven weigh in the
#: stream (scores' scale 0.0048 -> 0.014, the system's median 0.0054 ->
#: 0.0069) and ``q_scale`` 2 puts its softmax where a factor of 1.22 on the
#: scores shows most (0.010 at 1, 0.023 at 2, 0.014 at 4, 0.0043 at 8).
#: ``expert_out_scale`` 3 (the held experts' down projection): a token
#: meets a quarter of an expert here, a thirty-second of what the whole
#: layer's eight would add; at 3 the faults of the choice read a median of
#: 0.023-0.036 for the system's 0.0062-0.0081 (at 1: 0.0056-0.012 for
#: 0.0069; at 6: 0.054-0.070 for 0.0100, but the scores' scale then falls
#: under the limit) and the system's worst token goes from 0.11 to 0.34
#: (flips weigh three times as much: ``TOKEN_ATOL``).  ``router_scale``
#: stays 1: the flips' share does not depend on it and the bias is added
#: to scores, not logits (at 2 a spread of 0.02 already chooses alone:
#: low-margin share 0.34).  ``bias_spread`` 0.01, about two places of rank
#: among the 256 candidates: at 0.02 the largest expert takes 3.6 times the
#: mean and this chip's rows read 1.11 of a fair share where 0.01 reads 1.04.
TOKEN_ATOL = 0.7
MEDIAN_ATOL = 1.1e-2
MEAN_ATOL = 8e-4
#: a margin of the choice (in ``scores + bias``, of experts or of groups)
#: that bfloat16 arithmetic upstream can cross
LOW_MARGIN = 1e-3
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each but
#: ``bias_in_weights`` comes out not correct at the limits above
FAULTS = ("no_groups", "bias_not_in_choice", "bias_in_weights",
          "scale_by_nope", "k_pe_not_rotated", "k_pe_dropped",
          "no_latent_norm", "elementwise_gate", "softplus_decay", "beta_two")


def _solar():
    """The plain pieces this reference has in common with Solar-Open2's
    (RMSNorm, the rotary embedding in the halves convention, the short
    convolution, the L2 norm, the delta rule token by token): that
    family's, as its ``_round_through`` is OLMoE's."""
    return load_module("families", "solaropen2")


def _rms_norm(x, scale, eps):
    return _solar()._rms_norm(x, scale, eps)


def _rope(x, theta):
    return _solar()._rope(x, theta)


def _delta_attention(h, p, m, fault):
    """(attn(h), the median channel's half life in tokens)."""
    project = lambda name: jnp.einsum(  # noqa: E731
        "bse,ehd->bshd", h, p[name]["kernel"])
    solar = _solar()
    q, k, v = (solar._short_conv(project(name + "_proj"), p[name + "_conv"],
                                 None) for name in "qkv")
    q, k = solar._unit(q) * q.shape[-1] ** -0.5, solar._unit(k)
    steered = project("f_proj") + p["dt_bias"]
    rate = jnp.exp(p["A_log"])[:, None]
    if fault == "softplus_decay":       # the unbounded gate
        g = -rate * jax.nn.softplus(steered)
    else:
        g = float(m["kda_lower_bound"]) * jax.nn.sigmoid(rate * steered)
    beta = jax.nn.sigmoid(h @ p["beta_proj"]["kernel"])
    if fault == "beta_two":
        beta = 2.0 * beta
    out = solar._delta_rule(q, k, v, g, beta)
    out = _rms_norm(out, p["o_norm"]["scale"], float(m["rms_norm_eps"]))
    out = out * jax.nn.sigmoid(project("g_proj"))
    half_life = jnp.median(jnp.log(2.0) / -jnp.mean(g, axis=(0, 1)))
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"]), half_life


def _latent_attention(h, p, m, fault):
    """MLA, a block of queries at a time against every key; a head's key is
    ``[k_nope | k_pe]`` with the one rotated ``k_pe`` repeated."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    nope, rank = int(m["qk_nope_head_dim"]), int(m["kv_lora_rank"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    down = h @ p["kv_a_proj"]["kernel"]
    latent = down[..., :rank]
    if fault != "no_latent_norm":
        latent = _rms_norm(latent, p["kv_a_norm"]["scale"], eps)
    up = jnp.einsum("bsr,rhd->bshd", latent, p["kv_b_proj"]["kernel"])
    k_pe = down[..., None, rank:]
    if fault != "k_pe_not_rotated":
        k_pe = _rope(k_pe, theta)
    if fault == "k_pe_dropped":
        k_pe = jnp.zeros_like(k_pe)
    B, S, heads, _ = q.shape
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (B, S, heads, k_pe.shape[-1]))],
        axis=-1)
    v = up[..., nope:]
    scale = (nope if fault == "scale_by_nope" else q.shape[-1]) ** -0.5
    block = min(int(m["query_block"]), S)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k) * scale
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads, v.shape[-1])
    gate = (h @ p["gate_proj"]["kernel"])[..., None]
    if fault == "elementwise_gate":
        # a gate an element where the model has one a head: the heads'
        # gates tiled over the flat ``heads x width`` where each should be
        # repeated over its head's width, so element ``(h, d)`` takes the
        # gate of head ``(h * width + d) % heads``
        gate = jnp.tile(gate[..., 0], out.shape[-1]).reshape(out.shape)
    gate = jax.nn.sigmoid(gate)
    return jnp.einsum("bshd,hde->bse", out * gate, p["o_proj"]["kernel"])


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def _dense_mlp(h, p):
    return _swiglu(h, *(p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj")))


def _chosen(scores, bias, m, fault):
    """``(gates [.., E] (0 where not chosen), low-margin share, share of
    tokens the groups drop an expert of, rows each expert took)``: the
    choice on ``c = s + b``, by groups (a group's score the sum of its two
    largest ``c``, the ``topk_group`` best kept); the weights ``s / sum(s
    chosen) * factor``."""
    k, n_group = int(m["num_experts_per_tok"]), int(m["n_group"])
    c = scores if fault == "bias_not_in_choice" else scores + bias
    grouped = c.reshape(*c.shape[:-1], n_group, -1)
    best_two = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
    group_edge = jax.lax.top_k(best_two, int(m["topk_group"]) + 1)[0]
    kept = best_two >= group_edge[..., -2:-1]
    inside = c if fault == "no_groups" else jnp.where(
        kept[..., None], grouped, -jnp.inf).reshape(c.shape)
    edge = jax.lax.top_k(inside, k + 1)[0]
    chosen = inside >= edge[..., k - 1: k]
    weigh = scores + bias if fault == "bias_in_weights" else scores
    gates = jnp.where(chosen, weigh, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True) * float(
        m["routed_scaling_factor"])
    low = jnp.mean(
        (edge[..., k - 1] - edge[..., k] < LOW_MARGIN)
        | (group_edge[..., -2] - group_edge[..., -1] < LOW_MARGIN))
    free = c >= jax.lax.top_k(c, k)[0][..., -1:]
    dropped = jnp.mean((free & ~jnp.repeat(
        kept, grouped.shape[-1], axis=-1)).any(axis=-1))
    rows = chosen.sum(axis=tuple(range(chosen.ndim - 1)))
    return gates, low, dropped, rows


def _experts(h, p, bias, m, fault):
    """(ffn(h), low-margin share, dropped share, rows an expert): every
    held expert computes every token, one after the other; the experts that
    are not here add nothing; the shared expert once."""
    first = int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    gates, low, dropped, rows = _chosen(scores, bias, m, fault)
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        return out + gate[..., None] * _swiglu(h, gate_w, up_w, down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    return out + _dense_mlp(h, p["shared_expert"]), low, dropped, rows


def reference(params, buffers, input_ids, labels, m, round_through=None,
              fault=None):
    """(loss of every token [B, S]; a routed layer each, in the stack's
    order: the share of tokens with a low margin of the choice, the share
    the groups drop an expert of, the rows each of the router's experts
    took [layers, E]; the half life in tokens a delta-rule layer) from the
    program's parameter tree (unboxed; a run of equal layers stacked under
    ``prefix/<run>`` ``[run, ...]`` and ``layers/<run>`` ``[periods, run,
    ...]``) and the state's buffers (the same paths, ``mlp/selection_bias``).
    The loops over periods and over a run are ``jax.lax.scan``s of the plain
    body.  ``fault``: one of ``FAULTS``."""
    eps = float(m["rms_norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        if round_through is None:
            return t
        # rounding in float32 arithmetic: the chip's compiler removes a
        # conversion there and back (``families/olmoe.py::_round_through``)
        return load_module("families", "olmoe")._round_through(t, round_through)

    def layer(entry):
        kind, _, ffn = entry.partition(":")

        def body(x, at):
            p, b = at
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            if kind == "kda":
                mixed, life = _delta_attention(h, p["attn"], m, fault)
            else:
                mixed, life = _latent_attention(h, p["attn"], m, fault), ()
            x = x + mixed
            h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn:
                return x + _dense_mlp(h, p["mlp"]), (life, ())
            out, *seen = _experts(
                h, p["mlp"], b["mlp"]["selection_bias"], m, fault)
            return x + out, (life, tuple(seen))
        return body

    def stack(entries, x, p, b):
        seen = {}
        for name, entry, _ in runs(entries):
            x, seen[name] = jax.lax.scan(
                layer(entry), x,
                (p[name]["layer"], b.get(name, {}).get("layer")))
        return x, seen

    def period(x, at):
        return stack(m["layer_pattern"], x, *at)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        seen_prefix = {}
        if m["layer_prefix"]:
            x, seen_prefix = stack(
                m["layer_prefix"], x, params["prefix"],
                buffers.get("prefix", {}))
        x, seen = jax.lax.scan(
            period, x, (params["layers"], buffers["layers"]))
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # [periods, run] a run -> the stack's order, the prefix's first
    in_prefix = [(entry, seen_prefix[name])
                 for name, entry, _ in runs(m["layer_prefix"])]
    in_periods = [(entry, seen[name])
                  for name, entry, _ in runs(m["layer_pattern"])]
    is_kda = lambda entry: entry.startswith("kda")  # noqa: E731
    life = jnp.concatenate(
        [s[0] for entry, s in in_prefix if is_kda(entry)]
        + [jnp.concatenate([s[0] for entry, s in in_periods if is_kda(entry)],
                           axis=1).ravel()])
    routed = [s[1] for entry, s in in_periods if ":" not in entry]
    low, dropped = (jnp.concatenate([s[i] for s in routed], axis=1).ravel()
                    for i in (0, 1))
    rows = jnp.concatenate([s[2] for s in routed], axis=1)
    return losses, low, dropped, rows.reshape(-1, rows.shape[-1]), life


def _report(low, dropped, life):
    print(json.dumps({
        "phase": "reference_ling3",
        "choice_low_margin": LOW_MARGIN,
        "choice_low_margin_share_by_layer": [float(v) for v in low],
        "choice_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        "group_dropped_share_by_layer": [float(v) for v in dropped],
        "kda_decay_half_life_by_layer": [float(v) for v in life]}),
        file=sys.stderr, flush=True)


def _buffers_of(buffers):
    import flax.linen as nn

    buffers = _STATE["buffers"] if buffers is None else buffers
    if buffers is None:
        raise RuntimeError("no state made by condition() yet: the reference "
                           "has no selection bias to read")
    return nn.meta.unbox(buffers)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      buffers=None, **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each routed layer's tokens with a low margin
    of the choice, which it holds to ``LOW_MARGIN_SHARE_MAX``).  The
    counters that say the mechanisms decide something on this state go to
    standard error.  ``buffers``: the state's; ``None``: those of the state
    ``condition`` last made."""
    losses, low, dropped, _, life = reference(
        params, _buffers_of(buffers), input_ids, labels,
        sizes(config, rehearse), **planted)
    jax.debug.callback(_report, low, dropped, life)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
