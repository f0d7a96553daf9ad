"""Kanana-2-30B-A3B (``kakaocorp/kanana-2-30b-a3b-instruct-2601``,
``model_type`` ``deepseek_v3``) through the program's one decoder
(``models/llama.py``): latent attention (MLA without a query bottleneck:
scores in two products, one rotary key every head shares;
``ops/attention.py::latent_attention``) in EVERY layer, at all 32 heads,
rotary by interleaved pairs (``mla_rope_interleave``); a leading layer with
a dense SwiGLU, then ``models/moe.py``'s routed block: sigmoid scores, the
six largest of 128 under a selection bias the load moves (one group: no
choice by groups), renormalised weights times 2.448, two shared experts as
one SwiGLU of 1536; told which experts of the layer this chip holds.  Built
from a configuration file, with its counts of operations and bytes and its
plain reference (the benchmark's copy of
``dlrover_tpu/models/kanana2_reference.py``, which states the layers
equation by equation).

In the file ``n_routed_experts`` is the experts HELD HERE (``reduced``) and
``published.n_routed_experts`` the router's width; ``run.first_expert`` says
which.  The vocabulary in the file is this chip's share too; the heads are
whole.

**What is Ling-3.0's is imported, not copied** (``families/ling3.py``): the
count of the latent core's operations and bytes (``mla_step_flops``,
``mla_step_bytes``: both cells' roofline reads ONE count of the work the
model asks), the drawn selection bias, the runs of the stack, and the shell
that hands ``model.apply`` the buffers of the state ``condition`` made where
the harness names none (``jobs_shared.reference_check``)."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

#: loaded ONCE: ``load_module`` makes a new module a call, and the shell and
#: the reference must read the ``_STATE`` that ``condition`` wrote
_ling = load_module("families", "ling3")

mla_step_flops = _ling.mla_step_flops
mla_step_bytes = _ling.mla_step_bytes
runs, stacks = _ling.runs, _ling.stacks
#: rounding in float32 arithmetic: the chip's compiler removes a conversion
#: there and back (``families/olmoe.py::_round_through``)
_round_through = load_module("families", "olmoe")._round_through

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "n_shared_experts": 2,
        "num_experts_per_tok": 3, "routed_scaling_factor": 2.448,
        "max_position_embeddings": 128,
        "published": {"n_routed_experts": 16}}

#: published keys the program has one path for: only these values run
ONLY = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_method": "noaux_tc"}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    dense = int(src["first_k_dense_replace"])
    if not 0 < dense < int(src["num_hidden_layers"]):
        raise ValueError("first_k_dense_replace leaves no routed layer, or "
                         "no dense one")
    return {**src,
            "experts_total": int(src["published"]["n_routed_experts"]),
            "first_expert": first,
            "bias_update_rate": float(
                config.get("assumed", {}).get("bias_update_rate", 0.001)),
            # one kind all the way down; the period is one layer
            "layer_prefix": ("mla:dense",) * dense,
            "layer_pattern": ("mla",),
            # queries a block of the reference's attention, against every
            # key at every head: 128 x 16,384 x 32 float32 scores
            "query_block": 128}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if "mla_rope_interleave" not in fields:
        raise RuntimeError(
            "this checkout's latent attention turns its rotary columns by "
            "halves alone (MoELlamaConfig has no mla_rope_interleave): it "
            "cannot run Kanana-2's rotary by interleaved pairs")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    _ling._STATE["buffers"] = None
    heads = m["num_attention_heads"]
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        # MLA has one latent for every head: a key head a query head.
        # ``head_dim`` (the source's 64 is its rotary width) is read by no
        # ``mla`` layer
        num_heads=heads, num_kv_heads=heads,
        max_seq_len=seq, rms_norm_eps=float(m["rms_norm_eps"]),
        rope_theta=float(m["rope_theta"]),
        layer_prefix=m["layer_prefix"], layer_pattern=m["layer_pattern"],
        mla_kv_rank=m["kv_lora_rank"], mla_nope_dim=m["qk_nope_head_dim"],
        mla_rope_dim=m["qk_rope_head_dim"], mla_v_dim=m["v_head_dim"],
        mla_head_gate=False, mla_rope_interleave=True,
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        shared_experts=m["n_shared_experts"],
        shared_intermediate_size=m["moe_intermediate_size"],
        experts_held=m["n_routed_experts"], first_expert=m["first_expert"],
        # the published ``n_group`` 1 / ``topk_group`` 1 keeps its one group
        # always: no groups
        n_group=0, topk_group=0,
        selection_bias=True, bias_update_rate=m["bias_update_rate"],
        # ``noaux_tc`` is loss-free: no term in the objective
        load_balance_coef=0.0, router_z_coef=0.0,
        # a rehearsal compares a few hundred tokens, whose bfloat16 mean is
        # noise: it walks the harness in float32
        **({"dtype": jnp.float32} if rehearse else {}),
    )
    return _ling._WithStateBuffers(LlamaForCausalLM(cfg))


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}, read from the configuration file (none where the
    file names no ``run.state``): the embedding table times ``embed_scale``;
    each held expert's gate and up matrices times the square root of the
    number held and its down matrix by that times ``expert_out_scale``;
    every layer's query, down and output projections times ``q_scale``,
    ``latent_scale`` and ``mla_out_scale`` (a key that is absent is 1, as
    ``mla_out_scale`` is in the file: ``tests/precision_kanana2.py
    --rules`` swept it)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    scale = lambda key: float(state.get(key, 1.0))  # noqa: E731
    held = float(m["n_routed_experts"]) ** 0.5
    rule = {("embed_tokens",): scale("embed_scale")}
    for layer, _, entry in stacks(m):
        if ":" not in entry:
            rule.update({
                layer + ("mlp", "gate_proj"): held,
                layer + ("mlp", "up_proj"): held,
                layer + ("mlp", "down_proj"): held * scale("expert_out_scale")})
        attn = layer + ("attn",)
        rule.update({
            attn + ("q_proj", "kernel"): scale("q_scale"),
            attn + ("kv_a_proj", "kernel"): scale("latent_scale"),
            attn + ("o_proj", "kernel"): scale("mla_out_scale")})
    return {path: factor for path, factor in rule.items() if factor != 1.0}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors (same tree, shardings and dtypes, one multiply a leaf on
    the device, no forward pass, no look at a batch), and with the
    selection bias of every routed layer drawn with spread
    ``run.state.bias_spread`` (``families/ling3.py::drawn_bias``; the
    initialiser's 0 where the file names none): at 0 a fault in what the
    bias does is invisible.  Why each factor: under ``TOKEN_ATOL``."""
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
        buffers = jax.jit(lambda params: _ling.drawn_bias(
            params, buffers, spread))(nn.meta.unbox(state.params))
    state = state.replace(
        params=jax.tree_util.tree_map_with_path(scaled, state.params),
        buffers=buffers)
    _ling._STATE["buffers"] = state.buffers
    return state


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_counts(m):
    """(dense layers, routed layers) of the stack."""
    dense = len(m["layer_prefix"])
    return dense, m["num_hidden_layers"] - dense


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: every layer's
    query, down, up and output projections at all the heads; the dense
    layer's SwiGLU; in a routed layer the router, the shared SwiGLU and of
    the routed experts what a token's ``num_experts_per_tok`` assignments
    meet here under even routing (``k * held / all`` experts: three
    quarters of one, at 6 a token and 16 of 128 held); the output head.
    Not the embedding table or the norms."""
    m = sizes(config, rehearse)
    h, heads = m["hidden_size"], m["num_attention_heads"]
    nope, rope, wide = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"])
    latent = (h * heads * (nope + rope) + h * (m["kv_lora_rank"] + rope)
              + m["kv_lora_rank"] * heads * (nope + wide) + heads * wide * h)
    expert = 3 * h * m["moe_intermediate_size"]
    met = (m["num_experts_per_tok"] * m["n_routed_experts"]
           / m["experts_total"])
    routed = (h * m["experts_total"] + m["n_shared_experts"] * expert
              + met * expert)
    n_dense, n_routed = layer_counts(m)
    return (n_dense * (latent + 3 * h * m["intermediate_size"])
            + n_routed * (latent + routed) + h * m["vocab_size"])


def mla_shape(config, batch, seq, rehearse=False):
    """The shapes the latent-attention core works on in one step: every
    layer of the stack, all the heads."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["num_attention_heads"],
            "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
            "v": m["v_head_dim"], "layers": m["num_hidden_layers"]}


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` and the latent
    attention's core as the model asks for it (``mla_step_flops``)."""
    return (6 * matmul_params(config, rehearse)
            + mla_step_flops(mla_shape(config, 1, seq, rehearse)) / seq)


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no sort of assignments, no
# sharding, no remat; the attention a block of queries at a time against
# every key, every held expert looped over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores and the softmax
#: in float32); the reference is float32 throughout.  Beside the rounding a
#: dense model shows, one choice is discontinuous: a margin of the choice
#: under the bfloat16 error of the hidden state flips an expert
#: (``LOW_MARGIN``), and a flip here weighs 2.448 / 6 of an expert's whole
#: result: the system's worst tokens are flips.  Each limit stands between
#: readings on the chip at the published widths and the cell's own size (one
#: sequence of 16,384, six layers), on the state ``condition`` gives
#: (``tests/precision_kanana2.py``, each set of losses through
#: ``jobs_shared.compare_losses``; my chip runs, PR 55: twelve seeds
#: ..102-..113 of the system through the tool, the control and every fault
#: on five of them, ..103 and ..110-..113 (the control and four faults
#: also on ..102), and the system again in the cell's own runs; PERF.md
#: section 6 has the sweep):
#:
#:                  system            float8 control    the mildest faults it catches
#:   worst token    0.454-0.869       0.672-0.910       0.640-0.694 (scores over sqrt(128)), 0.736-0.859 (2.448 left out)
#:   median token   0.00657-0.00704   0.0469-0.0478     0.0487-0.0506 (scores over sqrt(128)),
#:                                                      0.0900-0.0937 (2.448 left out), 0.0964-0.107 (softmax scores)
#:   mean           8.8e-6-4.3e-4     7.9e-5-1.7e-3     3.4e-5-1.3e-2 (all eight it catches)
#:
#: (the other five it catches read a median of 0.162-0.479 on every seed:
#: the shared SwiGLU left out 0.162-0.166, ``k_pe`` not rotated
#: 0.169-0.173, rotary by halves on the pairs' layout 0.172-0.176, the
#: latent's norm left out 0.438-0.448, the weights not renormalised
#: 0.463-0.479.)  **The median holds the cell**: steady to 7% over twelve
#: seeds, the control's smallest of six seeds 0.0469, 6.7 times, and the
#: mildest fault's smallest 0.0487, 6.9 times the system's largest, so
#: ``MEDIAN_ATOL`` 0.018 stands 2.6 times over the one and 2.6 times under
#: the other.  **The worst token cannot tell the control from the system**
#: (0.672-0.910 beside 0.454-0.869: both are flips of an expert):
#: ``TOKEN_ATOL`` 1.7 is kept as a guard for a token or a row gone wrong
#: and nothing finer, 1.96 times the largest of twelve seeds and 1.46
#: times under the smallest reading of the two faults that break a row's
#: scale (the norm left out 2.48-3.19, the weights not renormalised
#: 3.06-3.40, five seeds each).  ``MEAN_ATOL`` 1.2e-3 is 2.8 times over
#: the system's largest in the tool and 2.3 over the cell's own runs'
#: 5.2e-4 (16,384 token errors with a tail of flips: their mean alone
#: swings by 2.5e-4); it separates little: the control swings across it
#: (one seed of six over), as do seven faults; the norm left out
#: (4.5e-3-1.0e-2) and the weights not renormalised (3.2e-3-1.3e-2) read
#: over it on every seed.  **The limits differ from Ling-3.0's (0.7 /
#: 0.011 / 8e-4) because the state differs, not because the program does**:
#: the same kernels and router read a median of 0.0061-0.0064 on Ling's
#: state (``families/ling3.py``); this state has six latent layers in the
#: stream where Ling's has one, and under Ling's rule as it stands
#: (``mla_out_scale`` 3) the first two runs of the cell read 0.0203 and
#: 0.0212, ``correct: false`` under Ling's limits.  The rule was then
#: changed (3 -> 1: 0.0066-0.0070) and the limits refitted to this state's
#: readings (below): at Ling's 0.011 the median's limit would stand 1.56
#: times over the system's largest, at 0.018 it keeps 2.6 times from both
#: sides.
#: **Three planted readings are not caught at the timed sizes.**  The bias
#: added to the weights before they are divided by their sum moves the
#: median token by 0.0015-0.0018 at a spread of 0.01 (six seeds), a quarter of the
#: system's own distance from float32 (Ling-3.0's finding again: the
#: division takes it back).  And the same forward pass with ONE part
#: through bfloat16 reads UNDER the system, which is bfloat16 in all its
#: matmuls: the router's matmul and scores 0.0053-0.0058, the attention's
#: scores 0.0023-0.0031 beside 0.0066-0.0070 (the router's input is the
#: bfloat16 stream in the system too, and a flip weighs the same whoever
#: causes it).  These three are held on the CPU in float32, where the
#: first reads a hundred times the agreement
#: (``tests/test_correct_kanana2.py``) and the kernels' float32 softmax is
#: held to ``jax.numpy``'s (``tests/test_latent_attention_kernels.py``); on
#: the chip at this cell's shape by ``scripts/latent_alone.py --shape
#: 1,16384,32`` alone (float32 operands against the ``jax.numpy`` core at
#: 2048 positions: the gradients within 3.4e-4, the summed output within
#: 3.4e-7 relative), which a change to the kernels' softmax or to the
#: router has to bring its own reading of: ``correct`` cannot see it
#: (PERF.md section 7).
#:
#: **Why the state's factors** (``condition``; PERF.md section 6 has the
#: sweep, seven rules at one seed each).  ``embed_scale`` 300 and each held
#: expert's matrices times sqrt(16), as Solar-Open2's, Ling's and Laguna's
#: cells, so that uniform random tokens spread over the 128 experts (this
#: chip's rows 0.904-1.108 of a fair share by layer over nine seeds, the
#: hottest expert 1.34-1.72 of the mean: the ladder's first extent holds
#: 1.25 of a fair share) and the initialiser's count of the expert axis
#: into the fan-in is undone; ``expert_out_scale`` 3, ``latent_scale`` 3
#: and ``q_scale`` 2 as Ling's (a token meets three quarters of an expert
#: here; the latent's norm has something to take back; the softmax sits
#: where the scores' scale shows: at ``q_scale`` 1 scores over sqrt(128)
#: falls from 7.6 to 4.3 times the system's median).  **``mla_out_scale``
#: stays 1**, where Ling's one latent layer of seven has 3: with six
#: latent layers at 3 the system reads 0.62-0.67 / 0.0203-0.0213 and the
#: control 5.0 times that; at 2 0.657 / 0.0129 and 5.4 times; at 1 0.454
#: / 0.0066 and 7.1 times, every fault of the attention still 7 to 66
#: times the system's median.  ``expert_out_scale`` 1 halves the router's
#: faults (2.448 left out 0.0408 beside a system of 0.0117).
#: ``bias_spread`` 0.01 and the routers as the initialiser leaves them, as
#: Ling's.
TOKEN_ATOL = 1.7
MEDIAN_ATOL = 1.8e-2
MEAN_ATOL = 1.2e-3
#: a margin of the choice (in ``scores + bias``) that bfloat16 arithmetic
#: upstream can cross
LOW_MARGIN = 1e-3
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above, or PERF.md names the one that does not
FAULTS = ("rope_halves", "k_pe_not_rotated", "scale_by_nope",
          "no_latent_norm", "bias_in_weights", "not_renormalised",
          "no_scaling_factor", "no_shared_experts", "softmax_scores")
#: the same forward pass at a precision below the configuration's: the
#: router's matmul and scores, or the attention's scores, through bfloat16
LOWER_PRECISION = ("bfloat16_router", "bfloat16_scores")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _bfloat16(t):
    return _round_through(t, jnp.bfloat16)


def _rope(x, theta, fault):
    """[B, S, H, D] at positions ``0..S-1``: the neighbours ``(x[2i],
    x[2i+1])`` turned by ``p theta^(-2i/D)``, each result where its operand
    stood (``rope_halves``: column ``i`` with ``i + D/2``, the convention
    the published layout is NOT in)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    if fault == "rope_halves":
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _latent_attention(h, p, m, fault):
    """MLA, a block of queries at a time against every key, the scores in
    the two products of the published equation."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    nope, rank = int(m["qk_nope_head_dim"]), int(m["kv_lora_rank"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    down = h @ p["kv_a_proj"]["kernel"]
    latent = down[..., :rank]
    if fault != "no_latent_norm":
        latent = _rms_norm(latent, p["kv_a_norm"]["scale"], eps)
    up = jnp.einsum("bsr,rhd->bshd", latent, p["kv_b_proj"]["kernel"])
    k_nope, v = up[..., :nope], up[..., nope:]
    k_pe = down[..., None, rank:]
    if fault != "k_pe_not_rotated":
        k_pe = _rope(k_pe, theta, fault)
    k_pe = k_pe[:, :, 0]
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], theta, fault)
    B, S, heads, _ = q.shape
    scale = (nope if fault == "scale_by_nope" else q.shape[-1]) ** -0.5
    block = min(int(m["query_block"]), S)

    def one_block(first):
        rows = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, first, block, 1)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", rows(q_nope), k_nope)
                  + jnp.einsum("bqhr,bkr->bhqk", rows(q_pe), k_pe)) * scale
        if fault == "bfloat16_scores":
            scores = _bfloat16(scores)
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads, v.shape[-1])
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def _dense_mlp(h, p):
    return _swiglu(h, *(p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj")))


def _experts(h, p, bias, m, fault):
    """(ffn(h), share of tokens with a low margin of the choice, rows each
    of the router's experts took): ``s = sigmoid(h W_r)``; the choice the k
    largest of ``s + b``; the weights ``s / (sum of the chosen + 1e-20) *
    factor``; every held expert computes every token, one after the other;
    the experts that are not here add nothing; the shared SwiGLU once."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    if fault == "bfloat16_router":
        logits = _bfloat16(_bfloat16(h) @ _bfloat16(p["router"]["kernel"]))
    else:
        logits = h @ p["router"]["kernel"]
    scores = (jax.nn.softmax(logits, axis=-1) if fault == "softmax_scores"
              else jax.nn.sigmoid(logits))
    if fault == "bfloat16_router":
        scores = _bfloat16(scores)
    c = scores + bias
    edge = jax.lax.top_k(c, k + 1)[0]
    chosen = c >= edge[..., k - 1: k]
    gates = jnp.where(
        chosen, c if fault == "bias_in_weights" else scores, 0.0)
    if fault != "not_renormalised":
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    if fault != "no_scaling_factor":
        gates = gates * float(m["routed_scaling_factor"])
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        return out + gate[..., None] * _swiglu(h, gate_w, up_w, down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    if fault != "no_shared_experts":
        out = out + _dense_mlp(h, p["shared_expert"])
    low = jnp.mean(edge[..., k - 1] - edge[..., k] < LOW_MARGIN)
    return out, low, chosen.sum(axis=tuple(range(chosen.ndim - 1)))


def reference(params, buffers, input_ids, labels, m, round_through=None,
              fault=None):
    """(loss of every token [B, S]; a routed layer each, in the stack's
    order: the share of tokens with a low margin of the choice, and the rows
    each of the router's experts took [layers, E]) from the program's
    parameter tree (unboxed; a run of equal layers stacked under
    ``prefix/<run>`` ``[run, ...]`` and ``layers/<run>`` ``[periods, run,
    ...]``) and the state's buffers (the same paths,
    ``mlp/selection_bias``).  The loops over periods and over a run are
    ``jax.lax.scan``s of the plain body: one layer's temporaries at a time
    beside the training state.  ``fault``: one of ``FAULTS`` or of
    ``LOWER_PRECISION``."""
    eps = float(m["rms_norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if round_through is None else _round_through(
            t, round_through)

    def layer(entry):
        def body(x, at):
            p, b = at
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + _latent_attention(h, p["attn"], m, fault)
            h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ":" in entry:
                return x + _dense_mlp(h, p["mlp"]), ()
            out, low, rows = _experts(
                h, p["mlp"], b["mlp"]["selection_bias"], m, fault)
            return x + out, (low, rows)
        return body

    def stack(entries, x, p, b):
        seen = {}
        for name, entry, _ in runs(entries):
            x, seen[name] = jax.lax.scan(
                layer(entry), x,
                (p[name]["layer"], b.get(name, {}).get("layer")))
        return x, seen

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, _ = stack(m["layer_prefix"], x, params["prefix"],
                     buffers.get("prefix", {}))
        x, seen = jax.lax.scan(
            lambda x, at: stack(m["layer_pattern"], x, *at), x,
            (params["layers"], buffers["layers"]))
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # [periods, run] a run -> the stack's order (the prefix is dense)
    low, rows = seen[runs(m["layer_pattern"])[0][0]]
    return losses, low.ravel(), rows.reshape(-1, rows.shape[-1])


def _report(low, rows, first, held):
    rows = [[int(n) for n in layer] for layer in rows]
    print(json.dumps({
        "phase": "reference_kanana2",
        "choice_low_margin": LOW_MARGIN,
        "choice_low_margin_share_by_layer": [float(v) for v in low],
        "choice_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        # this chip's rows over a fair share, and the hottest expert's load
        "share_rows_over_expected_by_layer": [
            sum(layer[first: first + held]) * len(layer) / (
                held * max(sum(layer), 1)) for layer in rows],
        "load_max_over_mean_by_layer": [
            max(layer) * len(layer) / max(sum(layer), 1) for layer in rows]}),
        file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      buffers=None, **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each routed layer's tokens with a low margin
    of the choice, which it holds to ``LOW_MARGIN_SHARE_MAX``).  The load
    the routing puts on this chip's experts goes to standard error.
    ``buffers``: the state's; ``None``: those of the state ``condition``
    last made."""
    m = sizes(config, rehearse)
    losses, low, rows = reference(
        params, _ling._buffers_of(buffers), input_ids, labels, m, **planted)
    jax.debug.callback(
        lambda low, rows: _report(
            low, rows, m["first_expert"], m["n_routed_experts"]), low, rows)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
