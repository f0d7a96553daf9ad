"""LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B``, ``model_type`` ``lfm2_moe``)
through the program's one decoder (``models/llama.py``): three layers of
four have a double-gated short convolution as their mixer (``conv``:
``ShortConvMixer`` over ``ops/short_conv.py``, no positions, no softmax, no
state beyond two taps), the fourth a softmax layer of GQA heads of 64 with
per-head q/k norms before RoPE (the FA2 kernels on the chip); a leading
layer with a dense SwiGLU, then ``models/moe.py``'s routed block: sigmoid
scores, the four largest of 64 under a selection bias the load moves,
weights over their sum ``+ 1e-6``, no shared expert; the head is the
embedding table.  Told which experts of the layer this chip holds.  Built
from a configuration file, with its counts of operations and bytes and its
plain reference (the benchmark's copy of
``dlrover_tpu/models/lfm2_reference.py``, which states the layers equation
by equation).

In the file ``num_experts`` is the experts HELD HERE (``reduced``) and
``published.num_experts`` the router's width; ``run.first_expert`` says
which.  The vocabulary in the file is this chip's share too; heads and the
convolution's channels are whole.  ``layer_types`` is the published list of
40: the cut keeps the LAST of the leading dense layers and the layers after
it (``num_dense_layers`` and ``num_hidden_layers`` of the file against
``published``'s), so published layer 1 and layers 2 to 9.

**What is Ling-3.0's is imported, not copied** (``families/ling3.py``): the
drawn selection bias, the runs of a stack, and the shell that hands
``model.apply`` the buffers of the state ``condition`` made where the
harness names none (``jobs_shared.reference_check``)."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

#: loaded ONCE: ``load_module`` makes a new module a call, and the shell and
#: the reference must read the ``_STATE`` that ``condition`` wrote
_ling = load_module("families", "ling3")
runs, stacks = _ling.runs, _ling.stacks
#: rounding in float32 arithmetic: the chip's compiler removes a conversion
#: there and back (``families/olmoe.py::_round_through``)
_round_through = load_module("families", "olmoe")._round_through

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "num_dense_layers": 1, "num_attention_heads": 4,
        "num_key_value_heads": 2, "norm_eps": 1e-5, "conv_L_cache": 3,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
        "num_experts": 4, "num_experts_per_tok": 3,
        "routed_scaling_factor": 1, "max_position_embeddings": 128,
        "published": {"num_experts": 16, "num_dense_layers": 2}}

#: published keys the program has one path for: only these values run
ONLY = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1}

#: a ``layer_types`` entry -> the program's kind
KIND_OF = {"conv": "conv", "full_attention": "gqa"}

#: what the published code adds to the sum of the chosen scores
NORM_TOPK_EPS = 1e-6


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    dense, depth = int(src["num_dense_layers"]), int(src["num_hidden_layers"])
    # the leading dense layers left out are the first ones
    skipped = int(src["published"]["num_dense_layers"]) - dense
    kinds = [KIND_OF[t] for t in src["layer_types"][skipped: skipped + depth]]
    if not 0 < dense < depth:
        raise ValueError("num_dense_layers leaves no routed layer, or no "
                         "dense one")
    # the shortest pattern whose periods spell the routed layers
    body = tuple(kinds[dense:])
    period = next(n for n in range(1, len(body) + 1)
                  if not len(body) % n and body == body[:n] * (len(body) // n))
    rope = src["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type={rope['rope_type']!r}: the program "
                         "runs plain RoPE here")
    return {**src,
            "head_dim": src.get("head_dim") or (
                int(src["hidden_size"]) // int(src["num_attention_heads"])),
            "rope_theta": float(rope["rope_theta"]),
            "experts_total": int(src["published"]["num_experts"]),
            "first_expert": first,
            "bias_update_rate": float(
                config.get("assumed", {}).get("bias_update_rate", 0.001)),
            "layer_prefix": tuple(kind + ":dense" for kind in kinds[:dense]),
            "layer_pattern": body[:period],
            # queries a block of the reference's attention, against every
            # key at every head: 128 x 16,384 x 32 float32 scores
            "query_block": 128}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LAYER_KINDS, LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if "conv" not in LAYER_KINDS or "norm_topk_eps" not in fields:
        raise RuntimeError(
            "this checkout's models have no layer whose mixer is a gated "
            "short convolution (LAYER_KINDS has no 'conv'): it cannot run "
            "LFM2")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    _ling._STATE["buffers"] = None
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["norm_eps"]),
        rope_theta=m["rope_theta"], qk_norm="head", tie_embeddings=True,
        conv_taps=m["conv_L_cache"],
        layer_prefix=m["layer_prefix"], layer_pattern=m["layer_pattern"],
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, norm_topk_eps=NORM_TOPK_EPS,
        router_scores="sigmoid",
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        experts_held=m["num_experts"], first_expert=m["first_expert"],
        selection_bias=True, bias_update_rate=m["bias_update_rate"],
        # the bias balances without a loss: no term in the objective
        load_balance_coef=0.0, router_z_coef=0.0,
        # the kernel, or (rehearsal, on the CPU) the jnp path: never a
        # silent change of path, "flash" raises off the chip.  A rehearsal
        # compares a few hundred tokens, whose bfloat16 mean is noise: it
        # walks the harness in float32
        **({"dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return _ling._WithStateBuffers(LlamaForCausalLM(cfg))


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}, read from the configuration file (none where the
    file names no ``run.state``): the embedding table times ``embed_scale``
    (the head is tied: a factor on the table is a factor on the logits);
    each held expert's gate and up matrices times the square root of the
    number held and its down matrix by that times ``expert_out_scale``;
    every ``conv`` mixer's output projection times ``conv_out_scale`` and
    its taps times ``tap_scale``; every softmax layer's output projection
    times ``attn_out_scale`` and its two per-head norms' scales times
    ``qk_norm_scale`` (a factor on ``W_q`` the norm would take back).  A
    key that is absent is 1."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    scale = lambda key: float(state.get(key, 1.0))  # noqa: E731
    held = float(m["num_experts"]) ** 0.5
    rule = {("embed_tokens",): scale("embed_scale")}
    for layer, _, entry in stacks(m):
        kind, _, ffn = entry.partition(":")
        if ffn != "dense":
            rule.update({
                layer + ("mlp", "gate_proj"): held,
                layer + ("mlp", "up_proj"): held,
                layer + ("mlp", "down_proj"): held * scale("expert_out_scale")})
        attn = layer + ("attn",)
        if kind == "conv":
            rule.update({
                attn + ("out_proj", "kernel"): scale("conv_out_scale"),
                attn + ("conv_weight",): scale("tap_scale")})
        else:
            rule.update({
                attn + ("o_proj", "kernel"): scale("attn_out_scale"),
                attn + ("q_norm", "scale"): scale("qk_norm_scale"),
                attn + ("k_norm", "scale"): scale("qk_norm_scale")})
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
    """{``"conv"``, ``"gqa"``, ``"dense"``, ``"routed"``: layers of the
    stack with such a mixer, such a feed-forward}."""
    periods = (m["num_hidden_layers"] - len(m["layer_prefix"])) // len(
        m["layer_pattern"])
    entries = m["layer_prefix"] + m["layer_pattern"] * periods
    mixers = [entry.partition(":")[0] for entry in entries]
    return {"conv": mixers.count("conv"), "gqa": mixers.count("gqa"),
            "dense": len(m["layer_prefix"]),
            "routed": len(entries) - len(m["layer_prefix"])}


def layer_params(m):
    """{part: parameters a token multiplies with in one such part on this
    chip}: a ``conv`` mixer's ``W_in`` and ``W_out``; a softmax mixer's
    four projections; the dense SwiGLU; in a routed feed-forward the
    router's columns (all of them) and of the routed experts what a token's
    ``num_experts_per_tok`` assignments meet here under even routing (``k *
    held / all`` experts: 4 x 8 / 64, half of one)."""
    h, d = m["hidden_size"], m["head_dim"]
    met = m["num_experts_per_tok"] * m["num_experts"] / m["experts_total"]
    return {"conv": h * 3 * h + h * h,
            "gqa": h * d * 2 * (m["num_attention_heads"]
                                + m["num_key_value_heads"]),
            "dense": 3 * h * m["intermediate_size"],
            "routed": (h * m["experts_total"]
                       + met * 3 * h * m["moe_intermediate_size"])}


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: every layer's
    (``layer_params``) and the tied output head.  Not the embedding's
    look-up, the norms or the taps."""
    m = sizes(config, rehearse)
    a_part = layer_params(m)
    return sum(n * a_part[part] for part, n in layer_counts(m).items()) + (
        m["hidden_size"] * m["vocab_size"])


def gconv_shape(config, batch, seq, rehearse=False):
    """The shapes the gated short convolution's core works on in one
    step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "channels": m["hidden_size"],
            "taps": m["conv_L_cache"], "layers": layer_counts(m)["conv"]}


def gconv_step_bytes(shape, itemsize=2):
    """Least bytes the cores move to and from HBM in one step: ``B``, ``C``
    and ``u`` read and the result written once forward; backward ``B``,
    ``C``, ``u`` and the result's gradient read and the three operands'
    gradients written once, all in the compute dtype.  Never ``v = B * u``
    or the taps' sum ``c``, never a second forward pass: whatever body runs
    moves at least these."""
    one = shape["batch"] * shape["seq"] * shape["channels"] * itemsize
    return shape["layers"] * (4 + 7) * one


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` and the
    softmax layers' causal core (``benchmarks/flops.py``).  The gated
    convolution's ten or so elementwise operations a channel are not
    counted: they are no matrix unit's work (``gconv_roofline_pct`` holds
    them to the memory's rate)."""
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    return train_flops_per_token(
        matmul_params(config, rehearse), layer_counts(m)["gqa"],
        m["num_attention_heads"] * m["head_dim"], seq)


def fa2_shape(config, batch_per_chip, seq):
    """Shape of one call of the FA2 kernels on one chip, and how often a
    step calls each: the softmax layers alone, one a period.  At heads of
    64 a block of the kernels holds two heads, so the backward is the split
    pair at every length (``ops/pallas/flash_attention.py::backward_path``:
    ``attention.path ... heads_per_block=2 backward=split``), the forward's
    ``out`` and LSE are not kept and the forward runs again under ``remat``
    (a run of one inside a scan over two periods is a loop the compiler
    does not unroll): four forward calls, two dQ and two dK/dV a step (my
    chip run, PR 66: ``%attn._attend`` twice a step in each loop's
    body)."""
    m = sizes(config, False)
    layers = layer_counts(m)["gqa"]
    return {"batch": batch_per_chip, "seq": seq,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "causal": True,
            "calls_per_step": {"fwd": 2 * layers, "dq": layers,
                               "dkv": layers}}


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy at "highest", no kernel, no sort of
# assignments, no sharding, no remat; the convolution three shifted
# multiplies, the attention a block of queries at a time against every key,
# every held expert looped over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores, the softmax and
#: the gates and taps of a ``conv`` layer in float32 on bfloat16 operands);
#: the reference is float32 throughout.  Beside the rounding a dense model
#: shows, one choice is discontinuous: a margin of the choice under the
#: bfloat16 error of the hidden state flips an expert (``LOW_MARGIN``), and
#: a flip here weighs a quarter of an expert's whole result.  Each limit
#: stands between readings on the chip at the published widths and the
#: cell's own size (one sequence of 16,384, nine layers), on the state
#: ``condition`` gives (``tests/precision_lfm2.py``, each set of losses
#: through ``jobs_shared.compare_losses``; my chip runs, PR 66: the system,
#: the control and every fault on seeds ..102-..105 through the tool, and
#: the system again in eight runs of the cell, twelve seeds in all; PERF.md
#: section 6 has the sweep of the rule):
#:
#:                  system            float8 control    the mildest faults it catches
#:   worst token    0.756-1.127       1.78-1.92         1.27-1.64 (the bias left out of the choice), 2.04-2.68 (the q/k norm left out)
#:   median token   0.0284-0.0313     0.270-0.278       0.118-0.134 (the bias left out of the choice), 0.209-0.214 (the q/k norm left out)
#:   mean           6.0e-5-1.6e-3     2.1e-4-4.1e-3     4.4e-4-2.5e-2 (all six)
#:
#: (the other four it catches read a median of 0.51-0.87 on every seed: the
#: weights not renormalised 0.512-0.522, SiLU put on the taps 0.706-0.712,
#: the taps shifted by one position 0.859-0.869, the ``B`` gate left out
#: 0.862-0.868: 17 to 29 times the system's.)  **The median holds the
#: cell**: steady to 5% over twelve seeds, the control's smallest 8.6 times
#: and the mildest fault's smallest 3.8 times the system's largest, so
#: ``MEDIAN_ATOL`` 0.06 stands 1.9 times over the one and 2.0 times under
#: the other.  **It is five times the other routed families' 0.011-0.018
#: because the state differs, not the arithmetic**: their streams are
#: dominated by an embedding table times 300, which is exact in both
#: programs and dilutes every branch's rounding; this model's head is the
#: table, so a factor on it is a factor on the logits (``embed_scale`` 4
#: read 0.264 with every fault in proportion: PERF.md section 6), the table
#: stays at the initialiser's 0.02 and the stream is the branches' sum: the
#: tied Phi-4-mini-flash reads 0.016-0.019 and the looped Ouro 0.016-0.021
#: for the same reason.  **The worst token is a flip of an expert and
#: swings with the seed** (twelve seeds: mean 0.90, spread 0.11):
#: ``TOKEN_ATOL`` 1.5 is there for a token or a row gone wrong, 1.33 times
#: the largest of twelve and 1.19 times under the control's smallest; the
#: bias left out of the choice reads on both sides of it (it is not correct
#: by the median on every seed).  It stood at 1.3 for the cell's first
#: eight runs, all correct; one read 1.127, too near for the driver's many
#: seeds.  The mean is the average of 16,384 token errors with a tail of
#: flips, and its distance from the reference's swings about zero by some
#: 9e-4: ``MEAN_ATOL`` 5e-3 is 3.1 times over the largest of twelve seeds
#: (3e-3 for the first eight runs, all correct: two standard deviations of
#: room were too few); the control reads under it on three seeds of four,
#: the three faults of the taps 1.3 to 5 times over it on most seeds: it
#: separates little, as in every routed family.  **One planted reading is
#: not caught, for the reason the other families found**: the two gates'
#: products and the taps' sum rounded through bfloat16 (the published
#: code's own arithmetic; ``LOWER_PRECISION``) read 0.0085-0.0087, UNDER
#: the system, which is bfloat16 in all its matmuls; on the CPU in float32
#: it reads a thousand times the agreement
#: (``benchmarks/tests/precision_lfm2.py --rehearse``: 1.1e-3 against 0).
#:
#: **Why the state's factors** (``condition``; PERF.md section 6 has the
#: sweep, seventeen rules over five seeds).  Each held expert's matrices
#: times sqrt(8), as every ``1ofN`` cell's: the initialiser's count of the
#: expert axis into the fan-in is undone.  ``qk_norm_scale`` 1.5: the two
#: per-head norms' scales start at 1 on projections that are unit-RMS
#: already, so at 1 the q/k norm left out reads 0.015-0.019, UNDER the
#: system; a factor on ``W_q`` the norm takes back; at 1.5 the scores' spread
#: is 2.25 and the fault reads 7 times the system; at 2 the softmax is sharp
#: enough that bfloat16's error in the scores doubles the system's median
#: (0.064) and the control falls to 6.3 times it.  ``bias_spread`` 0.02, a
#: spacing of the 4th and 5th of 64 sigmoid scores (0.0185 reckoned; 5% of
#: the tokens of a layer stand within ``LOW_MARGIN``): at 0.05 the share's
#: rows read 0.53-1.26 of a fair share and leave the ladder's first extent.
#: ``expert_out_scale`` and ``attn_out_scale`` stay 1: at 2 or 3 the worst
#: token doubles (flips times the factor, 1.7-2.2) and the control falls to
#: 6-8 times the system's median for nothing the median cannot see at 1.
#: ``embed_scale``, ``conv_out_scale`` and ``tap_scale`` stay 1: the taps'
#: share from earlier positions reads 0.615-0.63 at the initialiser's
#: values, inside the band the issue set (0.3-0.8), and every fault of the
#: convolution reads 24 to 29 times the system.  The routing of uniform
#: random tokens is LESS even than the other cells' (this chip's rows
#: 0.81-1.19 of a fair share by layer over six seeds, the hottest expert
#: 1.6-2.1 of the mean: no scaled table spreads the tokens), inside the
#: ladder's first extent of 1.25; inside the window the bias evens it.
TOKEN_ATOL = 1.5
MEDIAN_ATOL = 6e-2
MEAN_ATOL = 5e-3
#: a margin of the choice (in ``scores + bias``) that bfloat16 arithmetic
#: upstream can cross
LOW_MARGIN = 1e-3
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above, or PERF.md names the one that does not
FAULTS = ("no_b_gate", "taps_shifted", "silu_on_taps", "bias_not_in_choice",
          "no_qk_norm", "not_renormalised")
#: the same forward pass with ONE part at a precision below the one the
#: program states for it: the two gates' products and the taps' sum through
#: bfloat16 (the published code's own arithmetic)
LOWER_PRECISION = ("bfloat16_taps",)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _bfloat16(t):
    return _round_through(t, jnp.bfloat16)


def _short_conv(h, p, fault):
    """``(mixer(h), the share of the taps' result that the earlier
    positions make)``.  ``no_b_gate``: ``v = u``; ``taps_shifted``: every
    tap weighs the position one before its own; ``silu_on_taps``: the
    scans' convolution, SiLU on the sum."""
    wide = h.shape[-1]
    all_three = h @ p["in_proj"]["kernel"]
    B, C, u = (all_three[..., :wide], all_three[..., wide: 2 * wide],
               all_three[..., 2 * wide:])
    v, w = (u if fault == "no_b_gate" else B * u), p["conv_weight"]
    if fault == "bfloat16_taps":
        v = _bfloat16(v)
    taps, S = w.shape[0], v.shape[1]
    late = 1 if fault == "taps_shifted" else 0
    lead = jnp.pad(v, ((0, 0), (taps - 1 + late, 0), (0, 0)))
    parts = [lead[:, i: i + S] * w[i] for i in range(taps)]
    c = sum(parts)
    if fault == "silu_on_taps":
        c = jax.nn.silu(c)
    if fault == "bfloat16_taps":
        c = _bfloat16(c)
    past = jnp.abs(sum(parts[:-1])).mean()
    return ((C * c) @ p["out_proj"]["kernel"],
            past / (past + jnp.abs(parts[-1]).mean()))


def _rope(x, theta):
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, p, m, fault):
    """GQA, a block of queries at a time against every key."""
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    if fault != "no_qk_norm":
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    B, S, heads, d = q.shape
    kv = k.shape[2]
    q = q.reshape(B, S, kv, heads // kv, d)
    block = min(int(m["query_block"]), S)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", rows, k) * d ** -0.5
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads, d)
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def _experts(h, p, bias, m, fault):
    """(ffn(h), share of tokens with a low margin of the choice, rows each
    of the router's experts took): ``s = sigmoid(h W_r)``; the choice the k
    largest of ``s + b`` (``bias_not_in_choice``: of ``s``); the weights ``s
    / (sum of the chosen + 1e-6)`` times the factor; every held expert
    computes every token, one after the other; the experts that are not
    here add nothing."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    c = scores if fault == "bias_not_in_choice" else scores + bias
    edge = jax.lax.top_k(c, k + 1)[0]
    chosen = c >= edge[..., k - 1: k]
    gates = jnp.where(chosen, scores, 0.0)
    if fault != "not_renormalised":
        gates = gates / (gates.sum(axis=-1, keepdims=True) + NORM_TOPK_EPS)
    gates = gates * float(m.get("routed_scaling_factor", 1.0))
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        return out + gate[..., None] * _swiglu(h, gate_w, up_w, down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    low = jnp.mean(edge[..., k - 1] - edge[..., k] < LOW_MARGIN)
    return out, low, chosen.sum(axis=tuple(range(chosen.ndim - 1)))


def reference(params, buffers, input_ids, labels, m, round_through=None,
              fault=None):
    """(loss of every token [B, S]; a routed layer each, in the stack's
    order: the share of tokens with a low margin of the choice, and the rows
    each of the router's experts took [layers, E]; a ``conv`` layer each,
    the taps' share from earlier positions) from the program's parameter
    tree (unboxed; a run of equal layers stacked under ``prefix/<run>``
    ``[run, ...]`` and ``layers/<run>`` ``[periods, run, ...]``) and the
    state's buffers (the same paths, ``mlp/selection_bias``).  The loops
    over periods and over a run are ``jax.lax.scan``s of the plain body: one
    layer's temporaries at a time beside the training state.  ``fault``: one
    of ``FAULTS`` or of ``LOWER_PRECISION``."""
    eps = float(m["norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if round_through is None else _round_through(
            t, round_through)

    def layer(entry):
        kind, _, ffn = entry.partition(":")

        def body(x, at):
            p, b = at
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            seen = {}
            if kind == "conv":
                mixed, seen["past"] = _short_conv(h, p["attn"], fault)
            else:
                mixed = _attention(h, p["attn"], m, fault)
            x = x + mixed
            h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn == "dense":
                return x + _swiglu(h, *(p["mlp"][name]["kernel"] for name in (
                    "gate_proj", "up_proj", "down_proj"))), seen
            out, seen["low"], seen["rows"] = _experts(
                h, p["mlp"], b["mlp"]["selection_bias"], m, fault)
            return x + out, seen
        return body

    def stack(entries, x, p, b):
        seen = []
        for name, entry, _ in runs(entries):
            x, of_run = jax.lax.scan(
                layer(entry), x,
                (p[name]["layer"], b.get(name, {}).get("layer")))
            seen.append(of_run)
        return x, seen

    with jax.default_matmul_precision("highest"):
        table = f32(params["embed_tokens"])
        x = table[input_ids]
        x, first = stack(m["layer_prefix"], x, params["prefix"],
                         buffers.get("prefix", {}))
        x, body = jax.lax.scan(
            lambda x, at: stack(m["layer_pattern"], x, *at), x,
            (params["layers"], buffers["layers"]))
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ table.T, -1)      # the tied head
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    def in_order(key):
        """[periods, run(, E)] a run of the body -> the stack's order."""
        found = [run[key] for run in body if key in run]
        joined = jnp.concatenate(found, axis=1)
        return joined.reshape((-1,) + joined.shape[2:])

    past = jnp.concatenate(
        [run["past"] for run in first if "past" in run] + [in_order("past")])
    return losses, in_order("low"), in_order("rows"), past


def _report(low, rows, past, first, held):
    rows = [[int(n) for n in layer] for layer in rows]
    print(json.dumps({
        "phase": "reference_lfm2",
        "choice_low_margin": LOW_MARGIN,
        "choice_low_margin_share_by_layer": [float(v) for v in low],
        "choice_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        "gconv_past_tap_share_by_layer": [float(v) for v in past],
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
    the routing puts on this chip's experts and the taps' share from
    earlier positions go to standard error.  ``buffers``: the state's;
    ``None``: those of the state ``condition`` last made."""
    m = sizes(config, rehearse)
    losses, low, rows, past = reference(
        params, _ling._buffers_of(buffers), input_ids, labels, m, **planted)
    jax.debug.callback(
        lambda low, rows, past: _report(
            low, rows, past, m["first_expert"], m["num_experts"]),
        low, rows, past)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
