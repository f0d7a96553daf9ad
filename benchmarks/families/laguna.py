"""Laguna-XS.2 (``poolside/Laguna-XS.2``, ``model_type`` ``laguna``)
through the program's one decoder (``models/llama.py``): a leading
full-attention layer with a dense SwiGLU, then periods of three
sliding-window layers (64 query heads, a window of 512, plain RoPE over the
whole head at base 10,000) to one full-attention layer (48 query heads, RoPE
under YaRN on the first half of each head at base 500,000), all over 8 key
heads, every softmax layer's output gated a head; the period's layers
followed by ``models/moe.py``'s routed block: sigmoid scores, the eight
largest of 256 renormalised and times 2.5, a shared expert; told which
experts of the layer this chip holds.  On the chip both kinds of layer run
the FA2 kernels (``ops/pallas/flash_attention.py``), the window layers with
``window``.  Built from a configuration file, with its counts of operations
and bytes and its plain reference (the benchmark's copy of
``dlrover_tpu/models/laguna_reference.py``, which states the layers equation
by equation).

In the file ``num_experts`` is the experts HELD HERE (``reduced``) and
``published.num_experts`` the router's width; ``run.first_expert`` says
which.  The vocabulary in the file is this chip's share too; the heads are
whole."""

import dataclasses
import json
import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_hidden_layers": 5, "num_attention_heads": 6,
        "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
        "num_experts": 4, "num_experts_per_tok": 4,
        "moe_routed_scaling_factor": 2.5, "sliding_window": 16,
        "max_position_embeddings": 128, "published": {"num_experts": 16},
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 64, "beta_slow": 1,
                "beta_fast": 8, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6]}

#: published keys the program has one path for: only these values run
ONLY = {"attention_bias": False, "tie_word_embeddings": False,
        "gating": True, "moe_apply_router_weight_on_input": False}

KIND_OF = {"full_attention": "gqa", "sliding_attention": "swa"}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    n = int(src["num_hidden_layers"])
    # the three lists a layer stand in the file as published, whole: the
    # stack is their first ``num_hidden_layers`` entries
    lists = [src[key] for key in ("layer_types", "mlp_layer_types",
                                  "num_attention_heads_per_layer")]
    if min(map(len, lists)) < n:
        raise ValueError("layer_types, mlp_layer_types and "
                         "num_attention_heads_per_layer name fewer than "
                         "num_hidden_layers layers")
    kinds = [KIND_OF[t] for t in lists[0][:n]]
    ffns, per_layer = lists[1][:n], lists[2][:n]
    entries = [kind + (":dense" if ffn == "dense" else "")
               for kind, ffn in zip(kinds, ffns)]
    # the leading dense layers stand once; the rest is whole periods
    dense = next((i for i, ffn in enumerate(ffns) if ffn != "dense"), n)
    rest = entries[dense:]
    period = next(p for p in range(1, len(rest) + 1) if len(rest) % p == 0
                  and rest == rest[:p] * (len(rest) // p))
    heads = {}
    for kind, count in zip(kinds, per_layer):
        if heads.setdefault(kind, int(count)) != int(count):
            raise ValueError(f"{kind} layers of unlike head counts")
    if heads.get("gqa", src["num_attention_heads"]) != src[
            "num_attention_heads"]:
        raise ValueError("a full layer's heads are num_attention_heads")
    return {**src, "experts_total": int(src["published"]["num_experts"]),
            "first_expert": first, "heads": heads,
            "layer_prefix": tuple(entries[:dense]),
            "layer_pattern": tuple(rest[:period]),
            # queries a block of the reference's attention: a full layer's
            # block meets every key at 48 heads, a window layer's its span
            "query_block": 128, "window_query_block": 512}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"sliding_window", "swa_heads", "partial_rotary_factor",
            "yarn_factor", "attn_head_gate", "layer_prefix",
            "experts_held"} <= fields:
        raise RuntimeError(
            "this checkout's models have no window layer, no head count or "
            "rotary rule a kind, no YaRN and no gate a head: it cannot run "
            "Laguna-XS.2")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    full = m["rope_parameters"]["full_attention"]
    window = m["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], window["rope_type"]) != ("yarn", "default") or (
            float(window.get("partial_rotary_factor", 1)) != 1):
        raise ValueError("the program runs YaRN on the full layers and "
                         "plain RoPE over the whole head on the window's")
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["heads"]["gqa"], swa_heads=m["heads"]["swa"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["rms_norm_eps"]),
        layer_prefix=m["layer_prefix"], layer_pattern=m["layer_pattern"],
        sliding_window=int(m["sliding_window"]), attn_head_gate=True,
        rope_theta=float(full["rope_theta"]),
        swa_rope_theta=float(window["rope_theta"]),
        partial_rotary_factor=float(full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_len=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=float(m["moe_routed_scaling_factor"]),
        shared_experts=1,
        shared_intermediate_size=m["shared_expert_intermediate_size"],
        experts_held=m["num_experts"], first_expert=m["first_expert"],
        # no key names a balancing term: none in the objective
        load_balance_coef=0.0, router_z_coef=0.0,
        # the kernel, or (rehearsal, on the CPU) the reference core under
        # the same band: never a silent change of path, "flash" raises off
        # the chip.  A rehearsal compares a few hundred tokens, whose
        # bfloat16 mean is noise: it walks the harness in float32
        **({"dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return LlamaForCausalLM(cfg)


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
    each held expert's gate and up matrices times the square root of the
    number held and its down matrix by that times ``expert_out_scale``;
    every router times ``router_scale``; a softmax layer's query projection
    times ``q_scale`` (a full layer) or ``window_q_scale`` (a window
    layer), its gate's projection times ``gate_scale`` and its output
    projection times ``attn_out_scale`` (a key that is absent is 1)."""
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
        attn = layer + ("attn",)
        rule.update({
            attn + ("q_proj", "kernel"): scale(
                "window_q_scale" if kind == "swa" else "q_scale"),
            attn + ("head_gate_proj", "kernel"): scale("gate_scale"),
            attn + ("o_proj", "kernel"): scale("attn_out_scale")})
    return {path: factor for path, factor in rule.items() if factor != 1.0}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors (``families/solaropen2.py::condition``: same tree,
    shardings and dtypes, one multiply a leaf on the device, no forward
    pass, no look at a batch).  Why each factor: under ``TOKEN_ATOL``."""
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
    """``{entry: layers of it in the stack}``."""
    periods = (m["num_hidden_layers"] - len(m["layer_prefix"])) // len(
        m["layer_pattern"])
    counts = {}
    for entry in m["layer_prefix"]:
        counts[entry] = counts.get(entry, 0) + 1
    for entry in m["layer_pattern"]:
        counts[entry] = counts.get(entry, 0) + periods
    return counts


def kind_layers(m, kind):
    return sum(n for entry, n in layer_counts(m).items()
               if entry.partition(":")[0] == kind)


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: a softmax layer's q
    and o at its kind's heads, k and v at the key heads, its gate's column
    a head; the dense layer's SwiGLU; in a routed layer the router, the
    shared expert and of the routed experts what a token's
    ``num_experts_per_tok`` assignments meet here under even routing (``k *
    held / all`` experts: one, at 8 a token and 32 of 256 held); the output
    head.  Not the embedding table or the norms."""
    m = sizes(config, rehearse)
    h, d = m["hidden_size"], m["head_dim"]
    attn = {kind: h * d * (2 * heads + 2 * m["num_key_value_heads"])
            + h * heads for kind, heads in m["heads"].items()}
    met = m["num_experts_per_tok"] * m["num_experts"] / m["experts_total"]
    routed = (h * m["experts_total"]
              + 3 * h * m["shared_expert_intermediate_size"]
              + met * 3 * h * m["moe_intermediate_size"])
    dense = 3 * h * m["intermediate_size"]
    total = h * m["vocab_size"]
    for entry, n in layer_counts(m).items():
        kind, _, ffn = entry.partition(":")
        total += n * (attn[kind] + (dense if ffn else routed))
    return total


def swa_shape(config, batch, seq, rehearse=False):
    """The shapes the window layers' core works on in one step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["heads"]["swa"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "window": int(m["sliding_window"]),
            "layers": kind_layers(m, "swa")}


def full_shape(config, batch, seq, rehearse=False):
    """The shapes the full layers' core works on in one step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["heads"]["gqa"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "window": None, "layers": kind_layers(m, "gqa")}


def allowed_pairs(seq, window=None):
    """Query-key pairs a head attends: every causal pair, or the band's
    (``S W - W (W - 1) / 2``: the first ``W`` queries see fewer)."""
    w = seq if window is None else min(window, seq)
    return seq * w - w * (w - 1) // 2


def attn_step_flops(shape):
    """Operations the model asks of one step's softmax core, whatever
    computes them: an allowed pair of positions and head costs a
    multiply-add over ``head_dim`` for the score and one for the value
    forward, and twice that backward (``benchmarks/flops.py``'s rule for
    the dense cells): the allowed pairs only, no masked half of a block,
    no rematerialised forward, no score computed again in a backward
    kernel."""
    pairs = allowed_pairs(shape["seq"], shape["window"])
    return 3 * 4 * shape["head_dim"] * pairs * (
        shape["heads"] * shape["batch"] * shape["layers"])


def attn_step_bytes(shape, itemsize=2):
    """Least bytes the core moves to and from HBM: q, k, v in and o out
    once forward; backward q, k, v, o and o's gradient in, the gradients of
    q, k and v out; k and v at the key heads."""
    rows = shape["batch"] * shape["seq"] * shape["head_dim"]
    q, kv = rows * shape["heads"], rows * shape["kv_heads"]
    forward = 2 * q + 2 * kv
    backward = (3 * q + 2 * kv) + (q + 2 * kv)
    return shape["layers"] * itemsize * (forward + backward)


swa_step_flops = full_step_flops = attn_step_flops
swa_step_bytes = full_step_bytes = attn_step_bytes


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` and the two
    kinds' softmax cores as the model asks for them (``attn_step_flops``:
    every causal pair in a full layer, the band's in a window layer)."""
    return (6 * matmul_params(config, rehearse)
            + attn_step_flops(swa_shape(config, 1, seq, rehearse)) / seq
            + attn_step_flops(full_shape(config, 1, seq, rehearse)) / seq)


def fa2_shape(config, batch_per_chip, seq):
    """No shape for ``fa2_ms_per_step``'s reader, which counts calls by one
    number a layer: this family's calls are of two shapes, read by scope
    (``layer_metrics/swa_attn_*``, ``full_attn_*``)."""
    return None


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no sort of assignments, no
# sharding, no remat; the attention a block of queries at a time (a full
# layer's against every key, a window layer's against the keys its band can
# reach), every held expert looped over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores, the attention's
#: scores and softmax in float32); the reference is float32 throughout.
#: Beside the rounding a dense model shows, one choice is discontinuous: a
#: router margin under the bfloat16 error of the hidden state flips an
#: expert (``LOW_MARGIN``), and a flip here weighs 2.5 / 8 of an expert's
#: whole result: the system's worst tokens are flips.  Each limit stands
#: between readings on the chip at the published widths and the cell's own
#: size (one sequence of 16,384, five layers), on the state ``condition``
#: gives (``tests/precision_laguna.py``, each set of losses through
#: ``jobs_shared.compare_losses``; my chip runs, PR 51: seven seeds
#: ..101-..107 through the tool, the control and the faults on ..101, and
#: the system again in seven runs of the cell; PERF.md section 6 has the
#: table fault by fault):
#:
#:                  system            float8 control   the mildest faults it catches
#:   worst token    0.332-0.616       0.597            0.586 (2.5 left out), 0.696 (window layers causal)
#:   median token   0.00558-0.00578   0.0436           0.0735 (2.5 left out), 0.0836 (YaRN's factor left out),
#:                                                     0.0871 (window layers causal)
#:   mean           5.5e-5-2.1e-4     9.5e-4           2.7e-4-5.1e-3
#:
#: **The median holds the cell**: steady to 4% over fourteen seeds, the
#: control 7.5 times and the mildest fault it catches 12.7 times the
#: system's largest, so ``MEDIAN_ATOL`` 0.015 stands 2.6 times over the one
#: and 2.9 times under the other.  **The worst token cannot tell the
#: control from the system** (0.597 beside 0.332-0.616: both are flips of an
#: expert, which weigh the same in either): ``TOKEN_ATOL`` 1.2 is there for
#: a token or a row gone wrong, 1.95 times the largest of fourteen seeds
#: (the faults that move one read 1.06-3.28).  ``MEAN_ATOL`` 8e-4 is there
#: for a bias, 3.9 times over the system's largest; the control reads just
#: over it.  **Three planted faults of the thirteen are not caught at the
#: timed sizes**: the window off by one either way moves the median token
#: by 0.0029-0.0031 and the worst by 0.35-0.40, UNDER the system's own
#: distance from float32 (one key of 512 at 64 heads in three layers of
#: five), and so do the attention's scores through bfloat16 (0.0018 /
#: 0.43): these are held on the CPU in float32, where they read a hundred
#: times the agreement (``tests/test_reference_laguna.py``,
#: ``tests/test_window_attention_kernels.py``: the band to the position).
#:
#: **Why the state's factors** (``condition``): ``embed_scale`` 300 and each
#: held expert's matrices times sqrt(32), as Solar-Open2's and Ling's cells,
#: so that uniform random tokens spread over the 256 experts (this chip's
#: rows 0.970-1.028 of a fair share by layer over seven seeds, the hottest
#: expert 1.19-1.29 of the mean: the ladder's first extent holds 1.25 of a
#: fair share) and the initialiser's count of the expert axis into the
#: fan-in is undone; ``expert_out_scale`` 3, ``gate_scale`` 3 and
#: ``attn_out_scale`` 3 (Ling's readings: a token meets one expert of a
#: layer's eight here; gates that open and shut; a layer's attention weighs
#: in the stream) and ``q_scale`` = ``window_q_scale`` 2 (scores of unit
#: spread over thousands of keys are an average of the values with or
#: without positions, PR 41; at 2 a head attends to some nine keys of a
#: window's 512): on this first rule every fault of position, window, gate
#: and router but the three above reads 13 to 90 times the system's median,
#: so no factor was swept further.
TOKEN_ATOL = 1.2
MEDIAN_ATOL = 1.5e-2
MEAN_ATOL = 8e-4
LOW_MARGIN = 1e-2
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above, or PERF.md names the one that does not
FAULTS = ("window_511", "window_513", "window_layers_causal",
          "full_layers_windowed", "bases_swapped", "full_rotary_whole_head",
          "no_yarn_ramp", "no_yarn_factor", "no_gate", "elementwise_gate",
          "no_scaling_factor", "not_renormalised", "bfloat16_scores")


def _solar():
    """RMSNorm and the rounding through a narrower type: that family's."""
    return load_module("families", "solaropen2")


def _rms_norm(x, scale, eps):
    return _solar()._rms_norm(x, scale, eps)


def _frequencies(rule, head_dim, fault):
    """``(inverse frequencies [r / 2], the factor on cos and sin, r)`` of
    one entry of ``rope_parameters``: plain, or YaRN's (``transformers``'
    ``_compute_yarn_parameters``)."""
    share = float(rule.get("partial_rotary_factor", 1))
    if fault == "full_rotary_whole_head":
        share = 1.0
    r = int(head_dim * share)
    theta = float(rule["rope_theta"])
    pairs = jnp.arange(r // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * pairs / r)
    if rule.get("rope_type", "default") != "yarn":
        return freq, 1.0, r
    original = float(rule["original_max_position_embeddings"])

    def pair_of(turns):
        return r * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rule["beta_slow"]))), r - 1)
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    factor = float(rule["factor"])
    attention = float(
        rule.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    if fault == "no_yarn_ramp":         # the plain base
        ramp = jnp.zeros_like(ramp)
    if fault == "no_yarn_factor":
        attention = 1.0
    return freq * (1 - ramp) + freq / factor * ramp, attention, r


def _rope(x, rule, head_dim, fault):
    """[B, S, H, D] at positions ``0..S-1``: the first ``r`` columns turned
    (their first half paired with their second), the rest as they are."""
    freq, factor, r = _frequencies(rule, head_dim, fault)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = factor * jnp.cos(angles)[None, :, None, :]
    sin = factor * jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attend(q, k, v, window, block, fault):
    """Softmax attention of ``q`` [B, S, kv heads, groups, D] over ``k``,
    ``v`` [B, S, kv heads, D], a block of queries at a time: against every
    key under the causal mask (``window`` ``None``), or against the ``block
    + window - 1`` keys the block's band can reach."""
    B, S, kv, groups, d = q.shape
    block = min(block, S)
    back = 0 if window is None else window - 1
    if window is not None:      # keys before the start: masked below
        k, v = (jnp.pad(t, ((0, 0), (back, 0), (0, 0), (0, 0)))
                for t in (k, v))

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        t = first + jnp.arange(block)[:, None]
        if window is None:
            keys, values = k, v
            s = jnp.arange(S)[None, :]
            seen = s <= t
        else:
            keys, values = (jax.lax.dynamic_slice_in_dim(
                x, first, block + back, 1) for x in (k, v))
            s = first - back + jnp.arange(block + back)[None, :]
            seen = (s >= 0) & (s <= t) & (t - s < window)
        scores = jnp.einsum("bqngd,bknd->bqngk", rows, keys) * d ** -0.5
        if fault == "bfloat16_scores":
            scores = load_module("families", "olmoe")._round_through(
                scores, jnp.bfloat16)
        probs = jax.nn.softmax(
            jnp.where(seen[None, :, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqngk,bknd->bqngd", probs, values)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, kv * groups, d)


def _attention(h, p, m, kind, fault):
    """A softmax layer of ``kind`` (``gqa``: full, ``swa``: window)."""
    rules = m["rope_parameters"]
    rule_of = {"gqa": rules["full_attention"],
               "swa": rules["sliding_attention"]}
    if fault == "bases_swapped":
        rule_of = {
            "gqa": {**rule_of["gqa"],
                    "rope_theta": rule_of["swa"]["rope_theta"]},
            "swa": {**rule_of["swa"],
                    "rope_theta": rule_of["gqa"]["rope_theta"]}}
    window = int(m["sliding_window"]) if kind == "swa" else None
    if kind == "swa" and fault in ("window_511", "window_513"):
        window += {"window_511": -1, "window_513": 1}[fault]
    if kind == "swa" and fault == "window_layers_causal":
        window = None
    if kind == "gqa" and fault == "full_layers_windowed":
        window = int(m["sliding_window"])
    d = int(m["head_dim"])
    rope_fault = fault if kind == "gqa" else None
    q = _rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]),
              rule_of[kind], d, rope_fault)
    k = _rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]),
              rule_of[kind], d, rope_fault)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    B, S, heads, _ = q.shape
    # query head i reads key head i // groups
    q = q.reshape(B, S, k.shape[2], heads // k.shape[2], d)
    out = _attend(q, k, v, window, int(
        m["query_block" if window is None else "window_query_block"]), fault)
    gate = (h @ p["head_gate_proj"]["kernel"])[..., None]
    if fault == "elementwise_gate":
        # a gate an element where the model has one a head: the heads'
        # gates tiled over the flat ``heads x width`` where each should be
        # repeated over its head's width, so element ``(h, d)`` takes the
        # gate of head ``(h * width + d) % heads``
        gate = jnp.tile(gate[..., 0], out.shape[-1]).reshape(out.shape)
    gate = jax.nn.sigmoid(gate)
    if fault == "no_gate":
        gate = jnp.ones_like(gate)
    return jnp.einsum("bshd,hde->bse", out * gate, p["o_proj"]["kernel"])


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def _dense_mlp(h, p):
    return _swiglu(h, *(p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj")))


def _experts(h, p, m, fault):
    """(ffn(h), share of tokens with a low router margin, rows each of the
    router's experts took): every held expert computes every token, one
    after the other; a token's k kept scores are divided by their sum and
    multiplied by the factor; the experts that are not here add nothing;
    the shared expert once."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    scores = jax.nn.sigmoid(logits)
    largest = jax.lax.top_k(logits, k + 1)[0]
    chosen = logits >= largest[..., k - 1: k]
    gates = jnp.where(chosen, scores, 0.0)
    if fault != "not_renormalised":
        gates = gates / gates.sum(axis=-1, keepdims=True)
    if fault != "no_scaling_factor":
        gates = gates * float(m["moe_routed_scaling_factor"])
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        return out + gate[..., None] * _swiglu(h, gate_w, up_w, down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    rows = chosen.sum(axis=tuple(range(chosen.ndim - 1)))
    return out + _dense_mlp(h, p["shared_expert"]), low, rows


def reference(params, input_ids, labels, m, round_through=None, fault=None):
    """(loss of every token [B, S]; a routed layer each, in the stack's
    order: the share of tokens with a low router margin, and the rows each
    of the router's experts took [layers, E]) from the program's parameter
    tree (unboxed; a run of equal layers stacked under ``prefix/<run>``
    ``[run, ...]`` and ``layers/<run>`` ``[periods, run, ...]``), as ``m =
    sizes(config, rehearse)`` reads the file.  The loops over periods and
    over a run are ``jax.lax.scan``s of the plain body: one layer's
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

    def layer(entry):
        kind, _, ffn = entry.partition(":")

        def body(x, p):
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + _attention(h, p["attn"], m, kind, fault)
            h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
            if ffn:
                return x + _dense_mlp(h, p["mlp"]), ()
            out, low, rows = _experts(h, p["mlp"], m, fault)
            return x + out, (low, rows)
        return body

    def stack(entries, x, p):
        seen = {}
        for name, entry, _ in runs(entries):
            x, seen[name] = jax.lax.scan(layer(entry), x, p[name]["layer"])
        return x, seen

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        if m["layer_prefix"]:
            x, _ = stack(m["layer_prefix"], x, params["prefix"])
        x, seen = jax.lax.scan(
            lambda x, p: stack(m["layer_pattern"], x, p), x, params["layers"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # [periods, run] a run -> the stack's order (the prefix is dense)
    routed = [seen[name] for name, entry, _ in runs(m["layer_pattern"])
              if ":" not in entry]
    low = jnp.concatenate([s[0] for s in routed], axis=1).ravel()
    rows = jnp.concatenate([s[1] for s in routed], axis=1)
    return losses, low, rows.reshape(-1, rows.shape[-1])


def _report(low, rows, first, held):
    rows = [[int(n) for n in layer] for layer in rows]
    print(json.dumps({
        "phase": "reference_laguna",
        "router_low_margin": LOW_MARGIN,
        "router_low_margin_share_by_layer": [float(v) for v in low],
        "router_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        # this chip's rows over a fair share, and the hottest expert's load
        "share_rows_over_expected_by_layer": [
            sum(layer[first: first + held]) * len(layer) / (
                held * max(sum(layer), 1)) for layer in rows],
        "load_max_over_mean_by_layer": [
            max(layer) * len(layer) / max(sum(layer), 1) for layer in rows]}),
        file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each routed layer's tokens with a low router
    margin, which it holds to ``LOW_MARGIN_SHARE_MAX``).  The load the
    routing puts on this chip's experts goes to standard error."""
    m = sizes(config, rehearse)
    losses, low, rows = reference(params, input_ids, labels, m, **planted)
    jax.debug.callback(
        lambda low, rows: _report(
            low, rows, m["first_expert"], m["num_experts"]), low, rows)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
