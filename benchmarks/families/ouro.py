"""Ouro-2.6B (``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) through the program's one decoder
(``models/llama.py``): a stack of softmax layers (MHA 16 x 128, RoPE, SwiGLU)
with SANDWICH norms (each branch's output normalised before it is added),
run ``total_ut_steps`` = 4 times over the SAME weights with the final norm
inside the loop, a head and an exit gate read after every loop step, and the
expected loss over the exit distribution less an entropy term as the model's
own objective.  Built from a configuration file, with its counts of
operations and bytes and its plain reference (the benchmark's copy of
``dlrover_tpu/models/ouro_reference.py``, which states the step equation by
equation).

**A weight is counted once and used four times**: ``matmul_params`` is what
a token multiplies with in a step (every layer four times, the head four
times, the gate four times), ``parameters`` in the file what the chip
holds."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6, "total_ut_steps": 4,
        "max_position_embeddings": 128}

#: published keys the program has one path for: only these values run
ONLY = {"hidden_act": "silu", "tie_word_embeddings": False,
        "use_sliding_window": False, "sliding_window": None,
        "rope_scaling": None, "early_exit_threshold": 1}

#: rows a block of the reference's attention (its scores are ``[rows, heads,
#: keys]`` float32: 0.27 GB at 256 rows and 16,384 keys) and of its head (its
#: logits ``[rows, vocab]`` float32: 0.4 GB at 2048 rows)
REFERENCE_QUERY_BLOCK = 256
REFERENCE_HEAD_ROWS = 2048


def sizes(config, rehearse):
    src = TINY if rehearse else config
    assumed = config.get("assumed", {})
    return {**src,
            "exit_entropy_weight": float(
                assumed.get("exit_entropy_weight", 0.05)),
            "query_block": REFERENCE_QUERY_BLOCK,
            "head_rows": REFERENCE_HEAD_ROWS}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    if not {"loop_steps", "sandwich_norm", "exit_gate"} <= fields:
        raise RuntimeError(
            "this checkout's models have no looped stack (a layer stack run "
            "several times over the same weights with the final norm inside "
            "the loop, sandwich norms, an exit gate a loop step and the "
            "expected loss over the exit distribution): it cannot run Ouro")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
        if set(config["layer_types"]) != {"full_attention"}:
            raise ValueError("the program runs full attention in every layer")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    cfg = LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        loop_steps=m["total_ut_steps"], sandwich_norm=True, exit_gate=True,
        exit_entropy_weight=m["exit_entropy_weight"],
        # the kernel, or (rehearsal, on the CPU) the jnp path in float32: a
        # rehearsal compares a few hundred tokens, whose bfloat16 mean is
        # noise.  Never a silent change of path: "flash" raises off the chip
        **({"attention_impl": "reference", "dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return LlamaForCausalLM(cfg)


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_matmul_params(m):
    """Parameters a row multiplies with in ONE application of a layer: the
    four attention projections and the SwiGLU's three.  Not the four norms."""
    h, d = m["hidden_size"], m["head_dim"]
    attn = h * d * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])
    return attn + 3 * h * m["intermediate_size"]


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with in one step: every layer
    ``total_ut_steps`` times, and after every loop step the head and the
    gate's one column.  Not the embedding table (a lookup) or the norms; a
    weight the chip holds once counts as often as it is used."""
    m = sizes(config, rehearse)
    h = m["hidden_size"]
    return m["total_ut_steps"] * (
        m["num_hidden_layers"] * layer_matmul_params(m)
        + h * m["vocab_size"] + h)


def layer_applications(m):
    """Layers a token passes in a step: the depth times the loop steps."""
    return m["total_ut_steps"] * m["num_hidden_layers"]


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` and causal
    attention in every one of the ``total_ut_steps x num_hidden_layers``
    layer applications (``benchmarks/flops.py``'s rule).  What a
    rematerialised layer computes again is never counted."""
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    return train_flops_per_token(
        matmul_params(config, rehearse), layer_applications(m),
        m["num_attention_heads"] * m["head_dim"], seq)


def full_shape(config, batch, seq, rehearse=False):
    """The shapes the softmax core works on in one step (every layer is a
    full-attention layer; ``families/laguna.py``'s rule, ``layers`` the
    layer APPLICATIONS of a step: 32)."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "window": None, "layers": layer_applications(m)}


#: the softmax core's operations and least bytes a step, whatever computes
#: them: ``families/laguna.py``'s rule BY IMPORT (an allowed pair of positions
#: and head costs a multiply-add over ``head_dim`` for the score and one for
#: the value forward, twice that backward; q, k, v, o and their gradients
#: moved once a pass; no rematerialised forward counted)
_laguna = load_module("families", "laguna")
full_step_flops = _laguna.full_step_flops
full_step_bytes = _laguna.full_step_bytes


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy at ``highest`` precision, no kernel, no
# remat, nothing of ``models/llama.py``.  A Python loop over the loop steps;
# the loop over the layers is a ``jax.lax.scan`` of the plain body (where the
# program's copy has a second Python loop), so that the program compiles in
# seconds; attention a block of queries at a time, the head a block of rows.
# --------------------------------------------------------------------------

#: |system - reference| allowed: on the loss of the worst token, of the
#: median token and on the mean of ``z_T``'s token losses (what the harness
#: compares: the model's RESULT read at ``labels``); and, held inside
#: ``reference_forward``, on the worst token of EVERY exit's losses
#: (``EXIT_TOKEN_ATOL``), on the exit distribution's worst entry
#: (``EXIT_P_ATOL``) and on the objective (``OBJECTIVE_RTOL``, relative).
#: The system multiplies in bfloat16 with float32 accumulation, as the
#: configuration states (the softmax, the gates and the objective in
#: float32); the reference is float32 throughout.  Each limit stands between
#: readings on the chip at the published widths and the cell's own size (one
#: sequence of 16,384 tokens, 8 layers four times), on
#: ``Trainer.create_state``'s state (``tests/precision_ouro.py``, seeds
#: 6100000203, 211-218, 221, 222 and the cell's own 201, 202: thirteen of the
#: system, three of the control, one of each fault; my chip runs, PR 61):
#:
#:                       worst token  median token  mean           worst exit token  exit p       objective, relative
#:   system, 13 seeds    0.105-0.148  0.0158-0.0206 3.7e-5-3.8e-4  0.097-0.141       0.011-0.016  1.1e-6-1.7e-5
#:   float8 control, 3   1.57-1.82    0.253-0.263   1.4e-3-8.2e-3  1.57-1.82         0.132-0.168  6.5e-5-1.1e-4
#:   three_loop_steps    2.91         0.515         1.6e-3         (three exits: infinite)
#:   final_norm_outside  6.18         0.865         2.3e-3         6.18              0.382        4.1e-4
#:   no_mlp_out_norm     3.30         0.535         5.0e-4         3.37              0.326        6.4e-4
#:   last_exit_gated     0            0             0              0                 0.562        0.235
#:   entropy_sign        0            0             0              0                 0            0.0111
#:
#: ``TOKEN_ATOL`` 0.45: 3.0 times over the system's largest of thirteen
#: seeds (2.8 times over the largest of the cell's own eleven later runs,
#: 0.160), 3.5 times under the control's smallest; the faults of the loop and
#: of the norms read 6 to 14 times over it.  ``MEDIAN_ATOL`` 0.06 is what
#: holds the precision: steady to a quarter from seed to seed, 2.9 times
#: over the system's largest, 4.2 times under the control's smallest.  The
#: mean is the average of 16,384 token errors, which cancel: ``MEAN_ATOL``
#: 1.2e-3 is there for a bias, 3.1 times over the system's largest; the
#: control reads 1.2 to 6.8 times over it and need not fail it.
#: ``EXIT_TOKEN_ATOL`` as the result's, over all four exits.
#: ``EXIT_P_ATOL`` 0.045: 2.8 times over the system's largest (a gate's
#: logit is a sum of 2048 bfloat16 products: an entry of ``p`` moves by a
#: hundredth), 2.9 times under the control's smallest, 12 times under the
#: last exit gated.  ``OBJECTIVE_RTOL`` 1e-3 is for the objective's own
#: faults, which move no logit: 59 times over the system's largest, 11 times
#: under the entropy's sign at an entropy of 1.24 nats (7 times at the
#: least entropy a seed gave, 0.80); the control reads UNDER it (the
#: objective is a mean over tokens as well: it separates no precision).
TOKEN_ATOL = 0.45
MEDIAN_ATOL = 0.06
MEAN_ATOL = 1.2e-3
EXIT_TOKEN_ATOL = 0.45
EXIT_P_ATOL = 4.5e-2
OBJECTIVE_RTOL = 1e-3

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above.  ``three_loop_steps``: ``T - 1`` loop steps
#: for ``T``; ``final_norm_outside``: the loop carries the stream unnormed,
#: the final norm before the exits alone; ``no_mlp_out_norm``: the
#: feed-forward's sandwich norm left out; ``last_exit_gated``: ``p_T =
#: lam_T prod_{j<T} (1 - lam_j)`` in place of the mass that is left;
#: ``entropy_sign``: ``+ beta H`` (the last two move no logit: the exit
#: distribution's and the objective's own limits catch them)
FAULTS = ("three_loop_steps", "final_norm_outside", "no_mlp_out_norm",
          "last_exit_gated", "entropy_sign")

#: what the last forward check's system pass sowed (``reference_forward``):
#: the reader of ``loop_exit_entropy`` takes it where a window is too short
#: to hold a ``trainer.model_stats`` record
SEEN = {}


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotary embedding on [B, S, H, D] at positions ``0..S-1``, halves
    convention (the published ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(total, block):
    n = min(int(block), total)
    while total % n:
        n -= 1
    return n


def _attention(h, p, m):
    """Causal softmax attention: a block of query rows at a time against
    every key, every head of it at once."""
    theta = float(m["rope_theta"])
    B, S = h.shape[:2]
    q = _rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]), theta)
    k = _rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]), theta)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    heads, dim = q.shape[2:]
    n = _blocks(S, m["query_block"])
    q = q.reshape(B, S, k.shape[2], heads // k.shape[2], dim)
    o_proj = p["o_proj"]["kernel"].reshape(q.shape[2:] + (-1,))

    def one_block(first):
        mine = jax.lax.dynamic_slice_in_dim(q, first, n, 1)
        scores = jnp.einsum("bqngd,bknd->bqngk", mine, k) * dim ** -0.5
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(n)[:, None]
        probs = jax.nn.softmax(jnp.where(
            seen[None, :, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqngd,ngde->bqe", jnp.einsum(
            "bqngk,bknd->bqngd", probs, v), o_proj)

    mixed = jax.lax.map(one_block, jnp.arange(0, S, n))
    return jnp.moveaxis(mixed, 0, 1).reshape(B, S, -1)


def _head_losses(h, kernel, targets, m):
    """``-log softmax(h kernel)[targets]`` [B, S], a block of rows at a
    time."""
    B, S, _ = h.shape
    n = _blocks(S, m["head_rows"])

    def one_block(first):
        logits = jax.lax.dynamic_slice_in_dim(h, first, n, 1) @ kernel
        at = jax.lax.dynamic_slice_in_dim(targets, first, n, 1)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), at[..., None], axis=-1)[..., 0]

    losses = jax.lax.map(one_block, jnp.arange(0, S, n))
    return jnp.moveaxis(losses, 0, 1).reshape(B, S)


def reference(params, input_ids, targets, weights, m, round_through=None,
              fault=None):
    """``{"ce": every exit's token losses [T, B, S] at ``targets`` (the last
    is the model's result's, ``z_T``'s), "p": the exit distribution [T, B,
    S], "objective": ``sum_i w_i [sum_t p_t CE_t - beta H(p)] / sum_i w_i``,
    "entropy": the weighted mean ``H(p)``}`` from the program's parameter
    tree (unboxed, layers stacked on the leading axis), as ``m =
    sizes(config, rehearse)`` reads the file.  ``fault``: one of ``FAULTS``;
    ``round_through``: every parameter through that dtype first (the float8
    control)."""
    eps = float(m["rms_norm_eps"])
    steps = int(m["total_ut_steps"]) - (fault == "three_loop_steps")

    # rounding in float32 arithmetic: the chip's compiler removes a
    # conversion there and back (``families/olmoe.py::_round_through``)
    rounded = None if round_through is None else load_module(
        "families", "olmoe")._round_through

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if rounded is None else rounded(t, round_through)

    def layer(x, p):
        p = jax.tree.map(f32, p)
        mixed = _attention(
            _rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m)
        x = x + _rms_norm(mixed, p["attn_out_norm"]["scale"], eps)
        h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
        out = (jax.nn.silu(h @ p["mlp"]["gate_proj"]["kernel"])
               * (h @ p["mlp"]["up_proj"]["kernel"])
               ) @ p["mlp"]["down_proj"]["kernel"]
        if fault != "no_mlp_out_norm":
            out = _rms_norm(out, p["mlp_out_norm"]["scale"], eps)
        return x + out, None

    with jax.default_matmul_precision("highest"):
        final = f32(params["final_norm"]["scale"])
        kernel = f32(params["lm_head"]["kernel"])
        gate_w = f32(params["exit_gate"]["kernel"])[:, 0]
        gate_b = f32(params["exit_gate"]["bias"])[0]
        x = f32(params["embed_tokens"])[input_ids]
        ce, lam = [], []
        for _ in range(steps):
            x, _ = jax.lax.scan(layer, x, params["layers"]["layer"])
            normed = _rms_norm(x, final, eps)
            if fault != "final_norm_outside":
                x = normed
            ce.append(_head_losses(normed, kernel, targets, m))
            lam.append(jax.nn.sigmoid(normed @ gate_w + gate_b))
    ce = jnp.stack(ce)
    p, left = [], jnp.ones_like(lam[0])
    for gate in lam[:-1]:
        p.append(gate * left)
        left = left * (1.0 - gate)
    p.append(lam[-1] * left if fault == "last_exit_gated" else left)
    p = jnp.stack(p)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    sign = 1.0 if fault == "entropy_sign" else -1.0
    per_token = jnp.sum(p * ce, axis=0) + sign * float(
        m["exit_entropy_weight"]) * entropy
    total = jnp.sum(weights)
    return {"ce": ce, "p": p,
            "objective": jnp.sum(weights * per_token) / total,
            "entropy": jnp.sum(weights * entropy) / total}


def model_targets(input_ids, labels):
    """``(targets, weights)`` of the objective as the program's model takes
    them (it sees no labels): position ``i``'s target is token ``i + 1``,
    which is ``labels[i]`` for every position but a sequence's last; that
    one has weight 0 (the program reads a token that is no target there)."""
    weights = jnp.ones(labels.shape, jnp.float32).at[:, -1].set(0.0)
    return labels, weights


def system_exits(params, input_ids, config, rehearse):
    """What the program's own forward pass gives of the exits, at the
    cell's sizes and precision: the objective it sows (``losses``:
    ``exit_objective``), every exit's token losses and the exit distribution
    (the collection ``exits``), and its counters."""
    model = build(config, rehearse, input_ids.shape[1])
    _, sown = model.apply({"params": params}, input_ids,
                          mutable=["losses", "stats", "exits"])
    return {"objective": sown["losses"]["exit_objective"][0],
            "ce": sown["exits"]["token_losses"][0],
            "p": jnp.exp(sown["exits"]["log_p"][0]),
            **{name: sown["stats"][name][0] for name in (
                "loop_exit_entropy", "loop_exit_mass_last",
                "loop_ce_by_step")}}


def exit_errors(got, want, weights):
    """``{"exit_token_max_abs_err", "exit_p_max_abs_err",
    "objective_rel_err"}`` of two sets of exits (``reference``'s keys),
    over the positions that have a target.  A set with fewer exits (a
    planted loop step too few) is as far off as can be: infinity."""
    if got["ce"].shape != want["ce"].shape:
        inf = jnp.float32(jnp.inf)
        return {"exit_token_max_abs_err": inf, "exit_p_max_abs_err": inf,
                "objective_rel_err": inf}
    held = weights > 0
    return {
        "exit_token_max_abs_err": jnp.max(jnp.where(
            held, jnp.abs(got["ce"] - want["ce"]), 0.0)),
        "exit_p_max_abs_err": jnp.max(jnp.where(
            held, jnp.abs(got["p"] - want["p"]), 0.0)),
        "objective_rel_err": jnp.abs(
            got["objective"] - want["objective"]) / want["objective"]}


def exits_agree(errors):
    return ((errors["exit_token_max_abs_err"] <= EXIT_TOKEN_ATOL)
            & (errors["exit_p_max_abs_err"] <= EXIT_P_ATOL)
            & (errors["objective_rel_err"] <= OBJECTIVE_RTOL))


def _report(errors, got, want):
    limits = {"exit_token_max_abs_err": EXIT_TOKEN_ATOL,
              "exit_p_max_abs_err": EXIT_P_ATOL,
              "objective_rel_err": OBJECTIVE_RTOL}
    SEEN.update({name: [float(v) for v in jnp.ravel(got[name])] for name in (
        "loop_exit_entropy", "loop_exit_mass_last", "loop_ce_by_step")})
    print(json.dumps({
        "phase": "reference_exits",
        "objective_system": float(got["objective"]),
        "objective_reference": float(want["objective"]),
        "exit_entropy_reference": float(want["entropy"]),
        "exit_mass_reference": [float(v) for v in want["p_mean"]],
        "ce_by_step_reference": [float(v) for v in want["ce_mean"]],
        **SEEN,
        **{name: float(value) for name, value in errors.items()},
        **{name.replace("_err", "_limit"): limit
           for name, limit in limits.items()}}), file=sys.stderr, flush=True)
    for name, limit in limits.items():
        print(f"check {name}: {float(errors[name])} limit {limit}",
              file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token of the model's result ``z_T`` at ``labels``; no margins).
    The harness compares those token losses only, so the exits are held
    here, as ``families/sdar.py`` holds its NELBO: the program's own, from
    its forward pass at the cell's sizes (every exit's token losses, the
    exit distribution, the sown objective), against the reference's on the
    same targets; one further off than its limit turns every loss to NaN,
    which no comparison passes.  Every number goes to standard error beside
    its limit, and the program's counters into ``SEEN``."""
    targets, weights = model_targets(input_ids, labels)
    want = reference(params, input_ids, targets, weights,
                     sizes(config, rehearse), **planted)
    got = system_exits(params, input_ids, config, rehearse)
    errors = exit_errors(got, want, weights)
    held = weights / jnp.sum(weights)       # the means the program sows
    jax.debug.callback(_report, errors, got, {
        **want, "p_mean": jnp.sum(want["p"] * held, axis=(1, 2)),
        "ce_mean": jnp.sum(want["ce"] * held, axis=(1, 2))})
    return jnp.where(exits_agree(errors), want["ce"][-1], jnp.nan), jnp.zeros(0)


def stand_in(config, rehearse=False, hold_exits=True, **planted):
    """``(params, ids, labels) -> token losses`` for ``jobs_shared.
    reference_check``'s ``stand_in``: the reference with ``planted`` (the
    float8 control, a fault of ``FAULTS``) in the program's place.  As the
    program's own exits are held to the reference's inside
    ``reference_forward``, so are the planted one's here: further off than
    a limit turns every loss to NaN.  ``hold_exits`` false: the result's
    token losses as they are, to see whether the harness's own limits catch
    what was planted."""
    m = sizes(config, rehearse)

    def losses_of(params, input_ids, labels):
        targets, weights = model_targets(input_ids, labels)
        theirs = reference(params, input_ids, targets, weights, m, **planted)
        if not hold_exits:
            return theirs["ce"][-1]
        want = reference(params, input_ids, targets, weights, m)
        return jnp.where(exits_agree(exit_errors(theirs, want, weights)),
                         theirs["ce"][-1], jnp.nan)

    return losses_of


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses."""
    return reference_forward(
        params, input_ids, labels, config, rehearse, **planted)[0]
