"""EvaByte (``model_type`` ``evabyte``: a byte-level decoder whose attention
is EVA, arXiv:2302.04542, as its public model code simplifies it) through
the program's one decoder (``models/llama.py``): MHA with RoPE, attention
over the query's own window of keys and one learned summary of every chunk
of every earlier window under one softmax
(``ops/attention.py::eva_attention``), RMSNorm with a unit offset, a float32
residual stream, SwiGLU, and eight prediction heads in one projection.
Built from a configuration file, with its counts of operations and bytes and
its plain reference (the benchmark's copy of
``dlrover_tpu/models/evabyte_reference.py``, which states the layer equation
by equation)."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "window_size": 16, "chunk_size": 4, "num_pred_heads": 8,
        "max_position_embeddings": 128}

#: published keys the program has one path for: only these values run
ONLY = {"attention_class": "eva", "attention_bias": False,
        "hidden_act": "silu", "norm_add_unit_offset": True,
        "fp32_skip_add": True, "fp32_logits": True, "mixedp_attn": True,
        "tie_word_embeddings": False, "rope_scaling": None, "num_chunks": None}


def sizes(config, rehearse):
    """The published keys as a run reads them: the file's, or for a
    rehearsal ``TINY`` with the window and chunk ``run.rehearse`` names."""
    src = config
    if rehearse:
        src = {**TINY, **{k: v for k, v in config.get("run", {}).get(
            "rehearse", {}).items() if k in TINY}}
    return {**src,
            "head_dim": src["hidden_size"] // src["num_attention_heads"]}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    if not {"eva_window", "norm_unit_offset", "residual_dtype",
            "pred_heads"} <= fields:
        raise RuntimeError(
            "this checkout's models have no attention over windows and "
            "chunk summaries, no unit offset on the norm, no float32 "
            "residual and no further prediction heads: it cannot run EvaByte")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
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
        eva_window=m["window_size"], eva_chunk=m["chunk_size"],
        norm_unit_offset=True, residual_dtype=jnp.float32,
        pred_heads=m["num_pred_heads"],
        # a rehearsal compares a few hundred tokens, whose bfloat16 mean is
        # noise: it walks the harness in float32
        **({"dtype": jnp.float32} if rehearse else {}),
    )
    return LlamaForCausalLM(cfg)


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}: the whole rule, read from the configuration file
    (none where the file names no ``run.state``)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    scale = float(config["run"]["state"]["pool_scale"])
    attn = ("layers", "layer", "attn")
    return {attn + ("adaptive_mu_k",): scale, attn + ("adaptive_phi",): scale}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors; same tree, shardings and dtypes, one multiply a leaf on
    the device, no forward pass, no look at a batch (as
    ``families/keyevl.py::condition``).  Why these leaves is under
    ``TOKEN_ATOL`` below and in the configuration file's notes."""
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

def exact_pairs(seq, window):
    """Query-key pairs inside the windows: each window's causal triangle."""
    window = min(window, seq)
    return (seq // window) * (window * (window + 1) // 2)


def summary_pairs(seq, window, chunk):
    """Query-summary pairs: a query of window ``w`` sees the ``window /
    chunk`` summaries of each of the ``w`` windows before its own."""
    window = min(window, seq)
    windows = seq // window
    return window * (window // chunk) * (windows * (windows - 1) // 2)


def matmul_params(config, rehearse=False):
    """Parameters that take part in a matmul: the four attention
    projections, the three of the MLP, the output head's eight blocks.  Not
    the embedding table (a lookup), the norms or the pooling vectors."""
    m = sizes(config, rehearse)
    h = m["hidden_size"]
    attn = 4 * h * m["num_attention_heads"] * m["head_dim"]
    mlp = 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + mlp) + (
        h * m["num_pred_heads"] * m["vocab_size"])


def eva_attn_shape(config, batch, seq, rehearse=False):
    """The shapes the attention of one chip works on in one step."""
    m = sizes(config, rehearse)
    window = min(m["window_size"], seq)
    return {"batch": batch, "seq": seq, "window": window,
            "chunk": m["chunk_size"], "windows": seq // window,
            "heads": m["num_attention_heads"], "head_dim": m["head_dim"],
            "layers": m["num_hidden_layers"]}


def eva_attn_step_flops(shape):
    """Operations the model asks of one step's attention, whatever computes
    them: over the exact and the summary pairs two products forward and four
    backward; the pooling, for each of the two vectors a dot product a key
    and a weighted sum a chunk (``2 x 2 x 2`` operations a position, head
    and head dimension), forward and twice that backward.  No recomputation
    counted, no pair a dense block multiplies and the mask throws away."""
    width = shape["heads"] * shape["head_dim"]
    pairs = exact_pairs(shape["seq"], shape["window"]) + summary_pairs(
        shape["seq"], shape["window"], shape["chunk"])
    attention = 6 * 2 * width * pairs
    pooling = 3 * 8 * shape["seq"] * width
    return shape["layers"] * shape["batch"] * (attention + pooling)


def eva_attn_step_bytes(shape, itemsize=2):
    """Least bytes it moves to and from HBM, each operand read once and each
    result written once: forward q, k, v in and the output out; backward
    those and the output's gradient in, the three gradients out.  The
    summaries never need leave the chip; the two vectors a head are nothing
    beside these."""
    one = shape["batch"] * shape["seq"] * shape["heads"] * shape["head_dim"]
    forward = 3 * one + one
    backward = (3 * one + 2 * one) + 3 * one
    return shape["layers"] * (forward + backward) * itemsize


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` (head included)
    and the attention's two products over the exact and summary pairs,
    forward and twice backward: ``12 * layers * heads * head_dim * pairs /
    seq``.  The pooling (0.02% of it) is left to ``eva_attn_step_flops``."""
    m = sizes(config, rehearse)
    pairs = exact_pairs(seq, m["window_size"]) + summary_pairs(
        seq, m["window_size"], m["chunk_size"])
    return 6 * matmul_params(config, rehearse) + (
        12 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"]
        * pairs / seq)


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no remat; a window of
# queries at a time, exact keys and summaries exponentiated against their
# common maximum and summed into one ``Z``
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token, on the mean, and on the seven further heads' summed loss
#: (relative).  The system multiplies in bfloat16 with float32 accumulation,
#: as the configuration states (scores, softmax and pooling weights in
#: float32, the residual stream float32); the reference is float32
#: throughout.  Nothing of this model is chosen by a margin, so the errors
#: are rounding alone and steady from seed to seed.  Each limit stands
#: between readings on the chip at the published widths and the cell's own
#: size (one sequence of 16,384, four layers), on the state ``condition``
#: gives (``tests/precision_evabyte.py``, twelve seeds at ``pool_scale`` 2
#: and the same twelve at 1, each set of losses through
#: ``jobs_shared.compare_losses``; my chip runs, PR 35):
#:
#:                  system            float8 control   the smallest fault         the largest
#:   worst token    0.038-0.051       0.74-1.06        1.86 (mean pooling)        5.03
#:   median token   0.00687-0.00741   0.124-0.137      0.111 (last window gone)   0.461 (no summaries)
#:   mean           1.1e-5-4.9e-4     4.5e-4-5.2e-3    3.8e-5 (own window too)    2.8e-2
#:   further heads  5.4e-6-5.5e-5     7.0e-5-7.2e-4    7.0e-7                     1.6e-3
#:
#: The median is the number that holds the cell: steady to 4% from seed to
#: seed, the mildest fault (the last earlier window's summaries missing) 15
#: times and the control 17 times the system's largest, so ``MEDIAN_ATOL``
#: 0.015 stands 2.0 times over the system's largest and 7.4 times under the
#: mildest fault's smallest.  ``TOKEN_ATOL`` 0.15 (the dense families') is 2.6
#: times over the system's largest of forty-seven readings (0.058, a run of
#: the cell; 0.051 in the tool's twenty-four) and 4.9 times under the
#: control's smallest: it is there for one token or one row gone
#: wrong, which no median sees.  The mean is the average of 16,384 token
#: errors, which cancel: system, control and faults overlap, so no value
#: separates them; ``MEAN_ATOL`` 2e-3 (the dense families') is there for a
#: bias, 2.7 times over the system's largest of forty-seven readings (7.3e-4,
#: a run of the cell; 5.5e-4 in the tool's twenty-four).  The
#: further heads' term is a mean over 114,660 cross entropies, which cancel
#: as well: ``MULTI_BYTE_RTOL`` 5e-4 is nine times over the system's largest
#: and holds the term's arithmetic (a head left out moves it by a seventh, a
#: sum in place of a mean by four orders); on uniform random bytes no run can
#: see which byte a head is compared with.
#:
#: **Why ``pool_scale`` 2** (``condition``): on ``create_state``'s own state
#: the pooling vectors already give logits of order 1 against unit-variance
#: keys (``eva_pool_weight_max`` 0.133-0.181 where a plain mean reads 1/16 =
#: 0.0625) and the summaries hold 0.30-0.38 of the softmax mass, so every
#: fault is out at these limits there too (the mildest, the own window
#: summarised too, median 0.086-0.093).  Twice the vectors makes the pooling
#: plainly learned (0.232-0.345), leaves the mass (0.31-0.38) and the system's
#: errors where they were, and moves mean pooling from 0.117-0.132 to
#: 0.213-0.241 and the two off-by-one faults from 0.086-0.105 to 0.111-0.123.
TOKEN_ATOL = 0.15
MEDIAN_ATOL = 1.5e-2
MEAN_ATOL = 2e-3
MULTI_BYTE_RTOL = 5e-4

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above
FAULTS = ("mean_pooling", "no_summaries", "own_window_too",
          "last_window_missing", "two_softmaxes")


def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _summaries(k, v, mu, phi, chunk, mean_pooling):
    """(pooled keys, pooled values [B, S / chunk, H, D], the mean over
    chunks, heads and both poolings of the largest pooling weight)."""
    B, S, H, D = k.shape
    k = k.reshape(B, S // chunk, chunk, H, D)
    v = v.reshape(B, S // chunk, chunk, H, D)
    a = jax.nn.softmax(jnp.sum(k * mu, axis=-1), axis=2)
    b = jax.nn.softmax(jnp.sum(k * phi, axis=-1), axis=2)
    if mean_pooling:        # the planted fault: a plain mean of the chunk
        a = b = jnp.full_like(a, 1.0 / chunk)
    largest = 0.5 * (a.max(axis=2).mean() + b.max(axis=2).mean())
    return (jnp.sum(a[..., None] * k, axis=2),
            jnp.sum(b[..., None] * v, axis=2), largest)


def _attention(h, p, m, fault):
    """(o W_o, the mean over the queries past the first window of the
    softmax mass on summaries, the mean largest pooling weight)."""
    theta, chunk = float(m["rope_theta"]), int(m["chunk_size"])
    q = _rope(jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"]), theta)
    k = _rope(jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"]), theta)
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    B, S, H, D = q.shape
    window = min(int(m["window_size"]), S)
    per_window = window // chunk
    pooled_k, pooled_v, largest = _summaries(
        k, v, p["adaptive_mu_k"], p["adaptive_phi"], chunk,
        fault == "mean_pooling")
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    # the chunks a query of window ``w`` sees summarised: those of the ``w``
    # windows before its own; a planted fault sees another number
    seen = {"no_summaries": lambda w: 0, "own_window_too": lambda w: w + 1,
            "last_window_missing": lambda w: max(w - 1, 0)}.get(
                fault, lambda w: w)
    outs, mass = [], 0.0
    for w in range(S // window):
        own = slice(w * window, (w + 1) * window)
        earlier = slice(0, seen(w) * per_window)
        exact = jnp.einsum("bqhd,bkhd->bhqk", q[:, own], k[:, own]) * D ** -0.5
        exact = jnp.where(causal, exact, -jnp.inf)
        pooled = jnp.einsum(
            "bqhd,bjhd->bhqj", q[:, own], pooled_k[:, earlier]) * D ** -0.5
        if fault == "two_softmaxes" and w:
            # the planted fault: each kind under a softmax of its own, the
            # two results averaged
            out = 0.5 * (
                jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(exact, -1),
                           v[:, own])
                + jnp.einsum("bhqj,bjhd->bqhd", jax.nn.softmax(pooled, -1),
                             pooled_v[:, earlier]))
            outs.append(out)
            mass = mass + 0.5 * B * H * window
            continue
        top = jnp.maximum(exact.max(-1), pooled.max(-1, initial=-jnp.inf))
        on_keys = jnp.exp(exact - top[..., None])
        on_summaries = jnp.exp(pooled - top[..., None])
        z = on_keys.sum(-1) + on_summaries.sum(-1)
        out = (jnp.einsum("bhqk,bkhd->bqhd", on_keys, v[:, own])
               + jnp.einsum("bhqj,bjhd->bqhd", on_summaries,
                            pooled_v[:, earlier]))
        outs.append(out / jnp.moveaxis(z, 1, 2)[..., None])
        mass = mass + jnp.sum(on_summaries.sum(-1) / z)
    out = jnp.concatenate(outs, axis=1)
    share = mass / max(B * H * (S - window), 1)
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"]), share,
            largest)


def _later_heads_loss(logits, input_ids):
    """The sum over blocks 1 on of the mean cross entropy of block ``i`` at
    position ``t`` on ``input_ids[t + 1 + i]`` (the departure the
    configuration file notes: the model sees no labels, so each block goes
    without the one target that lies in ``labels`` alone)."""
    S = input_ids.shape[1]
    total = 0.0
    for i in range(1, min(logits.shape[2], S - 1)):
        logp = jax.nn.log_softmax(logits[:, : S - 1 - i, i], axis=-1)
        total = total - jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1 + i:, None], axis=-1))
    return total


def reference(params, input_ids, labels, m, round_through=None, fault=None):
    """(loss of every token [B, S] by block 0 on ``labels``, the further
    heads' summed loss, the summaries' share of the softmax mass a layer,
    the mean largest pooling weight a layer) from the program's parameter
    tree (unboxed, layers stacked on the leading axis), as ``m =
    sizes(config, rehearse)`` reads the file.  The loop over the layers is
    a ``jax.lax.scan`` of the plain body: one layer's temporaries at a time
    beside the training state.  ``fault``: one of ``FAULTS``."""
    eps = float(m["rms_norm_eps"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        if round_through is None:
            return t
        # rounding in float32 arithmetic: the chip's compiler removes a
        # conversion there and back (``families/olmoe.py::_round_through``)
        return load_module("families", "olmoe")._round_through(t, round_through)

    def layer(x, p):
        p = jax.tree.map(f32, p)
        mixed, share, largest = _attention(
            _rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m, fault)
        x = x + mixed
        h = _rms_norm(x, p["post_attn_norm"]["scale"], eps)
        hidden = jax.nn.silu(h @ p["mlp"]["gate_proj"]["kernel"]) * (
            h @ p["mlp"]["up_proj"]["kernel"])
        return x + hidden @ p["mlp"]["down_proj"]["kernel"], (share, largest)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, (share, largest) = jax.lax.scan(layer, x, params["layers"]["layer"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
    logits = logits.reshape(logits.shape[:2] + (int(m["num_pred_heads"]), -1))
    logp = jax.nn.log_softmax(logits[:, :, 0], axis=-1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return losses, _later_heads_loss(logits, input_ids), share, largest


def _report(multi_system, multi_reference, share, largest):
    rel = abs(float(multi_system) - float(multi_reference)) / float(
        multi_reference)
    print(json.dumps({
        "phase": "reference_eva",
        "multi_byte_loss_system": float(multi_system),
        "multi_byte_loss_reference": float(multi_reference),
        "eva_summary_mass_share_by_layer": [float(v) for v in share],
        "eva_pool_weight_max_by_layer": [float(v) for v in largest]}),
        file=sys.stderr, flush=True)
    print(f"check multi_byte_rel_err: {rel} limit {MULTI_BYTE_RTOL}",
          file=sys.stderr, flush=True)


def system_multi_byte_loss(params, input_ids, config, rehearse):
    """The further heads' loss as the program's own forward pass sows it
    (``stats``: ``multi_byte_loss``), at the cell's sizes and precision."""
    model = build(config, rehearse, input_ids.shape[1])
    sown = model.apply({"params": params}, input_ids, mutable=["stats"])[1]
    return sown["stats"]["multi_byte_loss"][0]


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token by block 0; no low-margin shares: nothing of this model is
    chosen by a margin).  The harness compares token losses only, so the
    seven further heads' term is held here: the program's own, sown by its
    forward pass, against the reference's; further off than
    ``MULTI_BYTE_RTOL`` turns every loss to NaN, which no comparison passes.
    Both go to standard error beside the limit, with the two counters that
    say the mechanism decides something on this state."""
    losses, multi_byte, share, largest = reference(
        params, input_ids, labels, sizes(config, rehearse), **planted)
    got = system_multi_byte_loss(params, input_ids, config, rehearse)
    jax.debug.callback(_report, got, multi_byte, share, largest)
    agree = jnp.abs(got - multi_byte) <= MULTI_BYTE_RTOL * multi_byte
    return jnp.where(agree, losses, jnp.nan), jnp.zeros(0)
