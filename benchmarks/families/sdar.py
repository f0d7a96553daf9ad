"""SDAR-30B-A3B (``model_type`` ``sdar_moe``; SDAR, arXiv:2510.06303)
through the program's one decoder (``models/llama.py``), trained by
diffusion over blocks (BD3-LMs, arXiv:2503.09573): a Qwen3-MoE decoder (GQA
with q and k RMS-normalised over each head, RoPE, ``models/moe.py``'s
routed block with renormalised top-k weights, told which experts of the
layer this chip holds) run on ``[noisy copy ; clean copy]`` of each
sequence, ``2S`` rows at the positions ``0..S-1`` twice, under the
block-diffusion mask (``ops/attention.py::block_diffusion_attention``),
with the NELBO over the masked tokens as the model's own objective and the
noise drawn on the device from a key the trainer makes from the step.
Built from a configuration file, with its counts of operations and bytes
and its plain reference (the benchmark's copy of
``dlrover_tpu/models/sdar_reference.py``, which states the step equation by
equation).

In the file ``num_experts`` is the experts HELD HERE (``reduced``) and
``published.num_experts`` the router's width; ``run.first_expert`` says
which.  **A token is a DATA token** (``run.batch x run.seq``, what a user
pays for): the model does the work of two rows for each, and the counts
below say so."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6, "num_experts": 2, "num_experts_per_tok": 3,
        "max_position_embeddings": 128, "published": {"num_experts": 8}}

#: published keys the program has one path for: only these values run
ONLY = {"attention_bias": False, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "use_sliding_window": False, "sliding_window": None,
        "rope_scaling": None}

#: query rows a block of the reference's attention (its scores are ``[heads,
#: rows, 2S]`` float32: 0.5 GiB at 256 rows and the cell's 16,384 keys)
REFERENCE_QUERY_BLOCK = 256


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    assumed = config.get("assumed", {})
    return {**src, "experts_total": int(src["published"]["num_experts"]),
            "first_expert": first,
            "router_aux_loss_coef": float(
                assumed.get("router_aux_loss_coef", 0.001)),
            "block_length": int(assumed.get("block_length", 4)),
            "noise_eps": float(assumed.get("noise_eps", 1e-3)),
            # the vocabulary's (slice's) last row
            "mask_token_id": int(src["vocab_size"]) - 1,
            "query_block": REFERENCE_QUERY_BLOCK}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"block_diffusion", "mask_token_id", "experts_held",
            "norm_topk_prob"} <= fields:
        raise RuntimeError(
            "this checkout's models have no block-diffusion training step "
            "(two copies of a sequence under its mask, the NELBO as the "
            "model's own objective, noise from the step's key): it cannot "
            "run SDAR")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
        if int(config["assumed"]["mask_token_id"]) != m["mask_token_id"]:
            raise ValueError("assumed.mask_token_id is not the vocabulary "
                             "slice's last row")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    cfg = MoELlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]), qk_norm="head",
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, experts_held=m["num_experts"],
        first_expert=m["first_expert"],
        load_balance_coef=m["router_aux_loss_coef"], router_z_coef=0.0,
        block_diffusion=m["block_length"], mask_token_id=m["mask_token_id"],
        noise_eps=m["noise_eps"],
        # a rehearsal compares a few hundred tokens, whose bfloat16 mean is
        # noise: it walks the harness in float32
        **({"dtype": jnp.float32} if rehearse else {}),
    )
    return LlamaForCausalLM(cfg)


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: the factor ``condition``
    multiplies it by}: the whole rule, read from the configuration file
    (none where the file names no ``run.state``): the embedding table times
    ``embed_scale``, but the mask token's row times ``mask_row_scale``
    (absent: the table's factor); each held expert's three matrices times
    ``expert_scale`` (absent: the square root of the number held, as
    ``families/keyevl.py``); the q norm's scale times ``q_scale`` (absent:
    1)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    table = float(state["embed_scale"])
    rows = np.full((m["vocab_size"], 1), table, np.float32)
    rows[m["mask_token_id"]] = float(state.get("mask_row_scale", table))
    held = float(state.get("expert_scale", float(m["num_experts"]) ** 0.5))
    layer = ("layers", "layer")
    rule = {("embed_tokens",): rows,
            layer + ("mlp", "gate_proj"): held,
            layer + ("mlp", "up_proj"): held,
            layer + ("mlp", "down_proj"): held}
    if float(state.get("q_scale", 1.0)) != 1.0:
        rule[layer + ("attn", "q_norm", "scale")] = float(state["q_scale"])
    return rule


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors; same tree, shardings and dtypes, one multiply a leaf on
    the device, no forward pass, no look at a seed, a batch or the routing.
    As ``families/keyevl.py::condition`` for the table (times 100 the router
    sees the token's own vector, so uniform ids spread evenly over the 128
    experts), and three factors for what only this family has: **a quarter
    of the model's rows are one token.**

    * ``mask_row_scale`` 0.1 on the mask token's row.  Under the table's
      factor its 4096 rows carry ONE vector into every router and go to the
      same 8 of 128 experts; each of those that this chip holds adds a
      quarter of a fair share, so the share's rows step 0.75, 1.0, 1.25 by
      layer and seed and the ladder's rung with them.  Small beside the
      table's, its rows carry what the layers bring them.
    * ``q_scale`` 2 on the q norm's learned scale.  An untrained head's
      unit-spread scores over thousands of keys hand every masked row the
      same running mean, and the rows crowd again (the share's rows
      0.86-1.26, the fullest expert 2.5-4.5 times the mean); at 2 a row's
      attention is its own (0.88-1.08 and 1.6-2.5).  At 4 and 8 the scores'
      bfloat16 rounding decides which keys a row sees: the system's median
      token 0.026 and 0.11 off the reference.
    * ``expert_scale`` 1.5 on each held expert's three matrices, where
      Keye's rule has sqrt(16) = 4 (the initialiser counts the expert axis
      into the fan-in).  A masked row has no vector of its own to hold it
      still: it is the sum of what attention and the experts bring it.  At 4
      a flipped expert (a router margin under the bfloat16 error of the
      hidden state: 15% of a layer's rows) moves such a row's loss by 2 to 4
      through the six layers' routers, in the system and in every planted
      fault alike, and no limit stands between them; at 1 the routed branch
      adds nothing a comparison can see (a router that does not renormalise
      reads as the system).  At 1.5 the system's worst token reads 0.21-0.25
      and the mildest fault's 0.60.

    What did NOT work (a factor on the output projection, 4 to 50, to make
    attention outweigh the table for every row; read in this PR and not
    kept in the rule): the system's
    worst token falls to 0.03, and the routing collapses from the second
    layer on (the fullest expert 10 to 16 times the mean: rows that are
    mixtures of mixtures grow alike with depth).  The readings are under
    ``FAULTS`` below and in PERF.md, section 6."""
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

def allowed_pairs(seq, block):
    """Query-key pairs a head the block-diffusion mask allows over the
    ``2S`` rows: the clean half causal by block, ``S^2 / 2 + L S / 2``; the
    noisy half every earlier clean block, ``S^2 / 2 - L S / 2``, and its own
    noisy block, ``L S``."""
    return seq * seq + block * seq


def layer_matmul_params(m):
    """Parameters a ROW multiplies with in one layer on this chip: the four
    attention projections, the router, and of the experts what a row's
    ``num_experts_per_tok`` assignments meet here under even routing (``k *
    held / all`` experts: one, at 8 a row and 16 of 128 held)."""
    h = m["hidden_size"]
    attn = h * m["head_dim"] * (
        2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    met = m["num_experts_per_tok"] * m["num_experts"] / m["experts_total"]
    return attn + h * m["experts_total"] + met * 3 * h * m[
        "moe_intermediate_size"]


def matmul_params(config, rehearse=False):
    """Parameters a DATA token multiplies with on this chip: two rows
    through every layer (its noisy and its clean copy), one through the
    output head (the clean half yields no logits).  Not the embedding table
    or the norms."""
    m = sizes(config, rehearse)
    return (2 * m["num_hidden_layers"] * layer_matmul_params(m)
            + m["hidden_size"] * m["vocab_size"])


def bd_attn_shape(config, batch, seq, rehearse=False):
    """The shapes the attention of one chip works on in one step: ``seq``
    data tokens, twice as many rows."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "rows": 2 * seq,
            "block": m["block_length"], "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "layers": m["num_hidden_layers"]}


def bd_attn_step_flops(shape):
    """Operations THE MODEL asks of one step's attention, whatever computes
    them: two products forward (``q k^T``, ``p v``) and four backward over
    the pairs the mask allows.  No recomputation counted, no pair a dense
    block multiplies and the mask throws away."""
    pairs = shape["batch"] * allowed_pairs(shape["seq"], shape["block"])
    return shape["layers"] * 6 * 2 * shape["heads"] * shape["head_dim"] * pairs


def bd_attn_step_bytes(shape, itemsize=2):
    """Least bytes it moves to and from HBM, each operand read once and
    each result written once a pass: forward q, k, v in and the output out;
    backward those and the output's gradient in, the three gradients out."""
    rows = shape["batch"] * shape["rows"]
    qo = rows * shape["heads"] * shape["head_dim"]
    kv = 2 * rows * shape["kv_heads"] * shape["head_dim"]
    forward = qo + kv + qo
    backward = (qo + kv + qo + qo) + (qo + kv)
    return shape["layers"] * (forward + backward) * itemsize


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per DATA token: ``6 * matmul_params`` (two rows
    through the layers, one through the head) and the attention's asked-for
    work over ``S^2 + L S`` pairs a layer (``bd_attn_step_flops``)."""
    return 6 * matmul_params(config, rehearse) + bd_attn_step_flops(
        bd_attn_shape(config, 1, seq, rehearse)) / seq


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no sort of assignments, no
# sharding, no remat, nothing of ``block_diffusion_attention``: the mask is
# the rule's four lines as a function of (row, column) over all 2S keys, a
# block of query rows at a time so that 8192 fits, every held expert looped
# over plainly.  The noise is DATA to it.
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean (the noisy half's logits read at ``labels``, as the
#: harness reads every model's), on the objective ``L`` (relative) and on
#: its terms token by token over the masked tokens (``masked_median_abs_err``;
#: both held inside ``reference_forward``).  The system multiplies in
#: bfloat16 with float32 accumulation, as the configuration states (router
#: scores, the softmax and the objective in float32); the reference is
#: float32 throughout.  Beside the rounding a dense model shows, one choice
#: is discontinuous: a router margin under the bfloat16 error of the hidden
#: state flips an expert (``LOW_MARGIN``, 14.6-15.6% of a layer's rows).
#: **Half the tokens compared are rows the noise left as they were**: the
#: table's factor holds their logits still whatever the layers do (which is
#: why the median over every token cannot see a fault of the mask that the
#: worst token shows plainly); the other half are masked rows, which are
#: nothing but what the layers bring them.  Each limit stands between
#: readings on the chip at the published widths and the cell's own size (one
#: sequence of 8192 data tokens, 16,384 rows, six layers), on the state
#: ``condition`` gives (``tests/precision_sdar.py``, seeds 4300000511-513,
#: each set of losses through ``jobs_shared.compare_losses``; my chip runs,
#: PR 43; PERF.md section 6 has the readings of every rule tried, and the
#: cell's own runs the system's over further seeds):
#:
#:                        worst token  median token   masked median  mean           L, relative
#:   system               0.21-0.25    0.0078-0.0081  0.0237-0.0246  1.5e-4-8.1e-4  1.4e-5-6.0e-5
#:   float8 control       3.2-3.8      0.078-0.080    0.58-0.61      7e-3-1e-2      3e-4-8e-4
#:   causal_rows          4.7-5.4      0.54-0.55      0.90-0.95
#:   positions_run_on     4.3-5.0      0.24-0.25      0.85-0.88
#:   clean_causal_by_token  0.85-1.04  0.0147-0.0151  0.0535-0.0565
#:   own_clean_block_seen   1.22-1.86  0.0088-0.0093  0.0468-0.0492
#:   own_noisy_block_unseen 1.56-1.77  0.0086-0.0095  0.0378-0.0466
#:   router_not_renormalised 0.60-1.06 0.0055-0.0057  0.0354-0.0461
#:   objective_unweighted   0          0              0              0              0.49
#:
#: ``TOKEN_ATOL`` 0.45 is what holds the cell: 1.5 times over the system's
#: largest of eighteen seeds (0.302 in fifteen runs of the cell, 0.18-0.30;
#: 1.8 times over the table's), 1.3 times under the mildest fault's smallest (a router that does
#: not renormalise; the faults of the mask read 0.85 and more: a block of
#: queries near the sequence's start has few keys, and four more or fewer
#: are a large share of them).  ``MASKED_MEDIAN_ATOL`` 0.031 is a second net
#: under the same faults, steady to 4% from seed to seed: 1.26 times over
#: the system's largest, 1.14 times under the router fault's smallest and
#: 1.2-1.8 under the mask's.  ``MEDIAN_ATOL`` 0.012 is for the precision (the
#: control 6.5 times over it) and for the clean half causal by token (1.2
#: over): 1.5 times over the system's largest; a fault that moves the masked
#: rows alone reads BELOW the system there.  The mean is the average of 8192
#: token errors, which cancel: ``MEAN_ATOL`` 2.5e-3 is there for a bias, 3
#: times over the system's largest; the control reads 3 to 4 times over it.
#: ``OBJECTIVE_RTOL`` 0.01: seventy times over the system's largest, fifty
#: times under the objective without ``1/t``, which moves no logit.
TOKEN_ATOL = 0.45
MEDIAN_ATOL = 1.2e-2
MEAN_ATOL = 2.5e-3
OBJECTIVE_RTOL = 1e-2
MASKED_MEDIAN_ATOL = 3.1e-2
LOW_MARGIN = 1e-2
LOW_MARGIN_SHARE_MAX = 0.25

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above.  ``causal_rows``: a causal mask over the 2S
#: rows; ``clean_causal_by_token``: the clean half causal by token, not by
#: block; ``own_clean_block_seen``: noisy queries allowed their OWN block's
#: clean keys (the leak that makes the loss meaningless); ``positions_run_on``:
#: positions ``arange(2S)``; ``own_noisy_block_unseen``: the noisy half's
#: own-block keys left out; ``router_not_renormalised``: the kept softmax
#: weights as they are; ``objective_unweighted``: the objective without
#: ``1/t`` (caught by ``L``'s own limit: it moves no logit).
FAULTS = ("causal_rows", "clean_causal_by_token", "own_clean_block_seen",
          "positions_run_on", "own_noisy_block_unseen",
          "router_not_renormalised", "objective_unweighted")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Rotary embedding on [B, R, H, D] at ``positions`` [R], halves
    convention (the published ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _allowed(r, c, seq, block, fault=None):
    """Whether query row ``r`` may see key row ``c``: rows ``[0, S)`` the
    noisy copy, ``[S, 2S)`` the clean one, a row's block ``(r mod S) //
    block``.  Noisy sees noisy: the same block.  Noisy sees clean: an
    earlier block.  Clean sees clean: the same or an earlier block.  Clean
    sees noisy: never."""
    if fault == "causal_rows":
        return c <= r
    r_noisy, c_noisy = r < seq, c < seq
    b_r, b_c = (r % seq) // block, (c % seq) // block
    noisy_noisy = b_r == b_c
    noisy_clean = b_c < b_r
    clean_clean = b_c <= b_r
    if fault == "own_noisy_block_unseen":
        noisy_noisy = jnp.zeros_like(noisy_noisy)
    if fault == "own_clean_block_seen":
        noisy_clean = b_c <= b_r
    if fault == "clean_causal_by_token":
        clean_clean = c <= r
    return jnp.where(r_noisy, jnp.where(c_noisy, noisy_noisy, noisy_clean),
                     ~c_noisy & clean_clean)


def _attention(h, p, m, fault):
    """The ``2S`` rows ``h`` [B, 2S, E]: a block of query rows at a time
    against every key, every head of it at once."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    B, rows = h.shape[:2]
    seq, L = rows // 2, int(m["block_length"])
    positions = jnp.arange(rows)
    if fault != "positions_run_on":
        positions = positions % seq
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), positions, theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), positions, theta)
    heads, dim = q.shape[2:]
    n = min(int(m["query_block"]), rows)
    while rows % n:
        n -= 1
    # query head i reads kv head i // groups: [B, R, kv heads, groups, D]
    q = q.reshape(B, rows, k.shape[2], heads // k.shape[2], dim)
    o_proj = p["o_proj"]["kernel"].reshape(q.shape[2:] + (-1,))

    def one_block(first):
        mine = jax.lax.dynamic_slice_in_dim(q, first, n, 1)
        scores = jnp.einsum("bqngd,bknd->bqngk", mine, k) * dim ** -0.5
        keep = _allowed(first + jnp.arange(n)[:, None],
                        jnp.arange(rows)[None, :], seq, L, fault)
        # a finite fill: a planted row with no key at all reads as a mean
        # of the values and not as NaN
        probs = jax.nn.softmax(jnp.where(
            keep[None, :, None, None], scores, jnp.finfo(jnp.float32).min), -1)
        return jnp.einsum("bqngd,ngde->bqe", jnp.einsum(
            "bqngk,bknd->bqngd", probs, v), o_proj)

    mixed = jax.lax.map(one_block, jnp.arange(0, rows, n))
    return jnp.moveaxis(mixed, 0, 1).reshape(B, rows, -1)


def _experts(h, p, m, fault):
    """(result, share of rows with a low router margin): every held expert
    computes every row, one after the other; a row's k kept weights are
    divided by their sum; the experts that are not here add nothing."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    largest = jax.lax.top_k(logits, k + 1)[0]
    gates = jnp.where(logits >= largest[..., k - 1: k], probs, 0.0)
    if fault != "router_not_renormalised":
        gates = gates / gates.sum(axis=-1, keepdims=True)
    here = p["gate_proj"].shape[0]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, low


def reference(params, noisy_ids, clean_ids, weights, labels, m,
              round_through=None, fault=None):
    """(loss of every token [B, S]: the noisy half's logits read at
    ``labels``; the objective's first term ``L = mean(weights * CE(noisy
    row i, clean token i))``; the share of each layer's rows with a low
    router margin; ``CE(noisy row i, clean token i)`` of every token, the
    objective's terms before their weights) from the program's parameter tree (unboxed, layers
    stacked on the leading axis), as ``m = sizes(config, rehearse)`` reads
    the file.  The loop over the layers is a ``jax.lax.scan`` of the plain
    body: one layer's float32 weights at a time beside the training state.
    ``fault``: one of ``FAULTS``."""
    eps = float(m["rms_norm_eps"])
    seq = clean_ids.shape[1]

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        if round_through is None:
            return t
        # rounding in float32 arithmetic: the chip's compiler removes a
        # conversion there and back (``families/olmoe.py::_round_through``)
        return load_module("families", "olmoe")._round_through(t, round_through)

    def layer(x, p):
        p = jax.tree.map(f32, p)
        x = x + _attention(
            _rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m, fault)
        out, low = _experts(
            _rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m,
            fault)
        return x + out, low

    with jax.default_matmul_precision("highest"):
        rows = jnp.concatenate([noisy_ids, clean_ids], axis=1)
        x = f32(params["embed_tokens"])[rows]
        x, low = jax.lax.scan(layer, x, params["layers"]["layer"])
        x = _rms_norm(x[:, :seq], f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)

    def taken(at):
        return -jnp.take_along_axis(logp, at[..., None], axis=-1)[..., 0]

    if fault == "objective_unweighted":
        weights = (weights > 0).astype(jnp.float32)
    terms = taken(clean_ids)
    return taken(labels), jnp.mean(weights * terms), low, terms


def draw_noise(input_ids, config, rehearse):
    """``(noisy_ids, weights)`` the program's forward pass draws for these
    ids where its caller gives no key (the harness's forward check): the
    program's own ``noise_blocks`` on its own default key, handed to both
    sides.  The noise is data to the reference, as weights are."""
    from dlrover_tpu.models.llama import noise_blocks

    cfg = build(config, rehearse, input_ids.shape[1]).config
    return noise_blocks(input_ids, cfg.step_rngs(0)["noise"],
                        cfg.block_diffusion, cfg.mask_token_id, cfg.noise_eps)


def system_objective(params, input_ids, config, rehearse):
    """``L``'s first term as the program's own forward pass sows it
    (``losses``: ``nelbo``), its terms before their weights (the cross
    entropy of every noisy row against the clean token at its position,
    from the logits of the same pass) and the counters of its noise, at the
    cell's sizes and precision."""
    model = build(config, rehearse, input_ids.shape[1])
    logits, sown = model.apply({"params": params}, input_ids,
                               mutable=["losses", "stats"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    terms = -jnp.take_along_axis(logp, input_ids[..., None], axis=-1)[..., 0]
    return (sown["losses"]["nelbo"][0], terms,
            sown["stats"]["bd_masked_share"][0],
            sown["stats"]["bd_weight_max"][0])


def masked_median_abs_err(got, want, weights):
    """The median over the MASKED tokens (``weights > 0``: the tokens the
    objective is over) of ``|got - want|``, two sets of the objective's
    terms.  The harness's median is over every token, and half of them are
    rows the noise left as they were, whose logits the embedding table's
    factor holds still whatever the layers do."""
    return jnp.nanmedian(jnp.where(weights > 0, jnp.abs(got - want), jnp.nan))


def _report(got, want, masked_median, masked, weight_max, router_low):
    rel = abs(float(got) - float(want)) / float(want)
    print(json.dumps({
        "phase": "reference_objective",
        "objective_system": float(got), "objective_reference": float(want),
        "objective_rel_err": rel, "objective_rtol": OBJECTIVE_RTOL,
        "masked_median_abs_err": float(masked_median),
        "masked_median_atol": MASKED_MEDIAN_ATOL,
        "bd_masked_share": float(masked), "bd_weight_max": float(weight_max),
        "router_low_margin": LOW_MARGIN,
        "router_low_margin_share_by_layer": [float(v) for v in router_low],
        "router_low_margin_share_max": LOW_MARGIN_SHARE_MAX}),
        file=sys.stderr, flush=True)
    print(f"check objective_rel_err: {rel} limit {OBJECTIVE_RTOL}",
          file=sys.stderr, flush=True)
    print(f"check masked_median_abs_err: {float(masked_median)} limit "
          f"{MASKED_MEDIAN_ATOL}", file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each layer's rows with a low router margin,
    which it holds to ``LOW_MARGIN_SHARE_MAX``).  The harness compares token
    losses only, so the objective is held here, as ``families/keyevl.py``
    holds ``L_I``: the program's own, sown by its forward pass, against the
    reference's on the same noise, and its terms token by token over the
    masked tokens (``masked_median_abs_err``); the one further off than
    ``OBJECTIVE_RTOL`` or the other than ``MASKED_MEDIAN_ATOL`` turns every
    loss to NaN, which no comparison passes.  Every number goes to
    standard error beside its limit."""
    noisy, weights = draw_noise(input_ids, config, rehearse)
    losses, objective, low, terms = reference(
        params, noisy, input_ids, weights, labels, sizes(config, rehearse),
        **planted)
    got, got_terms, masked, weight_max = system_objective(
        params, input_ids, config, rehearse)
    median = masked_median_abs_err(got_terms, terms, weights)
    jax.debug.callback(
        _report, got, objective, median, masked, weight_max, low)
    agree = (jnp.abs(got - objective) <= OBJECTIVE_RTOL * objective) & (
        median <= MASKED_MEDIAN_ATOL)
    return jnp.where(agree, losses, jnp.nan), low


def stand_in(config, rehearse=False, hold_objective=True, **planted):
    """``(params, ids, labels) -> token losses`` for ``jobs_shared.
    reference_check``'s ``stand_in``: the reference with ``planted`` (the
    float8 control, a fault of ``FAULTS``) in the program's place, on the
    noise the program's forward check draws.  As the program's own
    objective is held to the reference's inside ``reference_forward``, so is
    the planted one's here: further off than ``OBJECTIVE_RTOL`` turns every
    loss to NaN.  ``hold_objective`` false: the token losses as they are,
    to see whether the harness's own limits catch what was planted."""
    m = sizes(config, rehearse)

    def losses_of(params, input_ids, labels):
        noisy, weights = draw_noise(input_ids, config, rehearse)
        losses, objective, _, terms = reference(
            params, noisy, input_ids, weights, labels, m, **planted)
        if not hold_objective:
            return losses
        _, want, _, want_terms = reference(
            params, noisy, input_ids, weights, labels, m)
        agree = (jnp.abs(objective - want) <= OBJECTIVE_RTOL * want) & (
            masked_median_abs_err(terms, want_terms, weights)
            <= MASKED_MEDIAN_ATOL)
        return jnp.where(agree, losses, jnp.nan)

    return losses_of


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
