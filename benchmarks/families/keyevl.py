"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``; a Qwen3-MoE
decoder with a DeepSeek-V3.2-style sparse-attention indexer, ``sa_config``)
through the program's one decoder (``models/llama.py``): GQA with q and k
RMS-normalised over each head, RoPE, attention over the ``topk`` keys a
learned indexer selects (``ops/attention.py::indexed_sparse_attention``),
and ``models/moe.py``'s routed block with renormalised top-k weights, told
which experts of the layer this chip holds.  Built from a configuration
file, with its counts of operations and bytes and its plain reference (the
benchmark's copy of ``dlrover_tpu/models/keye_reference.py``, which states
the layer equation by equation).

In the file ``num_experts`` is the experts HELD HERE (``reduced``) and
``published.num_experts`` the router's width; ``run.first_expert`` says
which.  The vision tower is not built: the cell trains on text tokens, for
which the three position streams of ``mrope_section`` coincide and the
rotary embedding is plain RoPE."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

TINY = {"vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000000.0,
        "rms_norm_eps": 1e-6, "num_experts": 2, "num_experts_per_tok": 3,
        "max_position_embeddings": 128, "published": {"num_experts": 8},
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                      "q_chunk_size": 16, "topk": 16}}

#: published keys the program has one path for: only these values run
ONLY = {"attention_bias": False, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "use_sliding_window": False, "sliding_window": None}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    assumed = config.get("assumed", {})
    return {**src, "experts_total": int(src["published"]["num_experts"]),
            "first_expert": first,
            "router_aux_loss_coef": float(
                assumed.get("router_aux_loss_coef", 0.001)),
            "query_block": int(src["sa_config"]["q_chunk_size"])}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"index_topk", "experts_held", "norm_topk_prob"} <= fields:
        raise RuntimeError(
            "this checkout's models have no sparse-attention indexer, no "
            "renormalised top-k weights and no share of an expert layer: "
            "it cannot run Keye-VL-2.0's language model")
    m = sizes(config, rehearse)
    sa = m["sa_config"]
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
        if (sa["indexer_num_kv_heads"] != 1
                or sa["q_chunk_size"] != sa["kv_chunk_size"]
                or config["rope_scaling"]["rope_type"] != "default"):
            raise ValueError("sa_config or rope_scaling the program has no "
                             "path for")
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
        index_topk=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_block=sa["q_chunk_size"],
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
    rule = config["run"]["state"]
    held = float(sizes(config, rehearse)["num_experts"]) ** 0.5
    mlp = ("layers", "layer", "mlp")
    return {("embed_tokens",): float(rule["embed_scale"]),
            mlp + ("gate_proj",): held, mlp + ("up_proj",): held,
            mlp + ("down_proj",): held}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors; same tree, shardings and dtypes, one multiply a leaf on
    the device, no forward pass, no look at a batch.  As
    ``families/olmoe.py::condition``, for the same two reasons:

    * the embedding table times ``run.state.embed_scale``: the router then
      sees the token's own vector and not a sequence's slowly moving
      context, uniform random tokens spread evenly over the 128 experts,
      and the rows this chip's 16 experts take stay on the ladder's first
      extent on every seed (a share's rows swung 4.2 times the expected on
      ``create_state``'s state: ledger, PR 31);
    * each held expert's three matrices times the square root of the number
      held: the initialiser counts the expert axis of the stacked arrays
      into the fan-in, so the routed branch would add nothing a comparison
      of the model's output could see.

    The attention is left as ``create_state`` makes it: an untrained head's
    scores are N(0, 1) over the keys kept, and the nearest 2048 keys in
    place of the highest 2048 is still outside the limits below by the
    median token and by ``L_I`` (the readings are under ``TOKEN_ATOL``)."""
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

def causal_pairs(seq):
    return seq * (seq + 1) // 2


def kept_pairs(seq, topk):
    """Query-key pairs the selection keeps: every earlier key while there
    are no more than ``topk``, ``topk`` after."""
    short = min(seq, topk)
    return causal_pairs(short) + (seq - short) * topk


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: the four attention
    projections, the indexer's three, the router, the output head, and of
    the experts what a token's ``num_experts_per_tok`` assignments meet
    here under even routing: ``k * held / all`` experts (one, at 8 a token
    and 16 of 128 held).  Not the embedding table or the norms."""
    m = sizes(config, rehearse)
    h, sa = m["hidden_size"], m["sa_config"]
    attn = h * m["head_dim"] * (
        2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    indexer = h * (sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
                   + sa["indexer_head_dim"])
    experts_met = m["num_experts_per_tok"] * m["num_experts"] / m["experts_total"]
    experts = experts_met * 3 * h * m["moe_intermediate_size"]
    layer = attn + indexer + h * m["experts_total"] + experts
    return m["num_hidden_layers"] * layer + h * m["vocab_size"]


def sparse_attn_shape(config, batch, seq, rehearse=False):
    """The shapes the sparse attention of one chip works on in one step,
    and how many threshold searches a step runs."""
    m = sizes(config, rehearse)
    sa = m["sa_config"]
    block = min(sa["q_chunk_size"], seq)
    blocks = seq // block
    # blocks whose queries all have more keys than they may keep run the
    # threshold search: twice a layer (forward, and the layer's
    # rematerialised forward; the block's own rematerialisation is handed
    # the mask), two loops a search
    searching = sum(1 for i in range(blocks) if (i + 1) * block > sa["topk"])
    return {"batch": batch, "seq": seq, "block": block,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "index_heads": sa["indexer_num_heads"],
            "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
            "layers": m["num_hidden_layers"],
            "select_loops_per_step": 2 * 2 * searching * m["num_hidden_layers"]}


def sparse_attn_step_flops(shape):
    """Operations the model asks of one step's sparse attention, whatever
    computes them: the index scores over every causal pair (one product
    forward) and their gradient over the pairs kept (two), the weighting of
    the index heads beside each; attention over the pairs kept, two
    products forward and four backward.  No recomputation counted, no pair
    a dense block multiplies and the mask throws away."""
    causal = shape["batch"] * causal_pairs(shape["seq"])
    kept = shape["batch"] * kept_pairs(shape["seq"], shape["topk"])
    index = 2 * shape["index_heads"] * (shape["index_dim"] + 1) * (
        causal + 2 * kept)
    attention = 6 * 2 * shape["heads"] * shape["head_dim"] * kept
    return shape["layers"] * (index + attention)


def sparse_attn_step_bytes(shape, itemsize=2):
    """Least bytes it moves to and from HBM, each operand read once and
    each result written once: forward q, k, v, the indexer's q, k and w in
    and the output out; backward those and the output's gradient in, their
    gradients out."""
    rows = shape["batch"] * shape["seq"]
    qo = rows * shape["heads"] * shape["head_dim"]
    kv = 2 * rows * shape["kv_heads"] * shape["head_dim"]
    index = rows * (shape["index_heads"] * (shape["index_dim"] + 1)
                    + shape["index_dim"])
    forward = qo + kv + index + qo
    backward = (qo + kv + index + qo) + (qo + kv + index)
    return shape["layers"] * (forward + backward) * itemsize


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` and the sparse
    attention's asked-for work (``sparse_attn_step_flops``)."""
    shape = sparse_attn_shape(config, 1, seq, rehearse)
    return 6 * matmul_params(config, rehearse) + (
        sparse_attn_step_flops(shape) / seq)


def gmm_shape(config, tokens_per_step, chips=1):
    """The grouped matmuls of this chip in one step: ``rows`` expected
    through each layer's held experts under even routing (tokens x experts
    a token x held / all), three weight matrices of ``experts`` experts."""
    from dlrover_tpu.models.moe import ladder

    m = sizes(config, False)
    assignments = tokens_per_step * m["num_experts_per_tok"]
    return {"rows": assignments * m["num_experts"] // m["experts_total"],
            "experts": m["num_experts"], "hidden": m["hidden_size"],
            "width": m["moe_intermediate_size"],
            "layers": m["num_hidden_layers"],
            # what a pass over the sorted assignments may run at: the
            # program's own ladder, whose shapes mark its operations
            "extents": ladder(assignments, m["num_experts"],
                              m["experts_total"])}


def gmm_step_flops(shape):
    """Three matmuls a row forward (gate, up, down) and twice that
    backward; the forward that ``remat`` repeats is not counted."""
    per_layer = 3 * 2 * shape["rows"] * shape["hidden"] * shape["width"]
    return 3 * shape["layers"] * per_layer


def gmm_step_bytes(shape, itemsize=2):
    """As ``families/olmoe.py::gmm_step_bytes``: each operand read once and
    each result written once, backward twice the forward's traffic."""
    rows, h, w = shape["rows"], shape["hidden"], shape["width"]
    activations = (2 * rows * h + 2 * rows * w) + (rows * w + rows * h)
    weights = 3 * shape["experts"] * h * w
    return 3 * shape["layers"] * (activations + weights) * itemsize


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy, no kernel, no threshold search, no
# sort of assignments, no sharding, no remat; the selection by
# ``jax.lax.top_k`` on the whole row, every held expert looped over plainly,
# queries in blocks so that 8192 fits
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token, on the mean, and on ``L_I`` of each layer (relative).  The system
#: multiplies in bfloat16 with float32 accumulation, as the configuration
#: states (router and index scores in float32); the reference is float32
#: throughout.  Beside the rounding a dense model shows, two choices are
#: discontinuous: a router margin under the bfloat16 error of the hidden
#: state flips an expert (``LOW_MARGIN``, 14-16% of a layer's tokens), and
#: the 2048th and 2049th index scores of a query lie closer than
#: ``INDEX_LOW_MARGIN`` in 68% of a layer's queries, so system and
#: reference keep a few different keys of 2048 for most queries: rounding,
#: not a fault, and counted in the readings below.  Each limit stands
#: between readings on the chip at the published widths and the cell's own
#: size (one sequence of 8192, six layers), on the state ``condition`` gives
#: (``tests/precision_keyevl.py``, nineteen seeds, and for the system seven
#: runs of the cell besides, each set of losses through
#: ``jobs_shared.compare_losses``; my chip runs, PR 33; PERF.md section 6
#: has the readings on the state the rule gave at first, too):
#:
#:                 system           float8 control   nearest 2048    absent experts
#:   worst token   0.080-0.151      0.178-0.327      0.229-0.304     1.00-1.45
#:   median token  0.00476-0.00503  0.0308-0.0323    0.0223-0.0240   0.188-0.197
#:   mean          3.9e-6-2.7e-4    7.8e-7-9.7e-4    1.1e-5-1.2e-3   1.4e-4-7.2e-3
#:   L_I, relative 4.8e-5-2.0e-4    9.3e-4-2.5e-3    0.59-0.60       8.9e-4-5.0e-3
#:
#: The median is the number that holds the cell: steady to 3% from seed to
#: seed, the selection's fault 4.4 times and the control 6.1 times the
#: system's largest, so ``MEDIAN_ATOL`` 0.01 stands 2.0 times over the
#: system's largest and 2.2 times under the fault's smallest.  The worst
#: token swings with the routing and the selection (it is the largest of
#: 8192); ``TOKEN_ATOL`` is there for one token or one row gone wrong, which
#: no median sees: 1.5 times over the system's largest of twenty-six
#: seeds, 4.3 times under what the absent experts' part reads, at or under
#: the selection's fault on every seed; the control reads on both sides of
#: it and need not fail it.  The mean is the average of 8192 token errors,
#: which cancel: system and control overlap, so no value separates them;
#: ``MEAN_ATOL`` 6e-4 is there for a bias, 2.2 times over the system's
#: largest, under the absent experts' on seventeen seeds of nineteen.
#: ``L_I`` is held layer by layer: the system within 2e-4 of the reference,
#: the nearest keys 0.59 off; 0.01 is fifty times over the one and sixty
#: times under the other (float8 moves it by 2.5e-3 at most: the loss is a
#: divergence of two distributions both computed from the same rounded
#: weights).
TOKEN_ATOL = 0.23
MEDIAN_ATOL = 1e-2
MEAN_ATOL = 6e-4
INDEX_LOSS_RTOL = 1e-2
LOW_MARGIN = 1e-2
LOW_MARGIN_SHARE_MAX = 0.25
INDEX_LOW_MARGIN = 1e-3


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """Rotary embedding on [B, S, H, D], halves convention (the published
    ``rotate_half``)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _select(index_scores, first, topk, nearest, search):
    """(keep [B, q, S], low [B, q]) for the queries from ``first`` on: the
    ``topk`` highest scores among the earlier keys by ``jax.lax.top_k`` on
    the whole row (ties to the earlier key), all while there are no more
    (``search`` false: no query of the block has more).  ``nearest``: the
    planted fault, the ``topk`` nearest keys."""
    B, n, S = index_scores.shape
    t = first + jnp.arange(n)[:, None]
    causal = jnp.arange(S)[None, :] <= t
    none_low = jnp.zeros((B, n), bool)
    if nearest:
        return jnp.broadcast_to(causal & (jnp.arange(S) > t - topk),
                                index_scores.shape), none_low
    if not search:
        return jnp.broadcast_to(causal, index_scores.shape), none_low
    top, at = jax.lax.top_k(jnp.where(causal, index_scores, -jnp.inf), topk + 1)
    keep = jnp.zeros(index_scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(n)[None, :, None],
        at[..., :topk]].set(top[..., :topk] > -jnp.inf)
    low = (top[..., topk] > -jnp.inf) & (
        top[..., topk - 1] - top[..., topk] < INDEX_LOW_MARGIN)
    return keep, low


def _attention(h, p, m, nearest):
    """(o W_o, L_I of the layer, share of queries with a low selection
    margin): a block of queries at a time, every head of it at once."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    topk = int(m["sa_config"]["topk"])
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    q_i = _rope(jnp.einsum("bse,ejc->bsjc", h, p["index_q_proj"]["kernel"]),
                theta)
    k_i = _layer_norm(h @ p["index_k_proj"]["kernel"],
                      p["index_k_norm"]["scale"], p["index_k_norm"]["bias"])
    k_i = _rope(k_i[:, :, None], theta)[:, :, 0]
    w = h @ p["index_w_proj"]["kernel"]
    heads, dim = q.shape[2:]
    B, S = h.shape[:2]
    block = min(int(m["query_block"]), S)
    # query head i reads kv head i // groups: [B, S, kv heads, groups, D]
    q = q.reshape(B, S, k.shape[2], heads // k.shape[2], dim)
    o_proj = p["o_proj"]["kernel"].reshape(q.shape[2:] + (-1,))

    def one_block(first, search):
        rows = lambda t: jax.lax.dynamic_slice_in_dim(t, first, block, 1)  # noqa: E731
        dots = jnp.einsum("bqjc,bkc->bqjk", rows(q_i), k_i)
        index = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(dots), rows(w)) * (
            q_i.shape[-1] ** -0.5 * q_i.shape[-2] ** -0.5)
        keep, low = _select(index, first, topk, nearest, search)
        scores = jnp.einsum("bqngd,bknd->bqngk", rows(q), k) * dim ** -0.5
        probs = jax.nn.softmax(
            jnp.where(keep[:, :, None, None], scores, -jnp.inf), axis=-1)
        mixed = jnp.einsum("bqngd,ngde->bqe",
                           jnp.einsum("bqngk,bknd->bqngd", probs, v), o_proj)
        target = probs.sum(axis=(2, 3)) / heads
        log_index = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), -1)
        kl = jnp.where(keep & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - log_index), 0.0)
        return mixed, kl.sum(), low.sum()

    # ``top_k`` only where some query of the block has more keys than it keeps
    firsts = list(range(0, S, block))
    parts = [(search, [f for f in firsts if (f + block > topk) == search])
             for search in (False, True)]
    mixed, kl, low = (jnp.concatenate(part) for part in zip(*(
        jax.lax.map(lambda f, s=search: one_block(f, s), jnp.asarray(at))
        for search, at in parts if at)))
    mixed = jnp.moveaxis(mixed, 0, 1).reshape(B, S, -1)
    return mixed, kl.sum() / (B * S), low.sum() / (B * S)


def _experts(h, p, m, absent):
    """(result, share of tokens with a low router margin): every held
    expert computes every token, one after the other; a token's k kept
    weights are divided by their sum; the experts that are not here add
    nothing.  ``absent``: the planted fault, every expert's weight put on
    the held ones in turn, as if the absent chips' parts had come in."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    largest = jax.lax.top_k(logits, k + 1)[0]
    gates = jnp.where(logits >= largest[..., k - 1: k], probs, 0.0)
    gates = gates / gates.sum(axis=-1, keepdims=True)
    here = p["gate_proj"].shape[0]
    if absent:
        gates = gates.reshape(gates.shape[:-1] + (-1, here)).sum(axis=-2)
    else:
        gates = gates[..., first: first + here]

    def one_expert(out, expert):
        gate_w, up_w, down_w, gate = expert
        hidden = jax.nn.silu(h @ gate_w) * (h @ up_w)
        return out + gate[..., None] * (hidden @ down_w), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["gate_proj"], p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates, -1, 0)))
    low = jnp.mean(largest[..., k - 1] - largest[..., k] < LOW_MARGIN)
    return out, low


def reference(params, input_ids, labels, m, round_through=None,
              nearest=False, absent=False):
    """(loss of every token [B, S], L_I a layer, share of each layer's
    queries with a low selection margin, share of its tokens with a low
    router margin) from the program's parameter tree (unboxed, layers
    stacked on the leading axis), as ``m = sizes(config, rehearse)`` reads
    the file.  The loop over the layers is a ``jax.lax.scan`` of the plain
    body: one layer's float32 weights at a time beside the training state."""
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
        mixed, index_loss, index_low = _attention(
            _rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], m, nearest)
        x = x + mixed
        out, router_low = _experts(
            _rms_norm(x, p["post_attn_norm"]["scale"], eps), p["mlp"], m,
            absent)
        return x + out, (index_loss, index_low, router_low)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, (index_loss, index_low, router_low) = jax.lax.scan(
            layer, x, params["layers"]["layer"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return losses, index_loss, index_low, router_low


def _report(index_system, index_reference, index_low, router_low):
    rel = abs(index_system - index_reference) / index_reference
    print(json.dumps({
        "phase": "reference_index",
        "index_loss_system": [float(v) for v in index_system],
        "index_loss_reference": [float(v) for v in index_reference],
        "index_loss_rel_err": [float(v) for v in rel],
        "index_loss_rtol": INDEX_LOSS_RTOL,
        "index_low_margin": INDEX_LOW_MARGIN,
        "index_low_margin_share_by_layer": [float(v) for v in index_low],
        "router_low_margin": LOW_MARGIN,
        "router_low_margin_share_by_layer": [float(v) for v in router_low],
        "router_low_margin_share_max": LOW_MARGIN_SHARE_MAX}),
        file=sys.stderr, flush=True)
    for i, value in enumerate(rel):
        print(f"check index_loss_rel_err.layer{i}: {float(value)} limit "
              f"{INDEX_LOSS_RTOL}", file=sys.stderr, flush=True)


def system_index_loss(params, input_ids, config, rehearse):
    """``L_I`` layer by layer as the program's own forward pass sows it
    (``stats``: ``index_loss``), at the cell's sizes and precision."""
    model = build(config, rehearse, input_ids.shape[1])
    sown = model.apply({"params": params}, input_ids, mutable=["stats"])[1]
    return sown["stats"]["layers"]["layer"]["attn"]["index_loss"][0]


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each layer's tokens with a low router margin,
    which it holds to ``LOW_MARGIN_SHARE_MAX``).  The harness compares token
    losses only, so ``L_I`` is held here: the program's own, sown by its
    forward pass, against the reference's, layer by layer; a layer further
    off than ``INDEX_LOSS_RTOL`` turns every loss to NaN, which no
    comparison passes.  Every number goes to standard error beside its
    limit, with the share of queries whose selection hangs on rounding."""
    losses, index_loss, index_low, router_low = reference(
        params, input_ids, labels, sizes(config, rehearse), **planted)
    got = system_index_loss(params, input_ids, config, rehearse)
    jax.debug.callback(_report, got, index_loss, index_low, router_low)
    agree = jnp.all(jnp.abs(got - index_loss) <= INDEX_LOSS_RTOL * index_loss)
    return jnp.where(agree, losses, jnp.nan), router_low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
