"""Phi-4-mini-flash-reasoning (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``: SambaY with differential attention,
arXiv:2507.06607) through the program's one decoder (``models/llama.py``):
Mamba-1 layers (``MambaMixer`` over ``ops/selective_scan.py``), differential
attention under a window of 512 and whole, a pair of layers that hands on
its scan output and its keys and values, and a cross-decoder of gated
memory units and cross attention that reads them; LayerNorm with bias, a
tied head, no position signal but the scan's.  Built from a configuration
file, with its counts of operations and bytes and its plain reference (the
benchmark's copy of ``dlrover_tpu/models/phi4flash_reference.py``, which
states the layers equation by equation), with the faults and the controls
``tests/precision_phi4flash.py`` plants.

The layout is derived from ``num_hidden_layers``, ``mb_per_layer`` and
``sliding_window`` by the model code's rule (``kinds_of``; the program's is
``models/llama.py::hybrid_layout``): nothing lists the layers.  The
vocabulary in the file is this chip's share (``reduced``); every layer is
whole."""

import dataclasses
import json
import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

#: rounding in float32 arithmetic: the chip's compiler removes a conversion
#: there and back (``families/olmoe.py::_round_through``)
_round_through = load_module("families", "olmoe")._round_through

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 16, "mb_per_layer": 2,
        "layer_norm_eps": 1e-5, "max_position_embeddings": 128}

#: published keys the program has one path for: only these values run
ONLY = {"hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "embd_pdrop": 0, "resid_pdrop": 0}

#: ``Phi4FlashConfig``'s defaults, which the published file leaves alone
#: (the configuration file lists them under ``assumed``)
DEFAULTS = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}


def sizes(config, rehearse):
    src = TINY if rehearse else config
    assumed = {} if rehearse else config.get("assumed", {})
    m = {**src, **{key: int(assumed.get(key, default))
                   for key, default in DEFAULTS.items()}}
    if rehearse:
        m["mamba_d_state"] = 8
    hidden, heads = int(m["hidden_size"]), int(m["num_attention_heads"])
    m["head_dim"] = hidden // heads
    m["d_inner"] = m["mamba_expand"] * hidden
    m["mamba_dt_rank"] = int(assumed.get(
        "mamba_dt_rank", math.ceil(hidden / 16)))
    # queries a block of the reference's attention, against every key at
    # every pair of heads and both maps: 128 x 16,384 x 20 x 2 float32
    m["query_block"] = 128
    return m


def kinds_of(m):
    """``[(kind, window or None)]`` of every layer by the model code's rule:
    kinds ``mamba``, ``attn``, ``gmu``, ``cross``."""
    L, per = int(m["num_hidden_layers"]), int(m["mb_per_layer"])
    out = []
    for i in range(L):
        if i >= L // 2 + 2:
            out.append(("cross" if i % 2 else "gmu", None))
        elif i % per == 0:
            out.append(("mamba", None))
        else:
            out.append(("attn", int(m["sliding_window"])
                        if i < L // 2 and i % 2 else None))
    return out


def build(config, rehearse, seq):
    from dlrover_tpu.models import llama

    fields = {f.name for f in dataclasses.fields(llama.LlamaConfig)}
    if not {"mamba_state", "diff_attention", "memory_layers",
            "tie_embeddings", "norm"} <= fields:
        raise RuntimeError(
            "this checkout's models have no selective scan, no differential "
            "attention, no layers that hand on a memory, no tied head and "
            "no LayerNorm: it cannot run Phi-4-mini-flash")
    m = sizes(config, rehearse)
    if not rehearse:
        for key, only in ONLY.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key}={config[key]!r}: the program runs "
                                 f"only {only!r}")
    if seq > m["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds max_position_embeddings")
    cfg = llama.LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["layer_norm_eps"]),
        norm="layer", tie_embeddings=True, attention_bias=True,
        diff_attention=True, use_rope=False,
        sliding_window=int(m["sliding_window"]),
        mamba_state=m["mamba_d_state"], mamba_conv=m["mamba_d_conv"],
        mamba_expand=m["mamba_expand"], mamba_dt_rank=m["mamba_dt_rank"],
        **llama.hybrid_layout(m["num_hidden_layers"], m["mb_per_layer"]),
        # the kernels, or (rehearsal, on the CPU) the reference core and the
        # ``jax.numpy`` scan: "flash" raises off the chip.  A rehearsal
        # compares a few hundred tokens, whose bfloat16 mean is noise: it
        # walks the harness in float32
        **({"dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return llama.LlamaForCausalLM(cfg)


def layer_paths(m):
    """``[(path of the layer's parameters in the tree, index into the
    stacked leaves or ``()``, kind, window)]`` in the stack's order: the
    periods under ``layers/<run>/layer`` stacked ``[periods, 1, ...]``, the
    pair that hands on under ``memory/<run>/layer`` as it is, the
    cross-decoder under ``cross/<run>/layer`` ``[periods, 1, ...]``."""
    kinds = kinds_of(m)
    L, per = len(kinds), int(m["mb_per_layer"])
    names = [("mamba" if kind == "mamba" else "swa" if window else "gqa")
             + f"_{j}" for j, (kind, window) in enumerate(kinds[:per])]
    out = []
    for i, (kind, window) in enumerate(kinds):
        if i < L // 2:
            out.append((("layers", names[i % per], "layer"), (i // per, 0)))
        elif i < L // 2 + 2:
            out.append((("memory", ("mamba_0", "gqa_1")[i - L // 2],
                         "layer"), ()))
        else:
            j = i - L // 2 - 2
            out.append((("cross", ("gmu_0", "xattn_1")[j % 2], "layer"),
                        (j // 2, 0)))
    return [(path, index, kind, window)
            for (path, index), (kind, window) in zip(out, kinds)]


def state_rule(config, rehearse):
    """What ``condition`` does to ``Trainer.create_state``'s parameters,
    read from the configuration file's ``run.state`` (nothing where the file
    names none): ``{path of a leaf: ("times", factor) | ("add", constant) |
    ("fill", spread)}``.  The embedding table times ``embed_scale``; every
    Mamba layer's ``x_proj`` times ``x_proj_scale`` and ``dt_proj``'s bias
    plus ``dt_bias_add``; every q, k, v and output projection's bias filled
    with ``attn_bias_spread`` times the sum of its kernel over the inputs (a
    unit normal a bias, from the parameters alone); a gated memory unit's and
    a cross layer's output projections times ``gmu_out_scale`` and
    ``cross_out_scale`` (a key that is absent is 1, 0 or 0: the file names
    no ``embed_scale``; the head is tied, so a factor on the table is a
    factor on the logits)."""
    if "state" not in config["run"]:
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    rule = {("embed_tokens",): ("times", float(state.get("embed_scale", 1)))}
    for path, _, kind, _ in layer_paths(m):
        attn = path + ("attn",)
        if kind == "mamba":
            rule[attn + ("x_proj", "kernel")] = (
                "times", float(state.get("x_proj_scale", 1)))
            rule[attn + ("dt_proj", "bias")] = (
                "add", float(state.get("dt_bias_add", 0)))
        elif kind == "gmu":
            rule[attn + ("out_proj", "kernel")] = (
                "times", float(state.get("gmu_out_scale", 1)))
        elif kind in ("attn", "cross"):
            for name in ("q_proj", "k_proj", "v_proj", "o_proj")[
                    :: 3 if kind == "cross" else 1]:
                rule[attn + (name, "bias")] = (
                    "fill", float(state.get("attn_bias_spread", 0)))
            if kind == "cross":
                rule[attn + ("o_proj", "kernel")] = (
                    "times", float(state.get("cross_out_scale", 1)))
    return {path: (how, value) for path, (how, value) in rule.items()
            if value != {"times": 1.0, "add": 0.0, "fill": 0.0}[how]}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s under ``state_rule`` (same tree, shardings
    and dtypes; one small program a leaf on the device, no forward pass, no
    look at a batch).  Why each number: under ``TOKEN_ATOL``."""
    import flax.linen as nn

    rule = state_rule(config, rehearse)
    plain = nn.meta.unbox(state.params)

    def at(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    def changed(path, leaf):
        keys = tuple(k.key for k in path if hasattr(k, "key"))
        keys = keys[:-1] if keys[-1] == "value" else keys
        how, value = rule.get(keys, (None, None))
        if how is None:
            return leaf
        if how == "fill":
            kernel = at(plain, keys[:-1] + ("kernel",))
            # after the axes of a stack ([periods, run]) the kernel's
            # inputs, then the bias's own axes
            lead = 2 if keys[0] in ("layers", "cross") else 0
            inputs = tuple(range(lead, kernel.ndim - (leaf.ndim - lead)))
            return jax.jit(
                lambda k: (value * jnp.sum(k.astype(jnp.float32), inputs)
                           ).astype(leaf.dtype),
                out_shardings=leaf.sharding)(kernel)
        op = (lambda t: t * value) if how == "times" else (lambda t: t + value)
        return jax.jit(lambda t: op(t).astype(t.dtype), donate_argnums=0,
                       out_shardings=leaf.sharding)(leaf)

    return state.replace(
        params=jax.tree_util.tree_map_with_path(changed, state.params))


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_counts(m):
    """``{kind: layers}`` over ``mamba``, ``window`` and ``full`` (an
    attention layer with and without the window), ``gmu``, ``cross``."""
    out = {"mamba": 0, "window": 0, "full": 0, "gmu": 0, "cross": 0}
    for kind, window in kinds_of(m):
        out[{"attn": "window" if window else "full"}.get(kind, kind)] += 1
    return out


def layer_params(m):
    """``{kind: parameters of its mixer that a token multiplies with}``."""
    h, inner = m["hidden_size"], m["d_inner"]
    D, N, rank = m["head_dim"], m["mamba_d_state"], m["mamba_dt_rank"]
    q = m["num_attention_heads"] * D * h
    kv = 2 * m["num_key_value_heads"] * D * h
    own = q + kv + q                                 # q, k and v, o
    return {"mamba": h * 2 * inner + inner * (rank + 2 * N) + rank * inner
            + inner * h,
            "window": own, "full": own, "gmu": 2 * h * inner,
            "cross": 2 * q}


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with: every layer's SwiGLU and its
    mixer's projections (``layer_params``), and the tied table once, as the
    output head.  Not the embedding lookup, the norms, the biases, the
    convolution, ``A`` or ``D``."""
    m = sizes(config, rehearse)
    counts, params = layer_counts(m), layer_params(m)
    return (sum(counts[kind] * params[kind] for kind in counts)
            + m["num_hidden_layers"] * 3 * m["hidden_size"]
            * m["intermediate_size"]
            + m["hidden_size"] * m["vocab_size"])


def scan_shape(config, batch, seq, rehearse=False):
    """The shapes the selective scan works on in one step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "channels": m["d_inner"],
            "state": m["mamba_d_state"], "layers": layer_counts(m)["mamba"]}


#: operations of one update of one state entry forward, as the recurrence
#: is written: ``delta A`` and its exponential, the product with the state,
#: ``(delta a) B``, the sum, and ``s C`` with its sum into ``y``
SCAN_FORWARD_OPS = 7


def scan_step_flops(shape):
    """Operations the model asks of one step's scans: ``SCAN_FORWARD_OPS``
    an update forward, twice that backward (``benchmarks/flops.py``'s
    rule); no state computed again."""
    updates = (shape["batch"] * shape["seq"] * shape["channels"]
               * shape["state"] * shape["layers"])
    return 3 * SCAN_FORWARD_OPS * updates


def scan_step_bytes(shape, itemsize=2):
    """Least bytes the scans move to and from HBM: ``a``, ``delta``, ``z``
    (a channel a position) and ``B``, ``C`` (a column a position) read and
    ``Y`` written once forward; backward the five and ``Y``'s gradient read,
    the five's gradients written.  Never the state's history."""
    wide = shape["batch"] * shape["seq"] * shape["channels"]
    narrow = shape["batch"] * shape["seq"] * shape["state"]
    forward = 4 * wide + 2 * narrow
    backward = (4 * wide + 2 * narrow) + (3 * wide + 2 * narrow)
    return shape["layers"] * itemsize * (forward + backward)


def diff_shape(config, batch, seq, rehearse=False):
    """The shapes the differential core works on in one step: the window
    layers, and the layers that see every earlier key (whole and cross)."""
    m = sizes(config, rehearse)
    counts = layer_counts(m)
    return {"batch": batch, "seq": seq,
            "pairs": m["num_attention_heads"] // 2,
            "kv_pairs": m["num_key_value_heads"] // 2,
            "head_dim": m["head_dim"], "window": int(m["sliding_window"]),
            "window_layers": counts["window"],
            "causal_layers": counts["full"] + counts["cross"],
            "cross_layers": counts["cross"]}


def allowed_pairs(seq, window=None):
    """Query-key pairs a head attends: every causal pair, or the band's
    (``S W - W (W - 1) / 2``: the first ``W`` queries see fewer)."""
    w = seq if window is None else min(window, seq)
    return seq * w - w * (w - 1) // 2


def diff_step_flops(shape):
    """Operations the model asks of one step's differential cores: an
    allowed pair of positions and pair of heads costs two maps' scores (a
    multiply-add over ``head_dim`` each) and two maps' products with one
    value of ``2 head_dim`` forward, twice that backward; the allowed pairs
    only, nothing padded, no score computed again."""
    D = shape["head_dim"]
    forward = 2 * (2 * D + 2 * 2 * D)
    pairs = (shape["window_layers"] * allowed_pairs(
        shape["seq"], shape["window"])
        + shape["causal_layers"] * allowed_pairs(shape["seq"]))
    return 3 * forward * pairs * shape["pairs"] * shape["batch"]


def diff_step_bytes(shape, itemsize=2):
    """Least bytes the cores move to and from HBM: q, k, v in and o out
    once forward; backward q, k, v, o and o's gradient in, the gradients of
    q, k and v out; k and v at the key heads."""
    rows = shape["batch"] * shape["seq"] * 2 * shape["head_dim"]
    q, kv = rows * shape["pairs"], rows * shape["kv_pairs"]
    forward = 2 * q + 2 * kv
    backward = (3 * q + 2 * kv) + (q + 2 * kv)
    return (shape["window_layers"] + shape["causal_layers"]) * itemsize * (
        forward + backward)


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params``, the
    differential cores and the scans as the model asks for them."""
    return (6 * matmul_params(config, rehearse)
            + diff_step_flops(diff_shape(config, 1, seq, rehearse)) / seq
            + scan_step_flops(scan_shape(config, 1, seq, rehearse)) / seq)


def fa2_shape(config, batch_per_chip, seq):
    """No shape for ``fa2_ms_per_step``'s reader: this family's calls are
    read by scope (``layer_metrics/diff_attn_*``)."""
    return None


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy at "highest", no kernel, no chunk of
# the recurrence, no sharding, no remat; the scan a position at a time, the
# two softmax maps a block of queries at a time against every key
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation and keeps the scan's state, ``delta``, ``A`` and the scan's
#: arithmetic in float32, as the configuration states; the reference is
#: float32 throughout.  No choice in this model is discontinuous (no router,
#: no selection), so the worst token is rounding too.  Each limit stands
#: between readings on the chip at the published widths and the cell's own
#: size (one sequence of 16,384, eight layers), on the state ``condition``
#: gives (``tests/precision_phi4flash.py``, each set of losses through
#: ``jobs_shared.compare_losses``; my chip runs, PR 57: the system on
#: sixteen seeds, ..102-..110 through the tool and ..201-..207 in the cell's
#: own runs, the controls
#: and every fault on ..102 under the final rule and on ..102 and ..103
#: under the rule before it, without the two output factors, in brackets
#: where a reading differs):
#:
#:                  system            float8 control    the mildest faults it catches
#:   worst token    0.137-0.233       2.05-2.17         0.72 [0.30-0.31] (the cross layer not causal), 1.00-1.22 (lambda dropped)
#:   median token   0.0156-0.0187     0.196-0.224       0.114-0.142 (lambda dropped), 0.152-0.170 (lambda_init of the wrong layer)
#:   mean           2.0e-5-2.8e-4     7.3e-4-1.1e-3     1.3e-5-1.2e-2 (all twelve it catches)
#:
#: (the other nine it catches read a median of 0.146-0.975 on every seed:
#: the GMU fed the gated ``Y`` 0.257 [0.146-0.147], the heads paired ``(j, j
#: + 20)`` 0.325-0.377, ``1 - lambda_init`` dropped 0.414-0.482, ``D``
#: dropped 0.520-0.587, the sub-norm dropped 0.603-0.648, ``delta`` without
#: its bias 0.749-0.794, the convolution a tap late 0.781-0.805, an untied
#: head 0.962-0.975; ``delta`` without its softplus is no number at all.)
#: **The median holds the cell**: steady to 20% over sixteen seeds, the
#: control's smallest 0.196, 10 times, and the mildest fault's smallest
#: 0.114, 6.1 times the system's largest, so ``MEDIAN_ATOL`` 0.05 stands 2.7
#: times over the one and 2.3 times under the other.  ``TOKEN_ATOL`` 0.45 is
#: 1.9 times the largest of sixteen seeds and 1.6 times under the one fault
#: the median cannot see, a cross layer that reads keys after its query
#: (median 0.0084: late queries hardly change; worst token 0.72, the early
#: ones).  ``MEAN_ATOL`` 1e-3 is a guard, 3.6 times over the system's
#: largest; the control swings across it (7.3e-4, 9.6e-4, 1.09e-3), the
#: sub-norm and ``D`` dropped read ten times over it.
#: **Three planted readings are not caught at the timed sizes**, and are
#: written down with their size.  A window of 511 or of 513 positions
#: reads 0.167-0.320 / 0.0092-0.0109 / 1.3e-4-2.6e-4: one key of 512 under
#: a softmax moves a token by less than the system's own rounding does
#: (its median 0.0156-0.0187).  The scan's state through bfloat16 after
#: every position reads 0.045-0.052 / 0.0036-0.0041 / 5e-6-3e-5, a quarter
#: of the system's distance from float32: the system is bfloat16 in every
#: matmul around the scan.  Both are held elsewhere: the band to the
#: position by ``tests/test_window_attention_kernels.py`` and, in float32 at
#: sixty-four positions and a tenth of these limits, by
#: ``tests/test_correct_phi4flash.py`` (0.17 / 0.016 / 7.6e-3 there); the
#: state's float32 by
#: ``tests/test_phi4flash.py`` (the kernels against the recurrence within
#: 2e-4 of values up to 100) and on the chip by ``scripts/scan_alone.py``
#: (every gradient within 4.4e-7 of the ``jax.numpy`` body's largest entry):
#: a change to the band or to the scan's arithmetic has to bring its own
#: reading of those; ``correct`` cannot see it (PERF.md section 7).
#:
#: **Why the state's numbers** (``condition``; PERF.md section 6).
#: ``x_proj_scale`` 4: at the initialiser's scale the recurrence's part of
#: ``Y`` is a tenth of the skip ``D a`` and a fault in the scan shows in no
#: loss; at 4 it reads 2.4-2.9 times the skip in every Mamba layer, and
#: ``delta``'s input varies by token.  ``dt_bias_add`` 2: the median decay
#: ``exp(delta A)`` 0.60-0.63 (``ssm_decay_p50``) where the initialiser's
#: step sizes give 0.92: a position hears the ones before it and forgets
#: them within a window.  ``attn_bias_spread`` 0.3: the initialiser's biases
#: are 0, and a bias that is 0 hides a projection that drops it.
#: ``gmu_out_scale`` 2 and ``cross_out_scale`` 3: the two branches that read
#: the memory are 0.30 and 0.18 of the residual they are added to at the
#: initialiser's scale (the stream has summed six layers by then) and 0.59
#: and 0.35 at these, beside 0.23-0.85 for the six before; a cross layer
#: that is not causal went from 0.30 to 0.72 at the worst token, out of the
#: system's reach.  No ``embed_scale``: the head is tied, so a factor on
#: the table is a factor on the logits (at 50 the reference's loss is 26.8).
#: ``lambda`` needs no help: 0.28-0.93 by layer and seed.
TOKEN_ATOL = 0.45
MEDIAN_ATOL = 5e-2
MEAN_ATOL = 1e-3

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above, or PERF.md names the one that does not
FAULTS = ("window_511", "window_513", "no_lambda", "no_sub_norm",
          "no_one_minus_lambda_init", "lambda_init_wrong_layer",
          "heads_paired_far", "gmu_gated_y", "cross_not_causal",
          "delta_no_softplus", "delta_no_bias", "no_skip", "conv_shifted",
          "untied_head")
#: the same forward pass at a precision below the configuration's: the
#: scan's state through bfloat16 after every position
LOWER_PRECISION = ("bfloat16_state",)


def _layer_norm(x, p, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def _mamba(h, p, m, fault):
    """``(the mixer's output, Y, the gated Y, the skip's share of Y)``."""
    N, rank, taps = m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_d_conv"]
    both = h @ p["in_proj"]["kernel"]
    inner = both.shape[-1] // 2
    a, z = both[..., :inner], both[..., inner:]
    S = a.shape[1]
    # tap ``i`` weighs position ``t - (taps - 1) + i`` (``conv_shifted``:
    # one position earlier)
    shift = 1 if fault == "conv_shifted" else 0
    lead = jnp.pad(a, ((0, 0), (taps - 1 + shift, 0), (0, 0)))
    a = jax.nn.silu(p["conv_bias"] + sum(
        lead[:, i: i + S] * p["conv_weight"][i] for i in range(taps)))
    steer = a @ p["x_proj"]["kernel"]
    step_in = steer[..., :rank] @ p["dt_proj"]["kernel"]
    if fault != "delta_no_bias":
        step_in = step_in + p["dt_proj"]["bias"]
    delta = step_in if fault == "delta_no_softplus" else jax.nn.softplus(
        step_in)
    A = -jnp.exp(p["A_log"])

    def step(state, at):
        a_t, delta_t, b_t, c_t = at
        state = jnp.exp(delta_t[..., None] * A) * state + (
            delta_t * a_t)[..., None] * b_t[:, None, :]
        if fault == "bfloat16_state":
            state = _round_through(state, jnp.bfloat16)
        return state, jnp.einsum("bcn,bn->bc", state, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((a.shape[0], inner, N), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (
            a, delta, steer[..., rank: rank + N], steer[..., rank + N:])))
    scanned = jnp.moveaxis(y, 0, 1)
    skip = 0.0 if fault == "no_skip" else p["D"] * a
    y = scanned + skip
    gated = y * jax.nn.silu(z)
    report = {"scan_over_skip_rms": jnp.sqrt(
        jnp.mean(jnp.square(scanned)) / jnp.mean(jnp.square(p["D"] * a))),
        "decay_p50": jnp.median(jnp.exp(
            delta[:, :: max(S // 16, 1)][..., None] * A))}
    return gated @ p["out_proj"]["kernel"], y, gated, report


def _differential(h, p, m, i, window, handed, fault):
    """``(the layer's output, (K, V) as projected, lambda)``."""
    def project(name):
        return jnp.einsum("bse,ehd->bshd", h, p[name]["kernel"]) + (
            p[name]["bias"])

    q = project("q_proj")
    k, v = handed if handed is not None else (
        project("k_proj"), project("v_proj"))
    B, S, heads, D = q.shape
    groups = heads // k.shape[2]
    if fault == "heads_paired_far":      # (j, j + H/2) in place of (2j, 2j+1)
        q1, q2 = q[:, :, : heads // 2], q[:, :, heads // 2:]
    else:
        q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1, k2 = (jnp.repeat(k[:, :, half::2], groups, axis=2) for half in (0, 1))
    wide = jnp.repeat(
        v.reshape(B, S, k.shape[2] // 2, 2 * D), groups, axis=2)
    at = i + 1 if fault == "lambda_init_wrong_layer" else i
    first = 0.8 - 0.6 * math.exp(-0.3 * at)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + first)
    if fault == "no_lambda":
        lam = 0.0
    if window is not None:
        window += {"window_511": -1, "window_513": 1}.get(fault, 0)
    block = min(int(m["query_block"]), S)

    def one_block(start):
        rows = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, start, block, 1)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(S)[None, :]
        seen = ahead >= 0
        if handed is not None and fault == "cross_not_causal":
            seen = jnp.ones_like(seen)
        if window is not None:
            seen = seen & (ahead < window)

        def attend(q_half, k_half):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", rows(q_half), k_half) * D ** -0.5
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

        return jnp.einsum("bhqk,bkhd->bqhd",
                          attend(q1, k1) - lam * attend(q2, k2), wide)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads // 2, 2 * D)
    if fault != "no_sub_norm":
        var = jnp.mean(jnp.square(out), axis=-1, keepdims=True)
        out = out * jax.lax.rsqrt(var + 1e-5) * p["sub_norm"]["scale"]
    if fault != "no_one_minus_lambda_init":
        out = out * (1.0 - first)
    return (jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])
            + p["o_proj"]["bias"]), (k, v), lam


def reference(params, input_ids, labels, m, round_through=None, fault=None):
    """``(loss of every token [B, S], a report)`` from the program's
    parameter tree (unboxed).  ``fault``: one of ``FAULTS`` or of
    ``LOWER_PRECISION``; ``round_through``: every parameter through that
    dtype first (the float8 control).  The report: a layer each, the
    mixer's branch over the residual it is added to (RMS), and of a Mamba
    layer the scan's part of ``Y`` over the skip's and the median decay, of
    a differential layer ``lambda``."""
    eps = float(m["layer_norm_eps"])
    half = int(m["num_hidden_layers"]) // 2

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if round_through is None else _round_through(
            t, round_through)

    def at(path, index):
        tree = params
        for key in path:
            tree = tree[key]
        return jax.tree.map(lambda t: f32(t)[index], tree)

    report = []
    with jax.default_matmul_precision("highest"):
        table = f32(params["embed_tokens"])
        x = table[input_ids]
        y = handed = None
        for i, (path, index, kind, window) in enumerate(layer_paths(m)):
            p = at(path, index)
            h = _layer_norm(x, p["input_norm"], eps)
            seen = {}
            if kind == "mamba":
                out, scanned, gated, seen = _mamba(h, p["attn"], m, fault)
                if i == half:
                    y = gated if fault == "gmu_gated_y" else scanned
            elif kind == "gmu":
                out = (y * jax.nn.silu(h @ p["attn"]["in_proj"]["kernel"])
                       ) @ p["attn"]["out_proj"]["kernel"]
            else:
                out, projected, lam = _differential(
                    h, p["attn"], m, i, window,
                    handed if kind == "cross" else None, fault)
                seen = {"lambda": lam}
                handed = projected if i == half + 1 else handed
            seen["branch_over_residual_rms"] = jnp.sqrt(
                jnp.mean(jnp.square(out)) / jnp.mean(jnp.square(x)))
            report.append(seen)
            x = x + out
            x = x + _swiglu(_layer_norm(x, p["post_attn_norm"], eps),
                            p["mlp"])
        x = _layer_norm(x, jax.tree.map(f32, params["final_norm"]), eps)
        head = table[::-1] if fault == "untied_head" else table
        logp = jax.nn.log_softmax(x @ head.T, -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return losses, report


def _report(report):
    print(json.dumps({
        "phase": "reference_phi4flash",
        "layers": [{key: float(value) for key, value in layer.items()}
                   for layer in report]}), file=sys.stderr, flush=True)


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """What ``jobs_shared.reference_check`` calls: the reference's loss of
    every token.  What the state makes of the new parts, layer by layer,
    goes to standard error."""
    losses, report = reference(
        params, input_ids, labels, sizes(config, rehearse), **planted)
    jax.debug.callback(_report, report)
    return losses
