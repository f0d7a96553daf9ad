"""NVIDIA-Nemotron-3-Super-120B-A12B (``nvidia/NVIDIA-Nemotron-3-Super-
120B-A12B-BF16``, ``model_type`` ``nemotron_h``) through the program's one
decoder (``models/llama.py``): a stack whose layers are EACH a mixer or a
feed-forward alone (one norm, one branch): Mamba-2 (a scalar decay a head
and position, the scan in chunks of matrix products, ``ops/ssd.py``), a
softmax layer without positions (the FA2 kernels on the chip), and
``models/moe.py``'s routed block with experts of two matrices under
``relu(.)^2`` in a latent of 1024 beside a full-width shared expert:
sigmoid scores, the 22 largest of 512 under a selection bias the load moves,
renormalised weights times 5; told which experts of the layer this chip
holds.  Built from a configuration file, with its counts of operations and
bytes and its plain reference (the benchmark's copy of
``dlrover_tpu/models/nemotronh_reference.py``, which states the layers
equation by equation).

In the file ``n_routed_experts``, ``mamba_num_heads``, ``n_groups``,
``num_attention_heads``, ``num_key_value_heads`` and ``vocab_size`` are this
chip's SHARE (``reduced``) and ``published.*`` the model's; the router keeps
``published.n_routed_experts`` columns and ``run.first_expert`` says which
experts are here.  ``hybrid_override_pattern`` is the cut's stretch of the
published string: ``E`` a routed feed-forward alone, ``M`` a Mamba-2 mixer
alone, ``*`` an attention layer alone.

**The stack** (``stack_layout``): the program scans periods of a pattern
and runs a suffix once; ``EMEMEMEMEM*`` is five periods of ``(E, M)`` and
``*`` once, three layer bodies traced where eleven runs of one would trace
eleven.

**What is Ling-3.0's is imported, not copied** (``families/ling3.py``): the
drawn selection bias, the runs of a stack, and the shell that hands
``model.apply`` the buffers of the state ``condition`` made where the
harness names none (``jobs_shared.reference_check``).  The
multi-token-prediction module (``num_nextn_predict_layers`` 1) is not built
and nothing stands in for it."""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import load_module

#: loaded ONCE: ``load_module`` makes a new module a call, and the shell and
#: the reference must read the ``_STATE`` that ``condition`` wrote
_ling = load_module("families", "ling3")
runs = _ling.runs
#: rounding in float32 arithmetic: the chip's compiler removes a conversion
#: there and back (``families/olmoe.py::_round_through``)
_round_through = load_module("families", "olmoe")._round_through

TINY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
        "hybrid_override_pattern": "EMEM*", "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 1, "head_dim": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "moe_intermediate_size": 24,
        "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 48,
        "n_shared_experts": 1, "routed_scaling_factor": 5,
        "layer_norm_epsilon": 1e-5, "max_position_embeddings": 128,
        "published": {"n_routed_experts": 8}}

#: published keys the program has one path for: only these values run
ONLY = {"attention_bias": False, "mamba_hidden_act": "silu",
        "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_bias": False,
        "use_conv_bias": True, "n_shared_experts": 1,
        "moe_shared_expert_overlap": False, "residual_in_fp32": False}

#: a letter of ``hybrid_override_pattern`` -> the program's entry
ENTRY_OF = {"E": "ffn", "M": "mamba2:alone", "*": "gqa:alone"}


def stack_layout(letters):
    """``(pattern, periods, suffix)`` of a stretch of the published string:
    the split ``pattern * periods + suffix`` that traces the fewest layer
    bodies (``len(pattern) + len(suffix)``; the shorter pattern on a tie)."""
    entries = tuple(ENTRY_OF[letter] for letter in letters)
    best = None
    for size in range(1, len(entries) + 1):
        unit, periods = entries[:size], 1
        while entries[periods * size: (periods + 1) * size] == unit:
            periods += 1
        suffix = entries[periods * size:]
        if best is None or size + len(suffix) < len(best[0]) + len(best[2]):
            best = (unit, periods, suffix)
    return best


def sizes(config, rehearse):
    src = TINY if rehearse else config
    first = 0 if rehearse else int(config["run"].get("first_expert", 0))
    pattern, periods, suffix = stack_layout(src["hybrid_override_pattern"])
    if periods * len(pattern) + len(suffix) != int(src["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern does not spell "
                         "num_hidden_layers layers")
    return {**src,
            "experts_total": int(src["published"]["n_routed_experts"]),
            "first_expert": first,
            "bias_update_rate": float(
                config.get("assumed", {}).get("bias_update_rate", 0.001)),
            "layer_pattern": pattern, "periods": periods,
            "layer_suffix": suffix,
            # queries a block of the reference's attention, against every
            # key at every held head
            "query_block": 512}


def build(config, rehearse, seq):
    from dlrover_tpu.models.llama import LlamaForCausalLM
    from dlrover_tpu.models.moe import MoELlamaConfig

    fields = {f.name for f in dataclasses.fields(MoELlamaConfig)}
    if not {"mamba2_heads", "layer_suffix", "moe_latent_size",
            "mlp_matrices"} <= fields:
        raise RuntimeError(
            "this checkout's models have no Mamba-2 mixer, no layer of one "
            "branch, no experts of two matrices and no latent around them: "
            "it cannot run Nemotron-3-Super")
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
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_seq_len=seq, rms_norm_eps=float(m["layer_norm_epsilon"]),
        # no positional signal in the attention layers (``assumed``)
        use_rope=False,
        layer_pattern=m["layer_pattern"], layer_suffix=m["layer_suffix"],
        mamba2_heads=m["mamba_num_heads"], mamba2_head_dim=m["mamba_head_dim"],
        mamba2_groups=m["n_groups"], mamba2_state=m["ssm_state_size"],
        mamba2_chunk=m["chunk_size"], mamba_conv=m["conv_kernel"],
        num_experts=m["experts_total"], top_k=m["num_experts_per_tok"],
        norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        moe_latent_size=m["moe_latent_size"], mlp_matrices=2,
        mlp_activation="relu2", shared_experts=m["n_shared_experts"],
        shared_intermediate_size=m["moe_shared_expert_intermediate_size"],
        experts_held=m["n_routed_experts"], first_expert=m["first_expert"],
        # the published ``n_group`` 1 / ``topk_group`` 1 keeps its one group
        # always: no groups
        n_group=0, topk_group=0,
        selection_bias=True, bias_update_rate=m["bias_update_rate"],
        # the correction bias balances without a loss: no term in the
        # objective
        load_balance_coef=0.0, router_z_coef=0.0,
        # the kernel, or (rehearsal, on the CPU) the jnp path: never a
        # silent change of path, "flash" raises off the chip.  A rehearsal
        # compares a few hundred tokens, whose bfloat16 mean is noise: it
        # walks the harness in float32
        **({"dtype": jnp.float32} if rehearse
           else {"attention_impl": config["run"]["attention_impl"]}),
    )
    return _ling._WithStateBuffers(LlamaForCausalLM(cfg))


def stacks(m):
    """``[(path of the stack in the tree, name, entry)]`` of every run, the
    periods' first, then the suffix's."""
    return ([(("layers", name, "layer"), name, entry)
             for name, entry, _ in runs(m["layer_pattern"])]
            + [(("suffix", name, "layer"), name, entry)
               for name, entry, _ in runs(m["layer_suffix"])])


def state_rule(config, rehearse):
    """{path of a leaf of ``state.params``: what ``condition`` does to it},
    read from the configuration file (none where the file names no
    ``run.state``).  A number is a factor; ``("add", v)`` adds ``v``.  The
    embedding table times ``embed_scale``; each held expert's two matrices
    times the square root of the number held, the second also times
    ``expert_out_scale``; every branch's OUTPUT projection (``W_out``,
    ``W_o``, ``W_up``, the shared expert's second matrix) times its own
    ``mamba_out_scale``, ``attn_out_scale``, ``latent_out_scale`` or
    ``shared_out_scale`` (each NET of ``rescale_prenorm_residual``'s ``(2 x
    88)^-1/2``, which is an initialiser: the file's ``assumed``); the
    attention layer's query projection times ``q_scale``; every Mamba-2
    layer's taps times ``conv_scale`` and its ``dt_bias`` plus
    ``dt_bias_add`` (a key that is absent is 1, or adds 0)."""
    if "state" not in config["run"]:     # ``create_state``'s own state
        return {}
    m = sizes(config, rehearse)
    state = config["run"]["state"]
    scale = lambda key: float(state.get(key, 1.0))  # noqa: E731
    held = float(m["n_routed_experts"]) ** 0.5
    rule = {("embed_tokens",): scale("embed_scale")}
    for layer, _, entry in stacks(m):
        if entry == "ffn":
            mlp = layer + ("mlp",)
            rule.update({
                mlp + ("up_proj",): held,
                mlp + ("down_proj",): held * scale("expert_out_scale"),
                mlp + ("latent_up", "kernel"): scale("latent_out_scale"),
                mlp + ("shared_expert", "down_proj", "kernel"):
                    scale("shared_out_scale")})
        elif entry == "mamba2:alone":
            rule[layer + ("attn", "out_proj", "kernel")] = scale(
                "mamba_out_scale")
            rule[layer + ("attn", "conv_weight")] = scale("conv_scale")
            if float(state.get("dt_bias_add", 0.0)):
                rule[layer + ("attn", "dt_bias")] = (
                    "add", float(state["dt_bias_add"]))
        else:
            rule[layer + ("attn", "o_proj", "kernel")] = scale(
                "attn_out_scale")
            rule[layer + ("attn", "q_proj", "kernel")] = scale("q_scale")
    return {path: how for path, how in rule.items() if how != 1.0}


def condition(state, config, rehearse):
    """The state a cell of this family starts from (``program.make_state``):
    ``Trainer.create_state``'s, with the leaves of ``state_rule`` multiplied
    by its factors or moved by its offsets (same tree, shardings and dtypes,
    one small program a leaf on the device, no forward pass, no look at a
    batch), and with the selection bias of every routed layer drawn with
    spread ``run.state.bias_spread`` (``families/ling3.py::drawn_bias``; the
    initialiser's 0 where the file names none): at 0 a fault in what the
    bias does is invisible.  Why each number: under ``TOKEN_ATOL``."""
    import flax.linen as nn

    rule = state_rule(config, rehearse)

    def conditioned(path, leaf):
        how = rule.get(tuple(k.key for k in path[:-1]))   # [-1]: ``value``
        if how is None:
            return leaf
        moved = (lambda t: t + how[1]) if isinstance(how, tuple) else (
            lambda t: t * how)
        return jax.jit(lambda t: moved(t).astype(t.dtype),
                       donate_argnums=0, out_shardings=leaf.sharding)(leaf)

    spread = float(config["run"].get("state", {}).get("bias_spread", 0.0))
    buffers = state.buffers
    if spread:
        buffers = jax.jit(lambda params: _ling.drawn_bias(
            params, buffers, spread))(nn.meta.unbox(state.params))
    state = state.replace(
        params=jax.tree_util.tree_map_with_path(conditioned, state.params),
        buffers=buffers)
    _ling._STATE["buffers"] = state.buffers
    return state


# --------------------------------------------------------------------------
# the work the model asks for, from the shapes alone
# --------------------------------------------------------------------------

def layer_counts(m):
    """{entry: layers of it in the stack}."""
    entries = m["layer_pattern"] * m["periods"] + m["layer_suffix"]
    return {entry: entries.count(entry) for entry in ENTRY_OF.values()}


def layer_params(m):
    """{entry: parameters a token multiplies with in one such layer on this
    chip}: a Mamba-2 layer's input and output projections; the attention
    layer's four; in a routed layer the router's columns (all of them), the
    latent's two projections, the shared expert's two matrices and of the
    routed experts, TWO matrices each, what a token's
    ``num_experts_per_tok`` assignments meet here under even routing (``k *
    held / all`` experts: 22 x 16 / 512 = 0.6875 of one)."""
    h = m["hidden_size"]
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    wide = inner + 2 * m["n_groups"] * m["ssm_state_size"]
    heads, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["head_dim"])
    latent = m["moe_latent_size"]
    met = (m["num_experts_per_tok"] * m["n_routed_experts"]
           / m["experts_total"])
    return {
        "mamba2:alone": h * (inner + wide + m["mamba_num_heads"]) + inner * h,
        "gqa:alone": h * d * (2 * heads + 2 * kv),
        "ffn": (h * m["experts_total"] + 2 * h * latent
                + 2 * h * m["moe_shared_expert_intermediate_size"]
                + met * 2 * latent * m["moe_intermediate_size"])}


def matmul_params(config, rehearse=False):
    """Parameters a token multiplies with on this chip: every layer's
    (``layer_params``) and the output head.  Not the embedding table, the
    norms, the taps or the scan's numbers a head."""
    m = sizes(config, rehearse)
    a_layer = layer_params(m)
    return sum(n * a_layer[entry] for entry, n in layer_counts(m).items()) + (
        m["hidden_size"] * m["vocab_size"])


def ssd_shape(config, batch, seq, rehearse=False):
    """The shapes the Mamba-2 scan works on in one step."""
    m = sizes(config, rehearse)
    return {"batch": batch, "seq": seq, "heads": m["mamba_num_heads"],
            "head_dim": m["mamba_head_dim"], "groups": m["n_groups"],
            "state": m["ssm_state_size"],
            "layers": layer_counts(m)["mamba2:alone"]}


#: operations of one update of one state entry forward, as the recurrence is
#: written: the decay's product with the state, ``(d x) B``, the sum, and
#: ``S C`` with its sum into ``y``
SSD_FORWARD_OPS = 5


def ssd_step_flops(shape):
    """Operations the model asks of one step's scans: ``SSD_FORWARD_OPS`` an
    update of a state entry forward (a head's ``[P, n]`` state, every
    position), twice that backward (``benchmarks/flops.py``'s rule).  By
    the model's own count, the recurrence: not the chunked form's scores,
    masks and second product, and no state computed again."""
    updates = (shape["batch"] * shape["seq"] * shape["heads"]
               * shape["head_dim"] * shape["state"] * shape["layers"])
    return 3 * SSD_FORWARD_OPS * updates


def ssd_step_bytes(shape, itemsize=2):
    """Least bytes the scans move to and from HBM: ``x`` (a channel a
    position), ``B`` and ``C`` (a column a group and position) and the step
    size (a float32 a head and position) read and ``y`` written once
    forward; backward the four and ``y``'s gradient read, the four's
    gradients written.  Never the state's history."""
    rows = shape["batch"] * shape["seq"]
    wide = rows * shape["heads"] * shape["head_dim"] * itemsize
    narrow = rows * shape["groups"] * shape["state"] * itemsize
    step = rows * shape["heads"] * 4
    forward = 2 * wide + 2 * narrow + step
    backward = forward + (wide + 2 * narrow + step)
    return shape["layers"] * (forward + backward)


def flops_per_token(config, seq, rehearse=False):
    """Forward and backward per token: ``6 * matmul_params`` (an expert of
    two matrices counted as two), the attention layer's causal softmax
    (``benchmarks/flops.py``) and the scans as the model asks for them."""
    from benchmarks.flops import train_flops_per_token

    m = sizes(config, rehearse)
    softmax = train_flops_per_token(
        matmul_params(config, rehearse), layer_counts(m)["gqa:alone"],
        m["num_attention_heads"] * m["head_dim"], seq)
    return softmax + ssd_step_flops(ssd_shape(config, 1, seq, rehearse)) / seq


def fa2_shape(config, batch_per_chip, seq):
    """Shape of one call of the FA2 kernels on one chip, and how often a
    step calls each: the attention layers alone (the held query heads on
    the held key-value heads).  The layer stands in the suffix, a loop of
    one turn: the compiler unrolls it and finds the rematerialised forward
    in the forward (``families/solaropen2.py::fa2_shape``).  Over a stream
    of 16,384 keys the backward is ONE call
    (``ops/pallas/flash_attention.py::backward_path``), which
    ``fa2_ms_per_step``'s reader does not know: it then finds no dQ kernel
    and reads nothing; at a shorter stream it reads the split pair."""
    m = sizes(config, False)
    layers = layer_counts(m)["gqa:alone"]
    return {"batch": batch_per_chip, "seq": seq,
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "causal": True,
            "calls_per_step": {"fwd": layers, "dq": layers, "dkv": layers}}


# --------------------------------------------------------------------------
# plain reference: float32 jax.numpy at "highest", no kernel, no chunk of
# the scan, no sort of assignments, no sharding, no remat; the scan a
# position at a time, the attention a block of queries at a time against
# every key, every held expert looped over
# --------------------------------------------------------------------------

#: |system - reference| allowed on the loss of the worst token, of the median
#: token and on the mean.  The system multiplies in bfloat16 with float32
#: accumulation, as the configuration states (router scores, the softmax,
#: the scan's step size, decay, running sums and its state between chunks
#: in float32); the reference is float32 throughout.  Beside the rounding a
#: dense model shows, one choice is discontinuous: a margin of the choice
#: under the bfloat16 error of the hidden state flips an expert
#: (``LOW_MARGIN``), and a flip here weighs 5 / 22 of an expert's whole
#: result.  Each limit stands between readings on the chip at the published
#: widths and the cell's own size (one sequence of 8192, eleven layers), on
#: the state ``condition`` gives (``tests/precision_nemotronh.py``, each set
#: of losses through ``jobs_shared.compare_losses``; my chip runs, PR 64:
#: nine seeds ..102-..110 of the system through the tool and seven runs of
#: the cell, sixteen in all, and six more with the committed files; the
#: control and every fault on seed ..110, the control and the two mildest
#: faults also on ..111-..113, and all on seed ..101 under the rule's first
#: form, ``expert_out_scale`` 3):
#:
#:                  system              float8 control    the mildest faults it catches
#:   worst token    0.117-0.179         0.284-0.342       0.239-0.243 (the skip left out), 0.294-0.367 (5 left out), 0.377 (rotary positions)
#:   median token   0.00505-0.00529     0.0452-0.0466     0.0201-0.0240 (the skip left out), 0.0421-0.0449 (5 left out), 0.0589 (rotary positions), 0.0755 (softmax scores)
#:   mean           1.4e-5-2.5e-4       3.6e-5-1.7e-3     2.0e-5-1.5e-2
#:
#: (the other six it catches read a median of 0.102-0.599: the norm before
#: the gate 0.102, SiLU for relu squared 0.176, no decay 0.238, no
#: convolution 0.241, the shared expert left out 0.304, the weights not
#: renormalised 0.599.)  **The median holds the cell**: steady to 5% over
#: twenty-two seeds, the control's smallest of four seeds 8.5 times and the
#: mildest fault's smallest 3.8 times the system's largest, so
#: ``MEDIAN_ATOL`` 0.011 stands 2.1 times over the one and 1.8 times under
#: the other.  ``TOKEN_ATOL`` 0.32 is there for a token or a row gone wrong,
#: which no median sees: 1.8 times over the largest of twenty-two seeds
#: (their mean 0.145, their spread 0.021); the control reads on both sides
#: of it (it is not correct by the median on every seed) and so does "5 left
#: out"; the skip left out reads under it.  The mean is the average of 8192
#: token errors, which cancel: ``MEAN_ATOL`` 8e-4 is there for a bias, 3.2
#: times over the system's largest; the control swings across it.  **Three planted readings are not caught at the timed sizes, for one
#: reason: each reads UNDER the system, which is bfloat16 in all its
#: matmuls.**  The scan's state rounded to bfloat16 after every update reads
#: 0.059-0.122 / 0.00021-0.00025 on four seeds (a twentieth of the system's median: the state's error
#: is averaged away by the read-out over 128 columns and the gated norm, as
#: Phi-4-mini-flash's was); the router's matmul and scores through bfloat16
#: 0.150-0.173 / 0.00119-0.00134 (the router's input is the bfloat16 stream in the system
#: too, and a flip weighs the same whoever causes it); the bias added to the
#: weights before they are divided by their sum 0.101-0.153 / 0.0005-0.0006 at
#: a spread of 0.01 (Ling-3.0's and Kanana-2's finding again: the division takes it
#: back).  These three are held on the CPU in float32, where each reads a
#: hundred times the agreement (``tests/test_nemotronh.py``: the state in
#: bfloat16 over 5e-3 where the chunked scan agrees to 5e-5, the bias in the
#: weights, the bias left out of the choice) and where the scan's exponents
#: and state are held to float32 by the traced program's own types;
#: ``correct`` cannot see them (PERF.md section 7).
#:
#: **Why the state's numbers** (``condition``).  ``embed_scale`` 300 and
#: each held expert's two matrices times sqrt(16), as Solar-Open2's, Ling's,
#: Laguna's and Kanana's cells, so that uniform random tokens spread over the
#: 512 experts (this chip's rows 0.84-1.22 of a fair share by layer over ten
#: seeds, 0.95-1.03 in the traced window, every pass on the ladder's first
#: extent of 1.25; the hottest expert 1.5-3.5 of the mean) and the
#: initialiser's count of the expert axis into the fan-in is undone.
#: ``dt_bias_add`` 2 (the step size's median from 0.01 to 0.07, reckoned
#: from the initialiser): the scan's median decay ``exp(d A)`` reads
#: 0.27-0.79 by layer and seed, 0.48-0.72 in the window, where the
#: initialiser's, reckoned 0.92, is a state that only sums.  ``conv_scale``
#: 3: ``x``, ``B`` and ``C`` three times larger make the scan's part of
#: ``y`` weigh beside the skip ``D x`` (reckoned from the initialiser's
#: spreads, not swept: at 1 the skip is some nine tenths of ``y``; at 3 no
#: decay reads 45 times the system's median and the skip left out 4.5).  ``q_scale`` 4, as
#: Solar-Open2's: an untrained head's scores over thousands of keys are an
#: average of the values with or without positions; at 4 rotary positions
#: planted on the attention layer read 11 times the system's median.
#: ``bias_spread`` 0.01 and the routers as the initialiser leaves them, as
#: Ling's.  **One sweep, one factor** (``expert_out_scale``, seed ..101 and
#: the cell's first run against sixteen at 1): at 3, Ling's and Kanana's
#: value, the system read worst 0.57 and 0.89, median 0.0059-0.0063, and the
#: control's worst 0.82: a flip of one of 22 experts times three is as large
#: as anything float8 does, so the worst token told nothing; at 1 the
#: system's worst is 0.12-0.18 and the routing's faults still read 8 to 113
#: times its median.  The four output projections stay at 1 NET of
#: ``rescale_prenorm_residual``'s 0.0754 (the file's ``assumed``): at the
#: initialiser's own value every branch drowns in a stream of 6.
#: **``LOW_MARGIN_SHARE_MAX`` is 0.4, not the other routed families' 0.25**:
#: the 22nd and 23rd of 512 sigmoid scores of unit-normal logits lie 0.0028
#: apart in the mean (512 x 0.091 logits a unit at the 22nd's 1.72, times a
#: slope of 0.129), so a margin under ``LOW_MARGIN`` 1e-3 is a token in 0.30
#: by the order statistics alone, whatever the router's scale (0.286-0.314 by
#: layer over sixteen seeds); a flip matters here only where one of the two
#: experts is held, 16 in 512.
TOKEN_ATOL = 0.32
MEDIAN_ATOL = 1.1e-2
MEAN_ATOL = 8e-4
#: a margin of the choice (in ``scores + bias``) that bfloat16 arithmetic
#: upstream can cross
LOW_MARGIN = 1e-3
LOW_MARGIN_SHARE_MAX = 0.4

#: what ``reference(..., fault=...)`` can plant: each has to come out not
#: correct at the limits above, or PERF.md names the one that does not
FAULTS = ("no_decay", "no_conv", "norm_before_gate", "no_skip",
          "rope_on_gqa", "silu_experts", "bias_in_weights",
          "not_renormalised", "no_scaling_factor", "no_shared_expert",
          "softmax_scores")
#: the same forward pass at a precision below the configuration's: the
#: scan's state, or the router's matmul and scores, through bfloat16
LOWER_PRECISION = ("bfloat16_state", "bfloat16_router")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _relu2(t):
    return jnp.square(jax.nn.relu(t))


def _bfloat16(t):
    return _round_through(t, jnp.bfloat16)


def _mamba2(h, p, m, fault):
    """``(Mamba2(h), the median of exp(a_t))``: the recurrence a position at
    a time, the state ``[B, H, P, n]`` float32 (``bfloat16_state``: rounded
    after every update)."""
    H, P, G, n = (int(m[key]) for key in (
        "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    inner, taps = H * P, int(m["conv_kernel"])
    B, S, _ = h.shape
    both = h @ p["in_proj"]["kernel"]
    z, u, dt = (both[..., :inner], both[..., inner: 2 * inner + 2 * G * n],
                both[..., 2 * inner + 2 * G * n:])
    if fault == "no_conv":      # the last tap alone: the position itself
        u = jax.nn.silu(p["conv_bias"] + u * p["conv_weight"][taps - 1])
    else:
        lead = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        u = jax.nn.silu(p["conv_bias"] + sum(
            lead[:, i: i + S] * p["conv_weight"][i] for i in range(taps)))
    x = u[..., :inner].reshape(B, S, H, P)
    Bm, Cm = (jnp.repeat(t.reshape(B, S, G, n), H // G, axis=2) for t in (
        u[..., inner: inner + G * n], u[..., inner + G * n:]))
    d = jax.nn.softplus(dt + p["dt_bias"])
    a = d * -jnp.exp(p["A_log"])
    if fault == "no_decay":
        a = jnp.zeros_like(a)

    def step(state, at):
        x_t, d_t, a_t, b_t, c_t = at
        state = jnp.exp(a_t)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", d_t[..., None] * x_t, b_t)
        if fault == "bfloat16_state":
            state = _bfloat16(state)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, n), jnp.float32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, d, a, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1)
    if fault != "no_skip":
        y = y + p["D"][:, None] * x
    y = y.reshape(B, S, inner)

    def group_norm(t):
        t = t.reshape(B, S, G, inner // G)
        t = t * jax.lax.rsqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True)
                              + float(m["layer_norm_epsilon"]))
        return t.reshape(B, S, inner) * p["norm_scale"]

    if fault == "norm_before_gate":
        out = group_norm(y) * jax.nn.silu(z)
    else:
        out = group_norm(y * jax.nn.silu(z))
    return out @ p["out_proj"]["kernel"], jnp.median(jnp.exp(a))


def _rope(x, theta=10000.0):
    """Rotary embedding on [B, S, H, D], halves convention: only the
    planted fault ``rope_on_gqa`` calls it."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, p, m, fault):
    """The softmax layer without positions, a block of queries at a time
    against every key."""
    q = jnp.einsum("bse,ehd->bshd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bse,ehd->bshd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bse,ehd->bshd", h, p["v_proj"]["kernel"])
    if fault == "rope_on_gqa":
        q, k = _rope(q), _rope(k)
    B, S, heads, d = q.shape
    group = heads // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    block = min(int(m["query_block"]), S)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k) * d ** -0.5
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, heads, d)
    return jnp.einsum("bshd,hde->bse", out, p["o_proj"]["kernel"])


def _latent_moe(h, p, bias, m, fault):
    """(ffn(h), share of tokens with a low margin of the choice, rows each
    of the router's experts took): ``s = sigmoid(h W_r)`` on the full
    ``h``; the choice the k largest of ``s + b``; the weights ``s / (sum of
    the chosen) * factor``; every held expert computes every token's latent
    ``c = h W_down``, one after the other; the experts that are not here
    add nothing; ``r W_up`` and the shared expert once."""
    k, first = int(m["num_experts_per_tok"]), int(m["first_expert"])
    act = jax.nn.silu if fault == "silu_experts" else _relu2
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
        gates = gates / gates.sum(axis=-1, keepdims=True)
    if fault != "no_scaling_factor":
        gates = gates * float(m["routed_scaling_factor"])
    latent = h @ p["latent_down"]["kernel"]

    def one_expert(out, expert):
        up_w, down_w, gate = expert
        return out + gate[..., None] * (act(latent @ up_w) @ down_w), None

    here = p["up_proj"].shape[0]
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent), (
        p["up_proj"], p["down_proj"],
        jnp.moveaxis(gates[..., first: first + here], -1, 0)))
    out = routed @ p["latent_up"]["kernel"]
    if fault != "no_shared_expert":
        shared = p["shared_expert"]
        out = out + act(h @ shared["up_proj"]["kernel"]) @ shared[
            "down_proj"]["kernel"]
    low = jnp.mean(edge[..., k - 1] - edge[..., k] < LOW_MARGIN)
    return out, low, chosen.sum(axis=tuple(range(chosen.ndim - 1)))


def reference(params, buffers, input_ids, labels, m, round_through=None,
              fault=None):
    """(loss of every token [B, S]; a routed layer each, in the stack's
    order: the share of tokens with a low margin of the choice, and the rows
    each of the router's experts took [layers, E]; a Mamba-2 layer each: the
    median of ``exp(a_t)``) from the program's parameter tree (unboxed; a
    run of equal layers stacked under ``layers/<run>`` ``[periods, run,
    ...]`` and ``suffix/<run>`` ``[run, ...]``) and the state's buffers (the
    same paths, ``mlp/selection_bias``).  The loops over periods and over a
    run are ``jax.lax.scan``s of the plain body: one layer's temporaries at
    a time beside the training state.  ``fault``: one of ``FAULTS`` or of
    ``LOWER_PRECISION``."""
    eps = float(m["layer_norm_epsilon"])

    def f32(t):
        t = jnp.asarray(t, jnp.float32)
        return t if round_through is None else _round_through(
            t, round_through)

    def layer(entry):
        def body(x, at):
            p, b = at
            p = jax.tree.map(f32, p)
            h = _rms_norm(x, p["input_norm"]["scale"], eps)
            if entry == "ffn":
                out, low, rows = _latent_moe(
                    h, p["mlp"], b["mlp"]["selection_bias"], m, fault)
                return x + out, (low, rows)
            if entry == "mamba2:alone":
                out, median = _mamba2(h, p["attn"], m, fault)
                return x + out, median
            return x + _attention(h, p["attn"], m, fault), ()
        return body

    def stack(entries, x, p, b):
        seen = {}
        for name, entry, _ in runs(entries):
            x, found = jax.lax.scan(
                layer(entry), x,
                (p[name]["layer"], b.get(name, {}).get("layer")))
            seen.setdefault(entry, []).append(found)
        # an entry's runs side by side, in the stack's order
        return x, {entry: jax.tree.map(
            lambda *t: jnp.concatenate(t, axis=0), *found)
            for entry, found in seen.items()}

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"])[input_ids]
        x, seen = jax.lax.scan(
            lambda x, at: stack(m["layer_pattern"], x, *at), x,
            (params["layers"], buffers["layers"]))
        if m["layer_suffix"]:
            x, _ = stack(m["layer_suffix"], x, params["suffix"],
                         buffers.get("suffix", {}))
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        logp = jax.nn.log_softmax(x @ f32(params["lm_head"]["kernel"]), -1)
    losses = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # [periods, run] a run -> the stack's order (the suffix holds neither)
    low, rows = seen["ffn"]
    return (losses, low.ravel(), rows.reshape(-1, rows.shape[-1]),
            seen["mamba2:alone"].ravel())


def _report(low, rows, decay, first, held):
    rows = [[int(n) for n in layer] for layer in rows]
    print(json.dumps({
        "phase": "reference_nemotronh",
        "choice_low_margin": LOW_MARGIN,
        "choice_low_margin_share_by_layer": [float(v) for v in low],
        "choice_low_margin_share_max": LOW_MARGIN_SHARE_MAX,
        # this chip's rows over a fair share, and the hottest expert's load
        "share_rows_over_expected_by_layer": [
            sum(layer[first: first + held]) * len(layer) / (
                held * max(sum(layer), 1)) for layer in rows],
        "load_max_over_mean_by_layer": [
            max(layer) * len(layer) / max(sum(layer), 1) for layer in rows],
        "ssd_decay_p50_by_layer": [float(v) for v in decay]}),
        file=sys.stderr, flush=True)


def reference_forward(params, input_ids, labels, config, rehearse=False,
                      buffers=None, **planted):
    """What ``jobs_shared.reference_check`` calls: (the reference's loss of
    every token; the share of each routed layer's tokens with a low margin
    of the choice, which it holds to ``LOW_MARGIN_SHARE_MAX``).  The load
    the routing puts on this chip's experts and each scan's median decay go
    to standard error.  ``buffers``: the state's; ``None``: those of the
    state ``condition`` last made."""
    m = sizes(config, rehearse)
    losses, low, rows, decay = reference(
        params, _ling._buffers_of(buffers), input_ids, labels, m, **planted)
    jax.debug.callback(
        lambda low, rows, decay: _report(
            low, rows, decay, m["first_expert"], m["n_routed_experts"]),
        low, rows, decay)
    return losses, low


def reference_token_losses(params, input_ids, labels, config, rehearse=False,
                           **planted):
    """``reference_forward``'s losses, NaN where a layer's low-margin share
    is over ``LOW_MARGIN_SHARE_MAX``."""
    losses, low = reference_forward(
        params, input_ids, labels, config, rehearse, **planted)
    return jnp.where(jnp.max(low) <= LOW_MARGIN_SHARE_MAX, losses, jnp.nan)
