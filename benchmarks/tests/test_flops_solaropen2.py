"""The Solar-Open2 family's counts of operations and bytes on shapes worked
by hand, what the configuration file holds against the catalog's row, what
the step's program holds at the cell's sizes, and the readers of the
delta-rule layers' metrics on a made-up table of scopes."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "solaropen2")
CONFIG = read_json(HERE, "configs", "solaropen2_250b_1of32.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "solaropen2_250b_1of32.steady"
REDUCED = {"num_hidden_layers", "n_routed_experts", "num_attention_heads",
           "num_key_value_heads", "linear_attn_config", "vocab_size"}
SEQ = CONFIG["run"]["seq"]


def test_solar_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # inside the one nested group only the count of heads moved
    published = row["config"]["linear_attn_config"]
    assert {k for k, v in published.items()
            if CONFIG["linear_attn_config"][k] != v} == {"num_heads"}
    # every published width as it is
    for key, width in (("hidden_size", 4096), ("head_dim", 128),
                       ("moe_intermediate_size", 1280),
                       ("num_experts_per_tok", 8), ("n_shared_experts", 1)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["linear_attn_config"]["head_dim"] == 128
    assert CONFIG["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert CONFIG["published"]["n_routed_experts"] == 320
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert {"kda_layer", "gqa_gate", "router_scores", "selection_bias",
            "kda_initialisers", "router_aux_loss_coef",
            "l2_norm_eps"} <= set(CONFIG["assumed"])
    # the floors: a whole period, 8 routed experts, an eighth of the rows
    assert CONFIG["num_hidden_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solaropen2_250b_1of32")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"


def test_solar_program_holds_what_the_file_says():
    """966.7 M parameters (the file's notes), by kind of layer."""
    kda = (4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
           + 3 * 4 * 1024 + 8 + 1024 + 128)
    gqa = 3 * 4096 * 1024 + 2 * 4096 * 128
    assert (kda, gqa) == (18_134_152, 13_631_488)
    expert = 3 * 4096 * 1280
    beside = expert + 4096 * 320 + 2 * 4096 + 10 * expert
    whole = 3 * (kda + beside) + gqa + beside + 2 * 24576 * 4096 + 4096
    assert whole == 966_700_440
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_pattern == ("gqa", "kda", "kda", "kda")
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (8, 128, 4)
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (320, 8, 10)
    assert cfg.router_scores == "sigmoid" and cfg.shared_experts == 1
    assert cfg.use_rope is False and cfg.attn_gate is True
    assert cfg.attention_impl == "flash"
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "use_rope": True}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 21)


def test_solar_matmul_params_and_flops_by_hand():
    kda = 4 * 4096 * 1024 + 2 * (4096 + 1024) * 128 + 4096 * 8
    gqa = 3 * 4096 * 1024 + 2 * 4096 * 128
    # router, the shared expert, and a quarter of a routed one (8 x 10 / 320)
    ffn = 4096 * 320 + 1.25 * 3 * 4096 * 1280
    matmul = 3 * (kda + ffn) + gqa + ffn + 4096 * 24576
    assert family.matmul_params(CONFIG) == matmul == 252_542_976
    shape = family.kda_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "heads": 8, "head_dim": 128,
                     "layers": 3}
    per_token = 6 * matmul + 6 * 1 * 1024 * SEQ + (
        family.kda_step_flops(shape) / SEQ)
    assert family.flops_per_token(CONFIG, SEQ) == per_token
    # the recurrence is under 1% of the step's operations: its cost is time
    assert family.kda_step_flops(shape) / SEQ < 0.01 * per_token
    assert family.fa2_shape(CONFIG, 1, SEQ) == {
        "batch": 1, "seq": SEQ, "heads": 8, "kv_heads": 1, "head_dim": 128,
        # a run of one layer in one period: no second forward call
        "causal": True, "calls_per_step": {"fwd": 1, "dq": 1, "dkv": 1}}
    two_periods = {**CONFIG, "num_hidden_layers": 8}
    assert family.fa2_shape(two_periods, 1, SEQ)["calls_per_step"] == {
        "fwd": 4, "dq": 2, "dkv": 2}


def test_solar_kda_flops_and_bytes_by_hand():
    shape = {"batch": 2, "seq": 5, "heads": 3, "head_dim": 4, "layers": 7}
    # a 4 x 4 state: decay 16, S^T k 32, update 32, read-out 32, forward;
    # twice that backward
    assert family.kda_step_flops(shape) == 3 * (16 + 32 + 32 + 32) * (
        7 * 2 * 5 * 3)
    rows = 2 * 5 * 3
    operands = 3 * rows * 4 * 2 + rows * 4 * 4 + rows * 4
    out = rows * 4 * 2
    assert family.kda_step_bytes(shape) == 7 * (
        (operands + out) + (operands + out) + operands)
    cell = family.kda_shape(CONFIG, 1, SEQ)
    # memory-bound on a v5e: the state never leaves the chip
    assert family.kda_step_bytes(cell) / 819e9 > (
        family.kda_step_flops(cell) / 197e12)


def test_solar_step_holds_nothing_seq_by_seq():
    """The model's forward and backward pass at the cell's sizes, lowered
    from shapes alone (the reference core in the kernel's place, which no
    CPU lowers): the delta-rule layers hold no array with two dimensions of
    the whole sequence, and the scan between chunks is there."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    model = family.build(CONFIG, False, SEQ)
    only_kda = type(model)(dataclasses.replace(
        model.config, layer_pattern=("kda",), num_layers=1,
        attention_impl="reference"))
    ids = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(only_kda.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))
    params = nn.meta.unbox(shapes["params"])

    def loss(p, i):
        logits, sown = only_kda.apply({"params": p}, i, mutable=["losses"])
        return logits.astype(jnp.float32).mean() + sum(
            jnp.sum(t) for t in jax.tree.leaves(sown["losses"]))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    assert f"{SEQ}x{SEQ}" not in text
    chunks = SEQ // 64
    assert f"{chunks}x8x64x64xf32" in text          # A and P, a chunk a tile
    assert "1x8x128x128xf32" in text                # the state, float32


def _observed(rows):
    table = {"steps": 2, "period_ms": 100.0, "busy_ms": 99.0,
             "union_ms": 99.0, "unnamed_ms": 1.0, "unnamed_before_ms": 2.0,
             "unmatched": 0,
             "rows": {key: [ms, 1.0, 0.0] for key, ms in rows.items()}}
    return {"family": family, "config": CONFIG, "batch": 1, "seq": SEQ,
            "chips": 1, "peaks": PEAKS, "values": {},
            # a table an earlier reader of the run left: none is made anew
            "trace_loaded": trace.Trace(
                device_ops={0: [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]},
                host_spans=[], seen={}),
            "device_scopes": table}


def test_solar_readers_on_a_made_up_table():
    rows = {("attn.core", "chunk", "forward"): 10.0,
            ("attn.core", "chunk", "backward"): 20.0,
            ("attn.core", "state", "forward"): 3.0,
            ("attn.core", "state", "remat"): 3.0,
            ("attn.core", "state", "backward"): 6.0,
            ("attn.core", "conv", "forward"): 1.0,
            ("attn.core", "decay", "forward"): 2.0,
            ("attn.core", "gate", "backward"): 4.0,
            # the softmax layer's kernel and another model's parts: not KDA
            ("attn.core", "", "forward"): 8.0,
            ("attn.core", "windows", "forward"): 50.0,
            ("moe", "shared", "forward"): 7.0,
            ("mlp", "", "forward"): 30.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("kda_ms_per_step") == pytest.approx(49.0)
    assert read("kda_state_ms_per_step") == pytest.approx(12.0)
    shape = family.kda_shape(CONFIG, 1, SEQ)
    least = family.kda_step_bytes(shape) / 819e9
    assert read("kda_roofline_pct") == pytest.approx(100 * least / 0.049)
    assert read("kda_roofline_pct") < 100


def test_solar_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (the parent commit: no table at all, or
    a table with no delta-rule row), another family, a run without a trace,
    a program that sows no counter: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    names = ["kda_ms_per_step", "kda_state_ms_per_step", "kda_roofline_pct",
             "kda_beta_over_one_share"]
    other = load_module("families", "llama")
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for observed in (no_rows, {**no_rows, "device_scopes": None},
                     {**no_rows, "family": other},
                     {**no_rows, "trace_loaded": None,
                      "device_scopes": None}):
        for name in names:
            assert load_module("layer_metrics", name).read(observed) is None


def test_solar_scopes_are_the_programs_table():
    """The reader's sub-scopes are the ones the program's kind table has
    for a delta-rule layer, and the path of each resolves to them."""
    from dlrover_tpu.observability import trace as program_trace

    reader = load_module("layer_metrics", "kda_ms_per_step")
    assert set(reader.SUB_SCOPES) <= set(program_trace.SUB_SCOPES[reader.KIND])
    assert "shared" in program_trace.SUB_SCOPES["moe"]
    layer = "jit(step)/jvp(LlamaForCausalLM)/layers/while/body/kda_1/layer"
    assert program_trace.scope_of(f"{layer}/attn/attn.core/state/while/body/dot_general") == (
        "attn.core", "state", "forward")
    assert program_trace.scope_of(
        f"jit(step)/transpose(jvp(LlamaForCausalLM))/layers/kda_1/layer/attn/"
        "attn.core/chunk/jit(_solve_triangular)/triangular_solve") == (
            "attn.core", "chunk", "backward")
    assert program_trace.scope_of(f"{layer}/attn/attn.core/gate/o_norm/mul") == (
        "attn.core", "gate", "forward")
    assert program_trace.scope_of(f"{layer}/attn/q_proj/dot_general") == (
        "attn.proj", "", "forward")
    assert program_trace.scope_of(
        f"{layer}/mlp/moe/shared/shared_expert/up_proj/dot_general") == (
            "moe", "shared", "forward")
