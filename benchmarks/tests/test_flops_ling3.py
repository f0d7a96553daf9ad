"""The Ling-3.0 family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what the
step's program holds at the cell's sizes, and the readers of the new
metrics on a made-up table of scopes."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "ling3")
CONFIG = read_json(HERE, "configs", "ling3flashvl_125b_1of32.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "ling3flashvl_125b_1of32.steady"
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "num_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("mla_attn_ms_per_step", "mla_attn_roofline_pct",
       "mla_latent_ms_per_step", "moe_route_ms_per_step", "moe_bias_abs_max",
       "moe_group_dropped_share")


def test_ling_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # every published width as it is
    for key, width in (
            ("hidden_size", 2560), ("intermediate_size", 6144),
            ("moe_intermediate_size", 768),
            ("moe_shared_expert_intermediate_size", 768), ("head_dim", 128),
            ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
            ("qk_rope_head_dim", 64), ("v_head_dim", 128),
            ("num_experts_per_tok", 8), ("n_group", 8), ("topk_group", 4),
            ("short_conv_kernel_size", 4), ("layer_group_size", 6)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["published"]["num_experts"] == 512
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert {"mla_layer_place", "kda_safe_gate", "kda_beta", "kda_positions",
            "use_qk_norm", "head_wise_gate", "group_score",
            "bias_update_rate", "initialisers", "swiglu_limit"} <= set(
                CONFIG["assumed"])
    # the layers kept have no swiglu limit: none is built
    kept = [1] + list(range(6, 12))
    assert not any(CONFIG["expert_swiglu_limit_list"][i]
                   or CONFIG["share_expert_swiglu_limit_list"][i]
                   for i in kept)
    # the floors: the dense layer, a whole period, 8 routed experts, an
    # eighth of the rows
    assert CONFIG["num_hidden_layers"] == 1 + CONFIG["layer_group_size"]
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling3flashvl_125b_1of32")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) <= listed and "kda_roofline_pct" in listed
    assert "kda_beta_over_one_share" not in listed
    assert not any(name.startswith("fa2_") for name in listed)


def test_ling_program_holds_what_the_file_says():
    """860,980,016 parameters (the issue's arithmetic), by kind of layer."""
    kda = (6 * 2560 * 1024 + 2560 * 8 + 3 * 4 * 1024 + 8 + 1024 + 128)
    mla = (2560 * 8 * 192 + 2560 * 576 + 512 + 512 * 8 * 256
           + 1024 * 2560 + 2560 * 8)
    assert (kda, mla) == (15_762_568, 9_097_728)
    beside = 3 * 2560 * 768 + 2560 * 512 + 2 * 2560 + 16 * 3 * 2560 * 768
    dense = kda + 3 * 2560 * 6144 + 2 * 2560
    assert dense == 62_953_608
    whole = (dense + 5 * (kda + beside) + mla + beside
             + 2 * 19648 * 2560 + 2560)
    assert whole == 860_980_016
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_prefix == ("kda:dense",)
    assert cfg.layer_pattern == ("kda",) * 5 + ("mla",)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (8, 128, 4)
    assert cfg.kda_full_rank_gates and not cfg.kda_neg_eigval
    assert cfg.kda_decay_lower_bound == -5.0
    assert (cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim,
            cfg.mla_v_dim, cfg.mla_head_gate) == (512, 128, 64, 128, True)
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (512, 8, 16)
    assert (cfg.n_group, cfg.topk_group, cfg.selection_bias) == (8, 4, True)
    assert cfg.router_scores == "sigmoid" and cfg.shared_experts == 1
    assert cfg.routed_scaling_factor == 2.5 and cfg.bias_update_rate == 0.001
    assert cfg.load_balance_coef == 0.0 and cfg.router_z_coef == 0.0
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "use_nGPT": True}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 21)


def test_ling_matmul_params_and_flops_by_hand():
    kda = 6 * 2560 * 1024 + 2560 * 8
    mla = (2560 * 8 * 192 + 2560 * 576 + 512 * 8 * 256 + 1024 * 2560
           + 2560 * 8)
    # router, the shared expert, and a quarter of a routed one (8 x 16 / 512)
    ffn = 2560 * 512 + 1.25 * 3 * 2560 * 768
    matmul = (kda + 3 * 2560 * 6144 + 5 * (kda + ffn) + mla + ffn
              + 2560 * 19648)
    assert family.matmul_params(CONFIG) == matmul
    assert 250e6 < matmul < 256e6
    kda_shape = family.kda_shape(CONFIG, 1, SEQ)
    assert kda_shape == {"batch": 1, "seq": SEQ, "heads": 8, "head_dim": 128,
                         "layers": 6}
    mla_shape = family.mla_shape(CONFIG, 1, SEQ)
    assert mla_shape == {"batch": 1, "seq": SEQ, "heads": 8, "nope": 128,
                         "rope": 64, "v": 128, "layers": 1}
    per_token = 6 * matmul + (family.mla_step_flops(mla_shape)
                              + family.kda_step_flops(kda_shape)) / SEQ
    assert family.flops_per_token(CONFIG, SEQ) == per_token


def test_ling_mla_flops_and_bytes_by_hand():
    shape = {"batch": 2, "seq": 5, "heads": 3, "nope": 4, "rope": 2, "v": 4,
             "layers": 7}
    pairs = 15                          # 5 + 4 + 3 + 2 + 1
    # a pair: scores over 6 and values over 4 forward; scores again, dq and
    # dk over 6 each, dp and dv over 4 each backward; 2 operations each
    per_pair = 2 * (6 + 4) + 2 * (3 * 6 + 2 * 4)
    assert family.mla_step_flops(shape) == per_pair * pairs * 3 * 2 * 7
    rows = 2 * 5
    q, k_nope, k_pe, wide = rows * 3 * 6, rows * 3 * 4, rows * 2, rows * 3 * 4
    forward = q + k_nope + k_pe + 2 * wide
    backward = (q + k_nope + k_pe + 3 * wide) + (q + k_nope + k_pe + wide)
    assert family.mla_step_bytes(shape) == 7 * 2 * (forward + backward)
    cell = family.mla_shape(CONFIG, 1, SEQ)
    # compute-bound on a v5e at the cell's length
    assert family.mla_step_flops(cell) / 197e12 > (
        family.mla_step_bytes(cell) / 819e9)


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


def test_ling_readers_on_a_made_up_table():
    rows = {("attn.core", "latent", "forward"): 10.0,
            ("attn.core", "latent", "backward"): 25.0,
            ("attn.proj", "latent", "forward"): 2.0,
            ("attn.proj", "latent", "remat"): 2.0,
            ("attn.proj", "latent", "backward"): 4.0,
            ("moe", "route", "forward"): 3.0,
            ("moe", "route", "remat"): 3.0,
            ("moe", "route", "backward"): 1.0,
            ("optimizer", "bias", "forward"): 0.5,
            # the delta-rule layers' parts and the rest: not these readers'
            ("attn.core", "chunk", "forward"): 50.0,
            ("attn.proj", "", "forward"): 8.0,
            ("moe", "gmm", "forward"): 7.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("mla_attn_ms_per_step") == pytest.approx(35.0)
    assert read("mla_latent_ms_per_step") == pytest.approx(8.0)
    assert read("moe_route_ms_per_step") == pytest.approx(7.0)
    assert read("kda_ms_per_step") == pytest.approx(50.0)
    shape = family.mla_shape(CONFIG, 1, SEQ)
    least = family.mla_step_flops(shape) / 197e12
    assert read("mla_attn_roofline_pct") == pytest.approx(100 * least / 0.035)
    assert read("mla_attn_roofline_pct") < 100


def test_ling_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (the parent commit: no table at all, or
    a table with no such row), another family, a run without a trace, a
    program that sows no counter: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    other = load_module("families", "llama")
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for observed in (no_rows, {**no_rows, "device_scopes": None},
                     {**no_rows, "trace_loaded": None,
                      "device_scopes": None}):
        for name in NEW:
            assert load_module("layer_metrics", name).read(observed) is None
    # another family with the core's row but no count of its work
    foreign = {**_observed({("attn.core", "latent", "forward"): 8.0}),
               "family": other}
    assert load_module(
        "layer_metrics", "mla_attn_roofline_pct").read(foreign) is None


def test_ling_scopes_are_the_programs_table():
    """The readers' scopes are the ones the program's kind table has, and
    the path of each resolves to them."""
    from dlrover_tpu.observability import trace as program_trace

    assert "latent" in program_trace.SUB_SCOPES["attn.core"]
    assert program_trace.SUB_SCOPES["attn.proj"] == ("latent",)
    assert program_trace.SUB_SCOPES["optimizer"] == ("bias",)
    layer = "jit(step)/jvp(LlamaForCausalLM)/layers/while/body/mla_1/layer"
    scope_of = program_trace.scope_of
    assert scope_of(f"{layer}/attn/latent/kv_a_proj/dot_general") == (
        "attn.proj", "latent", "forward")
    assert scope_of(f"{layer}/attn/attn.core/latent/pallas_call") == (
        "attn.core", "latent", "forward")
    # a kernel's path that starts anew at its innermost scope: the core's
    assert scope_of("latent/pallas_call") == ("attn.core", "latent", "forward")
    assert scope_of(f"{layer}/attn/q_proj/dot_general") == (
        "attn.proj", "", "forward")
    assert scope_of(f"{layer}/mlp/moe/route/top_k") == (
        "moe", "route", "forward")
    assert scope_of(f"{layer}/mlp/moe/route/optimizer/bias/sign") == (
        "optimizer", "bias", "forward")
