"""The LFM2 family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what the
step's program holds at the cell's sizes, and the readers of the three new
metrics on a made-up table of scopes and made-up records."""

import json
import os
import types

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "lfm2")
CONFIG = read_json(HERE, "configs", "lfm2_24b_1of8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "lfm2_24b_1of8.steady"
REDUCED = {"num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("gconv_ms_per_step", "gconv_roofline_pct", "gconv_past_tap_share")


def test_lfm2_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == REDUCED == set(CONFIG["reduced"])
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # the nested groups whole: the list of 40 kinds and the rotary's
    assert CONFIG["layer_types"] == row["config"]["layer_types"]
    assert CONFIG["layer_types"].count("full_attention") == 10
    assert CONFIG["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default"}
    # every published width as it is
    for key, width in (
            ("hidden_size", 2048), ("intermediate_size", 11776),
            ("moe_intermediate_size", 1536), ("num_attention_heads", 32),
            ("num_key_value_heads", 8), ("conv_L_cache", 3),
            ("num_experts_per_tok", 4), ("routed_scaling_factor", 1)):
        assert CONFIG[key] == row["config"][key] == width
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert {"head_dim", "tie_word_embeddings", "w_in_columns", "taps",
            "final_norm", "qk_norm", "weights_sum", "bias_update_rate",
            "bias_update", "initialisers", "state"} <= set(CONFIG["assumed"])
    # the floors: two whole periods after the dense layer, 8 experts, an
    # eighth of the rows
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] == 8
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_1of8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    # (no count of cells and no "last of its list" here: a later PR's cell
    # must not break this one's test)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"moe_route_ms_per_step", "moe_bias_abs_max",
                       "moe_share_rows_over_expected", "mfu_pct",
                       "scope_unnamed_pct"} <= listed
    # heads of 64 take the split backward: the FA2 readers find their three
    assert {"fa2_ms_per_step", "fa2_roofline_pct"} <= listed
    assert not any(name.startswith(("kda_", "mla_", "ssm_", "ssd_"))
                   for name in listed)
    for name in NEW:        # added for this cell, and each moves the step
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"][0] == CELL
        assert metric["moves"] == "tokens_per_s"


def test_lfm2_program_holds_what_the_file_says():
    """832,651,520 parameters and 512 bias entries, part by part."""
    expert = 3 * 2048 * 1536
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    softmax = 2048 * 64 * (2 * 32 + 2 * 8) + 2 * 64
    beside = 8 * expert + 2048 * 64 + 2 * 2048
    assert (expert, conv, softmax, beside) == (
        9_437_184, 16_783_360, 10_485_888, 75_632_640)
    period = softmax + 3 * conv + 4 * beside
    dense = conv + 3 * 2048 * 11776 + 2 * 2048
    assert (period, dense) == (363_366_528, 89_139_200)
    whole = dense + 2 * period + 8192 * 2048 + 2048
    assert whole == 832_651_520
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_prefix == ("conv:dense",) and cfg.periods == 2
    assert cfg.layer_pattern == ("gqa", "conv", "conv", "conv")
    assert cfg.layer_runs() == [("gqa_0", "gqa", 1), ("conv_1", "conv", 3)]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert cfg.qk_norm == "head" and cfg.tie_embeddings and cfg.use_rope
    assert cfg.rope_theta == 1e6 and cfg.conv_taps == 3
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (64, 4, 8)
    assert (cfg.intermediate_size, cfg.dense_intermediate_size) == (
        1536, 11776)
    assert cfg.shared_experts == 0 and cfg.n_group == 0
    assert cfg.selection_bias and cfg.router_scores == "sigmoid"
    assert cfg.norm_topk_prob and cfg.norm_topk_eps == 1e-6
    assert cfg.routed_scaling_factor == 1.0
    assert cfg.bias_update_rate == 0.001 and cfg.rms_norm_eps == 1e-5
    assert cfg.load_balance_coef == 0.0 and cfg.router_z_coef == 0.0
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "use_expert_bias": False}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 18)


def test_lfm2_checkout_without_the_kind_is_refused_with_a_sentence(
        monkeypatch):
    """What the parent commit says when asked for the cell: at once, before
    any state is made."""
    from dlrover_tpu.models import llama

    monkeypatch.setattr(llama, "LAYER_KINDS", tuple(
        kind for kind in llama.LAYER_KINDS if kind != "conv"))
    with pytest.raises(RuntimeError, match="gated short convolution"):
        family.build(CONFIG, False, SEQ)


def test_lfm2_matmul_params_and_flops_by_hand():
    conv = 2048 * 6144 + 2048 * 2048
    softmax = 2048 * 64 * 80
    dense = 3 * 2048 * 11776
    # the router and 4 x 8 / 64, half, of a routed expert
    routed = 2048 * 64 + 0.5 * 3 * 2048 * 1536
    head = 2048 * 8192
    matmul = 7 * conv + 2 * softmax + dense + 8 * routed + head
    assert family.matmul_params(CONFIG) == matmul == 266_338_304
    # the issue's shares of the forward pass: 10.93 T a step
    core = 2 * 2 * 2048 * SEQ * SEQ          # QK^T and PV, causal, 2 layers
    forward = 2 * matmul * SEQ + core
    assert round(forward / 1e12, 2) == 10.93
    shares = {"conv": 2 * 7 * conv * SEQ, "dense": 2 * dense * SEQ,
              "softmax cores": core, "experts": 2 * 8 * routed * SEQ,
              "head": 2 * head * SEQ}
    assert {k: round(100 * v / forward) for k, v in shares.items()} == {
        "conv": 35, "dense": 22, "softmax cores": 20, "experts": 12,
        "head": 5}
    assert family.flops_per_token(CONFIG, SEQ) == (
        6 * matmul + 6 * 2 * 2048 * SEQ)
    shape = family.gconv_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "channels": 2048, "taps": 3,
                     "layers": 7}
    # B, C, u and the result forward (4); the three, the cotangent and
    # three gradients backward (7); bfloat16
    assert family.gconv_step_bytes(shape) == 7 * 11 * SEQ * 2048 * 2
    assert round(family.gconv_step_bytes(shape) / 819e9 * 1e3, 2) == 6.31
    fa2 = family.fa2_shape(CONFIG, 1, SEQ)
    assert (fa2["heads"], fa2["kv_heads"], fa2["head_dim"]) == (32, 8, 64)
    assert fa2["calls_per_step"] == {"fwd": 4, "dq": 2, "dkv": 2}


def _observed(rows):
    table = {"steps": 2, "period_ms": 1000.0, "busy_ms": 990.0,
             "union_ms": 990.0, "unnamed_ms": 1.0, "unnamed_before_ms": 2.0,
             "unmatched": 0,
             "rows": {key: [ms, 1.0, 0.0] for key, ms in rows.items()}}
    return {"family": family, "config": CONFIG, "batch": 1, "seq": SEQ,
            "chips": 1, "peaks": PEAKS, "values": {},
            # a table an earlier reader of the run left: none is made anew
            "trace_loaded": trace.Trace(
                device_ops={0: [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]},
                host_spans=[], seen={}),
            "device_scopes": table}


def test_lfm2_readers_on_a_made_up_table():
    rows = {("attn.core", "gconv", "forward"): 6.0,
            ("attn.core", "gconv", "remat"): 6.0,
            ("attn.core", "gconv", "backward"): 13.0,
            ("attn.core", "conv", "forward"): 4.0,  # another family's taps
            ("attn.core", "", "forward"): 20.0,     # the FA2 layers
            ("attn.proj", "", "forward"): 80.0,
            ("moe", "route", "forward"): 12.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("gconv_ms_per_step") == pytest.approx(25.0)
    shape = family.gconv_shape(CONFIG, 1, SEQ)
    least = family.gconv_step_bytes(shape) / 819e9
    assert read("gconv_roofline_pct") == pytest.approx(100 * least / 0.025)
    assert 0 < read("gconv_roofline_pct") < 100


def test_lfm2_share_reads_the_layer_farthest_from_a_half(monkeypatch, capfd):
    reader = load_module("layer_metrics", "gconv_past_tap_share")
    spans = [types.SimpleNamespace(attrs={"step": 10, "gconv_past_tap_share":
                                          [0.5, 0.6, 0.7]}),
             types.SimpleNamespace(attrs={"step": 20, "gconv_past_tap_share":
                                          [0.52, 0.31, 0.66]})]
    monkeypatch.setattr(reader.program_spans, "model_stats", lambda obs, name: [
        (s.attrs["step"], s.attrs[name]) for s in spans if name in s.attrs])
    assert reader.read({}) == 0.31
    assert '"phase": "gconv_taps"' in capfd.readouterr().err
    monkeypatch.setattr(reader.program_spans, "model_stats",
                        lambda obs, name: [])
    assert reader.read({}) is None


def test_lfm2_readers_return_nothing_where_there_is_nothing():
    """A program without the scope (a table with no such row, or no table
    at all), a run without a trace: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for name in ("gconv_ms_per_step", "gconv_roofline_pct"):
        reader = load_module("layer_metrics", name)
        for observed in (no_rows, {**no_rows, "device_scopes": None},
                         {**no_rows, "trace_loaded": None,
                          "device_scopes": None}):
            assert reader.read(observed) is None, name
    # another family's cell: no count of the core to read
    other = {**_observed({("attn.core", "gconv", "forward"): 8.0}),
             "family": types.SimpleNamespace()}
    assert load_module("layer_metrics", "gconv_roofline_pct").read(other) is None
