"""The Nemotron-H family's counts of operations and bytes on shapes worked
by hand, what the configuration file holds against the catalog's row, what
the step's program holds at the cell's sizes, and the readers of the five
new metrics on a made-up table of scopes and made-up records."""

import json
import os
import types

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "nemotronh")
CONFIG = read_json(HERE, "configs", "nemotron3super_120b_1of32.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "nemotron3super_120b_1of32.steady"
REDUCED = {"num_hidden_layers", "n_routed_experts", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("ssd_ms_per_step", "ssd_roofline_pct", "ssd_core_step_share_pct",
       "moe_latent_ms_per_step", "ssd_decay_p50")


def test_nemotron_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    # the pattern's string is the cut's stretch of the published one
    assert differ == REDUCED | {"hybrid_override_pattern"}
    assert set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {
        k: row["config"][k] for k in REDUCED | {"hybrid_override_pattern"}}
    whole = row["config"]["hybrid_override_pattern"]
    assert whole[26:37] == CONFIG["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert (whole.count("M"), whole.count("E"), whole.count("*")) == (
        40, 40, 8)
    # every published width as it is
    for key, width in (
            ("hidden_size", 4096), ("mamba_head_dim", 64),
            ("ssm_state_size", 128), ("head_dim", 128), ("conv_kernel", 4),
            ("chunk_size", 128), ("moe_latent_size", 1024),
            ("moe_intermediate_size", 2688), ("intermediate_size", 2688),
            ("moe_shared_expert_intermediate_size", 5376),
            ("num_experts_per_tok", 22), ("routed_scaling_factor", 5),
            ("expand", 2), ("n_shared_experts", 1)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["mlp_hidden_act"] == "relu2"
    assert CONFIG["published"]["n_routed_experts"] == 512
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert {"bias_update_rate", "bias_update", "no_positions",
            "norm_before_gate", "no_bias", "dt_unclamped", "initialisers",
            "rescale_prenorm_residual", "mtp", "state"} <= set(
                CONFIG["assumed"])
    assert "NOT built" in CONFIG["assumed"]["mtp"]
    # the floors: a whole period, 16 >= 8 experts, an eighth of the rows
    assert CONFIG["num_hidden_layers"] == 11
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron3super_120b_1of32")
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
    assert not any(name.startswith(("moe_share_gmm", "kda_", "mla_", "ssm_"))
                   for name in listed)
    for name in NEW:        # added for this cell, and each moves the step
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"][0] == CELL
        assert metric["moves"] == "tokens_per_s"


def test_nemotron_program_holds_what_the_file_says():
    """921,063,920 parameters and 2,560 bias entries (the issue's
    921,066,480), by kind of layer."""
    routed = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
              + 16 * 2 * 1024 * 2688 + 4096)
    mamba = (4096 * 2320 + 1024 * 4096 + 5 * 1280 + 1024 + 3 * 16 + 4096)
    attn = 4096 * 128 * (2 * 4 + 2 * 1) + 4096
    assert (routed + 512, mamba, attn) == (
        142_610_944, 13_708_592, 5_246_976)
    whole = 5 * routed + 5 * mamba + attn + 2 * 16384 * 4096 + 4096
    assert whole + 5 * 512 == 921_066_480
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_pattern == ("ffn", "mamba2:alone") and cfg.periods == 5
    assert cfg.layer_suffix == ("gqa:alone",) and cfg.layer_prefix == ()
    assert (cfg.mamba2_heads, cfg.mamba2_head_dim, cfg.mamba2_groups,
            cfg.mamba2_state, cfg.mamba2_chunk, cfg.mamba_conv) == (
                16, 64, 1, 128, 128, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, 1, 128)
    assert not cfg.use_rope
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (512, 22, 16)
    assert (cfg.moe_latent_size, cfg.intermediate_size) == (1024, 2688)
    assert (cfg.mlp_matrices, cfg.mlp_activation) == (2, "relu2")
    assert cfg.shared_experts == 1 and cfg.shared_width() == 5376
    assert (cfg.n_group, cfg.topk_group, cfg.selection_bias) == (0, 0, True)
    assert cfg.router_scores == "sigmoid" and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 5.0
    assert cfg.bias_update_rate == 0.001 and cfg.rms_norm_eps == 1e-5
    assert cfg.load_balance_coef == 0.0 and cfg.router_z_coef == 0.0
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "mlp_hidden_act": "silu"}, False, SEQ)
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "n_group": 8}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 19)
    with pytest.raises(ValueError, match="does not spell"):
        family.sizes({**CONFIG, "num_hidden_layers": 12}, False)


def test_nemotron_checkout_without_the_fields_is_refused_with_a_sentence(
        monkeypatch):
    """What the parent commit says when asked for the cell: at once, before
    any state is made."""
    import dataclasses

    from dlrover_tpu.models import moe

    real = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda cls: [
        f for f in real(cls) if f.name != "mamba2_heads"])
    with pytest.raises(RuntimeError, match="no Mamba-2 mixer"):
        family.build(CONFIG, False, SEQ)
    assert {"mamba2_heads", "moe_latent_size", "layer_suffix"} <= {
        f.name for f in real(moe.MoELlamaConfig)}


def test_nemotron_matmul_params_and_flops_by_hand():
    # the router, the latent's two projections, the shared expert and
    # 22 x 16 / 512 = 0.6875 of a routed expert of TWO matrices
    ffn = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
           + 0.6875 * 2 * 1024 * 2688)
    mamba = 4096 * 2320 + 1024 * 4096
    attn = 4096 * 128 * 10
    matmul = 5 * ffn + 5 * mamba + attn + 4096 * 16384
    assert family.matmul_params(CONFIG) == matmul == 432_390_144
    shape = family.ssd_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "heads": 16, "head_dim": 64,
                     "groups": 1, "state": 128, "layers": 5}
    # 15 operations an entry of a head's [64, 128] state and position
    assert family.ssd_step_flops(shape) == 15 * SEQ * 16 * 64 * 128 * 5
    # forward: x and y (2 x 1024 bf16), B and C (2 x 128 bf16), the step
    # (16 float32) a position; backward that and the gradients' four
    a_position = (2 * 2048 + 2 * 256 + 64) + (2 * 2048 + 2 * 256 + 64) + (
        2048 + 2 * 256 + 64)
    assert family.ssd_step_bytes(shape) == 5 * SEQ * a_position
    assert family.flops_per_token(CONFIG, SEQ) == (
        6 * matmul + 6 * 1 * 512 * SEQ + family.ssd_step_flops(shape) / SEQ)
    # memory-bound against the matrix unit's peak: the bytes decide
    assert (family.ssd_step_bytes(shape) / PEAKS["hbm_bytes_per_s"]
            > family.ssd_step_flops(shape) / PEAKS["bf16_flops_per_s"])
    fa2 = family.fa2_shape(CONFIG, 1, SEQ)
    assert (fa2["heads"], fa2["kv_heads"], fa2["head_dim"]) == (4, 1, 128)
    assert fa2["calls_per_step"] == {"fwd": 1, "dq": 1, "dkv": 1}


def _observed(rows, records=()):
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


def test_nemotron_readers_on_a_made_up_table():
    rows = {("attn.core", "ssd", "forward"): 30.0,
            ("attn.core", "ssd", "remat"): 30.0,
            ("attn.core", "ssd", "backward"): 39.0,
            ("attn.core", "conv", "forward"): 4.0,
            ("attn.core", "decay", "forward"): 1.0,
            ("attn.core", "gate", "backward"): 5.0,
            ("attn.core", "", "forward"): 20.0,     # the FA2 layer
            ("attn.proj", "", "forward"): 80.0,
            ("moe", "latent", "forward"): 6.0,
            ("moe", "latent", "backward"): 12.0,
            ("moe", "route", "forward"): 12.0,
            ("moe", "gmm", "forward"): 70.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("ssd_ms_per_step") == pytest.approx(99.0)
    assert read("ssd_core_step_share_pct") == pytest.approx(
        100 * 109.0 / 990.0)
    assert read("moe_latent_ms_per_step") == pytest.approx(18.0)
    assert read("moe_route_ms_per_step") == pytest.approx(12.0)
    shape = family.ssd_shape(CONFIG, 1, SEQ)
    least = family.ssd_step_bytes(shape) / 819e9
    assert read("ssd_roofline_pct") == pytest.approx(100 * least / 0.099)
    assert 0 < read("ssd_roofline_pct") < 5


def _span(step, **attrs):
    return types.SimpleNamespace(
        name="trainer.model_stats", start_ns=step, end_ns=step + 1,
        attrs={"step": step, **attrs}, events=[])


def test_nemotron_decay_reads_the_layer_farthest_from_a_half(
        monkeypatch, capfd):
    reader = load_module("layer_metrics", "ssd_decay_p50")
    spans = [_span(10, ssd_decay_p50=[0.5, 0.6, 0.7]),
             _span(20, ssd_decay_p50=[0.52, 0.31, 0.66])]
    monkeypatch.setattr(reader.program_spans, "model_stats", lambda obs, name: [
        (s.attrs["step"], s.attrs[name]) for s in spans if name in s.attrs])
    assert reader.read({}) == 0.31
    assert '"phase": "ssd_scan"' in capfd.readouterr().err
    monkeypatch.setattr(reader.program_spans, "model_stats",
                        lambda obs, name: [])
    assert reader.read({}) is None


def test_nemotron_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (a table with no such row, or no table
    at all), a run without a trace: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for name in ("ssd_ms_per_step", "ssd_roofline_pct",
                 "ssd_core_step_share_pct", "moe_latent_ms_per_step"):
        reader = load_module("layer_metrics", name)
        for observed in (no_rows, {**no_rows, "device_scopes": None},
                         {**no_rows, "trace_loaded": None,
                          "device_scopes": None}):
            assert reader.read(observed) is None, name
    # another family's cell: no count of the scan to read
    other = {**_observed({("attn.core", "ssd", "forward"): 8.0}),
             "family": types.SimpleNamespace()}
    assert load_module("layer_metrics", "ssd_roofline_pct").read(other) is None
