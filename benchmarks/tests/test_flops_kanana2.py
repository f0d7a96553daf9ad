"""The Kanana-2 family's counts of operations and bytes on shapes worked by
hand (the latent core's count is Ling-3.0's, by import: one count for both
cells' roofline), what the configuration file holds against the catalog's
row, what the step's program holds at the cell's sizes, and the readers of
the two new metrics on a made-up table of scopes and made-up records."""

import json
import os
import types

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "kanana2")
CONFIG = read_json(HERE, "configs", "kanana2_30b_1of8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "kanana2_30b_1of8.steady"
LING = "ling3flashvl_125b_1of32.steady"
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("mla_core_step_share_pct", "mla_rope_pairs")


def test_kanana_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # every published width as it is, and all 32 heads
    for key, width in (
            ("hidden_size", 2048), ("intermediate_size", 6144),
            ("moe_intermediate_size", 768), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("qk_head_dim", 192), ("head_dim", 64),
            ("num_attention_heads", 32), ("num_experts_per_tok", 6),
            ("n_shared_experts", 2), ("n_group", 1), ("topk_group", 1)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["routed_scaling_factor"] == 2.448
    assert CONFIG["rope_interleave"] is True and CONFIG["q_lora_rank"] is None
    assert CONFIG["published"]["n_routed_experts"] == 128
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert {"bias_update_rate", "bias_update", "head_dim", "n_group",
            "shared_experts", "initialisers", "state"} <= set(
                CONFIG["assumed"])
    # the floors: the dense layer, at least four layers after it (the
    # period is one layer), 8 routed experts, an eighth of the rows
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana2_30b_1of8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"mla_attn_ms_per_step", "mla_attn_roofline_pct",
                       "mla_latent_ms_per_step", "moe_route_ms_per_step",
                       "moe_bias_abs_max", "mfu_pct"} <= listed
    assert "moe_group_dropped_share" not in listed      # there are no groups
    assert not any(name.startswith(("fa2_", "kda_")) for name in listed)
    for name in NEW:        # both cells that run the latent layer today
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert {CELL, LING} <= set(metric["workloads"])


def test_kanana_program_holds_what_the_file_says():
    """687,502,336 parameters (the issue's arithmetic), by kind of layer."""
    latent = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
              + 4096 * 2048)
    assert latent == 26_345_984
    routed = (latent + 16 * 3 * 2048 * 768 + 3 * 2048 * 1536 + 2048 * 128
              + 2 * 2048)
    dense = latent + 3 * 2048 * 6144 + 2 * 2048
    assert (routed, dense) == (111_546_880, 64_098_816)
    whole = dense + 5 * routed + 2 * 16032 * 2048 + 2048
    assert whole == 687_502_336
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_prefix == ("mla:dense",)
    assert cfg.layer_pattern == ("mla",) and cfg.periods == 5
    assert (cfg.num_heads, cfg.mla_kv_rank, cfg.mla_nope_dim,
            cfg.mla_rope_dim, cfg.mla_v_dim) == (32, 512, 128, 64, 128)
    assert cfg.mla_rope_interleave and not cfg.mla_head_gate
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (128, 6, 16)
    assert (cfg.n_group, cfg.topk_group, cfg.selection_bias) == (0, 0, True)
    assert cfg.router_scores == "sigmoid" and cfg.norm_topk_prob
    assert cfg.shared_experts == 2 and cfg.shared_width() == 1536
    assert cfg.routed_scaling_factor == 2.448
    assert cfg.bias_update_rate == 0.001 and cfg.rope_theta == 1e6
    assert cfg.load_balance_coef == 0.0 and cfg.router_z_coef == 0.0
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "rope_interleave": False}, False, SEQ)
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "q_lora_rank": 1536}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 16)


def test_kanana_checkout_without_the_field_is_refused_with_a_sentence(
        monkeypatch):
    """What the parent commit says when asked for the cell: at once, before
    any state is made."""
    import dataclasses

    from dlrover_tpu.models import moe

    real = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda cls: [
        f for f in real(cls) if f.name != "mla_rope_interleave"])
    with pytest.raises(RuntimeError, match="rotary by interleaved pairs"):
        family.build(CONFIG, False, SEQ)
    assert "mla_rope_interleave" in {
        f.name for f in real(moe.MoELlamaConfig)}


def test_kanana_matmul_params_and_flops_by_hand():
    latent = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    # the router, the shared SwiGLU (two experts' width), and three
    # quarters of a routed expert (6 x 16 / 128)
    ffn = 2048 * 128 + (2 + 0.75) * 3 * 2048 * 768
    matmul = (latent + 3 * 2048 * 6144 + 5 * (latent + ffn) + 2048 * 16032)
    assert family.matmul_params(CONFIG) == matmul == 294_846_464
    shape = family.mla_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "heads": 32, "nope": 128,
                     "rope": 64, "v": 128, "layers": 6}
    # 2,304 operations a causal pair and head (the issue's count)
    pairs = SEQ * (SEQ + 1) // 2
    assert family.mla_step_flops(shape) == 2304 * pairs * 32 * 6
    assert 59.3e12 < family.mla_step_flops(shape) < 59.5e12
    assert family.flops_per_token(CONFIG, SEQ) == (
        6 * matmul + family.mla_step_flops(shape) / SEQ)
    # compute-bound on a v5e at the cell's length: 301 ms at the peak
    least = family.mla_step_flops(shape) / PEAKS["bf16_flops_per_s"]
    assert 0.300 < least < 0.303
    assert least > family.mla_step_bytes(shape) / PEAKS["hbm_bytes_per_s"]


def test_kanana_count_of_the_core_is_lings_own():
    """Imported, not copied: both cells' ``mla_attn_roofline_pct`` reads
    one count of the work the model asks."""
    ling = load_module("families", "ling3")
    shape = {"batch": 2, "seq": 5, "heads": 3, "nope": 4, "rope": 2, "v": 4,
             "layers": 7}
    assert family.mla_step_flops(shape) == ling.mla_step_flops(shape)
    assert family.mla_step_bytes(shape) == ling.mla_step_bytes(shape)
    assert family.mla_step_flops.__module__ == ling.mla_step_flops.__module__


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


def test_kanana_readers_on_a_made_up_table():
    rows = {("attn.core", "latent", "forward"): 200.0,
            ("attn.core", "latent", "backward"): 394.0,
            ("attn.proj", "latent", "forward"): 20.0,
            ("attn.proj", "latent", "backward"): 30.0,
            ("attn.proj", "", "forward"): 80.0,
            ("moe", "route", "forward"): 12.0,
            ("moe", "gmm", "forward"): 70.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("mla_attn_ms_per_step") == pytest.approx(594.0)
    assert read("mla_core_step_share_pct") == pytest.approx(60.0)
    assert read("mla_latent_ms_per_step") == pytest.approx(50.0)
    assert read("moe_route_ms_per_step") == pytest.approx(12.0)
    shape = family.mla_shape(CONFIG, 1, SEQ)
    least = family.mla_step_flops(shape) / 197e12
    assert read("mla_attn_roofline_pct") == pytest.approx(100 * least / 0.594)
    assert 50 < read("mla_attn_roofline_pct") < 51


def _span(name, attrs, events=()):
    return types.SimpleNamespace(name=name, attrs=attrs, events=list(events))


LATENT = {"impl": "latent", "heads": 32}


@pytest.mark.parametrize("kept, ring, want", [
    # the readings the program keeps for the run, whatever the ring holds
    # by the window's end: the last latent record decides
    ([{"impl": "kda"}, {**LATENT, "rope": "pairs"}], [], 1.0),
    ([{**LATENT, "rope": "halves"}], [], 0.0),       # Ling-3.0's layer
    ([{**LATENT, "rope": "halves"}, {**LATENT, "rope": "pairs"}],
     [_span("attention.path", {**LATENT, "rope": "halves"})], 1.0),
    # no latent layer traced (another family); a record with no word
    ([{"impl": "flash", "rope": "none"}], [], None),
    ([LATENT], [], None),
    ([], [_span("attention.path", {**LATENT, "rope": "pairs"})], None),
    # a program that keeps none (an older commit), from the ring: an event
    # on the span open while the step was traced
    (None, [_span("trainer.step.dispatch", {}, [
        {"name": "attention.path", "attrs": {"impl": "kda"}},
        {"name": "attention.path",
         "attrs": {**LATENT, "rope": "pairs"}}])], 1.0),
    # a span of its own
    (None, [_span("attention.path", {**LATENT, "rope": "halves"})], 0.0),
    # the parent commit's record says nothing of the rotary part
    (None, [_span("attention.path", LATENT)], None),
    (None, [_span("attention.path", {"impl": "flash", "rope": "none"})],
     None),
    (None, [], None)], ids=[
        "kept_pairs", "kept_halves", "kept_last_decides",
        "kept_another_family", "kept_no_word", "kept_over_ring",
        "ring_event", "ring_span", "ring_parent", "ring_another_family",
        "ring_empty"])
def test_kanana_rope_pairs_reads_the_programs_record(
        monkeypatch, kept, ring, want):
    from dlrover_tpu.observability import trace as program_trace

    reader = load_module("layer_metrics", "mla_rope_pairs")
    monkeypatch.setattr(reader.program_spans, "ring", lambda: ring)
    if kept is None:
        monkeypatch.delattr(program_trace, "trace_time_notes")
    else:
        monkeypatch.setattr(program_trace, "trace_time_notes",
                            lambda name: kept if name == reader.NAME else [])
    assert reader.read({}) == want


def test_kanana_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (a table with no such row, or no table
    at all), a run without a trace: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    reader = load_module("layer_metrics", "mla_core_step_share_pct")
    for observed in (no_rows, {**no_rows, "device_scopes": None},
                     {**no_rows, "trace_loaded": None,
                      "device_scopes": None}):
        assert reader.read(observed) is None
