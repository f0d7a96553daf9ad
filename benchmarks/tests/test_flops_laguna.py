"""The Laguna family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what the
step's program holds at the cell's sizes, and the readers of the new
metrics on a made-up table of scopes and a made-up record."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "laguna")
CONFIG = read_json(HERE, "configs", "laguna_xs2_33b_1of8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "laguna_xs2_33b_1of8.steady"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("swa_attn_ms_per_step", "swa_attn_roofline_pct",
       "full_attn_ms_per_step", "full_attn_roofline_pct",
       "swa_pairs_multiplied_over_allowed")


def test_laguna_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # every published width as it is, and both rotary rules
    for key, width in (
            ("hidden_size", 2048), ("intermediate_size", 8192),
            ("moe_intermediate_size", 512),
            ("shared_expert_intermediate_size", 512), ("head_dim", 128),
            ("num_attention_heads", 48), ("num_key_value_heads", 8),
            ("num_experts_per_tok", 8), ("sliding_window", 512),
            ("moe_routed_scaling_factor", 2.5),
            ("partial_rotary_factor", 0.5)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["rope_parameters"] == row["config"]["rope_parameters"]
    assert CONFIG["rope_parameters"]["full_attention"]["factor"] == 64
    # the three lists a layer stand whole; the stack is their first five
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert CONFIG[key] == row["config"][key] and len(CONFIG[key]) == 40
    assert CONFIG["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert CONFIG["published"]["num_experts"] == 256
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert {"gating", "router", "hidden_act", "qk_norm", "window", "rope"} <= (
        set(CONFIG["assumed"]))
    # the floors: the dense layer, a whole period, 8 routed experts, an
    # eighth of the rows
    assert CONFIG["num_hidden_layers"] == 1 + 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "laguna_xs2_33b_1of8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) <= listed
    assert {"mfu_pct", "moe_share_rows_over_expected",
            "scope_unnamed_pct"} <= listed
    # its reader counts calls by one number a layer: two shapes here
    assert not any(name.startswith("fa2_") for name in listed)
    for name in NEW:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]


def test_laguna_program_holds_what_the_file_says():
    """691,623,936 parameters (the issue's arithmetic), by kind of layer."""
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert (full, window) == (29_458_432, 37_879_808)
    routed = 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048 * 256
    assert routed == 104_333_312
    dense = full + 3 * 2048 * 8192 + 2 * 2048
    assert dense == 79_794_176
    whole = (dense + 3 * (window + routed + 4096) + (full + routed + 4096)
             + 2 * 12544 * 2048 + 2048)
    assert whole == 691_623_936
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_prefix == ("gqa:dense",)
    assert cfg.layer_pattern == ("swa", "swa", "swa", "gqa")
    assert cfg.attention_numbers("swa") == (64, 512, 10000.0, 128, None)
    assert cfg.attention_numbers("gqa") == (
        48, None, 500000.0, 64, (64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    assert cfg.attn_head_gate and not cfg.attn_gate and cfg.use_rope
    assert cfg.attention_impl == "flash"
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (256, 8, 32)
    assert cfg.router_scores == "sigmoid" and cfg.shared_experts == 1
    assert cfg.routed_scaling_factor == 2.5 and cfg.norm_topk_prob
    assert cfg.load_balance_coef == 0.0 and cfg.router_z_coef == 0.0
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "gating": False}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 21)


def test_laguna_matmul_params_and_flops_by_hand():
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    # router, the shared expert, and one routed expert (8 x 32 / 256)
    ffn = 2048 * 256 + 2 * 3 * 2048 * 512
    matmul = (full + 3 * 2048 * 8192 + 3 * (window + ffn) + full + ffn
              + 2048 * 12544)
    assert family.matmul_params(CONFIG) == matmul
    swa = family.swa_shape(CONFIG, 1, SEQ)
    assert swa == {"batch": 1, "seq": SEQ, "heads": 64, "kv_heads": 8,
                   "head_dim": 128, "window": 512, "layers": 3}
    whole = family.full_shape(CONFIG, 1, SEQ)
    assert whole == {"batch": 1, "seq": SEQ, "heads": 48, "kv_heads": 8,
                     "head_dim": 128, "window": None, "layers": 2}
    per_token = 6 * matmul + (family.swa_step_flops(swa)
                              + family.full_step_flops(whole)) / SEQ
    assert family.flops_per_token(CONFIG, SEQ) == per_token
    # 6.2% of the causal pairs: three window layers ask for less than a
    # seventh of what the two full layers do
    assert family.allowed_pairs(SEQ, 512) == 8_257_792
    assert family.allowed_pairs(SEQ) == 134_225_920
    assert family.swa_step_flops(swa) * 8 < family.full_step_flops(whole)
    assert family.fa2_shape(CONFIG, 1, SEQ) is None


def test_laguna_attention_flops_and_bytes_by_hand():
    shape = {"batch": 2, "seq": 5, "heads": 6, "kv_heads": 2, "head_dim": 4,
             "window": 3, "layers": 7}
    pairs = 1 + 2 + 3 + 3 + 3
    assert family.allowed_pairs(5, 3) == pairs
    assert family.allowed_pairs(5, 9) == family.allowed_pairs(5) == 15
    # a pair: a multiply-add over 4 for the score and one for the value
    # forward, twice that backward
    assert family.swa_step_flops(shape) == 3 * (2 * 2 * 4) * pairs * 6 * 2 * 7
    rows = 2 * 5 * 4
    q, kv = rows * 6, rows * 2
    forward = 2 * q + 2 * kv
    backward = (3 * q + 2 * kv) + (q + 2 * kv)
    assert family.swa_step_bytes(shape) == 7 * 2 * (forward + backward)
    # both kinds compute-bound on a v5e at the cell's length
    for cell in (family.swa_shape(CONFIG, 1, SEQ),
                 family.full_shape(CONFIG, 1, SEQ)):
        assert family.attn_step_flops(cell) / 197e12 > (
            family.attn_step_bytes(cell) / 819e9)


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


def test_laguna_readers_on_a_made_up_table():
    rows = {("attn.core", "window", "forward"): 10.0,
            ("attn.core", "window", "remat"): 10.0,
            ("attn.core", "window", "backward"): 30.0,
            ("attn.core", "", "forward"): 60.0,
            ("attn.core", "", "backward"): 140.0,
            # projections and the rest: not these readers'
            ("attn.proj", "", "forward"): 8.0,
            ("moe", "gmm", "forward"): 7.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("swa_attn_ms_per_step") == pytest.approx(50.0)
    assert read("full_attn_ms_per_step") == pytest.approx(200.0)
    swa = family.swa_step_flops(family.swa_shape(CONFIG, 1, SEQ)) / 197e12
    assert read("swa_attn_roofline_pct") == pytest.approx(100 * swa / 0.050)
    whole = family.full_step_flops(family.full_shape(CONFIG, 1, SEQ)) / 197e12
    assert read("full_attn_roofline_pct") == pytest.approx(
        100 * whole / 0.200)
    assert read("swa_attn_roofline_pct") < 100
    assert read("full_attn_roofline_pct") < 100


def test_laguna_pairs_reader_on_a_made_up_record(monkeypatch):
    from benchmarks import program_spans
    from dlrover_tpu.observability.trace import SpanTuple

    def span(name, attrs=None, events=()):
        return SpanTuple(name, 0, 1, 0, "", "", "", "", "", "", "",
                         attrs or {}, list(events))

    reader = load_module("layer_metrics", "swa_pairs_multiplied_over_allowed")
    windowed = {"impl": "flash", "window": 512, "blocks": (512, 512),
                "pairs_multiplied": 16515072, "pairs_allowed": 8257792}
    causal = {"impl": "flash", "blocks": (1024, 1024)}
    # an event on the span open while the step was traced
    monkeypatch.setattr(program_spans, "ring", lambda: [
        span("trainer.step.dispatch", events=[
            {"name": "attention.path", "attrs": causal},
            {"name": "attention.path", "attrs": windowed},
            {"name": "moe.path", "attrs": {"pairs_allowed": 1}}])])
    assert reader.read({}) == pytest.approx(1.99994, abs=1e-5)
    # a span of its own
    monkeypatch.setattr(program_spans, "ring", lambda: [
        span("attention.path", causal), span("attention.path", windowed)])
    assert reader.read({}) == pytest.approx(1.99994, abs=1e-5)
    # no windowed call traced: nothing
    monkeypatch.setattr(program_spans, "ring", lambda: [
        span("attention.path", causal)])
    assert reader.read({}) is None
    monkeypatch.setattr(program_spans, "ring", lambda: [])
    assert reader.read({}) is None


def test_laguna_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (the parent commit: no table at all, or
    a table with no such row), another family, a run without a trace:
    ``None``, never an error (the parent commit is measured with these
    readers too)."""
    other = load_module("families", "llama")
    no_rows = _observed({("attn.core", "latent", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for observed in (no_rows, {**no_rows, "device_scopes": None},
                     {**no_rows, "trace_loaded": None,
                      "device_scopes": None}):
        for name in NEW[:4]:
            assert load_module("layer_metrics", name).read(observed) is None
    # another family: its plain ``attn.core`` rows are FA2's, read by
    # ``fa2_ms_per_step``, and no full layer beside a window layer
    foreign = {**_observed({("attn.core", "", "forward"): 8.0,
                            ("attn.core", "window", "forward"): 8.0}),
               "family": other}
    for name in ("full_attn_ms_per_step", "full_attn_roofline_pct",
                 "swa_attn_roofline_pct"):
        assert load_module("layer_metrics", name).read(foreign) is None


def test_laguna_scopes_are_the_programs_table():
    """The readers' scopes are the ones the program's kind table has, and
    the path of each resolves to them."""
    from dlrover_tpu.observability import trace as program_trace

    assert "window" in program_trace.SUB_SCOPES["attn.core"]
    scope_of = program_trace.scope_of
    layers = "jit(step)/jvp(LlamaForCausalLM)/layers/while/body"
    assert scope_of(
        f"{layers}/swa_0/layer/attn/attn.core/window/attn._attend/"
        "pallas_call") == ("attn.core", "window", "forward")
    assert scope_of(
        f"{layers}/gqa_1/layer/attn/attn.core/attn._attend/pallas_call") == (
            "attn.core", "", "forward")
    assert scope_of(f"{layers}/swa_0/layer/attn/head_gate_proj/dot_general"
                    ) == ("attn.proj", "", "forward")
    # a path that starts anew at its innermost scope: the core's
    assert scope_of("window/pallas_call") == ("attn.core", "window", "forward")
