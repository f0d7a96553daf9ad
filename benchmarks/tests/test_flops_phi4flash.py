"""The Phi-4-mini-flash family's counts of operations and bytes on shapes
worked by hand, what the configuration file holds against the catalog's row,
what the step's program holds at the cell's sizes, and the readers of the
seven new metrics on a made-up table of scopes and made-up records."""

import json
import os
import types

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "phi4flash")
CONFIG = read_json(HERE, "configs", "phi4miniflash_l8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "phi4miniflash_l8.steady"
REDUCED = {"num_hidden_layers", "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("ssm_scan_ms_per_step", "ssm_scan_roofline_pct",
       "diff_attn_ms_per_step", "diff_attn_roofline_pct", "gmu_ms_per_step",
       "ssm_core_step_share_pct", "ssm_decay_p50")


def test_phi4flash_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # every published width as it is
    for key, width in (
            ("hidden_size", 2560), ("intermediate_size", 10240),
            ("num_attention_heads", 40), ("num_key_value_heads", 20),
            ("sliding_window", 512), ("mb_per_layer", 2)):
        assert CONFIG[key] == row["config"][key] == width
    assert CONFIG["tie_word_embeddings"] is True
    # and the family's own defaults, listed as assumed
    assert {key: CONFIG["assumed"][key] for key in (
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")
        } == {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
              "mamba_dt_rank": 160}
    m = family.sizes(CONFIG, False)
    assert (m["head_dim"], m["d_inner"]) == (64, 5120)
    assert "8 chips share the vocabulary" in CONFIG["deployment"]
    assert {"head_dim", "attention_bias", "sub_norm", "lambda_init",
            "initialisers", "no_positions", "state"} <= set(
                CONFIG["assumed"])
    # the floors: a multiple of 4 with every kind of layer, an eighth of
    # the rows
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi4miniflash_l8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"mfu_pct", "step_ms", "scope_unnamed_pct",
                       "attn_core_scope_ms_per_step"} <= listed
    assert not any(name.startswith(("fa2_", "kda_", "swa_", "full_", "mla_",
                                    "moe_")) for name in listed)
    for name in NEW:        # the one cell that runs these layers
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s"
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "tokens_per_s"]
    assert CELL in rate["workloads"]


def test_phi4flash_program_holds_what_the_file_says():
    """915,311,616 parameters, by kind of layer (the issue's table)."""
    swiglu = 2560 * 20480 + 10240 * 2560
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    attention = (2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128)
    gmu = 2 * 2560 * 5120
    cross = 2560 * 2560 + 2560 + 2560 * 2560 + 2560 + 4 * 64 + 128
    assert (swiglu, mamba, attention, gmu, cross) == (
        78_643_200, 41_241_600, 19_668_864, 26_214_400, 13_112_704)
    whole = (3 * mamba + 3 * attention + gmu + cross + 8 * swiglu
             + 8 * 10240 + 25008 * 2560 + 5120)
    assert whole == CONFIG["parameters"] == 915_311_616
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert cfg.layer_pattern == ("mamba", "swa") and cfg.periods == 2
    assert cfg.memory_layers == ("mamba", "gqa")
    assert cfg.cross_pattern == ("gmu", "xattn") and cfg.cross_periods == 1
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.sliding_window) == (40, 20, 64, 512)
    assert (cfg.mamba_state, cfg.mamba_conv, cfg.mamba_expand,
            cfg.mamba_dt_rank) == (16, 4, 2, 160)
    assert cfg.diff_attention and cfg.attention_bias and cfg.tie_embeddings
    assert cfg.norm == "layer" and not cfg.use_rope
    assert cfg.attention_impl == "flash" and cfg.rms_norm_eps == 1e-5
    # the family's own rule and the program's give one layout
    names = {"mamba": "mamba", "gmu": "gmu", "cross": "xattn"}
    assert list(cfg.layer_kinds()) == [
        names.get(kind, "swa" if window else "gqa")
        for kind, window in family.kinds_of(family.sizes(CONFIG, False))]
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "tie_word_embeddings": False}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 19)


def test_phi4flash_checkout_without_the_fields_is_refused_with_a_sentence(
        monkeypatch):
    """What the parent commit says when asked for the cell: at once, before
    any state is made."""
    import dataclasses

    from dlrover_tpu.models import llama

    real = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda cls: [
        f for f in real(cls) if f.name != "mamba_state"])
    with pytest.raises(RuntimeError, match="no selective scan"):
        family.build(CONFIG, False, SEQ)
    assert "mamba_state" in {f.name for f in real(llama.LlamaConfig)}


def test_phi4flash_matmul_params_and_flops_by_hand():
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * 5120 + 2560 * 2560
    gmu, cross = 2 * 2560 * 5120, 2 * 2560 * 2560
    want = (3 * mamba + 3 * attention + gmu + cross
            + 8 * 3 * 2560 * 10240 + 2560 * 25008)
    assert family.matmul_params(CONFIG) == want == 914_841_600
    scan = family.scan_shape(CONFIG, 1, SEQ)
    assert scan == {"batch": 1, "seq": 16384, "channels": 5120, "state": 16,
                    "layers": 3}
    # 1.34 G updates a layer, seven operations forward, three passes' worth
    assert family.scan_step_flops(scan) == 3 * 7 * 3 * 16384 * 5120 * 16
    assert family.scan_step_bytes(scan) == 3 * 2 * (
        11 * 16384 * 5120 + 6 * 16384 * 16)
    diff = family.diff_shape(CONFIG, 1, SEQ)
    assert diff == {"batch": 1, "seq": 16384, "pairs": 20, "kv_pairs": 10,
                    "head_dim": 64, "window": 512, "window_layers": 2,
                    "causal_layers": 2, "cross_layers": 1}
    band = 16384 * 512 - 512 * 511 // 2
    causal = 16384 * 16385 // 2
    assert family.allowed_pairs(16384, 512) == band
    assert family.allowed_pairs(16384) == causal
    # a pair of positions and of heads: two maps of 64, one value of 128
    assert family.diff_step_flops(diff) == 3 * 2 * (128 + 256) * 20 * (
        2 * band + 2 * causal)
    rows = 16384 * 128
    assert family.diff_step_bytes(diff) == 4 * 2 * (
        6 * 20 * rows + 6 * 10 * rows)
    assert family.flops_per_token(CONFIG, SEQ) == pytest.approx(
        6 * want + (family.diff_step_flops(diff)
                    + family.scan_step_flops(scan)) / SEQ)
    # at the tiny sizes too (the rehearsal's mfu is no device number)
    assert family.matmul_params(CONFIG, True) > 0
    assert family.fa2_shape(CONFIG, 1, SEQ) is None


def _observed(rows, busy_ms=1000.0):
    table = {"steps": 2, "period_ms": 1010.0, "busy_ms": busy_ms,
             "union_ms": busy_ms, "unnamed_ms": 1.0, "unnamed_before_ms": 2.0,
             "unmatched": 0,
             "rows": {key: [ms, 1.0, 0.0] for key, ms in rows.items()}}
    return {"family": family, "config": CONFIG, "batch": 1, "seq": SEQ,
            "chips": 1, "peaks": PEAKS, "values": {},
            # a table an earlier reader of the run left: none is made anew
            "trace_loaded": trace.Trace(
                device_ops={0: [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]},
                host_spans=[], seen={}),
            "device_scopes": table}


def test_phi4flash_readers_on_a_made_up_table():
    rows = {("attn.core", "scan", "forward"): 30.0,
            ("attn.core", "scan", "backward"): 90.0,
            ("attn.core", "conv", "forward"): 4.0,
            ("attn.core", "decay", "remat"): 3.0,
            ("attn.core", "gate", "backward"): 3.0,
            ("attn.core", "diff", "forward"): 60.0,
            ("attn.core", "diff", "remat"): 60.0,
            ("attn.core", "diff", "backward"): 140.0,
            ("attn.core", "gmu", "backward"): 10.0,
            ("attn.core", "window", "forward"): 999.0,   # another family's
            ("attn.proj", "", "forward"): 80.0,
            ("mlp", "", "forward"): 300.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("ssm_scan_ms_per_step") == pytest.approx(120.0)
    assert read("diff_attn_ms_per_step") == pytest.approx(260.0)
    assert read("gmu_ms_per_step") == pytest.approx(10.0)
    assert read("ssm_core_step_share_pct") == pytest.approx(40.0)
    scan = family.scan_shape(CONFIG, 1, SEQ)
    least = family.scan_step_bytes(scan) / 819e9      # the memory binds it
    assert least > family.scan_step_flops(scan) / 197e12
    assert read("ssm_scan_roofline_pct") == pytest.approx(100 * least / 0.120)
    assert 5 < read("ssm_scan_roofline_pct") < 6
    diff = family.diff_shape(CONFIG, 1, SEQ)
    least = family.diff_step_flops(diff) / 197e12
    assert read("diff_attn_roofline_pct") == pytest.approx(
        100 * least / 0.260)
    assert 25 < read("diff_attn_roofline_pct") < 26


def _span(step, **attrs):
    return types.SimpleNamespace(
        name="trainer.model_stats", start_ns=step, attrs={
            "step": step, **attrs})


def test_phi4flash_decay_reads_the_programs_counter(monkeypatch, capsys):
    reader = load_module("layer_metrics", "ssm_decay_p50")
    records = [(10, [0.5, 0.6, 0.7]), (12, [0.4, 0.6, 0.8])]
    sown = {"ssm_decay_p50": records,
            "diff_lambda": [(10, [0.3] * 4), (12, [0.3] * 4)],
            "memory_readers": [(10, [2]), (12, [2])]}
    monkeypatch.setattr(reader.program_spans, "model_stats",
                        lambda observed, name: sown.get(name, []))
    assert reader.read({}) == pytest.approx(0.6)    # the last record's mean
    said = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert said["phase"] == "ssm_scan" and len(said["records"]) == 2
    assert said["records"][0]["memory_readers"] == [2]
    monkeypatch.setattr(reader.program_spans, "model_stats",
                        lambda observed, name: [])
    assert reader.read({}) is None                  # a model that sows none


def test_phi4flash_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (a table with no such row, or no table
    at all), a run without a trace: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("attn.core", "window", "forward"): 8.0,
                         ("mlp", "", "forward"): 30.0})
    for name in NEW[:-1]:
        reader = load_module("layer_metrics", name)
        for observed in (no_rows, {**no_rows, "device_scopes": None},
                         {**no_rows, "trace_loaded": None,
                          "device_scopes": None}):
            assert reader.read(observed) is None, name
