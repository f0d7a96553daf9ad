"""The Ouro family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what the
program's tree holds at the published widths, and the readers of the four
new metrics on a made-up table of scopes and made-up records."""

import dataclasses
import json
import os
import types

import pytest

from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "ouro")
CONFIG = read_json(HERE, "configs", "ouro2b6_l8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ouro2b6_l8.steady"
SEQ = CONFIG["run"]["seq"]
NEW = ("loop_remat_ms_per_step", "loop_exit_ms_per_step",
       "loop_head_ms_per_step", "loop_exit_entropy")
#: the published config.json's keys, as the catalog's row has them
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152, "layer_types": ["full_attention"] * 48}


def test_ouro_file_keeps_every_published_key_but_the_depth():
    differ = {k for k, v in PUBLISHED.items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["num_hidden_layers"] == 8          # the guide's floor: 4
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == CONFIG["source"]
    assert {"bias", "sandwich_norms", "final_norm", "exit_entropy_weight",
            "objective", "targets", "initialisers", "head_dim"} <= set(
                CONFIG["assumed"])
    assert "One stage of six" in CONFIG["deployment"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "ouro2b6_l8")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"mfu_pct", "step_ms", "scope_unnamed_pct",
                       "attn_core_scope_ms_per_step",
                       "head_loss_scope_ms_per_step"} <= listed
    # the one-call backward: the readers by arity find nothing to read.
    # ``full_attn_*`` read this cell as it is (``full_shape``; the cases
    # below), but ``test_flops_laguna.py`` holds their ``workloads`` to
    # Laguna's cell alone, and no file of the benchmark is edited here: a
    # ``benchmark`` PR lists the cell (PERF.md section 7)
    assert not any(name.startswith(("fa2_", "kda_", "swa_", "mla_", "moe_",
                                    "full_")) for name in listed)
    for name in NEW:
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL]
        assert (metric["moves"], metric["layer"]) == (
            "tokens_per_s", "trainer step")
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "tokens_per_s"]
    assert rate["workloads"][-1] == CELL


def test_ouro_counts_by_hand():
    m = family.sizes(CONFIG, False)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert family.layer_matmul_params(m) == layer == 51_380_224
    assert family.layer_applications(m) == 32
    # every layer, the head and the gate's column four times a step
    assert family.matmul_params(CONFIG) == 4 * (
        8 * layer + 2048 * 49152 + 2048) == 2_046_828_544
    attention = 6 * 32 * 2048 * SEQ
    assert family.flops_per_token(CONFIG, SEQ) == (
        6 * 2_046_828_544 + attention) == 18_723_422_208
    # 306.8 TFLOP a step, the four heads a fifth of the matmul work
    assert round(family.flops_per_token(CONFIG, SEQ) * SEQ / 1e12, 1) == 306.8
    assert round(4 * 2048 * 49152 / family.matmul_params(CONFIG), 3) == 0.197
    shape = family.full_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "heads": 16, "kv_heads": 16,
                     "head_dim": 128, "window": None, "layers": 32}
    pairs = SEQ * (SEQ + 1) // 2
    assert family.full_step_flops(shape) == 12 * 128 * pairs * 16 * 32
    rows = SEQ * 128 * 16
    assert family.full_step_bytes(shape) == 32 * 2 * (4 * rows + 8 * rows)


def test_ouro_tree_at_the_published_widths_holds_a_weight_once():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaForCausalLM

    model = family.build(CONFIG, False, SEQ)
    cfg = model.config
    assert (cfg.loop_steps, cfg.sandwich_norm, cfg.exit_gate,
            cfg.exit_entropy_weight, cfg.attention_impl) == (
                4, True, True, 0.05, "flash")
    assert model.num_params() == CONFIG["parameters"] == 612_438_017
    whole = LlamaForCausalLM(dataclasses.replace(cfg, num_layers=48))
    assert whole.num_params() == CONFIG["published"]["parameters"] == (
        2_667_974_657)
    shapes = jax.eval_shape(
        LlamaForCausalLM(dataclasses.replace(
            cfg, attention_impl="reference")).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes["params"])) == (
        CONFIG["parameters"])


def test_ouro_build_refuses_what_the_program_has_one_path_for():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        family.build({**CONFIG, "early_exit_threshold": 0.5}, False, SEQ)
    with pytest.raises(ValueError, match="full attention"):
        family.build({**CONFIG, "layer_types": ["sliding_attention"] * 48},
                     False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 * 65536)


def _observed(rows):
    from benchmarks import trace

    table = {"steps": 2, "period_ms": 2000.0, "unnamed_ms": 1.0,
             "unnamed_before_ms": 2.0, "unmatched": 0, "rows": rows,
             "busy_ms": sum(row[0] for row in rows.values())}
    return {"family": family, "config": CONFIG, "batch": 1, "seq": SEQ,
            "chips": 1, "values": {}, "attempted": 0,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            # a table an earlier reader of the run left: none is made anew
            "trace_loaded": trace.Trace(
                device_ops={0: [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]},
                host_spans=[], seen={}),
            "device_scopes": table}


ROWS = {("head_loss", "", "forward"): [60.0, 4, 0.0],
        ("head_loss", "", "remat"): [70.0, 4, 0.0],
        ("head_loss", "", "backward"): [150.0, 4, 0.0],
        ("head_loss", "exit", "forward"): [1.5, 8, 0.0],
        ("head_loss", "exit", "backward"): [2.5, 8, 0.0],
        ("mlp", "", "remat"): [300.0, 32, 0.0],
        ("attn.core", "", "forward"): [280.0, 32, 0.0],
        ("attn.core", "", "remat"): [280.0, 32, 0.0],
        ("attn.core", "", "backward"): [840.0, 32, 0.0]}


@pytest.mark.parametrize("name,want", [
    ("loop_remat_ms_per_step", 650.0), ("loop_exit_ms_per_step", 4.0),
    ("loop_head_ms_per_step", 280.0), ("full_attn_ms_per_step", 1400.0)])
def test_ouro_readers_by_scope(name, want):
    observed = _observed(ROWS)
    got = load_module("layer_metrics", name).read(observed)
    assert got == pytest.approx(want)


def test_ouro_core_roofline_counts_the_models_work_not_the_remat():
    observed = _observed(ROWS)
    share = load_module("layer_metrics", "full_attn_roofline_pct").read(
        observed)
    least = family.full_step_flops(family.full_shape(CONFIG, 1, SEQ)) / 197e12
    assert share == pytest.approx(100.0 * least / 1.4)
    assert 30 < share < 45


def test_ouro_readers_find_nothing_on_a_program_without_the_scopes():
    observed = _observed({("mlp", "", "forward"): [5.0, 2, 0.0]})
    for name in ("loop_remat_ms_per_step", "loop_exit_ms_per_step"):
        assert load_module("layer_metrics", name).read(observed) is None
    bare = types.SimpleNamespace()          # another family, no table
    assert load_module("layer_metrics", "loop_head_ms_per_step").read(
        {**observed, "family": bare}) is None
    assert load_module("layer_metrics", "loop_exit_entropy").read(
        {**observed, "family": bare, "attempted": 0}) is None


def test_ouro_entropy_reader_takes_the_forward_checks_reading(capfd):
    """A window of 51 s at 3 s a step ends before the trainer's second
    reading of its model's counters: the reader falls back on what the
    forward check of the run sowed."""
    seen = types.SimpleNamespace(SEEN={
        "loop_exit_entropy": [1.17], "loop_exit_mass_last": [0.14],
        "loop_ce_by_step": [11.3, 11.3, 11.3, 11.3]})
    observed = {**_observed(ROWS), "family": seen}
    assert load_module("layer_metrics", "loop_exit_entropy").read(
        observed) == 1.17
    line = next(json.loads(entry) for entry in
                capfd.readouterr().err.splitlines()
                if entry.startswith('{"phase": "loop_exits"'))
    assert line["read_at"] == "forward check"
    assert line["loop_exit_mass_last"] == [0.14]
