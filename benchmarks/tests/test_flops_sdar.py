"""The SDAR family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what
the program holds at the cell's sizes, and the readers of the
block-diffusion metrics on a made-up table of scopes."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "sdar")
CONFIG = read_json(HERE, "configs", "sdar_30b_1of8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "sdar_30b_1of8.steady"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
SEQ = CONFIG["run"]["seq"]
NEW = ("bd_attn_ms_per_step", "bd_attn_roofline_pct", "bd_noise_ms_per_step",
       "bd_masked_share")


def test_sdar_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    # every published width as it is
    for key, width in (("hidden_size", 2048), ("head_dim", 128),
                       ("moe_intermediate_size", 768),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 4),
                       ("num_experts_per_tok", 8)):
        assert CONFIG[key] == row["config"][key] == width
    assert "8 chips share each layer" in CONFIG["deployment"]
    # what the catalog lists as not given is assumed, with its source
    assert row["not_given"] == ["block length", "noise schedule"]
    assert {"block_length", "noise_schedule", "target", "mask_token_id",
            "qk_norm", "router_aux_loss_coef", "state"} <= set(
                CONFIG["assumed"])
    assert CONFIG["assumed"]["block_length"] == 4
    assert CONFIG["assumed"]["mask_token_id"] == CONFIG["vocab_size"] - 1
    # the floors: four layers, 8 routed experts, an eighth of the rows
    assert CONFIG["num_hidden_layers"] >= 4 and CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "sdar_30b_1of8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == row["source_url"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady"
    # the cell reports tokens_per_s and the new readers, and no FA2 metric
    # (no FA2 call) nor step_p95_ms (under 100 samples)
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in bench[key] if CELL in m.get("workloads", ())}
    assert {"tokens_per_s", "mfu_pct", "scope_unnamed_pct", *NEW} <= listed
    assert not {n for n in listed if n.startswith("fa2_")}
    assert "step_p95_ms" not in listed
    for name in NEW:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s"


def test_sdar_program_holds_what_the_file_says():
    """645.6 M parameters (the file's notes)."""
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    layer = attn + 2048 * 128 + 16 * 3 * 2048 * 768 + 2 * 2048
    whole = 6 * layer + 2 * 18992 * 2048 + 2048
    assert whole == 645_623_296
    model = family.build(CONFIG, False, SEQ)
    assert model.num_params() == whole
    cfg = model.config
    assert (cfg.block_diffusion, cfg.mask_token_id) == (4, 18991)
    assert cfg.noise_eps == 1e-3 and cfg.own_objective
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held) == (128, 8, 16)
    assert cfg.qk_norm == "head" and cfg.norm_topk_prob
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "norm_topk_prob": False}, False, SEQ)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 2 ** 16)
    with pytest.raises(ValueError, match="last row"):
        family.build({**CONFIG, "assumed": {
            **CONFIG["assumed"], "mask_token_id": 7}}, False, SEQ)


def test_sdar_pairs_and_flops_by_hand():
    # two blocks of 2: clean 1 + 2 blocks of 4 pairs, noisy 1 earlier block
    # of 4 and 2 own blocks of 4
    assert family.allowed_pairs(4, 2) == 12 + 4 + 8 == 4 * 4 + 2 * 4
    assert family.allowed_pairs(SEQ, 4) == 67_141_632
    shape = family.bd_attn_shape(CONFIG, 1, SEQ)
    assert shape == {"batch": 1, "seq": SEQ, "rows": 2 * SEQ, "block": 4,
                     "heads": 32, "kv_heads": 4, "head_dim": 128,
                     "layers": 6}
    # two products forward, four backward, a multiply-add two operations
    assert family.bd_attn_step_flops(shape) == (
        6 * 6 * 2 * 32 * 128 * 67_141_632)
    small = {"batch": 2, "seq": 5, "rows": 10, "block": 1, "heads": 3,
             "kv_heads": 1, "head_dim": 4, "layers": 7}
    qo, kv = 2 * 10 * 3 * 4, 2 * 2 * 10 * 1 * 4
    assert family.bd_attn_step_bytes(small) == 7 * 2 * (
        (qo + kv + qo) + (qo + kv + qo + qo) + (qo + kv))
    # compute-bound on a v5e
    assert family.bd_attn_step_flops(shape) / 197e12 > (
        family.bd_attn_step_bytes(shape) / 819e9)


def test_sdar_matmul_params_count_two_rows_a_data_token():
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    # router, and one expert of the 16 held: 8 a row x 16 / 128
    layer = attn + 2048 * 128 + 3 * 2048 * 768
    m = family.sizes(CONFIG, False)
    assert family.layer_matmul_params(m) == layer == 23_855_104
    matmul = 2 * 6 * layer + 2048 * 18992
    assert family.matmul_params(CONFIG) == matmul == 325_156_864
    per_token = 6 * matmul + 6 * 6 * 2 * 32 * 128 * 67_141_632 / SEQ
    assert family.flops_per_token(CONFIG, SEQ) == per_token
    # 35.8 TFLOP a step; the attention under the mask over half of it
    step = per_token * SEQ
    assert 35.7e12 < step < 35.9e12
    assert family.bd_attn_step_flops(
        family.bd_attn_shape(CONFIG, 1, SEQ)) > 0.5 * step


def _observed(rows, stats=None):
    table = {"steps": 2, "period_ms": 600.0, "busy_ms": 599.0,
             "union_ms": 599.0, "unnamed_ms": 1.0, "unnamed_before_ms": 2.0,
             "unmatched": 0,
             "rows": {key: [ms, 1.0, 0.0] for key, ms in rows.items()}}
    return {"family": family, "config": CONFIG, "batch": 1, "seq": SEQ,
            "chips": 1, "peaks": PEAKS, "values": {},
            # a table an earlier reader of the run left: none is made anew
            "trace_loaded": trace.Trace(
                device_ops={0: [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]},
                host_spans=[], seen={}),
            "device_scopes": table}


def test_sdar_readers_on_a_made_up_table():
    rows = {("attn.core", "bd_clean", "forward"): 40.0,
            ("attn.core", "bd_clean", "remat"): 40.0,
            ("attn.core", "bd_clean", "backward"): 50.0,
            ("attn.core", "bd_noisy", "forward"): 45.0,
            ("attn.core", "bd_noisy", "backward"): 55.0,
            ("attn.core", "bd_keys", "forward"): 6.0,
            ("attn.core", "bd_keys", "backward"): 4.0,
            ("embed", "noise", "forward"): 0.25,
            # the slices between the blocks and another model's parts: not
            # this attention's
            ("attn.core", "", "forward"): 8.0,
            ("attn.core", "selected", "forward"): 50.0,
            ("embed", "", "forward"): 2.0,
            ("moe", "gmm", "forward"): 70.0}
    observed = _observed(rows)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("bd_attn_ms_per_step") == pytest.approx(240.0)
    assert read("bd_noise_ms_per_step") == pytest.approx(0.25)
    shape = family.bd_attn_shape(CONFIG, 1, SEQ)
    least = family.bd_attn_step_flops(shape) / 197e12
    assert read("bd_attn_roofline_pct") == pytest.approx(100 * least / 0.240)
    assert 30 < read("bd_attn_roofline_pct") < 100


def test_sdar_readers_return_nothing_where_there_is_nothing():
    """A program without the scopes (the parent commit: no table at all, or
    a table with no such row), another family, a run without a trace, a
    program that sows no counter: ``None``, never an error (the parent
    commit is measured with these readers too)."""
    other = load_module("families", "llama")
    no_rows = _observed({("attn.core", "", "forward"): 8.0,
                         ("embed", "", "forward"): 2.0,
                         ("mlp", "", "forward"): 30.0})
    for observed in (no_rows, {**no_rows, "device_scopes": None},
                     {**no_rows, "family": other},
                     {**no_rows, "trace_loaded": None,
                      "device_scopes": None}):
        for name in NEW:
            assert load_module("layer_metrics", name).read(observed) is None


def test_sdar_scopes_are_the_programs_table():
    """The readers' sub-scopes are the ones the program's kind table has
    for this attention and for the noise, and the path of each resolves to
    them."""
    from dlrover_tpu.observability import trace as program_trace

    reader = load_module("layer_metrics", "bd_attn_ms_per_step")
    assert set(reader.SUB_SCOPES) <= set(program_trace.SUB_SCOPES[reader.KIND])
    assert "noise" in program_trace.SUB_SCOPES["embed"]
    layer = "jit(step)/jvp(LlamaForCausalLM)/layers/while/body/layer"
    core = f"{layer}/attn/attn.core/jit(_block_diffusion_block)"
    assert program_trace.scope_of(f"{core}/bd_keys/concatenate") == (
        "attn.core", "bd_keys", "forward")
    assert program_trace.scope_of(f"{core}/bd_noisy/pallas_call") == (
        "attn.core", "bd_noisy", "forward")
    assert program_trace.scope_of(
        "jit(step)/transpose(jvp(LlamaForCausalLM))/layers/layer/attn/"
        "attn.core/jit(_block_diffusion_block)/bd_clean/pallas_call") == (
            "attn.core", "bd_clean", "backward")
    assert program_trace.scope_of(
        "jit(step)/jvp(LlamaForCausalLM)/embed/noise/jit(_uniform)/"
        "threefry2x32") == ("embed", "noise", "forward")
    assert program_trace.scope_of(
        "jit(step)/jvp(LlamaForCausalLM)/embed/gather") == (
            "embed", "", "forward")
    assert program_trace.scope_of(f"{layer}/attn/q_proj/dot_general") == (
        "attn.proj", "", "forward")
