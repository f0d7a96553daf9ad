"""The Keye-VL-2.0 family's counts of operations and bytes on shapes worked
by hand, what the configuration file holds against the catalog's row, and
the readers of the sparse attention's and the share's metrics on a made-up
trace."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "keyevl")
CONFIG = read_json(HERE, "configs", "keyevl2_30b_1of8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_keyevl_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["published"] == {
        k: row["config"][k] for k in CONFIG["reduced"]}
    assert "8 chips share each layer" in CONFIG["deployment"]
    # floors: four layers, 8 experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] >= 4 and CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["config"]["vocab_size"]


def test_keyevl_program_holds_what_the_file_says():
    """659.2 M parameters a chip at 6 layers (the file's note)."""
    attn = 2048 * 128 * (2 * 32 + 2 * 4) + 2 * 128
    indexer = 2048 * (16 * 64 + 64 + 16) + 2 * 64
    layer = attn + indexer + 2048 * 128 + 2 * 2048 + 16 * 3 * 2048 * 768
    per_chip = 6 * layer + 2 * 18992 * 2048 + 2048
    assert per_chip == 659_190_016
    model = family.build(CONFIG, False, 8192)
    assert model.num_params() == per_chip
    assert model.config.experts_held == 16 and model.config.num_experts == 128


def test_keyevl_pairs_and_flops_by_hand():
    assert family.causal_pairs(8192) == 33_558_528
    assert family.kept_pairs(8192, 2048) == 14_681_088
    assert family.kept_pairs(1024, 2048) == family.causal_pairs(1024)
    # 4 queries keeping at most 2 keys: 1 + 2 + 2 + 2
    assert family.kept_pairs(4, 2) == 7
    shape = {"batch": 1, "seq": 4, "topk": 2, "heads": 3, "head_dim": 5,
             "kv_heads": 1, "index_heads": 2, "index_dim": 3, "layers": 1}
    index = 2 * 2 * (3 + 1) * (10 + 2 * 7)
    attention = 6 * 2 * 3 * 5 * 7
    assert family.sparse_attn_step_flops(shape) == index + attention
    rows = 4
    forward = rows * (3 * 5 + 2 * 5 + (2 * 4 + 3) + 3 * 5)
    backward = forward + rows * (3 * 5 + 2 * 5 + (2 * 4 + 3))
    assert family.sparse_attn_step_bytes(shape) == 2 * (forward + backward)
    cell = family.sparse_attn_shape(CONFIG, 1, 8192)
    assert cell["select_loops_per_step"] == 2 * 2 * 12 * 6
    # compute-bound on a v5e
    assert family.sparse_attn_step_flops(cell) / 197e12 > (
        family.sparse_attn_step_bytes(cell) / 819e9)


def test_keyevl_matmul_params_are_what_a_token_meets_here():
    attn = 2048 * 128 * (2 * 32 + 2 * 4)
    indexer = 2048 * (16 * 65 + 64)
    one_expert = 3 * 2048 * 768          # 8 a token x 16 held / 128
    assert family.matmul_params(CONFIG) == 6 * (
        attn + indexer + 2048 * 128 + one_expert) + 2048 * 18992
    per_token = 6 * family.matmul_params(CONFIG) + (
        family.sparse_attn_step_flops(
            family.sparse_attn_shape(CONFIG, 1, 8192)) / 8192)
    assert family.flops_per_token(CONFIG, 8192) == per_token


def test_keyevl_gmm_counts():
    cell = family.gmm_shape(CONFIG, 8192)
    assert cell == {"rows": 8192, "experts": 16, "hidden": 2048, "width": 768,
                    "layers": 6, "extents": (10240, 12288, 16384, 65536)}
    assert family.gmm_step_flops(cell) == 3 * 6 * 3 * 2 * 8192 * 2048 * 768


def _observed(ops):
    return {
        "family": family, "config": CONFIG, "batch": 1, "seq": 8192,
        "chips": 1, "peaks": PEAKS,
        "trace_loaded": trace.Trace(
            device_ops={0: ops},
            host_spans=[("bench.window", 0.0, 100.0)], seen={}),
    }


SEARCH = ("%while.7 = (s32[], u32[1,512]{1,0}, u32[1,512,2560]{2,1,0:T(8,128)"
          "S(1)}, s32[]) while(%tuple.3), condition=%c, body=%b")
SCORES = ("%fusion.9 = f32[1,4,8,512,2560]{4,3,2,1,0} fusion(bf16[1,512,4,8,"
          "128]{4,3,2,1,0} %q, bf16[1,2560,4,128]{3,2,1,0} %k), kind=kOutput")
GRADIENT = ("%fusion.12 = bf16[1,2560,4,128]{3,2,1,0} fusion(bf16[1,4,8,512,"
            "2560]{4,3,2,1,0} %p, bf16[1,512,4,8,128]{4,3,2,1,0} %g)")
GMM = ('%ragged-dot-none.4 = bf16[10240,768]{1,0} custom-call(bf16[10240,2048]'
       '{1,0} %rows, bf16[16,2048,768]{2,1,0} %w), '
       'custom_call_target="tpu_custom_call"')
SORTED = "%fusion.20 = bf16[10240,2048]{1,0} fusion(bf16[8192,2048]{1,0} %x)"
LAYERS = ("%while.1 = (s32[], bf16[1,8192,2048]{2,1,0}, f32[6,2048,32,128]) "
          "while(%t), condition=%c0, body=%b0")
OTHER = "%fusion.1 = bf16[1,8192,2048]{2,1,0} fusion(bf16[1,8192,2048] %x)"


def test_keyevl_readers_on_a_made_up_trace():
    sparse = load_module("layer_metrics", "sparse_attn_ms_per_step")
    shape = sparse.shape_of(_observed([]))
    assert sparse.select_loop(SEARCH, shape)
    assert not sparse.select_loop(LAYERS, shape)
    for text, mine in ((SEARCH, True), (SCORES, True), (GRADIENT, True),
                       (GMM, False), (SORTED, False), (LAYERS, False),
                       (OTHER, False)):
        assert sparse.is_sparse_attn_op(text, shape) is mine, text
    # half a step: 144 of the 288 searches a step, 1 ms each, nested in the
    # layer loop; one block's scores and a gradient, 3 ms and 2 ms; the
    # share's grouped matmul and sorted rows, 4 ms and 1 ms
    ops = [(LAYERS, 0.0, 90.0)]
    ops += [(SEARCH, 1.0 + 0.01 * i, 1.001 + 0.01 * i) for i in range(144)]
    ops += [(SCORES, 10.0, 10.003), (GRADIENT, 11.0, 11.002),
            (GMM, 12.0, 12.004), (SORTED, 13.0, 13.001), (OTHER, 14.0, 14.5)]
    observed = _observed(ops)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("index_select_ms_per_step") == pytest.approx(144 / 0.5)
    assert read("sparse_attn_ms_per_step") == pytest.approx((144 + 5) / 0.5)
    assert read("moe_share_ms_per_step") == pytest.approx(5 / 0.5)
    assert read("moe_share_gmm_ms_per_step") == pytest.approx(4 / 0.5)
    least = family.sparse_attn_step_flops(shape) / 197e12
    assert read("sparse_attn_roofline_pct") == pytest.approx(
        100 * least / (0.298))
    assert read("moe_share_gmm_roofline_pct") == pytest.approx(
        100 * family.gmm_step_flops(family.gmm_shape(CONFIG, 8192)) / 197e12
        / 0.008)


def test_keyevl_readers_return_nothing_where_there_is_nothing():
    """A program without the indexer runs no search, another family has no
    such shape, a run without a trace has no operations: ``None``, never
    an error (the parent commit is measured with these readers too)."""
    names = ["sparse_attn_ms_per_step", "index_select_ms_per_step",
             "sparse_attn_roofline_pct", "moe_share_ms_per_step",
             "moe_share_gmm_ms_per_step", "moe_share_gmm_roofline_pct",
             "index_low_margin_share", "moe_share_rows_over_expected"]
    other = load_module("families", "llama")
    for observed in (_observed([(OTHER, 1.0, 2.0), (GMM, 3.0, 4.0)]),
                     {**_observed([(SEARCH, 1.0, 2.0)]), "family": other},
                     {**_observed([]), "trace_loaded": None}):
        observed.setdefault("values", {})
        for name in names:
            assert load_module("layer_metrics", name).read(observed) is None
