"""The EvaByte family's counts of operations and bytes on shapes worked by
hand, what the configuration file holds against the catalog's row, what the
step's program holds at the cell's sizes, and the readers of the attention's
metrics on a made-up trace."""

import json
import os

import pytest

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "evabyte")
CONFIG = read_json(HERE, "configs", "evabyte_l4.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_evabyte_file_keeps_every_published_key_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in CONFIG or CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    assert "chips that share a layer: 1" in CONFIG["deployment"]
    assert "input_ids shifted" in CONFIG["departure"]
    assert {"pooling", "pooling_vectors_init", "head_blocks",
            "multi_byte_loss"} <= set(CONFIG["assumed"])
    assert CONFIG["num_hidden_layers"] >= 4         # the floor
    bench = read_json(os.path.dirname(HERE), "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "evabyte_l4")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]


def test_evabyte_program_holds_what_the_file_says():
    """821.4 M parameters at 4 layers (the file's note), two pooling vectors
    a head and eight head blocks among them."""
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 67_108_864 + 135_266_304 + 16_384
    whole = 4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096
    assert whole == 821_366_784
    model = family.build(CONFIG, False, 16384)
    assert model.num_params() == whole
    cfg = model.config
    assert (cfg.eva_window, cfg.eva_chunk, cfg.pred_heads) == (2048, 16, 8)
    assert cfg.norm_unit_offset and cfg.residual_dtype.__name__ == "float32"
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(CONFIG, False, 65536)
    with pytest.raises(ValueError, match="the program runs only"):
        family.build({**CONFIG, "attention_class": "softmax"}, False, 16384)


def test_evabyte_pairs_and_flops_by_hand():
    # the cell: 8 windows; 30% of the pairs a layer attends are summaries
    assert family.exact_pairs(16384, 2048) == 8 * 2048 * 2049 // 2 == 16_785_408
    assert family.summary_pairs(16384, 2048, 16) == 2048 * 128 * 28 == 7_340_032
    assert 16_785_408 + 7_340_032 == 24_125_440
    assert 16384 * 16385 // 2 == 134_225_920
    # one window is causal attention: no summary
    assert family.summary_pairs(2048, 2048, 16) == 0
    assert family.exact_pairs(1024, 2048) == 1024 * 1025 // 2
    # 8 queries, windows of 4, chunks of 2: (1+2+3+4) twice; the second
    # window's 4 queries each see the first's 2 summaries
    assert family.exact_pairs(8, 4) == 20 and family.summary_pairs(8, 4, 2) == 8
    shape = {"batch": 2, "seq": 8, "window": 4, "chunk": 2, "windows": 2,
             "heads": 3, "head_dim": 5, "layers": 7}
    attention = 6 * 2 * 3 * 5 * (20 + 8)
    pooling = 3 * 2 * (2 * 8 * 3 * 5 + 2 * 8 * 3 * 5)
    assert family.eva_attn_step_flops(shape) == 7 * 2 * (attention + pooling)
    one = 2 * 8 * 3 * 5
    assert family.eva_attn_step_bytes(shape) == 7 * 2 * (4 * one + 8 * one)
    cell = family.eva_attn_shape(CONFIG, 1, 16384)
    assert cell == {"batch": 1, "seq": 16384, "window": 2048, "chunk": 16,
                    "windows": 8, "heads": 32, "head_dim": 128, "layers": 4}
    # compute-bound on a v5e
    assert family.eva_attn_step_flops(cell) / 197e12 > (
        family.eva_attn_step_bytes(cell) / 819e9)


def test_evabyte_flops_per_token_are_the_issues_formula():
    matmul = 4 * (67_108_864 + 135_266_304) + 4096 * 2560
    assert family.matmul_params(CONFIG) == matmul == 819_986_432
    per_token = 6 * matmul + 12 * 4 * 4096 * 24_125_440 / 16384
    assert family.flops_per_token(CONFIG, 16384) == per_token
    assert 5.2e9 < per_token < 5.25e9
    # the attention's share of the step's operations
    assert 0.05 < (per_token - 6 * matmul) / per_token < 0.06


def test_evabyte_step_holds_nothing_seq_by_seq():
    """The model's forward and backward pass at the cell's sizes, lowered
    from shapes alone: no array has two dimensions of the whole sequence,
    and the largest block of scores is one window by its keys and the
    summaries before it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    model = family.build(CONFIG, False, 16384)
    ids = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16384), jnp.int32))
    params = nn.meta.unbox(shapes["params"])

    def loss(p, i):
        logits, sown = model.apply({"params": p}, i, mutable=["losses"])
        return logits.astype(jnp.float32).mean() + sum(
            jnp.sum(t) for t in jax.tree.leaves(sown["losses"]))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    assert "16384x16384" not in text
    assert "32x2048x2944xf32" in text and "2048x2048xi1" in text


def _observed(ops):
    return {
        "family": family, "config": CONFIG, "batch": 1, "seq": 16384,
        "chips": 1, "peaks": PEAKS, "values": {},
        "trace_loaded": trace.Trace(
            device_ops={0: ops},
            host_spans=[("bench.window", 0.0, 100.0)], seen={}),
    }


LAYERS = ("%while.15 = (s32[], f32[1,16384,4096]{1,2,0}, f32[4,1,16384,4096]) "
          "while(%tuple.495), condition=%c0, body=%b0")
SCORES = ("%fusion.9 = f32[32,2048,2944]{2,1,0} fusion(bf16[1,2048,32,128]"
          "{3,2,1,0} %q, bf16[1,2944,32,128]{3,2,1,0} %keys), kind=kOutput")
FIRST = ("%fusion.3 = bf16[32,2048,2048]{2,1,0} fusion(f32[32,2048,2048]"
         "{2,1,0} %exp, f32[32,2048]{1,0} %sum), kind=kLoop")
KEYS = ("%fusion.2449 = bf16[1,2176,32,128]{1,3,2,0} fusion(bf16[1,16384,32,"
        "128]{3,2,1,0} %k, bf16[1,1024,32,128]{3,2,1,0} %pooled), kind=kLoop")
ROWS = ("%copy.7 = bf16[1,2048,32,128]{1,3,2,0} copy(bf16[1,2048,32,128]"
        "{3,2,1,0} %slice.4)")
POOL = ("%fusion.77 = f32[1,1024,16,32]{3,2,1,0} fusion(bf16[1,1024,16,32,128]"
        "{4,3,2,1,0} %k, bf16[32,128]{1,0} %mu), kind=kLoop")
MLP = "%fusion.1 = bf16[16384,11008]{1,0} fusion(bf16[1,16384,4096]{2,1,0} %h)"
HEAD = ("%fusion.988 = (bf16[4096]{0}, f32[16384]{0}, bf16[16384,4096]{0,1}) "
        "fusion(f32[16384,2560]{1,0} %dlogits, bf16[4096,2560]{1,0} %w)")
ROPE = ("%fusion.5 = bf16[1,16384,32,128]{3,2,1,0} fusion(bf16[1,16384,32,128]"
        "{3,2,1,0} %q, f32[16384,64]{1,0} %cos)")


def test_evabyte_readers_on_a_made_up_trace():
    eva = load_module("layer_metrics", "eva_attn_ms_per_step")
    shape = eva.shape_of(_observed([]))
    for text, window, pool in (
            (SCORES, True, False), (FIRST, True, False), (KEYS, True, False),
            (ROWS, False, False), (POOL, False, True), (MLP, False, False),
            (ROPE, False, False), (HEAD, False, False),
            (LAYERS, False, False)):
        assert eva.is_window_op(text, shape) is window, text
        assert eva.is_pool_op(text, shape) is pool, text
    # three runs of the layer loop start at 10, 20 and 30 s: two whole steps
    # from 10 to 30.  In each a window's scores 3 ms, its keys 1 ms inside
    # them (one union), the pooling 2 ms, the MLP 5 ms; what lies before
    # the first start is left out
    ops = [(LAYERS, 10.0 * i, 10.0 * i + 6.0) for i in (1, 2, 3)]
    ops += [(SCORES, 5.0, 5.003), (POOL, 6.0, 6.002)]
    for at in (11.0, 21.0):
        ops += [(SCORES, at, at + 0.003), (KEYS, at + 0.001, at + 0.002),
                (POOL, at + 1, at + 1.002), (MLP, at + 2, at + 2.005)]
    assert eva.whole_steps(ops) == (10.0, 30.0, 2)
    observed = _observed(ops)
    read = lambda name: load_module("layer_metrics", name).read(observed)  # noqa: E731
    assert read("eva_attn_ms_per_step") == pytest.approx(5.0)
    assert read("eva_pool_ms_per_step") == pytest.approx(2.0)
    least = family.eva_attn_step_flops(shape) / 197e12
    assert read("eva_attn_roofline_pct") == pytest.approx(100 * least / 0.005)


def test_evabyte_readers_return_nothing_where_there_is_nothing():
    """A trace with one run of the layer loop has no whole step, another
    family has no such shape, a run without a trace has no operations, a
    program that sows no counter leaves no record: ``None``, never an error
    (the parent commit is measured with these readers too)."""
    names = ["eva_attn_ms_per_step", "eva_pool_ms_per_step",
             "eva_attn_roofline_pct", "eva_summary_mass_share"]
    other = load_module("families", "llama")
    for observed in (_observed([(LAYERS, 1.0, 2.0), (SCORES, 1.1, 1.2)]),
                     _observed([(MLP, 1.0, 2.0), (MLP, 3.0, 4.0)]),
                     {**_observed([(LAYERS, 1.0, 2.0), (LAYERS, 3.0, 4.0),
                                   (SCORES, 1.1, 1.2)]), "family": other},
                     {**_observed([]), "trace_loaded": None}):
        for name in names:
            assert load_module("layer_metrics", name).read(observed) is None
