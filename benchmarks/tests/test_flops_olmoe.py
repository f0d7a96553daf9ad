"""The OLMoE family's counts of operations and bytes on shapes worked by
hand, and the readers of the expert layer's metrics on a made-up trace."""

from benchmarks import trace
from benchmarks.common import HERE, load_module, read_json

family = load_module("families", "olmoe")
CONFIG = read_json(HERE, "configs", "olmoe1b7b_ep4.json")


def test_matmul_params_are_the_active_ones():
    attn = 4 * 2048 * 2048                  # q, k, v, o: 16 heads of 128
    router = 2048 * 64
    experts = 8 * 3 * 2048 * 1024           # 8 of 64 experts, three matrices
    layers = CONFIG["num_hidden_layers"]
    assert family.matmul_params(CONFIG) == (
        layers * (attn + router + experts) + 2048 * 50304)
    # far under what the model holds: 64 experts a layer, and the table
    held = layers * (attn + router + 8 * experts) + 2 * 2048 * 50304
    assert family.matmul_params(CONFIG) < held / 2
    assert family.flops_per_token(CONFIG, 4096) == (
        6 * family.matmul_params(CONFIG) + 6 * layers * 2048 * 4096)


def test_the_program_holds_what_the_file_says():
    """911.5 M parameters a chip at 6 layers (the file's note)."""
    layers = CONFIG["num_hidden_layers"]
    experts = 64 * 3 * 2048 * 1024
    other = 4 * 2048 * 2048 + 2048 * 64 + 2 * 2048 + 2 * 2048
    table_and_head = 2 * 2048 * 50304 + 2048
    per_chip = layers * (experts // 4 + other) + table_and_head
    assert round(per_chip / 1e6, 1) == 911.5


def test_gmm_counts_by_hand():
    # 2 tokens x 2 experts a token on 1 chip: 4 rows; hidden 8, width 4,
    # 3 local experts, 1 layer
    shape = {"rows": 4, "experts": 3, "hidden": 8, "width": 4, "layers": 1}
    one_matmul = 2 * 4 * 8 * 4                   # 256 operations
    assert family.gmm_step_flops(shape) == 3 * 3 * one_matmul
    forward = (2 * 4 * 8 + 2 * 4 * 4) + (4 * 4 + 4 * 8) + 3 * 3 * 8 * 4
    assert family.gmm_step_bytes(shape) == 3 * forward * 2
    cell = family.gmm_shape(CONFIG, 8 * 4096, 4)
    assert cell == {"rows": 65536, "experts": 16, "hidden": 2048,
                    "width": 1024, "layers": CONFIG["num_hidden_layers"]}
    # compute-bound on a v5e: 14.8 TFLOP against 6.5 GB a chip a step
    assert family.gmm_step_flops(cell) / 197e12 > (
        family.gmm_step_bytes(cell) / 819e9)


def _observed(ops):
    return {
        "family": family, "config": CONFIG, "batch": 8, "seq": 4096,
        "chips": 4, "peaks": {"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9},
        "trace_loaded": trace.Trace(
            device_ops={0: ops, 1: []},
            host_spans=[("bench.window", 0.0, 100.0)], seen={}),
    }


FWD = ('%shard._attend.1 = (bf16[32,4096,128]{2,1,0}, f32[32,4096,128]{2,1,0})'
       ' custom-call(bf16[32,4096,128] %a), custom_call_target="tpu_custom_call"')
DQ = ('%shard._attend.2 = bf16[32,4096,128]{2,1,0} custom-call(bf16[32,4096,128]'
      ' %a), custom_call_target="tpu_custom_call"')
DKV = ('%shard._attend.3 = (bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0})'
       ' custom-call(bf16[32,4096,128] %a), custom_call_target="tpu_custom_call"')


def test_expert_layer_readers_on_a_made_up_trace():
    layers = CONFIG["num_hidden_layers"]
    ops, at = [], 0.0

    def op(text, seconds):
        nonlocal at
        ops.append((text, at, at + seconds))
        at += seconds

    for _ in range(2 * layers):              # one step's forward kernels
        op(FWD, 0.001)
    op(DQ, 0.001)
    op(DKV, 0.001)
    op("%fusion.1 = bf16[2,4096,2048]{2,1,0} fusion(bf16[2,4096,2048] %x)", 1.0)
    op("%top_k.3 = (f32[2,4096,64]{1,2,0}, s32[2,4096,64]) sort(%p)", 0.5)
    op("%all-gather.30 = bf16[4,8192,2048]{2,1,0} all-gather(%x)", 2.0)
    loop_start = at
    op("%ragged-dot-none.5 = bf16[65536,1024]{1,0} custom-call(%m, %x, %w), "
       'custom_call_target="tpu_custom_call"', 3.0)
    op("%fusion.9 = bf16[65536,2048]{1,0} fusion(bf16[8192,2048] %x, s32[65536] "
       "%i)", 1.5)
    ops.append(("%while.7 = (s32[], bf16[4,8192,2048]{2,1,0}) while(%t)",
                loop_start, at))            # the loop over the source ranks
    ops.append(("%while.2 = (s32[], bf16[6,2,4096,2048]{3,2,1,0}, "
                "bf16[4,8192,2048]{2,1,0}) while(%t)", 0.0, at + 5.0))
    op("%reduce_scatter.34 = bf16[1,8192,2048]{2,1,0} reduce-scatter(%y)", 2.5)
    op("%all-reduce.16 = bf16[2048,50304]{1,0} all-reduce(%g)", 4.0)
    observed = _observed(ops)
    read = lambda name: load_module(  # noqa: E731
        "layer_metrics", name).read(observed)
    # one step traced: top_k, gather, the loop (matmul and gather), scatter
    assert abs(read("moe_ms_per_step") - 1e3 * (0.5 + 2 + 4.5 + 2.5)) < 1e-6
    assert abs(read("moe_gmm_ms_per_step") - 3e3) < 1e-6
    assert abs(read("moe_collective_ms_per_step") - 4.5e3) < 1e-6
    shape = family.gmm_shape(CONFIG, 8 * 4096, 4)
    want = 100 * (family.gmm_step_flops(shape) / 197e12) / 3.0
    assert abs(read("moe_gmm_roofline_pct") - want) < 1e-9


def test_readers_leave_the_metric_out_where_there_is_nothing_to_read():
    llama = load_module("families", "llama")
    observed = {"family": llama, "config": read_json(
        HERE, "configs", "mistral7b_l2.json"), "batch": 2, "seq": 2048,
        "chips": 1, "peaks": None, "values": {}, "attempted": 0,
        "trace_loaded": trace.Trace({0: [("%fusion.1", 0.0, 1.0)]}, [], {})}
    for name in ("moe_ms_per_step", "moe_gmm_ms_per_step",
                 "moe_gmm_roofline_pct", "moe_collective_ms_per_step",
                 "moe_load_max_over_mean"):
        assert load_module("layer_metrics", name).read(observed) is None
    observed = _observed([])                 # the family, and an empty trace
    observed.update(values={}, attempted=0)
    for name in ("moe_ms_per_step", "moe_gmm_ms_per_step",
                 "moe_gmm_roofline_pct", "moe_collective_ms_per_step"):
        assert load_module("layer_metrics", name).read(observed) is None
