"""The FLOP and byte functions on shapes worked by hand."""

from benchmarks import flops
from benchmarks.common import load_module, read_json, HERE


def test_train_flops_per_token_by_hand():
    # 10 matmul parameters, 2 layers, attention width 4, 8 positions:
    # 6*10 + 6*2*4*8 = 60 + 384
    assert flops.train_flops_per_token(10, 2, 4, 8) == 444


def test_causal_pairs():
    assert flops.causal_pairs(1) == 1
    assert flops.causal_pairs(4) == 10          # 1+2+3+4
    assert flops.causal_pairs(2048) == 2048 * 2049 // 2


def test_fa2_call_flops_by_hand():
    # batch 1, seq 4 (10 kept pairs), 2 heads of size 8: one matmul over the
    # pairs is 2*1*2*10*8 = 320 operations
    assert flops.fa2_call_flops("fwd", 1, 4, 2, 8) == 2 * 320
    assert flops.fa2_call_flops("dq", 1, 4, 2, 8) == 3 * 320
    assert flops.fa2_call_flops("dkv", 1, 4, 2, 8) == 4 * 320
    assert flops.fa2_call_flops("fwd", 1, 4, 2, 8, causal=False) == 2 * 512


def test_fa2_call_bytes_by_hand():
    # batch 1, seq 4, 4 heads over 2 kv heads of size 8, bf16:
    # q = 4*4*8*2 = 256 B, k = v = 128 B, one float a row = 4*4*4 = 64 B
    assert flops.fa2_call_bytes("fwd", 1, 4, 4, 2, 8) == 256 + 256 + 256 + 64
    # backward: q, dO, O at the q heads, K and V at the kv heads, lse; dQ a
    # q head each, dK and dV a kv head each
    assert flops.fa2_call_bytes("dq", 1, 4, 4, 2, 8) == 3 * 256 + 256 + 64 + 256
    assert flops.fa2_call_bytes("dkv", 1, 4, 4, 2, 8) == 3 * 256 + 256 + 64 + 256
    # MHA (kv heads = heads): K, V, dK, dV as wide as q
    assert flops.fa2_call_bytes("dkv", 1, 4, 4, 4, 8) == 3 * 256 + 512 + 64 + 512


def test_fa2_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    shape = {"batch": 2, "seq": 2048, "heads": 32, "kv_heads": 8,
             "head_dim": 128}
    seconds, bound = flops.fa2_call_least_seconds("fwd", shape, peaks)
    assert bound == "compute"
    want = 2 * 2 * 2 * 32 * (2048 * 2049 // 2) * 128 / 197e12
    assert abs(seconds - want) < 1e-12
    tiny = {"batch": 1, "seq": 8, "heads": 1, "kv_heads": 1, "head_dim": 128}
    assert flops.fa2_call_least_seconds("fwd", tiny, peaks)[1] == "memory"


def test_mistral_matmul_params_leave_out_the_lookup():
    config = read_json(HERE, "configs", "mistral7b_l2.json")
    family = load_module("families", "llama")
    per_layer = 4096 * 128 * (2 * 32 + 2 * 8) + 3 * 4096 * 14336
    assert family.matmul_params(config) == 2 * per_layer + 4096 * 32000
    # the program's own count has the embedding table in it as well
    assert family.matmul_params(config) < 698_372_096 - 4096 * 32000 + 1
    assert family.flops_per_token(config, 2048) == (
        6 * family.matmul_params(config) + 6 * 2 * 4096 * 2048)


def test_gpt2_medium_matmul_params():
    config = read_json(HERE, "configs", "gpt2m.json")
    family = load_module("families", "gpt")
    assert family.matmul_params(config) == (
        24 * 12 * 1024 * 1024 + 1024 * 50304)


def test_gpt2_medium_fa2_shape():
    """The GPT cell's kernel calls: 16 heads of 64 (MHA) at S 1024, with
    remat the forward twice a layer."""
    config = read_json(HERE, "configs", "gpt2m.json")
    shape = load_module("families", "gpt").fa2_shape(config, 16, 1024)
    assert [shape[key] for key in ("batch", "seq", "heads", "kv_heads",
                                   "head_dim")] == [16, 1024, 16, 16, 64]
    assert shape["calls_per_step"] == {"fwd": 48, "dq": 24, "dkv": 24}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for kind in ("fwd", "dq", "dkv"):
        assert flops.fa2_call_least_seconds(kind, shape, peaks)[1] == "compute"
