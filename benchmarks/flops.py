"""The benchmark's own count of what the mathematics needs: operations
per token of a dense decoder's training step, and operations and bytes of
one call of each FA2 kernel.  Recomputed operations (remat) never count.
Pure Python: the checks in ``benchmarks/tests`` work them by hand."""


def train_flops_per_token(matmul_params, layers, attn_width, seq):
    """Forward and backward of a dense causal decoder, per token:
    ``6 * matmul_params`` (a multiply-add is 2 operations; backward costs
    twice the forward) plus causal attention ``6 * layers * attn_width *
    seq`` (scores and weighted sum are ``4 * seq * attn_width`` forward,
    halved by the causal mask, times 3).  ``matmul_params`` leaves out
    what is no matmul: the embedding lookup, norms, biases, positions.
    ``attn_width`` is heads times head size."""
    return 6 * matmul_params + 6 * layers * attn_width * seq


def causal_pairs(seq):
    """Query-key pairs a causal mask keeps."""
    return seq * (seq + 1) // 2


# matmuls over the kept pairs that each kernel needs to produce its own
# outputs from its own inputs: forward QK^T and PV; dQ needs QK^T, dO V^T
# and dS K; dK/dV needs QK^T, P^T dO, dO V^T and dS^T Q
FA2_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def fa2_call_flops(kind, batch, seq, heads, head_dim, causal=True):
    pairs = causal_pairs(seq) if causal else seq * seq
    return FA2_MATMULS[kind] * 2 * batch * heads * pairs * head_dim


def fa2_call_bytes(kind, batch, seq, heads, kv_heads, head_dim, itemsize=2):
    """Least bytes a call moves to and from HBM: each operand read once,
    each result written once, the per-row statistics as one float32 a
    row.  As ``ops/pallas/flash_attention.py::_flash_backward`` calls the
    kernels since PR 30: both backward kernels read (q, k, v, dO, O, lse)
    with K and V at the kv head count (no expansion to the q heads, no
    ``delta`` operand: the kernels take it from dO and O), and dK and dV
    leave at the kv head count."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    row = batch * seq * heads * 4
    if kind == "fwd":
        return q + 2 * kv + q + row           # q, k, v -> out, lse
    if kind == "dq":
        return 3 * q + 2 * kv + row + q       # q, k, v, do, o, lse -> dq
    if kind == "dkv":
        return 3 * q + 2 * kv + row + 2 * kv  # ... -> dk, dv
    raise KeyError(kind)


def fa2_call_least_seconds(kind, shape, peaks):
    """(seconds, which bound) the chip could not beat for one call."""
    flops = fa2_call_flops(kind, shape["batch"], shape["seq"],
                           shape["heads"], shape["head_dim"],
                           shape.get("causal", True))
    nbytes = fa2_call_bytes(kind, shape["batch"], shape["seq"],
                            shape["heads"], shape["kv_heads"],
                            shape["head_dim"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
