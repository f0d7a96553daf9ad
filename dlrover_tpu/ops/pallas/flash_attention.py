"""Pallas TPU flash attention, FA2-style: fused forward AND backward.

Forward: blocks of Q stay resident in VMEM while KV blocks stream through;
softmax is computed online with running (max, sum) so the S x S score
matrix never materializes in HBM — the memory win that lets long sequences
fit.  The kernel targets the MXU with bf16 inputs and fp32 accumulation,
and emits the per-row log-sum-exp (LSE) as the backward residual.

Backward: two blockwise kernels in the standard FA2 split — dQ iterates KV
blocks for a resident Q block; dK/dV iterate Q blocks for a resident KV
block — recomputing probabilities from (q, k, lse) so the backward is also
O(S) memory.  GQA backward runs on group-expanded heads and sum-reduces
dK/dV over each group afterwards (transient O(H) memory, no S x S).

Grids are sequential on TPU, so VMEM scratch carries accumulators across
the innermost dimension.  Causal masking skips fully-masked blocks.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# TPU vector lanes: per-row scalars (LSE, delta) are stored broadcast
# across a 128-lane trailing dim so their blocks meet Mosaic's (8, 128)
# tiling constraint (same layout as jax's reference TPU kernel).
MIN_LANES = 128

# What a caller that chooses for itself may send here.  The compiler
# takes more (head sizes 16 to 256 and blocks down to 8 rows compile for
# a described v5e), but these are the shapes the kernel has run at on
# the chip against the reference (tests_tpu/): the head sizes whose
# [block, D] tiles fill half or all of the 128 lanes, and sequences that
# a block of the tuner's sweep divides.  A shape outside them is the
# reference's until a test on the chip says otherwise.
KERNEL_HEAD_DIMS = (64, 128)
KERNEL_MIN_BLOCK = 128


def kernel_takes(seq_len: int, head_dim: int) -> bool:
    """Whether the kernel runs causal self-attention at this shape."""
    return head_dim in KERNEL_HEAD_DIMS and seq_len % KERNEL_MIN_BLOCK == 0


def _masked_scores(q, k, scale, causal, q_start, kv_start, block_q,
                   block_kv):
    """The one numerical core shared by forward and both backward
    kernels: fp32 scores with the causal mask applied."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        cols = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        s = jnp.where(rows >= cols, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, out_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv

    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _masked_scores(q, k, scale, causal, q_start, kv_start,
                           block_q, block_kv)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)
        if lse_ref is not None:
            lse = m_ref[:, :1] + jnp.log(safe_l)  # [block_q, 1]
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_forward(q, k, v, causal: bool, block_q: int, block_kv: int,
                   interpret: bool = False, with_residuals: bool = False):
    """q: [B, S, H, D]; k/v: [B, S, H_kv, D] (GQA via KV index mapping)."""
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    if H % H_kv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {H_kv}")
    groups = H // H_kv
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    if S % block_q or S % block_kv:
        raise ValueError(
            f"seq len {S} must be divisible by block sizes "
            f"({block_q}, {block_kv})"
        )
    scale = D ** -0.5
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H_kv, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H_kv, S, D)

    def kv_index(b, i, j):
        return (b // H) * H_kv + (b % H) // groups, j, 0

    grid = (B * H, S // block_q, S // block_kv)
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q,
        block_kv=block_kv,
        causal=causal,
        scale=scale,
    )
    if with_residuals:
        # lane-broadcast residual: [B*H, S, MIN_LANES] (see MIN_LANES)
        lse_spec = pl.BlockSpec(
            (1, block_q, MIN_LANES), lambda b, i, j: (b, i, 0)
        )
        lse_shape = jax.ShapeDtypeStruct(
            (B * H, S, MIN_LANES), jnp.float32
        )
    else:
        lse_spec, lse_shape = None, None
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), kv_index),
            pl.BlockSpec((1, block_kv, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            lse_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, MIN_LANES), jnp.float32),
            pltpu.VMEM((block_q, MIN_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    out4 = out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    if with_residuals:
        return out4, lse  # [B*H, S, MIN_LANES]
    return out4


# ---------------------------------------------------------------------------
# backward (FA2 split: dq kernel + dkv kernel, probabilities recomputed)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv
    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, :1]  # [block_q, 1] from lane-broadcast layout
        delta = delta_ref[0, :, :1]
        s = _masked_scores(q, k, scale, causal, q_start, kv_start,
                           block_q, block_kv)
        p = jnp.exp(s - lse)  # exact probabilities via saved LSE
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
):
    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv
    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, :1]  # [block_q, 1] from lane-broadcast layout
        delta = delta_ref[0, :, :1]
        s = _masked_scores(q, k, scale, causal, q_start, kv_start,
                           block_q, block_kv)
        p = jnp.exp(s - lse)  # [block_q, block_kv]
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(q_idx == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, grad_out, causal, block_q, block_kv,
                    interpret):
    """All inputs with EXPANDED heads: q,k,v,out,do: [B, S, H, D];
    lse: [B*H, S, MIN_LANES].  Returns (dq, dk, dv) with expanded heads."""
    B, S, H, D = q.shape
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    scale = D ** -0.5
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    ot = out.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    dot = grad_out.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    # delta_i = rowsum(dO_i * O_i): cheap elementwise, computed outside,
    # lane-broadcast to match the residual layout
    delta = jnp.sum(
        dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1
    )  # [B*H, S]
    delta = jnp.broadcast_to(delta[:, :, None], (B * H, S, MIN_LANES))

    lane_spec = pl.BlockSpec(
        (1, block_q, MIN_LANES), lambda b, i, j: (b, i, 0)
    )
    common_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),  # q
        pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),  # k
        pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),  # v
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),  # do
        lane_spec,  # lse
        lane_spec,  # delta
    ]

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_kv=block_kv,
            causal=causal, scale=scale,
        ),
        grid=(B * H, S // block_q, S // block_kv),
        in_specs=common_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # dkv grid: kv blocks outer (resident), q blocks inner (streamed)
    lane_spec_kv = pl.BlockSpec(
        (1, block_q, MIN_LANES), lambda b, j, i: (b, i, 0)
    )
    dkv_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # q
        pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),  # k
        pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),  # v
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # do
        lane_spec_kv,  # lse
        lane_spec_kv,  # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_kv=block_kv,
            causal=causal, scale=scale,
        ),
        grid=(B * H, S // block_kv, S // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    def unflat(x):
        return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    return unflat(dq), unflat(dk), unflat(dv)


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def pallas_flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                           block_kv: int = 512, interpret: bool = False):
    return _flash_forward(q, k, v, causal, block_q, block_kv, interpret)


def _fwd(q, k, v, causal, block_q, block_kv, interpret):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_kv, interpret, with_residuals=True
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_kv, interpret, residuals, grad_out):
    q, k, v, out, lse = residuals
    H, H_kv = q.shape[2], k.shape[2]
    groups = H // H_kv
    ke = jnp.repeat(k, groups, axis=2) if groups > 1 else k
    ve = jnp.repeat(v, groups, axis=2) if groups > 1 else v
    dq, dk, dv = _flash_backward(
        q, ke, ve, out, lse, grad_out, causal, block_q, block_kv, interpret
    )
    if groups > 1:
        B, S, _, D = dk.shape
        dk = dk.reshape(B, S, H_kv, groups, D).sum(axis=3)
        dv = dv.reshape(B, S, H_kv, groups, D).sum(axis=3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


pallas_flash_attention.defvjp(_fwd, _bwd)
