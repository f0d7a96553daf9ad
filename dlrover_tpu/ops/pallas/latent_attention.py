"""Pallas TPU kernels for the core of latent attention (MLA,
arXiv:2405.04434): causal softmax attention whose score is the SUM OF TWO
PRODUCTS, ``q_nope k_nope^T`` a head (128 wide) and ``q_pe k_pe^T`` against
ONE rotary key head that every query head shares (64 wide), and whose
values are 128 wide.  Two kernels: the forward under the online softmax,
and ONE backward call that scores a live pair of blocks once and yields
that pair's part of all five gradients (nine block products; FA2's split
into a dQ and a dK/dV kernel, which this file ran until PR 56, scores a
pair twice: eleven).  The numerics are ``flash_attention.py``'s: float32
scores and softmax, float32 accumulation, the per-row log-sum-exp the
backward's residual; a ``[block_q, block_kv]`` tile of scores lives and
dies in VMEM, so no ``[heads, S, S]`` array reaches HBM in either pass.

Why kernels of their own: FA2's takes one head size for q, k and v and one
key head a query head (or a GQA group); here the contraction is 128 + 64 =
192 wide in two operands of different widths, the second operand's key has
no head axis, and the value is narrower than the score.  Padding the 64 to
128 lanes in HBM would move a third more bytes of q and k and count work
the model does not ask for; concatenating k_nope with the shared k_pe a
head would write the rotary key once a head.  Neither is done: the two
products are two matmuls into one float32 tile.

HBM interface: the 128-wide operands and results (``q_nope, k_nope, v,
out`` and their gradients) are the model's ``[B, S, H, 128]`` arrays seen
as ``[B, S, H*128]``, a head one 128-lane column block, as FA2's.  The
64-wide ones cannot be column blocks of ``[B, S, H*64]`` (a block's lanes
are a multiple of 128 or the whole axis): ``q_pe`` and its gradient go
head-major, ``[B, H, S, 64]`` (a transpose of a sixth of q's bytes), the
shared ``k_pe`` is ``[B, S, 64]`` as it is, and its gradient leaves the
backward kernel a head at a time as float32 ``[B, H, S, 64]`` and is summed
over the heads outside (the kernel's grid is parallel over heads).  The
backward kernel walks key blocks outermost, so dQ gathers across grid
steps that are not neighbours: its two parts are float32 accumulators in
HBM, ``[B, S, H*128]`` and head-major ``[B, H, S, 128]`` (the 64-wide part
in the lower lanes: a 64-lane array is stored 128 lanes wide in HBM anyway,
and Mosaic takes no 64-lane slice of one), cast once after the call.

What a rematerialised layer keeps (``kept.py``): ``out`` and the LSE as
``[B, H, S]`` float32; its backward pass recomputes the projections in
``jax.numpy`` and does not run the forward kernel again.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.pallas.flash_attention import (
    LANES,
    NEG_INF,
    _checked_blocks,
    _first_q_block,
    _last_kv_block,
)

#: the widths the kernels take: a head's 128-wide part is one column block
NOPE_DIM = V_DIM = LANES

# the backward kernel holds q, dO and O blocks, two key blocks, a q
# block's two dQ tiles and a tile's scores, probabilities and their
# gradients in float32: 1024 x 1024 tiles compile for a v5e under 48 MiB
# of its core's 128
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: (block_q, block_kv) on a v5e (PERF.md section 6, PR 48, has the sweep)
BLOCKS = (1024, 1024)


def kernels_take(seq_len: int, nope_dim: int, rope_dim: int,
                 v_dim: int) -> bool:
    """Whether the kernels run causal self-attention at these widths."""
    return (nope_dim == NOPE_DIM and v_dim == V_DIM
            and rope_dim % 8 == 0 and seq_len % LANES == 0)


def blocks_for(seq_len: int):
    """The tile of ``BLOCKS`` shrunk to a divisor of ``seq_len``."""
    out = []
    for block in BLOCKS:
        block = min(block, seq_len)
        while seq_len % block:
            block -= LANES
        out.append(block)
    return tuple(out)


def _compiler_params(third_axis: str):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", third_axis, "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _scores(qn, qp, kn, kp, scale, q_start, kv_start):
    """[block_q, block_kv] float32: the two products' sum, scaled, under
    the causal mask by position."""
    nt = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(qn, kn, nt, preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qp, kp, nt,
                               preferred_element_type=jnp.float32)) * scale
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


def _fwd_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, out_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, block_q, block_kv, scale):
    """grid (batch, head, q block, kv block): online softmax over the kv
    blocks up to the diagonal."""
    q_start = pl.program_id(2) * block_q
    kv_idx = pl.program_id(3)
    kv_start = kv_idx * block_kv

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(kv_start <= q_start + block_q - 1)
    def _compute():
        v = v_ref[0]
        s = _scores(qn_ref[0], qp_ref[0, 0], kn_ref[0], kp_ref[0], scale,
                    q_start, kv_start)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_idx == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:, :1]     # a row's own position is always kept: l >= 1
        out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l), lse_ref.shape[2:])


def _bwd_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref, o_ref, lse_ref,
                dqn_hbm, dqp_hbm, dkn_ref, dkp_ref, dv_ref, dkn_acc, dkp_acc,
                dv_acc, dqn_tile, dqp_tile, zeros, arrived, left,
                *, block_q, block_kv, scale):
    """grid (batch, head, kv block, q block): a key block resident, the
    head's q blocks streamed past it from the diagonal on.  A live step
    scores its pair ONCE and adds its part to dV and both parts of dK in
    their accumulators and to both parts of the q block's dQ, which live in
    HBM as float32 between key blocks (``flash_attention.py::
    _flash_bwd_kernel``'s way): the step fetches the two blocks, adds, and
    writes them back before it ends, so the next step that names them reads
    what this one wrote; a masked step touches nothing.  Key block 0, which
    every query block sees, adds to zeros: nothing zero-fills the
    accumulators."""
    batch, head = pl.program_id(0), pl.program_id(1)
    kv_idx, q_idx = pl.program_id(2), pl.program_id(3)
    kv_start, q_start = kv_idx * block_kv, q_idx * block_q

    @pl.when(q_idx == 0)
    def _init():
        for ref in (dkn_acc, dkp_acc, dv_acc, zeros):
            ref[:] = jnp.zeros_like(ref)

    # dQ's two parts, each (fetch, the same from zeros, write-back, tile)
    rows = pl.ds(q_start, block_q)
    parts = [
        (pltpu.make_async_copy(block, tile, arrived.at[n]),
         pltpu.make_async_copy(zeros, tile, arrived.at[n]),
         pltpu.make_async_copy(tile, block, left.at[n]), tile)
        for n, (block, tile) in enumerate((
            (dqn_hbm.at[batch, rows, pl.ds(head * NOPE_DIM, NOPE_DIM)],
             dqn_tile),
            (dqp_hbm.at[batch, head, rows], dqp_tile)))]

    @pl.when(kv_start <= q_start + block_q - 1)
    def _compute():
        # what dQ holds so far is on its way while the pair is scored: the
        # blocks from HBM or, at key block 0, zeros from VMEM by the same
        # semaphores, so that the waits and the adds below are
        # straight-line code whatever the key block (PERF.md section 6,
        # PR 54: in a region of their own they cost FA2's call 3%)
        for fetch, from_zeros, _, _ in parts:
            pl.when(kv_idx > 0)(fetch.start)
            pl.when(kv_idx == 0)(from_zeros.start)
        qn, qp, kn, kp = qn_ref[0], qp_ref[0, 0], kn_ref[0], kp_ref[0]
        do, o = do_ref[0], o_ref[0]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        p = jnp.exp(_scores(qn, qp, kn, kp, scale, q_start, kv_start)
                    - lse_ref[0, 0, :, :1])
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(qn.dtype)
        # dQ first: its write-back runs under the three products after it
        nn = (((1,), (0,)), ((), ()))
        for (fetch, _, write_back, tile), k in zip(parts, (kn, kp)):
            mine = jax.lax.dot_general(
                ds, k, nn, preferred_element_type=jnp.float32)
            fetch.wait()
            tile[:, :k.shape[-1]] += mine
            write_back.start()
        tn = (((0,), (0,)), ((), ()))
        dv_acc[:] += jax.lax.dot_general(       # P^T dO
            p.astype(do.dtype), do, tn, preferred_element_type=jnp.float32)
        dkn_acc[:] += jax.lax.dot_general(      # dS^T Q, a part each
            ds, qn, tn, preferred_element_type=jnp.float32)
        dkp_acc[:] += jax.lax.dot_general(
            ds, qp, tn, preferred_element_type=jnp.float32)
        for _, _, write_back, _ in parts:
            write_back.wait()

    @pl.when(q_idx == pl.num_programs(3) - 1)
    def _finalize():
        dkn_ref[0] = dkn_acc[:].astype(dkn_ref.dtype)
        dkp_ref[0, 0] = dkp_acc[:]
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


class _Call:
    """Shapes and block specs of one call.  ``where`` gives a grid step's
    (batch, head, q block, kv block)."""

    def __init__(self, q_nope, q_pe, block_q, block_kv):
        self.B, self.S, self.H, self.D = q_nope.shape
        self.R = q_pe.shape[-1]
        self.block_q, self.block_kv = _checked_blocks(
            self.S, block_q, block_kv)
        self.num_q = self.S // self.block_q
        self.num_kv = self.S // self.block_kv
        self.settings = dict(block_q=self.block_q, block_kv=self.block_kv,
                             scale=(self.D + self.R) ** -0.5)

    def specs(self, where):
        def at(pick):
            return lambda *ids: pick(*where(*ids))

        bq, bkv = self.block_q, self.block_kv
        return dict(
            q=pl.BlockSpec((1, bq, self.D), at(lambda b, h, i, j: (b, i, h))),
            q_pe=pl.BlockSpec((1, 1, bq, self.R),
                              at(lambda b, h, i, j: (b, h, i, 0))),
            kv=pl.BlockSpec((1, bkv, self.D),
                            at(lambda b, h, i, j: (b, j, h))),
            k_pe=pl.BlockSpec((1, bkv, self.R),
                              at(lambda b, h, i, j: (b, j, 0))),
            # one head's gradient of the shared key, [B, H, S, R]
            dk_pe=pl.BlockSpec((1, 1, bkv, self.R),
                               at(lambda b, h, i, j: (b, h, j, 0))),
            # lane-broadcast per-row scalars, as FA2's: [B, H, S, LANES]
            lse=pl.BlockSpec((1, 1, bq, LANES),
                             at(lambda b, h, i, j: (b, h, i, 0))),
        )


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def _head_major(x):
    return jnp.swapaxes(x, 1, 2)


def _forward(q_nope, q_pe, k_nope, k_pe, v, block_q, block_kv, interpret):
    """``(out [B, S, H, 128], lse [B, H, S, LANES])``; ``q_pe`` head-major
    ``[B, H, S, R]``, ``k_pe`` ``[B, S, R]``."""
    call = _Call(q_nope, q_pe, block_q, block_kv)
    B, S, H, D = call.B, call.S, call.H, call.D

    def step(b, h, i, j):   # a masked step asks for the block it has
        return b, h, i, jnp.minimum(
            j, _last_kv_block(i, call.block_q, call.block_kv))

    spec = call.specs(step)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **call.settings),
        grid=(B, H, call.num_q, call.num_kv),
        in_specs=[spec["q"], spec["q_pe"], spec["kv"], spec["k_pe"],
                  spec["kv"]],
        out_specs=[spec["q"], spec["lse"]],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), v.dtype),
                   jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((call.block_q, D), jnp.float32),
                        pltpu.VMEM((call.block_q, LANES), jnp.float32),
                        pltpu.VMEM((call.block_q, LANES), jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
    )(_flat(q_nope), q_pe, _flat(k_nope), k_pe, _flat(v))
    return out.reshape(v.shape), lse


def _backward(q_nope, q_pe, k_nope, k_pe, v, out, lse, grad_out, block_q,
              block_kv, interpret):
    """``(dq_nope, dq_pe [B, H, S, R], dk_nope, dk_pe [B, S, R], dv)`` from
    ONE call.  Both parts of dQ gather over the key blocks, the grid's
    third axis, in float32 results that stay in HBM (no block spec: the
    kernel copies a block in and out itself, so no pipeline stands between
    a write and the next read of one block) and are cast once after the
    call.  The key-block axis is ``"arbitrary"`` for them (a v5e chip has
    one core: nothing is lost)."""
    call = _Call(q_nope, q_pe, block_q, block_kv)
    B, S, H, D, R = call.B, call.S, call.H, call.D, call.R
    bq, bkv = call.block_q, call.block_kv

    # kv blocks resident, q blocks streamed from the diagonal on: a masked
    # step asks for the block the next live step takes
    def step(b, h, j, i):
        return b, h, jnp.maximum(i, _first_q_block(j, bq, bkv)), j

    spec = call.specs(step)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    dq_nope, dq_pe, dk_nope, dk_pe, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, **call.settings),
        grid=(B, H, call.num_kv, call.num_q),
        in_specs=[spec["q"], spec["q_pe"], spec["kv"], spec["k_pe"],
                  spec["kv"], spec["q"], spec["q"], spec["lse"]],
        out_specs=[in_hbm, in_hbm, spec["kv"], spec["dk_pe"], spec["kv"]],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, H * D), k_nope.dtype),
                   jax.ShapeDtypeStruct((B, H, S, R), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, H * D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, D), jnp.float32),
                        pltpu.VMEM((bkv, R), jnp.float32),
                        pltpu.VMEM((bkv, D), jnp.float32),
                        # a q block's dQ, and the zeros key block 0 adds to
                        pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(_flat(q_nope), q_pe, _flat(k_nope), k_pe, _flat(v), _flat(grad_out),
      _flat(out), lse)
    return (dq_nope.astype(q_nope.dtype).reshape(q_nope.shape),
            dq_pe[..., :R].astype(q_pe.dtype), dk_nope.reshape(k_nope.shape),
            dk_pe.sum(axis=1).astype(k_pe.dtype), dv.reshape(v.shape))


def kept_bytes(v) -> dict:
    """What a layer's rematerialisation keeps of the forward kernel, in
    bytes by name: ``out`` as ``v`` and the LSE ``[B, H, S]`` float32."""
    B, S, H, _ = v.shape
    return {kept.ATTN_OUT: kept.nbytes(v.shape, v.dtype),
            kept.ATTN_LSE: kept.nbytes((B, H, S), jnp.float32)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def latent_attention_kernels(q_nope, q_pe, k_nope, k_pe, v, block_q,
                             block_kv, interpret: bool = False):
    """``softmax_causal((q_nope k_nope^T + q_pe k_pe^T) / sqrt(D + R)) v``
    a head: ``q_nope, k_nope, v`` [B, S, H, 128], ``q_pe`` [B, S, H, R],
    ``k_pe`` [B, S, R] (one rotary key every head shares) -> [B, S, H,
    128]."""
    return _latent_fwd(q_nope, q_pe, k_nope, k_pe, v, block_q, block_kv,
                       interpret)[0]


def _latent_fwd(q_nope, q_pe, k_nope, k_pe, v, block_q, block_kv, interpret):
    q_pe = _head_major(q_pe)
    out, lse = _forward(q_nope, q_pe, k_nope, k_pe, v, block_q, block_kv,
                        interpret)
    # the kernel writes every lane of a row's LSE alike: lane 0 is all of it
    out, = kept.named(kept.ATTN_OUT, out)
    lse, = kept.named(kept.ATTN_LSE, lse[..., 0])
    return out, (q_nope, q_pe, k_nope, k_pe, v, out, lse)


def _latent_bwd(block_q, block_kv, interpret, residuals, grad_out):
    q_nope, q_pe, k_nope, k_pe, v, out, lse = residuals
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))
    dq_nope, dq_pe, dk_nope, dk_pe, dv = _backward(
        q_nope, q_pe, k_nope, k_pe, v, out, lse, grad_out, block_q, block_kv,
        interpret)
    return dq_nope, _head_major(dq_pe), dk_nope, dk_pe, dv


latent_attention_kernels.defvjp(_latent_fwd, _latent_bwd)
