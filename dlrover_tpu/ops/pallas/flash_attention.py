"""Pallas TPU flash attention, FA2-style: fused forward AND backward.

Forward: blocks of Q stay resident in VMEM while KV blocks stream through;
softmax is computed online with running (max, sum) so the S x S score
matrix never materializes in HBM — the memory win that lets long sequences
fit.  Operands come as given (bfloat16 in training), are cast to float32
in the kernel body, and every matmul runs at the default precision with
float32 accumulation; the per-row log-sum-exp (LSE) is the backward
residual.

Backward: two blockwise kernels in the standard FA2 split — dQ iterates KV
blocks for a resident Q block; dK/dV iterate Q blocks for a resident KV
block — recomputing probabilities from (q, k, lse) so the backward is also
O(S) memory.  ``delta = rowsum(dO * O)`` is computed in both from the
blocks of dO and O they hold.  GQA: K and V are read through the kv index
map, forward and backward, never expanded; the dK/dV kernel walks the q
heads of a kv head one after the other and sums the group in its
accumulators, so dK and dV come out at the kv head count.

HBM interface: every operand and result is the model's own ``[B, S, H, D]``
array seen as ``[B, S, H*D]`` (a reshape of the trailing two dimensions,
no transpose), and a block is ``(1, block, 128)``: 128 lanes at column
block ``h``.  At head size 128 that is one head; at head size 64 it is
heads ``2h`` and ``2h+1``, which the kernel body takes one after the other
by selecting the head's lanes (the other lanes read as zero, so each
matmul contracts over, or writes, a whole 128-lane tile).  An odd head
count leaves the last block with one head: the absent head is skipped, and
its lanes, which lie outside the array, reach nothing (a select, never a
multiply by zero, keeps them out).

Grids are sequential on TPU, so VMEM scratch carries accumulators across
the innermost dimension.  Causal masking skips fully-masked blocks.

A causal ``window`` (a query at ``t`` sees the keys ``t - window < s <=
t``: itself and the ``window - 1`` before it) is a second condition of
the mask, made in the kernel from positions, and it SHORTENS the streamed
axis of every grid: a resident block's band touches few blocks of the
other kind, so the axis has only as many steps as the widest band needs
(``band_steps``), a step counts from the band's first block
(``_first_kv_block``; in dK/dV from the causal first q block) through the
index maps, which clamp as the causal ones do, and a step past the band's
last block or the sequence's edge is skipped.  At 16,384 positions, a
window of 512 and blocks of 512 a query block visits 2 key blocks of 32.
``window=None`` is the causal kernel as it was, the same program.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# TPU vector lanes: a block's trailing dimension, and the width at which
# per-row scalars (LSE) are stored broadcast so their blocks meet Mosaic's
# (8, 128) tiling constraint (same layout as jax's reference TPU kernel).
LANES = 128

# What a caller that chooses for itself may send here.  The compiler
# takes more (every head size that divides, or is a multiple of, the 128
# lanes, and blocks down to 8 rows), but these are the shapes the kernel
# has run at on the chip against the reference (tests_tpu/): the head
# sizes whose heads fill half or all of a 128-lane block, and sequences
# that a block of the tuner's sweep divides.  A shape outside them is the
# reference's until a test on the chip says otherwise.
KERNEL_HEAD_DIMS = (64, 128)
KERNEL_MIN_BLOCK = 128

# Mosaic's default of 16 MiB of VMEM a kernel holds every kernel here at
# 1024 x 1024 blocks but the backward ones where two heads share a block
# (the LSE tile is two heads': 18.4 MiB); a v5e core has 128 MiB.  Only
# the calls of a shared block ask for more: what a kernel may take, the
# program around it may not keep there.
SHARED_BLOCK_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def heads_per_block(head_dim: int) -> int:
    """How many heads one 128-lane column block of ``[B, S, H*D]`` holds."""
    return max(1, LANES // head_dim)


def kernel_takes(seq_len: int, head_dim: int, heads: int,
                 kv_heads: int, window=None) -> bool:
    """Whether the kernel runs causal self-attention at this shape.  Any
    head count goes, odd ones too, as long as the kv heads divide it; so
    does any causal ``window`` of at least one position (``None``: every
    earlier key), under, at or over a block and the sequence."""
    return (head_dim in KERNEL_HEAD_DIMS
            and seq_len % KERNEL_MIN_BLOCK == 0
            and heads % kv_heads == 0
            and (window is None or window >= 1))


def _compiler_params(per_block: int):
    """grid: (batch, head block, resident block, streamed block)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=(
            SHARED_BLOCK_VMEM_LIMIT_BYTES if per_block > 1 else None),
    )


def _block_lanes(head_dim: int) -> int:
    """Lanes of one column block: 128, or the whole head where it is
    wider."""
    if LANES % head_dim == 0:
        return LANES
    if head_dim % LANES == 0:
        return head_dim
    raise ValueError(
        f"head size {head_dim} neither divides nor is a multiple of "
        f"{LANES} lanes"
    )


def _lanes_at(place, head_dim: int, width: int):
    """[1, width] mask of the lanes of the head at ``place`` of its block
    (a Python int, or traced); ``None`` where the block is one head and
    nothing needs selecting."""
    if head_dim == width:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= place * head_dim) & (lane < (place + 1) * head_dim)


def _head_tile(ref, place, onto, head_dim: int):
    """The float32 tile of ``ref`` with the head at ``place`` of the block
    turned onto the lanes of place ``onto`` and every other lane read as
    zero: a select, so that what the other lanes hold (another head, or
    nothing at all past the array's edge) cannot reach the result."""
    x = ref[0].astype(jnp.float32)
    width = x.shape[-1]
    if head_dim == width:
        return x
    # the same Python int, or the same traced value: nothing to turn
    if place is not onto:
        per_block = width // head_dim
        x = pltpu.roll(x, (onto - place + per_block) * head_dim % width, 1)
    return jnp.where(_lanes_at(onto, head_dim, width), x, 0.0)


def _kv_place(q_head_block, i: int, groups: int, per_block: int):
    """Where in its kv block the kv head of q head ``i`` of
    ``q_head_block`` sits: the q head's own place without GQA."""
    if groups == 1:
        return i
    return (q_head_block * per_block + i) // groups % per_block


def _each_head(q_head_block, heads: int, per_block: int, q_head_blocks: int,
               body):
    """Run ``body(i)`` for the q heads of this block, one after the other.
    Where the grid's ``q_head_blocks`` hold more than ``heads`` (an odd
    head count, a kv block that is not full), a head past the last one is
    skipped."""
    for i in range(per_block):
        run = functools.partial(body, i)
        if q_head_blocks * per_block > heads:
            pl.when(q_head_block * per_block + i < heads)(run)
        else:
            run()


def _masked_scores(q, k, scale, causal, q_start, kv_start, block_q,
                   block_kv, window=None):
    """The one numerical core shared by forward and both backward
    kernels: fp32 scores with the causal mask, and the window's, applied."""
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
        allowed = rows >= cols
        if window is not None:
            allowed &= rows - cols < window
        s = jnp.where(allowed, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, out_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
    head_dim: int, heads: int, groups: int, window=None,
):
    head_block = pl.program_id(1)
    q_idx = pl.program_id(2)
    step = pl.program_id(3)
    # under a window the streamed axis counts from the band's first block
    kv_idx = _streamed_kv_block(q_idx, step, block_q, block_kv, window)
    width = acc_ref.shape[-1]
    per_block = width // head_dim

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv

    # a block the window masks whole for SOME of its rows, before their
    # first live one, leaves them a running maximum of NEG_INF and sums of
    # exp(0): the first live block's correction, exp(NEG_INF - m), is 0
    # and wipes both (every row has a live key, itself)
    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )

    def one_head(i):
        q = _head_tile(q_ref, i, i, head_dim)
        kv_at = _kv_place(head_block, i, groups, per_block)
        k = _head_tile(k_ref, kv_at, i, head_dim)
        v = _head_tile(v_ref, kv_at, i, head_dim)
        s = _masked_scores(q, k, scale, causal, q_start, kv_start,
                           block_q, block_kv, window)

        m_prev = m_ref[i, :, :1]
        l_prev = l_ref[i, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        grown = acc_ref[:] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        lanes = _lanes_at(i, head_dim, width)
        acc_ref[:] = (
            grown if lanes is None else jnp.where(lanes, grown, acc_ref[:])
        )
        m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(needed)
    def _compute():
        _each_head(head_block, heads, per_block, pl.num_programs(1),
                   one_head)

    @pl.when(step == pl.num_programs(3) - 1)
    def _finalize():
        out = None
        for i in range(per_block):
            l = l_ref[i, :, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            lanes = _lanes_at(i, head_dim, width)
            mine = acc_ref[:] / safe_l
            out = mine if out is None else jnp.where(lanes, mine, out)
            if lse_ref is not None:
                lse = m_ref[i, :, :1] + jnp.log(safe_l)  # [block_q, 1]
                lse_ref[0, i] = jnp.broadcast_to(lse, lse_ref.shape[2:])
        out_ref[0] = out.astype(out_ref.dtype)


def _last_kv_block(q_block, block_q: int, block_kv: int):
    """The last kv block a causal q block attends to."""
    return ((q_block + 1) * block_q - 1) // block_kv


def _first_q_block(kv_block, block_q: int, block_kv: int):
    """The first q block that attends to a causal kv block."""
    return (kv_block * block_kv) // block_q


def _first_kv_block(q_block, block_q: int, block_kv: int, window: int):
    """The first kv block the band of a q block touches: the one that
    holds the first key of the block's first query."""
    return jnp.maximum(q_block * block_q - (window - 1), 0) // block_kv


def _last_q_block(kv_block, block_q: int, block_kv: int, window: int,
                  num_q: int):
    """The last q block whose band touches a kv block: the one that holds
    the last query of the block's last key, or the sequence's last."""
    return jnp.minimum(
        ((kv_block + 1) * block_kv + window - 2) // block_q, num_q - 1)


def _streamed_kv_block(q_block, step, block_q: int, block_kv: int, window):
    """The kv block of a grid step of a resident q block."""
    if window is None:
        return step
    return _first_kv_block(q_block, block_q, block_kv, window) + step


def _kv_blocks_visited(seq_len: int, block_q: int, block_kv: int,
                       window: int):
    """The kv blocks each q block's band touches, a count a q block."""
    return [((i + 1) * block_q - 1) // block_kv
            - max(i * block_q - (window - 1), 0) // block_kv + 1
            for i in range(seq_len // block_q)]


def band_steps(seq_len: int, block_q: int, block_kv: int, window: int):
    """``(kv blocks a q block's band touches at most, q blocks a kv
    block's)``: the lengths of the streamed axes under a causal window."""
    num_q = seq_len // block_q
    q_steps = max(
        min(((j + 1) * block_kv + window - 2) // block_q, num_q - 1)
        - (j * block_kv) // block_q + 1
        for j in range(seq_len // block_kv))
    return max(_kv_blocks_visited(seq_len, block_q, block_kv, window)), q_steps


def band_pairs(seq_len: int, block_q: int, block_kv: int, window: int):
    """``(multiplied, allowed)`` query-key pairs of one head's forward
    pass: the scores of every block a q block visits, and the band's."""
    visited = sum(_kv_blocks_visited(seq_len, block_q, block_kv, window))
    w = min(window, seq_len)
    return visited * block_q * block_kv, seq_len * w - w * (w - 1) // 2


def _checked_blocks(seq_len: int, block_q: int, block_kv: int):
    block_q = min(block_q, seq_len)
    block_kv = min(block_kv, seq_len)
    if seq_len % block_q or seq_len % block_kv:
        raise ValueError(
            f"seq len {seq_len} must be divisible by block sizes "
            f"({block_q}, {block_kv})"
        )
    return block_q, block_kv


def _flash_forward(q, k, v, causal: bool, block_q: int, block_kv: int,
                   interpret: bool = False, with_residuals: bool = False,
                   window=None):
    """q: [B, S, H, D]; k/v: [B, S, H_kv, D] (GQA via KV index mapping)."""
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal attention and at least one "
            "position")
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    if H % H_kv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {H_kv}")
    groups = H // H_kv
    block_q, block_kv = _checked_blocks(S, block_q, block_kv)
    width = _block_lanes(D)
    per_block = width // D

    def q_index(b, h, i, j):
        return b, i, h

    def kv_index(b, h, i, j):
        j = _streamed_kv_block(i, j, block_q, block_kv, window)
        if causal:  # a masked step asks for the block it already has
            j = jnp.minimum(j, _last_kv_block(i, block_q, block_kv))
        return b, j, h // groups

    kv_steps = S // block_kv
    if window is not None:
        kv_steps, _ = band_steps(S, block_q, block_kv, window)

    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q,
        block_kv=block_kv,
        causal=causal,
        scale=D ** -0.5,
        head_dim=D,
        heads=H,
        groups=groups,
        window=window,
    )
    if with_residuals:
        # lane-broadcast residual: [B, H, S, LANES] (see LANES)
        lse_spec = pl.BlockSpec(
            (1, per_block, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)
        )
        lse_shape = jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32)
    else:
        lse_spec, lse_shape = None, None
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, pl.cdiv(H, per_block), S // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, width), q_index),
            pl.BlockSpec((1, block_kv, width), kv_index),
            pl.BlockSpec((1, block_kv, width), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), q_index),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            lse_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, width), jnp.float32),
            pltpu.VMEM((per_block, block_q, LANES), jnp.float32),
            pltpu.VMEM((per_block, block_q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(per_block),
        interpret=interpret,
    )(q.reshape(B, S, H * D), k.reshape(B, S, H_kv * D),
      v.reshape(B, S, H_kv * D))
    out = out.reshape(B, S, H, D)
    if with_residuals:
        return out, lse  # [B, H, S, LANES]
    return out


# ---------------------------------------------------------------------------
# backward (FA2 split: dq kernel + dkv kernel, probabilities recomputed)
# ---------------------------------------------------------------------------


def _recomputed(q, k, v, do, o, lse, scale, causal, q_start, kv_start,
                block_q, block_kv, window=None):
    """``(p, ds)`` of one head from its tiles, all on the same lanes, and
    its LSE ``[block_q, 1]``."""
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    s = _masked_scores(q, k, scale, causal, q_start, kv_start,
                       block_q, block_kv, window)
    p = jnp.exp(s - lse)  # exact probabilities via saved LSE
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta) * scale


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, acc_ref,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
    head_dim: int, heads: int, groups: int, window=None,
):
    head_block = pl.program_id(1)
    q_idx = pl.program_id(2)
    step = pl.program_id(3)
    kv_idx = _streamed_kv_block(q_idx, step, block_q, block_kv, window)
    per_block = acc_ref.shape[-1] // head_dim

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv
    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )

    def one_head(i):
        # everything on the q head's lanes: dQ lands where q lies
        q, do, o = (_head_tile(ref, i, i, head_dim)
                    for ref in (q_ref, do_ref, o_ref))
        kv_at = _kv_place(head_block, i, groups, per_block)
        k = _head_tile(k_ref, kv_at, i, head_dim)
        v = _head_tile(v_ref, kv_at, i, head_dim)
        _, ds = _recomputed(
            q, k, v, do, o, lse_ref[0, i, :, :1], scale, causal, q_start,
            kv_start, block_q, block_kv, window)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(needed)
    def _compute():
        _each_head(head_block, heads, per_block, pl.num_programs(1),
                   one_head)

    @pl.when(step == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, block_kv: int, causal: bool, scale: float,
    head_dim: int, heads: int, groups: int, num_q: int, window=None,
    seq_q_blocks: int = 0,
):
    # the streamed axis walks the q blocks of every q head-block whose kv
    # heads lie in this kv block, so a GQA group is summed here; under a
    # window ``num_q`` is the q blocks a kv block's band touches at most,
    # counted from the causal first one, of ``seq_q_blocks`` in all
    kv_idx = pl.program_id(2)
    q_head_block = pl.program_id(1) * groups + pl.program_id(3) // num_q
    q_idx = pl.program_id(3) % num_q
    if window is not None:
        q_idx += _first_q_block(kv_idx, block_q, block_kv)
    per_block = dk_acc.shape[-1] // head_dim

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_kv
    needed = jnp.logical_or(
        jnp.logical_not(causal), kv_start <= q_start + block_q - 1
    )
    if window is not None:  # past the band's last q block, or the edge
        needed &= q_idx <= _last_q_block(
            kv_idx, block_q, block_kv, window, seq_q_blocks)

    def one_head(i):
        # everything on the kv head's lanes: dK and dV land where k lies
        kv_at = _kv_place(q_head_block, i, groups, per_block)
        q, do, o = (_head_tile(ref, i, kv_at, head_dim)
                    for ref in (q_ref, do_ref, o_ref))
        k = _head_tile(k_ref, kv_at, kv_at, head_dim)
        v = _head_tile(v_ref, kv_at, kv_at, head_dim)
        p, ds = _recomputed(
            q, k, v, do, o, lse_ref[0, i, :, :1], scale, causal, q_start,
            kv_start, block_q, block_kv, window)
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(needed)
    def _compute():
        _each_head(q_head_block, heads, per_block,
                   pl.num_programs(1) * groups, one_head)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, grad_out, causal, block_q, block_kv,
                    interpret, window=None):
    """q, out, do: [B, S, H, D]; k, v: [B, S, H_kv, D]; lse:
    [B, H, S, LANES].  Returns (dq, dk, dv) in the shapes of (q, k, v):
    the dK/dV kernel sums a GQA group itself."""
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    groups = H // H_kv
    block_q, block_kv = _checked_blocks(S, block_q, block_kv)
    num_q, num_kv = S // block_q, S // block_kv
    width = _block_lanes(D)
    per_block = width // D
    q_head_blocks = pl.cdiv(H, per_block)
    operands = [x.reshape(B, S, -1) for x in (q, k, v, grad_out, out)]
    operands.append(lse)
    settings = dict(block_q=block_q, block_kv=block_kv, causal=causal,
                    scale=D ** -0.5, head_dim=D, heads=H, groups=groups,
                    window=window)
    # the streamed axes: every block, or the widest band's under a window
    kv_steps, q_steps = num_kv, num_q
    if window is not None:
        kv_steps, q_steps = band_steps(S, block_q, block_kv, window)

    def in_specs(where):
        """Block specs of (q, k, v, do, o, lse) from ``where``, which gives
        a grid step's (batch, q block, kv block, q head-block, kv
        head-block)."""
        def at_q(*ids):
            b, i, _, h, _ = where(*ids)
            return b, i, h

        def at_kv(*ids):
            b, _, j, _, h_kv = where(*ids)
            return b, j, h_kv

        def at_lse(*ids):
            b, i, _, h, _ = where(*ids)
            return b, h, i, 0

        q_spec = pl.BlockSpec((1, block_q, width), at_q)
        kv_spec = pl.BlockSpec((1, block_kv, width), at_kv)
        lse_spec = pl.BlockSpec((1, per_block, block_q, LANES), at_lse)
        return [q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec]

    # Causal: the streamed block of a masked step is the one the next (or
    # last) live step takes, so nothing is fetched for a step that waits.

    # dq grid: q blocks resident, kv blocks streamed
    def dq_step(b, h, i, j):
        j = _streamed_kv_block(i, j, block_q, block_kv, window)
        if causal:
            j = jnp.minimum(j, _last_kv_block(i, block_q, block_kv))
        return b, i, j, h, h // groups

    specs = in_specs(dq_step)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **settings),
        grid=(B, q_head_blocks, num_q, kv_steps),
        in_specs=specs,
        out_specs=specs[0],
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, width), jnp.float32)],
        compiler_params=_compiler_params(per_block),
        interpret=interpret,
    )(*operands)

    # dkv grid: kv blocks resident; streamed, the q blocks of each of the
    # ``groups`` q head-blocks whose kv heads lie in the kv head-block
    def dkv_step(b, h_kv, j, x):
        # an odd head count: the last kv head-block's last q head-block
        # may not be there (the kernel skips it), so ask for none past it
        h = jnp.minimum(h_kv * groups + x // q_steps, q_head_blocks - 1)
        i = x % q_steps
        if window is not None:
            i = jnp.minimum(
                i + _first_q_block(j, block_q, block_kv),
                _last_q_block(j, block_q, block_kv, window, num_q))
        elif causal:
            i = jnp.maximum(i, _first_q_block(j, block_q, block_kv))
        return b, i, j, h, h_kv

    specs = in_specs(dkv_step)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, num_q=q_steps,
                          seq_q_blocks=num_q, **settings),
        grid=(B, pl.cdiv(H_kv, per_block), num_kv, groups * q_steps),
        in_specs=specs,
        out_specs=[specs[1], specs[1]],
        out_shape=[jax.ShapeDtypeStruct((B, S, H_kv * D), k.dtype),
                   jax.ShapeDtypeStruct((B, S, H_kv * D), v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_kv, width), jnp.float32),
            pltpu.VMEM((block_kv, width), jnp.float32),
        ],
        compiler_params=_compiler_params(per_block),
        interpret=interpret,
    )(*operands)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def pallas_flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                           block_kv: int = 512, interpret: bool = False,
                           window=None):
    return _flash_forward(q, k, v, causal, block_q, block_kv, interpret,
                          window=window)


def _fwd(q, k, v, causal, block_q, block_kv, interpret, window):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_kv, interpret, with_residuals=True,
        window=window,
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_kv, interpret, window, residuals, grad_out):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, grad_out, causal, block_q, block_kv, interpret,
        window,
    )


pallas_flash_attention.defvjp(_fwd, _bwd)
