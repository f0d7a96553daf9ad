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

A band in ONE visit (the ``_band_*`` kernels).  A band is not a long row
of key blocks: a query block of ``block_q`` rows sees the keys ``[start
- (window - 1), start + block_q)`` and no others, and where they fit the
fast memory at once the online softmax buys nothing.  There a grid step
holds the query block, its own keys and the ``back`` keys before them
(``window - 1`` rounded up to ``block_kv``; fetched through block specs
of their own, clamped at block 0 and masked there by position:
``_band_reach``), and takes ``block_kv`` rows at a time against the
``block_kv + back`` keys those rows can see, static slices of the
resident tiles that cost no grid step: ``m = max(s)``, ``p = exp(s -
m)``, ``l = sum(p)``, ``out = p v / l``, the LSE written once.  No
running maximum or sum, no correction, no rescaled accumulator, no
scratch, no streamed axis; dQ likewise (``dq = ds k`` written directly),
and in dK/dV a resident key block meets the queries ``[start, start +
block_q + back)`` of one q head-block in one step, the streamed axis the
GQA group alone.  Scores, softmax and accumulation are float32 from the
operands as given, the band the same to the position, the residuals
``(q, k, v, out, lse)`` the same: the streamed kernels' result to
float32 rounding.  At 16,384 positions, a window of 512 and a head of
128 a forward call alone went from 11.8 ms (streamed, 512 x 512) to 5.2
(1,024 rows a step, 256 at a time: PERF.md, PR 52).

Which kernels a windowed call runs is ``band_path``, one rule over the
shapes: one visit where ``block_kv`` tiles ``block_q``, the keys a step
holds are no more than the sequence, and the step's tiles
(``band_vmem_bytes``) fit ``BAND_VMEM_LIMIT_BYTES``, whose arithmetic
stands beside it.  The streamed windowed kernels stay for every wider
band: Mistral's 4,096 on a longer sequence (six score tiles of ``[4608,
512]`` alone are 54 MiB), a window at or over the sequence, key blocks
that do not tile the query block.
"""

import functools
import math

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

# Which kernels a windowed call runs (``band_path``).
ONE_VISIT, STREAMED = "one_visit", "streamed"

# What a band kernel, which holds all of a resident block's band at once,
# may take of VMEM (``band_vmem_bytes`` has the count).  At the Laguna
# cell's shape (a window of 512, a head of 128 a block, float32 counted
# throughout) and whole blocks of 512 rows: two buffers of q, dO, O and
# the LSE over 1,024 rows, of k, v, dK and dV over 512, 2 x 3 MiB; their
# float32 tiles, 3 MiB; six ``[1024, 512]`` float32 tiles of scores, 12
# MiB: 21 MiB.  As shipped (1,024 rows a block, 256 at a time against
# their 768): 2 x 5 + 5 + 4.5 = 19.5 MiB.  Half as much again for what
# the compiler keeps beside them: twice Mosaic's default, a quarter of a
# v5e core's 128 MiB.
BAND_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


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


def _head_tile(ref, place, onto, head_dim: int, rows=None):
    """The float32 tile of ``ref`` (its ``rows`` alone, a static slice,
    where given) with the head at ``place`` of the block turned onto the
    lanes of place ``onto`` and every other lane read as zero: a select,
    so that what the other lanes hold (another head, or nothing at all
    past the array's edge) cannot reach the result."""
    x = (ref[0] if rows is None else ref[0, rows]).astype(jnp.float32)
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
    """q: [B, S, H, D]; k/v: [B, S, H_kv, D] (GQA via KV index mapping).
    A windowed call whose band fits one visit (``band_path``) runs the
    band kernel, every other call the streamed one."""
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal attention and at least one "
            "position")
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    if H % H_kv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {H_kv}")
    forward = (_band_forward if _takes_one_visit(S, D, block_q, block_kv,
                                                 window)
               else _streamed_forward)
    return forward(q, k, v, causal, block_q, block_kv, interpret,
                   with_residuals, window)


def _streamed_forward(q, k, v, causal, block_q, block_kv, interpret,
                      with_residuals, window):
    """Q blocks resident, KV blocks streamed under the online softmax:
    every causal block, or under a window the band's."""
    B, S, H, D = q.shape
    H_kv = k.shape[2]
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
    the dK/dV kernel sums a GQA group itself.  The band kernels where the
    forward took them (``band_path``: the same shapes, the same answer)."""
    backward = (_band_backward if _takes_one_visit(
        q.shape[1], q.shape[3], block_q, block_kv, window)
                else _streamed_backward)
    return backward(q, k, v, out, lse, grad_out, causal, block_q, block_kv,
                    interpret, window)


def _streamed_backward(q, k, v, out, lse, grad_out, causal, block_q,
                       block_kv, interpret, window):
    """The FA2 split: dQ streams KV blocks past a resident Q block, dK/dV
    stream the Q blocks of a GQA group past a resident KV block."""
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
# a window's band in one visit (``band_path``): plain softmax, no streamed
# axis in forward and dQ, the GQA group alone in dK/dV
# ---------------------------------------------------------------------------


def band_tiles(block_q: int, block_kv: int, window: int):
    """``(back, fetch)``: the rows next to its own that a resident block
    holds in a band step, ``window - 1`` rounded up to ``block_kv``, and
    the rows of one block of their fetch, the largest that tiles both
    ``back`` and ``block_q`` (so a block index says where they start)."""
    back = -(-(window - 1) // block_kv) * block_kv
    return back, math.gcd(block_q, back)


def band_vmem_bytes(block_q: int, block_kv: int, window: int,
                    head_dim: int) -> int:
    """What the hungriest band step (dK/dV) holds at once, counted at
    float32 operands: the pipeline's two buffers of a head's q, dO, O
    and LSE over ``block_q + back`` rows, of k and v and of the two
    results over ``block_q``; the float32 tiles the body casts them to;
    the two accumulators; and, ``[block_kv + back, block_kv]`` each, the
    scores, the probabilities, dP, dS and the two position grids of the
    mask."""
    back, _ = band_tiles(block_q, block_kv, window)
    width = _block_lanes(head_dim)
    rows = block_q + back
    operands = rows * (3 * width + heads_per_block(head_dim) * LANES) \
        + 2 * block_q * width
    results = 2 * block_q * width
    tiles = 6 * (block_kv + back) * block_kv
    return 4 * (2 * (operands + results) + operands + results + tiles)


def band_path(seq_len: int, block_q: int, block_kv: int, window: int,
              head_dim: int) -> str:
    """``ONE_VISIT`` or ``STREAMED``: which kernels a windowed call runs,
    from its shapes and nothing else.  One visit where ``block_kv`` tiles
    ``block_q``, the keys a step holds (``block_q + back``) are no more
    than the sequence (past it every step would hold a clamped, masked
    fetch; the streamed kernels visit a block once) and the step fits
    ``BAND_VMEM_LIMIT_BYTES``."""
    back, _ = band_tiles(block_q, block_kv, window)
    if (block_q % block_kv == 0 and block_q + back <= seq_len
            and band_vmem_bytes(block_q, block_kv, window, head_dim)
            <= BAND_VMEM_LIMIT_BYTES):
        return ONE_VISIT
    return STREAMED


def _takes_one_visit(seq_len: int, head_dim: int, block_q: int,
                     block_kv: int, window) -> bool:
    """Whether a call, its blocks as handed over, runs the band kernels."""
    return window is not None and band_path(
        seq_len, *_checked_blocks(seq_len, block_q, block_kv), window,
        head_dim) == ONE_VISIT


def band_record(seq_len: int, block_q: int, block_kv: int, window: int,
                head_dim: int) -> dict:
    """What ``attention.path`` says of a windowed call, the numbers of
    the kernels that run: ``band``, the key blocks a query block visits
    at most, and the query-key pairs of one head's forward pass, those
    every score tile holds and those the band allows.  One visit: every
    ``block_kv`` rows of a query block against ``block_kv + back`` keys,
    the sequence's first blocks too (their clamped fetch is multiplied
    and masked)."""
    path = band_path(seq_len, block_q, block_kv, window, head_dim)
    multiplied, allowed = band_pairs(seq_len, block_q, block_kv, window)
    if path == ONE_VISIT:
        back, _ = band_tiles(block_q, block_kv, window)
        visited, multiplied = 1, seq_len * (block_kv + back)
    else:
        visited = band_steps(seq_len, block_q, block_kv, window)[0]
    return dict(band=path, kv_blocks_visited=visited,
                pairs_multiplied=multiplied, pairs_allowed=allowed)


def _band_rows(refs, lo: int, hi: int, read):
    """Rows ``[lo, hi)`` of what the tiles ``refs`` hold one after the
    other along their second-to-last axis: ``read(ref, rows)`` of each
    tile the range touches, joined."""
    pieces, at = [], 0
    for ref in refs:
        n = ref.shape[-2]
        if max(lo, at) < min(hi, at + n):
            pieces.append(read(ref, slice(max(lo, at) - at,
                                          min(hi, at + n) - at)))
        at += n
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)


def _band_tile(refs, lo: int, hi: int, place, onto, head_dim: int):
    """``_head_tile`` of the rows ``[lo, hi)`` of the tiles ``refs``."""
    return _band_rows(refs, lo, hi, lambda ref, rows: _head_tile(
        ref, place, onto, head_dim, rows))


def _band_reach(start, rows: int, window: int, seq_len: int):
    """``[rows, 1]``: how many keys, itself the last, each of ``rows``
    queries from position ``start`` sees: ``window``; fewer where the
    sequence has no earlier ones, which is what masks the keys a fetch
    clamped at block 0 holds at positions under 0; none for a query past
    the sequence's end (a fetch clamped at the last block).  Handed to
    ``_masked_scores`` as its window, a row's own."""
    at = start + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.where(at < seq_len, jnp.minimum(window, at + 1), 0)


def _band_compiler_params(streamed_axes: int = 0):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3 + ("arbitrary",) * streamed_axes,
        vmem_limit_bytes=BAND_VMEM_LIMIT_BYTES)


def _band_fwd_kernel(
    q_ref, k_refs, v_refs, out_ref, lse_ref,
    *, block_q: int, block_kv: int, back: int, scale: float, head_dim: int,
    heads: int, groups: int, window: int, seq_len: int,
):
    # k_refs, v_refs: the ``back`` keys before the block's own, then its
    # own.  ``block_kv`` rows at a time meet the ``block_kv + back`` keys
    # they can see, all at once: the softmax is the plain one
    head_block = pl.program_id(1)
    q_start = pl.program_id(2) * block_q
    per_block = q_ref.shape[-1] // head_dim
    held = block_kv + back

    def one_head(i):
        kv_at = _kv_place(head_block, i, groups, per_block)
        for lo in range(0, block_q, block_kv):
            rows = slice(lo, lo + block_kv)
            s = _masked_scores(
                _head_tile(q_ref, i, i, head_dim, rows),
                _band_tile(k_refs, lo, lo + held, kv_at, i, head_dim),
                scale, True, q_start + lo, q_start + lo - back, block_kv,
                held, _band_reach(q_start + lo, block_kv, window, seq_len))
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)  # at least 1: itself
            mine = (jax.lax.dot_general(
                p, _band_tile(v_refs, lo, lo + held, kv_at, i, head_dim),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) / l).astype(out_ref.dtype)
            # the other heads' lanes of ``mine`` are zero
            out_ref[0, rows] = mine if i == 0 else out_ref[0, rows] + mine
            if lse_ref is not None:
                lse_ref[0, i, rows] = jnp.broadcast_to(
                    m + jnp.log(l), (block_kv, LANES))

    _each_head(head_block, heads, per_block, pl.num_programs(1), one_head)


def _band_dq_kernel(
    q_ref, k_refs, v_refs, do_ref, o_ref, lse_ref, dq_ref,
    *, block_q: int, block_kv: int, back: int, scale: float, head_dim: int,
    heads: int, groups: int, window: int, seq_len: int,
):
    head_block = pl.program_id(1)
    q_start = pl.program_id(2) * block_q
    per_block = q_ref.shape[-1] // head_dim
    held = block_kv + back

    def one_head(i):
        # everything on the q head's lanes: dQ lands where q lies
        kv_at = _kv_place(head_block, i, groups, per_block)
        for lo in range(0, block_q, block_kv):
            rows = slice(lo, lo + block_kv)
            q, do, o = (_head_tile(ref, i, i, head_dim, rows)
                        for ref in (q_ref, do_ref, o_ref))
            k, v = (_band_tile(refs, lo, lo + held, kv_at, i, head_dim)
                    for refs in (k_refs, v_refs))
            _, ds = _recomputed(
                q, k, v, do, o, lse_ref[0, i, rows, :1],
                scale, True, q_start + lo, q_start + lo - back, block_kv,
                held, _band_reach(q_start + lo, block_kv, window, seq_len))
            mine = jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dq_ref.dtype)
            dq_ref[0, rows] = mine if i == 0 else dq_ref[0, rows] + mine

    _each_head(head_block, heads, per_block, pl.num_programs(1), one_head)


def _band_dkv_kernel(
    q_refs, k_ref, v_ref, do_refs, o_refs, lse_refs, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, block_kv: int, back: int, scale: float, head_dim: int,
    heads: int, groups: int, window: int, seq_len: int,
):
    # q_refs, do_refs, o_refs, lse_refs: the rows of the key block's own
    # positions, then the ``back`` after them.  The streamed axis walks
    # the q head-blocks whose kv heads lie in this kv block, one visit
    # each: ``block_kv`` keys at a time meet the ``block_kv + back``
    # queries that can see them
    kv_start = pl.program_id(2) * block_q
    q_head_block = pl.program_id(1) * groups + pl.program_id(3)
    per_block = k_ref.shape[-1] // head_dim
    met = block_kv + back

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def one_head(i):
        # everything on the kv head's lanes: dK and dV land where k lies
        kv_at = _kv_place(q_head_block, i, groups, per_block)
        for lo in range(0, block_q, block_kv):
            cols = slice(lo, lo + block_kv)
            q, do, o = (_band_tile(refs, lo, lo + met, i, kv_at, head_dim)
                        for refs in (q_refs, do_refs, o_refs))
            lse = _band_rows(lse_refs, lo, lo + met,
                             lambda ref, rows: ref[0, i, rows])[:, :1]
            p, ds = _recomputed(
                q, _head_tile(k_ref, kv_at, kv_at, head_dim, cols),
                _head_tile(v_ref, kv_at, kv_at, head_dim, cols), do, o, lse,
                scale, True, kv_start + lo, kv_start + lo, met, block_kv,
                _band_reach(kv_start + lo, met, window, seq_len))
            # dV += P^T dO
            dv_acc[cols] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # dK += dS^T Q
            dk_acc[cols] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _each_head(q_head_block, heads, per_block, pl.num_programs(1) * groups,
               one_head)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _band_specs(where, S, block_q, block_kv, window, width, ahead,
                lse_heads=0):
    """Block specs of the tiles of one operand of a band step, in the
    order their rows lie: the resident block's own rows, and the ``back``
    rows beside them, one spec a fetched block: after the block's own
    (``ahead``, clamped at the sequence's last block) or before them
    (clamped at block 0).  ``where`` gives a grid step's (batch, resident
    block, head block of the operand); ``lse_heads``: the operand is
    ``[B, H, S, LANES]``, that many heads a block."""
    back, fetch = band_tiles(block_q, block_kv, window)
    n, per = back // fetch, block_q // fetch

    def spec(rows, block_of):
        def at(*ids):
            b, i, h = where(*ids)
            return (b, h, block_of(i), 0) if lse_heads else (
                b, block_of(i), h)
        return pl.BlockSpec(
            (1, lse_heads, rows, LANES) if lse_heads else (1, rows, width),
            at)

    own = [spec(block_q, lambda i: i)]
    if ahead:
        return own + [
            spec(fetch, lambda i, t=t: jnp.minimum(
                (i + 1) * per + t, S // fetch - 1)) for t in range(n)]
    return [spec(fetch, lambda i, t=t: jnp.maximum(i * per - n + t, 0))
            for t in range(n)] + own


def _band_forward(q, k, v, causal, block_q, block_kv, interpret,
                  with_residuals, window):
    """Grid (batch, head block, query block), every axis parallel: a step
    holds a query block and the keys ``[start - back, start + block_q)``
    and writes its output and LSE once."""
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    groups = H // H_kv
    block_q, block_kv = _checked_blocks(S, block_q, block_kv)
    width = _block_lanes(D)
    per_block = width // D
    specs = functools.partial(_band_specs, S=S, block_q=block_q,
                              block_kv=block_kv, window=window, width=width,
                              ahead=False)
    q_spec = specs(lambda b, h, i: (b, i, h))[-1]
    kv_specs = specs(lambda b, h, i: (b, i, h // groups))
    k = k.reshape(B, S, H_kv * D)
    v = v.reshape(B, S, H_kv * D)
    out, lse = pl.pallas_call(
        functools.partial(
            _band_fwd_kernel, block_q=block_q, block_kv=block_kv,
            back=band_tiles(block_q, block_kv, window)[0], scale=D ** -0.5,
            head_dim=D, heads=H, groups=groups, window=window, seq_len=S),
        grid=(B, pl.cdiv(H, per_block), S // block_q),
        in_specs=[q_spec, kv_specs, kv_specs],
        out_specs=[
            q_spec,
            specs(lambda b, h, i: (b, i, h), lse_heads=per_block)[-1]
            if with_residuals else None,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            # lane-broadcast residual: [B, H, S, LANES] (see LANES)
            jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32)
            if with_residuals else None,
        ],
        compiler_params=_band_compiler_params(),
        interpret=interpret,
    )(q.reshape(B, S, H * D), [k] * len(kv_specs), [v] * len(kv_specs))
    out = out.reshape(B, S, H, D)
    if with_residuals:
        return out, lse
    return out


def _band_backward(q, k, v, out, lse, grad_out, causal, block_q, block_kv,
                   interpret, window):
    """dQ on the forward's grid, written once a step; dK/dV with a key
    block resident and the q head-blocks of its GQA group streamed, each
    in one visit over the queries ``[start, start + block_q + back)``."""
    shapes = q.shape, k.shape, v.shape
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    groups = H // H_kv
    block_q, block_kv = _checked_blocks(S, block_q, block_kv)
    width = _block_lanes(D)
    per_block = width // D
    q_head_blocks = pl.cdiv(H, per_block)
    q, k, v, grad_out, out = (
        x.reshape(B, S, -1) for x in (q, k, v, grad_out, out))
    settings = dict(block_q=block_q, block_kv=block_kv,
                    back=band_tiles(block_q, block_kv, window)[0],
                    scale=D ** -0.5, head_dim=D, heads=H, groups=groups,
                    window=window, seq_len=S)
    specs = functools.partial(_band_specs, S=S, block_q=block_q,
                              block_kv=block_kv, window=window, width=width)

    def at_q(b, h, i):
        return b, i, h

    q_spec = specs(at_q, ahead=False)[-1]
    kv_specs = specs(lambda b, h, i: (b, i, h // groups), ahead=False)
    dq = pl.pallas_call(
        functools.partial(_band_dq_kernel, **settings),
        grid=(B, q_head_blocks, S // block_q),
        in_specs=[q_spec, kv_specs, kv_specs, q_spec, q_spec,
                  specs(at_q, ahead=False, lse_heads=per_block)[-1]],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_band_compiler_params(),
        interpret=interpret,
    )(q, [k] * len(kv_specs), [v] * len(kv_specs), grad_out, out, lse)

    def at_group(b, h_kv, j, x):
        # an odd head count: the last kv head-block's last q head-block
        # may not be there (the kernel skips it), so ask for none past it
        return b, j, jnp.minimum(h_kv * groups + x, q_head_blocks - 1)

    q_specs = specs(at_group, ahead=True)
    kv_spec = specs(lambda b, h_kv, j, x: (b, j, h_kv), ahead=True)[0]
    n = len(q_specs)
    dk, dv = pl.pallas_call(
        functools.partial(_band_dkv_kernel, **settings),
        grid=(B, pl.cdiv(H_kv, per_block), S // block_q, groups),
        in_specs=[q_specs, kv_spec, kv_spec, q_specs, q_specs,
                  specs(at_group, ahead=True, lse_heads=per_block)],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_q, width), jnp.float32),
            pltpu.VMEM((block_q, width), jnp.float32),
        ],
        compiler_params=_band_compiler_params(streamed_axes=1),
        interpret=interpret,
    )([q] * n, k, v, [grad_out] * n, [out] * n, [lse] * n)
    return tuple(g.reshape(shape) for g, shape in zip((dq, dk, dv), shapes))


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
