"""Pallas TPU kernels for an indexer's scores of one block of queries, and
for their gradient.

``ops/attention.py::indexed_sparse_attention`` ranks a block's keys by ``I
[B, Q, K]``, ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` over the
``J`` index heads, float32.  In ``jax.numpy`` the products of all heads,
``[B, Q, J, K]`` float32 (268 MB for 512 queries, 16 heads and 8192 keys),
go out to HBM and come back for the relu, the weighting and the sum, and
again for each step of the gradient.  Here one head's ``[Q, block_kv]``
tile of products lives and dies in VMEM, forward and backward, and what
leaves the chip's fast memory is ``I`` and the three gradients.

Numerics, as the ``jax.numpy`` body's: the operands go to the matrix unit
as the model gives them, the products are float32, and relu, weighting and
the sum over the heads are float32 on the vector unit.  No causal logic:
``I`` is only ever read under a mask.

HBM interface, as FA2's at a head size under 128: ``q_I`` ``[B, Q, J, C]``
as ``[B, Q, J*C]`` in column blocks of 128 lanes, ``128 / C`` heads in
each.  A block's heads share the one key head: the kernels get it repeated
across the lanes (``[B, K, 128]``) and keep, for each place of a column
block, a copy with every other place's lanes zero, so a head's product is
one 128-deep contraction of the whole column block and nothing is sliced
inside a vreg.

The gradient is ONE kernel, as the selected attention's: a block of queries
a call, so ``dq_I`` and ``dw`` accumulate over the kv tiles in VMEM while
each tile's ``dk_I``, summed over the heads, is written as the tile is left.
The residuals are the three operands; the products are computed again.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import LANES
from dlrover_tpu.ops.pallas.selected_attention import (
    _compiler_params,
    _head_cols,
    kv_tile,
)


def kernels_take(block: int, index_heads: int, index_dim: int) -> bool:
    """Whether the kernels run a block of ``block`` queries over keys that
    are a multiple of it, at this indexer's heads."""
    return (LANES % index_dim == 0
            and (index_heads * index_dim) % LANES == 0
            and block % LANES == 0)


def _at_place(x, place, dim):
    """``x`` [rows, 128] with every lane outside place ``place`` of the
    column block zero (a select in float32, as FA2's ``_head_tile``)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    mine = (lane >= place * dim) & (lane < (place + 1) * dim)
    return jnp.where(mine, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _product(q, k):
    """[Q, block_kv] float32: the head's ``q . k`` where ``k`` has its
    lanes alone."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _each_column_block(ref, body, carry, unroll):
    """``body(p, carry) -> carry`` over the 128-lane column blocks of
    ``ref`` [1, Q, J*C], ``unroll`` of them straight-line code a turn of
    the loop (``selected_attention._each_head`` has what that buys and
    costs)."""
    blocks = ref.shape[2] // LANES
    unroll = min(unroll, blocks)
    if blocks % unroll:
        raise ValueError(f"{blocks} column blocks, {unroll} a turn")

    def turn(t, carry):
        for u in range(unroll):
            carry = body(t * unroll + u, carry)
        return carry

    if unroll == blocks:
        return turn(0, carry)
    return jax.lax.fori_loop(0, blocks // unroll, turn, carry)


def _spread_weights(w_ref, spread_ref):
    """``w`` [1, Q, J] as ``[J, Q, 128]``, a head's column across the
    lanes (as FA2's per-row scalars): once a call, so that a head's weight
    is a plain read wherever the loop over the heads stands."""
    w = w_ref[0]
    head_of = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    for j in range(w.shape[1]):
        column = jnp.sum(jnp.where(head_of == j, w, 0.0), axis=1,
                         keepdims=True)
        spread_ref[j] = jnp.broadcast_to(column, spread_ref.shape[1:])


def _fwd_kernel(q_ref, k_ref, w_ref, out_ref, spread_ref, *, dim, unroll):
    """grid (batch, kv tile): the heads' weighted relu products summed
    into one ``[Q, block_kv]`` float32 tile, written once."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _spread_weights(w_ref, spread_ref)

    per_block = LANES // dim
    k = [_at_place(k_ref[0], i, dim) for i in range(per_block)]

    def one_column_block(p, total):
        q = q_ref[0, :, _head_cols(p, LANES)]
        for i in range(per_block):
            total = total + spread_ref[p * per_block + i, :, :1] * jnp.maximum(
                _product(q, k[i]), 0.0)
        return total

    out_ref[0] = _each_column_block(
        q_ref, one_column_block, jnp.zeros(out_ref.shape[1:], jnp.float32),
        unroll)


def _bwd_kernel(q_ref, k_ref, w_ref, di_ref, dq_ref, dk_ref, dw_ref, dq_acc,
                spread_ref, *, dim, unroll):
    """grid (batch, kv tile): per head the products computed again, ``g =
    dI w_j [product > 0]``, ``dq_j += g k``, ``dk += g^T q_j``, ``dw_j +=
    rowsum(dI relu(product))``; ``dq`` and ``dw`` accumulated over the
    tiles, the tile's ``dk`` summed over the heads and written."""
    kv_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        dw_ref[0] = jnp.zeros_like(dw_ref[0])
        _spread_weights(w_ref, spread_ref)

    per_block = LANES // dim
    k = [_at_place(k_ref[0], i, dim) for i in range(per_block)]
    di = di_ref[0]
    head_of = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 1)

    def one_column_block(p, dk):
        """``dk`` [block_kv, 128]: a place's lanes hold its heads' sum."""
        q = q_ref[0, :, _head_cols(p, LANES)]
        dq = jnp.zeros(q.shape, jnp.float32)
        for i in range(per_block):
            j = p * per_block + i
            s = _product(q, k[i])
            live = jnp.where(s > 0.0, di, 0.0)
            g = (live * spread_ref[j, :, :1]).astype(q.dtype)
            dq += jax.lax.dot_general(
                g, k[i], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk += jax.lax.dot_general(  # g^T q_j, on the head's own lanes
                g, _at_place(q, i, dim), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw_ref[0] += jnp.where(
                head_of == j, jnp.sum(live * s, axis=1, keepdims=True), 0.0)
        dq_acc[:, _head_cols(p, LANES)] += dq
        return dk

    dk = _each_column_block(
        q_ref, one_column_block,
        jnp.zeros((k_ref.shape[1], LANES), jnp.float32), unroll)
    # the places' sums onto the first place's lanes: the one key head's
    folded = dk
    for i in range(1, per_block):
        folded = folded + pltpu.roll(dk, LANES - i * dim, 1)
    dk_ref[0] = folded[:, :dim].astype(dk_ref.dtype)

    @pl.when(kv_idx == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


class _Call:
    """Shapes, operands and block specs of one call: ``index_q`` [B, Q, J,
    C], ``index_k`` [B, K, C], ``index_w`` [B, Q, J] float32; ``tiling``
    ``(keys a tile at most, column blocks a turn of the loop over them)``
    as ``tuning.index_tiling`` gives it."""

    def __init__(self, index_q, index_k, index_w, tiling):
        self.B, self.Q, self.J, self.C = index_q.shape
        self.K = index_k.shape[1]
        self.tile = kv_tile(self.K, tiling[0])
        self.grid = (self.B, self.K // self.tile)
        wide = self.J * self.C
        self.operands = (
            index_q.reshape(self.B, self.Q, wide),
            jnp.tile(index_k, (1, 1, LANES // self.C)), index_w)
        self.q = pl.BlockSpec((1, self.Q, wide), lambda b, j: (b, 0, 0))
        self.k = pl.BlockSpec((1, self.tile, LANES), lambda b, j: (b, j, 0))
        self.dk = pl.BlockSpec((1, self.tile, self.C), lambda b, j: (b, j, 0))
        self.w = pl.BlockSpec((1, self.Q, self.J), lambda b, j: (b, 0, 0))
        self.scores = pl.BlockSpec((1, self.Q, self.tile),
                                   lambda b, j: (b, 0, j))
        # a head's weight across the lanes, made once a call
        self.spread = pltpu.VMEM((self.J, self.Q, LANES), jnp.float32)
        self.settings = dict(dim=self.C, unroll=tiling[1])


def _forward(index_q, index_k, index_w, tiling, interpret):
    call = _Call(index_q, index_k, index_w, tiling)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **call.settings),
        grid=call.grid,
        in_specs=[call.q, call.k, call.w],
        out_specs=call.scores,
        out_shape=jax.ShapeDtypeStruct((call.B, call.Q, call.K), jnp.float32),
        scratch_shapes=[call.spread],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*call.operands)


def _backward(index_q, index_k, index_w, grad, tiling, interpret):
    call = _Call(index_q, index_k, index_w, tiling)
    dq, dk, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, **call.settings),
        grid=call.grid,
        in_specs=[call.q, call.k, call.w, call.scores],
        out_specs=[call.q, call.dk, call.w],
        out_shape=[
            jax.ShapeDtypeStruct(call.operands[0].shape, index_q.dtype),
            jax.ShapeDtypeStruct(index_k.shape, index_k.dtype),
            jax.ShapeDtypeStruct(index_w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(call.operands[0].shape[1:], jnp.float32),
                        call.spread],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*call.operands, grad)
    return dq.reshape(index_q.shape), dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def index_scores(index_q, index_k, index_w, tiling, interpret: bool = False):
    """``I [B, Q, K]`` float32, ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
    k_I[s])``, for one block of queries ``index_q`` [B, Q, J, C] over the
    one key head ``index_k`` [B, K, C], ``index_w`` [B, Q, J] float32."""
    return _forward(index_q, index_k, index_w, tiling, interpret)


def _scores_fwd(index_q, index_k, index_w, tiling, interpret):
    return (_forward(index_q, index_k, index_w, tiling, interpret),
            (index_q, index_k, index_w))


def _scores_bwd(tiling, interpret, residuals, grad):
    return _backward(*residuals, grad, tiling, interpret)


index_scores.defvjp(_scores_fwd, _scores_bwd)
