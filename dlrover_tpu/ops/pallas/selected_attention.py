"""Pallas TPU kernels for attention over the keys a mask keeps: one block
of queries against the keys up to its last query, forward and backward.

``ops/attention.py::indexed_sparse_attention`` walks the sequence by
blocks of queries; an indexer's selection gives each block a mask ``keep
[B, Q, K]`` that already holds causality.  In ``jax.numpy`` a block's
``[heads, Q, K]`` float32 scores go out to HBM and come back for every
step of mask, softmax, cast and product.  Here a ``[Q, block_kv]`` tile of
scores lives and dies in VMEM, as in ``flash_attention.py``, whose
numerics these kernels share: float32 scores and softmax from the
operands as given, float32 accumulation, the per-row log-sum-exp (LSE)
the backward's residual.

What differs from FA2, and why these are kernels of their own:

* the mask is an operand, applied as an additive ``0 / NEG_INF`` tile
  where FA2 compares two iotas, so there is no causal logic, no diagonal
  case and nothing to skip on;
* a grid step takes one kv tile for ALL the query heads of a kv head (a
  GQA group, 8 heads at Keye's widths), one after the other under the one
  mask tile and the one k and v tile it fetched: the mask is read once a
  group, not once a head;
* there is one block of queries a call, so the backward is ONE kernel:
  dQ accumulates over the kv tiles in VMEM while each tile's dK and dV,
  summed over the group, are written as the tile is left.  FA2's split
  recomputes scores and probabilities twice because its dQ and dK/dV
  accumulate along different axes of a grid of q blocks by kv blocks;
* a third kernel gives the mean over the heads of the probabilities,
  ``target [B, Q, K]`` float32, that the indexer's loss is taught from:
  it walks the groups innermost and sums ``exp(s - lse) / heads`` into a
  resident ``[Q, block_kv]`` tile.

HBM interface, as FA2's: the model's ``[B, S, H, D]`` arrays as ``[B, S,
H*D]``, a group's heads the column block ``[Q, group*D]``.  Head size 128
only: a head is one 128-lane column block.

A row of ``keep`` with no key at all reads as the ``jax.numpy`` path
does (the mean of ``v``); ``indexed_sparse_attention`` never makes one.

Of a block's work in ``indexed_sparse_attention`` these kernels are the
attention under ``keep`` and the heads' mean; the index scores ``I`` that
``keep`` is chosen from, and their gradient, are the kernels of
``index_scores.py``; the threshold searches that make ``keep`` from ``I``
and the elementwise part of the indexer's loss (a masked ``log_softmax`` of
``I`` against the heads' mean) stay ``jax.numpy``.

Two entry points over the forward and backward kernels, by what the
caller's model has: ``selected_attention`` (an indexer to teach: the
heads' mean with the output) and ``masked_attention`` (none: the output
and the LSE).  The second has one model caller, ``ops/attention.py::
eva_attention`` (the attention under the block-diffusion mask, which called
it without ``shared`` once a block and half until PR 58, has kernels of its
own: a mask that a rule on positions gives is made inside them,
``block_diffusion_attention.py``): once a
window of 2048 queries, a key head a query head, its window's keys under
a causal ``keep`` and the summaries of earlier windows as ``shared`` keys
that every query attends to: ``2048 + 128 w`` keys have no even tiling
(17, 19 and 23 times 128), the window's 2048 have, and a kernel's last
grid step takes the summaries whole.

**What is kept for the backward pass.**  Both entry points' custom
gradient holds ``(q, k, v, keep, out, lse)`` (and ``shared``), the LSE as
``[B, H, Q]`` float32: the kernels read and write it lane-broadcast ``[B,
H, Q, 128]``, 128 times the bytes, and the backward broadcasts it again on
its way in.  ``out`` and that LSE carry the names ``attn_out`` and
``attn_lse`` (``kept.py``), which a rematerialised decoder layer keeps
beside its input: its backward pass recomputes ``q, k, v, keep`` and
``shared`` in ``jax.numpy`` and does NOT run the forward kernel again.
``selected_attention``'s ``target`` (``[Q, K]`` float32 a block) has no
name: the heads' mean kernel runs again, from the kept LSE.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.pallas.flash_attention import LANES, NEG_INF

KERNEL_HEAD_DIM = LANES

# A group of 8 heads at 512 queries by 2048 keys compiles for a v5e under
# 40 MiB in every kernel (the backward holds most: q, dO and O blocks twice
# over, the LSE, three float32 accumulators and a tile's scores,
# probabilities and their gradients at 4 MiB each); Mosaic's default is 16
# MiB of a v5e core's 128.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def kernels_take(block: int, head_dim: int, heads: int, kv_heads: int) -> bool:
    """Whether the kernels run a block of ``block`` queries over keys that
    are a multiple of it, at these heads."""
    return (head_dim == KERNEL_HEAD_DIM and block % LANES == 0
            and heads % kv_heads == 0)


def kv_tile(keys: int, block_kv: int) -> int:
    """The largest multiple of 128 lanes that divides ``keys`` and is no
    more than ``block_kv``."""
    tile = min(block_kv, keys) // LANES * LANES
    while tile > LANES and keys % tile:
        tile -= LANES
    if tile < LANES or keys % tile:
        raise ValueError(f"{keys} keys are not a multiple of {LANES}")
    return tile


def _compiler_params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _bias(keep_ref):
    """[Q, block_kv] float32: 0 on a kept key, ``NEG_INF`` elsewhere.
    Added to finite scores it leaves a kept one as it is and makes any
    other ``NEG_INF`` exactly."""
    return (keep_ref[0].astype(jnp.float32) - 1.0) * -NEG_INF


def _scores(q, k, scale, bias):
    """``bias`` ``None``: every key attended to."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    return s if bias is None else s + bias


def _whole_tiles(ref, tile):
    """A resident ``[1, keys, D]`` block in turns of at most ``tile`` keys
    (the last one what is left): what a kernel's last grid step visits of
    the keys every query attends to."""
    keys = ref.shape[1]
    return [ref[0, pl.ds(at, min(tile, keys - at)), :]
            for at in range(0, keys, tile)]


def _head_cols(r, head_dim):
    """The lanes of head ``r`` of a group's column block."""
    if isinstance(r, int):
        return pl.ds(r * head_dim, head_dim)
    return pl.ds(pl.multiple_of(r * head_dim, head_dim), head_dim)


def _each_head(group, body, carry=None, unrolled=False):
    """``body(r, carry) -> carry`` over a group's heads.  In a loop, a
    kernel's code, and the time Mosaic takes over it, does not grow with
    the group; ``unrolled``, the heads are straight-line code and the
    compiler runs one head's matmul under another's exponentials.  On a v5e
    at 8 heads (``fa_tuned.json`` has the sweep): unrolled, the heads' mean
    takes 23 ms a step for 47 in a loop at any tile, the forward 52 for 61
    at tiles of 2048 keys, the backward (five matmuls a head) 54 either
    way; and a step whose every kernel is unrolled at 2048 compiles in
    140-160 s for 64.  So only the heads' mean is unrolled, at a small tile
    of its own: its code is the least, and it has no reduction along a row
    that a wide tile would spread over more keys."""
    if unrolled:
        for r in range(group):
            carry = body(r, carry)
        return carry
    return jax.lax.fori_loop(0, group, body, carry)


def _fwd_kernel(q_ref, k_ref, v_ref, keep_ref, *rest,
                scale, group, head_dim):
    """grid (batch, kv head, kv tile): online softmax over the tiles for
    the ``group`` query heads of the kv head.  ``rest``: the results and
    scratch, after ``(k, v)`` of the keys every query attends to where the
    caller has such (``_Block.shared``), which the last grid step visits
    after its tile."""
    *shared, out_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def visit(k, v, bias):
        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            s = _scores(q_ref[0, :, cols], k, scale, bias)
            m_prev = m_ref[r, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row with no kept key so far has m_new == NEG_INF and p == 1:
            # the first kept key's correction, exp(NEG_INF - m), wipes it
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_ref[r, :, :1] * correction + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[:, cols] = (
                acc_ref[:, cols] * correction + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        _each_head(group, one_head)

    bias = _bias(keep_ref)
    visit(k_ref[0], v_ref[0], bias)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        if shared:
            tile = k_ref.shape[1]
            for k, v in zip(*(_whole_tiles(ref, tile) for ref in shared)):
                visit(k, v, None)

        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            l = l_ref[r, :, :1]  # at least 1: the row's largest score
            out_ref[0, :, cols] = (acc_ref[:, cols] / l).astype(out_ref.dtype)
            lse_ref[0, r] = jnp.broadcast_to(
                m_ref[r, :, :1] + jnp.log(l), lse_ref.shape[2:])

        _each_head(group, one_head)


def _target_kernel(q_ref, k_ref, keep_ref, lse_ref, target_ref,
                   *, scale, group, head_dim, heads):
    """grid (batch, kv tile, kv head): the groups innermost, each adding
    its heads' probabilities over ``heads`` to the resident tile."""
    kv_head = pl.program_id(2)

    @pl.when(kv_head == 0)
    def _init():
        target_ref[0] = jnp.zeros_like(target_ref[0])

    bias = _bias(keep_ref)
    k = k_ref[0]

    def one_head(r, total):
        s = _scores(q_ref[0, :, _head_cols(r, head_dim)], k, scale, bias)
        return total + jnp.exp(s - lse_ref[0, kv_head * group + r, :, :1])

    total = _each_head(group, one_head, jnp.zeros_like(bias), unrolled=True)
    target_ref[0] += total * (1.0 / heads)


def _bwd_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, o_ref, lse_ref, *rest,
                scale, group, head_dim):
    """grid (batch, kv head, kv tile): probabilities recomputed from (q,
    k, lse) under the mask; dQ of the group accumulated over the tiles,
    the tile's dK and dV summed over the group and written.  ``rest`` as
    the forward's: with ``(k, v)`` of the keys every query attends to come
    their ``(dk, dv)`` after the other results, written by the last grid
    step."""
    shared = rest[:(len(rest) - 5) // 2]
    dq_ref, dk_ref, dv_ref, *shared_grads, dq_acc, delta_ref = rest[len(shared):]
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            delta = jnp.sum(
                do_ref[0, :, cols].astype(jnp.float32)
                * o_ref[0, :, cols].astype(jnp.float32),
                axis=-1, keepdims=True)
            delta_ref[r] = jnp.broadcast_to(delta, delta_ref.shape[1:])

        _each_head(group, one_head)

    def visit(k, v, bias):
        """dQ accumulated; ``(dk, dv)`` of these keys, float32."""
        def one_head(r, sums):
            dk, dv = sums
            cols = _head_cols(r, head_dim)
            q, do = q_ref[0, :, cols], do_ref[0, :, cols]
            p = jnp.exp(_scores(q, k, scale, bias) - lse_ref[0, r, :, :1])
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[r, :, :1]) * scale
            dq_acc[:, cols] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv += jax.lax.dot_general(  # P^T dO
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk += jax.lax.dot_general(  # dS^T Q
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        zero = jnp.zeros(k.shape, jnp.float32)
        return _each_head(group, one_head, (zero, zero))

    bias = _bias(keep_ref)
    dk, dv = visit(k_ref[0], v_ref[0], bias)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        if shared:
            tile, at = k_ref.shape[1], 0
            for k, v in zip(*(_whole_tiles(ref, tile) for ref in shared)):
                rows = pl.ds(at, k.shape[0])
                for ref, grad in zip(shared_grads, visit(k, v, None)):
                    ref[0, rows, :] = grad.astype(ref.dtype)
                at += k.shape[0]
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


class _Block:
    """Shapes and block specs of one call: ``q`` [B, Q, H, D], ``k`` [B,
    K, G, D], tiles of at most ``block_kv`` keys; ``shared`` ``None`` or
    ``(k, v)`` [B, keys, G, D] of the keys every query attends to.
    ``where`` gives a grid step's (batch, kv head, kv tile)."""

    def __init__(self, q, k, block_kv, shared=None):
        self.B, self.Q, self.H, self.D = q.shape
        self.K, self.G = k.shape[1:3]
        self.group = self.H // self.G
        self.tile = kv_tile(self.K, block_kv)
        self.tiles = self.K // self.tile
        self.shared = [_flat(x) for x in shared or ()]
        self.settings = dict(scale=self.D ** -0.5, group=self.group,
                             head_dim=self.D)

    def specs(self, where):
        def at(pick):
            return lambda *ids: pick(*where(*ids))

        wide = self.group * self.D
        return dict(
            q=pl.BlockSpec((1, self.Q, wide), at(lambda b, g, j: (b, 0, g))),
            kv=pl.BlockSpec((1, self.tile, self.D),
                            at(lambda b, g, j: (b, j, g))),
            keep=pl.BlockSpec((1, self.Q, self.tile),
                              at(lambda b, g, j: (b, 0, j))),
            # lane-broadcast per-row scalars, as FA2's: [B, H, Q, LANES]
            lse=pl.BlockSpec((1, self.group, self.Q, LANES),
                             at(lambda b, g, j: (b, g, 0, 0))),
            # whole and resident while a kv head's tiles go by
            shared=[pl.BlockSpec((1, x.shape[1], self.D),
                                 at(lambda b, g, j: (b, 0, g)))
                    for x in self.shared],
        )


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def kept_bytes(q) -> dict:
    """What a layer's rematerialisation keeps of the forward kernel's
    calls over all of ``q``'s rows ``[B, rows, H, D]``, in bytes by name:
    ``out`` as ``q`` and the LSE ``[B, H, rows]`` float32."""
    B, rows, H, _ = q.shape
    return {kept.ATTN_OUT: kept.nbytes(q.shape, q.dtype),
            kept.ATTN_LSE: kept.nbytes((B, H, rows), jnp.float32)}


def _lanes(lse):
    """The kept LSE ``[B, H, Q]`` as the kernels read it: lane-broadcast
    ``[B, H, Q, LANES]``."""
    return jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))


def _attend_kept(q, k, v, keep, shared, block_kv, interpret):
    """``_attend`` with its results under the names a rematerialised layer
    keeps: ``(out [B, Q, H, D], lse [B, H, Q])``.  The kernel writes every
    lane of a row's LSE alike, so lane 0 is all of it."""
    out, lse = _attend(q, k, v, keep, shared, block_kv, interpret)
    return (*kept.named(kept.ATTN_OUT, out),
            *kept.named(kept.ATTN_LSE, lse[..., 0]))


def _attend(q, k, v, keep, shared, block_kv, interpret):
    """``(out [B, Q, H, D], lse [B, H, Q, LANES])``; ``keep`` int8."""
    blk = _Block(q, k, block_kv, shared)
    B, Q, H, D = blk.B, blk.Q, blk.H, blk.D
    spec = blk.specs(lambda b, g, j: (b, g, j))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **blk.settings),
        grid=(B, blk.G, blk.tiles),
        in_specs=[spec["q"], spec["kv"], spec["kv"], spec["keep"],
                  *spec["shared"]],
        out_specs=[spec["q"], spec["lse"]],
        out_shape=[jax.ShapeDtypeStruct((B, Q, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Q, LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((Q, blk.group * D), jnp.float32),
            pltpu.VMEM((blk.group, Q, LANES), jnp.float32),
            pltpu.VMEM((blk.group, Q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), keep, *blk.shared)
    return out.reshape(q.shape), lse


def _heads_mean(q, k, keep, lse, block_kv, interpret):
    """``target [B, Q, K]`` float32: tiles of its own, the groups
    innermost; every head's LSE resident, fetched once a call."""
    blk = _Block(q, k, block_kv)
    B, Q, H, K = blk.B, blk.Q, blk.H, blk.K
    spec = blk.specs(lambda b, j, g: (b, g, j))
    return pl.pallas_call(
        functools.partial(_target_kernel, heads=H, **blk.settings),
        grid=(B, blk.tiles, blk.G),
        in_specs=[spec["q"], spec["kv"], spec["keep"],
                  pl.BlockSpec((1, H, Q, LANES), lambda b, j, g: (b, 0, 0, 0))],
        out_specs=spec["keep"],
        out_shape=jax.ShapeDtypeStruct((B, Q, K), jnp.float32),
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_flat(q), _flat(k), keep, lse)


def _backward(q, k, v, keep, shared, out, lse, grad_out, block_kv, interpret):
    """``(dq, dk, dv, the gradients of shared)`` in their operands'
    shapes."""
    blk = _Block(q, k, block_kv, shared)
    B, Q, H, D, K = blk.B, blk.Q, blk.H, blk.D, blk.K
    spec = blk.specs(lambda b, g, j: (b, g, j))
    dq, dk, dv, *shared_grads = pl.pallas_call(
        functools.partial(_bwd_kernel, **blk.settings),
        grid=(B, blk.G, blk.tiles),
        in_specs=[spec["q"], spec["kv"], spec["kv"], spec["keep"],
                  spec["q"], spec["q"], spec["lse"], *spec["shared"]],
        out_specs=[spec["q"], spec["kv"], spec["kv"], *spec["shared"]],
        out_shape=[jax.ShapeDtypeStruct((B, Q, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, K, blk.G * D), k.dtype),
                   jax.ShapeDtypeStruct((B, K, blk.G * D), v.dtype),
                   *(jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in blk.shared)],
        scratch_shapes=[
            pltpu.VMEM((Q, blk.group * D), jnp.float32),
            pltpu.VMEM((blk.group, Q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), keep, _flat(grad_out), _flat(out), lse,
      *blk.shared)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            shared and tuple(
                g.reshape(x.shape) for g, x in zip(shared_grads, shared)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def selected_attention(q, k, v, keep, tiling, interpret: bool = False):
    """One block of queries ``q`` [B, Q, H, D] over ``k``/``v`` [B, K, G,
    D] (GQA) where ``keep`` [B, Q, K] bool allows: ``(out [B, Q, H, D],
    target [B, Q, K] float32)``, ``target`` the mean over the heads of the
    attention's probabilities.  ``target`` carries no gradient: what comes
    back for it is dropped, as under ``stop_gradient``.  ``tiling``:
    ``(block_kv, mean_block_kv)`` as ``tuning.selected_tiling`` gives it."""
    return _selected_fwd(q, k, v, keep, tiling, interpret)[0]


def _selected_fwd(q, k, v, keep, tiling, interpret):
    keep = keep.astype(jnp.int8)  # the kernels' mask, a residual
    out, lse = _attend_kept(q, k, v, keep, None, tiling[0], interpret)
    # from the kept LSE: a rematerialised layer runs this kernel again
    # (``target`` has no name: ``[Q, K]`` float32 a block) and not the
    # forward one
    target = _heads_mean(q, k, keep, _lanes(lse), tiling[1], interpret)
    return (out, target), (q, k, v, keep, out, lse)


def _selected_bwd(tiling, interpret, residuals, grads):
    q, k, v, keep, out, lse = residuals
    dq, dk, dv, _ = _backward(
        q, k, v, keep, None, out, _lanes(lse), grads[0], tiling[0], interpret)
    return dq, dk, dv, None


selected_attention.defvjp(_selected_fwd, _selected_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def masked_attention(q, k, v, keep, shared, block_kv, interpret: bool = False):
    """``selected_attention`` for a model with no indexer to teach: ``(out
    [B, Q, H, D], lse [B, H, Q] float32)``, the log of each row's sum of
    exponentiated scores, and no heads' mean.  ``shared``: ``None``, or
    ``(k, v)`` [B, keys, G, D] of further keys that EVERY query attends
    to (``keep`` says nothing of them): a kernel's last grid step visits
    them, in turns of at most a tile, so ``k``'s own keys keep their even
    tiles whatever their number.  The same kernels and residuals (``q, k,
    v, keep, out, lse``, and ``shared``); ``lse`` carries no gradient."""
    return _masked_fwd(q, k, v, keep, shared, block_kv, interpret)[0]


def _masked_fwd(q, k, v, keep, shared, block_kv, interpret):
    keep = keep.astype(jnp.int8)
    out, lse = _attend_kept(q, k, v, keep, shared, block_kv, interpret)
    return (out, lse), (q, k, v, keep, shared, out, lse)


def _masked_bwd(block_kv, interpret, residuals, grads):
    q, k, v, keep, shared, out, lse = residuals
    dq, dk, dv, shared_grads = _backward(
        q, k, v, keep, shared, out, _lanes(lse), grads[0], block_kv,
        interpret)
    return dq, dk, dv, None, shared_grads


masked_attention.defvjp(_masked_fwd, _masked_bwd)
