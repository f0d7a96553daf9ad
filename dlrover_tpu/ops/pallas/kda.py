"""Pallas TPU kernels for the gated delta rule with a decay for every
channel (``ops/linear_attention.py`` has the mathematics and the
``jax.numpy`` body these stand in for), forward and backward, in chunks of
64 positions at heads of 128.

In ``jax.numpy`` a chunk's decayed keys (a set for each sub-block), the
pair-by-pair products inside sub-blocks and the triangular solve's
expansion go through HBM, and a scan carries the state between chunks
through HBM as well.  Here a chunk's q, k, v and log decay are read once
into fast memory, and what leaves it is what the next kernel reads.

**The work inside a chunk** (``_chunk_fwd_kernel``, scope ``chunk``; every
grid step its own chunks, all heads).  The decay hazard is the module's
first rule and holds here in a finer form: ``exp(G_i - G_j)`` is never
factorised with a positive exponent and nothing is clamped.  A chunk is
halved again and again, down to single positions (``LEVELS``): at the
level of halves of ``s`` positions a row ``i`` of an upper half meets the
keys ``j`` of the lower half beside it, and the exponent is split at the
last position ``b`` of that lower half, ``(G_i - G_b) + (G_b - G_j)``, both
parts sums of ``g`` over a run of positions and so at most 0.  Every level
is one product on the matrix unit of all rows against all keys, each scaled
by its own part, under the level's mask; a pair on the diagonal has no
decay.  So no pair is left to be taken pair by pair (``SUB`` 1).  The sums
of ``g`` over those runs (and the running sum, and the sum to the chunk's
end) are ONE product of a 0/1 matrix with ``g``, exact to float32: ``g``
goes in as three bfloat16 thirds that add up to it.  The unit
lower-triangular system is solved by products, float32 at ``highest``: the
inverse of ``I + A`` from the inverses of its diagonal blocks, doubled
level by level, the matrices of two chunks or heads side by side in one
product (ten products in a chain are what the kernel's time is made of:
side by side they cost 1.55 ms a layer's forward on a v5e for 2.30 one by
one).

**The state between chunks** (``_state_fwd_kernel``, scope ``state``; the
chunk axis the grid's sequential one): a head's float32 ``[128, 128]`` state
stays in a VMEM scratch from a sequence's first chunk, where it is zeroed,
to its last; it is held transposed, value channels by key channels, so
that the decay to the chunk's end scales its lanes.  Several heads a grid
step, straight-line code: one head's chain runs under the others'.

**The backward pass** is the same two in reverse: ``_state_bwd_kernel``
walks the chunks from the last with the state's gradient resident and
hands each chunk the gradients of what the chunk kernel gave the forward
walk; ``_chunk_bwd_kernel`` takes them through the solve (whose inverse
the forward kept) and the decayed products (computed again, level by
level) to q, k, v, g and beta.  Residuals: the operands, what the chunk
kernel wrote, the chunk-start states and ``U`` in the compute dtype.

**What a rematerialised layer keeps.**  The forward rule names what the
two forward kernels wrote (``kept.py``): the chunk kernel's ``w, u0, qg,
ke, p, t, th`` as ``kda_chunk`` and the state kernel's ``out, u, starts``
as ``kda_state`` (``out`` with them: the layer's gate and output
projection are computed again from it, so without it the state kernel
would run again whatever else is kept).  A decoder layer's
rematerialisation keeps exactly those beside its input, so its backward
pass recomputes the operands (projections, convolutions, the decay) in
``jax.numpy`` and runs neither forward kernel a second time.

Numerics, as the ``jax.numpy`` body's: matmul operands in the operands'
dtype with float32 accumulation, cast where that body casts; ``g``, its
sums, the solve and the state float32; elementwise work float32.

HBM interface: the model's ``[B, S, H, D]`` arrays as ``[B, S, H*D]``, a
head one column block of 128 lanes (as FA2's); ``[64, 64]`` matrices of a
chunk and head as ``[B, H, S, 64]``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.pallas.flash_attention import LANES
from dlrover_tpu.ops.pallas.selected_attention import (
    _compiler_params,
    _head_cols,
)

#: positions of a chunk
CHUNK = 64
#: the halves a chunk is halved into, in positions
LEVELS = (1, 2, 4, 8, 16, 32)
#: positions of the sub-blocks inside which a pair's decay would be taken
#: pair by pair: single positions, so none is
SUB = 1

NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def kernels_take(seq: int, chunk: int, head_dim: int) -> bool:
    """Whether the kernels run a sequence of ``seq`` positions in chunks of
    ``chunk`` at heads of ``head_dim`` key and value channels."""
    return head_dim == LANES and chunk == CHUNK and seq % CHUNK == 0


def _decay_sums() -> np.ndarray:
    """``[8 * 64, 64]`` of 0 and 1: times a chunk's ``g`` it gives, 64 rows
    each, the running sum ``G``, the sum from the next position to the
    chunk's end ``G_last - G``, and for each of ``LEVELS`` the part of the
    exponent that is a position's own: the sum of ``g`` between it and the
    last position ``b`` of its block's lower half (``G_i - G_b`` above,
    ``G_b - G_j`` below: at most 0 where ``g`` is)."""
    i = np.arange(CHUNK)[:, None]
    c = np.arange(CHUNK)[None, :]
    blocks = [c <= i, c > i]
    for half in LEVELS:
        b = i // (2 * half) * (2 * half) + half - 1
        blocks.append((np.minimum(i, b) < c) & (c <= np.maximum(i, b)))
    return np.concatenate(blocks).astype(np.float32)


def _mm(a, b, dims=NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _mm32(a, b, dims=NN):
    """A float32 product as float32 (the solve's)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _sums(zero_one, x, dims=NN):
    """``zero_one`` (bfloat16, 0 and 1) times ``x`` float32, to float32's
    accuracy in three passes: ``x`` as three bfloat16 that add up to it."""
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return (_mm(zero_one, high, dims) + _mm(zero_one, mid, dims)
            + _mm(zero_one, low, dims))


def _decays(sums_ref, g):
    """``(G, G_last - G, [a position's part of the exponent at each of
    LEVELS])``, each ``[64, 128]`` float32."""
    parts = _sums(sums_ref[...], g)
    return [parts[n * CHUNK:(n + 1) * CHUNK]
            for n in range(2 + len(LEVELS))]


def _masks(down: int = 1, across: int = 1):
    """``[64, 64]`` masks, ``down`` copies one under the other and
    ``across`` side by side: first the diagonal (in the first copy down
    alone: ``P`` has one, ``A`` none), then for each of ``LEVELS`` the
    pairs of a row in an upper half with a key in the lower half beside
    it.  Together: ``j <= i``."""
    shape = (down * CHUNK, across * CHUNK)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    i = row & (CHUNK - 1)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1) & (CHUNK - 1)
    masks = [(i == j) & (row < CHUNK)]
    for bits in range(len(LEVELS)):
        masks.append(((i >> bits) == (j >> bits) + 1)
                     & (((j >> bits) & 1) == 0))
    return masks


def _column(block, h):
    """Column ``h`` of ``block`` [64, H] as ``[64, 1]`` (a select: ``h``
    may be a loop's index)."""
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(at == h, block, 0.0), axis=1, keepdims=True)


def _rows(c):
    return pl.ds(c * CHUNK, CHUNK)


def _each_turn(ref, body, heads):
    """``body([head, ...])`` over the 128-lane column blocks of ``ref``
    [1, rows, H*128], ``heads`` of them a turn of the loop: straight-line
    code inside a turn, so that one head's chain of products runs under
    another's elementwise work; in a loop, so that a kernel's code, and
    the time Mosaic takes over it, does not grow with the heads."""
    blocks = ref.shape[2] // LANES

    def turn(t, carry):
        body([t * heads + u for u in range(heads)])
        return carry

    if heads == blocks:
        turn(0, None)
    else:
        jax.lax.fori_loop(0, blocks // heads, turn, None)


def _inverse_pair(a0, a1, masks):
    """``((I + a0)^-1, (I + a1)^-1)`` for two ``[64, 64]`` strictly lower
    matrices: of single positions the inverse is I; of a block twice the
    size, from its two halves' ``T``: ``T - T a_off T``, level by level.
    Two at the price of one on the matrix unit: side by side ``[64, 128]``
    (``masks`` as wide) times their factors one under the other on the
    diagonal of a ``[128, 128]``."""
    own = (jax.lax.broadcasted_iota(jnp.int32, (2 * CHUNK, LANES), 0) >= CHUNK
           ) == (jax.lax.broadcasted_iota(
               jnp.int32, (2 * CHUNK, LANES), 1) >= CHUNK)

    def on_diagonal(x):
        return jnp.where(own, jnp.concatenate([x, x]), 0.0)

    a = jnp.concatenate([a0, a1], axis=1)
    t = jnp.where(masks[0], 1.0, 0.0) - jnp.where(masks[1], a, 0.0)
    for mask in masks[2:]:
        t = t - _mm32(
            _mm32(t, on_diagonal(jnp.where(mask, a, 0.0))), on_diagonal(t))
    return t[:, :CHUNK], t[:, CHUNK:]


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                      w_ref, u0_ref, qg_ref, ke_ref, p_ref, t_ref, th_ref, *,
                      chunks, unroll):
    """grid (batch, chunks a step): per chunk and head ``P``, the inverse
    ``T`` of ``I + A``, ``W`` and ``U0``, q and k decayed from the chunk's
    start and to its end, and the decay through the chunk."""
    masks2, masks_wide = _masks(down=2), _masks(across=2)

    def products(c, h):
        """``(A, Diag(beta) [K exp(G), V])`` of one chunk and head; ``P``,
        the decayed q and k and the decay through the chunk written."""
        rows, cols = _rows(c), _head_cols(h, LANES)
        q, k = q_ref[0, rows, cols], k_ref[0, rows, cols]
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        beta = _column(beta_ref[0, rows, :], h)
        run, to_end, *levels = _decays(sums_ref, g_ref[0, rows, cols])
        # q's rows over k's: P from the first, A from the second
        pa = jnp.where(masks2[0], _mm(jnp.concatenate([q, k]), k, NT), 0.0)
        for mask, part in zip(masks2[1:], levels):
            decay = jnp.exp(part)
            keys = (kf * decay).astype(k.dtype)
            both = jnp.concatenate([(qf * decay).astype(q.dtype), keys])
            pa += jnp.where(mask, _mm(both, keys, NT), 0.0)
        from_start = jnp.exp(run)
        qg_ref[0, rows, cols] = (qf * from_start).astype(qg_ref.dtype)
        ke_ref[0, rows, cols] = (kf * jnp.exp(to_end)).astype(ke_ref.dtype)
        p_ref[0, h, rows, :] = pa[:CHUNK].astype(p_ref.dtype)
        th_ref[0, c, :, cols] = from_start[CHUNK - 1:]
        return beta * pa[CHUNK:], jnp.concatenate(
            [beta * (kf * from_start),
             beta * v_ref[0, rows, cols].astype(jnp.float32)], axis=1)

    def solve(c, h, t, rhs):
        """``[W, U0] = T Diag(beta) [K exp(G), V]``."""
        rows, cols = _rows(c), _head_cols(h, LANES)
        solved = _mm32(t, rhs)
        w_ref[0, rows, cols] = solved[:, :LANES].astype(w_ref.dtype)
        u0_ref[0, rows, cols] = solved[:, LANES:]
        t_ref[0, h, rows, :] = t

    def turn(heads):
        todo = [(c, h) for h in heads for c in range(chunks)]
        for first, second in zip(todo[::2], todo[1::2]):
            (a0, rhs0), (a1, rhs1) = products(*first), products(*second)
            t0, t1 = _inverse_pair(a0, a1, masks_wide)
            solve(*first, t0, rhs0)
            solve(*second, t1, rhs1)
        if len(todo) % 2:    # one left over: beside itself
            a, rhs = products(*todo[-1])
            solve(*todo[-1], _inverse_pair(a, a, masks_wide)[0], rhs)

    _each_turn(q_ref, turn, unroll)


def _state_fwd_kernel(w_ref, u0_ref, qg_ref, ke_ref, p_ref, th_ref,
                      out_ref, u_ref, start_ref, state_ref, *, chunks):
    """grid (batch, heads a step, chunks a step): ``U = U0 - W S``, ``O =
    (Q exp(G)) S + P U``, ``S' = Diag(exp(G_last)) S + (K exp(G_last -
    G))^T U`` with ``S`` resident, transposed ``[value, key]``."""
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype = w_ref.dtype
    for c in range(chunks):
        rows = _rows(c)
        for h in range(state_ref.shape[0]):
            cols = _head_cols(h, LANES)
            state = state_ref[h]
            start = state.astype(dtype)
            start_ref[0, c, h] = start
            u = (u0_ref[0, rows, cols]
                 - _mm(w_ref[0, rows, cols], start, NT)).astype(dtype)
            u_ref[0, rows, cols] = u
            out_ref[0, rows, cols] = (
                _mm(qg_ref[0, rows, cols], start, NT)
                + _mm(p_ref[0, h, rows, :], u)).astype(out_ref.dtype)
            state_ref[h] = state * th_ref[0, c, :, cols] + _mm(
                u, ke_ref[0, rows, cols], TN)


def _state_bwd_kernel(do_ref, qg_ref, ke_ref, p_ref, w_ref, u_ref, start_ref,
                      th_ref, du_ref, dw_ref, dqg_ref, dke_ref, dp_ref,
                      dth_ref, dstate_ref, *, chunks):
    """grid (batch, heads a step, chunks a step FROM THE LAST): the
    gradients of ``U``, ``W``, ``P``, the decayed q and k and the decay
    through the chunk, with the state's gradient resident (transposed as
    the state)."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dtype = w_ref.dtype
    for c in reversed(range(chunks)):
        rows = _rows(c)
        for h in range(dstate_ref.shape[0]):
            cols = _head_cols(h, LANES)
            dstate = dstate_ref[h]
            d_end = dstate.astype(dtype)
            start, u = start_ref[0, c, h], u_ref[0, rows, cols]
            do, p = do_ref[0, rows, cols], p_ref[0, h, rows, :]
            qg, ke = qg_ref[0, rows, cols], ke_ref[0, rows, cols]
            du = (_mm(p, do, TN) + _mm(ke, d_end, NT)).astype(dtype)
            du_ref[0, rows, cols] = du
            dw_ref[0, rows, cols] = (-_mm(du, start)).astype(dtype)
            dqg_ref[0, rows, cols] = _mm(do, start).astype(dtype)
            dke_ref[0, rows, cols] = _mm(u, d_end).astype(dtype)
            dp_ref[0, h, rows, :] = _mm(do, u, NT).astype(dtype)
            dth_ref[0, c, :, cols] = jnp.sum(
                dstate * start.astype(jnp.float32), axis=0, keepdims=True)
            dstate_ref[h] = (
                dstate * th_ref[0, c, :, cols] + _mm(do, qg, TN)
                - _mm(du, w_ref[0, rows, cols], TN))


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, t_ref,
                      w_ref, u0_ref, du_ref, dw_ref, dqg_ref, dke_ref, dp_ref,
                      dth_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                      chunks, unroll):
    """grid (batch, chunks a step): through the solve (``d[K exp(G) beta,
    V beta] = T^T [dW, dU]``, ``dA = -(that) [W, U0]^T``) and the decayed
    products, level by level as the forward, to dq, dk, dv, dg and dbeta.
    ``exp(G_i - G_j)`` has the gradient of its product with ``+`` on ``G_i``
    and ``-`` on ``G_j``: a row's share of dq or dk times q or k, less a
    key's share of dk times k (the split's own position cancels)."""
    masks, masks2 = _masks(), _masks(down=2)
    last_row = jax.lax.broadcasted_iota(
        jnp.int32, (CHUNK, LANES), 0) == CHUNK - 1
    head_of = jax.lax.broadcasted_iota(
        jnp.int32, (CHUNK, dbeta_ref.shape[2]), 1)

    def one(c, h):
        rows, cols = _rows(c), _head_cols(h, LANES)
        q, k = q_ref[0, rows, cols], k_ref[0, rows, cols]
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        vf = v_ref[0, rows, cols].astype(jnp.float32)
        beta = _column(beta_ref[0, rows, :], h)
        run, to_end, *levels = _decays(sums_ref, g_ref[0, rows, cols])
        from_start, till_end = jnp.exp(run), jnp.exp(to_end)
        k_start, k_end = kf * from_start, kf * till_end
        # the solve
        d_rhs = _mm32(t_ref[0, h, rows, :], jnp.concatenate(
            [dw_ref[0, rows, cols].astype(jnp.float32),
             du_ref[0, rows, cols].astype(jnp.float32)], axis=1), TN)
        dkb, dvb = d_rhs[:, :LANES], d_rhs[:, LANES:]
        da = -(_mm32(dkb, w_ref[0, rows, cols].astype(jnp.float32), NT)
               + _mm32(dvb, u0_ref[0, rows, cols], NT))
        # the decayed products: dP over beta dA, as the forward's rows
        d_pa = jnp.concatenate(
            [dp_ref[0, h, rows, :].astype(jnp.float32), beta * da])
        zero = jnp.zeros((CHUNK, LANES), jnp.float32)
        dq_row, dk_row, dk_key = zero, zero, zero
        a_raw = jnp.zeros((CHUNK, CHUNK), jnp.float32)
        for n, (mask, mask2) in enumerate(zip(masks, masks2)):
            d = jnp.where(mask2, d_pa, 0.0).astype(q.dtype)
            if n == 0:      # the diagonal: no decay, and none of A's
                queries, keys, decay = q, k, 1.0
            else:
                decay = jnp.exp(levels[n - 1])
                queries = (qf * decay).astype(q.dtype)
                keys = (kf * decay).astype(k.dtype)
                a_raw += jnp.where(mask, _mm(keys, keys, NT), 0.0)
            by_row = _mm(d, keys)
            dq_row += by_row[:CHUNK] * decay
            dk_row += by_row[CHUNK:] * decay
            dk_key += _mm(d, jnp.concatenate([queries, keys]), TN) * decay
        dkg = beta * dkb
        dqg = dqg_ref[0, rows, cols].astype(jnp.float32)
        dke = dke_ref[0, rows, cols].astype(jnp.float32)
        dq_ref[0, rows, cols] = (dq_row + dqg * from_start).astype(
            dq_ref.dtype)
        dk_ref[0, rows, cols] = (
            dk_row + dk_key + dkg * from_start + dke * till_end).astype(
                dk_ref.dtype)
        dv_ref[0, rows, cols] = (beta * dvb).astype(dv_ref.dtype)
        d_run = (qf * dq_row + kf * (dk_row - dk_key) + dkg * k_start
                 + dqg * (qf * from_start) - dke * k_end)
        d_last = (jnp.sum(dke * k_end, axis=0, keepdims=True)
                  + dth_ref[0, c, :, cols] * from_start[CHUNK - 1:])
        d_run += jnp.where(last_row, d_last, 0.0)
        # g reaches G_i of every later position of its chunk
        dg_ref[0, rows, cols] = _sums(sums_ref[:CHUNK, :], d_run, TN)
        dbeta = (jnp.sum(dkb * k_start + dvb * vf, axis=1, keepdims=True)
                 + jnp.sum(da * a_raw, axis=1, keepdims=True))
        dbeta_ref[0, rows, :] = jnp.where(
            head_of == h, dbeta, dbeta_ref[0, rows, :])

    def turn(heads):
        for h in heads:
            for c in range(chunks):
                one(c, h)

    _each_turn(q_ref, turn, unroll)


class _Call:
    """Shapes and block specs of the four calls for q, k, v, g ``[B, S, H,
    128]`` and beta ``[B, S, H]``; ``tile`` ``(chunks, heads, state_heads)``:
    the chunks a grid step of every kernel, the heads a turn of the chunk
    kernels' loop over them, and the heads a grid step of the kernels that
    walk the chunks (their bodies are a few products each, and one head's
    chain runs under the others'); each cut to a divisor of what there
    is."""

    def __init__(self, q, tile):
        self.B, self.S, self.H, _ = q.shape
        self.N = self.S // CHUNK
        self.chunks = math.gcd(self.N, tile[0])
        self.unroll = math.gcd(self.H, tile[1])
        self.heads = math.gcd(self.H, tile[2])
        self.dtype = q.dtype
        B, S, H, N = self.B, self.S, self.H, self.N
        self.wide, self.square = (B, S, H * LANES), (B, H, S, CHUNK)
        self.through, self.starts = (B, N, 1, H * LANES), (
            B, N, H, LANES, LANES)
        self.sums = jnp.asarray(_decay_sums(), jnp.bfloat16)

    def chunk_specs(self):
        """Every head a step: ``(wide, beta, square, through, sums)``."""
        rows, H = self.chunks * CHUNK, self.H
        return (
            pl.BlockSpec((1, rows, H * LANES), lambda b, n: (b, n, 0)),
            pl.BlockSpec((1, rows, H), lambda b, n: (b, n, 0)),
            pl.BlockSpec((1, H, rows, CHUNK), lambda b, n: (b, 0, n, 0)),
            pl.BlockSpec((1, self.chunks, 1, H * LANES),
                         lambda b, n: (b, n, 0, 0)),
            pl.BlockSpec(self.sums.shape, lambda b, n: (0, 0)))

    def state_specs(self, step_of):
        """``heads`` a step, the grid's third axis over the chunks through
        ``step_of``: ``(wide, square, through, starts)``."""
        rows, heads = self.chunks * CHUNK, self.heads
        return (
            pl.BlockSpec((1, rows, heads * LANES),
                         lambda b, h, n: (b, step_of(n), h)),
            pl.BlockSpec((1, heads, rows, CHUNK),
                         lambda b, h, n: (b, h, step_of(n), 0)),
            pl.BlockSpec((1, self.chunks, 1, heads * LANES),
                         lambda b, h, n: (b, step_of(n), 0, h)),
            pl.BlockSpec((1, self.chunks, heads, LANES, LANES),
                         lambda b, h, n: (b, step_of(n), h, 0, 0)))

    def shape(self, shape, dtype=None):
        return jax.ShapeDtypeStruct(shape, dtype or self.dtype)

    @property
    def chunk_grid(self):
        return (self.B, self.N // self.chunks)

    @property
    def state_grid(self):
        return (self.B, self.H // self.heads, self.N // self.chunks)

    @property
    def state_scratch(self):
        return pltpu.VMEM((self.heads, LANES, LANES), jnp.float32)


def kept_bytes(q) -> dict:
    """What a layer's rematerialisation keeps of the two forward kernels'
    results for ``q`` ``[B, S, H, 128]``, in bytes by name: the chunk
    kernel's ``w, qg, ke`` (as ``q``), ``u0`` (float32), ``p``, ``t``
    (float32) and ``th`` (float32); the state kernel's ``out, u`` and
    ``starts``, as ``q``."""
    item = q.dtype.itemsize
    wide = q.size                       # [B, S, H * 128]
    square = wide * CHUNK // LANES      # [B, H, S, 64]
    through = wide // CHUNK             # [B, S / 64, 1, H * 128]
    starts = wide * LANES // CHUNK      # [B, S / 64, H, 128, 128]
    return {
        kept.KDA_CHUNK: (wide * (3 * item + 4) + square * (item + 4)
                         + through * 4),
        kept.KDA_STATE: (2 * wide + starts) * item,
    }


def _flat(t):
    return t.reshape(t.shape[:2] + (-1,))


def _chunk_forward(call, q, k, v, g, beta, interpret):
    wide, small, square, through, sums = call.chunk_specs()
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, chunks=call.chunks,
                          unroll=call.unroll),
        grid=call.chunk_grid,
        in_specs=[wide, wide, wide, wide, small, sums],
        out_specs=[wide, wide, wide, wide, square, square, through],
        out_shape=[call.shape(call.wide), call.shape(call.wide, f32),
                   call.shape(call.wide), call.shape(call.wide),
                   call.shape(call.square), call.shape(call.square, f32),
                   call.shape(call.through, f32)],
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta, call.sums)


def _state_forward(call, w, u0, qg, ke, p, th, interpret):
    wide, square, through, starts = call.state_specs(lambda n: n)
    return pl.pallas_call(
        functools.partial(_state_fwd_kernel, chunks=call.chunks),
        grid=call.state_grid,
        in_specs=[wide, wide, wide, wide, square, through],
        out_specs=[wide, wide, starts],
        out_shape=[call.shape(call.wide), call.shape(call.wide),
                   call.shape(call.starts)],
        scratch_shapes=[call.state_scratch],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(w, u0, qg, ke, p, th)


def _state_backward(call, do, qg, ke, p, w, u, starts, th, interpret):
    last = call.N // call.chunks - 1
    wide, square, through, starts_spec = call.state_specs(lambda n: last - n)
    return pl.pallas_call(
        functools.partial(_state_bwd_kernel, chunks=call.chunks),
        grid=call.state_grid,
        in_specs=[wide, wide, wide, square, wide, wide, starts_spec, through],
        out_specs=[wide, wide, wide, wide, square, through],
        out_shape=[call.shape(call.wide)] * 4 + [
            call.shape(call.square), call.shape(call.through, jnp.float32)],
        scratch_shapes=[call.state_scratch],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_flat(do), qg, ke, p, w, u, starts, th)


def _chunk_backward(call, q, k, v, g, beta, t, w, u0, grads, interpret):
    wide, small, square, through, sums = call.chunk_specs()
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, chunks=call.chunks,
                          unroll=call.unroll),
        grid=call.chunk_grid,
        in_specs=[wide, wide, wide, wide, small, sums, square, wide, wide,
                  wide, wide, wide, wide, square, through],
        out_specs=[wide, wide, wide, wide, small],
        out_shape=[call.shape(call.wide), call.shape(call.wide),
                   call.shape(call.wide), call.shape(call.wide, f32),
                   call.shape(beta.shape, f32)],
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta, call.sums, t, w, u0,
      *grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, tile, interpret):
    return _kda_fwd(q, k, v, g, beta, tile, interpret)[0]


def _kda_fwd(q, k, v, g, beta, tile, interpret):
    call = _Call(q, tile)
    with jax.named_scope("chunk"):
        w, u0, qg, ke, p, t, th = kept.named(kept.KDA_CHUNK, *_chunk_forward(
            call, q, k, v, g, beta, interpret))
    with jax.named_scope("state"):
        out, u, starts = kept.named(kept.KDA_STATE, *_state_forward(
            call, w, u0, qg, ke, p, th, interpret))
    return out.reshape(v.shape), (
        q, k, v, g, beta, w, u0, qg, ke, p, t, th, u, starts)


def _kda_bwd(tile, interpret, residuals, do):
    q, k, v, g, beta, w, u0, qg, ke, p, t, th, u, starts = residuals
    call = _Call(q, tile)
    with jax.named_scope("state"):
        grads = _state_backward(
            call, do, qg, ke, p, w, u, starts, th, interpret)
    with jax.named_scope("chunk"):
        dq, dk, dv, dg, dbeta = _chunk_backward(
            call, q, k, v, g, beta, t, w, u0, grads, interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


_kda.defvjp(_kda_fwd, _kda_bwd)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def kda_kernels(q, k, v, g, beta, *, tile, interpret: bool = False):
    """q, k, v ``[B, S, H, 128]`` in the compute dtype, g ``[B, S, H, 128]``
    and beta ``[B, S, H]`` float32, ``S`` a multiple of 64 -> ``[B, S, H,
    128]`` in ``v``'s dtype.  ``tile``: ``(chunks, heads, state_heads)`` as
    ``tuning.kda_tiling`` gives it.  Under ``jax.jit`` so that a program
    which traces the model more than once traces the kernels once."""
    return _kda(q, k, v, g, beta, tile, interpret)
