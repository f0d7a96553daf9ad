"""Pallas TPU kernels for the core of differential attention in its
head-paired form (arXiv:2410.05258; Phi-4-mini-flash's model code): for a
pair of query heads ``(q1, q2)`` of 64 against a pair of key heads ``(k1,
k2)`` of 64 and ONE value of 128, ``O1 = softmax(q1 k1^T / 8) V`` and ``O2
= softmax(q2 k2^T / 8) V`` under the causal mask (and a window), the
layer's result ``O1 - lam O2``.  Two kernels: the forward, which visits a
query tile and a key tile once for BOTH maps, and ONE backward call of
eight block products a live pair of tiles and head pair.

Why kernels of their own: FA2 takes one head size for q, k and v and knows
no pair.  Through it (until PR 60) the two maps were 40 unrelated heads of
128: q and k padded with 64 columns of zeros, ``V`` written twice, and a
backward that computed ``dP = dO V^T`` and ``dV = P^T dO`` once a map, ten
products.  Here the operands are the model's own arrays:

* ``q [B, S, H, 64]`` seen as ``[B, S, H*64]``: a 128-lane column block IS
  ``[q1_j | q2_j]`` (heads ``2j, 2j+1``); ``k`` likewise ``[k1_m | k2_m]``;
  ``v [B, S, G, 64]`` seen as ``[B, S, (G/2)*128]``: a block IS ``V_m``.
  Query pair ``j`` reads key pair ``j // group``.  No pad, no concatenate,
  no factor outside (``64 ** -0.5`` is a power of two: the kernels scale
  the resident operand's halves by it, exactly), no second copy of ``V``.
* a map's scores contract over its own 64 lanes: the RESIDENT operand is
  held twice in VMEM, once with each half selected to zero (``q`` in the
  forward, ``k`` in the backward: a select a resident tile, never one a
  step), so ``[q1 | 0] [k1 | k2]^T = q1 k1^T`` is one 128-deep product
  (which takes what a 64-deep one takes on this matrix unit) and ``dS1 [k1
  | 0] + dS2 [0 | k2]`` IS ``[dQ1 | dQ2]``.
* the layer's result is ``O1 - lam O2`` over ONE value, so the cotangents
  are ``dO1 = dO`` and ``dO2 = -lam dO``: ``dP = dO V^T`` is one product for
  both maps (``dP2 = -lam dP``) and ``dV = (P1 - lam P2)^T dO`` is one.  With
  ``S1``, ``S2``, ``dQ1``, ``dQ2``, ``dK1``, ``dK2``: eight.  The custom
  gradient stands around the DIFFERENCE for that reason; a step makes the
  two ``delta`` row sums (``sum(dO O1)``, ``sum(dO O2)``) from the tiles it
  holds, and ``d lam = -sum(dO O2)`` is one ``jax.numpy`` reduction outside.

The mask is a rule on positions (``0 <= r - c < window``), so the kernels
make it, and only where it cuts.  A grid step is a query tile and a key
tile at a distance ``d = first row - first key``; of the distances a call
can meet (``Walk``, a static table) a step is FULL (every pair allowed: NO
mask, one visit of the whole tile), CUT (the diagonal, a window's far edge:
walked by sub-tiles of ``sub`` rows against the keys those rows can see,
dead sub-tiles skipped, the mask an additive ``0 / NEG_INF`` tile from one
``iota``, made once a visit for both maps) or not walked at all: under a
window the streamed axis has only as many steps as the widest band touches
(``flash_attention.py::band_steps`` and its index helpers, imported).  One
pair of kernels serves the window layers, the whole layer and the cross
layer.

Numerics, ``flash_attention.py``'s: operands as given (bfloat16 in
training), float32 scores, softmax statistics and accumulators, the
probabilities cast to the operands' dtype at the value product, the per-row
log-sum-exp (LSE) the backward's residual.

The backward (``latent_attention.py``'s way): a KEY tile resident with its
two halves selected and the accumulators of ``dK1``, ``dK2`` and ``dV``; the
query tiles of its group's pairs streamed past it; ``dQ`` a float32 ``[B,
S, (H/2)*128]`` result that stays in HBM, whose block a live step copies
in (zeros at the block's first visit: nothing zero-fills it), adds to and
copies out under the products after it, cast once after the call.

What a rematerialised layer keeps (``kept.py``): ``O1`` and ``O2`` in the
compute dtype and the two LSEs as a row a head, ``[B, H, S]`` float32
(never lane-broadcast: the kernels turn a tile's round in VMEM); its
backward pass recomputes q, k and v in ``jax.numpy`` and does not run the
forward kernel again.
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
    _first_kv_block,
    _first_q_block,
    _last_kv_block,
    _last_q_block,
    _streamed_kv_block,
    band_steps,
)
from dlrover_tpu.ops.pallas.selected_attention import _flat

#: the width of a map's scores; a pair, and its value, fill a column block
HEAD_DIM = LANES // 2

# the backward kernel holds q, dO, O1 and O2 tiles, two key tiles, the key
# tile's two selected halves, three accumulators, three dQ tiles and,
# ``[tile, tile]`` float32 each, two maps' scores, probabilities and
# gradients and dP: 1024 x 1024 tiles compile for a v5e well under this
# half of its core's 128 MiB
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: ``(query tile, key tile, sub-tile)`` of a call without a window and of
#: one under a window, on a v5e (PERF.md section 6, PR 60, has the sweep)
WHOLE_TILES = (1024, 1024, 256)
WINDOW_TILES = (2048, 2048, 256)


def kernels_take(seq_len: int, head_dim: int, heads: int,
                 kv_heads: int) -> bool:
    """Whether the kernels run the differential core at this shape."""
    return (head_dim == HEAD_DIM and seq_len % LANES == 0
            and heads % 2 == 0 and kv_heads % 2 == 0
            and heads % kv_heads == 0)


def tiles_for(seq_len: int, window=None):
    """``(query tile, key tile, sub-tile)`` of a call: the table's, each
    shrunk to a divisor of ``seq_len`` (the sub-tile: of both tiles)."""
    wanted = WHOLE_TILES if window is None or window >= seq_len else (
        WINDOW_TILES)
    tile_q, tile_kv, sub = (min(n, seq_len) for n in wanted)
    while seq_len % tile_q:
        tile_q -= LANES
    while seq_len % tile_kv:
        tile_kv -= LANES
    while tile_q % sub or tile_kv % sub:
        sub -= LANES
    return tile_q, tile_kv, sub


class Walk:
    """Which pairs of a query tile and a key tile a call walks, and how:
    static, from the shapes.  ``distance = first row - first key``; a
    tile's pairs lie at ``distance - (tile_kv - 1) <= r - c <= distance +
    tile_q - 1`` and are allowed where ``0 <= r - c < window``."""

    def __init__(self, seq_len, tile_q, tile_kv, sub, window):
        if (seq_len % tile_q or seq_len % tile_kv or tile_q % sub
                or tile_kv % sub):
            raise ValueError(
                f"{seq_len} positions in tiles of {tile_q} x {tile_kv} by "
                f"sub-tiles of {sub}")
        self.seq_len, self.sub = seq_len, sub
        self.tile_q, self.tile_kv = tile_q, tile_kv
        # a window over the sequence cuts nothing
        self.window = None if window is None or window >= seq_len else window
        self.num_q, self.num_kv = seq_len // tile_q, seq_len // tile_kv
        if self.window is None:
            self.kv_steps, self.q_steps = self.num_kv, self.num_q
        else:
            self.kv_steps, self.q_steps = band_steps(
                seq_len, tile_q, tile_kv, self.window)
        distances = [i * tile_q - j * tile_kv
                     for i in range(self.num_q) for j in range(self.num_kv)]
        kinds = [self._kind(distance) for distance in distances]
        #: the distances whose tile the mask cuts, each a branch of a
        #: kernel with its own static visits
        self.cut = sorted({distance for distance, kind in zip(
            distances, kinds) if kind == "cut"})
        self.any_full = "full" in kinds
        #: a head's live tiles, and the grid steps its forward pass takes
        self.tiles_live = len(kinds) - kinds.count("dead")
        self.tiles_walked = self.num_q * self.kv_steps

    def _span(self, distance, rows, keys):
        """``(least, most)`` of ``r - c`` over ``rows x keys`` of a tile."""
        return (distance + rows[0] - (keys[1] - 1),
                distance + rows[1] - 1 - keys[0])

    def _allowed(self, least, most):
        """``"dead"``, ``"full"`` or ``"cut"`` of a span of ``r - c``."""
        window = self.window
        if most < 0 or (window is not None and least >= window):
            return "dead"
        if least >= 0 and (window is None or most < window):
            return "full"
        return "cut"

    def _kind(self, distance):
        return self._allowed(*self._span(
            distance, (0, self.tile_q), (0, self.tile_kv)))

    def is_full(self, distance):
        """Traced: whether the tile at ``distance`` needs no mask."""
        full = distance >= self.tile_kv - 1
        if self.window is not None:
            full &= distance + self.tile_q - 1 < self.window
        return full

    def visits(self, distance):
        """``(rows, keys, masked)`` of a cut tile's visits: ``sub`` rows at
        a time against the sub-tiles of keys they can see, which lie side
        by side (a band is convex), as ``slice``s of the tile."""
        found, sub = [], self.sub
        for r0 in range(0, self.tile_q, sub):
            rows = (r0, r0 + sub)
            kinds = {k0: self._allowed(*self._span(
                distance, rows, (k0, k0 + sub)))
                for k0 in range(0, self.tile_kv, sub)}
            seen = [k0 for k0, kind in kinds.items() if kind != "dead"]
            if seen:
                found.append((
                    slice(*rows), slice(seen[0], seen[-1] + sub),
                    any(kinds[k0] == "cut" for k0 in seen)))
        return found

    def bias(self, distance, rows, keys):
        """``[rows, keys]`` float32: 0 on an allowed pair, ``NEG_INF``
        elsewhere."""
        shape = (rows.stop - rows.start, keys.stop - keys.start)
        ahead = (distance + rows.start - keys.start
                 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        keep = ahead >= 0
        if self.window is not None:
            keep &= ahead < self.window
        return jnp.where(keep, 0.0, NEG_INF)

    def each_visit(self, distance, visit):
        """``visit(rows, keys, bias, before, last)`` over what the tile at
        the traced ``distance`` multiplies (``before``: the rows its
        earlier visits took; ``last``: whether it is the tile's last): a
        branch a kind, straight-line code inside.  A tile the walk does not
        reach (a step past the diagonal or the band, whose index maps
        clamp) matches none."""
        def cut(static):
            def run():
                found = self.visits(static)
                for n, (rows, keys, masked) in enumerate(found):
                    visit(rows, keys,
                          self.bias(static, rows, keys) if masked else None,
                          [seen for seen, _, _ in found[:n]],
                          n == len(found) - 1)
            return run

        for static in self.cut:
            pl.when(distance == static)(cut(static))
        if self.any_full:
            pl.when(self.is_full(distance))(lambda: visit(
                slice(0, self.tile_q), slice(0, self.tile_kv), None, [],
                True))


#: ``64 ** -0.5``, a power of two: times it, a bfloat16 operand rounds
#: nowhere and a float32 product's sum is the scaled sum to the bit, so the
#: kernels scale the RESIDENT operand's halves once a tile (``_halves``) and
#: never a tile of scores
SCALE = HEAD_DIM ** -0.5


def _first_head():
    """``[1, LANES]`` bool: the lanes of a block's first head."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) < HEAD_DIM


def _halves(x):
    """``SCALE`` times ``([x1 | 0], [0 | x2])`` of a tile ``[x1 | x2]`` of
    two heads."""
    x, zero = x * jnp.asarray(SCALE, x.dtype), jnp.zeros_like(x)
    return (jnp.where(_first_head(), x, zero),
            jnp.where(_first_head(), zero, x))


def _scores(a, b, bias):
    """``a b^T``, float32, under the visit's mask; one of the two carries
    the scale."""
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return s if bias is None else s + bias


def _column(row, rows):
    """A row of per-position scalars ``[1, rows]`` as a column in every
    lane, ``[rows, LANES]``."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, rows)))


def _compiler_params(resident_axis: str):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", resident_axis,
                             "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fwd_kernel(q_ref, k_ref, v_ref, o1_ref, o2_ref, lse_ref, q_halves,
                acc_ref, m_ref, l_ref, *, walk):
    """grid (batch, query pair, query tile, step): the online softmax of
    both maps over the key tiles the query tile can see, against ONE value
    tile."""
    q_idx, step = pl.program_id(2), pl.program_id(3)
    kv_idx = _streamed_kv_block(q_idx, step, walk.tile_q, walk.tile_kv,
                                walk.window)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        q_halves[0], q_halves[1] = _halves(q_ref[0])

    def visit(rows, keys, bias, before, last):
        del before, last
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        for n in range(2):
            s = _scores(q_halves[n, rows, :], k, bias)
            m_prev = m_ref[n, rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row with no key so far (a window's far edge cuts a tile's
            # last rows whole) has m_new == NEG_INF and p == 1: the first
            # key it sees, its own at the latest, wipes that by its
            # correction exp(NEG_INF - m)
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_ref[n, rows, :1] * correction + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[n, rows, :] = (
                acc_ref[n, rows, :] * correction + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[n, rows] = jnp.broadcast_to(m_new, (s.shape[0], LANES))
            l_ref[n, rows] = jnp.broadcast_to(l_new, (s.shape[0], LANES))

    walk.each_visit(q_idx * walk.tile_q - kv_idx * walk.tile_kv, visit)

    @pl.when(step == pl.num_programs(3) - 1)
    def _finalize():
        for n, out_ref in enumerate((o1_ref, o2_ref)):
            l = l_ref[n, :, :1]    # at least 1: the row's largest score
            out_ref[0] = (acc_ref[n] / l).astype(out_ref.dtype)
            # a row of the result: the column, lane-broadcast, turned round
            lse_ref[0, n] = jnp.transpose(m_ref[n] + jnp.log(l_ref[n]))[:1]


def _bwd_kernel(lam_ref, q_ref, k_ref, v_ref, do_ref, o1_ref, o2_ref,
                lse_ref, dq_hbm, dk_ref, dv_ref, k_halves, dk_acc, dv_acc,
                dq_tile, dq_new, zeros, columns, arrived, left,
                *, walk, group):
    """grid (batch, key pair, key tile, group x step): a key tile resident,
    the query tiles of its ``group`` query pairs streamed past it from the
    diagonal on.  A live step scores its pairs ONCE a map and adds its part
    to ``dV``, ``dK1`` and ``dK2`` in their accumulators and to the query
    tile's ``[dQ1 | dQ2]``, which lives in HBM as float32 between key
    tiles: the step fetches the block while it scores, adds, and writes it
    back under its last products, before it ends: the next step that names
    the block reads what this one wrote."""
    batch, kv_pair, kv_idx = (pl.program_id(n) for n in range(3))
    q_pair = kv_pair * group + pl.program_id(3) // walk.q_steps
    step = pl.program_id(3) % walk.q_steps
    tile_q, tile_kv = walk.tile_q, walk.tile_kv
    q_idx = _first_q_block(kv_idx, tile_q, tile_kv) + step
    lam = lam_ref[0]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        zeros[:] = jnp.zeros_like(zeros)
        k_halves[0], k_halves[1] = _halves(k_ref[0])

    # (a step past the last query tile, which runs nothing, names it)
    dq_block = dq_hbm.at[
        batch, pl.ds(jnp.minimum(q_idx, walk.num_q - 1) * tile_q, tile_q),
        pl.ds(q_pair * LANES, LANES)]
    fetch = pltpu.make_async_copy(dq_block, dq_tile, arrived)
    from_zeros = pltpu.make_async_copy(zeros, dq_tile, arrived)
    write_back = pltpu.make_async_copy(dq_tile, dq_block, left)
    # the first key tile a query tile sees adds to zeros
    fresh = kv_idx == (0 if walk.window is None else _first_kv_block(
        q_idx, tile_q, tile_kv, walk.window))

    def visit(rows, keys, bias, before, last):
        if not before:
            # what dQ holds so far is on its way while the pairs are
            # scored, from HBM or from zeros by the same semaphore; the
            # rows of LSE turned into columns, and a map's delta, sum(dO
            # O) a row (dO2 = -lam dO goes in with -lam P2 below)
            pl.when(jnp.logical_not(fresh))(fetch.start)
            pl.when(fresh)(from_zeros.start)
            grad = do_ref[0].astype(jnp.float32)
            for n, o_ref in enumerate((o1_ref, o2_ref)):
                columns[n] = _column(lse_ref[0, n], tile_q)
                columns[2 + n] = jnp.broadcast_to(jnp.sum(
                    grad * o_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True), (tile_q, LANES))
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        k1, k2, v = k_halves[0, keys, :], k_halves[1, keys, :], v_ref[
            0, keys, :]
        p1 = jnp.exp(_scores(q, k1, bias) - columns[0, rows, :1])
        # dO1 = dO and dO2 = -lam dO: with -lam P2 for P2, dP and dV are
        # one product each for both maps
        p2 = jnp.exp(_scores(q, k2, bias) - columns[1, rows, :1]) * -lam
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # (the scale of dS: in k's halves for dQ, at the end for dK)
        ds1 = (p1 * (dp - columns[2, rows, :1])).astype(q.dtype)
        ds2 = (p2 * (dp - columns[3, rows, :1])).astype(q.dtype)
        nn = (((1,), (0,)), ((), ()))
        dq_new[rows, :] = (     # [dS1 k1 | dS2 k2]
            jax.lax.dot_general(ds1, k1, nn,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(ds2, k2, nn,
                                  preferred_element_type=jnp.float32))
        if last:
            # dQ's way back runs under the visit's three other products
            fetch.wait()
            for seen in before + [rows]:
                dq_tile[seen, :] += dq_new[seen, :]
            write_back.start()
        tn = (((0,), (0,)), ((), ()))
        dv_acc[keys, :] += jax.lax.dot_general(     # (P1 - lam P2)^T dO
            (p1 + p2).astype(do.dtype), do, tn,
            preferred_element_type=jnp.float32)
        dk_acc[0, keys, :] += jax.lax.dot_general(  # dS^T [q1 | q2], a map
            ds1, q, tn, preferred_element_type=jnp.float32)
        dk_acc[1, keys, :] += jax.lax.dot_general(
            ds2, q, tn, preferred_element_type=jnp.float32)
        if last:
            write_back.wait()

    # a step past the sequence's last query tile reaches no branch
    distance = jnp.where(q_idx < walk.num_q,
                         q_idx * tile_q - kv_idx * tile_kv, -walk.seq_len)
    walk.each_visit(distance, visit)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0] = (jnp.where(_first_head(), dk_acc[0], dk_acc[1])
                     * SCALE).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


class _Call:
    """Shapes and block specs of one call: ``q`` [B, S, H, 64], ``k`` [B,
    S, G, 64].  ``where`` gives a grid step's (batch, query pair, query
    tile, key tile)."""

    def __init__(self, q, k, window, tiles):
        self.B, S, H, D = q.shape
        G = k.shape[2]
        if not kernels_take(S, D, H, G):
            raise ValueError(
                f"{H} heads of {D} on {G} over {S} positions: the "
                f"differential kernels take pairs of heads of {HEAD_DIM}")
        self.pairs, self.kv_pairs = H // 2, G // 2
        self.group = H // G
        self.walk = Walk(S, *tiles, window)
        self.settings = dict(walk=self.walk)

    def specs(self, where):
        def at(pick):
            return lambda *ids: pick(*where(*ids))

        walk, group = self.walk, self.group
        return dict(
            q=pl.BlockSpec((1, walk.tile_q, LANES),
                           at(lambda b, p, i, j: (b, i, p))),
            kv=pl.BlockSpec((1, walk.tile_kv, LANES),
                            at(lambda b, p, i, j: (b, j, p // group))),
            # per-row scalars a row a head, [B, H, 1, S]: a pair's two
            rows=pl.BlockSpec((1, 2, 1, walk.tile_q),
                              at(lambda b, p, i, j: (b, p, 0, i))))


# (under ``jax.jit``, both: a layer's ``remat``, its loop and its gradient
# each trace the forward rule again, ten times a kind of layer in the
# Phi-4-mini-flash step, and a kernel's body with its cut tiles' visits
# written out is slow to trace: the second trace finds the first's.  The
# compiler names a kernel's instruction after the function:
# ``%diff_forward.N``, ``%diff_backward.N``)
@functools.partial(jax.jit, static_argnames=("window", "tiles", "interpret"))
def diff_forward(q, k, v, window, tiles, interpret):
    """``(O1, O2 [B, S, (H/2)*128], lse [B, H, 1, S])``."""
    call = _Call(q, k, window, tiles)
    walk, B, S = call.walk, call.B, q.shape[1]

    def step(b, p, i, x):   # a masked step asks for the block it has
        j = _streamed_kv_block(i, x, walk.tile_q, walk.tile_kv, walk.window)
        return b, p, i, jnp.minimum(
            j, _last_kv_block(i, walk.tile_q, walk.tile_kv))

    spec = call.specs(step)
    out = jax.ShapeDtypeStruct((B, S, call.pairs * LANES), q.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **call.settings),
        grid=(B, call.pairs, walk.num_q, walk.kv_steps),
        in_specs=[spec["q"], spec["kv"], spec["kv"]],
        out_specs=[spec["q"], spec["q"], spec["rows"]],
        out_shape=[out, out, jax.ShapeDtypeStruct(
            (B, 2 * call.pairs, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, walk.tile_q, LANES), q.dtype),
                        pltpu.VMEM((2, walk.tile_q, LANES), jnp.float32),
                        pltpu.VMEM((2, walk.tile_q, LANES), jnp.float32),
                        pltpu.VMEM((2, walk.tile_q, LANES), jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v))


@functools.partial(jax.jit, static_argnames=("window", "tiles", "interpret"))
def diff_backward(q, k, v, lam, grad_out, o1, o2, lse, window, tiles,
                  interpret):
    """``(dq, dk, dv)`` in their operands' shapes from ONE call;
    ``grad_out``, ``o1``, ``o2`` ``[B, S, (H/2)*128]`` in the operands'
    dtype, ``lse`` ``[B, H, 1, S]`` float32.  dQ gathers over the key
    tiles, the grid's third axis, in a float32 result that stays in HBM (no
    block spec: the kernel copies a block in and out itself, so no pipeline
    stands between a write and the next read of one block) and is cast once
    after the call.  The key-tile axis is ``"arbitrary"`` for it (a v5e
    chip has one core: nothing is lost)."""
    call = _Call(q, k, window, tiles)
    walk, B, S, group = call.walk, call.B, q.shape[1], call.group
    tile_q, tile_kv = walk.tile_q, walk.tile_kv

    # key tiles resident, a pair's query tiles streamed from the diagonal
    # on: a step past the band or the sequence asks for the last live block
    def step(b, m, j, x):
        i = _first_q_block(j, tile_q, tile_kv) + x % walk.q_steps
        last = walk.num_q - 1 if walk.window is None else _last_q_block(
            j, tile_q, tile_kv, walk.window, walk.num_q)
        return b, m * group + x // walk.q_steps, jnp.minimum(i, last), j

    spec = call.specs(step)
    # the resident blocks are the key pair's own, not a query pair's
    kv = pl.BlockSpec((1, tile_kv, LANES), lambda b, m, j, x: (b, j, m))
    q_tile = pltpu.VMEM((tile_q, LANES), jnp.float32)
    kv_tile = pltpu.VMEM((tile_kv, LANES), jnp.float32)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, group=group, **call.settings),
        grid=(B, call.kv_pairs, walk.num_kv, group * walk.q_steps),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec["q"], kv, kv,
                  spec["q"], spec["q"], spec["q"], spec["rows"]],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), kv, kv],
        out_shape=[jax.ShapeDtypeStruct((B, S, call.pairs * LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((B, S, call.kv_pairs * LANES),
                                        k.dtype),
                   jax.ShapeDtypeStruct((B, S, call.kv_pairs * LANES),
                                        v.dtype)],
        scratch_shapes=[pltpu.VMEM((2, tile_kv, LANES), k.dtype),
                        pltpu.VMEM((2, tile_kv, LANES), jnp.float32),
                        kv_tile,
                        # a query tile's dQ: what HBM holds so far, this
                        # step's part, and the zeros a first visit adds to
                        q_tile, q_tile, q_tile,
                        # the LSE and delta of both maps as columns
                        pltpu.VMEM((4, tile_q, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(jnp.reshape(lam, (1,)).astype(jnp.float32), _flat(q), _flat(k),
      _flat(v), grad_out, o1, o2, lse)
    return (dq.astype(q.dtype).reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


def kept_bytes(q) -> dict:
    """What a layer's rematerialisation keeps of the forward kernel, in
    bytes by name: ``O1`` and ``O2``, each as wide as ``q``, and the LSE
    ``[B, H, S]`` float32."""
    B, S, H, _ = q.shape
    return {kept.ATTN_OUT: 2 * kept.nbytes(q.shape, q.dtype),
            kept.ATTN_LSE: kept.nbytes((B, H, S), jnp.float32)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def differential_attention_kernels(q, k, v, lam, window, tiles,
                                   interpret: bool = False):
    """``(softmax(q1 k1^T / 8) - lam softmax(q2 k2^T / 8)) V`` under the
    causal mask and ``window`` (``None``: every earlier key), float32 ``[B,
    S, H/2, 128]``: ``q`` [B, S, H, 64] whose heads ``(2j, 2j+1)`` are
    ``q1_j, q2_j``; ``k``, ``v`` [B, S, G, 64] whose heads ``(2m, 2m+1)``
    are ``k1_m, k2_m`` and, side by side, ``V_m``; ``lam`` a float32
    scalar; ``tiles``: ``tiles_for``'s."""
    return _diff_fwd(q, k, v, lam, window, tiles, interpret)[0]


def _difference(o1, o2, lam, pairs):
    # one pass over the kernels' own arrays, as they lie: what reads the
    # result lays ``[.., H/2, 128]`` out its own way, and without the
    # barrier it turns O1 and O2 round one by one, in float32
    out = jax.lax.optimization_barrier(
        o1.astype(jnp.float32) - lam * o2.astype(jnp.float32))
    return out.reshape(*out.shape[:2], pairs, LANES)


def _diff_fwd(q, k, v, lam, window, tiles, interpret):
    o1, o2, lse = diff_forward(q, k, v, window, tiles, interpret)
    o1, o2 = kept.named(kept.ATTN_OUT, o1, o2)
    lse, = kept.named(kept.ATTN_LSE, lse[:, :, 0])
    return (_difference(o1, o2, lam, q.shape[2] // 2),
            (q, k, v, lam, o1, o2, lse))


def _diff_bwd(window, tiles, interpret, residuals, grad_out):
    q, k, v, lam, o1, o2, lse = residuals
    grad_out = _flat(grad_out)
    dq, dk, dv = diff_backward(
        q, k, v, lam, grad_out.astype(q.dtype), o1, o2, lse[:, :, None],
        window, tiles, interpret)
    dlam = -jnp.sum(grad_out * o2.astype(jnp.float32))
    return dq, dk, dv, dlam.astype(lam.dtype).reshape(lam.shape)


differential_attention_kernels.defvjp(_diff_fwd, _diff_bwd)
