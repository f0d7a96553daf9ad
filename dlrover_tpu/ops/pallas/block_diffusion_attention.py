"""Pallas TPU kernels for attention under the block-diffusion mask (BD3-LMs,
arXiv:2503.09573; SDAR, arXiv:2510.06303): the ``2S`` rows ``[noisy copy ;
clean copy]`` of one sequence of ``S`` positions in blocks of ``L``, forward
and backward, ONE call a pass over the model's whole arrays.

The mask is a rule on two positions and a block length, so the kernels make
it.  For a query at position ``r`` of its half: a clean query sees the clean
key at ``c`` where ``c // L <= r // L``; a noisy query sees the clean key
where ``c // L < r // L`` and the noisy key where ``c // L == r // L``
(``ops/attention.py::block_diffusion_keep`` states it for the ``jax.numpy``
path and the tests); a noisy row's clean keys and its own noisy block stand
under ONE softmax.  With query tiles and key tiles of one size ``tile`` (a
multiple of ``L``: no block of the sequence straddles two) the rule leaves
four kinds of tile alive, and ``walk`` lists them in the order the kernels
take them, a query tile's key tiles one after the other:

* ``FULL``: a clean key tile strictly under the diagonal, for either half:
  every pair allowed, NO mask;
* ``CLEAN_CUT``: the clean half's diagonal tile, ``c // L <= r // L``;
* ``NOISY_CUT``: the noisy half over the clean keys of its own tile,
  ``c // L < r // L``;
* ``OWN``: a noisy tile's own noisy keys, the block diagonal ``c // L == r
  // L``, reached through the index map at its row offset into the same k
  and v arrays (nothing is joined).

A dead tile is never a grid step: the walk is a table in scalar memory
(``PrefetchScalarGridSpec``) that the index maps and the kernels read, so
the third grid axis counts live tiles alone.  The three cut kinds are walked
by sub-tiles of ``sub`` rows and keys with the dead ones skipped
(``visits``): ``sub`` rows at a time against the keys they can see, the
mask an additive ``0 / NEG_INF`` tile made from one ``iota`` and a row's two
bounds, once a visit for all the heads.

What these kernels share with ``selected_attention.py``, whose numerics they
keep to the cast: a grid step takes one kv tile for ALL the query heads of a
kv head (a GQA group, 8 heads at SDAR's widths) one after the other, k and v
fetched once a group; bfloat16 operands as given, float32 scores and
softmax, float32 accumulation, the per-row log-sum-exp (LSE) the backward's
residual.  What differs: that file's mask is an operand (a selection no rule
gives) and its call is one block of queries, so its backward has dQ resident
and writes each key tile's dK and dV as it leaves it.  Here the query tiles
are on the grid too, so dQ and dK/dV gather along different axes, and the
backward is still ONE kernel of five products a live pair
(``flash_attention.py::_flash_bwd_kernel``'s way, turned round): a QUERY
tile is resident with its dQ in VMEM, and dK and dV are float32 accumulators
in HBM, ``[B, 2S, G*D]`` each, that a step fetches, adds to and writes back
(the next step waits for that before it fetches).  Turned round because of the group: a group's dQ tile is
``group`` times a key tile's dK and dV together (2 MiB against 512 KiB at
8 heads and 512 rows), and a resident query tile fetches q, dO, O and the
LSE once and computes its ``delta`` once, where a resident key tile would
stream all five past it at every step.  A key tile's first visit adds to
zeros (``FRESH``): nothing zero-fills the accumulators.

HBM interface, as FA2's: the model's ``[B, 2S, H, D]`` arrays as ``[B, 2S,
H*D]``, a group's heads the column block ``[tile, group*D]``.  Head size 128
only.

What a rematerialised layer keeps (``kept.py``): ``out`` and the LSE as
``[B, H, 2S]`` float32, one pair of whole arrays a call; its backward pass
recomputes q, k and v in ``jax.numpy`` and does not run the forward kernel
again.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.pallas.flash_attention import LANES, NEG_INF
from dlrover_tpu.ops.pallas.selected_attention import (
    VMEM_LIMIT_BYTES,
    _each_head,
    _flat,
    _head_cols,
    _scores,
)

#: rows of a query tile and keys of a key tile, and the rows and keys of a
#: cut tile's sub-tiles, on a v5e (PERF.md section 6, PR 58, has the sweep)
TILE = 1024
SUB = 256

#: keys of a wide tile: the forward walks the clean keys strictly under a
#: query tile's diagonal that many at a time where they are so many (the
#: online softmax rescales a row's sums once a key tile: at tiles of 512
#: keys a layer's forward took 21.5 ms alone where the mask-operand
#: kernels, at 2048, took 14.5; PERF.md section 6, PR 58)
WIDE_KEYS = 2048

#: the kinds of live tile (``WIDE``: ``FULL``, over a wide tile's keys)
FULL, CLEAN_CUT, NOISY_CUT, OWN, WIDE = range(5)
#: the rows of ``walk``'s table
Q_AT, KV_AT, WIDE_AT, KIND, FIRST, LAST, FRESH = range(7)


def tile_for(dtype) -> int:
    """``TILE`` for operands of two bytes, half of it for wider ones: a
    backward step holds a group's q, dO, O and dQ tiles twice over, and at
    float32 operands, 8 heads and 1024 rows the compiler counts 103 MiB of
    a v5e core's 128 and refuses it."""
    return TILE if jnp.dtype(dtype).itemsize <= 2 else TILE // 2


def sub_tile(tile: int, block: int) -> int:
    """The sub-tile a cut tile of ``tile`` is walked by: ``SUB`` where it
    tiles ``tile`` and whole blocks of the sequence tile it (a dead
    sub-tile is dead for every pair), else the tile whole, under its
    mask."""
    return SUB if tile % SUB == 0 and SUB % block == 0 else tile


def wide_tile(seq: int, tile: int) -> int:
    """The keys of the forward's wide tiles: ``WIDE_KEYS`` where tiles of
    ``tile`` tile it and it tiles the sequence, else 0 (none)."""
    fits = WIDE_KEYS > tile and WIDE_KEYS % tile == 0 == seq % WIDE_KEYS
    return WIDE_KEYS if fits else 0


def _held(at):
    """``at`` with every -1 (a step that does not read that operand)
    taking the block of the step before, or of the first that reads one:
    an operand's block index that does not change is not fetched again."""
    at = np.array(at, np.int32)
    reads = at >= 0
    if not reads.any():
        return np.zeros_like(at)
    last = np.maximum.accumulate(np.where(reads, np.arange(len(at)), -1))
    return at[np.where(last < 0, np.argmax(reads), last)]


def walk(seq: int, tile: int, wide: int = 0):
    """The live tiles in the order the kernels take them, ``int32 [7,
    steps]``: a step's query tile and key tile, both as row tiles of the
    ``2S`` rows (the noisy half's ``0 .. n-1``, the clean half's ``n ..
    2n-1``), its wide key tile (of ``wide`` keys, counted the same way; 0
    throughout without), its kind, whether it is its query tile's first and
    last step, and whether it is its key tile's first visit.  The noisy
    half first: a query tile's clean key tiles up to its own (``wide``
    keys at a time as far as whole wide tiles lie under the diagonal),
    then its own noisy one, so every row ends on a key it sees."""
    n = seq // tile
    per = wide // tile if wide else n    # without: none whole under any

    def under(q, i):    # the clean keys strictly under query tile i's own
        whole = i // per
        return ([(q, -1, seq // wide + w, WIDE) for w in range(whole)]
                + [(q, n + j, -1, FULL) for j in range(whole * per, i)])

    steps = []
    for i in range(n):
        steps += under(i, i) + [(i, n + i, -1, NOISY_CUT), (i, i, -1, OWN)]
    for i in range(n):
        steps += under(n + i, i) + [(n + i, n + i, -1, CLEAN_CUT)]
    q_at, kv_at, wide_at, kind = np.array(steps, np.int32).T
    edge = q_at[1:] != q_at[:-1]
    first = np.concatenate([[True], edge])
    last = np.concatenate([edge, [True]])
    fresh = np.zeros(len(steps), bool)
    fresh[np.unique(kv_at, return_index=True)[1]] = True
    return np.stack([q_at, _held(kv_at), _held(wide_at), kind, first, last,
                     fresh & (kv_at >= 0)]).astype(np.int32)


def visits(kind: int, tile: int, sub: int, wide: int = 0):
    """``(first row, last row, first key, last key)`` of the sub-tiles a
    step of ``kind`` multiplies, ``sub`` rows at a time against the keys
    they can see: every pair of a ``FULL`` or ``WIDE`` tile; of a cut tile
    on the diagonal a row sub-tile's keys up to its own; of ``OWN`` the
    diagonal sub-tiles alone."""
    if kind in (FULL, WIDE):
        return [(0, tile, 0, wide if kind == WIDE else tile)]
    if kind == OWN:
        return [(at, at + sub, at, at + sub) for at in range(0, tile, sub)]
    return [(at, at + sub, 0, at + sub) for at in range(0, tile, sub)]


def pairs_multiplied(seq: int, tile: int, sub: int) -> int:
    """Query-key pairs of one head's forward pass: every score of every
    sub-tile the walk visits (the same with wide tiles as without)."""
    return sum((r1 - r0) * (k1 - k0) for kind in walk(seq, tile)[KIND]
               for r0, r1, k0, k1 in visits(int(kind), tile, sub))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _bias(kind, visit, block):
    """``[rows, keys]`` float32 of a cut tile's visit: 0 on a pair the rule
    allows, ``NEG_INF`` elsewhere.  Positions count from the tile's first,
    which is the same for queries and keys (the cut tiles are diagonal ones)
    and a multiple of ``block``.  ``kind``: ``None`` for ``OWN``, else a
    traced one of the two cuts."""
    r0, r1, k0, k1 = visit
    at = r0 + jax.lax.broadcasted_iota(jnp.int32, (r1 - r0, 1), 0)
    lo = at // block * block          # the first position of the row's block
    c = k0 + jax.lax.broadcasted_iota(jnp.int32, (r1 - r0, k1 - k0), 1)
    if kind is None:
        keep = (c >= lo) & (c < lo + block)
    else:
        keep = c < lo + jnp.where(kind == CLEAN_CUT, block, 0)
    return jnp.where(keep, 0.0, NEG_INF)


def _each_visit(kind, tile, sub, block, visit, wide=0):
    """``visit(rows, keys, bias, wide)`` over the sub-tiles of this step's
    kind: a branch a kind (the two cuts share one: their masks differ by a
    scalar), straight-line code inside."""
    def walked(static, traced):
        def run():
            for found in visits(static, tile, sub, wide):
                r0, r1, k0, k1 = found
                visit(slice(r0, r1), slice(k0, k1),
                      None if static in (FULL, WIDE)
                      else _bias(traced, found, block), static == WIDE)
        return run

    pl.when(kind == FULL)(walked(FULL, None))
    pl.when((kind == CLEAN_CUT) | (kind == NOISY_CUT))(
        walked(CLEAN_CUT, kind))
    pl.when(kind == OWN)(walked(OWN, None))
    if wide:
        pl.when(kind == WIDE)(walked(WIDE, None))


def _fwd_kernel(table, q_ref, k_ref, v_ref, *rest, scale, group, head_dim,
                block, sub, wide):
    """grid (batch, kv head, live tile): online softmax over a query
    tile's live key tiles for the ``group`` query heads of the kv head.
    ``rest``: with ``wide``, k and v once more by wide tiles; the results
    and scratch."""
    *wide_refs, out_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    step = pl.program_id(2)
    tile = q_ref.shape[1]

    @pl.when(table[FIRST, step] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def visit(rows, keys, bias, is_wide):
        k, v = (ref[0, keys, :] for ref in (
            wide_refs if is_wide else (k_ref, v_ref)))

        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            s = _scores(q_ref[0, rows, cols], k, scale, bias)
            m_prev = m_ref[r, rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row with no key so far (a noisy block's first rows under
            # ``NOISY_CUT``) has m_new == NEG_INF and p == 1: the first key
            # it sees, in ``OWN`` at the latest, wipes it by its correction
            # exp(NEG_INF - m)
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_ref[r, rows, :1] * correction + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[rows, cols] = (
                acc_ref[rows, cols] * correction + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[r, rows] = jnp.broadcast_to(m_new, (s.shape[0], LANES))
            l_ref[r, rows] = jnp.broadcast_to(l_new, (s.shape[0], LANES))

        _each_head(group, one_head)

    _each_visit(table[KIND, step], tile, sub, block, visit, wide)

    @pl.when(table[LAST, step] == 1)
    def _finalize():
        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            l = l_ref[r, :, :1]  # at least 1: the row's largest score
            out_ref[0, :, cols] = (acc_ref[:, cols] / l).astype(out_ref.dtype)
            # a row of the result: the column, lane-broadcast, turned round
            lse_ref[0, r] = jnp.transpose(
                m_ref[r] + jnp.log(l_ref[r]))[:1]

        _each_head(group, one_head)


def _bwd_kernel(table, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                dk_hbm, dv_hbm, dq_acc, delta_ref, lse_col, dk_new, dv_new,
                dk_held, dv_held, zeros, arrived, left,
                *, scale, group, head_dim, block, sub):
    """grid (batch, kv head, live tile), the forward's walk: a query tile
    resident with the group's dQ, its live key tiles streamed past it.  A
    step scores its pairs ONCE and adds their part to dQ in VMEM and to the
    key tile's dK and dV, summed over the group, which live in HBM as
    float32 between a key tile's visits: the step fetches the two blocks
    (zeros at the tile's first visit) while it computes, adds, and starts
    their way back, which the next step waits for before it fetches: a
    step that names them, however soon, reads what this one wrote."""
    batch, kv_head, step = (pl.program_id(n) for n in range(3))
    tile = q_ref.shape[1]

    @pl.when(table[FIRST, step] == 1)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        zeros[:] = jnp.zeros_like(zeros)

        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            delta = jnp.sum(
                do_ref[0, :, cols].astype(jnp.float32)
                * o_ref[0, :, cols].astype(jnp.float32),
                axis=-1, keepdims=True)
            delta_ref[r] = jnp.broadcast_to(delta, delta_ref.shape[1:])
            # the kept LSE is a row: turned round, a column in every lane
            lse_col[r] = jnp.transpose(jnp.broadcast_to(
                lse_ref[0, r], (LANES, tile)))

        _each_head(group, one_head)

    # dK and dV of the step's key tile, each (fetch, the same from zeros,
    # write-back, what HBM holds so far, this step's part)
    at = (batch,
          pl.ds(pl.multiple_of(table[KV_AT, step] * tile, tile), tile),
          pl.ds(pl.multiple_of(kv_head * head_dim, head_dim), head_dim))
    parts = [
        (pltpu.make_async_copy(hbm.at[at], held, arrived.at[n]),
         pltpu.make_async_copy(zeros, held, arrived.at[n]),
         pltpu.make_async_copy(held, hbm.at[at], left.at[n]), held, new)
        for n, (hbm, held, new) in enumerate(
            ((dk_hbm, dk_held, dk_new), (dv_hbm, dv_held, dv_new)))]
    fresh = table[FRESH, step] == 1
    for fetch, from_zeros, write_back, _, new in parts:
        # the step before wrote its blocks back from the same tiles, under
        # its last lines and this step's first: landed before anything is
        # fetched into them, and before a block it names is read again
        pl.when(step > 0)(write_back.wait)
        pl.when(jnp.logical_not(fresh))(fetch.start)
        pl.when(fresh)(from_zeros.start)
        new[:] = jnp.zeros_like(new)

    def visit(rows, keys, bias, is_wide):
        del is_wide     # the backward walks by key tiles of one size
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]

        def one_head(r, _):
            cols = _head_cols(r, head_dim)
            q, do = q_ref[0, rows, cols], do_ref[0, rows, cols]
            p = jnp.exp(_scores(q, k, scale, bias) - lse_col[r, rows, :1])
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[r, rows, :1]) * scale
            dq_acc[rows, cols] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_new[keys, :] += jax.lax.dot_general(  # P^T dO
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_new[keys, :] += jax.lax.dot_general(  # dS^T Q
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _each_head(group, one_head)

    _each_visit(table[KIND, step], tile, sub, block, visit)

    for fetch, _, write_back, held, new in parts:
        fetch.wait()
        held[:] += new[:]
        write_back.start()

    @pl.when(table[LAST, step] == 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _landed():
        for _, _, write_back, _, _ in parts:
            write_back.wait()


class _Call:
    """Shapes, table and block specs of one call: ``q`` [B, 2S, H, D],
    ``k`` [B, 2S, G, D]; ``wide``: the keys of the walk's wide tiles, or
    0."""

    def __init__(self, q, k, block, tile, sub, wide=0):
        self.B, rows, self.H, self.D = q.shape
        self.G = k.shape[2]
        self.group = self.H // self.G
        self.table = walk(rows // 2, tile, wide)
        self.steps = self.table.shape[1]
        self.settings = dict(scale=self.D ** -0.5, group=self.group,
                             head_dim=self.D, block=block, sub=sub)

        def at(row, pick):
            return lambda b, g, s, table: pick(b, g, table[row, s])

        self.q = pl.BlockSpec((1, tile, self.group * self.D),
                              at(Q_AT, lambda b, g, i: (b, i, g)))
        self.kv = pl.BlockSpec((1, tile, self.D),
                               at(KV_AT, lambda b, g, j: (b, j, g)))
        self.kv_wide = pl.BlockSpec((1, wide or tile, self.D),
                                    at(WIDE_AT, lambda b, g, j: (b, j, g)))
        # per-row scalars as the layer keeps them, a row a head: [B, H, 1,
        # 2S] (FA2's are lane-broadcast ``[B, H, 2S, LANES]``, 128 times
        # the bytes in HBM; the kernels turn a tile's round in VMEM)
        self.lse = pl.BlockSpec((1, self.group, 1, tile),
                                at(Q_AT, lambda b, g, i: (b, g, 0, i)))

    def grid_spec(self, in_specs, out_specs, scratch_shapes):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(self.B, self.G, self.steps),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes)


def _forward(q, k, v, block, tile, sub, interpret):
    """``(out [B, 2S, H, D], lse [B, H, 1, 2S])``.  Where the sequence has
    wide tiles (``wide_tile``) k and v are operands twice, by tiles of
    ``tile`` keys and by wide ones: a step reads one pair, and the other's
    block index stands still."""
    rows = q.shape[1]
    wide = wide_tile(rows // 2, tile)
    call = _Call(q, k, block, tile, sub, wide)
    B, H, D, group = call.B, call.H, call.D, call.group
    q, k, v = _flat(q), _flat(k), _flat(v)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, wide=wide, **call.settings),
        grid_spec=call.grid_spec(
            [call.q, call.kv, call.kv] + (2 if wide else 0) * [call.kv_wide],
            [call.q, call.lse],
            [pltpu.VMEM((tile, group * D), jnp.float32),
             pltpu.VMEM((group, tile, LANES), jnp.float32),
             pltpu.VMEM((group, tile, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, rows, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, rows), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(jnp.asarray(call.table), q, k, v, *((k, v) if wide else ()))
    return out.reshape(B, rows, H, D), lse


def _backward(q, k, v, out, lse, grad_out, block, tile, sub, interpret):
    """``(dq, dk, dv)`` in their operands' shapes from ONE call.  dK and dV
    gather over the query tiles in float32 results that stay in HBM (no
    block spec: the kernel copies a block in and out itself, so no pipeline
    stands between a write and the next read of one block) and are cast
    once after the call."""
    call = _Call(q, k, block, tile, sub)
    B, G, D, group = call.B, call.G, call.D, call.group
    rows = q.shape[1]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    key_tile = pltpu.VMEM((tile, D), jnp.float32)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, **call.settings),
        grid_spec=call.grid_spec(
            [call.q, call.kv, call.kv, call.q, call.q, call.lse],
            [call.q, in_hbm, in_hbm],
            [pltpu.VMEM((tile, group * D), jnp.float32),
             pltpu.VMEM((group, tile, LANES), jnp.float32),
             pltpu.VMEM((group, tile, LANES), jnp.float32),
             # a key tile's dK and dV: this step's part, what HBM holds so
             # far, and the zeros a first visit adds to
             key_tile, key_tile, key_tile, key_tile, key_tile,
             pltpu.SemaphoreType.DMA((2,)),
             pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((B, rows, call.H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, rows, G * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, rows, G * D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(jnp.asarray(call.table), _flat(q), _flat(k), _flat(v), _flat(grad_out),
      _flat(out), lse)
    return (dq.reshape(q.shape), dk.astype(k.dtype).reshape(k.shape),
            dv.astype(v.dtype).reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def block_diffusion_kernels(q, k, v, block, tile, sub,
                            interpret: bool = False):
    """Attention of ``[noisy copy ; clean copy]``, ``q`` [B, 2S, H, D],
    ``k``/``v`` [B, 2S, G, D] (GQA), under the block-diffusion mask of
    blocks of ``block`` -> [B, 2S, H, D]; ``S`` a whole number of tiles of
    ``tile``, ``tile`` of ``sub`` and of ``block``, and ``sub`` either a
    whole number of blocks or the tile (``sub_tile``)."""
    return _bd_fwd(q, k, v, block, tile, sub, interpret)[0]


def _bd_fwd(q, k, v, block, tile, sub, interpret):
    seq = q.shape[1] // 2
    if (q.shape[1] % 2 or seq % tile or tile % sub or tile % block
            or (sub % block and sub != tile)):
        raise ValueError(
            f"{q.shape[1]} rows in tiles of {tile} by sub-tiles of {sub} "
            f"under blocks of {block}")
    out, lse = _forward(q, k, v, block, tile, sub, interpret)
    out, = kept.named(kept.ATTN_OUT, out)
    lse, = kept.named(kept.ATTN_LSE, lse[:, :, 0])
    return out, (q, k, v, out, lse)


def _bd_bwd(block, tile, sub, interpret, residuals, grad_out):
    q, k, v, out, lse = residuals
    return _backward(q, k, v, out, lse[:, :, None], grad_out, block, tile,
                     sub, interpret)


block_diffusion_kernels.defvjp(_bd_fwd, _bd_bwd)
