"""Pallas TPU kernels for the selective scan of a state-space layer
(``ops/selective_scan.py`` has the mathematics and the ``jax.numpy`` body
these stand in for), forward and backward::

    s_t = exp(delta_t A) * s_{t-1} + drive_t B_t^T        y_t = s_t C_t

The work is elementwise on ``[channels, N]`` and sequential in ``t``: it
belongs to the vector unit, and the one thing to arrange is that the state's
history never leaves fast memory.

**Layout.**  Channels lie on the 128 lanes, the ``N`` state columns on
sublanes: a group of 128 channels' state is an ``[N, 128]`` tile (two
float32 vregs at ``N`` 16) and ALL groups' state, ``[G, N, 128]`` (320 KiB
at 5120 channels), is a VMEM scratch that lives from a sequence's first
chunk to its last.  The per-position operands are seen time-major with the
groups on sublanes, ``[B, S, G, 128]`` (a reshape of ``[B, S, channels]``):
position ``t`` is an index into an untiled leading dimension, a group's row
a static sublane slice, so no load or store has a dynamic offset inside a
tile.  ``B_t`` and ``C_t`` come broadcast along lanes, ``[B, S, N, 128]``
(134 MB a layer at 16,384 positions, made and dropped around the call):
a column a lane is what multiplies an ``[N, 128]`` tile.  ``y_t`` of a group
is a sum over sublanes, a row.

**Forward** (``_fwd_kernel``; grid ``(batch, chunk)``, the chunk axis
sequential): walks a chunk's ``CHUNK`` positions, every group at each, and
writes ``y`` and, once a chunk, the state the chunk STARTED from (84 MB a
layer at 16,384 positions and 5120 channels).

**Backward** (``_bwd_kernel``; the chunks from the last): computes a
chunk's states again from its start into a VMEM scratch (``CHUNK + 1``
states: 21 MB), then walks the chunk backwards with the state's gradient
resident: ``g_t = dy_t C_t^T + exp(delta_{t+1} A) g_{t+1}``; the gradients
of ``drive`` and ``delta`` are rows (sums over sublanes), ``A``'s
accumulates in its output block over the whole sequence, and of ``B_t`` and
``C_t``, which sum over ALL channels, the kernel writes the sum over groups
alone, ``[N, 128]`` a position; the sum over lanes is ``jax.numpy``'s after
the call (an ``[S, N, 128]`` array each, never ``[S, channels, N]``).

**What a rematerialised layer keeps** (``kept.py``): ``y`` in the dtype
the caller takes it in (the compute dtype: 168 MB a layer where float32
is 335) and the chunks' start states, named ``ssm_scan`` inside the
forward rule; the layer's second
forward pass then runs no scan kernel, and the backward kernel reads the
starts.

Numerics: float32 throughout, as the ``jax.numpy`` body's.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.selective_scan import CHUNK

LANES = 128
SUBLANES = 8

# The backward kernel holds a chunk's states (CHUNK + 1 of [channels, N]
# float32: 21 MiB at 5120 x 16), two buffers of five [CHUNK, channels]
# operands and results (1.25 MiB each) and of four [CHUNK, N, 128] ones
# (0.5 MiB each), the state's gradient and A's: 40 MiB at the widths this
# repository runs, where Mosaic's default is 16 and a v5e core has 128.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def kernels_take(seq: int, channels: int, state: int) -> bool:
    """Whether the kernels walk this shape: whole chunks, whole lane groups
    of channels, a state that fills whole sublane tiles."""
    return (seq % CHUNK == 0 and channels % LANES == 0
            and state % SUBLANES == 0)


def kept_bytes(drive, state: int, dtype) -> dict:
    """What a layer keeps of one scan (``kept.note``'s arguments)."""
    B, S, channels = drive.shape
    return {kept.SSM_SCAN: kept.nbytes(drive.shape, dtype)
            + kept.nbytes((B, S // CHUNK, channels, state), jnp.float32)}


def _fwd_kernel(drive_ref, delta_ref, a_ref, b_ref, c_ref, y_ref, starts_ref,
                state, *, groups):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    starts_ref[0, 0] = state[...]

    def step(t, carry):
        b_t, c_t = b_ref[0, t], c_ref[0, t]                 # [N, 128]
        for g in range(groups):
            row = pl.ds(g, 1)
            delta = delta_ref[0, t, row, :]                 # [1, 128]
            s = jnp.exp(delta * a_ref[g]) * state[g] + (
                drive_ref[0, t, row, :] * b_t)
            state[g] = s
            y_ref[0, t, row, :] = jnp.sum(s * c_t, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, CHUNK, step, 0)


def _bwd_kernel(drive_ref, delta_ref, a_ref, b_ref, c_ref, starts_ref, dy_ref,
                ddrive_ref, ddelta_ref, da_ref, db_ref, dc_ref, states, grad,
                *, groups):
    @pl.when(pl.program_id(1) == 0)
    def _():
        grad[...] = jnp.zeros_like(grad)
        da_ref[...] = jnp.zeros_like(da_ref)

    # the chunk's states again: ``states[t]`` before position t, ``[t + 1]``
    # after it
    states[0] = starts_ref[0, 0]

    def forward(t, carry):
        b_t = b_ref[0, t]
        for g in range(groups):
            row = pl.ds(g, 1)
            states[t + 1, g] = (
                jnp.exp(delta_ref[0, t, row, :] * a_ref[g]) * states[t, g]
                + drive_ref[0, t, row, :] * b_t)
        return carry

    jax.lax.fori_loop(0, CHUNK, forward, 0)

    def backward(i, carry):
        t = CHUNK - 1 - i
        b_t, c_t = b_ref[0, t], c_ref[0, t]
        db = jnp.zeros_like(b_t)
        dc = jnp.zeros_like(c_t)
        for g in range(groups):
            row = pl.ds(g, 1)
            delta, a_g = delta_ref[0, t, row, :], a_ref[g]
            dy = dy_ref[0, t, row, :]
            # dL/ds_t: what the later positions hand back and this
            # position's read-out
            g_t = grad[g] + dy * c_t
            dc = dc + dy * states[t + 1, g]
            db = db + g_t * drive_ref[0, t, row, :]
            through = g_t * jnp.exp(delta * a_g)
            grad[g] = through
            pulled = through * states[t, g]     # dL/d(delta_t A) of the pair
            da_ref[0, g] += pulled * delta
            ddelta_ref[0, t, row, :] = jnp.sum(
                pulled * a_g, axis=0, keepdims=True)
            ddrive_ref[0, t, row, :] = jnp.sum(
                g_t * b_t, axis=0, keepdims=True)
        db_ref[0, t] = db
        dc_ref[0, t] = dc
        return carry

    jax.lax.fori_loop(0, CHUNK, backward, 0)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


class _Call:
    """The shapes and block specs of one scan's two calls."""

    def __init__(self, drive, state):
        self.B, self.S, channels = drive.shape
        self.G, self.N = channels // LANES, state
        self.chunks = self.S // CHUNK

    def specs(self, chunk_of):
        """(a ``[B, S, G, 128]`` operand's, a ``[B, S, N, 128]`` one's,
        ``A``'s, the starts') with grid step ``j`` at chunk
        ``chunk_of(j)``."""
        G, N = self.G, self.N
        wide = pl.BlockSpec(
            (1, CHUNK, G, LANES), lambda b, j: (b, chunk_of(j), 0, 0))
        column = pl.BlockSpec(
            (1, CHUNK, N, LANES), lambda b, j: (b, chunk_of(j), 0, 0))
        a_spec = pl.BlockSpec((G, N, LANES), lambda b, j: (0, 0, 0))
        starts = pl.BlockSpec(
            (1, 1, G, N, LANES), lambda b, j: (b, chunk_of(j), 0, 0, 0))
        return wide, column, a_spec, starts

    def shape(self, *dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32)


def _grouped(t):
    """``[B, S, channels]`` -> ``[B, S, G, 128]``."""
    return t.reshape(t.shape[:2] + (t.shape[2] // LANES, LANES))


def _by_group(A):
    """``A`` ``[channels, N]`` -> ``[G, N, 128]``."""
    return jnp.swapaxes(A.reshape(-1, LANES, A.shape[1]), 1, 2)


def _along_lanes(m):
    """``[B, S, N]`` -> ``[B, S, N, 128]``, a value a lane."""
    return jnp.broadcast_to(m[..., None], m.shape + (LANES,))


def _forward(call, drive, delta, A, Bm, Cm, interpret):
    wide, column, a_spec, starts = call.specs(lambda j: j)
    B, S, G, N = call.B, call.S, call.G, call.N
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=G),
        grid=(B, call.chunks),
        in_specs=[wide, wide, a_spec, column, column],
        out_specs=[wide, starts],
        out_shape=[call.shape(B, S, G, LANES),
                   call.shape(B, call.chunks, G, N, LANES)],
        scratch_shapes=[pltpu.VMEM((G, N, LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_grouped(drive), _grouped(delta), _by_group(A), _along_lanes(Bm),
      _along_lanes(Cm))


def _backward(call, drive, delta, A, Bm, Cm, starts, dy, interpret):
    last = call.chunks - 1
    wide, column, a_spec, starts_spec = call.specs(lambda j: last - j)
    B, S, G, N = call.B, call.S, call.G, call.N
    da_spec = pl.BlockSpec((1, G, N, LANES), lambda b, j: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, groups=G),
        grid=(B, call.chunks),
        in_specs=[wide, wide, a_spec, column, column, starts_spec, wide],
        out_specs=[wide, wide, da_spec, column, column],
        out_shape=[call.shape(B, S, G, LANES), call.shape(B, S, G, LANES),
                   call.shape(B, G, N, LANES), call.shape(B, S, N, LANES),
                   call.shape(B, S, N, LANES)],
        scratch_shapes=[pltpu.VMEM((CHUNK + 1, G, N, LANES), jnp.float32),
                        pltpu.VMEM((G, N, LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_grouped(drive), _grouped(delta), _by_group(A), _along_lanes(Bm),
      _along_lanes(Cm), starts, _grouped(dy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(drive, delta, A, Bm, Cm, dtype, interpret):
    return _scan_fwd(drive, delta, A, Bm, Cm, dtype, interpret)[0]


def _scan_fwd(drive, delta, A, Bm, Cm, dtype, interpret):
    call = _Call(drive, A.shape[1])
    y, starts = _forward(call, drive, delta, A, Bm, Cm, interpret)
    # what a layer keeps: ``y`` as its caller takes it, the starts float32
    y, starts = kept.named(
        kept.SSM_SCAN, y.reshape(drive.shape).astype(dtype), starts)
    return y, (drive, delta, A, Bm, Cm, starts)


def _scan_bwd(dtype, interpret, residuals, dy):
    drive, delta, A, Bm, Cm, starts = residuals
    call = _Call(drive, A.shape[1])
    ddrive, ddelta, da, db, dc = _backward(
        call, drive, delta, A, Bm, Cm, starts, dy.astype(jnp.float32),
        interpret)
    # [B, G, N, 128] -> [channels, N]; the lanes' sum of B's and C's
    da = jnp.swapaxes(da.sum(axis=0), 1, 2).reshape(A.shape)
    return (ddrive.reshape(drive.shape), ddelta.reshape(delta.shape), da,
            db.sum(axis=-1), dc.sum(axis=-1))


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def scan_kernels(drive, delta, A, Bm, Cm, *, dtype=jnp.float32,
                 interpret: bool = False):
    """``s_t C_t`` ``[B, S, channels]`` in ``dtype`` (computed in float32,
    rounded once): drive, delta ``[B, S, channels]``, A ``[channels, N]``,
    Bm, Cm ``[B, S, N]``, all float32, at a shape ``kernels_take``.  Under
    ``jax.jit`` so that a program which traces the model more than once
    traces the kernels once."""
    return _scan(drive, delta, A, Bm, Cm, jnp.dtype(dtype), interpret)
