"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752): a
linear recurrence whose state is a vector a channel and whose decay differs
by channel AND by state column.

Per sequence, with ``a_t`` the layer's activation ``[channels]``, ``delta_t``
its step size ``[channels]`` (positive), ``A`` ``[channels, N]`` (negative),
``B_t`` and ``C_t`` ``[N]`` and ``D`` ``[channels]``::

    s_t = exp(delta_t A) * s_{t-1} + (delta_t a_t) B_t^T,    s_0 = 0
    y_t = s_t C_t + D * a_t

``selective_scan_recurrent`` is that, a position at a time (the tests'
yardstick and the shape a decoding step would take).  Because the decay
``exp(delta_t[c] A[c, n])`` is a number for every (channel, column) pair
there is no chunk of matrix products to fall back on (Mamba-2's scalar decay
a head is what makes that form): the work is elementwise, 16 updates a
channel and position.  What must not happen is the state's history ``[S,
channels, N]`` in HBM.

**Which body runs where.**  ``selective_scan`` is the one entry.  On a TPU,
under no mesh of several devices, at a length that is a multiple of
``CHUNK``, channels a multiple of the 128 lanes and a state that fills
whole sublane tiles (``scan_path``), the recurrence goes through the Pallas
kernels of ``ops/pallas/selective_scan.py``, forward and backward behind one
``jax.custom_vjp``: the state of all channels stays in VMEM while the
kernel walks the positions and only ``y`` and the state at chunk boundaries
leave it.  Everywhere else (off the chip, a ragged length, a small state) it
is the ``jax.numpy`` body of this file: an associative scan inside a chunk
(``[chunk, channels, N]`` at a time), a carry between chunks, the chunk's
body under ``jax.checkpoint`` so that its backward pass holds one chunk's
history too.  Both compute in float32 whatever they are given; the skip
``D * a`` and the drive ``delta * a`` are ``jax.numpy`` on both paths.
``scan_core`` says which was taken, for the ``attention.path`` event.
"""

import jax
import jax.numpy as jnp

#: positions between two kept states, and the ``jax.numpy`` body's chunk
CHUNK = 64


def selective_scan_recurrent(a, delta, A, Bm, Cm, D):
    """The recurrence a position at a time in float32: a, delta ``[B, S,
    channels]``, A ``[channels, N]``, Bm, Cm ``[B, S, N]``, D ``[channels]``
    -> ``[B, S, channels]``."""
    a, delta, A, Bm, Cm, D = (
        jnp.asarray(t, jnp.float32) for t in (a, delta, A, Bm, Cm, D))

    def step(state, at):
        a_t, delta_t, b_t, c_t = at
        state = jnp.exp(delta_t[..., None] * A) * state + (
            delta_t * a_t)[..., None] * b_t[:, None, :]
        return state, jnp.einsum("bcn,bn->bc", state, c_t) + D * a_t

    state = jnp.zeros((a.shape[0],) + A.shape, jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (a, delta, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def scan_path(backend: str, seq: int, channels: int, state: int,
              devices: int = 1) -> str:
    """``"pallas"`` or ``"jnp"``: which body walks ``seq`` positions of
    ``channels`` channels with ``state`` columns each (as
    ``ops/linear_attention.py::kda_path``).  ``devices``: the active
    mesh's; a Mosaic kernel cannot be partitioned automatically, and under
    several devices the ``jax.numpy`` body is what GSPMD shards."""
    from dlrover_tpu.ops.pallas.selective_scan import kernels_take

    if backend == "tpu" and devices == 1 and kernels_take(
            seq, channels, state):
        return "pallas"
    return "jnp"


def scan_core(seq: int, channels: int, state: int) -> dict:
    """What ``selective_scan`` takes at these shapes on this backend and
    mesh, as the fields of the ``attention.path`` event."""
    from dlrover_tpu.ops.ring_attention import active_mesh

    mesh = active_mesh()
    core = scan_path(jax.default_backend(), seq, channels, state,
                     1 if mesh is None else mesh.size)
    return dict(core=core, chunk=min(CHUNK, seq))


def selective_scan(a, delta, A, Bm, Cm, D, dtype=jnp.float32):
    """``y`` ``[B, S, channels]`` float32 of the recurrence above: a, delta
    ``[B, S, channels]``, A ``[channels, N]``, Bm, Cm ``[B, S, N]``, D
    ``[channels]``, each taken to float32.  ``dtype``: what the recurrence's
    part ``s_t C_t`` is rounded to, once, before the skip is added (the
    model's compute dtype: it is what a rematerialised layer keeps of the
    kernels, and in bfloat16 it is half).  Through the Pallas kernels where
    ``scan_path`` says so, else the ``jax.numpy`` body below."""
    a, delta, A, Bm, Cm, D = (
        jnp.asarray(t, jnp.float32) for t in (a, delta, A, Bm, Cm, D))
    S, channels = a.shape[1:]
    with jax.named_scope("scan"):
        drive = delta * a
        if scan_core(S, channels, A.shape[1])["core"] == "pallas":
            y = _scan_kernels(drive, delta, A, Bm, Cm, dtype)
        else:
            y = _scan_chunked(drive, delta, A, Bm, Cm, CHUNK).astype(dtype)
        return y.astype(jnp.float32) + D * a


def _scan_kernels(drive, delta, A, Bm, Cm, dtype=jnp.float32,
                  interpret=False):
    """The kernels' entry, a name of this module so that a test can run
    them in the interpreter."""
    from dlrover_tpu.ops.pallas import kept
    from dlrover_tpu.ops.pallas.selective_scan import kept_bytes, scan_kernels

    kept.note("ssm", **kept_bytes(drive, A.shape[1], dtype))
    return scan_kernels(drive, delta, A, Bm, Cm, dtype=dtype,
                        interpret=interpret)


def _scan_chunked(drive, delta, A, Bm, Cm, chunk):
    """``s_t C_t`` of ``s_t = exp(delta_t A) s_{t-1} + drive_t B_t^T`` in
    ``jax.numpy``: off the chip and at shapes the kernels do not take, and
    beside ``selective_scan_recurrent`` the kernels' yardstick.  A length
    that is no multiple of the chunk is padded with positions that change
    nothing (``delta = 0``, ``drive = 0``)."""
    B, S, channels = drive.shape
    C = min(chunk, S)
    pad = -S % C
    if pad:
        drive, delta, Bm, Cm = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
            for t in (drive, delta, Bm, Cm))

    def chunks(t):      # [B, S, ...] -> [n, B, C, ...]
        return jnp.moveaxis(
            t.reshape((B, (S + pad) // C, C) + t.shape[2:]), 1, 0)

    def combine(left, right):
        # ``x -> right_decay (left_decay x + left_add) + right_add``
        return right[0] * left[0], right[0] * left[1] + right[1]

    @jax.checkpoint
    def one_chunk(state, at):
        drive_, delta_, b_, c_ = at
        decay = jnp.exp(delta_[..., None] * A)              # [B, C, ch, N]
        add = drive_[..., None] * b_[:, :, None, :]
        through, added = jax.lax.associative_scan(
            combine, (decay, add), axis=1)
        states = through * state[:, None] + added
        return states[:, -1], jnp.einsum("bscn,bsn->bsc", states, c_)

    _, y = jax.lax.scan(
        one_chunk, jnp.zeros((B,) + A.shape, jnp.float32),
        tuple(chunks(t) for t in (drive, delta, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(B, S + pad, channels)[:, :S]
