"""Ring reduce-scatter for the grad-sync bucket shapes.

``lax.psum_scatter`` leaves the collective's algorithm to XLA.  This
module owns it instead, FlexLink-style: an explicit ring where each hop
moves one accumulating packet to the right neighbor while every other
hop's packet is in flight — the shape that (a) keeps every ICI link busy
in both the send and receive direction and (b) exposes the per-hop
accumulate as a kernel this repo controls.

Three tiers, selected by ``GradSyncPolicy.transport`` /
``DLROVER_TPU_GRAD_TRANSPORT`` with a correctness fallback to
``lax.psum_scatter`` whenever a tier's preconditions fail:

``ring``
    the ring decomposed at the jax level: ``world - 1`` ``lax.ppermute``
    hops, each followed by an accumulate of the local contribution.
    Runs on every backend (the CPU-mesh tests pin its numerics against
    ``psum_scatter``), and on TPU each hop lowers to a collective
    permute the latency-hiding scheduler can overlap with the
    accumulate of the previous hop.
``ring_pallas``
    the same ring, but the per-hop accumulate runs as a Pallas kernel —
    interpreted on CPU (so the tier-1 tests execute the real kernel
    body) and compiled for the MXU-adjacent VPU on TPU.  Falls back to
    the jnp accumulate when the bucket width doesn't meet the TPU
    tiling precondition (``width % 1024 == 0``).
``ring_rdma`` (prototype, additionally gated by
    ``DLROVER_TPU_GRAD_RING_RDMA=1``)
    the whole reduce-scatter as ONE Pallas TPU kernel: double-buffered
    ``pltpu.make_async_remote_copy`` RDMA around the ring with neighbor
    barrier semaphores, per the accelerator guide's ring-collective
    pattern.  TPU-only (remote DMA has no interpret-mode execution
    path here); anything else falls back to the jax-level ring.
``ring_pallas_q`` (r21, QUANTIZED buckets)
    the fused-quantization exchange: the blockwise codec ENCODE runs
    inside a Pallas kernel (:func:`fused_quantize` — codes, scales and
    the error-feedback dequant produced in one pass) and the exchange
    is decomposed into ``world - 1`` shifted ``ppermute`` hops whose
    decode + accumulate is a second fused kernel
    (:func:`fused_dequant_add`) — the full-width ``(world, width)``
    fp32 decode buffer the two-stage all_to_all path materializes in
    HBM between quantize and exchange never exists.  Interpreted on
    CPU so tier-1 executes the real kernel bodies.  The orchestration
    (padding, residuals, tolls) lives in
    ``parallel.collectives._quantized_ring_exchange``.

All tiers compute the same mathematical result as
``lax.psum_scatter(..., tiled=True)``; the ring sums in hop order, so
fp32 results agree with psum_scatter to reduction-order rounding (the
equivalence tests use integer-valued payloads for bit-exactness —
``ring_pallas_q`` additionally pins its per-source encode, and thus
the error-feedback residuals, bit-identical to the two-stage path).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

RING_TRANSPORTS = ("ring", "ring_pallas", "ring_rdma", "ring_pallas_q")

#: codec formats the fused-quantization kernels implement.  blockwise
#: rides the int4 kernels for its base codes; the (tiny) int8
#: refinement is applied by the collectives-layer orchestration.
QUANT_RING_FORMATS = ("int8", "int4", "blockwise")

# TPU tiling precondition for the compiled accumulate kernel: rows of
# (8, 128) fp32 tiles, so the packet must reshape to (width//128, 128)
# with the row count a multiple of 8.
_TPU_TILE_ELEMS = 8 * 128


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


def _pallas_add(a, b, interpret: bool):
    """Elementwise accumulate as a Pallas kernel.  ``a``/``b`` arrive as
    ``(width,)`` packets; reshaped to lane-tiled 2D for Mosaic."""
    width = a.shape[0]
    shaped = a.reshape(width // 128, 128)
    out = pl.pallas_call(
        _add_kernel,
        out_shape=jax.ShapeDtypeStruct(shaped.shape, a.dtype),
        interpret=interpret,
    )(shaped, b.reshape(shaped.shape))
    return out.reshape(width)


def pallas_accum_supported(width: int) -> bool:
    return width % _TPU_TILE_ELEMS == 0


# -- fused-quantization kernels (`ring_pallas_q`) ---------------------------
#
# The quantize math must stay BIT-IDENTICAL to the two-stage codecs in
# ``parallel.collectives`` (blockwise_quantize / blockwise_quantize4 /
# their dequantizers): the error-feedback residual is derived from the
# kernel's own dequant output, so any op-order drift here would silently
# fork the EF state between transports.  int4 dequantizes THROUGH the
# packed nibbles (sign-extending arithmetic shifts), exactly like the
# receiver-side decode.


def _q8_encode_kernel(x_ref, q_ref, s_ref, d_ref):
    x = x_ref[...]
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale
    d_ref[...] = q.astype(jnp.float32) * scale


def _q4_encode_kernel(x_ref, q_ref, s_ref, d_ref):
    x = x_ref[...]
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -7, 7).astype(jnp.int8)
    lo = q[..., 0::2]
    hi = q[..., 1::2]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, jnp.int8(0x0F)), jnp.left_shift(hi, 4)
    ).astype(jnp.int8)
    q_ref[...] = packed
    s_ref[...] = scale
    plo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    phi = jnp.right_shift(packed, 4)
    uq = jnp.stack([plo, phi], axis=-1).reshape(x.shape)
    d_ref[...] = uq.astype(jnp.float32) * scale


def _q8_accum_kernel(q_ref, s_ref, a_ref, o_ref):
    o_ref[...] = a_ref[...] + q_ref[...].astype(jnp.float32) * s_ref[...]


def _q4_accum_kernel(q_ref, s_ref, a_ref, o_ref):
    packed = q_ref[...]
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    uq = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],)
    )
    o_ref[...] = a_ref[...] + uq.astype(jnp.float32) * s_ref[...]


def pallas_q_supported(block: int, qformat) -> bool:
    """`ring_pallas_q` kernel precondition: a codec format the fused
    kernels implement, with block rows lane-aligned both full-width and
    nibble-packed (``block % 256``; int4 packing halves the lane dim)."""
    return qformat in QUANT_RING_FORMATS and block % 256 == 0


def fused_quantize(x, fmt: str, interpret: bool):
    """Encode ``x`` of shape ``(world, nblk, block)`` in ONE fused
    Pallas pass: per-block max-abs scales, nearest-rounded codes, and
    the dequantized view the caller turns into the error-feedback
    residual — no intermediate full-width array lands between the
    stages.  ``fmt``: ``int8`` or ``int4`` (packed nibbles).  Returns
    ``(codes, scales, dequant)`` with leading dims restored."""
    world, nblk, block = x.shape
    rows = world * nblk
    flat = x.reshape(rows, block)
    if fmt == "int8":
        kernel, qcols = _q8_encode_kernel, block
    elif fmt == "int4":
        kernel, qcols = _q4_encode_kernel, block // 2
    else:
        raise ValueError(f"no fused encode kernel for format {fmt!r}")
    q, s, d = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((rows, qcols), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, block), jnp.float32),
        ],
        interpret=interpret,
    )(flat)
    return (
        q.reshape(world, nblk, qcols),
        s.reshape(world, nblk, 1),
        d.reshape(world, nblk, block),
    )


def fused_dequant_add(acc, q, s, fmt: str, interpret: bool):
    """One ring hop's decode + accumulate as a fused Pallas kernel:
    ``acc + dequant(q, s)`` for a single arriving chunk — ``acc`` of
    shape ``(nblk, block)``, ``q`` ``(nblk, block[//2])``, ``s``
    ``(nblk, 1)``.  The arriving codes never expand into a standalone
    fp32 buffer outside the kernel."""
    if fmt == "int8":
        kernel = _q8_accum_kernel
    elif fmt == "int4":
        kernel = _q4_accum_kernel
    else:
        raise ValueError(f"no fused accumulate kernel for format {fmt!r}")
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        interpret=interpret,
    )(q, s, acc)


def ring_reduce_scatter(x, axis: str, world: int, accum: str = "jnp",
                        interpret: Optional[bool] = None):
    """Inside shard_map: reduce-scatter ``x`` of shape ``(world, width)``
    over ``axis`` with an explicit ppermute ring.

    Replica ``r`` returns ``sum_j x_j[r]`` of shape ``(width,)`` — the
    same contract as ``lax.psum_scatter(x, axis, scatter_dimension=0,
    tiled=True)`` reshaped to a row.

    The packet created on replica ``s`` carries the chunk destined for
    replica ``(s - 1) % world``; after ``world - 1`` right-hops every
    replica has hosted (and accumulated into) exactly the packet that
    ends on it.  ``accum="pallas"`` runs each hop's accumulate through
    :func:`_pallas_add` (interpreted off-TPU so tests execute the real
    kernel body).
    """
    if world <= 1:
        return x.reshape(-1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    width = x.shape[1]
    use_pallas = accum == "pallas" and pallas_accum_supported(width)

    def row(c):
        return lax.dynamic_slice_in_dim(x, c, 1, axis=0)[0]

    def add(p, c):
        contrib = row(c)
        if use_pallas:
            return _pallas_add(p, contrib, interpret)
        return p + contrib

    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % world) for i in range(world)]
    p = row(jnp.mod(idx - 1, world))
    for t in range(world - 1):
        p = lax.ppermute(p, axis, perm)
        p = add(p, jnp.mod(idx - t - 2, world))
    return p


# -- RDMA prototype ---------------------------------------------------------


def _rdma_ring_kernel(x_ref, o_ref, comm_ref, send_sem, recv_sem,
                      hand_sem, *, axis: str, world: int):
    """One-kernel ring reduce-scatter: double-buffered remote copies.

    Packets are lane-tiled 2-D ``(rows, 128)`` blocks (remote DMA
    rejects 1-D refs).  ``comm_ref`` is a 2-slot VMEM scratch; slot
    parity alternates per hop so hop ``t+1``'s send never overwrites
    the buffer hop ``t`` is still landing into on the neighbor.

    A per-hop neighbor handshake precedes every send: ``rdma.wait()``
    orders a device against its own send and its inbound from the
    LEFT, but nothing orders it against its RIGHT neighbor — without
    the handshake, my hop ``t+1`` write into the right neighbor's slot
    ``t%2`` could land while that neighbor's hop-``t`` outbound DMA is
    still reading the same slot.  The handshake uses one REGULAR
    semaphore PER DIRECTION (``hand_sem[0]`` signaled by my left,
    ``[1]`` by my right): a single shared counter could be satisfied
    by two early signals from the same fast neighbor, which is exactly
    the skew the handshake exists to exclude.  It costs one
    hop-latency per hop; a credit-based free-slot scheme could
    pipeline that away (future work — this tier is a prototype).
    """
    my = lax.axis_index(axis)
    left = jax.lax.rem(my + world - 1, world)
    right = jax.lax.rem(my + 1, world)

    # entry barrier: nobody's remote writes may land before every
    # neighbor has entered the kernel (scratch buffers live)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)

    def local_row(c):
        return x_ref[pl.ds(c, 1)][0]

    acc = local_row(jax.lax.rem(my + world - 1, world))
    for t in range(world - 1):
        send_slot = t % 2
        recv_slot = (t + 1) % 2
        # tell each neighbor this device reached hop t, then wait for
        # BOTH to arrive: the right neighbor's hop-(t-1) outbound is
        # done reading the slot this hop's remote write lands in
        pltpu.semaphore_signal(hand_sem.at[1], inc=1, device_id=left)
        pltpu.semaphore_signal(hand_sem.at[0], inc=1, device_id=right)
        pltpu.semaphore_wait(hand_sem.at[0], 1)
        pltpu.semaphore_wait(hand_sem.at[1], 1)
        comm_ref[send_slot] = acc
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[send_slot],
            dst_ref=comm_ref.at[recv_slot],
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        own = jax.lax.rem(my + 2 * world - t - 2, world)
        acc = comm_ref[recv_slot] + local_row(own)
    o_ref[...] = acc


def rdma_ring_reduce_scatter(x, axis: str, world: int):
    """The ring as ONE Pallas TPU kernel (prototype; see module doc).

    Preconditions (checked by the caller's transport selection): TPU
    backend, ``world > 1``, packet width lane-aligned (``width % 128 ==
    0``).  The whole ``(world, width)`` buffer must fit VMEM alongside
    the 2-slot comm scratch — true for the grad-sync bucket sizes this
    exists for (buckets default to 4 MB).  Lowering through the Mosaic
    TPU pipeline is exercised by the bench's degraded-mode evidence;
    on-device execution awaits a multi-chip round.
    """
    width = x.shape[1]
    rows = width // 128
    kernel = functools.partial(_rdma_ring_kernel, axis=axis, world=world)
    compiler_params = pltpu.CompilerParams(collective_id=13)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 128), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, rows, 128), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=compiler_params,
    )(x.reshape(x.shape[0], rows, 128))
    return out.reshape(width)


def select_transport(transport: str, quantized: bool, world: int,
                     width: int, rdma_enabled: bool,
                     multi_axis: bool = False, qformat=None,
                     rounding: str = "nearest",
                     block_size: int = 256) -> str:
    """Resolve a policy transport request to what actually runs, with
    the correctness fallback chain.  Returns one of ``"all_to_all"``
    (the codec exchange — the quantized default), ``"ring_pallas_q"``
    (the fused-quantization ring), ``"psum_scatter"``, ``"ring"``,
    ``"ring_pallas"``, ``"ring_rdma"``.

    Quantized buckets default to the all_to_all exchange (their payload
    is a multi-array codec, not a single fp32 buffer); an explicit
    ``ring_pallas_q`` request routes them through the fused-quantize
    ring instead when the kernel preconditions hold (single named axis,
    nearest rounding — the fused encode carries no PRNG plumbing — and
    a lane-aligned ``block_size``).  An explicit ``all_to_all`` request
    on an exact bucket resolves to ``psum_scatter``, the stock
    single-buffer collective (there is no separate exact all_to_all
    implementation).

    ``multi_axis``: the collective spans a TUPLE of mesh axes (the flat
    combined ``(slice, dp)`` baseline on a two-level mesh) — the ring
    kernels address one named axis, so those buckets take the stock
    collective / codec exchange.
    """
    if quantized:
        if (
            transport == "ring_pallas_q"
            and world > 1
            and not multi_axis
            and rounding == "nearest"
            and pallas_q_supported(block_size, qformat)
        ):
            return "ring_pallas_q"
        return "all_to_all"
    if world <= 1 or transport in ("auto", "all_to_all") or multi_axis:
        return "psum_scatter"
    if transport == "ring":
        return "ring"
    if transport in ("ring_pallas", "ring_pallas_q"):
        # a ring_pallas_q request on an EXACT bucket has no codec to
        # fuse; the plain Pallas-accumulate ring is its exact-mode twin
        return "ring_pallas" if pallas_accum_supported(width) else "ring"
    if transport == "ring_rdma":
        if (
            rdma_enabled
            and jax.default_backend() == "tpu"
            and width % 128 == 0
        ):
            return "ring_rdma"
        # correctness fallback: the jax-level ring is semantically
        # identical and runs everywhere
        return "ring_pallas" if pallas_accum_supported(width) else "ring"
    return "psum_scatter"


def resolve_transport(policy, world: int, width: int, axis,
                      rdma_enabled=None, request=None) -> str:
    """THE transport-resolution helper: every consumer of a
    ``GradSyncPolicy`` + sync-axis pair (``bucket_reduce_scatter``,
    ``commscope.BucketScope.transport_of``, the trainer's
    ``grad_sync_summary`` and ``parallel.fabric_tuner``) derives the
    resolved per-bucket transport HERE instead of each re-assembling
    ``select_transport`` arguments — one place for the fallback chain
    to be right.

    ``request`` overrides the policy's transport field (the tuner's
    per-bucket decision); the fallback chain still applies, so an
    infeasible override degrades to a correct tier instead of failing.
    """
    if rdma_enabled is None:
        from dlrover_tpu.common import envs

        rdma_enabled = envs.get_bool("DLROVER_TPU_GRAD_RING_RDMA")
    return select_transport(
        request if request is not None else policy.transport,
        policy.quantized, world, width, bool(rdma_enabled),
        multi_axis=not isinstance(axis, str),
        qformat=policy.qformat, rounding=policy.rounding,
        block_size=policy.block_size,
    )
