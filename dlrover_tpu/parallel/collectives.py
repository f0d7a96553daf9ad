"""Communication-efficient data-parallel gradient sync.

The default data-parallel sync is a full-precision XLA all-reduce of every
gradient followed by a fully replicated optimizer update on every dp
replica.  Both halves are redundant work (EQuARX, "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training" — PAPERS.md):

* **Quantized all-reduce**: the all-reduce is decomposed (``shard_map``
  over the dp axis) into a reduce-scatter whose payload is blockwise
  int8-quantized (per-block max-abs scale, nearest or stochastic
  rounding) followed by a full-precision all-gather.  The quantization
  error is NOT lost: every replica keeps an **error-feedback residual**
  (one full-gradient-sized buffer, dp-sharded across replicas as a
  ``(world, *leaf)`` leading-axis stack in ``TrainState.ef_residual``)
  that is re-injected into the next step's gradient before quantizing —
  the standard EF trick that keeps SGD/Adam convergence intact while the
  wire carries ~1/4 of the reduce-scatter bytes.

* **Sharded weight update (ZeRO-1 over dp)**: after the (quantized or
  exact) reduce-scatter each replica holds one 1/world slice of the mean
  gradient, so it runs the optax update only on that slice against
  dp-sharded optimizer moments and all-gathers the updated params —
  optimizer-state HBM and update FLOPs drop by the dp degree.  Moment
  leaves keep their full *global* shapes (the dp shard is expressed in
  the ``NamedSharding``), so flash-checkpoint reshard restore across dp
  degrees keeps working unchanged.

Layout rule: a leaf shards along its first dimension divisible by the dp
world size; leaves with no such dimension (odd shapes, scalars) ride an
exact ``psum`` and a replicated update — the same fallback the automatic
weight-update-sharding paper uses for non-divisible tensors.

Everything here is pure-jax and mesh-agnostic: the numerics are fully
testable on a virtual CPU mesh (``tests/test_grad_sync.py``).
"""

import dataclasses
import math
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map as _shard_map


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking off (``check_vma``).
    Needed because values produced from psum'd inputs through an optax
    update ARE replicated, but the checker cannot prove it."""
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


GRAD_SYNC_MODES = (
    "exact", "exact_sharded",
    "int8", "int8_sharded",
    "int4", "int4_sharded",
    "blockwise", "blockwise_sharded",
)

_QUANT_PREFIXES = ("int8", "int4", "blockwise")

TRANSPORTS = (
    "auto", "all_to_all", "ring", "ring_pallas", "ring_rdma",
    "ring_pallas_q",
)

#: wire codecs the hierarchical DCN leg may use (r18): ``exact`` keeps
#: the cross-slice exchange full-precision; the quantized tiers apply
#: the EQuARX observation that cross-fabric hops tolerate heavier
#: quantization than intra-fabric ones.
DCN_FORMATS = ("exact", "int8", "int4", "blockwise")


@dataclasses.dataclass(frozen=True)
class GradSyncPolicy:
    """Data-parallel gradient sync policy (``Trainer(grad_sync=...)``).

    Modes:

    ``exact``
        the GSPMD status quo: full-precision all-reduce inserted by XLA,
        replicated update.  No shard_map, no behavior change.
    ``exact_sharded``
        fp32 reduce-scatter + dp-sharded optimizer update (ZeRO-1) +
        param all-gather.  Bitwise-equivalent update math, 1/world the
        optimizer-state HBM and update FLOPs.
    ``int8`` / ``int4``
        blockwise int8- (or packed int4-) quantized reduce-scatter with
        error feedback, then a full-precision grad all-gather and
        replicated update (isolates the quantization effect for A/B
        runs).
    ``blockwise``
        mixed-precision by grad statistics: every block ships packed
        int4, and the top ``hi_frac`` blocks per chunk by magnitude
        additionally ship an int8 refinement that overrides the int4
        decode — the high-dynamic-range blocks that dominate the
        quantization error get 16 levels -> 255 levels for a few
        percent extra wire bytes.  Error feedback absorbs the rest.
    ``*_sharded``
        the same wire format + ZeRO-1 sharded update + param
        all-gather.

    ``bucket_mb`` (r14): >0 packs shardable leaves into deterministic
    size-targeted buckets (``parallel.bucketing``) so each bucket moves
    through ONE fused collective whose chain is independent of every
    other bucket's — the overlap-friendly shape.  ``None`` resolves
    from ``DLROVER_TPU_GRAD_BUCKET_MB`` at trainer configure time;
    ``0`` keeps the r6 per-leaf collectives.

    ``transport`` selects the exact-bucket reduce-scatter
    implementation (``auto`` = ``lax.psum_scatter``; the ``ring*``
    tiers are the explicit ring / Pallas kernels in
    ``ops.pallas.ring_reduce_scatter``, with automatic correctness
    fallback).  Quantized buckets always exchange via ``all_to_all``.

    ``clip_norm``: the sharded paths compute the *global* grad norm with
    a cross-replica psum and pre-scale the gradient shards, because an
    optax ``clip_by_global_norm`` inside the chain would only ever see
    one replica's shard.  Pass the optimizer WITHOUT its clip stage and
    set the bound here instead (``docs/design.md``).
    """

    mode: str = "exact"
    block_size: int = 256
    rounding: str = "nearest"  # or "stochastic"
    clip_norm: Optional[float] = None
    seed: int = 17
    bucket_mb: Optional[float] = None  # None: DLROVER_TPU_GRAD_BUCKET_MB
    transport: str = "auto"  # auto|all_to_all|ring|ring_pallas|ring_rdma
    hi_frac: Optional[float] = None  # None: DLROVER_TPU_GRAD_HI_FRAC
    # r18 topology awareness: on a mesh with an active slice axis,
    # `hierarchical` decomposes the dp sync into ICI reduce-scatter ->
    # one aggregated DCN exchange in the heavier `dcn_format` codec ->
    # intra-slice all-gather.  None defers both to the env registry
    # (DLROVER_TPU_GRAD_HIERARCHICAL / DLROVER_TPU_GRAD_DCN_FORMAT);
    # False forces the flat combined-axis collectives even on a
    # two-level mesh (the bench baseline).
    hierarchical: Optional[bool] = None
    dcn_format: Optional[str] = None  # exact|int8|int4|blockwise
    # r21 dual-fabric striping: the fraction of each hierarchical
    # bucket's columns routed DCN-FIRST (cross-slice exchange of the
    # full-width striped block, concurrent with the ICI stage of the
    # rest) instead of through the ICI-first two-level chain — the
    # FlexLink observation that the second fabric is idle bandwidth
    # while it waits for the aggregated stage-2 chunk.  None defers to
    # DLROVER_TPU_GRAD_STRIPE (default 0 = no striping); the
    # fabric_tuner overrides it per bucket from measured link data.
    stripe: Optional[float] = None

    def __post_init__(self):
        if self.mode not in GRAD_SYNC_MODES:
            raise ValueError(
                f"unknown grad_sync mode {self.mode!r}; "
                f"expected one of {GRAD_SYNC_MODES}"
            )
        if self.rounding not in ("nearest", "stochastic"):
            raise ValueError(f"unknown rounding {self.rounding!r}")
        if self.block_size < 8 or self.block_size % 2:
            raise ValueError("block_size must be >= 8 and even")
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.transport!r}; "
                f"expected one of {TRANSPORTS}"
            )
        if self.bucket_mb is not None and self.bucket_mb < 0:
            raise ValueError("bucket_mb must be >= 0")
        if self.hi_frac is not None and not (0.0 < self.hi_frac <= 1.0):
            raise ValueError("hi_frac must be in (0, 1]")
        if self.dcn_format is not None and self.dcn_format not in DCN_FORMATS:
            raise ValueError(
                f"unknown dcn_format {self.dcn_format!r}; "
                f"expected one of {DCN_FORMATS}"
            )
        if self.stripe is not None and not (0.0 <= self.stripe < 1.0):
            raise ValueError("stripe must be in [0, 1)")

    @property
    def active(self) -> bool:
        return self.mode != "exact"

    @property
    def quantized(self) -> bool:
        return self.mode.startswith(_QUANT_PREFIXES)

    @property
    def qformat(self) -> Optional[str]:
        """Wire codec: ``int8`` / ``int4`` / ``blockwise`` / None."""
        for prefix in _QUANT_PREFIXES:
            if self.mode.startswith(prefix):
                return prefix
        return None

    @property
    def sharded_update(self) -> bool:
        return self.mode.endswith("_sharded")

    def resolve(self) -> "GradSyncPolicy":
        """Fill env-deferred fields (``bucket_mb``, ``hi_frac``,
        ``transport``) from the knob registry.  Called once at trainer
        configure time so the policy a step compiles against is
        concrete and hashable."""
        from dlrover_tpu.common import envs

        bucket = self.bucket_mb
        if bucket is None:
            bucket = envs.get_float("DLROVER_TPU_GRAD_BUCKET_MB")
        transport = self.transport
        if transport == "auto":
            transport = envs.get_str("DLROVER_TPU_GRAD_TRANSPORT")
        hi = self.hi_frac
        if hi is None:
            hi = envs.get_float("DLROVER_TPU_GRAD_HI_FRAC")
        hier = self.hierarchical
        if hier is None:
            hier = envs.get_bool("DLROVER_TPU_GRAD_HIERARCHICAL")
        dcn = self.dcn_format
        if dcn is None:
            dcn = envs.get_str("DLROVER_TPU_GRAD_DCN_FORMAT")
            if dcn not in DCN_FORMATS:
                from dlrover_tpu.common.log import logger

                logger.warning(
                    "DLROVER_TPU_GRAD_DCN_FORMAT=%r unknown; using int4",
                    dcn,
                )
                dcn = "int4"
        stripe = self.stripe
        if stripe is None:
            stripe = envs.get_float("DLROVER_TPU_GRAD_STRIPE")
            if not 0.0 <= stripe < 1.0:
                from dlrover_tpu.common.log import logger

                logger.warning(
                    "DLROVER_TPU_GRAD_STRIPE=%r out of [0, 1); using 0",
                    stripe,
                )
                stripe = 0.0
        return dataclasses.replace(
            self, bucket_mb=float(bucket), transport=transport,
            hi_frac=float(hi), hierarchical=bool(hier), dcn_format=dcn,
            stripe=float(stripe),
        )

    def dcn_policy(self) -> Optional["GradSyncPolicy"]:
        """The wire-codec policy of the hierarchical DCN leg, or None
        for an exact cross-slice exchange.  Only quantized base modes
        get a quantized DCN leg: the stage-2 quantization error lives
        in the same per-leaf error-feedback stacks the base mode
        already carries, and exact modes have none."""
        fmt = self.dcn_format or "int4"
        if not self.quantized or fmt == "exact":
            return None
        return dataclasses.replace(self, mode=fmt)

    def hi_blocks(self, nblk: int) -> int:
        """Blockwise mode: refined-block count for an ``nblk``-block
        chunk (at least one — a chunk always has a dominant block)."""
        frac = self.hi_frac if self.hi_frac is not None else 0.125
        return max(1, min(nblk, int(round(nblk * frac))))

    @classmethod
    def parse(cls, spec) -> "GradSyncPolicy":
        if spec is None:
            return cls()
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            return cls(mode=spec)
        raise TypeError(f"grad_sync must be a mode string or policy: {spec!r}")


# -- pytree plumbing -------------------------------------------------------

# the SAME rendering the flash-checkpoint snapshot meta uses — the
# elastic restore matches leaves across the two by these strings
from dlrover_tpu.common.pytree import path_str as _path_str  # noqa: E402


def leaf_items(tree) -> List[Tuple[str, Any]]:
    """(path, leaf) pairs in flatten order (same path scheme the
    flash-checkpoint snapshot meta uses)."""
    return [
        (_path_str(kp), leaf)
        for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def _map_leaves(fn, tree):
    """tree_map with the leaf's path string as first argument."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [fn(_path_str(kp), leaf) for kp, leaf in flat]
    )


def shard_dim_for(shape, world: int) -> Optional[int]:
    """First dimension divisible by ``world`` (the dp shard axis for
    this leaf), or None when the leaf must stay replicated."""
    if world <= 1:
        return None
    for dim, size in enumerate(shape):
        if size >= world and size % world == 0:
            return dim
    return None


class GradLayout:
    """Static per-leaf shard decisions for one params pytree."""

    def __init__(self, params, world: int):
        self.world = int(world)
        self.dims: Dict[str, Optional[int]] = {
            path: shard_dim_for(tuple(leaf.shape), self.world)
            for path, leaf in leaf_items(params)
        }

    def sharded_paths(self) -> List[str]:
        return [p for p, d in self.dims.items() if d is not None]


# -- blockwise int8 quantization ------------------------------------------


def blockwise_quantize(blocks, rounding: str = "nearest", key=None):
    """Quantize ``blocks`` (..., block) to (int8, per-block scale).

    scale = max|block| / 127; zero blocks quantize to zeros with scale 0
    (dequantization multiplies by the stored scale, so the 1.0 divisor
    guard never leaks into values).  ``stochastic`` rounding needs a PRNG
    key and makes the quantizer unbiased per element.
    """
    blocks = blocks.astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    x = blocks / safe
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        x = jnp.floor(x + jax.random.uniform(key, x.shape))
    else:
        x = jnp.round(x)
    q = jnp.clip(x, -127, 127).astype(jnp.int8)
    return q, scale


def blockwise_dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def blockwise_quantize4(blocks, rounding: str = "nearest", key=None):
    """Packed int4 variant of :func:`blockwise_quantize`: codes in
    [-7, 7] with scale ``max|block| / 7``, two codes per int8 byte
    (even element in the low nibble).  The block size must be even
    (``GradSyncPolicy`` enforces it)."""
    blocks = blocks.astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 7.0
    safe = jnp.where(scale > 0, scale, 1.0)
    x = blocks / safe
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        x = jnp.floor(x + jax.random.uniform(key, x.shape))
    else:
        x = jnp.round(x)
    q = jnp.clip(x, -7, 7).astype(jnp.int8)
    lo = q[..., 0::2]
    hi = q[..., 1::2]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, jnp.int8(0x0F)), jnp.left_shift(hi, 4)
    ).astype(jnp.int8)
    return packed, scale


def blockwise_dequantize4(packed, scale):
    """Inverse of :func:`blockwise_quantize4` (arithmetic shifts
    sign-extend the nibbles)."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    q = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],)
    )
    return q.astype(jnp.float32) * scale


# -- wire codecs (shared by the per-bucket exchange and the bytes
#    accounting) ------------------------------------------------------------


def encode_chunks(flat, policy: "GradSyncPolicy", key=None) -> Dict[str, Any]:
    """Quantize ``flat`` of shape ``(world, nblk, block)`` into the
    policy's wire payload — a dict of arrays whose LEADING axis is the
    destination-replica axis, so the caller can push every entry
    through one ``all_to_all`` each.

    ``int8``: {q8, s8}.  ``int4``: {q4, s4} (packed nibbles).
    ``blockwise``: {q4, s4, idx, q8, s8} — int4 for every block plus an
    int8 refinement of the top ``hi_blocks`` blocks per chunk by
    max-abs (per-block precision selection by grad statistics); the
    receiver's decode overrides the refined blocks' int4 codes.
    """
    fmt = policy.qformat
    if fmt == "int8":
        q8, s8 = blockwise_quantize(flat, policy.rounding, key)
        return {"q8": q8, "s8": s8}
    if fmt == "int4":
        q4, s4 = blockwise_quantize4(flat, policy.rounding, key)
        return {"q4": q4, "s4": s4}
    if fmt == "blockwise":
        nblk = flat.shape[1]
        k = policy.hi_blocks(nblk)
        maxabs = jnp.max(jnp.abs(flat), axis=-1)  # (world, nblk)
        _, idx = lax.top_k(maxabs, k)  # (world, k)
        hi = jnp.take_along_axis(flat, idx[..., None], axis=1)
        key4 = key8 = None
        if key is not None:
            key4 = jax.random.fold_in(key, 4)
            key8 = jax.random.fold_in(key, 8)
        q4, s4 = blockwise_quantize4(flat, policy.rounding, key4)
        q8, s8 = blockwise_quantize(hi, policy.rounding, key8)
        return {"q4": q4, "s4": s4, "idx": idx.astype(jnp.int32),
                "q8": q8, "s8": s8}
    raise ValueError(f"policy {policy.mode!r} has no wire codec")


def decode_chunks(payload: Dict[str, Any], policy: "GradSyncPolicy"):
    """Inverse of :func:`encode_chunks`: payload -> fp32
    ``(world, nblk, block)``."""
    fmt = policy.qformat
    if fmt == "int8":
        return blockwise_dequantize(payload["q8"], payload["s8"])
    if fmt == "int4":
        return blockwise_dequantize4(payload["q4"], payload["s4"])
    if fmt == "blockwise":
        deq = blockwise_dequantize4(payload["q4"], payload["s4"])
        refined = blockwise_dequantize(payload["q8"], payload["s8"])
        world = deq.shape[0]
        rows = jnp.arange(world)[:, None]
        return deq.at[rows, payload["idx"]].set(refined)
    raise ValueError(f"policy {policy.mode!r} has no wire codec")


def codec_chunk_bytes(nblk: int, block: int,
                      policy: "GradSyncPolicy") -> Dict[str, int]:
    """Wire bytes of ONE encoded chunk (``nblk`` blocks of ``block``),
    split into quantized payload vs quantization metadata (fp32
    per-block scales, refinement indices; the codecs are symmetric so
    there are no zero-points).  This is the accounting the bytes
    estimate under-counted pre-r14: metadata was folded into a single
    per-tensor scale guess."""
    fmt = policy.qformat
    if fmt == "int8":
        return {"payload": nblk * block, "metadata": 4 * nblk}
    if fmt == "int4":
        return {"payload": nblk * (block // 2), "metadata": 4 * nblk}
    if fmt == "blockwise":
        k = policy.hi_blocks(nblk)
        return {
            "payload": nblk * (block // 2) + k * block,
            "metadata": 4 * nblk + 4 * k + 4 * k,  # s4 + idx + s8
        }
    raise ValueError(f"policy {policy.mode!r} has no wire codec")


def _quantized_exchange(flat, width: int, policy: "GradSyncPolicy",
                        axis: str, key=None):
    """Shared quantized reduce-scatter core on a ``(world, width)``
    row-aligned buffer: pad to the block grid, encode with the policy's
    codec, exchange every payload array with one ``all_to_all`` each,
    decode + sum on the receiver.  Returns ``(shard_row, residual)``:
    this replica's ``(width,)`` chunk of the cross-replica SUM and the
    full ``(world, width)`` quantization error ``buf - dequant(q(buf))``
    (the error-feedback state)."""
    world = flat.shape[0]
    block = policy.block_size
    pad = (-width) % block
    padded = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
    nblk = (width + pad) // block
    payload = encode_chunks(padded.reshape(world, nblk, block), policy, key)
    deq_own = decode_chunks(payload, policy).reshape(world, -1)
    residual = flat - deq_own[:, :width]
    recv = {
        k: lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=True)
        for k, v in payload.items()
    }
    # simulated DCN boundary: when this exchange crosses the slice
    # axis (the flat baseline on a two-level mesh, or the hierarchical
    # DCN leg), the payload pays the byte-priced link toll before the
    # decode can run — a no-op compile-time branch otherwise
    from dlrover_tpu.parallel import hierarchy as _hierarchy

    cb = codec_chunk_bytes(nblk, block, policy)
    recv = _hierarchy.toll_payload(
        recv, (world - 1) * (cb["payload"] + cb["metadata"]), axis
    )
    shard = decode_chunks(recv, policy).sum(axis=0)
    return shard.reshape(-1)[:width], residual


def _quantized_ring_exchange(flat, width: int, policy: "GradSyncPolicy",
                             axis: str, key=None, interpret=None):
    """The ``ring_pallas_q`` tier: same ``(shard_row, residual)``
    contract as :func:`_quantized_exchange`, but the encode runs inside
    a fused Pallas kernel and the exchange is ``world - 1`` shifted
    ``ppermute`` hops whose decode + accumulate is a second fused
    kernel (``ops.pallas.ring_reduce_scatter``) — the ``(world,
    width)`` fp32 decode buffer the all_to_all path materializes in
    HBM between quantize and exchange never exists; peak extra HBM is
    ONE fp32 chunk.

    Every source's contribution is encoded ONCE from its original
    values (hop ``d`` ships the already-encoded chunk destined ``d``
    replicas leftward — no re-quantization of partial sums), so the
    error-feedback residual is bit-identical to the two-stage path and
    the received values are the same set, summed in hop order instead
    of source-index order (bit-exact on integer payloads, the pinned
    test shape).  Wire bytes per device match all_to_all exactly:
    ``world - 1`` encoded chunks out; the simulated-DCN toll books the
    same total, one link crossing per hop."""
    from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring
    from dlrover_tpu.parallel import hierarchy as _hierarchy

    del key  # ring_pallas_q only resolves for nearest rounding
    world = flat.shape[0]
    block = policy.block_size
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pad = (-width) % block
    padded = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
    nblk = (width + pad) // block
    x = padded.reshape(world, nblk, block)
    fmt = policy.qformat
    base_fmt = "int4" if fmt == "blockwise" else fmt
    q, s, deq = ring.fused_quantize(x, base_fmt, interpret)
    refine = None
    if fmt == "blockwise":
        # blockwise = the int4 base above + an int8 refinement of the
        # top hi_frac blocks; the refinement is k blocks per chunk —
        # small enough to ride jnp while the base stays in-kernel
        k = policy.hi_blocks(nblk)
        maxabs = jnp.max(jnp.abs(x), axis=-1)  # (world, nblk)
        _, idx = lax.top_k(maxabs, k)  # (world, k)
        hi = jnp.take_along_axis(x, idx[..., None], axis=1)
        q8, s8 = blockwise_quantize(hi, policy.rounding, None)
        refine = {"idx": idx.astype(jnp.int32), "q8": q8, "s8": s8}
        rows = jnp.arange(world)[:, None]
        deq = deq.at[rows, idx].set(blockwise_dequantize(q8, s8))
    residual = flat - deq.reshape(world, -1)[:, :width]
    cb = codec_chunk_bytes(nblk, block, policy)
    hop_bytes = cb["payload"] + cb["metadata"]
    idx_mine = lax.axis_index(axis)

    def row(a, c):
        return lax.dynamic_slice_in_dim(a, c, 1, axis=0)[0]

    # own contribution first (the chunk destined for me that never
    # leaves this device), then one arriving chunk per shift
    acc = row(deq, idx_mine)
    for d in range(1, world):
        perm = [(i, (i - d) % world) for i in range(world)]
        send = jnp.mod(idx_mine - d, world)
        packet = {"q": row(q, send), "s": row(s, send)}
        if refine is not None:
            packet.update(
                idx=row(refine["idx"], send),
                q8=row(refine["q8"], send),
                s8=row(refine["s8"], send),
            )
        packet = {
            k: lax.ppermute(v, axis, perm) for k, v in packet.items()
        }
        packet = _hierarchy.toll_payload(packet, hop_bytes, axis)
        if refine is None:
            acc = ring.fused_dequant_add(
                acc, packet["q"], packet["s"], base_fmt, interpret
            )
        else:
            # per-source decode matches decode_chunks exactly: int4
            # base (fused kernel), refined blocks OVERRIDE, then add
            c = ring.fused_dequant_add(
                jnp.zeros_like(acc), packet["q"], packet["s"],
                base_fmt, interpret,
            )
            c = c.at[packet["idx"]].set(
                blockwise_dequantize(packet["q8"], packet["s8"])
            )
            acc = acc + c
    return acc.reshape(-1)[:width], residual


def quantized_reduce_scatter(
    t,
    dim: int,
    axis: str,
    world: int,
    block_size: int,
    rounding: str = "nearest",
    key=None,
    policy: Optional["GradSyncPolicy"] = None,
):
    """Inside shard_map: quantized reduce-scatter of ``t`` along ``dim``.

    Every replica splits its full-leaf contribution into ``world``
    chunks, blockwise-quantizes each with the policy's codec (int8
    default; packed int4 / blockwise-mixed via ``policy``), and
    exchanges them with one ``all_to_all`` per payload array; the
    receiver dequantizes and sums, so each replica ends with its chunk
    of the cross-replica SUM.  Returns ``(shard, residual)`` where
    ``residual`` is this replica's full-leaf quantization error
    ``t - dequant(q(t))`` — the error-feedback state to re-inject next
    step.
    """
    if policy is None:
        policy = GradSyncPolicy(
            mode="int8", block_size=block_size, rounding=rounding
        )
    moved = jnp.moveaxis(t, dim, 0)
    chunk_rows = moved.shape[0] // world
    rest = moved.shape[1:]
    chunk_elems = chunk_rows * math.prod(rest)
    flat = moved.reshape(world, chunk_elems)
    shard_row, residual = _quantized_exchange(
        flat, chunk_elems, policy, axis, key
    )
    residual = jnp.moveaxis(residual.reshape(moved.shape), 0, dim)
    shard = shard_row.reshape((chunk_rows,) + rest)
    return jnp.moveaxis(shard, 0, dim), residual


def bucket_reduce_scatter(buf, policy: "GradSyncPolicy", axis: str,
                          world: int, key=None, interpret=None,
                          transport: Optional[str] = None):
    """Inside shard_map: reduce-scatter ONE packed bucket buffer
    (``parallel.bucketing``) of shape ``(world, width)``.

    Exact policies move the fp32 rows through the selected transport
    (``lax.psum_scatter`` or an ``ops.pallas.ring_reduce_scatter``
    tier); quantized policies ride the codec ``all_to_all`` exchange or
    the fused-quantization ``ring_pallas_q`` ring.  ``transport``
    overrides the policy's transport request for THIS bucket (the
    fabric tuner's per-bucket decision) — the resolution fallback chain
    still applies.  Returns ``((width,) shard row, (world, width)
    residual-or-None)``.
    """
    width = buf.shape[1]
    from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

    resolved = ring.resolve_transport(
        policy, world, width, axis, rdma_enabled=_ring_rdma_enabled(),
        request=transport,
    )
    if not policy.quantized:
        from dlrover_tpu.parallel import hierarchy as _hierarchy

        rs_bytes = (world - 1) * 4 * width
        if resolved == "ring_rdma":
            out = ring.rdma_ring_reduce_scatter(buf, axis, world)
            return _hierarchy.maybe_toll(out, rs_bytes, axis), None
        if resolved in ("ring", "ring_pallas"):
            accum = "pallas" if resolved == "ring_pallas" else "jnp"
            out = ring.ring_reduce_scatter(
                buf, axis, world, accum=accum, interpret=interpret
            )
            return _hierarchy.maybe_toll(out, rs_bytes, axis), None
        out = lax.psum_scatter(buf, axis, scatter_dimension=0, tiled=True)
        out = _hierarchy.maybe_toll(out, rs_bytes, axis)
        return out.reshape(-1), None
    if resolved == "ring_pallas_q":
        return _quantized_ring_exchange(
            buf, width, policy, axis, key, interpret
        )
    return _quantized_exchange(buf, width, policy, axis, key)


def _ring_rdma_enabled() -> bool:
    from dlrover_tpu.common import envs

    return envs.get_bool("DLROVER_TPU_GRAD_RING_RDMA")


def _dcn_allreduce(vec, dcn_pol: Optional["GradSyncPolicy"],
                   dcn_axis: str, dcn_world: int, key2=None, key3=None):
    """Cross-slice all-reduce of one ``(n,)`` vector in the DCN leg's
    codec — the r18 stage-2 shape, shared by the hierarchical chain
    (``vec`` = the in-slice chunk) and the dual-fabric stripe (``vec``
    = this device's whole striped contribution block).

    Quantized leg: reduce-scatter of the vector's slice-destined pieces
    + the quantized return all-gather of the summed sub-chunks (every
    slice decodes the SAME wire payload — replication stays bit-exact).
    Exact leg (``dcn_pol`` None): one fp32 psum through the toll.

    Returns ``(summed, err)``: the globally summed ``(n,)`` vector and
    this device's quantization error on its contribution (the
    send-side encode error plus the return-gather re-encode error
    placed at this slice's sub-chunk window), or ``None`` err for the
    exact leg."""
    from dlrover_tpu.parallel import hierarchy as _hierarchy

    n = vec.shape[0]
    if dcn_pol is None:
        summed = lax.psum(vec, dcn_axis)
        summed = _hierarchy.maybe_toll(
            summed, (2 * (dcn_world - 1) * 4 * n) // dcn_world, dcn_axis
        )
        return summed, None
    pad = (-n) % dcn_world
    padded = jnp.pad(vec, (0, pad)) if pad else vec
    sub_w = (n + pad) // dcn_world
    sub, resid2 = _quantized_exchange(
        padded.reshape(dcn_world, sub_w), sub_w, dcn_pol, dcn_axis, key2
    )
    # quantized return all-gather: every slice decodes the SAME wire
    # payload (this device's own piece included — consistency across
    # slices is what keeps params replicated bit-exactly)
    block = dcn_pol.block_size
    pad2 = (-sub_w) % block
    sub_p = jnp.pad(sub, (0, pad2)) if pad2 else sub
    nblk = (sub_w + pad2) // block
    payload = encode_chunks(sub_p.reshape(1, nblk, block), dcn_pol, key3)
    deq_own = decode_chunks(payload, dcn_pol).reshape(-1)[:sub_w]
    resid3 = sub - deq_own
    gathered = {
        k: lax.all_gather(v, dcn_axis, axis=0, tiled=True)
        for k, v in payload.items()
    }
    cb = codec_chunk_bytes(nblk, block, dcn_pol)
    gathered = _hierarchy.toll_payload(
        gathered,
        (dcn_world - 1) * (cb["payload"] + cb["metadata"]),
        dcn_axis,
    )
    summed = (
        decode_chunks(gathered, dcn_pol)
        .reshape(dcn_world, -1)[:, :sub_w]
        .reshape(-1)[:n]
    )
    s_mine = lax.axis_index(dcn_axis)
    placed3 = lax.dynamic_update_slice(
        jnp.zeros((n + pad,), jnp.float32), resid3, (s_mine * sub_w,)
    )[:n]
    err = resid2.reshape(-1)[:n] + placed3
    return summed, err


def hierarchical_bucket_reduce_scatter(
    buf,
    policy: "GradSyncPolicy",
    ici_axis: str,
    dcn_axis: str,
    ici_world: int,
    dcn_world: int,
    key=None,
    transport: Optional[str] = None,
):
    """Inside shard_map: the two-level reduce of ONE packed bucket
    buffer of shape ``(ici_world, width)`` on a ``slice × dp`` mesh.

    Stage 1 — **ICI reduce-scatter within the slice**: the r14 bucket
    chain unchanged (``bucket_reduce_scatter`` with the policy's own
    codec), handing this device its ``(width,)`` chunk of the SLICE's
    partial sum.

    Stage 2 — **one aggregated DCN exchange across slices**: the chunk
    is re-quantized with the heavier ``policy.dcn_policy()`` codec
    (int4/blockwise per EQuARX; exact base modes stay exact), pushed
    through a reduce-scatter over the slice axis, and the globally
    summed sub-chunks return via a quantized all-gather — so every
    slice's device ``i`` ends holding the IDENTICAL (bit-exact, both
    decode the same wire payload) globally-summed chunk ``i``, and
    cross-slice bytes-on-wire are ``1/ici_world`` of the bucket instead
    of the whole bucket.

    Stage 3 — the intra-slice param all-gather — is the caller's
    existing ``all_gather_tree_bucketed`` over the ICI axis: no param
    bytes ever cross DCN.

    Returns ``(chunk, residual)``: the ``(width,)`` globally-summed
    chunk and this device's ``(ici_world, width)`` error-feedback block
    (stage-1 error over the full contribution + the stage-2 errors
    scatter-added into the rows this device owned at that stage), or
    ``None`` residual for exact policies.  The residual stays in the
    r6/r14 per-leaf bucket coordinates, so checkpoint layouts and the
    elastic-resize redistribution are untouched."""
    key1 = key2 = key3 = None
    if key is not None:
        key1 = jax.random.fold_in(key, 1)
        key2 = jax.random.fold_in(key, 2)
        key3 = jax.random.fold_in(key, 3)
    shard, resid1 = bucket_reduce_scatter(
        buf, policy, ici_axis, ici_world, key1, transport=transport
    )
    if dcn_world <= 1:
        # degenerate single-slice topology: stage 2 is the identity
        # and the program is EXACTLY the flat r14 chain
        return shard, resid1
    chunk, err_chunk = _dcn_allreduce(
        shard, policy.dcn_policy(), dcn_axis, dcn_world, key2, key3
    )
    if resid1 is None:
        return chunk, None
    if err_chunk is None:
        # exact DCN leg under a quantized base mode: only stage-1
        # errors exist
        return chunk, resid1
    # fold the stage-2 errors into the row this device owned there:
    # the send-side encode error and the return-gather re-encode error
    # both live at bucket row i_mine (the chunk this device carried
    # into the DCN leg)
    i_mine = lax.axis_index(ici_axis)
    residual = resid1.at[i_mine].add(err_chunk)
    return chunk, residual


def stripe_cols(width: int, stripe: float, block: int) -> int:
    """Number of trailing bucket columns the dual-fabric stripe routes
    over DCN: ``stripe`` of ``width`` snapped DOWN to the codec block
    grid (so both sub-buffers stay block-aligned and the stripe split
    never lands mid-block), with at least one block left on the ICI
    side; 0 when the bucket is too small to split at all."""
    if stripe <= 0.0 or width < 2 * block:
        return 0
    w_d = int(width * stripe) // block * block
    return min(w_d, width - block)


def striped_bucket_reduce_scatter(
    buf,
    policy: "GradSyncPolicy",
    ici_axis: str,
    dcn_axis: str,
    ici_world: int,
    dcn_world: int,
    stripe: float,
    key=None,
    transport: Optional[str] = None,
):
    """Inside shard_map: the FlexLink dual-fabric variant of
    :func:`hierarchical_bucket_reduce_scatter` — split the bucket's
    columns so ``stripe`` of them cross DCN *concurrently* with the
    ICI reduce-scatter of the rest, instead of strictly after it.

    The ICI-side columns ``[:width-w_d]`` ride the unchanged two-stage
    hierarchical chain.  The DCN-side columns' raw contribution block
    crosses DCN FIRST (:func:`_dcn_allreduce` in the DCN codec) — an
    exchange with no data dependency on the ICI stage, so XLA (and on
    hardware, the disjoint fabrics) can run both at once — then one
    exact ``psum_scatter`` over ICI splits the slice-summed block into
    per-device chunks.  On a DCN-idle fabric the stripe soaks up free
    cross-slice bandwidth the hierarchical schedule would leave unused;
    the per-bucket ``stripe`` fraction is the fabric tuner's knob.

    Returns the same ``(chunk, residual)`` contract as the
    hierarchical chain: the ``(width,)`` globally-summed chunk this
    device owns and the ``(ici_world, width)`` EF block (stripe-column
    errors in their own columns), or ``None`` for exact policies."""
    width = buf.shape[1]
    w_d = stripe_cols(width, stripe, policy.block_size)
    if w_d <= 0 or dcn_world <= 1:
        return hierarchical_bucket_reduce_scatter(
            buf, policy, ici_axis, dcn_axis, ici_world, dcn_world,
            key, transport=transport,
        )
    from dlrover_tpu.parallel import hierarchy as _hierarchy

    key1 = key2 = key3 = None
    if key is not None:
        key1 = jax.random.fold_in(key, 10)
        key2 = jax.random.fold_in(key, 11)
        key3 = jax.random.fold_in(key, 12)
    w_i = width - w_d
    chunk_i, resid_i = hierarchical_bucket_reduce_scatter(
        buf[:, :w_i], policy, ici_axis, dcn_axis, ici_world, dcn_world,
        key1, transport=transport,
    )
    blk = buf[:, w_i:].reshape(-1)
    blk_sum, err = _dcn_allreduce(
        blk, policy.dcn_policy(), dcn_axis, dcn_world, key2, key3
    )
    part = blk_sum.reshape(ici_world, w_d)
    chunk_d = lax.psum_scatter(
        part, ici_axis, scatter_dimension=0, tiled=True
    ).reshape(-1)
    chunk_d = _hierarchy.maybe_toll(
        chunk_d, (ici_world - 1) * 4 * w_d, ici_axis
    )
    chunk = jnp.concatenate([chunk_i, chunk_d])
    if not policy.quantized:
        return chunk, None
    err_blk = (
        err.reshape(ici_world, w_d)
        if err is not None
        else jnp.zeros((ici_world, w_d), jnp.float32)
    )
    resid = (
        resid_i
        if resid_i is not None
        else jnp.zeros((ici_world, w_i), jnp.float32)
    )
    return chunk, jnp.concatenate([resid, err_blk], axis=1)


def stripe_dcn_bytes(width: int, ici_world: int, dcn_world: int,
                     stripe: float, policy: "GradSyncPolicy") -> int:
    """Per-device cross-slice (DCN) bytes-on-wire of ONE striped
    bucket's DCN leg — the pricing twin of
    :func:`striped_bucket_reduce_scatter`'s tolls, consumed by the
    fabric tuner and the meter==estimator assertions.  The stripe block
    is the FULL ``(ici_world, w_d)`` contribution (it crosses DCN
    before any ICI reduction), exchanged as reduce-scatter + return
    all-gather in the DCN codec; 0 when the stripe collapses."""
    w_d = stripe_cols(width, stripe, policy.block_size)
    if w_d <= 0 or dcn_world <= 1:
        return 0
    n = ici_world * w_d
    dcn_pol = policy.dcn_policy()
    if dcn_pol is None:
        return (2 * (dcn_world - 1) * 4 * n) // dcn_world
    sub_w = -(-n // dcn_world)
    nblk = -(-sub_w // dcn_pol.block_size)
    cb = codec_chunk_bytes(nblk, dcn_pol.block_size, dcn_pol)
    per_leg = (dcn_world - 1) * (cb["payload"] + cb["metadata"])
    return 2 * per_leg


def sync_gradient_tree_hierarchical(
    grads,
    residuals: Optional[Dict[str, Any]],
    layout: GradLayout,
    buckets,
    policy: GradSyncPolicy,
    ici_axis: str,
    dcn_axis: str,
    dcn_world: int,
    key=None,
    plan=None,
):
    """Hierarchical sync on a two-level ``slice × dp`` mesh — the
    :func:`sync_gradient_tree_bucketed` skeleton with the per-bucket
    reduce swapped for :func:`hierarchical_bucket_reduce_scatter`
    (see that docstring for the contract)."""
    return sync_gradient_tree_bucketed(
        grads, residuals, layout, buckets, policy, ici_axis, key,
        dcn_axis=dcn_axis, dcn_world=dcn_world, plan=plan,
    )


# -- gradient-tree sync (inside shard_map) ---------------------------------


def sync_gradient_tree(
    grads,
    residuals: Optional[Dict[str, Any]],
    layout: GradLayout,
    policy: GradSyncPolicy,
    axis: str,
    key=None,
):
    """Reduce the per-replica mean-gradient contributions across ``axis``.

    Returns ``(synced, new_residuals)``: sharded leaves come back as
    their 1/world slice along their shard dim (SUM over replicas — the
    caller already normalized by the global weight); non-shardable
    leaves come back full via an exact psum.  ``new_residuals`` carries
    the per-replica quantization error as ``(1, *leaf)`` local blocks of
    the dp-stacked error-feedback state (None for exact modes).
    """
    new_resid: Dict[str, Any] = {}

    def sync_leaf(path, g):
        g = g.astype(jnp.float32)
        dim = layout.dims.get(path)
        if dim is None:
            return lax.psum(g, axis)
        if not policy.quantized:
            out = lax.psum_scatter(
                g, axis, scatter_dimension=dim, tiled=True
            )
            from dlrover_tpu.parallel import hierarchy as _hierarchy

            return _hierarchy.maybe_toll(
                out,
                ((layout.world - 1) * 4 * g.size) // layout.world,
                axis,
            )
        t = g
        if residuals is not None and path in residuals:
            t = g + residuals[path][0]
        leaf_key = None
        if policy.rounding == "stochastic":
            leaf_key = jax.random.fold_in(key, zlib.crc32(path.encode()))
        shard, resid = quantized_reduce_scatter(
            t, dim, axis, layout.world, policy.block_size,
            policy.rounding, leaf_key, policy=policy,
        )
        new_resid[path] = resid[None]
        return shard

    synced = _map_leaves(sync_leaf, grads)
    # `or None`: a model with zero shardable leaves carries no EF state,
    # and the output structure must match the input's None exactly
    return synced, ((new_resid or None) if policy.quantized else None)


def sync_gradient_tree_bucketed(
    grads,
    residuals: Optional[Dict[str, Any]],
    layout: GradLayout,
    buckets,
    policy: GradSyncPolicy,
    axis: str,
    key=None,
    dcn_axis: Optional[str] = None,
    dcn_world: int = 1,
    plan=None,
):
    """Bucketed variant of :func:`sync_gradient_tree`: shardable leaves
    move through their bucket's ONE fused collective instead of a
    per-leaf swarm (``parallel.bucketing.BucketLayout``).

    Every bucket's chain — EF inject, pack, quantize, exchange, decode,
    unpack — depends only on its own member leaves' gradients, so the
    XLA scheduler can run bucket exchanges concurrently with other
    buckets' math and with whatever backward compute is still pending.
    Same contract as the per-leaf path: sharded leaves return as their
    1/world slice, non-shardable leaves ride an exact psum, and the
    residual dict keeps the r6 per-LEAF ``(1, *leaf)`` layout (so
    checkpoint save/restore and elastic dp-resize redistribution are
    byte-compatible with every earlier round).

    With ``dcn_axis`` set (r18: a two-level ``slice × dp`` mesh, layout
    world = the in-slice dp degree), each bucket rides
    :func:`hierarchical_bucket_reduce_scatter` instead — non-shardable
    leaves psum over BOTH axes, every device ends with its in-slice
    chunk of the GLOBALLY summed gradient (identical across slices),
    and the residual dict holds ``(1, *leaf)`` local blocks of a
    ``(dcn_world * layout.world, *leaf)`` dp-stacked EF state.

    ``plan`` — a fabric-tuner ``TunerPlan`` (anything with
    ``for_bucket(index) -> decision-or-None`` where a decision carries
    ``transport`` and ``stripe``) — overrides, per bucket, the
    transport request and the dual-fabric stripe fraction; without it
    the policy's own ``stripe`` applies uniformly."""
    reduce_axes = (dcn_axis, axis) if dcn_axis is not None else axis
    vals = dict(leaf_items(grads))
    synced_map: Dict[str, Any] = {}
    new_resid: Dict[str, Any] = {}
    for path, g in vals.items():
        if layout.dims.get(path) is None:
            synced_map[path] = lax.psum(
                g.astype(jnp.float32), reduce_axes
            )

    def contribution(path):
        t = vals[path].astype(jnp.float32)
        if (
            policy.quantized
            and residuals is not None
            and path in residuals
        ):
            t = t + residuals[path][0]
        return t

    for b in buckets.buckets:
        bkey = None
        if policy.quantized and policy.rounding == "stochastic":
            bkey = jax.random.fold_in(key, b.index)
        buf = buckets.pack(b, contribution)
        decision = plan.for_bucket(b.index) if plan is not None else None
        req = decision.transport if decision is not None else None
        if dcn_axis is not None:
            stripe = (
                decision.stripe
                if decision is not None
                else (policy.stripe or 0.0)
            ) or 0.0
            if stripe > 0.0 and dcn_world > 1:
                shard_row, resid_buf = striped_bucket_reduce_scatter(
                    buf, policy, axis, dcn_axis, layout.world,
                    dcn_world, stripe, bkey, transport=req,
                )
            else:
                shard_row, resid_buf = (
                    hierarchical_bucket_reduce_scatter(
                        buf, policy, axis, dcn_axis, layout.world,
                        dcn_world, bkey, transport=req,
                    )
                )
        else:
            shard_row, resid_buf = bucket_reduce_scatter(
                buf, policy, axis, layout.world, bkey, transport=req
            )
        synced_map.update(buckets.unpack_shard(b, shard_row))
        if resid_buf is not None:
            for path, full in buckets.unpack_full(b, resid_buf).items():
                new_resid[path] = full[None]

    synced = _map_leaves(lambda p, g: synced_map[p], grads)
    return synced, ((new_resid or None) if policy.quantized else None)


def global_grad_norm(synced, layout: GradLayout, axis: str):
    """Exact global norm of a mixed shard/full gradient tree: sharded
    leaves partition the full tensors, so the cross-replica psum of
    their local sum-of-squares is the true total; replicated leaves
    (identical on every replica after psum) count once."""
    local = jnp.zeros((), jnp.float32)
    replicated = jnp.zeros((), jnp.float32)
    for path, g in leaf_items(synced):
        ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if layout.dims.get(path) is None:
            replicated = replicated + ss
        else:
            local = local + ss
    return jnp.sqrt(lax.psum(local, axis) + replicated)


def shard_like(tree, layout: GradLayout, axis: str):
    """Slice each shardable leaf of a REPLICATED tree down to this
    replica's chunk (the param-side view for the sharded update)."""
    idx = lax.axis_index(axis)

    def f(path, p):
        dim = layout.dims.get(path)
        if dim is None:
            return p
        chunk = p.shape[dim] // layout.world
        return lax.dynamic_slice_in_dim(p, idx * chunk, chunk, dim)

    return _map_leaves(f, tree)


def all_gather_tree(tree, layout: GradLayout, axis: str):
    """Rebuild full leaves from shards (params after the sharded update,
    or grads for the replicated-update int8 mode)."""

    def f(path, x):
        dim = layout.dims.get(path)
        if dim is None:
            return x
        out = lax.all_gather(x, axis, axis=dim, tiled=True)
        from dlrover_tpu.parallel import hierarchy as _hierarchy

        return _hierarchy.maybe_toll(
            out, (layout.world - 1) * x.dtype.itemsize * x.size, axis
        )

    return _map_leaves(f, tree)


def all_gather_tree_bucketed(tree, layout: GradLayout, buckets, axis: str):
    """Bucketed :func:`all_gather_tree`: pack each bucket's per-leaf
    shards into one ``(width,)`` row and rebuild the full leaves from
    ONE all-gather per bucket — the mirror of
    :func:`sync_gradient_tree_bucketed`, with the same per-bucket chain
    independence.

    Rows are grouped by LEAF DTYPE within each bucket (one gather per
    group): unlike the fp32-normalized sync path, this gathers raw
    updated params, and a mixed-dtype concatenate would silently
    promote (a bf16 leaf coming back fp32 breaks the donated step's
    avals).  Single-dtype trees — the common case — still fuse to one
    collective per bucket."""
    vals = dict(leaf_items(tree))
    full_map: Dict[str, Any] = {}
    for b in buckets.buckets:
        groups: Dict[Any, list] = {}
        for s in b.slices:
            groups.setdefault(jnp.asarray(vals[s.path]).dtype, []).append(s)
        for slices in groups.values():
            rows = [
                jnp.moveaxis(vals[s.path], s.dim, 0).reshape(-1)
                for s in slices
            ]
            row = jnp.concatenate(rows) if len(rows) > 1 else rows[0]
            buf = lax.all_gather(row, axis, axis=0, tiled=False)
            from dlrover_tpu.parallel import hierarchy as _hierarchy

            buf = _hierarchy.maybe_toll(
                buf,
                (layout.world - 1) * row.dtype.itemsize * row.size,
                axis,
            )
            off = 0
            for s in slices:
                full_map[s.path] = buckets.leaf_from_rows(
                    s, buf[:, off:off + s.width]
                )
                off += s.width

    return _map_leaves(lambda p, x: full_map.get(p, x), tree)


# -- host-side helpers -----------------------------------------------------


def error_feedback_init(params, layout: GradLayout,
                        total_world: Optional[int] = None):
    """Zero error-feedback buffers, one ``(world, *leaf)`` stack per
    quantized (= shardable) leaf, keyed by the leaf's path string.  The
    leading axis is the dp replica axis (sharded over dp), so each
    replica holds exactly its own residual.

    ``total_world`` (r18): the hierarchical sync derives shardability
    from the IN-SLICE world (``layout.world``) but every one of the
    ``slices * ici_dp`` replicas carries its own residual row — pass
    the full replica count so the stack spans them all (sharded over
    both mesh axes)."""
    world = int(total_world) if total_world else layout.world
    return {
        path: jnp.zeros((world,) + tuple(leaf.shape), jnp.float32)
        for path, leaf in leaf_items(params)
        if layout.dims.get(path) is not None
    }


def materialize_ef_stack(per, world: int, sharding):
    """Build a ``(world, *leaf)`` dp-sharded error-feedback stack whose
    every replica row is ``per`` — the redistribution step of an elastic
    dp change (``Trainer.load_state``).

    The invariant that matters for convergence is the TOTAL un-injected
    error ``sum_r residual_r`` (next step every replica adds its
    residual back before quantizing, and the reduce sums across
    replicas); the caller passes ``per = total / world`` so the first
    post-restore sync re-injects exactly what the old fleet still owed.
    Assembled via ``make_array_from_callback`` serving the single
    leaf-sized host array to every shard — neither host RAM nor HBM
    ever holds ``world`` copies.
    """
    import numpy as np

    per = np.ascontiguousarray(per, dtype=np.float32)
    shape = (int(world),) + per.shape

    def cb(index):
        lead = index[0]
        start = lead.start if lead.start is not None else 0
        stop = lead.stop if lead.stop is not None else int(world)
        sub = per[tuple(index[1:])]
        return np.broadcast_to(sub, (stop - start,) + sub.shape)

    return jax.make_array_from_callback(shape, sharding, cb)


def estimate_sync_bytes(params, world: int, policy: GradSyncPolicy) -> Dict:
    """Estimated per-step dp bytes-on-wire per replica (ring-collective
    accounting: a reduce-scatter or all-gather moves ``(world-1)/world``
    of the payload off-replica; an all-reduce moves both phases).

    ``exact``: fp32 all-reduce of every gradient element.
    Quantized modes: the codec payload + per-block quantization
    metadata (scales, refinement indices — ``codec_chunk_bytes``) +
    the fp32 all-gather (updated params or gathered grads — same
    size).  Non-shardable leaves ride the exact all-reduce in every
    mode.  ``metadata_bytes`` is reported separately: pre-r14 the
    estimate folded scales into a single per-tensor guess and
    under-counted blockwise formats.
    """
    layout = GradLayout(params, world)
    off = (world - 1) / world if world > 1 else 0.0
    exact = 0.0
    quant = 0.0
    meta = 0.0
    for path, leaf in leaf_items(params):
        elems = math.prod(tuple(leaf.shape)) if leaf.shape else 1
        exact += 2 * off * 4 * elems
        if layout.dims.get(path) is None:
            quant += 2 * off * 4 * elems
        else:
            chunk = elems // world
            if policy.quantized:
                nblk = -(-chunk // policy.block_size)
                cb = codec_chunk_bytes(nblk, policy.block_size, policy)
            else:
                cb = {"payload": 4 * chunk, "metadata": 0}
            # reduce-scatter: world encoded chunks leave this replica...
            quant += off * world * (cb["payload"] + cb["metadata"])
            meta += off * world * cb["metadata"]
            # ... then a full-precision all-gather
            quant += off * 4 * elems
    result = {
        "world": int(world),
        "exact_allreduce_bytes": int(exact),
        "quantized_bytes": int(quant),
        "metadata_bytes": int(meta),
    }
    if quant > 0:
        result["reduction_x"] = round(exact / quant, 2)
    return result


def estimate_bucket_bytes(buckets, policy: GradSyncPolicy,
                          world: int) -> List[Dict]:
    """Per-BUCKET bytes-on-wire accounting for the bucketed sync path:
    padding is charged per bucket (not per leaf — a bucket pads its
    packed row once to the block grid) and quantization metadata
    (scales / refinement indices) is itemized per bucket, which is what
    ``grad_sync_bench`` reports and what the legacy single-tensor
    estimate under-counted for blockwise modes."""
    off = (world - 1) / world if world > 1 else 0.0
    out = []
    for b in buckets.buckets:
        width = b.width
        if policy.quantized:
            block = policy.block_size
            nblk = -(-width // block)
            cb = codec_chunk_bytes(nblk, block, policy)
            rs_payload = off * world * cb["payload"]
            rs_meta = off * world * cb["metadata"]
        else:
            rs_payload = off * world * 4 * width
            rs_meta = 0.0
        out.append({
            "bucket": b.index,
            "leaves": len(b.slices),
            "width": width,
            "rs_payload_bytes": int(rs_payload),
            "rs_metadata_bytes": int(rs_meta),
            "allgather_bytes": int(off * world * 4 * width),
        })
    return out
