"""Host snapshots of sharded jax arrays: the shm staging format.

TPU-native counterpart of the reference's shm tensor staging
(``dlrover/python/elastic_agent/torch/ckpt_saver.py:118-231``
``_create_tensor_meta``/``_traverse_copy_to_shm``): each process copies the
*addressable, replica-0* shards of every array in the train state into one
POSIX shared-memory segment — device->host is the only blocking cost of a
checkpoint.  Layout::

    [0:8)    meta length (big-endian u64); 0 = no committed snapshot
    [8:16)   generation (big-endian u64); odd = write in progress / torn
    [16:16+L) meta JSON: step, extras, per-leaf dtype/global-shape and
             per-shard global index + byte offset
    [...]    raw shard bytes, C-contiguous

The meta carries *global* index ranges, so any reader (the agent's async
saver, a restore with a different mesh) can reassemble without knowing the
original sharding.

Two writers share the format:

- ``write_snapshot`` — the blocking save, the synchronous fallback and
  peer restore: host arrays already staged (``extract_host_shards``,
  every transfer kicked up front), packed with one memcpy per shard.
- ``plan_shards`` + ``stream_snapshot`` — the background stager: the shm
  layout (every shard's byte offset) is computed from abstract shapes
  BEFORE any transfer, then each paced D2H chunk lands directly at its
  final shm offset.  No intermediate full host copy exists, so host peak
  RSS is bounded by shm + one chunk instead of 2x state, and each chunk
  costs exactly ONE host-side copy (the zero-copy invariant, counted
  by ``StageCounters``: ``host_copies == chunks``).

Both run the seqlock-style generation commit: the generation word
is bumped to ODD before any byte of meta/payload changes and bumped back
to EVEN only after the meta length is restored.  A writer killed
mid-stream leaves an odd generation; readers (``read_snapshot_meta``,
the agent's ``save_shm_on_failure``) treat that as "no snapshot" and
fall back to storage candidates — crash consistency without doubling
the shm.
"""

import json
import math
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.chaos import point as _chaos_point
from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import SharedMemoryBuffer

# shm prefix layout (see module docstring).  _HEADER is the meta-length
# word: zeroing it invalidates the snapshot (tests rely on that).
_HEADER = 8
_GEN_OFF = 8
_META_OFF = 16

_MIN_CHUNK = 1 << 20  # 1 MiB: below this, per-transfer overhead dominates
_MAX_CHUNK = 256 << 20
_DEFAULT_CHUNK = 8 << 20
# Step baselines below this are not real device step times: a loop that
# never blocks on device results dispatches steps in microseconds, and
# pacing against that collapsed baseline would read routine scheduler
# jitter as "inflation" and throttle staging to a crawl.  Below the
# floor the pacer runs unpaced instead (the trainer is not waiting on
# the device, so fast staging costs it nothing observable).
_MIN_BASELINE_S = 0.005


class StageCounters:
    """What one staging of a snapshot did, counted where it happens: the
    attributes of the ``flash.stage`` span, and what the tests read
    when they stage without an engine.  Chunks are counted, not spanned:
    a save has hundreds to thousands of them.

    ``host_copies`` counts every host-side buffer copy: the streaming
    path's promise is one per chunk, and any refactor that slips an
    intermediate host buffer back in still yields bit-exact snapshots,
    so a tier-1 test holds ``host_copies == chunks`` there."""

    __slots__ = (
        "bytes", "chunk_sizes", "host_copies", "host_copy_bytes",
        "pace_sleep_s", "slice_s", "d2h_wait_s", "shm_copy_s",
    )

    def __init__(self):
        self.bytes = 0
        self.chunk_sizes: List[int] = []
        self.host_copies = 0
        self.host_copy_bytes = 0
        self.pace_sleep_s = 0.0  # StagePacer.gate's sleeps
        self.slice_s = 0.0  # dispatch of slice_in_dim / reshape
        self.d2h_wait_s = 0.0  # blocked in np.asarray(device array)
        self.shm_copy_s = 0.0  # memcpy into the segment

    def chunk(self, nbytes: int) -> None:
        self.bytes += nbytes
        self.chunk_sizes.append(nbytes)

    def host_copy(self, nbytes: int) -> None:
        self.host_copies += 1
        self.host_copy_bytes += nbytes

    @property
    def chunks(self) -> int:
        return len(self.chunk_sizes)

    def as_attrs(self) -> Dict[str, Any]:
        sizes = sorted(self.chunk_sizes)
        return {
            "bytes": self.bytes,
            "chunks": len(sizes),
            "chunk_bytes_min": sizes[0] if sizes else 0,
            "chunk_bytes_median": sizes[len(sizes) // 2] if sizes else 0,
            "chunk_bytes_max": sizes[-1] if sizes else 0,
            "host_copies": self.host_copies,
            "pace_sleep_s": round(self.pace_sleep_s, 6),
            "slice_s": round(self.slice_s, 6),
            "d2h_wait_s": round(self.d2h_wait_s, 6),
            "shm_copy_s": round(self.shm_copy_s, 6),
        }


class StagePacer:
    """Closed-loop throttle for background device->host staging.

    Replaces the manual ``DLROVER_TPU_STAGE_PACE`` knob with feedback
    control: transfers are CHUNKED so a concurrently dispatched train
    step ever waits behind at most one chunk, and the chunk size is
    chosen from the measured link bandwidth and the observed step-time
    baseline so that the wait stays within ``(factor - 1)`` of a step
    (default factor 1.5, env ``DLROVER_TPU_STAGE_FACTOR``).  Observed
    step inflation then trims the chunk size and inserts duty-cycle
    sleeps if the bound is still exceeded; when the step clock reports
    training idle, staging runs at full speed with maximal chunks.
    ``DLROVER_TPU_STAGE_PACE`` (sleep = pace x transfer time between
    chunks) is still honored as a manual override for operators who
    want a fixed duty cycle.
    """

    # fraction of the (factor-1) step slack one chunk may occupy —
    # headroom for dispatch overhead and queueing jitter
    _SLACK_MARGIN = 0.6

    def __init__(self, factor: Optional[float] = None, clock=None):
        from dlrover_tpu.utils.step_clock import get_step_clock

        self.clock = clock if clock is not None else get_step_clock()
        self.manual_pace = envs.get_float("DLROVER_TPU_STAGE_PACE")
        if factor is None:
            factor = envs.get_float("DLROVER_TPU_STAGE_FACTOR")
        self.factor = max(1.05, factor)
        self.chunk_bytes = _DEFAULT_CHUNK
        self.sleep_ratio = 0.0  # sleep = ratio * last chunk transfer time
        self.best_bw = 0.0  # bytes/s, max observed (robust to overhead)
        self.last_chunk_s = 0.0
        self.slept_s = 0.0  # what gate() has slept, summed
        self._mark = time.monotonic()
        self._calibrated = False

    # -- feedback ----------------------------------------------------------

    def note_transfer(self, nbytes: int, seconds: float) -> None:
        self.last_chunk_s = seconds
        if seconds > 0:
            self.best_bw = max(self.best_bw, nbytes / seconds)
        if not self._calibrated:
            self._calibrate()

    def _calibrate(self) -> None:
        """Jump straight to the bandwidth-derived chunk size: converging
        by halving alone would blow the step budget for the handful of
        steps the bound exists to protect."""
        base = self.clock.baseline()
        if not self.best_bw or base is None:
            return
        if base < _MIN_BASELINE_S:
            self.chunk_bytes = _MAX_CHUNK
            self.sleep_ratio = 0.0
            self._calibrated = True
            logger.info(
                "stage pacer: step baseline %.2gs below the %.0fms floor "
                "(non-blocking training loop); staging unpaced",
                base, _MIN_BASELINE_S * 1e3,
            )
            return
        slack = (self.factor - 1.0) * base * self._SLACK_MARGIN
        self.chunk_bytes = int(
            min(_MAX_CHUNK, max(_MIN_CHUNK, self.best_bw * slack))
        )
        self._calibrated = True
        logger.info(
            "stage pacer calibrated: bw=%.1f MB/s step=%.3fs chunk=%d KiB",
            self.best_bw / 1e6, base, self.chunk_bytes // 1024,
        )

    def _adjust(self) -> None:
        steps = self.clock.steps_since(self._mark)
        if not steps:
            return
        self._mark = time.monotonic()
        base = self.clock.baseline()
        if base is None:
            # no baseline to judge against: pace conservatively
            self.sleep_ratio = max(self.sleep_ratio, 1.0)
            return
        if base < _MIN_BASELINE_S:
            # collapsed baseline = meaningless cadence signal; never
            # escalate sleeps against scheduler jitter
            self.sleep_ratio = 0.0
            return
        med = sorted(steps)[len(steps) // 2]
        if med > self.factor * base:
            if self.chunk_bytes > _MIN_CHUNK:
                self.chunk_bytes = max(_MIN_CHUNK, self.chunk_bytes // 2)
            else:
                self.sleep_ratio = min(8.0, max(0.5, self.sleep_ratio * 1.6))
        elif med < max(1.0, 0.8 * self.factor) * base:
            # comfortably under the bound: recover staging throughput
            if self.sleep_ratio > 0.05:
                self.sleep_ratio *= 0.6
            else:
                self.sleep_ratio = 0.0
                self.chunk_bytes = min(_MAX_CHUNK, self.chunk_bytes * 2)

    def gate(self) -> None:
        """Call before dispatching each chunk: applies the duty-cycle
        sleep and adapts chunking to the latest observed steps."""
        if self.manual_pace > 0:
            if self.last_chunk_s > 0:
                self._sleep(min(30.0, self.manual_pace * self.last_chunk_s))
            return
        if self.clock.idle():
            # nothing is training: drain at full speed
            self.sleep_ratio = 0.0
            self.chunk_bytes = min(_MAX_CHUNK, self.chunk_bytes * 2)
            return
        self._adjust()
        if self.sleep_ratio > 0 and self.last_chunk_s > 0:
            self._sleep(min(10.0, self.sleep_ratio * self.last_chunk_s))

    def _sleep(self, seconds: float) -> None:
        t0 = time.perf_counter()
        time.sleep(seconds)
        self.slept_s += time.perf_counter() - t0

    def summary(self) -> Dict[str, Any]:
        """Where the control loop stands: the ``pacer`` attribute of the
        ``flash.stage`` span."""
        return {
            "best_bw": round(self.best_bw, 1),
            "baseline_step_s": self.clock.baseline(),
            "chunk_bytes": self.chunk_bytes,
            "sleep_ratio": round(self.sleep_ratio, 4),
        }


from dlrover_tpu.common.pytree import path_str as _path_str  # noqa: E402


def set_stream_fault(fn: Optional[Callable[[int], None]]) -> None:
    """LEGACY shim: torn-snapshot fault hook, now a ``callback`` fault
    on the ``snapshot.stream_chunk`` chaos point (``dlrover_tpu.chaos``).

    ``fn(chunk_idx)`` is called with the 0-based index of each landed
    chunk during ``stream_snapshot``/``_stream_shard``; raising aborts
    the stream mid-write, leaving the seqlock generation dirty.  New
    code should inject a spec on ``snapshot.stream_chunk`` directly
    (any kind, nth-call scheduling, seeded traces); this shim survives
    for the reshard drill and pre-chaos tests."""
    from dlrover_tpu import chaos

    chaos.clear("snapshot.stream_chunk")
    if fn is not None:
        chaos.inject(  # graftlint: disable=GL501 (legacy shim: only runs when a drill/test calls set_stream_fault; nothing arms it ambiently)
            chaos.FaultSpec(
                point="snapshot.stream_chunk",
                kind=chaos.CALLBACK,
                callback=lambda chunk=0: fn(chunk),
            )
        )


def _enumerate_shards(state: Any) -> List[Dict]:
    """Flatten a pytree into this process's shard list WITHOUT any
    device->host transfer: ``shard['data']`` stays the device array (or
    the original host array for non-jax leaves).

    ALL addressable shards are enumerated (not just replica 0): a
    process's shm must be self-sufficient for a same-mesh restart, and
    with dp replication the replica-0 copy may live on another process
    entirely.  Identical local replicas are deduplicated to keep the shm
    bounded; cross-process duplication of replicated leaves is the price
    of local restartability (same trade the reference makes for DDP shm
    snapshots)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    leaves = []
    for key_path, leaf in flat:
        path = _path_str(key_path)
        if hasattr(leaf, "addressable_shards"):
            shards = []
            seen_indices = set()
            for shard in leaf.addressable_shards:
                index = []
                for dim, sl in enumerate(shard.index):
                    start = sl.start if sl.start is not None else 0
                    stop = (
                        sl.stop if sl.stop is not None else leaf.shape[dim]
                    )
                    index.append([int(start), int(stop)])
                key = tuple(tuple(i) for i in index)
                if key in seen_indices:
                    continue  # identical replica on another local device
                seen_indices.add(key)
                shards.append({"index": index, "data": shard.data})
            if not shards:
                continue
            leaves.append(
                {
                    "path": path,
                    "dtype": str(np.dtype(leaf.dtype)),
                    "gshape": [int(d) for d in leaf.shape],
                    "shards": shards,
                }
            )
        else:
            data = np.asarray(leaf)
            leaves.append(
                {
                    "path": path,
                    "dtype": str(data.dtype),
                    "gshape": [int(d) for d in data.shape],
                    "shards": [
                        {
                            "index": [[0, int(d)] for d in data.shape],
                            "data": data,
                        }
                    ],
                }
            )
    return leaves


def extract_host_shards(
    state: Any, counters: Optional[StageCounters] = None,
) -> List[Dict]:
    """Flatten a pytree of (possibly sharded) jax Arrays into this
    process's shard list, every shard copied to the host: what the
    blocking save, the synchronous fallback and the tests hand to
    ``write_snapshot``.  (The background stager never calls this: it
    plans with ``plan_shards`` and ``stream_snapshot`` moves the bytes,
    paced, chunk by chunk.)

    ALL addressable shards are snapshotted (not just replica 0): a
    process's shm must be self-sufficient for a same-mesh restart, and
    with dp replication the replica-0 copy may live on another process
    entirely.  Deduplicating identical replicas within one process keeps
    the shm bounded; cross-process duplication of replicated leaves is the
    price of local restartability (same trade the reference makes for DDP
    shm snapshots).

    Every device->host DMA is kicked up front so transfers overlap
    maximally — lowest total staging time, for a caller that is blocked
    until the last byte lands anyway.  The async prefetch is issued on
    the per-shard ``shard.data`` arrays — the same objects later
    converted — NOT on the parent leaf: a parent-level
    ``copy_to_host_async`` caches on the parent, and
    ``np.asarray(shard.data)`` would then run a second, synchronous
    transfer, doubling D2H traffic and defeating the pipeline."""
    leaves = _enumerate_shards(state)
    shard_arrays = [
        shard["data"]
        for leaf in leaves
        for shard in leaf["shards"]
        if not isinstance(shard["data"], np.ndarray)
    ]
    c = counters if counters is not None else StageCounters()

    def _kick(arr) -> bool:
        try:
            arr.copy_to_host_async()
            return True
        except (AttributeError, RuntimeError):
            return False  # backend without async copies: asarray blocks

    for arr in shard_arrays:
        if not _kick(arr):
            break

    t0 = time.perf_counter()
    for leaf in leaves:
        for shard in leaf["shards"]:
            data = shard["data"]
            if isinstance(data, np.ndarray):
                continue
            shard["data"] = np.asarray(data)
            c.chunk(shard["data"].nbytes)
    c.d2h_wait_s += time.perf_counter() - t0
    return leaves


def snapshot_nbytes(leaves: List[Dict]) -> int:
    total = 0
    for leaf in leaves:
        for shard in leaf["shards"]:
            total += shard["data"].nbytes
    return total


def _shard_nbytes(data) -> int:
    dt = np.dtype(data.dtype)
    return (
        int(np.prod(data.shape)) * dt.itemsize if data.shape else dt.itemsize
    )


def plan_shards(state: Any) -> List[Dict]:
    """Enumerate this process's shards with NO device->host transfer —
    the first half of the streaming path.  Shapes/dtypes come from array
    metadata, so the full shm layout can be computed before a single
    payload byte moves."""
    return _enumerate_shards(state)


def compute_layout(
    step: int, leaves: List[Dict], extras: Optional[Dict] = None
) -> Tuple[bytes, List[Tuple[int, Any]], int]:
    """Precompute the exact shm layout from abstract shapes.

    Returns ``(meta_bytes, placements, total)`` where ``placements`` is
    a flat ``[(payload_offset, shard_dict), ...]`` in storage order and
    ``total`` is the full segment size (prefix + meta + payload).  The
    meta is byte-identical in structure to what ``write_snapshot``
    produces, so readers cannot tell which path staged a snapshot."""
    meta_leaves = []
    placements: List[Tuple[int, Any]] = []
    offset = 0
    for leaf in leaves:
        shard_metas = []
        for shard in leaf["shards"]:
            data = shard["data"]
            nbytes = _shard_nbytes(data)
            shard_metas.append(
                {
                    "index": shard["index"],
                    "offset": offset,
                    "nbytes": int(nbytes),
                    # 0-d scalars are stored as [1]: the historical meta
                    # shape (ascontiguousarray promotes 0-d to 1-d), so
                    # both write paths stay byte-identical
                    "shape": [int(d) for d in data.shape] or [1],
                }
            )
            placements.append((offset, shard))
            offset += nbytes
        meta_leaves.append(
            {
                "path": leaf["path"],
                "dtype": leaf["dtype"],
                "gshape": leaf["gshape"],
                "shards": shard_metas,
            }
        )
    meta = {
        "step": int(step),
        "extras": extras or {},
        "leaves": meta_leaves,
        "payload_bytes": offset,
    }
    meta_bytes = json.dumps(meta).encode("utf-8")
    total = _META_OFF + len(meta_bytes) + offset
    return meta_bytes, placements, total


def read_generation(shm: SharedMemoryBuffer) -> Optional[int]:
    """The seqlock generation word, or None when no segment/too small."""
    if not shm.attach() or shm.size < _META_OFF:
        return None
    return struct.unpack(">Q", bytes(shm.buf[_GEN_OFF : _GEN_OFF + 8]))[0]


def is_torn(shm: SharedMemoryBuffer) -> bool:
    """True when a writer died mid-write (odd generation): the payload
    is part old snapshot, part new — unusable, and distinguishable from
    'no snapshot was ever taken'."""
    gen = read_generation(shm)
    return gen is not None and gen % 2 == 1


def _begin_write(buf) -> int:
    """Invalidate the snapshot and mark the generation dirty.  Order
    matters: the generation goes odd FIRST, so a reader can never see a
    valid-looking meta length over a half-written payload."""
    (gen,) = struct.unpack(">Q", bytes(buf[_GEN_OFF : _GEN_OFF + 8]))
    if gen % 2 == 0:
        gen += 1
    buf[_GEN_OFF : _GEN_OFF + 8] = struct.pack(">Q", gen)
    buf[0:_HEADER] = struct.pack(">Q", 0)
    return gen


def _commit_write(buf, gen: int, meta_len: int) -> None:
    """Publish: meta length first, then the even generation LAST — the
    reverse of ``_begin_write``, completing the seqlock protocol."""
    buf[0:_HEADER] = struct.pack(">Q", meta_len)
    buf[_GEN_OFF : _GEN_OFF + 8] = struct.pack(">Q", gen + 1)


def _buffer_safe(data: np.ndarray) -> np.ndarray:
    """Zero-copy same-width uint reinterpretation for extension dtypes
    (ml_dtypes bfloat16/fp8), which lack the buffer protocol ("cannot
    include dtype 'E'").  Readback is unaffected — read_shard_bytes
    rebuilds from raw bytes with the dtype recorded in the leaf meta."""
    if data.dtype.kind not in "biufc":
        data = data.view({
            1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64,
        }[data.dtype.itemsize])
    return data


def _byte_view(data: np.ndarray) -> memoryview:
    """Flat byte view of an array (made C-contiguous if needed)."""
    return memoryview(
        np.ascontiguousarray(_buffer_safe(data))
    ).cast("B")


#: public alias: the distributed persist path (``distributed.py``)
#: serializes host shards through the same extension-dtype-safe view
#: the shm writers use, so bf16/fp8 leaves round-trip identically on
#: both paths
byte_view = _byte_view


def _stream_shard(
    buf, dst_off: int, arr, pacer: "StagePacer",
    chunk_override: int, c: StageCounters,
) -> None:
    """Stream one shard into its final shm offset, chunk by chunk.

    Chunks are row blocks along axis 0 — the one axis whose slices are
    contiguous in the C-order destination, so every chunk lands with a
    single bounded memcpy at ``dst_off + start_row * row_bytes``.  The
    NEXT chunk's D2H is kicked asynchronously (``copy_to_host_async``)
    before the current one is converted, so transfer N+1 overlaps the
    shm write of chunk N (double buffering)."""
    if isinstance(arr, np.ndarray):
        # host-resident leaf: one memcpy per chunk, no D2H
        view = _byte_view(arr)
        nbytes = len(view)
        pos = 0
        while pos < nbytes:
            n = min(max(1, chunk_override or pacer.chunk_bytes),
                    nbytes - pos)
            pacer.gate()
            t0 = time.perf_counter()
            buf[dst_off + pos : dst_off + pos + n] = view[pos : pos + n]
            c.shm_copy_s += time.perf_counter() - t0
            c.chunk(n)
            c.host_copy(n)
            _chaos_point("snapshot.stream_chunk", chunk=c.chunks - 1)
            pos += n
        return

    import jax

    np_dtype = np.dtype(arr.dtype)
    nbytes = _shard_nbytes(arr)

    def _kick(dev) -> None:
        try:
            dev.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # backend without async copies: asarray blocks

    def _land(dev, off: int, n: int) -> None:
        t0 = time.perf_counter()
        host = np.asarray(dev)
        t1 = time.perf_counter()
        pacer.note_transfer(n, t1 - t0)
        buf[off : off + n] = _byte_view(host)
        c.d2h_wait_s += t1 - t0
        c.shm_copy_s += time.perf_counter() - t1
        c.chunk(n)
        c.host_copy(n)
        _chaos_point("snapshot.stream_chunk", chunk=c.chunks - 1)

    chunk_bytes = chunk_override or pacer.chunk_bytes
    if not arr.shape or nbytes <= chunk_bytes or nbytes <= 2 * _MIN_CHUNK:
        pacer.gate()
        _kick(arr)
        _land(arr, dst_off, nbytes)
        return
    n_rows = int(arr.shape[0])
    row_bytes = max(1, nbytes // n_rows)
    if row_bytes > max(chunk_bytes, _MIN_CHUNK):
        # the leading dim is too coarse to pace (e.g. a (1, big, big)
        # scan-stacked shard would stream as ONE giant transfer — the
        # exact step-stall the chunker exists to bound).  Flatten on
        # device: a row-major reshape of a contiguous array is a
        # metadata-level bitcast for XLA, and element granularity makes
        # every chunk size reachable.
        t0 = time.perf_counter()
        arr = jax.numpy.reshape(arr, (-1,))
        c.slice_s += time.perf_counter() - t0
        n_rows = int(arr.shape[0])
        row_bytes = max(1, nbytes // n_rows)
    pending: Optional[Tuple[Any, int, int]] = None
    start = 0
    while start < n_rows:
        chunk_bytes = chunk_override or pacer.chunk_bytes
        rows = max(1, int(chunk_bytes // row_bytes))
        stop = min(n_rows, start + rows)
        pacer.gate()
        t0 = time.perf_counter()
        dev = (
            arr if (start == 0 and stop == n_rows)
            else jax.lax.slice_in_dim(arr, start, stop, axis=0)
        )
        _kick(dev)
        c.slice_s += time.perf_counter() - t0
        if pending is not None:
            _land(*pending)
        pending = (dev, dst_off + start * row_bytes,
                   (stop - start) * row_bytes)
        start = stop
    if pending is not None:
        _land(*pending)


def stream_snapshot(
    shm: SharedMemoryBuffer,
    step: int,
    leaves: List[Dict],
    extras: Optional[Dict] = None,
    pacer: Optional["StagePacer"] = None,
    chunk_bytes: int = 0,
    release_shards: bool = True,
    counters: Optional[StageCounters] = None,
) -> StageCounters:
    """Streaming zero-copy write: precomputed layout, paced D2H chunks
    landing directly at their final shm offsets, seqlock commit.

    ``leaves`` comes from ``plan_shards`` (device arrays still in
    place).  ``release_shards`` drops each shard's device reference as
    soon as its bytes land, so the async-save HBM overhead shrinks as
    staging progresses instead of persisting until the end.  Returns
    the counters of what it did (``counters`` where given, so that a
    caller's span and an engine-less drill read the same numbers), with
    one ``flash.stage.shard`` span a placement.  Raising mid-stream
    (fault, kill) leaves the generation dirty — readers fall back to
    storage candidates."""
    from dlrover_tpu.observability import trace

    c = counters if counters is not None else StageCounters()
    if pacer is None:
        pacer = StagePacer()
    slept = pacer.slept_s
    if not chunk_bytes:
        chunk_bytes = envs.get_int("DLROVER_TPU_STREAM_CHUNK_BYTES")
    meta_bytes, placements, total = compute_layout(step, leaves, extras)
    shm.init(total)
    buf = shm.buf
    gen = _begin_write(buf)
    buf[_META_OFF : _META_OFF + len(meta_bytes)] = meta_bytes
    base = _META_OFF + len(meta_bytes)
    # placements are in storage order: leaf by leaf, shard by shard
    paths = [leaf["path"] for leaf in leaves for _ in leaf["shards"]]
    try:
        for (offset, shard), path in zip(placements, paths):
            with trace.span("flash.stage.shard", attrs={"path": path}) as sp:
                bytes0, chunks0 = c.bytes, c.chunks
                _stream_shard(
                    buf, base + offset, shard["data"], pacer, chunk_bytes, c,
                )
                sp.set_attr("bytes", c.bytes - bytes0)
                sp.set_attr("chunks", c.chunks - chunks0)
            if release_shards:
                # free the device chunk as soon as it has landed: the HBM
                # held by the async-save copy drains with staging progress
                shard["data"] = None
    finally:
        c.pace_sleep_s += pacer.slept_s - slept
    _commit_write(buf, gen, len(meta_bytes))
    return c


def write_snapshot(
    shm: SharedMemoryBuffer,
    step: int,
    leaves: List[Dict],
    extras: Optional[Dict] = None,
    counters: Optional[StageCounters] = None,
) -> int:
    """Pack host-staged leaves (``extract_host_shards``) into shm;
    returns total bytes used.  For the callers that are blocked until
    the snapshot lands: the blocking save, the synchronous fallback,
    peer restore.  (The stager's writer is ``plan_shards`` +
    ``stream_snapshot``.)"""
    for leaf in leaves:
        for shard in leaf["shards"]:
            shard["data"] = np.ascontiguousarray(shard["data"])
    meta_bytes, placements, total = compute_layout(step, leaves, extras)
    shm.init(total)
    buf = shm.buf
    # seqlock invalidate -> write -> commit: a process killed mid-write
    # — likely now that staging runs on a background thread concurrent
    # with training — leaves an odd generation and a zero meta length,
    # which reads as "no snapshot" instead of step-N metadata over torn
    # payload bytes that save-on-failure would persist as if valid.
    gen = _begin_write(buf)
    buf[_META_OFF : _META_OFF + len(meta_bytes)] = meta_bytes
    base = _META_OFF + len(meta_bytes)
    flat = [
        (base + offset, _buffer_safe(shard["data"]))
        for offset, shard in placements
    ]
    from dlrover_tpu.common import fastcopy

    t0 = time.perf_counter()
    if not fastcopy.copy_into(buf, flat):
        # no native copier (or batch too small for threads to pay)
        for offset, data in flat:
            view = memoryview(data).cast("B")
            buf[offset : offset + data.nbytes] = view
    if counters is not None:
        counters.shm_copy_s += time.perf_counter() - t0
        for _, data in flat:
            counters.host_copy(data.nbytes)
    # commit: only a fully-written snapshot ever becomes readable
    _commit_write(buf, gen, len(meta_bytes))
    return total


def read_snapshot_meta(shm: SharedMemoryBuffer) -> Optional[Dict]:
    if not shm.attach():
        return None
    buf = shm.buf
    if shm.size < _META_OFF:
        return None
    if is_torn(shm):
        return None  # writer died mid-stream: meta may cover torn bytes
    (meta_len,) = struct.unpack(">Q", bytes(buf[0:_HEADER]))
    if meta_len == 0 or _META_OFF + meta_len > shm.size:
        return None
    try:
        return json.loads(bytes(buf[_META_OFF : _META_OFF + meta_len]))
    except ValueError:
        return None


def read_meta_bytes(shm: SharedMemoryBuffer) -> Optional[bytes]:
    """The committed meta's RAW json bytes (None when absent/torn).
    The peer-restore serve endpoint ships these verbatim so a fetcher
    can crc-check exactly what the donor's seqlock committed."""
    if not shm.attach() or shm.size < _META_OFF or is_torn(shm):
        return None
    (meta_len,) = struct.unpack(">Q", bytes(shm.buf[0:_HEADER]))
    if meta_len == 0 or _META_OFF + meta_len > shm.size:
        return None
    return bytes(shm.buf[_META_OFF : _META_OFF + meta_len])


def read_payload_range(
    shm: SharedMemoryBuffer, offset: int, nbytes: int
) -> Optional[bytes]:
    """``nbytes`` of the committed payload starting at payload-relative
    ``offset`` (None when absent/torn/out of range).  The caller pins
    the seqlock generation around this read — the range itself makes
    no atomicity promise."""
    if not shm.attach() or shm.size < _META_OFF or is_torn(shm):
        return None
    base = payload_base(shm)
    start = base + int(offset)
    end = start + int(nbytes)
    if offset < 0 or nbytes < 0 or end > shm.size:
        return None
    return bytes(shm.buf[start:end])


def payload_base(shm: SharedMemoryBuffer) -> int:
    """Byte offset where the payload starts (after prefix + meta)."""
    (meta_len,) = struct.unpack(">Q", bytes(shm.buf[0:_HEADER]))
    return _META_OFF + int(meta_len)


def read_shard_bytes(shm: SharedMemoryBuffer, meta: Dict, shard_meta: Dict,
                     dtype: str) -> np.ndarray:
    base = payload_base(shm)
    start = base + shard_meta["offset"]
    raw = bytes(shm.buf[start : start + shard_meta["nbytes"]])
    return np.frombuffer(raw, dtype=np.dtype(dtype)).reshape(
        shard_meta["shape"]
    )


class ShardIndexMap:
    """Assemble arbitrary slices of a leaf from stored global-index shards."""

    def __init__(self, dtype: str, gshape: List[int]):
        self.dtype = np.dtype(dtype)
        self.gshape = gshape
        self._pieces: List[Tuple[List[List[int]], np.ndarray]] = []

    def add(self, index: List[List[int]], data: np.ndarray):
        self._pieces.append((index, data))

    def add_lazy(self, index: List[List[int]], loader):
        """Register a shard whose bytes are fetched only if a ``read``
        actually needs it (remote restores: ranged GETs for the target
        sharding's slices, never whole blobs).  ``loader`` is a zero-arg
        callable returning the shard ndarray."""
        self._pieces.append((index, loader))

    def covers(self, target: Tuple[slice, ...]) -> bool:
        """Cheap coverage check (no copying) for the given slice."""
        try:
            self._check_coverage(target)
            return True
        except ValueError:
            return False

    def _check_coverage(self, target: Tuple[slice, ...]):
        tgt = []
        for dim, sl in enumerate(target):
            start = sl.start if sl.start is not None else 0
            stop = sl.stop if sl.stop is not None else self.gshape[dim]
            tgt.append((int(start), int(stop)))
        need = math.prod(b - a for a, b in tgt) if tgt else 1
        got = 0
        for index, _ in self._pieces:
            overlap = 1
            for (ts, te), (ss, se) in zip(tgt, index):
                lo, hi = max(ts, ss), min(te, se)
                if lo >= hi:
                    overlap = 0
                    break
                overlap *= hi - lo
            got += overlap
        # pieces never overlap each other (distinct shard indices), so
        # summed overlap == need implies full coverage
        if got < need:
            raise ValueError(f"coverage {got}/{need}")

    def read(self, target: Tuple[slice, ...]) -> np.ndarray:
        tgt = []
        for dim, sl in enumerate(target):
            start = sl.start if sl.start is not None else 0
            stop = sl.stop if sl.stop is not None else self.gshape[dim]
            tgt.append((int(start), int(stop)))
        out = np.zeros([b - a for a, b in tgt], dtype=self.dtype)
        filled = 0
        for pos, (index, data) in enumerate(self._pieces):
            src_slices, dst_slices = [], []
            ok = True
            for (ts, te), (ss, se) in zip(tgt, index):
                lo, hi = max(ts, ss), min(te, se)
                if lo >= hi:
                    ok = False
                    break
                src_slices.append(slice(lo - ss, hi - ss))
                dst_slices.append(slice(lo - ts, hi - ts))
            if ok:
                if callable(data):
                    # materialize once; replicated dims hit a shard from
                    # several device indices and must not re-download
                    data = data()
                    self._pieces[pos] = (index, data)
                piece = data[tuple(src_slices)]
                out[tuple(dst_slices)] = np.asarray(piece).reshape(
                    out[tuple(dst_slices)].shape
                )
                filled += math.prod(
                    s.stop - s.start for s in dst_slices
                ) if dst_slices else out.size
        if filled < out.size:
            raise ValueError(
                f"checkpoint does not cover requested slice (filled "
                f"{filled}/{out.size} elements)"
            )
        return out
