"""Device prefetch: overlap host batch prep + H2D with device compute.

Counterpart of the reference loader's prefetch/queue knobs
(``prefetch_count`` rides the paral-config wire, comm.py; torch
DataLoader workers prefetch host-side).  On TPU the win is hiding the
host->HBM copy behind the MXU: ``jax.device_put`` (inside
``shard_batch``'s ``make_array_from_process_local_data``) dispatches
asynchronously, so staging batch N+1 while the device computes step N
makes the input pipeline free as long as host prep + transfer fits in a
step time — the same pattern as ``flax.jax_utils.prefetch_to_device``,
generalized to arbitrary ``NamedSharding`` over a mesh.
"""

import queue
import threading
from typing import Any, Iterable, Iterator, Optional, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.parallel.sharding import DATA_AXES

_END = object()


class DevicePrefetcher:
    """Wrap a host batch iterator; yield mesh-staged batches ``depth``
    ahead.

    ``depth`` bounds the number of staged batches alive at once (each
    holds device memory — keep it small; 2 hides one step of latency).
    The worker thread performs ``fetch -> shard_batch`` for upcoming
    batches; exceptions it hits are re-raised to the consumer at the
    position they occurred, and ``close()`` releases the worker and the
    queued buffers promptly (safe to call mid-epoch, e.g. on an elastic
    restart).

    Data-position bookkeeping rides CONSUMPTION, not production: call
    ``sampler.record_batch`` (or save the loader offset) after
    ``train_step`` consumes a batch — up to ``depth`` staged batches
    are in flight ahead of the trained position, and a restart must
    replay them, not skip them."""

    def __init__(
        self,
        batches: Iterable[Any],
        mesh,
        data_axes: Tuple[str, ...] = DATA_AXES,
        depth: int = 2,
    ):
        from dlrover_tpu.parallel.sharding import shard_batch

        self._source = iter(batches)
        self._mesh = mesh
        self._data_axes = data_axes
        self._shard = shard_batch
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._done = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="device-prefetch"
        )
        self._thread.start()

    def _worker(self):
        try:
            for batch in self._source:
                if self._stop.is_set():
                    return
                staged = self._shard(self._mesh, batch, self._data_axes)
                # blocking put bounds staged device memory; poll the
                # stop flag so close() never deadlocks against a full
                # queue nobody is draining
                while not self._stop.is_set():
                    try:
                        self._queue.put(staged, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    # stopped while waiting for a slot: exit WITHOUT
                    # pulling another source item (an elastic restart
                    # must not advance the host data stream further)
                    return
            if not self._stop.is_set():
                self._queue.put(_END)
        except BaseException as e:  # noqa: BLE001 - forward to consumer
            if not self._stop.is_set():
                try:
                    self._queue.put(e)
                except Exception:  # noqa: BLE001
                    logger.exception("prefetch error lost")

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._stop.is_set() or self._done:
            raise StopIteration
        item = self._queue.get()
        if item is _END:
            self._done = True  # iterating again must not block forever
            raise StopIteration
        if isinstance(item, BaseException):
            self.close()
            raise item
        return item

    def close(self):
        """Stop the worker and drop staged batches (their device
        buffers free once the consumer releases its references).  Join
        BEFORE draining: a worker blocked in put() could otherwise
        re-insert a staged batch after the drain, pinning its buffers
        until GC."""
        self._stop.set()
        self._thread.join(timeout=10)
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None
