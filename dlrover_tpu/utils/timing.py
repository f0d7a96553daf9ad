"""Trustworthy device synchronization for timing code.

``jax.block_until_ready`` is only as good as the backend's ready-event
plumbing: a backend whose ready event resolved at *enqueue* time would
make any benchmark that trusts it report dispatch latency as compute
time.  A data-dependent host fetch cannot complete before the producing
computation, so that is the barrier all timing code here uses.  Whether
the installed TPU runtime needs it is measured, not assumed:
``chip_smoke.py``'s ``sync`` phase prints the ratio of the two barriers
on the chip.

Counterpart concern in the reference: its timers read CUDA events
recorded on the stream (xpu_timer/xpu_timer/common/manager.h:50), which
are device-side and immune to this class of bug; a host-side framework
must build the equivalent guarantee explicitly.
"""

from typing import Any

import jax


def hard_block(tree: Any) -> Any:
    """Block until every array in ``tree`` has actually been computed.

    Uses ``block_until_ready`` first (correct and cheapest on healthy
    backends, and it drains transfer queues), then forces a 1-element
    data-dependent device->host fetch per distinct device so a lying
    ready-event cannot fake completion.  Returns ``tree`` unchanged.
    """
    jax.block_until_ready(tree)
    leaves = [x for x in jax.tree.leaves(tree) if hasattr(x, "dtype")]
    # one probe per device is enough: PJRT executes a device's queue in
    # order, so the last-enqueued probe implies everything before it.
    # Probes are limited to fully-addressable arrays — slicing a
    # multi-host global array eagerly is not legal, and a probe on any
    # same-device local array still drains the queue.  If no leaf is
    # probeable (pure multi-host tree), block_until_ready above is the
    # best available barrier.
    seen = set()
    probes = []
    try:
        for leaf in reversed(leaves):
            try:
                if not getattr(leaf, "is_fully_addressable", False):
                    continue
                devs = frozenset(leaf.devices())
            except Exception:  # noqa: BLE001 - non-jax array leaf
                continue
            if devs in seen:
                continue
            seen.add(devs)
            probes.append(jax.numpy.ravel(leaf)[:1])
        if probes:
            jax.device_get(probes)
    except Exception:  # noqa: BLE001 - a barrier must never crash training
        pass
    return tree
