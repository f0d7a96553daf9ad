"""Hierarchical multi-slice grad-sync support: the DCN boundary, priced.

Real TPU production scale is many pod slices joined by a slow DCN
fabric, but every r14 transport tier assumed one flat mesh — a single
cross-slice hop priced at full gradient volume.  The hierarchical sync
(``collectives.hierarchical_bucket_reduce_scatter``) splits the dp
reduce into a quantized reduce-scatter over ICI within a slice, ONE
aggregated (more aggressively quantized, per EQuARX) exchange over DCN
across slices, and an intra-slice all-gather.  This module holds the
pieces that sit AROUND that chain:

Simulated DCN boundary (``DLROVER_TPU_SLICE_SIM``)
    On a CPU mesh there is no slow fabric to beat, so every
    cross-slice exchange routes its payload through a host-side toll
    (``jax.pure_callback`` inside the shard_map body): sleep
    ``bytes / DLROVER_TPU_SLICE_SIM_GBPS + DLROVER_TPU_SLICE_SIM_LAT_US``,
    and fire the ``comm.axis_delay.<axis>`` chaos point INSIDE the
    sleep window so a seeded DELAY fault is extra injected link
    latency — the same point the commscope probe prices, so the fabric
    digest and the executed step agree on which axis is slow.  Tolls
    run per device and concurrently (like the real link), so measured
    wall time genuinely separates flat (full volume over DCN) from
    hierarchical (1/ici_dp of the volume over DCN).

:class:`DcnMeter`
    Host-side bytes-on-wire ledger per fabric tier: every toll books
    the exchange's off-device bytes, so benches and the CI smoke can
    assert MEASURED cross-slice bytes (not just the estimator's
    topology math) dropped by the intra-slice dp factor.

Auto-demotion (``DLROVER_TPU_HIER_DEMOTION``)
    When the r16 ``SlowLinkDiagnostician`` names a degraded cross-slice
    axis, :class:`DcnDemotionHook` demotes the policy's DCN leg one
    quantization tier (int8 -> int4, blockwise -> int4) — logged,
    counted in ``dlrover_tpu_hier_dcn_demotions_total``, and applied by
    recompiling the step against the heavier wire format.

Per-tier bytes accounting
    :func:`estimate_tiered_bytes` itemizes a bucket layout's
    reduce-scatter + all-gather bytes per fabric tier (metadata
    included) for both the flat and hierarchical programs — the
    numbers ``grad_sync_bench`` reports in its ``hierarchy`` section
    and the smoke's DCN-reduction assertion reads.
"""

import threading
from typing import Any, Callable, Dict, List, Optional

from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger
from dlrover_tpu.parallel.mesh import (
    FABRIC_DCN,
    FABRIC_ICI,
    SLICE_AXIS,
    SliceTopology,
    axis_fabric,
)

#: chaos point prefix shared with the commscope probe: a seeded DELAY
#: on ``comm.axis_delay.slice`` is injected DCN link latency, paid by
#: every tolled cross-slice exchange AND the probe's timed window.
AXIS_DELAY_POINT = "comm.axis_delay."


def sim_enabled() -> bool:
    """Whether cross-slice exchanges pay the simulated DCN toll."""
    return envs.get_bool("DLROVER_TPU_SLICE_SIM")


class DcnMeter:
    """Process-level bytes-on-wire account per fabric tier (host side,
    booked by the simulator toll).  Thread-safe; per-device callbacks
    each book their own off-device bytes."""

    def __init__(self):
        self._mu = threading.Lock()
        self._bytes: Dict[str, float] = {}
        self._exchanges: Dict[str, int] = {}

    def record(self, tier: str, nbytes: float) -> None:
        with self._mu:
            self._bytes[tier] = self._bytes.get(tier, 0.0) + float(nbytes)
            self._exchanges[tier] = self._exchanges.get(tier, 0) + 1

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._mu:
            return {
                tier: {
                    "bytes": int(self._bytes.get(tier, 0.0)),
                    "exchanges": int(self._exchanges.get(tier, 0)),
                }
                for tier in sorted(self._bytes)
            }

    def bytes_for(self, tier: str) -> int:
        with self._mu:
            return int(self._bytes.get(tier, 0.0))

    def reset(self) -> None:
        with self._mu:
            self._bytes.clear()
            self._exchanges.clear()


_METER: Optional[DcnMeter] = None
_METER_MU = threading.Lock()


def meter() -> DcnMeter:
    global _METER
    if _METER is None:
        with _METER_MU:
            if _METER is None:
                _METER = DcnMeter()
    return _METER


def reset_meter() -> DcnMeter:
    """Fresh meter (benches isolate flat-vs-hierarchical runs)."""
    global _METER
    with _METER_MU:
        _METER = DcnMeter()
        return _METER


def _toll_host(arr, nbytes: int, axis_name: str):
    """The host side of one tolled exchange: book the bytes, fire the
    chaos link-delay point (a seeded DELAY sleeps here), then sleep out
    the byte-priced link time.  Runs once per device, concurrently —
    wall clock pays ~one link crossing, like the real fabric."""
    import time as _time

    meter().record(FABRIC_DCN, nbytes)
    try:
        from dlrover_tpu import chaos

        chaos.point(AXIS_DELAY_POINT + axis_name, nbytes=int(nbytes))
    except Exception:  # noqa: BLE001 - chaos must not break the step
        pass
    gbps = envs.get_float("DLROVER_TPU_SLICE_SIM_GBPS")
    lat_s = envs.get_float("DLROVER_TPU_SLICE_SIM_LAT_US") / 1e6
    delay = lat_s + (float(nbytes) / (gbps * 1e9) if gbps > 0 else 0.0)
    if delay > 0:
        _time.sleep(delay)
    return arr


def dcn_toll(x, nbytes: int, axis) -> Any:
    """Route ``x`` (one exchanged array) through the simulated DCN
    link: identity on the data, but the host sleeps the link time for
    ``nbytes`` off-device bytes before anything downstream of ``x`` can
    run.  Caller decides AT TRACE TIME whether to insert the toll
    (``sim_enabled()`` + the axis crossing DCN) — a disabled sim
    compiles to nothing."""
    import functools

    import jax

    # the chaos point is named after the DCN MEMBER axis: a flat
    # combined-axis collective (("slice", "dp")) crosses the same
    # physical link as the hierarchical slice-only leg, so both must
    # pay the same armed comm.axis_delay.slice fault
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    dcn_members = [
        a for a in names
        if axis_fabric(a) == FABRIC_DCN
    ]
    axis_name = (dcn_members or list(names))[0]
    cb = functools.partial(
        _toll_host, nbytes=int(nbytes), axis_name=axis_name
    )
    return jax.pure_callback(
        cb, jax.ShapeDtypeStruct(x.shape, x.dtype), x
    )


def maybe_toll(x, nbytes: int, axis) -> Any:
    """``dcn_toll`` iff the simulator is on AND ``axis`` crosses the
    slice boundary; otherwise ``x`` untouched (zero trace cost)."""
    if not sim_enabled() or axis_fabric(axis) != FABRIC_DCN:
        return x
    return dcn_toll(x, nbytes, axis)


def toll_payload(payload: Dict[str, Any], nbytes: int, axis) -> Dict[str, Any]:
    """Toll a multi-array exchange payload ONCE: the decode consumes
    every entry, so delaying one (the first) delays the whole decode —
    one link crossing per exchange, not one per payload array."""
    if not sim_enabled() or axis_fabric(axis) != FABRIC_DCN:
        return payload
    out = dict(payload)
    first = next(iter(out))
    out[first] = dcn_toll(out[first], nbytes, axis)
    return out


# -- per-tier bytes accounting ----------------------------------------------


def estimate_tiered_bytes(
    buckets,
    policy,
    topo: SliceTopology,
    hierarchical: bool,
) -> Dict[str, Any]:
    """Per-fabric-tier bytes-on-wire (per device per step, quantization
    metadata included) for a bucket layout on a two-level mesh.

    Flat program: every bucket moves through ONE collective over the
    combined ``(slice, dp)`` axis — a ring spanning the slice boundary,
    so the whole reduce-scatter + all-gather volume is priced DCN (the
    slow hop bottlenecks the ring; this is the accounting the toll
    simulator executes).  Note the flat layout's world is
    ``topo.world``.

    Hierarchical program (bucket layout world = ``topo.ici_dp``):

    * ICI: the in-slice quantized reduce-scatter of the full bucket
      (world ``ici_dp``) + the in-slice fp32 param all-gather;
    * DCN: the aggregated cross-slice exchange of ONE in-slice chunk
      (1/ici_dp of the bucket) in the heavier ``dcn_format`` codec —
      reduce-scatter across slices plus the quantized return
      all-gather of the summed sub-chunks.
    """
    from dlrover_tpu.parallel import collectives

    world = topo.world
    ici = topo.ici_dp
    nslices = topo.num_slices
    rows: List[Dict[str, Any]] = []
    totals = {
        FABRIC_ICI: 0.0, FABRIC_DCN: 0.0,
        "metadata_" + FABRIC_ICI: 0.0, "metadata_" + FABRIC_DCN: 0.0,
    }

    def codec_bytes(width: int, pol) -> Dict[str, float]:
        if pol is not None and pol.quantized:
            block = pol.block_size
            nblk = -(-width // block)
            cb = collectives.codec_chunk_bytes(nblk, block, pol)
            return {"payload": float(cb["payload"]),
                    "metadata": float(cb["metadata"])}
        return {"payload": 4.0 * width, "metadata": 0.0}

    dcn_pol = policy.dcn_policy() if hierarchical else None
    for b in buckets.buckets:
        width = b.width
        if hierarchical:
            # stage 1: in-slice RS — each device ships (ici-1) encoded
            # chunks of its (ici, width) buffer
            cb1 = codec_bytes(width, policy if policy.quantized else None)
            ici_rs = (ici - 1) * (cb1["payload"] + cb1["metadata"])
            ici_meta = (ici - 1) * cb1["metadata"]
            # stage 3: in-slice fp32 param all-gather of the bucket
            ici_ag = (ici - 1) * 4.0 * width
            # stage 2: cross-slice exchange of the (width,) chunk —
            # RS of the chunk's slice-destined pieces + the quantized
            # return all-gather of the summed sub-chunk
            sub = -(-width // nslices)
            cb2 = codec_bytes(sub, dcn_pol)
            dcn_rs = (nslices - 1) * (cb2["payload"] + cb2["metadata"])
            dcn_ag = (nslices - 1) * (cb2["payload"] + cb2["metadata"])
            dcn_meta = 2 * (nslices - 1) * cb2["metadata"]
            row = {
                "bucket": b.index, "width": width,
                "ici_bytes": int(ici_rs + ici_ag),
                "dcn_bytes": int(dcn_rs + dcn_ag),
                "ici_metadata_bytes": int(ici_meta),
                "dcn_metadata_bytes": int(dcn_meta),
            }
            totals[FABRIC_ICI] += ici_rs + ici_ag
            totals[FABRIC_DCN] += dcn_rs + dcn_ag
            totals["metadata_" + FABRIC_ICI] += ici_meta
            totals["metadata_" + FABRIC_DCN] += dcn_meta
        else:
            cb1 = codec_bytes(width, policy if policy.quantized else None)
            rs = (world - 1) * (cb1["payload"] + cb1["metadata"])
            ag = (world - 1) * 4.0 * width
            meta = (world - 1) * cb1["metadata"]
            row = {
                "bucket": b.index, "width": width,
                "ici_bytes": 0,
                "dcn_bytes": int(rs + ag),
                "ici_metadata_bytes": 0,
                "dcn_metadata_bytes": int(meta),
            }
            totals[FABRIC_DCN] += rs + ag
            totals["metadata_" + FABRIC_DCN] += meta
        rows.append(row)
    return {
        "hierarchical": bool(hierarchical),
        "num_slices": nslices,
        "ici_dp": ici,
        "per_bucket": rows,
        "ici_bytes": int(totals[FABRIC_ICI]),
        "dcn_bytes": int(totals[FABRIC_DCN]),
        "ici_metadata_bytes": int(totals["metadata_" + FABRIC_ICI]),
        "dcn_metadata_bytes": int(totals["metadata_" + FABRIC_DCN]),
    }


# -- auto-demotion (SlowLinkDiagnostician -> heavier DCN codec) -------------

#: heavier-tier ladder for the DCN leg: fewer wire bytes per step.
#: ``int4`` is the floor (blockwise ships MORE bytes than int4 — its
#: refinement rides on top — so a degraded link demotes it down too).
DCN_DEMOTION_LADDER: Dict[str, str] = {
    "int8": "int4",
    "blockwise": "int4",
}


def demoted_dcn_format(fmt: str) -> Optional[str]:
    """The next-heavier DCN wire format, or None at the floor (or for
    exact legs, which carry no error-feedback state to absorb
    quantization)."""
    return DCN_DEMOTION_LADDER.get(fmt)


# process-level demotion target: a Trainer running the hierarchical
# sync registers itself at configure time, and a hook constructed
# WITHOUT an explicit holder (the master's register_sentinels path)
# resolves it lazily — so in-process runtimes (unified local masters,
# drills, tests) get end-to-end auto-demotion with zero extra wiring.
# Weakly referenced: a dead trainer must not be demoted, or kept alive.
_DEMOTION_TARGET: Any = None
_DEMOTION_MU = threading.Lock()


def register_demotion_target(holder: Any) -> None:
    """Register ``holder`` (anything with ``apply_dcn_demotion()``) as
    the process's DCN-demotion target; None clears it."""
    import weakref

    global _DEMOTION_TARGET
    with _DEMOTION_MU:
        _DEMOTION_TARGET = (
            weakref.ref(holder) if holder is not None else None
        )


def demotion_target() -> Any:
    with _DEMOTION_MU:
        ref = _DEMOTION_TARGET
    return ref() if ref is not None else None


# -- cross-process demotion staging (the Brain v2 action channel) -----------
#
# A `brain_demote` action lands at the AGENT, but the policy lives in
# the TRAINER — often another process.  The agent applies the demotion
# directly when a target is registered in its own process (unified
# local runtimes, drills); otherwise it stages a sequence bump in a
# small file next to the rank digest files, which the trainer polls on
# its digest cadence — the same file-handshake pattern the config
# tuner uses, so no new RPC surface on the workers.


def _demotion_file() -> str:
    from dlrover_tpu.common.constants import ConfigPath

    return envs.get_str(ConfigPath.ENV_RUNTIME_METRICS) + ".demote"


def stage_demotion(reason: str = "") -> Optional[str]:
    """Handle one delivered ``brain_demote``: apply in-process when a
    demotion target is registered here, else bump the staging file's
    sequence for the out-of-process trainer.  Returns the new wire
    format, ``"staged"`` for the file path, or None when nothing could
    be done (no target and the file write failed)."""
    target = demotion_target()
    if target is not None:
        demote = getattr(target, "apply_dcn_demotion", None)
        if demote is not None:
            return demote()
    import json
    import os
    import time as _time

    path = _demotion_file()
    try:
        seq = 0
        try:
            with open(path) as f:
                seq = int(json.load(f).get("seq", 0))
        except (OSError, ValueError):
            seq = 0
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {"seq": seq + 1, "reason": reason,
                 "ts": round(_time.time(), 3)}, f,
            )
        os.replace(tmp, path)
        logger.info(
            "DCN demotion staged (seq %d) for the training process: %s",
            seq + 1, reason,
        )
        return "staged"
    except OSError as e:
        logger.warning("DCN demotion staging failed: %s", e)
        return None


def staged_seq() -> int:
    """The staging file's current sequence (0 when absent/unreadable).
    Trainers BASELINE on this at construction, so a stale file from an
    earlier incident cannot demote a fresh trainer — while a staging
    that lands before the first digest tick still applies."""
    import json

    try:
        with open(_demotion_file()) as f:
            return int(json.load(f).get("seq", 0))
    except (OSError, ValueError):
        return 0


def poll_staged_demotion(holder: Any,
                         applied_seq: Optional[int]) -> Optional[int]:
    """Trainer-side poll (digest cadence): apply stagings newer than
    ``applied_seq`` to ``holder`` and return the new watermark.
    ``applied_seq=None`` (a holder that never baselined) falls back to
    baselining on the current sequence without applying."""
    seq = staged_seq()
    if applied_seq is None:
        return seq
    steps = seq - applied_seq
    if steps <= 0:
        return applied_seq
    demote = getattr(holder, "apply_dcn_demotion", None)
    if demote is not None:
        # several stagings between polls collapse into at most the
        # ladder's depth of applications (int8 -> int4 -> floor)
        for _ in range(min(steps, len(DCN_DEMOTION_LADDER) + 1)):
            if demote() is None:
                break
    return seq


class DcnDemotionHook:
    """Bridges the r16 :class:`SlowLinkDiagnostician` to the policy:
    when a breach names an axis that crosses the DCN boundary, ask the
    holder (a ``Trainer`` — anything with ``apply_dcn_demotion()``) to
    demote its DCN leg one quantization tier.  Gated by
    ``DLROVER_TPU_HIER_DEMOTION``; never raises into the diagnosis
    loop.

    Constructed without a holder (the master-side ``register_sentinels``
    path), the hook resolves the PROCESS-registered target
    (:func:`register_demotion_target`) at breach time — in-process
    runtimes demote directly.  When NO in-process target exists and an
    ``action_sink`` is wired (the master's job-context queue, or the
    Brain's tracked channel), the demotion is queued as a
    ``brain_demote`` action instead: agents deliver it to the training
    process (directly or via :func:`stage_demotion`'s file handshake),
    closing the old master-without-a-co-resident-trainer gap."""

    def __init__(self, holder: Any = None,
                 demote: Optional[Callable[[], Optional[str]]] = None,
                 action_sink: Optional[
                     Callable[[str, str], Any]
                 ] = None):
        if demote is None and holder is not None:
            demote = getattr(holder, "apply_dcn_demotion", None)
        self._demote = demote
        self._action_sink = action_sink
        self.demotions = 0
        self.reroutes = 0

    def _resolve(self) -> Optional[Callable[[], Optional[str]]]:
        if self._demote is not None:
            return self._demote
        target = demotion_target()
        if target is None:
            return None
        return getattr(target, "apply_dcn_demotion", None)

    def __call__(self, axis: str, metric: str,
                 breach: Dict[str, Any]) -> Optional[str]:
        try:
            if not envs.get_bool("DLROVER_TPU_HIER_DEMOTION"):
                return None
            if axis_fabric(axis) != FABRIC_DCN:
                return None
            # the r21 fast cure first: a fabric-tuner re-route around
            # the slow axis is a plan swap at the next train_step —
            # far cheaper than a quantization demotion, and the grads
            # keep their wire precision.  Demotion stays the backstop
            # when no tuner is live or the re-tune changes nothing.
            from dlrover_tpu.parallel import fabric_tuner

            if fabric_tuner.reroute_on_breach(axis):
                self.reroutes += 1
                logger.warning(
                    "slow DCN link on axis %r (%s breach): fabric "
                    "tuner re-routed around it (no demotion)",
                    axis, metric,
                )
                return "rerouted"
            demote = self._resolve()
            if demote is None:
                if self._action_sink is not None:
                    reason = (
                        f"slow DCN link on axis {axis!r} "
                        f"({metric} breach)"
                    )
                    self._action_sink(axis, reason)
                    self.demotions += 1
                    logger.warning(
                        "%s: brain_demote queued on the action "
                        "channel", reason,
                    )
                    return "action_channel"
                return None
            new_fmt = demote()
            if new_fmt is not None:
                self.demotions += 1
                logger.warning(
                    "slow DCN link on axis %r (%s breach): grad-sync "
                    "DCN leg demoted to %s", axis, metric, new_fmt,
                )
            return new_fmt
        except Exception as e:  # noqa: BLE001 - a broken hook must not
            # break the diagnosis loop
            logger.warning("DCN demotion hook failed: %s", e)
            return None
