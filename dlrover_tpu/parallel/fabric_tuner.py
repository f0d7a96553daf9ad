"""Per-bucket fabric transport auto-tuner (r21).

The r16 observatory measures per-axis latency and achieved GB/s
(``commscope.FabricModel``) but until this round nothing consumed the
measurements on the training hot path — transport selection stayed a
static env-driven ladder.  :class:`FabricTuner` closes that loop: it
prices every transport tier (and, on a two-level mesh, every dual-fabric
stripe fraction) for every gradient bucket against a frozen
``FabricModel.snapshot()`` and emits a :class:`TunerPlan` of per-bucket
decisions.  The trainer re-tunes on the probe cadence, stages a changed
plan under the demotion lock and swaps it at the next ``train_step`` —
the r18 demotion pattern, so the sentinel thread never nulls the jitted
step out from under an in-flight dispatch.

The pricing model (documented in ``docs/design.md`` §12) is deliberately
coarse — per-device bytes-on-wire over measured bandwidth plus per-hop
latency, an optional HBM round-trip term for the two-stage
quantize→exchange paths, and for the dual-fabric stripe a two-phase
schedule ``max(stage1_ici, stripe_dcn) + max(stage2_dcn, ps_ici)`` in
which each fabric is a shared serial resource (see :meth:`FabricTuner.price`).
It only has to rank candidates consistently with the byte meter, which
is what the tuner smoke and ``grad_sync_bench`` assert on CPU; on
hardware the measured snapshot feeds the same formulas real numbers.

Cold start: before the first live probe fires, the ``fabric`` section of
the file ``DLROVER_TPU_TUNER_SEED_FILE`` names (:func:`seed_snapshot`; by
default ``BENCH_comm.json`` in the working directory, which
``grad_sync_bench`` writes and git does not hold) seeds the plan; with no
such file, as in a checkout, the static ladder stands.  The ``ring_rdma``
tier is only eligible once a ``grad_sync_bench`` run on the chip proved
it end-to-end (:func:`rdma_proven` on the ``BENCH_grad_overlap.json``
that run left in the working directory)."""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import threading
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common import envs

logger = logging.getLogger(__name__)

# plan provenance, worst-informed first
PLAN_SOURCES = ("static", "seed", "probe", "breach")


@dataclasses.dataclass(frozen=True)
class BucketDecision:
    """One bucket's tuned route: the transport tier requested from
    ``bucket_reduce_scatter`` and, on a two-level mesh, the dual-fabric
    stripe fraction; ``priced_us`` is the model's cost of this route
    under the snapshot the plan was derived from."""

    bucket: int
    transport: str
    stripe: float
    priced_us: float


@dataclasses.dataclass(frozen=True)
class TunerPlan:
    """A frozen set of per-bucket decisions plus where they came from
    (``static`` ladder, bench-file ``seed``, live ``probe``, or the
    slow-link ``breach`` fast path).  Ducked by
    ``collectives.sync_gradient_tree_bucketed`` via ``for_bucket``."""

    decisions: Tuple[BucketDecision, ...]
    source: str

    def for_bucket(self, index: int) -> Optional[BucketDecision]:
        for d in self.decisions:
            if d.bucket == index:
                return d
        return None

    @property
    def total_us(self) -> float:
        return sum(d.priced_us for d in self.decisions)

    def signature(self) -> Tuple[Tuple[str, float], ...]:
        """The hot-path-relevant content — a plan whose signature is
        unchanged needs no recompile/swap."""
        return tuple(
            (d.transport, round(d.stripe, 4)) for d in self.decisions
        )

    def summary(self) -> Dict[str, Any]:
        return {
            "source": self.source,
            "priced_total_us": round(self.total_us, 3),
            "per_bucket": [
                {
                    "bucket": d.bucket,
                    "transport": d.transport,
                    "stripe": round(d.stripe, 4),
                    "priced_us": round(d.priced_us, 3),
                }
                for d in self.decisions
            ],
        }


def seed_snapshot(path: Optional[str] = None) -> Optional[Dict]:
    """Cold-start fabric snapshot from a ``grad_sync_bench`` comm file
    (its ``fabric`` section IS ``FabricModel.snapshot()`` output).
    None when the file is missing/unreadable/empty, as in a checkout
    (git holds no such file) — the static ladder stands until the first
    live probe."""
    if path is None:
        path = envs.get_str("DLROVER_TPU_TUNER_SEED_FILE")
    if not path:
        return None
    try:
        with open(path) as f:
            fabric = json.load(f).get("fabric")
    except (OSError, ValueError):
        return None
    if not isinstance(fabric, dict) or not fabric:
        return None
    out = {}
    for axis, entry in fabric.items():
        try:
            out[axis] = {
                "lat_us": float(entry["lat_us"]),
                "gbps": float(entry["gbps"]),
            }
        except (KeyError, TypeError, ValueError):
            continue
    return out or None


def rdma_proven(path: str = "BENCH_grad_overlap.json") -> bool:
    """True only when a ``grad_sync_bench`` run on the chip drove the
    ``ring_rdma`` Pallas kernel end-to-end on real hardware and left
    ``status == "ok"`` in the working directory's round file (git holds
    none: False in a checkout) — the tuner must never route production
    gradients through a tier whose lowering was never executed."""
    try:
        with open(path) as f:
            evidence = json.load(f).get("ring_rdma")
    except (OSError, ValueError):
        return False
    return bool(evidence) and evidence.get("status") == "ok"


def _bw_us(nbytes: float, gbps: float) -> float:
    """Microseconds to move ``nbytes`` at ``gbps`` GB/s (inf-safe)."""
    if gbps <= 0:
        return float("inf") if nbytes > 0 else 0.0
    return nbytes / (gbps * 1e9) * 1e6


class FabricTuner:
    """Prices transport × stripe candidates per bucket against a fabric
    snapshot.  Stateless between ``decide`` calls except for the grid
    geometry captured at construction."""

    def __init__(self, buckets, policy, ici_axis, ici_world: int,
                 dcn_axis: Optional[str] = None, dcn_world: int = 1,
                 rdma_ok: Optional[bool] = None):
        self._buckets = buckets
        self._policy = policy
        self._ici_axis = ici_axis
        self._ici_world = int(ici_world)
        self._dcn_axis = dcn_axis
        self._dcn_world = int(dcn_world)
        self._rdma_ok = bool(
            rdma_proven() if rdma_ok is None else rdma_ok
        )
        self._hbm_gbps = envs.get_float("DLROVER_TPU_TUNER_HBM_GBPS")
        self._stripe_max = min(
            0.99, max(0.0, envs.get_float("DLROVER_TPU_TUNER_STRIPE_MAX"))
        )

    # -- snapshot access ----------------------------------------------------

    def _entry(self, snap: Dict, axis) -> Optional[Dict[str, float]]:
        """Measured (lat_us, gbps) for one sync axis.  A flat
        multi-axis sync (``("slice", "dp")``) is priced at its WORST
        member — the combined collective cannot beat its slowest
        fabric."""
        if isinstance(axis, str):
            e = snap.get(axis)
            if not e or e.get("gbps", 0) <= 0:
                return None
            return {"lat_us": float(e["lat_us"]),
                    "gbps": float(e["gbps"])}
        members = [self._entry(snap, a) for a in axis]
        if any(m is None for m in members) or not members:
            return None
        return {
            "lat_us": max(m["lat_us"] for m in members),
            "gbps": min(m["gbps"] for m in members),
        }

    # -- candidate enumeration ----------------------------------------------

    def _transports(self, width: int) -> List[str]:
        """Transport tiers whose preconditions hold for this bucket:
        every request is pushed through ``resolve_transport`` and the
        RESOLVED tier is the candidate, so the priced plan is exactly
        what the hot path executes (resolved names round-trip — a
        ``psum_scatter`` / ``all_to_all`` / ring-tier request resolves
        to itself under the same preconditions)."""
        from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

        pol = self._policy
        reqs = (
            ["all_to_all", "ring_pallas_q"]
            if pol.quantized
            else ["auto", "ring", "ring_pallas", "ring_rdma"]
        )
        out: List[str] = []
        for req in reqs:
            if req == "ring_rdma" and not self._rdma_ok:
                continue
            res = ring.resolve_transport(
                pol, self._ici_world, width, self._ici_axis,
                request=req,
            )
            if res not in out:
                out.append(res)
        return out

    def _stripes(self, width: int) -> List[float]:
        if self._dcn_axis is None or self._dcn_world <= 1:
            return [0.0]
        grid = [0.0, 0.125, 0.25, 0.375, 0.5]
        return [s for s in grid if s <= self._stripe_max or s == 0.0]

    # -- the pricing model --------------------------------------------------

    def _wire_bytes(self, width: int, pol) -> int:
        """Per-device reduce-scatter bytes-on-wire for one exchange of
        a ``(world, width)`` bucket in ``pol``'s codec."""
        from dlrover_tpu.parallel.collectives import codec_chunk_bytes

        world = self._ici_world
        if pol is None or not pol.quantized:
            return (world - 1) * 4 * width
        nblk = -(-width // pol.block_size)
        cb = codec_chunk_bytes(nblk, pol.block_size, pol)
        return (world - 1) * (cb["payload"] + cb["metadata"])

    def _hbm_us(self, width: int) -> float:
        """The two-stage quantize path's HBM round-trip the fused
        ``ring_pallas_q`` tier removes: the full-width fp32 bucket is
        written back after encode and re-read for the EF decode.  Off
        (0) when unpriced — CPU simulation."""
        if self._hbm_gbps <= 0:
            return 0.0
        return _bw_us(
            2 * 4 * self._ici_world * width, self._hbm_gbps
        )

    def _flat_us(self, width: int, transport: str,
                 ici: Dict[str, float]) -> float:
        """One single-fabric bucket exchange over the sync axis."""
        world = self._ici_world
        pol = self._policy
        wire = self._wire_bytes(width, pol if pol.quantized else None)
        t = _bw_us(wire, ici["gbps"])
        if transport in ("ring", "ring_pallas", "ring_pallas_q"):
            t += (world - 1) * ici["lat_us"]
        elif transport == "ring_rdma":
            # async per-hop copies hide all but the first latency
            t += ici["lat_us"]
        else:  # auto/psum_scatter, codec all_to_all: one fused program
            t += max(1.0, math.log2(max(2, world))) * ici["lat_us"]
        if pol.quantized and transport != "ring_pallas_q":
            t += self._hbm_us(width)
        return t

    def _dcn_stage2_us(self, width: int,
                       dcn: Dict[str, float]) -> float:
        """Hierarchical stage 2: the chunk's DCN reduce-scatter plus
        the quantized return all-gather (two serialized exchanges)."""
        from dlrover_tpu.parallel.collectives import codec_chunk_bytes

        S = self._dcn_world
        dcn_pol = self._policy.dcn_policy()
        if dcn_pol is None:
            nbytes = (2 * (S - 1) * 4 * width) // S
        else:
            sub = -(-width // S)
            nblk = -(-sub // dcn_pol.block_size)
            cb = codec_chunk_bytes(nblk, dcn_pol.block_size, dcn_pol)
            nbytes = 2 * (S - 1) * (cb["payload"] + cb["metadata"])
        return 2 * dcn["lat_us"] + _bw_us(nbytes, dcn["gbps"])

    def price(self, width: int, transport: str, stripe: float,
              snap: Dict) -> float:
        """Model cost (µs) of one bucket exchange under ``snap``.

        Flat mesh: the single-fabric exchange.  Two-level mesh: the
        striped chain is a two-phase schedule over two fabrics that
        are each a SHARED serial resource —

        * phase 1: the ICI stage-1 reduce-scatter on the hierarchical
          columns runs concurrently with the stripe's DCN block
          all-reduce (different fabrics → ``max``);
        * phase 2: the stage-2 DCN exchange of the stage-1 chunk runs
          concurrently with the stripe's ICI ``psum_scatter``
          (again different fabrics → ``max``).

        Striping therefore only wins while the DCN has idle headroom
        under the stage-1 window; it never wins by pretending two
        flows on the SAME degraded DCN are free parallelism
        (:func:`collectives.striped_bucket_reduce_scatter`'s actual
        dataflow)."""
        from dlrover_tpu.parallel.collectives import (
            stripe_cols,
            stripe_dcn_bytes,
        )

        ici = self._entry(snap, self._ici_axis)
        if ici is None:
            return float("inf")
        if self._dcn_axis is None or self._dcn_world <= 1:
            return self._flat_us(width, transport, ici)
        dcn = self._entry(snap, self._dcn_axis)
        if dcn is None:
            return float("inf")
        pol = self._policy
        w_d = stripe_cols(width, stripe, pol.block_size)
        w_i = width - w_d
        stage1 = self._flat_us(w_i, transport, ici)
        stage2 = self._dcn_stage2_us(w_i, dcn)
        if w_d <= 0:
            return stage1 + stage2
        stripe_bytes = stripe_dcn_bytes(
            width, self._ici_world, self._dcn_world, stripe, pol
        )
        stripe_dcn = (
            2 * dcn["lat_us"] + _bw_us(stripe_bytes, dcn["gbps"])
        )
        ps_ici = (
            max(1.0, math.log2(max(2, self._ici_world)))
            * ici["lat_us"]
            + _bw_us((self._ici_world - 1) * 4 * w_d, ici["gbps"])
        )
        return max(stage1, stripe_dcn) + max(stage2, ps_ici)

    # -- plans --------------------------------------------------------------

    def static_plan(self, snap: Optional[Dict] = None) -> TunerPlan:
        """The env-ladder's uniform route, priced under ``snap`` when
        one exists (inf otherwise) — the baseline every tuned plan is
        compared against."""
        from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

        pol = self._policy
        stripe = float(getattr(pol, "stripe", 0.0) or 0.0)
        decisions = []
        for b in self._buckets.buckets:
            t = ring.resolve_transport(
                pol, self._ici_world, b.width, self._ici_axis
            )
            priced = (
                self.price(b.width, t, stripe, snap)
                if snap else float("inf")
            )
            decisions.append(
                BucketDecision(b.index, t, stripe, priced)
            )
        return TunerPlan(tuple(decisions), "static")

    def uniform_plan(self, transport: str, stripe: float,
                     snap: Dict) -> TunerPlan:
        """One (transport, stripe) applied to every bucket, priced —
        the static legs of the bench's tuner-vs-static comparison."""
        decisions = tuple(
            BucketDecision(
                b.index, transport, stripe,
                self.price(b.width, transport, stripe, snap),
            )
            for b in self._buckets.buckets
        )
        return TunerPlan(decisions, "static")

    def decide(self, snap: Optional[Dict],
               source: str = "probe") -> TunerPlan:
        """Per-bucket argmin over the transport × stripe grid.  The
        static resolution is candidate 0, so price ties keep the
        status quo; an unpriceable snapshot (missing axis, zero
        bandwidth, None) returns the static plan unpriced."""
        from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

        if not snap or self._entry(snap, self._ici_axis) is None:
            return self.static_plan(snap)
        pol = self._policy
        decisions = []
        for b in self._buckets.buckets:
            static_t = ring.resolve_transport(
                pol, self._ici_world, b.width, self._ici_axis
            )
            cands = self._transports(b.width)
            if static_t in cands:
                cands = [static_t] + [
                    t for t in cands if t != static_t
                ]
            best: Optional[BucketDecision] = None
            for transport in cands:
                for stripe in self._stripes(b.width):
                    priced = self.price(
                        b.width, transport, stripe, snap
                    )
                    if best is None or priced < best.priced_us:
                        best = BucketDecision(
                            b.index, transport, stripe, priced
                        )
            decisions.append(best)
        if any(
            d is None or not math.isfinite(d.priced_us)
            for d in decisions
        ):
            return self.static_plan(snap)
        return TunerPlan(tuple(decisions), source)

    def gain_ok(self, new: TunerPlan, live: Optional[TunerPlan],
                snap: Dict) -> bool:
        """Hysteresis: stage a swap only when the new plan prices at
        least ``DLROVER_TPU_TUNER_MIN_GAIN`` faster than the LIVE
        routes re-priced under the SAME snapshot (so a stale live plan
        cannot defend itself with stale prices)."""
        if live is None:
            return True
        live_total = sum(
            self.price(b.width, d.transport, d.stripe, snap)
            for b, d in zip(self._buckets.buckets, live.decisions)
        )
        if not math.isfinite(live_total):
            return True
        min_gain = max(
            0.0, envs.get_float("DLROVER_TPU_TUNER_MIN_GAIN")
        )
        return new.total_us <= live_total * (1.0 - min_gain)


# -- process-level re-tune target (the slow-link breach fast path) ----------
#
# Mirrors hierarchy.register_demotion_target: a Trainer running the
# tuner registers itself, and the DcnDemotionHook tries a re-tune
# around the slow axis FIRST — a plan swap is a far cheaper cure than a
# quantization demotion, and it lands at the next train_step instead of
# after the sentinel's breach-confirmation window.

_TARGET: Any = None
_TARGET_MU = threading.Lock()


def register_tuner_target(holder: Any) -> None:
    """Register ``holder`` (anything with ``retune_comm(axis)``) as the
    process's re-tune target; None clears it."""
    import weakref

    global _TARGET
    with _TARGET_MU:
        _TARGET = weakref.ref(holder) if holder is not None else None


def tuner_target() -> Any:
    with _TARGET_MU:
        ref = _TARGET
    return ref() if ref is not None else None


def reroute_on_breach(axis: str) -> bool:
    """Ask the registered trainer to re-tune around ``axis``; True when
    a changed plan was actually staged (the breach is cured without a
    quantization demotion).  Never raises into the diagnosis loop."""
    target = tuner_target()
    if target is None:
        return False
    retune = getattr(target, "retune_comm", None)
    if retune is None:
        return False
    try:
        return bool(retune(axis))
    except Exception as e:  # noqa: BLE001 - diagnosis loop safety
        logger.warning("fabric re-tune on breach failed: %s", e)
        return False
