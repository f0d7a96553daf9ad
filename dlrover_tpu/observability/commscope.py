"""Comm observatory: per-bucket exchange telemetry + active mesh probes.

The r15 goodput ledger answers "how much wall clock was communication"
(``exposed_comm``), but not *where*: the r14 transport tiers
(psum_scatter / ppermute ring / Pallas / RDMA) ship zero per-bucket or
per-mesh-axis attribution, so "which collective, on which link, is
slow" — the question the reference xpu_timer exists to answer — had no
answer here.  This module is that measurement layer, three pieces:

:class:`FabricModel`
    The per-axis price list: for every active mesh axis, an EWMA
    latency (µs per hop) and achieved bandwidth (GB/s), built from
    probe samples.  ``digest()`` flattens it into ``fxl_<axis>`` /
    ``fxb_<axis>`` floats that ride the existing rank-digest-file ->
    agent-heartbeat channel to the master, where
    ``master/timeseries.py`` turns them into ``node<N>.comm.<axis>.*``
    and worst-case ``job.comm.<axis>.*`` series — the input of the
    ``SlowLinkDiagnostician`` sentinel (``observability/sentinel.py``).

:class:`MeshProbe`
    The active prober: every ``DLROVER_TPU_COMM_PROBE_EVERY`` steps the
    trainer runs one tiny timed collective pair per mesh axis — a
    small ``ppermute`` ring hop (latency) and a ~1MB ``psum``
    (bandwidth), each a jitted shard_map program compiled once per
    axis.  Probes are SAMPLED and collective: every process fires them
    at the same digest-step count, so the fleet dispatches them in
    lockstep like any other collective.  The chaos point
    ``comm.axis_delay.<axis>`` fires INSIDE the timed latency window —
    a seeded DELAY fault is an injected link latency on exactly one
    axis, the simulated DCN slice boundary the ROADMAP's multi-slice
    item needs priced before hardware exists.  For device-free tests
    and drills a ``runner`` callable replaces the jitted collectives;
    the timing, chaos, span, and model plumbing stay identical.

:class:`BucketScope`
    Per-bucket attribution for the r14 overlapped sync: one sync-only
    jitted program per bucket (the same
    ``collectives.bucket_reduce_scatter`` chain the train step fuses —
    pack -> encode -> exchange -> decode), timed on the probe cadence.
    A fused train step cannot be timed per-bucket from the host (XLA
    owns the schedule — the same reason ``timer/device_events.py``
    samples the profiler), so this is the sampled measurement of each
    bucket's chain cost: every measurement emits a ``comm.bucket<i>``
    span carrying the resolved transport tier, the sync mesh axis, the
    wire bytes (``collectives.estimate_bucket_bytes``), and the
    achieved GB/s — the flight recorder and the merged Perfetto
    timeline get comm lanes, and ``grad_sync_bench`` gets its
    per-bucket rows.

:class:`CommScope` (process singleton, :func:`scope`)
    Ties it together and keeps the ``exposed_comm`` SUB-account: when a
    bench/drill measures exposed (non-overlapped) sync time, it calls
    :meth:`CommScope.attribute_exposed` with the transport tier and
    axis — the seconds are charged to the r15 goodput ledger's
    ``exposed_comm`` phase as before AND booked per ``(transport,
    axis)``, so the ledger's one undifferentiated phase gains the
    breakdown the ROADMAP's hierarchical-collective claims will be
    judged against.

Everything here is guarded: a broken probe can never break a training
step, and every knob lives in the env registry
(``DLROVER_TPU_COMM_*``).
"""

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger

#: digest-key prefixes (flat floats riding ``comm.HeartBeat.digest``):
#: ``fxl_<axis>`` = EWMA probe latency (µs/hop), ``fxb_<axis>`` = EWMA
#: achieved bandwidth (GB/s).  The agent merges rank files WORST-case
#: (max latency, min bandwidth) — a node is as healthy as its slowest
#: link.
DIGEST_LAT = "fxl_"
DIGEST_BW = "fxb_"

#: chaos injection point prefix: ``comm.axis_delay.<axis>`` fires
#: inside the probe's timed latency window (and each bucket
#: measurement window on the sync axis), so a seeded DELAY fault IS an
#: injected per-axis link latency.
AXIS_DELAY_POINT = "comm.axis_delay."


def _fire_axis_delay(axis: str) -> None:
    from dlrover_tpu import chaos

    chaos.point(AXIS_DELAY_POINT + axis, axis=axis)


class FabricModel:
    """Per-mesh-axis latency/bandwidth estimates from probe samples.

    EWMA-smoothed (``DLROVER_TPU_COMM_EWMA_ALPHA``) so one noisy probe
    does not flap the digest, while a sustained injected delay moves
    the estimate within a couple of samples.  Thread-safe."""

    def __init__(self, alpha: Optional[float] = None):
        self._alpha = float(
            alpha if alpha is not None
            else envs.get_float("DLROVER_TPU_COMM_EWMA_ALPHA")
        )
        if not (0.0 < self._alpha <= 1.0):
            self._alpha = 0.5
        self._mu = threading.Lock()
        # axis -> {world, lat_us, gbps, samples, ts}
        self._axes: Dict[str, Dict[str, float]] = {}

    def update(self, axis: str, world: int, lat_s: float,
               gbps: float) -> None:
        now = time.time()
        with self._mu:
            entry = self._axes.get(axis)
            lat_us = max(0.0, float(lat_s)) * 1e6
            gbps = max(0.0, float(gbps))
            if entry is None:
                entry = self._axes[axis] = {
                    "world": int(world), "lat_us": lat_us, "gbps": gbps,
                    "samples": 0,
                }
            else:
                a = self._alpha
                entry["lat_us"] += a * (lat_us - entry["lat_us"])
                entry["gbps"] += a * (gbps - entry["gbps"])
                entry["world"] = int(world)
            entry["samples"] += 1
            entry["ts"] = round(now, 6)

    def axes(self) -> List[str]:
        with self._mu:
            return sorted(self._axes)

    def get(self, axis: str) -> Optional[Dict[str, float]]:
        with self._mu:
            entry = self._axes.get(axis)
            return dict(entry) if entry else None

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        from dlrover_tpu.parallel.mesh import axis_fabric

        with self._mu:
            return {
                axis: {
                    "world": entry["world"],
                    "lat_us": round(entry["lat_us"], 3),
                    "gbps": round(entry["gbps"], 6),
                    "samples": int(entry["samples"]),
                    "ts": entry.get("ts", 0.0),
                    # fabric tier (r18): which interconnect this axis
                    # rides — the slice axis is the DCN boundary
                    "tier": axis_fabric(axis),
                }
                for axis, entry in self._axes.items()
            }

    def digest(self) -> Dict[str, float]:
        """Flat floats for the heartbeat-digest channel."""
        out: Dict[str, float] = {}
        with self._mu:
            for axis, entry in self._axes.items():
                out[DIGEST_LAT + axis] = round(entry["lat_us"], 3)
                out[DIGEST_BW + axis] = round(entry["gbps"], 6)
        return out


# ---------------------------------------------------------------------------
# Active mesh probe.
# ---------------------------------------------------------------------------


class MeshProbe:
    """Timed micro-collectives per mesh axis.

    ``axes`` maps axis name -> world size (only sizes > 1 are probed).
    With a ``mesh``, the default runner builds one jitted shard_map
    program per (axis, kind): a ``lat_bytes`` int32 ``ppermute`` ring
    hop for latency and a ``bw_bytes`` fp32 ``psum`` for bandwidth.
    With an injected ``runner(axis, kind)`` (tests, the chaos drill's
    synthetic fabric) no devices are touched — timing, chaos injection,
    spans and model updates are identical either way.
    """

    def __init__(self, axes: Dict[str, int], mesh=None,
                 runner: Optional[Callable[[str, str], Any]] = None,
                 lat_bytes: Optional[int] = None,
                 bw_bytes: Optional[int] = None,
                 reps: Optional[int] = None):
        self.axes = {
            a: int(w) for a, w in (axes or {}).items() if int(w) > 1
        }
        self._mesh = mesh
        self._runner = runner
        self._lat_bytes = int(
            lat_bytes if lat_bytes is not None
            else envs.get_int("DLROVER_TPU_COMM_PROBE_LAT_BYTES")
        )
        self._bw_bytes = int(
            bw_bytes if bw_bytes is not None
            else envs.get_int("DLROVER_TPU_COMM_PROBE_BW_BYTES")
        )
        self.reps = max(
            1,
            int(reps if reps is not None
                else envs.get_int("DLROVER_TPU_COMM_PROBE_REPS")),
        )
        # (axis, kind) -> (jitted fn, input array)
        self._fns: Dict[Any, Any] = {}
        self.probes_done = 0
        # warm the chaos engine's one-time env probe NOW: the first
        # injection-point call pays it, and it must not land inside the
        # first probe's timed latency window (a ~1ms phantom "link")
        from dlrover_tpu import chaos

        chaos.point("comm.probe.init")

    @classmethod
    def for_mesh(cls, mesh, **kwargs) -> Optional["MeshProbe"]:
        """A probe over ``mesh``'s active (size > 1) axes, or None when
        every axis is trivial (nothing to probe)."""
        if mesh is None:
            return None
        axes = {
            str(a): int(s) for a, s in mesh.shape.items() if int(s) > 1
        }
        if not axes:
            return None
        return cls(axes, mesh=mesh, **kwargs)

    # -- the real (jitted-collective) runner --------------------------------

    def _built_fn(self, axis: str, kind: str):
        key = (axis, kind)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec

        from dlrover_tpu.parallel.collectives import shard_map_unchecked

        world = self.axes[axis]
        if kind == "lat":
            elems = max(2, self._lat_bytes // 4)
            x = jnp.zeros((elems,), jnp.int32)
            perm = [(i, (i + 1) % world) for i in range(world)]

            def body(v):
                # one ring hop: the smallest message the axis can carry
                return lax.ppermute(v, axis, perm)
        else:
            elems = max(256, self._bw_bytes // 4)
            # the accounting must price the ACTUAL payload: the floor
            # and the //4 rounding can diverge from the configured knob
            self._bw_bytes = 4 * elems
            x = jnp.ones((elems,), jnp.float32)

            def body(v):
                # all-reduce: ring accounting moves 2(w-1)/w of the
                # payload off-replica per device
                return lax.psum(v, axis)

        jitted = jax.jit(shard_map_unchecked(
            body, mesh=self._mesh,
            in_specs=PartitionSpec(), out_specs=PartitionSpec(),
        ))
        fn = (jitted, x)
        self._fns[key] = fn
        return fn

    def _run(self, axis: str, kind: str) -> None:
        """Execute one probe op (compiled path or injected runner)."""
        if self._runner is not None:
            self._runner(axis, kind)
            return
        jitted, x = self._built_fn(axis, kind)
        with self._mesh:
            out = jitted(x)
        import jax

        jax.block_until_ready(out)

    # -- probing -------------------------------------------------------------

    def _probe_axis(self, axis: str, model: FabricModel) -> Dict[str, float]:
        import time as _time

        from dlrover_tpu.observability import metrics as obs_metrics
        from dlrover_tpu.observability import trace

        world = self.axes[axis]
        with trace.span(f"comm.probe.{axis}",
                        attrs={"axis": axis, "world": world}) as sp:
            # warm-up outside the window: the first dispatch compiles
            self._run(axis, "lat")
            t0 = _time.perf_counter()
            # the injected per-axis link latency lands INSIDE the timed
            # window — chaos prices the axis exactly like a slow link
            _fire_axis_delay(axis)
            for _ in range(self.reps):
                self._run(axis, "lat")
            lat_s = (_time.perf_counter() - t0) / self.reps
            self._run(axis, "bw")  # warm-up/compile
            t0 = _time.perf_counter()
            for _ in range(self.reps):
                self._run(axis, "bw")
            bw_elapsed = (_time.perf_counter() - t0) / self.reps
            # ring all-reduce accounting: bytes leaving each replica
            off = 2.0 * (world - 1) / world
            moved = off * float(self._bw_bytes)
            gbps = (moved / bw_elapsed / 1e9) if bw_elapsed > 0 else 0.0
            sp.set_attr("lat_us", round(lat_s * 1e6, 3))
            sp.set_attr("gbps", round(gbps, 6))
        model.update(axis, world, lat_s, gbps)
        reg = obs_metrics.registry()
        reg.counter_inc(
            "dlrover_tpu_comm_probes_total",
            help=obs_metrics._help("dlrover_tpu_comm_probes_total"),
            axis=axis,
        )
        reg.gauge_set(
            "dlrover_tpu_comm_probe_latency_us", round(lat_s * 1e6, 3),
            help=obs_metrics._help("dlrover_tpu_comm_probe_latency_us"),
            axis=axis,
        )
        reg.gauge_set(
            "dlrover_tpu_comm_probe_bandwidth_gbps", round(gbps, 6),
            help=obs_metrics._help("dlrover_tpu_comm_probe_bandwidth_gbps"),
            axis=axis,
        )
        return {"lat_s": lat_s, "gbps": gbps}

    def probe_once(self, model: Optional[FabricModel] = None
                   ) -> Dict[str, Dict[str, float]]:
        """One probe round over every active axis; feeds ``model``
        (default: the process scope's fabric model).  Returns the raw
        per-axis samples."""
        if model is None:
            model = scope().fabric
        out: Dict[str, Dict[str, float]] = {}
        for axis in sorted(self.axes):
            out[axis] = self._probe_axis(axis, model)
        self.probes_done += 1
        return out


# ---------------------------------------------------------------------------
# Per-bucket chain measurement (the r14 overlapped sync, attributed).
# ---------------------------------------------------------------------------


class BucketScope:
    """Sampled per-bucket timing of the bucketed grad-sync chains.

    One sync-only jitted program per bucket — the same
    ``bucket_reduce_scatter`` chain (EF-free: pack -> encode ->
    exchange -> decode) the fused train step runs, isolated so the
    host can time it.  Measurements emit ``comm.bucket<i>`` spans with
    the resolved transport tier, sync axis, wire bytes and achieved
    GB/s, and land in the per-(transport, axis) histogram.
    """

    def __init__(self, mesh, buckets, policy, axis: str, world: int):
        self._mesh = mesh
        self._buckets = buckets
        self._policy = policy
        self._axis = axis
        self._world = int(world)
        self._fns: Dict[int, Any] = {}
        from dlrover_tpu.parallel import collectives

        self._bytes = {
            row["bucket"]: row
            for row in collectives.estimate_bucket_bytes(
                buckets, policy, self._world
            )
        }

    @classmethod
    def for_trainer(cls, trainer) -> Optional["BucketScope"]:
        """From a configured ``Trainer`` running the bucketed sync, or
        None when the sync path is per-leaf/exact."""
        buckets = getattr(trainer, "_bucket_layout", None)
        axis = getattr(trainer, "_sync_axis", None)
        if buckets is None or axis is None:
            return None
        return cls(
            trainer.mesh, buckets, trainer.grad_sync, axis,
            trainer._sync_world,  # noqa: SLF001 - observability introspection
        )

    def transport_of(self, bucket) -> str:
        from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

        return ring.resolve_transport(
            self._policy, self._world, bucket.width, self._axis
        )

    def _chain_fn(self, bucket):
        fn = self._fns.get(bucket.index)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from dlrover_tpu.parallel import collectives

        policy = self._policy
        axis = self._axis
        world = self._world
        width = bucket.width

        def chain(buf):
            key = None
            if policy.quantized and policy.rounding == "stochastic":
                key = jax.random.PRNGKey(policy.seed + bucket.index)
            shard, _ = collectives.bucket_reduce_scatter(
                buf, policy, axis, world, key
            )
            return jnp.sum(shard)

        jitted = jax.jit(collectives.shard_map_unchecked(
            chain, mesh=self._mesh,
            in_specs=PartitionSpec(), out_specs=PartitionSpec(),
        ))
        x = jnp.ones((world, width), jnp.float32)
        fn = (jitted, x)
        self._fns[bucket.index] = fn
        return fn

    def measure(self, reps: int = 3) -> List[Dict[str, Any]]:
        """Time every bucket's chain; returns per-bucket rows (the
        shape ``grad_sync_bench`` reports)."""
        import time as _time

        import jax

        from dlrover_tpu.observability import metrics as obs_metrics
        from dlrover_tpu.observability import trace

        reps = max(1, int(reps))
        rows: List[Dict[str, Any]] = []
        for bucket in self._buckets.buckets:
            transport = self.transport_of(bucket)
            wire = self._bytes.get(bucket.index, {})
            wire_bytes = int(
                wire.get("rs_payload_bytes", 0)
                + wire.get("rs_metadata_bytes", 0)
            )
            jitted, x = self._chain_fn(bucket)
            with self._mesh:
                out = jitted(x)  # compile outside the window
                jax.block_until_ready(out)
                with trace.span(
                    f"comm.bucket{bucket.index}",
                    attrs={
                        "axis": self._axis, "transport": transport,
                        "wire_bytes": wire_bytes,
                        "leaves": len(bucket.slices),
                        "width": bucket.width,
                    },
                ) as sp:
                    t0 = _time.perf_counter()
                    # the injected axis latency prices every exchange
                    # riding this axis, not just the probe
                    _fire_axis_delay(self._axis)
                    for _ in range(reps):
                        out = jitted(x)
                    jax.block_until_ready(out)
                    chain_s = (_time.perf_counter() - t0) / reps
                    gbps = (
                        wire_bytes / chain_s / 1e9 if chain_s > 0 else 0.0
                    )
                    sp.set_attr("chain_ms", round(chain_s * 1e3, 3))
                    sp.set_attr("gbps", round(gbps, 6))
            obs_metrics.registry().observe(
                "dlrover_tpu_comm_bucket_exchange_seconds", chain_s,
                help=obs_metrics._help(
                    "dlrover_tpu_comm_bucket_exchange_seconds"
                ),
                transport=transport, axis=self._axis,
            )
            from dlrover_tpu.parallel.mesh import axis_fabric

            rows.append({
                "bucket": bucket.index,
                "axis": self._axis,
                "tier": axis_fabric(self._axis),
                "transport": transport,
                "leaves": len(bucket.slices),
                "width": bucket.width,
                "wire_bytes": wire_bytes,
                "chain_ms": round(chain_s * 1e3, 3),
                "gbps": round(gbps, 6),
            })
        return rows


# ---------------------------------------------------------------------------
# The process scope: fabric model + exposed-comm sub-account.
# ---------------------------------------------------------------------------


class CommScope:
    """Per-process comm telemetry owner (see :func:`scope`)."""

    def __init__(self):
        self.fabric = FabricModel()
        self._mu = threading.Lock()
        # (transport, axis) -> cumulative exposed seconds
        self._exposed: Dict[Any, float] = {}

    def attribute_exposed(self, axis: str, transport: str, dur_s: float,
                          end_ts: Optional[float] = None) -> None:
        """Book measured exposed (non-overlapped) sync time: charges
        the goodput ledger's ``exposed_comm`` phase as before AND keeps
        the per-(transport, axis) breakdown the ledger's one phase
        lacked.  Callers are the benches/drills that MEASURE exposure
        (the ledger's exposed_comm contract, ``goodput.py``)."""
        dur_s = float(dur_s)
        if dur_s <= 0:
            return
        with self._mu:
            key = (str(transport), str(axis))
            self._exposed[key] = self._exposed.get(key, 0.0) + dur_s
        try:
            from dlrover_tpu.observability import goodput

            goodput.charge("exposed_comm", dur_s, end_ts)
        except Exception:  # noqa: BLE001 - the ledger must not break
            pass  # the measuring caller
        try:
            from dlrover_tpu.observability import metrics as obs_metrics

            obs_metrics.registry().counter_inc(
                "dlrover_tpu_comm_exposed_seconds_total", dur_s,
                help=obs_metrics._help(
                    "dlrover_tpu_comm_exposed_seconds_total"
                ),
                transport=str(transport), axis=str(axis),
            )
        except Exception:  # noqa: BLE001 - instrumentation only
            pass

    def exposed_breakdown(self) -> Dict[str, Any]:
        """The ``exposed_comm`` sub-account: seconds and share per
        ``<transport>/<axis>``."""
        with self._mu:
            items = {
                f"{transport}/{axis}": seconds
                for (transport, axis), seconds in self._exposed.items()
            }
        total = sum(items.values())
        return {
            "total_s": round(total, 6),
            "by": {k: round(v, 6) for k, v in sorted(items.items())},
            "share": {
                k: round(v / total, 4) for k, v in sorted(items.items())
            } if total > 0 else {},
        }

    def summary(self) -> Dict[str, Any]:
        return {
            "fabric": self.fabric.snapshot(),
            "exposed_comm": self.exposed_breakdown(),
        }

    def digest(self) -> Dict[str, float]:
        return self.fabric.digest()


_SCOPE: Optional[CommScope] = None
_SCOPE_MU = threading.Lock()


def scope() -> CommScope:
    global _SCOPE
    if _SCOPE is None:
        with _SCOPE_MU:
            if _SCOPE is None:
                _SCOPE = CommScope()
    return _SCOPE


def reset_scope() -> CommScope:
    """Replace the singleton (tests, per-scenario drill isolation)."""
    global _SCOPE
    with _SCOPE_MU:
        _SCOPE = CommScope()
        return _SCOPE


def probe_every() -> int:
    """Steps between active probes (0 = probing off)."""
    return envs.get_int("DLROVER_TPU_COMM_PROBE_EVERY")


def digest_axes(digest: Dict[str, float]) -> List[str]:
    """Axes present in a heartbeat digest's fabric keys."""
    return sorted({
        key[len(DIGEST_LAT):]
        for key in digest
        if key.startswith(DIGEST_LAT)
    } | {
        key[len(DIGEST_BW):]
        for key in digest
        if key.startswith(DIGEST_BW)
    })
