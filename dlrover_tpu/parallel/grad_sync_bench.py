"""Grad-sync policy micro-bench: step time, overlap efficiency, bytes.

Runs the same tiny-Llama data-parallel training loop under each
``grad_sync`` policy on a virtual multi-device CPU mesh — the r6
post-backward per-leaf sync AND the r14 overlapped bucketed sync — plus
a dp=1 run at the same per-device batch (the compute-only floor the
ROADMAP's success metric is measured against: "dp>=4 step time with
sync overlapped approaches the dp=1 step time").

Per overlapped mode the bench also times a sync-only program (the
bucket pack/quantize/exchange/unpack chains on the real gradient
shapes, nothing else), which prices the total communication chain; the
exposed share is what the full step pays over the dp=1 floor:

    exposed_ms           = max(0, step_ms - dp1_ms)
    overlap_efficiency   = 1 - exposed_ms / comm_ms   (clamped to [0,1])

Bytes-on-wire are per-BUCKET with quantization metadata (scales,
refinement indices) itemized — ``collectives.estimate_bucket_bytes`` —
fixing the r6 single-tensor estimate that under-counted blockwise
formats.  CPU step times bound the NUMERICS overhead (the XLA program
is the same shape the TPU runs); wire bytes are topology math, valid
for any backend.  Written to ``BENCH_grad_overlap.json`` and
``BENCH_comm.json`` at the checkout's root (git ignores both), where
``fabric_tuner.rdma_proven`` and ``seed_snapshot`` look for them.

Run standalone::

    python -m dlrover_tpu.parallel.grad_sync_bench
"""

import json
import os
import sys
import time
import uuid
from typing import Dict

# the r6 baselines (post-backward, one collective per leaf) and the r14
# overlapped bucketed modes measured against them
LEGACY_MODES = ("exact", "exact_sharded", "int8_sharded")
OVERLAP_MODES = (
    "exact_sharded", "int8_sharded", "int4_sharded", "blockwise_sharded"
)
# the headline pair for the gap-reduction acceptance: the r6 quantized
# flagship vs its overlapped successor
HEADLINE_MODE = "int8_sharded"


def _slice_sim_cores_short() -> str:
    """Core-count preflight for the SLICE_SIM-executing legs.

    The simulated DCN boundary prices cross-slice exchanges through a
    host-side callback that must drain on a SECOND core while the main
    thread blocks inside the collective — on a 1-core host the flat
    leg wedges forever (pre-existing deadlock, not a perf cliff).
    Returns the skip reason, or "" when the host has enough cores."""
    from dlrover_tpu.common import envs

    min_cores = envs.get_int("DLROVER_TPU_BENCH_MIN_CORES")
    cores = os.cpu_count() or 1
    if cores >= min_cores:
        return ""
    return (
        f"host has {cores} core(s) < DLROVER_TPU_BENCH_MIN_CORES="
        f"{min_cores}: the SLICE_SIM host-callback exchange would "
        "deadlock on this machine"
    )


def _timed_loop(trainer, batch_host, steps: int):
    import jax

    from dlrover_tpu.utils.timing import hard_block

    state = trainer.create_state(
        jax.random.PRNGKey(0), batch_host["input_ids"]
    )
    batch = trainer.shard_batch(batch_host)
    state, m = trainer.train_step(state, batch)  # compile
    hard_block(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = trainer.train_step(state, batch)
    hard_block(m["loss"])
    step_ms = (time.perf_counter() - t0) / steps * 1000
    final_loss = float(jax.device_get(m["loss"]))
    return state, round(step_ms, 2), round(final_loss, 5)


def _comm_only_ms(trainer, state, steps: int) -> float:
    """Time ONLY the sync chains (pack -> encode -> exchange -> decode
    -> unpack -> all-gather) on the real gradient shapes: the total
    communication-chain cost the overlapped step hides behind
    compute."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from dlrover_tpu.parallel import collectives
    from dlrover_tpu.utils.timing import hard_block

    policy = trainer.grad_sync
    layout = trainer._grad_layout  # noqa: SLF001 - bench introspection
    buckets = trainer._bucket_layout  # noqa: SLF001
    axis = trainer._sync_axis  # noqa: SLF001

    def body(grads):
        if buckets is not None:
            synced, _ = collectives.sync_gradient_tree_bucketed(
                grads, None, layout, buckets, policy, axis
            )
            return collectives.all_gather_tree_bucketed(
                synced, layout, buckets, axis
            )
        synced, _ = collectives.sync_gradient_tree(
            grads, None, layout, policy, axis
        )
        return collectives.all_gather_tree(synced, layout, axis)

    grads = jax.tree.map(
        lambda p: jnp.ones(p.shape, jnp.float32), state.params
    )
    fn = jax.jit(collectives.shard_map_unchecked(
        body, mesh=trainer.mesh,
        in_specs=PartitionSpec(), out_specs=PartitionSpec(),
    ))
    with trainer.mesh:
        out = fn(grads)
        hard_block(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(grads)
        hard_block(out)
    return round((time.perf_counter() - t0) / steps * 1000, 3)


def _comm_observatory(trainer, exposed_ms: float, steps: int) -> Dict:
    """Per-bucket / per-axis comm attribution for one overlapped
    trainer (the headline mode), the ``BENCH_comm.json`` payload:

    * each bucket's chain (pack -> encode -> exchange -> decode) timed
      standalone via ``commscope.BucketScope`` — transport tier, sync
      axis, wire bytes, achieved GB/s per bucket;
    * the measured EXPOSED step time split across buckets by their
      chain-cost share and booked into the comm scope's
      ``exposed_comm`` sub-account (the goodput breakdown by
      transport/axis);
    * probe-measured per-axis fabric latency/bandwidth
      (``commscope.MeshProbe`` on the real mesh — hardware numbers
      when this bench runs on the chip).
    """
    from dlrover_tpu.observability import commscope

    scope = commscope.scope()
    bucket_scope = commscope.BucketScope.for_trainer(trainer)
    rows = []
    if bucket_scope is not None:
        rows = bucket_scope.measure(reps=max(2, steps // 2))
    total_chain = sum(r["chain_ms"] for r in rows)
    for row in rows:
        share = (
            row["chain_ms"] / total_chain if total_chain > 0 else 0.0
        )
        row["exposed_ms"] = round(max(0.0, exposed_ms) * share, 3)
        scope.attribute_exposed(
            row["axis"], row["transport"], row["exposed_ms"] / 1e3
        )
    probe = commscope.MeshProbe.for_mesh(trainer.mesh)
    model = commscope.FabricModel()
    if probe is not None:
        for _ in range(3):
            probe.probe_once(model)
    return {
        "per_bucket": rows,
        "exposed_comm_ms": round(max(0.0, exposed_ms), 3),
        "exposed_breakdown": scope.exposed_breakdown(),
        "fabric": model.snapshot(),
        "sync": trainer.grad_sync_summary(),
    }


def _hierarchy_bench(model, batch_host, devices, steps: int) -> Dict:
    """Flat vs hierarchical on a two-slice mesh (r18): same model, same
    global batch, same base quantization — one trainer syncs over the
    flat combined ``(slice, dp)`` axis, the other runs the two-level
    ICI reduce-scatter -> aggregated int4 DCN exchange -> intra-slice
    all-gather.  Bytes-on-wire are itemized per FABRIC TIER (ICI vs
    DCN, quantization metadata included) from both the topology
    estimator and the executed toll meter; on CPU backends the
    simulated DCN boundary (``DLROVER_TPU_SLICE_SIM``) prices the
    cross-slice exchanges so wall times genuinely separate.  The
    returned dict is the flat-vs-hierarchical comparison the round
    file carries (hardware numbers need a run on a real multi-slice
    topology with the sim off)."""
    import jax
    import optax

    from dlrover_tpu.diagnosis.chaos_drill import _env
    from dlrover_tpu.parallel import hierarchy
    from dlrover_tpu.parallel.collectives import GradSyncPolicy
    from dlrover_tpu.parallel.mesh import (
        MeshConfig,
        build_slice_mesh,
        slice_topology,
    )
    from dlrover_tpu.trainer.train import Trainer

    n = len(devices)
    if n < 4 or n % 2:
        return {"skipped": f"{n} devices cannot form two slices"}
    mesh = build_slice_mesh(2, MeshConfig(dp=n // 2), devices=devices)
    topo = slice_topology(mesh)
    # the simulated boundary only makes sense where there is no real
    # one: CPU meshes price DCN via the host-side toll, hardware
    # multi-slice topologies measure the real fabric
    sim = {"DLROVER_TPU_SLICE_SIM": "1"} if (
        jax.default_backend() == "cpu"
    ) else {}
    if sim:
        reason = _slice_sim_cores_short()
        if reason:
            from dlrover_tpu.common.log import logger

            logger.warning("hierarchy bench skipped: %s", reason)
            return {"skipped": reason}

    def run(policy):
        hierarchy.reset_meter()
        trainer = Trainer(
            model, optax.adamw(1e-2), mesh, grad_sync=policy
        )
        state, step_ms, final_loss = _timed_loop(
            trainer, batch_host, steps
        )
        # steps + 1: the compile dispatch inside _timed_loop syncs too
        per_dev = hierarchy.meter().bytes_for("dcn") / (steps + 1) / n
        return trainer, {
            "step_ms": step_ms,
            "final_loss": final_loss,
            "sync": trainer.grad_sync_summary(),
            "measured_dcn_bytes_per_step": int(per_dev),
        }

    with _env(**sim):
        flat_tr, flat = run(GradSyncPolicy(
            mode="int8_sharded", bucket_mb=4.0, transport="all_to_all",
            hi_frac=0.125, hierarchical=False,
        ))
        hier_tr, hier = run(GradSyncPolicy(
            mode="int8_sharded", bucket_mb=4.0, transport="all_to_all",
            hi_frac=0.125, hierarchical=True, dcn_format="int4",
        ))
    for trainer, entry, is_hier in (
        (flat_tr, flat, False), (hier_tr, hier, True),
    ):
        buckets = trainer._bucket_layout  # noqa: SLF001 - bench
        if buckets is not None:
            entry["tiered_bytes"] = hierarchy.estimate_tiered_bytes(
                buckets, trainer.grad_sync, topo, hierarchical=is_hier
            )
    out = {
        "num_slices": topo.num_slices,
        "ici_dp": topo.ici_dp,
        "simulated_dcn": bool(sim),
        "flat": flat,
        "hierarchical": hier,
    }
    flat_dcn = flat.get("tiered_bytes", {}).get("dcn_bytes", 0)
    hier_dcn = hier.get("tiered_bytes", {}).get("dcn_bytes", 0)
    if hier_dcn > 0:
        out["dcn_reduction_x"] = round(flat_dcn / hier_dcn, 2)
    if hier["step_ms"] > 0:
        out["wall_speedup_x"] = round(
            flat["step_ms"] / hier["step_ms"], 3
        )
    return out


def _tuner_bench(model, batch_host, devices, steps: int) -> Dict:
    """The r21 fabric-auto-tuner leg: price every static transport
    tier against the tuner's per-bucket plan on synthetic measured
    fabrics (the CPU-assertable domain — the same pricing model the
    live trainer re-tunes with), then execute a short tuned training
    loop with the simulated DCN boundary to prove the staged plan
    swaps into a live jitted step.

    Acceptance numbers: ``tuned_us <= min(static)`` on the asymmetric
    fabric, and on a DCN-idle fabric the dual-fabric stripe strictly
    beating every single-fabric (stripe=0) static schedule."""
    import jax
    import optax

    from dlrover_tpu.diagnosis.chaos_drill import _env
    from dlrover_tpu.parallel import fabric_tuner
    from dlrover_tpu.parallel.collectives import GradSyncPolicy
    from dlrover_tpu.parallel.mesh import MeshConfig, build_slice_mesh
    from dlrover_tpu.trainer.train import Trainer

    n = len(devices)
    if n < 4 or n % 2:
        return {"skipped": f"{n} devices cannot form two slices"}
    mesh = build_slice_mesh(2, MeshConfig(dp=n // 2), devices=devices)
    policy = GradSyncPolicy(
        mode="int8_sharded", bucket_mb=4.0, transport="all_to_all",
        hi_frac=0.125, hierarchical=True, dcn_format="int4",
    )
    trainer = Trainer(model, optax.adamw(1e-2), mesh, grad_sync=policy)
    trainer.create_state(
        jax.random.PRNGKey(0), batch_host["input_ids"]
    )
    buckets = trainer._bucket_layout  # noqa: SLF001 - bench
    if buckets is None:
        return {"skipped": "no bucket layout"}
    tuner = fabric_tuner.FabricTuner(
        buckets, trainer.grad_sync, "dp", n // 2, "slice", 2,
        rdma_ok=False,
    )
    # synthetic measured fabrics (lat_us, GB/s): the asymmetric shape
    # the slow-link sentinel fires on, and a healthy DCN sitting idle
    # next to a comparable ICI — the FlexLink stripe's win condition
    asym = {
        "dp": {"lat_us": 1.0, "gbps": 200.0},
        "slice": {"lat_us": 150.0, "gbps": 1.0},
    }
    idle = {
        "dp": {"lat_us": 1.0, "gbps": 25.0},
        "slice": {"lat_us": 1.0, "gbps": 25.0},
    }

    def leg(snap):
        static = {
            transport: round(
                tuner.uniform_plan(transport, 0.0, snap).total_us, 3
            )
            for transport in ("all_to_all", "ring_pallas_q")
        }
        tuned = tuner.decide(snap)
        return {
            "static_us": static,
            "tuned_us": round(tuned.total_us, 3),
            "tuned_plan": tuned.summary(),
            "tuner_beats_all_static": bool(
                tuned.total_us <= min(static.values()) + 1e-6
            ),
        }

    out = {"asymmetric_fabric": leg(asym), "dcn_idle": leg(idle)}
    idle_tuned = tuner.decide(idle)
    single_fabric = tuner.uniform_plan("all_to_all", 0.0, idle).total_us
    out["dcn_idle"]["stripe_used"] = max(
        d.stripe for d in idle_tuned.decisions
    )
    if idle_tuned.total_us > 0:
        out["dcn_idle"]["stripe_gain_x"] = round(
            single_fabric / idle_tuned.total_us, 3
        )
    # executed: the tuned trainer under the simulated DCN boundary —
    # the probe fires on cadence, the plan stages, the live jitted
    # step swaps it in (wall numbers are informative on CPU; the
    # priced comparison above is the assertable acceptance)
    sim = {
        "DLROVER_TPU_SLICE_SIM": "1",
        "DLROVER_TPU_TUNER": "1",
        "DLROVER_TPU_TUNER_APPLY": "1",
        "DLROVER_TPU_TUNER_MIN_GAIN": "0.0",
        "DLROVER_TPU_COMM_PROBE_EVERY": "2",
    } if jax.default_backend() == "cpu" else {
        "DLROVER_TPU_TUNER": "1",
        "DLROVER_TPU_TUNER_APPLY": "1",
        "DLROVER_TPU_COMM_PROBE_EVERY": "2",
    }
    reason = _slice_sim_cores_short() if (
        sim.get("DLROVER_TPU_SLICE_SIM") == "1"
    ) else ""
    if reason:
        from dlrover_tpu.common.log import logger

        logger.warning("tuner executed leg skipped: %s", reason)
        out["executed"] = {"skipped": reason}
        return out
    with _env(**sim):
        tuned_tr = Trainer(
            model, optax.adamw(1e-2), mesh, grad_sync=policy
        )
        _, step_ms, final_loss = _timed_loop(
            tuned_tr, batch_host, steps
        )
    out["executed"] = {
        "step_ms": step_ms,
        "final_loss": final_loss,
        "sync": tuned_tr.grad_sync_summary(),
    }
    return out


def _ring_rdma_evidence(devices) -> Dict:
    """Drive the r14 ``ring_rdma`` Pallas kernel end-to-end and record
    the outcome — ``status: ok`` (lowered, executed, bit-identical to
    ``psum_scatter``) with timing, or the PRECISE degradation cause.
    ``fabric_tuner.rdma_proven`` reads this entry from
    ``BENCH_grad_overlap.json``: the tuner only makes the RDMA tier
    eligible after a real-hardware run proved it here."""
    import jax

    from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring

    world = len(devices)
    width = 256
    out: Dict = {
        "world": world, "width": width,
        "backend": jax.default_backend(),
    }
    if jax.default_backend() != "tpu":
        out.update(
            status="degraded",
            cause=(
                f"backend={jax.default_backend()}: the pltpu RDMA "
                "kernel (make_async_remote_copy + device semaphores) "
                "lowers only on TPU; interpret mode has no semaphore "
                "model"
            ),
        )
        return out
    if ring.pltpu is None:
        out.update(
            status="degraded",
            cause="jax.experimental.pallas.tpu import unavailable",
        )
        return out
    try:
        import time as _time

        import jax.numpy as jnp
        import numpy as np
        from jax import lax
        from jax.sharding import PartitionSpec

        from dlrover_tpu.parallel import collectives
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(dp=world), devices=devices)

        def body(buf):
            return ring.rdma_ring_reduce_scatter(buf, "dp", world)

        def ref(buf):
            return lax.psum_scatter(
                buf, "dp", scatter_dimension=0, tiled=True
            ).reshape(-1)

        x = jnp.arange(
            world * width, dtype=jnp.float32
        ).reshape(world, width)
        fn = jax.jit(collectives.shard_map_unchecked(
            body, mesh=mesh, in_specs=PartitionSpec(),
            out_specs=PartitionSpec("dp"),
        ))
        rf = jax.jit(collectives.shard_map_unchecked(
            ref, mesh=mesh, in_specs=PartitionSpec(),
            out_specs=PartitionSpec("dp"),
        ))
        with mesh:
            got = np.asarray(jax.block_until_ready(fn(x)))
            want = np.asarray(jax.block_until_ready(rf(x)))
            if not np.array_equal(got, want):
                out.update(
                    status="failed",
                    cause="executed but output differs from "
                          "psum_scatter (integer fp32 sums must be "
                          "bit-identical)",
                )
                return out
            t0 = _time.perf_counter()
            for _ in range(10):
                y = fn(x)
            jax.block_until_ready(y)
            out.update(
                status="ok",
                exchange_us=round(
                    (_time.perf_counter() - t0) / 10 * 1e6, 1
                ),
            )
    except Exception as e:  # noqa: BLE001 - evidence, not a gate
        out.update(
            status="failed",
            cause=f"{type(e).__name__}: {e}"[:300],
        )
    return out


def write_comm_file(comm: Dict, path: str = None):
    """Persist the comm round file (BENCH_comm.json; git ignores it) at
    the repo root: probe-measured axis bandwidths + per-bucket exposed
    ms."""
    _write_repo_file(comm, "BENCH_comm.json", path)


ALL_LEGS = ("modes", "comm", "hierarchy", "tuner", "rdma")


def _selected_legs() -> set:
    """``DLROVER_TPU_BENCH_LEGS``: 'all' or a comma subset of
    :data:`ALL_LEGS`.  A partial run refreshes only the named legs and
    keeps the prior round file's other sections — a chip run can
    re-prove one leg's evidence (say ``rdma`` after a driver fix)
    without paying the full matrix, and one wedged leg (host-callback
    + collective starvation on small CPU hosts) stops blocking fresh
    evidence for the rest."""
    from dlrover_tpu.common import envs

    raw = {
        s.strip() for s in
        envs.get_str("DLROVER_TPU_BENCH_LEGS").split(",") if s.strip()
    }
    if not raw or "all" in raw:
        return set(ALL_LEGS)
    return {leg for leg in raw if leg in ALL_LEGS}


def _prior_round_file() -> Dict:
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "BENCH_grad_overlap.json",
    )
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def run_grad_sync_bench(n_devices: int = 4, steps: int = 8) -> Dict:
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.parallel import collectives
    from dlrover_tpu.parallel.collectives import GradSyncPolicy
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.train import Trainer

    legs = _selected_legs()
    prior = _prior_round_file() if legs != set(ALL_LEGS) else {}

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, 65))
    batch_host = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    init_rng = jax.random.PRNGKey(0)
    devices = jax.devices()[:n_devices]

    def trainer_for(policy, dp):
        mesh = build_mesh(MeshConfig(dp=dp), devices=devices[:dp])
        return Trainer(model, optax.adamw(1e-2), mesh, grad_sync=policy)

    # dp=1 floor: the same per-device batch with no dp sync at all
    per_dev = {
        k: v[: v.shape[0] // n_devices] for k, v in batch_host.items()
    }
    dp1_ms = prior.get("dp1_ms", 0.0)

    modes: Dict[str, Dict] = {}
    abstract_params = None
    headline_trainer = [None]  # the overlapped headline trainer, kept
    # for the comm-observatory attribution pass

    def measure(tag, policy, overlapped):
        nonlocal abstract_params
        trainer = trainer_for(policy, n_devices)
        if tag == f"{HEADLINE_MODE}+overlap":
            headline_trainer[0] = trainer
        state, step_ms, final_loss = _timed_loop(
            trainer, batch_host, steps
        )
        if abstract_params is None:
            abstract_params = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.params,
            )
        entry = {
            "step_ms": step_ms,
            "final_loss": final_loss,
            "gap_vs_dp1_ms": round(step_ms - dp1_ms, 2),
            "sync": trainer.grad_sync_summary(),
        }
        pol = trainer.grad_sync
        if pol.active:
            wire = collectives.estimate_sync_bytes(
                abstract_params, n_devices, pol
            )
            entry["wire_bytes_per_step"] = (
                wire["quantized_bytes"] if pol.quantized
                else wire["exact_allreduce_bytes"]
            )
            entry["wire_metadata_bytes"] = wire["metadata_bytes"]
        else:
            wire = collectives.estimate_sync_bytes(
                abstract_params, n_devices, GradSyncPolicy(mode="exact")
            )
            entry["wire_bytes_per_step"] = wire["exact_allreduce_bytes"]
        if overlapped and trainer._bucket_layout is not None:  # noqa: SLF001
            entry["per_bucket_bytes"] = collectives.estimate_bucket_bytes(
                trainer._bucket_layout, pol, n_devices  # noqa: SLF001
            )
            comm_ms = _comm_only_ms(trainer, state, steps)
            exposed = max(0.0, step_ms - dp1_ms)
            entry["overlap"] = {
                "comm_chain_ms": comm_ms,
                "exposed_comm_ms": round(exposed, 2),
                "efficiency": round(
                    max(0.0, min(1.0, 1.0 - exposed / comm_ms)), 3
                ) if comm_ms > 0 else 0.0,
            }
        modes[tag] = entry

    headline: Dict = dict(prior.get("overlap_headline", {}))
    if "modes" in legs:
        _, dp1_ms, _ = _timed_loop(
            trainer_for("exact", 1), per_dev, steps
        )
        for mode in LEGACY_MODES:
            measure(mode, GradSyncPolicy(mode=mode, bucket_mb=0.0),
                    False)
        for mode in OVERLAP_MODES:
            # every env-resolvable field pinned: exported
            # DLROVER_TPU_GRAD_{BUCKET_MB,TRANSPORT,HI_FRAC} overrides
            # must not silently contaminate the comparison rows
            # ("all_to_all" = the stock exchange: psum_scatter for
            # exact buckets)
            measure(
                f"{mode}+overlap",
                GradSyncPolicy(mode=mode, bucket_mb=4.0,
                               transport="all_to_all", hi_frac=0.125),
                True,
            )

        # the acceptance headline: how much of the r6 post-backward
        # gap the overlapped path closes toward the dp=1 floor
        legacy_gap = modes[HEADLINE_MODE]["gap_vs_dp1_ms"]
        over_gap = modes[f"{HEADLINE_MODE}+overlap"]["gap_vs_dp1_ms"]
        headline = {
            "mode": HEADLINE_MODE,
            "dp1_ms": dp1_ms,
            "legacy_step_ms": modes[HEADLINE_MODE]["step_ms"],
            "overlapped_step_ms": modes[
                f"{HEADLINE_MODE}+overlap"]["step_ms"],
            "legacy_gap_ms": legacy_gap,
            "overlapped_gap_ms": over_gap,
        }
        if legacy_gap > 0:
            # clamped: noise can land the overlapped step BELOW the
            # dp=1 floor (negative gap); >1.0 is not a meaningful
            # fraction and the raw gap_ms fields above keep the
            # unclamped signal
            headline["gap_reduction"] = round(
                min(1.0, 1.0 - over_gap / legacy_gap), 3
            )
    else:
        modes = prior.get("modes", {})

    # comm observatory: per-bucket attribution of the headline mode's
    # exposed comm + probe-measured axis fabric numbers (needs the
    # executed headline trainer, so a partial run without the modes
    # matrix carries the prior comm section forward)
    comm = prior.get("comm", {})
    if "comm" in legs and headline_trainer[0] is not None:
        try:
            comm = _comm_observatory(
                headline_trainer[0],
                max(0.0, headline["overlapped_gap_ms"]),
                steps,
            )
            comm["mode"] = f"{HEADLINE_MODE}+overlap"
        except Exception as e:  # noqa: BLE001 - attribution must not
            # kill the bench's contractual JSON line
            comm = {"error": f"{type(e).__name__}: {e}"}

    # r18: the two-slice flat-vs-hierarchical comparison with per-tier
    # (ICI vs DCN) bytes itemized — the multi-slice acceptance numbers
    hier = prior.get("hierarchy", {})
    if "hierarchy" in legs:
        try:
            hier = _hierarchy_bench(model, batch_host, devices, steps)
        except Exception as e:  # noqa: BLE001 - the comparison must
            # not kill the bench's contractual JSON line
            hier = {"error": f"{type(e).__name__}: {e}"}

    # r21: the fabric auto-tuner leg (priced static tiers vs the
    # per-bucket tuned plan) and the ring_rdma proof-of-execution
    # record the tuner's RDMA eligibility gate reads back
    tuner_leg = prior.get("tuner", {})
    if "tuner" in legs:
        try:
            tuner_leg = _tuner_bench(model, batch_host, devices, steps)
        except Exception as e:  # noqa: BLE001 - the leg must not kill
            # the bench's contractual JSON line
            tuner_leg = {"error": f"{type(e).__name__}: {e}"}
    rdma = prior.get("ring_rdma", {})
    if "rdma" in legs:
        try:
            rdma = _ring_rdma_evidence(devices)
        except Exception as e:  # noqa: BLE001
            rdma = {"status": "failed",
                    "cause": f"{type(e).__name__}: {e}"[:300]}

    policy = GradSyncPolicy(mode="int8_sharded")
    if abstract_params is None:
        abstract_params = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            batch_host["input_ids"],
        )["params"]
    wire = collectives.estimate_sync_bytes(
        abstract_params, n_devices, policy
    )
    return {
        "world": n_devices,
        "backend": jax.default_backend(),
        "dp1_ms": dp1_ms,
        "modes": modes,
        "overlap_headline": headline,
        "comm": comm,
        "hierarchy": hier,
        "tuner": tuner_leg,
        "ring_rdma": rdma,
        "wire_estimate": wire,
        "note": (
            "CPU-mesh numerics drill: step times bound quantization "
            "overhead and measure the overlap/fusion win (the XLA "
            "program is the shape the TPU runs); wire bytes are "
            "topology estimates incl. per-bucket quantization metadata"
        ),
    }


def _write_repo_file(payload: Dict, filename: str, path: str = None):
    """Write a standalone round artifact at the repo root (one shared
    path derivation for every file this bench persists)."""
    if path is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            filename,
        )
    try:
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
    except OSError as e:
        print(f"grad_sync_bench: {filename} write failed: {e}",
              file=sys.stderr, flush=True)


def write_round_file(result: Dict, path: str = None):
    """Persist the round file (BENCH_grad_overlap.json; git ignores
    it) at the repo root."""
    _write_repo_file(result, "BENCH_grad_overlap.json", path)


def main() -> int:
    """Entry point: force a virtual multi-device CPU backend, write the
    two round files and print one JSON line."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    os.environ.setdefault(
        "DLROVER_TPU_JOB_NAME", f"gs{uuid.uuid4().hex[:6]}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    result = run_grad_sync_bench(4)
    write_round_file(result)
    if result.get("comm"):
        write_comm_file({
            "world": result["world"],
            "backend": result["backend"],
            **result["comm"],
        })
    print("GRAD_SYNC_BENCH " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
