"""Sharded training harness: state creation, train step, grad accumulation.

The mesh-native equivalent of the reference's ``ElasticTrainer`` wrapper
(``dlrover/trainer/torch/elastic/trainer.py``): builds a TrainState whose
params/optimizer state are laid out by the logical-axis rules, jit-compiles
a donated train step with explicit in/out shardings, and adjusts gradient
accumulation to world-size changes (the reference adjusts accumulation when
workers join/leave; here the global batch is preserved across mesh shapes
the same way).
"""

from typing import Any, Callable, Dict, Optional, Tuple, Union

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import collectives
from dlrover_tpu.parallel.collectives import GradSyncPolicy
from dlrover_tpu.parallel.sharding import DATA_AXES, DEFAULT_LOGICAL_RULES
from dlrover_tpu.training_event.emitter import (
    TrainerEvents,
    get_default_emitter,
)


class TrainState(flax.struct.PyTreeNode):
    """``ef_residual`` (new in r6) is the error-feedback state of the
    int8-quantized gradient sync: a dict of per-param ``(dp, *leaf)``
    stacks, dp-sharded, holding each replica's un-injected quantization
    error.  None unless the trainer runs a quantized ``grad_sync``
    policy (docs/migration.md).

    ``buffers`` is what the model keeps in its ``buffers`` collection:
    arrays that are state and no parameters (a router's selection bias,
    ``models/moe.py``).  The step hands them to the model, mutable, and
    stores what it hands back; no gradient is taken with respect to them
    and the optimizer never sees them (no moment, no weight decay, not in
    the gradient norm).  Saved and restored with the rest of the state.
    None for a model that declares none (docs/migration.md)."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    ef_residual: Any = None
    buffers: Any = None


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token cross entropy in fp32; labels [B,S], logits [B,S,V].

    Spelled ``logsumexp - gold_logit`` rather than materializing
    ``log_softmax``: same math, but the only [B,S,V]-sized fp32 value is
    the logits themselves — at a 32k vocab the full log-probability tensor
    is gigabytes of HBM traffic that the reduction never needed."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    token_loss = lse - gold
    if mask is not None:
        token_loss = token_loss * mask
        return token_loss.sum() / jnp.maximum(mask.sum(), 1)
    return token_loss.mean()


class Trainer:
    """Holds (model, optimizer, mesh, rules) and exposes sharded init/step.

    Usage::

        trainer = Trainer(model, optax.adamw(3e-4), mesh)
        state = trainer.create_state(rng, sample_batch["input_ids"])
        state, metrics = trainer.train_step(state, batch)
    """

    def __init__(
        self,
        model: nn.Module,
        optimizer: optax.GradientTransformation,
        mesh,
        rules=None,
        loss_fn: Optional[Callable] = None,
        grad_accum_steps: int = 1,
        data_axes: Tuple[str, ...] = DATA_AXES,
        timer=None,
        grads_dtype=None,
        accum_dtype=None,
        grad_sync: Union[str, GradSyncPolicy, None] = "exact",
    ):
        """``grads_dtype=jnp.bfloat16`` differentiates w.r.t. a bf16 view
        of the (fp32 master) params, so the gradient pytree and its XLA
        temps are half-size — the standard mixed-precision recipe, and
        the memory lever that fits ~1B-param training on one 16GB chip.
        The optimizer still updates fp32 masters (moment math casts up).

        ``accum_dtype`` is the microbatch gradient ACCUMULATOR dtype and
        defaults to fp32 independently of ``grads_dtype``: repeated bf16
        summation (8-bit mantissa) swallows small late-microbatch
        contributions once the running sum grows, degrading gradients as
        ``grad_accum_steps`` rises.  Pass ``accum_dtype=jnp.bfloat16``
        only when the full-size fp32 accumulator pytree genuinely does
        not fit, accepting that accuracy cost.

        ``grad_sync`` selects the data-parallel gradient sync policy
        (``parallel.collectives.GradSyncPolicy``): ``"exact"`` keeps the
        GSPMD full-precision all-reduce + replicated update; the other
        modes decompose the sync with shard_map over the dp axis —
        ``"exact_sharded"`` (ZeRO-1 sharded weight update),
        ``"int8"``/``"int8_sharded"`` (blockwise-quantized reduce-scatter
        with a persistent error-feedback residual in the TrainState).
        Non-exact modes require a pure data-parallel mesh (every non-data
        axis of size 1) and, when clipping, the clip bound passed via
        ``GradSyncPolicy.clip_norm`` with a clip-free optimizer
        (docs/design.md §4)."""
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.rules = list(rules or DEFAULT_LOGICAL_RULES)
        self.grad_accum_steps = max(1, grad_accum_steps)
        # a two-level slice mesh (parallel.mesh.build_slice_mesh) always
        # data-shards the batch over the slice axis too: slices are DCN
        # domains of the SAME data-parallel world, not model parallelism
        if (
            mesh is not None
            and int(dict(mesh.shape).get("slice", 1)) > 1
            and "slice" not in data_axes
        ):
            data_axes = ("slice",) + tuple(data_axes)
        self.data_axes = data_axes
        self.grads_dtype = grads_dtype
        self.accum_dtype = accum_dtype
        self.grad_sync = GradSyncPolicy.parse(grad_sync)
        # the ORIGINALLY requested policy: a live reshard re-runs
        # _configure_grad_sync from this, so a dp=1 demotion (or a DCN
        # demotion) never outlives the mesh that caused it
        self._grad_sync_requested = self.grad_sync
        self._sync_axis = None  # str, or an axis tuple for the flat
        # combined-axis baseline on a two-level mesh
        self._sync_world = 1
        # r18 hierarchy: the cross-slice (DCN) axis when the policy runs
        # the two-level ICI+DCN decomposition; _ef_world is the TOTAL
        # dp-replica count (ici * slices) the error-feedback stacks span
        self._dcn_axis: Optional[str] = None
        self._dcn_world = 1
        self._ef_world = 1
        # DCN-leg demotion staging: the sentinel thread stages the
        # demoted policy here; the training thread swaps + recompiles
        # at the next train_step (never mid-dispatch)
        import threading as _threading

        self._demotion_mu = _threading.Lock()
        self._pending_grad_sync: Optional[GradSyncPolicy] = None
        # r22 live reshard: a Brain-ordered in-place mesh transition is
        # staged here ({"axes", "reason"}) and applied on the training
        # thread at the next step boundary — never mid-dispatch
        self._pending_reshard: Optional[Dict] = None
        # r21 fabric tuner: _tuner_plan is the per-bucket plan the
        # compiled step closes over; a re-tune stages its replacement
        # under the same lock and the training thread swaps it at the
        # next train_step.  _tuner_decision is the last COMPUTED plan
        # (recorded in grad_sync_summary even when apply is off).
        self._tuner = None
        self._tuner_plan = None
        self._pending_tuner_plan = None
        self._tuner_decision = None
        self._grad_layout: Optional[collectives.GradLayout] = None
        self._bucket_layout = None  # parallel.bucketing.BucketLayout
        if self.grad_sync.active and mesh is not None:
            self._configure_grad_sync()
        self._warn_fp32_accum_if_needed()
        # (params, batch) -> (loss, what the model sowed into ``stats``)
        self._loss_fn = self._model_loss if loss_fn is None else (
            lambda params, batch: (loss_fn(params, batch), ({}, None))
        )
        self.state_shardings = None
        self._jit_step = None
        self._jit_init = None
        if timer is None:
            from dlrover_tpu.trainer.bootstrap import monitoring_enabled

            if monitoring_enabled():
                # feed the monitor's hang watchdog automatically when the
                # job runs under a master (tpurun)
                from dlrover_tpu.timer import get_timer
                from dlrover_tpu.timer.py_tracing import enable_from_env

                timer = get_timer()
                self._py_tracer = enable_from_env(timer)
        self._timer = timer
        self._device_events = None
        if self._timer is not None:
            # sampled device-event capture (timer/device_events.py):
            # every Nth step runs under jax.profiler and its device-lane
            # ops land in the timer ring under XPU_TIMER_COLL_*/KERNEL_*
            # names.  DLROVER_TPU_DEVICE_PROFILE_EVERY=0 disables.
            from dlrover_tpu.timer.device_events import (
                DeviceEventCollector,
            )

            collector = DeviceEventCollector(self._timer)
            if collector.every_n_steps > 0:
                self._device_events = collector
        # comm observatory (observability/commscope.py): every
        # DLROVER_TPU_COMM_PROBE_EVERY steps run timed micro-collectives
        # per active mesh axis (latency + bandwidth -> FabricModel) and,
        # when the sync is bucketed, time each bucket's chain.  The
        # fabric digest rides the same rank-file -> heartbeat channel as
        # step times and the goodput ledger.
        self._comm_probe = None
        self._comm_bucket_scope = None
        if mesh is not None:
            try:
                from dlrover_tpu.observability import commscope

                if commscope.probe_every() > 0:
                    self._comm_probe = commscope.MeshProbe.for_mesh(mesh)
            except Exception as e:  # noqa: BLE001 - telemetry must not
                # break trainer construction
                from dlrover_tpu.common.log import logger

                logger.debug("comm probe unavailable: %s", e)
        self._steps_done = 0
        self._step_calls = 0  # the ``step`` of the ``trainer.step`` span
        # recorder-feed step counter: _steps_done only advances when the
        # native timer is attached, but the flight-recorder ring and the
        # per-rank digest file must count steps on EVERY loop shape
        self._digest_steps = 0
        # (digest step, the model's sown ``stats`` of that step) of the
        # last cadence tick, read at the next one
        self._stats_kept = None
        # brain_demote staged-file watermark — _configure_grad_sync
        # already baselined it on slice meshes (a stale staging file
        # must not demote a fresh trainer); flat meshes never poll
        if not hasattr(self, "_demote_seq"):
            self._demote_seq = None
        # r22 live-reshard handshake: register as the process target so
        # an in-process agent (unified local runtimes, drills) stages a
        # live ScalePlan directly, and baseline the staging file's
        # sequence — a stale request from an earlier incident must not
        # reshard a fresh trainer
        self._reshard_seq = None
        if mesh is not None:
            from dlrover_tpu.parallel import reshard as _reshard

            _reshard.register_reshard_target(self)
            try:
                self._reshard_seq = _reshard.staged_seq()
            except Exception:  # noqa: BLE001 - handshake is optional
                self._reshard_seq = None
        from dlrover_tpu.utils.step_clock import get_step_clock

        self._step_clock = get_step_clock()
        self._last_step_ts = None
        self._events = get_default_emitter("trainer")
        from dlrover_tpu.trainer.step_account import StepAccount

        # what the stepping thread did between two ``trainer.step`` closes,
        # and the record of a slow one
        self._step_account = StepAccount(self._step_clock, self._events)
        self._ticked = False  # the tick ran in the step that is open
        self._events.instant(
            TrainerEvents.INIT,
            {"mesh": {k: int(v) for k, v in mesh.shape.items()}
             if mesh is not None else {},
             "grad_accum_steps": self.grad_accum_steps},
        )

    def _configure_grad_sync(self):
        """Resolve the sync axis/world for a non-exact grad_sync policy.

        The shard_map decomposition runs the model apply on each
        replica's local batch, which is only correct when params are
        fully replicated across every manual mesh axis — so non-data
        axes (tp/cp/ep/pp) must be inactive, and exactly one data axis
        may be sharded (dp; fsdp shards the params themselves)."""
        active = [a for a in self.data_axes if self.mesh.shape.get(a, 1) > 1]
        nondata = [
            a for a, s in self.mesh.shape.items()
            if a not in self.data_axes and s > 1
        ]
        if nondata:
            raise ValueError(
                f"grad_sync={self.grad_sync.mode!r} needs a pure "
                f"data-parallel mesh; non-data axes {nondata} are active "
                "(use grad_sync='exact' with model parallelism)"
            )
        bad = [a for a in active if a not in ("dp", "slice")]
        if bad:
            # dp (and the slice axis above it) are the axes whose
            # contract is pure param replication (parallel/mesh.py);
            # fsdp shards the params themselves, and running the manual
            # shard_map body on a param SLICE would compute silently
            # wrong gradients
            raise ValueError(
                f"grad_sync={self.grad_sync.mode!r} requires replicated "
                f"params over the sync axes; active data axes {bad} "
                "shard params (use grad_sync='exact' with fsdp)"
            )
        if not active:
            import dataclasses

            from dlrover_tpu.common.log import logger

            logger.info(
                "grad_sync=%s demoted to exact: data-parallel world is 1",
                self.grad_sync.mode,
            )
            # keep clip_norm: the exact path applies it too, so a job
            # that elastically shrinks to dp=1 keeps identical update
            # math instead of silently losing gradient clipping
            self.grad_sync = dataclasses.replace(
                self.grad_sync, mode="exact"
            )
            return
        # make the policy concrete (bucket target, transport, blockwise
        # refine fraction, hierarchy + DCN codec) from the env registry
        # ONCE, here — the step program is compiled against these values
        self.grad_sync = self.grad_sync.resolve()
        shape = dict(self.mesh.shape)
        slice_world = int(shape.get("slice", 1))
        dp_world = int(shape.get("dp", 1))
        if slice_world > 1 and dp_world > 1 and self.grad_sync.hierarchical:
            # two-level decomposition: quantized reduce-scatter over
            # ICI within the slice, one aggregated (heavier-quantized)
            # exchange over DCN across slices, intra-slice all-gather.
            # The bucket layout / ZeRO-1 shards span the ICI world;
            # the EF stacks span every replica (slices * ici dp).
            if not (self.grad_sync.bucket_mb or 0.0) > 0:
                raise ValueError(
                    "hierarchical grad sync rides the bucketed chains; "
                    "bucket_mb=0 (the r6 per-leaf path) is only "
                    "available with GradSyncPolicy(hierarchical=False)"
                )
            self._sync_axis = "dp"
            self._sync_world = dp_world
            self._dcn_axis = "slice"
            self._dcn_world = slice_world
            # make this trainer the process's DCN-demotion target: an
            # in-process SlowLinkDiagnostician breach on the slice axis
            # can then demote the DCN leg with zero extra wiring
            from dlrover_tpu.parallel import hierarchy

            hierarchy.register_demotion_target(self)
            # baseline the cross-process demotion handshake NOW: a
            # stale staging file from an earlier incident must not
            # demote this fresh trainer, but a brain_demote staged any
            # time after this line applies at the next digest tick
            try:
                self._demote_seq = hierarchy.staged_seq()
            except Exception:  # noqa: BLE001 - handshake is optional
                self._demote_seq = None
        elif slice_world > 1 and dp_world > 1:
            # flat baseline on a two-level mesh: ONE collective over
            # the combined axis — every byte crosses the DCN boundary
            self._sync_axis = ("slice", "dp")
            self._sync_world = slice_world * dp_world
        elif slice_world > 1:
            self._sync_axis = "slice"
            self._sync_world = slice_world
        else:
            self._sync_axis = "dp"
            self._sync_world = dp_world
        self._ef_world = self._sync_world * self._dcn_world
        if self.grad_sync.sharded_update and self.grad_sync.clip_norm is None:
            from dlrover_tpu.common.log import logger

            # cannot be verified at runtime: an optax chain is opaque, so
            # a cross-leaf transform inside it (clip_by_global_norm) would
            # silently clip against each replica's SHARD norm
            logger.warning(
                "grad_sync=%s runs the optimizer on per-replica gradient "
                "shards: if your optax chain contains clip_by_global_norm "
                "(or any cross-leaf transform), remove it and pass the "
                "bound as GradSyncPolicy(clip_norm=...) instead — an "
                "in-chain clip would use shard-local norms "
                "(docs/design.md §4)", self.grad_sync.mode,
            )

    @property
    def _sync_active(self) -> bool:
        return self.grad_sync.active and self._sync_world > 1

    def grad_sync_summary(self) -> Dict:
        """What the compiled sync path actually does (bench/debug):
        policy mode + transport, and when bucketed the bucket count,
        per-bucket row widths, and the deterministic layout signature
        (equal across processes iff the assignments agree)."""
        info: Dict[str, Any] = {
            "mode": self.grad_sync.mode,
            "bucketed": self._bucket_layout is not None,
            "transport": self.grad_sync.transport,
        }
        if self._dcn_axis is not None:
            info.update(
                hierarchical=True,
                ici_axis=self._sync_axis,
                ici_world=self._sync_world,
                dcn_axis=self._dcn_axis,
                num_slices=self._dcn_world,
                dcn_format=(
                    "exact" if self.grad_sync.dcn_policy() is None
                    else self.grad_sync.dcn_policy().mode
                ),
            )
        elif isinstance(self._sync_axis, tuple):
            # the flat combined-axis baseline on a two-level mesh
            info.update(hierarchical=False, flat_axes=self._sync_axis)
        if self._bucket_layout is not None:
            from dlrover_tpu.ops.pallas import (
                ring_reduce_scatter as ring,
            )

            plan = self._tuner_plan

            def _resolved(b):
                d = (
                    plan.for_bucket(b.index)
                    if plan is not None else None
                )
                return ring.resolve_transport(
                    self.grad_sync, self._sync_world, b.width,
                    self._sync_axis,
                    request=d.transport if d is not None else None,
                )

            info.update(
                n_buckets=len(self._bucket_layout),
                bucket_mb=self.grad_sync.bucket_mb,
                signature=self._bucket_layout.signature(),
                bucket_widths=[
                    b.width for b in self._bucket_layout.buckets
                ],
                # what the fallback chain picked, per bucket — the
                # "transport" field above is only the REQUEST (the
                # live tuner plan's per-bucket override included)
                transport_resolved=sorted({
                    _resolved(b)
                    for b in self._bucket_layout.buckets
                }),
            )
        if self.grad_sync.stripe:
            info["stripe"] = self.grad_sync.stripe
        if self._tuner_decision is not None:
            tuner_info = self._tuner_decision.summary()
            tuner_info["applied"] = bool(
                self._tuner_plan is not None
                and self._tuner_plan.signature()
                == self._tuner_decision.signature()
            )
            info["tuner"] = tuner_info
        return info

    def apply_dcn_demotion(self) -> Optional[str]:
        """Demote the hierarchical DCN leg one quantization tier
        (``parallel.hierarchy.DCN_DEMOTION_LADDER``) in response to a
        degraded cross-slice link.  Returns the new format, or None
        when there is nothing to demote (flat mesh, exact leg, or
        already at the int4 floor).  The error-feedback stacks absorb
        the extra quantization error, so the state (and its
        checkpoints) are untouched.

        Thread contract: callable from the sentinel/diagnosis thread —
        the demoted policy is STAGED and the policy swap + recompile
        happen on the training thread at the next ``train_step``
        (nulling ``_jit_step`` from another thread could race the
        dispatch mid-step)."""
        import dataclasses

        from dlrover_tpu.parallel import hierarchy

        if self._dcn_axis is None:
            return None
        with self._demotion_mu:
            current = self._pending_grad_sync or self.grad_sync
            dcn_pol = current.dcn_policy()
            if dcn_pol is None:
                return None
            new_fmt = hierarchy.demoted_dcn_format(dcn_pol.mode)
            if new_fmt is None:
                return None
            self._pending_grad_sync = dataclasses.replace(
                current, dcn_format=new_fmt
            )
        from dlrover_tpu.common.log import logger

        logger.warning(
            "grad-sync DCN leg demoted %s -> %s (slow cross-slice "
            "link); step recompiles on next dispatch",
            dcn_pol.mode, new_fmt,
        )
        try:
            from dlrover_tpu.observability import metrics as obs_metrics

            obs_metrics.registry().counter_inc(
                "dlrover_tpu_hier_dcn_demotions_total",
                help=obs_metrics._help(  # noqa: SLF001
                    "dlrover_tpu_hier_dcn_demotions_total"
                ),
                to=new_fmt,
            )
        except Exception:  # noqa: BLE001 - instrumentation only
            pass
        return new_fmt

    # -- fabric auto-tuner (r21) -------------------------------------------

    def _ensure_tuner(self):
        """Lazily build the per-bucket fabric tuner once the bucket
        layout exists.  Gated by ``DLROVER_TPU_TUNER``; also registers
        this trainer as the process re-tune target so a slow-link
        breach can cure itself with a plan swap before the demotion
        ladder fires."""
        if self._tuner is not None:
            return self._tuner
        from dlrover_tpu.common import envs

        if not envs.get_bool("DLROVER_TPU_TUNER"):
            return None
        if self._bucket_layout is None or not self._sync_active:
            return None
        from dlrover_tpu.parallel import fabric_tuner

        self._tuner = fabric_tuner.FabricTuner(
            self._bucket_layout, self.grad_sync, self._sync_axis,
            self._sync_world, self._dcn_axis, self._dcn_world,
        )
        fabric_tuner.register_tuner_target(self)
        return self._tuner

    def _maybe_retune(self, source: str = "probe"):
        """Price the transport × stripe grid against the freshest
        fabric view (live probe snapshot, else ``fabric_tuner``'s
        cold-start seed) and stage the winning plan when it clears the
        hysteresis gate.  Returns the staged plan or None.  Safe from
        the sentinel thread — staging rides the demotion lock."""
        tuner = self._ensure_tuner()
        if tuner is None:
            return None
        from dlrover_tpu.parallel import fabric_tuner

        snap = None
        try:
            from dlrover_tpu.observability import commscope

            snap = commscope.scope().fabric.snapshot()
        except Exception:  # noqa: BLE001 - observability is optional
            snap = None
        if not snap:
            snap = fabric_tuner.seed_snapshot()
            if snap:
                source = "seed"
        plan = tuner.decide(snap, source=source)
        return self._stage_plan(plan, snap)

    def _stage_plan(self, plan, snap):
        """Record ``plan`` (summary + span) and, when
        ``DLROVER_TPU_TUNER_APPLY`` is on and the plan both CHANGES the
        hot path and clears the min-gain hysteresis, stage it for the
        next ``train_step``'s swap."""
        self._tuner_decision = plan
        try:
            from dlrover_tpu.observability import trace

            with trace.span("comm.retune", attrs={
                "source": plan.source,
                "priced_total_us": round(plan.total_us, 3),
                "transports": ",".join(sorted({
                    d.transport for d in plan.decisions
                })),
                "max_stripe": max(
                    (d.stripe for d in plan.decisions), default=0.0
                ),
            }):
                pass
        except Exception:  # noqa: BLE001 - telemetry only
            pass
        from dlrover_tpu.common import envs

        if not envs.get_bool("DLROVER_TPU_TUNER_APPLY"):
            return None
        live = self._tuner_plan
        if plan.source == "static" and live is None:
            # the static ladder IS the no-plan hot path
            return None
        if live is not None and plan.signature() == live.signature():
            return None
        if snap and not self._tuner.gain_ok(plan, live, snap):
            return None
        with self._demotion_mu:
            self._pending_tuner_plan = plan
        from dlrover_tpu.common.log import logger

        logger.info(
            "fabric tuner staged a new comm plan (%s, %.1fus priced): "
            "step recompiles on next dispatch",
            plan.source, plan.total_us,
        )
        return plan

    def retune_comm(self, axis: str) -> bool:
        """Slow-link breach fast path (``fabric_tuner.
        reroute_on_breach``): re-tune around the degraded ``axis``
        NOW instead of waiting for the probe cadence.  True when a
        changed plan was staged — the breach is cured without a
        quantization demotion."""
        del axis  # the snapshot already prices the degraded axis
        return self._maybe_retune(source="breach") is not None

    # -- state creation ----------------------------------------------------

    def _init_fn(self, rng, sample_input):
        variables = self.model.init(rng, sample_input)
        params = variables["params"]
        ef = None
        if self._sync_active and self.grad_sync.quantized:
            layout = collectives.GradLayout(params, self._sync_world)
            ef = collectives.error_feedback_init(
                params, layout, total_world=self._ef_world
            ) or None
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.optimizer.init(params),
            ef_residual=ef,
            buffers=variables.get("buffers"),
        )

    def state_sharding_for(self, rng, sample_input):
        """Derive NamedShardings for the whole TrainState from the model's
        logical annotations (boxes survive optax.init — it maps pytrees)."""
        # trace under the mesh so mesh-dependent dispatch (ring attention)
        # resolves identically to the real jitted step
        with self.mesh, nn.logical_axis_rules(self.rules):
            abstract = jax.eval_shape(
                lambda r: self._init_fn(r, sample_input), rng
            )
            logical_spec = nn.get_partition_spec(abstract)
            shardings = nn.logical_to_mesh_sharding(
                logical_spec, self.mesh, self.rules
            )
        if self._sync_active:
            shardings = self._overlay_sync_shardings(abstract, shardings)
        return shardings

    def _overlay_sync_shardings(self, abstract, shardings):
        """Grad-sync layout overlay: dp-sharded optimizer moments (ZeRO-1
        update) and dp-stacked error-feedback buffers.  Moment GLOBAL
        shapes stay identical to the exact policy's, so checkpoints
        reshard across dp degrees generically; only the EF leaves carry
        the dp degree in their shape (handled by ``load_state``)."""
        from jax.sharding import NamedSharding, PartitionSpec

        self._grad_layout = collectives.GradLayout(
            abstract.params, self._sync_world
        )
        self._bucket_layout = None
        # a fresh bucket layout invalidates any tuner plan (decisions
        # are keyed by bucket index/width — elastic resize reshapes both)
        self._tuner = None
        self._tuner_plan = None
        with self._demotion_mu:
            self._pending_tuner_plan = None
        bucket_mb = self.grad_sync.bucket_mb or 0.0
        if bucket_mb > 0:
            from dlrover_tpu.parallel.bucketing import BucketLayout

            buckets = BucketLayout.build(
                self._grad_layout, abstract.params,
                int(bucket_mb * 1024 * 1024),
            )
            if len(buckets):
                self._bucket_layout = buckets
        if self.grad_sync.sharded_update:
            from dlrover_tpu.trainer.optim import moment_sharding_specs

            shardings = shardings.replace(
                opt_state=moment_sharding_specs(
                    abstract.opt_state,
                    abstract.params,
                    shardings.opt_state,
                    self.mesh,
                    self._sync_axis,
                    self._sync_world,
                )
            )
        if abstract.ef_residual is not None:
            # hierarchical: every (slice, ici) replica owns one row of
            # the (slices * ici_dp, *leaf) stack — shard the leading
            # axis over BOTH mesh axes (slice-major, matching the
            # shard_map row order).  Flat meshes keep the single-axis
            # (or combined-tuple) spec.
            ef_axes = (
                (self._dcn_axis, self._sync_axis)
                if self._dcn_axis is not None else self._sync_axis
            )
            ef_sharding = NamedSharding(
                self.mesh, PartitionSpec(ef_axes)
            )
            shardings = shardings.replace(
                ef_residual=jax.tree.map(
                    lambda _: ef_sharding, abstract.ef_residual
                )
            )
        return shardings

    def create_state(self, rng, sample_input) -> TrainState:
        self.state_shardings = self.state_sharding_for(rng, sample_input)
        with self.mesh, nn.logical_axis_rules(self.rules):
            init = jax.jit(
                lambda r: self._init_fn(r, sample_input),
                out_shardings=self.state_shardings,
            )
            return init(rng)

    def abstract_state(self, rng, sample_input):
        """ShapeDtypeStruct tree of the state (for checkpoint restore)."""
        with self.mesh, nn.logical_axis_rules(self.rules):
            return jax.eval_shape(
                lambda r: self._init_fn(r, sample_input), rng
            )

    # -- train step ----------------------------------------------------------

    def _default_loss(self, params, batch):
        """``(loss, stats)`` of ``_model_loss``."""
        loss, (stats, _) = self._model_loss(params, batch)
        return loss, stats

    def _model_loss(self, params, batch):
        """Cross entropy plus every term the model sows into its
        ``losses`` collection, weighted by the model (a routed block's
        load-balancing and z-loss; nothing for a dense model), and what
        it sows into ``stats``.  A model whose configuration declares its
        ``own_objective`` (block diffusion's NELBO) is given no cross
        entropy on top: its loss is what it sows, and the batch's
        ``labels`` go unused.  ``batch["rngs"]``: the step's random streams
        for a model that draws some (``_model_step_rngs``).
        ``batch["buffers"]``: the state's buffers for a model that declares
        some, handed over mutable; the auxiliary result is ``(stats, the
        buffers as the model left them)``."""
        variables, mutable = {"params": params}, ["losses", "stats"]
        if batch.get("buffers") is not None:
            variables["buffers"] = batch["buffers"]
            mutable.append("buffers")
        logits, sown = self.model.apply(
            variables, batch["input_ids"],
            mutable=mutable, rngs=batch.get("rngs"),
        )
        config = getattr(self.model, "config", None)
        with jax.named_scope("head_loss"):
            if getattr(config, "own_objective", False):
                loss = jnp.float32(0)
            else:
                loss = cross_entropy_loss(
                    logits, batch["labels"], batch.get("mask"))
            for term in jax.tree.leaves(sown.get("losses", {})):
                loss = loss + jnp.sum(term)
        return loss, (sown.get("stats", {}), sown.get("buffers"))

    def _loss_and_grads(self, params, batch):
        """``((loss, stats), grads)``, optionally w.r.t. a low-precision
        param view."""
        (loss, (stats, _)), grads = self._loss_buffers_and_grads(params, batch)
        return (loss, stats), grads

    def _loss_buffers_and_grads(self, params, batch):
        """``((loss, (stats, the model's buffers after the step)), grads)``:
        ``_loss_and_grads`` with what a model that declares buffers hands
        back (``None`` for any other)."""
        if self.grads_dtype is not None:
            with jax.named_scope("optimizer"):
                params = jax.tree.map(
                    lambda p: p.astype(self.grads_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating)
                    else p,
                    params,
                )
        return jax.value_and_grad(self._loss_fn, has_aux=True)(params, batch)

    def _grad_fn(self, params, batch):
        (loss, _), grads = self._loss_and_grads(params, batch)
        return loss, grads

    def _model_step_rngs(self, step) -> Dict:
        """The random streams the model's configuration asks for in step
        ``step`` (``step_rngs``: block diffusion's noise), ``{}`` for a
        model that draws nothing: keys made from the step inside the
        compiled program, so the state carries none."""
        step_rngs = getattr(
            getattr(self.model, "config", None), "step_rngs", None)
        return step_rngs(step) if step_rngs else {}

    def _train_step(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        rngs = self._model_step_rngs(state.step)
        if rngs or state.buffers is not None:
            if self._sync_active or self.grad_accum_steps != 1:
                # both split the batch by rows, keys and all, and would
                # move a buffer once a part
                raise NotImplementedError(
                    "a model that draws noise or moves buffers in its step "
                    "runs on the exact path without gradient accumulation")
            if rngs:
                batch = {**batch, "rngs": rngs}
            if state.buffers is not None:
                batch = {**batch, "buffers": state.buffers}
        if self._sync_active:
            return self._sync_train_step(state, batch)
        return self._exact_train_step(state, batch)

    def _exact_train_step(
        self, state: TrainState, batch
    ) -> Tuple[TrainState, Dict]:
        stats, buffers = {}, state.buffers
        if self.grad_accum_steps == 1:
            (loss, (stats, moved)), grads = self._loss_buffers_and_grads(
                state.params, batch)
            if buffers is not None:
                buffers = moved
        else:
            loss_sum, grad_sum, w_sum = self._accumulate_scan(
                state.params, batch
            )
            with jax.named_scope("optimizer"):
                w_sum = jnp.maximum(w_sum, 1e-8)
                loss = loss_sum / w_sum
                grads = jax.tree.map(
                    lambda g: g / w_sum.astype(g.dtype), grad_sum
                )

        with jax.named_scope("optimizer"):
            grad_norm = optax.global_norm(grads)
            if self.grad_sync.clip_norm is not None:
                # policy-level clipping also applies on the exact path, so
                # a GradSyncPolicy(clip_norm=...) job behaves identically
                # when the dp world (elastically) collapses to 1
                scale = jnp.minimum(
                    1.0, self.grad_sync.clip_norm / jnp.maximum(
                        grad_norm, 1e-12
                    )
                )
                grads = jax.tree.map(
                    lambda g: g * scale.astype(g.dtype), grads
                )
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state,
            buffers=buffers,
        )
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if stats:
            metrics["stats"] = stats
        return new_state, metrics

    # -- shared gradient accumulation --------------------------------------

    @staticmethod
    def _mb_weight(mb, default_n):
        # token weight so masked (micro)batches average correctly
        if isinstance(mb, dict) and mb.get("mask") is not None:
            return mb["mask"].sum().astype(jnp.float32)
        return jnp.asarray(float(default_n), jnp.float32)

    def _accumulate_scan(self, params, batch):
        """Microbatch accumulation scan shared by the exact and
        grad-sync paths: UNNORMALIZED ``(loss_sum, grad_sum, w_sum)``
        over the (local) batch, mask-weighted so the caller's division
        by the (possibly psum'd) weight reproduces the exact mean."""
        accum = self.grad_accum_steps
        batch_dim = jax.tree.leaves(batch)[0].shape[0]
        if batch_dim % accum != 0:
            raise ValueError(
                f"batch size {batch_dim} not divisible by "
                f"grad_accum_steps {accum}; no sample may be dropped"
            )
        micro = batch_dim // accum

        def microbatch(i, b):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(
                    x, i * micro, micro, 0
                ),
                b,
            )

        def scan_body(carry, i):
            loss_sum, grad_sum, w_sum = carry
            mb = microbatch(i, batch)
            w = self._mb_weight(mb, micro)
            loss, grads = self._grad_fn(params, mb)
            with jax.named_scope("optimizer"):
                return (
                    loss_sum + loss * w,
                    # keep the multiply in the accumulator dtype: a bf16
                    # grad times an fp32 scalar would silently promote
                    # the whole accumulated pytree back to fp32
                    jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype) * w.astype(a.dtype),
                        grad_sum, grads,
                    ),
                    w_sum + w,
                ), None

        # fp32 accumulator by default even for bf16 grads: repeated
        # bf16 summation loses late-microbatch contributions as the
        # running sum grows.  accum_dtype=bf16 is an explicit opt-in
        # for HBM-tight jobs that cannot fit the fp32 pytree.
        accum_dtype = self.accum_dtype or jnp.float32
        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, accum_dtype), params
        )
        (loss_sum, grad_sum, w_sum), _ = jax.lax.scan(
            scan_body,
            (jnp.zeros((), jnp.float32), zero_grads,
             jnp.zeros((), jnp.float32)),
            jnp.arange(accum),
        )
        return loss_sum, grad_sum, w_sum

    # -- grad-sync (shard_map) train step ----------------------------------

    def _accumulate_local(self, params, batch):
        """Per-replica UNNORMALIZED gradient contribution for the
        shard_map sync path: ``(loss_sum, grad_sum, w_sum)`` over this
        replica's local batch, so the cross-replica reduce
        ``psum(grad_sum) / psum(w_sum)`` reproduces the exact global
        (mask-weighted) mean gradient."""
        if self.grad_accum_steps == 1:
            w = self._mb_weight(
                batch, jax.tree.leaves(batch)[0].shape[0]
            )
            loss, grads = self._grad_fn(params, batch)
            return (
                loss * w,
                jax.tree.map(
                    lambda g: g.astype(jnp.float32) * w, grads
                ),
                w,
            )
        return self._accumulate_scan(params, batch)

    def _sync_body(self, state: TrainState, batch):
        """Per-replica body of the shard_map train step: local grads,
        (quantized) reduce-scatter, (sharded) update, param all-gather.
        Runs with every mesh axis manual — collectives are explicit, and
        the model's logical sharding constraints no-op (no rules bound)."""
        from jax import lax

        axis = self._sync_axis
        policy = self.grad_sync
        layout = self._grad_layout
        # all dp replicas — on a two-level mesh the loss/weight reduce
        # and the stochastic-rounding key must span BOTH axes (every
        # (slice, ici) device is one replica of the same global batch)
        reduce_axes = (
            (self._dcn_axis, axis) if self._dcn_axis is not None else axis
        )
        loss_sum, grad_sum, w_sum = self._accumulate_local(
            state.params, batch
        )
        # what the sync path adds to the exact one: the reduce of the
        # replicas' sums and the (quantized) exchange of the gradients
        with jax.named_scope("grad_sync"):
            w_global = jnp.maximum(lax.psum(w_sum, reduce_axes), 1e-8)
            loss = lax.psum(loss_sum, reduce_axes) / w_global
            ghat = jax.tree.map(
                lambda g: g.astype(jnp.float32) / w_global, grad_sum
            )
            key = None
            if policy.rounding == "stochastic":
                key = jax.random.fold_in(
                    jax.random.PRNGKey(policy.seed), state.step
                )
                key = jax.random.fold_in(key, lax.axis_index(reduce_axes))
            if self._dcn_axis is not None and self._bucket_layout is not None:
                # r18 two-level path: quantized ICI reduce-scatter within
                # the slice, ONE aggregated heavier-quantized DCN exchange
                # across slices, and (below) an intra-slice all-gather —
                # cross-slice bytes drop by the in-slice dp factor
                synced, new_ef = collectives.sync_gradient_tree_hierarchical(
                    ghat, state.ef_residual, layout, self._bucket_layout,
                    policy, axis, self._dcn_axis, self._dcn_world, key,
                    plan=self._tuner_plan,
                )
            elif self._dcn_axis is not None:
                # hierarchical mesh but zero shardable leaves (no bucket
                # layout): every leaf rides the exact psum over both axes
                synced, new_ef = collectives.sync_gradient_tree(
                    ghat, state.ef_residual, layout, policy, reduce_axes,
                    key,
                )
            elif self._bucket_layout is not None:
                # overlapped path: one fused collective per bucket, every
                # bucket's chain independent — the scheduler hides the
                # exchange behind remaining backward/quantize compute
                synced, new_ef = collectives.sync_gradient_tree_bucketed(
                    ghat, state.ef_residual, layout, self._bucket_layout,
                    policy, axis, key, plan=self._tuner_plan,
                )
            else:
                synced, new_ef = collectives.sync_gradient_tree(
                    ghat, state.ef_residual, layout, policy, axis, key
                )
        with jax.named_scope("optimizer"):
            grad_norm = collectives.global_grad_norm(synced, layout, axis)
            if policy.clip_norm is not None:
                scale = jnp.minimum(
                    1.0, policy.clip_norm / jnp.maximum(grad_norm, 1e-12)
                )
                synced = jax.tree.map(lambda g: g * scale, synced)

        def gather(tree):
            with jax.named_scope("grad_sync"):
                if self._bucket_layout is not None:
                    return collectives.all_gather_tree_bucketed(
                        tree, layout, self._bucket_layout, axis
                    )
                return collectives.all_gather_tree(tree, layout, axis)

        def update(grads, params):
            with jax.named_scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, params
                )
                return optax.apply_updates(params, updates), opt_state

        if policy.sharded_update:
            new_shards, opt_state = update(
                synced, collectives.shard_like(state.params, layout, axis))
            params = gather(new_shards)
        else:
            params, opt_state = update(gather(synced), state.params)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            opt_state=opt_state,
            ef_residual=new_ef,
        )
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    def _sync_train_step(
        self, state: TrainState, batch
    ) -> Tuple[TrainState, Dict]:
        from jax.sharding import PartitionSpec

        if self._grad_layout is None:
            raise RuntimeError("call create_state() first")
        state_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings
        )
        fn = collectives.shard_map_unchecked(
            self._sync_body,
            mesh=self.mesh,
            in_specs=(state_specs, PartitionSpec(self.data_axes)),
            # metrics are psum results — replicated by construction,
            # which the rep checker cannot prove through the optax update
            out_specs=(state_specs, PartitionSpec()),
        )
        return fn(state, batch)

    def compile_train_step(self, donate: bool = True):
        if self.state_shardings is None:
            raise RuntimeError("call create_state() first")
        from jax.sharding import NamedSharding, PartitionSpec

        data_sharding = NamedSharding(
            self.mesh, PartitionSpec(self.data_axes)
        )

        def wrapped(state, batch):
            if self._sync_active:
                # no logical rules bound: inside the fully-manual
                # shard_map region the model's with_logical_constraint
                # calls must resolve to no-ops, not to sharding
                # constraints over manual mesh axes
                return self._train_step(state, batch)
            with nn.logical_axis_rules(self.rules):
                return self._train_step(state, batch)

        jit_step = jax.jit(
            wrapped,
            # data_sharding broadcasts over the whole batch pytree
            in_shardings=(self.state_shardings, data_sharding),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )
        try:
            # compile observatory: every (re)compile of the step program
            # becomes a classified event — which function, how many
            # compile seconds, and WHY (shape/dtype/sharding/mesh drift,
            # donation flip, or a persistent-cache miss on a supposedly
            # warm restart).  The cached hot path costs two counter
            # reads; a broken observatory never breaks the step.
            from dlrover_tpu.observability import jitscope

            if jitscope.enabled():
                jit_step = jitscope.watch(
                    jit_step, "trainer.train_step",
                    static={"donate": bool(donate),
                            "accum": self.grad_accum_steps},
                )
        except Exception as e:  # noqa: BLE001 - telemetry must not
            # break compilation
            from dlrover_tpu.common.log import logger

            logger.debug("jitscope watch unavailable: %s", e)
        self._jit_step = jit_step
        return self._jit_step

    def lower_train_step(self, state, batch):
        """The step program lowered (not compiled) for these arguments,
        arrays or ``ShapeDtypeStruct``s alike: ``.as_text()`` shows
        whether the kernel is in it, ``.compile()`` what the chip's
        compiler makes of it.  Its second user is ``trace.device_scopes``
        (``_leave_device_scopes``), which reads each instruction's scope
        off the compiled text."""
        if self._jit_step is None:
            self.compile_train_step()
        with self.mesh:
            return self._jit_step.lower(state, batch)

    def _leave_device_scopes(self, state, batch):
        """Leave with ``trace.device_scopes("trainer.step")`` the way to
        the compiled step's text: the same program lowered for the same
        abstract arguments (shapes, dtypes and shardings; no array is
        held).  Nothing is lowered or compiled here: whoever asks pays, and
        finds the executable in JAX's caches."""
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
            (state, batch),
        )
        trace.register_device_scopes(
            "trainer.step",
            lambda: self.lower_train_step(*abstract).compile().as_text(),
        )

    def _dispatch(self, state, batch, compiled: bool = False):
        # ``compiled``: the first call of a program, which compiles it
        with trace.span(
            "trainer.step.dispatch", attrs={"compiled": compiled}
        ), self.mesh:
            return self._jit_step(state, batch)

    def train_step(self, state: TrainState, batch):
        """One optimizer step.  All of it is the span ``trainer.step``
        (``step``: calls of this trainer so far); its child
        ``trainer.step.dispatch`` is the jitted call, and the rest is
        the host's bookkeeping around it.  At its close the span takes
        what its thread did since the last one closed
        (``trainer/step_account.py``)."""
        self._step_calls += 1
        with trace.span(
            "trainer.step", attrs={"step": self._step_calls}
        ) as span:
            result = self._step_on_host(state, batch)
            ticked, self._ticked = self._ticked, False
            if span is not trace.NOOP_SPAN:
                self._step_account.close(span, self._step_calls, ticked)
            return result

    def _step_on_host(self, state: TrainState, batch):
        import time as _time

        if self._pending_reshard is not None:
            # a staged live reshard (Brain ScalePlan via the agent, or
            # the file handshake): apply it HERE, at the step boundary
            # on the training thread — the mesh swap + recompile can
            # never race a dispatch in flight.  A refused plan (fit
            # gate, missing donor) keeps training on the old mesh.
            with self._demotion_mu:
                pending_reshard, self._pending_reshard = (
                    self._pending_reshard, None
                )
            state, batch = self._apply_pending_reshard(
                pending_reshard, state, batch
            )
        if (
            self._pending_grad_sync is not None
            or self._pending_tuner_plan is not None
        ):
            # a sentinel-staged DCN demotion or tuner plan: apply it
            # HERE, on the training thread, so the recompile can never
            # race a dispatch in flight
            with self._demotion_mu:
                pending, self._pending_grad_sync = (
                    self._pending_grad_sync, None
                )
                pending_plan, self._pending_tuner_plan = (
                    self._pending_tuner_plan, None
                )
            if pending is not None:
                self.grad_sync = pending
                # the pricing grid closed over the old policy
                self._tuner = None
                self._jit_step = None
            if pending_plan is not None:
                self._tuner_plan = pending_plan
                self._jit_step = None
        if self._jit_step is None:
            self.compile_train_step()
            # a new program invalidates the step-time baseline the
            # checkpoint-staging pacer calibrates against
            self._step_clock.reset()
            self._step_account.reset()
            self._last_step_ts = None
            # the real XLA compile happens on the first dispatch; the
            # span makes "where did the first minute go" answerable from
            # the offline timeline (reference TrainerEventName compile)
            mem_before = 0.0
            try:
                from dlrover_tpu.observability import memscope

                if memscope.enabled():
                    mem_before = memscope.scope().device_used_bytes()
            except Exception:  # noqa: BLE001 - telemetry must not
                pass  # break compilation
            with self._events.duration(TrainerEvents.COMPILE):
                from dlrover_tpu.utils.timing import hard_block

                compile_t0 = _time.time()
                self._leave_device_scopes(state, batch)
                result = self._dispatch(state, batch, compiled=True)
                hard_block(result)
            try:
                from dlrover_tpu.observability import goodput

                # measured compile seconds (the jitscope wrapper around
                # _jit_step recorded the event during the dispatch)
                # split the window exactly: compile head, execution
                # remainder as compute.  None falls back to the old
                # whole-window heuristic.
                event = getattr(self._jit_step, "last_event", None)
                goodput.charge_compile_window(
                    compile_t0, _time.time(),
                    event.get("compile_s") if event else None,
                )
            except Exception:  # noqa: BLE001 - ledger must not break
                pass  # a training step
            self._register_memscope(state, mem_before)
        else:
            if (
                self._device_events is not None
                and self._device_events.should_sample()
            ):
                # sampled step: profile + block so device events exist
                from dlrover_tpu.utils.timing import hard_block

                with self._device_events.window():
                    result = self._dispatch(state, batch)
                    hard_block(result)
            else:
                result = self._dispatch(state, batch)
            # feed the staging pacer: inter-dispatch wall time tracks the
            # true step cadence in any loop that fetches device results
            now = _time.monotonic()
            if self._last_step_ts is not None:
                dur = now - self._last_step_ts
                self._step_clock.record(dur)
                self._digest_steps += 1
                self._after_step(self._digest_steps, dur, result[1])
            self._last_step_ts = now
        if self._timer is not None:
            self._steps_done += 1
            # records step wall time and kicks the native hang watchdog
            self._timer.tick_step(self._steps_done)
        return result

    def _register_memscope(self, state, mem_before_b: float):
        """Adopt the live train state as the memory observatory's
        attribution plan (per-leaf abstract shapes + sharding specs ->
        per-chip bytes per subsystem), price the bucketed grad-sync
        buffers, and book the compile-window live-buffer delta.  Runs
        once per compiled program; never raises into the training
        loop."""
        try:
            from dlrover_tpu.observability import memscope

            if not memscope.enabled():
                return
            sc = memscope.scope()
            mesh_axes = (
                {str(a): int(s) for a, s in self.mesh.shape.items()}
                if self.mesh is not None else None
            )
            sc.register_state(state, mesh_axes)
            if self._bucket_layout is not None:
                sc.register_buckets(
                    self._bucket_layout, self._sync_world
                )
            if mem_before_b > 0:
                sc.note_compile_delta(
                    mem_before_b, sc.device_used_bytes()
                )
        except Exception as e:  # noqa: BLE001 - telemetry must not
            # break a training step
            from dlrover_tpu.common.log import logger

            logger.debug("memscope registration failed: %s", e)

    def _maybe_probe_comm(self, step: int):
        """On the probe cadence, run the active mesh probe (and the
        per-bucket chain measurement when the sync is bucketed) into
        the process comm scope.  Probes are jitted collectives fired at
        the same digest-step count on every process, so the fleet
        dispatches them in lockstep; a broken probe never breaks the
        step."""
        if self._comm_probe is None:
            return
        try:
            from dlrover_tpu.common import envs
            from dlrover_tpu.observability import commscope

            every = commscope.probe_every()
            if every <= 0 or step % every != 0:
                return
            self._comm_probe.probe_once(commscope.scope().fabric)
            # re-price the transport/stripe grid against the fresh
            # measurements on the same cadence (swap is staged; the
            # training thread applies it at the next step)
            self._maybe_retune(source="probe")
            if (
                self._bucket_layout is not None
                and envs.get_bool("DLROVER_TPU_COMM_BUCKET_PROBE")
            ):
                if self._comm_bucket_scope is None:
                    self._comm_bucket_scope = commscope.BucketScope.\
                        for_trainer(self)
                if self._comm_bucket_scope is not None:
                    self._comm_bucket_scope.measure(reps=1)
        except Exception as e:  # noqa: BLE001 - telemetry must not
            # break a training step
            from dlrover_tpu.common.log import logger

            logger.debug("comm probe failed: %s", e)

    def _after_step(self, step: int, dur_s: float, metrics):
        """The bookkeeping after a dispatch.  Most steps feed two rings;
        every ``DLROVER_TPU_DIGEST_EVERY`` steps the *tick* runs (the
        polls, the memory sample, the digests and their file, the read
        of the model's sown ``stats``, the comm probe where its own
        cadence falls on it) under one span ``trainer.step.tick`` whose
        attributes time its parts and carry ``host_pressure``'s
        counters."""
        from dlrover_tpu.common import envs

        every = envs.get_int("DLROVER_TPU_DIGEST_EVERY")
        if every <= 0 or step % every != 0:
            self._note_step_time(step, dur_s)
            self._maybe_probe_comm(step)
            return
        self._ticked = True
        with trace.span("trainer.step.tick", attrs={"step": step}) as tick:
            parts: Dict[str, Any] = {}
            self._note_step_time(step, dur_s, parts)
            self._note_model_stats(step, metrics, parts)
            self._maybe_probe_comm(step)
            if tick is not trace.NOOP_SPAN:
                from dlrover_tpu.trainer.step_account import host_pressure

                parts.update(host_pressure())
                tick.set_attrs(parts)

    def _note_step_time(self, step: int, dur_s: float,
                        parts: Optional[Dict[str, Any]] = None):
        """Feed the flight recorder's step ring and, every
        ``DLROVER_TPU_DIGEST_EVERY`` steps, drop this rank's step-time
        digest file (``ConfigPath.RUNTIME_METRICS``.rank<id>) — the file
        the agent folds into its heartbeat digest, which is what the
        master's straggler/stall screens read.  ``parts``, where given,
        takes the seconds of the drop's parts: ``poll_s``, ``memscope_s``,
        ``digests_s``, ``write_s``.  Never raises into the training
        loop."""
        import time as _time

        if parts is None:
            parts = {}
        try:
            from dlrover_tpu.observability import flight_recorder, goodput

            flight_recorder.on_step(step, dur_s)
            goodput.on_step(step, dur_s)
            from dlrover_tpu.common import envs

            every = envs.get_int("DLROVER_TPU_DIGEST_EVERY")
            if every <= 0 or step % every != 0:
                return
            t_poll = _time.perf_counter()
            # brain action channel: apply any cross-process DCN
            # demotion the agent staged since the last digest window
            if getattr(self, "_dcn_axis", None) is not None:
                from dlrover_tpu.parallel import hierarchy

                self._demote_seq = hierarchy.poll_staged_demotion(
                    self, getattr(self, "_demote_seq", None)
                )
            # ... and any staged live reshard (r22): polled on the same
            # cadence, so a Brain-ordered in-place transition resumes
            # within DIGEST_EVERY steps plus one step-boundary swap
            from dlrover_tpu.parallel import reshard as _reshard

            self._reshard_seq = _reshard.poll_staged_reshard(
                self, getattr(self, "_reshard_seq", None)
            )
            t_digests = _time.perf_counter()
            parts["poll_s"] = t_digests - t_poll
            import json
            import os

            from dlrover_tpu.common.constants import ConfigPath, NodeEnv

            digest = flight_recorder.recorder().step_digest()
            if not digest:
                return
            # this rank's cumulative goodput account rides the same
            # file -> agent heartbeat -> master channel as step times
            if goodput.enabled():
                digest.update(goodput.ledger().digest())
            # ... and so does the fabric model (probe-measured per-axis
            # latency/bandwidth, fxl_/fxb_ keys)
            from dlrover_tpu.observability import commscope

            digest.update(commscope.scope().digest())
            # ... and the memory account (sampled HERE, on the digest
            # cadence: device stats + host RSS/shm + the subsystem
            # attribution, mm_/mms_ keys)
            from dlrover_tpu.observability import memscope

            t_memscope = _time.perf_counter()
            memscope.sample()
            parts["memscope_s"] = _time.perf_counter() - t_memscope
            digest.update(memscope.scope().digest())
            # ... and the compile observatory (cumulative compile
            # seconds / cache hits+misses / stalls, js_ keys)
            from dlrover_tpu.observability import jitscope

            if jitscope.enabled():
                digest.update(jitscope.scope().digest())
            t_write = _time.perf_counter()
            # the four observatories' digests, less the memory sample
            parts["digests_s"] = (
                t_write - t_digests - parts["memscope_s"])
            path = (
                envs.get_str(ConfigPath.ENV_RUNTIME_METRICS)
                + f".rank{envs.get_int(NodeEnv.PROCESS_ID)}"
            )
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(digest, f)
            os.replace(tmp, path)
            parts["write_s"] = _time.perf_counter() - t_write
        except Exception as e:  # noqa: BLE001 - telemetry must not
            # break a training step
            from dlrover_tpu.common.log import logger

            logger.debug("step digest drop failed: %s", e)

    def _note_model_stats(self, step: int, metrics,
                          parts: Dict[str, Any]):
        """On the tick, keep what the model sowed into ``stats`` in this
        step (still in flight) and record what was kept the tick before,
        finished long since, as one ``trainer.model_stats`` span whose
        attributes are the sown names with their values layer by layer.
        The read of the kept leaves is inside the span and timed
        (``parts``: ``stats_read_s``, ``stats_leaves``).  The stepping
        thread never waits for the device here."""
        import time as _time

        stats = metrics.get("stats")
        if not stats:
            return
        kept, self._stats_kept = self._stats_kept, (step, stats)
        if kept is None:
            return
        leaves = jax.tree_util.tree_leaves_with_path(kept[1])
        if not all(leaf.is_ready() for _, leaf in leaves):
            return
        with trace.span(
            "trainer.model_stats", attrs={"step": kept[0]}
        ) as span:
            t_read = _time.perf_counter()
            attrs: Dict[str, Any] = {}
            for path, leaf in leaves:
                name = next(str(key.key) for key in reversed(path)
                            if hasattr(key, "key"))
                attrs.setdefault(name, []).extend(
                    jax.device_get(leaf).ravel().tolist()
                )
            parts["stats_read_s"] = _time.perf_counter() - t_read
            parts["stats_leaves"] = len(leaves)
            span.set_attrs(attrs)

    # -- data --------------------------------------------------------------

    def shard_batch(self, batch):
        from dlrover_tpu.parallel.sharding import shard_batch

        with trace.span("trainer.shard_batch", attrs={"bytes": sum(
            getattr(x, "nbytes", 0) for x in jax.tree.leaves(batch)
        )}):
            return shard_batch(self.mesh, batch, self.data_axes)

    # -- elasticity --------------------------------------------------------

    def load_state(self, checkpointer, rng, sample_input):
        """Checkpoint restore that survives a dp-degree change under a
        quantized grad_sync policy.

        Optimizer moments keep dp-independent global shapes, so the
        generic resharding restore covers them.  The error-feedback
        stacks are the one dp-shaped leaf (``(dp, *leaf)``): when the
        stored degree differs, the stacks are summed host-side and
        re-split — every new replica carries
        ``sum(old residuals) / dp_new``, preserving the total
        un-injected quantization error the old fleet still owed
        (``collectives.materialize_ef_stack``).  Also sets
        ``self.state_shardings`` so the restored state is dispatchable.

        Returns ``(state, step)``; ``(None, -1)`` when nothing restores.
        """
        abstract = self.abstract_state(rng, sample_input)
        shardings = self.state_sharding_for(rng, sample_input)
        self.state_shardings = shardings
        if abstract.ef_residual is None:
            return checkpointer.load_checkpoint(abstract, shardings)
        from dlrover_tpu.common.log import logger

        # First attempt: the full abstract, EF stacks included.  The
        # engine's load is COLLECTIVE (all processes agree on one step),
        # and its global-shape coverage guard rejects an EF stack saved
        # at a different dp degree — so success means a same-degree
        # restore (shm fast path or storage), and failure is job-wide
        # consistent.
        state, step = checkpointer.load_checkpoint(abstract, shardings)
        if state is not None:
            # guard against the engine's fall-back-to-older-candidates
            # scan having skipped a NEWER step it could not cover (one
            # saved at a different dp degree): the newest-step check is
            # agreed collectively so every process takes the same
            # branch.  An agreement failure (-1) keeps this restore.
            newest = checkpointer.engine._agree_on_step(  # noqa: SLF001
                checkpointer.engine.latest_step()
            )
            if newest <= step:
                return state, step
            logger.info(
                "grad-sync restore: step %d restored but step %d exists "
                "(saved at another dp degree); re-restoring the newer "
                "step with redistributed error feedback", step, newest,
            )
            newer_state, newer_step = self._load_state_rebuild_ef(
                checkpointer, abstract, shardings
            )
            if newer_state is None or newer_step <= step:
                return state, step
            return newer_state, newer_step
        return self._load_state_rebuild_ef(checkpointer, abstract, shardings)

    def _load_state_rebuild_ef(self, checkpointer, abstract, shardings):
        """Fallback restore for ``load_state``: the rest of the state
        without the EF leaves, then stacks rebuilt from whatever the
        agreed step stores (redistributed across the current dp degree,
        zero where absent)."""
        # Fallback: restore the rest of the state without the EF leaves
        # (also collective), then rebuild the stacks from whatever the
        # AGREED step stores — every process reads the same step, so no
        # per-host storage peek can diverge the fleet:
        #  * EF stored at another dp degree -> redistribute: each new
        #    replica carries sum(old residuals)/dp_new, preserving the
        #    total un-injected error;
        #  * no EF at that step (checkpoint predates the quantized
        #    policy) -> zero stacks, what a fresh quantized run has.
        state, step = checkpointer.load_checkpoint(
            abstract.replace(ef_residual=None),
            shardings.replace(ef_residual=None),
        )
        if state is None:
            return None, -1
        import numpy as np

        from dlrover_tpu.common.log import logger

        # full-state paths of the EF leaves, resolved by leaf identity
        # (the flax-struct field renders as ".ef_residual" in key paths
        # — never hardcode the prefix)
        ef_ids = {
            id(leaf): path
            for path, leaf in collectives.leaf_items(abstract.ef_residual)
        }
        ef_full_paths = {
            path: ef_ids[id(leaf)]
            for path, leaf in collectives.leaf_items(abstract)
            if id(leaf) in ef_ids
        }
        # host-side, summed per leaf as read: peak host RAM is one
        # leaf's (dp_old, *leaf) stack, and no replicated device arrays
        # ever exist (dp_old full-gradient-sized fp32 copies per device
        # would blow HBM on exactly the large-model restores this path
        # exists for)
        stored_ef = checkpointer.engine.storage_leaves_to_host(
            list(ef_full_paths),
            step=step,
            transform=lambda a: np.asarray(a, np.float32).sum(axis=0),
        )
        # zeros for every stack, stored totals overlaid where present:
        # a dp shrink can make leaves shardable that the old degree
        # never quantized (no stored residual), and a checkpoint saved
        # under an exact policy stores none at all — in both cases zero
        # is exactly the pending error those leaves carry
        totals = {
            path: np.zeros(tuple(leaf.shape[1:]), np.float32)
            for path, leaf in collectives.leaf_items(abstract.ef_residual)
        }
        n_restored = 0
        if stored_ef is not None:
            for full, total in stored_ef[1].items():
                totals[ef_full_paths[full]] = total
                n_restored += 1
        logger.info(
            "grad-sync restore at step %d: redistributing "
            "error-feedback residuals across dp=%d (%d/%d stacks "
            "stored, rest zero-initialized)",
            step, self._ef_world, n_restored, len(totals),
        )
        with self.mesh:
            new_ef = {
                path: collectives.materialize_ef_stack(
                    # _ef_world = every replica (slices * in-slice dp on
                    # a two-level mesh): the stack's leading dim
                    totals[path] / float(self._ef_world),
                    self._ef_world,
                    shardings.ef_residual[path],
                )
                for path in totals
            }
        return state.replace(ef_residual=new_ef), step

    def adjust_accum_for_world(self, global_batch: int,
                               per_device_batch: int) -> int:
        """Preserve the global batch across mesh-size changes (reference
        ElasticTrainer's gradient-accumulation adjustment)."""
        data_size = 1
        for axis in self.data_axes:
            data_size *= self.mesh.shape[axis]
        denom = max(1, per_device_batch * data_size)
        self.grad_accum_steps = max(1, global_batch // denom)
        self._jit_step = None  # force re-compile with the new accumulation
        # the elastic path can raise accumulation above 1 long after
        # construction — the fp32-accumulator footprint warning must
        # fire wherever grad_accum_steps becomes effective
        self._warn_fp32_accum_if_needed()
        return self.grad_accum_steps

    # -- live elastic resharding (r22) -------------------------------------

    def rebind_mesh(self, new_mesh):
        """Re-form this trainer around ``new_mesh`` WITHOUT tearing the
        process down (r22 live reshard): restores the originally
        requested grad-sync policy (a dp=1 demotion must not outlive
        the shrink that caused it), re-resolves the sync axes/worlds,
        and invalidates every mesh-derived artifact — shardings, the
        bucket layout (rebuilt through the same deterministic
        ``bucketing.signature()`` path a fresh start takes), tuner
        plans, the comm probe, the jitted programs, and the step-time
        baseline (the reshard gap must not be charged as compute)."""
        self.mesh = new_mesh
        data_axes = tuple(a for a in self.data_axes if a != "slice")
        if (
            new_mesh is not None
            and int(dict(new_mesh.shape).get("slice", 1)) > 1
        ):
            data_axes = ("slice",) + data_axes
        self.data_axes = data_axes
        self.grad_sync = self._grad_sync_requested
        self._sync_axis = None
        self._sync_world = 1
        self._dcn_axis = None
        self._dcn_world = 1
        self._ef_world = 1
        self._grad_layout = None
        self._bucket_layout = None
        self._tuner = None
        self._tuner_plan = None
        self._tuner_decision = None
        with self._demotion_mu:
            self._pending_grad_sync = None
            self._pending_tuner_plan = None
        if self.grad_sync.active and new_mesh is not None:
            self._configure_grad_sync()
        self.state_shardings = None
        self._jit_step = None
        self._jit_init = None
        self._comm_bucket_scope = None
        self._comm_probe = None
        if new_mesh is not None:
            try:
                from dlrover_tpu.observability import commscope

                if commscope.probe_every() > 0:
                    self._comm_probe = commscope.MeshProbe.for_mesh(
                        new_mesh
                    )
            except Exception:  # noqa: BLE001 - telemetry must not
                self._comm_probe = None  # break the transition
        self._step_clock.reset()
        self._step_account.reset()
        self._last_step_ts = None

    def stage_live_reshard(self, axes, reason: str = ""):
        """Stage a live mesh transition (safe from the agent/sentinel
        thread); the training thread applies it at the next step
        boundary — never mid-dispatch."""
        from dlrover_tpu.common.log import logger

        axes = {str(a): int(s) for a, s in dict(axes or {}).items()}
        if not axes:
            return
        with self._demotion_mu:
            self._pending_reshard = {
                "axes": axes, "reason": str(reason or ""),
            }
        logger.info(
            "live reshard to %s staged: applies at the next step "
            "boundary (%s)", axes, reason or "unspecified",
        )

    def live_reshard(self, state, new_axes, *, sample_input, rng=None,
                     survivors=None, donor=None, reason: str = ""):
        """Synchronous in-place mesh transition (r22): plan (gated by
        the r17 measured fit report), pull survivor-held state over the
        existing wire, donor-read only the shards no survivor holds
        from the r13 sealed manifest, rebind this trainer to the new
        mesh and return ``(new_state, report)``.  Raises
        ``parallel.reshard.ReshardRefused`` when the plan cannot be
        honored — the caller falls back to the restart path."""
        from dlrover_tpu.parallel import reshard as _reshard

        old_axes = (
            {str(a): int(s) for a, s in self.mesh.shape.items()}
            if self.mesh is not None else {}
        )
        plan = _reshard.plan_reshard(
            old_axes, new_axes, survivors=survivors, reason=reason
        )
        if donor is None:
            donor = _reshard.donor_engine()
        return _reshard.execute_reshard(
            self, state, plan, sample_input=sample_input, rng=rng,
            donor=donor,
        )

    def _apply_pending_reshard(self, pending, state, batch):
        """Apply one staged live-reshard request at the step boundary:
        reshard onto the new mesh and re-lay the in-flight batch out on
        it.  A refusal logs and keeps the old mesh and state."""
        import numpy as np

        from dlrover_tpu.common.log import logger
        from dlrover_tpu.parallel import reshard as _reshard

        axes = dict((pending or {}).get("axes") or {})
        if not axes:
            return state, batch
        host_batch = jax.tree.map(np.asarray, batch)
        sample = (
            host_batch.get("input_ids")
            if isinstance(host_batch, dict) else None
        )
        if sample is None:
            sample = jax.tree_util.tree_leaves(host_batch)[0]
        try:
            state, report = self.live_reshard(
                state, axes, sample_input=sample,
                reason=str(pending.get("reason", "")),
            )
        except _reshard.ReshardRefused as e:
            logger.warning(
                "staged live reshard to %s refused; continuing on the "
                "current mesh: %s", axes, e,
            )
            return state, batch
        logger.info(
            "live reshard applied at the step boundary: %s -> %s "
            "(%d donor bytes)", report["old_axes"], report["new_axes"],
            report["donor_bytes_read"],
        )
        return state, self.shard_batch(host_batch)

    def _warn_fp32_accum_if_needed(self):
        """r4 behavior change, called out loudly: with grad accumulation
        the accumulator now defaults to fp32 even for low-precision
        grads, re-adding a full-size fp32 pytree.  A previously-fitting
        ~1B single-chip job that OOMs on upgrade should set
        ``accum_dtype=jnp.bfloat16`` to restore the old footprint
        (docs/migration.md)."""
        if (
            self.grad_accum_steps > 1
            and self.grads_dtype is not None
            and self.accum_dtype is None
            and jnp.dtype(self.grads_dtype).itemsize < 4
        ):
            from dlrover_tpu.common.log import logger

            name = jnp.dtype(self.grads_dtype).name
            logger.warning(
                "grad accumulation with grads_dtype=%s now uses an fp32 "
                "accumulator by default (accuracy over memory); pass "
                "accum_dtype=%s to restore the pre-r4 low-precision "
                "accumulator if this no longer fits", name, name,
            )
