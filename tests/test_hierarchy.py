"""Hierarchical multi-slice grad sync (r18): the two-level mesh, the
ICI+DCN bucket chain, the simulated DCN boundary, auto-demotion, the
multi-slice rendezvous, and the elastic-resize EF invariants.

Covers the r18 tentpole on the virtual CPU mesh:

* ``build_slice_mesh`` / ``slice_topology`` / ``axis_fabric`` and the
  ``GradSyncPolicy`` hierarchy fields (``hierarchical``/``dcn_format``);
* the hierarchical bucket chain: bit-identical to the flat
  ``psum_scatter`` path on integer payloads, replicated across slices,
  and error-feedback CONSERVING (exact_total == decoded + sum of
  residuals) through both quantization stages;
* trainer plumbing: two-level configure, the flat combined-axis
  baseline, EF stacks spanning slices × ici_dp, DCN-leg demotion;
* the byte-priced DCN simulator: meter/estimator agreement, off = free;
* elastic resizes under hierarchy: in-slice dp shrink, whole-slice
  leave AND join all keep per-leaf EF residual totals bit-exact;
* ``SlowLinkDiagnostician`` -> ``DcnDemotionHook`` driven from a
  synthetic fabric digest;
* multi-slice rendezvous: slice-contiguous worlds, whole-slice
  truncation, per-slice groups, and the fleet harness verification.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

from jax.sharding import PartitionSpec as P

from dlrover_tpu.parallel import collectives, hierarchy
from dlrover_tpu.parallel.collectives import (
    GradSyncPolicy,
    shard_map_unchecked,
)
from dlrover_tpu.parallel.mesh import (
    FABRIC_DCN,
    FABRIC_ICI,
    MeshConfig,
    SliceTopology,
    axis_fabric,
    build_mesh,
    build_slice_mesh,
    slice_topology,
)
from dlrover_tpu.trainer.train import Trainer


def _env(monkeypatch, **overrides):
    for key, value in overrides.items():
        monkeypatch.setenv(key, value)


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        h = nn.tanh(nn.Dense(32)(x))
        h = nn.tanh(nn.Dense(33)(h))  # odd bias: replicated fallback
        return nn.Dense(1)(h)[..., 0]


def _mse_loss(model):
    def loss_fn(params, batch):
        pred = model.apply({"params": params}, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    return loss_fn


def _batch(n=16, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    y = np.tanh(x[:, 0] * 1.5 - x[:, 1]).astype(np.float32)
    return {"x": x, "y": y}


def _slice_trainer(policy, num_slices=2, dp=2, optimizer=None, **kw):
    model = _MLP()
    devices = jax.devices()[: num_slices * dp]
    mesh = build_slice_mesh(
        num_slices, MeshConfig(dp=dp), devices=devices
    )
    return Trainer(
        model, optimizer or optax.adamw(1e-2), mesh,
        loss_fn=_mse_loss(model), grad_sync=policy, **kw,
    )


def _run(trainer, steps=4, batch=None):
    batch = batch or _batch()
    state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
    sharded = trainer.shard_batch(batch)
    losses = []
    for _ in range(steps):
        state, m = trainer.train_step(state, sharded)
        losses.append(float(jax.device_get(m["loss"])))
    return state, np.asarray(losses)


# ---------------------------------------------------------------------------
# mesh + policy
# ---------------------------------------------------------------------------


class TestSliceMesh:
    def test_two_level_shape_and_topology(self):
        mesh = build_slice_mesh(
            2, MeshConfig(dp=2), devices=jax.devices()[:4]
        )
        shape = dict(mesh.shape)
        assert shape["slice"] == 2 and shape["dp"] == 2
        topo = slice_topology(mesh)
        assert topo == SliceTopology(num_slices=2, ici_dp=2)
        assert topo.world == 4

    def test_four_slices_on_eight_devices(self):
        mesh = build_slice_mesh(4, MeshConfig(dp=2))
        assert dict(mesh.shape)["slice"] == 4
        assert slice_topology(mesh).world == 8

    def test_single_slice_is_flat(self):
        mesh = build_slice_mesh(
            1, MeshConfig(dp=4), devices=jax.devices()[:4]
        )
        assert slice_topology(mesh) is None

    def test_flat_mesh_has_no_topology(self):
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        assert slice_topology(mesh) is None

    def test_indivisible_devices_rejected(self):
        with pytest.raises(ValueError, match="not divisible"):
            build_slice_mesh(3, devices=jax.devices()[:4])

    def test_slice_count_env_builds_two_level_mesh(self, monkeypatch):
        """An operator's DLROVER_TPU_SLICE_COUNT takes effect through
        the standard build_mesh entry point — no code change needed to
        declare a multi-slice topology."""
        _env(monkeypatch, DLROVER_TPU_SLICE_COUNT="2")
        mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:4])
        topo = slice_topology(mesh)
        assert topo == SliceTopology(num_slices=2, ici_dp=2)

    def test_slice_count_env_incompatible_falls_back_flat(
        self, monkeypatch
    ):
        # dp=4 cannot fit inside a 2-device slice: loud flat fallback,
        # never a crashed job
        _env(monkeypatch, DLROVER_TPU_SLICE_COUNT="2")
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        assert slice_topology(mesh) is None
        assert dict(mesh.shape)["dp"] == 4

    def test_slice_count_env_indivisible_falls_back_flat(
        self, monkeypatch
    ):
        _env(monkeypatch, DLROVER_TPU_SLICE_COUNT="3")
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        assert slice_topology(mesh) is None

    def test_axis_fabric(self):
        assert axis_fabric("slice") == FABRIC_DCN
        assert axis_fabric("dp") == FABRIC_ICI
        assert axis_fabric(("dp", "fsdp")) == FABRIC_ICI
        # one DCN hop bottlenecks a combined collective
        assert axis_fabric(("slice", "dp")) == FABRIC_DCN


class TestPolicyHierarchyFields:
    def test_dcn_format_validated(self):
        with pytest.raises(ValueError, match="dcn_format"):
            GradSyncPolicy(mode="int8_sharded", dcn_format="fp8")

    def test_resolve_fills_from_env(self, monkeypatch):
        _env(monkeypatch, DLROVER_TPU_GRAD_HIERARCHICAL="0",
             DLROVER_TPU_GRAD_DCN_FORMAT="blockwise")
        pol = GradSyncPolicy(mode="int8_sharded").resolve()
        assert pol.hierarchical is False
        assert pol.dcn_format == "blockwise"

    def test_resolve_defaults(self):
        pol = GradSyncPolicy(mode="int8_sharded").resolve()
        assert pol.hierarchical is True
        assert pol.dcn_format == "int4"

    def test_dcn_policy_none_for_exact_base(self):
        assert GradSyncPolicy(
            mode="exact_sharded", dcn_format="int4"
        ).dcn_policy() is None

    def test_dcn_policy_none_for_exact_format(self):
        assert GradSyncPolicy(
            mode="int8_sharded", dcn_format="exact"
        ).dcn_policy() is None

    def test_dcn_policy_mode(self):
        pol = GradSyncPolicy(mode="int8_sharded", dcn_format="int4")
        assert pol.dcn_policy().mode == "int4"
        assert pol.dcn_policy().block_size == pol.block_size

    def test_demotion_ladder(self):
        assert hierarchy.demoted_dcn_format("int8") == "int4"
        assert hierarchy.demoted_dcn_format("blockwise") == "int4"
        assert hierarchy.demoted_dcn_format("int4") is None
        assert hierarchy.demoted_dcn_format("exact") is None


# ---------------------------------------------------------------------------
# the hierarchical bucket chain
# ---------------------------------------------------------------------------


def _chain_outputs(mesh, policy, per_dev, ici_world, dcn_world, width):
    """Run the hierarchical chain on every device; returns (chunks,
    residuals) stacked device-major (slice-major row order)."""

    def body(buf):
        chunk, resid = collectives.hierarchical_bucket_reduce_scatter(
            buf.reshape(ici_world, width), policy, "dp", "slice",
            ici_world, dcn_world,
        )
        if resid is None:
            resid = jnp.zeros((ici_world, width), jnp.float32)
        return chunk[None], resid[None]

    fn = jax.jit(shard_map_unchecked(
        body, mesh=mesh,
        in_specs=P(("slice", "dp")),
        out_specs=(P(("slice", "dp")), P(("slice", "dp"))),
    ))
    chunks, resids = fn(per_dev)
    return np.asarray(chunks), np.asarray(resids)


class TestHierarchicalChain:
    def setup_method(self):
        self.mesh = build_slice_mesh(
            2, MeshConfig(dp=2), devices=jax.devices()[:4]
        )
        self.W, self.I, self.S = 4, 2, 2

    def test_exact_chain_bit_identical_to_flat_on_integers(self):
        width = 24
        rng = np.random.default_rng(3)
        ints = rng.integers(-40, 40, size=(self.W, self.I * width))
        per_dev = jnp.asarray(ints.astype(np.float32))
        exact = GradSyncPolicy(mode="exact_sharded", bucket_mb=4.0)
        chunks, _ = _chain_outputs(
            self.mesh, exact, per_dev, self.I, self.S, width
        )
        want = ints.sum(axis=0).astype(np.float32).reshape(
            self.I, width
        )
        # device (s, i) holds chunk i of the exact global sum,
        # identically on both slices — bit-exact (integer fp32 sums
        # are order-independent)
        for dev in range(self.W):
            np.testing.assert_array_equal(chunks[dev], want[dev % self.I])

    def test_quantized_chain_replicated_across_slices(self):
        width = 256
        rng = np.random.default_rng(4)
        per_dev = jnp.asarray(
            rng.standard_normal((self.W, self.I * width))
            .astype(np.float32)
        )
        pol = GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                             dcn_format="int4")
        chunks, _ = _chain_outputs(
            self.mesh, pol, per_dev, self.I, self.S, width
        )
        # slices decode the SAME wire payload: chunk i identical on
        # slice 0 and slice 1, bitwise
        for i in range(self.I):
            np.testing.assert_array_equal(chunks[i], chunks[self.I + i])

    @pytest.mark.parametrize("dcn_format", ["int8", "int4", "blockwise"])
    def test_error_feedback_conserved_through_both_stages(
        self, dcn_format
    ):
        """The EF contract across the two quantization stages: the
        exact global sum equals the decoded output plus the sum of
        EVERY device's residual block — no error is lost between the
        ICI codec, the DCN reduce-scatter, and the quantized return
        gather."""
        width = 256
        rng = np.random.default_rng(5)
        vals = rng.standard_normal(
            (self.W, self.I * width)
        ).astype(np.float32)
        pol = GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                             dcn_format=dcn_format)
        chunks, resids = _chain_outputs(
            self.mesh, pol, jnp.asarray(vals), self.I, self.S, width
        )
        exact_total = vals.sum(axis=0).reshape(self.I, width)
        # decoded output: one copy per slice — take slice 0's chunks
        decoded = chunks[: self.I]
        resid_total = resids.sum(axis=0)  # (I, width) summed over devices
        np.testing.assert_allclose(
            decoded + resid_total, exact_total, rtol=0, atol=2e-4
        )

    def test_degenerate_single_slice_skips_dcn_stage(self):
        """dcn_world=1 returns the stage-1 result untouched — the
        program IS the flat r14 chain."""
        width = 64
        rng = np.random.default_rng(6)
        vals = jnp.asarray(
            rng.standard_normal((4, width)).astype(np.float32)
        )
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        pol = GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                             dcn_format="int4")

        def hier_body(buf):
            chunk, resid = (
                collectives.hierarchical_bucket_reduce_scatter(
                    buf.reshape(4, width // 4), pol, "dp", "slice",
                    4, 1,
                )
            )
            return chunk[None], resid[None]

        def flat_body(buf):
            chunk, resid = collectives.bucket_reduce_scatter(
                buf.reshape(4, width // 4), pol, "dp", 4
            )
            return chunk[None], resid[None]

        per_dev = vals  # row d = device d's flattened (4, width//4) buf
        h = jax.jit(shard_map_unchecked(
            hier_body, mesh=mesh, in_specs=P("dp"),
            out_specs=(P("dp"), P("dp")),
        ))(per_dev)
        f = jax.jit(shard_map_unchecked(
            flat_body, mesh=mesh, in_specs=P("dp"),
            out_specs=(P("dp"), P("dp")),
        ))(per_dev)
        np.testing.assert_array_equal(np.asarray(h[0]), np.asarray(f[0]))
        np.testing.assert_array_equal(np.asarray(h[1]), np.asarray(f[1]))


# ---------------------------------------------------------------------------
# trainer plumbing
# ---------------------------------------------------------------------------


class TestTrainerHierarchy:
    def test_configure_two_level(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           hierarchical=True, dcn_format="int4")
        )
        info_needed = {"hierarchical": True, "ici_axis": "dp",
                       "ici_world": 2, "dcn_axis": "slice",
                       "num_slices": 2, "dcn_format": "int4"}
        _run(tr, steps=1)
        summary = tr.grad_sync_summary()
        for key, want in info_needed.items():
            assert summary[key] == want
        assert "slice" in tr.data_axes

    def test_flat_baseline_uses_combined_axis(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           hierarchical=False)
        )
        assert tr._sync_axis == ("slice", "dp")  # noqa: SLF001
        assert tr._sync_world == 4  # noqa: SLF001
        state, losses = _run(tr, steps=2)
        assert np.isfinite(losses).all()
        summary = tr.grad_sync_summary()
        assert summary["hierarchical"] is False
        assert summary["flat_axes"] == ("slice", "dp")

    def test_hierarchical_requires_buckets(self):
        with pytest.raises(ValueError, match="bucket"):
            _slice_trainer(
                GradSyncPolicy(mode="int8_sharded", bucket_mb=0.0,
                               hierarchical=True)
            )

    def test_fsdp_still_rejected_on_slice_mesh(self):
        model = _MLP()
        mesh = build_slice_mesh(
            2, MeshConfig(dp=1, fsdp=2), devices=jax.devices()[:4]
        )
        with pytest.raises(ValueError, match="shard params"):
            Trainer(model, optax.adamw(1e-2), mesh,
                    loss_fn=_mse_loss(model),
                    grad_sync="int8_sharded")

    def test_ef_stack_spans_all_replicas(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0)
        )
        state, _ = _run(tr, steps=1)
        assert tr._ef_world == 4  # noqa: SLF001
        for leaf in state.ef_residual.values():
            assert leaf.shape[0] == 4

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_quantized_hierarchical_tracks_exact(self, seed):
        """Closeness on random payloads (the bit-identity of the chain
        is asserted on integers in TestHierarchicalChain).  The int4 DCN
        leg costs the run a fraction of a step, and on a curve that
        still halves per step a fraction of a step is 20% of the
        current loss: so the gap is held against the loss the run
        started from, at every step (largest seen: 2.9%, 4 seeds)."""
        batch = _batch(seed=seed)
        exact = _slice_trainer(
            GradSyncPolicy(mode="exact_sharded", bucket_mb=4.0)
        )
        _, l_exact = _run(exact, steps=6, batch=batch)
        quant = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           dcn_format="int4")
        )
        _, l_quant = _run(quant, steps=6, batch=batch)
        assert np.isfinite(l_quant).all()
        assert l_quant[-1] < 0.7 * l_quant[0]
        assert np.abs(l_quant - l_exact).max() < 0.05 * l_exact[0]

    def test_params_replicated_bit_identical(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           dcn_format="int4")
        )
        state, _ = _run(tr, steps=3)
        for leaf in jax.tree.leaves(state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            for other in shards[1:]:
                np.testing.assert_array_equal(shards[0], other)

    def test_apply_dcn_demotion_ladder(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           dcn_format="int8")
        )
        batch = _batch()
        state = tr.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = tr.shard_batch(batch)
        state, _ = tr.train_step(state, sharded)
        assert tr.apply_dcn_demotion() == "int4"
        # STAGED, not applied: the sentinel thread must never null the
        # jitted step out from under an in-flight dispatch
        assert tr.grad_sync.dcn_format == "int8"
        assert tr._jit_step is not None  # noqa: SLF001
        # at the floor (the ladder reads the staged policy): no further
        assert tr.apply_dcn_demotion() is None
        # the next step — on the training thread — applies + recompiles
        state, m = tr.train_step(state, sharded)
        assert tr.grad_sync.dcn_format == "int4"
        assert np.isfinite(float(jax.device_get(m["loss"])))

    def test_demotion_noop_on_flat_mesh(self):
        model = _MLP()
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        tr = Trainer(model, optax.adamw(1e-2), mesh,
                     loss_fn=_mse_loss(model),
                     grad_sync=GradSyncPolicy(mode="int8_sharded"))
        assert tr.apply_dcn_demotion() is None

    def test_demotion_noop_for_exact_leg(self):
        tr = _slice_trainer(
            GradSyncPolicy(mode="exact_sharded", bucket_mb=4.0)
        )
        assert tr.apply_dcn_demotion() is None


# ---------------------------------------------------------------------------
# the simulated DCN boundary
# ---------------------------------------------------------------------------


class TestDcnSimulator:
    def _step_bytes(self, policy, monkeypatch, steps=2):
        _env(monkeypatch, DLROVER_TPU_SLICE_SIM="1",
             DLROVER_TPU_SLICE_SIM_GBPS="100.0",
             DLROVER_TPU_SLICE_SIM_LAT_US="0")
        hierarchy.reset_meter()
        tr = _slice_trainer(policy)
        batch = _batch()
        state = tr.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = tr.shard_batch(batch)
        state, m = tr.train_step(state, sharded)
        jax.block_until_ready(m["loss"])
        hierarchy.reset_meter()
        for _ in range(steps):
            state, m = tr.train_step(state, sharded)
        jax.block_until_ready(m["loss"])
        return tr, hierarchy.meter().bytes_for("dcn") / steps / 4

    def test_meter_matches_estimator(self, monkeypatch):
        topo = SliceTopology(num_slices=2, ici_dp=2)
        flat_tr, flat_b = self._step_bytes(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           hierarchical=False), monkeypatch,
        )
        hier_tr, hier_b = self._step_bytes(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           hierarchical=True, dcn_format="int4"),
            monkeypatch,
        )
        est_flat = hierarchy.estimate_tiered_bytes(
            flat_tr._bucket_layout, flat_tr.grad_sync,  # noqa: SLF001
            topo, hierarchical=False,
        )
        est_hier = hierarchy.estimate_tiered_bytes(
            hier_tr._bucket_layout, hier_tr.grad_sync,  # noqa: SLF001
            topo, hierarchical=True,
        )
        assert flat_b == est_flat["dcn_bytes"]
        assert hier_b == est_hier["dcn_bytes"]
        # the acceptance ratio: DCN bytes cut by >= the in-slice dp
        # factor (here far more: int4 + 1/ici of the volume)
        assert flat_b / hier_b >= topo.ici_dp
        # flat has no ICI tier; hierarchical moves most bytes there
        assert est_flat["ici_bytes"] == 0
        assert est_hier["ici_bytes"] > est_hier["dcn_bytes"]

    def test_metadata_itemized(self):
        topo = SliceTopology(num_slices=2, ici_dp=2)
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           dcn_format="blockwise")
        )
        tr.create_state(jax.random.PRNGKey(0), _batch()["x"])
        est = hierarchy.estimate_tiered_bytes(
            tr._bucket_layout, tr.grad_sync, topo,  # noqa: SLF001
            hierarchical=True,
        )
        assert est["ici_metadata_bytes"] > 0
        assert est["dcn_metadata_bytes"] > 0
        for row in est["per_bucket"]:
            assert row["dcn_bytes"] < row["ici_bytes"]

    def test_sim_off_tolls_nothing(self, monkeypatch):
        monkeypatch.delenv("DLROVER_TPU_SLICE_SIM", raising=False)
        hierarchy.reset_meter()
        tr = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           hierarchical=False)
        )
        _run(tr, steps=2)
        assert hierarchy.meter().bytes_for("dcn") == 0

    def test_ici_axis_never_tolled(self, monkeypatch):
        _env(monkeypatch, DLROVER_TPU_SLICE_SIM="1")
        model = _MLP()
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        hierarchy.reset_meter()
        tr = Trainer(model, optax.adamw(1e-2), mesh,
                     loss_fn=_mse_loss(model),
                     grad_sync=GradSyncPolicy(mode="int8_sharded",
                                              bucket_mb=4.0))
        _run(tr, steps=1)
        assert hierarchy.meter().bytes_for("dcn") == 0


# ---------------------------------------------------------------------------
# elastic resizes under hierarchy (satellite: r6/r14 extension)
# ---------------------------------------------------------------------------


class TestElasticResizeHierarchy:
    def _save(self, state, ckpt_dir, scope, step):
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        ckpt = Checkpointer(str(ckpt_dir), scope=scope,
                            async_snapshot=False)
        ckpt.save_checkpoint(step, state, StorageType.DISK)
        assert ckpt.wait_latest_checkpoint(timeout=120)
        ckpt.close()

    def _restore(self, trainer, ckpt_dir, scope, batch):
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        ckpt = Checkpointer(str(ckpt_dir), scope=scope)
        restored, step = trainer.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        ckpt.engine.unlink_memory()
        ckpt.close()
        return restored, step

    def _ef_totals(self, state):
        return {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in state.ef_residual.items()
        }

    def _train_and_save(self, trainer, tmp_path, scope, batch):
        state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = trainer.shard_batch(batch)
        for _ in range(3):
            state, _ = trainer.train_step(state, sharded)
        totals = self._ef_totals(state)
        self._save(state, tmp_path, scope, 3)
        return totals

    @pytest.mark.parametrize(
        "dst_kind",
        ["in_slice_shrink", "whole_slice_leave", "whole_slice_join"],
    )
    def test_resize_keeps_ef_totals_bit_exact(self, tmp_path, dst_kind):
        """Power-of-two topology changes preserve per-leaf EF residual
        totals bit-exactly: dp shrink WITHIN each slice (2x2 -> 2x1),
        whole-slice leave (2x2 -> flat dp=2), and whole-slice join
        (flat dp=2 -> 2x2) — the r6/r14 invariant extended to the
        two-level EF world."""
        batch = _batch()
        policy = GradSyncPolicy(mode="int4_sharded", bucket_mb=4.0,
                                dcn_format="int4")
        if dst_kind == "whole_slice_join":
            model = _MLP()
            src = Trainer(
                model, optax.adamw(1e-2),
                build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2]),
                loss_fn=_mse_loss(model),
                grad_sync=GradSyncPolicy(mode="int4_sharded",
                                         bucket_mb=4.0),
            )
        else:
            src = _slice_trainer(policy)
        # scope names carry the parametrization: shm segments are keyed
        # by scope, and a stale segment from the previous case must not
        # shadow this case's disk checkpoint
        totals = self._train_and_save(
            src, tmp_path, f"hsrc_{dst_kind}", batch
        )

        if dst_kind == "in_slice_shrink":
            # each slice keeps its membership but halves its dp: the
            # sync runs over the slice axis alone (ici world 1)
            dst = _slice_trainer(policy, num_slices=2, dp=1)
            expect_world = 2
        elif dst_kind == "whole_slice_leave":
            model = _MLP()
            dst = Trainer(
                model, optax.adamw(1e-2),
                build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2]),
                loss_fn=_mse_loss(model),
                grad_sync=GradSyncPolicy(mode="int4_sharded",
                                         bucket_mb=4.0),
            )
            expect_world = 2
        else:  # whole_slice_join: a second slice arrives
            dst = _slice_trainer(policy)
            expect_world = 4
        restored, step = self._restore(
            dst, tmp_path, f"hdst_{dst_kind}", batch
        )
        assert restored is not None and step == 3
        assert dst._ef_world == expect_world  # noqa: SLF001
        restored_totals = self._ef_totals(restored)
        for key, total in totals.items():
            np.testing.assert_array_equal(restored_totals[key], total)
        for leaf in restored.ef_residual.values():
            assert leaf.shape[0] == expect_world
        # training continues on the new topology
        state2, m = dst.train_step(restored, dst.shard_batch(batch))
        assert np.isfinite(float(jax.device_get(m["loss"])))


# ---------------------------------------------------------------------------
# auto-demotion from a synthetic fabric digest (satellite)
# ---------------------------------------------------------------------------


def _slice_fx(lat_slice, bw_slice, lat_dp=2.0, bw_dp=3.0):
    from dlrover_tpu.observability.commscope import DIGEST_BW, DIGEST_LAT

    return {
        DIGEST_LAT + "slice": lat_slice, DIGEST_BW + "slice": bw_slice,
        DIGEST_LAT + "dp": lat_dp, DIGEST_BW + "dp": bw_dp,
    }


class TestDcnDemotionHook:
    def _diagnose(self, monkeypatch, degrade_axis="slice",
                  trainer=None, enabled=True, holderless=False):
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import (
            SlowLinkDiagnostician,
        )

        _env(monkeypatch,
             DLROVER_TPU_SENTINEL_MIN_SAMPLES="2",
             DLROVER_TPU_SENTINEL_CONSECUTIVE="1",
             DLROVER_TPU_HIER_DEMOTION="1" if enabled else "0")
        if trainer is None and not holderless:
            trainer = _slice_trainer(
                GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                               dcn_format="int8")
            )
        hook = (
            hierarchy.DcnDemotionHook() if holderless
            else hierarchy.DcnDemotionHook(trainer)
        )
        store = TimeSeriesStore()
        base = time.time() - 12
        for i in range(10):
            lat = 9000.0 if i >= 5 else 2.0
            digest = (
                _slice_fx(lat, 3.0) if degrade_axis == "slice"
                else _slice_fx(2.0, 3.0, lat_dp=lat)
            )
            store.record_digest(0, digest, ts=base + i)
        diag = SlowLinkDiagnostician(
            store, res_s=1.0, demotion_hook=hook
        )
        obs = diag.observe()
        return trainer, hook, obs

    def test_dcn_breach_demotes_from_synthetic_digest(
        self, monkeypatch
    ):
        trainer, hook, obs = self._diagnose(monkeypatch)
        assert obs.observed
        assert obs.extra["axis"] == "slice"
        assert obs.extra["dcn_demoted_to"] == "int4"
        # staged for the training thread to apply at the next step
        assert trainer._pending_grad_sync.dcn_format == "int4"  # noqa: SLF001
        assert hook.demotions == 1
        assert "demoted to int4" in obs.detail

    def test_demotion_counted_in_metrics(self, monkeypatch):
        from dlrover_tpu.observability import metrics as obs_metrics

        def total():
            snap = obs_metrics.registry().snapshot()
            return sum(
                snap.get("counters", {})
                .get("dlrover_tpu_hier_dcn_demotions_total", {})
                .values()
            )

        before = total()
        self._diagnose(monkeypatch)
        assert total() == before + 1

    def test_ici_breach_never_demotes(self, monkeypatch):
        trainer, hook, obs = self._diagnose(
            monkeypatch, degrade_axis="dp"
        )
        assert obs.observed and obs.extra["axis"] == "dp"
        assert obs.extra["dcn_demoted_to"] is None
        assert trainer.grad_sync.dcn_format == "int8"
        assert hook.demotions == 0

    def test_demotion_killswitch(self, monkeypatch):
        trainer, hook, obs = self._diagnose(monkeypatch, enabled=False)
        assert obs.observed
        assert trainer.grad_sync.dcn_format == "int8"
        assert hook.demotions == 0

    def test_holderless_hook_resolves_registered_trainer(
        self, monkeypatch
    ):
        """The production wiring: register_sentinels constructs the
        hook WITHOUT a holder; a hierarchical trainer registered as
        the process demotion target is resolved at breach time."""
        trainer = _slice_trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=4.0,
                           dcn_format="int8")
        )
        # _configure_grad_sync registered the trainer; prove the
        # holder-less hook (what register_sentinels builds) finds it
        assert hierarchy.demotion_target() is trainer
        _, hook, obs = self._diagnose(
            monkeypatch, trainer=None, holderless=True
        )
        assert obs.observed
        assert obs.extra["dcn_demoted_to"] == "int4"
        assert trainer._pending_grad_sync.dcn_format == "int4"  # noqa: SLF001
        hierarchy.register_demotion_target(None)

    def test_holderless_hook_noops_without_target(self, monkeypatch):
        hierarchy.register_demotion_target(None)
        _env(monkeypatch, DLROVER_TPU_HIER_DEMOTION="1")
        hook = hierarchy.DcnDemotionHook()
        assert hook("slice", "lat_us", {}) is None
        assert hook.demotions == 0

    def test_register_sentinels_wires_the_hook(self, monkeypatch):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import (
            SlowLinkDiagnostician,
            register_sentinels,
        )

        sentinels = register_sentinels(
            DiagnosisManager(), TimeSeriesStore()
        )
        slow = [
            s for s in sentinels
            if isinstance(s, SlowLinkDiagnostician)
        ]
        assert slow and isinstance(
            slow[0]._demotion_hook,  # noqa: SLF001
            hierarchy.DcnDemotionHook,
        )

    def test_broken_holder_never_breaks_diagnosis(self, monkeypatch):
        class Broken:
            def apply_dcn_demotion(self):
                raise RuntimeError("boom")

        _env(monkeypatch, DLROVER_TPU_HIER_DEMOTION="1")
        hook = hierarchy.DcnDemotionHook(Broken())
        assert hook("slice", "lat_us", {}) is None


# ---------------------------------------------------------------------------
# multi-slice rendezvous (satellite)
# ---------------------------------------------------------------------------


class TestMultiSliceRendezvous:
    def _manager(self, min_nodes, max_nodes, node_unit,
                 waiting_timeout=0.05):
        from dlrover_tpu.master.rdzv_manager import (
            ElasticTrainingRendezvousManager,
        )

        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(
            min_nodes, max_nodes, waiting_timeout, node_unit
        )
        return mgr

    def _join(self, mgr, node_id, slice_id):
        mgr.add_alive_node(node_id)
        mgr.join_rendezvous(
            node_id, node_rank=node_id, slice_id=slice_id
        )

    def test_world_carries_slice_ids_and_groups(self):
        mgr = self._manager(4, 4, node_unit=2)
        for node in range(4):
            self._join(mgr, node, slice_id=node // 2)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 4
        assert {meta.slice_id for meta in world.values()} == {0, 1}
        groups = mgr.slice_groups()
        assert groups == {0: [0, 1], 1: [2, 3]}
        # slice-contiguous ranks: each group is one unbroken range
        for ranks in groups.values():
            assert ranks == list(range(ranks[0], ranks[0] + len(ranks)))

    def test_partial_slice_truncated_to_whole_slices(self):
        mgr = self._manager(2, 4, node_unit=2, waiting_timeout=0.05)
        self._join(mgr, 0, slice_id=0)
        self._join(mgr, 1, slice_id=0)
        self._join(mgr, 2, slice_id=1)  # slice 1 half-joined
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 2
        assert {m.node_id for m in world.values()} == {0, 1}

    def test_partial_slice_sorted_first_does_not_displace_complete(
        self,
    ):
        """A half slice with the SMALLEST slice_id must not push a
        complete slice's member out of the sealed round."""
        mgr = self._manager(2, 4, node_unit=2, waiting_timeout=0.05)
        self._join(mgr, 0, slice_id=0)  # slice 0: one of two
        self._join(mgr, 1, slice_id=1)
        self._join(mgr, 2, slice_id=1)  # slice 1 complete
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(1)
        assert len(world) == 2
        assert {m.node_id for m in world.values()} == {1, 2}

    def test_oversubscribed_slice_capped_at_unit_multiple(self):
        """A slice with MORE waiters than its node_unit (e.g. a
        restarted host re-joined under a new node_id beside its stale
        entry) contributes only a node_unit multiple — the extras must
        not leak into the world and break another slice."""
        mgr = self._manager(4, 8, node_unit=2, waiting_timeout=0.05)
        for node in (0, 1, 2):  # slice 0 oversubscribed: 3 waiters
            self._join(mgr, node, slice_id=0)
        self._join(mgr, 3, slice_id=1)
        self._join(mgr, 4, slice_id=1)  # slice 1 complete: 2 waiters
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 4
        by_slice = {}
        for meta in world.values():
            by_slice.setdefault(meta.slice_id, []).append(meta.node_id)
        assert sorted(by_slice[0]) == [0, 1]  # capped at node_unit
        assert sorted(by_slice[1]) == [3, 4]  # slice 1 intact

    def test_max_nodes_path_honors_whole_slices(self):
        """Raw waiting reaching max_nodes must NOT instant-seal slice
        fragments: with only 2 whole-slice-usable nodes the manager
        waits out the timeout rule and seals the complete slice."""
        mgr = self._manager(2, 4, node_unit=2, waiting_timeout=0.05)
        self._join(mgr, 0, slice_id=0)
        self._join(mgr, 1, slice_id=0)  # slice 0 complete
        self._join(mgr, 2, slice_id=1)  # half
        self._join(mgr, 3, slice_id=2)  # half
        # waiting=4 >= max_nodes=4, but whole-slice usable is 2: the
        # instant path must decline (no world before the timeout)
        round_, _, world = mgr.get_comm_world(0)
        assert world == {}
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(0)
        assert {m.node_id for m in world.values()} == {0, 1}

    def test_max_nodes_path_seals_whole_slices_instantly(self):
        mgr = self._manager(4, 4, node_unit=2, waiting_timeout=30.0)
        for node in range(4):
            self._join(mgr, node, slice_id=node // 2)
        # all slices whole: seals without waiting out the timeout
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 4

    def test_fleet_rejects_indivisible_slices(self):
        from dlrover_tpu.diagnosis.fleet_bench import (
            FleetConfig,
            run_mode,
        )

        with pytest.raises(ValueError, match="not divisible"):
            run_mode(FleetConfig(agents=10, slices=3))

    def test_single_slice_keeps_legacy_truncation(self):
        mgr = self._manager(2, 4, node_unit=2, waiting_timeout=0.05)
        for node in range(3):
            self._join(mgr, node, slice_id=0)
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 2

    def test_fleet_harness_multi_slice(self):
        from dlrover_tpu.diagnosis.fleet_bench import (
            FleetConfig,
            run_mode,
        )

        cfg = FleetConfig(
            agents=8, slices=2, mode="longpoll", stagger_s=0.2,
            barriers=1, barrier_delay_s=0.2, heartbeats=1,
            shards_per_agent=1, straggler_s=0.2,
            agent_deadline_s=60.0,
        )
        result = run_mode(cfg)
        assert result["agent_error_count"] == 0
        report = result["slices"]
        assert report["ok"], report
        assert report["count"] == 2
        assert report["group_sizes"] == {0: 4, 1: 4}
