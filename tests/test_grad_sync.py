"""Communication-efficient data-parallel sync (``parallel/collectives``).

Covers the r6 tentpole numerics on the virtual CPU mesh:

* blockwise int8 quantization properties and the error-feedback
  invariant (dropped rounding error == carried residual, and repeated
  sync with EF converges to the exact mean gradient);
* the quantized + sharded policies against the exact GSPMD baseline
  (loss parity over a short training loop);
* sharded (ZeRO-1) vs replicated weight update equivalence — bitwise on
  integer-valued gradients, to the last bits in fp32 on random ones,
  storage-rounding-tight for bf16 moments — across dp2/dp4;
* elasticity: flash-checkpoint save -> restore across a dp-degree
  change round-trips dp-sharded moments and redistributes the
  error-feedback stacks (total preserved);
* mesh gates and the bytes-on-wire estimate.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

from dlrover_tpu.parallel import collectives
from dlrover_tpu.parallel.collectives import (
    GradLayout,
    GradSyncPolicy,
    blockwise_dequantize,
    blockwise_quantize,
    estimate_sync_bytes,
    quantized_reduce_scatter,
    shard_dim_for,
    shard_map_unchecked,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer


class _MLP(nn.Module):
    """Tiny regression model with a deliberately odd-sized layer so the
    non-divisible (replicated-update) fallback path is exercised."""

    @nn.compact
    def __call__(self, x):
        h = nn.tanh(nn.Dense(32)(x))
        h = nn.tanh(nn.Dense(33)(h))  # bias (33,): not divisible by dp
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


def _trainer(mode, dp, optimizer=None, **kw):
    model = _MLP()
    mesh = build_mesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])
    return Trainer(
        model,
        optimizer or optax.adamw(1e-2),
        mesh,
        loss_fn=_mse_loss(model),
        grad_sync=mode,
        **kw,
    )


def _run(trainer, steps=5, seed=0):
    batch = _batch(seed=seed)
    state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
    sharded = trainer.shard_batch(batch)
    losses = []
    for _ in range(steps):
        state, m = trainer.train_step(state, sharded)
        losses.append(float(jax.device_get(m["loss"])))
    return state, losses


def _host_tree(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


class TestPolicy:
    def test_parse_modes(self):
        assert GradSyncPolicy.parse(None).mode == "exact"
        assert not GradSyncPolicy.parse("exact").active
        p = GradSyncPolicy.parse("int8_sharded")
        assert p.quantized and p.sharded_update and p.active
        assert GradSyncPolicy.parse("exact_sharded").sharded_update
        assert not GradSyncPolicy.parse("int8").sharded_update
        same = GradSyncPolicy(mode="int8")
        assert GradSyncPolicy.parse(same) is same

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError):
            GradSyncPolicy(mode="fp4")
        with pytest.raises(ValueError):
            GradSyncPolicy(rounding="truncate")
        with pytest.raises(TypeError):
            GradSyncPolicy.parse(42)

    def test_shard_dim_for(self):
        assert shard_dim_for((8, 3), 4) == 0
        assert shard_dim_for((3, 8), 4) == 1
        assert shard_dim_for((3, 5), 4) is None
        assert shard_dim_for((), 4) is None
        assert shard_dim_for((2,), 4) is None  # smaller than world
        assert shard_dim_for((8,), 1) is None  # world 1: nothing to do


class TestQuantization:
    def test_nearest_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        blocks = jnp.asarray(
            rng.standard_normal((7, 64)).astype(np.float32)
        )
        q, scale = blockwise_quantize(blocks, "nearest")
        deq = blockwise_dequantize(q, scale)
        err = np.abs(np.asarray(blocks) - np.asarray(deq))
        bound = np.asarray(scale) / 2 + 1e-7
        assert (err <= bound).all()

    def test_zero_block_roundtrips_to_zero(self):
        blocks = jnp.zeros((3, 32), jnp.float32)
        q, scale = blockwise_quantize(blocks, "nearest")
        assert np.asarray(scale).max() == 0.0
        np.testing.assert_array_equal(
            np.asarray(blockwise_dequantize(q, scale)), 0.0
        )

    def test_stochastic_needs_key_and_is_bounded(self):
        blocks = jnp.asarray(
            np.random.default_rng(1)
            .standard_normal((4, 32))
            .astype(np.float32)
        )
        with pytest.raises(ValueError):
            blockwise_quantize(blocks, "stochastic")
        q, scale = blockwise_quantize(
            blocks, "stochastic", jax.random.PRNGKey(0)
        )
        err = np.abs(
            np.asarray(blocks)
            - np.asarray(blockwise_dequantize(q, scale))
        )
        # stochastic rounding moves at most one quantization step
        assert (err <= np.asarray(scale) + 1e-7).all()


class TestErrorFeedbackInvariant:
    def _mesh(self, dp):
        return build_mesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])

    def test_dropped_error_equals_carried_residual(self):
        """sum_r t_r == all-gathered(shards) + sum_r residual_r: the
        quantization error the reduce dropped is exactly what the
        replicas carry forward."""
        from jax.sharding import PartitionSpec as P

        dp = 4
        mesh = self._mesh(dp)
        rng = np.random.default_rng(0)
        t = rng.standard_normal((dp, 8, 6)).astype(np.float32)

        def body(tl):
            shard, resid = quantized_reduce_scatter(
                tl[0], 0, "dp", dp, block_size=16
            )
            return shard[None], resid[None]

        fn = shard_map_unchecked(
            body, mesh=mesh, in_specs=P("dp"),
            out_specs=(P("dp"), P("dp")),
        )
        shards, resids = jax.jit(fn)(t)
        true_sum = t.sum(axis=0)
        got = np.asarray(shards).reshape(8, 6) + np.asarray(resids).sum(
            axis=0
        )
        np.testing.assert_allclose(got, true_sum, rtol=1e-5, atol=1e-6)

    def test_repeated_sync_with_ef_converges_to_exact_mean(self):
        """Constant per-replica gradients: the running mean of the
        EF-corrected quantized sync approaches the exact mean — the
        "matches the exact all-reduce within rtol after error feedback"
        acceptance property."""
        from jax.sharding import PartitionSpec as P

        dp = 4
        mesh = self._mesh(dp)
        rng = np.random.default_rng(1)
        t = rng.standard_normal((dp, 16, 4)).astype(np.float32)
        rounds = 8

        def body(tl):
            g = tl[0]
            resid = jnp.zeros_like(g)
            acc = jnp.zeros((16 // dp, 4), jnp.float32)

            def one(carry, _):
                resid, acc = carry
                shard, resid = quantized_reduce_scatter(
                    g + resid, 0, "dp", dp, block_size=16
                )
                return (resid, acc + shard), None

            (resid, acc), _ = jax.lax.scan(
                one, (resid, acc), None, length=rounds
            )
            return acc[None]

        fn = shard_map_unchecked(
            body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")
        )
        acc = np.asarray(jax.jit(fn)(t)).reshape(16, 4) / rounds
        exact = t.sum(axis=0)
        single, _ = jax.jit(
            shard_map_unchecked(
                lambda tl: quantized_reduce_scatter(
                    tl[0], 0, "dp", dp, block_size=16
                )[0][None],
                mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            )
        )(t), None
        single_err = np.abs(
            np.asarray(single).reshape(16, 4) - exact
        ).max()
        ef_err = np.abs(acc - exact).max()
        # EF averages the rounding error away; one-shot does not
        assert ef_err <= single_err / 2 + 1e-7
        np.testing.assert_allclose(acc, exact, rtol=2e-2, atol=2e-3)


class TestTrainingParity:
    def test_quantized_loop_tracks_exact(self):
        _, exact = _run(_trainer("exact", dp=4), steps=8)
        _, int8 = _run(_trainer("int8_sharded", dp=4), steps=8)
        np.testing.assert_allclose(int8, exact, rtol=5e-2, atol=5e-3)
        assert int8[-1] < int8[0]  # it actually trains

    def test_stochastic_rounding_loop_trains(self):
        policy = GradSyncPolicy(mode="int8_sharded", rounding="stochastic")
        _, losses = _run(_trainer(policy, dp=4), steps=8)
        _, exact = _run(_trainer("exact", dp=4), steps=8)
        assert np.isfinite(losses).all()
        np.testing.assert_allclose(losses, exact, rtol=8e-2, atol=8e-3)

    def test_bf16_grads_supported(self):
        _, losses = _run(
            _trainer("int8_sharded", dp=4, grads_dtype=jnp.bfloat16),
            steps=4,
        )
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_grad_accum_inside_sync(self):
        _, plain = _run(_trainer("int8_sharded", dp=4), steps=4)
        _, accum = _run(
            _trainer("int8_sharded", dp=4, grad_accum_steps=2), steps=4
        )
        np.testing.assert_allclose(accum, plain, rtol=5e-3, atol=1e-4)

    def test_adjust_accum_recompiles_sync_step(self):
        trainer = _trainer("int8_sharded", dp=4)
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = trainer.shard_batch(batch)
        state, _ = trainer.train_step(state, sharded)
        # elastic accumulation change forces a recompile of the
        # shard_map step; the global batch is preserved via accum
        assert trainer.adjust_accum_for_world(
            global_batch=32, per_device_batch=4
        ) == 2
        state, m = trainer.train_step(state, sharded)
        assert np.isfinite(float(jax.device_get(m["loss"])))


class TestShardedUpdateEquivalence:
    @pytest.mark.parametrize("dp", [2, 4])
    def test_bitwise_vs_replicated_on_integer_grads(self, dp):
        """Identical reduce-scatter inputs, sharded vs replicated
        update, through the real step: BITWISE identical where the
        arithmetic is exact.  A loss linear in the params makes every
        replica's gradient the integers it is handed; every second
        element is +-127, so each int8 block's scale is exactly 1/dp
        and the codec drops nothing; SGD with momentum at 0.5 is exact
        on such values.  What is left to differ is which chunk goes
        where."""
        model = _MLP()
        local = 16 // dp
        abstract = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16))
        )["params"]
        rng = np.random.default_rng(11)

        def ints(leaf):
            g = rng.integers(-127, 128, size=(dp, leaf.size))
            g[:, ::2] = rng.choice([-127, 127], size=g[:, ::2].shape)
            g = g.reshape((dp,) + leaf.shape).astype(np.float32)
            return np.repeat(g, local, axis=0)  # one gradient a replica

        batch = {"x": _batch()["x"], "c": jax.tree.map(ints, abstract)}

        def loss_fn(params, batch):
            return sum(
                jnp.vdot(p, c.mean(0)) for p, c in zip(
                    jax.tree.leaves(params), jax.tree.leaves(batch["c"])
                )
            )

        def run(mode):
            trainer = Trainer(
                model, optax.sgd(0.5, momentum=0.5),
                build_mesh(MeshConfig(dp=dp), devices=jax.devices()[:dp]),
                loss_fn=loss_fn, grad_sync=mode,
            )
            state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
            first = _host_tree(state.params)
            sharded = trainer.shard_batch(batch)
            for _ in range(3):
                state, _ = trainer.train_step(state, sharded)
            return first, state

        p0, s_rep = run("int8")
        _, s_shd = run("int8_sharded")
        for a, b in zip(
            jax.tree.leaves(_host_tree((s_rep.params, s_rep.opt_state))),
            jax.tree.leaves(_host_tree((s_shd.params, s_shd.opt_state))),
        ):
            np.testing.assert_array_equal(a, b)
        # the codec dropped nothing, and the params moved by exactly
        # the mean of the integers handed in
        for resid in jax.tree.leaves(s_shd.ef_residual):
            assert not np.asarray(resid).any()
        mean = jax.tree.map(
            lambda c: c.reshape((dp, local) + c.shape[1:])[:, 0].mean(0),
            batch["c"],
        )
        for p, g, got in zip(jax.tree.leaves(p0), jax.tree.leaves(mean),
                             jax.tree.leaves(_host_tree(s_shd.params))):
            trace = np.zeros_like(g)
            for _ in range(3):
                trace = g + np.float32(0.5) * trace
                p = p - np.float32(0.5) * trace
            np.testing.assert_array_equal(got, p)

    @pytest.mark.parametrize("dp", [2, 4])
    def test_fp32_close_to_replicated(self, dp):
        """The same on random payloads, under Adam: the update is
        elementwise, so the dp-sharded one equals the replicated one —
        to the last bits, not in them: the two are different XLA
        programs and the installed CPU backend contracts them in another
        order (max relative 1e-7 seen).  The bit-identity is asserted on
        integer-valued gradients above."""
        s_rep, _ = _run(_trainer("int8", dp=dp), steps=5)
        s_shd, _ = _run(_trainer("int8_sharded", dp=dp), steps=5)
        for a, b in zip(
            jax.tree.leaves(_host_tree(s_rep.params)),
            jax.tree.leaves(_host_tree(s_shd.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        # dp-sharded moments hold the same values as replicated ones
        for a, b in zip(
            jax.tree.leaves(_host_tree(s_rep.opt_state)),
            jax.tree.leaves(_host_tree(s_shd.opt_state)),
        ):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("dp", [2, 4])
    def test_bf16_moments_within_storage_rounding(self, dp):
        from dlrover_tpu.trainer.optim import create_optimizer

        def opt():
            return create_optimizer(
                peak_lr=1e-2, warmup_steps=2, total_steps=100,
                grad_clip_norm=None, moment_dtype=jnp.bfloat16,
            )

        s_rep, _ = _run(_trainer("int8", dp=dp, optimizer=opt()), steps=5)
        s_shd, _ = _run(
            _trainer("int8_sharded", dp=dp, optimizer=opt()), steps=5
        )
        for a, b in zip(
            jax.tree.leaves(_host_tree(s_rep.params)),
            jax.tree.leaves(_host_tree(s_shd.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_exact_sharded_tracks_gspmd_exact(self):
        s_exact, l_exact = _run(_trainer("exact", dp=4), steps=5)
        s_shard, l_shard = _run(_trainer("exact_sharded", dp=4), steps=5)
        np.testing.assert_allclose(l_shard, l_exact, rtol=2e-3, atol=1e-4)
        for a, b in zip(
            jax.tree.leaves(_host_tree(s_exact.params)),
            jax.tree.leaves(_host_tree(s_shard.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-5)

    def test_policy_clip_matches_optax_clip(self):
        exact_opt = optax.chain(
            optax.clip_by_global_norm(0.05), optax.adamw(1e-2)
        )
        _, l_exact = _run(
            _trainer("exact", dp=4, optimizer=exact_opt), steps=5
        )
        policy = GradSyncPolicy(mode="exact_sharded", clip_norm=0.05)
        _, l_shard = _run(_trainer(policy, dp=4), steps=5)
        np.testing.assert_allclose(l_shard, l_exact, rtol=2e-3, atol=1e-4)

    def test_moment_hbm_is_sharded(self):
        """The ZeRO-1 point: each replica stores 1/dp of the moments."""
        trainer = _trainer("exact_sharded", dp=4)
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        flat = [
            (path, leaf)
            for path, leaf in collectives.leaf_items(state.opt_state)
            if leaf.ndim > 0 and shard_dim_for(leaf.shape, 4) is not None
        ]
        assert flat, "no shardable moment leaves found"
        for path, leaf in flat:
            dim = shard_dim_for(leaf.shape, 4)
            for shard in leaf.addressable_shards:
                sl = shard.index[dim]
                start = sl.start or 0
                stop = sl.stop if sl.stop is not None else leaf.shape[dim]
                assert stop - start == leaf.shape[dim] // 4, (
                    f"{path} not dp-sharded: {shard.index}"
                )


class TestMeshGates:
    def test_model_parallel_mesh_rejected(self):
        model = _MLP()
        mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="pure data-parallel"):
            Trainer(
                model, optax.adamw(1e-2), mesh,
                loss_fn=_mse_loss(model), grad_sync="int8_sharded",
            )

    def test_fsdp_sync_axis_rejected(self):
        """fsdp is a data axis but shards the params; running the manual
        shard_map body on a param slice would be silently wrong."""
        model = _MLP()
        mesh = build_mesh(
            MeshConfig(dp=1, fsdp=4), devices=jax.devices()[:4]
        )
        with pytest.raises(ValueError, match="shard params"):
            Trainer(
                model, optax.adamw(1e-2), mesh,
                loss_fn=_mse_loss(model), grad_sync="exact_sharded",
            )

    def test_two_active_data_axes_rejected(self):
        # fsdp stays rejected even alongside dp: only dp (and the r18
        # slice axis above it) keep params replicated
        model = _MLP()
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        with pytest.raises(ValueError, match="shard params"):
            Trainer(
                model, optax.adamw(1e-2), mesh,
                loss_fn=_mse_loss(model), grad_sync="exact_sharded",
            )

    def test_dp1_demotes_to_exact(self):
        trainer = _trainer("int8_sharded", dp=1)
        assert trainer.grad_sync.mode == "exact"
        state, losses = _run(trainer, steps=2)
        assert state.ef_residual is None
        assert np.isfinite(losses).all()

    def test_dp1_demotion_keeps_clip_norm(self):
        """A clip-free optimizer + policy clip must keep clipping when
        the dp world (elastically) collapses to 1 — the exact path
        applies the policy clip too."""
        policy = GradSyncPolicy(mode="int8_sharded", clip_norm=0.05)
        trainer = _trainer(policy, dp=1)
        assert trainer.grad_sync.mode == "exact"
        assert trainer.grad_sync.clip_norm == 0.05
        # behaves like an optax-chain clip at the same bound
        exact_opt = optax.chain(
            optax.clip_by_global_norm(0.05), optax.adamw(1e-2)
        )
        _, l_ref = _run(
            _trainer("exact", dp=1, optimizer=exact_opt), steps=4
        )
        _, l_pol = _run(trainer, steps=4)
        np.testing.assert_allclose(l_pol, l_ref, rtol=1e-5, atol=1e-7)

    def test_exact_states_carry_no_ef(self):
        state, _ = _run(_trainer("exact", dp=4), steps=1)
        assert state.ef_residual is None
        state2, _ = _run(_trainer("exact_sharded", dp=4), steps=1)
        assert state2.ef_residual is None

    def test_quantized_state_has_dp_stacked_ef(self):
        state, _ = _run(_trainer("int8_sharded", dp=4), steps=1)
        assert state.ef_residual, "quantized policy must carry EF"
        for path, stack in state.ef_residual.items():
            assert stack.shape[0] == 4, (path, stack.shape)


class TestElasticRestore:
    def _save(self, trainer, state, ckpt_dir, scope):
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        ckpt = Checkpointer(
            str(ckpt_dir), scope=scope, async_snapshot=False
        )
        ckpt.save_checkpoint(int(jax.device_get(state.step)), state,
                             StorageType.DISK)
        assert ckpt.wait_latest_checkpoint(timeout=120)
        ckpt.close()

    def _eval(self, trainer, state, batch):
        with trainer.mesh:
            return float(
                jax.device_get(
                    _mse_loss(trainer.model)(state.params, batch)
                )
            )

    @pytest.mark.parametrize("dp_from,dp_to", [(4, 2), (2, 4)])
    def test_dp_change_roundtrips_moments_and_ef(
        self, tmp_path, dp_from, dp_to
    ):
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        batch = _batch()
        src = _trainer("int8_sharded", dp=dp_from)
        state = src.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = src.shard_batch(batch)
        for _ in range(3):
            state, _ = src.train_step(state, sharded)
        loss_before = self._eval(src, state, batch)
        ef_total = {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in state.ef_residual.items()
        }
        moments_before = _host_tree(state.opt_state)
        self._save(src, state, tmp_path, f"src{dp_from}")

        dst = _trainer("int8_sharded", dp=dp_to)
        ckpt = Checkpointer(str(tmp_path), scope=f"dst{dp_to}")
        restored, step = dst.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        assert restored is not None and step == 3
        # params and loss are continuous
        assert self._eval(dst, restored, batch) == pytest.approx(
            loss_before, rel=1e-6
        )
        # dp-sharded optimizer moments reshard bit-for-bit (global
        # shapes are dp-independent; only the NamedSharding changed)
        for a, b in zip(
            jax.tree.leaves(moments_before),
            jax.tree.leaves(_host_tree(restored.opt_state)),
        ):
            np.testing.assert_array_equal(a, b)
        # EF stacks re-split across the new degree, total preserved
        assert set(restored.ef_residual) == set(ef_total)
        for k, stack in restored.ef_residual.items():
            assert stack.shape[0] == dp_to
            np.testing.assert_allclose(
                np.asarray(stack, np.float32).sum(axis=0),
                ef_total[k], rtol=1e-5, atol=1e-7,
            )
        # training continues on the new degree
        state2, m = dst.train_step(restored, dst.shard_batch(batch))
        assert np.isfinite(float(jax.device_get(m["loss"])))
        ckpt.engine.unlink_memory()
        ckpt.close()

    def test_same_dp_restore_is_exact(self, tmp_path):
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        batch = _batch()
        src = _trainer("int8_sharded", dp=4)
        state = src.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = src.shard_batch(batch)
        state, _ = src.train_step(state, sharded)
        ef_before = {
            k: np.asarray(v) for k, v in state.ef_residual.items()
        }
        self._save(src, state, tmp_path, "same_a")
        dst = _trainer("int8_sharded", dp=4)
        ckpt = Checkpointer(str(tmp_path), scope="same_b")
        restored, step = dst.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        assert step == 1
        for k, arr in ef_before.items():
            np.testing.assert_array_equal(
                np.asarray(restored.ef_residual[k]), arr
            )
        ckpt.engine.unlink_memory()
        ckpt.close()

    def test_newer_other_degree_step_beats_stale_same_degree(
        self, tmp_path
    ):
        """dp2 saves step 1, dp4 continues and saves step 2, dp2
        restores: the engine's candidate scan would cover the STALE
        step 1 (its EF stack matches dp2), but load_state must detect
        the newer step and restore it with redistributed residuals."""
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        batch = _batch()
        t2 = _trainer("int8_sharded", dp=2)
        state = t2.create_state(jax.random.PRNGKey(0), batch["x"])
        state, _ = t2.train_step(state, t2.shard_batch(batch))
        self._save(t2, state, tmp_path, "st_a")

        t4 = _trainer("int8_sharded", dp=4)
        ckpt4 = Checkpointer(str(tmp_path), scope="st_b")
        state4, step = t4.load_state(ckpt4, jax.random.PRNGKey(0),
                                     batch["x"])
        assert step == 1
        state4, _ = t4.train_step(state4, t4.shard_batch(batch))
        self._save(t4, state4, tmp_path, "st_c")
        params_at_2 = _host_tree(state4.params)
        ckpt4.engine.unlink_memory()
        ckpt4.close()

        back = _trainer("int8_sharded", dp=2)
        ckpt2 = Checkpointer(str(tmp_path), scope="st_d")
        restored, step = back.load_state(
            ckpt2, jax.random.PRNGKey(0), batch["x"]
        )
        assert step == 2, f"stale same-degree step won: {step}"
        for a, b in zip(
            jax.tree.leaves(params_at_2),
            jax.tree.leaves(_host_tree(restored.params)),
        ):
            np.testing.assert_array_equal(a, b)
        ckpt2.engine.unlink_memory()
        ckpt2.close()

    def test_dp_shrink_with_newly_shardable_leaves(self, tmp_path):
        """A dp shrink can make leaves shardable that the old degree
        never quantized: their residuals zero-init while every stored
        stack still restores (no all-or-nothing failure)."""
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        class GrowthMLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.tanh(nn.Dense(6)(x))  # bias (6,): dp4 no, dp2 yes
                return nn.Dense(1)(h)[..., 0]

        def mk(mode, dp):
            model = GrowthMLP()
            mesh = build_mesh(
                MeshConfig(dp=dp), devices=jax.devices()[:dp]
            )
            return Trainer(
                model, optax.adamw(1e-2), mesh,
                loss_fn=_mse_loss(model), grad_sync=mode,
            )

        batch = _batch()
        src = mk("int8_sharded", 4)
        state = src.create_state(jax.random.PRNGKey(0), batch["x"])
        for _ in range(2):
            state, _ = src.train_step(state, src.shard_batch(batch))
        ef_total = {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in state.ef_residual.items()
        }
        self._save(src, state, tmp_path, "gr_a")

        dst = mk("int8_sharded", 2)
        ckpt = Checkpointer(str(tmp_path), scope="gr_b")
        restored, step = dst.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        assert restored is not None and step == 2
        grown = set(restored.ef_residual) - set(ef_total)
        assert grown, "expected newly-shardable leaves at dp2"
        for k, stack in restored.ef_residual.items():
            total = np.asarray(stack, np.float32).sum(axis=0)
            if k in ef_total:
                np.testing.assert_allclose(
                    total, ef_total[k], rtol=1e-5, atol=1e-7
                )
            else:
                np.testing.assert_array_equal(total, 0.0)
        state2, m = dst.train_step(restored, dst.shard_batch(batch))
        assert np.isfinite(float(jax.device_get(m["loss"])))
        ckpt.engine.unlink_memory()
        ckpt.close()

    def test_policy_upgrade_restores_exact_checkpoint(self, tmp_path):
        """A checkpoint saved under grad_sync='exact' (no EF leaves)
        must restore under a quantized policy — with zero-initialized
        EF stacks — not be silently discarded as unreadable."""
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        batch = _batch()
        src = _trainer("exact", dp=4)
        state = src.create_state(jax.random.PRNGKey(0), batch["x"])
        sharded = src.shard_batch(batch)
        for _ in range(2):
            state, _ = src.train_step(state, sharded)
        loss_before = self._eval(src, state, batch)
        self._save(src, state, tmp_path, "up_a")

        dst = _trainer("int8_sharded", dp=4)
        ckpt = Checkpointer(str(tmp_path), scope="up_b")
        restored, step = dst.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        assert restored is not None and step == 2
        assert self._eval(dst, restored, batch) == pytest.approx(
            loss_before, rel=1e-6
        )
        assert restored.ef_residual, "EF stacks must be zero-initialized"
        for path, stack in restored.ef_residual.items():
            assert stack.shape[0] == 4
            np.testing.assert_array_equal(np.asarray(stack), 0.0)
        state2, m = dst.train_step(restored, dst.shard_batch(batch))
        assert np.isfinite(float(jax.device_get(m["loss"])))
        ckpt.engine.unlink_memory()
        ckpt.close()

    def test_gshape_mismatch_never_assembles_a_corner(self, tmp_path):
        """Engine guard: an abstract leaf with a SMALLER global shape
        than stored must not silently restore the stored tensor's
        corner slice (the failure load_state exists to prevent)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
        full = NamedSharding(mesh, PartitionSpec())
        state = {"w": jax.device_put(np.arange(8.0, dtype=np.float32), full)}
        ckpt = Checkpointer(str(tmp_path), scope="gsm_a",
                            async_snapshot=False)
        ckpt.save_checkpoint(1, state, StorageType.DISK)
        assert ckpt.wait_latest_checkpoint(timeout=60)
        ckpt.close()
        ckpt2 = Checkpointer(str(tmp_path), scope="gsm_b")
        smaller = {"w": jax.ShapeDtypeStruct((4,), np.float32)}
        got, step = ckpt2.load_checkpoint(smaller, {"w": full})
        assert got is None and step == -1
        ckpt2.close()


class TestWireEstimate:
    def test_quantized_cheaper_than_exact(self):
        params = {
            "w": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
            "odd": jax.ShapeDtypeStruct((7,), jnp.float32),
        }
        est = estimate_sync_bytes(
            params, 4, GradSyncPolicy(mode="int8_sharded")
        )
        assert est["quantized_bytes"] < est["exact_allreduce_bytes"]
        assert est["reduction_x"] > 1.3
        # world 1: nothing on the wire
        est1 = estimate_sync_bytes(params, 1, GradSyncPolicy(mode="int8"))
        assert est1["exact_allreduce_bytes"] == 0

    def test_layout_covers_all_leaves(self):
        params = {
            "a": jax.ShapeDtypeStruct((8, 3), jnp.float32),
            "b": jax.ShapeDtypeStruct((3, 5), jnp.float32),
        }
        layout = GradLayout(params, 4)
        assert layout.dims["a"] == 0
        assert layout.dims["b"] is None
        assert layout.sharded_paths() == ["a"]
