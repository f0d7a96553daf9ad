"""Overlapped bucketed grad sync (r14): ``parallel/bucketing``, the
int4/blockwise codecs, the ring reduce-scatter tiers, and the trainer's
bucketed step.

Covers the r14 tentpole on the virtual CPU mesh:

* deterministic size-targeted bucket assignment (in-process AND across
  a real second process) and pack/unpack roundtrips;
* int4 / blockwise-mixed quantize-dequantize error bounds and the
  refinement selection by grad statistics;
* ring reduce-scatter (jax-level and Pallas-accumulate tiers) vs
  ``lax.psum_scatter`` numerical equivalence on CPU-interpretable
  shapes, plus the transport fallback matrix;
* end-to-end: overlapped ``exact_sharded`` is bit-identical to the r6
  per-leaf path, quantized bucketed training tracks exact, and the
  elastic dp-resize restore keeps EF totals bit-exact per bucket;
* per-bucket bytes accounting including quantization metadata.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

from jax.sharding import PartitionSpec as P

from dlrover_tpu.parallel import collectives
from dlrover_tpu.parallel.bucketing import BucketLayout
from dlrover_tpu.parallel.collectives import (
    GradLayout,
    GradSyncPolicy,
    blockwise_dequantize4,
    blockwise_quantize4,
    codec_chunk_bytes,
    decode_chunks,
    encode_chunks,
    estimate_bucket_bytes,
    estimate_sync_bytes,
    shard_map_unchecked,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring
from dlrover_tpu.trainer.train import Trainer


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


def _trainer(policy, dp, optimizer=None, **kw):
    model = _MLP()
    mesh = build_mesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])
    return Trainer(
        model, optimizer or optax.adamw(1e-2), mesh,
        loss_fn=_mse_loss(model), grad_sync=policy, **kw,
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


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


_SHAPES = {
    "a/kernel": (16, 4), "a/bias": (32,), "b/kernel": (64, 8),
    "b/bias": (33,), "c/kernel": (128, 2),
}
_DIMS = {"a/kernel": 0, "a/bias": 0, "b/kernel": 0, "b/bias": None,
         "c/kernel": 0}


class TestPolicy:
    def test_new_modes_parse(self):
        for mode in ("int4", "int4_sharded", "blockwise",
                     "blockwise_sharded"):
            p = GradSyncPolicy.parse(mode)
            assert p.quantized and p.active
            assert p.qformat == mode.split("_")[0].replace("wise", "wise")
        assert GradSyncPolicy.parse("int4_sharded").sharded_update
        assert GradSyncPolicy.parse("blockwise").qformat == "blockwise"
        assert GradSyncPolicy.parse("exact").qformat is None

    def test_invalid_fields_rejected(self):
        with pytest.raises(ValueError):
            GradSyncPolicy(transport="nccl")
        with pytest.raises(ValueError):
            GradSyncPolicy(bucket_mb=-1.0)
        with pytest.raises(ValueError):
            GradSyncPolicy(hi_frac=0.0)
        with pytest.raises(ValueError):
            GradSyncPolicy(block_size=15)  # int4 packing needs even

    def test_resolve_fills_from_env(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_GRAD_BUCKET_MB", "2.5")
        monkeypatch.setenv("DLROVER_TPU_GRAD_TRANSPORT", "ring")
        monkeypatch.setenv("DLROVER_TPU_GRAD_HI_FRAC", "0.25")
        p = GradSyncPolicy(mode="blockwise_sharded").resolve()
        assert p.bucket_mb == 2.5
        assert p.transport == "ring"
        assert p.hi_frac == 0.25
        # explicit fields beat the env
        q = GradSyncPolicy(
            mode="int8", bucket_mb=0.0, transport="all_to_all",
            hi_frac=0.5,
        ).resolve()
        assert q.bucket_mb == 0.0
        assert q.transport == "all_to_all"
        assert q.hi_frac == 0.5

    def test_hi_blocks_bounds(self):
        p = GradSyncPolicy(mode="blockwise", hi_frac=0.125)
        assert p.hi_blocks(1) == 1  # always at least one
        assert p.hi_blocks(16) == 2
        assert p.hi_blocks(100) == 12
        full = GradSyncPolicy(mode="blockwise", hi_frac=1.0)
        assert full.hi_blocks(8) == 8


class TestBucketLayout:
    def test_greedy_size_targeted(self):
        # 4 KB target: a/kernel (256 B) + a/bias (128 B) share, b/kernel
        # (2 KB) joins, c/kernel (1 KB) closes over... walk the math
        layout = BucketLayout(_DIMS, _SHAPES, world=4, bucket_bytes=2048)
        assert len(layout) >= 2
        # non-shardable leaf never appears
        all_paths = [s.path for b in layout.buckets for s in b.slices]
        assert "b/bias" not in all_paths
        assert set(all_paths) == {p for p, d in _DIMS.items()
                                  if d is not None}
        # offsets are contiguous per bucket
        for b in layout.buckets:
            off = 0
            for s in b.slices:
                assert s.offset == off
                off += s.width
            assert b.width == off

    def test_oversized_leaf_gets_own_bucket(self):
        shapes = {"small": (8,), "huge": (4096, 4), "tail": (8,)}
        dims = {"small": 0, "huge": 0, "tail": 0}
        layout = BucketLayout(dims, shapes, world=4, bucket_bytes=1024)
        huge_bucket = layout.buckets[layout.bucket_of("huge")]
        assert [s.path for s in huge_bucket.slices] == ["huge"]

    def test_signature_deterministic_and_shape_sensitive(self):
        a = BucketLayout(_DIMS, _SHAPES, 4, 2048)
        b = BucketLayout(_DIMS, _SHAPES, 4, 2048)
        assert a.signature() == b.signature()
        grown = dict(_SHAPES, **{"c/kernel": (256, 2)})
        c = BucketLayout(_DIMS, grown, 4, 2048)
        assert a.signature() != c.signature()

    def test_signature_agrees_across_processes(self):
        """The cross-process contract: a second interpreter building
        from the same shapes derives the same assignment."""
        code = (
            "from dlrover_tpu.parallel.bucketing import BucketLayout\n"
            f"layout = BucketLayout({_DIMS!r}, {_SHAPES!r}, 4, 2048)\n"
            "print('SIG', layout.signature())\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        sig = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("SIG ")][0].split()[1]
        assert sig == BucketLayout(_DIMS, _SHAPES, 4, 2048).signature()

    def test_pack_unpack_roundtrip(self):
        layout = BucketLayout(_DIMS, _SHAPES, 4, 2048)
        rng = np.random.default_rng(3)
        vals = {p: jnp.asarray(rng.standard_normal(s), jnp.float32)
                for p, s in _SHAPES.items() if _DIMS[p] is not None}
        for b in layout.buckets:
            buf = layout.pack(b, vals.__getitem__)
            assert buf.shape == (4, b.width)
            # full inverse
            back = layout.unpack_full(b, buf)
            for path, arr in back.items():
                np.testing.assert_array_equal(
                    np.asarray(arr), np.asarray(vals[path])
                )
            # row r unpacks to each leaf's r-th chunk
            shards = layout.unpack_shard(b, buf[1])
            for s in b.slices:
                moved = np.moveaxis(np.asarray(vals[s.path]), s.dim, 0)
                chunk = moved.shape[0] // 4
                expect = np.moveaxis(moved[chunk:2 * chunk], 0, s.dim)
                np.testing.assert_array_equal(
                    np.asarray(shards[s.path]), expect
                )


class TestInt4Codec:
    def test_nearest_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        blocks = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
        q4, scale = blockwise_quantize4(blocks, "nearest")
        assert q4.shape == (5, 32)  # two codes per byte
        deq = blockwise_dequantize4(q4, scale)
        err = np.abs(np.asarray(blocks) - np.asarray(deq))
        bound = np.asarray(scale) / 2 + 1e-7
        assert (err <= bound).all()

    def test_representable_values_roundtrip_exact(self):
        """Codes -7..7 at a known scale survive pack/unpack bit-for-bit
        (the nibble sign-extension is the risky part)."""
        codes = np.arange(-7, 8, dtype=np.float32)  # 15 values
        block = np.concatenate([codes, [7.0]])  # even length, max 7
        blocks = jnp.asarray(block[None], jnp.float32)
        q4, scale = blockwise_quantize4(blocks, "nearest")
        assert float(scale[0, 0]) == 1.0
        np.testing.assert_array_equal(
            np.asarray(blockwise_dequantize4(q4, scale))[0], block
        )

    def test_zero_block_roundtrips_to_zero(self):
        q4, scale = blockwise_quantize4(jnp.zeros((2, 16)), "nearest")
        assert np.asarray(scale).max() == 0.0
        np.testing.assert_array_equal(
            np.asarray(blockwise_dequantize4(q4, scale)), 0.0
        )

    def test_stochastic_bounded_and_needs_key(self):
        blocks = jnp.asarray(
            np.random.default_rng(1).standard_normal((3, 32)), jnp.float32
        )
        with pytest.raises(ValueError):
            blockwise_quantize4(blocks, "stochastic")
        q4, scale = blockwise_quantize4(
            blocks, "stochastic", jax.random.PRNGKey(0)
        )
        err = np.abs(
            np.asarray(blocks)
            - np.asarray(blockwise_dequantize4(q4, scale))
        )
        assert (err <= np.asarray(scale) + 1e-7).all()


class TestBlockwiseMixed:
    def _flat(self, world=4, nblk=8, block=32, seed=0):
        rng = np.random.default_rng(seed)
        flat = rng.standard_normal((world, nblk, block)).astype(np.float32)
        flat[:, 3] *= 50.0  # one dominant block per chunk
        return jnp.asarray(flat)

    def test_refined_blocks_get_int8_accuracy(self):
        policy = GradSyncPolicy(mode="blockwise", hi_frac=0.125,
                                block_size=32)
        flat = self._flat()
        payload = encode_chunks(flat, policy)
        assert set(payload) == {"q4", "s4", "idx", "q8", "s8"}
        # the dominant block is what the statistics select
        assert (np.asarray(payload["idx"]) == 3).all()
        deq = np.asarray(decode_chunks(payload, policy))
        err = np.abs(deq - np.asarray(flat))
        scale8 = np.abs(np.asarray(flat[:, 3])).max(-1) / 127.0
        # refined block: int8 bound; an int4-only decode would be ~16x
        assert (err[:, 3] <= scale8[:, None] / 2 + 1e-6).all()
        # int4-coded blocks keep the int4 bound
        scale4 = np.abs(np.asarray(flat[:, 0])).max(-1) / 7.0
        assert (err[:, 0] <= scale4[:, None] / 2 + 1e-6).all()

    def test_decode_matches_int4_on_unrefined(self):
        policy = GradSyncPolicy(mode="blockwise", hi_frac=0.125,
                                block_size=32)
        flat = self._flat(seed=2)
        deq = np.asarray(decode_chunks(encode_chunks(flat, policy), policy))
        p4 = GradSyncPolicy(mode="int4", block_size=32)
        deq4 = np.asarray(decode_chunks(encode_chunks(flat, p4), p4))
        idx = 3  # refined
        mask = np.ones(flat.shape[1], bool)
        mask[idx] = False
        np.testing.assert_array_equal(deq[:, mask], deq4[:, mask])
        assert not np.array_equal(deq[:, idx], deq4[:, idx])

    def test_chunk_bytes_accounting(self):
        block = 256
        nblk = 64
        i8 = codec_chunk_bytes(nblk, block, GradSyncPolicy(mode="int8"))
        i4 = codec_chunk_bytes(nblk, block, GradSyncPolicy(mode="int4"))
        bw = codec_chunk_bytes(
            nblk, block, GradSyncPolicy(mode="blockwise", hi_frac=0.125)
        )
        assert i4["payload"] == i8["payload"] // 2
        assert i8["metadata"] == i4["metadata"] == 4 * nblk
        # blockwise: int4 base + k int8 refinements, metadata adds
        # idx + refine scales
        k = 8
        assert bw["payload"] == i4["payload"] + k * block
        assert bw["metadata"] == 4 * nblk + 8 * k
        # the satellite fix: metadata must be accounted, not folded away
        assert bw["metadata"] > 0


class TestRingReduceScatter:
    def _mesh(self, dp):
        return build_mesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])

    def _run_ring(self, x, world, accum="jnp"):
        mesh = self._mesh(world)
        fn = shard_map_unchecked(
            lambda t: ring.ring_reduce_scatter(
                t[0], "dp", world, accum=accum, interpret=True
            )[None],
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
        return np.asarray(jax.jit(fn)(x)).reshape(world, -1)

    def _run_psum_scatter(self, x, world):
        mesh = self._mesh(world)
        fn = shard_map_unchecked(
            lambda t: jax.lax.psum_scatter(
                t[0], "dp", scatter_dimension=0, tiled=True
            ).reshape(1, -1),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
        return np.asarray(jax.jit(fn)(x)).reshape(world, -1)

    @pytest.mark.parametrize("world", [2, 4])
    def test_matches_psum_scatter(self, world):
        rng = np.random.default_rng(world)
        x = rng.standard_normal((world, world, 96)).astype(np.float32)
        got = self._run_ring(jnp.asarray(x), world)
        ref = self._run_psum_scatter(jnp.asarray(x), world)
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def test_integer_payload_bit_exact(self):
        """Integer-valued fp32 sums are order-independent below 2^24:
        the ring must agree with psum_scatter EXACTLY."""
        rng = np.random.default_rng(9)
        x = rng.integers(-1000, 1000, size=(4, 4, 64)).astype(np.float32)
        got = self._run_ring(jnp.asarray(x), 4)
        ref = self._run_psum_scatter(jnp.asarray(x), 4)
        np.testing.assert_array_equal(got, ref)

    def test_pallas_accumulate_tier(self):
        """width=1024 meets the tile precondition, so the Pallas add
        kernel actually executes (interpret mode on CPU)."""
        assert ring.pallas_accum_supported(1024)
        rng = np.random.default_rng(5)
        x = rng.integers(-100, 100, size=(4, 4, 1024)).astype(np.float32)
        got = self._run_ring(jnp.asarray(x), 4, accum="pallas")
        ref = self._run_psum_scatter(jnp.asarray(x), 4)
        np.testing.assert_array_equal(got, ref)

    def test_world1_identity(self):
        x = jnp.arange(8.0).reshape(1, 8)
        out = ring.ring_reduce_scatter(x, "dp", 1)
        np.testing.assert_array_equal(np.asarray(out), np.arange(8.0))

    def test_rdma_kernel_lowers_for_tpu(self):
        """The RDMA prototype can't EXECUTE off-TPU, but it must LOWER
        through the Mosaic pipeline (remote-DMA legality) — via
        cross-platform export on CPU, the same trick the FA2 bench
        evidence uses."""
        from jax import export as jexport
        from jax.sharding import AbstractMesh

        mesh = AbstractMesh((4,), ("dp",))
        fn = shard_map_unchecked(
            lambda t: ring.rdma_ring_reduce_scatter(t[0], "dp", 4)[None],
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
        x = jax.ShapeDtypeStruct((4, 4, 1024), jnp.float32)
        exported = jexport.export(jax.jit(fn), platforms=["tpu"])(x)
        assert len(exported.mlir_module_serialized) > 0

    def test_select_transport_fallbacks(self):
        sel = ring.select_transport
        # quantized buckets never ring: they run the codec exchange
        assert sel("ring", True, 4, 1024, False) == "all_to_all"
        assert sel("auto", False, 4, 1024, False) == "psum_scatter"
        assert sel("ring", False, 4, 1000, False) == "ring"
        # pallas tier needs the tile precondition
        assert sel("ring_pallas", False, 4, 1024, False) == "ring_pallas"
        assert sel("ring_pallas", False, 4, 1000, False) == "ring"
        # rdma prototype: disabled or off-TPU falls back to a jax ring
        assert sel("ring_rdma", False, 4, 1024, False) in (
            "ring", "ring_pallas"
        )
        assert sel("ring", False, 1, 1024, False) == "psum_scatter"


class TestOverlappedTraining:
    def test_exact_bucketed_sync_bit_identical_on_integer_grads(self):
        """Bucketing the exact policy is collective fusion only: on
        integer-valued gradients (fp32 integer sums are exact in any
        order) the fused and the per-leaf sync give the SAME bits."""
        batch = _batch()
        trainer = _trainer(
            GradSyncPolicy(mode="exact_sharded", bucket_mb=0.001), dp=4
        )
        state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        abstract = trainer.abstract_state(jax.random.PRNGKey(0), batch["x"])
        layout = GradLayout(abstract.params, 4)
        buckets = trainer._bucket_layout  # noqa: SLF001
        assert buckets is not None and len(buckets) > 1
        rng = np.random.default_rng(5)
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                rng.integers(-1000, 1000, size=(4,) + p.shape), jnp.float32
            ),
            jax.tree.map(np.asarray, state.params),
        )

        def body(g):
            g = jax.tree.map(lambda x: x[0], g)
            fused, _ = collectives.sync_gradient_tree_bucketed(
                g, None, layout, buckets, trainer.grad_sync, "dp"
            )
            per_leaf, _ = collectives.sync_gradient_tree(
                g, None, layout, trainer.grad_sync, "dp"
            )
            return (
                collectives.all_gather_tree_bucketed(
                    fused, layout, buckets, "dp"
                ),
                collectives.all_gather_tree(per_leaf, layout, "dp"),
            )

        fn = jax.jit(shard_map_unchecked(
            body, mesh=trainer.mesh, in_specs=P("dp"), out_specs=P(),
        ))
        with trainer.mesh:
            fused, per_leaf = fn(grads)
        for got, ref, g in zip(jax.tree.leaves(fused),
                               jax.tree.leaves(per_leaf),
                               jax.tree.leaves(grads)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(g).sum(axis=0)
            )

    def test_exact_overlapped_bit_identical_to_legacy(self):
        """The loss-trajectory equivalence acceptance: bucketing the
        exact policy is collective fusion only.  On random gradients the
        fused collective may sum in another order than the per-leaf one
        (the installed CPU backend does), so the trajectories agree to
        the last bits, not in them; the bit-identity itself is asserted
        on integer-valued gradients above."""
        s_leg, l_leg = _run(
            _trainer(GradSyncPolicy(mode="exact_sharded", bucket_mb=0.0),
                     dp=4), steps=6,
        )
        s_ovl, l_ovl = _run(
            _trainer(
                GradSyncPolicy(mode="exact_sharded", bucket_mb=0.001),
                dp=4,
            ), steps=6,
        )
        np.testing.assert_allclose(l_leg, l_ovl, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(_host(s_leg.params)),
                        jax.tree.leaves(_host(s_ovl.params))):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
        for a, b in zip(jax.tree.leaves(_host(s_leg.opt_state)),
                        jax.tree.leaves(_host(s_ovl.opt_state))):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)

    def test_ring_transport_tracks_psum(self):
        _, l_ps = _run(
            _trainer(GradSyncPolicy(mode="exact_sharded",
                                    bucket_mb=0.001), dp=4), steps=5,
        )
        _, l_ring = _run(
            _trainer(
                GradSyncPolicy(mode="exact_sharded", bucket_mb=0.001,
                               transport="ring"), dp=4,
            ), steps=5,
        )
        np.testing.assert_allclose(l_ring, l_ps, rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("mode", ["int4_sharded", "blockwise_sharded"])
    def test_quantized_bucketed_tracks_exact(self, mode):
        _, exact = _run(_trainer("exact", dp=4), steps=8)
        _, quant = _run(
            _trainer(GradSyncPolicy(mode=mode, bucket_mb=0.001), dp=4),
            steps=8,
        )
        np.testing.assert_allclose(quant, exact, rtol=8e-2, atol=8e-3)
        assert quant[-1] < quant[0]

    def test_grad_accum_parity_bucketed(self):
        _, plain = _run(
            _trainer(GradSyncPolicy(mode="int8_sharded",
                                    bucket_mb=0.001), dp=4), steps=4,
        )
        _, accum = _run(
            _trainer(GradSyncPolicy(mode="int8_sharded", bucket_mb=0.001),
                     dp=4, grad_accum_steps=2), steps=4,
        )
        np.testing.assert_allclose(accum, plain, rtol=5e-3, atol=1e-4)

    def test_bucketed_ef_invariant(self):
        """Per-bucket EF invariant: the quantization error the fused
        reduce dropped equals the carried residual — summed per bucket,
        sum_r t_r == all-gathered(shards) + sum_r residual_r."""
        model = _MLP()
        batch = _batch()
        policy = GradSyncPolicy(mode="int4_sharded", bucket_mb=0.001)
        trainer = _trainer(policy, dp=4)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        abstract = trainer.abstract_state(jax.random.PRNGKey(0), batch["x"])
        layout = GradLayout(abstract.params, 4)
        buckets = trainer._bucket_layout  # noqa: SLF001
        assert buckets is not None and len(buckets) > 1

        rng = np.random.default_rng(11)
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                rng.standard_normal(p.shape), jnp.float32
            ),
            jax.tree.map(np.asarray, state.params),
        )

        def body(g):
            synced, resid = collectives.sync_gradient_tree_bucketed(
                g, None, layout, buckets, trainer.grad_sync, "dp"
            )
            full = collectives.all_gather_tree_bucketed(
                synced, layout, buckets, "dp"
            )
            return full, resid

        fn = jax.jit(shard_map_unchecked(
            body, mesh=trainer.mesh, in_specs=P(), out_specs=(P(), P("dp")),
        ))
        with trainer.mesh:
            full, resid = fn(grads)
        for path, g in collectives.leaf_items(grads):
            if layout.dims.get(path) is None:
                continue
            reduced = np.asarray(
                dict(collectives.leaf_items(full))[path]
            )
            carried = np.asarray(resid[path]).sum(axis=0)
            # every replica contributed the same g: the true sum is 4g
            np.testing.assert_allclose(
                reduced + carried, 4.0 * np.asarray(g),
                rtol=1e-4, atol=1e-5,
            )

    def test_bucketed_all_gather_preserves_mixed_dtypes(self):
        """A bucket mixing bf16 and fp32 leaves must gather each leaf
        back in ITS dtype: a mixed concatenate would silently promote
        bf16 params to fp32 and break the donated step's avals."""
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        rng = np.random.default_rng(4)
        tree = {
            "a": jnp.asarray(rng.standard_normal((8, 2)), jnp.bfloat16),
            "b": jnp.asarray(rng.standard_normal((8, 3)), jnp.float32),
            "c": jnp.asarray(rng.standard_normal((8, 2)), jnp.bfloat16),
        }
        layout = GradLayout(tree, 4)
        buckets = BucketLayout.build(layout, tree, 1 << 20)
        assert len(buckets) == 1  # genuinely mixed within one bucket

        def body(t):
            shards = collectives.shard_like(t, layout, "dp")
            return collectives.all_gather_tree_bucketed(
                shards, layout, buckets, "dp"
            )

        fn = jax.jit(shard_map_unchecked(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
        ))
        with mesh:
            out = fn(tree)
        for path, leaf in tree.items():
            assert out[path].dtype == leaf.dtype, path
            np.testing.assert_array_equal(
                np.asarray(out[path], np.float32),
                np.asarray(leaf, np.float32),
            )

    def test_summary_reports_buckets(self):
        trainer = _trainer(
            GradSyncPolicy(mode="int8_sharded", bucket_mb=0.001), dp=4
        )
        batch = _batch()
        trainer.create_state(jax.random.PRNGKey(0), batch["x"])
        info = trainer.grad_sync_summary()
        assert info["bucketed"] and info["n_buckets"] > 1
        assert len(info["bucket_widths"]) == info["n_buckets"]
        assert info["signature"]


class TestElasticResizeBucketed:
    def _save(self, state, ckpt_dir, scope):
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        ckpt = Checkpointer(str(ckpt_dir), scope=scope,
                            async_snapshot=False)
        ckpt.save_checkpoint(int(jax.device_get(state.step)), state,
                             StorageType.DISK)
        assert ckpt.wait_latest_checkpoint(timeout=120)
        ckpt.close()

    def test_dp_resize_ef_bit_exact_per_bucket(self, tmp_path):
        """dp4 -> dp2 under int4 bucketed sync: per-leaf EF totals are
        preserved bit-exactly (power-of-two redistribution is exact in
        fp32), therefore so is every NEW bucket's packed total."""
        from dlrover_tpu.trainer.flash_checkpoint import Checkpointer

        batch = _batch()
        policy = GradSyncPolicy(mode="int4_sharded", bucket_mb=0.001)
        src = _trainer(policy, dp=4)
        state = src.create_state(jax.random.PRNGKey(0), batch["x"])
        for _ in range(3):
            state, _ = src.train_step(state, src.shard_batch(batch))
        ef_total = {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in state.ef_residual.items()
        }
        self._save(state, tmp_path, "bov_a")

        dst = _trainer(policy, dp=2)
        ckpt = Checkpointer(str(tmp_path), scope="bov_b")
        restored, step = dst.load_state(
            ckpt, jax.random.PRNGKey(0), batch["x"]
        )
        assert restored is not None and step == 3
        restored_total = {
            k: np.asarray(v, np.float32).sum(axis=0)
            for k, v in restored.ef_residual.items()
        }
        # per-leaf totals: bit-exact (sum of dp_new identical rows of
        # total/dp_new recovers total exactly for power-of-two worlds)
        for k, total in ef_total.items():
            np.testing.assert_array_equal(restored_total[k], total)
        # ... and therefore per-BUCKET packed totals under the new
        # layout are bit-exact too
        buckets = dst._bucket_layout  # noqa: SLF001
        assert buckets is not None
        for b in buckets.buckets:
            old = buckets.pack(
                b, lambda p: jnp.asarray(ef_total.get(
                    p, np.zeros(_SHAPES.get(p, (1,)), np.float32)
                ))
            ) if all(s.path in ef_total for s in b.slices) else None
            if old is None:
                continue
            new = buckets.pack(
                b, lambda p: jnp.asarray(restored_total[p])
            )
            np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        # training continues on the new degree
        state2, m = dst.train_step(restored, dst.shard_batch(batch))
        assert np.isfinite(float(jax.device_get(m["loss"])))
        ckpt.engine.unlink_memory()
        ckpt.close()


class TestBytesAccounting:
    def _params(self):
        return {
            "w": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
            "odd": jax.ShapeDtypeStruct((7,), jnp.float32),
        }

    def test_int4_halves_payload_metadata_itemized(self):
        i8 = estimate_sync_bytes(
            self._params(), 4, GradSyncPolicy(mode="int8_sharded")
        )
        i4 = estimate_sync_bytes(
            self._params(), 4, GradSyncPolicy(mode="int4_sharded")
        )
        bw = estimate_sync_bytes(
            self._params(), 4,
            GradSyncPolicy(mode="blockwise_sharded", hi_frac=0.125),
        )
        assert i4["quantized_bytes"] < i8["quantized_bytes"]
        assert i4["reduction_x"] > i8["reduction_x"]
        # blockwise sits between int4 and int8 on the wire
        assert (i4["quantized_bytes"] < bw["quantized_bytes"]
                < i8["quantized_bytes"])
        for est in (i8, i4, bw):
            assert est["metadata_bytes"] > 0
        assert bw["metadata_bytes"] > i4["metadata_bytes"]

    def test_per_bucket_accounting(self):
        layout = BucketLayout(_DIMS, _SHAPES, 4, 2048)
        policy = GradSyncPolicy(mode="blockwise", block_size=64,
                                hi_frac=0.25)
        per = estimate_bucket_bytes(layout, policy, 4)
        assert len(per) == len(layout)
        for entry in per:
            assert entry["rs_metadata_bytes"] > 0
            assert entry["allgather_bytes"] == int(
                0.75 * 4 * 4 * entry["width"]
            )
        exact = estimate_bucket_bytes(
            layout, GradSyncPolicy(mode="exact_sharded"), 4
        )
        assert all(e["rs_metadata_bytes"] == 0 for e in exact)
        assert sum(e["rs_payload_bytes"] for e in per) < sum(
            e["rs_payload_bytes"] for e in exact
        )


class TestOptimHelper:
    def test_clip_moves_into_sharded_policy(self):
        from dlrover_tpu.trainer.optim import (
            create_sharded_sync_optimizer,
        )

        opt, policy = create_sharded_sync_optimizer(
            "int4_sharded", peak_lr=1e-2, warmup_steps=2,
            total_steps=100, grad_clip_norm=0.5,
        )
        assert policy.clip_norm == 0.5
        assert policy.mode == "int4_sharded"
        assert opt is not None

    def test_preset_policy_clip_respected(self):
        """A clip the caller already bound on the policy must survive
        (not be clobbered by the helper's 1.0 default), and an
        explicit conflicting kwarg must raise."""
        from dlrover_tpu.trainer.optim import (
            create_sharded_sync_optimizer,
        )

        preset = GradSyncPolicy(mode="int8_sharded", clip_norm=5.0)
        _, policy = create_sharded_sync_optimizer(
            preset, peak_lr=1e-2, warmup_steps=2, total_steps=100
        )
        assert policy.clip_norm == 5.0
        with pytest.raises(ValueError, match="conflicting"):
            create_sharded_sync_optimizer(
                preset, peak_lr=1e-2, warmup_steps=2, total_steps=100,
                grad_clip_norm=1.0,
            )

    def test_replicated_policy_keeps_chain_clip(self):
        from dlrover_tpu.trainer.optim import (
            create_sharded_sync_optimizer,
        )

        opt, policy = create_sharded_sync_optimizer(
            "int8", peak_lr=1e-2, warmup_steps=2, total_steps=100,
            grad_clip_norm=0.5,
        )
        assert policy.clip_norm is None  # replicated update: chain clips

    def test_policy_clip_matches_optax_clip_bucketed(self):
        exact_opt = optax.chain(
            optax.clip_by_global_norm(0.05), optax.adamw(1e-2)
        )
        _, l_exact = _run(
            _trainer("exact", dp=4, optimizer=exact_opt), steps=5
        )
        policy = GradSyncPolicy(mode="exact_sharded", clip_norm=0.05,
                                bucket_mb=0.001)
        _, l_shard = _run(_trainer(policy, dp=4), steps=5)
        np.testing.assert_allclose(l_shard, l_exact, rtol=2e-3, atol=1e-4)
