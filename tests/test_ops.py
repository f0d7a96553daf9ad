"""Kernel tests: Pallas flash attention (interpret mode) and ring
attention vs the reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import flash_attention, reference_attention
from dlrover_tpu.ops.pallas.flash_attention import pallas_flash_attention
from dlrover_tpu.ops.pallas.tuning import tuned_blocks
from dlrover_tpu.ops.ring_attention import ring_attention_sharded
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _qkv(rng_seed, B, S, H, D, kv_heads=None, dtype=jnp.float32):
    kv_heads = kv_heads or H
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(rng_seed), 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, kv_heads, D), dtype)
    v = jax.random.normal(k3, (B, S, kv_heads, D), dtype)
    return q, k, v


def _causal_mask(S):
    return jnp.tril(jnp.ones((S, S), dtype=bool))[None, None, :, :]


def _shipped_gpt_blocks(seq):
    """The blocks shipped for GPT-2's attention (S 1024, head size 64),
    scaled to a sequence the interpreter gets through."""
    block_q, block_kv = tuned_blocks(1024, 64)
    return block_q * seq // 1024, block_kv * seq // 1024


class TestPallasFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("blocks", [(64, 64), "shipped_s1024_d64"])
    def test_matches_reference_multi_block(self, causal, blocks):
        B, S, H, D = 2, 256, 4, 64
        q, k, v = _qkv(0, B, S, H, D)
        if blocks == "shipped_s1024_d64":
            blocks = _shipped_gpt_blocks(S)
        out = pallas_flash_attention(
            q, k, v, causal, *blocks, True  # interpret mode
        )
        mask = _causal_mask(S) if causal else None
        ref = reference_attention(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

    @pytest.mark.parametrize(
        "heads, kv_heads, head_dim",
        [(2, 2, 64), (5, 5, 64), (4, 2, 64), (6, 3, 64), (2, 1, 128),
         (8, 2, 32)],
        ids=["two_heads_one_block", "odd_count_last_block_half_empty",
             "gqa_both_q_heads_on_one_kv_half", "gqa_odd_kv_count",
             "d128_one_head_a_block", "d32_four_heads_a_block"],
    )
    def test_each_head_is_its_own(self, heads, kv_heads, head_dim):
        """Heads share a 128-lane block of [B, S, H*D]: every head of the
        result must be the attention of that head alone (heads of very
        different sizes, so lanes of a neighbour would show), and equal
        whatever else is in the block."""
        B, S = 1, 128
        q, k, v = _qkv(9, B, S, heads, head_dim, kv_heads=kv_heads)
        sizes = 10.0 ** jnp.arange(kv_heads)  # 1, 10, 100, ...
        v = v * sizes[None, None, :, None]
        out = pallas_flash_attention(q, k, v, True, 64, 64, True)
        groups = heads // kv_heads
        for h in range(heads):
            g = h // groups
            alone = reference_attention(
                q[:, :, h:h + 1], k[:, :, g:g + 1], v[:, :, g:g + 1],
                _causal_mask(S),
            )
            np.testing.assert_allclose(
                out[:, :, h:h + 1] / sizes[g], alone / sizes[g],
                rtol=2e-3, atol=2e-3,
            )

    def test_gqa_expansion(self):
        B, S, H, D = 1, 128, 8, 32
        q, k, v = _qkv(1, B, S, H, D, kv_heads=2)
        out = pallas_flash_attention(q, k, v, True, 64, 64, True)
        ref = reference_attention(q, k, v, _causal_mask(S))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

    def test_bf16_inputs(self):
        B, S, H, D = 1, 128, 2, 64
        q, k, v = _qkv(2, B, S, H, D, dtype=jnp.bfloat16)
        out = pallas_flash_attention(q, k, v, True, 64, 64, True)
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(q, k, v, _causal_mask(S))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    @pytest.mark.parametrize(
        "shape, blocks",
        [((1, 128, 2, 32), (64, 64)),
         ((1, 256, 2, 64), "shipped_s1024_d64"),
         ((2, 128, 5, 64), (64, 64)),
         ((1, 128, 2, 128), (64, 64))],
        ids=["d32", "d64_shipped_blocks", "d64_5_heads", "d128"],
    )
    def test_gradients_match_reference(self, shape, blocks):
        B, S, H, D = shape
        q, k, v = _qkv(3, B, S, H, D)
        # heads of different sizes: a lane taken from the neighbour in
        # the same 128-lane block would not pass for rounding
        v = v * (1.0 + jnp.arange(H))[None, None, :, None]
        if blocks == "shipped_s1024_d64":
            blocks = _shipped_gpt_blocks(S)

        def loss_flash(q_, k_, v_):
            return jnp.sum(
                pallas_flash_attention(q_, k_, v_, True, *blocks, True) ** 2
            )

        def loss_ref(q_, k_, v_):
            return jnp.sum(
                reference_attention(q_, k_, v_, _causal_mask(S)) ** 2
            )

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert np.isfinite(np.asarray(a)).all()
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale,
                rtol=2e-3, atol=2e-3,
            )

    @pytest.mark.parametrize(
        "heads, head_dim", [(2, 64), (3, 64), (1, 128)],
        ids=["two_heads_a_block", "odd_count", "d128"],
    )
    def test_backward_kernels_take_delta_from_o(self, heads, head_dim):
        """``delta = rowsum(dO * O)`` is computed inside both backward
        kernels from the blocks of dO and O, head by head.  With an O
        that is NOT the attention's output, the gradients must be the
        ones the formulas give with that delta."""
        from dlrover_tpu.ops.pallas.flash_attention import (
            LANES,
            _flash_backward,
        )

        B, S = 1, 128
        q, k, v = _qkv(10, B, S, heads, head_dim)
        o, do, _ = _qkv(11, B, S, heads, head_dim)
        scale = head_dim ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(_causal_mask(S), s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)  # [B, H, S]
        p = jnp.exp(s - lse[..., None])
        delta = jnp.einsum("bqhd,bqhd->bhq", do, o)
        ds = p * (jnp.einsum("bqhd,bkhd->bhqk", do, v)
                  - delta[..., None]) * scale
        want = (jnp.einsum("bhqk,bkhd->bqhd", ds, k),
                jnp.einsum("bhqk,bqhd->bkhd", ds, q),
                jnp.einsum("bhqk,bqhd->bkhd", p, do))
        got = _flash_backward(
            q, k, v, o, jnp.broadcast_to(lse[..., None], (*lse.shape, LANES)),
            do, True, 64, 64, True,
        )
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_gradients_match_reference(self, causal):
        """The backward's group-summed dK/dV must match reference grads."""
        B, S, H, D = 1, 128, 4, 32
        q, k, v = _qkv(8, B, S, H, D, kv_heads=2)
        mask = _causal_mask(S) if causal else None

        def loss_flash(q_, k_, v_):
            return jnp.sum(
                pallas_flash_attention(q_, k_, v_, causal, 64, 64, True)
                ** 2
            )

        def loss_ref(q_, k_, v_):
            return jnp.sum(reference_attention(q_, k_, v_, mask) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape  # dk/dv at KV head count
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
            )

    def test_indivisible_seq_raises(self):
        q, k, v = _qkv(4, 1, 100, 2, 32)
        with pytest.raises(ValueError):
            pallas_flash_attention(q, k, v, True, 64, 64, True)


class TestFlashAttentionDispatch:
    """``ops.attention.flash_attention`` is the kernel and nothing else:
    it never turns into the reference, and under a mesh of several
    devices it runs per shard through ``shard_map``."""

    def test_off_the_chip_it_raises(self):
        q, k, v = _qkv(0, 1, 128, 2, 64)
        with pytest.raises(RuntimeError, match="needs a TPU backend"):
            flash_attention(q, k, v)

    def test_model_with_flash_raises_off_the_chip(self):
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        model = LlamaForCausalLM(LlamaConfig.tiny(attention_impl="flash"))
        ids = jnp.zeros((2, 16), jnp.int32)
        with pytest.raises(RuntimeError, match="needs a TPU backend"):
            model.init(jax.random.PRNGKey(0), ids)

    def test_interpret_by_argument_matches_reference(self):
        q, k, v = _qkv(1, 2, 128, 4, 64, kv_heads=2)
        out = flash_attention(q, k, v, interpret=True)
        ref = reference_attention(q, k, v, _causal_mask(128))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("head_dim, per_block", [(128, 1), (64, 2)])
    def test_the_direct_call_writes_the_path_record(
        self, monkeypatch, head_dim, per_block
    ):
        """The Llama code calls ``flash_attention`` itself: the
        ``attention.path`` record comes from here, not from the chooser."""
        records = []
        monkeypatch.setattr(
            attention.trace, "note_trace_time",
            lambda name, **attrs: records.append((name, attrs)),
        )
        q, k, v = _qkv(1, 1, 128, 4, head_dim, kv_heads=2)
        flash_attention(q, k, v, interpret=True)
        assert records == [("attention.path", dict(
            impl="flash", seq=128, head_dim=head_dim, heads=4,
            blocks=tuned_blocks(128, head_dim), layout="bsd",
            heads_per_block=per_block,
        ))]

    @pytest.mark.parametrize(
        "layout, batch_axes",
        [(dict(fsdp=4), ("fsdp",)), (dict(dp=2, fsdp=2), ("dp", "fsdp")),
         (dict(fsdp=2, tp=2), ("fsdp",))],
        ids=["fsdp4", "dp2_fsdp2", "fsdp2_tp2"],
    )
    def test_sharded_under_a_mesh(self, layout, batch_axes):
        """Forward and backward through the wrap agree with the
        reference, and the output stays sharded: batch over the data
        axes, heads over tp."""
        mesh = build_mesh(
            MeshConfig(**{"dp": 1, **layout}), devices=jax.devices()[:4]
        )
        q, k, v = _qkv(2, 4, 128, 4, 64, kv_heads=2)
        mask = _causal_mask(128)

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, interpret=True)
            return (out ** 2).sum(), out

        def ref_loss(q, k, v):
            return (reference_attention(q, k, v, mask) ** 2).sum()

        with mesh:
            (_, out), grads = jax.jit(
                jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                   has_aux=True)
            )(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(
            out, reference_attention(q, k, v, mask), atol=2e-5, rtol=2e-5
        )
        for g, w in zip(grads, want):
            np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)
        spec = out.sharding.spec
        got = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        assert got == batch_axes
        if "tp" in layout:
            assert spec[2] == "tp"

    def test_inside_a_shard_map_it_does_not_wrap_again(self):
        """The manual grad-sync step runs the model inside a shard_map:
        the mesh axes are already manual there, the call is per shard."""
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.parallel.collectives import shard_map_unchecked

        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        q, k, v = _qkv(3, 4, 128, 2, 64)
        fn = shard_map_unchecked(
            lambda q, k, v: flash_attention(q, k, v, interpret=True),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
        with mesh:
            out = jax.jit(fn)(q, k, v)
        ref = reference_attention(q, k, v, _causal_mask(128))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _gpt_head_dims():
    from dlrover_tpu.models.gpt import GPTConfig

    medium = GPTConfig(n_embd=1024, n_layer=24, n_head=16)
    return {
        name: (cfg.block_size, cfg.n_embd // cfg.n_head, cfg.n_head,
               cfg.n_head)
        for name, cfg in [("gpt2_medium", medium),
                          ("gpt2_xl", GPTConfig.gpt2_xl()),
                          ("gpt_tiny", GPTConfig.tiny())]
    }


@pytest.fixture
def kernel_by_interpreter(monkeypatch):
    """Steer ``causal_attention`` onto the kernel path off the chip: the
    backend reads as a TPU and the kernel runs in the Pallas interpreter.
    The program has no option for either."""
    import functools

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention, "flash_attention",
        functools.partial(flash_attention, interpret=True),
    )


class TestCausalAttentionChoice:
    """``ops.attention.causal_attention``: the kernel wherever it can
    run, chosen from the backend and the shape while the step is traced."""

    @pytest.mark.parametrize(
        "backend, shape, path",
        [("tpu", "gpt2_medium", "flash"),
         ("tpu", "gpt2_xl", "flash"),        # 25 heads: an odd count
         ("tpu", (2048, 128, 32, 8), "flash"),
         ("tpu", (1024, 64, 4, 2), "flash"),
         ("tpu", "gpt_tiny", "reference"),   # head size 16, S 64
         ("tpu", (1000, 64, 16, 16), "reference"),  # no block divides it
         ("tpu", (1024, 80, 16, 16), "reference"),  # head size not run yet
         ("tpu", (1024, 64, 6, 4), "reference"),  # kv heads do not divide
         ("cpu", "gpt2_medium", "reference"),
         ("gpu", (2048, 128, 32, 8), "reference")],
    )
    def test_path_by_backend_and_shape(self, backend, shape, path):
        if isinstance(shape, str):
            shape = _gpt_head_dims()[shape]
        assert attention.attention_path(backend, *shape) == path

    def test_the_kernel_path_agrees_with_the_reference(
        self, kernel_by_interpreter
    ):
        q, k, v = _qkv(4, 2, 256, 2, 64)
        mask = _causal_mask(256)
        got = attention.causal_attention(q, k, v, mask)
        np.testing.assert_allclose(
            got, reference_attention(q, k, v, mask), atol=2e-5, rtol=2e-5
        )

    def test_off_the_chip_it_is_the_reference_to_the_bit(self):
        q, k, v = _qkv(5, 2, 128, 2, 64)
        mask = _causal_mask(128)
        np.testing.assert_array_equal(
            attention.causal_attention(q, k, v, mask),
            reference_attention(q, k, v, mask),
        )


def _gpt_d64(dtype, **kw):
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=256, n_embd=128, n_layer=2, n_head=2,
                    block_size=128, dtype=dtype, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    return GPT(cfg), ids


class TestGPTThroughTheKernel:
    """A GPT of head size 64: the kernel path (steered, in the
    interpreter) against the reference path the CPU takes by itself."""

    @pytest.mark.parametrize(
        "dtype, tol",
        # float32 holds the wiring (mask, scale, layout) to rounding;
        # bfloat16 is what trains, at the tolerance of the Llama ring
        # parity test below
        [(jnp.float32, 2e-4), (jnp.bfloat16, 5e-2)],
        ids=["float32", "bfloat16"],
    )
    def test_forward_and_parameter_gradients_match(
        self, request, monkeypatch, dtype, tol
    ):
        from dlrover_tpu.trainer.train import cross_entropy_loss

        model, ids = _gpt_d64(dtype)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]

        def loss(p):
            logits = model.apply({"params": p}, ids)
            return cross_entropy_loss(logits, ids), logits

        run = jax.jit(jax.value_and_grad(loss, has_aux=True))
        (want_loss, want_logits), want_grads = run(params)
        request.getfixturevalue("kernel_by_interpreter")
        paths = []
        monkeypatch.setattr(
            attention.trace, "note_trace_time",
            lambda name, **kw: paths.append(
                (kw["impl"], kw.get("layout"), kw.get("heads_per_block"))),
        )
        run = jax.jit(jax.value_and_grad(loss, has_aux=True))
        (got_loss, got_logits), got_grads = run(params)
        assert paths and set(paths) == {("flash", "bsd", 2)}
        np.testing.assert_allclose(got_loss, want_loss, rtol=tol)
        np.testing.assert_allclose(
            got_logits, want_logits, rtol=tol, atol=tol
        )
        for got, want in zip(jax.tree.leaves(got_grads),
                             jax.tree.leaves(want_grads)):
            scale = max(1e-6, float(jnp.abs(want).max()))
            np.testing.assert_allclose(
                got / scale, want / scale, atol=tol, rtol=tol
            )

    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scanned", "unrolled"])
    @pytest.mark.parametrize("steered", [False, True],
                             ids=["reference", "flash"])
    def test_one_path_record_for_each_trace(
        self, request, scan_layers, steered
    ):
        from dlrover_tpu.observability import trace

        if steered:
            request.getfixturevalue("kernel_by_interpreter")
        model, ids = _gpt_d64(jnp.bfloat16, scan_layers=scan_layers)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)

        def traced_under_a_span():
            with trace.span("trainer.step.dispatch") as open_span:
                jax.eval_shape(
                    jax.grad(lambda p: model.apply(p, ids).sum()), params
                )
            return [e for e in open_span.events
                    if e["name"] == "attention.path"]

        for _ in range(2):  # a second trace makes a second record
            (record,) = traced_under_a_span()
            want = dict(impl="reference", seq=128, head_dim=64, heads=2,
                        blocks=None)
            if steered:
                want.update(impl="flash", blocks=tuned_blocks(128, 64),
                            layout="bsd", heads_per_block=2)
            assert record["attrs"] == want

    def test_with_no_span_open_the_record_is_a_span_of_its_own(
        self, monkeypatch
    ):
        from dlrover_tpu.observability import trace

        monkeypatch.setattr(trace, "_noted_without_span", set())
        exported = []
        trace.set_span_sink(exported.append)
        try:
            model, ids = _gpt_d64(jnp.bfloat16, scan_layers=False)
            assert trace.current_span() is None
            jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        finally:
            trace.set_span_sink(None)
        (record,) = [r for r in exported if r["name"] == "attention.path"]
        assert record["attrs"]["impl"] == "reference"
        assert record["attrs"]["head_dim"] == 64


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=2, cp=4))
        B, S, H, D = 2, 64, 4, 16
        q, k, v = _qkv(5, B, S, H, D)
        out = ring_attention_sharded(mesh, q, k, v, causal=causal)
        mask = _causal_mask(S) if causal else None
        ref = reference_attention(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

    def test_cp8_full_ring(self):
        mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, cp=8))
        B, S, H, D = 1, 64, 2, 16
        q, k, v = _qkv(6, B, S, H, D)
        out = ring_attention_sharded(mesh, q, k, v, causal=True)
        ref = reference_attention(q, k, v, _causal_mask(S))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

    def test_gqa(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1, cp=4))
        B, S, H, D = 2, 32, 4, 16
        q, k, v = _qkv(7, B, S, H, D, kv_heads=2)
        out = ring_attention_sharded(mesh, q, k, v, causal=True)
        ref = reference_attention(q, k, v, _causal_mask(S))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )


class TestRingAttentionInModel:
    @pytest.mark.slow
    def test_llama_ring_attention_trains(self):
        """attention_impl='ring' on a cp=2 mesh: loss decreases and the
        result stays consistent with the reference implementation."""
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from dlrover_tpu.trainer.train import Trainer

        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=2, cp=2))
        cfg = LlamaConfig.tiny(
            attention_impl="ring", remat=False, scan_layers=False
        )
        model = LlamaForCausalLM(cfg)
        trainer = Trainer(model, optax.adamw(1e-2), mesh)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 33))
        batch = {
            "input_ids": np.asarray(ids[:, :-1], np.int32),
            "labels": np.asarray(ids[:, 1:], np.int32),
        }
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        losses = []
        for _ in range(4):
            state, m = trainer.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

        # numerics agree with reference attention on the same params
        cfg_ref = LlamaConfig.tiny(remat=False, scan_layers=False)
        model_ref = LlamaForCausalLM(cfg_ref)
        with mesh:
            import flax.linen as nn

            from dlrover_tpu.parallel.sharding import DEFAULT_LOGICAL_RULES

            with nn.logical_axis_rules(DEFAULT_LOGICAL_RULES):
                out_ring = model.apply(
                    {"params": state.params}, batch["input_ids"]
                )
                out_ref = model_ref.apply(
                    {"params": state.params}, batch["input_ids"]
                )
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_ref), rtol=5e-2, atol=5e-2
        )
