"""Compile the main path's kernels and step for a DESCRIBED TPU v5e.

The TPU compiler is installed where the tests run, and it compiles for a
chip that is described and not attached: what it refuses here (a kernel
that cannot be partitioned, a tile that does not align, a program that
does not fit) costs no chip time.  Nothing here runs, so nothing here
says anything about results or times.

This is the only test file that describes a chip.  The topology is
described inside a module-scoped fixture — never at import, in a
``skipif``, in ``parametrize`` or in ``conftest.py``: only one process at
a time may load the TPU's library, every xdist worker imports every test
file, and the worker that is given this file is the one that loads it.
"""

import dataclasses
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.pallas import ring_reduce_scatter as ring
from dlrover_tpu.ops.pallas.flash_attention import pallas_flash_attention
from dlrover_tpu.ops.pallas.tuning import tuned_blocks
from dlrover_tpu.parallel.collectives import shard_map_unchecked
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no compiler: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # this file asserts what the TPU compiler makes of a program (kernel
    # counts, what a loop holds, that a cut fits), so the suite's constant
    # (``conftest.py``: most optimisations off) is out for the module too
    unoptimised = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield desc
    jax.config.update("jax_disable_most_optimizations", unoptimised)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.array(topo.devices), ("dp",))


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    the test steers it, the program has no option for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _fa2_fwd_bwd(q, k, v):
    block_q, block_kv = tuned_blocks(q.shape[1], q.shape[-1])

    def loss(q, k, v):
        out = pallas_flash_attention(q, k, v, True, block_q, block_kv)
        return out.astype(jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _attend_calls(text):
    """The optimized HLO's lines that are FA2 custom calls (named after
    the ``_attend`` scope), as the device trace would name them."""
    return [line.strip() for line in text.split("\n")
            if "_attend" in line.split(" = ")[0]
            and 'custom_call_target="tpu_custom_call"' in line]


def _kernel_names(text):
    """The names of the optimized HLO's Pallas kernel calls."""
    return re.findall(r"%([\w.\-]+) = [^\n]*? custom-call\([^\n]*"
                      r'custom_call_target="tpu_custom_call"', text)


def _switch_branches(text, count=4):
    """The bodies of every ``switch`` over ``count`` branches, first
    branch first."""
    return [[text[text.index(f"\n{name.strip()} ("):].split("\n}\n")[0]
             for name in names.split(",")]
            for names in re.findall(r"branch_computations=\{([^}]*)\}", text)
            if names.count(",") == count - 1]


def _sums_by_token_as_the_path_says(text, combine, slots, width):
    """Every switch over the ladder: a rung whose sums by token run over
    the rows held (``moe.path``'s ``combine=``) holds no array of the
    slots' extent times the width, a rung that gathers the slots does."""
    switches = _switch_branches(text, len(combine))
    assert len(switches) >= 2           # forward and backward
    tokens = slots // 8
    whole = re.compile(
        rf"\[({slots},{width}|{tokens},8,{width}|{tokens},{width},8)\]")
    for branches in switches:
        assert [bool(whole.search(body)) for body in branches] == [
            body == "slots" for body in combine]


def _load_layer_metric(name):
    """``benchmarks/layer_metrics/<name>.py``, loaded by path as
    ``benchmarks/common.py::load_module`` loads it."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _benchmark_kind_of():
    return _load_layer_metric("fa2_ms_per_step").kind_of


@pytest.mark.parametrize(
    "shape", [(4, 2048, 16, 128), (16, 1024, 16, 64)],
    ids=["b4_s2048_h16_d128", "b16_s1024_h16_d64"],
)
class TestFlashAttentionKernel:
    def test_forward_compiles(self, one_chip, shape):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        block_q, block_kv = tuned_blocks(shape[1], shape[-1])
        compiled = jax.jit(
            lambda q, k, v: pallas_flash_attention(
                q, k, v, True, block_q, block_kv
            )
        ).lower(x, x, x).compile()
        assert "tpu_custom_call" in compiled.as_text()

    def test_backward_compiles(self, one_chip, shape):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        compiled = jax.jit(_fa2_fwd_bwd).lower(x, x, x).compile()
        # forward, dQ, and dK/dV kernels
        assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize(
    "heads, blocks, window, band",
    [(64, None, 512, "one_visit"), (64, (256, 256), 512, "one_visit"),
     (64, (1024, 512), 512, "one_visit"), (64, (512, 128), 512, "one_visit"),
     (64, (512, 1024), 512, "streamed"), (48, None, None, None)],
    ids=["window_512_shipped_blocks", "window_512_blocks_256",
         "window_512_blocks_1024_512", "window_512_blocks_512_128",
         "window_512_streamed_512_1024", "full_groups_of_6"])
def test_windowed_fa2_kernels_compile_at_the_cells_shape(
        one_chip, heads, blocks, window, band):
    """The Laguna cell's two kinds of call at one sequence of 16,384 on 8
    key heads: 64 query heads under a window of 512 (the band kernels, one
    visit a query block, at the shipped blocks and three more; the
    streamed ones, whose axis has the band's blocks alone, where the key
    block does not tile the query block) and 48 causal ones (groups of 6,
    a count no other cell has), whose backward is ONE call since PR 54
    (``backward_path``: dQ a float32 result the kernel itself copies
    blocks of, cast after the call).  Three custom calls, two for the
    full layers', nothing ``[S, S]``."""
    from dlrover_tpu.ops.pallas.flash_attention import (
        ONE_CALL, backward_path, band_path)

    S = 16384
    q = jax.ShapeDtypeStruct((1, S, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, 8, 128), jnp.bfloat16, sharding=one_chip)
    block_q, block_kv = blocks or tuned_blocks(S, 128, window)
    if window is not None:
        assert band_path(S, block_q, block_kv, window, 128) == band

    def loss(q, k, v):
        out = pallas_flash_attention(
            q, k, v, True, block_q, block_kv, False, window)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    one_call = backward_path(S, 128, block_q, block_kv, window) == ONE_CALL
    assert one_call == (window is None)
    assert len(_kernel_names(text)) == (2 if one_call else 3)
    if one_call:    # the accumulator is the call's own result, float32
        assert re.search(
            r"\(f32\[1,16384,6144\]\S*, bf16\[1,16384,1024\]\S*, "
            r"bf16\[1,16384,1024\]\S*\) custom-call", text)
    assert not re.search(r"\[[\d,]*16384,16384[\d,]*\]", text)


@pytest.mark.parametrize("keys", [512, 2560, 8192])
def test_selected_attention_kernels_compile_and_the_reader_sees_them(
        one_chip, keys):
    """The Keye cell's shapes: a block of 512 queries, 32 heads on 4 kv
    heads of 128, an early, an odd and the last block's keys.  Forward,
    heads' mean and backward are three custom calls, and each carries the
    block's mask ``[1, 512, keys]``, which is how
    ``benchmarks/layer_metrics/sparse_attn_ms_per_step.py`` knows them."""
    from dlrover_tpu.ops.pallas.selected_attention import selected_attention
    from dlrover_tpu.ops.pallas.tuning import selected_tiling

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tiling = selected_tiling(512, 128)

    def both(q, k, v, keep):
        def loss(q, k, v):
            out, target = selected_attention(q, k, v, keep, tiling)
            return out.astype(jnp.float32).sum(), target

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    kv = sds((1, keys, 4, 128), jnp.bfloat16)
    text = jax.jit(both).lower(
        sds((1, 512, 32, 128), jnp.bfloat16), kv, kv,
        sds((1, 512, keys), jnp.bool_)).compile().as_text()
    calls = [line.strip() for line in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    is_sparse_attn_op = _load_layer_metric(
        "sparse_attn_ms_per_step").is_sparse_attn_op
    shape = {"batch": 1, "block": 512, "seq": 8192}
    assert all(is_sparse_attn_op(call, shape) for call in calls)


def test_latent_attention_kernels_compile_and_hold_nothing_seq_by_seq(
        one_chip):
    """The Ling-3.0 cell's shapes: 16384 positions, 8 heads, scores over
    128 + 64 against one shared rotary key, values of 128.  The forward
    and the ONE backward call are two custom calls named after the
    ``latent`` scope, and no array of the compiled program has two
    dimensions of the sequence."""
    from dlrover_tpu.ops.pallas.latent_attention import (
        blocks_for,
        latent_attention_kernels,
    )

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    S, H = 16384, 8
    blocks = blocks_for(S)

    def both(q_nope, q_pe, k_nope, k_pe, v):
        def loss(*operands):
            with jax.named_scope("latent"):
                out = latent_attention_kernels(*operands, *blocks)
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            q_nope, q_pe, k_nope, k_pe, v)

    wide = sds(1, S, H, 128)
    text = jax.jit(both).lower(
        wide, sds(1, S, H, 64), wide, sds(1, S, 64), wide).compile().as_text()
    names = _kernel_names(text)
    assert len(names) == 2 and all("latent" in name for name in names)
    assert not re.search(r"\[[\d,]*16384,16384[\d,]*\]", text)
    # the shared key's gradient: a head at a time out of the kernel, summed
    assert f"f32[1,{H},{S},64]" in text


@pytest.mark.parametrize("keys", [512, 2560, 8192])
def test_index_scores_kernels_compile_and_the_reader_sees_them(
        one_chip, keys):
    """The Keye cell's indexer: a block of 512 queries, 16 index heads of
    64 on one key head.  The scores and their gradient are two custom
    calls; the forward returns ``I f32[1, 512, keys]`` alone and the
    backward takes ``dI`` of that shape, which is how
    ``benchmarks/layer_metrics/sparse_attn_ms_per_step.py`` knows both,
    and both carry the heads' weights ``f32[1, 512, 16]``, which is how
    ``scripts/fa_blocks_in_step.py --index`` knows them from the selected
    attention's."""
    from dlrover_tpu.ops.pallas.index_scores import index_scores, kernels_take
    from dlrover_tpu.ops.pallas.tuning import index_tiling

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert kernels_take(512, 16, 64)
    tiling = index_tiling(512, 64)

    def both(index_q, index_k, index_w, weights):
        def loss(*index):
            return (index_scores(*index, tiling) * weights).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(
            index_q, index_k, index_w)

    text = jax.jit(both).lower(
        sds((1, 512, 16, 64), jnp.bfloat16), sds((1, keys, 64), jnp.bfloat16),
        sds((1, 512, 16), jnp.float32), sds((1, 512, keys), jnp.float32),
    ).compile().as_text()
    calls = [line.strip() for line in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    forward, backward = sorted(calls, key=lambda call: "= (" in call)
    assert f"= f32[1,512,{keys}]" in forward
    assert all(f"{dtype}[1,{rows},{cols}]" in backward.split("custom-call")[0]
               for dtype, rows, cols in (("bf16", 512, 1024),
                                         ("bf16", keys, 64), ("f32", 512, 16)))
    is_sparse_attn_op = _load_layer_metric(
        "sparse_attn_ms_per_step").is_sparse_attn_op
    shape = {"batch": 1, "block": 512, "seq": 8192}
    assert len(calls) == 2
    assert all(is_sparse_attn_op(call, shape) for call in calls)
    assert all("f32[1,512,16]" in call for call in calls)
    assert "[1,512,16,%d]" % keys not in text   # no head's products whole


@pytest.mark.parametrize(
    "q_shape, kv_heads",
    [((3, 1024, 25, 64), 25), ((2, 1024, 4, 64), 2),
     ((2, 2048, 32, 128), 8)],
    ids=["25_heads_of_64_last_block_half_empty",
         "gqa_inside_a_block_kv_lanes_turned", "gqa_32_over_8_of_128"],
)
def test_kernel_compiles_at_every_head_layout(one_chip, q_shape, kv_heads):
    """What the interpreter cannot refuse: a 128-lane block that ends past
    the array (an odd count of 64-wide heads), and the lane rotation that
    brings a kv head under its q head when GQA meets two heads a block."""
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(
        (*q_shape[:2], kv_heads, q_shape[3]), jnp.bfloat16,
        sharding=one_chip)
    compiled = jax.jit(_fa2_fwd_bwd).lower(q, kv, kv).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


def test_sharded_flash_attention_compiles_on_fsdp4(topo, as_if_on_tpu):
    """The production call (``ops.attention.flash_attention``) inside a
    program sharded over four chips: a bare Mosaic call cannot be
    partitioned, the shard_map wrap can."""
    mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
    data = NamedSharding(mesh, P(("dp", "fsdp")))
    x = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16, sharding=data)

    def loss(q, k, v):
        return attention.flash_attention(q, k, v).astype(jnp.float32).sum()

    with mesh:
        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2))
        ).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # each chip holds its quarter of the batch, whole sequences
    grads = compiled.output_shardings[1]
    assert all(g.spec[0] in ("fsdp", ("dp", "fsdp"), ("fsdp",))
               for g in grads)


def test_pallas_accumulate_ring_compiles_on_four_chips(ring_mesh):
    fn = shard_map_unchecked(
        # interpret=False by argument: left to itself the ring asks
        # jax.default_backend(), which is still the CPU here
        lambda t: ring.ring_reduce_scatter(
            t[0], "dp", 4, accum="pallas", interpret=False
        )[None],
        mesh=ring_mesh, in_specs=P("dp"), out_specs=P("dp"),
    )
    x = jax.ShapeDtypeStruct(
        (4, 4, 1024), jnp.float32,
        sharding=NamedSharding(ring_mesh, P("dp")),
    )
    compiled = jax.jit(fn).lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_rdma_ring_compiles_on_four_chips(ring_mesh):
    fn = shard_map_unchecked(
        lambda t: ring.rdma_ring_reduce_scatter(t[0], "dp", 4)[None],
        mesh=ring_mesh, in_specs=P("dp"), out_specs=P("dp"),
    )
    x = jax.ShapeDtypeStruct(
        (4, 4, 1024), jnp.float32,
        sharding=NamedSharding(ring_mesh, P("dp")),
    )
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _llama_1b_2_layers():
    """The 1.24B widths cut to 2 layers, B4 S2048."""
    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = dataclasses.replace(
        LlamaConfig.llama2_1b(max_seq_len=2048, attention_impl="flash"),
        num_layers=2,
    )
    return LlamaForCausalLM(cfg), (4, 2048)


def _trainer_step_compiled(mesh, model_and_batch=_llama_1b_2_layers):
    """The whole ``Trainer`` step with the optimizer and dtypes the
    benchmark's cells train with, lowered from shapes (a described device
    holds no array) and compiled."""
    from dlrover_tpu.trainer.optim import create_optimizer
    from dlrover_tpu.trainer.train import Trainer

    model, batch_shape = model_and_batch()
    opt = create_optimizer(
        peak_lr=3e-4, warmup_steps=10, total_steps=10_000,
        moment_dtype=jnp.bfloat16,
    )
    trainer = Trainer(model, opt, mesh, grads_dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    sample = np.zeros(batch_shape, np.int32)
    shardings = trainer.state_sharding_for(rng, sample)
    trainer.state_shardings = shardings
    # the shardings are a prefix tree of the (boxed) state: one
    # NamedSharding stands for everything inside a partitioning box
    state = jax.tree.map(
        lambda s, sub: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            sub,
        ),
        shardings, trainer.abstract_state(rng, sample),
    )
    data = NamedSharding(mesh, P(trainer.data_axes))
    ids = jax.ShapeDtypeStruct(batch_shape, jnp.int32, sharding=data)
    batch = {"input_ids": ids, "labels": ids}
    return trainer.lower_train_step(state, batch).compile()


@pytest.fixture(scope="module")
def llama_one_chip_text(topo):
    """The Llama widths' step compiled once for the tests that read it."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        return _trainer_step_compiled(mesh).as_text()


class TestTrainerStep:
    def test_one_chip(self, llama_one_chip_text):
        text = llama_one_chip_text
        # forward, recomputed forward, dQ, dK/dV: found by their scope
        assert len(_attend_calls(text)) == 4
        assert "all-gather" not in text and "all-reduce" not in text

    def test_the_benchmark_tells_the_three_kernels_apart(
        self, llama_one_chip_text
    ):
        """``fa2_ms_per_step`` reads the kernels' kinds from their result
        forms: (out, float32 statistics), one array, two arrays."""
        kind_of = _benchmark_kind_of()
        assert sorted(
            kind_of(call) for call in _attend_calls(llama_one_chip_text)
        ) == ["dkv", "dq", "fwd", "fwd"]

    def test_gpt2_medium_one_chip(self, topo, as_if_on_tpu):
        """The GPT code has no attention option: at B16 S1024 with 16
        heads of 64 it takes the kernel by itself, in the forward, the
        recomputed forward and both halves of the backward, and no
        [B, H, S, S] array is left in the step."""
        from dlrover_tpu.models.gpt import GPT, GPTConfig

        def gpt2_medium():
            return GPT(GPTConfig(n_embd=1024, n_layer=24, n_head=16)), (
                16, 1024)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        text = _trainer_step_compiled(mesh, gpt2_medium).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 4
        assert len(_attend_calls(text)) == 4
        assert "[16,16,1024,1024]" not in text
        # the kernels take [B, S, H*D] as it is: nothing of the
        # [B, H, S, D] or [B*H, S, D] forms is left, in any operation,
        # and delta is no array (the one f32 of 128 lanes a row is the
        # LSE residual, [B, H, S, 128], written by the forward kernel)
        for gone in ("[16,16,1024,64]", "[256,1024,64]", "[256,1024,128]"):
            assert gone not in text
        moved = re.findall(
            r"= \w+\[16,16,1024,128\]\S* (copy|transpose|broadcast)\(", text)
        assert not moved

    def test_gpt2_xl_widths_one_block(self, topo, as_if_on_tpu):
        """25 heads of 64: an odd count keeps the kernel (the last
        128-lane block holds one head), and no [B, H, S, S] array."""
        from dlrover_tpu.models.gpt import GPT, GPTConfig

        def gpt2_xl_one_block():
            cfg = dataclasses.replace(GPTConfig.gpt2_xl(), n_layer=1)
            return GPT(cfg), (3, 1024)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        text = _trainer_step_compiled(mesh, gpt2_xl_one_block).as_text()
        # one layer: the recomputed forward may fold into the forward
        kind_of = _benchmark_kind_of()
        assert {kind_of(call) for call in _attend_calls(text)} == {
            "fwd", "dq", "dkv"}
        assert "[3,25,1024,1024]" not in text

    def test_fsdp4(self, topo, as_if_on_tpu):
        mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
        compiled = _trainer_step_compiled(mesh)
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert "all-gather" in text  # ZeRO-3: params gathered per layer
        mem = compiled.memory_analysis()
        # 2 layers at these widths: 266M params, fp32 masters + bf16
        # moments = 8 bytes each, a quarter of it on every chip
        assert mem.argument_size_in_bytes < 0.3 * 266e6 * 8 + (1 << 20)


    def test_evabyte_widths_one_layer(self, topo, as_if_on_tpu):
        """One layer of EvaByte at B1 S16384 (the cell's shapes; the scan
        makes depth one trace): the attention over windows and summaries
        goes through the mask-operand kernels, a window a call (forward, the
        layer's rematerialised forward, backward), so no window's scores are
        an array, no array has two dimensions of the whole sequence, the
        benchmark's reader knows every call by the window's mask ``s8[1,
        2048, 2048]``, and the step fits the chip beside a float32
        residual."""
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        def evabyte_one_layer():
            cfg = LlamaConfig(
                vocab_size=320, hidden_size=4096, intermediate_size=11008,
                num_layers=1, num_heads=32, num_kv_heads=32, head_dim=128,
                max_seq_len=16384, rope_theta=1e5, eva_window=2048,
                eva_chunk=16, norm_unit_offset=True,
                residual_dtype=jnp.float32, pred_heads=8)
            return LlamaForCausalLM(cfg), (1, 16384)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, evabyte_one_layer)
        text = compiled.as_text()
        assert "16384,16384]" not in text
        assert not re.search(r"f32\[(1,)?32,2048,(2048|2944)\]", text)
        eva = _load_layer_metric("eva_attn_ms_per_step")
        shape = {"batch": 1, "seq": 16384, "window": 2048, "chunk": 16,
                 "windows": 8, "heads": 32, "head_dim": 128}
        calls = [line.strip() for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        # forward and backward; with one layer the compiler finds the
        # rematerialised forward in the forward (the cell's four have it)
        assert len(calls) == 8 * 2
        assert all("s8[1,2048,2048]" in call for call in calls)
        assert all(eva.is_window_op(call, shape) for call in calls)
        # the summaries are operands of their own, as many as came before
        assert sum("bf16[1,896,4096]" in call for call in calls) == 2
        pooling = [line.strip() for line in text.splitlines()
                   if line.startswith("  %") and " fusion(" in line
                   and eva.is_pool_op(line.strip(), shape)]
        assert len(pooling) >= 4    # of keys and of values, with gradients
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9

    def test_solar_open2_widths_one_period(self, topo, as_if_on_tpu,
                                           monkeypatch):
        """One period of Solar-Open2 at B1 S8192 and the cell's share (8
        delta-rule heads, 8 query heads on 1 key head, 10 of 320 experts
        beside the shared one): one FA2 layer through the kernel, three
        delta-rule layers through theirs (``ops/pallas/kda.py``: the work
        inside chunks and the walk between them, forward, rematerialised
        and backward, a head's float32 state in the walk's scratch), no
        array with two dimensions of the whole sequence and none of the
        ``jax.numpy`` body's decayed keys, pair-by-pair products or
        triangular solve, every instruction of the delta rule under one of
        the sub-scopes the benchmark's readers sum, and the step fits the
        chip with no more temporaries than it held in ``jax.numpy``; at
        the ladder's three small extents the sums by token run over the
        rows held and nothing has the extent of the 65,536 slots times the
        width."""
        from dlrover_tpu.models.llama import LlamaForCausalLM
        from dlrover_tpu.models.moe import MoELlamaConfig
        from dlrover_tpu.observability import trace

        paths = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: paths.append(attrs.get("combine")))

        def one_period():
            cfg = MoELlamaConfig(
                vocab_size=24576, hidden_size=4096, intermediate_size=1280,
                num_layers=4, num_heads=8, num_kv_heads=1, head_dim=128,
                max_seq_len=8192, attention_impl="flash",
                layer_pattern=("gqa", "kda", "kda", "kda"), use_rope=False,
                attn_gate=True, kda_heads=8, kda_head_dim=128,
                num_experts=320, top_k=8, norm_topk_prob=True,
                router_scores="sigmoid", shared_experts=1, experts_held=10,
                load_balance_coef=0.001, router_z_coef=0.0)
            return LlamaForCausalLM(cfg), (1, 8192)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, one_period)
        text = compiled.as_text()
        assert "8192,8192]" not in text
        assert set(filter(None, paths)) == {"rows,rows,rows,slots"}
        _sums_by_token_as_the_path_says(
            text, ["rows", "rows", "rows", "slots"], 65536, 4096)
        calls = _kernel_names(text)
        # the one softmax layer: forward, dQ, dK/dV; its loops have one
        # turn each, so the compiler finds the rematerialised forward in
        # the forward (``families/solaropen2.py::fa2_shape`` counts so)
        assert sum("_attend" in name for name in calls) == 3
        found = trace.parse_device_scopes(text)
        subs = {sub for kind, sub, _ in found.scopes.values()
                if kind == "attn.core"}
        assert {"conv", "decay", "chunk", "state", "gate"} <= subs
        assert ("moe", "shared", "forward") in set(found.scopes.values())
        # the delta rule's kernels: the three layers are one loop, so one
        # call of each kind a pass, under the sub-scopes the readers of
        # ``kda_ms_per_step`` and ``kda_state_ms_per_step`` sum; and NO
        # forward kernel in the rematerialised pass: the layer keeps what
        # both wrote (``ops/pallas/kept.py``)
        kernels = sorted(
            found.scopes["%" + name] for name in calls
            if found.scopes["%" + name][:2] in (
                ("attn.core", "chunk"), ("attn.core", "state")))
        assert kernels == sorted(
            ("attn.core", sub, which) for sub in ("chunk", "state")
            for which in ("forward", "backward"))
        # nothing of the ``jax.numpy`` body: a sub-block's decayed keys,
        # the pair-by-pair products, the solve's expansion
        assert not re.search(r"f32\[[\d,]*4,64,128\]", text)
        assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
        assert "triangular" not in text
        # the state between chunks: float32, a head a [128, 128] matrix,
        # in the scratch of the kernels that walk the chunks
        from dlrover_tpu.ops.pallas.kda import kda_kernels
        from dlrover_tpu.ops.pallas.tuning import kda_tiling

        wide = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
        decay = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.float32)
        beta = jax.ShapeDtypeStruct((1, 8192, 8), jnp.float32)
        traced = str(jax.make_jaxpr(functools.partial(
            kda_kernels, tile=kda_tiling(64, 128)))(
                wide, wide, wide, decay, beta))
        assert re.search(r"Ref<vmem>\{f32\[\d+,128,128\]\}", traced)
        # accepted for a chip of 15.75 GiB (a refusal raises): 966.7 M
        # parameters at 8 bytes of state each are the arguments; the
        # temporaries were 9,214,244,864 bytes with the delta rule in
        # ``jax.numpy`` (the parent of PR 42, this test's configuration);
        # since PR 44 the three ``kda`` layers keep what their forward
        # kernels wrote, 176,685,056 bytes a layer (``remat.kept``):
        # 9,378,737,152; since PR 46 all four keep their expert share's
        # two products at the first extent, 13,107,200 bytes a layer, and
        # the backward of the worst-case rung asks for no down product:
        # 8,729,937,408
        mem = compiled.memory_analysis()
        assert 7.7e9 < mem.argument_size_in_bytes < 7.8e9
        assert mem.temp_size_in_bytes <= (
            9_214_244_864 + 3 * 176_685_056 + 4 * 13_107_200)

    def test_laguna_widths_the_dense_layer_and_one_period(
            self, topo, as_if_on_tpu, monkeypatch):
        """The Laguna cell's configuration file through its family at B1
        S4096 (a quarter of the cell's length, for the test's time; the
        cell's own 16,384 is ``benchmarks/tests/compile_described.py
        laguna_xs2_33b_1of8``: 5.15 GiB of arguments, 8.71 of
        temporaries): the three window layers' calls under ``attn.core`` /
        ``window`` (one forward, one rematerialised, dQ and dK/dV inside
        the run's loop of three), the two full layers' outside any
        sub-scope (loops of one turn: no second forward), nothing ``[S,
        S]``, the windowed call's record with the pairs its blocks
        multiply, and the step fits the chip."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = read_json(HERE, "configs", "laguna_xs2_33b_1of8.json")
        family = load_module("families", "laguna")
        S = 4096

        def cell():
            return family.build(config, False, S), (1, S)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, cell)
        text = compiled.as_text()
        assert f"{S},{S}]" not in text
        found = trace.parse_device_scopes(text)
        # (the grouped matmuls are custom calls too, under ``moe/gmm``)
        attend = [name for name in _kernel_names(text) if "_attend" in name]
        kernels = sorted(found.scopes["%" + name] for name in attend)
        assert len(attend) < len(_kernel_names(text))
        assert kernels == sorted(
            [("attn.core", "window", which) for which in (
                "forward", "remat", "backward", "backward")]
            + [("attn.core", "", "forward")] * 2
            + [("attn.core", "", "backward")] * 4)
        # what a kernel is FOR is read off its path; its name is the
        # innermost scope's (``_attend``), where the by-shape reader looks
        windowed = [attrs for name, attrs in notes
                    if name == "attention.path" and "window" in attrs]
        causal = [attrs for name, attrs in notes
                  if name == "attention.path" and "window" not in attrs]
        assert windowed and causal
        assert windowed[0]["heads"] == 64 and causal[0]["heads"] == 48
        assert windowed[0]["window"] == 512
        assert windowed[0]["gate"] == "sigmoid_a_head"
        blocks = windowed[0]["blocks"]
        assert windowed[0]["pairs_allowed"] == S * 512 - 512 * 511 // 2
        assert windowed[0]["band"] == "one_visit"
        assert windowed[0]["kv_blocks_visited"] == 1
        # a part of a block at a time: under the streamed kernels' 2.0
        assert windowed[0]["pairs_multiplied"] <= 1.7 * windowed[0][
            "pairs_allowed"], blocks
        mem = compiled.memory_analysis()
        assert 5.5e9 < mem.argument_size_in_bytes < 5.6e9

    def test_kanana2_widths_the_dense_layer_and_two_periods_of_one(
            self, topo, as_if_on_tpu, monkeypatch):
        """The Kanana-2 cell's configuration file through its family at B1
        S4096 and three of its six layers (a quarter of the cell's length
        and half its depth, for the test's time; the cell's own is
        ``benchmarks/tests/compile_described.py kanana2_30b_1of8``: 5.122
        GiB of arguments, 9.524 of temporaries at 16,384 x 6 layers,
        accepted): ONE kind all the way down, so the prefix's dense latent
        layer is a loop of one turn (forward, ONE backward call: no second
        forward) and the periods of one layer are one loop a pass whose
        rematerialised pass runs NO latent kernel (the layer keeps ``out``
        and the LSE, ``ops/pallas/kept.py``); every kernel under
        ``attn.core`` / ``latent`` at all 32 heads, nothing ``[S, S]``, the
        record says the rotary part turned pairs, the routed block names
        its two shared experts and ran no pass over groups."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = {**read_json(HERE, "configs", "kanana2_30b_1of8.json"),
                  "num_hidden_layers": 3}
        family = load_module("families", "kanana2")
        S = 4096

        def cell():
            return family.build(config, False, S), (1, S)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, cell)
        text = compiled.as_text()
        # no head's scores are an array ([1, S, 32 x 128] is the attention's
        # output, as wide as the sequence is long here)
        assert not re.search(rf"32,{S},{S}\]|{S},32,{S}\]|{S},{S},32\]", text)
        found = trace.parse_device_scopes(text)
        # (the grouped matmuls are custom calls too, under ``moe/gmm``)
        latent = sorted(
            found.scopes["%" + name] for name in _kernel_names(text)
            if found.scopes["%" + name][:2] == ("attn.core", "latent"))
        assert latent == sorted(
            [("attn.core", "latent", "forward")] * 2
            + [("attn.core", "latent", "backward")] * 2)
        (path,) = [attrs for name, attrs in notes
                   if name == "attention.path"][:1]
        assert path["impl"] == "latent" and path["exact"] == "pallas"
        assert path["heads"] == 32 and path["qk"] == "128+64"
        assert path["rope"] == "pairs" and path["backward"] == "one_call"
        (moe,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                  if name == "moe.path"}
        assert dict(moe)["shared_experts"] == 2
        assert dict(moe)["shared_width"] == 1536
        assert dict(moe)["held"] == 16 and dict(moe)["experts"] == 128
        assert ("moe", "shared", "forward") in set(found.scopes.values())
        # the dense layer, two routed layers, an eighth of the vocabulary:
        # 352.9 M parameters at 8 bytes of state
        mem = compiled.memory_analysis()
        assert 2.8e9 < mem.argument_size_in_bytes < 2.9e9

    def test_phi4flash_widths_every_kind_of_layer(
            self, topo, as_if_on_tpu, monkeypatch):
        """The Phi-4-mini-flash cell's configuration file through its family
        at B1 S2048 (an eighth of the cell's length, for the test's time;
        the cell's own is ``benchmarks/tests/compile_described.py
        phi4miniflash_l8``: 6.82 GiB of arguments, 9.67 of temporaries at
        16,384, accepted) and all eight layers, the least depth with every
        kind: the selective scan goes through its Pallas kernels
        (``ops/pallas/selective_scan.py``) under ``attn.core`` / ``scan``,
        a forward and a backward call a loop and NO forward call in the
        rematerialised pass (the layer keeps ``y`` and the chunks' starts);
        the differential cores through kernels of their own
        (``ops/pallas/differential_attention.py``) under ``attn.core`` /
        ``diff`` (``window`` / ``diff`` for the band), ONE forward and ONE
        backward call a layer over the model's own arrays and NO forward
        call in the rematerialised pass (the layer keeps ``O1``, ``O2`` and
        the LSEs a row a head); no array of the compiled step holds the
        state's history ``[S, 5120, 16]`` in any layout; the head reads the
        embedding table."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = read_json(HERE, "configs", "phi4miniflash_l8.json")
        family = load_module("families", "phi4flash")
        S = 2048

        def cell():
            return family.build(config, False, S), (1, S)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, cell)
        text = compiled.as_text()
        # the state's history, whole or by lane groups, and no head's scores
        assert not re.search(
            rf"{S},5120,16\]|5120,16,{S}\]|{S},40,16,128\]|{S},{S}\]", text)
        found = trace.parse_device_scopes(text)
        kernels = [found.scopes["%" + name] for name in _kernel_names(text)]
        # the periods' loop and the memory layer: a call each a pass
        assert sorted(k for k in kernels if k[1] == "scan") == sorted(
            [("attn.core", "scan", "forward")] * 2
            + [("attn.core", "scan", "backward")] * 2)
        # window (the periods' loop), whole (the memory layer), cross: a
        # forward and a backward call each, and none under ``remat``
        diff = [k for k in kernels if k[1] == "diff"]
        assert sorted(diff) == sorted(
            [("attn.core", "diff", "forward")] * 3
            + [("attn.core", "diff", "backward")] * 3)
        assert len(diff) == len(kernels) - 4
        # the band's stand under ``window`` too
        names = set(_kernel_names(text))
        paths_of = {name: line for line in text.splitlines()
                    for name in re.findall(
                        r"^\s*(?:ROOT )?%([\w.\-]+) = ", line)
                    if name in names}
        assert sum("/window/diff/" in line
                   for line in paths_of.values()) == 2
        # the model's own arrays, a pair a column block: nothing padded to
        # 128 a head, no second copy of V, no lane-broadcast LSE
        calls = [line for line in paths_of.values() if "/diff/" in line]
        assert len(calls) == 6
        for call in calls:
            assert f"bf16[1,{S},2560]" in call and f"bf16[1,{S},1280]" in call
            assert not re.search(rf"\[1,{S},(40|20),128\]|\[1,{S},5120\]",
                                 call.split("custom-call(")[1])
        assert f"f32[1,40,{S},128]" not in text
        paths = [attrs for name, attrs in notes if name == "attention.path"]
        mamba = next(a for a in paths if a["impl"] == "mamba")
        assert mamba["core"] == "pallas" and mamba["channels"] == 5120
        assert (mamba["state"], mamba["conv"], mamba["dt_rank"]) == (16, 4, 160)
        assert not [a for a in paths if a["impl"] == "flash"]
        cores = [a for a in paths if a["impl"] == "differential"]
        assert {a.get("window") for a in cores} == {512, None}
        assert all(a["heads"] == 40 and a["head_dim"] == 64
                   and a["core"] == "pallas" and a["maps"] == 2
                   and a["scores_over"] == 64 and a["value"] == 128
                   and a["backward_products"] == 8 for a in cores)
        whole = next(a for a in cores if "window" not in a)
        band = next(a for a in cores if "window" in a)
        # two tiles of 1,024: three live of four steps, the diagonal's cut;
        # the band's one tile of 2,048, cut by sub-tiles
        assert (whole["tiles_live"], whole["tiles_walked"]) == (3, 4)
        assert (band["tiles_live"], band["tiles_walked"]) == (1, 1)
        kept = {attrs["core"]: attrs for name, attrs in notes
                if name == "remat.kept"}
        assert set(kept) == {"ssm", "diff", "mlp"}
        # at an eighth of the cell's rows the eight SwiGLUs' gate and up
        # products, 640 MiB, fit 1/24 of the described chip's 15.75 GiB
        # (``kept.keeps_mlp_products``; the cell's own 5 GiB do not)
        assert kept["mlp"]["names"] == "mlp_products"
        assert kept["mlp"]["bytes_per_layer"] == 2 * S * 10240 * 2
        # y in bfloat16 and the starts of 32 chunks in float32
        assert kept["ssm"]["bytes_per_layer"] == (
            S * 5120 * 2 + (S // 64) * 5120 * 16 * 4)
        # O1 and O2 in bfloat16 and a row a head of LSE in float32
        assert kept["diff"]["names"] == "attn_out,attn_lse"
        assert kept["diff"]["bytes_per_layer"] == (
            2 * S * 2560 * 2 + 40 * S * 4)
        # what the forward loop leaves the backward one: the LSEs as rows
        assert re.search(rf"f32\[(\d,)?1,40,(1,)?{S}\]", text)
        assert "lm_head" not in "".join(
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(
                jax.eval_shape(family.build(config, False, S).init,
                               jax.random.PRNGKey(0),
                               jnp.zeros((1, 128), jnp.int32))["params"]))
        # 915.3 M parameters at 8 bytes of state
        mem = compiled.memory_analysis()
        assert 7.3e9 < mem.argument_size_in_bytes < 7.4e9

    def test_ouro_cell_whole_fits_the_chip(self, topo, as_if_on_tpu,
                                           monkeypatch):
        """The Ouro cell's own step, B1 S16384 and all 8 layers run 4 times
        (the compile takes a quarter of a minute): the described chip's
        compiler takes it (it refuses what does not fit: 12 layers, or the
        four heads' logits whole).  What the forward loop leaves the
        backward one is every layer application's input, 2 GiB, and, since
        the one-call rule also keeps the FA2 forward's results
        (``flash_attention.py::backward_path``), its ``out``, 2 GiB more,
        and its LSE as ``[B, H, S]`` float32, 32 MiB; no ``[16384,
        vocab]`` array stands in the step: the head is worked by blocks of
        4096 rows, each block ONCE (``weighted_token_losses`` takes the
        gradient while a block's logits stand: nothing ``[4096, vocab]``
        under ``rematted_computation``, and the ``head.path`` record says
        so).  The FA2 kernels by scope: a forward call in the forward
        loop and ONE backward call, none in the rematerialised pass.
        ``memory_analysis()`` reads 14.62 GiB of temporaries beside 4.56 of
        state (14.31 with the heads inside the loop, 9.57 before ``out``
        was kept: that sum is no guide to what fits, 19.2 of 15.75 GiB
        here; the compiler's own assignment holds state and temporaries in
        14.68 GiB, 14.62 and 11.76 before: PERF.md section 6, PR 62 and PR
        65)."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = read_json(HERE, "configs", "ouro2b6_l8.json")
        family = load_module("families", "ouro")
        S = config["run"]["seq"]
        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(
            mesh, lambda: (family.build(config, False, S), (1, S)))
        mem = compiled.memory_analysis()
        # 612.4 M parameters at 8 bytes of state
        assert 4.89e9 < mem.argument_size_in_bytes < 4.91e9
        assert mem.temp_size_in_bytes < 14.75 * 2 ** 30
        text = compiled.as_text()
        # what the loops hand on: the kept layer inputs and the kept
        # ``out`` (as the kernel wrote it, ``[B, S, H*D]``: no stack with
        # the heads apart), and the kept LSE, never lane-broadcast
        loops = [line.split(" while(")[0] for line in text.splitlines()
                 if " while(" in line]
        assert max(loop.count(f"bf16[4,8,1,{S},2048]") for loop in loops) == 2
        assert max(loop.count(f"f32[4,8,1,16,{S}]") for loop in loops) == 1
        assert f"bf16[4,8,1,{S},16,128]" not in text
        assert f"f32[4,8,1,16,{S},128]" not in text
        assert not re.search(rf"\[(\d,)*{S},49152\]", text)
        assert re.search(r"f32\[(\d,)*4096,49152\]", text)
        # the head's products are counted: no block's logits a second time
        assert not [line for line in text.splitlines()
                    if "rematted_computation" in line
                    and re.search(r" = f32\[(\d+,)*4096,49152\]", line)]
        (head,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                   if name == "head.path"}
        assert dict(head) == {"exits": 4, "rows": 4096, "blocks": 16,
                              "grad": "forward"}
        found = trace.parse_device_scopes(text)
        kernels = sorted(found.scopes["%" + name]
                         for name in _kernel_names(text))
        assert kernels == [("attn.core", "", "backward"),
                           ("attn.core", "", "forward")]
        # the new scopes stand in the table: nothing of theirs is ``other``
        kinds = {(kind, sub) for kind, sub, _ in found.scopes.values()}
        assert ("head_loss", "exit") in kinds and ("norm", "") in kinds
        (path,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                   if name == "attention.path"}
        assert dict(path)["backward"] == "one_call"
        assert (dict(path)["seq"], dict(path)["heads"]) == (S, 16)
        (held,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                   if name == "remat.kept"}
        assert dict(held)["core"] == "flash"
        assert dict(held)["names"] == "attn_out,attn_lse"
        # ``out`` 64 MiB and the LSE 1 MiB a layer application
        assert dict(held)["bytes_per_layer"] == 67108864 + 1048576

    def test_nemotron3super_widths_one_layer_of_each_kind(
            self, topo, as_if_on_tpu, monkeypatch):
        """The Nemotron-3-Super cell's configuration file through its family
        at B1 S2048 and three of its eleven layers, ``EM*`` (a quarter of the
        cell's length, one layer of each kind, for the test's time; the
        cell's own is ``benchmarks/tests/compile_described.py
        nemotron3super_120b_1of32``: 6.86 GiB of arguments, 9.27 of
        temporaries at 8192 x 11 layers; 16,384 is refused, 1.57 GiB over):
        the described chip's compiler takes the chunked scan as
        ``jax.numpy`` wrote it (no kernel asked for), every instruction of
        the mixer's core under ``conv``, ``decay``, ``ssd`` or ``gate``, the
        latent's two projections under ``moe`` / ``latent``; the attention
        layer alone runs Pallas kernels, the FA2 split triple at 4 query
        heads on 1; nothing has a chunk's mask at the sequence's extent
        squared; the ladder's worst case is 16 rows a token, not 22."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = {**read_json(HERE, "configs",
                              "nemotron3super_120b_1of32.json"),
                  "num_hidden_layers": 3, "hybrid_override_pattern": "EM*"}
        family = load_module("families", "nemotronh")
        S = 2048

        def cell():
            return family.build(config, False, S), (1, S)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, cell)
        text = compiled.as_text()
        assert not re.search(rf"\[(\d+,)*{S},{S}\]", text)
        found = trace.parse_device_scopes(text)
        kernels = sorted(found.scopes["%" + name]
                         for name in _kernel_names(text))
        # (the grouped matmuls are custom calls too, under ``moe/gmm``)
        assert {scope[:2] for scope in kernels} == {
            ("attn.core", ""), ("moe", "gmm")}
        assert {scope[2] for scope in kernels
                if scope[0] == "attn.core"} >= {"forward", "backward"}
        kinds = {(kind, sub) for kind, sub, _ in found.scopes.values()}
        assert {("attn.core", "conv"), ("attn.core", "decay"),
                ("attn.core", "ssd"), ("attn.core", "gate"),
                ("moe", "latent"), ("moe", "route"), ("moe", "gmm"),
                ("moe", "shared"), ("optimizer", "bias")} <= kinds
        # a chunk's mask a head: [chunks, heads, 128, 128], float32
        assert re.search(r"f32\[(\d+,)*16,16,128,128\]|"
                         r"f32\[(\d+,)*16,1,16,128,128\]", text)
        paths = {attrs["impl"]: attrs for name, attrs in notes
                 if name == "attention.path"}
        assert paths["mamba2"]["core"] == "jnp"
        assert (paths["mamba2"]["heads"], paths["mamba2"]["groups"],
                paths["mamba2"]["state"], paths["mamba2"]["chunk"],
                paths["mamba2"]["chunks"]) == (16, 1, 128, 128, 16)
        assert paths["mamba2"]["state_dtype"] == "float32"
        assert (paths["flash"]["heads"], paths["flash"]["rope"],
                paths["flash"]["backward"]) == (4, "none", "split")
        (moe,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                  if name == "moe.path"}
        moe = dict(moe)
        assert (moe["experts"], moe["top_k"], moe["held"]) == (512, 22, 16)
        assert (moe["matrices"], moe["activation"], moe["latent"]) == (
            2, "relu2", 1024)
        assert moe["backward"] == 4 and moe["shared_width"] == 5376
        # 22 x 16 / 512 of 2048 x 22 rows expected; the worst case 16 a token
        assert moe["extents"] == (1792, 2176, 2816, 16 * S)
        mem = compiled.memory_analysis()
        # (142.6 M + 13.7 M + 5.2 M + 134.2 M) x 8 bytes of state
        assert 2.36e9 < mem.argument_size_in_bytes < 2.37e9

    def test_lfm2_widths_one_layer_of_each_kind(
            self, topo, as_if_on_tpu, monkeypatch):
        """The LFM2 cell's configuration file through its family at B1 S2048
        and three of its nine layers: the dense ``conv`` layer, a routed
        softmax layer and a routed ``conv`` layer (an eighth of the cell's
        length, one layer of each kind, for the test's time; the cell's own
        is ``benchmarks/tests/compile_described.py lfm2_24b_1of8``: 6.20 GiB
        of arguments and 10.62 of temporaries, which alias the donated state,
        at 16,384 x 9 layers, accepted).  The described chip's compiler
        takes the gated convolution as ``jax.numpy`` wrote it (no kernel
        asked for): every instruction of the core under ``attn.core`` /
        ``gconv``, forward and in the backward rule's own pass; the softmax layer alone runs Pallas kernels, the FA2 split
        triple at two heads of 64 a block; a ``conv`` layer keeps nothing
        by name."""
        from benchmarks.common import HERE, load_module, read_json
        from dlrover_tpu.observability import trace

        notes = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: notes.append((name, attrs)))
        config = {**read_json(HERE, "configs", "lfm2_24b_1of8.json"),
                  "num_hidden_layers": 3,
                  "layer_types": ["conv", "conv", "full_attention", "conv"]}
        family = load_module("families", "lfm2")
        S = 2048

        def cell():
            return family.build(config, False, S), (1, S)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, cell)
        text = compiled.as_text()
        found = trace.parse_device_scopes(text)
        kernels = sorted(found.scopes["%" + name]
                         for name in _kernel_names(text))
        # (the grouped matmuls are custom calls too, under ``moe/gmm``)
        assert {scope[:2] for scope in kernels} == {
            ("attn.core", ""), ("moe", "gmm")}
        core = [which for kind, sub, which in found.scopes.values()
                if (kind, sub) == ("attn.core", "gconv")]
        # (loops of one turn here: the compiler unrolls them and finds the
        # rematerialised forward in the forward, as the softmax layer's;
        # ``tests/test_device_scopes.py`` holds the pass ``remat`` where a
        # run of three is a loop)
        assert set(core) >= {"forward", "backward"}
        kinds = {(kind, sub) for kind, sub, _ in found.scopes.values()}
        assert {("moe", "route"), ("moe", "gmm"), ("optimizer", "bias"),
                ("mlp", "")} <= kinds
        assert not {("attn.core", "conv"), ("moe", "shared")} & kinds
        paths = {attrs["impl"]: attrs for name, attrs in notes
                 if name == "attention.path"}
        assert paths["short_conv"] == {
            "impl": "short_conv", "seq": S, "channels": 2048, "conv": 3,
            "core": "jnp"}
        assert (paths["flash"]["heads"], paths["flash"]["head_dim"],
                paths["flash"]["heads_per_block"],
                paths["flash"]["backward"]) == (32, 64, 2, "split")
        (moe,) = {tuple(sorted(attrs.items())) for name, attrs in notes
                  if name == "moe.path"}
        moe = dict(moe)
        assert (moe["experts"], moe["top_k"], moe["held"]) == (64, 4, 8)
        assert moe["backward"] == 6 and moe["shared_width"] == 0
        # what a rematerialised layer keeps by name: the routed block's
        # products and route, the dense layer's gate and up products (PR
        # 63's rule: one dense layer's fit); the mixers nothing (a split
        # FA2 backward keeps no ``out``, a ``conv`` layer has nothing named)
        assert {attrs["core"] for name, attrs in notes
                if name == "remat.kept"} == {"moe", "mlp"}
        mem = compiled.memory_analysis()
        # (89.1 M + 86.1 M + 92.4 M + 16.8 M) x 8 bytes of state
        assert 2.27e9 < mem.argument_size_in_bytes < 2.29e9

    def test_sdar_widths_one_layer(self, topo, as_if_on_tpu):
        """One layer of SDAR-30B-A3B's block-diffusion step at the cell's
        widths and share (32 query heads on 4 key heads of 128, 16 of 128
        experts, an eighth of the vocabulary), B1 S2048 = 4096 rows: the
        attention under the block-diffusion mask is ONE kernel call a pass
        over the whole ``[1, 4096, heads * 128]`` arrays (``ops/pallas/
        block_diffusion_attention.py``), its mask made inside: no mask
        operand, no joined keys, no block of rows sliced out or
        concatenated; no array has two dimensions of the rows; both calls
        stand under a sub-scope the benchmark's readers sum, and the noise
        under its own."""
        from dlrover_tpu.models.llama import LlamaForCausalLM
        from dlrover_tpu.models.moe import MoELlamaConfig
        from dlrover_tpu.observability import trace

        def one_layer():
            cfg = MoELlamaConfig(
                vocab_size=18992, hidden_size=2048, intermediate_size=768,
                num_layers=1, num_heads=32, num_kv_heads=4, head_dim=128,
                max_seq_len=2048, rope_theta=1e6, rms_norm_eps=1e-6,
                qk_norm="head", num_experts=128, top_k=8,
                norm_topk_prob=True, experts_held=16,
                load_balance_coef=0.001, router_z_coef=0.0,
                block_diffusion=4, mask_token_id=18991)
            return LlamaForCausalLM(cfg), (1, 2048)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        compiled = _trainer_step_compiled(mesh, one_layer)
        text = compiled.as_text()
        # (``[1, 4096, 4096]`` is q itself here: 32 heads of 128)
        assert "32,4096,4096]" not in text
        # no tile's scores are an array (the LSE is ``f32[1,32,4096,128]``)
        assert not re.search(r"f32\[(1,)?32,512,(256|512)\]", text)
        # the mask-operand kernels are gone, and their masks with them
        assert "s8[1,512," not in text
        found = trace.parse_device_scopes(text)
        kernels = {name: found.scopes["%" + name]
                   for name in _kernel_names(text)
                   if found.scopes["%" + name][0] == "attn.core"}
        # one call forward and one backward (with one layer the compiler
        # finds the rematerialised forward in the forward)
        assert sorted(kernels.values()) == [
            ("attn.core", "bd_noisy", "backward"),
            ("attn.core", "bd_noisy", "forward")]
        calls = {kernels[name][2]: line
                 for line in text.splitlines()
                 for name in re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = ", line)
                 if name in kernels}
        # the whole arrays as the model has them, heads as column blocks;
        # the backward's dK and dV leave it as float32 accumulators
        for call in calls.values():
            assert "bf16[1,4096,4096]" in call and "bf16[1,4096,512]" in call
        assert calls["backward"].count("f32[1,4096,512]") == 2
        # nothing of the attention stands outside the names the readers sum
        scopes = set(found.scopes.values())
        assert {sub for kind, sub, _ in scopes if kind == "attn.core"} <= {
            "", "bd_noisy"}
        assert ("embed", "noise", "forward") in scopes
        reader = _load_layer_metric("bd_attn_ms_per_step")
        assert "bd_noisy" in reader.SUB_SCOPES
        # 172.5 M parameters at 8 bytes of state each are the arguments
        mem = compiled.memory_analysis()
        assert 1.37e9 < mem.argument_size_in_bytes < 1.40e9

    def test_block_diffusion_forward_kernels_run_once_a_layer(
            self, topo, as_if_on_tpu):
        """Two scanned block-diffusion layers at small widths (8 query
        heads on 2 key heads of 128, a dense block, B1 S1024 = 2048 rows:
        two tiles of 512 queries a half): the layers are one loop a pass,
        and the backward pass's loop holds the ONE backward kernel and NO
        forward kernel: the layer's rematerialisation keeps ``out`` and
        the LSE (``ops/pallas/kept.py``), one pair of whole arrays a layer,
        the LSE as ``[1, 8, 2048]`` and never lane-broadcast in the saved
        stack."""
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from dlrover_tpu.observability import trace

        def two_layers():
            cfg = LlamaConfig(
                vocab_size=4096, hidden_size=512, intermediate_size=1024,
                num_layers=2, num_heads=8, num_kv_heads=2, head_dim=128,
                max_seq_len=1024, qk_norm="head", block_diffusion=4,
                mask_token_id=4095)
            return LlamaForCausalLM(cfg), (1, 1024)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        text = _trainer_step_compiled(mesh, two_layers).as_text()
        found = trace.parse_device_scopes(text)
        names = _kernel_names(text)
        kernels = sorted(found.scopes["%" + name] for name in names)
        assert kernels == [("attn.core", "bd_noisy", "backward"),
                           ("attn.core", "bd_noisy", "forward")]
        # the stacks the forward loop leaves for the backward one
        assert "bf16[2,1,2048,8,128]" in text and "f32[2,1,8,2048]" in text
        assert "f32[2,1,8,2048,128]" not in text

    def test_a_rematerialised_router_runs_no_matmul_and_no_sort(
            self, topo, as_if_on_tpu, monkeypatch):
        """Two scanned layers with Ling-3.0-flash-VL's routed block
        (16,384 tokens x 2560, 512 router columns in 8 groups of which 4
        are kept, 8 experts a token under a selection bias, 16 experts
        held): the layers are one loop a pass, and what the backward
        pass's loop computes again of ``moe/route`` holds no sort (what
        ``top_k`` is on the chip), no float32 ``[16384, 2560] x [2560,
        512]`` product and no gather: the layer keeps the router's logits
        and its choice (``kept.MOE_ROUTE``) and reads the weights at the
        choice by compare and sum.  The forward pass of the same text is
        the control: there ``moe/route`` holds the one product, at
        ``highest``, and the one sort (that the kept name is what takes
        them out of the other pass is held on the CPU:
        ``test_remat_kept.py::test_a_router_keeps_its_logits_its_choice_and_its_count``,
        three routers).  At the ladder's three small extents the sums by
        token run over the rows held: nothing there has the extent of the
        131,072 slots times the width."""
        from dlrover_tpu.models import llama
        from dlrover_tpu.models.moe import MoELlamaConfig
        from dlrover_tpu.observability import trace

        paths = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: paths.append(attrs.get("combine")))

        def two_layers():
            cfg = MoELlamaConfig(
                vocab_size=4096, hidden_size=2560, intermediate_size=768,
                num_layers=2, num_heads=8, num_kv_heads=8, head_dim=128,
                max_seq_len=16384, attention_impl="flash", num_experts=512,
                top_k=8, norm_topk_prob=True, router_scores="sigmoid",
                routed_scaling_factor=2.5, n_group=8, topk_group=4,
                selection_bias=True, shared_experts=1, experts_held=16)
            return llama.LlamaForCausalLM(cfg), (1, 16384)

        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
        text = _trainer_step_compiled(mesh, two_layers).as_text()
        (combine,) = set(filter(None, paths))
        assert combine == "rows,rows,rows,slots"
        _sums_by_token_as_the_path_says(
            text, combine.split(","), 131072, 2560)
        found = trace.parse_device_scopes(text)

        def route(in_pass):
            """The instructions of ``moe/route`` in one pass."""
            return [
                line for line in text.split("\n")
                for name in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", line)
                if found.scopes["%" + name] == ("moe", "route", in_pass)]

        def products(lines):
            return [line for line in lines if re.search(
                r"= f32\[16384,512\]\S* (convolution|dot)\(", line)]

        def sorts(lines):
            return [line for line in lines if re.search(
                r" sort\(|TopK|top_k", line.split("metadata=")[0])]

        forward, again = route(trace.FORWARD), route(trace.REMAT)
        (product,) = products(forward)
        assert "highest" in product
        assert len(sorts(forward)) == 1
        assert again                # the sigmoid, the losses' terms
        assert not products(again) and not sorts(again)
        assert not [line for line in again if " gather(" in line]

    def test_olmoe_widths_ep4(self, topo, as_if_on_tpu):
        """One layer of OLMoE-1B-7B at B8 S4096 over ``ep=4``: the grouped
        matmuls are the compiler's own kernel, forward and both gradients;
        the passes over the sorted rows at four extents, the small ones
        free of worst-case buffers;
        tokens cross chips by all-gather and reduce-scatter; the FA2 calls
        keep ``_attend`` in their names under the shard_map (the
        benchmark's readers find them by it); 16 of 64 experts a chip."""
        import re

        from dlrover_tpu.models.llama import LlamaForCausalLM
        from dlrover_tpu.models.moe import MoELlamaConfig

        def olmoe_one_layer():
            cfg = MoELlamaConfig.olmoe_1b_7b(
                num_layers=1, attention_impl="flash")
            return LlamaForCausalLM(cfg), (8, 4096)

        mesh = build_mesh(MeshConfig(ep=4), devices=list(topo.devices))
        compiled = _trainer_step_compiled(mesh, olmoe_one_layer)
        text = compiled.as_text()
        calls = _kernel_names(text)
        assert sum("_attend" in name for name in calls) == 4
        # gate, up, down forward in each of the four rungs (12); backward
        # the first rung holds the six gradients alone, from the products
        # the forward pass kept, and each higher rung eight: gate and up
        # again inside ``jax.vjp`` of the rung, never down (24 + 6)
        assert sum(name.startswith("ragged-dot-none") for name in calls) == 42
        # what is kept: the two products of every source rank's pass at
        # the first extent, the loop's stacked result, and of no other
        assert "bf16[4,20480,1024]" in text
        assert not re.search(r"bf16\[4,(24576|32768|65536),(1024|2048)\]", text)
        # one switch in the forward pass and one in the backward pass
        switches = [[name.strip() for name in names.split(",")]
                    for names in re.findall(
                        r"branch_computations=\{([^}]*)\}", text)]
        assert [len(names) for names in switches] == [4, 4]
        for names in switches:
            *small, top = [
                text[text.index(f"\n{name} ("):].split("\n}\n")[0]
                for name in names]
            for extent, body in zip((20480, 24576, 32768), small):
                assert f"bf16[{extent},2048]" in body
                assert f"bf16[{extent},1024]" in body
                # of the worst case a small rung holds the index vectors
                # and the gathers of the two sums by token, nothing else
                assert "[65536,1024]" not in body
                whole = [line for line in body.split("\n")
                         if re.search(r" = \(?\w+\[65536,2048\]", line)]
                assert 1 <= len(whole) <= 2, whole
                assert all("kind=kCustom" in line for line in whole), whole
            assert "bf16[65536,1024]" in top
        assert "bf16[65536,2048]" in text       # 8192 tokens x 8, one rank's
        assert "[262144," not in text           # never all four at once
        assert re.search(r"%all-gather[\w.\-]* = bf16\[4,8192,2048\]", text)
        assert re.search(r"%reduce[_-]scatter[\w.\-]* = bf16\[1,8192,2048\]",
                         text)
        params = 16 * 3 * 2048 * 1024 + 625_000_000 - 64 * 3 * 2048 * 1024
        assert compiled.memory_analysis().argument_size_in_bytes < (
            params * 8 * 1.02)
