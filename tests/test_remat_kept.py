"""What a rematerialised decoder layer keeps (``ops/pallas/kept.py``): a
stack of two scanned layers under ``models/llama.py::_layer_class``'s
policy, once for each attention core, the Pallas kernels in the interpreter
on the CPU.

For a core whose kernels name their results (``selected_attention``,
``block_diffusion_kernels``, ``masked_attention`` with ``shared`` keys,
``_kda``, and the FA2 kernel over a stream of keys long enough for
``flash_attention.py::backward_path``'s one-call rule): the
forward pass keeps exactly the layer's input and the named values, the LSE
as ``[B, H, Q]``, at the bytes a layer that ``remat.kept`` notes; the
gradient's jaxpr holds each forward kernel once a layer, where it holds it
twice under ``nothing_saveable``; and the loss and every gradient are those
under ``nothing_saveable`` bit for bit.  For a core that names nothing (the
FA2 kernel over a shorter stream, the reference, a ``jax.numpy`` body) the
two policies lower to the same program.

The expert layer (``models/moe.py``) names the two products of a pass's
first grouped matmuls the same way, ``jax.numpy`` and all, and its router's
logits, choice and count of rows: the same stack with one chip's share of
an expert layer in each, once for each way the router chooses.

The dense SwiGLU (``models/llama.py::MLP``) names its gate and up products
where ``kept.keeps_mlp_products`` says that all layer applications' fit a
share of the device's memory: the same three readings of the stack on a
device of a v5e's memory, no matmul of the pair in the second pass at the
Mistral cell's own shapes, and the rule alone over the shapes of the
benchmark's other Llama-path cells, each of which it refuses.
"""

import collections
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.models.moe import MoELlamaConfig, ladder
from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops import linear_attention
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.ops.pallas import kept
from shared_memo import shared_memo

LAYERS, HEADS, DIM, HIDDEN = 2, 2, 128, 64
F32 = jnp.dtype("float32")
#: what a v5e's runtime gives its programs, for ``kept.device_bytes`` where
#: a test stands for the chip (the CPU's says nothing: 0, and every dense
#: feed-forward of this file's other stacks names nothing)
V5E_BYTES = kept.DESCRIBED_DEVICE_BYTES["TPU v5 lite"]


def _interpreted(module, name):
    return module, name, functools.partial(
        getattr(module, name), interpret=True)


def _config(**fields):
    return LlamaConfig.tiny(**{**dict(
        num_layers=LAYERS, num_heads=HEADS, num_kv_heads=1, head_dim=DIM,
        hidden_size=HIDDEN, dtype=jnp.float32), **fields})


#: core -> (configuration, the layers' kind, rows of the residual stream,
#: the entry to run in the interpreter, the forward kernels by name with
#: their calls a layer, the named values a layer keeps as (shape, number),
#: constants of the program set otherwise as (module, name, value))
Core = collections.namedtuple(
    "Core", "config kind rows entry forward_kernels kept constants",
    defaults=((),))
KERNEL_CORES = {
    # two blocks of 128 queries, the second selecting 96 of its 256 keys
    "selected_attention": Core(
        _config(index_topk=96, index_heads=2, index_head_dim=16,
                index_block=128, max_seq_len=256), "gqa", 256,
        (ops, "_attend_selected_kernels"), {"_fwd_kernel": 2},
        {(1, 128, HEADS, DIM): 2, (1, HEADS, 128): 2}),
    # 128 data tokens: a tile of noisy queries and one of clean, ONE call
    # over both, one pair of whole arrays kept
    "block_diffusion_kernels": Core(
        _config(block_diffusion=4, max_seq_len=128), "gqa", 256,
        (ops, "block_diffusion_attention"), {"_fwd_kernel": 1},
        {(1, 256, HEADS, DIM): 1, (1, HEADS, 256): 1}),
    # two windows of 256: the second over the first's 128 summaries
    "masked_attention_shared": Core(
        _config(num_kv_heads=HEADS, eva_window=256, eva_chunk=2,
                max_seq_len=512), "gqa", 512,
        (ops, "_eva_window_kernels"), {"_fwd_kernel": 2},
        {(1, 256, HEADS, DIM): 2, (1, HEADS, 256): 2}),
    # two chunks of 64: w, u0, qg, ke, out, u; p, t; th; starts
    "_kda": Core(
        _config(kda_heads=HEADS, kda_head_dim=DIM, max_seq_len=128), "kda",
        128, (linear_attention, "_kda_kernels"),
        {"_chunk_fwd_kernel": 1, "_state_fwd_kernel": 1},
        {(1, 128, HEADS * DIM): 6, (1, HEADS, 128, 64): 2,
         (1, 2, 1, HEADS * DIM): 1, (1, 2, HEADS, DIM, DIM): 1}),
    # FA2 over a stream the one-call rule takes (16,384 keys as shipped;
    # 256 here, the constant brought down to them): ``out`` as the kernel
    # wrote it, ``[B, S, H*D]``, and the LSE
    "fa2_long_stream": Core(
        _config(attention_impl="flash", max_seq_len=256), "gqa", 256,
        (ops, "flash_attention"), {"_flash_fwd_kernel": 1},
        {(1, 256, HEADS * DIM): 1, (1, HEADS, 256): 1},
        ((fa, "ONE_CALL_MIN_KEYS", 256),)),
    # no kernel: the reference core and the dense SwiGLU of 128 over 64
    # rows, on a device that says what a v5e does: gate and up
    "mlp_products_taken": Core(
        _config(max_seq_len=64), "gqa", 64, None, {}, {(1, 64, 128): 2},
        ((kept, "device_bytes", lambda device: V5E_BYTES),)),
}
#: cores that name nothing: the FA2 kernel where the rule leaves it (128
#: keys are under ``ONE_CALL_MIN_KEYS``, as shipped and as brought down
#: above: a split call, the program it was), the reference, and the
#: ``jax.numpy`` bodies the CPU gives the cores above
UNNAMED_CORES = {
    "fa2": Core(_config(attention_impl="flash", max_seq_len=128), "gqa", 128,
                (ops, "flash_attention"), {}, {}),
    "reference": Core(_config(max_seq_len=64), "gqa", 64, None, {}, {}),
    "block_diffusion_jnp": Core(
        _config(block_diffusion=4, max_seq_len=64), "gqa", 128, None, {}, {}),
    "kda_jnp": Core(
        _config(kda_heads=HEADS, kda_head_dim=16, max_seq_len=64), "kda", 64,
        None, {}, {}),
}


class _Stack:
    """Two scanned layers of ``core`` under ``policy`` and a loss over
    them; with an ``entry``, as a TPU backend would run it."""

    def __init__(self, monkeypatch, core, policy):
        self.records = []
        monkeypatch.setattr(llama, "LAYER_POLICY", policy)
        monkeypatch.setattr(
            kept.trace, "note_trace_time",
            lambda name, **attrs: self.records.append((name, attrs)))
        if core.entry:
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            monkeypatch.setattr(*_interpreted(*core.entry))
        for constant in core.constants:
            monkeypatch.setattr(*constant)
        cfg = core.config
        self.stack = llama._stacked(llama._layer_class(cfg, True), LAYERS)(
            cfg, core.kind)
        seq = core.rows // 2 if cfg.block_diffusion else core.rows
        at = jnp.arange(seq)
        if cfg.block_diffusion:
            at = jnp.concatenate([at, at])
        self.positions = at[None]
        # only the reference core reads it
        self.mask = jnp.tril(
            jnp.ones((core.rows, core.rows), bool))[None, None]
        self.x = jax.random.normal(
            jax.random.PRNGKey(0), (1, core.rows, HIDDEN))
        # one program, of which the compiler keeps the parameters alone
        # (and a biased router's buffers: never differentiated)
        self.buffers = dict(jax.jit(self.stack.init)(
            jax.random.PRNGKey(1), self.x, self.positions, self.mask))
        self.params = self.buffers.pop("params")

    def loss(self, params, x):
        (y, _), sown = self.stack.apply(
            {"params": params, **self.buffers}, x, self.positions, self.mask,
            mutable=["losses", "stats"])
        taught = sum(jnp.sum(t) for t in jax.tree.leaves(
            sown.get("losses", {})))
        return jnp.sin(y).sum() + 5.0 * taught

    def value_and_grad(self):
        """Compiled with the compiler's fusions off: the interpreter hands
        XLA a kernel as plain instructions, and fused with its neighbours
        differently in a program that runs it once and in one that runs it
        twice, its results differ in their last bits.  (On the chip a
        kernel is opaque, and the same holds of the fusions around a call
        that is gone.)"""
        step = jax.jit(jax.value_and_grad(self.loss, argnums=(0, 1)))
        return step.lower(self.params, self.x).compile(compiler_options={
            "xla_disable_hlo_passes": "fusion,cpu-instruction-fusion",
        })(self.params, self.x)

    def kept_note(self):
        """The one ``remat.kept`` record of the traces so far."""
        notes = [attrs for name, attrs in self.records if name == "remat.kept"]
        assert notes and all(note == notes[0] for note in notes)
        return notes[0]


def _kernel_calls(jaxpr, counts=None):
    """kernel's name -> ``pallas_call`` equations in ``jaxpr`` and every
    jaxpr inside it (a scan's body counts once: calls a layer)."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["jaxpr"].debug_info.func_name] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(inner, counts)
    return counts


POLICIES = {"kept": kept.LAYER_POLICY,
            "again": jax.checkpoint_policies.nothing_saveable}


@shared_memo
def _measured(name):
    """What the tests of one kernel core read, from one stack a policy:
    policy -> the forward kernels' calls in the gradient's jaxpr and the
    loss with every gradient; under the layer's policy also the stacked
    residuals of the forward pass and the ``remat.kept`` note of every
    trace made."""
    core = KERNEL_CORES[name]
    measured = {}
    for policy_name, policy in POLICIES.items():
        with pytest.MonkeyPatch.context() as patch:
            stack = _Stack(patch, core, policy)
            facts = measured[policy_name] = {}
            if policy_name == "kept":
                facts["residuals"] = collections.Counter(
                    aval.shape[1:] for aval, why in saved_residuals(
                        stack.loss, stack.params, stack.x)
                    if "output of scan" in why and aval.shape[0] == LAYERS)
            counts = _kernel_calls(jax.make_jaxpr(jax.grad(
                stack.loss, argnums=(0, 1)))(stack.params, stack.x).jaxpr)
            facts["calls"] = {
                kernel: counts[kernel] for kernel in core.forward_kernels}
            facts["loss_and_gradients"] = jax.tree.leaves(
                stack.value_and_grad())
            if policy_name == "kept":
                facts["note"] = stack.kept_note()
    return measured


@pytest.fixture(scope="module", params=list(KERNEL_CORES))
def name(request):
    """A module's fixture and not a ``parametrize``: the three tests of
    one core stand next to each other in the collection."""
    return request.param


def test_the_forward_pass_keeps_the_input_and_the_named_values(name):
    core, facts = KERNEL_CORES[name], _measured(name)["kept"]
    layer_input = {(1, core.rows, HIDDEN): 1}
    assert facts["residuals"] == collections.Counter(
        {**core.kept, **layer_input})
    held = sum(math.prod(shape) * count * F32.itemsize
               for shape, count in core.kept.items())
    note = facts["note"]
    assert note["bytes_per_layer"] == held
    assert note["names"] == ",".join(
        name for name in kept.NAMES if f"{name}_bytes" in note)
    assert note["bytes_per_layer"] == sum(
        note[f"{name}_bytes"] for name in note["names"].split(","))


def test_the_gradient_runs_each_forward_kernel_once_a_layer(name):
    core, measured = KERNEL_CORES[name], _measured(name)
    assert measured["kept"]["calls"] == core.forward_kernels
    # what the policy is for: without a kept name every one runs twice
    assert measured["again"]["calls"] == {
        kernel: 2 * n for kernel, n in core.forward_kernels.items()}


def test_loss_and_gradients_are_those_of_a_layer_computed_again_whole(name):
    measured = _measured(name)
    got = measured["kept"]["loss_and_gradients"]
    want = measured["again"]["loss_and_gradients"]
    assert len(got) == len(want) > 2
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("unnamed", list(UNNAMED_CORES))
def test_a_core_that_names_nothing_is_computed_again_whole(monkeypatch,
                                                           unnamed):
    core = UNNAMED_CORES[unnamed]

    def lowered(policy):
        stack = _Stack(monkeypatch, core, policy)
        text = jax.jit(jax.grad(stack.loss, argnums=(0, 1))).lower(
            stack.params, stack.x).as_text()
        assert not [r for r in stack.records if r[0] == "remat.kept"]
        return text

    assert lowered(kept.LAYER_POLICY) == lowered(
        jax.checkpoint_policies.nothing_saveable)


#: what a router keeps of 256 tokens with 2 of 8 experts each: the logits,
#: the choice and a share's count of rows
ROUTE = {(1, 256, 8): 1, (1, 256, 2): 1, (8,): 1}
#: 256 tokens with 2 experts each, 2 of 8 experts here: the first extent
#: holds 256 of the 512 assignments; the two products, and the sort they
#: are in (two index vectors of all assignments, the two groups' sizes)
PRODUCTS = {(256, 128): 2, (512,): 2, (2,): 1}


def _experts(**router):
    return Core(
        MoELlamaConfig.tiny_moe(
            num_layers=LAYERS, num_heads=HEADS, num_kv_heads=1, head_dim=DIM,
            hidden_size=HIDDEN, intermediate_size=128, dtype=jnp.float32,
            num_experts=8, top_k=2, experts_held=2, max_seq_len=256,
            **router),
        "gqa", 256, None, {}, {**PRODUCTS, **ROUTE})


EXPERTS = _experts()
#: the ways ``models/moe.py::_choose`` chooses: the largest softmax scores;
#: the largest sigmoid scores under a selection bias; those inside the 2 of
#: 4 groups whose two best add up highest
ROUTERS = {
    "plain": EXPERTS,
    "biased": _experts(router_scores="sigmoid", selection_bias=True,
                       norm_topk_prob=True),
    "grouped": _experts(router_scores="sigmoid", selection_bias=True,
                        norm_topk_prob=True, routed_scaling_factor=2.5,
                        n_group=4, topk_group=2),
}


def _stacked_residuals(stack):
    return collections.Counter(
        aval.shape[1:] for aval, why in saved_residuals(
            stack.loss, stack.params, stack.x)
        if "output of scan" in why and aval.shape[0] == LAYERS)


def _bytes(shapes):
    return sum(math.prod(shape) * count * F32.itemsize
               for shape, count in shapes.items())


def test_an_expert_layer_keeps_its_input_and_the_two_products(monkeypatch):
    stack = _Stack(monkeypatch, EXPERTS, kept.LAYER_POLICY)
    assert ladder(256 * 2, 2, 8) == (256, 512)
    del stack.records[:]
    assert _stacked_residuals(stack) == collections.Counter(
        {**EXPERTS.kept, (1, EXPERTS.rows, HIDDEN): 1})
    # every trace of the layer makes one reading, so ``note_trace_time``
    # keeps one record of each a program
    paths = [attrs for name, attrs in stack.records if name == "moe.path"]
    path = paths[0]
    assert all(other == path for other in paths)
    both = f"{kept.MOE_PRODUCTS},{kept.MOE_ROUTE}"
    assert path["kept"] == both and path["backward"] == 6
    assert path["extents"] == (256, 512)
    note = stack.kept_note()
    assert note["core"] == "moe" and note["names"] == both
    assert note["moe_products_bytes"] == _bytes(PRODUCTS)
    assert note["moe_route_bytes"] == _bytes(ROUTE)
    assert note["bytes_per_layer"] == _bytes(EXPERTS.kept)


def test_an_expert_layers_gradients_are_those_computed_again_whole(
        monkeypatch):
    """And under ``nothing_saveable`` the forward switch runs a second
    time, for the same products."""
    runs = {}
    for policy in (kept.LAYER_POLICY,
                   jax.checkpoint_policies.nothing_saveable):
        stack = _Stack(monkeypatch, EXPERTS, policy)
        conds = str(jax.make_jaxpr(jax.grad(stack.loss, argnums=(0, 1)))(
            stack.params, stack.x)).count(" cond[")
        runs[policy] = conds, jax.tree.leaves(stack.value_and_grad())
    (conds, got), (conds_again, want) = runs.values()
    assert (conds, conds_again) == (2, 3)
    assert len(got) == len(want) > 2
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(a, b)


def _top_k_eqns(stack):
    """``top_k`` equations in the gradient's jaxpr (a scan's body counts
    once: one a layer and pass that runs it)."""
    return str(jax.make_jaxpr(jax.grad(stack.loss, argnums=(0, 1)))(
        stack.params, stack.x)).count(" top_k[")


@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_router_keeps_its_logits_its_choice_and_its_count(monkeypatch,
                                                           router):
    """Whichever way it chooses: the forward pass keeps the router's three
    arrays beside the products and nothing else, the second pass runs no
    ``top_k``, and loss and gradients are those computed again whole."""
    runs = {}
    for policy in (kept.LAYER_POLICY,
                   jax.checkpoint_policies.nothing_saveable):
        stack = _Stack(monkeypatch, ROUTERS[router], policy)
        if policy is kept.LAYER_POLICY:
            assert _stacked_residuals(stack) == collections.Counter(
                {**PRODUCTS, **ROUTE, (1, EXPERTS.rows, HIDDEN): 1})
            assert stack.kept_note()["moe_route_bytes"] == _bytes(ROUTE)
        runs[policy] = _top_k_eqns(stack), jax.tree.leaves(
            stack.value_and_grad())
    (sorts, got), (sorts_again, want) = runs.values()
    assert (sorts, sorts_again) == (1, 2)
    assert len(got) == len(want) > 2
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(a, b)


def _top_k_on_the_host(flips_after):
    """A ``jax.lax.top_k`` that runs on the host and counts its runs: the
    first ``flips_after`` break a tie for the lower column, as ``top_k``
    does, every later one for the higher, as a second pass would whose
    scores the compiler had rounded the other way."""
    runs = []

    def on_host(k, x):
        later = len(runs) >= flips_after
        runs.append(later)
        x = np.asarray(x)
        columns = np.arange(x.shape[-1])[::-1] if later else np.arange(
            x.shape[-1])
        first = columns[np.argsort(-x[..., columns], axis=-1, kind="stable")]
        chosen = first[..., :k].astype(np.int32)
        return np.take_along_axis(x, chosen, axis=-1), chosen

    def top_k(x, k):
        return jax.pure_callback(
            functools.partial(on_host, k),
            (jax.ShapeDtypeStruct((*x.shape[:-1], k), x.dtype),
             jax.ShapeDtypeStruct((*x.shape[:-1], k), jnp.int32)), x)

    return top_k, runs


def test_on_a_tie_the_second_pass_weights_the_experts_the_first_chose(
        monkeypatch):
    """Two experts with one router column: wherever they tie at the edge of
    a token's two best, a ``top_k`` run again could choose the other one.
    The kept layer never runs it again, so its gradients are those of a
    ``top_k`` that always breaks ties one way; a layer computed again whole
    pairs the second choice's weights with nothing the first pass did."""
    def gradients(policy, flips_after):
        stack = _Stack(monkeypatch, EXPERTS, policy)
        router = stack.params["layer"]["mlp"]["router"]
        columns = router["kernel"].value
        router["kernel"] = router["kernel"].replace_boxed(
            columns.at[..., 4].set(columns[..., 3]))
        top_k, runs = _top_k_on_the_host(flips_after)
        monkeypatch.setattr(jax.lax, "top_k", top_k)
        return jax.tree.leaves(stack.value_and_grad()), runs

    again = jax.checkpoint_policies.nothing_saveable
    want, _ = gradients(again, flips_after=math.inf)
    got, runs = gradients(kept.LAYER_POLICY, flips_after=LAYERS)
    assert runs == LAYERS * [False]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # and the ties are there: a second ``top_k`` that breaks them the other
    # way moves the gradients of a layer that runs one
    other, runs = gradients(again, flips_after=LAYERS)
    assert runs == LAYERS * [False] + LAYERS * [True]
    assert any(float(jnp.abs(a - b).max()) > 0 for a, b in zip(other, want))


CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")


def _cell_asks(config):
    """What ``keeps_mlp_products`` reads of a benchmark configuration's
    cell, from its file alone: layer applications, the rows a chip holds
    (a block-diffusion step's are twice these: further from fitting), the
    dense feed-forward's width."""
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        m = json.load(f)
    run = m["run"]
    return (m["num_hidden_layers"] * m.get("total_ut_steps", 1),
            run["batch"] * run["seq"] // math.prod(run["mesh"].values()),
            m["intermediate_size"])


def test_mlp_products_taken_at_the_mistral_cells_shapes(monkeypatch):
    """Two layers of Mistral-7B's widths over 4,096 rows on a v5e: the
    rule takes the pair with a quarter to spare, 224 MiB a layer; the
    forward pass keeps it and the gradient's jaxpr holds the gate and the
    up matmul once a layer, where on a device of half the memory, which
    the rule refuses, it holds them twice.  Shapes alone: nothing is
    computed."""
    asks = _cell_asks("mistral7b_l2")
    assert asks == (2, 4096, 14336)
    assert kept.keeps_mlp_products(*asks, jnp.bfloat16, V5E_BYTES / 1.25)
    assert kept.mlp_products_bytes(4096, 14336, jnp.bfloat16) == 224 * 2 ** 20
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=2048)
    model = llama.LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 2048), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)

    def loss(params):
        return model.apply(params, ids).astype(jnp.float32).sum()

    def products_made(of_device):
        """(matmuls onto ``[B, S, I]`` in the gradient's jaxpr, a scan's
        body once: gate and up wherever they are made, and the pull-back
        of the down projection; the stacked arrays of that shape the
        forward pass keeps; the layer's ``remat.kept`` records)"""
        records = []
        monkeypatch.setattr(kept, "device_bytes", lambda device: of_device)
        monkeypatch.setattr(
            kept.trace, "note_trace_time",
            lambda name, **attrs: records.append(attrs))

        def count(jaxpr):
            return sum(
                (eqn.primitive.name == "dot_general"
                 and eqn.outvars[0].aval.shape == (2, 2048, 14336))
                + sum(map(count, jax.core.jaxprs_in_params(eqn.params)))
                for eqn in jaxpr.eqns)

        made = count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        held = [aval for aval, _ in saved_residuals(loss, params)
                if aval.shape == (2, 2, 2048, 14336)]
        return made, held, [r for r in records if r.get("core") == "mlp"]

    made, held, notes = products_made(V5E_BYTES)
    assert made == 2 + 1 and len(held) == 2
    assert all(aval.dtype == jnp.bfloat16 for aval in held)
    assert notes and all(note == {
        "core": "mlp", "names": "mlp_products",
        "bytes_per_layer": 224 * 2 ** 20,
        "mlp_products_bytes": 224 * 2 ** 20} for note in notes)
    assert products_made(V5E_BYTES // 2) == (2 + 2 + 1, [], [])


@pytest.mark.parametrize("config", [
    "keyevl2_30b_1of8", "evabyte_l4", "solaropen2_250b_1of32",
    "sdar_30b_1of8", "ling3flashvl_125b_1of32", "laguna_xs2_33b_1of8",
    "kanana2_30b_1of8", "phi4miniflash_l8", "ouro2b6_l8"])
def test_mlp_products_refused(config):
    """The benchmark's other Llama-path cells, whose steps fill the chip:
    every one asks over half as much again as the share gives."""
    applications, rows, width = _cell_asks(config)
    assert not kept.keeps_mlp_products(
        applications, rows, width, jnp.bfloat16, 1.5 * V5E_BYTES)
    # and a device that does not say how much it has keeps nothing
    assert not kept.keeps_mlp_products(1, 8, 8, jnp.bfloat16, 0)
