"""What a rematerialised decoder layer keeps (``ops/pallas/kept.py``): a
stack of two scanned layers under ``models/llama.py::_layer_class``'s
policy, once for each attention core, the Pallas kernels in the interpreter
on the CPU.

For a core whose kernels name their results (``selected_attention``,
``block_diffusion_kernels``, ``masked_attention`` with ``shared`` keys,
``_kda``): the
forward pass keeps exactly the layer's input and the named values, the LSE
as ``[B, H, Q]``, at the bytes a layer that ``remat.kept`` notes; the
gradient's jaxpr holds each forward kernel once a layer, where it holds it
twice under ``nothing_saveable``; and the loss and every gradient are those
under ``nothing_saveable`` bit for bit.  For a core that names nothing (the
FA2 kernel, the reference, a ``jax.numpy`` body) the two policies lower to
the same program.

The expert layer (``models/moe.py``) names the two products of a pass's
first grouped matmuls the same way, ``jax.numpy`` and all, and its router's
logits, choice and count of rows: the same stack with one chip's share of
an expert layer in each, once for each way the router chooses.
"""

import collections
import functools
import math

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
from dlrover_tpu.ops.pallas import kept
from shared_memo import shared_memo

LAYERS, HEADS, DIM, HIDDEN = 2, 2, 128, 64
F32 = jnp.dtype("float32")


def _interpreted(module, name):
    return module, name, functools.partial(
        getattr(module, name), interpret=True)


def _config(**fields):
    return LlamaConfig.tiny(**{**dict(
        num_layers=LAYERS, num_heads=HEADS, num_kv_heads=1, head_dim=DIM,
        hidden_size=HIDDEN, dtype=jnp.float32), **fields})


#: core -> (configuration, the layers' kind, rows of the residual stream,
#: the entry to run in the interpreter, the forward kernels by name with
#: their calls a layer, the named values a layer keeps as (shape, number))
Core = collections.namedtuple(
    "Core", "config kind rows entry forward_kernels kept")
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
}
#: cores that name nothing: the FA2 kernel, the reference, and the
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
