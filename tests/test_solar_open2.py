"""Solar-Open2 as the program runs it (``models/llama.py`` with a layer
pattern, ``DeltaAttention``, gated softmax attention without positions,
``models/moe.py`` with sigmoid scores and a shared expert) against its plain
reference (``models/solar_open2_reference.py``) on the CPU in float32: token
losses, the loss the step minimises, the gradients of every parameter, the
counters.  And **the shares add up**: the head shares of a delta-rule layer
and of a softmax layer sum to the whole layer's result, and the expert
shares, with the shared expert counted once, to the uncut layer's."""

import collections
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import solar_open2_reference as reference
from dlrover_tpu.models.llama import (
    Attention,
    DeltaAttention,
    LlamaForCausalLM,
)
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP
from against_reference import (
    init_params,
    inputs_and_labels,
    jitted,
    perturbed,
    reference_loss_and_gradients,
    system,
    system_loss,
)

PATTERN = ("gqa", "kda", "kda", "kda")
SEQ = 48


def _config(**changes):
    fields = dict(
        num_layers=4, layer_pattern=PATTERN, use_rope=False, attn_gate=True,
        kda_heads=4, kda_head_dim=16, kda_chunk=16, dtype=jnp.float32,
        num_experts=8, top_k=3, norm_topk_prob=True, router_scores="sigmoid",
        shared_experts=1, load_balance_coef=0.001, router_z_coef=0.0)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"rms_norm_eps": cfg.rms_norm_eps, "layer_pattern": PATTERN,
            "num_experts_per_tok": cfg.top_k,
            "experts_total": cfg.num_experts,
            "first_expert": cfg.first_expert,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "router_aux_loss_coef": cfg.load_balance_coef,
            "query_block": 16, **changes}


def _ids(seed=0):
    return inputs_and_labels(2, SEQ, seed=seed)


#: what the fixture computed, once a parameter: the system's
#: ``((total, (token losses, sown)), gradients)`` and the reference's
#: dictionary and gradients
Made = collections.namedtuple(
    "Made", "cfg model params inputs labels got want want_grads")


@pytest.fixture(scope="module", params=[0, 2], ids=["every_expert", "a_share"])
def made(request):
    cfg = _config(experts_held=request.param, first_expert=request.param * 2)
    model = LlamaForCausalLM(cfg)
    inputs, labels = _ids()
    params = perturbed(init_params(model, inputs))
    m = _published(cfg)
    want, want_grads = reference_loss_and_gradients(
        lambda p: reference.forward(p, inputs, labels, m), params)
    return Made(cfg, model, params, inputs, labels,
                system(model, params, inputs, labels), want, want_grads)


class TestAgainstReference:
    def test_losses_and_every_counter(self, made):
        (total, (token, sown)), _ = made.got
        want = made.want
        np.testing.assert_allclose(token, want["token_losses"], rtol=0,
                                   atol=2e-5)
        np.testing.assert_allclose(total, want["loss"], rtol=1e-6)
        kda = sown["stats"]["layers"]["kda_1"]["layer"]["attn"]
        np.testing.assert_allclose(
            kda["kda_beta_over_one_share"][0].ravel(),
            want["beta_over_one_share"], atol=1e-6)
        np.testing.assert_allclose(
            kda["kda_decay_half_life"][0].ravel(), want["decay_half_life"],
            rtol=1e-4)
        # the mechanism decides something on this state
        assert 0.2 < float(want["beta_over_one_share"].min())
        assert float(want["beta_over_one_share"].max()) < 0.8

    def test_gradients_of_every_parameter(self, made):
        _, got = made.got
        flat = jax.tree_util.tree_leaves_with_path(got)
        for (path, g), w in zip(flat, jax.tree.leaves(made.want_grads)):
            name = "/".join(str(k.key) for k in path)
            assert float(jnp.abs(w).max()) > 0, name     # every leaf is used
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2e-4 * max(1.0, float(jnp.abs(w).max())),
                err_msg=name)
        assert len(flat) > 30

    @pytest.mark.parametrize("changes", [
        {"use_rope": True}, {"attn_gate": False}, {"shared_experts": 0},
        {"router_scores": "softmax"}, {"kda_conv": 1}],
        ids=lambda c: next(iter(c)))
    def test_a_departure_is_far_outside_float32_agreement(self, made, changes):
        cfg, params, inputs, labels = (
            made.cfg, made.params, made.inputs, made.labels)
        want = made.want["token_losses"]
        other = LlamaForCausalLM(dataclasses.replace(cfg, **changes))
        shapes = jax.eval_shape(other.init, jax.random.PRNGKey(1), inputs)
        # the other model's tree from this one's leaves where they exist
        have = {jax.tree_util.keystr(p): leaf for p, leaf in
                jax.tree_util.tree_leaves_with_path(params)}
        theirs = jax.tree_util.tree_map_with_path(
            lambda p, s: have[jax.tree_util.keystr(p)][
                tuple(slice(0, n) for n in s.shape)]
            if jax.tree_util.keystr(p) in have else jnp.ones(s.shape, s.dtype),
            nn.meta.unbox(shapes["params"]))
        _, (token, _) = system_loss(other, theirs, inputs, labels)
        assert float(jnp.abs(token - want).max()) > 1e-2


def _delta(cfg, params, x):
    return jitted(lambda p, x: DeltaAttention(cfg).apply(
        {"params": p}, x, None, None), params, x)


def _softmax(cfg, params, x):
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    mask = jnp.tril(jnp.ones((x.shape[1],) * 2, bool))[None, None]
    return jitted(lambda p, x: Attention(cfg).apply(
        {"params": p}, x, positions, mask), params, x)


#: which axis of a leaf counts heads, by the leaf's name
HEAD_AXIS = {"q_proj": 1, "k_proj": 1, "v_proj": 1, "gate_proj": 1,
             "f_up": 1, "g_up": 1, "beta_proj": 1, "o_proj": 0,
             "q_conv": 1, "k_conv": 1, "v_conv": 1, "A_log": 0, "dt_bias": 0}


def _head_share(params, first, held, kv=None):
    """The leaves of an attention module cut to ``held`` heads from
    ``first`` (``kv``: (first, held) of the key heads of a softmax layer);
    what every chip holds alike (the low-rank down projections, the head
    norm's scale) whole."""
    def cut(path, leaf):
        name = path[0].key
        if name not in HEAD_AXIS:
            return leaf
        lo, n = (kv if kv and name in ("k_proj", "v_proj") else (first, held))
        return jax.lax.slice_in_dim(leaf, lo, lo + n, axis=HEAD_AXIS[name])
    return jax.tree_util.tree_map_with_path(cut, params)


class TestTheSharesAddUp:
    """One chip of ``tp`` holds some of a layer's heads: a share of heads
    is the model with fewer heads, because heads are independent up to the
    output projection's sum."""

    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))

    def test_head_shares_of_a_delta_rule_layer(self, x):
        cfg = _config(kda_heads=8)
        full = perturbed(init_params(
            DeltaAttention(cfg), x, None, None, seed=4))
        whole = _delta(cfg, full, x)
        share_cfg = dataclasses.replace(cfg, kda_heads=2)
        parts = [_delta(share_cfg, _head_share(full, first, 2), x)
                 for first in (0, 2, 4, 6)]
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        for part in parts:      # no share is the whole and none is nothing
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(whole).mean())
        # and against the reference given the same share
        m = _published(cfg)
        want = jitted(lambda p: reference.delta_attention(x, p, m)[0],
                      _head_share(full, 4, 2))
        np.testing.assert_allclose(parts[2], want, rtol=0, atol=2e-5)

    def test_head_shares_of_a_softmax_layer(self, x):
        """Eight query heads on two key heads: a share is four query heads
        and the one key head that serves them, so the share is whole."""
        cfg = _config(num_heads=8, num_kv_heads=2)
        full = perturbed(init_params(Attention(cfg), x, None, None, seed=5))
        whole = _softmax(cfg, full, x)
        share_cfg = dataclasses.replace(cfg, num_heads=4, num_kv_heads=1)
        parts = [_softmax(share_cfg, _head_share(
            full, 4 * i, 4, kv=(i, 1)), x) for i in (0, 1)]
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        want = jitted(
            lambda p: reference.gated_attention(x, p, _published(cfg)),
            _head_share(full, 4, 4, kv=(1, 1)))
        np.testing.assert_allclose(parts[1], want, rtol=0, atol=2e-5)

    def test_expert_shares_with_the_shared_expert_counted_once(self, x):
        """Four chips' shares of eight experts: every chip computes the
        shared expert alike, so the sum of the shares holds it four times;
        counted once, the shares sum to the uncut reference's layer."""
        cfg = _config(num_layers=1, layer_pattern=())
        full = perturbed(init_params(MoEMLP(cfg), x, seed=6))
        m = _published(cfg)
        want, balance, _ = jitted(
            lambda p: reference.experts(x, p, m, whole=True), full)
        shared = jitted(reference.swiglu, x, full["shared_expert"])
        parts = []
        for first in (0, 2, 4, 6):
            share = dataclasses.replace(cfg, experts_held=2,
                                        first_expert=first)
            held = {**full, **{name: full[name][first: first + 2] for name in
                               ("gate_proj", "up_proj", "down_proj")}}
            out, sown = jitted(lambda p: MoEMLP(share).apply(
                {"params": p}, x, mutable=["losses", "stats"]), held)
            alone = jitted(lambda p: reference.experts(
                x, p, {**m, "first_expert": first})[0], held)
            np.testing.assert_allclose(out, alone, rtol=0, atol=2e-5)
            # every share computes the same loss: the routing's, over all
            np.testing.assert_allclose(
                sown["losses"]["load_balance"][0] / cfg.load_balance_coef,
                balance, rtol=1e-5)
            parts.append(out)
        np.testing.assert_allclose(
            sum(parts) - 3 * shared, want, rtol=0, atol=5e-5)
        assert float(jnp.abs(shared).mean()) > 0.05 * float(
            jnp.abs(want).mean())


def test_the_reference_walks_the_stack_in_the_programs_order():
    cfg = _config(num_layers=8)
    model = LlamaForCausalLM(cfg)
    inputs, _ = _ids()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), inputs)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          nn.meta.unbox(shapes["params"]))
    kinds = [kind for kind, _ in reference.layers_of(params, PATTERN)]
    assert tuple(kinds) == PATTERN * 2
