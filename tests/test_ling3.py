"""Ling-3.0's language model as the program runs it (``models/llama.py``
with a dense prefix before the periods, ``DeltaAttention`` under full-rank
bounded gates, ``LatentAttention``, ``models/moe.py`` choosing by groups
under a selection bias) against its plain reference
(``models/ling3_reference.py``) on the CPU in float32: token losses, the
gradients of every parameter, and three steps of ``Trainer`` whose bias
after each is the reference's.  **The shares add up**: the head shares of a
delta-rule layer and of the latent-attention layer (``W_kva`` and the
latent's norm on every chip) sum to the whole layer's result, and the
expert shares, with the shared expert and the router counted once, to the
uncut reference's block.  And a state that holds the buffer goes through a
save and a restore and resumes to the bit."""

import collections
import dataclasses
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import ling3_reference as reference
from dlrover_tpu.models.llama import (
    DeltaAttention,
    LatentAttention,
    LlamaForCausalLM,
)
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
from dlrover_tpu.trainer.train import Trainer
from against_reference import (
    inputs_and_labels,
    jitted,
    perturbed,
    reference_loss_and_gradients,
)

PREFIX, PATTERN = ("kda:dense",), ("kda", "kda", "mla")
SEQ = 48


def _config(**changes):
    fields = dict(
        num_layers=4, layer_prefix=PREFIX, layer_pattern=PATTERN,
        dense_intermediate_size=96, intermediate_size=32, num_heads=4,
        num_kv_heads=4, rope_theta=6e6, rms_norm_eps=1e-6,
        kda_heads=4, kda_head_dim=16, kda_chunk=16,
        kda_full_rank_gates=True, kda_decay_lower_bound=-5.0,
        kda_neg_eigval=False, mla_kv_rank=24, mla_nope_dim=16,
        mla_rope_dim=8, mla_v_dim=16, mla_head_gate=True,
        num_experts=16, top_k=4, norm_topk_prob=True,
        router_scores="sigmoid", routed_scaling_factor=2.5,
        shared_experts=1, n_group=4, topk_group=2, selection_bias=True,
        bias_update_rate=0.001, load_balance_coef=0.0, router_z_coef=0.0,
        dtype=jnp.float32)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "layer_prefix": cfg.layer_prefix,
            "layer_pattern": cfg.layer_pattern,
            "kda_lower_bound": cfg.kda_decay_lower_bound,
            "kv_lora_rank": cfg.mla_kv_rank,
            "qk_nope_head_dim": cfg.mla_nope_dim,
            "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group, "experts_total": cfg.num_experts,
            "first_expert": cfg.first_expert,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "bias_update_rate": cfg.bias_update_rate, **changes}


def _init(module, *args, seed=1):
    """``(parameters, buffers)`` of ``module.init``, unboxed, every leaf
    moved (a bias of 0 decides nothing)."""
    made = nn.meta.unbox(jitted(
        lambda key, *a: module.init(key, *a), jax.random.PRNGKey(seed), *args))
    buffers = made.get("buffers")
    return (perturbed(made["params"]),
            buffers and perturbed(buffers, seed=3, scale=0.05))


def _system(model, params, buffers, inputs, labels):
    """``((loss, (token losses, what the model sowed and its buffers after
    the step)), gradients)`` as ``Trainer``'s default loss computes them."""
    def loss_fn(p):
        logits, sown = model.apply(
            {"params": p, "buffers": buffers}, inputs,
            mutable=["losses", "stats", "buffers"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        token = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        extra = sum(jnp.sum(t) for t in jax.tree.leaves(sown["losses"]))
        return token.mean() + extra, (token, sown)

    return jitted(jax.value_and_grad(loss_fn, has_aux=True), params)


Made = collections.namedtuple(
    "Made", "cfg model params buffers inputs labels got want want_grads")


@pytest.fixture(scope="module", params=[0, 4], ids=["every_expert", "a_share"])
def made(request):
    cfg = _config(experts_held=request.param, first_expert=request.param * 2)
    model = LlamaForCausalLM(cfg)
    inputs, labels = inputs_and_labels(2, SEQ)
    params, buffers = _init(model, inputs)
    m = _published(cfg)
    want, want_grads = reference_loss_and_gradients(
        lambda p: reference.forward(p, buffers, inputs, labels, m), params)
    return Made(cfg, model, params, buffers, inputs, labels,
                _system(model, params, buffers, inputs, labels), want,
                want_grads)


def _bias_by_layer(buffers):
    """[routed layers, E] in the stack's order."""
    layers = buffers["layers"]
    return np.concatenate([
        np.asarray(layers[name]["layer"]["mlp"]["selection_bias"])[0]
        for name in ("kda_0", "mla_1")])


class TestAgainstReference:
    def test_token_losses_and_the_loss(self, made):
        (total, (token, sown)), _ = made.got
        np.testing.assert_allclose(token, made.want["token_losses"], rtol=0,
                                   atol=2e-5)
        # the objective has no balance term: what the routed block sows is 0
        np.testing.assert_allclose(total, made.want["loss"], rtol=1e-6)
        assert all(float(jnp.abs(t).max()) == 0
                   for t in jax.tree.leaves(sown["losses"]))

    def test_gradients_of_every_parameter(self, made):
        _, got = made.got
        flat = jax.tree_util.tree_leaves_with_path(got)
        for (path, g), w in zip(flat, jax.tree.leaves(made.want_grads)):
            name = "/".join(str(k.key) for k in path)
            assert float(jnp.abs(w).max()) > 0, name     # every leaf is used
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2e-4 * max(1.0, float(jnp.abs(w).max())),
                err_msg=name)
        assert len(flat) > 40

    def test_the_bias_after_the_step_is_the_references(self, made):
        (_, (_, sown)), _ = made.got
        rows = np.asarray(made.want["rows"])
        assert rows.shape == (3, 16) and rows.sum() == 3 * 2 * SEQ * 4
        want = np.stack([
            reference.bias_update(b, n, made.cfg.bias_update_rate)
            for b, n in zip(_bias_by_layer(made.buffers), rows)])
        np.testing.assert_array_equal(_bias_by_layer(sown["buffers"]), want)
        moved = np.abs(want - _bias_by_layer(made.buffers))
        assert np.allclose(moved[rows != rows.mean(axis=1, keepdims=True)],
                           made.cfg.bias_update_rate, atol=1e-7)

    def test_the_counters(self, made):
        (_, (_, sown)), _ = made.got
        stats = {path[-2].key: np.asarray(leaf).ravel() for path, leaf in
                 jax.tree_util.tree_leaves_with_path(
                     sown["stats"]["layers"]["kda_0"])}
        assert (stats["group_dropped_share"] > 0.2).all()
        np.testing.assert_allclose(
            stats["bias_abs_max"],
            np.abs(_bias_by_layer(made.buffers)[:2]).max(axis=1), rtol=1e-6)
        # beta has no factor 2 here: none is over 1
        assert not stats["kda_beta_over_one_share"].any()

    @pytest.mark.parametrize("changes", [
        {"n_group": 0, "topk_group": 0}, {"kda_neg_eigval": True},
        {"kda_decay_lower_bound": 0.0}, {"mla_head_gate": False},
        {"rope_theta": 100.0}, {"routed_scaling_factor": 1.0}],
        ids=lambda c: next(iter(c)))
    def test_a_departure_is_far_outside_float32_agreement(self, made, changes):
        other = LlamaForCausalLM(dataclasses.replace(made.cfg, **changes))
        params = made.params
        if "mla_head_gate" in changes:      # a tree without the gate
            params = jax.tree.map(lambda x: x, params)      # a copy
            del params["layers"]["mla_1"]["layer"]["attn"]["gate_proj"]
        (_, (token, _)), _ = _system(
            other, params, made.buffers, made.inputs, made.labels)
        assert float(jnp.abs(token - made.want["token_losses"]).max()) > 1e-2

    def test_no_bias_in_the_choice_is_another_model(self, made):
        zero = jax.tree.map(jnp.zeros_like, made.buffers)
        (_, (token, _)), _ = _system(
            made.model, made.params, zero, made.inputs, made.labels)
        assert float(jnp.abs(token - made.want["token_losses"]).max()) > 1e-2


def test_three_steps_move_the_bias_as_the_reference_does():
    """``Trainer``'s compiled step on one device: after each of three steps
    the state's bias is the reference's update of the bias before, from the
    reference's own routing of that step's parameters; the optimizer holds
    no moment for it and its gradient is in no norm."""
    cfg = _config()
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-3), mesh)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = {"input_ids": np.asarray(inputs), "labels": np.asarray(labels)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    assert not any(np.any(np.asarray(b)) for b in jax.tree.leaves(state.buffers))
    n_params = len(jax.tree.leaves(state.params))
    moments = [x for x in jax.tree.leaves(state.opt_state) if x.ndim]
    assert len(moments) == 2 * n_params      # mu and nu of parameters alone
    m = _published(cfg)
    for step in range(3):
        # the step donates its state: what the reference needs of it first
        before = _bias_by_layer(state.buffers)
        rows = jitted(lambda p, b: reference.forward(
            p, b, inputs, labels, m)["rows"],
            nn.meta.unbox(state.params), state.buffers)
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        want = np.stack([
            reference.bias_update(b, n, cfg.bias_update_rate)
            for b, n in zip(before, np.asarray(rows))])
        np.testing.assert_array_equal(_bias_by_layer(state.buffers), want)
        assert np.isfinite(float(metrics["loss"]))
    assert float(np.abs(_bias_by_layer(state.buffers)).max()) == pytest.approx(
        3 * cfg.bias_update_rate)


def test_a_model_with_buffers_refuses_the_paths_that_split_a_batch():
    cfg = _config()
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-3), mesh,
                      grad_accum_steps=2)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = {"input_ids": np.asarray(inputs), "labels": np.asarray(labels)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    with pytest.raises(NotImplementedError, match="moves buffers"):
        trainer.train_step(state, trainer.shard_batch(batch))


def test_a_state_with_the_buffer_resumes_to_the_bit(tmp_path):
    """Two steps, a memory save, one more step; the restored state takes
    the same step to the same loss, parameters and bias, bit for bit."""
    cfg = _config()
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-2), mesh)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = {"input_ids": np.asarray(inputs), "labels": np.asarray(labels)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    for _ in range(2):
        state, _ = trainer.train_step(state, trainer.shard_batch(batch))
    ckpt = Checkpointer(str(tmp_path), scope=f"t{uuid.uuid4().hex[:8]}")
    try:
        ckpt.save_checkpoint(2, state, StorageType.MEMORY)
        # the save holds copies: the step below donates the live state
        restored, step = ckpt.load_checkpoint(
            jax.eval_shape(lambda s: s, state), trainer.state_shardings)
    finally:
        ckpt.close()
    assert step == 2
    kept = jax.tree.map(np.asarray, state.buffers)
    for x, y in zip(jax.tree.leaves(kept), jax.tree.leaves(restored.buffers)):
        np.testing.assert_array_equal(x, np.asarray(y))
    assert float(np.abs(jax.tree.leaves(kept)[0]).max()) > 0
    went_on, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    resumed, again = trainer.train_step(restored, trainer.shard_batch(batch))
    assert float(metrics["loss"]) == float(again["loss"])
    for x, y in zip(jax.tree.leaves(went_on), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


#: which axis of a leaf counts heads, by the leaf's name
HEAD_AXIS = {"q_proj": 1, "k_proj": 1, "v_proj": 1, "f_proj": 1, "g_proj": 1,
             "beta_proj": 1, "gate_proj": 1, "kv_b_proj": 1, "o_proj": 0,
             "q_conv": 1, "k_conv": 1, "v_conv": 1, "A_log": 0, "dt_bias": 0}


def _head_share(params, first, held):
    """The leaves of an attention module cut to ``held`` heads from
    ``first``; what every chip holds alike (the latent's down-projection
    and norm, the head norm's scale) whole."""
    def cut(path, leaf):
        name = path[0].key
        if name not in HEAD_AXIS:
            return leaf
        return jax.lax.slice_in_dim(
            leaf, first, first + held, axis=HEAD_AXIS[name])
    return jax.tree_util.tree_map_with_path(cut, params)


class TestTheSharesAddUp:
    """One chip of ``tp`` holds some of a layer's heads, one of ``ep`` some
    of its experts: the parts all shares give, with what every chip
    computes alike counted once, add up to the uncut reference's layer."""

    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))

    def test_head_shares_of_a_delta_rule_layer(self, x):
        cfg = _config(kda_heads=8)
        full, _ = _init(DeltaAttention(cfg), x, None, None, seed=4)
        m = _published(cfg)
        whole = jitted(lambda p: reference.delta_attention(x, p, m), full)
        share_cfg = dataclasses.replace(cfg, kda_heads=2)
        parts = [jitted(lambda p: DeltaAttention(share_cfg).apply(
            {"params": p}, x, None, None), _head_share(full, first, 2))
            for first in (0, 2, 4, 6)]
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        for part in parts:      # no share is the whole and none is nothing
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(whole).mean())

    def test_head_shares_of_the_latent_attention_layer(self, x):
        """Eight query heads over four chips: a share is two heads' columns
        of ``W_q``, ``W_kvb``, ``w_gate`` and rows of ``W_o``; ``W_kva`` and
        the latent's norm are whole on every chip."""
        cfg = _config(num_heads=8, num_kv_heads=8)
        positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
        full, _ = _init(LatentAttention(cfg), x, positions, None, seed=5)
        assert set(full) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                             "gate_proj", "o_proj"}
        m = _published(cfg)
        whole = jitted(lambda p: reference.latent_attention(x, p, m), full)
        share_cfg = dataclasses.replace(cfg, num_heads=2, num_kv_heads=2)
        parts = []
        for first in (0, 2, 4, 6):
            share = _head_share(full, first, 2)
            for name in ("kv_a_proj", "kv_a_norm"):
                np.testing.assert_array_equal(
                    jax.tree.leaves(share[name])[0],
                    jax.tree.leaves(full[name])[0])
            parts.append(jitted(lambda p: LatentAttention(share_cfg).apply(
                {"params": p}, x, positions, None), share))
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        for part in parts:
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(whole).mean())

    def test_expert_shares_with_what_every_chip_holds_counted_once(self, x):
        """Four chips' shares of sixteen experts: every chip computes the
        router (all 16 columns, the bias, the groups) and the shared expert
        alike, so the sum of the shares holds the shared expert four times;
        counted once, the shares sum to the uncut reference's block, and
        every share counts the same load."""
        cfg = _config(num_layers=1, layer_prefix=(), layer_pattern=())
        full, buffers = _init(MoEMLP(cfg), x, seed=6)
        bias = buffers["selection_bias"]
        m = _published(cfg)
        want, rows = jitted(
            lambda p: reference.experts(x, p, bias, m), full)
        shared = jitted(reference.swiglu, x, full["shared_expert"])
        parts = []
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, experts_held=4,
                                        first_expert=first)
            held = {**full, **{name: full[name][first: first + 4] for name in
                               ("gate_proj", "up_proj", "down_proj")}}
            out, sown = jitted(lambda p: MoEMLP(share).apply(
                {"params": p, "buffers": buffers}, x,
                mutable=["losses", "stats", "buffers"]), held)
            alone = jitted(lambda p: reference.experts(
                x, p, bias, {**m, "first_expert": first})[0], held)
            np.testing.assert_allclose(out, alone, rtol=0, atol=2e-5)
            # every share moves the bias alike: by the load of all columns
            np.testing.assert_array_equal(
                sown["buffers"]["selection_bias"],
                reference.bias_update(bias, rows, cfg.bias_update_rate))
            parts.append(out)
        np.testing.assert_allclose(
            sum(parts) - 3 * shared, want, rtol=0, atol=5e-5)
        assert float(jnp.abs(shared).mean()) > 0.05 * float(
            jnp.abs(want).mean())


def test_the_reference_walks_the_stack_in_the_programs_order():
    cfg = _config(num_layers=7)
    model = LlamaForCausalLM(cfg)
    inputs, _ = inputs_and_labels(2, SEQ)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), inputs)
    zeros = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jnp.zeros(s.shape, s.dtype), nn.meta.unbox(tree))
    layers = reference.layers_of(
        zeros(shapes["params"]), zeros(shapes["buffers"]), _published(cfg))
    assert tuple(entry for entry, _, _ in layers) == PREFIX + PATTERN * 2
    assert [b is None for _, _, b in layers] == [True] + [False] * 6
    assert model.num_params() == sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree.leaves(nn.meta.unbox(shapes["params"])))
