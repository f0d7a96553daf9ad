"""Laguna-XS.2 as the program runs it (``models/llama.py`` with a dense
leading layer and a pattern of three window layers to one full layer, head
counts, rotary bases and rotary shares a kind, YaRN, a gate a head;
``models/moe.py`` with sigmoid scores renormalised over the chosen, a factor
and a shared expert) against its plain reference
(``models/laguna_reference.py``) on the CPU in float32: logits, token
losses, the loss the step minimises, the gradients of every parameter.  The
YaRN frequencies against the closed form at the published numbers, partial
rotary, and **the shares add up**: the routed block's results of all the
shares, the shared expert counted once, equal the uncut reference's
layer."""

import collections
import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import laguna_reference as reference
from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
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

PREFIX = ("gqa:dense",)
PATTERN = ("swa", "swa", "swa", "gqa")
SEQ = 48
WINDOW = 10
#: ``rope_parameters`` at the tiny size: the published rules at a head of
#: 16 (8 rotary columns, 4 pairs), a ramp that lies inside them
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 8, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}}


def _config(**changes):
    full, window = ROPE["full_attention"], ROPE["sliding_attention"]
    fields = dict(
        num_layers=5, layer_prefix=PREFIX, layer_pattern=PATTERN,
        dense_intermediate_size=96, num_heads=6, swa_heads=8, num_kv_heads=2,
        head_dim=16, sliding_window=WINDOW, attn_head_gate=True,
        rope_theta=float(full["rope_theta"]),
        swa_rope_theta=float(window["rope_theta"]),
        partial_rotary_factor=full["partial_rotary_factor"],
        yarn_factor=float(full["factor"]),
        yarn_original_max_len=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=full["attention_factor"],
        rms_norm_eps=1e-6, dtype=jnp.float32,
        num_experts=16, top_k=4, norm_topk_prob=True, router_scores="sigmoid",
        routed_scaling_factor=2.5, shared_experts=1,
        load_balance_coef=0.0, router_z_coef=0.0)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"rms_norm_eps": cfg.rms_norm_eps, "head_dim": cfg.head_dim,
            "layer_prefix": PREFIX, "layer_pattern": PATTERN,
            "sliding_window": cfg.sliding_window, "rope_parameters": ROPE,
            "num_experts_per_tok": cfg.top_k,
            "experts_total": cfg.num_experts,
            "first_expert": cfg.first_expert,
            "moe_routed_scaling_factor": cfg.routed_scaling_factor,
            **changes}


Made = collections.namedtuple(
    "Made", "cfg model params inputs labels got logits want want_grads")


@functools.lru_cache(maxsize=None)
def _made(held):
    cfg = _config(experts_held=held, first_expert=held * 2)
    model = LlamaForCausalLM(cfg)
    inputs, labels = inputs_and_labels(2, SEQ)
    params = perturbed(init_params(model, inputs))
    m = _published(cfg)
    want, want_grads = reference_loss_and_gradients(
        lambda p: reference.forward(p, inputs, labels, m), params)
    logits = jitted(lambda p: model.apply({"params": p}, inputs), params)
    return Made(cfg, model, params, inputs, labels,
                system(model, params, inputs, labels), logits, want,
                want_grads)


@pytest.fixture(scope="module", params=[0, 4], ids=["every_expert", "a_share"])
def made(request):
    return _made(request.param)


@pytest.fixture(scope="module")
def made_share():
    """The share's case alone: a departure shows on either."""
    return _made(4)


class TestAgainstReference:
    def test_the_stack_is_a_dense_layer_three_windows_and_a_full_layer(
            self, made):
        layers = made.params["layers"]
        assert set(made.params["prefix"]) == {"gqa_dense_0"}
        assert set(layers) == {"swa_0", "gqa_1"}
        # unlike head counts over the same two key heads
        assert layers["swa_0"]["layer"]["attn"]["q_proj"]["kernel"].shape == (
            1, 3, 64, 8, 16)
        assert layers["gqa_1"]["layer"]["attn"]["q_proj"]["kernel"].shape == (
            1, 1, 64, 6, 16)
        assert layers["swa_0"]["layer"]["attn"]["head_gate_proj"][
            "kernel"].shape == (1, 3, 64, 8)
        assert layers["gqa_1"]["layer"]["attn"]["k_proj"]["kernel"].shape == (
            1, 1, 64, 2, 16)
        assert WINDOW < SEQ
        kinds = [entry for entry, _ in reference.layers_of(
            made.params, _published(made.cfg))]
        assert tuple(kinds) == PREFIX + PATTERN
        assert made.model.num_params() == sum(
            leaf.size for leaf in jax.tree.leaves(made.params))

    def test_logits(self, made):
        np.testing.assert_allclose(
            made.logits, made.want["logits"], rtol=0, atol=5e-5)

    def test_losses(self, made):
        (total, (token, _)), _ = made.got
        np.testing.assert_allclose(
            token, made.want["token_losses"], rtol=0, atol=2e-5)
        np.testing.assert_allclose(total, made.want["loss"], rtol=1e-6)
        # every token chooses top_k of all the router's experts
        rows = np.asarray(made.want["rows"])
        assert rows.shape == (4, 16) and (rows.sum(axis=1) == 2 * SEQ * 4).all()

    def test_gradients_of_every_parameter(self, made):
        _, got = made.got
        flat = jax.tree_util.tree_leaves_with_path(got)
        for (path, g), w in zip(flat, jax.tree.leaves(made.want_grads)):
            name = "/".join(str(k.key) for k in path)
            assert float(jnp.abs(w).max()) > 0, name     # every leaf is used
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2e-4 * max(1.0, float(jnp.abs(w).max())),
                err_msg=name)
        assert len(flat) >= 36

    @pytest.mark.parametrize("changes", [
        {"sliding_window": WINDOW - 1}, {"sliding_window": WINDOW + 1},
        {"sliding_window": SEQ}, {"swa_rope_theta": 500000.0},
        {"rope_theta": 10000.0}, {"partial_rotary_factor": 1.0},
        {"yarn_factor": 0.0}, {"yarn_attention_factor": 1.0},
        {"yarn_original_max_len": 4096}, {"routed_scaling_factor": 1.0},
        {"norm_topk_prob": False}, {"shared_experts": 0},
        {"router_scores": "softmax"}],
        ids=lambda c: "{}={}".format(*next(iter(c.items()))))
    def test_a_departure_is_far_outside_float32_agreement(
            self, made_share, changes):
        made = made_share
        other = LlamaForCausalLM(dataclasses.replace(made.cfg, **changes))
        params = made.params
        if "shared_experts" in changes:     # a tree without the shared expert
            shapes = nn.meta.unbox(jax.eval_shape(
                other.init, jax.random.PRNGKey(1), made.inputs)["params"])
            have = {jax.tree_util.keystr(p): leaf for p, leaf in
                    jax.tree_util.tree_leaves_with_path(params)}
            params = jax.tree_util.tree_map_with_path(
                lambda p, s: have[jax.tree_util.keystr(p)], shapes)
        _, (token, _) = system_loss(other, params, made.inputs, made.labels)
        assert float(jnp.abs(
            token - made.want["token_losses"]).max()) > 1e-2

    def test_a_head_gate_is_not_an_elementwise_one(self, made):
        cfg = dataclasses.replace(
            made.cfg, attn_head_gate=False, attn_gate=True)
        shapes = nn.meta.unbox(jax.eval_shape(
            LlamaForCausalLM(cfg).init, jax.random.PRNGKey(1),
            made.inputs)["params"])
        attn = shapes["layers"]["swa_0"]["layer"]["attn"]
        assert "head_gate_proj" not in attn
        assert attn["gate_proj"]["kernel"].shape == (1, 3, 64, 8, 16)
        both = LlamaForCausalLM(dataclasses.replace(cfg, attn_head_gate=True))
        assert both.num_params() - made.model.num_params() == 64 * 16 * (
            2 * 6 + 3 * 8)


class TestYarn:
    """``transformers``' ``_compute_yarn_parameters`` at the published
    numbers, in closed form."""

    PUBLISHED = dict(dim=64, theta=500000.0, factor=64.0, original=4096,
                     beta_fast=64.0, beta_slow=1.0)

    def test_the_ramp_runs_from_pair_5_to_pair_16(self):
        freq, (low, high) = llama.yarn_frequencies(**self.PUBLISHED)
        assert (low, high) == (5, 16) and freq.shape == (32,)
        plain = 500000.0 ** (-np.arange(32) / 32.0)
        # fast pairs as they were, slow pairs stretched 64 times, between
        # them the blend
        np.testing.assert_allclose(freq[:6], plain[:6], rtol=1e-12)
        np.testing.assert_allclose(freq[16:], plain[16:] / 64, rtol=1e-12)
        for i in range(6, 16):
            ramp = (i - 5) / 11
            np.testing.assert_allclose(
                freq[i], plain[i] * (1 - ramp) + plain[i] / 64 * ramp,
                rtol=1e-12)
        assert np.all(np.diff(freq) < 0)

    def test_the_factor_on_cos_and_sin(self):
        cfg = LlamaConfig.tiny(yarn_factor=64.0, yarn_original_max_len=4096)
        assert cfg.attention_numbers("gqa").yarn[4] == pytest.approx(
            1.4158883083359672, rel=1e-12)
        assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)
        given = LlamaConfig.tiny(yarn_factor=64.0, yarn_original_max_len=4096,
                                 yarn_attention_factor=1.25)
        assert given.attention_numbers("gqa").yarn[4] == 1.25

    def test_the_reference_has_the_same_frequencies(self):
        published = {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
        freq, factor, rotary = reference.frequencies(published, 128)
        assert rotary == 64 and factor == 1.4158883083359672
        np.testing.assert_allclose(
            freq, llama.yarn_frequencies(**self.PUBLISHED)[0], rtol=2e-6)

    def test_rotation_by_yarn_scales_the_rotated_half_alone(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 16))
        positions = jnp.arange(12)[None]
        yarn = (64.0, 64, 8.0, 1.0, 1.5)
        turned = llama._rope(x, positions, 500000.0, 8, yarn)
        # the last half of a head passes untouched, bit for bit
        np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
        # a rotation times the factor: the pairs' norms grow by it
        norms = lambda t: jnp.sqrt(  # noqa: E731
            jnp.square(t[..., :4]) + jnp.square(t[..., 4:8]))
        np.testing.assert_allclose(norms(turned), 1.5 * norms(x), rtol=1e-5)
        # position 0 turns nothing
        np.testing.assert_allclose(turned[:, 0, :, :8], 1.5 * x[:, 0, :, :8],
                                   rtol=1e-6)

    def test_partial_rotary_is_the_whole_rope_on_the_leading_columns(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 3, 16))
        positions = jnp.broadcast_to(jnp.arange(9), (2, 9))
        part = llama._rope(x, positions, 10000.0, 8)
        np.testing.assert_array_equal(part[..., 8:], x[..., 8:])
        np.testing.assert_array_equal(
            part[..., :8], llama._rope(x[..., :8], positions, 10000.0))
        # the whole head by default, the same program as ever
        np.testing.assert_array_equal(
            llama._rope(x, positions, 10000.0, 16),
            llama._rope(x, positions, 10000.0))


class TestTheSharesAddUp:
    def test_expert_shares_with_the_shared_expert_counted_once(self):
        """Four chips' shares of sixteen experts: every chip computes the
        shared expert alike, so the sum of the shares holds it four times;
        counted once, the shares sum to the uncut reference's layer."""
        x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
        cfg = _config(num_layers=1, layer_prefix=(), layer_pattern=())
        full = perturbed(init_params(MoEMLP(cfg), x, seed=6))
        m = _published(cfg)
        want, rows = jitted(lambda p: reference.experts(x, p, m), full)
        assert int(rows.sum()) == 2 * SEQ * 4
        shared = jitted(reference.swiglu, x, full["shared_expert"])
        parts = []
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, experts_held=4,
                                        first_expert=first)
            held = {**full, **{name: full[name][first: first + 4] for name in
                               ("gate_proj", "up_proj", "down_proj")}}
            out, _ = jitted(lambda p: MoEMLP(share).apply(
                {"params": p}, x, mutable=["losses", "stats"]), held)
            alone = jitted(lambda p: reference.experts(
                x, p, {**m, "first_expert": first})[0], held)
            np.testing.assert_allclose(out, alone, rtol=0, atol=2e-5)
            parts.append(out)
        np.testing.assert_allclose(
            sum(parts) - 3 * shared, want, rtol=0, atol=5e-5)
        assert float(jnp.abs(shared).mean()) > 0.05 * float(
            jnp.abs(want).mean())
        # no share is the whole and none is nothing
        for part in parts:
            assert float(jnp.abs(part - shared).mean()) > 0.02 * float(
                jnp.abs(want).mean())


class TestTheConfigurationRefuses:
    @pytest.mark.parametrize("fields, says", [
        (dict(sliding_window=8, index_topk=4, index_heads=2,
              index_head_dim=8), "index_topk"),
        (dict(sliding_window=8, eva_window=16, eva_chunk=4, num_kv_heads=4),
         "eva_window"),
        (dict(sliding_window=8, block_diffusion=4), "block_diffusion"),
        (dict(sliding_window=8, swa_heads=5), "swa_heads"),
        (dict(layer_pattern=("swa", "gqa")), "sliding_window"),
        (dict(partial_rotary_factor=0.3), "partial_rotary_factor"),
        (dict(partial_rotary_factor=0.0), "partial_rotary_factor"),
        (dict(yarn_factor=64.0), "yarn_original_max_len"),
    ], ids=lambda v: v if isinstance(v, str) else "")
    def test_a_pair_that_cannot_run(self, fields, says):
        with pytest.raises(ValueError, match=says):
            LlamaConfig.tiny(**fields)

    def test_the_ring_knows_no_window(self):
        cfg = LlamaConfig.tiny(
            layer_pattern=("swa", "gqa"), sliding_window=8,
            attention_impl="ring")
        ids = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(NotImplementedError, match="window"):
            jax.eval_shape(LlamaForCausalLM(cfg).init, jax.random.PRNGKey(0),
                           ids)

    def test_defaults_name_no_window_and_plain_rope(self):
        defaults = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
        assert defaults["sliding_window"] == 0 and defaults["swa_heads"] == 0
        assert defaults["partial_rotary_factor"] == 1.0
        assert defaults["yarn_factor"] == 0.0
        assert defaults["attn_head_gate"] is False
        numbers = LlamaConfig.tiny().attention_numbers("gqa")
        assert numbers == (4, None, 10000.0, 16, None)
        assert "swa" in llama.LAYER_KINDS


def test_three_trainer_steps_through_the_normal_path():
    """``Trainer`` on the pattern: the loss is finite and falls, with the
    window layers' and the full layers' parameters both moved."""
    import optax

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.train import Trainer

    cfg = _config(experts_held=4)
    model = LlamaForCausalLM(cfg)
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(model, optax.adam(1e-2), mesh)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = trainer.shard_batch({"input_ids": np.asarray(inputs),
                                 "labels": np.asarray(labels)})
    state = trainer.create_state(jax.random.PRNGKey(0), np.asarray(inputs))
    before = jax.tree.map(np.asarray, nn.meta.unbox(state.params))
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    after = nn.meta.unbox(state.params)
    for name in ("swa_0", "gqa_1"):
        for leaf in ("q_proj", "head_gate_proj"):
            was = before["layers"][name]["layer"]["attn"][leaf]["kernel"]
            now = after["layers"][name]["layer"]["attn"][leaf]["kernel"]
            assert float(jnp.abs(now - was).max()) > 0, (name, leaf)
