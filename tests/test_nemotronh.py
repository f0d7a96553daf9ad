"""Nemotron-3-Super's language model as the program runs it
(``models/llama.py`` with layers of ONE branch, ``Mamba2Mixer`` over
``ops/ssd.py``, a softmax layer without positions, ``models/moe.py`` with
experts of two matrices under ``relu(.)^2`` in a latent beside a full-width
shared expert, the choice under a selection bias) against its plain
reference (``models/nemotronh_reference.py``) on the CPU in float32: token
losses, the loss, the gradient of every parameter, the bias after a step
and the counters.  The chunked scan against its token-at-a-time body.
**The shares add up**: head-and-group shares of a Mamba-2 layer, head shares
of the attention layer and expert shares of the latent layer (the latent's
two projections, the router and the shared expert whole on every chip, the
shared expert counted once) sum to the uncut reference's layer."""

import collections
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import nemotronh_reference as reference
from dlrover_tpu.models.llama import (
    Attention,
    LlamaForCausalLM,
    Mamba2Mixer,
    layer_branches,
)
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP, ladder
from dlrover_tpu.ops.ssd import ssd, ssd_core, ssd_recurrent
from against_reference import (
    inputs_and_labels,
    jitted,
    perturbed,
    reference_loss_and_gradients,
)

#: ``EMEM*``: two periods of (feed-forward, Mamba-2), the attention layer once
PATTERN, SUFFIX = ("ffn", "mamba2:alone"), ("gqa:alone",)
SEQ = 40        # five chunks of 8; the scan's own tests run a ragged 37


def _config(**changes):
    fields = dict(
        num_layers=5, layer_pattern=PATTERN, layer_suffix=SUFFIX,
        use_rope=False, num_heads=4, num_kv_heads=1, head_dim=16,
        rms_norm_eps=1e-5, mamba2_heads=4, mamba2_head_dim=8,
        mamba2_groups=2, mamba2_state=16, mamba2_chunk=8, mamba_conv=4,
        num_experts=8, top_k=3, intermediate_size=24, moe_latent_size=16,
        mlp_matrices=2, mlp_activation="relu2", shared_experts=1,
        shared_intermediate_size=48, norm_topk_prob=True,
        router_scores="sigmoid", routed_scaling_factor=5.0,
        selection_bias=True, bias_update_rate=0.001, load_balance_coef=0.0,
        router_z_coef=0.0, dtype=jnp.float32)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"layer_norm_epsilon": cfg.rms_norm_eps,
            "layer_pattern": cfg.layer_pattern,
            "layer_suffix": cfg.layer_suffix,
            "mamba_num_heads": cfg.mamba2_heads,
            "mamba_head_dim": cfg.mamba2_head_dim,
            "n_groups": cfg.mamba2_groups,
            "ssm_state_size": cfg.mamba2_state,
            "conv_kernel": cfg.mamba_conv,
            "num_experts_per_tok": cfg.top_k,
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


@pytest.fixture(scope="module", params=[0, 2], ids=["every_expert", "a_share"])
def made(request):
    """A share holds 2 experts of 8 at 3 a token: fewer than a token takes,
    as the benchmark's cell holds 16 at 22."""
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
    return np.asarray(
        buffers["layers"]["ffn_0"]["layer"]["mlp"]["selection_bias"])[:, 0]


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
        assert len(flat) == 25

    def test_the_bias_after_the_step_is_the_references(self, made):
        (_, (_, sown)), _ = made.got
        rows = np.asarray(made.want["rows"])
        assert rows.shape == (2, 8) and rows.sum() == 2 * 2 * SEQ * 3
        want = np.stack([
            reference.bias_update(b, n, made.cfg.bias_update_rate)
            for b, n in zip(_bias_by_layer(made.buffers), rows)])
        np.testing.assert_array_equal(_bias_by_layer(sown["buffers"]), want)
        moved = np.abs(want - _bias_by_layer(made.buffers))
        assert np.allclose(moved[rows != rows.mean(axis=1, keepdims=True)],
                           made.cfg.bias_update_rate, atol=1e-7)

    def test_the_counters(self, made):
        (_, (_, sown)), _ = made.got
        layers = sown["stats"]["layers"]
        decay = np.asarray(layers["mamba2_alone_1"]["layer"]["attn"][
            "ssd_decay_p50"][0]).ravel()
        np.testing.assert_allclose(decay, made.want["decay_p50"], rtol=1e-5)
        assert ((decay > 0.05) & (decay < 0.95)).all()
        routed = layers["ffn_0"]["layer"]["mlp"]
        np.testing.assert_allclose(
            np.asarray(routed["bias_abs_max"][0]).ravel(),
            np.abs(_bias_by_layer(made.buffers)).max(axis=1), rtol=1e-6)
        rows = np.asarray(made.want["rows"])
        np.testing.assert_allclose(
            np.asarray(routed["load_max_over_mean"][0]).ravel(),
            rows.max(axis=1) / rows.mean(axis=1), rtol=1e-6)
        if made.cfg.experts_held:
            first, held = made.cfg.first_expert, made.cfg.experts_held
            np.testing.assert_allclose(
                np.asarray(routed["share_rows_over_expected"][0]).ravel(),
                rows[:, first: first + held].sum(axis=1) * 8 / (
                    held * rows.sum(axis=1)), rtol=1e-6)

    @pytest.mark.parametrize("changes", [
        {"mlp_activation": "silu"}, {"routed_scaling_factor": 1.0},
        {"norm_topk_prob": False}, {"use_rope": True},
        {"rms_norm_eps": 0.1}, {"mamba2_chunk": 16}],
        ids=lambda c: next(iter(c)))
    def test_a_departure_is_far_outside_float32_agreement(self, made, changes):
        """``silu`` in relu²'s place, the factor 5 left out, positions on
        the attention layer, another epsilon in the norms (the gated group
        norm's too): each reads hundreds of times float32 agreement; the
        chunk's length is no departure: it changes nothing."""
        other = LlamaForCausalLM(dataclasses.replace(made.cfg, **changes))
        (_, (token, _)), _ = _system(
            other, made.params, made.buffers, made.inputs, made.labels)
        err = float(jnp.abs(token - made.want["token_losses"]).max())
        if "mamba2_chunk" in changes:
            assert err < 2e-5
        else:
            assert err > 1e-2

    def test_the_bias_in_the_weights_is_another_model(self, made):
        """The benchmark's copy of the reference with its planted fault
        (``s + b`` in the weights' place of ``s``); without the fault it is
        the repository's reference."""
        from benchmarks.common import load_module

        family = load_module("families", "nemotronh")
        m = _published(made.cfg, query_block=8)
        plain, planted = (jitted(lambda p: family.reference(
            p, made.buffers, made.inputs, made.labels, m, fault=fault)[0],
            made.params) for fault in (None, "bias_in_weights"))
        np.testing.assert_allclose(plain, made.want["token_losses"], rtol=0,
                                   atol=2e-5)
        assert float(jnp.abs(planted - plain).max()) > 1e-3

    def test_no_bias_in_the_choice_is_another_model(self, made):
        zero = jax.tree.map(jnp.zeros_like, made.buffers)
        (_, (token, _)), _ = _system(
            made.model, made.params, zero, made.inputs, made.labels)
        assert float(jnp.abs(token - made.want["token_losses"]).max()) > 1e-2


def test_a_one_branch_layer_has_no_dead_leaf_and_the_count_is_the_trees():
    cfg = _config(experts_held=2)
    model = LlamaForCausalLM(cfg)
    inputs, _ = inputs_and_labels(2, SEQ)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), inputs)
    params = nn.meta.unbox(shapes["params"])
    assert set(params) == {"embed_tokens", "layers", "suffix", "final_norm",
                           "lm_head"}
    ffn, mamba = (params["layers"][name]["layer"]
                  for name in ("ffn_0", "mamba2_alone_1"))
    attn = params["suffix"]["gqa_alone_0"]["layer"]
    assert set(ffn) == {"input_norm", "mlp"}
    assert set(mamba) == set(attn) == {"input_norm", "attn"}
    assert set(ffn["mlp"]) == {"router", "latent_down", "latent_up",
                               "up_proj", "down_proj", "shared_expert"}
    assert set(ffn["mlp"]["shared_expert"]) == {"up_proj", "down_proj"}
    # [periods, run, held, latent, width]
    assert ffn["mlp"]["up_proj"].shape == (2, 1, 2, 16, 24)
    assert ffn["mlp"]["down_proj"].shape == (2, 1, 2, 24, 16)
    assert ffn["mlp"]["shared_expert"]["up_proj"]["kernel"].shape == (
        2, 1, 64, 48)
    assert set(mamba["attn"]) == {
        "in_proj", "conv_weight", "conv_bias", "A_log", "dt_bias", "D",
        "norm_scale", "out_proj"}
    assert mamba["attn"]["in_proj"]["kernel"].shape == (
        2, 1, 64, 32 + (32 + 2 * 2 * 16) + 4)
    assert mamba["attn"]["conv_weight"].shape == (2, 1, 4, 96)
    assert attn["attn"]["k_proj"]["kernel"].shape == (1, 64, 1, 16)
    assert model.num_params() == sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert [layer_branches(e) for e in ("ffn", "ffn:dense", "gqa:alone",
                                        "gqa", "kda:dense")] == [
        (None, True), (None, True), ("gqa", False), ("gqa", True),
        ("kda", True)]
    layers = reference.layers_of(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params),
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                     nn.meta.unbox(shapes["buffers"])), _published(cfg))
    assert tuple(entry for entry, _, _ in layers) == PATTERN * 2 + SUFFIX


@pytest.mark.parametrize("changes,match", [
    ({"layer_pattern": ("ffn:alone", "mamba2:alone")}, "entries"),
    ({"mamba2_heads": 0}, "mamba2_heads"),
    ({"mamba2_groups": 3}, "mamba2_heads"),
    ({"num_layers": 6}, "whole number of periods"),
    ({"mlp_activation": "gelu"}, "mlp_activation"),
])
def test_what_the_config_refuses(changes, match):
    with pytest.raises(ValueError, match=match):
        _config(**changes)


def test_more_experts_a_token_than_are_held_bound_the_worst_rung():
    """22 of 512 a token, 16 held: a token meets an expert once, so the
    worst case is 16 rows a token and not 22."""
    rows = 16384 * 22
    assert ladder(rows, 16, 512, 22) == (14080, 16896, 22528, 16384 * 16)
    assert ladder(rows, 16, 512)[-1] == rows
    assert ladder(16384 * 8, 16, 128, 8) == ladder(16384 * 8, 16, 128)


# --------------------------------------------------------------------------
# the chunked scan against the token-at-a-time body
# --------------------------------------------------------------------------

def _scan_operands(seq, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    Bt, H, P, G, n = 2, 4, 8, 2, 16
    return (jax.random.normal(k[0], (Bt, seq, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (Bt, seq, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (Bt, seq, G, n)),
            jax.random.normal(k[4], (Bt, seq, G, n)),
            jax.random.normal(k[5], (H,)))


@pytest.fixture(scope="module")
def scanned():
    """The recurrence's value and gradients at a ragged length, once."""
    operands = _scan_operands(37)

    def both(fn):
        return jitted(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))),
            *operands)

    return operands, both, both(ssd_recurrent), jitted(
        ssd_recurrent, *operands)


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_scan_is_the_recurrence(scanned, chunk):
    """Forward and the gradients of all six operands, at two chunk sizes
    and a length (37) that is a multiple of neither."""
    operands, both, (want, want_grads), y = scanned
    got, got_grads = both(lambda *a: ssd(*a, chunk))
    np.testing.assert_allclose(
        jitted(lambda *a: ssd(*a, chunk), *operands), y, rtol=0, atol=5e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-4 * max(1.0, float(jnp.abs(w).max())))
    assert ssd_core(37, chunk) == {
        "core": "jnp", "chunk": chunk, "chunks": -(-37 // chunk)}


def test_no_exponent_in_the_scan_is_positive_and_its_state_is_float32():
    """A decay strong enough that ``exp(-Gs_j)`` alone would overflow
    float32 inside one chunk: the pairwise differences do not.  And every
    ``exp`` of the traced scan reads float32, as does the carried state."""
    x, dt, A, Bm, Cm, D = _scan_operands(32, seed=1)
    strong = dt * 40.0           # a chunk of 16 sums to some -600
    got = jitted(lambda *a: ssd(*a, 16), x, strong, A, Bm, Cm, D)
    want = jitted(ssd_recurrent, x, strong, A, Bm, Cm, D)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-5)
    half = [t.astype(jnp.bfloat16) for t in (x, Bm, Cm)]
    jaxpr = jax.make_jaxpr(lambda x, Bm, Cm: ssd(
        x, dt, A, Bm, Cm, D, 16))(*half)
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "exp":
                found.append(eqn.invars[0].aval.dtype)
            if eqn.primitive.name == "scan":
                found.extend(v.aval.dtype for v in eqn.outvars[:1])
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", None)
                if inner is not None:
                    walk(inner)
    walk(jaxpr.jaxpr)
    assert len(found) >= 5 and set(found) == {jnp.dtype("float32")}


def test_a_bfloat16_state_is_far_outside_float32_agreement():
    """The token-at-a-time body with its state rounded to bfloat16 after
    every update: hundreds of times the chunked scan's distance."""
    x, dt, A, Bm, Cm, D = _scan_operands(37)
    Bh, Ch = (jnp.repeat(t, 2, axis=2) for t in (Bm, Cm))

    def rounded(x, dt, A, Bm, Cm, D):
        def step(state, at):
            x_t, dt_t, b_t, c_t = at
            state = (jnp.exp(dt_t * A)[..., None, None] * state + jnp.einsum(
                "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t)).astype(
                    jnp.bfloat16).astype(jnp.float32)
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + (
                D[:, None] * x_t)
        _, y = jax.lax.scan(step, jnp.zeros((2, 4, 8, 16)), tuple(
            jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
        return jnp.moveaxis(y, 0, 1)

    want = jitted(ssd_recurrent, x, dt, A, Bm, Cm, D)
    low = jitted(rounded, x, dt, A, Bh, Ch, D)
    assert float(jnp.abs(low - want).max()) > 5e-3


# --------------------------------------------------------------------------
# the shares add up
# --------------------------------------------------------------------------

def _mamba_share(params, cfg, group):
    """One group's heads of a Mamba-2 layer (``tp`` over the groups: a
    chip's heads, its group's ``B`` and ``C``, its channels of the
    convolution and of the gated norm, its rows of ``W_out``)."""
    H, P, G, n = (cfg.mamba2_heads, cfg.mamba2_head_dim, cfg.mamba2_groups,
                  cfg.mamba2_state)
    inner, per = H * P, H // G
    chans = np.arange(group * per * P, (group + 1) * per * P)
    heads = np.arange(group * per, (group + 1) * per)
    conv = np.r_[chans, inner + group * n + np.arange(n),
                 inner + G * n + group * n + np.arange(n)]
    columns = np.r_[chans, inner + conv, 2 * inner + 2 * G * n + heads]
    return {
        "in_proj": {"kernel": params["in_proj"]["kernel"][:, columns]},
        "conv_weight": params["conv_weight"][:, conv],
        "conv_bias": params["conv_bias"][conv],
        "A_log": params["A_log"][heads], "dt_bias": params["dt_bias"][heads],
        "D": params["D"][heads], "norm_scale": params["norm_scale"][chans],
        "out_proj": {"kernel": params["out_proj"]["kernel"][chans]}}


class TestTheSharesAddUp:
    """One chip of ``tp`` holds some of a layer's heads, one of ``ep`` some
    of its experts: the parts all shares give, with what every chip
    computes alike counted once, add up to the uncut reference's layer."""

    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))

    @pytest.mark.parametrize("kind", ["mamba2", "attention", "latent_moe"])
    def test_shares_sum_to_the_uncut_layer(self, x, kind):
        getattr(self, "_" + kind)(x)

    def _mamba2(self, x):
        """Eight heads in four groups over four chips: a share is one
        group, its two heads, its own ``B`` and ``C`` and the one group of
        channels its gated norm runs over."""
        cfg = _config(mamba2_heads=8, mamba2_groups=4)
        full, _ = _init(Mamba2Mixer(cfg), x, None, None, seed=4)
        m = _published(cfg)
        whole = jitted(lambda p: reference.mamba2(x, p, m)[0], full)
        share_cfg = dataclasses.replace(cfg, mamba2_heads=2, mamba2_groups=1)
        parts = [jitted(lambda p: Mamba2Mixer(share_cfg).apply(
            {"params": p}, x, None, None), _mamba_share(full, cfg, group))
            for group in range(4)]
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        for part in parts:      # no share is the whole and none is nothing
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(whole).mean())

    def _attention(self, x):
        """Eight query heads on two key-value heads over four chips: a
        share is two query heads and the ONE key-value head they read (a
        key-value head is on two chips)."""
        cfg = _config(num_heads=8, num_kv_heads=2)
        full, _ = _init(Attention(cfg), x, None, jnp.tril(
            jnp.ones((SEQ, SEQ), bool))[None, None], seed=5)
        mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))[None, None]
        assert set(full) == {"q_proj", "k_proj", "v_proj", "o_proj"}
        whole = jitted(
            lambda p: reference.attention(x, p, _published(cfg)), full)
        share_cfg = dataclasses.replace(cfg, num_heads=2, num_kv_heads=1)
        parts = []
        for first in (0, 2, 4, 6):
            kv = first // 4
            share = {
                "q_proj": {"kernel": full["q_proj"]["kernel"][
                    :, first: first + 2]},
                "k_proj": {"kernel": full["k_proj"]["kernel"][:, kv: kv + 1]},
                "v_proj": {"kernel": full["v_proj"]["kernel"][:, kv: kv + 1]},
                "o_proj": {"kernel": full["o_proj"]["kernel"][
                    first: first + 2]}}
            parts.append(jitted(lambda p: Attention(share_cfg).apply(
                {"params": p}, x, None, mask), share))
        np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=2e-5)
        for part in parts:
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(whole).mean())

    def _latent_moe(self, x):
        """Four chips' shares of eight experts: every chip computes the
        router (all 8 columns, the bias), ``W_down``, ``W_up`` and the
        shared expert alike; ``W_up`` is linear, so the shares' parts
        through it add up, and the sum of the shares holds the shared
        expert four times: counted once, they sum to the uncut reference's
        block, and every share counts the same load."""
        cfg = _config(num_layers=1, layer_pattern=(), layer_suffix=())
        full, buffers = _init(MoEMLP(cfg), x, seed=6)
        bias = buffers["selection_bias"]
        m = _published(cfg)
        want, rows = jitted(
            lambda p: reference.latent_moe(x, p, bias, m), full)
        shared_p = full["shared_expert"]
        shared = jitted(lambda p: reference.relu2(
            x @ p["up_proj"]["kernel"]) @ p["down_proj"]["kernel"], shared_p)
        parts = []
        for first in (0, 2, 4, 6):
            share = dataclasses.replace(cfg, experts_held=2,
                                        first_expert=first)
            held = {**full, **{name: full[name][first: first + 2]
                               for name in ("up_proj", "down_proj")}}
            out, sown = jitted(lambda p: MoEMLP(share).apply(
                {"params": p, "buffers": buffers}, x,
                mutable=["losses", "stats", "buffers"]), held)
            alone = jitted(lambda p: reference.latent_moe(
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


def test_three_steps_move_the_bias_as_the_reference_does():
    """``Trainer``'s compiled step on one device: after each of three steps
    the state's bias is the reference's update of the bias before, from the
    reference's own routing of that step's parameters."""
    import optax

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.train import Trainer

    cfg = _config(experts_held=2)
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-3), mesh)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = {"input_ids": np.asarray(inputs), "labels": np.asarray(labels)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    m = _published(cfg)
    for _ in range(3):
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
