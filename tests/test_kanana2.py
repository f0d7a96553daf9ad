"""Kanana-2-30B-A3B's language model as the program runs it
(``models/llama.py`` with ONE kind all the way down, ``mla:dense`` once and
then ``mla``, rotary by interleaved pairs, no gate; ``models/moe.py``
choosing the plain top-k of sigmoid scores under a selection bias beside two
shared experts) against its plain reference
(``models/kanana2_reference.py``) on the CPU in float32: token losses, the
loss the step minimises, the gradients of every parameter, the bias after
the step.  Rotary by pairs against the complex-number statement of it and
against halves on de-interleaved columns; rotary by halves on the same
weights FAILS the comparison.  **The shares add up**: the routed block's
results of all eight shares, with what every chip computes alike (the
attention, the router, the two shared experts) counted once, equal the
uncut reference's layer.  ``n_group`` 1 is no groups; the bias moves with
the load and is saved and restored with the state; the name map of a
``deepseek_v3``-layout checkpoint at the tiny size."""

import collections
import dataclasses
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import kanana2_reference as reference
from dlrover_tpu.models.llama import (
    LatentAttention,
    LlamaForCausalLM,
    _rope,
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

PREFIX, PATTERN = ("mla:dense",), ("mla",)
SEQ = 48
EXPERTS = 16


def _config(**changes):
    fields = dict(
        num_layers=3, layer_prefix=PREFIX, layer_pattern=PATTERN,
        dense_intermediate_size=96, intermediate_size=32, num_heads=4,
        num_kv_heads=4, rope_theta=1e6, rms_norm_eps=1e-6,
        mla_kv_rank=24, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
        mla_head_gate=False, mla_rope_interleave=True,
        num_experts=EXPERTS, top_k=3, norm_topk_prob=True,
        router_scores="sigmoid", routed_scaling_factor=2.448,
        shared_experts=2, shared_intermediate_size=32, selection_bias=True,
        bias_update_rate=0.001, load_balance_coef=0.0, router_z_coef=0.0,
        dtype=jnp.float32)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "layer_prefix": cfg.layer_prefix,
            "layer_pattern": cfg.layer_pattern,
            "kv_lora_rank": cfg.mla_kv_rank,
            "qk_nope_head_dim": cfg.mla_nope_dim,
            "num_attention_heads": cfg.num_heads,
            "num_experts_per_tok": cfg.top_k, "experts_total": cfg.num_experts,
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


def _token_losses(model, params, buffers, inputs, labels):
    logits = model.apply({"params": params, "buffers": buffers}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


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


@pytest.fixture(scope="module")
def made():
    """A share: experts 8 to 11 of 16 (the whole layer's experts are
    ``TestTheSharesAddUp``'s)."""
    cfg = _config(experts_held=4, first_expert=8)
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
        buffers["layers"]["mla_0"]["layer"]["mlp"]["selection_bias"])[:, 0]


def _as_checkpoint(params, buffers, m):
    """The program's tree (unboxed) as a flat ``{checkpoint name: array}``
    in the ``deepseek_v3`` layout (``torch.nn.Linear`` weights ``[out,
    in]``; an expert a module)."""
    heads = params["lm_head"]["kernel"]
    out = {"model.embed_tokens.weight": params["embed_tokens"],
           "model.norm.weight": params["final_norm"]["scale"],
           "lm_head.weight": heads.T}
    layers = reference.layers_of(params, buffers, m)
    for i, (entry, p, b) in enumerate(layers):
        at, a = f"model.layers.{i}.", p["attn"]
        hidden = a["q_proj"]["kernel"].shape[0]
        out.update({
            at + "input_layernorm.weight": p["input_norm"]["scale"],
            at + "post_attention_layernorm.weight":
                p["post_attn_norm"]["scale"],
            at + "self_attn.q_proj.weight":
                a["q_proj"]["kernel"].reshape(hidden, -1).T,
            at + "self_attn.kv_a_proj_with_mqa.weight":
                a["kv_a_proj"]["kernel"].T,
            at + "self_attn.kv_a_layernorm.weight": a["kv_a_norm"]["scale"],
            at + "self_attn.kv_b_proj.weight":
                a["kv_b_proj"]["kernel"].reshape(
                    a["kv_b_proj"]["kernel"].shape[0], -1).T,
            at + "self_attn.o_proj.weight":
                a["o_proj"]["kernel"].reshape(-1, hidden).T})
        mlp = p["mlp"]
        if entry.endswith(":dense"):
            out.update({at + f"mlp.{name}.weight": mlp[name]["kernel"].T
                        for name in ("gate_proj", "up_proj", "down_proj")})
            continue
        out[at + "mlp.gate.weight"] = mlp["router"]["kernel"].T
        out[at + "mlp.gate.e_score_correction_bias"] = (
            b["mlp"]["selection_bias"])
        out.update({
            at + f"mlp.shared_experts.{name}.weight":
                mlp["shared_expert"][name]["kernel"].T
            for name in ("gate_proj", "up_proj", "down_proj")})
        first = int(m["first_expert"])
        for e in range(mlp["gate_proj"].shape[0]):
            out.update({
                at + f"mlp.experts.{first + e}.{name}.weight": mlp[name][e].T
                for name in ("gate_proj", "up_proj", "down_proj")})
    return out


class TestAgainstReference:
    def test_the_stack_is_one_kind_all_the_way_down(self, made):
        assert set(made.params["prefix"]) == {"mla_dense_0"}
        assert set(made.params["layers"]) == {"mla_0"}
        attn = made.params["layers"]["mla_0"]["layer"]["attn"]
        # no gate, no query bottleneck; [periods, run, ...]
        assert set(attn) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                             "o_proj"}
        assert attn["q_proj"]["kernel"].shape == (2, 1, 64, 4, 24)
        mlp = made.params["layers"]["mla_0"]["layer"]["mlp"]
        # two shared experts: ONE SwiGLU of twice an expert's width
        assert mlp["shared_expert"]["gate_proj"]["kernel"].shape[-1] == 64
        assert mlp["gate_proj"].shape == (2, 1, 4, 64, 32)
        assert "buffers" not in made.params and set(made.buffers) == {"layers"}
        assert made.model.num_params() == sum(
            leaf.size for leaf in jax.tree.leaves(made.params))

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
        assert len(flat) == 27

    def test_the_bias_after_the_step_is_the_references(self, made):
        (_, (_, sown)), _ = made.got
        rows = np.asarray(made.want["rows"])
        assert rows.shape == (2, EXPERTS) and rows.sum() == 2 * 2 * SEQ * 3
        want = np.stack([
            reference.bias_update(b, n, made.cfg.bias_update_rate)
            for b, n in zip(_bias_by_layer(made.buffers), rows)])
        np.testing.assert_array_equal(_bias_by_layer(sown["buffers"]), want)
        moved = np.abs(want - _bias_by_layer(made.buffers))
        assert np.allclose(moved[rows != rows.mean(axis=1, keepdims=True)],
                           made.cfg.bias_update_rate, atol=1e-7)

    @pytest.mark.parametrize("changes", [
        {"mla_rope_interleave": False}, {"routed_scaling_factor": 1.0},
        {"shared_experts": 1, "shared_intermediate_size": 64}],
        ids=["rotary_by_halves", "no_scaling_factor",
             "one_shared_expert_of_64_is_the_same"])
    def test_a_departure_is_far_outside_float32_agreement(self, made, changes):
        """Rotary by halves on the weights laid out for pairs is another
        model (the acceptance criterion); and two shared experts of 32 ARE
        one of 64: only their width is in the tree."""
        other = LlamaForCausalLM(dataclasses.replace(made.cfg, **changes))
        token = jitted(
            lambda p: _token_losses(other, p, made.buffers, made.inputs,
                                    made.labels), made.params)
        err = float(jnp.abs(token - made.want["token_losses"]).max())
        assert (err < 2e-5) if "shared_experts" in changes else (err > 1e-2)

    def test_the_name_map_of_a_deepseek_v3_checkpoint(self, made):
        """docs/migration.md's table as code: the program's tree written
        under a ``deepseek_v3`` checkpoint's names (``torch.nn.Linear``
        weights ``[out, in]``, an expert a module, the bias a buffer of the
        gate) has that layout's shapes, and read back through the map it is
        the tree the reference computes the same losses from; the rotary
        columns go through as stored."""
        m = _published(made.cfg)
        named = _as_checkpoint(made.params, made.buffers, m)
        at = "model.layers.1."
        shapes = {name[len(at):]: tuple(t.shape) for name, t in named.items()
                  if name.startswith(at) and ".experts." not in name}
        assert shapes == {
            "input_layernorm.weight": (64,),
            "post_attention_layernorm.weight": (64,),
            "self_attn.q_proj.weight": (4 * 24, 64),
            "self_attn.kv_a_proj_with_mqa.weight": (24 + 8, 64),
            "self_attn.kv_a_layernorm.weight": (24,),
            "self_attn.kv_b_proj.weight": (4 * 32, 24),
            "self_attn.o_proj.weight": (64, 4 * 16),
            "mlp.gate.weight": (EXPERTS, 64),
            "mlp.gate.e_score_correction_bias": (EXPERTS,),
            "mlp.shared_experts.gate_proj.weight": (64, 64),
            "mlp.shared_experts.up_proj.weight": (64, 64),
            "mlp.shared_experts.down_proj.weight": (64, 64)}
        # this chip's experts under their numbers in the whole layer
        assert {name.split(".")[5] for name in named
                if name.startswith(at + "mlp.experts.")} == {
                    "8", "9", "10", "11"}
        assert named[at + "mlp.experts.9.down_proj.weight"].shape == (64, 32)
        assert "model.layers.0.mlp.gate.weight" not in named    # dense
        assert named["model.layers.0.mlp.gate_proj.weight"].shape == (96, 64)
        assert named["lm_head.weight"].shape == (256, 64)
        params, buffers = reference.from_checkpoint_names(
            named, made.params, made.buffers, m)
        for want, got in ((made.params, params), (made.buffers, buffers)):
            assert jax.tree.structure(want) == jax.tree.structure(got)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_array_equal(w, g)
        # a head's rotary columns are the LAST rope columns of its 24 rows
        # of q_proj.weight, in the stored order
        q = np.asarray(named[at + "self_attn.q_proj.weight"]).reshape(
            4, 24, 64)
        np.testing.assert_array_equal(
            q[2, 16:], np.asarray(made.params["layers"]["mla_0"]["layer"][
                "attn"]["q_proj"]["kernel"])[0, 0, :, 2, 16:].T)


class TestRotaryByPairs:
    D = 8

    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 3, self.D))

    @pytest.fixture(scope="class")
    def positions(self):
        return jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))

    def test_against_the_complex_number_statement(self, x, positions):
        """``z_i = x[2i] + j x[2i+1]`` times ``exp(j p theta^(-2i/D))``; the
        program leaves pair ``i``'s real part at ``i`` and its imaginary
        part at ``i + D/2``, the reference leaves both in place."""
        theta = 1e6
        z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
        angle = np.arange(SEQ)[:, None] * theta ** (
            -np.arange(0, self.D, 2) / self.D)
        turned = z * np.exp(1j * angle)[None, :, None, :]
        got = _rope(x, positions, theta, interleave=True)
        np.testing.assert_allclose(
            got, np.concatenate([turned.real, turned.imag], -1), atol=1e-5)
        in_place = reference.rope_pairs(x, theta)
        np.testing.assert_allclose(in_place[..., 0::2], turned.real, atol=1e-5)
        np.testing.assert_allclose(in_place[..., 1::2], turned.imag, atol=1e-5)

    def test_against_halves_on_de_interleaved_columns(self, x, positions):
        apart = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        np.testing.assert_array_equal(
            _rope(x, positions, 1e6, interleave=True),
            _rope(apart, positions, 1e6))
        # and not the halves' rotation of the columns as they stand
        assert float(jnp.abs(_rope(x, positions, 1e6, interleave=True)
                             - _rope(x, positions, 1e6)).max()) > 0.1

    def test_a_score_does_not_see_the_layout(self, x, positions):
        """``q . k`` after pairs in place (the reference) and after pairs
        moved to halves (the program): one permutation of both operands."""
        q, k = x, x[:, :, :1] * 0.5 + 1.0
        program = jnp.einsum(
            "bqhd,bkd->bhqk", _rope(q, positions, 1e6, interleave=True),
            _rope(k, positions, 1e6, interleave=True)[:, :, 0])
        plain = jnp.einsum(
            "bqhd,bkd->bhqk", reference.rope_pairs(q, 1e6),
            reference.rope_pairs(k, 1e6)[:, :, 0])
        np.testing.assert_allclose(program, plain, atol=1e-4)

    def test_off_is_the_program_it_was(self, x, positions):
        """The new argument at its default traces to the same jaxpr as a
        call that does not name it (Ling-3.0's latent layer, every rotary
        cell): digests equal, and another under pairs."""
        text = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
            lambda t: _rope(t, positions, 6e6, **kw))(x))
        assert text() == text(interleave=False) != text(interleave=True)

    def test_the_record_says_which(self, x, positions, monkeypatch):
        from dlrover_tpu.observability import trace

        records = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: records.append((name, attrs)))
        h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
        for pairs, word in ((True, "pairs"), (False, "halves")):
            module = LatentAttention(_config(mla_rope_interleave=pairs))
            jax.eval_shape(module.init, jax.random.PRNGKey(0), h, positions,
                           None)
            assert records[-1] == ("attention.path", dict(
                blocks=None, exact="reference", impl="latent", seq=SEQ,
                heads=4, qk="16+8", v=16, rope=word))

    def test_the_record_is_kept_when_the_ring_has_turned(
            self, positions, monkeypatch):
        """A step is traced once and the recorder's ring rotates: the
        reading stays in ``trace.trace_time_notes`` for the run, once
        however many layers and traces made it."""
        from dlrover_tpu.observability import flight_recorder, trace

        monkeypatch.setattr(trace, "_trace_time_notes", {})
        monkeypatch.setattr(trace, "_noted_without_span", set())
        h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
        module = LatentAttention(_config(mla_rope_interleave=True))
        for _ in range(2):
            jax.eval_shape(module.init, jax.random.PRNGKey(0), h, positions,
                           None)
        ring = flight_recorder.recorder().spans
        assert any(s.name == "attention.path" for s in ring)
        for _ in range(ring.maxlen):
            with trace.span("trainer.step"):
                pass
        assert not any(s.name == "attention.path" for s in ring)
        (kept,) = trace.trace_time_notes("attention.path")
        assert kept["impl"] == "latent" and kept["rope"] == "pairs"
        kept["rope"] = "halves"             # a copy: the record stays
        assert trace.trace_time_notes("attention.path")[0]["rope"] == "pairs"
        assert trace.trace_time_notes("moe.path") == []


class TestTheRouter:
    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))

    def test_one_group_is_no_groups(self, x):
        """The published ``n_group`` 1 / ``topk_group`` 1 and the family's
        ``n_group`` 0 choose the same experts at the same weights: either
        reading of the file is the published one."""
        none = _config(num_layers=1, layer_prefix=(), layer_pattern=())
        one = dataclasses.replace(none, n_group=1, topk_group=1)
        params, buffers = _init(MoEMLP(none), x, seed=6)

        def block(cfg):
            return jitted(lambda p: MoEMLP(cfg).apply(
                {"params": p, "buffers": buffers}, x,
                mutable=["stats", "buffers", "losses"]), params)

        (out_none, sown_none), (out_one, sown_one) = block(none), block(one)
        np.testing.assert_array_equal(out_none, out_one)
        np.testing.assert_array_equal(
            sown_none["buffers"]["selection_bias"],
            sown_one["buffers"]["selection_bias"])
        # no pass over groups ran: its counter is not sown
        assert "group_dropped_share" not in sown_one["stats"]
        want, _ = jitted(lambda p: reference.experts(
            x, p, buffers["selection_bias"], _published(none)), params)
        np.testing.assert_allclose(out_one, want, rtol=0, atol=2e-5)

    def test_the_path_names_the_shared_experts(self, x, monkeypatch):
        from dlrover_tpu.observability import trace

        records = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: records.append((name, attrs)))
        cfg = _config(num_layers=1, layer_prefix=(), layer_pattern=())
        jax.eval_shape(MoEMLP(cfg).init, jax.random.PRNGKey(0), x)
        (path,) = {tuple(sorted(attrs.items())) for name, attrs in records
                   if name == "moe.path"}
        assert dict(path)["shared_experts"] == 2
        assert dict(path)["shared_width"] == 64


class TestTheSharesAddUp:
    """One chip of ``ep`` = 8 holds two of a layer's sixteen experts; every
    chip computes the attention, the router (all 16 columns and the bias)
    and the two shared experts alike: the parts all shares give, with those
    counted once, add up to the uncut reference's layer."""

    def test_eight_expert_shares_the_shared_swiglu_and_the_attention(self):
        cfg = _config(num_layers=1, layer_prefix=(), layer_pattern=())
        x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
        positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
        attn, _ = _init(LatentAttention(cfg), x, positions, None, seed=5)
        full, buffers = _init(MoEMLP(cfg), x, seed=6)
        bias = buffers["selection_bias"]
        m = _published(cfg)
        ones = jnp.ones((64,))

        def uncut(attn, full):
            """The reference's layer: ``x + MLA(norm x)``, then the block
            of all sixteen experts on the normed stream."""
            stream = x + reference.latent_attention(
                reference.rms_norm(x, ones, 1e-6), attn, m)
            h = reference.rms_norm(stream, ones, 1e-6)
            out, rows = reference.experts(h, full, bias, m)
            return stream, h, stream + out, rows, reference.swiglu(
                h, full["shared_expert"])

        stream, h, want, rows, shared = jitted(uncut, attn, full)
        # what every chip computes alike: the program's attention, once
        mixed = jitted(lambda p: LatentAttention(cfg).apply(
            {"params": p}, reference.rms_norm(x, ones, 1e-6), positions,
            None), attn)
        np.testing.assert_allclose(x + mixed, stream, rtol=0, atol=2e-5)
        parts = []
        for first in range(0, EXPERTS, 2):
            share = dataclasses.replace(cfg, experts_held=2,
                                        first_expert=first)
            held = {**full, **{name: full[name][first: first + 2] for name in
                               ("gate_proj", "up_proj", "down_proj")}}
            out, sown = jitted(lambda p: MoEMLP(share).apply(
                {"params": p, "buffers": buffers}, h,
                mutable=["losses", "stats", "buffers"]), held)
            # every share moves the bias alike: by the load of all columns
            np.testing.assert_array_equal(
                sown["buffers"]["selection_bias"],
                reference.bias_update(bias, rows, cfg.bias_update_rate))
            parts.append(out)
        np.testing.assert_allclose(
            x + mixed + sum(parts) - 7 * shared, want, rtol=0, atol=1e-4)
        # no share is the whole and the shared SwiGLU is not nothing
        assert float(jnp.abs(shared).mean()) > 0.05 * float(
            jnp.abs(want - stream).mean())
        assert all(float(jnp.abs(p - shared).mean()) > 0 for p in parts)


def test_the_bias_moves_with_the_load_and_resumes_with_the_state(tmp_path):
    """``Trainer``'s compiled step on one device: after a step the state's
    bias is the reference's update of the bias before, from the reference's
    own routing of that step's parameters; a memory save holds it, and the
    restored state takes the same next step to the same loss, parameters and
    bias, bit for bit."""
    cfg = _config()
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-2), mesh)
    inputs, labels = inputs_and_labels(2, SEQ)
    batch = {"input_ids": np.asarray(inputs), "labels": np.asarray(labels)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    assert not any(np.any(np.asarray(b)) for b in jax.tree.leaves(state.buffers))
    # mu and nu of parameters alone: the optimizer holds no moment for it
    moments = [x for x in jax.tree.leaves(state.opt_state) if x.ndim]
    assert len(moments) == 2 * len(jax.tree.leaves(state.params))
    m = _published(cfg)
    for _ in range(2):
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
        2 * cfg.bias_update_rate)
    ckpt = Checkpointer(str(tmp_path), scope=f"t{uuid.uuid4().hex[:8]}")
    try:
        ckpt.save_checkpoint(2, state, StorageType.MEMORY)
        # the save holds copies: the step below donates the live state
        restored, step = ckpt.load_checkpoint(
            jax.eval_shape(lambda s: s, state), trainer.state_shardings)
    finally:
        ckpt.close()
    assert step == 2
    np.testing.assert_array_equal(
        _bias_by_layer(state.buffers), _bias_by_layer(restored.buffers))
    went_on, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    resumed, again = trainer.train_step(restored, trainer.shard_batch(batch))
    assert float(metrics["loss"]) == float(again["loss"])
    for x, y in zip(jax.tree.leaves(went_on), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
