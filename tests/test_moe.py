"""The routed feed-forward block (``models/moe.py``) against its plain
reference (``models/olmoe_reference.py``), on one device and expert-parallel
on the 8-device CPU mesh, and through ``Trainer``'s default loss."""

import dataclasses
import functools
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.interpreters import partial_eval as pe

from dlrover_tpu.models import moe
from dlrover_tpu.models import olmoe_reference as reference
from dlrover_tpu.models.llama import Attention, LlamaConfig, LlamaForCausalLM
from dlrover_tpu.models.moe import (
    MoELlamaConfig, MoEMLP, ladder, local_experts)
from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer
from against_reference import init_params, jitted, perturbed, system

#: the model below as the reference reads it (published key names)
PUBLISHED = dict(num_hidden_layers=2, num_experts=8, num_experts_per_tok=3,
                 rms_norm_eps=1e-5, rope_theta=10000.0)


def _config(**kw):
    return MoELlamaConfig.tiny_moe(
        num_experts=8, top_k=3, qk_norm=True, dtype=jnp.float32, **kw)


def _batch(cfg, rows=8, seq=32, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(rows, seq + 1))
    return {"input_ids": np.asarray(ids[:, :-1], np.int32),
            "labels": np.asarray(ids[:, 1:], np.int32)}


def _perturbed(params, seed=2):
    return perturbed(params, seed, scale=0.05)


def _system(model, params, batch):
    """(total loss, token losses) and gradients as ``Trainer``'s
    default loss computes them."""
    (total, (token, _)), grads = system(
        model, params, batch["input_ids"], batch["labels"])
    return (total, token), grads


def _max_err(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.abs(x - y).max()), a, b)))


class TestAgainstReference:
    def test_forward_shapes_and_expert_axis(self):
        cfg = MoELlamaConfig.tiny_moe()
        model = LlamaForCausalLM(cfg)
        ids = jnp.zeros((2, 16), jnp.int32)
        params = init_params(model, ids, seed=0)
        assert jitted(model.apply, {"params": params}, ids).shape == (
            2, 16, cfg.vocab_size)
        mlp = params["layers"]["layer"]["mlp"]
        assert mlp["gate_proj"].shape == (
            cfg.num_layers, cfg.num_experts, cfg.hidden_size,
            cfg.intermediate_size)
        assert model.num_params() == sum(
            x.size for x in jax.tree.leaves(params))

    def test_losses_and_gradients_match_the_reference_in_float32(self):
        """Token losses, the total loss with both router terms, and the
        gradient of every leaf, to 1e-4 (measured 1e-6)."""
        cfg = _config()
        model = LlamaForCausalLM(cfg)
        batch = jax.tree.map(jnp.asarray, _batch(cfg, rows=2))
        params = _perturbed(init_params(model, batch["input_ids"]))
        (total, token), grads = _system(model, params, batch)
        want = jitted(lambda p: reference.forward(
            p, batch["input_ids"], batch["labels"], PUBLISHED), params)
        want_total, want_grads = jitted(jax.value_and_grad(
            lambda p: reference.total_loss(
                p, batch["input_ids"], batch["labels"], PUBLISHED,
                cfg.load_balance_coef, cfg.router_z_coef)), params)
        np.testing.assert_allclose(token, want["token_losses"], atol=1e-4)
        np.testing.assert_allclose(total, want_total, atol=1e-4)
        assert _max_err(grads, want_grads) < 1e-4
        # the router terms are in the total: it is not the cross entropy
        assert float(total) > float(token.mean()) + 1e-3

    def test_weights_are_the_softmax_weights_not_renormalised(self):
        """A renormalised top-k is another model: the reference with its
        kept weights divided by their sum is far from the system."""
        cfg = _config(num_layers=1)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.hidden_size))
        mlp = MoEMLP(cfg)
        params = _perturbed(init_params(mlp, x))
        got = jitted(lambda p: mlp.apply({"params": p}, x), params)
        want = jitted(lambda p: reference.experts(x, p, PUBLISHED)[0], params)
        with jax.default_matmul_precision("highest"):
            probs = jax.nn.softmax(x @ params["router"]["kernel"])
            kept = jax.lax.top_k(probs, cfg.top_k)[0].sum(-1, keepdims=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        assert float(jnp.abs(got - want / kept).max()) > 1e-2

    def test_at_most_k_experts_carry_weight(self):
        cfg = _config(num_layers=1)
        x = jax.random.normal(jax.random.PRNGKey(0), (64, cfg.hidden_size))
        logits = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits), cfg.top_k)
        w = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 64, 128)) * 0.1
        out, sizes, held = jax.jit(lambda *a: local_experts(*a, 0))(
            x, top_i, top_w, w[0], w[1], w[2].swapaxes(1, 2))
        assert int(sizes.sum()) == 64 * cfg.top_k == int(held)
        np.testing.assert_array_equal(
            sizes, np.bincount(np.asarray(top_i).ravel(), minlength=8))
        assert out.shape == x.shape and out.dtype == jnp.float32

    @pytest.mark.parametrize("experts", [4, 8, 16])
    def test_rows_scale_with_topk_not_with_experts(self, experts):
        """The grouped matmul is given tokens x top_k rows however many
        experts share them (what the capacity router's FLOP test held)."""
        cfg = MoELlamaConfig.tiny_moe(
            num_experts=experts, top_k=2, num_layers=1, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, cfg.hidden_size))
        mlp = MoEMLP(cfg)
        variables = jax.eval_shape(mlp.init, jax.random.PRNGKey(1), x)
        lowered = jax.jit(lambda v, x: mlp.apply(v, x)).lower(
            {"params": variables["params"]}, x).as_text()
        assert f"tensor<{2 * 64 * 2}x{cfg.hidden_size}xf32>" in lowered
        assert f"tensor<{2 * 64 * experts}x" not in lowered


def _forced_router(params, experts):
    """Router weights that send every token to ``experts`` (in that order
    of preference) whatever the token holds: a large bias on a constant
    feature cannot be had without a bias, so the kernel is made to read
    one input feature that the test holds at 1."""
    kernel = np.zeros(params["router"]["kernel"].shape, np.float32)
    for rank, e in enumerate(experts):
        kernel[0, e] = 20.0 - rank
    return {**params, "router": {"kernel": jnp.asarray(kernel)}}


class TestNoTokenIsLost:
    @pytest.mark.parametrize("ep,chosen", [
        (1, [5]), (4, [5]),                     # every token to one expert
        (1, [2, 3, 0]), (4, [2, 3, 0]),         # k experts, all of one rank
    ])
    def test_forced_routing_equals_the_reference(self, ep, chosen):
        """The whole batch on one expert (top_k 1), and on the top_k
        experts of one ``ep`` rank: every row is processed, none dropped,
        and the result is the reference's."""
        cfg = MoELlamaConfig.tiny_moe(
            num_experts=8, top_k=len(chosen), num_layers=1,
            dtype=jnp.float32)
        published = dict(PUBLISHED, num_experts_per_tok=len(chosen))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, cfg.hidden_size))
        x = x.at[..., 0].set(1.0)
        mlp = MoEMLP(cfg)
        params = _forced_router(_perturbed(init_params(mlp, x)), chosen)
        mesh = build_mesh(MeshConfig(dp=8 // ep, ep=ep))
        want = jitted(lambda p: reference.experts(x, p, published)[0], params)
        with jax.default_matmul_precision("highest"):
            with mesh:
                got, sown = jax.jit(lambda p, x: mlp.apply(
                    {"params": p}, x, mutable=["stats", "losses"]))(params, x)
        np.testing.assert_allclose(got, want, atol=1e-5)
        # all rows on len(chosen) of 8 experts
        np.testing.assert_allclose(
            sown["stats"]["load_max_over_mean"][0], 8 / len(chosen))


def _split_router(params, flagged, others):
    """Router weights that send the tokens whose second feature is 1 to
    the experts ``flagged`` and the rest to ``others`` (the first feature
    is held at 1, as for ``_forced_router``)."""
    kernel = np.zeros(params["router"]["kernel"].shape, np.float32)
    for rank, (e, o) in enumerate(zip(flagged, others)):
        kernel[1, e] = 40.0 - rank
        kernel[0, o] = 20.0 - rank
    return {**params, "router": {"kernel": jnp.asarray(kernel)}}


#: share of a source rank's assignments that the forced routing puts on
#: ``ep`` rank 0 -> index of the rung that rank's passes run at
RUNGS = [(1 / 4, 0), (1 / 3, 1), (1 / 2, 2), (1.0, 3)]


@functools.lru_cache(maxsize=None)
def _rung_inputs(seq, share):
    """(configuration, layer, parameters, tokens, flagged tokens): the
    first ``share`` of every sequence's ``seq`` tokens sent to experts 0
    and 1, the rest to 2 and 4."""
    cfg = MoELlamaConfig.tiny_moe(
        num_experts=8, top_k=2, num_layers=1, dtype=jnp.float32)
    flagged = int(seq * share)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, seq, cfg.hidden_size))
    x = x.at[..., 0].set(1.0).at[..., 1].set(
        (jnp.arange(seq) < flagged).astype(x.dtype))
    mlp = MoEMLP(cfg)
    params = _split_router(
        _perturbed(init_params(mlp, x[:1, :16])),
        flagged=[0, 1], others=[2, 4])
    return cfg, mlp, params, x, flagged


class TestEveryRung:
    """``ep=4``, 2 of 8 experts a rank, 1024 tokens a source rank with 2
    experts each: extents of 640, 768, 1024 and 2048 rows."""

    SEQ = 1024

    def _inputs(self, share):
        return _rung_inputs(self.SEQ, share)

    def test_the_ladder_of_these_shapes(self):
        assert ladder(2 * self.SEQ, 2, 8) == (640, 768, 1024, 2048)
        # every expert here, or a buffer of a few tiles: the worst case alone
        assert ladder(2 * self.SEQ, 8, 8) == (2 * self.SEQ,)
        assert ladder(64, 2, 8) == (64,)
        # the benchmark's cell
        assert ladder(8192 * 8, 16, 64) == (20480, 24576, 32768, 65536)

    @pytest.mark.parametrize("share,rung", RUNGS)
    def test_forced_routing_equals_the_reference(self, share, rung):
        """Each rank's passes run at the smallest extent that holds its
        rows, none is dropped, and the layer's result is the reference's;
        the two counters say which extents were taken."""
        cfg, mlp, params, x, flagged = self._inputs(share)
        published = dict(PUBLISHED, num_experts_per_tok=2)
        want = jitted(lambda p: reference.experts(x, p, published)[0], params)
        with jax.default_matmul_precision("highest"):
            with build_mesh(MeshConfig(dp=2, ep=4)):
                got, sown = jax.jit(lambda p, x: mlp.apply(
                    {"params": p}, x, mutable=["stats", "losses"]))(params, x)
        np.testing.assert_allclose(got, want, atol=1e-5)
        extents = ladder(2 * self.SEQ, 2, 8)
        # rows of one source rank on ep ranks 0 to 3
        live = np.array([2 * flagged, self.SEQ - flagged,
                         self.SEQ - flagged, 0])
        taken = [next(e for e in extents if e >= n) for n in live]
        assert taken[0] == extents[rung]
        np.testing.assert_allclose(
            sown["stats"]["rows_held_over_live"][0],
            sum(taken) / (2 * self.SEQ), rtol=1e-6)
        np.testing.assert_allclose(
            sown["stats"]["chip_rows_max_over_mean"][0],
            live.max() / live.mean(), rtol=1e-6)

    @pytest.mark.parametrize("share,rung", RUNGS)
    def test_result_and_gradients_equal_the_top_rung(self, share, rung):
        """One rank's share of the layer (experts 0 and 1 of 8) at the
        extent its routing picks against the same function at the worst
        case alone: the result, and the gradient of the tokens, of the
        weights and of every expert matrix, to 1e-6 of the largest
        entry."""
        cfg, mlp, params, x, _ = self._inputs(share)
        tokens = x[0]
        probs = jax.nn.softmax(tokens @ params["router"]["kernel"])
        top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
        top_w = top_w * jax.random.uniform(
            jax.random.PRNGKey(3), top_w.shape, minval=0.5, maxval=1.0)
        experts = [params[n][:2] for n in ("gate_proj", "up_proj",
                                           "down_proj")]

        def run(num_experts):
            def loss(tokens, top_w, *experts):
                out, rows, held = local_experts(
                    tokens, top_i, top_w, *experts, 0, num_experts)
                return jnp.sum(out * jnp.cos(out)), (out, rows, held)

            with jax.default_matmul_precision("highest"):
                return jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                        tokens, top_w, *experts)

        (_, (out, rows, held)), grads = run(8)
        (_, (top_out, top_rows, top_held)), top_grads = run(None)
        extents = ladder(2 * self.SEQ, 2, 8)
        assert float(held) == extents[rung] and float(top_held) == extents[-1]
        np.testing.assert_array_equal(rows, top_rows)
        for got, want in zip((out,) + grads, (top_out,) + top_grads):
            assert float(jnp.abs(want).max()) > 0
            assert float(jnp.abs(got - want).max()) <= 1e-6 * max(
                1.0, float(jnp.abs(want).max()))


#: one chip's share of a wide layer, routed by hand: (tokens, top_k,
#: experts, experts held, the body of the sums by token at each extent of
#: the ladder).  ``1of8``: ``top_k`` no power of two
SHARES = {
    "1of32": (2048, 8, 256, 8, ("rows", "rows", "rows", "slots")),
    "1of8": (2720, 3, 32, 4, ("rows", "slots", "slots", "slots")),
}
SHARE_RUNGS = [(share, rung) for share in SHARES for rung in range(4)]


@functools.lru_cache(maxsize=None)
def _share_inputs(share, rung):
    """(tokens, chosen experts, their weights, the held experts' three
    matrices, the ladder): the routing fills this chip's rows to a few
    under the ladder's extent ``rung``.  Token 0 has all its experts here
    and two equal weights (a tie), token 1 none, the rest one or two."""
    tokens, k, experts, held, _ = SHARES[share]
    extents = ladder(tokens * k, held, experts)
    rng = np.random.default_rng(rung)
    here = np.zeros(tokens, np.int64)
    here[0] = k
    here[2:] = 1 + np.arange(tokens - 2) % 2
    target = min(extents[rung], tokens * k // 2) - 5
    here[np.cumsum(here) > target] = 0
    assert here.sum() > ([0] + list(extents))[rung]
    top_i = np.stack([rng.permutation(np.concatenate([
        rng.choice(held, n, replace=False),
        held + rng.choice(experts - held, k - n, replace=False)]))
        for n in here]).astype(np.int32)
    top_w = rng.uniform(0.5, 1.0, size=top_i.shape).astype(np.float32)
    top_w[0, 1] = top_w[0, 0]
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (tokens, 64))
    gate_w, up_w = (0.2 * jax.random.normal(key, (held, 64, 128))
                    for key in keys[1:3])
    down_w = 0.2 * jax.random.normal(keys[3], (held, 128, 64))
    return (x, jnp.asarray(top_i), jnp.asarray(top_w),
            (gate_w, up_w, down_w), extents)


def _share_reference(x, top_i, top_w, gate_w, up_w, down_w):
    """Every held expert over every token, each result weighted where the
    token chose that expert: no sort, no rows, no sum by token."""
    hidden = nn.silu(jnp.einsum("td,edf->etf", x, gate_w)) * jnp.einsum(
        "td,edf->etf", x, up_w)
    chose = top_i[None] == jnp.arange(len(gate_w))[:, None, None]
    return jnp.einsum("etf,efd,et->td", hidden, down_w,
                      jnp.where(chose, top_w[None], 0).sum(axis=-1))


def _value_and_grads(fn, x, top_w, experts):
    def loss(x, top_w, *experts):
        out = fn(x, top_w, *experts)
        return jnp.sum(out * jnp.cos(out)), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, top_w, *experts)
    return (out,) + grads


class TestTheSumsByTokenOverRows:
    """Where a pass holds few rows beside the slots it serves the sums by
    token run over the rows (``moe._sum_by_token``): the same function as
    the gather of every slot, at every extent of a share's ladder."""

    @pytest.mark.parametrize("share,rung", SHARE_RUNGS)
    def test_a_share_equals_the_slots_body_and_the_reference(
            self, monkeypatch, share, rung):
        """The result and the gradient of the tokens, of the weights and
        of every expert matrix: to 2e-6 of the largest entry against the
        same pass with every sum over the slots, to 1e-5 against the dense
        reference; the extent is the one the routing asks for and the body
        the one the shapes ask for."""
        tokens, k, experts, held, bodies = SHARES[share]
        x, top_i, top_w, expert_w, extents = _share_inputs(share, rung)
        taken = []

        def system(x, top_w, *expert_w):
            out, _, at = local_experts(
                x, top_i, top_w, *expert_w, 0, experts)
            taken.append(at)
            return out

        assert tuple(moe._combine_body(e, tokens * k)
                     for e in extents) == bodies
        got = _value_and_grads(system, x, top_w, expert_w)
        asked = []
        monkeypatch.setattr(
            moe, "_combine_body",
            lambda extent, slots: asked.append(extent) or "slots")
        by_slots = _value_and_grads(system, x, top_w, expert_w)
        assert set(asked) == set(extents)
        want = _value_and_grads(
            lambda x, top_w, *expert_w: _share_reference(
                x, top_i, top_w, *expert_w), x, top_w, expert_w)
        for a, b, c in zip(got, by_slots, want):
            scale = max(1.0, float(jnp.abs(c).max()))
            assert float(jnp.abs(c).max()) > 0
            assert float(jnp.abs(a - b).max()) <= 2e-6 * scale
            assert float(jnp.abs(a - c).max()) <= 1e-5 * scale
        # token 1 has no expert here, token 0 all of its own
        assert float(jnp.abs(got[0][1]).max()) == 0
        assert float(jnp.abs(got[0][0]).max()) > 0

    @pytest.mark.parametrize("weighted", [True, False])
    def test_a_row_without_an_assignment_is_never_read(self, weighted):
        """The rows behind the last group are undefined after a grouped
        matmul: NaN planted there does not reach a token's sum, with
        weights (the forward sum) or without (the backward one)."""
        tokens, k, experts, held, bodies = SHARES["1of32"]
        _, top_i, top_w, _, extents = _share_inputs("1of32", 0)
        assert bodies[0] == "rows"
        mine = np.asarray(top_i) < held
        key = np.where(mine, top_i, held).reshape(-1)
        order = np.argsort(key, kind="stable")
        inverse = np.argsort(order)
        used = int(mine.sum())
        rows = np.random.default_rng(0).normal(
            size=(extents[0], 64)).astype(np.float32)
        want = np.zeros((tokens, 64), np.float32)
        scale = np.where(mine, top_w, 0).astype(np.float32)
        for row, assignment in enumerate(order[:used]):
            want[assignment // k] += rows[row] * (
                scale.reshape(-1)[assignment] if weighted else 1.0)
        rows[used:] = np.nan
        got = jax.jit(moe._sum_by_token)(
            jnp.asarray(rows), jnp.asarray(order[:extents[0]], jnp.int32),
            jnp.asarray(inverse.reshape(tokens, k), jnp.int32),
            (jnp.arange(extents[0]) < used)[:, None],
            *([jnp.asarray(scale)] if weighted else []))
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_the_body_of_every_extent_is_in_the_path(self, monkeypatch):
        """``moe.path``'s ``combine=`` from the shapes alone: the slots at
        the benchmark's ``ep=4`` shapes and on every ladder's last rung,
        the rows at the first extents of a share of 1/32 (16 of 512
        experts at 16,384 tokens x 2560; 10 of 320 at 8,192 x 4096) and
        at the first extent alone of a share of 1/8 (16 of 128 at 2048
        wide: 5/32 of its slots, the next extent 6/32)."""
        from dlrover_tpu.observability import trace

        records = []
        monkeypatch.setattr(
            trace, "note_trace_time",
            lambda name, **attrs: records.append((name, attrs)))

        def path(mesh_cfg, batch, seq, **fields):
            cfg = MoELlamaConfig.tiny_moe(
                num_layers=1, top_k=8, dtype=jnp.bfloat16, **fields)
            mlp = MoEMLP(cfg)
            x = jax.ShapeDtypeStruct((batch, seq, cfg.hidden_size),
                                     jnp.bfloat16)
            del records[:]
            with _mesh(mesh_cfg):
                variables = jax.eval_shape(
                    mlp.init, jax.random.PRNGKey(0), x)
                jax.eval_shape(mlp.apply, variables, x)
            (attrs,) = {tuple(sorted(attrs.items()))
                        for name, attrs in records if name == "moe.path"}
            return dict(attrs)

        olmoe = path(MeshConfig(ep=4), 8, 4096, hidden_size=2048,
                     intermediate_size=1024, num_experts=64)
        assert olmoe["extents"] == (20480, 24576, 32768, 65536)
        assert olmoe["combine"] == "slots,slots,slots,slots"
        ling = path(MeshConfig(dp=1), 1, 16384, hidden_size=2560,
                    intermediate_size=768, num_experts=512, experts_held=16)
        assert ling["extents"] == (5120, 6144, 8192, 131072)
        assert ling["combine"] == "rows,rows,rows,slots"
        solar = path(MeshConfig(dp=1), 1, 8192, hidden_size=4096,
                     intermediate_size=1280, num_experts=320,
                     experts_held=10)
        assert solar["extents"] == (2560, 3072, 4096, 65536)
        assert solar["combine"] == "rows,rows,rows,slots"
        for tokens in (8192, 16384):        # Keye's cell, SDAR's
            eighth = path(MeshConfig(dp=1), 1, tokens, hidden_size=2048,
                          intermediate_size=768, num_experts=128,
                          experts_held=16)
            assert eighth["extents"][0] == tokens * 8 * 5 // 32
            assert eighth["combine"] == "rows,slots,slots,slots"
        # every expert on the one chip: one rung, the slots
        assert path(MeshConfig(dp=1), 1, 64, num_experts=8)[
            "combine"] == "slots"


def _plain_rung(extent, x, weights, order, inverse, sizes, gate_w, up_w,
                down_w):
    """A pass over the sorted rows as the parent of PR 46 wrote it, with
    nothing between it and autodiff: the reference every pull-back of
    ``models/moe.py`` is held to (its weights' gradient is ``sum(g *
    product)``, from the down matmul run again)."""
    slot = inverse.reshape(weights.shape)
    live = (jnp.arange(extent) < sizes.sum())[:, None]
    rows = jnp.where(live, x[order[:extent] // slot.shape[1]], 0)

    def grouped(rows, expert_w):
        return jax.lax.ragged_dot(rows, expert_w, group_sizes=sizes,
                                  preferred_element_type=x.dtype)

    hidden = nn.silu(grouped(rows, gate_w)) * grouped(rows, up_w)
    out = jnp.where(live, grouped(hidden, down_w), 0)
    mine = out.at[slot].get(mode="fill", fill_value=0)
    return jnp.einsum("tkd,tk->td", mine, weights,
                      preferred_element_type=jnp.float32)


def _grouped_matmuls(jaxpr):
    """``ragged_dot`` and its transposes in a jaxpr and every jaxpr inside
    it."""
    found = sum(e.primitive.name.startswith("ragged_dot")
                for e in jaxpr.eqns)
    return found + sum(_grouped_matmuls(j) for j in _sub_jaxprs(jaxpr))


#: what a layer of each kind is: (configuration's fields, mesh)
LAYERS_THAT_KEEP = {
    "ep1_share": (dict(experts_held=2), MeshConfig(dp=1)),
    "ep1_share_shared_expert": (
        dict(experts_held=2, shared_experts=1), MeshConfig(dp=1)),
    "ep4": ({}, MeshConfig(dp=2, ep=4)),
}


class TestTheBackwardTakesTheProductsFromTheForward:
    """One rematerialised layer (``x + experts(x)`` under the policy of
    ``models/llama.py::_layer_class``) over the forced routings of
    ``TestEveryRung``: on the ladder's first rung the backward pass pulls
    back from the two products the forward pass kept, on a higher rung
    through ``jax.vjp`` of the rung."""

    def _layer(self, kind, share):
        fields, mesh_cfg = LAYERS_THAT_KEEP[kind]
        cfg, _, params, x, _ = TestEveryRung()._inputs(share)
        cfg = dataclasses.replace(cfg, router_z_coef=0.0, **fields)
        mlp = MoEMLP(cfg)
        if cfg.experts_held:
            params = {**params, **{
                name: params[name][:cfg.experts_held]
                for name in ("gate_proj", "up_proj", "down_proj")}}
        if cfg.shared_experts:
            params = {**params, "shared_expert": _perturbed(init_params(
                mlp, x[:1, :16], seed=5))["shared_expert"]}
        if mesh_cfg.ep == 1:
            x = x[:1]

        @functools.partial(jax.checkpoint, policy=kept.LAYER_POLICY)
        def layer(params, x):
            return x + mlp.apply({"params": params}, x)

        def loss(params, x):
            out = layer(params, x)
            return jnp.sum(out * jnp.cos(out))

        return loss, params, x, _mesh(mesh_cfg)

    @pytest.fixture(scope="class", params=list(LAYERS_THAT_KEEP))
    def switches(self, request):
        """The ``switch``es of the gradient's jaxpr that hold a grouped
        matmul, what nothing reads removed: ``jax.vjp`` of a half traces
        that half's forward products too, and the compiler drops them as
        this does."""
        loss, params, x, mesh = self._layer(request.param, 1 / 4)
        with mesh:
            jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
                params, x).jaxpr
        jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        found = [[_grouped_matmuls(b.jaxpr) for b in e.params["branches"]]
                 for e in _conds(jaxpr)]
        assert _grouped_matmuls(jaxpr) == sum(map(sum, found))
        return [counts for counts in found if any(counts)]

    @pytest.mark.parametrize("rung", range(4))
    def test_grouped_matmuls_of_a_rung(self, switches, rung):
        """Nine on the first rung, three forward and six backward: no
        forward matmul runs a second time, under the layer's ``remat`` or
        under ``ep``'s loop over source ranks.  Eleven on a higher one:
        gate and up run again inside ``jax.vjp``; down never does
        (``_down_and_sum``'s pull-back asks for no product)."""
        forward, backward = switches    # and no third: nothing recomputed
        assert forward[rung] == 3
        assert backward[rung] == (8 if rung else 6)

    def test_a_ladder_of_one_rung_runs_the_same_nine_and_no_switch(self):
        """Every expert on the one chip: ``jax.lax.switch`` over one branch
        is a call, and the same rules keep the same products."""
        cfg, mlp, params, x, _ = TestEveryRung()._inputs(1 / 4)

        @functools.partial(jax.checkpoint, policy=kept.LAYER_POLICY)
        def layer(params, x):
            return x + mlp.apply({"params": params}, x)

        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda params, x: jnp.sum(jnp.sin(layer(params, x))),
            argnums=(0, 1)))(params, x[:1]).jaxpr
        jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        assert _conds(jaxpr) == [] and _grouped_matmuls(jaxpr) == 9

    @pytest.mark.parametrize("share,rung", RUNGS)
    @pytest.mark.parametrize("kind", list(LAYERS_THAT_KEEP))
    def test_loss_and_gradients_equal_the_plain_pull_back(
            self, monkeypatch, kind, share, rung):
        loss, params, x, mesh = self._layer(kind, share)

        def run():
            with mesh, jax.default_matmul_precision("highest"):
                return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
                    params, x)

        got = run()
        monkeypatch.setattr(
            moe, "_at_rung",
            lambda extents, activation, rung, *args: _plain_rung(
                extents[-1], *args))
        want = run()
        got = jax.tree_util.tree_leaves_with_path(got)
        want = jax.tree.leaves(want)
        assert len(got) == len(want) >= 6
        for (path, a), b in zip(got, want):
            # a forced router's softmax is flat: its gradient may be none
            assert "router" in str(path) or float(jnp.abs(b).max()) > 0
            assert float(jnp.abs(a - b).max()) <= 2e-6 * max(
                1.0, float(jnp.abs(b).max())), path


def _sub_jaxprs(jaxpr):
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield inner


def _conds(jaxpr):
    """Every ``cond`` of a jaxpr, those inside its inner jaxprs too."""
    found = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    for inner in _sub_jaxprs(jaxpr):
        found += _conds(inner)
    return found


def _avals(jaxpr):
    found = [v.aval for e in jaxpr.eqns for v in e.outvars]
    found += [v.aval for v in jaxpr.invars]
    for inner in _sub_jaxprs(jaxpr):
        found += _avals(inner)
    return found


class TestExtentsInTheLoweredStep:
    TOKENS = 192  # a source rank's; 3 experts each

    def _step_jaxpr(self, mesh_cfg):
        cfg = _config(max_seq_len=self.TOKENS)
        _, trainer = _trainer(cfg, mesh_cfg)
        batch = _batch(cfg, seq=self.TOKENS)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            return cfg, jax.make_jaxpr(trainer._loss_and_grads)(
                state.params, trainer.shard_batch(batch)).jaxpr

    def test_small_rungs_hold_no_buffer_of_the_worst_case(self):
        """``ep=4``, 576 assignments a source rank at 3 a token, 2 experts
        a rank: extents of 256 and 384 rows (the worst case is 2 a token: a
        token meets an expert once), one ``switch`` in the forward pass and
        one in the backward pass.  Inside the two small rungs the rows and the
        experts' hidden rows have the rung's extent; what has the extent
        of all assignments is an index or weight vector ([rows] or
        [tokens, k]) or the gathered operand of a sum by token ([tokens,
        k, hidden]): no [rows, hidden], nothing of the experts' width."""
        cfg, jaxpr = self._step_jaxpr(MeshConfig(dp=2, ep=4))
        tokens, k = self.TOKENS, cfg.top_k
        rows, hidden, width = tokens * k, cfg.hidden_size, cfg.intermediate_size
        extents = ladder(rows, 2, cfg.num_experts, k)
        assert extents == (256, 384)
        assert ladder(rows, 2, cfg.num_experts) == (256, 384, 576)
        switches = [e for e in _conds(jaxpr)
                    if len(e.params["branches"]) == len(extents)]
        assert len(switches) == 2
        allowed = {(rows,), (tokens, k), (tokens, k, 1), (tokens, k, hidden)}
        for switch in switches:
            *small, top = [
                {a.shape for a in _avals(b.jaxpr) if hasattr(a, "shape")}
                for b in switch.params["branches"]]
            for extent, shapes in zip(extents, small):
                assert {(extent, hidden), (extent, width)} <= shapes
                whole = {s for s in shapes
                         if rows in s or s[:2] == (tokens, k)}
                assert whole <= allowed, whole - allowed
            assert {(extents[-1], hidden), (extents[-1], width)} <= top

    def test_one_rank_of_experts_has_no_conditional(self):
        """``ep=1``: every assignment is local, the rows in use are the
        buffer, and the step holds no ``cond`` at all."""
        _, jaxpr = self._step_jaxpr(MeshConfig(dp=2))
        assert _conds(jaxpr) == []
        _, jaxpr = self._step_jaxpr(MeshConfig(dp=1))
        assert _conds(jaxpr) == []


def _mesh(mesh_cfg):
    sizes = [getattr(mesh_cfg, a) for a in ("dp", "fsdp", "tp", "cp", "ep")]
    return build_mesh(mesh_cfg, devices=jax.devices()[:int(np.prod(sizes))])


def _trainer(cfg, mesh_cfg):
    model = LlamaForCausalLM(cfg)
    return model, Trainer(model, optax.sgd(1.0), _mesh(mesh_cfg))


class TestExpertParallel:
    @pytest.fixture(scope="class")
    def one_device(self):
        cfg = _config()
        model, trainer = _trainer(cfg, MeshConfig(dp=1))
        batch = _batch(cfg)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        state = state.replace(params=_perturbed(state.params))
        with trainer.mesh:
            (loss, stats), grads = jax.jit(trainer._loss_and_grads)(
                state.params, trainer.shard_batch(batch))
        load = stats["layers"]["layer"]["mlp"]["load_max_over_mean"][0]
        return cfg, batch, jax.device_get(nn.meta.unbox(state.params)), (
            float(loss), jax.device_get(nn.meta.unbox(grads))), (
            jax.device_get(load))

    @pytest.mark.parametrize("mesh_cfg", [
        MeshConfig(dp=2, ep=4), MeshConfig(dp=2, ep=2, fsdp=2),
    ], ids=["ep4_dp2", "ep2_dp2_fsdp2"])
    def test_loss_and_gradients_equal_one_device(self, one_device, mesh_cfg):
        """Experts over ``ep``, the batch over ``ep`` and the other data
        axes: the same loss (router terms over the whole batch) and the
        same gradients as on one device, and the same count of the rows
        of every expert."""
        cfg, batch, params, (want_loss, want_grads), want_load = one_device
        model, trainer = _trainer(cfg, mesh_cfg)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        placed = jax.tree.map(
            lambda leaf, new: jax.device_put(new, leaf.sharding),
            nn.meta.unbox(state.params), params)
        gate = placed["layers"]["layer"]["mlp"]["gate_proj"]
        assert gate.sharding.spec[1] == "ep"
        sharded = trainer.shard_batch(batch)
        assert "ep" in sharded["input_ids"].sharding.spec[0]
        with trainer.mesh, nn.logical_axis_rules(trainer.rules), \
                jax.default_matmul_precision("highest"):
            (loss, stats), grads = jax.jit(trainer._loss_and_grads)(
                placed, sharded)
        np.testing.assert_allclose(float(loss), want_loss, atol=1e-5)
        assert _max_err(jax.device_get(grads), want_grads) < 1e-4
        np.testing.assert_allclose(
            stats["layers"]["layer"]["mlp"]["load_max_over_mean"][0],
            want_load, rtol=1e-6)

    @pytest.mark.slow
    def test_ep_tp_dp_training_loss_decreases(self):
        cfg = MoELlamaConfig.tiny_moe()
        model = LlamaForCausalLM(cfg)
        trainer = Trainer(model, optax.adamw(1e-2),
                          build_mesh(MeshConfig(dp=2, tp=2, ep=2)))
        batch = _batch(cfg, seq=16)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        losses = []
        for _ in range(6):
            state, m = trainer.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_ep4_bfloat16_training_with_remat_and_scan(self):
        """The default dtype, ``remat`` and the scanned stack under ep=4."""
        cfg = MoELlamaConfig.tiny_moe(num_experts=8, qk_norm=True)
        model, trainer = _trainer(cfg, MeshConfig(dp=2, ep=4))
        batch = _batch(cfg, seq=16)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        losses = []
        for _ in range(4):
            state, m = trainer.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]


class TestDefaultLoss:
    def test_trainer_without_loss_fn_differentiates_the_router_terms(self):
        """``Trainer(model, opt, mesh)``: the loss is cross entropy plus
        what the layers sow, and the router gets the terms' gradient: with
        the coefficients at 0 its gradient is another."""
        batch = _batch(_config())
        grads, losses = {}, {}
        for name, coefs in (("on", {}), ("off", dict(
                load_balance_coef=0.0, router_z_coef=0.0))):
            cfg = _config(**coefs)
            model, trainer = _trainer(cfg, MeshConfig(dp=1))
            state = trainer.create_state(
                jax.random.PRNGKey(0), batch["input_ids"])
            with trainer.mesh:
                (losses[name], _), grads[name] = jax.jit(
                    trainer._loss_and_grads)(
                        state.params, trainer.shard_batch(batch))
        router = lambda g: nn.meta.unbox(g)[  # noqa: E731
            "layers"]["layer"]["mlp"]["router"]["kernel"]
        assert float(losses["on"]) > float(losses["off"]) + 1e-3
        assert float(jnp.abs(router(grads["on"]) - router(
            grads["off"])).max()) > 1e-6

    def test_step_reports_stats_and_records_them_on_the_cadence(
            self, monkeypatch):
        """The step's metrics carry what the model sowed; every
        DIGEST_EVERY steps the trainer records the values kept at the tick
        before as a ``trainer.model_stats`` span, never the step in
        flight."""
        from dlrover_tpu.observability import flight_recorder

        monkeypatch.setenv("DLROVER_TPU_DIGEST_EVERY", "2")
        cfg = _config()
        model, trainer = _trainer(cfg, MeshConfig(dp=1))
        batch = _batch(cfg)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        for _ in range(7):
            state, metrics = trainer.train_step(state, batch)
            float(metrics["loss"])  # a loop that logs: the step has ended
        mlp = metrics["stats"]["layers"]["layer"]["mlp"]
        assert mlp["load_max_over_mean"][0].shape == (cfg.num_layers,)
        spans = [s for s in flight_recorder.recorder().spans
                 if s.name == "trainer.model_stats"]
        assert spans, "no trainer.model_stats span after three ticks"
        attrs = spans[-1].attrs
        assert len(attrs["load_max_over_mean"]) == cfg.num_layers
        assert all(v >= 1.0 for v in attrs["load_max_over_mean"])
        assert attrs["step"] % 2 == 0
        # every expert on the one chip: the passes ran over the rows in
        # use and no chip holds more than another
        assert attrs["rows_held_over_live"] == [1.0] * cfg.num_layers
        assert attrs["chip_rows_max_over_mean"] == [1.0] * cfg.num_layers

    def test_dense_model_step_has_no_stats_and_the_same_loss(self):
        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        model = LlamaForCausalLM(cfg)
        trainer = Trainer(model, optax.sgd(0.1), _mesh(MeshConfig(dp=1)))
        batch = _batch(cfg)
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        from dlrover_tpu.trainer.train import cross_entropy_loss

        want = jax.jit(lambda p: cross_entropy_loss(
            model.apply({"params": p}, batch["input_ids"]),
            batch["labels"]))(state.params)
        state, metrics = trainer.train_step(state, batch)
        assert set(metrics) == {"loss", "grad_norm"}
        np.testing.assert_allclose(metrics["loss"], want, rtol=1e-6)


class TestBalanceLoss:
    @pytest.mark.parametrize("coef", [1.0, 0.0])
    def test_descending_the_balance_term_evens_the_loads(self, coef):
        """Tokens that share a direction prefer the same experts (what
        untrained weights make of random tokens: the benchmark's cell
        reads 4 to 8 times the mean from its first step).  Gradient
        descent on the load-balancing term alone takes the largest load
        from 3 times the mean to near it and the term to its floor of 1;
        with the coefficient at 0 the router has no gradient and nothing
        moves."""
        cfg = MoELlamaConfig.tiny_moe(
            num_experts=8, top_k=2, num_layers=1, dtype=jnp.float32,
            load_balance_coef=coef, router_z_coef=0.0)
        kx, kc, kp = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (4, 64, cfg.hidden_size)) + (
            1.5 * jax.random.normal(kc, (cfg.hidden_size,)))
        mlp = MoEMLP(cfg)
        params = nn.meta.unbox(mlp.init(kp, x)["params"])

        def term(p):
            _, sown = mlp.apply(
                {"params": p}, x, mutable=["losses", "stats"])
            return (sum(jnp.sum(t) for t in jax.tree.leaves(sown["losses"])),
                    sown["stats"]["load_max_over_mean"][0])

        @jax.jit
        def descend(p):
            (value, load), grads = jax.value_and_grad(term, has_aux=True)(p)
            router = p["router"]["kernel"] - 0.05 * grads["router"]["kernel"]
            return {**p, "router": {"kernel": router}}, value, load

        values, loads = [], []
        for _ in range(13):
            params, value, load = descend(params)
            values.append(float(value))
            loads.append(float(load))
        assert loads[0] > 2.5
        if coef:
            assert loads[-1] < 1.3 and abs(values[-1] - 1.0) < 0.05
            assert values[-1] < values[0]
        else:
            assert values == [0.0] * 13 and loads == [loads[0]] * 13


class TestQKNorm:
    def test_off_is_the_attention_without_it_bit_for_bit(self):
        """``qk_norm=False`` (every model but OLMoE): no norm parameters,
        and the block's result is the projections, RoPE, the reference
        core and the output projection, to the bit."""
        from dlrover_tpu.models.llama import _rope
        from dlrover_tpu.ops.attention import reference_attention

        cfg = LlamaConfig.tiny()
        x = jax.random.normal(
            jax.random.PRNGKey(0), (2, 16, cfg.hidden_size), cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
        mask = jnp.tril(jnp.ones((16, 16), bool))[None, None]
        attn = Attention(cfg)
        params = init_params(attn, x, positions, mask)
        assert set(params) == {"q_proj", "k_proj", "v_proj", "o_proj"}
        got = attn.apply({"params": params}, x, positions, mask)

        def proj(name, t, axis=-1):
            return nn.DenseGeneral(
                features=params[name]["kernel"].shape[
                    (1 if axis == -1 else 2):],
                axis=axis, use_bias=False, dtype=cfg.dtype,
            ).apply({"params": params[name]}, t)

        q, k, v = (proj(n, x) for n in ("q_proj", "k_proj", "v_proj"))
        out = reference_attention(
            _rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v, mask)
        want = proj("o_proj", out, axis=(-2, -1))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_on_normalises_over_the_whole_projected_width(self):
        cfg = LlamaConfig.tiny(qk_norm=True, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.hidden_size))
        positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
        mask = jnp.tril(jnp.ones((16, 16), bool))[None, None]
        attn = Attention(cfg)
        params = _perturbed(init_params(attn, x, positions, mask))
        assert params["q_norm"]["scale"].shape == (
            cfg.num_heads * cfg.head_dim,)
        assert params["k_norm"]["scale"].shape == (
            cfg.num_kv_heads * cfg.head_dim,)
        got = jitted(lambda p: attn.apply({"params": p}, x, positions, mask),
                     params)
        want = jitted(lambda p: reference.attention(x, p, PUBLISHED), params)
        np.testing.assert_allclose(got, want, atol=1e-5)


class TestFlashCheckpoint:
    def test_expert_sharded_state_round_trips_and_reshards(self, tmp_path):
        """State whose largest leaves are split on the expert axis: saved
        under ep=4, it restores under ep=4 and under ep=2."""
        from dlrover_tpu.common.multi_process import SharedMemoryBuffer
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer, StorageType)
        from dlrover_tpu.trainer.flash_checkpoint.engine import shm_name

        cfg = MoELlamaConfig.tiny_moe(num_experts=8, qk_norm=True)
        batch = _batch(cfg, seq=16)

        def make(mesh_cfg):
            model = LlamaForCausalLM(cfg)
            trainer = Trainer(model, optax.adamw(1e-2), build_mesh(mesh_cfg))
            return trainer, trainer.create_state(
                jax.random.PRNGKey(0), batch["input_ids"])

        trainer, state = make(MeshConfig(dp=2, ep=4))
        state, _ = trainer.train_step(state, batch)
        scope = f"t{uuid.uuid4().hex[:8]}"
        ckpt = Checkpointer(str(tmp_path), scope=scope)
        try:
            ckpt.save_checkpoint(7, state, StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=120)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state), trainer.state_shardings)
        finally:
            ckpt.close()
        assert step == 7
        want = jax.device_get(jax.tree.leaves(state))
        for got, leaf in zip(jax.tree.leaves(restored), want):
            np.testing.assert_array_equal(np.asarray(got), leaf)
        SharedMemoryBuffer(shm_name(0, scope)).unlink()

        trainer2, state2 = make(MeshConfig(dp=4, ep=2))
        ckpt2 = Checkpointer(str(tmp_path), scope=f"t{uuid.uuid4().hex[:8]}")
        try:
            restored, step = ckpt2.load_checkpoint(
                jax.eval_shape(lambda s: s, state2), trainer2.state_shardings)
        finally:
            ckpt2.close()
        assert step == 7
        gate = nn.meta.unbox(restored.params)["layers"]["layer"]["mlp"][
            "gate_proj"]
        assert gate.sharding.spec[1] == "ep"
        assert gate.sharding.mesh.shape["ep"] == 2
        for got, leaf in zip(jax.tree.leaves(restored), want):
            np.testing.assert_array_equal(np.asarray(got), leaf)
        state2, metrics = trainer2.train_step(restored, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestSigmoidRouterAndSharedExpert:
    """``router_scores="sigmoid"``, ``routed_scaling_factor`` and
    ``shared_experts`` (DeepSeek-V3's router; Solar-Open2's) against
    ``models/solar_open2_reference.py::experts``, which loops over the
    experts plainly."""

    M = {"num_experts_per_tok": 3, "experts_total": 8, "first_expert": 0,
         "routed_scaling_factor": 1.0}

    def _layer(self, x, **kw):
        cfg = _config(router_scores="sigmoid", norm_topk_prob=True,
                      shared_experts=1, router_z_coef=0.0, num_layers=1, **kw)
        params = _perturbed(init_params(MoEMLP(cfg), x, seed=4), seed=5)
        return cfg, params

    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))

    @pytest.mark.parametrize("scale", [1.0, 2.5])
    def test_result_and_balance_loss_equal_the_reference(self, x, scale):
        from dlrover_tpu.models import solar_open2_reference

        cfg, params = self._layer(x, routed_scaling_factor=scale)
        assert set(params) == {"router", "gate_proj", "up_proj", "down_proj",
                               "shared_expert"}
        got, sown = jitted(lambda p: MoEMLP(cfg).apply(
            {"params": p}, x, mutable=["losses", "stats"]), params)
        want, balance, _ = jitted(lambda p: solar_open2_reference.experts(
            x, p, {**self.M, "routed_scaling_factor": scale}, whole=True),
            params)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
        np.testing.assert_allclose(
            sown["losses"]["load_balance"][0] / cfg.load_balance_coef,
            balance, rtol=1e-5)
        assert float(sown["losses"]["router_z"][0]) == 0.0

    def test_gradients_equal_the_references(self, x):
        from dlrover_tpu.models import solar_open2_reference

        cfg, params = self._layer(x)
        weights = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(
            x.shape)

        def system(p, h):
            return jnp.sum(MoEMLP(cfg).apply({"params": p}, h) * weights)

        def plain(p, h):
            return jnp.sum(solar_open2_reference.experts(
                h, p, self.M, whole=True)[0] * weights)

        got = jitted(jax.grad(system, argnums=(0, 1)), params, x)
        want = jitted(jax.grad(plain, argnums=(0, 1)), params, x)
        assert _max_err(got, want) < 2e-4
        # the shared expert and the router both get a gradient
        assert float(jnp.abs(
            got[0]["shared_expert"]["down_proj"]["kernel"]).max()) > 1e-3
        assert float(jnp.abs(got[0]["router"]["kernel"]).max()) > 1e-4

    def test_sigmoid_scores_are_not_softmax_weights(self, x):
        """The same parameters under the other scoring give another
        result: the kept weights differ even where the kept experts are
        the same."""
        cfg, params = self._layer(x)
        import dataclasses

        other = dataclasses.replace(cfg, router_scores="softmax")
        got = jitted(lambda p: MoEMLP(cfg).apply({"params": p}, x), params)
        soft = jitted(lambda p: MoEMLP(other).apply({"params": p}, x), params)
        assert float(jnp.abs(got - soft).max()) > 1e-2

    def test_the_shared_expert_is_added_once_and_unweighted(self, x):
        """The layer less its routed part is the shared SwiGLU of the
        input, whatever the router says."""
        import dataclasses

        from dlrover_tpu.models.llama import MLP

        cfg, params = self._layer(x)
        without = dataclasses.replace(cfg, shared_experts=0)
        routed = {k: v for k, v in params.items() if k != "shared_expert"}
        both = jitted(lambda p: MoEMLP(cfg).apply({"params": p}, x), params)
        alone = jitted(lambda p: MoEMLP(without).apply({"params": p}, x),
                       routed)
        shared = jitted(lambda p: MLP(dataclasses.replace(
            cfg, intermediate_size=cfg.shared_width())).apply(
                {"params": p}, x), params["shared_expert"])
        np.testing.assert_allclose(both - alone, shared, rtol=0, atol=2e-5)
        assert cfg.shared_width() == cfg.intermediate_size

    def test_what_the_config_refuses_and_counts(self):
        with pytest.raises(ValueError, match="router_scores"):
            _config(router_scores="tanh")
        plain = _config()
        two = _config(shared_experts=2, shared_intermediate_size=48)
        assert two.shared_width() == 96 and plain.shared_width() == 0
        assert two.feed_forward_params() - plain.feed_forward_params() == (
            3 * 64 * 96)
