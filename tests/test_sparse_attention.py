"""Attention over the keys a learned indexer selects
(``ops/attention.py::indexed_sparse_attention``), per-head q/k norm,
renormalised router weights and one chip's share of an expert layer
(``models/moe.py``), against the plain reference
(``models/keye_reference.py``) at small sizes on the CPU in float32, and
through ``Trainer``."""

import collections
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import keye_reference as reference
from dlrover_tpu.models.llama import Attention, LlamaConfig, LlamaForCausalLM
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP
from dlrover_tpu.ops import attention as ops
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer
from against_reference import (
    init_params,
    inputs_and_labels,
    jitted,
    perturbed,
    reference_loss_and_gradients,
    system,
    system_loss,
)

SEQ, TOPK, BLOCK = 32, 8, 8


def _config(**kw):
    fields = dict(
        num_experts=8, top_k=3, experts_held=2, first_expert=4,
        norm_topk_prob=True, qk_norm="head", dtype=jnp.float32,
        load_balance_coef=0.001, router_z_coef=0.0, index_topk=TOPK,
        index_heads=2, index_head_dim=8, index_block=BLOCK, max_seq_len=SEQ)
    fields.update(kw)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg):
    """``cfg`` as the reference reads it (published key names)."""
    return {
        "num_experts_per_tok": cfg.top_k, "experts_total": cfg.num_experts,
        "first_expert": cfg.first_expert, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta, "query_block": 16,
        "router_aux_loss_coef": cfg.load_balance_coef,
        "sa_config": {"topk": cfg.index_topk}}


def _batch(cfg, rows=2, seed=0):
    inputs, labels = inputs_and_labels(rows, SEQ, cfg.vocab_size, seed)
    return {"input_ids": inputs, "labels": labels}


def _params(model, batch):
    return perturbed(init_params(model, batch["input_ids"]), scale=0.05)


def _reference(cfg, params, batch, **kw):
    m = _published(cfg)
    return lambda p: reference.forward(
        p, batch["input_ids"], batch["labels"], m, **kw)


#: what the fixture computed once: the system's ``((total, (token losses,
#: sown)), gradients)``, the reference's dictionary and gradients
Made = collections.namedtuple(
    "Made", "cfg model batch params got want want_grads")


@pytest.fixture(scope="module")
def made():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    batch = _batch(cfg)
    params = _params(model, batch)
    got = system(model, params, batch["input_ids"], batch["labels"])
    return Made(cfg, model, batch, params, got, *reference_loss_and_gradients(
        _reference(cfg, params, batch), params))


INDEXER = ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")


class TestAgainstReference:
    def test_losses_and_every_sown_term(self, made):
        cfg, want = made.cfg, made.want
        (total, (token, sown)), _ = made.got
        terms = sown["losses"]["layers"]["layer"]
        stats = sown["stats"]["layers"]["layer"]
        np.testing.assert_allclose(token, want["token_losses"], atol=2e-5)
        np.testing.assert_allclose(total, want["loss"], atol=2e-5)
        layers = cfg.num_layers
        np.testing.assert_allclose(
            terms["attn"]["index"][0] * layers, want["index_loss"], atol=1e-5)
        np.testing.assert_allclose(
            stats["attn"]["index_loss"][0], want["index_loss"], atol=1e-5)
        np.testing.assert_allclose(
            terms["mlp"]["load_balance"][0] * layers / cfg.load_balance_coef,
            want["load_balance"], rtol=1e-5)
        np.testing.assert_allclose(
            stats["attn"]["index_low_margin_share"][0],
            want["index_low_margin"], atol=1e-6)
        # the selection bites: 24 of 32 queries have more keys than they keep
        assert float(want["index_loss"].min()) > 1e-3

    def test_gradients_of_every_parameter(self, made):
        _, got = made.got
        worst = jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), got, made.want_grads)
        assert max(jax.tree.leaves(worst)) < 5e-5, worst
        attn = got["layers"]["layer"]["attn"]
        for name in INDEXER:        # and none of them is a gradient of zero
            assert max(float(jnp.abs(g).max())
                       for g in jax.tree.leaves(attn[name])) > 1e-4, name

    def test_which_loss_reaches_which_parameter(self, made):
        """The indexer's parameters get no gradient from the language
        model's loss, and ``L_I`` gives one to them and to nothing else."""
        model, batch, params = made.model, made.batch, made.params
        _, lm = system(model, params, batch["input_ids"], batch["labels"],
                       terms=lambda path: "index" not in path)
        _, index = system(model, params, batch["input_ids"],
                          terms=lambda path: "index" in path)

        def largest(tree):
            return max(float(jnp.abs(g).max()) for g in jax.tree.leaves(tree))

        attn_lm = lm["layers"]["layer"]["attn"]
        attn_index = index["layers"]["layer"]["attn"]
        for name in INDEXER:
            assert largest(attn_lm[name]) == 0.0, name
            assert largest(attn_index[name]) > 1e-4, name
        others = {**index, "layers": {"layer": {
            **index["layers"]["layer"],
            "attn": {k: v for k, v in attn_index.items()
                     if k not in INDEXER}}}}
        assert largest(others) == 0.0
        assert largest({k: v for k, v in attn_lm.items()
                        if k not in INDEXER}) > 1e-4

    def test_the_nearest_keys_in_place_of_the_highest_are_far_off(self, made):
        """The fault the chip's control plants: visible at this size at a
        hundred times the float32 agreement above."""
        wrong = jitted(_reference(
            made.cfg, made.params, made.batch, nearest=True), made.params)
        assert float(jnp.abs(
            made.want["token_losses"] - wrong["token_losses"]).max()) > 2e-3


class TestSelection:
    def _reference(self, scores, topk):
        return reference.select(scores, 0, topk)

    def test_a_planted_selection_ties_included(self):
        """Scores forced so that the kept set is known: query ``t`` keeps
        the three highest of its keys; where keys tie at the threshold,
        the earlier."""
        n = 8
        scores = jnp.asarray([[
            [9, 0, 0, 0, 0, 0, 0, 0],      # t=0: one key
            [1, 2, 0, 0, 0, 0, 0, 0],      # t=1: two keys, both kept
            [1, 2, 3, 0, 0, 0, 0, 0],      # t=2: three keys, all kept
            [5, 1, 5, 5, 0, 0, 0, 0],      # t=3: 5, 5, 5
            [2, 2, 2, 2, 2, 0, 0, 0],      # t=4: all tie: the first three
            [1, 7, 3, 3, 3, 9, 0, 0],      # t=5: 9, 7 and the first 3
            [-1, -5, -1, -1, -2, -9, -1, 0],   # t=6: the first three -1
            [0., -0., 4, -3, 0, 8, 0, 0],  # t=7: 8, 4, the first zero (+0)
        ]], jnp.float32)
        causal = jnp.tril(jnp.ones((n, n), bool))[None]
        keep, low = ops.select_top_keys(scores, causal, 3)
        want = np.zeros((n, n), bool)
        for t, kept in enumerate([[0], [0, 1], [0, 1, 2], [0, 2, 3],
                                  [0, 1, 2], [5, 1, 2], [0, 2, 3],
                                  [5, 2, 0]]):
            want[t, kept] = True
        np.testing.assert_array_equal(np.asarray(keep[0]), want)
        # -0. sorts below +0., as the bit pattern and ``lax.top_k`` have it
        ref_keep, ref_low = self._reference(scores, 3)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))
        np.testing.assert_array_equal(np.asarray(low), np.asarray(ref_low))
        # a low margin: rows 4 to 7 (ties, or 3 against 3), never rows
        # that keep every key
        np.testing.assert_array_equal(
            np.asarray(low[0]), [0, 0, 0, 0, 1, 1, 1, 1])

    @pytest.mark.parametrize("levels", [0, 3, 17])
    def test_the_same_set_as_top_k_on_the_whole_row(self, levels):
        """Random rows, real-valued or drawn from a few levels (many ties):
        the threshold found by counting keeps what ``jax.lax.top_k`` keeps."""
        rng = np.random.default_rng(levels)
        n, topk = 64, 11
        scores = rng.normal(size=(2, n, n)).astype(np.float32)
        if levels:
            scores = np.round(scores * levels / 4) * 0.5
        causal = jnp.tril(jnp.ones((n, n), bool))[None]
        keep, low = ops.select_top_keys(jnp.asarray(scores), causal, topk)
        ref_keep, ref_low = self._reference(jnp.asarray(scores), topk)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))
        np.testing.assert_array_equal(np.asarray(low), np.asarray(ref_low))
        kept = np.asarray(keep).sum(-1)
        np.testing.assert_array_equal(
            kept, np.broadcast_to(np.minimum(np.arange(n) + 1, topk), (2, n)))

    def test_blocks_see_the_same_keys_as_the_whole_sequence(self, made):
        """The result does not depend on the block of queries worked at a
        time (``q_chunk_size`` is a tile size, not mathematics)."""
        cfg, batch, params = made.cfg, made.batch, made.params
        token = []
        for block in (4, 16, 32):
            other = LlamaForCausalLM(
                dataclasses.replace(cfg, index_block=block))
            token.append(system_loss(
                other, params, batch["input_ids"], batch["labels"])[1][0])
        np.testing.assert_allclose(token[0], token[1], atol=2e-6)
        np.testing.assert_allclose(token[0], token[2], atol=2e-6)


class TestAgainstTheDenseLayer:
    def test_topk_at_least_seq_is_the_dense_causal_layer(self):
        """With every key kept the layer equals the attention the tree
        has: same q, k, v and o parameters, ``reference_attention``."""
        sparse = LlamaConfig.tiny(
            dtype=jnp.float32, qk_norm="head", index_topk=SEQ, index_heads=2,
            index_head_dim=8, index_block=BLOCK)
        dense = dataclasses.replace(sparse, index_topk=0)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64))
        positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
        mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))[None, None]
        params = perturbed(init_params(
            Attention(sparse), x, positions, None), scale=0.05)
        shared = {k: v for k, v in params.items() if k not in INDEXER}
        got, sown = jitted(lambda p: Attention(sparse).apply(
            {"params": p}, x, positions, None, mutable=["losses", "stats"]),
            params)
        want = jitted(lambda p: Attention(dense).apply(
            {"params": p}, x, positions, mask), shared)
        np.testing.assert_allclose(got, want, atol=2e-6)
        assert float(sown["stats"]["index_low_margin_share"][0]) == 0.0

    def test_per_head_norm_against_a_hand_written_one(self):
        cfg = LlamaConfig.tiny(dtype=jnp.float32, qk_norm="head")
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
        positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
        mask = jnp.tril(jnp.ones((16, 16), bool))[None, None]
        params = perturbed(init_params(
            Attention(cfg), x, positions, mask), scale=0.05)
        assert params["q_norm"]["scale"].shape == (cfg.head_dim,)
        assert params["k_norm"]["scale"].shape == (cfg.head_dim,)

        def by_hand(t, scale):
            t = np.asarray(t, np.float64)
            rms = np.sqrt((t ** 2).mean(-1, keepdims=True) + cfg.rms_norm_eps)
            return t / rms * np.asarray(scale, np.float64)

        got = jitted(lambda p: Attention(cfg).apply(
            {"params": p}, x, positions, mask), params)
        with jax.default_matmul_precision("highest"):
            q = by_hand(jnp.einsum("bse,ehd->bshd", x,
                                   params["q_proj"]["kernel"]),
                        params["q_norm"]["scale"])
            k = by_hand(jnp.einsum("bse,ehd->bshd", x,
                                   params["k_proj"]["kernel"]),
                        params["k_norm"]["scale"])
            v = jnp.einsum("bse,ehd->bshd", x, params["v_proj"]["kernel"])
            q, k = (reference.rope(jnp.asarray(t, jnp.float32), cfg.rope_theta)
                    for t in (q, k))
            want = jnp.einsum(
                "bshd,hde->bse", ops.reference_attention(q, k, v, mask),
                params["o_proj"]["kernel"])
        np.testing.assert_allclose(got, want, atol=1e-5)
        # OLMoE's norm over the whole width is another layer
        whole = dataclasses.replace(cfg, qk_norm=True)
        assert jax.eval_shape(
            Attention(whole).init, jax.random.PRNGKey(1), x, positions, mask
        )["params"]["q_norm"]["scale"].value.shape == (
            cfg.num_heads * cfg.head_dim,)

    def test_what_the_config_refuses(self):
        with pytest.raises(ValueError, match="qk_norm"):
            LlamaConfig.tiny(qk_norm="heads")
        with pytest.raises(ValueError, match="index_heads"):
            LlamaConfig.tiny(index_topk=8)
        with pytest.raises(ValueError, match="not among"):
            MoELlamaConfig.tiny_moe(num_experts=8, experts_held=4,
                                    first_expert=6)
        with pytest.raises(ValueError, match="multiple of the block"):
            ops.indexed_sparse_attention(
                *(jnp.zeros((1, 12, 2, 4)),) * 4, jnp.zeros((1, 12, 4)),
                jnp.zeros((1, 12, 2)), topk=4, block=8)


class TestAShareOfTheExpertLayer:
    def _layer(self, held, first, x, full):
        cfg = _config(experts_held=held, first_expert=first, num_layers=1)
        params = {
            "router": full["router"],
            **{name: full[name][first: first + held]
               for name in ("gate_proj", "up_proj", "down_proj")}}
        out, sown = jitted(lambda p: MoEMLP(cfg).apply(
            {"params": p}, x, mutable=["losses", "stats"]), params)
        return cfg, out, sown

    @pytest.fixture(scope="class")
    def whole(self):
        cfg = _config(experts_held=0, num_layers=1)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
        full = perturbed(init_params(MoEMLP(cfg), x, seed=4), scale=0.05)
        return cfg, x, full

    def test_the_shares_add_up_to_the_uncut_layer(self, whole):
        """Four chips' shares of eight experts (``first_expert`` 0, 2, 4,
        6) sum to what the uncut reference gives for the whole layer."""
        cfg, x, full = whole
        m = _published(cfg)
        want, balance, _ = jitted(
            lambda p: reference.experts(x, p, m, whole=True), full)
        parts = [self._layer(2, first, x, full) for first in (0, 2, 4, 6)]
        np.testing.assert_allclose(
            sum(out for _, out, _ in parts), want, atol=1e-5)
        # no share is the whole and none is nothing
        for _, out, _ in parts:
            assert 0.05 < float(jnp.abs(out).mean() / jnp.abs(want).mean()) < 0.9
        # every share computes the same loss: the routing's, over all eight
        for share_cfg, _, sown in parts:
            np.testing.assert_allclose(
                sown["losses"]["load_balance"][0] / share_cfg.load_balance_coef,
                balance, rtol=1e-5)

    def test_a_share_against_the_reference_given_the_same_share(self, whole):
        cfg, x, full = whole
        share_cfg, out, sown = self._layer(2, 4, x, full)
        held = {"router": full["router"], **{
            name: full[name][4:6]
            for name in ("gate_proj", "up_proj", "down_proj")}}
        want, _, _ = jitted(lambda p: reference.experts(
            x, p, _published(share_cfg)), held)
        np.testing.assert_allclose(out, want, atol=1e-5)

    def test_what_a_share_sows(self, whole):
        """Ratios, each saying what a share means: the passes' extent over
        the rows this chip's experts took, those rows over a fair share,
        the largest of ALL experts over their mean."""
        cfg, x, full = whole
        share_cfg, _, sown = self._layer(2, 4, x, full)
        stats = {k: float(v[0]) for k, v in sown["stats"].items()}
        logits = np.asarray(x @ full["router"]["kernel"])
        top = np.argsort(-logits, axis=-1)[..., :cfg.top_k]
        counts = np.bincount(top.ravel(), minlength=8)
        live = counts[4:6].sum()
        assert stats["share_rows_over_expected"] == pytest.approx(
            live / (2 * SEQ * cfg.top_k * 2 / 8))
        assert stats["load_max_over_mean"] == pytest.approx(
            counts.max() / counts.mean())
        # the ladder's extents for 192 assignments, 2 of 8 experts here
        assert round(stats["rows_held_over_live"] * live, 3) in (128.0, 192.0)
        assert 1.0 <= stats["rows_held_over_live"] < 8.0

    def test_renormalised_weights_sum_to_one(self, whole):
        """``norm_topk_prob``: the kept weights divided by their sum, so
        with identity-like experts the layer returns its input's sum of
        weights: 1.  Without it, less than 1."""
        cfg, x, full = whole
        seen = {}

        def spy(x, top_i, top_w, *rest):
            seen["weights"] = top_w
            return whole_fn(x, top_i, top_w, *rest)

        from dlrover_tpu.models import moe

        whole_fn, moe.local_experts = moe.local_experts, spy
        try:
            for norm in (True, False):
                MoEMLP(dataclasses.replace(cfg, norm_topk_prob=norm)).apply(
                    {"params": full}, x, mutable=["losses", "stats"])
                sums = np.asarray(seen["weights"].sum(-1))
                if norm:
                    np.testing.assert_allclose(sums, 1.0, atol=1e-6)
                else:
                    assert sums.max() < 0.99
        finally:
            moe.local_experts = whole_fn

    def test_a_share_does_not_split_over_ep(self):
        cfg = _config(num_layers=1)
        mesh = build_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])
        x = jnp.zeros((2, SEQ, 64))
        with mesh, pytest.raises(ValueError, match="one chip's share"):
            MoEMLP(cfg).init(jax.random.PRNGKey(0), x)


class TestThroughTrainer:
    def test_train_steps_and_what_the_step_reports(self):
        cfg = _config()
        model = LlamaForCausalLM(cfg)
        mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        trainer = Trainer(model, optax.adam(1e-2), mesh)
        batch = {k: np.asarray(v) for k, v in _batch(cfg, rows=4).items()}
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        assert model.num_params() == sum(
            int(np.prod(x.shape))
            for x in jax.tree.leaves(nn.meta.unbox(state.params)))
        sharded = trainer.shard_batch(batch)
        losses, index_losses = [], []
        for _ in range(8):
            state, metrics = trainer.train_step(state, sharded)
            losses.append(float(metrics["loss"]))
            index_losses.append(
                np.asarray(metrics["stats"]["layers"]["layer"]["attn"][
                    "index_loss"][0]))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        # the indexer learns the attention's distribution: L_I falls
        assert index_losses[-1].sum() < index_losses[0].sum()
        stats = metrics["stats"]["layers"]["layer"]
        assert set(stats["attn"]) == {"index_loss", "index_low_margin_share"}
        assert {"share_rows_over_expected", "rows_held_over_live",
                "load_max_over_mean"} <= set(stats["mlp"])
        assert stats["attn"]["index_low_margin_share"][0].shape == (2,)
