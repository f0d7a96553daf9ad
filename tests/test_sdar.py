"""SDAR's block-diffusion training step as the program runs it
(``models/llama.py`` with ``block_diffusion``: a noisy and a clean copy of
the input in one row of ``2S`` positions, ``ops/attention.py::
block_diffusion_attention``, the NELBO sown as the model's own objective,
the noise from a key the trainer makes from the step) against its plain
reference (``models/sdar_reference.py``) on the CPU in float32: logits, the
objective, the gradients of every parameter.  The mask against the
four-line rule written out as a ``[2S, 2S]`` table, the noise's
properties, **the shares add up**, the noise differs by step and a resumed
job repeats the uninterrupted one's losses bit for bit, and nothing moves
for a model without the field."""

import collections
import dataclasses
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import sdar_reference as reference
from dlrover_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    noise_blocks,
)
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP
from dlrover_tpu.ops import attention as attention_ops
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
from dlrover_tpu.trainer.train import Trainer
from against_reference import (
    init_params,
    jitted,
    perturbed,
    reference_loss_and_gradients,
    system,
    system_loss,
    token_ids,
)

SEQ, BLOCK, MASK = 40, 4, 255


def _config(**changes):
    fields = dict(
        qk_norm="head", rope_theta=1e6, rms_norm_eps=1e-6, dtype=jnp.float32,
        num_experts=8, top_k=3, norm_topk_prob=True, load_balance_coef=0.001,
        router_z_coef=0.0, block_diffusion=BLOCK, mask_token_id=MASK,
        noise_seed=5)
    fields.update(changes)
    return MoELlamaConfig.tiny_moe(**fields)


def _published(cfg, **changes):
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "experts_total": cfg.num_experts,
            "first_expert": cfg.first_expert,
            "router_aux_loss_coef": cfg.load_balance_coef,
            "block_length": cfg.block_diffusion, "query_block": 16, **changes}


def _ids(batch=2, seq=SEQ, seed=0):
    return jnp.asarray(token_ids(batch, seq, MASK, seed))


def _noise(cfg, ids, step=0):
    return noise_blocks(ids, cfg.step_rngs(step)["noise"],
                        cfg.block_diffusion, cfg.mask_token_id, cfg.noise_eps)


def _reference(cfg, params, ids, noisy, weights):
    """(the reference's dictionary, its loss's gradients)."""
    m = _published(cfg)
    return reference_loss_and_gradients(
        lambda p: reference.forward(p, noisy, ids, weights, m), params)


#: what the fixture computed, once a parameter: the system's ``((the
#: step's loss, (logits, sown)), gradients)``, the noise the reference is
#: given, the reference's dictionary and gradients
Made = collections.namedtuple(
    "Made", "cfg model params ids got noisy weights want want_grads")


@pytest.fixture(scope="module", params=[0, 2], ids=["every_expert", "a_share"])
def made(request):
    cfg = _config(experts_held=request.param, first_expert=request.param * 2)
    model = LlamaForCausalLM(cfg)
    ids = _ids()
    params = perturbed(init_params(model, ids))
    noisy, weights = _noise(cfg, ids)
    return Made(cfg, model, params, ids, system(model, params, ids),
                noisy, weights, *_reference(cfg, params, ids, noisy, weights))


class TestAgainstReference:
    def test_logits_objective_and_counters(self, made):
        cfg, ids, weights, want = made.cfg, made.ids, made.weights, made.want
        (total, (logits, sown)), _ = made.got
        assert logits.shape == ids.shape + (cfg.vocab_size,)
        np.testing.assert_allclose(logits, want["logits"], rtol=0, atol=5e-5)
        np.testing.assert_allclose(
            sown["losses"]["nelbo"][0], want["nelbo"], rtol=1e-5)
        np.testing.assert_allclose(total, want["loss"], rtol=1e-5)
        np.testing.assert_allclose(
            sown["stats"]["bd_masked_share"][0], jnp.mean(weights > 0))
        np.testing.assert_allclose(
            sown["stats"]["bd_weight_max"][0], weights.max())
        # the routing's loss is over all 2S rows, a value a layer
        np.testing.assert_allclose(
            sown["losses"]["layers"]["layer"]["mlp"]["load_balance"][0]
            * cfg.num_layers / cfg.load_balance_coef,
            want["load_balance"], rtol=1e-5)

    def test_gradients_of_every_parameter(self, made):
        _, got = made.got
        flat = jax.tree_util.tree_leaves_with_path(got)
        for (path, g), w in zip(flat, jax.tree.leaves(made.want_grads)):
            name = "/".join(str(k.key) for k in path)
            assert float(jnp.abs(w).max()) > 0, name     # every leaf is used
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2e-4 * max(1.0, float(jnp.abs(w).max())),
                err_msg=name)
        assert len(flat) >= 14

    def test_the_steps_key_reaches_the_model(self, made):
        """The model draws from flax's ``noise`` stream of the step's key
        (its first ``make_rng``: the key with the count 1 folded in)."""
        from flax.core.scope import LazyRng

        cfg, model, params, ids = made[:4]
        _, (logits, _) = system_loss(
            model, params, ids, rngs=cfg.step_rngs(3))
        drawn = LazyRng.create(cfg.step_rngs(3)["noise"], 1).as_jax_rng()
        noisy, weights = noise_blocks(
            ids, drawn, cfg.block_diffusion, cfg.mask_token_id, cfg.noise_eps)
        m = _published(cfg)
        want = jitted(lambda p: reference.forward(
            p, noisy, ids, weights, m)["logits"], params)
        np.testing.assert_allclose(logits, want, rtol=0, atol=5e-5)
        assert not np.array_equal(noisy, made.noisy)

    @pytest.mark.parametrize("planted", [
        "causal_by_token", "own_clean_block_seen", "positions_run_on",
        "own_noisy_block_unseen"])
    def test_a_departure_is_far_outside_float32_agreement(self, made, planted,
                                                          monkeypatch):
        """The reference with one line of the rule changed reads far from
        the system: the agreement above is of this mask and these
        positions, not of any."""
        cfg, params, ids = made.cfg, made.params, made.ids
        _, (logits, _) = made.got[0]
        seq = ids.shape[1]
        true_allowed, true_rope = reference.allowed, reference.rope

        def allowed(r, c, seq_, block):
            b_r, b_c = (r % seq) // block, (c % seq) // block
            if planted == "causal_by_token":
                return jnp.where((r >= seq) & (c >= seq), c <= r,
                                 true_allowed(r, c, seq_, block))
            if planted == "own_clean_block_seen":
                return jnp.where((r < seq) & (c >= seq), b_c <= b_r,
                                 true_allowed(r, c, seq_, block))
            if planted == "own_noisy_block_unseen":
                return jnp.where((r < seq) & (c < seq), r == c,
                                 true_allowed(r, c, seq_, block))
            return true_allowed(r, c, seq_, block)

        def rope(x, positions, theta):
            if planted == "positions_run_on":
                positions = jnp.arange(x.shape[1])
            return true_rope(x, positions, theta)

        monkeypatch.setattr(reference, "allowed", allowed)
        monkeypatch.setattr(reference, "rope", rope)
        m = _published(cfg)
        other = jitted(lambda p: reference.forward(
            p, made.noisy, ids, made.weights, m)["logits"], params)
        assert float(jnp.abs(logits - other).max()) > 1e-2


def _table(seq, block):
    """The four lines of the rule written out, ``[2S, 2S]``."""
    rows = np.arange(2 * seq)
    table = np.zeros((2 * seq, 2 * seq), bool)
    for r in rows:
        for c in rows:
            b_r, b_c = (r % seq) // block, (c % seq) // block
            if r < seq and c < seq:
                table[r, c] = b_r == b_c
            elif r < seq:
                table[r, c] = b_c < b_r
            elif c >= seq:
                table[r, c] = b_c <= b_r
    return table


def _dense(q, k, v, table):
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(table, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class TestTheMask:
    @pytest.mark.parametrize("seq,block,query_block", [
        (40, 4, 16), (32, 4, 16), (24, 8, 512), (12, 4, 4)],
        ids=["a_short_last_block", "whole_blocks", "one_block", "a_block_each"])
    def test_attention_equals_the_rule_written_out(self, seq, block,
                                                   query_block):
        keys = jax.random.split(jax.random.PRNGKey(seq), 3)
        q = jax.random.normal(keys[0], (2, 2 * seq, 4, 16))
        k = jax.random.normal(keys[1], (2, 2 * seq, 2, 16))
        v = jax.random.normal(keys[2], (2, 2 * seq, 2, 16))
        table = _table(seq, block)
        got = jitted(lambda *a: attention_ops.block_diffusion_attention(
            *a, block, query_block), q, k, v)
        want = jitted(lambda *a: _dense(*a, table), q, k, v)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
        assert table.sum() == attention_ops.block_diffusion_pairs(seq, block)
        # the reference's function is the same table
        rows = jnp.arange(2 * seq)
        np.testing.assert_array_equal(
            reference.allowed(rows[:, None], rows[None, :], seq, block), table)

    def test_a_blocks_mask_is_the_tables_rows_and_columns(self):
        seq, block, first, last = 40, 4, 16, 32
        table = _table(seq, block)
        clean = attention_ops.block_diffusion_keep(first, last, block, False)
        np.testing.assert_array_equal(
            clean[0], table[seq + first: seq + last, seq: seq + last])
        noisy = attention_ops.block_diffusion_keep(first, last, block, True)
        np.testing.assert_array_equal(noisy[0], np.concatenate(
            [table[first:last, seq: seq + last], table[first:last, first:last]],
            axis=1))
        # what the blocks leave out is what the rule forbids
        assert not table[seq + first: seq + last, seq + last:].any()
        assert not table[seq:, :seq].any()
        assert not table[first:last, :first].any()
        assert not table[first:last, last:seq].any()

    def test_gradients_reach_both_copies(self):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(key, (1, 48, 2, 16)) for key in keys)
        table = _table(24, 4)
        got = jitted(jax.grad(lambda *a: jnp.sum(jnp.square(
            attention_ops.block_diffusion_attention(*a, 4, 8))),
            (0, 1, 2)), q, k, v)
        want = jitted(jax.grad(lambda *a: jnp.sum(jnp.square(
            _dense(*a, table))), (0, 1, 2)), q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=0, atol=5e-5)
            assert float(jnp.abs(g[:, :24]).max()) > 0
            assert float(jnp.abs(g[:, 24:]).max()) > 0

    @pytest.mark.parametrize("rows,block,query_block,match", [
        (47, 4, 16, "two copies"), (48, 5, 16, "two copies"),
        (48, 4, 6, "straddles")])
    def test_what_it_refuses(self, rows, block, query_block, match):
        x = jnp.zeros((1, rows, 2, 16))
        with pytest.raises(ValueError, match=match):
            attention_ops.block_diffusion_attention(x, x, x, block, query_block)

    def test_the_path_is_chosen_from_what_the_code_sees(self):
        path = attention_ops.block_diffusion_path
        assert path("tpu", 8192, 512, 128, 32, 4) == "pallas"
        assert path("cpu", 8192, 512, 128, 32, 4) == "jnp"
        assert path("tpu", 8192 + 256, 512, 128, 32, 4) == "jnp"
        assert path("tpu", 8192, 512, 64, 32, 4) == "jnp"


class TestNoise:
    def test_a_blocks_masked_share_tends_to_its_rate(self):
        ids = jnp.zeros((64, 4096), jnp.int32)
        noisy, weights = noise_blocks(ids, jax.random.PRNGKey(0), 64, 7, 1e-3)
        masked = np.asarray(weights > 0).reshape(64, -1, 64)
        t = 1.0 / np.asarray(weights).reshape(64, -1, 64).max(axis=-1).clip(1)
        seen = masked.any(axis=-1)          # a block with no mask hides its t
        share = masked.mean(axis=-1)
        assert abs((share[seen] - t[seen]).mean()) < 5e-3
        assert np.abs(share[seen] - t[seen]).max() < 0.3
        # t is uniform over the blocks: half the tokens masked
        assert abs(float(masked.mean()) - 0.5) < 0.01

    def test_weights_are_m_over_t_and_masks_sit_where_m_is_1(self):
        ids = _ids(4, 64, seed=3)
        noisy, weights = noise_blocks(ids, jax.random.PRNGKey(2), 4, MASK)
        m = np.asarray(weights > 0)
        np.testing.assert_array_equal(np.asarray(noisy)[m], MASK)
        np.testing.assert_array_equal(np.asarray(noisy)[~m], np.asarray(ids)[~m])
        blocks = np.asarray(weights).reshape(4, -1, 4)
        for block in blocks.reshape(-1, 4):     # one t a block, at least 1/t
            assert len(set(block[block > 0])) <= 1
        assert blocks[blocks > 0].min() >= 1.0
        assert weights.dtype == jnp.float32 and noisy.dtype == ids.dtype

    def test_the_noise_differs_by_step_and_by_seed(self):
        cfg, ids = _config(), _ids()
        draws = [np.asarray(_noise(cfg, ids, step)[0]) for step in range(4)]
        for i, a in enumerate(draws):
            for b in draws[i + 1:]:
                assert not np.array_equal(a, b)
        np.testing.assert_array_equal(draws[2], _noise(cfg, ids, 2)[0])
        other = dataclasses.replace(cfg, noise_seed=6)
        assert not np.array_equal(draws[0], _noise(other, ids)[0])


class TestTheSharesAddUp:
    """Four chips' shares of eight experts on the ``2S`` rows give the
    uncut layer: the routed block sees rows, not copies."""

    def test_expert_shares_sum_to_the_uncut_layer(self):
        cfg = _config()
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 2 * SEQ, 64))
        full = perturbed(init_params(MoEMLP(cfg), x, seed=6))
        m = _published(cfg)
        want, balance, _ = jitted(
            lambda p: reference.experts(x, p, m, whole=True), full)
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
            np.testing.assert_allclose(
                sown["losses"]["load_balance"][0] * cfg.num_layers
                / cfg.load_balance_coef, balance, rtol=1e-5)
            parts.append(out)
        np.testing.assert_allclose(sum(parts), want, rtol=0, atol=5e-5)
        for part in parts:      # no share is the whole and none is nothing
            assert 0.05 < float(jnp.abs(part).mean() / jnp.abs(want).mean())


def _trainer(cfg, **kw):
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    return Trainer(LlamaForCausalLM(cfg), optax.adamw(3e-3), mesh, **kw)


def _batch(seed=4):
    ids = np.asarray(_ids(2, SEQ + 1, seed))
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


class TestTheTrainer:
    def test_a_resumed_job_repeats_the_losses_bit_for_bit(self, tmp_path):
        """The same batch every step: what differs from step to step is
        the noise alone, drawn from the step the state carries."""
        trainer = _trainer(_config(dtype=jnp.bfloat16))
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])

        def run(state, steps):
            losses = []
            for _ in range(steps):
                state, metrics = trainer.train_step(
                    state, trainer.shard_batch(batch))
                losses.append(np.asarray(metrics["loss"]))
            return state, losses

        state, before = run(state, 3)
        ckpt = Checkpointer(str(tmp_path), scope=f"t{uuid.uuid4().hex[:8]}")
        try:
            ckpt.save_checkpoint(3, state, StorageType.MEMORY)
            _, uninterrupted = run(state, 4)
            restored, step = ckpt.load_checkpoint(
                jax.eval_shape(lambda s: s, state), trainer.state_shardings)
        finally:
            ckpt.close()
        assert step == 3 and int(restored.step) == 3
        _, resumed = run(restored, 4)
        for a, b in zip(uninterrupted, resumed):
            assert a.tobytes() == b.tobytes()
        # one batch, yet no two steps' losses alike: the noise moved
        every = [float(x) for x in before + uninterrupted]
        assert len(set(every)) == len(every) and np.isfinite(every).all()

    def test_the_step_counts_what_was_masked(self):
        trainer = _trainer(_config())
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        shares = []
        for _ in range(3):
            state, metrics = trainer.train_step(
                state, trainer.shard_batch(batch))
            shares.append(float(metrics["stats"]["bd_masked_share"][0]))
            assert float(metrics["stats"]["bd_weight_max"][0]) >= 1.0
        assert len(set(shares)) > 1 and all(0.1 < s < 0.9 for s in shares)

    def test_no_cross_entropy_on_top_of_a_models_own_objective(self):
        cfg = _config()
        trainer = _trainer(cfg)
        batch = {k: jnp.asarray(v) for k, v in _batch().items()}
        params = init_params(trainer.model, batch["input_ids"], seed=0)
        loss, stats = jitted(trainer._default_loss, params, batch)
        total, _ = system_loss(trainer.model, params, batch["input_ids"])
        # the labels go unused
        other, _ = jitted(trainer._default_loss, params, {
            **batch, "labels": batch["labels"][:, ::-1]})
        np.testing.assert_allclose(loss, total, rtol=1e-6)
        assert float(loss) == float(other)
        assert "bd_masked_share" in stats

    def test_cross_entropy_still_for_every_other_model(self):
        cfg = _config(block_diffusion=0)
        trainer = _trainer(cfg)
        batch = {k: jnp.asarray(v) for k, v in _batch().items()}
        params = init_params(trainer.model, batch["input_ids"], seed=0)
        loss, _ = jitted(trainer._default_loss, params, batch)
        logits, sown = jitted(lambda p: trainer.model.apply(
            {"params": p}, batch["input_ids"], mutable=["losses"]), params)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        token = -jnp.take_along_axis(
            logp, batch["labels"][..., None], -1)[..., 0]
        want = token.mean() + sum(
            jnp.sum(t) for t in jax.tree.leaves(sown["losses"]))
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        assert float(token.mean()) > 1.0

    @pytest.mark.parametrize("kw", [{"grad_accum_steps": 2}])
    def test_noise_and_accumulation_are_refused_not_mixed(self, kw):
        trainer = _trainer(_config(), **kw)
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        with pytest.raises(NotImplementedError, match="noise"):
            trainer.train_step(state, trainer.shard_batch(batch))


class TestNothingMovesWithoutTheField:
    def test_the_fields_default_to_todays_model(self):
        defaults = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
        assert defaults["block_diffusion"] == 0
        for cfg in (LlamaConfig.tiny(), MoELlamaConfig.tiny_moe()):
            assert cfg.own_objective is False and cfg.step_rngs(0) == {}

    def test_the_parameter_tree_is_the_causal_models_name_for_name(self):
        ids = _ids()
        trees = []
        for block in (0, BLOCK):
            model = LlamaForCausalLM(_config(block_diffusion=block))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
            trees.append({jax.tree_util.keystr(p): (s.shape, s.dtype) for p, s
                          in jax.tree_util.tree_leaves_with_path(
                              nn.meta.unbox(shapes["params"]))})
        assert trees[0] == trees[1] and len(trees[0]) >= 14

    def test_a_causal_models_step_draws_nothing(self):
        """No random bits, no second copy and no mask token in the step of
        a model without the field: its text names none of them."""
        cfg = _config(block_diffusion=0)
        trainer = _trainer(cfg)
        batch = _batch()
        state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
        text = jax.jit(trainer._train_step).lower(
            state, trainer.shard_batch(batch)).as_text()
        assert "threefry" not in text and "rng_bit_generator" not in text
        with_noise = _trainer(_config())
        state = with_noise.create_state(
            jax.random.PRNGKey(0), batch["input_ids"])
        text = jax.jit(with_noise._train_step).lower(
            state, with_noise.shard_batch(batch)).as_text()
        assert "threefry" in text or "rng_bit_generator" in text

    @pytest.mark.parametrize("changes", [
        {"index_topk": 8, "index_heads": 2, "index_head_dim": 8},
        {"layer_pattern": ("gqa", "gqa")}, {"pred_heads": 2},
        {"mask_token_id": 256}], ids=lambda c: next(iter(c)))
    def test_what_the_config_refuses(self, changes):
        with pytest.raises(ValueError, match="block_diffusion"):
            _config(**changes)
