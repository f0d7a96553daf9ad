"""Ouro's looped training step as the program runs it (``models/llama.py``
with ``loop_steps``, ``sandwich_norm`` and ``exit_gate``: the stack run four
times over the same weights with the final norm inside the loop, a head and
a gate read after every loop step, the expected loss over the exit
distribution sown as the model's own objective) against its plain reference
(``models/ouro_reference.py``) on the CPU in float32: the result's token
losses, every exit's, the exit distribution, the objective, the gradient of
every leaf, against the reference's and against FOUR separate copies of the
weights whose gradients are summed (the tie is what is tested).  Planted
faults each come out over a limit, the plain program is the parent's
instruction for instruction, and ``num_params`` counts a weight once.

One compiled program a side, in the module's fixture; a case is a
comparison."""

import dataclasses
import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import ouro_reference as reference
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer
from against_reference import init_params, jitted, perturbed, token_ids
from shared_memo import shared_memo

BATCH, SEQ, STEPS, BETA = 2, 48, 4, 0.05
#: float32 against float32: what a planted fault has to pass and the system
#: stay under (the system reads 1e-5 and less, the mildest fault 4e-3)
LIMIT = 1e-3


def _config(**changes):
    fields = dict(
        num_kv_heads=4, rope_theta=1e6, rms_norm_eps=1e-6, dtype=jnp.float32,
        loop_steps=STEPS, sandwich_norm=True, exit_gate=True,
        exit_entropy_weight=BETA)
    fields.update(changes)
    return LlamaConfig.tiny(**fields)


def _published(cfg, **changes):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.loop_steps,
            "exit_entropy_weight": cfg.exit_entropy_weight,
            # blocks that do not divide 48: the reference takes a divisor
            "query_block": 20, "head_rows": 20, **changes}


def _targets(ids):
    """What the program's model takes for targets and weights: the next
    token, and nothing for a sequence's last position."""
    weights = jnp.ones(ids.shape, jnp.float32).at[:, -1].set(0.0)
    return jnp.roll(ids, -1, axis=1), weights


def _system(model, params, ids):
    def loss_fn(p):
        logits, sown = model.apply(
            {"params": p}, ids, mutable=["losses", "stats", "exits"])
        return sown["losses"]["exit_objective"][0], (logits, sown)

    return jitted(jax.value_and_grad(loss_fn, has_aux=True), params)


def _reference(cfg, params, ids, fault=None):
    targets, weights = _targets(ids)
    return jitted(lambda p: reference.reference(
        p, ids, targets, weights, _published(cfg), fault), params)


def _ids(cfg):
    return jnp.asarray(token_ids(BATCH, SEQ, cfg.vocab_size))


@shared_memo
def _worked():
    """The system and the reference on the same perturbed parameters: every
    array a case compares, computed by ONE of the run's workers."""
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    ids = _ids(cfg)
    params = perturbed(init_params(model, ids))
    targets, weights = _targets(ids)
    m = _published(cfg)
    (loss, (logits, sown)), grads = _system(model, params, ids)
    want, want_grads = jitted(jax.value_and_grad(
        lambda p: (lambda out: (out["objective"], out))(
            reference.reference(p, ids, targets, weights, m)),
        has_aux=True), params)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return {
        "params": params, "loss": loss, "grads": grads, "sown": sown,
        "result_losses": -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0],
        "want": want[1], "want_grads": want_grads,
        "unrolled": jitted(lambda p: reference.unrolled_gradients(
            p, ids, targets, weights, m), params),
    }


@pytest.fixture(scope="module")
def worked():
    cfg = _config()
    return {"cfg": cfg, "model": LlamaForCausalLM(cfg), "ids": _ids(cfg),
            **_worked()}


def _leaf_paths(**changes):
    """Every leaf of the model's parameter tree as ``a/b/c``, sorted."""
    shapes = jax.eval_shape(
        LlamaForCausalLM(_config(**changes)).init, jax.random.PRNGKey(0),
        jnp.zeros((BATCH, SEQ), jnp.int32))
    return sorted(
        "/".join(str(key.key) for key in path) for path, _ in
        jax.tree_util.tree_leaves_with_path(nn.meta.unbox(shapes["params"])))


def _at(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return np.asarray(tree)


# every position but a sequence's last has a target
HELD = np.s_[..., :-1]


class TestAgainstTheReference:
    def test_the_tree_is_the_plain_models_with_three_names_more(self, worked):
        plain = set(_leaf_paths(
            loop_steps=1, sandwich_norm=False, exit_gate=False))
        assert set(_leaf_paths()) - plain == {
            "exit_gate/kernel", "exit_gate/bias",
            "layers/layer/attn_out_norm/scale",
            "layers/layer/mlp_out_norm/scale"}
        assert plain <= set(_leaf_paths())
        # a weight is there once, whatever the loop steps
        assert worked["params"]["layers"]["layer"]["mlp"]["up_proj"][
            "kernel"].shape[0] == worked["cfg"].num_layers

    @pytest.mark.parametrize("what", [
        "result", "every_exit", "distribution", "objective", "entropy",
        "mass_last", "ce_by_step"])
    def test_the_forward_pass(self, worked, what):
        want, sown = worked["want"], worked["sown"]
        ce = np.asarray(sown["exits"]["token_losses"][0])
        p = np.exp(np.asarray(sown["exits"]["log_p"][0]))
        if what == "result":      # z_T's token losses, from the logits
            got, expected = worked["result_losses"][HELD], want["ce"][-1][HELD]
        elif what == "every_exit":
            assert ce.shape == (STEPS, BATCH, SEQ)
            got, expected = ce[HELD], want["ce"][HELD]
            # the exits differ: no loop step is a copy of another
            assert np.abs(ce[0] - ce[-1])[HELD].max() > 0.1
        elif what == "distribution":
            np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
            got, expected = p, want["p"]
            assert 0.01 < p[-1].mean() < 0.9
        elif what == "objective":
            got, expected = worked["loss"], want["objective"]
        elif what == "entropy":
            got, expected = sown["stats"]["loop_exit_entropy"][0], want[
                "entropy"]
            assert 0.1 < float(got) < np.log(STEPS)
        elif what == "mass_last":
            got = sown["stats"]["loop_exit_mass_last"][0]
            expected = want["p"][-1][HELD].mean()
        else:
            got = sown["stats"]["loop_ce_by_step"][0]
            assert got.shape == (STEPS,)
            expected = want["ce"][HELD].mean(axis=(1, 2))
        np.testing.assert_allclose(got, expected, rtol=0, atol=2e-5)

    @pytest.mark.parametrize("against", ["want_grads", "unrolled"])
    @pytest.mark.parametrize("path", _leaf_paths())
    def test_the_gradient_of(self, worked, path, against):
        """``want_grads``: ``jax.grad`` of the reference, which reads the
        one tree four times; ``unrolled``: four copies of the weights, loop
        step ``t`` reading copy ``t``, their gradients summed."""
        got, want = _at(worked["grads"], path), _at(worked[against], path)
        assert np.abs(want).max() > 1e-6, "the leaf takes no part"
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 + 2e-4 * np.abs(want).max())


#: the quantity each planted fault moves, which has to read over ``LIMIT``
#: where the system reads under it
FAULT_SHOWS_IN = {
    "three_loop_steps": "result", "final_norm_outside": "result",
    "no_mlp_out_norm": "result", "last_exit_gated": "distribution",
    "entropy_sign": "objective"}


def test_every_fault_the_reference_can_plant_is_listed():
    assert set(FAULT_SHOWS_IN) == set(reference.FAULTS)


@pytest.mark.parametrize("fault", sorted(FAULT_SHOWS_IN))
def test_a_planted_fault_comes_out_over_the_limit(worked, fault):
    planted = _reference(worked["cfg"], worked["params"], worked["ids"], fault)
    want, sown = worked["want"], worked["sown"]

    def off(got, sound, wrong):
        return (float(np.abs(np.asarray(got) - np.asarray(sound)).max()),
                float(np.abs(np.asarray(got) - np.asarray(wrong)).max()))

    if FAULT_SHOWS_IN[fault] == "result":
        system, faulty = off(worked["result_losses"][HELD],
                             want["ce"][-1][HELD], planted["ce"][-1][HELD])
    elif FAULT_SHOWS_IN[fault] == "distribution":
        system, faulty = off(np.exp(sown["exits"]["log_p"][0]), want["p"],
                             planted["p"])
    else:
        system, faulty = off(worked["loss"], want["objective"],
                             planted["objective"])
    assert system < LIMIT < faulty, (fault, system, faulty)


#: how ``weighted_token_losses`` is asked: every row at once, four blocks of
#: 8 rows an exit, and the blocks over a tied table ``[vocab, hidden]``
HEAD_CASES = {"whole": (2 ** 30, False), "blocks": (None, False),
              "tied_blocks": (None, True)}


@pytest.fixture(scope="module")
def heads():
    """The weighted head and the plain ``jax.numpy`` formula, value and
    gradients, on two exits of different streams under uneven weights that
    hold zeros (a sequence's last position) and an upstream factor that is
    not 1: the rule's scalar cotangent is what is tested."""
    from dlrover_tpu.models import llama

    cfg = _config()
    T, B, S = 2, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (T, B, S, cfg.hidden_size))
    kernel = jax.random.normal(
        jax.random.PRNGKey(1), (cfg.hidden_size, cfg.vocab_size)) * 0.1
    targets = jnp.asarray(token_ids(B, S, cfg.vocab_size, seed=5))
    weights = jax.random.uniform(
        jax.random.PRNGKey(2), (T, B, S)).at[..., -1].set(0.0)

    def plain(x, kernel, weights):
        logits = jnp.einsum("tbse,ev->tbsv", x, kernel, precision="highest")
        losses = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.broadcast_to(targets, (T, B, S))[..., None],
            axis=-1)[..., 0]
        return 3.0 * jnp.sum(weights * losses), losses

    def both(function, kernel):
        (total, losses), grads = jitted(jax.value_and_grad(
            function, argnums=(0, 1, 2), has_aux=True), x, kernel, weights)
        return {"total": total, "losses": losses,
                **dict(zip(("x", "kernel", "weights"), grads))}

    worked = {"plain": both(plain, kernel)}
    with pytest.MonkeyPatch.context() as patch:
        for case, (limit, tied) in HEAD_CASES.items():
            patch.setattr(llama, "HEAD_BLOCK_BYTES",
                          limit or 4 * B * 8 * cfg.vocab_size)

            def system(x, kernel, weights, tied=tied):
                total, losses = llama.weighted_token_losses(
                    cfg, x, kernel, targets, weights, tied)
                return 3.0 * total, losses

            table = kernel.T if tied else kernel
            worked[case] = both(system, table)
            if tied:
                worked[case]["kernel"] = worked[case]["kernel"].T
            # not differentiated: the same walk, neither gradient product
            worked[case]["read"] = jitted(system, x, table, weights)
            worked[case]["read_jaxpr"] = str(jax.make_jaxpr(system)(
                x, table, weights))
            worked[case]["through_losses"] = jitted(jax.grad(
                lambda x: jnp.sum(system(x, table, weights)[1])), x)
    return worked


@pytest.mark.parametrize("what", ["x", "kernel", "weights", "losses"])
@pytest.mark.parametrize("case", sorted(HEAD_CASES))
def test_the_weighted_head_is_the_plain_formula(heads, case, what):
    """``weighted_token_losses`` takes its gradient in its forward pass, a
    block of rows at a time where a sequence's float32 logits pass
    ``HEAD_BLOCK_BYTES`` (the cell: 4096 rows of 16,384): the same ``L``,
    ``CE`` and gradients as ``jax.grad`` of ``sum(w * (logsumexp -
    taken))`` under a cotangent of 3; ``CE`` itself carries none."""
    got, want = heads[case], heads["plain"]
    if what != "losses":
        assert np.abs(want[what]).max() > 1e-3
        np.testing.assert_allclose(
            got[what], want[what], rtol=1e-5, atol=2e-5)
        return
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=0,
                               atol=2e-5)
    np.testing.assert_allclose(got["total"], want["total"], rtol=1e-6)
    for mine, differentiated in zip(got["read"], (got["total"],
                                                   got["losses"])):
        np.testing.assert_allclose(mine, differentiated, rtol=1e-6)
    # one product a block where the differentiated walk makes three
    assert got["read_jaxpr"].count("dot_general") == 1
    assert ("while" in got["read_jaxpr"] or "scan" in got["read_jaxpr"])
    assert not np.asarray(got["through_losses"]).any()


class TestThePlainProgramIsTheParents:
    #: ``tests/test_layer_pattern.py::BEFORE["empty"]``: the digest of the
    #: plain tiny model's loss and gradient program, pinned at PR 47
    PINNED = "483fc5aaffc3fc24"

    def test_one_loop_step_without_the_new_parts_is_the_old_program(self):
        """``loop_steps`` 1, no sandwich norm, no gate: the code path of
        every configuration the benchmark had, instruction for instruction
        (so its loss is the old one to the bit), and ``mistral7b_l2``'s
        tiny program (``benchmarks/families/llama.py``) tree and logits."""
        from benchmarks.common import load_module

        cfg = LlamaConfig.tiny(
            loop_steps=1, sandwich_norm=False, exit_gate=False)
        assert cfg == LlamaConfig.tiny() and not cfg.own_objective
        model = LlamaForCausalLM(cfg)
        ids = jnp.zeros((2, 48), jnp.int32)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             nn.meta.unbox(shapes["params"]))

        def loss(p):
            logits, sown = model.apply(
                {"params": p}, ids, mutable=["losses", "stats"])
            return logits.astype(jnp.float32).mean() + sum(
                jnp.sum(t) for t in jax.tree.leaves(sown.get("losses", {})))

        text = re.sub(r"0x[0-9a-f]+", "0x",
                      str(jax.make_jaxpr(jax.value_and_grad(loss))(zeros)))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == self.PINNED

        mistral = load_module("families", "llama").build(
            {"run": {"attention_impl": "reference"}}, True, 48)
        assert dataclasses.replace(
            mistral.config, max_seq_len=cfg.max_seq_len) == cfg
        tokens = jnp.asarray(token_ids(2, 48))
        params = init_params(mistral, tokens)
        assert jax.tree.structure(params) == jax.tree.structure(zeros)
        np.testing.assert_array_equal(
            jitted(lambda p: mistral.apply({"params": p}, tokens), params),
            jitted(lambda p: model.apply({"params": p}, tokens), params))

    @pytest.mark.parametrize("fields", [
        dict(loop_steps=0), dict(loop_steps=1, exit_gate=True),
        dict(loop_steps=2, layer_pattern=("gqa", "gqa")),
        dict(loop_steps=2, tie_embeddings=True),
        dict(loop_steps=2, scan_layers=False),
        dict(loop_steps=2, block_diffusion=4)])
    def test_what_a_loop_does_not_go_with_is_refused(self, fields):
        with pytest.raises(ValueError, match="loop_steps"):
            LlamaConfig.tiny(**fields)

    def test_a_routed_feed_forward_is_refused_in_a_loop(self):
        from dlrover_tpu.models.moe import MoELlamaConfig

        with pytest.raises(ValueError, match="loop_steps"):
            MoELlamaConfig.tiny_moe(loop_steps=2)
        assert MoELlamaConfig.tiny_moe().loop_steps == 1


class TestTheCounts:
    #: the published widths (``benchmarks/configs/ouro2b6_l8.json``)
    WIDTHS = dict(
        vocab_size=49152, hidden_size=2048, intermediate_size=5632,
        num_heads=16, num_kv_heads=16, head_dim=128, loop_steps=4,
        sandwich_norm=True, exit_gate=True)

    @pytest.mark.parametrize("layers,params", [
        (8, 612_438_017), (48, 2_667_974_657)])
    def test_num_params_counts_a_weight_once(self, layers, params):
        model = LlamaForCausalLM(LlamaConfig(num_layers=layers, **self.WIDTHS))
        assert model.num_params() == params
        # a layer: four projections, the SwiGLU, four norms
        assert (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048) == 51_388_416

    def test_num_params_is_the_trees(self, worked):
        assert worked["model"].num_params() == sum(
            leaf.size for leaf in jax.tree.leaves(worked["params"]))

    def test_a_loop_without_a_gate_is_a_plain_models_objective(self, worked):
        """``loop_steps`` alone: the looped stack's ``z_T``, nothing sown,
        the trainer's cross entropy on top as for any model."""
        cfg = _config(exit_gate=False, exit_entropy_weight=0.0)
        assert not cfg.own_objective
        params = {k: v for k, v in worked["params"].items()
                  if k != "exit_gate"}
        logits, sown = jitted(lambda p: LlamaForCausalLM(cfg).apply(
            {"params": p}, worked["ids"], mutable=["losses", "stats"]),
            params)
        assert not jax.tree.leaves(sown)
        targets, _ = _targets(worked["ids"])
        got = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None],
            axis=-1)[..., 0]
        np.testing.assert_allclose(
            got[HELD], worked["want"]["ce"][-1][HELD], rtol=0, atol=2e-5)


@shared_memo
def _stepped():
    """Two steps of a ``Trainer`` that differentiates a bfloat16 view
    (the benchmark's ``grads_dtype``), and the first step's gradients."""
    cfg = _config()
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-3), mesh,
                      grads_dtype=jnp.bfloat16)
    ids = token_ids(BATCH, SEQ + 1, cfg.vocab_size, seed=3)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    # before a step donates the state
    rounded = jax.tree.map(
        lambda t: t.astype(jnp.bfloat16).astype(jnp.float32),
        nn.meta.unbox(state.params))
    (loss, (stats, _)), grads = jitted(
        trainer._loss_buffers_and_grads, state.params,
        {k: jnp.asarray(v) for k, v in batch.items()})
    metrics = []
    for _ in range(2):
        state, out = trainer.train_step(state, trainer.shard_batch(batch))
        metrics.append(jax.device_get(out))
    targets, weights = _targets(jnp.asarray(batch["input_ids"]))
    want, want_grads = jitted(jax.value_and_grad(
        lambda p: reference.reference(
            p, jnp.asarray(batch["input_ids"]), targets, weights,
            _published(cfg))["objective"]), rounded)
    return {"loss": loss, "grads": nn.meta.unbox(grads), "stats": stats,
            "metrics": metrics, "want": want, "want_grads": want_grads}


class TestTheTrainer:
    @pytest.fixture(scope="class")
    def stepped(self):
        return _stepped()

    def test_the_loss_is_the_objective_alone(self, stepped):
        """``own_objective``: no cross entropy on top of what is sown."""
        np.testing.assert_allclose(stepped["loss"], stepped["want"], rtol=1e-5)
        np.testing.assert_allclose(
            stepped["metrics"][0]["loss"], stepped["want"], rtol=1e-5)
        assert np.isfinite(stepped["metrics"][1]["loss"])
        assert set(stepped["metrics"][0]["stats"]) == {
            "loop_exit_entropy", "loop_exit_mass_last", "loop_ce_by_step"}

    def test_a_tied_weights_bfloat16_gradient_is_the_sum_of_four_uses(
            self, stepped):
        """The sum over loop steps is taken by the scan's transpose in the
        cotangent's dtype, bfloat16 here (three roundings of a running sum
        where a float32 sum would round once): every leaf within a
        hundredth of its largest entry of the float32 reference's."""
        for path, got in jax.tree_util.tree_leaves_with_path(stepped["grads"]):
            assert got.dtype == jnp.bfloat16, path
            want = np.asarray(_at(
                stepped["want_grads"],
                "/".join(str(key.key) for key in path)))
            np.testing.assert_allclose(
                np.asarray(got, np.float32), want, rtol=0,
                atol=1e-2 * np.abs(want).max(), err_msg=str(path))
