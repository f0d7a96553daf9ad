"""Phi-4-mini-flash-reasoning on the program's one decoder: the selective
scan (``ops/selective_scan.py`` and its Pallas kernels in the interpreter)
against the recurrence a position at a time; ``models/llama.py``'s Mamba,
differential-attention, memory and cross-decoder layers, LayerNorm and tied
head against ``models/phi4flash_reference.py`` in logits, loss and every
parameter's gradient; the layout from the configuration's rule; the
parameter counts; a ``Trainer``'s steps and a checkpoint's round trip of
the new tree.  Tiny widths, seeded random weights, float32.

Tolerances: system and reference compute the same float32 mathematics in
another order (a scan in chunks or in kernels beside a scan by position, a
softmax over a padded head beside two, sums in another association): the
limits below are some ten times what the comparisons read on the CPU
(logits within 4e-6, gradients within 2e-5 of values up to 1), and a
thousandth of what a planted fault moves
(``benchmarks/tests/test_correct_phi4flash.py``)."""

import json
import os
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import against_reference
from dlrover_tpu.models import phi4flash_reference as reference
from dlrover_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    hybrid_layout,
)
from dlrover_tpu.ops import selective_scan as scan
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, VOCAB, WINDOW = 24, 64, 5
LOGITS_ATOL, GRAD_ATOL, SCAN_ATOL = 5e-5, 2e-4, 2e-4


def _config(layers=8, **changes):
    return LlamaConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        num_layers=layers, num_heads=8, num_kv_heads=4, head_dim=8,
        max_seq_len=64, sliding_window=WINDOW, use_rope=False, norm="layer",
        tie_embeddings=True, attention_bias=True, diff_attention=True,
        mamba_state=4, dtype=jnp.float32, **hybrid_layout(layers, 2)),
        **changes})


def _sown_by_name(stats):
    """{name: every layer's value}, whichever part of the stack sowed it."""
    sown = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
        sown.setdefault(path[-2].key, []).extend(np.asarray(leaf).ravel())
    return sown


def _sizes(layers=8):
    return dict(num_hidden_layers=layers, mb_per_layer=2,
                sliding_window=WINDOW, layer_norm_eps=1e-5, mamba_d_state=4,
                mamba_d_conv=4, mamba_dt_rank=2)


@pytest.fixture(scope="module", params=[8, 12])
def both(request):
    """System and reference, computed once a depth: 12 layers have two
    periods of the cross-decoder, so the memory's gradient is a sum over
    four readers."""
    layers = request.param
    model = LlamaForCausalLM(_config(layers))
    ids, labels = against_reference.inputs_and_labels(2, SEQ, VOCAB)
    params = against_reference.perturbed(
        against_reference.init_params(model, ids), scale=0.05)
    (loss, (token, sown)), grads = against_reference.system(
        model, params, ids, labels)
    logits = against_reference.jitted(
        lambda p: model.apply({"params": p}, ids), params)
    want, want_grads = against_reference.reference_loss_and_gradients(
        lambda p: reference.forward(p, ids, labels, _sizes(layers)), params)
    return dict(layers=layers, model=model, params=params, ids=ids,
                labels=labels, loss=loss, token=token, sown=sown,
                grads=grads, logits=logits, want=want,
                want_grads=want_grads)


# -- the layout and the counts ---------------------------------------------

@pytest.mark.parametrize("layers,self_periods,cross_periods", [
    (32, 8, 7), (8, 2, 1), (12, 3, 2), (4, 1, 0)])
def test_the_kinds_of_every_layer_follow_the_configs_rule(
        layers, self_periods, cross_periods):
    cfg = _config(layers)
    kinds = list(cfg.layer_kinds())
    assert kinds == (["mamba", "swa"] * self_periods + ["mamba", "gqa"]
                     + ["gmu", "xattn"] * cross_periods)
    assert cfg.periods == self_periods
    # the reference derives the same from the same three numbers
    names = {"mamba": "mamba", "gmu": "gmu", "cross": "xattn"}
    assert kinds == [
        names.get(kind, "swa" if window else "gqa")
        for kind, window in reference.kinds_of(_sizes(layers))]


@pytest.mark.parametrize("layers,per", [(6, 2), (8, 3), (10, 2)])
def test_a_depth_the_rule_cannot_lay_out_is_refused(layers, per):
    with pytest.raises(ValueError, match="multiple of 4"):
        hybrid_layout(layers, per)


@pytest.mark.parametrize("changes,match", [
    (dict(memory_layers=("mamba", "mamba")), "memory_layers"),
    (dict(cross_periods=0), "cross_periods"),
    (dict(layer_pattern=("mamba", "gmu")), "cross_pattern alone"),
    (dict(mamba_state=0), "mamba_state"),
    (dict(num_heads=7, num_kv_heads=7), "pairs an even number"),
    (dict(norm="batch"), "norm is"),
    (dict(diff_attention=False), "diff_attention"),
])
def test_what_the_config_refuses(changes, match):
    with pytest.raises(ValueError, match=match):
        _config(**changes)


def _published(**changes):
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "phi4miniflash_l8.json")) as f:
        config = json.load(f)
    src = {**config, **changes}
    return config, LlamaConfig(
        vocab_size=src["vocab_size"], hidden_size=src["hidden_size"],
        intermediate_size=src["intermediate_size"],
        num_layers=src["num_hidden_layers"],
        num_heads=src["num_attention_heads"],
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src["hidden_size"] // src["num_attention_heads"],
        sliding_window=src["sliding_window"], use_rope=False, norm="layer",
        tie_embeddings=True, attention_bias=True, diff_attention=True,
        mamba_state=src["assumed"]["mamba_d_state"],
        mamba_conv=src["assumed"]["mamba_d_conv"],
        mamba_expand=src["assumed"]["mamba_expand"],
        mamba_dt_rank=src["assumed"]["mamba_dt_rank"],
        **hybrid_layout(src["num_hidden_layers"], src["mb_per_layer"]))


def test_the_whole_published_model_counts_the_model_cards_parameters():
    config, cfg = _published()
    whole = LlamaForCausalLM(_published(**config["published"])[1])
    assert abs(whole.num_params() - 3.85e9) < 0.01 * 3.85e9


def test_the_cut_counts_what_the_configuration_file_says():
    config, cfg = _published()
    model = LlamaForCausalLM(cfg)
    assert model.num_params() == config["parameters"] == 915_311_616
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    assert model.num_params() == sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree.leaves(nn.meta.unbox(shapes["params"])))


def test_num_params_equals_the_trees_count(both):
    assert both["model"].num_params() == sum(
        leaf.size for leaf in jax.tree.leaves(both["params"]))


# -- the model against the reference ---------------------------------------

def test_logits_equal_the_references(both):
    np.testing.assert_allclose(
        both["logits"], both["want"]["logits"], atol=LOGITS_ATOL)


def test_loss_and_token_losses_equal_the_references(both):
    np.testing.assert_allclose(
        both["token"], both["want"]["token_losses"], atol=LOGITS_ATOL)
    assert abs(float(both["loss"]) - float(both["want"]["loss"])) < 1e-5


def test_every_parameters_gradient_equals_the_references(both):
    got = jax.tree_util.tree_leaves_with_path(both["grads"])
    want = jax.tree.leaves(both["want_grads"])
    assert len(got) == len(want) > 60
    for (path, g), w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, w, atol=GRAD_ATOL, err_msg=jax.tree_util.keystr(path))


def test_the_tree_has_one_table_and_every_kind_of_layer(both):
    params = both["params"]
    assert "lm_head" not in params and "embed_tokens" in params
    assert sorted(params) == ["cross", "embed_tokens", "final_norm",
                              "layers", "memory"]
    periods = both["layers"] // 4
    assert params["layers"]["mamba_0"]["layer"]["attn"]["A_log"].shape == (
        periods, 1, 64, 4)
    assert params["memory"]["gqa_1"]["layer"]["attn"]["k_proj"][
        "kernel"].shape == (32, 4, 8)
    cross = params["cross"]["xattn_1"]["layer"]["attn"]
    assert "k_proj" not in cross and "v_proj" not in cross
    assert cross["q_proj"]["kernel"].shape == (periods - 1, 1, 32, 8, 8)
    assert sorted(params["cross"]["gmu_0"]["layer"]["attn"]) == [
        "in_proj", "out_proj"]


def test_the_tables_gradient_is_the_sum_of_both_uses(both):
    """Tying: the lookup's gradient and the head's, each taken with the
    other use held fixed, add up to the one table's."""
    ids, labels, m = both["ids"], both["labels"], _sizes(both["layers"])
    params = both["params"]

    def loss(lookup, head):
        return reference.forward(
            {**params, "embed_tokens": lookup}, ids, labels, m,
            head=head)["loss"]

    table = params["embed_tokens"]
    by_lookup, by_head = against_reference.jitted(
        jax.grad(loss, argnums=(0, 1)), table, table)
    assert float(jnp.abs(by_lookup).max()) > 0
    assert float(jnp.abs(by_head).max()) > 0
    np.testing.assert_allclose(
        both["grads"]["embed_tokens"], by_lookup + by_head, atol=GRAD_ATOL)


def test_the_counters_come_back_a_value_a_layer(both):
    sown = _sown_by_name(both["sown"]["stats"])
    assert sown["memory_readers"] == [both["layers"] // 2 - 2]
    # the median decay of each scan, inside (0, 1); lambda of each core
    assert len(sown["ssm_decay_p50"]) == both["layers"] // 4 + 1
    assert all(0 < decay < 1 for decay in sown["ssm_decay_p50"])
    assert len(sown["diff_lambda"]) == both["layers"] // 2


# -- layer kind by layer kind ----------------------------------------------

def _layer_against_reference(kind, depth):
    """One ``DecoderLayer``'s mixer of ``kind`` at index ``depth`` and the
    reference's function on the same parameters and input."""
    from dlrover_tpu.models.llama import ATTENTION_OF

    cfg = _config(8)
    module = ATTENTION_OF[kind](cfg)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, SEQ, 32))
    memory = (jax.random.normal(key, (2, SEQ, 64)),
              jax.random.normal(jax.random.fold_in(key, 1), (2, SEQ, 4, 8)),
              jax.random.normal(jax.random.fold_in(key, 2), (2, SEQ, 4, 8)))
    mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))[None, None]
    args = (x, None, mask, memory, jnp.int32(depth))
    params = against_reference.perturbed(
        against_reference.init_params(module, *args), scale=0.05)
    m = _sizes(8)

    def got(p, x):
        return module.apply({"params": p}, x, *args[1:])

    def want(p, x):
        with jax.default_matmul_precision("highest"):
            if kind == "mamba":
                return reference.mamba(x, p, m)[0]
            if kind == "gmu":
                return reference.gated_memory(x, p, memory[0])
            return reference.differential_attention(
                x, p, m, depth, WINDOW if kind == "swa" else None,
                memory[1:] if kind == "xattn" else None)[0]

    return (against_reference.jitted(jax.value_and_grad(
        lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1)), params, x)
        for fn in (got, want))


@pytest.mark.parametrize("kind,depth", [
    ("mamba", 0), ("swa", 1), ("gqa", 5), ("gmu", 6), ("xattn", 7)])
def test_a_layers_mixer_equals_the_references(kind, depth):
    (got, got_grads), (want, want_grads) = _layer_against_reference(
        kind, depth)
    assert abs(float(got) - float(want)) < 1e-3
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=GRAD_ATOL)


def test_lambda_init_follows_the_layers_index():
    from dlrover_tpu.models.llama import diff_lambda_init

    for depth in (0, 1, 5, 31):
        assert abs(float(diff_lambda_init(depth))
                   - reference.lambda_init(depth)) < 1e-6


# -- the selective scan ----------------------------------------------------

def _scan_operands(B, S, channels, N, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (B, S, channels)),
            jax.nn.softplus(jax.random.normal(keys[1], (B, S, channels)) - 2),
            -jnp.exp(jax.random.normal(keys[2], (channels, N)) * 0.5),
            jax.random.normal(keys[3], (B, S, N)),
            jax.random.normal(keys[4], (B, S, N)),
            jax.random.normal(keys[5], (channels,)))


def _through_kernels(a, delta, A, Bm, Cm, D):
    return scan._scan_kernels(
        delta * a, delta, A, Bm, Cm, interpret=True) + D * a


def _value_and_grads(fn, operands):
    weights = jax.random.normal(
        jax.random.PRNGKey(9), operands[0].shape)
    return against_reference.jitted(jax.value_and_grad(
        lambda *o: jnp.sum(fn(*o) * weights), argnums=tuple(range(6))),
        *operands)


#: (batch, positions, channels, state columns): whole chunks at the kernels'
#: widths, one chunk, a ragged length, channels and a state they refuse
SCAN_SHAPES = {"two_chunks": (2, 128, 256, 16), "one_chunk": (1, 64, 128, 8),
               "ragged": (1, 100, 96, 4), "short": (2, 24, 64, 4)}


@pytest.fixture(scope="module", params=sorted(SCAN_SHAPES))
def scanned(request):
    shape = SCAN_SHAPES[request.param]
    operands = _scan_operands(*shape)
    out = dict(shape=shape, operands=operands,
               want=_value_and_grads(scan.selective_scan_recurrent, operands),
               got=_value_and_grads(scan.selective_scan, operands))
    if scan.scan_path("tpu", *shape[1:]) == "pallas":
        out["kernels"] = _value_and_grads(_through_kernels, operands)
    return out


@pytest.mark.parametrize("body", ["got", "kernels"])
def test_the_scan_equals_the_recurrence_forward_and_backward(scanned, body):
    if body not in scanned:
        assert scanned["shape"] in (SCAN_SHAPES["ragged"],
                                    SCAN_SHAPES["short"])
        return
    (value, grads), (want, want_grads) = scanned[body], scanned["want"]
    assert abs(float(value) - float(want)) < 1e-3 * max(
        1.0, abs(float(want)))
    for name, g, w in zip("a delta A B C D".split(), grads, want_grads):
        np.testing.assert_allclose(
            g, w, atol=SCAN_ATOL, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("backend,seq,channels,state,devices,path", [
    ("tpu", 16384, 5120, 16, 1, "pallas"), ("cpu", 16384, 5120, 16, 1, "jnp"),
    ("tpu", 16384, 5120, 16, 4, "jnp"), ("tpu", 100, 5120, 16, 1, "jnp"),
    ("tpu", 128, 96, 16, 1, "jnp"), ("tpu", 128, 128, 4, 1, "jnp")])
def test_which_body_walks_which_shape(
        backend, seq, channels, state, devices, path):
    assert scan.scan_path(backend, seq, channels, state, devices) == path


def _largest_array(jaxpr):
    largest = 0
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            largest = max(largest, int(np.prod(var.aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            largest = max(largest, _largest_array(sub))
    return largest


@pytest.mark.parametrize("body", ["jnp", "kernels"])
def test_no_pass_holds_the_states_history(body):
    """Neither pass of either body has an array of ``S x channels x N``
    elements: the ``jax.numpy`` body holds a chunk's (a quarter of that
    here), the kernels' largest is the lane-broadcast ``B`` (``S x N x
    128``: half of it at two lane groups of channels, a fortieth at the
    published 5120)."""
    B, S, channels, N = 1, 256, 256, 8
    operands = _scan_operands(B, S, channels, N)
    fn = scan.selective_scan if body == "jnp" else _through_kernels
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(fn(*o)), argnums=tuple(range(6))))(*operands)
    assert _largest_array(jaxpr.jaxpr) < S * channels * N
    assert _largest_array(jaxpr.jaxpr) >= scan.CHUNK * channels * N


def test_the_kernels_results_are_kept_by_name():
    """A rematerialised layer keeps ``y`` and the chunks' start states: the
    names are on the forward rule's results."""
    from dlrover_tpu.ops.pallas import kept

    operands = _scan_operands(1, 64, 128, 8)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(_through_kernels(*o))))(*operands))
    assert text.count(f"name={kept.SSM_SCAN}") == 2
    assert kept.SSM_SCAN in kept.NAMES


# -- the normal path -------------------------------------------------------

def test_the_model_trains_through_the_trainer():
    cfg = _config(8, dtype=jnp.bfloat16)
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(3e-3), mesh)
    ids = against_reference.token_ids(4, SEQ + 1, VOCAB)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    losses = []
    for _ in range(6):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    sown = _sown_by_name(metrics["stats"])
    # a value a Mamba layer and a differential layer, wherever it stands
    assert len(sown["ssm_decay_p50"]) == 3 and len(sown["diff_lambda"]) == 4


def test_the_state_through_a_memory_save_and_restore(tmp_path):
    """The checkpoint path sees the new tree: every leaf of a sharded
    state, parameters and moments, comes back bit for bit."""
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        StorageType,
    )

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    trainer = Trainer(LlamaForCausalLM(_config(8)), optax.adamw(1e-2), mesh)
    ids = against_reference.token_ids(8, 17, VOCAB)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    state, _ = trainer.train_step(state, trainer.shard_batch(batch))
    ckpt = Checkpointer(str(tmp_path), scope=f"t{uuid.uuid4().hex[:8]}")
    try:
        ckpt.save_checkpoint(7, state, StorageType.MEMORY)
        restored, step = ckpt.load_checkpoint(
            jax.eval_shape(lambda s: s, state), trainer.state_shardings)
    finally:
        ckpt.close()
    assert step == 7
    before, after = jax.tree.leaves(state), jax.tree.leaves(restored)
    assert len(before) == len(after) > 150
    for x, y in zip(before, after):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    inner = nn.meta.unbox(restored.params)["memory"]["mamba_0"]["layer"][
        "attn"]["in_proj"]["kernel"]
    assert inner.shape == (32, 128) and "tp" in str(inner.sharding.spec)


def test_the_other_families_fields_keep_their_defaults():
    """No other family gains a field it must set."""
    import dataclasses

    defaults = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
    assert defaults["norm"] == "rms" and defaults["tie_embeddings"] is False
    assert defaults["diff_attention"] is False
    assert defaults["attention_bias"] is False
    assert defaults["mamba_state"] == 0 and defaults["memory_layers"] == ()
    assert defaults["cross_pattern"] == () and defaults["cross_periods"] == 0
    assert not LlamaConfig.tiny().hybrid
