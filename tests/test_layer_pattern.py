"""Layers of two kinds in one stack (``LlamaConfig.layer_pattern``): a scan
over periods with a scan over each run of equal layers inside, parameters
stacked by run.  The patterned stack equals the same layers unrolled;
``num_params()`` equals the tree's count; an empty pattern gives the tree the
program had before, name for name; a patterned training state goes through a
Flash Checkpoint memory save and restore bit for bit."""

import dataclasses
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.llama import (
    DecoderLayer,
    LlamaConfig,
    LlamaForCausalLM,
    RMSNorm,
)
from dlrover_tpu.models.moe import MoELlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
from dlrover_tpu.trainer.train import Trainer
from against_reference import init_params, perturbed, token_ids

PATTERN = ("gqa", "kda", "kda", "kda")
SEQ = 48


def _config(**changes):
    fields = dict(
        num_layers=8, layer_pattern=PATTERN, use_rope=False, attn_gate=True,
        kda_heads=2, kda_head_dim=16, kda_chunk=16, dtype=jnp.float32)
    fields.update(changes)
    return LlamaConfig.tiny(**fields)


def _ids(batch=2, seq=SEQ, seed=0):
    return jnp.asarray(token_ids(batch, seq, seed=seed))


@pytest.fixture(scope="module")
def made():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    ids = _ids()
    return cfg, model, ids, perturbed(init_params(model, ids), seed=7)


def test_layer_runs_of_a_pattern():
    assert _config().layer_runs() == [("gqa_0", "gqa", 1), ("kda_1", "kda", 3)]
    cfg = _config(layer_pattern=("kda", "gqa", "gqa", "kda"), num_layers=4)
    assert cfg.layer_runs() == [
        ("kda_0", "kda", 1), ("gqa_1", "gqa", 2), ("kda_2", "kda", 1)]
    assert LlamaConfig.tiny().layer_runs() == []
    # a prefix stands once before the periods; a dense entry names its run
    cfg = _config(layer_prefix=("kda:dense", "kda:dense", "gqa"),
                  layer_pattern=("kda", "mla"), num_layers=7,
                  dense_intermediate_size=96, mla_kv_rank=24)
    assert cfg.layer_runs(cfg.layer_prefix) == [
        ("kda_dense_0", "kda:dense", 2), ("gqa_1", "gqa", 1)]
    assert cfg.layer_runs() == [("kda_0", "kda", 1), ("mla_1", "mla", 1)]
    assert cfg.periods == 2


@pytest.mark.parametrize("changes,match", [
    ({"layer_pattern": ("gqa", "lstm")}, "kinds of"),
    ({"num_layers": 6}, "whole number of periods"),
    ({"kda_heads": 0}, "kda_heads"),
    ({"layer_pattern": ("gqa", "mla"), "num_layers": 2}, "mla_kv_rank"),
    ({"layer_pattern": ("gqa:dense", "kda"), "num_layers": 2},
     "dense_intermediate_size"),
    ({"layer_pattern": ("gqa:sparse", "kda"), "num_layers": 2}, "entries"),
    ({"layer_prefix": ("kda",), "num_layers": 8}, "less the prefix"),
    ({"layer_prefix": ("kda",), "layer_pattern": (), "num_layers": 1},
     "entries"),
])
def test_what_the_config_refuses(changes, match):
    with pytest.raises(ValueError, match=match):
        _config(**changes)


def test_parameters_are_stacked_by_run(made):
    cfg, _, _, params = made
    assert set(params["layers"]) == {"gqa_0", "kda_1"}
    gqa, kda = (params["layers"][name]["layer"] for name in ("gqa_0", "kda_1"))
    # [periods, run, ...]
    assert gqa["attn"]["gate_proj"]["kernel"].shape == (2, 1, 64, 4, 16)
    assert kda["attn"]["q_conv"].shape == (2, 3, 4, 2, 16)
    assert kda["attn"]["A_log"].shape == (2, 3, 2)
    assert "gate_proj" not in kda["attn"] and "q_conv" not in gqa["attn"]
    assert set(gqa) == set(kda) == {
        "attn", "input_norm", "mlp", "post_attn_norm"}


def test_patterned_stack_equals_the_same_layers_unrolled(made):
    """Each layer applied by hand in the stack's order (period by period,
    run by run) with its slice of the stacked parameters."""
    cfg, model, ids, params = made
    got = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    positions = jnp.broadcast_to(jnp.arange(SEQ), ids.shape)
    mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))[None, None]
    kinds = []

    def unrolled(params):
        x = params["embed_tokens"][ids]
        for period in range(cfg.num_layers // len(PATTERN)):
            for name, kind, length in cfg.layer_runs():
                for i in range(length):
                    layer = jax.tree.map(lambda t: t[period, i],
                                         params["layers"][name]["layer"])
                    x = DecoderLayer(cfg, kind).apply(
                        {"params": layer}, x, positions, mask)
                    kinds.append(kind)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype).apply(
            {"params": params["final_norm"]}, x)
        return x @ params["lm_head"]["kernel"]

    want = jax.jit(unrolled)(params)
    assert tuple(kinds) == PATTERN * 2
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_a_kda_layer_is_not_a_gqa_layer(made):
    """The same parameters cannot be read by the other kind: the kinds
    differ in what they hold, not only in what they compute."""
    cfg, _, ids, params = made
    x = params["embed_tokens"][ids]
    layer = jax.tree.map(lambda t: t[0, 0], params["layers"]["kda_1"]["layer"])
    with pytest.raises(Exception):
        DecoderLayer(cfg, "gqa").apply({"params": layer}, x, None, None)


@pytest.mark.parametrize("make", [
    lambda: _config(),
    lambda: _config(num_layers=4),
    lambda: _config(layer_pattern=("kda", "gqa"), num_layers=2, attn_gate=False),
    lambda: MoELlamaConfig.tiny_moe(
        num_layers=4, layer_pattern=PATTERN, kda_heads=2, kda_head_dim=16,
        attn_gate=True, use_rope=False, router_scores="sigmoid",
        shared_experts=1, num_experts=8, experts_held=2),
    lambda: LlamaConfig.tiny(),
    lambda: LlamaConfig.tiny(attn_gate=True),
    lambda: MoELlamaConfig.tiny_moe(shared_experts=2,
                                    shared_intermediate_size=48),
], ids=["two_periods", "one_period", "kda_first", "moe_share", "no_pattern",
        "gated", "two_shared"])
def test_num_params_equals_the_trees_count(make):
    cfg = make()
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), _ids())
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree.leaves(nn.meta.unbox(shapes["params"])))
    assert model.num_params() == count


def test_an_empty_pattern_gives_todays_tree_name_for_name():
    """The tree of the four Llama-code cells: their checkpoints and their
    ``condition`` rules read these names."""
    model = LlamaForCausalLM(LlamaConfig.tiny())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), _ids())
    paths = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf in
             jax.tree_util.tree_leaves_with_path(
                 nn.meta.unbox(shapes["params"]))}
    assert paths == {
        "embed_tokens": (256, 64),
        "final_norm/scale": (64,),
        "lm_head/kernel": (64, 256),
        "layers/layer/input_norm/scale": (2, 64),
        "layers/layer/post_attn_norm/scale": (2, 64),
        "layers/layer/attn/q_proj/kernel": (2, 64, 4, 16),
        "layers/layer/attn/k_proj/kernel": (2, 64, 2, 16),
        "layers/layer/attn/v_proj/kernel": (2, 64, 2, 16),
        "layers/layer/attn/o_proj/kernel": (2, 4, 16, 64),
        "layers/layer/mlp/gate_proj/kernel": (2, 64, 128),
        "layers/layer/mlp/up_proj/kernel": (2, 64, 128),
        "layers/layer/mlp/down_proj/kernel": (2, 128, 64),
    }
    # and the axes' logical names: one ``layers`` in front, as ever
    boxed = jax.tree.leaves(
        shapes["params"], is_leaf=lambda x: isinstance(x, nn.Partitioned))
    assert {b.names[0] for b in boxed if len(b.names) > 2} == {"layers"}


def test_no_mask_where_no_layer_reads_one():
    """The ``[S, S]`` mask is built only for the reference core: not under
    the kernel, not for a stack of delta-rule layers alone."""
    ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32)

    def lowered(cfg):
        model = LlamaForCausalLM(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                jnp.zeros((1, 1024), jnp.int32))
        return jax.jit(model.apply).lower(
            {"params": nn.meta.unbox(shapes["params"])}, ids).as_text()

    assert "1024x1024xi1" in lowered(LlamaConfig.tiny(max_seq_len=1024))
    only_kda = _config(layer_pattern=("kda",), num_layers=2)
    assert "1024x1024" not in lowered(only_kda)


def test_patterned_model_trains():
    """A few steps through ``Trainer`` on one device: the loss falls, the
    counters a delta-rule layer sows come back a value a layer."""
    cfg = _config(num_layers=4, dtype=jnp.bfloat16)
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(3e-3), mesh)
    ids = np.asarray(_ids(4, SEQ + 1))
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    losses = []
    for _ in range(6):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    sown = {path[-2].key: np.asarray(leaf).ravel() for path, leaf in
            jax.tree_util.tree_leaves_with_path(metrics["stats"])}
    assert sown["kda_beta_over_one_share"].shape == (3,)
    assert (0.05 < sown["kda_beta_over_one_share"]).all()
    assert (sown["kda_decay_half_life"] > 0.3).all()


def test_patterned_state_through_a_memory_save_and_restore(tmp_path):
    """The checkpoint path sees the new tree: every leaf of a sharded
    patterned state, parameters and moments, comes back bit for bit."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    cfg = _config(num_layers=4)
    trainer = Trainer(LlamaForCausalLM(cfg), optax.adamw(1e-2), mesh)
    ids = np.asarray(_ids(8, 17))
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
    assert len(before) == len(after) > 40
    for x, y in zip(before, after):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the run's parameters kept their sharding: heads over tp
    conv = nn.meta.unbox(restored.params)["layers"]["kda_1"]["layer"][
        "attn"]["q_proj"]["kernel"]
    assert conv.shape == (1, 3, 64, 2, 16)
    assert "tp" in str(conv.sharding.spec)


#: sha256 (16 hex digits) of the jaxpr of ``value_and_grad`` of a loss of
#: the model on zeros at [2, 48] ids, addresses struck out, taken at the
#: commit BEFORE the pattern learnt its prefix, its dense entries and its
#: third kind (PR 47's tree, in a clone beside this one, PR 48): the program
#: that computes a loss is the old one instruction for instruction, so the
#: loss is the old one to the bit on whatever machine runs it.  The routed
#: model's was taken again at PR 49 (35dbeac905ed37c7 until then): its router
#: names its logits, its choice and its count of rows for a rematerialised
#: layer to keep (``kept.MOE_ROUTE``: three ``name`` equations a layer) and
#: reads its weights at the kept choice by compare and sum (``moe._at_kept``)
#: where it gathered them; and again at PR 50 (e58e89a0a600250d until then):
#: the two sums by token are told which rows hold an assignment
#: (``moe._rows_of`` carries ``live``), at these shapes they gather the
#: slots as they did; and again at PR 64 (b522af41a96bbbe9 until then, and
#: still with ``moe.ladder``'s new rule switched off): this model has 3
#: experts a token and HOLDS 2, so the ladder's worst case is 2 rows a token
#: and no longer 3 (extents (128, 192) where they were (128, 192, 288)); no
#: cell of the benchmark holds fewer experts than a token takes
BEFORE = {"empty": "483fc5aaffc3fc24", "solar": "def5410a2c443246",
          "solar_routed": "55d34056c602b346"}


def _before_and_now(which):
    from dlrover_tpu.models.moe import MoELlamaConfig

    if which == "empty":
        return LlamaConfig.tiny()
    if which == "solar":
        return _config()
    return MoELlamaConfig.tiny_moe(
        num_layers=4, layer_pattern=PATTERN, use_rope=False, attn_gate=True,
        kda_heads=2, kda_head_dim=16, kda_chunk=16, dtype=jnp.float32,
        num_experts=8, top_k=3, norm_topk_prob=True, router_scores="sigmoid",
        shared_experts=1, experts_held=2)


@pytest.mark.parametrize("which", sorted(BEFORE))
def test_todays_patterns_compute_their_losses_by_the_same_program(which):
    import hashlib
    import re

    model = LlamaForCausalLM(_before_and_now(which))
    ids = jnp.zeros((2, SEQ), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    assert "buffers" not in shapes              # none where none is asked
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          nn.meta.unbox(shapes["params"]))

    def loss(p):
        logits, sown = model.apply(
            {"params": p}, ids, mutable=["losses", "stats"])
        return logits.astype(jnp.float32).mean() + sum(
            jnp.sum(t) for t in jax.tree.leaves(sown.get("losses", {})))

    text = re.sub(r"0x[0-9a-f]+", "0x",
                  str(jax.make_jaxpr(jax.value_and_grad(loss))(params)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == BEFORE[which]


def test_solars_pattern_gives_todays_tree_name_for_name():
    """The tree of ``solaropen2_250b_1of32.steady``: its ``condition`` rule
    and a checkpoint of it read these names."""
    shapes = jax.eval_shape(LlamaForCausalLM(_config()).init,
                            jax.random.PRNGKey(0), _ids())
    paths = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf in
             jax.tree_util.tree_leaves_with_path(
                 nn.meta.unbox(shapes["params"]))}
    both = {"input_norm/scale": (64,), "post_attn_norm/scale": (64,),
            "mlp/gate_proj/kernel": (64, 128), "mlp/up_proj/kernel": (64, 128),
            "mlp/down_proj/kernel": (128, 64), "attn/o_proj/kernel": None}
    gqa = {"attn/q_proj/kernel": (64, 4, 16), "attn/k_proj/kernel": (64, 2, 16),
           "attn/v_proj/kernel": (64, 2, 16),
           "attn/gate_proj/kernel": (64, 4, 16),
           "attn/o_proj/kernel": (4, 16, 64)}
    kda = {**{f"attn/{n}_proj/kernel": (64, 2, 16) for n in "qkv"},
           **{f"attn/{n}_conv": (4, 2, 16) for n in "qkv"},
           **{f"attn/{n}_down/kernel": (64, 16) for n in "fg"},
           **{f"attn/{n}_up/kernel": (16, 2, 16) for n in "fg"},
           "attn/beta_proj/kernel": (64, 2), "attn/A_log": (2,),
           "attn/dt_bias": (2, 16), "attn/o_norm/scale": (16,),
           "attn/o_proj/kernel": (2, 16, 64)}
    want = {"embed_tokens": (256, 64), "final_norm/scale": (64,),
            "lm_head/kernel": (64, 256)}
    for run, length, own in (("gqa_0", 1, gqa), ("kda_1", 3, kda)):
        for name, shape in {**both, **own}.items():
            want[f"layers/{run}/layer/{name}"] = (2, length) + shape
    assert paths == want


def test_a_prefix_is_the_same_layers_before_the_periods(made):
    """Two periods of the pattern, or the first period's layers as a prefix
    and one period after it: one stack of eight layers either way."""
    cfg, model, ids, params = made
    as_prefix = LlamaForCausalLM(dataclasses.replace(
        cfg, layer_prefix=PATTERN))
    layers = params["layers"]
    moved = {**params,
             "prefix": jax.tree.map(lambda t: t[0], layers),
             "layers": jax.tree.map(lambda t: t[1:], layers)}
    shapes = jax.eval_shape(as_prefix.init, jax.random.PRNGKey(0), ids)
    assert jax.tree.map(lambda t: t.shape, moved) == jax.tree.map(
        lambda s: s.shape, nn.meta.unbox(shapes["params"]))
    assert as_prefix.num_params() == model.num_params()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(model.apply)({"params": params}, ids)
        got = jax.jit(as_prefix.apply)({"params": moved}, ids)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_a_dense_entry_has_the_dense_feed_forward():
    from dlrover_tpu.models.moe import MoELlamaConfig

    cfg = MoELlamaConfig.tiny_moe(
        num_layers=3, layer_prefix=("gqa:dense",), layer_pattern=("gqa",),
        dense_intermediate_size=96, num_experts=4, top_k=2)
    model = LlamaForCausalLM(cfg)
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _ids())["params"])
    dense = shapes["prefix"]["gqa_dense_0"]["layer"]["mlp"]
    assert set(dense) == {"gate_proj", "up_proj", "down_proj"}
    assert dense["gate_proj"]["kernel"].shape == (1, 64, 96)
    routed = shapes["layers"]["gqa_0"]["layer"]["mlp"]
    assert routed["gate_proj"].shape == (2, 1, 4, 64, 128)
    assert "router" in routed
    assert model.num_params() == sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_dataclass_fields_keep_their_defaults():
    """What the other cells' families do not name keeps today's behaviour."""
    defaults = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
    assert defaults["layer_pattern"] == () and defaults["use_rope"] is True
    assert defaults["attn_gate"] is False and defaults["kda_heads"] == 0
    assert defaults["layer_prefix"] == () and defaults["mla_kv_rank"] == 0
    assert defaults["kda_full_rank_gates"] is False
    assert defaults["kda_decay_lower_bound"] == 0.0
    assert defaults["kda_neg_eigval"] is True
    # what PR 64 added keeps the older programs as they were
    assert defaults["layer_suffix"] == () and defaults["mamba2_heads"] == 0
    assert defaults["mlp_matrices"] == 3
    assert defaults["mlp_activation"] == "silu"
    moe = {f.name: f.default for f in dataclasses.fields(MoELlamaConfig)}
    assert moe["moe_latent_size"] == 0
    # and what PR 66 added: no epsilon under the renormalised weights
    assert moe["norm_topk_eps"] == 0.0 and defaults["conv_taps"] == 3


def _one_branch(**changes):
    fields = dict(
        num_layers=7, layer_pattern=("ffn:dense", "mamba2:alone"),
        layer_prefix=("gqa:alone",), layer_suffix=("kda:alone", "ffn:dense"),
        dense_intermediate_size=96, mamba2_heads=4, mamba2_head_dim=8,
        mamba2_groups=2, mamba2_state=16, mamba2_chunk=16, kda_heads=2,
        kda_head_dim=16, kda_chunk=16, dtype=jnp.float32)
    fields.update(changes)
    return LlamaConfig.tiny(**fields)


def test_a_one_branch_entrys_tree_has_no_dead_leaf():
    """A mixer with no feed-forward and a feed-forward with no mixer: one
    norm and one branch a layer, in the prefix, the periods and the suffix;
    ``num_params()`` counts what the tree holds."""
    cfg = _one_branch()
    assert cfg.periods == 2
    assert cfg.layer_runs(cfg.layer_suffix) == [
        ("kda_alone_0", "kda:alone", 1), ("ffn_dense_1", "ffn:dense", 1)]
    assert cfg.layer_kinds() == (
        ("gqa:alone",) + ("ffn:dense", "mamba2:alone") * 2
        + ("kda:alone", "ffn:dense"))
    model = LlamaForCausalLM(cfg)
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _ids())["params"])
    assert set(shapes) == {"embed_tokens", "prefix", "layers", "suffix",
                           "final_norm", "lm_head"}
    for part, run, branch in (
            ("prefix", "gqa_alone_0", "attn"),
            ("layers", "ffn_dense_0", "mlp"),
            ("layers", "mamba2_alone_1", "attn"),
            ("suffix", "kda_alone_0", "attn"),
            ("suffix", "ffn_dense_1", "mlp")):
        assert set(shapes[part][run]["layer"]) == {"input_norm", branch}
    dense = shapes["layers"]["ffn_dense_0"]["layer"]["mlp"]
    assert dense["gate_proj"]["kernel"].shape == (2, 1, 64, 96)
    assert model.num_params() == sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_one_branch_layers_are_the_same_layers_unrolled():
    """The stack with a prefix, periods and a suffix of one-branch layers
    equals ``x + branch(norm(x))`` layer after layer."""
    cfg = _one_branch()
    model = LlamaForCausalLM(cfg)
    ids = _ids()
    params = perturbed(init_params(model, ids), seed=9)
    S = ids.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), ids.shape)
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]

    def unrolled(params):
        x = params["embed_tokens"][ids]
        at = lambda tree, *i: jax.tree.map(lambda t: t[i], tree)  # noqa: E731
        layers = [("gqa:alone", at(params["prefix"]["gqa_alone_0"], 0))]
        for period in range(2):
            layers += [
                ("ffn:dense", at(params["layers"]["ffn_dense_0"], period, 0)),
                ("mamba2:alone",
                 at(params["layers"]["mamba2_alone_1"], period, 0))]
        layers += [("kda:alone", at(params["suffix"]["kda_alone_0"], 0)),
                   ("ffn:dense", at(params["suffix"]["ffn_dense_1"], 0))]
        for entry, p in layers:
            x = DecoderLayer(cfg, entry).apply(
                {"params": p["layer"]}, x, positions, mask)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype).apply(
            {"params": params["final_norm"]}, x)
        return x @ params["lm_head"]["kernel"]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(unrolled)(params)
        got = jax.jit(model.apply)({"params": params}, ids)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("changes,match", [
    ({"layer_suffix": ("ffn:alone",)}, "entries"),
    ({"layer_suffix": ("mamba2",), "mamba2_heads": 0, "num_layers": 6},
     "mamba2_heads"),
    ({"num_layers": 8}, "and the suffix"),
    ({"mlp_matrices": 4}, "mlp_matrices"),
])
def test_what_the_config_refuses_of_one_branch_layers(changes, match):
    with pytest.raises(ValueError, match=match):
        _one_branch(**changes)



def _conv(**changes):
    """The tenth kind in small, as LFM2 lays it out: a dense ``conv`` layer
    once, then two periods of a softmax layer and a run of THREE ``conv``
    layers; per-head q/k norms and a tied head beside a pattern."""
    fields = dict(
        num_layers=9, layer_prefix=("conv:dense",),
        layer_pattern=("gqa", "conv", "conv", "conv"),
        dense_intermediate_size=96, qk_norm="head", tie_embeddings=True,
        dtype=jnp.float32)
    fields.update(changes)
    return LlamaConfig.tiny(**fields)


@pytest.mark.parametrize("changes, runs, tree", [
    # the ``conv`` entry alone: one run, every layer of it
    ({"num_layers": 3, "layer_prefix": (), "layer_pattern": ("conv",)},
     [("conv_0", "conv", 1)], {"layers/conv_0": (3, 1)}),
    # ``conv:dense`` standing once before periods of its own kind
    ({"num_layers": 3, "layer_pattern": ("conv",)},
     [("conv_0", "conv", 1)],
     {"prefix/conv_dense_0": (1,), "layers/conv_0": (2, 1)}),
    # inside a period beside ``gqa``: a run of one and a run of three
    ({}, [("gqa_0", "gqa", 1), ("conv_1", "conv", 3)],
     {"prefix/conv_dense_0": (1,), "layers/gqa_0": (2, 1),
      "layers/conv_1": (2, 3)})],
    ids=["alone", "dense_prefix", "beside_gqa"])
def test_a_conv_entrys_runs_and_tree(changes, runs, tree):
    cfg = _conv(**changes)
    assert cfg.layer_runs() == runs
    model = LlamaForCausalLM(cfg)
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _ids())["params"])
    assert "lm_head" not in shapes              # the head is the table
    for path, lead in tree.items():
        top, run = path.split("/")
        layer = shapes[top][run]["layer"]
        if "conv" in run:   # no bias, no norm, nothing else in the mixer
            assert {name: leaf.shape for name, leaf in
                    jax.tree_util.tree_leaves_with_path(layer["attn"])} == {
                (jax.tree_util.DictKey("in_proj"),
                 jax.tree_util.DictKey("kernel")): lead + (64, 192),
                (jax.tree_util.DictKey("conv_weight"),): lead + (3, 64),
                (jax.tree_util.DictKey("out_proj"),
                 jax.tree_util.DictKey("kernel")): lead + (64, 64)}
        else:
            assert layer["attn"]["q_norm"]["scale"].shape == lead + (16,)
        width = 96 if "dense" in run else 128
        assert layer["mlp"]["gate_proj"]["kernel"].shape == lead + (64, width)
    assert model.num_params() == sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_what_the_config_refuses_of_conv_layers():
    with pytest.raises(ValueError, match="conv_taps"):
        _conv(conv_taps=0)
    with pytest.raises(ValueError, match="dense_intermediate_size"):
        _conv(dense_intermediate_size=0)
