"""Attention over a window's own keys and learned summaries of every
earlier window's chunks (``ops/attention.py::eva_attention``), the norm's
unit offset, the float32 residual stream and the eight prediction heads
(``models/llama.py``), against the plain reference
(``models/evabyte_reference.py``) at small sizes on the CPU in float32, and
through ``Trainer``."""

import collections

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import evabyte_reference as reference
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
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
from shared_memo import shared_memo

SEQ, WINDOW, CHUNK, HEADS, DIM = 32, 8, 2, 3, 4


def _operands(seed=0, seq=SEQ, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, seq, HEADS, DIM))
               for key in keys[:3])
    mu, phi = (jax.random.normal(key, (HEADS, DIM)) for key in keys[3:])
    return q, 1.5 * k, v, mu, phi


def _by_the_equations(q, k, v, mu, phi, window=WINDOW, chunk=CHUNK):
    """Every query against every key and every summary at once, the sets
    ``E_t`` and ``C_t`` as two whole masks (fine at this size): ``(out, the
    mass on summaries [B, H, S])``."""
    S, D = q.shape[1], q.shape[-1]
    pooled_k, pooled_v, _ = reference.summaries(k, v, mu, phi, chunk)
    t = np.arange(S)
    own_window = (t[:, None] // window == t[None, :] // window) & (
        t[None, :] <= t[:, None])
    earlier = np.arange(S // chunk)[None, :] < (
        window // chunk) * (t[:, None] // window)
    scores = jnp.concatenate([
        jnp.where(own_window, jnp.einsum("bqhd,bkhd->bhqk", q, k), -jnp.inf),
        jnp.where(earlier, jnp.einsum("bqhd,bjhd->bhqj", q, pooled_k), -jnp.inf),
    ], axis=-1) * D ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                     jnp.concatenate([v, pooled_v], axis=1))
    return out, probs[..., S:].sum(-1)


def test_eva_attention_is_the_equations():
    q, k, v, mu, phi = _operands()
    got, share, weight = jitted(
        lambda *a: ops.eva_attention(*a, WINDOW, CHUNK), q, k, v, mu, phi)
    want, mass = jitted(_by_the_equations, q, k, v, mu, phi)
    # float32 on both sides, sums in another order
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(share, mass[..., WINDOW:].mean(), rtol=1e-5)
    largest = reference.summaries(k, v, mu, phi, CHUNK)[2]
    np.testing.assert_allclose(weight, largest.mean(), rtol=1e-6)
    # the summaries decide something at this size, and the pooling is no mean
    assert 0.2 < float(share) < 0.8 and float(weight) > 1.2 / CHUNK


@shared_memo
def _gradients():
    """(eva_attention's, the equations') gradients of one scalar of the
    result with respect to all five operands."""
    operands = _operands(1)
    probe = jax.random.normal(jax.random.PRNGKey(9), operands[0].shape)

    def through(fn):
        def scalar(*args):
            return jnp.sum(fn(*args)[0] * probe)
        return jitted(jax.grad(scalar, argnums=range(5)), *operands)

    return (through(lambda *a: ops.eva_attention(*a, WINDOW, CHUNK)),
            through(_by_the_equations))


@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "mu", "phi"])
def test_eva_attention_gradients(wrt):
    """Through the exact part and through the pooling, to every operand."""
    got, want = (grads[wrt] for grads in _gradients())
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_eva_attention_one_window_is_causal_attention():
    q, k, v, mu, phi = _operands(2, seq=WINDOW)
    causal = jnp.tril(jnp.ones((WINDOW, WINDOW), bool))[None, None]
    want = jax.jit(ops.reference_attention)(q, k, v, causal)
    for window in (WINDOW, 4 * WINDOW):     # a shorter sequence is one window
        got, share, _ = jax.jit(lambda *a: ops.eva_attention(
            *a, window, CHUNK))(q, k, v, mu, phi)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
        assert float(share) == 0.0


def test_eva_attention_first_window_ignores_the_pooling_vectors():
    q, k, v, mu, phi = _operands(3)
    whole = jax.jit(lambda mu_, phi_: ops.eva_attention(
        q, k, v, mu_, phi_, WINDOW, CHUNK)[0])
    first = lambda mu_, phi_: whole(mu_, phi_)[:, :WINDOW]  # noqa: E731
    later = lambda mu_, phi_: whole(mu_, phi_)[:, WINDOW:]  # noqa: E731
    np.testing.assert_array_equal(first(mu, phi), first(-mu, 2 * phi))
    assert float(jnp.abs(later(mu, phi) - later(-mu, phi)).max()) > 1e-2
    assert float(jnp.abs(later(mu, phi) - later(mu, 2 * phi)).max()) > 1e-2
    grads = jax.jit(jax.grad(
        lambda m_, p_: first(m_, p_).sum(), argnums=(0, 1)))(mu, phi)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_eva_attention_refuses_what_it_cannot_window():
    q, k, v, mu, phi = _operands(4, seq=SEQ + CHUNK)
    with pytest.raises(ValueError, match="not a multiple of the window"):
        ops.eva_attention(q, k, v, mu, phi, WINDOW, CHUNK)
    q, k, v, mu, phi = _operands(4)
    with pytest.raises(ValueError, match="multiple"):
        ops.eva_attention(q, k, v, mu, phi, WINDOW, 3)
    with pytest.raises(ValueError, match="a key head a query head"):
        ops.eva_attention(q, k[:, :, :1], v[:, :, :1], mu, phi, WINDOW, CHUNK)
    with pytest.raises(ValueError, match="eva_window needs"):
        LlamaConfig.tiny(eva_window=WINDOW, eva_chunk=CHUNK)   # GQA
    with pytest.raises(ValueError, match="eva_window needs"):
        LlamaConfig.tiny(num_kv_heads=4, eva_window=WINDOW, eva_chunk=3)


# --------------------------------------------------------------------------
# the model: norm offset, float32 residual, eight heads, against the reference
# --------------------------------------------------------------------------

def _config(**kw):
    fields = dict(num_kv_heads=4, eva_window=WINDOW, eva_chunk=CHUNK,
                  norm_unit_offset=True, residual_dtype=jnp.float32,
                  pred_heads=8, max_seq_len=SEQ, dtype=jnp.float32,
                  rope_theta=100000.0)
    fields.update(kw)
    return LlamaConfig.tiny(**fields)


def _published(cfg):
    """``cfg`` as the reference reads it (published key names)."""
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "window_size": cfg.eva_window, "chunk_size": cfg.eva_chunk,
            "num_pred_heads": cfg.pred_heads}


def _batch(cfg, rows=2, seed=0):
    inputs, labels = inputs_and_labels(rows, SEQ, cfg.vocab_size, seed)
    return {"input_ids": inputs, "labels": labels}


#: what the fixture computed once: the system's ``((loss, (token losses,
#: sown)), gradients)``, the reference's dictionary and gradients
Made = collections.namedtuple(
    "Made", "cfg model batch params got want want_grads")


@pytest.fixture(scope="module")
def made():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    batch = _batch(cfg)
    params = perturbed(init_params(model, batch["input_ids"]))
    m = _published(cfg)
    want, want_grads = reference_loss_and_gradients(
        lambda p: reference.forward(
            p, batch["input_ids"], batch["labels"], m), params)
    got = system(model, params, batch["input_ids"], batch["labels"])
    return Made(cfg, model, batch, params, got, want, want_grads)


def test_eva_model_agrees_with_the_reference(made):
    cfg, model, batch, params, want = (
        made.cfg, made.model, made.batch, made.params, made.want)
    (loss, (token, sown)), _ = made.got
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(token, want["token_losses"], rtol=0, atol=2e-5)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-6)
    np.testing.assert_allclose(
        sown["losses"]["multi_byte"][0], want["multi_byte"], rtol=1e-6)
    attn = sown["stats"]["layers"]["layer"]["attn"]
    np.testing.assert_allclose(attn["eva_summary_mass_share"][0],
                               want["summary_mass_share"], rtol=1e-5)
    np.testing.assert_allclose(attn["eva_pool_weight_max"][0],
                               want["pool_weight_max"], rtol=1e-5)
    np.testing.assert_allclose(sown["stats"]["multi_byte_loss"][0],
                               want["multi_byte"], rtol=1e-6)
    # seven further heads, each near log(vocab) on random weights
    assert 7 * 4.0 < float(want["multi_byte"]) < 7 * 9.0
    logits = jitted(lambda p: model.apply({"params": p}, batch["input_ids"]),
                    params)
    assert logits.shape == (2, SEQ, cfg.vocab_size)


def test_eva_model_gradients_agree_with_the_reference(made):
    """All eight heads' loss, through the pooling into ``adaptive_mu_k`` and
    ``adaptive_phi``, through the float32 residual and the norms' offsets."""
    _, got = made.got
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, leaf in jax.tree_util.tree_leaves_with_path(made.want_grads):
        scale = float(jnp.abs(leaf).max())
        assert scale > 1e-4, path            # every leaf is reached
        # float32 on both sides: 1e-4 of the leaf's largest gradient
        np.testing.assert_allclose(flat_got[path], leaf, rtol=0,
                                   atol=1e-4 * scale, err_msg=str(path))


@pytest.mark.parametrize("what", [
    "no_unit_offset", "mean_pooling", "one_window", "first_head_only",
    "another_chunk"])
def test_eva_departure_is_far_outside_float32_agreement(made, what):
    batch, params, want = made.batch, made.params, made.want
    changed = {"no_unit_offset": {"norm_unit_offset": False},
               "one_window": {"eva_window": SEQ},
               "another_chunk": {"eva_chunk": 2 * CHUNK},
               "first_head_only": {}, "mean_pooling": {}}[what]
    wrong = LlamaForCausalLM(_config(**changed))
    tree = params
    if what == "mean_pooling":
        attn = {**params["layers"]["layer"]["attn"]}
        attn["adaptive_mu_k"] = attn["adaptive_phi"] = jnp.zeros_like(
            attn["adaptive_phi"])
        tree = {**params, "layers": {"layer": {
            **params["layers"]["layer"], "attn": attn}}}
    _, (token, _) = system_loss(
        wrong, tree, batch["input_ids"], batch["labels"])
    if what == "first_head_only":
        assert abs(float(token.mean()) - float(want["loss"])) > 1.0
    else:
        assert float(jnp.abs(token - want["token_losses"]).max()) > 1e-2


def test_eva_num_params_at_the_published_widths():
    """Two vectors a head and eight head blocks counted, held to the created
    state's count without building it: 4 layers of EvaByte."""
    cfg = LlamaConfig(
        vocab_size=320, hidden_size=4096, intermediate_size=11008,
        num_layers=4, num_heads=32, num_kv_heads=32, head_dim=128,
        max_seq_len=4096, eva_window=2048, eva_chunk=16, pred_heads=8,
        norm_unit_offset=True, residual_dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 4096), jnp.int32))
    created = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree.leaves(nn.meta.unbox(shapes["params"])))
    by_hand = 4 * (67_108_864 + 135_266_304 + 16_384) + (
        1_310_720 + 10_485_760 + 4_096)
    assert created == model.num_params() == by_hand == 821_366_784


def test_eva_model_through_the_trainer(made, monkeypatch):
    """The normal path: ``Trainer`` adds the sown multi-byte term to its own
    cross entropy, carries the counters in ``metrics["stats"]``, and the
    trace of the step writes which attention ran."""
    cfg, model, batch = made.cfg, made.model, made.batch
    records = []
    monkeypatch.setattr(ops.trace, "note_trace_time",
                        lambda name, **attrs: records.append((name, attrs)))
    trainer = Trainer(model, optax.sgd(1e-2), build_mesh(
        MeshConfig(dp=1), devices=jax.devices()[:1]))
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    before = jax.tree.map(np.asarray, nn.meta.unbox(state.params))
    want = reference.forward(
        before, batch["input_ids"], batch["labels"], _published(cfg))
    new_state, metrics = trainer.train_step(
        state, trainer.shard_batch(dict(batch)))
    np.testing.assert_allclose(metrics["loss"], want["loss"], rtol=2e-5)
    attn = metrics["stats"]["layers"]["layer"]["attn"]
    assert attn["eva_summary_mass_share"][0].shape == (cfg.num_layers,)
    np.testing.assert_allclose(attn["eva_pool_weight_max"][0],
                               want["pool_weight_max"], rtol=1e-4)
    assert float(metrics["stats"]["multi_byte_loss"][0]) > 0
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         nn.meta.unbox(new_state.params), before)
    assert moved["layers"]["layer"]["attn"]["adaptive_mu_k"] > 0
    assert moved["layers"]["layer"]["attn"]["adaptive_phi"] > 0
    assert records[-1] == ("attention.path", dict(
        impl="eva", seq=SEQ, window=WINDOW, chunk=CHUNK, windows=4,
        summaries_max=12, heads=4, head_dim=16, exact="jnp"))


def test_eva_norm_offset_starts_as_a_plain_norm():
    cfg = _config()
    batch = _batch(cfg)
    params = init_params(LlamaForCausalLM(cfg), batch["input_ids"], seed=0)
    assert float(jnp.abs(params["final_norm"]["scale"]).max()) == 0.0
    vectors = params["layers"]["layer"]["attn"]["adaptive_mu_k"]
    assert float(jnp.abs(vectors).max()) <= cfg.head_dim ** -0.5
    assert params["lm_head"]["kernel"].shape == (64, 8 * cfg.vocab_size)
