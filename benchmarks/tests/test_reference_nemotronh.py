"""The Nemotron-H family's plain reference against the system's model at
the tiny size on the CPU, in float32 on both sides (as
``test_reference_kanana2.py`` does for its family), against the repository's
copy, and what the comparison does where it cannot tell tokens apart.  The
planted faults go through the harness's own comparison in
``test_correct_nemotronh.py``."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "nemotronh")
SEQ = 64


@pytest.fixture(scope="module")
def nemotron_tiny():
    """(model, parameters, buffers, inputs, labels, the system's losses),
    every leaf moved: untrained norm scales are 1 and the bias 0, and a
    reference that forgot one would pass."""
    model = family.build({}, True, SEQ)._model
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    made = nn.meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(1), inputs))

    def moved(tree, by, seed):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf + by * jax.random.normal(k, leaf.shape, leaf.dtype)
            for leaf, k in zip(leaves, keys)])

    params = moved(made["params"], 0.1, 2)
    buffers = moved(made["buffers"], 0.05, 3)
    return (model, params, buffers, inputs, labels,
            _system_losses(model, params, buffers, inputs, labels))


def _system_losses(model, params, buffers, inputs, labels):
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)(
            {"params": params, "buffers": buffers}, inputs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(
        -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])


def test_nemotron_reference_agrees_with_the_model_in_float32(
        nemotron_tiny, capfd):
    model, params, buffers, inputs, labels, got = nemotron_tiny
    losses, low = family.reference_forward(
        params, inputs, labels, {}, True, buffers=buffers)
    assert got.shape == np.asarray(losses).shape == (2, SEQ)
    # float32 on both sides; a loss of 6 resolves to 5e-7
    np.testing.assert_allclose(got, losses, rtol=0, atol=1e-4)
    assert low.shape == (2,)                    # a share a routed layer
    err = capfd.readouterr().err
    assert '"phase": "reference_nemotronh"' in err
    assert '"share_rows_over_expected_by_layer"' in err
    assert '"ssd_decay_p50_by_layer"' in err


def test_nemotron_copy_is_the_repositorys_reference(nemotron_tiny):
    """The reference twice, in the repository for its tests and here for
    the benchmark (scans over periods and runs, the attention in blocks of
    queries, the planted faults): the two give the same losses, loads and
    decays."""
    from dlrover_tpu.models import nemotronh_reference

    model, params, buffers, inputs, labels, _ = nemotron_tiny
    m = family.sizes({}, True)
    assert m["layer_pattern"] == ("ffn", "mamba2:alone")
    assert m["layer_suffix"] == ("gqa:alone",) and m["periods"] == 2
    got = family.reference(params, buffers, inputs, labels, m)
    want = nemotronh_reference.forward(params, buffers, inputs, labels, m)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[2], want["rows"])
    np.testing.assert_allclose(got[3], want["decay_p50"], rtol=1e-6)
    assert got[2].shape == (2, 8) and int(got[2][0].sum()) == 2 * SEQ * 3
    assert float(np.max(got[1])) < family.LOW_MARGIN_SHARE_MAX
    # a block of queries smaller than the sequence: the same losses
    blocks = family.reference(
        params, buffers, inputs, labels, {**m, "query_block": 16})
    np.testing.assert_allclose(blocks[0], got[0], rtol=0, atol=2e-5)
    assert [path for path, _, _ in family.stacks(m)] == [
        ("layers", "ffn_0", "layer"), ("layers", "mamba2_alone_1", "layer"),
        ("suffix", "gqa_alone_0", "layer")]


@pytest.mark.parametrize("letters, want", [
    ("EMEMEMEMEM*", (("ffn", "mamba2:alone"), 5, ("gqa:alone",))),
    ("EMEM*", (("ffn", "mamba2:alone"), 2, ("gqa:alone",))),
    ("MMMM", (("mamba2:alone",), 4, ())),
    # a tie (three bodies either way): the shorter pattern
    ("ME*", (("mamba2:alone",), 1, ("ffn", "gqa:alone"))),
    ("M*M*E", (("mamba2:alone", "gqa:alone"), 2, ("ffn",)))])
def test_nemotron_stack_layout_traces_the_fewest_bodies(letters, want):
    assert family.stack_layout(letters) == want
    pattern, periods, suffix = want
    assert pattern * periods + suffix == tuple(
        family.ENTRY_OF[letter] for letter in letters)


@pytest.mark.parametrize("changes, same", [
    ({"use_rope": True}, False), ({"mlp_activation": "silu"}, False),
    ({"routed_scaling_factor": 1.0}, False), ({"mamba2_chunk": 32}, True)],
    ids=["positions", "silu_experts", "no_factor", "another_chunk"])
def test_nemotron_departure_in_the_program(nemotron_tiny, changes, same):
    """The program under another convention is a hundred times the 1e-4 of
    the test above away from the reference; at another chunk of the scan it
    is where it was."""
    model, params, buffers, inputs, labels, got = nemotron_tiny
    other = type(model)(dataclasses.replace(model.config, **changes))
    err = np.abs(_system_losses(other, params, buffers, inputs, labels)
                 - got).max()
    assert (err < 2e-5) if same else (err > 1e-2)


def test_nemotron_low_margin_share_over_its_limit_fails_the_comparison(
        nemotron_tiny, monkeypatch):
    """A routed family's losses are NaN where too many tokens of a layer
    cannot be told apart: a comparison token by token says nothing then."""
    model, params, buffers, inputs, labels, _ = nemotron_tiny
    sound = family.reference_token_losses(
        params, inputs, labels, {}, True, buffers=buffers)
    assert np.isfinite(np.asarray(sound)).all()
    monkeypatch.setattr(family, "LOW_MARGIN_SHARE_MAX", -1.0)
    got = family.reference_token_losses(
        params, inputs, labels, {}, True, buffers=buffers)
    assert np.isnan(np.asarray(got)).all()


def test_nemotron_reference_without_a_state_says_so(
        nemotron_tiny, monkeypatch):
    """The harness hands the reference parameters alone: before
    ``condition`` has made a state there is no bias to read."""
    model, params, _, inputs, labels, _ = nemotron_tiny
    monkeypatch.setitem(family._ling._STATE, "buffers", None)
    with pytest.raises(RuntimeError, match="no selection bias"):
        family.reference_forward(params, inputs, labels, {}, True)
