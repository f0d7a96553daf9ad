"""The LFM2 family's plain reference against the repository's copy at the
tiny size on the CPU (``tests/test_lfm2.py`` holds the system to that copy
in losses and every gradient, ``test_correct_lfm2.py`` holds the system to
this one through the harness's own comparison, with the planted faults),
and the cut the family reads from the published list of layers."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module

family = load_module("families", "lfm2")
SEQ = 64


@pytest.fixture(scope="module")
def lfm2_tiny():
    """(parameters, buffers, inputs, labels) in the TINY model's tree, by
    shape alone (no program is compiled for them): every leaf drawn, the
    norms' scales about 1, so that a copy that forgot a leaf would differ."""
    model = family.build({}, True, SEQ)._model
    rng = np.random.default_rng(0)
    vocab = family.sizes({}, True)["vocab_size"]
    ids = jnp.asarray(rng.integers(0, vocab, size=(2, SEQ + 1)), jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(1), inputs))

    def drawn(path, leaf):
        around = 1.0 if path[-1].key == "scale" else 0.0
        return jnp.asarray(
            around + 0.1 * rng.standard_normal(leaf.shape), leaf.dtype)

    made = jax.tree_util.tree_map_with_path(
        drawn, {name: shapes[name] for name in ("params", "buffers")})
    return made["params"], made["buffers"], inputs, labels


def test_lfm2_copy_is_the_repositorys_reference(lfm2_tiny):
    """The reference twice, in the repository for its tests and here for
    the benchmark (scans over periods and runs, the attention in blocks of
    queries, the planted faults): the two give the same losses, loads and
    shares of the taps; the cut of ``layer_types`` is the stack's."""
    from dlrover_tpu.models import lfm2_reference

    params, buffers, inputs, labels = lfm2_tiny
    m = family.sizes({}, True)
    assert m["layer_prefix"] == ("conv:dense",)
    assert m["layer_pattern"] == ("gqa", "conv", "conv", "conv")
    assert m["head_dim"] == 16 and m["rope_theta"] == 1e6
    got = jax.jit(lambda p, b: family.reference(
        p, b, inputs, labels, {**m, "query_block": 16}))(params, buffers)
    want = jax.jit(lambda p, b: lfm2_reference.forward(
        p, b, inputs, labels, m))(params, buffers)
    np.testing.assert_allclose(got[0], want["token_losses"], rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[2], want["rows"])
    np.testing.assert_allclose(got[3], want["past_tap_share"], rtol=1e-5)
    assert got[2].shape == (4, 16) and int(got[2][0].sum()) == 2 * SEQ * 3
    assert float(np.max(got[1])) < family.LOW_MARGIN_SHARE_MAX
    assert [path for path, _, _ in family.stacks(m)] == [
        ("prefix", "conv_dense_0", "layer"), ("layers", "gqa_0", "layer"),
        ("layers", "conv_1", "layer")]


def test_lfm2_cut_reads_the_published_list_from_the_last_dense_layer():
    from benchmarks.common import HERE, read_json

    config = read_json(HERE, "configs", "lfm2_24b_1of8.json")
    m = family.sizes(config, False)
    assert len(config["layer_types"]) == 40
    assert m["layer_prefix"] == ("conv:dense",)
    assert m["layer_pattern"] == ("gqa", "conv", "conv", "conv")
    assert family.layer_counts(m) == {
        "conv": 7, "gqa": 2, "dense": 1, "routed": 8}
    assert m["head_dim"] == 64 and m["experts_total"] == 64
    with pytest.raises(ValueError, match="runs only"):
        family.build({**config, "conv_bias": True}, False, 128)
