"""The Ouro family's plain reference (the benchmark's copy, whose loop over
the layers is a scan) against the program's copy
(``dlrover_tpu/models/ouro_reference.py``: Python loops alone) at the tiny
size on the CPU, sound and under every planted fault: one set of equations,
twice.  (The program's model against its reference, gradients and all:
``tests/test_ouro.py``.)"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import load_module
from dlrover_tpu.models import ouro_reference as program_copy

family = load_module("families", "ouro")
SEQ = 48


@pytest.fixture(scope="module")
def both_copies():
    """Every variant of both copies, one compiled program a copy."""
    model = family.build({}, True, SEQ)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, size=(2, SEQ + 1)), jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(1), inputs)["params"])
    # untrained norm scales are 1 and the gate's bias 0: move every leaf
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    targets, weights = family.model_targets(inputs, labels)
    # blocks smaller than the sequence: both copies walk theirs
    m = {**family.sizes({}, True), "query_block": 16, "head_rows": 24}
    variants = (None,) + family.FAULTS
    return variants, *(
        jax.jit(lambda p: [copy.reference(
            p, inputs, targets, weights, m, fault=fault)
            for fault in variants])(params)
        for copy in (family, program_copy))


def test_ouro_copies_plant_the_same_faults():
    assert family.FAULTS == program_copy.FAULTS


@pytest.mark.parametrize("which", range(1 + len(family.FAULTS)))
def test_ouro_benchmarks_copy_is_the_programs(both_copies, which):
    variants, ours, theirs = both_copies
    for key in ("ce", "p", "objective", "entropy"):
        np.testing.assert_allclose(
            ours[which][key], theirs[which][key], rtol=0, atol=2e-5,
            err_msg=f"{variants[which]}: {key}")
    if which:       # and the fault is one: it moves what it is there to move
        moved = max(float(jnp.abs(ours[which][key] - ours[0][key]).max())
                    for key in ("p", "objective")
                    if ours[which][key].shape == ours[0][key].shape)
        assert moved > 1e-3, variants[which]
