"""``program.make_state``: the one place that makes a cell's state, at the
``TINY`` sizes on the CPU, on as many devices as the session has (one
under a bare ``pytest benchmarks/tests``; one test runs this file again on
four virtual devices, where the expert-parallel mesh is the cell's own
``ep=4``).  The dense families' states are ``Trainer.create_state``'s bit for bit; the
OLMoE family's is conditioned by a rule that reads the configuration file
alone (``families/olmoe.py::condition``), from the seed."""

import os
import subprocess
import sys

import flax.linen as nn
import jax
import numpy as np
import pytest

from benchmarks import program
from benchmarks.common import HERE, read_json

SEED, OTHER = 3200000017, 3200000018


def _mesh(name):
    """Every device of the session: data ranks for the dense cells (the
    values a key gives do not depend on the sharding), ``ep=4`` for the
    expert-parallel one where the devices come in fours."""
    n = jax.device_count()
    if name != "olmoe1b7b_ep4":
        return {"dp": n}
    ep = 4 if n % 4 == 0 else 1
    return {"dp": n // ep, "ep": ep}



def _made(name, seed, **run):
    config = read_json(HERE, "configs", name + ".json")
    config["run"].update({"mesh": _mesh(name), **run})
    family, model, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, seed, family)
    state = program.make_state(trainer, family, config, True, seed, pool)
    return config, family, model, trainer, pool, state


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _bytes(tree):
    return {path: np.asarray(leaf).tobytes()
            for path, leaf in _leaves(tree).items()}


@pytest.mark.parametrize("name", ["mistral7b_l2", "gpt2m", "olmoe1b7b_ep4"])
def test_the_seed_alone_makes_the_state(name):
    first = _bytes(_made(name, SEED)[-1])
    assert first == _bytes(_made(name, SEED)[-1])
    other = _bytes(_made(name, OTHER)[-1])
    assert first.keys() == other.keys() and first != other


@pytest.mark.parametrize("name", ["mistral7b_l2", "gpt2m"])
def test_a_dense_familys_state_is_create_states_bit_for_bit(name):
    _, family, _, trainer, pool, state = _made(name, SEED)
    assert not hasattr(family, "condition")
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    assert _bytes(state) == _bytes(plain)


def test_the_olmoe_state_differs_only_in_the_leaves_the_rule_names():
    config, family, _, trainer, pool, state = _made("olmoe1b7b_ep4", SEED)
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(plain))
    got, want = _leaves(state), _leaves(plain)
    rule = {".params" + "".join(f"['{key}']" for key in path) + ".value": factor
            for path, factor in family.state_rule(config, True).items()}
    assert rule[".params['embed_tokens'].value"] == (
        config["run"]["state"]["embed_scale"]) > 1
    experts = family.sizes(config, True)["num_experts"] ** 0.5
    assert sorted(rule.values())[:3] == [experts] * 3
    changed = []
    for path, leaf in got.items():
        assert leaf.dtype == want[path].dtype, path
        assert leaf.shape == want[path].shape, path
        assert leaf.sharding == want[path].sharding, path
        if np.asarray(leaf).tobytes() != np.asarray(want[path]).tobytes():
            changed.append(path)
    assert sorted(changed) == sorted(rule)
    for path in changed:
        np.testing.assert_array_equal(
            np.asarray(got[path]),
            (np.asarray(want[path]) * np.float32(rule[path])).astype(np.float32))


def test_each_experts_matrices_are_drawn_as_a_matrix_of_their_own_shape():
    """What the second half of the rule repairs: the initialiser counts the
    expert axis into the fan-in of the stacked arrays."""
    _, _, _, _, _, state = _made("olmoe1b7b_ep4", SEED)
    mlp = nn.meta.unbox(state.params)["layers"]["layer"]["mlp"]
    for name in ("gate_proj", "up_proj", "down_proj"):
        fan_in = mlp[name].shape[-2]
        assert abs(float(np.std(np.asarray(mlp[name]))) * fan_in ** 0.5 - 1) < 0.1


@pytest.mark.parametrize("seed", [SEED, OTHER, 7])
def test_the_conditioned_state_spreads_random_tokens_over_the_experts(seed):
    """8 sequences of 64 ids: the largest expert's rows over the mean, from
    the program's own counter, is lower in every layer than on
    ``create_state``'s state (4 sequences of 128: 1.14-1.51 against
    1.67-3.70 over six seeds)."""
    _, _, model, trainer, pool, state = _made(
        "olmoe1b7b_ep4", seed, rehearse={"batch": 8, "seq": 64})
    plain = trainer.create_state(program.make_key(seed), pool[0]["input_ids"])

    @jax.jit
    def load(params, ids):
        return program.stats_by_name(model.apply(
            {"params": params}, ids, mutable=["stats"])[1]["stats"])[
                "load_max_over_mean"]

    ids = trainer.shard_batch(pool[0])["input_ids"]
    with trainer.mesh, nn.logical_axis_rules(trainer.rules):
        conditioned = np.asarray(load(state.params, ids))
        untouched = np.asarray(load(plain.params, ids))
    assert conditioned.shape == untouched.shape == (2,)
    assert (conditioned < untouched).all(), (conditioned, untouched)
    assert conditioned.max() < 1.6


def test_the_configuration_file_states_the_rule():
    config = read_json(HERE, "configs", "olmoe1b7b_ep4.json")
    assert set(config["run"]["state"]) == {"rule", "embed_scale"}
    assert "embed_scale" in config["assumed"]
    assert any("embed_scale" in note for note in config["notes"])
    assert "square root of num_experts" in config["run"]["state"]["rule"]


def test_the_same_on_four_virtual_devices():
    """The cases above under the cell's own mesh, ``ep=4``."""
    if jax.device_count() % 4 == 0:
        pytest.skip("this session already has the devices in fours")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-p", "no:cacheprovider", "-k", "not four_virtual"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    # every case ran and passed: dots alone, no ``s`` and no ``F``
    assert proc.stdout.split()[0].strip(".") == "", proc.stdout


def test_a_file_without_a_state_rule_gets_create_states_own_state():
    """The candidate ``olmoe1b7b_ep4_init`` (``candidates.json``): the state
    the cell ran on before PR 32, on which the ladder of extents is climbed."""
    config = read_json(HERE, "configs", "olmoe1b7b_ep4_init.json")
    assert "state" not in config["run"]
    admitted = read_json(HERE, "configs", "olmoe1b7b_ep4.json")
    for key in admitted:
        if key not in ("name", "notes", "assumed", "run"):
            assert config[key] == admitted[key], key
    assert {**admitted["run"], "state": None} == {**config["run"], "state": None}
    config["run"]["mesh"] = _mesh("olmoe1b7b_ep4")
    family, _, trainer = program.make_trainer(config, True)
    pool = program.make_pool(config, True, SEED, family)
    state = program.make_state(trainer, family, config, True, SEED, pool)
    plain = trainer.create_state(program.make_key(SEED), pool[0]["input_ids"])
    assert _bytes(state) == _bytes(plain)
