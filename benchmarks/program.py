"""How a job of the benchmark builds the system under test from a
configuration file and a seed: model, mesh, optimizer and ``Trainer`` as
``chip_smoke.py`` builds them, the pool of host batches, the key the weights
are made from, the state a cell starts from.  Imports JAX: only a process
that may hold the chip calls it."""

import numpy as np

from benchmarks import common


def sizes(config, rehearse):
    cfg = config["run"]
    src = cfg["rehearse"] if rehearse else cfg
    return int(src["batch"]), int(src["seq"])


def make_trainer(config, rehearse):
    """(family module, model, trainer) on the mesh the file names."""
    import jax.numpy as jnp

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.optim import create_optimizer
    from dlrover_tpu.trainer.train import Trainer

    cfg = config["run"]
    family = common.load_module("families", config["family"])
    model = family.build(config, rehearse, sizes(config, rehearse)[1])
    mesh = build_mesh(MeshConfig(**{"dp": 1, **cfg["mesh"]}))
    opt_cfg = dict(cfg["optimizer"])
    opt_cfg["moment_dtype"] = jnp.dtype(opt_cfg["moment_dtype"])
    trainer = Trainer(model, create_optimizer(**opt_cfg), mesh,
                      grads_dtype=jnp.dtype(cfg["grads_dtype"]))
    return family, model, trainer


def make_pool(config, rehearse, seed, family):
    """The seed's host batches: token ids uniform over the vocabulary,
    labels the ids shifted by one.  Every seed gives the same sizes."""
    batch, seq = sizes(config, rehearse)
    vocab = family.sizes(config, rehearse)["vocab_size"]
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(int(config["run"]["pool_batches"])):
        ids = rng.integers(0, vocab, size=(batch, seq + 1))
        pool.append({"input_ids": np.asarray(ids[:, :-1], np.int32),
                     "labels": np.asarray(ids[:, 1:], np.int32)})
    return pool


def make_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_state(trainer, family, config, rehearse, seed, pool):
    """The state every job of a cell starts from, made from the seed alone:
    ``Trainer.create_state`` and then, where the family has one, its
    ``condition(state, config, rehearse)``: a rule on the parameters that
    reads only the configuration file (``families/olmoe.py``; the dense
    families have none, their states are ``create_state``'s bit for bit)."""
    state = trainer.create_state(make_key(seed), pool[0]["input_ids"])
    condition = getattr(family, "condition", None)
    return state if condition is None else condition(state, config, rehearse)


def stats_by_name(stats):
    """{name: one value a layer} of a tree the model sowed into ``stats``
    (``model.apply(..., mutable=["stats"])[1]["stats"]``, or a step's
    ``metrics["stats"]``)."""
    import jax

    return {path[-2].key: leaf.ravel() for path, leaf in
            jax.tree_util.tree_leaves_with_path(stats)}
