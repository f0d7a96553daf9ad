#!/usr/bin/env python3
"""Compile a configuration's whole ``Trainer`` step for a DESCRIBED TPU
v5e:2x2 (no chip attached; nothing runs, so these are sizes and never
results) and print what one chip must hold: arguments, temporaries, and
both with the transient snapshot copy of an asynchronous save beside them.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/compile_described.py <config> [batch seq]

Run it before the first chip call of a new configuration: what the
compiler refuses here costs no chip time."""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks import common
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.optim import create_optimizer
    from dlrover_tpu.trainer.train import Trainer

    config = common.read_json(common.HERE, "configs", argv[1] + ".json")
    cfg = config["run"]
    batch = int(argv[2]) if len(argv) > 2 else int(cfg["batch"])
    seq = int(argv[3]) if len(argv) > 3 else int(cfg["seq"])
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the program asks; steer it here
    layout = {"dp": 1, **cfg["mesh"]}
    chips = int(np.prod(list(layout.values())))
    mesh = build_mesh(MeshConfig(**layout), devices=list(topo.devices)[:chips])
    family = common.load_module("families", config["family"])
    model = family.build(config, False, seq)
    opt_cfg = dict(cfg["optimizer"])
    opt_cfg["moment_dtype"] = jnp.dtype(opt_cfg["moment_dtype"])
    trainer = Trainer(model, create_optimizer(**opt_cfg), mesh,
                      grads_dtype=jnp.dtype(cfg["grads_dtype"]))
    rng = jax.random.PRNGKey(0)
    sample = np.zeros((batch, seq), np.int32)
    shardings = trainer.state_sharding_for(rng, sample)
    trainer.state_shardings = shardings
    abstract = trainer.abstract_state(rng, sample)
    state = jax.tree.map(
        lambda s, sub: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), sub),
        shardings, abstract,
    )
    data = NamedSharding(mesh, P(trainer.data_axes))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
    compiled = trainer.lower_train_step(
        state, {"input_ids": ids, "labels": ids}).compile()
    mem = compiled.memory_analysis()
    params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(abstract.params))
    gib = 2.0 ** 30
    args_b, temp_b = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    print(json.dumps({
        "config": argv[1], "batch": batch, "seq": seq, "chips": chips,
        "params": params, "matmul_params": family.matmul_params(config),
        "flops_per_token": family.flops_per_token(config, seq),
        "arguments_gib": args_b / gib, "temporaries_gib": temp_b / gib,
        "with_snapshot_copy_gib": (2 * args_b + temp_b) / gib,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
    }))


if __name__ == "__main__":
    main(sys.argv)
