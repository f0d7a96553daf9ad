"""What the jobs share and that needs JAX: the agreement of the system's
forward pass with the configuration's plain reference."""

import numpy as np


def reference_check(config, rehearse, family, model, trainer, state, pool):
    """(a) of ``correct``: the system's forward pass against the plain
    float32 reference, on as many seeded sequences as the mesh has data
    shards (one on one chip)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    n = 1
    for axis in trainer.data_axes:
        n *= int(dict(trainer.mesh.shape).get(axis, 1))
    host = {k: v[:n] for k, v in pool[0].items()}
    batch = trainer.shard_batch(host)

    def system_losses(params, ids, labels):
        logits = model.apply({"params": params}, ids).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    def reference_losses(params, ids, labels):
        return family.reference_token_losses(
            params, ids, labels, config, rehearse)

    with trainer.mesh, nn.logical_axis_rules(trainer.rules):
        got = jax.jit(system_losses)(
            state.params, batch["input_ids"], batch["labels"])
    with trainer.mesh:
        want = jax.jit(reference_losses)(
            nn.meta.unbox(state.params), batch["input_ids"], batch["labels"])
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    token_err = float(np.abs(got - want).max())
    mean_err = float(abs(got.mean() - want.mean()))
    ok = (np.isfinite(got).all() and token_err <= family.TOKEN_ATOL
          and mean_err <= family.MEAN_ATOL)
    return bool(ok), {
        "sequences": n, "tokens": int(got.size),
        "loss_system": float(got.mean()), "loss_reference": float(want.mean()),
        "token_max_abs_err": token_err, "token_atol": family.TOKEN_ATOL,
        "mean_abs_err": mean_err, "mean_atol": family.MEAN_ATOL,
    }


