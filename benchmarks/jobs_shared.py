"""What the jobs share and that needs JAX: the agreement of the system's
forward pass with the configuration's plain reference."""

import numpy as np


def compare_losses(family, got, want):
    """(ok, detail): token losses held to the family's limits against the
    reference's, each number compared beside its limit."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want)
    detail = {
        "tokens": int(got.size),
        "loss_system": float(got.mean()), "loss_reference": float(want.mean()),
        "token_max_abs_err": float(err.max()), "token_atol": family.TOKEN_ATOL,
        "mean_abs_err": float(abs(got.mean() - want.mean())),
        "mean_atol": family.MEAN_ATOL,
    }
    ok = (np.isfinite(got).all()
          and detail["token_max_abs_err"] <= family.TOKEN_ATOL
          and detail["mean_abs_err"] <= family.MEAN_ATOL)
    if hasattr(family, "MEDIAN_ATOL"):
        # the median token's error: steady from seed to seed where the worst
        # token's swings with the routing (``families/olmoe.py``)
        detail["token_median_abs_err"] = float(np.median(err))
        detail["median_atol"] = family.MEDIAN_ATOL
        ok = ok and detail["token_median_abs_err"] <= family.MEDIAN_ATOL
    return bool(ok), detail


def reference_check(config, rehearse, family, model, trainer, state, pool,
                    stand_in=None):
    """(a) of ``correct``: the system's forward pass against the plain
    float32 reference, on as many seeded sequences as the mesh has data
    shards (one on one chip).  ``stand_in(params, ids, labels)``, where
    given, takes the system's place and is held to the same limits: how a
    probe or a test plants the control (the reference at the precision
    below the configuration's) or a fault, which has to come out as not
    correct."""
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
        # a routed family's reference also counts the tokens it cannot tell
        # apart, layer by layer: over the limit a comparison token by token
        # says nothing, and the run is not correct
        if hasattr(family, "reference_forward"):
            return family.reference_forward(
                params, ids, labels, config, rehearse)
        return family.reference_token_losses(
            params, ids, labels, config, rehearse), jnp.zeros(0)

    ids, labels = batch["input_ids"], batch["labels"]
    plain = nn.meta.unbox(state.params)
    if stand_in is None:
        with trainer.mesh, nn.logical_axis_rules(trainer.rules):
            got = jax.jit(system_losses)(state.params, ids, labels)
    else:
        with trainer.mesh:
            got = jax.jit(stand_in)(plain, ids, labels)
    with trainer.mesh:
        want, low = jax.jit(reference_losses)(plain, ids, labels)
    ok, detail = compare_losses(family, got, want)
    margins = {}
    if low.size:
        margins = {"low_margin_share_by_layer": [float(v) for v in low],
                   "low_margin_share_max": family.LOW_MARGIN_SHARE_MAX}
        ok = ok and max(margins["low_margin_share_by_layer"]) <= (
            family.LOW_MARGIN_SHARE_MAX)
    return ok, {**margins, "sequences": n, **detail}
