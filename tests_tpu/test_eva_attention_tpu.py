"""The windows-and-summaries attention on the REAL TPU at the shape of
``evabyte_l4.steady`` (``[1, 16384, 32, 128]`` bfloat16, windows of 2048,
chunks of 16): the kernels path (compiled by Mosaic, not interpreted)
against the ``jax.numpy`` path, outputs and the five gradients; and the
cell's whole step compiled for this chip, with what it holds."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention as ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, HEADS, HEAD_DIM, WINDOW, CHUNK = 16384, 32, 128, 2048, 16


def _operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):   # q and k as after a norm: scores of order 1
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)

    def draw(key):
        return jax.random.normal(key, (1, SEQ, HEADS, HEAD_DIM), jnp.float32)

    q, k, v = unit(draw(ks[0])), 2.0 * unit(draw(ks[1])), draw(ks[2])
    # the cell's pooling vectors: normal, clipped, head_dim ** -0.5, times 2
    mu, phi = (2.0 * HEAD_DIM ** -0.5 * jnp.clip(jax.random.normal(
        key, (HEADS, HEAD_DIM)), -1.0, 1.0) for key in ks[3:5])
    weights = jax.random.normal(ks[5], q.shape, jnp.float32)
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v, mu, phi)), weights


def _close(got, want, atol, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    np.testing.assert_allclose(got, want, atol=atol, rtol=0, err_msg=what)


def test_the_whole_sequence_forward_and_backward(tpu_backend):
    operands, weights = _operands()
    assert ops.eva_exact_path(
        jax.default_backend(), WINDOW, CHUNK, HEAD_DIM, HEADS) == "pallas"

    def run():  # a new function each call: traced anew
        def loss(*xs):
            out, mass, _ = ops.eva_attention(*xs, WINDOW, CHUNK)
            return (out.astype(jnp.float32) * weights).sum(), (out, mass)

        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(*operands)

    (_, (out, mass)), grads = run()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ops, "eva_exact_path", lambda *a: "jnp")
        (_, (want_out, want_mass)), want_grads = run()
    # bfloat16 operands and results, float32 accumulation on both sides:
    # they differ by the rounding of outputs and of the probabilities
    _close(out, want_out, 2e-2, "out")
    assert 0.05 < float(want_mass) < 0.95
    assert abs(float(mass) - float(want_mass)) <= 2e-3 * float(want_mass)
    for name, got, want in zip(("q", "k", "v", "mu", "phi"), grads, want_grads):
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        assert scale > 0, name
        _close(got, want, 4e-2 * scale, f"gradient of {name}")


def _the_cell():
    """(``benchmarks/program.py``, the cell's configuration file)."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import program

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "evabyte_l4.json")) as f:
        return program, json.load(f)


def test_the_cells_forward_sows_the_same_counters(tpu_backend):
    """The model on the cell's own state (seed 36), before any step: what
    the forward pass sows a layer, ``eva_summary_mass_share`` (from the LSE
    on the kernels path, from the probabilities on the other) and the
    further heads' loss, by both paths."""
    program, config = _the_cell()
    family, model, trainer = program.make_trainer(config, False)
    pool = program.make_pool(config, False, 36, family)
    params = program.make_state(
        trainer, family, config, False, 36, pool).params

    def sown():  # a new function each call: traced anew
        return program.stats_by_name(jax.jit(lambda p, ids: model.apply(
            {"params": p}, ids, mutable=["stats"])[1]["stats"])(
                params, pool[0]["input_ids"]))

    got = sown()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ops, "eva_exact_path", lambda *a: "jnp")
        want = sown()
    share = np.asarray(want["eva_summary_mass_share"])
    assert share.shape == (4,) and share.min() > 0.2
    print(json.dumps({name: [np.asarray(x[name]).tolist() for x in (got, want)]
                      for name in ("eva_summary_mass_share",
                                   "multi_byte_loss")}))
    np.testing.assert_allclose(
        got["eva_summary_mass_share"], share, atol=1e-3, rtol=0)
    np.testing.assert_allclose(
        got["multi_byte_loss"], want["multi_byte_loss"], rtol=5e-4)


def test_the_cells_step_fits_the_chip(tpu_backend):
    """``benchmarks/tests/compile_described.py``'s compile, for the chip
    that is here: accepted, every window's kernels in it."""
    program, config = _the_cell()
    _, _, trainer = program.make_trainer(config, False)
    rng = program.make_key(0)
    sample = np.zeros((int(config["run"]["batch"]), SEQ), np.int32)
    shardings = trainer.state_sharding_for(rng, sample)
    trainer.state_shardings = shardings
    state = jax.tree.map(
        lambda s, sub: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), sub),
        shardings, trainer.abstract_state(rng, sample))
    ids = jax.ShapeDtypeStruct(
        sample.shape, jnp.int32, sharding=jax.sharding.NamedSharding(
            trainer.mesh, jax.sharding.PartitionSpec(trainer.data_axes)))
    compiled = trainer.lower_train_step(
        state, {"input_ids": ids, "labels": ids}).compile()
    # the chip's compiler refuses a program that does not fit (as it does
    # this one at ``seq`` 32768); what it counted, for the record
    mem = compiled.memory_analysis()
    print(json.dumps({"arguments_gib": mem.argument_size_in_bytes / 2 ** 30,
                      "temporaries_gib": mem.temp_size_in_bytes / 2 ** 30}))
    # forward, the layer's rematerialised forward and backward of eight
    # windows, once in the text of the loop over layers or once a layer
    calls = [line for line in compiled.as_text().split("\n")
             if 'custom_call_target="tpu_custom_call"' in line
             and f"s8[1,{WINDOW},{WINDOW}]" in line]
    assert len(calls) in (24, 96)
