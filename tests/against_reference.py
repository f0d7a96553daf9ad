"""What the model-against-reference files share: parameters with every
leaf moved, token ids, and the system's loss and gradients as ``Trainer``'s
default loss computes them.  Everything here that computes runs under
``jax.jit`` and ``default_matmul_precision("highest")``: a primitive at a
time the same work is some hundred small compilations, and most of a case.

A file's module-scoped fixture calls these once and keeps **the results**;
a test is then the comparison it is named after."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def jitted(fn, *args, **kwargs):
    """``fn(*args)`` as one compiled program in float32's full precision;
    ``kwargs`` are ``jax.jit``'s."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn, **kwargs)(*args)


def init_params(module, *args, seed=1, **kwargs):
    """``module.init``'s parameters, unboxed."""
    variables = jitted(
        lambda key, *a: module.init(key, *a, **kwargs),
        jax.random.PRNGKey(seed), *args)
    return nn.meta.unbox(variables["params"])


def perturbed(params, seed=2, scale=0.1):
    """Untrained scales are 1, offsets 0 and a router near uniform: move
    every leaf, or a reference that forgot one would pass."""
    leaves, tree = jax.tree.flatten(params)
    ends = np.cumsum([leaf.size for leaf in leaves])

    def moved(leaves):
        # one draw for the whole tree: a draw a leaf is some fifty
        # generators to compile
        noise = jnp.split(
            jax.random.normal(jax.random.PRNGKey(seed), (ends[-1],)),
            ends[:-1])
        return [leaf + scale * part.reshape(leaf.shape).astype(leaf.dtype)
                for leaf, part in zip(leaves, noise)]

    return jax.tree.unflatten(tree, jax.jit(moved)(leaves))


def token_ids(rows, seq, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq)).astype(np.int32)


def inputs_and_labels(rows, seq, vocab=256, seed=0):
    ids = token_ids(rows, seq + 1, vocab, seed)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _loss_fn(model, inputs, labels, rngs, terms):
    def loss_fn(params):
        logits, sown = model.apply(
            {"params": params}, inputs, mutable=["losses", "stats"],
            rngs=rngs)
        total = sum(
            jnp.sum(term) for path, term in
            jax.tree_util.tree_leaves_with_path(sown.get("losses", {}))
            if terms is None or terms(jax.tree_util.keystr(path)))
        if labels is None:
            return total, (logits, sown)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        token = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return token.mean() + total, (token, sown)
    return loss_fn


def system_loss(model, params, inputs, labels=None, rngs=None, terms=None):
    """``(the step's loss, (token losses, what the model sowed))`` as
    ``Trainer``'s default loss computes them: the cross entropy of
    ``labels`` plus what the model sows into ``losses`` (``terms``: which
    of them, by the path's string; all by default).  Without ``labels``
    the sown terms alone, as for a model with its own objective, and the
    logits in the token losses' place."""
    return jitted(_loss_fn(model, inputs, labels, rngs, terms), params)


def system(model, params, inputs, labels=None, rngs=None, terms=None):
    """``(system_loss(...), the gradients of every parameter)``."""
    return jitted(jax.value_and_grad(
        _loss_fn(model, inputs, labels, rngs, terms), has_aux=True), params)


def reference_loss_and_gradients(forward, params):
    """``forward(params)`` is a reference's dictionary with its ``loss``:
    ``(the dictionary, the loss's gradients)``, one program."""
    def loss_fn(p):
        out = forward(p)
        return out["loss"], out

    (_, out), grads = jitted(jax.value_and_grad(loss_fn, has_aux=True), params)
    return out, grads
