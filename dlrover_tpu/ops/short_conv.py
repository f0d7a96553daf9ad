"""Causal depthwise convolutions of a few taps, and the core of a
double-gated one (LFM2's ``Lfm2ShortConv``, ``model_type`` ``lfm2`` /
``lfm2_moe``).

``taps_sum`` is the one shifting routine: ``weight`` ``[taps, channels]``,
tap ``i`` weighs position ``t - (taps - 1) + i``, zeros before the start.
The SiLU'd convolutions in front of the scans' and the delta rule's cores
(``models/llama.py::_tap_conv``) are it with a bias and an activation
around; ``gated_short_conv`` is it between two gates and nothing else::

    v_t = B_t * u_t
    c_t = sum_i w_i v_{t - (taps - 1) + i}        no bias, NO activation
    out_t = C_t * c_t

all elementwise a channel, in float32 from operands in the compute dtype.
No positions, no softmax, no state beyond ``taps - 1`` positions.

**What the backward pass reads** (``jax.custom_vjp``): ``B``, ``C``, ``u``
and the taps, as the forward pass was handed them, and nothing else.
Autodiff of the plain form holds ``v`` and ``c`` too, in float32 (two ``[B,
S, channels]`` arrays a layer, twice the three operands together); here the
backward pass makes them again from the operands, two multiplies and the
shifted sum::

    dC = g * c                 dc = g * C
    dv_t = sum_i w_i dc_{t + (taps - 1) - i}      the same sum, mirrored
    dB = dv * u                du = dv * B
    dw_i = sum_{b,t} dc_t v_{t - (taps - 1) + i}

A rematerialised layer (``models/llama.py::_layer_class``) keeps NONE of
it by name: the layer's second pass runs ``W_in``'s product and this
forward again (one matmul of a layer's four and three elementwise passes),
and the rule above holds inside that pass.

One body, ``jax.numpy``, on every backend and mesh: some ten elementwise
operations a loaded value, bound by memory traffic.
"""

import jax
import jax.numpy as jnp


def _shifted(t, taps, mirrored=False):
    """The ``taps`` views of ``t`` ``[B, S, channels]`` that a tap weighs,
    float32, one after the other: view ``i`` holds position ``s - (taps - 1) +
    i`` at ``s`` (``mirrored``: ``s + (taps - 1) - i``), zeros beyond the ends."""
    S = t.shape[1]
    lead = jnp.pad(t, ((0, 0), (0, taps - 1) if mirrored else (taps - 1, 0),
                       (0, 0)))
    for i in range(taps):
        first = taps - 1 - i if mirrored else i
        yield lead[:, first: first + S].astype(jnp.float32)


def taps_sum(t, weight, mirrored=False):
    """The causal depthwise convolution of ``t`` ``[B, S, channels]`` in
    float32: ``sum_i weight[i] t[s - (taps - 1) + i]``.  ``mirrored``: its
    transpose, ``sum_i weight[i] t[s + (taps - 1) - i]``."""
    return sum(view * weight[i] for i, view in enumerate(
        _shifted(t, weight.shape[0], mirrored)))


def past_tap_share(b, u, weight, positions=1024):
    """How much of the convolution's result comes from EARLIER positions:
    ``mean |sum_{i < last} w_i v_{t - (taps - 1) + i}|`` over that plus
    ``mean |w_last v_t|``, ``v = b * u``, the means over every channel at
    ``positions`` positions of the sequence (the last of each of that many
    equal runs; every position of a sequence that has no such runs).  Near
    0 the layer hears the present position alone.  No gradient."""
    b, u, weight = (jax.lax.stop_gradient(t) for t in (b, u, weight))
    taps, S = weight.shape[0], b.shape[1]
    stride = S // positions
    if stride >= taps and not S % stride:
        ends = [t.reshape(t.shape[0], positions, stride, -1)[
            :, :, -taps:].astype(jnp.float32) for t in (b, u)]
        views = jnp.moveaxis(ends[0] * ends[1], 2, 0)
    else:
        views = list(_shifted(
            b.astype(jnp.float32) * u.astype(jnp.float32), taps))
    past = jnp.abs(sum(views[i] * weight[i] for i in range(taps - 1))).mean()
    return past / (past + jnp.abs(views[-1] * weight[-1]).mean())


@jax.custom_vjp
def gated_short_conv(b, c, u, weight):
    """``c * conv(b * u)`` in the operands' dtype: b, c, u ``[B, S,
    channels]``, ``weight`` ``[taps, channels]`` float32."""
    v = b.astype(jnp.float32) * u.astype(jnp.float32)
    return (c.astype(jnp.float32) * taps_sum(v, weight)).astype(c.dtype)


def _fwd(b, c, u, weight):
    return gated_short_conv(b, c, u, weight), (b, c, u, weight)


def _bwd(kept, g):
    b, c, u, weight = kept
    b32, c32, u32, g32 = (t.astype(jnp.float32) for t in (b, c, u, g))
    # ``v`` under each tap, once: the taps' sum and their gradient read them
    views = list(_shifted(b32 * u32, weight.shape[0]))
    conv = sum(view * weight[i] for i, view in enumerate(views))
    dc = g32 * c32
    dv = taps_sum(dc, weight, mirrored=True)
    dw = jnp.stack([(view * dc).sum(axis=(0, 1)) for view in views])
    return ((dv * u32).astype(b.dtype), (g32 * conv).astype(c.dtype),
            (dv * b32).astype(u.dtype), dw.astype(weight.dtype))


gated_short_conv.defvjp(_fwd, _bwd)
