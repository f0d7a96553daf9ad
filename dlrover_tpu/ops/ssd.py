"""The scan of a Mamba-2 layer (state space duality, arXiv:2405.21060): a
linear recurrence whose state is a matrix ``[P, n]`` a head and whose decay
is ONE number a head and position.

Per sequence and head, with ``x_t`` the head's activation ``[P]``, ``d_t``
its step size (positive), ``A`` one negative number a head, ``B_t`` and
``C_t`` ``[n]`` (shared by the ``H / G`` heads of a group: head ``h`` reads
group ``h // (H / G)``) and ``D`` one number a head::

    S_t = exp(d_t A) S_{t-1} + d_t x_t B_t^T,    S_0 = 0 in R^{P x n}
    y_t = S_t C_t + D x_t

``ssd_recurrent`` is that, a position at a time (the tests' yardstick and
the shape a decoding step would take).  Because the decay is a scalar a
head (Mamba-1's differs by channel AND column: ``ops/selective_scan.py``),
a chunk of ``L`` positions that starts from ``S`` is matrix products.  With
``a_t = d_t A <= 0`` and ``Gs_i`` its running sum inside the chunk::

    M_ij = (C_i . B_j) exp(Gs_i - Gs_j) d_j        (j <= i, else 0)
    Y    = M X + Diag(exp(Gs)) C S^T
    S'   = exp(Gs_L) S + sum_j exp(Gs_L - Gs_j) d_j x_j B_j^T

``ssd`` computes that: the scores ``C B^T`` once a GROUP, the decay mask a
head, taken pair by pair, so every exponent is a difference ``Gs_i - Gs_j``
with ``j <= i`` (or ``Gs_L - Gs_j``, or ``Gs_i`` itself): at most 0, nothing
can overflow and nothing is clamped; what underflows to 0 is smaller in the
mathematics still.  The state between chunks is a ``jax.lax.scan`` over the
chunks' contributions, all of them made at once by one product.  The
mathematics does not depend on ``L``.

Matmul operands are in the operands' own dtype (the model's compute dtype)
with float32 accumulation; ``d``, ``a``, its running sums, the mask and the
state ``S`` between chunks are float32.

**Which body runs where.**  ``ssd`` is the one entry and today has one
body, this file's ``jax.numpy``, on every backend and mesh (``ssd_core``
says so for the ``attention.path`` event: a kernel would be chosen there,
from shapes and backend alone).
"""

import jax
import jax.numpy as jnp


def ssd_recurrent(x, dt, A, Bm, Cm, D):
    """The recurrence a position at a time in float32: x ``[B, S, H, P]``,
    dt ``[B, S, H]``, A and D ``[H]``, Bm and Cm ``[B, S, G, n]`` -> ``[B,
    S, H, P]``."""
    x, dt, A, Bm, Cm, D = (
        jnp.asarray(t, jnp.float32) for t in (x, dt, A, Bm, Cm, D))
    H, G = x.shape[2], Bm.shape[2]
    Bm, Cm = (jnp.repeat(t, H // G, axis=2) for t in (Bm, Cm))   # a head

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + (
            D[:, None] * x_t)

    state = jnp.zeros(x.shape[:1] + x.shape[2:] + Bm.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def ssd_core(seq: int, chunk: int) -> dict:
    """What ``ssd`` takes at these shapes, as the fields of the
    ``attention.path`` event."""
    L = min(chunk, seq)
    return dict(core="jnp", chunk=L, chunks=-(-seq // L))


def ssd(x, dt, A, Bm, Cm, D, chunk=128):
    """``y`` ``[B, S, H, P]`` float32 of the recurrence above in chunks of
    ``chunk`` positions: x ``[B, S, H, P]`` and Bm, Cm ``[B, S, G, n]`` in
    the compute dtype, dt ``[B, S, H]``, A and D ``[H]`` float32.  A length
    that is no multiple of the chunk is padded with positions that change
    nothing (``dt = 0``: a decay of 1 and no input)."""
    Bt, S, H, P = x.shape
    G, n = Bm.shape[2:]
    L = min(chunk, S)
    pad = -S % L
    dt, A, D = (jnp.asarray(t, jnp.float32) for t in (dt, A, D))
    with jax.named_scope("ssd"):
        skip = D[:, None] * x.astype(jnp.float32)
        if pad:
            x, dt, Bm, Cm = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (x, dt, Bm, Cm))
        nc = (S + pad) // L
        # [B, chunks, L, ...], a group's heads side by side: [G, H / G]
        xc = x.reshape(Bt, nc, L, G, H // G, P)
        dc = dt.reshape(Bt, nc, L, G, H // G)
        bc, cc = (t.reshape(Bt, nc, L, G, n) for t in (Bm, Cm))
        sums = jnp.cumsum(dc * A.reshape(G, H // G), axis=2)       # Gs
        # the work inside the chunks: scores once a group, the mask a head
        scores = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                            preferred_element_type=jnp.float32)
        heads = jnp.moveaxis(sums, 2, -1)                 # [B, c, G, k, L]
        seen = jnp.tril(jnp.ones((L, L), bool))
        decay = jnp.exp(jnp.where(
            seen, heads[..., :, None] - heads[..., None, :], -jnp.inf))
        step = jnp.moveaxis(dc, 2, -1)[..., None, :]      # d_j
        mixed = (scores[:, :, :, None] * decay * step).astype(x.dtype)
        y = jnp.einsum("bcgkij,bcjgkp->bcigkp", mixed, xc,
                       preferred_element_type=jnp.float32)
        # what each chunk adds to the state, all chunks in one product
        last = sums[:, :, -1:]                            # Gs_L
        into = (jnp.exp(last - sums) * dc)[..., None]     # [B, c, L, G, k, 1]
        added = jnp.einsum(
            "bcjgkp,bcjgn->bcgkpn", (xc * into).astype(x.dtype), bc,
            preferred_element_type=jnp.float32)
        through = jnp.exp(last[:, :, 0])                  # [B, c, G, k]

        def carry(state, at):
            kept, new = at
            return kept[..., None, None] * state + new, state

        _, before = jax.lax.scan(
            carry, jnp.zeros((Bt, G, H // G, P, n), jnp.float32),
            (jnp.moveaxis(through, 1, 0), jnp.moveaxis(added, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)               # S at a chunk's start
        y = y + jnp.exp(sums)[..., None] * jnp.einsum(
            "bcign,bcgkpn->bcigkp", cc, before.astype(x.dtype),
            preferred_element_type=jnp.float32)
        return y.reshape(Bt, S + pad, H, P)[:, :S] + skip
