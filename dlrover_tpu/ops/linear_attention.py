"""Linear attention with a state: the gated delta rule with a decay for
every channel (Kimi Delta Attention, arXiv:2510.26692), chunked.

Per head, with ``alpha_t = exp(g_t)`` in (0, 1] a vector over the key
channels and ``beta_t`` a scalar::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        S_0 = 0 in R^{d_k x d_v}

``kda_recurrent`` is that, a token at a time (the tests' yardstick and the
shape a decoding step would take).  ``kda`` computes the same in chunks of
``chunk`` positions.  With ``G_i`` the running sum of ``g`` inside a chunk
and ``u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)`` the update is
``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``, so inside a chunk that starts
from ``S``::

    A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j < i)
    P_ij =        sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j <= i)
    (I + A) [W, U0] = Diag(beta) [K * exp(G), V]   (a triangular solve)
    U = U0 - W S
    O = (Q * exp(G)) S + P U
    S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T U

**The per-channel decay is the hazard.**  ``exp(G_i - G_j)`` is at most 1,
but factorised as ``exp(G_i) * exp(-G_j)`` so that it becomes a matmul, the
second factor overflows float32 inside one chunk at a strong decay.  Here no
factor ever has a positive exponent, and nothing is clamped: a chunk is cut
into sub-blocks of ``SUB`` positions; between sub-blocks ``I > J`` the
exponent is split at the boundary before ``I``, ``(G_i - R_I) + (R_I -
G_j)``, both parts at most 0 (a matmul, one set of decayed keys for each
``I``); inside a sub-block the differences are taken pair by pair.  What
underflows to 0 is smaller in the mathematics still.

Matmul operands are in the operands' own dtype (the model's compute dtype)
with float32 accumulation; ``g``, its running sums, the solve and the state
``S`` between chunks are float32.

**Which body runs where.**  ``kda`` is the one entry.  On a TPU, at heads of
128 key and value channels, chunks of 64 and a length that is a multiple of
the chunk (``kda_path``; the benchmark's Solar-Open2 cell), it goes through
the Pallas kernels of ``ops/pallas/kda.py``, forward and backward behind one
``jax.custom_vjp``: the work inside a chunk in fast memory (the sub-blocks
halved down to single positions, so nothing is left to be taken pair by
pair; the solve by float32 products), the float32 state resident across a
sequence's chunks.  Everywhere else (off the chip, the tests' small heads,
another chunk, a ragged length) it is the ``jax.numpy`` body of this file,
``_kda_chunked``, whose backward pass is JAX's, of the chunked form, with
the pair-by-pair part recomputed there, not kept; that body and
``kda_recurrent`` are the kernels' yardsticks.  ``kda_core`` says which was
taken, with the kernels' tile, for the ``attention.path`` event.
"""

import functools
import math

import jax
import jax.numpy as jnp

#: positions of a sub-block: inside one the decay is taken pair by pair
SUB = 16


def kda_recurrent(q, k, v, g, beta):
    """The recurrence a token at a time, in the operands' dtype (float32 in
    the tests): q, k, g ``[B, S, H, K]``, v ``[B, S, H, V]``, beta ``[B, S,
    H]`` -> ``[B, S, H, V]``."""
    B, S, H, K = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state = jnp.zeros((B, H, K, v.shape[-1]), q.dtype)
    _, out = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _inside_sub_blocks(rows, keys, running, sub):
    """``sum_c rows_i[c] keys_j[c] exp(G_i[c] - G_j[c])`` for the pairs ``j
    <= i`` of one sub-block, 0 elsewhere: ``[..., n, sub, sub]`` float32
    from ``[..., n, sub, K]``.  Pair by pair: the differences are at most 0
    as they stand."""
    at = jnp.arange(sub)
    diff = running[..., :, None, :] - running[..., None, :, :]
    decay = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[..., None], diff, -jnp.inf))
    return jnp.sum(rows[..., :, None, :].astype(jnp.float32)
                   * keys[..., None, :, :].astype(jnp.float32) * decay, -1)


def _decayed_products(rows, keys, running, before, sub):
    """``[..., C, C]`` float32: ``sum_c rows_i[c] keys_j[c] exp(G_i[c] -
    G_j[c])`` over the pairs ``j <= i`` of a chunk, 0 above.  ``rows``,
    ``keys``, ``running`` ``[..., C, K]``; ``before`` is ``running`` less
    ``g``: the sum up to the position before."""
    C, K = keys.shape[-2:]
    n = C // sub
    blocks = lambda t: t.reshape(t.shape[:-2] + (n, sub, K))  # noqa: E731
    # R_I: the running sum at the boundary before sub-block I
    boundary = blocks(before)[..., 0, :]
    rows_s = blocks(rows).astype(jnp.float32) * jnp.exp(
        blocks(running) - boundary[..., None, :])
    earlier = (jnp.arange(C) // sub)[None, :] < jnp.arange(n)[:, None]
    keys_s = keys[..., None, :, :].astype(jnp.float32) * jnp.exp(jnp.where(
        earlier[..., None],
        boundary[..., :, None, :] - running[..., None, :, :], -jnp.inf))
    between = jnp.einsum(
        "...ncd,...nkd->...nck", rows_s.astype(rows.dtype),
        keys_s.astype(keys.dtype), preferred_element_type=jnp.float32)
    inside = _inside_sub_blocks(blocks(rows), blocks(keys), blocks(running),
                                sub)
    inside = inside[..., :, :, None, :] * jnp.eye(
        n, dtype=jnp.float32)[:, None, :, None]
    return between.reshape(between.shape[:-3] + (C, C)) + inside.reshape(
        inside.shape[:-4] + (C, C))


def kda_path(backend: str, seq: int, chunk: int, head_dim: int) -> str:
    """``"pallas"`` or ``"jnp"``: which body computes the chunked rule for
    ``seq`` positions in chunks of ``chunk`` at heads of ``head_dim`` key
    and value channels (as ``ops/attention.py::index_scores_path``)."""
    from dlrover_tpu.ops.pallas.kda import kernels_take

    if backend == "tpu" and kernels_take(seq, chunk, head_dim):
        return "pallas"
    return "jnp"


def kda_core(seq: int, chunk: int, head_dim: int) -> dict:
    """What ``kda`` takes at these shapes on this backend, as the fields of
    the ``attention.path`` event: ``core`` and, from the kernels, the tile
    (``tuning.kda_tiling``) and ``sub``."""
    core = kda_path(jax.default_backend(), seq, min(chunk, seq), head_dim)
    if core == "jnp":
        return dict(core=core)
    from dlrover_tpu.ops.pallas.kda import SUB as sub
    from dlrover_tpu.ops.pallas.tuning import kda_tiling

    chunks, heads, state_heads = kda_tiling(chunk, head_dim)
    return dict(core=core, chunks_per_step=chunks, heads_per_turn=heads,
                state_heads_per_step=state_heads, sub=sub)


def kda(q, k, v, g, beta, chunk=64):
    """The gated delta rule with a per-channel decay, chunked: q, k ``[B,
    S, H, K]`` (the model normalises them), v ``[B, S, H, V]``, g ``[B, S,
    H, K]`` the log of the decay (at most 0), beta ``[B, S, H]`` ->
    ``[B, S, H, V]`` in ``v``'s dtype.  A length that is no multiple of the
    chunk is padded with positions that change nothing (``k = 0``).
    Through the Pallas kernels where ``kda_path`` says so, else the
    ``jax.numpy`` body below."""
    S, K = q.shape[1], q.shape[3]
    core = kda_core(S, chunk, K if v.shape[-1] == K else 0)
    if core["core"] == "pallas":
        return _kda_kernels(
            q.astype(v.dtype), k.astype(v.dtype), v, g.astype(jnp.float32),
            beta.astype(jnp.float32), (
                core["chunks_per_step"], core["heads_per_turn"],
                core["state_heads_per_step"]))
    return _kda_chunked(q, k, v, g, beta, chunk)


def _kda_kernels(q, k, v, g, beta, tile, interpret=False):
    """The kernels' entry, a name of this module so that a test can run
    them in the interpreter."""
    from dlrover_tpu.ops.pallas import kept
    from dlrover_tpu.ops.pallas.kda import kda_kernels, kept_bytes

    kept.note("kda", **kept_bytes(q))
    return kda_kernels(q, k, v, g, beta, tile=tile, interpret=interpret)


def _kda_chunked(q, k, v, g, beta, chunk):
    """``kda`` in ``jax.numpy``: off the chip and at shapes the kernels do
    not take, and beside ``kda_recurrent`` the kernels' yardstick."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    dtype = v.dtype
    C = min(chunk, S)
    sub = math.gcd(C, SUB)
    pad = -S % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    N = (S + pad) // C

    def chunks(t):      # [B, S, H, ...] -> [B, N, H, C, ...]
        t = t.reshape((B, N, C) + t.shape[2:])
        return jnp.moveaxis(t, 2, 3)

    with jax.named_scope("chunk"):
        q, k, v = chunks(q), chunks(k), chunks(v)
        g = chunks(g.astype(jnp.float32))
        beta = chunks(beta.astype(jnp.float32))[..., None]
        running = jnp.cumsum(g, axis=-2)
        before = running - g
        last = running[..., -1:, :]
        # P from q's rows and A from k's, a call each: stacked into one
        # product against one set of decayed keys the step is 16 ms slower
        # on the chip (PERF.md section 6, PR 41); A is strictly below the
        # diagonal
        p = _decayed_products(q, k, running, before, sub)
        a = beta * jnp.tril(
            _decayed_products(k, k, running, before, sub), -1)
        # (I + A) [W, U0] = Diag(beta) [K exp(G), V]: forward substitution
        solved = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(C, dtype=jnp.float32),
            beta * jnp.concatenate(
                [k.astype(jnp.float32) * jnp.exp(running),
                 v.astype(jnp.float32)], axis=-1),
            lower=True, unit_diagonal=True)
        w, u0 = solved[..., :K].astype(dtype), solved[..., K:]
        q_start = (q.astype(jnp.float32) * jnp.exp(running)).astype(dtype)
        k_end = (k.astype(jnp.float32) * jnp.exp(last - running)).astype(dtype)
        through = jnp.exp(last[..., 0, :])

    def matmul(spec, a_, b_):
        return jnp.einsum(spec, a_, b_, preferred_element_type=jnp.float32)

    def between_chunks(state, chunk_):
        w_, u0_, k_end_, through_ = chunk_
        start = state.astype(dtype)
        u = (u0_ - matmul("bhck,bhkv->bhcv", w_, start)).astype(dtype)
        state = through_[..., None] * state + matmul(
            "bhck,bhcv->bhkv", k_end_, u)
        return state, (start, u)

    with jax.named_scope("state"):
        _, (starts, u) = jax.lax.scan(
            between_chunks, jnp.zeros((B, H, K, V), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0) for t in (w, u0, k_end, through)))
    with jax.named_scope("chunk"):
        starts, u = jnp.moveaxis(starts, 0, 1), jnp.moveaxis(u, 0, 1)
        out = matmul("bnhck,bnhkv->bnhcv", q_start, starts) + matmul(
            "bnhij,bnhjv->bnhiv", p.astype(dtype), u)
        out = jnp.moveaxis(out.astype(dtype), 2, 3).reshape(B, S + pad, H, V)
    return out[:, :S]
