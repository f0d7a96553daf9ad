"""Attention kernels: one reference core, a TPU flash path on top.

The reference math lives in exactly one place so numerics policy (fp32
logits, mask fill value, fp32 softmax) can never diverge between model
families.  ``flash_attention`` is the Pallas TPU kernel
(ops/pallas/flash_attention.py) and nothing else: it never turns into the
reference.  Off the chip it raises unless the caller asks for the Pallas
interpreter by argument; under a mesh of several devices it runs the kernel
per shard through ``shard_map`` (a Mosaic kernel cannot be partitioned
automatically).  ``causal_attention`` is the one place that chooses between
the two for a model that has no opinion (the GPT family): from the backend
and the shape, once, while the step is traced.  All four take a causal
``window`` (a query sees itself and the ``window - 1`` positions before
it; ``None``: every earlier key): the kernel masks by position and visits
only the blocks the band touches, the reference core applies the same band
to its mask.
``indexed_sparse_attention`` (at the end) is the attention of a model
whose configuration carries an indexer: each query attends to the keys a
learned scorer ranks highest, by blocks of queries: on a TPU the index
scores with their gradient and the attention over the selection in Pallas
kernels (``ops/pallas/index_scores.py``, ``selected_attention.py``), both
in ``jax.numpy`` elsewhere; the selection's searches and the elementwise
part of the indexer's loss in ``jax.numpy`` everywhere.  ``eva_attention``
(after it) is the attention of a model whose queries see the keys of their
own window exactly and every earlier window through learned summaries of
its chunks, under one softmax: a window of queries at a time, through the
selected attention's kernels' other entry point on a TPU and in
``jax.numpy`` elsewhere.  ``block_diffusion_attention`` (last) is the
attention of a model trained by diffusion over blocks: a noisy and a clean
copy of a sequence in one row of ``2S`` positions, under a mask that is not
causal: ONE call a pass of kernels of its own on a TPU (``ops/pallas/
block_diffusion_attention.py``: the mask made from positions, only live
tiles walked), a block of queries at a time in ``jax.numpy`` elsewhere.
``latent_attention`` is the core of latent attention
(MLA): scores that are the sum of a product a head and a product against
one rotary key every head shares, values narrower than the scores, in
kernels of its own on a TPU (``ops/pallas/latent_attention.py``).
``differential_attention`` (at the very end) is the core of differential
attention in its head-paired form: two softmax maps of one head size over
one value of twice that, their difference; on a TPU both maps in ONE visit
of a pair of tiles (``ops/pallas/differential_attention.py``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.observability import trace


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Plain attention; q,k,v: [B, S, H, D] (k/v heads may be fewer: GQA).

    fp32 logits + softmax regardless of input dtype; mask is broadcastable
    to [B, H, Sq, Sk] with True = attend.  ``window``: self-attention in
    which query ``t`` sees the keys ``t - window < s <= t`` alone, whatever
    else the mask allows.
    """
    if window is not None:
        mask = band_mask(q.shape[1], window) if mask is None else (
            mask & band_mask(q.shape[1], window))
    if k.shape[2] != q.shape[2]:
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def band_mask(seq_len: int, window: int) -> jnp.ndarray:
    """[1, 1, S, S] bool: the causal band of ``window`` positions."""
    ahead = jnp.arange(seq_len)[:, None] - jnp.arange(seq_len)[None, :]
    return ((ahead >= 0) & (ahead < window))[None, None]


def _flash_shard_specs(mesh):
    """(q_spec, kv_spec) for the per-shard kernel call on ``mesh``, from
    the logical rules table (``ring_attention_sharded`` does the same):
    batch over the data axes, heads over ``tp``.  The sequence stays whole
    on every shard — the kernel's causal mask is positional."""
    import flax.linen as nn

    from dlrover_tpu.parallel.sharding import spec_on_mesh

    rules = list(nn.get_logical_axis_rules()) or None
    return tuple(
        spec_on_mesh(mesh, ("batch", None, heads_axis, None), rules)
        for heads_axis in ("heads", "kv_heads")
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: bool = False,
    path_attrs: Optional[dict] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention: the Pallas TPU kernel, never the reference.

    Block sizes default to the autotuned table (``ops/pallas/tuning.py``)
    for this (seq_len, head_dim), a windowed call's under a key of its own;
    pass explicit values to override.  ``window`` (causal calls): the band
    a query sees.  Which kernels then run is one rule over the shapes
    (``ops/pallas/flash_attention.py::band_path``): where a query block
    and the ``window - 1`` keys before it (rounded up to ``block_kv``,
    which tiles ``block_q``) are no more than the sequence and fit the
    band kernels' VMEM budget, a query block meets all the keys it can
    see in ONE grid step under a plain softmax, ``block_kv`` rows at a
    time against the ``block_kv + back`` keys they can see; a wider band
    (Mistral's 4,096 on a longer sequence, a window over the sequence)
    streams its key blocks under the online softmax as the causal kernels
    do.  The record says which (``band=one_visit`` or ``streamed``), how
    many key blocks a query block visits at most and the pairs a head's
    forward pass multiplies beside those the band allows: the numbers of
    the kernels that run.  ``backward=one_call`` or ``split`` says, of
    every call, whether its backward pass scores a live pair of blocks
    once (dQ, dK and dV from one kernel: a causal call without a window,
    a head a block, over a stream of keys long enough to pay for dQ's
    float32 accumulator, ``flash_attention.py::backward_path``) or twice
    (dQ and dK/dV apart).
    ``path_attrs``: what the caller does around the kernel (``rope=none``,
    ``gate=sigmoid``), written at the end of the ``attention.path`` line.
    ``interpret=True`` runs the kernel in the Pallas interpreter (tests off
    the chip); without it a backend that is not a TPU is an error.  Under
    an active mesh of more than one device, outside any ``shard_map``, the
    call is wrapped in one.
    """
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "flash_attention needs a TPU backend (found "
            f"{jax.default_backend()!r}); use attention_impl='reference' "
            "off the chip, or pass interpret=True"
        )
    from dlrover_tpu.ops.pallas.flash_attention import (
        backward_path,
        band_record,
        heads_per_block,
        pallas_flash_attention,
    )
    from dlrover_tpu.ops.pallas.tuning import tuned_blocks
    from dlrover_tpu.ops.ring_attention import active_mesh

    seq_len, heads, head_dim = q.shape[1:]
    if not block_q or not block_kv:
        tuned_q, tuned_kv = tuned_blocks(seq_len, head_dim, window)
        block_q = block_q or tuned_q
        block_kv = block_kv or tuned_kv
    banded = {}
    if window is not None:
        banded = dict(window=window, **band_record(
            seq_len, block_q, block_kv, window, head_dim))
    # ``layout``: the kernels read and write [B, S, H*D] in column blocks
    # of 128 lanes, ``heads_per_block`` heads in each
    trace.note_trace_time(
        "attention.path", impl="flash", seq=seq_len, head_dim=head_dim,
        heads=heads, blocks=(block_q, block_kv), layout="bsd",
        heads_per_block=heads_per_block(head_dim), **banded,
        backward=backward_path(
            seq_len, head_dim, block_q, block_kv, window),
        **(path_attrs or {}),
    )

    def kernel(q_, k_, v_):
        return pallas_flash_attention(
            q_, k_, v_, causal, block_q, block_kv, interpret, window
        )

    mesh = active_mesh()
    if (
        mesh is None
        or mesh.size == 1
        # already per-shard: the enclosing shard_map owns the mesh axes
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return kernel(q, k, v)
    from dlrover_tpu.parallel.collectives import shard_map_unchecked

    def per_shard(q_, k_, v_):
        # the compiler names a kernel's instruction after the innermost
        # scope around its call, which the shard_map would make
        # "shard_map": keep ``_attend``, by which the by-shape reader
        # ``fa2_ms_per_step`` finds the unsharded call (``attn._attend``).
        # What the kernel is FOR is read off the whole path, whose
        # ``attn.core`` the model puts around ``_attend``
        # (``observability/trace.py::scope_of``): new scopes go around
        # that name, never between it and the call
        with jax.named_scope("shard._attend"):
            return kernel(q_, k_, v_)

    q_spec, kv_spec = _flash_shard_specs(mesh)
    return shard_map_unchecked(
        per_shard, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )(q, k, v)


def attention_path(backend: str, seq_len: int, head_dim: int, heads: int,
                   kv_heads: int, window: Optional[int] = None) -> str:
    """``"flash"`` or ``"reference"``: the kernel wherever it can run, from
    what the code can observe and nothing else."""
    from dlrover_tpu.ops.pallas.flash_attention import kernel_takes

    if backend == "tpu" and kernel_takes(seq_len, head_dim, heads, kv_heads,
                                         window):
        return "flash"
    return "reference"


def causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Causal self-attention, q/k/v: [B, S, H, D]: the FA2 kernel on a TPU
    at a shape it takes (no [B, H, S, S] tensor in HBM, forward or
    backward), the reference core with the caller's causal ``mask``
    everywhere else, both under the causal ``window`` where one is given.
    Both compute float32 scores and softmax from the operands as given and
    accumulate in float32."""
    seq_len, heads, head_dim = q.shape[1:]
    if attention_path(jax.default_backend(), seq_len, head_dim, heads,
                      k.shape[2], window) == "flash":
        # writes the record
        return flash_attention(q, k, v, causal=True, window=window)
    trace.note_trace_time(
        "attention.path", impl="reference", seq=seq_len, head_dim=head_dim,
        heads=heads, blocks=None,
        **({} if window is None else {"window": window}))
    return reference_attention(q, k, v, mask, window)


# --------------------------------------------------------------------------
# Attention over the keys a learned indexer selects (DeepSeek-V3.2-Exp's
# "lightning indexer"; Keye-VL-2.0's ``sa_config``)
# --------------------------------------------------------------------------

#: index scores of the ``topk``-th and the next key closer than this: the
#: selection of that query hangs on rounding (``index_low_margin_share``)
INDEX_LOW_MARGIN = 1e-3


def _sortable(x):
    """float32 -> uint32 with the same order (negative zero below zero);
    every real value maps above 0, which stands for "no key"."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(0x80000000)
    return jnp.where(bits >= top, ~bits, bits | top)


def _kth_largest(keys, k, bits=32):
    """Per row of uint32 ``keys`` [..., n] the ``k``-th largest (``k`` [...]
    int32, at least 1), 0 where a row has fewer than ``k`` nonzero keys (no
    key of such a row is at 0, so none is kept by it):
    the threshold is built from its highest bit down, one count of the
    row for each bit, so nothing is sorted and the result is exact."""
    def add_bit(i, found):
        bit = jnp.left_shift(jnp.uint32(1), (bits - 1 - i).astype(jnp.uint32))
        tried = found | bit
        enough = (keys >= tried[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, tried, found)

    return jax.lax.fori_loop(
        0, bits, add_bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def select_top_keys(scores, causal, topk):
    """``(keep [..., q, n] bool, low [..., q] bool)``: for each query the
    ``topk`` largest float32 ``scores`` among the keys ``causal`` allows,
    ties to the earlier key (what ``jax.lax.top_k`` keeps), all of them
    where there are no more than ``topk``; ``low`` where the ``topk``-th and
    the next score lie closer than ``INDEX_LOW_MARGIN``.  Exact: a
    threshold found by counting, then the earliest of the keys that equal
    it, found the same way."""
    n = scores.shape[-1]
    keys = jnp.where(causal, _sortable(scores), jnp.uint32(0))
    thr = _kth_largest(keys, jnp.int32(topk))[..., None]
    above = keys > thr
    equal = (keys == thr) & causal
    # fewer than ``topk`` keys lie above the threshold, by its definition
    need = topk - above.sum(-1, dtype=jnp.int32)
    # of the keys at the threshold the ``need`` earliest: the same search
    # on their positions counted from the end
    from_end = jnp.where(equal, jnp.uint32(n) - jnp.arange(n, dtype=jnp.uint32),
                         jnp.uint32(0))
    latest = _kth_largest(from_end, need, n.bit_length())
    keep = above | (equal & (from_end >= latest[..., None]))
    # the next score below the kept ones: the threshold itself where more
    # keys equal it than are needed
    at_thr = jnp.min(jnp.where(above | equal, scores, jnp.inf), axis=-1)
    below = jnp.max(jnp.where(causal & ~above & ~equal, scores, -jnp.inf), axis=-1)
    tied = equal.sum(-1, dtype=jnp.int32) > need
    bites = causal.sum(-1, dtype=jnp.int32) > topk
    low = bites & (tied | (at_thr - below < INDEX_LOW_MARGIN))
    return keep, low


def _index_scores(index_q, index_k, index_w):
    """[B, q, keys] float32: ``sum_j w[t, j] relu(q_I[t, j] . k_I[s])``."""
    dots = jnp.einsum("bqjc,bkc->bqjk", index_q, index_k,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(dots), index_w)


def _dense_selected(q, k, v, keep):
    """One block of queries ``q`` [B, q, H, D] over the keys ``keep`` [B,
    q, keys] allows, in ``jax.numpy`` over dense ``[heads, q, keys]``
    scores: ``(out [B, q, H, D], the probabilities' mean over the heads
    [B, q, keys] float32)``.  The reference of the kernels
    (``ops/pallas/selected_attention.py``) and the path wherever they do
    not run."""
    B, Q, H, D = q.shape
    G = k.shape[2]
    q = q.reshape(B, Q, G, H // G, D)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                        preferred_element_type=jnp.float32) * D ** -0.5
    logits = jnp.where(keep[:, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(q.dtype), v)
    return out.reshape(B, Q, H, D), probs.mean(axis=(1, 2))


def _index_kl(index_scores, keep, target):
    """Sum over a block's queries of ``KL(target || softmax over the kept
    keys of the index scores)``: elementwise work and row sums over ``[B,
    q, keys]``, the same on every path."""
    lowest = jnp.finfo(jnp.float32).min
    log_index = jax.nn.log_softmax(jnp.where(keep, index_scores, lowest), axis=-1)
    kl = jnp.where(keep, jax.scipy.special.xlogy(target, target)
                   - target * log_index, 0.0)
    return kl.sum()


@jax.checkpoint
def _attend_selected(q, k, v, index_scores, keep):
    """One block of queries over the keys ``keep`` allows: ``(out [B, q, H,
    D], sum over the block's queries of KL(p || softmax(index scores)))``
    with ``p`` the attention's own probabilities averaged over the heads,
    under ``stop_gradient``.  Rematerialised: the backward pass holds one
    block's ``[heads, q, keys]`` scores at a time."""
    with jax.named_scope("selected"):
        out, target = _dense_selected(q, k, v, keep)
    with jax.named_scope("index_loss"):
        return out, _index_kl(index_scores, keep,
                              jax.lax.stop_gradient(target))


def _attend_selected_kernels(q, k, v, index_scores, keep, tiling,
                             interpret=False):
    """``_attend_selected`` with the attention and the heads' mean
    probabilities in Pallas kernels: no ``[heads, q, keys]`` tensor leaves
    the chip's fast memory, forward or backward.  The kernels' custom
    gradient keeps ``out`` and the LSE, and names them so that the layer's
    rematerialisation keeps them too (``ops/pallas/kept.py``; the LSE as
    ``[B, H, q]`` float32): the backward pass of a rematerialised layer
    runs the heads' mean kernel again, from the kept LSE, and not the
    attention's forward kernel.  The loss, rematerialised, keeps ``target``
    and the index scores it was given (16 MB for the last block of 8192
    keys), not its softmax; the layer keeps neither."""
    from dlrover_tpu.ops.pallas.selected_attention import selected_attention

    with jax.named_scope("selected"):
        out, target = selected_attention(q, k, v, keep, tiling, interpret)
    with jax.named_scope("index_loss"):
        return out, jax.checkpoint(_index_kl)(
            index_scores, keep, jax.lax.stop_gradient(target))


def _index_scores_kernels(index_q, index_k, index_w, tiling, interpret=False):
    """``_index_scores`` in Pallas kernels, its gradient too: no ``[q,
    index heads, keys]`` tensor leaves the chip's fast memory."""
    from dlrover_tpu.ops.pallas.index_scores import index_scores

    return index_scores(index_q, index_k, index_w, tiling, interpret)


def _note_kept(core, q):
    """``remat.kept``, beside ``attention.path``: what a rematerialised
    layer keeps of the mask-operand kernels' calls over all of ``q``'s
    rows.  A core on its ``jax.numpy`` body keeps nothing and notes
    nothing."""
    from dlrover_tpu.ops.pallas import kept
    from dlrover_tpu.ops.pallas.selected_attention import kept_bytes

    kept.note(core, **kept_bytes(q))


def selected_attend_path(backend: str, block: int, head_dim: int, heads: int,
                         kv_heads: int) -> str:
    """``"pallas"`` or ``"jnp"``: which body attends to a block's selected
    keys, from what the code can observe (as ``attention_path``)."""
    from dlrover_tpu.ops.pallas.selected_attention import kernels_take

    if backend == "tpu" and kernels_take(block, head_dim, heads, kv_heads):
        return "pallas"
    return "jnp"


def index_scores_path(backend: str, block: int, index_heads: int,
                      index_dim: int) -> str:
    """``"pallas"`` or ``"jnp"``: which body computes a block's index
    scores and their gradient (as ``selected_attend_path``)."""
    from dlrover_tpu.ops.pallas.index_scores import kernels_take

    if backend == "tpu" and kernels_take(block, index_heads, index_dim):
        return "pallas"
    return "jnp"


@functools.partial(jax.jit, static_argnames=(
    "first", "topk", "tiling", "index_tiling"))
def _attend_block(q, k, v, index_q, index_k, index_w, *, first, topk, tiling,
                  index_tiling):
    """One block of queries, the ``first``-th of the sequence on, over the
    keys up to its last query: ``(out, the block's sum of KL, its count of
    queries with a low selection margin)``.  The block's index scores are
    computed once: the selection reads them under ``stop_gradient``, the
    loss as they are.  ``tiling`` ``None``: the attention in ``jax.numpy``;
    ``index_tiling`` ``None``: the index scores in ``jax.numpy``, their
    products rematerialised.  Under ``jax.jit`` so that a program which
    traces the model more than once (its initialisation, a forward pass,
    the step) traces a block's selection, kernels and loss once: on the
    host of a v5e a trace of the 48 kernel calls of one layer takes a second
    and there are five in a benchmark run's set-up."""
    B, block = q.shape[:2]
    last = k.shape[1]
    causal = (jnp.arange(first, last)[:, None] >= jnp.arange(last))[None]
    low = jnp.float32(0)
    with jax.named_scope("scores"):
        if index_tiling is None:
            scores = jax.checkpoint(_index_scores)(index_q, index_k, index_w)
        else:
            scores = _index_scores_kernels(
                index_q, index_k, index_w, index_tiling)
    if last <= topk:
        keep = jnp.broadcast_to(causal, (B, block, last))
    else:
        with jax.named_scope("select"):
            keep, low_margin = select_top_keys(
                jax.lax.stop_gradient(scores), causal, topk)
            low = low_margin.sum(dtype=jnp.float32)
    if tiling is None:
        out, kl = _attend_selected(q, k, v, scores, keep)
    else:
        out, kl = _attend_selected_kernels(q, k, v, scores, keep, tiling)
    return out, kl, low


def indexed_sparse_attention(q, k, v, index_q, index_k, index_w, topk,
                             block=512):
    """Causal attention in which each query attends to the ``topk`` keys
    its indexer scores highest: ``(out [B, S, H, D], index loss, share of
    queries with a low selection margin)``.

    ``q`` [B, S, H, D], ``k``/``v`` [B, S, G, D] (GQA).  The indexer:
    ``index_q`` [B, S, J, C], one shared key head ``index_k`` [B, S, C] and
    head weights ``index_w`` [B, S, J] give, in float32, ``I[t, s] = sum_j
    w[t, j] relu(q_I[t, j] . k_I[s]) (J C)^-0.5`` for ``s <= t``.  The
    selection (``select_top_keys``: exact, on the float32 scores) carries
    no gradient; the index loss ``mean_t KL(p[t, S_t] || softmax_{S_t}
    I[t, .])`` teaches the indexer from the attention's own probabilities
    and reaches nothing else.

    Blocks of ``block`` queries, each over the keys up to its last query,
    so nothing of size ``heads x S x S`` is ever whole: a block's selection
    is a mask ``[block, keys]``, its scores ``[H, block, keys]`` in
    ``jax.numpy`` and tiles in fast memory in the kernels, which run on a
    TPU at the shapes they take (``selected_attend_path``); so the index
    heads' products ``[block, J, keys]`` (``index_scores_path``), of which
    ``I [block, keys]`` alone is ever whole, computed once a block and
    pass."""
    B, S, H, D = q.shape
    J, C = index_q.shape[2:]
    block = min(block, S)
    if S % block:
        raise ValueError(f"seq {S} is not a multiple of the block {block}")
    tiling = None
    path = dict(attend=selected_attend_path(
        jax.default_backend(), block, D, H, k.shape[2]))
    if path["attend"] == "pallas":
        from dlrover_tpu.ops.pallas.tuning import selected_tiling

        tiling = selected_tiling(block, D)
        path.update(block_kv=tiling[0], mean_block_kv=tiling[1])
    index_tiling = None
    path["index"] = index_scores_path(jax.default_backend(), block, J, C)
    if path["index"] == "pallas":
        from dlrover_tpu.ops.pallas.tuning import index_tiling as tuned

        index_tiling = tuned(block, C)
        path.update(index_block_kv=index_tiling[0])
    trace.note_trace_time(
        "attention.path", impl="indexed_sparse", seq=S, head_dim=D, heads=H,
        topk=topk, index_heads=J, index_dim=C, block=block,
        select="threshold_by_counting", **path)
    if tiling is not None:
        _note_kept("indexed_sparse", q)
    index_w = index_w.astype(jnp.float32) * (J * C) ** -0.5
    outs, loss, low = [], jnp.float32(0), jnp.float32(0)
    for first in range(0, S, block):
        last = first + block
        out, kl, low_here = _attend_block(
            q[:, first:last], k[:, :last], v[:, :last],
            index_q[:, first:last], index_k[:, :last], index_w[:, first:last],
            first=first, topk=topk, tiling=tiling, index_tiling=index_tiling)
        outs.append(out)
        loss, low = loss + kl, low + low_here
    return jnp.concatenate(outs, axis=1), loss / (B * S), low / (B * S)


# --------------------------------------------------------------------------
# Attention over a window's own keys and learned summaries of every earlier
# window's chunks (EVA, arXiv:2302.04542, as EvaByte's model code has it)
# --------------------------------------------------------------------------

def eva_pool(k, v, mu, phi, chunk):
    """One summary a chunk of ``chunk`` positions: ``(pooled keys, pooled
    values [B, S / chunk, H, D], the largest pooling weight of each chunk
    [2, B, S / chunk, H] float32)``.  Keys are pooled by ``softmax_m(mu .
    k_m)`` and values by ``softmax_m(phi . k_m)`` over the chunk's
    positions ``m``; ``mu``, ``phi`` [H, D] are the head's learned vectors,
    the logits unscaled and in float32."""
    B, S, H, D = k.shape
    k = k.reshape(B, S // chunk, chunk, H, D)
    v = v.reshape(B, S // chunk, chunk, H, D)

    def weights(vector):
        return jax.nn.softmax(jnp.einsum(
            "bjmhd,hd->bjmh", k, vector.astype(k.dtype),
            preferred_element_type=jnp.float32), axis=2)

    def pooled(weight, rows):
        # the weights in the operands' dtype, as an attention's probabilities
        return jnp.einsum("bjmh,bjmhd->bjhd", weight.astype(rows.dtype), rows,
                          preferred_element_type=jnp.float32).astype(rows.dtype)

    a, b = weights(mu), weights(phi)
    pooled_k, pooled_v = pooled(a, k), pooled(b, v)
    largest = jnp.stack([a.max(axis=2), b.max(axis=2)])
    return pooled_k, pooled_v, jax.lax.stop_gradient(largest)


@jax.checkpoint
def _eva_window(q, k, v, pooled_k, pooled_v):
    """One window's queries ``q`` [B, W, H, D] over ``[its own keys, causal;
    the summaries of every earlier window]`` under one softmax: ``(out [B,
    W, H, D], the softmax mass on the summaries summed over the window's
    queries and heads)``.  Rematerialised: the backward pass holds one
    window's ``[H, W, W + summaries]`` scores at a time."""
    W, D = q.shape[1], q.shape[-1]
    keys = jnp.concatenate([k, pooled_k], axis=1)
    values = jnp.concatenate([v, pooled_v], axis=1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                        preferred_element_type=jnp.float32) * D ** -0.5
    allowed = jnp.concatenate(
        [jnp.tril(jnp.ones((W, W), bool)),
         jnp.ones((W, pooled_k.shape[1]), bool)], axis=1)
    logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), values)
    with jax.named_scope("summary_mass"):
        return out, jax.lax.stop_gradient(probs[..., W:].sum())


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def _eva_window_kernels(q, k, v, pooled_k, pooled_v, *, block_kv,
                        interpret=False):
    """``_eva_window`` with the attention in the Pallas kernels of
    ``ops/pallas/selected_attention.py``: no ``[H, W, keys]`` tensor leaves
    the chip's fast memory, forward or backward.  The window's keys go under
    a causal mask, the kernels' operand; the summaries are the keys every
    query attends to.  No ``jax.checkpoint`` of its own: the kernels'
    custom gradient keeps ``out`` and the LSE, and names them so that the
    layer's rematerialisation, which recomputes the mask, the slices and
    the mass below, keeps them too (``ops/pallas/kept.py``; the LSE as
    ``[B, H, W]`` float32) and runs no forward kernel a second time.  The
    mass on the summaries is ``sum exp(q . pooled_k - lse)``, one product
    more over the summaries' columns alone.
    Under ``jax.jit``, as ``_attend_block``: a second trace of the model
    finds a window's kernels traced."""
    from dlrover_tpu.ops.pallas.selected_attention import masked_attention

    B, W, _, D = q.shape
    keep = jnp.broadcast_to(jnp.tril(jnp.ones((W, W), bool)), (B, W, W))
    if not pooled_k.shape[1]:   # the first window: no summary yet
        out, _ = masked_attention(q, k, v, keep, None, block_kv, interpret)
        return out, jnp.float32(0)
    out, lse = masked_attention(
        q, k, v, keep, (pooled_k, pooled_v), block_kv, interpret)
    with jax.named_scope("summary_mass"):
        on_summaries = jnp.einsum(
            "bqhd,bkhd->bhqk", q, pooled_k,
            preferred_element_type=jnp.float32) * D ** -0.5
        mass = jnp.exp(on_summaries - lse[..., None]).sum()
    return out, jax.lax.stop_gradient(mass)


def eva_exact_path(backend: str, window: int, chunk: int, head_dim: int,
                   heads: int) -> str:
    """``"pallas"`` or ``"jnp"``: which body attends to a window's keys and
    summaries, from what the code can observe (as ``selected_attend_path``):
    the kernels on a TPU where they take a block of ``window`` queries and a
    window's summaries fill whole 128-lane tiles."""
    from dlrover_tpu.ops.pallas.flash_attention import LANES

    if (window // chunk) % LANES == 0 and selected_attend_path(
            backend, window, head_dim, heads, heads) == "pallas":
        return "pallas"
    return "jnp"


def eva_attention(q, k, v, mu, phi, window, chunk):
    """Causal attention in which a query attends exactly to the keys of its
    own window of ``window`` positions and, through one learned summary (a
    pooled key and a pooled value, ``eva_pool``) of each ``chunk`` positions,
    to every EARLIER window, none of its own; one softmax over both:
    ``(out [B, S, H, D], the mean over the queries past the first window of
    the softmax mass on summaries, the mean over chunks, heads and the two
    poolings of the largest pooling weight)``.

    ``q``, ``k``, ``v`` [B, S, H, D] (as many key heads as query heads),
    ``mu``, ``phi`` [H, D].  In the first window there is no summary and the
    layer is plain causal attention; a sequence shorter than the window is
    one window.  Gradients flow through the summaries into ``k``, ``v``,
    ``mu`` and ``phi``.  A window of queries at a time against ``[its
    window's keys ; the summaries before it]``, so nothing ``[S, S]`` is
    whole: the largest block of scores is ``[H, window, window + (S -
    window) / chunk]`` in ``jax.numpy`` and tiles in fast memory in the
    kernels, which run on a TPU at the shapes they take
    (``eva_exact_path``); the pooling is ``jax.numpy`` on both paths."""
    B, S, H, D = q.shape
    window = min(window, S)
    if S % window or window % chunk:
        raise ValueError(f"seq {S} is not a multiple of the window {window}, "
                         f"or the window of the chunk {chunk}")
    if k.shape != q.shape:
        raise ValueError(f"eva attention wants a key head a query head; got "
                         f"q {q.shape}, k {k.shape}")
    windows, per_window = S // window, window // chunk
    attend_window = _eva_window
    path = dict(exact=eva_exact_path(
        jax.default_backend(), window, chunk, D, H))
    if path["exact"] == "pallas":
        from dlrover_tpu.ops.pallas.tuning import selected_tiling

        path.update(block_kv=selected_tiling(window, D)[0])
        attend_window = functools.partial(
            _eva_window_kernels, block_kv=path["block_kv"])
    trace.note_trace_time(
        "attention.path", impl="eva", seq=S, window=window, chunk=chunk,
        windows=windows, summaries_max=(windows - 1) * per_window, heads=H,
        head_dim=D, **path)
    if path["exact"] == "pallas":
        _note_kept("eva", q)
    with jax.named_scope("pool"):
        pooled_k, pooled_v, largest = eva_pool(k, v, mu, phi, chunk)
    outs, mass = [], jnp.float32(0)
    # the windows' slicing, the kernels and the gathering of their outputs;
    # the mass on summaries has its own scope inside a window's body
    with jax.named_scope("windows"):
        for w in range(windows):
            own = slice(w * window, (w + 1) * window)
            out, on_summaries = attend_window(
                q[:, own], k[:, own], v[:, own],
                pooled_k[:, :w * per_window], pooled_v[:, :w * per_window])
            outs.append(out)
            mass = mass + on_summaries
        out = jnp.concatenate(outs, axis=1)
    later_queries = B * H * (S - window)
    return out, mass / max(later_queries, 1), largest.mean()


# --------------------------------------------------------------------------
# Attention under the block-diffusion mask (BD3-LMs, arXiv:2503.09573, the
# vectorised training step; SDAR, arXiv:2510.06303): a noisy and a clean
# copy of one sequence in one batch row
# --------------------------------------------------------------------------

def block_diffusion_pairs(seq: int, block: int) -> int:
    """Query-key pairs a head the mask allows: the clean half causal by
    block (``S^2 / 2 + L S / 2``), the noisy half every earlier clean block
    (``S^2 / 2 - L S / 2``) and its own noisy block (``L S``)."""
    return seq * seq + block * seq


def block_diffusion_keep(first, last, block, noisy):
    """The mask of the queries at positions ``[first, last)``: ``keep [1,
    last - first, keys]`` bool, from the rule by ``iota``.  A clean query
    at position ``r`` over the clean keys ``[0, last)``: allowed where
    ``c // block <= r // block`` (causal by block, a block both ways).  A
    noisy one over ``[clean keys [0, last) ; noisy keys [first, last)]``:
    a clean key where ``c // block < r // block`` (every EARLIER block), a
    noisy key where ``c // block == r // block`` (its own block, both
    ways).  A clean query sees no noisy key: none is among its keys."""
    r = jnp.arange(first, last)[:, None] // block
    c = jnp.arange(last)[None, :] // block
    if not noisy:
        return (c <= r)[None]
    own = jnp.arange(first, last)[None, :] // block
    return jnp.concatenate([c < r, own == r], axis=1)[None]


@functools.partial(jax.jit, static_argnames=("first", "block", "noisy"))
def _block_diffusion_block(q, k, v, *, first, block, noisy):
    """One block of queries ``q`` [B, n, H, D] at positions ``[first, first
    + n)`` over the keys the mask can allow it, ``k``/``v`` [B, keys, G, D]:
    the clean keys ``[0, first + n)`` and, for ``noisy`` queries, their own
    noisy keys joined behind them, ONE softmax over both parts.
    ``jax.numpy`` over dense ``[heads, n, keys]`` scores, rematerialised:
    the body of every backend but a TPU (there the kernels take the whole
    arrays, ``block_diffusion_attention``).  Under ``jax.jit``, as
    ``_attend_block``: a second trace of the model finds a block traced."""
    B, n = q.shape[:2]
    with jax.named_scope("bd_keys"):
        keep = jnp.broadcast_to(
            block_diffusion_keep(first, first + n, block, noisy),
            (B, n, k.shape[1]))
    with jax.named_scope("bd_noisy" if noisy else "bd_clean"):
        return jax.checkpoint(
            lambda *operands: _dense_selected(*operands)[0])(q, k, v, keep)


def block_diffusion_path(backend: str, seq: int, query_block: int,
                         head_dim: int, heads: int, kv_heads: int) -> str:
    """``"pallas"`` or ``"jnp"``: which body attends under the
    block-diffusion mask, from what the code can observe (as
    ``selected_attend_path``, whose rule on a block's shape is the rule on
    a tile's here): the kernels of ``ops/pallas/
    block_diffusion_attention.py`` on a TPU where they take a tile of
    ``query_block`` positions and the sequence is a whole number of them."""
    if seq % query_block == 0 and selected_attend_path(
            backend, query_block, head_dim, heads, kv_heads) == "pallas":
        return "pallas"
    return "jnp"


def block_diffusion_attention(q, k, v, block, query_block=None,
                              interpret: bool = False):
    """Attention of the ``2S`` rows ``[noisy copy ; clean copy]`` of one
    sequence of ``S`` positions in blocks of ``block``, ``q`` [B, 2S, H, D],
    ``k``/``v`` [B, 2S, G, D] (GQA), under the block-diffusion mask: a clean
    row sees the clean rows of its own and every earlier block; a noisy row
    sees the NOISY rows of its own block and the CLEAN rows of every
    earlier block, never its own block's clean rows; no row sees a noisy
    row of another block.  ``S^2 + block S`` pairs a head
    (``block_diffusion_pairs``), twice a causal layer's.

    By tiles of ``query_block`` queries (a multiple of ``block``, so no
    block of the sequence straddles two; ``None``: the kernels' tile on a
    TPU, 512 in ``jax.numpy``),
    each against only the keys the mask can allow it, so nothing ``[2S,
    2S]`` is ever whole.  On a TPU at the shapes they take
    (``block_diffusion_path``) the kernels of
    ``ops/pallas/block_diffusion_attention.py``: ONE call a pass over the
    whole arrays, the mask made from positions inside it, only the live
    tiles walked (every tile under the diagonal without a mask, the cut
    ones by sub-tiles with the dead ones skipped), under the sub-scope
    ``bd_noisy`` for both halves; the ``attention.path`` record then says
    what a head's forward pass multiplies (``pairs_multiplied``: ``S^2 +
    (2 sub + tile) S / 2`` where the cut tiles go by sub-tiles of ``sub``)
    beside the allowed ``pairs`` and the ``calls`` a layer and pass;
    ``interpret``: as on a TPU, the kernels in the Pallas interpreter
    (tests off the chip).  Elsewhere ``jax.numpy`` block by block: a block's mask is
    ``[query_block, keys]`` (``block_diffusion_keep``) and its scores
    ``[H, query_block, keys]``."""
    from dlrover_tpu.ops.pallas import block_diffusion_attention as kernels

    B, rows, H, D = q.shape
    S = rows // 2
    if rows % 2 or S % block:
        raise ValueError(f"{rows} rows are not two copies of a whole number "
                         f"of blocks of {block}")
    backend = "tpu" if interpret else jax.default_backend()
    query_block = min(S, query_block or (
        kernels.tile_for(q.dtype) if backend == "tpu" else 512))
    if query_block % block:
        raise ValueError(f"a block of {query_block} queries straddles the "
                         f"sequence's blocks of {block}")
    path = dict(exact=block_diffusion_path(
        backend, S, query_block, D, H, k.shape[2]))
    if path["exact"] == "pallas":
        sub = kernels.sub_tile(query_block, block)
        path.update(
            sub=sub, calls=1,
            pairs_multiplied=kernels.pairs_multiplied(S, query_block, sub))
    trace.note_trace_time(
        "attention.path", impl="block_diffusion", seq=S, rows=rows,
        block=block, query_block=query_block,
        pairs=block_diffusion_pairs(S, block), heads=H, head_dim=D, **path)
    if path["exact"] == "pallas":
        _note_kept("block_diffusion", q)
        with jax.named_scope("bd_noisy"):   # one call for both halves
            return kernels.block_diffusion_kernels(
                q, k, v, block, query_block, sub, interpret)
    noisy, clean = [], []
    for first in range(0, S, query_block):
        last = min(first + query_block, S)
        seen = slice(S, S + last)
        attend = functools.partial(
            _block_diffusion_block, first=first, block=block)
        clean.append(attend(
            q[:, S + first: S + last], k[:, seen], v[:, seen], noisy=False))
        with jax.named_scope("bd_keys"):    # [clean keys ; own noisy keys]
            joined = [jnp.concatenate([t[:, seen], t[:, first:last]], axis=1)
                      for t in (k, v)]
        noisy.append(attend(q[:, first:last], *joined, noisy=True))
    return jnp.concatenate(noisy + clean, axis=1)


# --------------------------------------------------------------------------
# The core of latent attention (MLA, arXiv:2405.04434): every head's score
# is q_nope k_nope^T + q_pe k_pe^T, the second against ONE rotary key
# --------------------------------------------------------------------------

def latent_attention_path(backend: str, seq: int, heads: int,
                          nope_dim: int = 128, rope_dim: int = 64,
                          v_dim: int = 128) -> str:
    """``"pallas"`` or ``"reference"``: the kernels of
    ``ops/pallas/latent_attention.py`` on a TPU at the widths and lengths
    they take, the ``jax.numpy`` body everywhere else; from what the code can observe and nothing
    else."""
    from dlrover_tpu.ops.pallas.latent_attention import kernels_take

    del heads   # any count: a head is a grid step of the kernels
    if backend == "tpu" and kernels_take(seq, nope_dim, rope_dim, v_dim):
        return "pallas"
    return "reference"


def _latent_reference(q_nope, q_pe, k_nope, k_pe, v):
    """The ``jax.numpy`` body: float32 scores and softmax whole (``[B, H,
    S, S]``: the CPU's tests and rehearsals, never the chip's cell)."""
    S = q_nope.shape[1]
    scale = (q_nope.shape[-1] + q_pe.shape[-1]) ** -0.5
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def latent_attention(q_nope, q_pe, k_nope, k_pe, v, interpret: bool = False,
                     rope: Optional[str] = None):
    """Causal self-attention a head with scores ``(q_nope k_nope^T + q_pe
    k_pe^T) / sqrt(D + R)``: ``q_nope, k_nope`` [B, S, H, D], ``q_pe`` [B,
    S, H, R], ``k_pe`` [B, S, R] (one rotary key head, shared by every
    query head), ``v`` [B, S, H, Dv] -> [B, S, H, Dv].  Softmax in float32.
    On a TPU through the Pallas kernels (no ``[H, S, S]`` array in HBM,
    forward or backward), ``jax.numpy`` elsewhere; ``interpret``: the
    kernels in the Pallas interpreter (tests off the chip);
    ``rope``: how the caller turned the rotary parts (``pairs`` or
    ``halves``), for the ``attention.path`` record alone, which on the
    kernels' path also says ``backward=one_call`` (a live pair of blocks
    scored once, all five gradients from one kernel, as FA2's record says
    of its calls).  Sub-scope ``latent`` of the caller's ``attn.core``."""
    B, S, H, D = q_nope.shape
    R, Dv = q_pe.shape[-1], v.shape[-1]
    exact = "pallas" if interpret else latent_attention_path(
        jax.default_backend(), S, H, D, R, Dv)
    attrs = dict(impl="latent", seq=S, heads=H, qk=f"{D}+{R}", v=Dv)
    if rope is not None:
        attrs["rope"] = rope
    with jax.named_scope("latent"):
        if exact != "pallas":
            trace.note_trace_time("attention.path", blocks=None,
                                  exact="reference", **attrs)
            return _latent_reference(q_nope, q_pe, k_nope, k_pe, v)
        from dlrover_tpu.ops.pallas import kept
        from dlrover_tpu.ops.pallas.latent_attention import (
            blocks_for,
            kept_bytes,
            latent_attention_kernels,
        )

        blocks = blocks_for(S)
        trace.note_trace_time("attention.path", blocks=blocks,
                              exact="pallas", backward="one_call", **attrs)
        kept.note("latent", **kept_bytes(v))
        return latent_attention_kernels(
            q_nope, q_pe, k_nope, k_pe, v, *blocks, interpret)


# --------------------------------------------------------------------------
# Differential attention (arXiv:2410.05258; the head-paired form of
# Phi-4-mini-flash's model code)
# --------------------------------------------------------------------------

def differential_attention(q, k, v, lam, mask=None, window=None,
                           impl: str = "reference",
                           interpret: bool = False) -> jnp.ndarray:
    """``(softmax(q1 k1^T / sqrt(D)) - lam softmax(q2 k2^T / sqrt(D))) V``
    under the causal mask (and ``window``), float32 ``[B, S, H/2, 2D]``: q
    ``[B, S, H, D]`` whose heads ``(2j, 2j+1)`` are ``q1_j, q2_j``; k, v
    ``[B, S, G, D]`` whose heads ``(2m, 2m+1)`` are ``k1_m, k2_m`` and, side
    by side, ``V_m`` of ``2D``; ``H/2`` a multiple of ``G/2`` (query pair
    ``j`` reads key pair ``j // (H / G)``); ``lam`` a scalar.

    ``impl`` ``"reference"``: the reference core twice, ``(q1, k1, V)`` and
    ``(q2, k2, V)``, the value at its own width.  ``"flash"``: the kernels
    of ``ops/pallas/differential_attention.py`` (heads of 64: a pair, and
    its value, are one 128-lane column block of the arrays as they are), one
    forward call that visits a pair of tiles once for both maps against ONE
    value tile, and one backward call of eight block products a pair of
    tiles (``dO V^T`` and ``(P1 - lam P2)^T dO`` serve both maps), the mask
    and a window's band made from positions in the tiles they cut and
    nowhere else; never the reference: off a TPU it raises unless
    ``interpret`` asks for the Pallas interpreter.  The ``attention.path``
    record says what runs (``tiles_live`` a head of ``tiles_walked`` grid
    steps of its forward pass), ``remat.kept`` what a rematerialised layer
    keeps of it (``O1``, ``O2`` and the two LSEs)."""
    B, S, H, D = q.shape
    G = k.shape[2]
    attrs = dict(impl="differential", seq=S, heads=H, head_dim=D)
    band = {} if window is None else {"window": window}
    if impl != "flash":
        trace.note_trace_time(
            "attention.path", **attrs, exact="reference", **band)
        q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
        k1, k2 = k[:, :, 0::2], k[:, :, 1::2]
        wide = v.reshape(B, v.shape[1], G // 2, 2 * D)
        first = reference_attention(q1, k1, wide, mask, window)
        second = reference_attention(q2, k2, wide, mask, window)
        return first.astype(jnp.float32) - lam * second.astype(jnp.float32)
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "the differential kernels need a TPU backend (found "
            f"{jax.default_backend()!r}); use attention_impl='reference' "
            "off the chip, or pass interpret=True")
    from dlrover_tpu.ops.pallas import differential_attention as kernels
    from dlrover_tpu.ops.pallas import kept

    tiles = kernels.tiles_for(S, window)
    walk = kernels.Walk(S, *tiles, window)
    trace.note_trace_time(
        "attention.path", **attrs, core="pallas", maps=2, scores_over=D,
        value=2 * D, backward_products=8, **band, tiles=tiles,
        tiles_live=walk.tiles_live, tiles_walked=walk.tiles_walked)
    kept.note("diff", **kernels.kept_bytes(q))
    return kernels.differential_attention_kernels(
        q, k, v, jnp.asarray(lam, jnp.float32), window, tiles, interpret)
