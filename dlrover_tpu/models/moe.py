"""Mixture-of-experts feed-forward block for the Llama decoder: dropless
top-k routing, experts sharded over the ``ep`` mesh axis.

``MoELlamaConfig.feed_forward`` hands ``models/llama.py``'s one decoder this
block in place of the dense SwiGLU; everything else (attention, norms,
``remat``, the scanned layer stack, the head) is the dense model's code.

Routing (OLMoE, arXiv:2409.02060): ``softmax(x W_r)`` in float32 over all
experts, the ``top_k`` largest kept with their softmax weights as they are,
or divided by their sum where the model says so (``norm_topk_prob``);
``router_scores="sigmoid"`` scores each expert by ``sigmoid(x W_r)``
instead (DeepSeek-V3's), and ``shared_experts`` adds a dense SwiGLU every
token visits (scope ``moe/shared``).  With ``n_group`` the choice is made
by groups (DeepSeek-V3's, arXiv:2412.19437): the experts in ``n_group``
runs of consecutive columns, a group's score the sum of its two largest
scores, the ``topk_group`` best groups kept and the ``top_k`` largest
scores inside them chosen.  With ``selection_bias`` the CHOICE (groups and
experts) is made on ``scores + b`` and the weights stay the scores' own:
``b`` is a buffer a layer (collection ``buffers``, no parameter: no
gradient, no optimizer moment, no weight decay), moved by the load after
each step, ``b_e += bias_update_rate * sign(mean(n) - n_e)`` with ``n_e``
the tokens the step routed to expert ``e`` over ALL columns (section 2.1.2
there: balance without a loss term); the layer writes the moved bias back
where the caller made ``buffers`` mutable (``Trainer``'s step does, scope
``optimizer/bias``) and leaves it alone elsewhere.  No
token is ever dropped and every shape is static:
the ``tokens x top_k`` assignments are sorted by expert, this chip's
first, one grouped matmul (``jax.lax.ragged_dot``, which the TPU compiler
turns into its own grouped kernel and skips the rows no group holds) runs
over the sorted rows, and the results go back weighted.

The passes over the sorted rows (the row gather, the masks, the matmuls'
operands, SwiGLU, the weighted sum) run at an extent chosen from the
routing.  ``ladder`` derives a few static extents from the shapes alone:
1.25, 1.5 and 2 times the rows expected on a chip that holds ``n_local``
of ``num_experts`` experts, and the worst case, every assignment on this
chip's experts (where a token takes more experts than the chip holds, one
row a held expert and token: a token meets an expert once).
``jax.lax.switch`` takes the smallest that holds the rows
in use (``sizes.sum()``, a value on the device).  The last rung holds
whatever the router does, so dropless still holds, and every rung is the
same mathematics: ``tests/test_moe.py`` holds a forced routing in each rung
to the reference and to the last rung's gradients.  Inside a rung only the
two index vectors of the sort have the extent of all assignments, and,
where the rung holds more than a sixth as many rows as it serves slots,
the gathered operand of the two sums by token (each token's ``top_k``
slots, those with no row here reading one row); a smaller rung adds up the
rows it holds, in the order of their tokens (``_sum_by_token``: which of
the two is a rule on the static shapes, rung by rung).  The ``switch`` sits
under a ``custom_vjp``: differentiated as it stands it pads every branch's
residuals to the union of all branches, which writes the worst case in the
small rungs; the backward pass switches too.  What the forward pass keeps
for it is the layer's inputs and, at the ladder's FIRST extent alone, the
two products of a pass's first grouped matmuls (the sorted rows times
``gate_w`` and ``up_w``, ``[extent, I]`` each in the compute dtype, under
``ops/pallas/kept.py``'s ``moe_products``, which a rematerialised decoder
layer and ``ep``'s loop over source ranks keep, with the sort's two index
vectors and the groups' sizes under the same name: the products are only
those rows' under that sort): a pass at the first extent
pulls its gradient back from them, six grouped matmuls and no forward one,
nine a layer and pass; a pass on a higher rung pulls back through
``jax.vjp`` of the rung, which multiplies by ``gate_w`` and ``up_w`` again,
eleven.  No pull-back asks for the down product (``_down_and_sum``).  It
adapts by the rung the routing picks: no field, argument or variable.  Where
one chip holds every expert the ladder has one rung, a ``switch`` over one
branch is a plain call (no conditional in the step), and the same rules give
the same nine.  The router's float32 logits, the experts it chose and a
share's count of rows carry ``kept.py``'s ``moe_route`` the same way: the
second pass of a rematerialised layer computes scores and weights from
them, elementwise (``_at_kept``), with no router matmul, no ``top_k``, no pass
over groups and no gather, and the sort it kept is of the choice it kept.

Expert parallelism: ``ep`` ranks hold ``num_experts / ep`` experts each and
are data ranks for everything else.  Inside a ``shard_map`` the tokens of
an ``ep`` group are all-gathered, each rank runs its own experts over the
rows routed to them, one source rank's tokens at a time, and a
reduce-scatter sums the partial results back to the rank that owns the
token.  With 8 of 64 experts a token and 16 a rank, a
token has an expert on a given rank with probability 0.91: an all-to-all
would move about the same bytes and need a bound on what a rank receives.
With ``ep == 1`` the same code runs with no collective.

One chip's share of a layer whose other experts lie on chips that are not
here (``experts_held``, ``first_expert``: the cut a benchmark makes of a
model too large for its chips): the stacked expert arrays hold
``experts_held`` experts, the router keeps its ``num_experts`` columns,
``local_experts`` is told which experts these are and the ladder that
``num_experts`` exist, and the layer's result is these experts' part.
Nothing stands in for the absent chips or their traffic.

Experts of another form (``mlp_matrices`` 2, ``mlp_activation``
``"relu2"``: ``relu(x W_up)^2 W_down``, no gate; Nemotron-H's) run through
the same passes with ONE first product where SwiGLU has two (``_products``
returns a tuple, ``_finish`` takes it): the kept products, the pull-back
from them (four grouped matmuls where SwiGLU's runs six) and the ladder are
the same code.  The shared expert is the dense ``MLP`` of the same form.
With ``moe_latent_size`` the routed experts work in a latent (LatentMoE):
``c = h W_down`` before the sort, the experts' matrices at the latent's
width, ``r W_up`` after the weighted sum, both projections whole on every
chip (scope ``moe/latent``), while the router and the shared expert read
the full ``h``; ``W_up`` is linear, so a share's part through it still adds
up with the other shares' to the whole layer's result.

Each layer sows two loss terms into the ``losses`` collection, already
weighted and divided by the number of layers (``Trainer``'s default loss
adds whatever a model sows there): the load-balancing loss
``E * sum_e f_e P_e`` (``f_e``: share of the assignments that went to expert
``e``, ``P_e``: its mean router probability; 1 at uniform routing) and the
router z-loss ``mean(logsumexp(logits)^2)``.  Into ``stats`` it sows the
largest expert's rows over the mean (``load_max_over_mean``), counted from
the groups the matmul was given; the extents the chips' passes ran at over
the rows in use (``rows_held_over_live``: 1 is no wasted row, ``ep`` the
worst case everywhere); and the hottest chip's rows over the chips' mean
(``chip_rows_max_over_mean``: what picks the rung on the chip the others
wait for).  There is no count of dropped rows: none can be.  A share's
loss and ``load_max_over_mean`` are the routing's, over every expert;
its ``rows_held_over_live`` is the pass's extent over the rows ITS experts
took, and ``share_rows_over_expected`` those rows over a fair share.
"""

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    ACTIVATIONS, MLP, LlamaConfig, _projection)
from dlrover_tpu.observability import trace
from dlrover_tpu.ops.pallas import kept


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig(LlamaConfig):
    """``intermediate_size`` is the width of one expert."""

    num_experts: int = 8
    top_k: int = 2
    load_balance_coef: float = 0.01
    router_z_coef: float = 0.001
    # the kept router weights divided by their sum (``norm_topk_prob``),
    # by their sum plus ``norm_topk_eps`` where a model's code adds one
    # (LFM2's ``+ 1e-6``).  0: the sum alone, the program as it was
    norm_topk_prob: bool = False
    norm_topk_eps: float = 0.0
    # one chip's share of an expert layer that further chips hold the rest
    # of: the experts ``[first_expert, first_expert + experts_held)`` of
    # ``num_experts`` are here, the router keeps its ``num_experts`` columns
    # and the layer's result is these experts' part.  0: every expert
    experts_held: int = 0
    first_expert: int = 0
    # what the router's logits become before the ``top_k`` are taken:
    # ``softmax`` over all experts, or ``sigmoid`` of each (DeepSeek-V3's;
    # Solar-Open2's); the kept weights are multiplied by
    # ``routed_scaling_factor``, after ``norm_topk_prob``'s division
    router_scores: str = "softmax"
    routed_scaling_factor: float = 1.0
    # experts every token visits, beside the routed ones: one dense SwiGLU
    # of width ``shared_experts * shared_intermediate_size`` (0: the routed
    # experts' width), its result added unweighted.  Whole on every chip: a
    # share (``experts_held``) adds it once
    shared_experts: int = 0
    shared_intermediate_size: int = 0
    # the choice by groups: ``n_group`` runs of consecutive experts, the
    # ``topk_group`` with the largest sum of their two best scores kept,
    # the ``top_k`` chosen inside them.  0: no groups (and so is one group,
    # or every group kept: the plain ``top_k``)
    n_group: int = 0
    topk_group: int = 0
    # a bias a layer added to the scores for the CHOICE alone, a buffer the
    # load moves by ``bias_update_rate`` a step (the module's text)
    selection_bias: bool = False
    bias_update_rate: float = 0.001
    # the routed experts work in a latent (Nemotron 3's LatentMoE): ``c = h
    # W_down`` of this width before the sort, the experts' matrices at it,
    # ``r W_up`` back to ``hidden_size`` after the weighted sum; both whole
    # on every chip and linear, so a share's part (``experts_held``) through
    # ``W_up`` adds up with the other shares'.  The router and the shared
    # expert read the full ``h``.  0: the experts at ``hidden_size``.  The
    # experts' and the shared expert's form is ``mlp_matrices`` and
    # ``mlp_activation`` (``LlamaConfig``): three matrices under SiLU, or
    # two under ``relu(.)^2``
    moe_latent_size: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.n_group and not (
                self.num_experts % self.n_group == 0
                and 0 < self.topk_group <= self.n_group
                and self.num_experts // self.n_group >= 2
                and self.top_k <= self.topk_group
                * (self.num_experts // self.n_group)):
            raise ValueError(
                f"n_group={self.n_group} topk_group={self.topk_group}: "
                f"groups of at least two that divide {self.num_experts} "
                f"experts and hold top_k={self.top_k} in those kept")
        if self.router_scores not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scores={self.router_scores!r} not in "
                             "('softmax', 'sigmoid')")
        if self.experts_held and not (
                0 <= self.first_expert
                <= self.num_experts - self.experts_held):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.experts_held}) are not among {self.num_experts}")

    def feed_forward(self):
        return MoEMLP

    def shared_width(self) -> int:
        return self.shared_experts * (
            self.shared_intermediate_size or self.intermediate_size)

    def feed_forward_params(self) -> int:
        held = self.experts_held or self.num_experts
        latent = self.moe_latent_size
        return (held * self.mlp_matrices * (latent or self.hidden_size)
                * self.intermediate_size
                + self.hidden_size * self.num_experts
                + self.mlp_matrices * self.hidden_size * self.shared_width()
                + 2 * self.hidden_size * latent)

    @classmethod
    def tiny_moe(cls, **kw) -> "MoELlamaConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=128, num_experts=4, top_k=2,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def olmoe_1b_7b(cls, **kw) -> "MoELlamaConfig":
        """allenai/OLMoE-1B-7B-0125 (``config.json``).  The two loss
        coefficients are the paper's (section 3, "auxiliary losses")."""
        defaults = dict(
            vocab_size=50304, hidden_size=2048, intermediate_size=1024,
            num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
            max_seq_len=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
            qk_norm=True, num_experts=64, top_k=8,
        )
        defaults.update(kw)
        return cls(**defaults)


#: rows a rung of the ladder is rounded up to (the MXU's edge)
_TILE = 128


def ladder(rows, n_local, num_experts, top_k=None):
    """The static extents a pass over ``rows`` sorted assignments may run
    at when ``n_local`` of ``num_experts`` experts are here: 1.25, 1.5 and
    2 times the rows expected under even routing, each rounded up to a
    tile, then the worst case, which holds whatever the routing does:
    ``rows`` itself, or, where a token's ``top_k`` assignments outnumber
    the ``n_local`` experts here, ``n_local`` a token (a token meets an
    expert once).  Where every expert is local, or the buffer is a few
    tiles, the worst case alone."""
    worst = rows
    if top_k is not None and top_k > n_local:
        worst = rows // top_k * n_local
    below = {
        -(-rows * n_local * num // (num_experts * den * _TILE)) * _TILE
        for num, den in ((5, 4), (3, 2), (2, 1))
    }
    return tuple(sorted(e for e in below if e < worst)) + (worst,)


def _combine_body(extent, slots):
    """``rows`` or ``slots``: how a pass over ``extent`` sorted rows that
    serves ``slots`` slots sums by token.  A rule on static shapes, from
    the two bodies timed alone on the chip at the benchmark's shapes and
    then in the step (``scripts/combine_alone.py``; PERF.md, PR 50): the
    rows' body takes a fifth of the slots' time at 5/128, a half to the
    whole of it at 5/32, by how far the rows outgrow the chip's fast
    memory, and twice at 5/16."""
    return "rows" if 6 * extent <= slots else "slots"


@jax.named_scope("combine")
def _sum_by_token(rows, picked, slot, live, weights=None):
    """[tokens, D] float32: for each token the sum of its assignments'
    rows, each times its weight where ``weights`` [tokens, fan] is given.
    ``picked`` [extent] is the assignment of each row, ``live`` [extent, 1]
    whether it holds one, ``slot`` [tokens, fan] the row of each
    assignment, ``len(rows)`` or more for one that has no row here and
    adds nothing.  Where the rows are few beside the slots
    (``_combine_body``) the sum runs over the rows (``_sum_over_rows``).
    Elsewhere a gather of every slot and a sum over ``fan``, as it has
    been since PR 28: the rows behind the last group are then read at
    weight 0 and must hold 0.  (A scatter-add of the rows takes three to
    ten times either: PERF.md, PR 28 and PR 50.)"""
    if _combine_body(len(rows), slot.size) == "rows":
        by_row = () if weights is None else (
            weights.reshape(-1)[picked].astype(jnp.float32),)
        return _sum_over_rows(rows, picked, slot, live, by_row)
    if len(rows) < slot.size:
        mine = rows.at[slot].get(mode="fill", fill_value=0)
    else:
        mine = rows[slot]
    if weights is None:
        return mine.sum(axis=1, dtype=jnp.float32)
    return jnp.einsum("tkd,tk->td", mine, weights,
                      preferred_element_type=jnp.float32)


# a program of its own shapes: a step meets it six times a routed layer
# and is traced five times a run, and Python reads this body once
@jax.jit
def _sum_over_rows(rows, picked, slot, live, by_row):
    """``_sum_by_token`` over the rows held, ``by_row`` the rows' weights
    ``([extent] float32,)`` or ``()``.  Sorted by assignment, the rows that
    hold none behind every token, a token's rows stand in one run of at
    most ``fan``; one pass adds to each row the ``fan - 1`` behind it that
    are the same token's, and a token reads the first row of its run, or a
    row of zeros.  What a row without an assignment holds is never read."""
    tokens, fan = slot.shape
    extent = len(rows)
    key, at, *by_row = jax.lax.sort(
        (jnp.where(live[:, 0], picked, tokens * fan),
         jnp.arange(extent, dtype=jnp.int32), *by_row), num_keys=1)
    token = key // fan
    theirs = jnp.pad(token, (0, fan - 1), constant_values=-1)
    behind = jnp.pad(rows[at], ((0, fan - 1), (0, 0)))
    by_row = [jnp.pad(w, (0, fan - 1)) for w in by_row]
    total = 0
    for j in range(fan):
        term = behind[j:j + extent].astype(jnp.float32)
        for w in by_row:
            term = term * w[j:j + extent, None]
        total = total + jnp.where(
            (theirs[j:j + extent] == token)[:, None], term, 0)
    here = (slot < live.sum()).sum(axis=1)
    first = jnp.where(here > 0, jnp.cumsum(here) - here, extent)
    return jnp.pad(total, ((0, 1), (0, 0))).at[first].get(
        mode="promise_in_bounds")


@jax.custom_vjp
def _rows_of(x, picked, slot, live):
    """``x[picked // fan]``: for each sorted row its token's.  The
    transpose is a sum by token, where autodiff would scatter-add."""
    with jax.named_scope("sort"):
        return x[picked // slot.shape[1]]


def _rows_of_fwd(x, picked, slot, live):
    return _rows_of(x, picked, slot, live), (picked, slot, live)


def _rows_of_bwd(index, g):
    return _sum_by_token(g, *index).astype(g.dtype), None, None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


def _sorted(extent, weights, order, inverse, sizes):
    """``(picked, slot, live)`` of a pass over the first ``extent`` sorted
    rows: the assignment of each row, the row of each assignment
    ``[tokens, fan]``, and ``[extent, 1]`` whether a row holds one."""
    with jax.named_scope("sort"):
        # the grouped matmul leaves the rows behind the last group
        # undefined: they are masked on the way in (so no gradient comes
        # back through them) and on the way out
        live = (jnp.arange(extent) < sizes.sum())[:, None]
        return order[:extent], inverse.reshape(weights.shape), live


def _grouped(rows, expert_w, sizes):
    return jax.lax.ragged_dot(rows, expert_w, group_sizes=sizes,
                              preferred_element_type=rows.dtype)


@jax.custom_vjp
def _down_and_sum(hidden, down_w, weights, order, slot, sizes, live):
    """[tokens, D] float32: the rows of ``hidden`` [extent, I] through
    their experts' ``down_w``, each times its weight, summed by token
    (``order[:extent]`` is the assignment of each row).  The transpose
    gathers the token's cotangent for each row and never asks for the
    product again: with ``t`` the cotangent through ``down_w`` transposed,
    a weight's gradient is ``sum(hidden * t)`` over a row (what ``sum(g *
    product)`` is, written on the narrow side), the rows' is ``t`` times
    the weight, so nothing of the extent ``tokens x fan`` but a vector is
    built and no forward grouped matmul runs in a backward pass."""
    with jax.named_scope("gmm"):
        out = jnp.where(live, _grouped(hidden, down_w, sizes), 0)
    return _sum_by_token(out, order[:len(hidden)], slot, live, weights)


def _down_and_sum_fwd(*args):
    return _down_and_sum(*args), args


def _down_and_sum_bwd(res, g):
    hidden, down_w, weights, order, slot, sizes, live = res
    picked = order[:len(hidden)]
    with jax.named_scope("combine"):
        g = jnp.where(live, g[picked // slot.shape[1]], 0).astype(hidden.dtype)
        by_row = weights.reshape(-1)[picked].astype(jnp.float32)[:, None]
    with jax.named_scope("gmm"):
        t, = jax.linear_transpose(
            lambda hidden: _grouped(hidden, down_w, sizes), hidden)(g)
        d_down, = jax.linear_transpose(
            lambda down_w: _grouped(
                (hidden * by_row).astype(hidden.dtype), down_w, sizes),
            down_w)(g)
    with jax.named_scope("combine"):
        # the rows behind the last group are undefined in both factors
        d_by_row = jnp.where(
            live[:, 0], (hidden * t).sum(axis=-1, dtype=jnp.float32), 0)
        # back to the assignments' order: a sort by the permutation, a tenth
        # of the time of a gather of single elements on the chip
        d_weights = jax.lax.sort(
            (order, jnp.pad(d_by_row, (0, len(order) - len(hidden)))),
            num_keys=1)[1]
    return ((t * by_row).astype(hidden.dtype), d_down,
            d_weights.reshape(slot.shape).astype(weights.dtype),
            None, None, None, None)


_down_and_sum.defvjp(_down_and_sum_fwd, _down_and_sum_bwd)


def _products(extent, x, weights, order, inverse, sizes, *in_w):
    """The first half of a pass over the first ``extent`` sorted rows,
    which hold every row of ``sizes``: the rows gathered, masked and
    multiplied by their experts' first matrices ``in_w`` (``gate_w`` and
    ``up_w``, or a two-matrix expert's ``up_w`` alone), a tuple of
    ``[extent, I]`` in the compute dtype.  What a backward pass keeps of the
    forward (``kept.MOE_PRODUCTS``)."""
    picked, slot, live = _sorted(extent, weights, order, inverse, sizes)
    rows = _rows_of(x, picked, slot, live)
    with jax.named_scope("gmm"):
        rows = jnp.where(live, rows, 0)
        return tuple(_grouped(rows, w, sizes) for w in in_w)


def _finish(extent, activation, products, weights, order, inverse, sizes,
            down_w):
    """The second half: the experts' weighted results [tokens, D] float32
    from the products.  ``act(gate) * up`` (one product: ``act(up)``) is
    computed here, forward and backward, and kept by nobody."""
    _, slot, live = _sorted(extent, weights, order, inverse, sizes)
    with jax.named_scope("gmm"):
        hidden = ACTIVATIONS[activation](products[0])
        if len(products) == 2:
            hidden = hidden * products[1]
    return _down_and_sum(hidden, down_w, weights, order, slot, sizes, live)


def _rung(extent, activation, x, weights, order, inverse, sizes, *expert_w):
    """The experts' weighted results [tokens, D] float32 from the first
    ``extent`` sorted rows, which hold every row of ``sizes``.  Only the
    index vectors ``order`` and ``inverse`` have the extent of all
    assignments.  ``expert_w``: the first matrices, then ``down_w``."""
    index = (weights, order, inverse, sizes)
    products = _products(extent, x, *index, *expert_w[:-1])
    return _finish(extent, activation, products, *index, expert_w[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _at_rung(extents, activation, rung, x, weights, order, inverse, sizes,
             *expert_w):
    """``_rung`` at ``extents[rung]``, ``rung`` a value on the device.
    ``jax.lax.switch`` differentiated as it stands pads every branch's
    residuals to the union of all branches, which would write the worst
    case in the small rungs.  So the forward rule keeps the inputs and, at
    the FIRST extent alone, the two products of ``_products`` (under
    ``kept.MOE_PRODUCTS``, on the switch's result: a rematerialised layer
    then has them without running the switch again; a higher rung hands
    back zeros of that shape).  The backward pass switches too: on the
    first rung it pulls back from the kept products, six grouped matmuls
    and no forward one; on a higher rung through ``jax.vjp`` of the same
    rung, which runs gate and up again."""
    return jax.lax.switch(
        rung, [functools.partial(_rung, extent, activation)
               for extent in extents],
        x, weights, order, inverse, sizes, *expert_w)


def _at_rung_fwd(extents, activation, rung, *args):
    def first(x, weights, order, inverse, sizes, *expert_w):
        index = (weights, order, inverse, sizes)
        products = _products(extents[0], x, *index, *expert_w[:-1])
        return _finish(extents[0], activation, products, *index,
                       expert_w[-1]), products

    def above(extent):
        def branch(x, *rest):
            nothing = jnp.zeros((extents[0], rest[-1].shape[1]), x.dtype)
            return (_rung(extent, activation, x, *rest),
                    (nothing,) * (len(rest) - 5))   # a product a first matrix
        return branch

    out, products = jax.lax.switch(
        rung, [first] + [above(extent) for extent in extents[1:]], *args)
    return out, (rung, args, kept.named(kept.MOE_PRODUCTS, *products))


def _at_rung_bwd(extents, activation, res, g):
    rung, args, products = res

    def from_products(x, weights, order, inverse, sizes, *expert_w):
        *d_products, d_weights, d_down = jax.vjp(
            lambda *at: _finish(
                extents[0], activation, at[:-2], at[-2], order, inverse,
                sizes, at[-1]),
            *products, weights, expert_w[-1])[1](g)
        d_x, *d_in = jax.vjp(
            lambda x, *in_w: _products(
                extents[0], x, weights, order, inverse, sizes, *in_w),
            x, *expert_w[:-1])[1](tuple(d_products))
        return (d_x, d_weights, *d_in, d_down)

    def from_inputs(extent):
        def branch(x, weights, order, inverse, sizes, *expert_w):
            return jax.vjp(
                lambda x, weights, *expert_w: _rung(
                    extent, activation, x, weights, order, inverse, sizes,
                    *expert_w),
                x, weights, *expert_w)[1](g)
        return branch

    d_x, d_weights, *d_expert_w = jax.lax.switch(
        rung, [from_products] + [from_inputs(extent) for extent in extents[1:]],
        *args)
    return (None, d_x, d_weights, None, None, None, *d_expert_w)


_at_rung.defvjp(_at_rung_fwd, _at_rung_bwd)


def local_experts(x, top_i, top_w, gate_w, up_w, down_w, first_expert,
                  num_experts=None, activation="silu"):
    """What the experts ``[first_expert, first_expert + len(down_w))`` of
    ``num_experts`` (default: these are all) add to the layer's result
    for tokens ``x`` [T, D] routed by ``top_i`` and weighted by ``top_w``
    (both [T, k]): ``([T, D] float32, rows each of these experts
    processed, rows the passes ran over)``.  ``gate_w`` ``None``: experts of
    two matrices, ``activation(x up_w) down_w``."""
    tokens, k = top_i.shape
    n_local = down_w.shape[0]
    expert_w = tuple(w for w in (gate_w, up_w, down_w) if w is not None)
    with jax.named_scope("sort"):
        local = top_i - first_expert
        mine = (local >= 0) & (local < n_local)
        # assignments of other ranks' experts sort behind every group
        key = jnp.where(mine, local, n_local).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        sizes = (key[:, None] == jnp.arange(n_local)).sum(
            axis=0, dtype=jnp.int32)
        weights = jnp.where(mine, top_w, 0).astype(x.dtype)
    # kept with the products, for they say which rows those are: a
    # rematerialised layer that sorted again, by a router whose scores the
    # compiler rounded another way in its second pass, would pair a
    # token on a tie with another expert's row, or with a row behind the
    # last group, which is undefined.  The choice the sort is of is kept
    # with it (``MoEMLP``, ``kept.MOE_ROUTE``): a second pass weights a
    # kept row by the score of the expert the first pass gave it
    order, inverse, sizes = kept.named(
        kept.MOE_PRODUCTS, order, inverse, sizes)
    extents = ladder(tokens * k, n_local, num_experts or n_local, k)
    # the smallest extent that holds every row of ``sizes`` (a ladder of
    # one rung: that one, and ``jax.lax.switch`` over one branch is a call)
    rung = (sizes.sum() > jnp.asarray(extents[:-1], jnp.int32)).sum(
        dtype=jnp.int32)
    held = jnp.asarray(extents, jnp.float32)[rung]
    out = _at_rung(extents, activation, rung, x, weights, order, inverse,
                   sizes, *expert_w)
    return out, sizes, held


def _choose(module, scores):
    """Of ``module``, a ``MoEMLP`` inside its ``__call__`` (functions and no
    methods: a method would put its own name into every instruction's
    path).  ``(weights, experts)`` [B, S, k] of each token's ``top_k``: the
    largest ``scores``, or with a selection bias the largest ``scores +
    b``, or with groups the largest inside the ``topk_group`` groups
    whose two best add up highest.  The choice carries no gradient and is
    KEPT (``_at_kept``): the weights are the scores' own at those experts."""
    cfg = module.config
    k = cfg.top_k
    choice = jax.lax.stop_gradient(scores)
    if cfg.selection_bias:
        bias = module.variable(
            "buffers", "selection_bias", jnp.zeros, (cfg.num_experts,),
            jnp.float32).value
        choice = choice + jax.lax.stop_gradient(bias)
        module.sow("stats", "bias_abs_max", jnp.abs(bias).max())
    # no groups, or every group kept (``n_group`` 1, DeepSeek-V3's own
    # degenerate case): the plain top-k, and no pass over groups runs
    if cfg.topk_group >= cfg.n_group:
        return _at_kept(scores, jax.lax.top_k(choice, k)[1])
    # ONE ``top_k`` over the columns (a sort on the chip, 20 ms a layer
    # and pass at 16384 x 512: PERF.md, PR 48); the groups by passes of
    # ``max``: a group's two best as its maximum and the maximum of the
    # rest, the groups kept as those fewer than ``topk_group`` others beat
    # (a tie at the edge keeps both, as a threshold would)
    grouped = choice.reshape(*choice.shape[:-1], cfg.n_group, -1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, grouped.shape,
                                     grouped.ndim - 1)
    first = jnp.argmax(grouped, axis=-1, keepdims=True)
    group_score = grouped.max(axis=-1) + jnp.where(
        lanes == first, -jnp.inf, grouped).max(axis=-1)
    beaten = (group_score[..., None, :] > group_score[..., :, None]).sum(
        axis=-1)
    kept_group = (beaten < cfg.topk_group)[..., None]
    top_c, top_i = jax.lax.top_k(
        jnp.where(kept_group, grouped, -jnp.inf).reshape(choice.shape), k)
    # the tokens whose k best experts are not all in the groups kept (an
    # expert outside them beats the last one chosen): where the groups
    # decide something
    outside = jnp.where(kept_group, -jnp.inf, grouped).max(axis=(-2, -1))
    module.sow("stats", "group_dropped_share",
               jnp.mean(outside > top_c[..., -1]))
    return _at_kept(scores, top_i)


def _at_kept(scores, top_i):
    """``(scores at top_i, top_i)``, both [B, S, k], of ``scores`` [B, S,
    E]: the experts under ``kept.MOE_ROUTE``, so that the second pass of a
    rematerialised layer reads the choice and runs no ``top_k`` or pass
    over groups for it, and the weights FROM the kept experts: what
    ``take_along_axis`` gives, by k passes of compare and sum over the
    columns.  Those are elementwise, and so is their pull-back (a token's
    k gradients selected into its row); the chip's gather of one float a
    slot and the scatter its pull-back is each cost what the router's
    matmul or its ``top_k`` does (1.40 and 1.31 ms against under 0.25 at
    16384 x 512: PERF.md, PR 49).  One term of a sum is not zero: the
    gather's values to the bit."""
    (top_i,) = kept.named(kept.MOE_ROUTE, top_i)
    lanes = jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                     scores.ndim - 1)
    return jnp.stack([
        jnp.where(lanes == top_i[..., j:j + 1], scores, 0).sum(axis=-1)
        for j in range(top_i.shape[-1])], axis=-1), top_i


def _move_bias(module, rows):
    """After the step's routing: ``b_e += rate * sign(mean(n) - n_e)``
    from the rows ``n`` each of the E experts took, written back where
    the caller made ``buffers`` mutable (a training step), nowhere
    else."""
    cfg = module.config
    if not (cfg.selection_bias and module.is_mutable_collection("buffers")
            ) or module.is_initializing():
        return
    with jax.named_scope("optimizer"), jax.named_scope("bias"):
        bias = module.get_variable("buffers", "selection_bias")
        load = jax.lax.stop_gradient(rows.astype(jnp.float32))
        module.put_variable(
            "buffers", "selection_bias",
            bias + cfg.bias_update_rate * jnp.sign(load.mean() - load))


class MoEMLP(nn.Module):
    """Top-k routed experts (SwiGLU, or by the configuration two matrices
    under another activation, in a latent), expert-sharded over ``ep``."""

    config: MoELlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, D = x.shape
        E, k = cfg.num_experts, cfg.top_k
        with jax.named_scope("moe"):
            with jax.named_scope("route"):
                logits = nn.DenseGeneral(
                    features=E, use_bias=False,
                    # routing decisions in float32: on a TPU a float32
                    # matmul at the default precision multiplies in bfloat16
                    dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.lecun_normal(), ("embed", None)
                    ),
                    name="router",
                )(x)
                # what a rematerialised layer keeps of its router
                # (``kept.MOE_ROUTE``; the choice in ``_choose``): its
                # second pass reads them and runs no matmul for them
                (logits,) = kept.named(kept.MOE_ROUTE, logits)
                if cfg.router_scores == "sigmoid":
                    scores = jax.nn.sigmoid(logits)
                    # the balance loss reads each expert's share of the
                    # scores: the normalised scores where no softmax did it
                    probs = scores / scores.sum(axis=-1, keepdims=True)
                else:
                    scores = probs = jax.nn.softmax(logits, axis=-1)
                top_w, top_i = _choose(self, scores)
                if cfg.norm_topk_prob:
                    total = top_w.sum(axis=-1, keepdims=True)
                    if cfg.norm_topk_eps:
                        total = total + cfg.norm_topk_eps
                    top_w = top_w / total
                if cfg.routed_scaling_factor != 1.0:
                    top_w = top_w * cfg.routed_scaling_factor

            def expert_weight(name, shape, axes):
                return self.param(
                    name,
                    nn.with_logical_partitioning(
                        nn.initializers.lecun_normal(), axes
                    ),
                    shape, cfg.param_dtype,
                ).astype(cfg.dtype)

            F = cfg.intermediate_size
            here = cfg.experts_held or E
            wide = cfg.moe_latent_size or D     # what the experts work at
            expert_w = tuple(
                expert_weight(name, (here,) + shape, ("expert",) + axes)
                for name, shape, axes in (
                    ("gate_proj", (wide, F), ("embed", "mlp")),
                    ("up_proj", (wide, F), ("embed", "mlp")),
                    ("down_proj", (F, wide), ("mlp", "embed")))
                if cfg.mlp_matrices == 3 or name != "gate_proj")
            inside = x
            if cfg.moe_latent_size:
                with jax.named_scope("latent"):
                    inside = _projection(cfg, wide, "latent_down",
                                         ("embed", None))(x)
            mixed, rows, held, chip_rows = self._experts(
                inside.astype(cfg.dtype), top_i, top_w, *expert_w)
            if cfg.moe_latent_size:
                with jax.named_scope("latent"):
                    mixed = _projection(cfg, D, "latent_up",
                                        (None, "embed"))(mixed)
            # the routing's loss terms and counts
            with jax.named_scope("route"):
                live = B * S * k
                if cfg.experts_held:
                    # the groups count this chip's experts; the loss and the
                    # balance are the routing's, over every expert
                    live = jnp.maximum(rows.sum(), 1)
                    self.sow("stats", "share_rows_over_expected",
                             live * (E / (B * S * k * here)))
                    (rows,) = kept.named(kept.MOE_ROUTE, (
                        top_i[..., None] == jnp.arange(E)).sum(
                            axis=(0, 1, 2), dtype=jnp.int32))
                assigned = rows.astype(jnp.float32) / (B * S * k)
                self.sow(
                    "losses", "load_balance",
                    (cfg.load_balance_coef / cfg.num_layers) * E
                    * jnp.sum(assigned * probs.mean(axis=(0, 1))),
                )
                self.sow(
                    "losses", "router_z",
                    (cfg.router_z_coef / cfg.num_layers)
                    * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
                )
                _move_bias(self, rows)
                self.sow("stats", "load_max_over_mean", rows.max() / rows.mean())
                self.sow("stats", "rows_held_over_live", held / live)
                self.sow("stats", "chip_rows_max_over_mean",
                         chip_rows.max() / chip_rows.mean())
            if cfg.shared_experts:
                with jax.named_scope("shared"):
                    mixed = mixed + MLP(
                        dataclasses.replace(
                            cfg, intermediate_size=cfg.shared_width()),
                        keeps_products=False,
                        name="shared_expert")(x).astype(mixed.dtype)
        return nn.with_logical_constraint(mixed, ("batch", "seq", "embed"))

    def _experts(self, x, top_i, top_w, *expert_w):
        """``(the experts' weighted results [B, S, D], rows each of the E
        experts processed over the whole batch, rows the chips' passes ran
        over in all, live rows of each chip)``."""
        from dlrover_tpu.ops.ring_attention import active_mesh

        cfg = self.config
        D, k = x.shape[-1], cfg.top_k
        down_w = expert_w[-1]
        # ``local_experts`` takes (gate_w, up_w, down_w), ``gate_w`` ``None``
        # for experts of two matrices
        absent = (None,) * (3 - len(expert_w))
        mesh = active_mesh()
        sharded = (
            mesh is not None and mesh.size > 1
            # already per-shard: the enclosing shard_map owns the mesh
            # axes, and its parameters are whole on every shard
            and not jax.sharding.get_abstract_mesh().manual_axes
        )
        ep = int(mesh.shape.get("ep", 1)) if sharded else 1
        if cfg.num_experts % ep:
            raise ValueError(
                f"{cfg.num_experts} experts do not split over ep={ep}"
            )
        if cfg.experts_held and ep > 1:
            raise ValueError(
                "experts_held is one chip's share: it does not split over "
                f"ep={ep}")

        x_spec = w_spec = None
        chips = ()
        if sharded:
            from dlrover_tpu.parallel.sharding import spec_on_mesh

            rules = list(nn.get_logical_axis_rules()) or None
            x_spec = spec_on_mesh(mesh, ("batch", "seq", None), rules)
            w_spec = spec_on_mesh(mesh, ("expert", None, None), rules)
            # the axes that split tokens: each chip along them holds other
            # tokens, so the rows an expert processed add up over those
            # beside ``ep``
            chips = tuple(
                a for axes in x_spec if axes
                for a in (axes if isinstance(axes, tuple) else (axes,))
            )
        others = tuple(a for a in chips if a != "ep")

        exchange = jax.named_scope("exchange")

        # the scope again: a ``shard_map``'s body does not always carry the
        # caller's name stack to its instructions (inside the loop over
        # ranks the compiled step's ``op_name`` starts anew)
        @jax.named_scope("moe")
        def per_shard(x, top_i, top_w, *expert_w):
            tokens = (x.reshape(-1, D), top_i.reshape(-1, k),
                      top_w.reshape(-1, k))
            if ep == 1:
                out, rows, held = local_experts(
                    *tokens, *absent, *expert_w, cfg.first_expert,
                    cfg.num_experts, cfg.mlp_activation)
                out = out.astype(cfg.dtype)
                live = rows.sum()
            else:
                first = jax.lax.axis_index("ep") * expert_w[-1].shape[0]

                # one rank's tokens at a time: only one buffer of sorted
                # rows, of masks and of ``silu(gate) * up`` is alive, at the
                # extent that rank's routing asks for (the backward pass
                # gathers and masks each again in its turn).  What stays
                # alive of every rank is what the layer keeps, so the
                # layer's policy stands here too: the products at the first
                # extent, the loop's stacked result ``[ep, extent, I]`` twice
                @functools.partial(jax.checkpoint, policy=kept.LAYER_POLICY)
                @jax.named_scope("moe")
                def one_rank(its_tokens):
                    out, rows, held = local_experts(
                        *its_tokens, *absent, *expert_w, first,
                        cfg.num_experts, cfg.mlp_activation)
                    return out.astype(cfg.dtype), rows, held

                with exchange:
                    gathered = [jax.lax.all_gather(t, "ep", axis=0)
                                for t in tokens]
                out, rows, held = jax.lax.map(one_rank, gathered)
                with exchange:
                    out = jax.lax.psum_scatter(
                        out, "ep", scatter_dimension=0)
                    held, live = held.sum(), rows.sum()
                    rows = jax.lax.all_gather(
                        rows.sum(axis=0), "ep", axis=0, tiled=True)
            with exchange:
                if others:
                    rows = jax.lax.psum(rows, others)
                counts = jnp.stack([held, live.astype(held.dtype)])[None]
                if chips:
                    counts = jax.lax.all_gather(
                        counts, chips, axis=0, tiled=True)
            return (out.reshape(x.shape), rows,
                    counts[:, 0].sum(), counts[:, 1])

        if sharded:
            from jax.sharding import PartitionSpec

            from dlrover_tpu.parallel.collectives import shard_map_unchecked

            per_shard = shard_map_unchecked(
                per_shard, mesh=mesh,
                in_specs=(x_spec,) * 3 + (w_spec,) * len(expert_w),
                out_specs=(x_spec,) + 3 * (PartitionSpec(),),
            )
        rows = x.shape[0] * x.shape[1] * k
        # a source rank's assignments on one chip, and of one pass
        chip = rows // math.prod(mesh.shape[a] for a in chips)
        extents = ladder(chip, down_w.shape[0] // ep, cfg.num_experts, k)
        trace.note_trace_time(
            "moe.path", impl="ragged_dot", experts=cfg.num_experts,
            top_k=k, ep=ep, tokens=x.shape[0] * x.shape[1], rows=rows,
            layers=cfg.num_layers, extents=extents,
            held=down_w.shape[0], first_expert=cfg.first_expert,
            # grouped matmuls in the pull-back of a pass at the first
            # extent, from the products the forward pass kept (two a matrix)
            backward=2 * len(expert_w),
            kept=f"{kept.MOE_PRODUCTS},{kept.MOE_ROUTE}",
            # how the passes at each extent sum by token
            combine=",".join(_combine_body(e, chip) for e in extents),
            # the dense SwiGLU every token visits beside the routed experts
            shared_experts=cfg.shared_experts, shared_width=cfg.shared_width(),
            # experts of two matrices, or in a latent: said where it is so
            **({"matrices": len(expert_w), "activation": cfg.mlp_activation}
               if len(expert_w) != 3 else {}),
            **({"latent": cfg.moe_latent_size} if cfg.moe_latent_size else {}),
        )
        # a source rank's two products and the sort they are in; this
        # chip's tokens' logits and choice, and a share's count of rows
        kept.note("moe", **{
            kept.MOE_PRODUCTS: ep * (
                (len(expert_w) - 1) * kept.nbytes(
                    (extents[0], cfg.intermediate_size), cfg.dtype)
                + kept.nbytes((2 * chip + down_w.shape[0] // ep,),
                              jnp.int32)),
            kept.MOE_ROUTE: (
                kept.nbytes((chip // k, cfg.num_experts), jnp.float32)
                + kept.nbytes((chip + bool(cfg.experts_held)
                               * cfg.num_experts,), jnp.int32))})
        return per_shard(x, top_i, top_w, *expert_w)
