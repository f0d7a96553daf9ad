"""What a rematerialised decoder layer keeps of its attention core and of
its expert layer: the results a core's forward KERNELS wrote that its
backward kernels read, the two products of the experts' first grouped
matmuls that their pull-back reads, and the router's logits with the choice
it made of them.

A layer of ``models/llama.py`` is rematerialised whole: the forward pass
keeps the layer's input and the backward pass computes the layer again.
For everything ``jax.numpy`` computes that is the trade wanted.  For a
kernel it is the forward kernel run a second time to get back what its
custom gradient had declared as residuals.  So a kernel-backed core names
those results inside its custom gradient's forward rule (``named``), and
the layer's ``nn.remat`` keeps exactly the named values (``LAYER_POLICY``)
and nothing else.  Neither half does anything alone.

It adapts by what the core runs and has no switch: a core on its
``jax.numpy`` body names nothing, the FA2 kernel names nothing, a dense
feed-forward names nothing, and ``LAYER_POLICY`` over a layer without names
is ``nothing_saveable``.  The expert layer (``models/moe.py``) names its
products on the result of its switch over extents, at the first extent
alone, and gives ``LAYER_POLICY`` to its own ``jax.checkpoint`` around one
source rank's pass under ``ep``.  Its router names the float32 logits, the
experts chosen and, where it counts them itself, the rows each expert took:
the second pass then has no router matmul, no ``top_k`` and no pass over
groups, and weights every kept row by the score of the expert the FIRST
pass chose for it.
"""

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.observability import trace

#: ``out`` of the mask-operand attention kernels
#: (``selected_attention.py``), in the compute dtype: one more
#: residual-stream tensor a layer at the heads' width
ATTN_OUT = "attn_out"
#: their per-row log-sum-exp as ``[B, H, Q]`` float32, never the kernels'
#: lane-broadcast ``[B, H, Q, 128]``
ATTN_LSE = "attn_lse"
#: what the gated delta rule's chunk kernel wrote (``kda.py``): ``w, u0,
#: qg, ke, p, t, th``
KDA_CHUNK = "kda_chunk"
#: what its state kernel wrote: ``out, u, starts``
KDA_STATE = "kda_state"

#: the two products of an expert layer's first grouped matmuls
#: (``models/moe.py::_products``: the sorted rows times ``gate_w`` and
#: ``up_w``, ``[extent, I]`` each in the compute dtype), at the ladder's
#: first extent, one pair a source rank; and the sort they are in (two
#: index vectors of all assignments and the groups' sizes, int32)
MOE_PRODUCTS = "moe_products"
#: what an expert layer's router decided (``models/moe.py::MoEMLP``): the
#: logits ``[B, S, E]`` float32 (32 MiB a layer at 16,384 tokens and 512
#: experts), the chosen experts ``[B, S, k]`` int32 and, of one chip's share
#: of the experts, the rows ``[E]`` int32 each expert took
MOE_ROUTE = "moe_route"

#: what a selective scan's forward kernel wrote (``selective_scan.py``):
#: ``y`` float32 and the state each chunk started from
SSM_SCAN = "ssm_scan"

NAMES = (ATTN_OUT, ATTN_LSE, KDA_CHUNK, KDA_STATE, MOE_PRODUCTS, MOE_ROUTE,
         SSM_SCAN)

#: the policy of every rematerialised decoder layer (``models/llama.py::
#: _layer_class``, ``models/pipeline_llama.py``)
LAYER_POLICY = jax.checkpoint_policies.save_only_these_names(*NAMES)


def named(name, *values):
    """``values`` under ``name``, for ``LAYER_POLICY`` to keep."""
    return tuple(checkpoint_name(value, name) for value in values)


def nbytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def note(core: str, **bytes_by_name: int) -> None:
    """The ``remat.kept`` record of a compiled program, beside the core's
    ``attention.path`` (``core="moe"``: the expert layer's, beside
    ``moe.path``): the names a layer of this core keeps and the bytes
    a layer they hold (counted from shapes; the kernels' results carry the
    names whether or not a ``remat`` stands around the layer)."""
    trace.note_trace_time(
        "remat.kept", core=core, names=",".join(bytes_by_name),
        bytes_per_layer=sum(bytes_by_name.values()),
        **{f"{name}_bytes": held for name, held in bytes_by_name.items()})
