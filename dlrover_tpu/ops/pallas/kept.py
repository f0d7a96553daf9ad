"""What a rematerialised decoder layer keeps of its attention core and of
its feed-forward: the results a core's forward KERNELS wrote that its
backward kernels read, the two products of the experts' first grouped
matmuls that their pull-back reads, the router's logits with the choice
it made of them, and a dense SwiGLU's gate and up products where all
layers' fit a share of the device's memory.

A layer of ``models/llama.py`` is rematerialised whole: the forward pass
keeps the layer's input and the backward pass computes the layer again.
For everything ``jax.numpy`` computes that is the trade wanted.  For a
kernel it is the forward kernel run a second time to get back what its
custom gradient had declared as residuals.  So a kernel-backed core names
those results inside its custom gradient's forward rule (``named``), and
the layer's ``nn.remat`` keeps exactly the named values (``LAYER_POLICY``)
and nothing else.  Neither half does anything alone.

It adapts by what the core runs and has no switch: a core on its
``jax.numpy`` body names nothing, and ``LAYER_POLICY`` over a layer without
names is ``nothing_saveable``.  A dense feed-forward (``models/llama.py::
MLP``) names its gate and up products where ``keeps_mlp_products`` says
that every layer application's pair fits ``MLP_PRODUCTS_SHARE`` of the
device's memory, read from the operand's shape, the mesh and the device:
the second pass then runs no matmul of the feed-forward's first two; where
they do not fit it names nothing and is the program it was.  The
FA2 kernel names its ``out`` and LSE where its stream of keys is long
(``flash_attention.py::backward_path``: the rule that sends the backward
to one call; a second forward costs the square of the stream, what is kept
the stream) and nothing under it: a short stream's layer is computed again
whole, the program it was.  The expert layer (``models/moe.py``) names its
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

#: ``out`` of an attention core's forward kernel (the mask-operand
#: kernels of ``selected_attention.py``, the latent, block-diffusion and
#: differential ones, FA2 over a long stream), in the compute dtype: one
#: more residual-stream tensor a layer at the heads' width
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

#: the gate and the up products of a dense SwiGLU (``models/llama.py::
#: MLP``: the layer's input times ``gate_proj`` and ``up_proj``, ``[B, S,
#: I]`` each, as the matmuls wrote them in the compute dtype), where
#: ``keeps_mlp_products`` takes them; ``silu(gate) * up`` is made again
MLP_PRODUCTS = "mlp_products"

NAMES = (ATTN_OUT, ATTN_LSE, KDA_CHUNK, KDA_STATE, MOE_PRODUCTS, MOE_ROUTE,
         SSM_SCAN, MLP_PRODUCTS)

#: the policy of every rematerialised decoder layer (``models/llama.py::
#: _layer_class``, ``models/pipeline_llama.py``)
LAYER_POLICY = jax.checkpoint_policies.save_only_these_names(*NAMES)


#: the share of a device's memory that the dense feed-forwards' gate and up
#: products of ALL layer applications may take together (``layers x loop
#: steps x 2 x rows a chip x intermediate x itemsize``: every layer counted
#: at this one's width, what a layer can see of a stack; the width whole,
#: so under ``tp`` a bound from above).  Kept, they save the fourth pass of
#: the feed-forward's two widest matmuls, 8% of Mistral-7B's step at 4,096
#: rows; they are the widest thing a layer can keep, 3.5 times its input
#: there.  Set between what the benchmark's Llama-path configurations ask
#: of a 15.75 GiB chip (the described v5e's compile of each,
#: ``benchmarks/tests/compile_described.py``, PR 63): two layers of
#: Mistral-7B at 4,096 rows ask 448 MiB, 1/36 of the chip, and the step's
#: assignment goes from 7.9 to 8.1 GiB; every other asks 1/14 (1,152 MiB,
#: six layers of 6,144 at 8,192 rows) or more at 16,384 rows, Ouro's
#: looped stack 11 GiB, where the steps stand at 13.9 to 15.4 GiB
#: assigned.  1/24 is 672 MiB: half as much again as what is taken, under
#: three fifths of the least refused
MLP_PRODUCTS_SHARE = 1 / 24

#: the memory of a device that cannot be asked, by ``device_kind``: one
#: described to the compiler and not attached.  A v5e's runtime gives 2 MiB
#: less as its ``bytes_limit`` (16,909,336,064)
DESCRIBED_DEVICE_BYTES = {"TPU v5 lite": 63 * 2 ** 28}


def device_bytes(device) -> int:
    """The memory programs on ``device`` are assigned from: what its
    runtime says (``memory_stats()["bytes_limit"]``), a described chip's by
    its kind, and 0 of a backend that does not say (the CPU)."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:   # no runtime: a described device
        return DESCRIBED_DEVICE_BYTES.get(device.device_kind, 0)
    return int((stats or {}).get("bytes_limit", 0))


def mlp_products_bytes(rows: int, intermediate: int, dtype) -> int:
    """A layer application's ``MLP_PRODUCTS``: gate and up, ``[rows,
    intermediate]`` each."""
    return 2 * nbytes((rows, intermediate), dtype)


def keeps_mlp_products(applications: int, rows: int, intermediate: int,
                       dtype, of_device: int) -> bool:
    """Whether a dense SwiGLU names its products: ``applications`` layer
    applications' (layers x loop steps) over ``rows`` (a chip's) fit
    ``MLP_PRODUCTS_SHARE`` of the device's ``of_device`` bytes."""
    return applications * mlp_products_bytes(
        rows, intermediate, dtype) <= MLP_PRODUCTS_SHARE * of_device


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
