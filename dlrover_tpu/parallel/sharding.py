"""Logical-axis sharding rules (GSPMD annotation layer).

Models annotate parameters and activations with *logical* axis names
("embed", "heads", "batch"...); one table maps logical names to mesh axes.
Changing the parallelism strategy = changing the table, never the model.
XLA inserts the collectives (psum/all-gather/reduce-scatter over ICI) from
the annotations — nothing here issues a collective by hand.
"""

import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

MeshAxis = Union[str, Tuple[str, ...], None]

#: the mesh axes a batch is split over
DATA_AXES: Tuple[str, ...] = ("dp", "fsdp", "ep")

# logical axis -> mesh axis (or tuple of axes, or None = replicated)
DEFAULT_LOGICAL_RULES: List[Tuple[str, MeshAxis]] = [
    # global batch over all data-ish axes: ``ep`` ranks hold different
    # experts and different tokens (everything outside an expert layer is
    # plain data parallelism over them)
    ("batch", DATA_AXES),
    ("seq", "cp"),              # context parallelism over sequence
    ("vocab", "tp"),
    ("embed", "fsdp"),          # ZeRO-3-style param shard over fsdp
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("expert", "ep"),
    ("layers", None),           # scanned-layer leading axis stays replicated
    ("periods", None),          # and the axis of a layer pattern's periods
]


def rules_to_dict(
    rules: Sequence[Tuple[str, MeshAxis]]
) -> Dict[str, MeshAxis]:
    return dict(rules)


def spec_for_logical_axes(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, MeshAxis]]] = None,
):
    """Map a tuple of logical axis names to a PartitionSpec."""
    from jax.sharding import PartitionSpec

    table = rules_to_dict(rules or DEFAULT_LOGICAL_RULES)
    out = []
    used = set()
    for name in logical_axes:
        axis = table.get(name) if name else None
        # a mesh axis may appear only once in a spec; drop repeats
        if axis is not None:
            flat = axis if isinstance(axis, tuple) else (axis,)
            if any(a in used for a in flat):
                axis = None
            else:
                used.update(flat)
        out.append(axis)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def logical_to_mesh_sharding(
    logical_specs,
    mesh,
    rules: Optional[Sequence[Tuple[str, MeshAxis]]] = None,
):
    """Convert a pytree of logical-axis tuples to NamedShardings."""
    import jax
    from jax.sharding import NamedSharding

    def convert(axes):
        return NamedSharding(mesh, spec_for_logical_axes(axes, rules))

    return jax.tree.map(
        convert,
        logical_specs,
        is_leaf=lambda x: isinstance(x, (tuple, type(None))),
    )


def spec_on_mesh(
    mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, MeshAxis]]] = None,
):
    """``spec_for_logical_axes`` cut to the axes ``mesh`` really splits:
    the specs of a ``shard_map`` around a per-shard kernel."""
    from jax.sharding import PartitionSpec

    out = []
    for axis in spec_for_logical_axes(logical_axes, rules):
        names = axis if isinstance(axis, tuple) else (axis,)
        out.append(
            tuple(a for a in names if mesh.shape.get(a, 1) > 1) or None
        )
    return PartitionSpec(*out)


def ways_split(
    mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, MeshAxis]]] = None,
) -> int:
    """Over how many chips of ``mesh`` an array of these logical axes is
    split: a chip holds that share of its elements."""
    return math.prod(
        mesh.shape[a] for axes in spec_on_mesh(mesh, logical_axes, rules)
        if axes for a in (axes if isinstance(axes, tuple) else (axes,))
    )


def shard_batch(mesh, batch, data_axes: Tuple[str, ...] = DATA_AXES):
    """Shard a host-local batch pytree onto the mesh's data axes.

    Every process passes its local portion; returns global jax Arrays
    (the multi-host path of feeding a pjit'd step function).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(data_axes))

    def convert(x):
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree.map(convert, batch)


def param_logical_axes(params):
    """Extract logical axis annotations from a flax variables tree
    (``nn.with_logical_partitioning`` boxes)."""
    import flax.linen as nn
    import jax

    def get_axes(x):
        if isinstance(x, nn.Partitioned):
            return x.names
        return None

    return jax.tree.map(
        get_axes,
        params,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )


def unbox_params(params):
    """Strip flax Partitioned boxes, keeping raw arrays."""
    import flax.linen as nn
    import jax

    return jax.tree.map(
        lambda x: x.value if isinstance(x, nn.Partitioned) else x,
        params,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )
