"""Device time of the pooling alone in one step, on the first chip: a
chunk's keys weighted by ``softmax(mu . k)`` and its values by ``softmax(phi
. k)``, forward, rematerialised forward and backward into k, v and the two
learned vectors (``eva_attn_ms_per_step.is_pool_op``: the operations that
carry chunks by the positions of a chunk)."""

from benchmarks.common import load_module


def read(observed):
    eva = load_module("layer_metrics", "eva_attn_ms_per_step")
    return eva.union_ms_per_step(observed, eva.is_pool_op)
