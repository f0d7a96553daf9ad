"""Device self time a step of the differential-attention core (two softmax
maps of one head size over one value of twice that, their difference, the
sub-norm and ``1 - lambda_init``), all passes, on the first chip, over the
window layers, the whole layers and the cross layers alike: the program's
scopes of kind ``attn.core`` with the sub-scope ``diff``
(``models/llama.py::Attention._differential`` around
``ops/attention.py::differential_attention``; under a window the path is
``attn.core/window/diff`` and the innermost names it), from
``benchmarks/device_scopes.py``'s table.  By scope, not by shape.  Nothing
to read where the program has no such scope (an older commit, another
family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "diff")
