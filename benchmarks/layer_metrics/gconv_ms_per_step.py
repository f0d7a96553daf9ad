"""Device self time a step of the ``conv`` layers' core, all passes, on the
first chip: the program's scopes of kind ``attn.core`` with the sub-scope
``gconv`` (``models/llama.py::ShortConvMixer`` around
``ops/short_conv.py::gated_short_conv``: the gate ``B * u``, the taps, the
gate ``C * c``, forward, rematerialised and the backward rule's own three
passes; NOT ``W_in`` and ``W_out``, which stand under the layer's ``attn``
scope and in ``attn_proj_scope_ms_per_step``), from
``benchmarks/device_scopes.py``'s table.  By scope, not by shape: whatever
implements the core is read the same.  Nothing to read where the program
has no such scope (an older commit, another family)."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.core", "gconv")
