"""Device self time a step of what latent attention adds around its core
(sub-scope ``latent`` of kind ``attn.proj``, all passes): the
down-projection to the latent and the rotary key, the latent's norm, the
up-projection to a head's k_nope and v, and the rotary embedding of q_pe
and k_pe (``mla_attn_ms_per_step.ms_of``).  The query and output
projections and the gate are ``attn.proj``'s own."""

from benchmarks.common import load_module


def read(observed):
    return load_module("layer_metrics", "mla_attn_ms_per_step").ms_of(
        observed, "attn.proj")
