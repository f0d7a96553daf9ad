from dlrover_tpu.ops.attention import (  # noqa: F401
    causal_attention,
    flash_attention,
    reference_attention,
)
