from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: F401
from dlrover_tpu.models.gpt import GPTConfig, GPT  # noqa: F401
from dlrover_tpu.models.moe import MoELlamaConfig, MoEMLP  # noqa: F401
from dlrover_tpu.models.vit import ViTConfig, ViTForImageClassification  # noqa: F401
