"""Flagship model families (the north-star training configs, SURVEY.md).

The reference ships vision models in-tree (python/paddle/vision/models/) and
serves LLMs through fleet-parallel layer building blocks
(python/paddle/distributed/fleet/layers/mpu/mp_layers.py) that PaddleNLP
assembles into GPT/LLaMA. Here the assembled decoder LM is in-tree: it is the
framework's flagship model, bench target, and the exercise ground for
TP/SP/PP/sharding.
"""

from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    GPTPretrainingCriterion,
    gpt3_tiny,
    gpt3_125m,
    gpt3_350m,
    gpt3_1p3b,
    gpt3_6p7b,
    gpt3_13b,
)
from .gpt_pipe import (  # noqa: F401
    GPTForCausalLMPipe,
    stack_layered_state_dict,
    unstack_to_layered_state_dict,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaModel,
    LlamaForCausalLM,
    llama_tiny,
    llama_7b,
    llama_13b,
)

__all__ = [
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "gpt3_tiny", "gpt3_125m", "gpt3_350m", "gpt3_1p3b", "gpt3_6p7b", "gpt3_13b",
    "GPTForCausalLMPipe", "stack_layered_state_dict", "unstack_to_layered_state_dict",
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "llama_tiny", "llama_7b", "llama_13b",
]

from .bert import (  # noqa: F401,E402
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    bert_base,
    bert_tiny,
)
from .unet import UNetConfig, UNetModel, unet_tiny  # noqa: F401,E402
from .granite_hybrid import (  # noqa: F401,E402
    GraniteHybridConfig,
    GraniteHybridForCausalLM,
    granite_hybrid_tiny,
)
__all__ += [
    "GraniteHybridConfig", "GraniteHybridForCausalLM", "granite_hybrid_tiny",
    "BertConfig", "BertModel", "BertForPretraining",
    "BertForSequenceClassification", "BertPretrainingCriterion",
    "bert_base", "bert_tiny", "UNetConfig", "UNetModel", "unet_tiny",
]
