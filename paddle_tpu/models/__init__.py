"""paddle_tpu.models — flagship model families.

Transformer LMs (GPT decoder, BERT encoder) are tensor-parallel-ready via
meta_parallel layers; vision models live in paddle_tpu.vision.models.
"""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    gpt_tiny,
    gpt_small,
)
from .latent_moe import (  # noqa: F401
    LatentMoEConfig,
    LatentMoEModel,
    LatentMoEForCausalLM,
)
from .hybrid import (  # noqa: F401
    HybridConfig,
    HybridModel,
    HybridForCausalLM,
)
from .wide_deep import (  # noqa: F401
    WideDeep,
    wide_deep_tiny,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertModel,
    BertForPretraining,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    bert_base,
    bert_tiny,
)
