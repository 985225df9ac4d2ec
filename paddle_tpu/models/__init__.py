"""In-repo transformer model zoo (SURVEY.md §2.4: PaddleNLP/PaddleClas are
separate repos upstream — the build needs in-repo equivalents: a
transformer-LM family (BERT/ERNIE/GPT/Llama) plus the ResNet family that
lives in ``paddle_tpu.vision.models``).

Each model family exposes ``sharding_rules()`` — an ordered list of
``(param-name-regex, PartitionSpec-tuple)`` pairs mapping parameters onto the
named hybrid mesh axes (``paddle_tpu.distributed.mesh.HYBRID_AXES``). That is
the TPU-native form of the reference's mp/sharding wrappers: annotate, and
XLA's SPMD partitioner inserts the collectives (SURVEY.md §7.0).
"""
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,
                    LlamaPretrainingCriterion, LlamaForCausalLMPipe,
                    build_llama_pipe, llama3_8b, llama_tiny)
from .t5 import (T5Config, T5ForConditionalGeneration,  # noqa: F401
                 t5_tiny)
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, GPTForCausalLMPipe,
                  gpt3_1p3b, gpt_tiny)
from .bert import (BertConfig, BertModel, BertForSequenceClassification,
                   BertForPretraining, ErnieConfig, ErnieModel,
                   ErnieForSequenceClassification, bert_base, bert_tiny)
from .ppyoloe import (PPYOLOE, DetectionLoss, ppyoloe_lite, CSPBackbone,
                      FPNNeck, ETHead)
from .mixtral import (MixtralConfig, MixtralModel, MixtralForCausalLM,
                      MixtralSparseMoeBlock, mixtral_8x7b, mixtral_tiny)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3Model,
                          DeepseekV3ForCausalLM, deepseek_v3_tiny)
from .smallthinker import (SmallThinkerConfig, SmallThinkerModel,
                           SmallThinkerForCausalLM, smallthinker_tiny)

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "LlamaPretrainingCriterion", "LlamaForCausalLMPipe",
    "build_llama_pipe", "llama3_8b", "llama_tiny",
    "MixtralConfig", "MixtralModel", "MixtralForCausalLM",
    "MixtralSparseMoeBlock", "mixtral_8x7b", "mixtral_tiny",
    "DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
    "deepseek_v3_tiny",
    "SmallThinkerConfig", "SmallThinkerModel", "SmallThinkerForCausalLM",
    "smallthinker_tiny",
    "T5Config", "T5ForConditionalGeneration", "t5_tiny",
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTForCausalLMPipe",
    "gpt3_1p3b", "gpt_tiny",
    "BertConfig", "BertModel", "BertForSequenceClassification",
    "BertForPretraining", "ErnieConfig", "ErnieModel",
    "ErnieForSequenceClassification", "bert_base", "bert_tiny",
    "PPYOLOE", "DetectionLoss", "ppyoloe_lite", "CSPBackbone", "FPNNeck",
    "ETHead",
]
