from .gpt import (GPTConfig, GPTForCausalLM, GPTModel, gpt3_1p3b,  # noqa: F401
                  gpt3_6p7b, gpt3_124m, gpt3_350m, gpt3_tiny)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,  # noqa: F401
                    llama2_7b, llama2_13b, llama_tiny)
from .bert import (BertConfig, BertForMaskedLM,  # noqa: F401
                   BertForSequenceClassification, BertModel, bert_base,
                   bert_tiny)
from .glm_moe_dsa import (GlmMoeDsaConfig, GlmMoeDsaForCausalLM,  # noqa: F401
                          glm_moe_dsa_tiny)
