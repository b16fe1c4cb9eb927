"""Flagship model families built on the public API (BASELINE.md configs)."""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, create_train_step,
                  gpt2_small, gpt2_tiny, write_back)  # noqa: F401
from .llama import (LlamaConfig, LlamaForCausalLM, llama_7b, llama_13b,  # noqa: F401
                    llama_tiny, llama_param_spec, llama_fsdp_spec,
                    llama_pipeline_model)
from .laguna import (LagunaConfig, LagunaForCausalLM,  # noqa: F401
                     laguna_rope_tables, laguna_tiny)
from .glm_moe_lite import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,  # noqa: F401
                           MLAttention, glm_moe_lite_tiny)
from .decode import (ContiguousKV, decode_attention,  # noqa: F401
                     init_contiguous_cache)
from .trainer import (create_multistep_train_step,  # noqa: F401
                      create_sharded_train_step, place_by_spec, run_steps)
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, bert_base, bert_large,
                   bert_tiny, bert_pipeline_model, bert_param_spec)
