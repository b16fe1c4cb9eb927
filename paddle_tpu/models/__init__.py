"""Flagship model families built on the public API (BASELINE.md configs).

``minicpm_sala`` (MiniCPM-SALA) is the one family whose mixers are not
attention over all visible keys: ``mixer_types`` picks, layer by layer,
block-sparse attention over key blocks each query chose (``SparseMixer``; its
one switch is the sequence length against ``sparse.dense_len``, under which it
is plain causal attention) or linear attention with a per-head decay
(``LightningMixer``). Its ``sparse_config`` sizes other than top-64, its decay
slopes, its norms' layout, its elementwise gate and its rotary pairing are not
in the published config: they follow the family's conventions and are listed
as ``assumed`` in ``benchmarks/configs/minicpm-sala-l4-v8.json``."""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, create_train_step,
                  gpt2_small, gpt2_tiny, write_back)  # noqa: F401
from .llama import (LlamaConfig, LlamaForCausalLM, llama_7b, llama_13b,  # noqa: F401
                    llama_tiny, llama_param_spec, llama_fsdp_spec,
                    llama_pipeline_model)
from .laguna import (LagunaConfig, LagunaForCausalLM,  # noqa: F401
                     laguna_rope_tables, laguna_tiny)
from .glm_moe_lite import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,  # noqa: F401
                           MLAttention, glm_moe_lite_tiny)
from .minicpm_sala import (MiniCPMSALAConfig, MiniCPMSALAForCausalLM,  # noqa: F401
                           LightningMixer, SparseMixer, minicpm_sala_tiny)
from .qwen3_next import (Qwen3NextConfig, Qwen3NextForCausalLM,  # noqa: F401
                         qwen3_next_tiny)
from .nemotron_h import (NemotronHConfig, NemotronHForCausalLM,  # noqa: F401
                         nemotron_h_tiny)
from .decode import (ContiguousKV, decode_attention,  # noqa: F401
                     init_contiguous_cache)
from .trainer import (create_multistep_train_step,  # noqa: F401
                      create_sharded_train_step, place_by_spec, run_steps)
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, bert_base, bert_large,
                   bert_tiny, bert_pipeline_model, bert_param_spec)
