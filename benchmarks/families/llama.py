"""How the program builds its dense GQA decoder (``models/llama.py``) from a
configuration's file of published keys, and what it calls the leaves that
``reference/llama.py`` names. The program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    h, hq = values["hidden_size"], values["num_attention_heads"]
    if (values.get("head_dim") or h // hq) != h // hq:
        raise ValueError("models/llama.py takes head_dim = hidden / heads")
    if values["hidden_act"] != "silu" or values["tie_word_embeddings"]:
        raise ValueError("models/llama.py is SwiGLU with an untied head")
    cfg = LlamaConfig(vocab_size=values["vocab_size"], hidden_size=h,
                      intermediate_size=values["intermediate_size"],
                      num_layers=values["num_hidden_layers"], num_heads=hq,
                      num_kv_heads=values["num_key_value_heads"],
                      max_position_embeddings=values["max_position_embeddings"],
                      rms_norm_eps=values["rms_norm_eps"],
                      rope_theta=float(values["rope_theta"]))
    return LlamaForCausalLM(cfg)


_LAYER = {"input_norm": "input_layernorm", "post_norm":
          "post_attention_layernorm", "q": "self_attn.q_proj",
          "k": "self_attn.k_proj", "v": "self_attn.v_proj",
          "o": "self_attn.o_proj", "gate": "mlp.gate_proj",
          "up": "mlp.up_proj", "down": "mlp.down_proj"}


def program_name(ref_name: str) -> str:
    if ref_name == "embed":
        return "model.embed_tokens.weight"
    if ref_name == "norm.weight":
        return "model.norm.weight"
    if ref_name == "head.weight":
        return "lm_head.weight"
    _, i, rest = ref_name.split(".", 2)
    mod, leaf = rest.rsplit(".", 1)
    return f"model.layers.{i}.{_LAYER[mod]}.{leaf}"
