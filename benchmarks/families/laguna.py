"""How the program builds its Laguna decoder (``models/laguna.py``) from a
configuration's file of published keys, and what it calls the leaves that
``reference/laguna.py`` names. The program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
    if values["tie_word_embeddings"] or values.get("attention_bias"):
        raise ValueError("models/laguna.py has an untied head and no biases")
    share = values.get("expert_share")
    cfg = LagunaConfig.from_published(
        values,
        num_experts=share["of"] if share else values["num_experts"],
        experts_held=(share["first"], share["held"]) if share else None,
        # the layer body recomputed in the backward pass, as a deployment
        # at these sizes would: the cell's batch does not fit otherwise
        use_recompute=bool(values.get("recompute_layers", True)),
        lm_ce="blockwise")
    return LagunaForCausalLM(cfg)


_LAYER = {
    "input_norm.weight": "input_layernorm.weight",
    "post_norm.weight": "post_attention_layernorm.weight",
    "q.weight": "self_attn.q_proj.weight",
    "k.weight": "self_attn.k_proj.weight",
    "v.weight": "self_attn.v_proj.weight",
    "o.weight": "self_attn.o_proj.weight",
    "g.weight": "self_attn.g_proj.weight",
    "gate.weight": "mlp.gate_proj.weight", "up.weight": "mlp.up_proj.weight",
    "down.weight": "mlp.down_proj.weight",
    "router.weight": "mlp.router_weight",
    "experts.gate": "mlp.gate_proj", "experts.up": "mlp.up_proj",
    "experts.down": "mlp.down_proj",
    "shared.gate.weight": "mlp.shared.gate_proj.weight",
    "shared.up.weight": "mlp.shared.up_proj.weight",
    "shared.down.weight": "mlp.shared.down_proj.weight",
}


def program_name(ref_name: str) -> str:
    if ref_name == "embed":
        return "model.embed_tokens.weight"
    if ref_name == "norm.weight":
        return "model.norm.weight"
    if ref_name == "head.weight":
        return "lm_head.weight"
    _, i, rest = ref_name.split(".", 2)
    return f"model.layers.{i}.{_LAYER[rest]}"
