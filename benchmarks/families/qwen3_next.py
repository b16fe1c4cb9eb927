"""How the program builds its Qwen3-Next decoder (``models/qwen3_next.py``)
from a configuration's file of published keys, and what it calls the leaves
that ``reference/qwen3_next.py`` names. The program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    share = values.get("expert_share")
    cfg = Qwen3NextConfig.from_published(
        values,
        num_experts=share["of"] if share else values["num_experts"],
        experts_held=(share["first"], share["held"]) if share else None,
        # the layer body recomputed in the backward pass, as a deployment
        # at these sizes would: the cell's sequence does not fit otherwise
        use_recompute=bool(values.get("recompute_layers", True)),
        lm_ce="blockwise")
    return Qwen3NextForCausalLM(cfg)


_LAYER = {
    "input_norm.weight": "input_layernorm.weight",
    "post_norm.weight": "post_attention_layernorm.weight",
    "q.weight": "self_attn.q_proj.weight",
    "k.weight": "self_attn.k_proj.weight",
    "v.weight": "self_attn.v_proj.weight",
    "o.weight": "self_attn.o_proj.weight",
    "q_norm.weight": "self_attn.q_norm.weight",
    "k_norm.weight": "self_attn.k_norm.weight",
    "qkvz.weight": "linear_attn.in_proj_qkvz.weight",
    "ba.weight": "linear_attn.in_proj_ba.weight",
    "conv.weight": "linear_attn.conv_weight",
    "A_log": "linear_attn.A_log",
    "dt_bias": "linear_attn.dt_bias",
    "gnorm.weight": "linear_attn.norm_weight",
    "out.weight": "linear_attn.out_proj.weight",
    "router.weight": "mlp.router_weight",
    "experts.gate": "mlp.gate_proj", "experts.up": "mlp.up_proj",
    "experts.down": "mlp.down_proj",
    "shared.gate.weight": "mlp.shared.gate_proj.weight",
    "shared.up.weight": "mlp.shared.up_proj.weight",
    "shared.down.weight": "mlp.shared.down_proj.weight",
    "shared_gate.weight": "mlp.shared.expert_gate",
}
_TOP = {"embed": "model.embed_tokens.weight",
        "norm.weight": "model.norm.weight", "head.weight": "lm_head.weight"}


def program_name(ref_name: str) -> str:
    if ref_name in _TOP:
        return _TOP[ref_name]
    _, i, rest = ref_name.split(".", 2)
    return f"model.layers.{i}.{_LAYER[rest]}"
