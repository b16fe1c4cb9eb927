"""How the program builds its MiniCPM-SALA decoder
(``models/minicpm_sala.py``) from a configuration's file of published keys,
and what it calls the leaves that ``reference/minicpm_sala.py`` names. The
program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    from paddle_tpu.models import MiniCPMSALAConfig, MiniCPMSALAForCausalLM
    cfg = MiniCPMSALAConfig.from_published(
        values,
        # the layer body recomputed in the backward pass, as a deployment
        # at these sizes would
        use_recompute=bool(values.get("recompute_layers", True)),
        lm_ce="blockwise")
    return MiniCPMSALAForCausalLM(cfg)


_LAYER = {
    "input_norm.weight": "input_layernorm.weight",
    "post_norm.weight": "post_attention_layernorm.weight",
    "q.weight": "self_attn.q_proj.weight",
    "k.weight": "self_attn.k_proj.weight",
    "v.weight": "self_attn.v_proj.weight",
    "q_norm.weight": "self_attn.q_norm.weight",
    "k_norm.weight": "self_attn.k_norm.weight",
    "o_norm.weight": "self_attn.o_norm.weight",
    "g.weight": "self_attn.g_proj.weight",
    "o.weight": "self_attn.o_proj.weight",
    "gate.weight": "mlp.gate_proj.weight", "up.weight": "mlp.up_proj.weight",
    "down.weight": "mlp.down_proj.weight",
}
_TOP = {"embed": "model.embed_tokens.weight",
        "norm.weight": "model.norm.weight", "head.weight": "lm_head.weight"}


def program_name(ref_name: str) -> str:
    if ref_name in _TOP:
        return _TOP[ref_name]
    _, i, rest = ref_name.split(".", 2)
    return f"model.layers.{i}.{_LAYER[rest]}"
