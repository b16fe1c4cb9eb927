"""How the program builds its GLM MoE "lite" decoder
(``models/glm_moe_lite.py``) from a configuration's file of published keys,
and what it calls the leaves that ``reference/glm4_moe_lite.py`` names. The
program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    import jax.numpy as jnp

    from benchmarks.reference import glm4_moe_lite as reference
    from paddle_tpu.models import GlmMoeLiteConfig, GlmMoeLiteForCausalLM
    share = values.get("expert_share")
    cfg = GlmMoeLiteConfig.from_published(
        values,
        n_routed_experts=share["of"] if share else values["n_routed_experts"],
        experts_held=(share["first"], share["held"]) if share else None,
        mtp_loss_weight=values["mtp_loss_weight"],
        # the layer body recomputed in the backward pass, as a deployment
        # at these sizes would
        use_recompute=bool(values.get("recompute_layers", True)),
        lm_ce="blockwise")
    model = GlmMoeLiteForCausalLM(cfg)
    # the frozen correction biases are the benchmark's data, as the weights
    # are; no trained leaf, so the runner's write_back does not carry them
    for layer, moe in model.sparse_layers():
        moe.e_score_correction_bias._data = jnp.asarray(
            reference.score_bias(values, layer))
    return model


_LAYER = {
    "input_norm.weight": "input_layernorm.weight",
    "post_norm.weight": "post_attention_layernorm.weight",
    "q_a.weight": "self_attn.q_a_proj.weight",
    "q_a_norm.weight": "self_attn.q_a_layernorm.weight",
    "q_b.weight": "self_attn.q_b_proj.weight",
    "kv_a.weight": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm.weight": "self_attn.kv_a_layernorm.weight",
    "kv_b.weight": "self_attn.kv_b_proj.weight",
    "o.weight": "self_attn.o_proj.weight",
    "gate.weight": "mlp.gate_proj.weight", "up.weight": "mlp.up_proj.weight",
    "down.weight": "mlp.down_proj.weight",
    "router.weight": "mlp.router_weight",
    "experts.gate": "mlp.gate_proj", "experts.up": "mlp.up_proj",
    "experts.down": "mlp.down_proj",
    "shared.gate.weight": "mlp.shared.gate_proj.weight",
    "shared.up.weight": "mlp.shared.up_proj.weight",
    "shared.down.weight": "mlp.shared.down_proj.weight",
}
_TOP = {"embed": "model.embed_tokens.weight",
        "norm.weight": "model.norm.weight", "head.weight": "lm_head.weight",
        "mtp.enorm.weight": "mtp.enorm.weight",
        "mtp.hnorm.weight": "mtp.hnorm.weight",
        "mtp.eh.weight": "mtp.eh_proj.weight",
        "mtp.norm.weight": "mtp.norm.weight"}


def program_name(ref_name: str) -> str:
    if ref_name in _TOP:
        return _TOP[ref_name]
    if ref_name.startswith("mtp.layer."):
        return "mtp.block." + _LAYER[ref_name[len("mtp.layer."):]]
    _, i, rest = ref_name.split(".", 2)
    return f"model.layers.{i}.{_LAYER[rest]}"
