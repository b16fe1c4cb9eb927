"""How the program builds its Nemotron-H decoder (``models/nemotron_h.py``)
from a configuration's file of published keys, and what it calls the leaves
that ``reference/nemotron_h.py`` names. The program side of the family."""
from __future__ import annotations


def build_model(values: dict):
    import jax.numpy as jnp

    from benchmarks.reference import nemotron_h as reference
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
    share = values.get("expert_share")
    cfg = NemotronHConfig.from_published(
        values,
        n_routed_experts=share["of"] if share else values["n_routed_experts"],
        experts_held=(share["first"], share["held"]) if share else None,
        # the layer body recomputed in the backward pass, as a deployment
        # at these sizes would: the cell's sequence does not fit otherwise
        use_recompute=bool(values.get("recompute_layers", True)),
        lm_ce="blockwise")
    model = NemotronHForCausalLM(cfg)
    # the frozen correction biases are the benchmark's data, as the weights
    # are; no trained leaf, so the runner's write_back does not carry them
    for layer, moe in model.sparse_layers():
        moe.e_score_correction_bias._data = jnp.asarray(
            reference.score_bias(values, layer))
    return model


_LAYER = {
    "norm.weight": "norm.weight",
    "in.weight": "mixer.in_proj.weight",
    "conv.weight": "mixer.conv_weight",
    "conv.bias": "mixer.conv_bias",
    "A_log": "mixer.A_log",
    "dt_bias": "mixer.dt_bias",
    "D": "mixer.D",
    "gnorm.weight": "mixer.norm_weight",
    "out.weight": "mixer.out_proj.weight",
    "router.weight": "mixer.router_weight",
    "experts.up": "mixer.up_proj",
    "experts.down": "mixer.down_proj",
    "shared.up.weight": "mixer.shared.up_proj.weight",
    "shared.down.weight": "mixer.shared.down_proj.weight",
    "q.weight": "mixer.q_proj.weight",
    "k.weight": "mixer.k_proj.weight",
    "v.weight": "mixer.v_proj.weight",
    "o.weight": "mixer.o_proj.weight",
}
_TOP = {"embed": "model.embeddings.weight",
        "norm.weight": "model.norm_f.weight", "head.weight": "lm_head.weight"}


def program_name(ref_name: str) -> str:
    if ref_name in _TOP:
        return _TOP[ref_name]
    _, i, rest = ref_name.split(".", 2)
    return f"model.layers.{i}.{_LAYER[rest]}"
