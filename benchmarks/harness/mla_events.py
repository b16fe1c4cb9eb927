"""The traced window's latent-attention kernels, found by operand shape: what
``metrics/mla_attn_roofline.py`` and ``metrics/mla_attn_device_ms_per_step.py``
both read. A family with latent attention publishes ``kv_lora_rank`` and the
split of its head (``qk_nope_head_dim``, ``qk_rope_head_dim``); its attention
calls reach the flash kernels as ``[B x heads, S, nope + rope]``, and an "XLA
Ops" event is named by its whole HLO instruction, operand shapes included."""
from __future__ import annotations

import re

PATTERN = r'\[{bh},{s},{d}\].*custom_call_target="tpu_custom_call"'
BACKWARD = re.compile(r"transpose|bwd")


def find(run):
    """None where there is nothing to read (no trace, a family without
    latent attention, no such event); else the events' summed seconds, their
    count and forward / backward split, the call's (B, heads, S, D) and the
    attention calls a step requires (the main layers and the MTP
    modules)."""
    t = run["trace"]
    v = run["cell"].config.values
    if not t or not t["steps"] or "kv_lora_rank" not in v:
        return None
    b, s = run["cell"].params["batch"], run["cell"].params["seq"]
    heads = v["num_attention_heads"]
    d = v["qk_nope_head_dim"] + v["qk_rope_head_dim"]
    pattern = re.compile(PATTERN.format(bh=b * heads, s=s, d=d))
    fwd = bwd = 0.0
    count = 0
    for start, end, name in t["ops"]:
        if not pattern.search(name):
            continue
        count += 1
        if BACKWARD.search(name.partition(" = ")[0]):
            bwd += end - start
        else:
            fwd += end - start
    if not count or fwd + bwd <= 0:
        return None
    return {"seconds": (fwd + bwd) / 1e9, "forward_s": fwd / 1e9,
            "backward_s": bwd / 1e9, "count": count, "shape": (b, heads, s, d),
            "calls_a_step": v["num_hidden_layers"]
            + v.get("num_nextn_predict_layers", 0)}
