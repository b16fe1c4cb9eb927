"""Operations and bytes that a sparse layer's grouped products and a window
layer's attention require, from shapes alone. Kept with the benchmark, beside
``flops.py`` (which no PR after the first may edit), so that no later PR can
move the yardstick. Nothing recomputed is counted: a block recomputed in the
backward pass is charged its time and credited the required work once.

**The grouped products' count is an expectation.** A chip that holds
``held`` of ``of`` experts gets ``tokens x k x held / of`` assignments a
layer when routing is uniform (16,384 in ``laguna-xs2-train-s8192``); what it
really gets depends on the routing, and the cell's routing does not stay
uniform: on its uniform random ids at a constant 3e-4 the routers drift
within some twenty steps to the same 8 experts for every token, so a held
expert gets every token or none, and most sparse layers of share 0 end near
no assignment at all (PERF.md section 6, "PR 28": 15,590 / 16,180 / 17,379 /
15,548 a layer at step 0, 462 / 0 / 0 / 0 at step 15). The grouped products
then still read every held expert's weights once (one tile an expert at
least) and walk their dead tiles, so the share reads HIGH there, 67-73%
against 34% at uniform routing, without the kernels being any better: read it beside
``moe_device_ms_per_step``, and as a share of a roofline only while the
routing is near the expectation. The rows that pad each
expert's group to whole tiles are the kernel's own cost and are not counted.
"""
from __future__ import annotations

from benchmarks.harness import flops as _flops


def expected_assignments(values: dict, tokens: int) -> float:
    share = values.get("expert_share") or {"held": values["num_experts"],
                                           "of": values["num_experts"]}
    return tokens * values["num_experts_per_tok"] * share["held"] \
        / share["of"]


def held_experts(values: dict) -> int:
    share = values.get("expert_share")
    return share["held"] if share else values["num_experts"]


def grouped_products(values: dict, tokens: int, itemsize: int = 2):
    """(FLOPs, bytes) of ONE sparse layer's grouped products over the held
    experts, forward and backward, at the expected assignments: gate and up
    (rows x H times H x 2f), down (rows x f times f x H). Each product is
    three matrix products (forward, the rows' gradient, the weights'
    gradient) of ``2 rows K N`` FLOPs; the forward reads the rows and the
    held weights and writes its rows, the rows' gradient likewise, the
    weights' gradient reads both row operands and writes the weights'."""
    rows = expected_assignments(values, tokens)
    held = held_experts(values)
    h, f = values["hidden_size"], values["moe_intermediate_size"]
    ops = moved = 0.0
    for k, n in ((h, 2 * f), (f, h)):
        ops += 3 * 2.0 * rows * k * n
        lhs, out, w = rows * k, rows * n, held * k * n
        moved += itemsize * ((lhs + w + out) + (out + w + lhs)
                             + (lhs + out + w))
    return ops, moved


def mean_band(seq: int, window: int) -> float:
    """Keys a query sees on average under a causal window: the first
    ``window`` queries see fewer."""
    if window >= seq:
        return (seq + 1) / 2.0
    return window - window * (window - 1) / (2.0 * seq)


def window_attention(batch: int, heads: int, kv_heads: int, seq: int,
                     head_dim: int, window: int, itemsize: int = 2):
    """(FLOPs, bytes) of ONE window layer's attention, forward and backward:
    six matrix products (QK^T and PV forward; dV, dP, dQ, dK backward) of
    ``2 B H S n D`` FLOPs over the band's ``n`` keys a query; the bytes are
    attention's least (``flops.attention_bytes``: the band does not change
    what has to be read and written once)."""
    ops = 6 * 2.0 * batch * heads * seq * mean_band(seq, window) * head_dim
    return ops, _flops.attention_bytes(batch, heads, kv_heads, seq, head_dim,
                                       itemsize)


def least_seconds(ops: float, moved: float, peaks: dict):
    """(seconds, which side bounds) on a chip of ``peaks``."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_mem), "compute" if t_ops >= t_mem else "memory"
