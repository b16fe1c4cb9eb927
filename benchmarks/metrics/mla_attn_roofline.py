"""Latent attention's share of its roofline: the least time the chip could
take for the causal attention the traced steps require, one call a main layer
and one for the MTP module at ``(B, heads, heads, S, nope + rope)`` (forward
and backward, causal half; ``harness/flops.py::attention_flops`` /
``attention_bytes``; neither the backward pass's recomputation of the scores
nor the recomputed layer body's second forward is counted), over the summed
device time of the Mosaic calls with an operand ``[B x heads, S, nope +
rope]`` (``harness/mla_events.py``). The reader says which side bounds it."""
from benchmarks.harness import flops, mla_events

NAME = "mla_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = mla_events.find(run)
    if found is None:
        return None
    t, peaks = run["trace"], run["peaks"]
    b, heads, s, d = found["shape"]
    n = t["steps"] * found["calls_a_step"]
    t_flops = n * flops.attention_flops(b, heads, s, d) \
        / peaks["bf16_flops_per_s"]
    t_bytes = n * flops.attention_bytes(b, heads, heads, s, d) \
        / peaks["hbm_bytes_per_s"]
    run["log"](f"mla_attn_roofline: {found['count']} events, "
               f"{found['seconds'] * 1e3:.3f} ms in {t['steps']} steps; "
               f"least time by compute {t_flops * 1e3:.3f} ms, by memory "
               f"{t_bytes * 1e3:.3f} ms: "
               f"{'compute' if t_flops >= t_bytes else 'memory'}-bound")
    return 100.0 * max(t_flops, t_bytes) / found["seconds"]
