"""The gated full attention's share of its roofline (Qwen3-Next): the least
time the chip could take for the causal attention the traced steps require,
one call a full layer (every ``full_attention_interval``-th) at ``(B,
num_attention_heads, num_key_value_heads, S, head_dim)`` (forward and
backward, causal half; ``harness/flops.py::attention_flops`` /
``attention_bytes``; neither the backward pass's recomputation of the scores
nor a recomputed layer body's second forward is counted), over the summed
device time of the operations whose instruction name holds ``flash_``. The
reader says which side bounds it. Nothing where the family has no
``full_attention_interval`` or the trace no such event."""
from benchmarks.harness import flops, sala_work

NAME = "gated_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "flash_"


def read(run):
    t = run["trace"]
    v = run["cell"].config.values
    if not t or not t["steps"] or "full_attention_interval" not in v:
        return None
    seconds, count, _ = sala_work.kernel_seconds(t, PREFIX)
    if not count or seconds <= 0:
        return None
    b, s = run["cell"].params["batch"], run["cell"].params["seq"]
    hq, hkv, d = (v["num_attention_heads"], v["num_key_value_heads"],
                  v["head_dim"])
    n = t["steps"] * (v["num_hidden_layers"] // v["full_attention_interval"])
    least, side = sala_work.least_seconds(
        n * flops.attention_flops(b, hq, s, d),
        n * flops.attention_bytes(b, hq, hkv, s, d), run["peaks"])
    run["log"](f"{NAME}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; least time {least * 1e3:.3f} ms at "
               f"({b}, {hq}, {hkv}, {s}, {d}): {side}-bound")
    return 100.0 * least / seconds
