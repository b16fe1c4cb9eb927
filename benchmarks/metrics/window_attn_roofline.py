"""Windowed flash attention's share of its roofline: the least time the chip
could take for the band of the window layers that the traced steps require
(forward and backward, ``12 B H S n D`` FLOPs a layer with ``n`` the keys a
query sees on average; ``harness/sparse_flops.py``; nothing recomputed
counted, nor the scores a tile computes outside the band) over the summed
device time of the ``flash_win_`` events (``ops/pallas/flash_attention.py``
gives a call with a window these names). The reader says which side bounds
it."""
import re

from benchmarks.harness import sparse_flops

NAME = "window_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PATTERN = re.compile(r"flash_win_")


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    spent = [e - s for s, e, name in t["ops"]
             if PATTERN.search(name.partition(" = ")[0])]
    seconds = sum(spent) / 1e9
    if not spent or seconds <= 0:
        return None
    cell = run["cell"]
    v = cell.config.values
    n = v["num_hidden_layers"]
    b, s = cell.params["batch"], cell.params["seq"]
    ops = moved = 0.0
    for kind, heads in zip(v["layer_types"][:n],
                           v["num_attention_heads_per_layer"][:n]):
        if kind == "sliding_attention":
            o, m = sparse_flops.window_attention(
                b, heads, v["num_key_value_heads"], s, v["head_dim"],
                v["sliding_window"])
            ops, moved = ops + o, moved + m
    least, side = sparse_flops.least_seconds(t["steps"] * ops,
                                             t["steps"] * moved, run["peaks"])
    run["log"](f"window_attn_roofline: {len(spent)} events, "
               f"{seconds * 1e3:.3f} ms in {t['steps']} steps; least time "
               f"{least * 1e3:.3f} ms: {side}-bound")
    return 100.0 * least / seconds
