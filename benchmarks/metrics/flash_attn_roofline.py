"""Flash attention's share of its roofline: the least time the chip could
take for the attention the traced steps require (forward and backward, causal
half, the backward pass's recomputation of the scores not counted; FLOPs and
bytes from ``harness/flops.py``) over the summed device time of the attention
kernels' events in the traced window. The reader says which side bounds it.

The events are found by name. An "XLA Ops" event is named by its whole HLO
instruction, and today's Mosaic calls of ``ops/pallas/flash_attention.py``
come out as ``%jvp__.N`` / ``%transpose_jvp___.N`` custom calls with the
target ``tpu_custom_call``: nothing in the name says attention. So the
pattern asks for that target and for an operand of attention's own shape,
``[B*Hq, S, D]``. If attention is routed elsewhere the events vanish, the
reader returns nothing, and ``step_mfu`` is what still bounds a claim
(PERF.md, Open questions C: one stable named scope for attention)."""
from benchmarks.harness import flops
from benchmarks.harness import trace as _trace

NAME = "flash_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PATTERN = r'\[{bh},{s},{d}\].*custom_call_target="tpu_custom_call"'


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    cell, peaks = run["cell"], run["peaks"]
    values = cell.config.values
    d = run["bench"].module("reference", values["family"]).dims(values)
    b, s = cell.params["batch"], cell.params["seq"]
    seconds, count = _trace.op_seconds(t, PATTERN.format(
        bh=b * d["heads"], s=s, d=d["head_dim"]))
    if not count or seconds <= 0:
        return None
    n = t["steps"] * d["layers"]
    need_flops = n * flops.attention_flops(b, d["heads"], s, d["head_dim"])
    need_bytes = n * flops.attention_bytes(b, d["heads"], d["kv_heads"], s,
                                           d["head_dim"])
    t_flops = need_flops / peaks["bf16_flops_per_s"]
    t_bytes = need_bytes / peaks["hbm_bytes_per_s"]
    run["log"](f"flash_attn_roofline: {count} events, {seconds * 1e3:.3f} ms "
               f"in {t['steps']} steps; least time by compute "
               f"{t_flops * 1e3:.3f} ms, by memory {t_bytes * 1e3:.3f} ms: "
               f"{'compute' if t_flops >= t_bytes else 'memory'}-bound")
    return 100.0 * max(t_flops, t_bytes) / seconds
