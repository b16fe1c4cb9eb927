"""Operations and bytes that a Gated DeltaNet layer's recurrence requires, from
shapes alone. Kept with the benchmark, beside ``flops.py`` and
``sala_work.py`` (which no later PR may edit), so that no later PR can move
the yardstick.

The recurrence a token requires, per value head, is one state update (``S^T
k`` and the rank-one write ``k delta^T``) and one read ``S^T q``: ``3 dk dv``
multiply-adds, ``6 dk dv`` FLOPs forward and twice that backward, so ``18 Hv
dk dv`` a token. Nothing of the chunked form is credited: the intra-chunk
products, the triangular solve and the backward pass's second forward are
charged their time and credited nothing. Bytes: q, k (key heads), v, o
(value heads) in the storage dtype and g, beta in float32, each with its
gradient, each moved once; the chunk states a scan parks between its passes
are the kernels' own cost.
"""
from __future__ import annotations

from benchmarks.harness import sala_work

LINEAR = "linear_attention"


def gated_delta(values: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) one DeltaNet layer's recurrence requires a step,
    forward and backward."""
    hk, hv = values["linear_num_key_heads"], values["linear_num_value_heads"]
    dk, dv = values["linear_key_head_dim"], values["linear_value_head_dim"]
    tokens = batch * seq
    ops = 18.0 * hv * dk * dv * tokens
    values_once = tokens * (2 * hk * dk * itemsize + 2 * hv * dv * itemsize
                            + 2 * hv * 4)
    return ops, 2.0 * values_once


def deltanet_layers(values: dict) -> int:
    n = values["full_attention_interval"]
    return sum(1 for i in range(values["num_hidden_layers"])
               if (i + 1) % n != 0)


def read_roofline(run, name: str, prefix: str):
    """The events' summed device time against the least time of every
    DeltaNet layer's required work over the traced steps; the reader says
    which side bounds it. Nothing where the trace holds no such event."""
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds, count, _ = sala_work.kernel_seconds(t, prefix)
    if not count or seconds <= 0:
        return None
    cell = run["cell"]
    ops, moved = gated_delta(cell.config.values, cell.params["batch"],
                             cell.params["seq"])
    n = t["steps"] * deltanet_layers(cell.config.values)
    least, side = sala_work.least_seconds(n * ops, n * moved, run["peaks"])
    run["log"](f"{name}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; least time {least * 1e3:.3f} ms: "
               f"{side}-bound")
    return 100.0 * least / seconds
