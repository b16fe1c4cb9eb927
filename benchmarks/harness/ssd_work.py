"""Operations and bytes that a Mamba-2 layer's SSD scan requires, from shapes
alone, and the readers of its kernels' events. Kept with the benchmark,
beside ``flops.py`` and ``gdn_work.py`` (which no later PR may edit), so
that no later PR can move the yardstick.

The scan a token requires, per head, is one state update (the rank-one
write ``(dt x) B^T``) and one read ``S C``: ``2 P N`` multiply-adds, ``4 P
N`` FLOPs forward and twice that backward, so ``12 H P N`` a token. Nothing
of the chunked form is credited: the intra-chunk products ``C B^T`` and
``(C B^T * L) X`` and their gradients are charged their time and credited
nothing. Bytes: x and y (heads) and B, C (groups) in the storage dtype, dt
in float32, each with its gradient, each moved once; the chunk states a
scan parks between its passes are the kernels' own cost.
"""
from __future__ import annotations

from benchmarks.harness import sala_work

MAMBA = "M"
# the two kernels' names (``ops/pallas/ssd.py``'s ``pallas_call``s)
KERNELS = ("ssd_fwd", "ssd_bwd")


def scan_flops_per_token(values: dict) -> float:
    """Required FLOPs of one Mamba-2 layer's scan a token, forward and
    backward."""
    return 12.0 * values["mamba_num_heads"] * values["mamba_head_dim"] \
        * values["ssm_state_size"]


def ssd(values: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) one Mamba-2 layer's scan requires a step, forward and
    backward."""
    h, p = values["mamba_num_heads"], values["mamba_head_dim"]
    g, n = values["n_groups"], values["ssm_state_size"]
    tokens = batch * seq
    values_once = tokens * (2 * h * p * itemsize + 2 * g * n * itemsize
                            + h * 4)
    return scan_flops_per_token(values) * tokens, 2.0 * values_once


def mamba_layers(values: dict) -> int:
    return values["hybrid_override_pattern"].count(MAMBA)


def kernel_seconds(trace: dict):
    """(seconds, events, {kernel: seconds}) of the two kernels' events."""
    total, count, by_name = 0.0, 0, {}
    for prefix in KERNELS:
        s, c, names = sala_work.kernel_seconds(trace, prefix)
        total, count = total + s, count + c
        by_name.update(names)
    return total, count, by_name


def read_device_ms(run, name: str):
    """The events' summed device time over the traced steps, the split by
    kernel logged; nothing where the trace holds no such event."""
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds, count, by_name = kernel_seconds(t)
    if not count or seconds <= 0:
        return None
    run["log"](f"{name}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; " + ", ".join(
                   f"{k} {v * 1e3 / t['steps']:.3f} ms a step"
                   for k, v in sorted(by_name.items())))
    return seconds * 1e3 / t["steps"]


def read_roofline(run, name: str):
    """The events' summed device time against the least time of every
    Mamba-2 layer's required scan over the traced steps; the reader says
    which side bounds it. Nothing where the trace holds no such event."""
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds, count, _ = kernel_seconds(t)
    if not count or seconds <= 0:
        return None
    cell = run["cell"]
    ops, moved = ssd(cell.config.values, cell.params["batch"],
                     cell.params["seq"])
    n = t["steps"] * mamba_layers(cell.config.values)
    least, side = sala_work.least_seconds(n * ops, n * moved, run["peaks"])
    run["log"](f"{name}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; least time {least * 1e3:.3f} ms: "
               f"{side}-bound")
    return 100.0 * least / seconds
