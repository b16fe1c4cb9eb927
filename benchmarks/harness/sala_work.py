"""Operations and bytes that a layer of attention over chosen key blocks and
a layer of linear attention with decay require, from shapes alone. Kept with
the benchmark, beside ``flops.py`` and ``sparse_flops.py`` (which no later PR
may edit), so that no later PR can move the yardstick. Nothing recomputed is
counted: a sweep over keys a query did not choose, a chunk's masked
intra-chunk product and a recomputed block's second forward are charged their
time and credited nothing.

**Chosen-block attention** (``sparse_attn_*`` kernels). A query at position
``t`` attends ``n(t)`` keys: all ``t + 1`` while it sees ``topk`` blocks or
fewer, then ``topk - 1`` whole blocks and its own block up to itself
(``reference/minicpm_sala.py::mean_attended_keys`` is the mean). Forward is
QK^T and PV, backward dV, dP, dQ, dK: 6 products of ``2 Hq D n(t)`` FLOPs a
token. The choice itself (pooled keys, scores, top-k) runs outside these
kernels and is not in their work. Bytes: the causal-attention minimum of
``flops.attention_bytes`` (q, k, v, o, dO read, o, dq, dk, dv written, nothing
of size S x S) plus the choice, ``topk`` int32 a token and kv head, read
once forward and once backward.

**Linear attention with decay** (``linear_attn_*`` kernels). The recurrence a
token requires is one state update ``k^T v`` and one read-out ``q KV`` a
head, ``2 D^2`` FLOPs each forward, twice that backward: ``12 H D^2`` a
token. Bytes: forward reads q, k, v and writes o; backward reads q, k, v, dO
and writes dq, dk, dv; the states a chunked scan parks between its passes
are the kernel's own cost.
"""
from __future__ import annotations

from benchmarks.harness import flops as _flops


def sparse_attention(values: dict, batch: int, seq: int,
                     mean_keys: float, itemsize: int = 2):
    """(FLOPs, bytes) one chosen-block layer's attention requires a step,
    forward and backward. ``mean_keys``: the mean keys a query attends."""
    hq, hkv, d = values["num_attention_heads"], \
        values["num_key_value_heads"], values["head_dim"]
    ops = 12.0 * hq * d * mean_keys * batch * seq
    moved = _flops.attention_bytes(batch, hq, hkv, seq, d, itemsize) \
        + 2.0 * 4 * batch * hkv * seq * values["sparse_config"]["topk"]
    return ops, moved


def linear_attention(values: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) one lightning layer's recurrence requires a step,
    forward and backward."""
    h, d = values["lightning_nh"], values["lightning_head_dim"]
    ops = 12.0 * h * d * d * batch * seq
    moved = (4 + 7) * float(batch * seq * h * d * itemsize)
    return ops, moved


def least_seconds(ops: float, moved: float, peaks: dict):
    """(seconds, the side that bounds): the roofline's least time."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_mem), "compute" if t_ops >= t_mem else "memory"


def kernel_seconds(trace: dict, prefix: str):
    """(seconds, events, {kernel name: seconds}) of the traced window's
    operations whose instruction name holds ``prefix`` (the kernels' ``name=``
    on their ``pallas_call``)."""
    total, count, by_name = 0.0, 0, {}
    for start, end, name in trace["ops"]:
        head = name.partition(" = ")[0]
        at = head.find(prefix)
        if at < 0:
            continue
        kernel = head[at:].split(".")[0].rstrip("_0123456789")
        total += end - start
        count += 1
        by_name[kernel] = by_name.get(kernel, 0.0) + (end - start) / 1e9
    return total / 1e9, count, by_name


def read_device_ms(run, name: str, prefix: str):
    """A ``<kernels>_device_ms_per_step`` reader's body: the events' summed
    device time over the traced steps, the split by kernel logged; nothing
    where the trace holds no such event."""
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds, count, by_name = kernel_seconds(t, prefix)
    if not count or seconds <= 0:
        return None
    run["log"](f"{name}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; " + ", ".join(
                   f"{k} {v * 1e3 / t['steps']:.3f} ms a step"
                   for k, v in sorted(by_name.items())))
    return seconds * 1e3 / t["steps"]


def read_roofline(run, name: str, prefix: str, kind: str, work, note=""):
    """A ``<kernels>_roofline`` reader's body: ``work`` = (FLOPs, bytes) one
    layer of ``kind`` requires a step, times the layers and the traced
    steps, at the roofline's least time, over the events' summed time."""
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds, count, _ = kernel_seconds(t, prefix)
    if not count or seconds <= 0:
        return None
    n = t["steps"] * layers_of(run["cell"].config.values, kind)
    least, side = least_seconds(n * work[0], n * work[1], run["peaks"])
    run["log"](f"{name}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; least time {least * 1e3:.3f} ms"
               f"{note}: {side}-bound")
    return 100.0 * least / seconds


def layers_of(values: dict, kind: str) -> int:
    n = values["num_hidden_layers"]
    return sum(1 for m in values.get("mixer_types", [])[:n] if m == kind)
