"""Operations and bytes that the work requires, from shapes alone.

Kept with the benchmark so that no later PR can move the yardstick. Nothing
recomputed is counted: a flash kernel that recomputes the scores in its
backward pass is charged the time and credited only the required work. The
sizes come from the family's plain reference (``reference/<family>.py::dims``),
so a new family brings its own.
"""
from __future__ import annotations


def train_flops_per_token(d: dict, seq: int) -> float:
    """Required FLOPs of one training step per token: 6 x the parameters that
    sit in matrix multiplications (2 forward, 4 backward; the head counts, the
    embedding look-up does not), plus causal attention at half density, which
    is ``6 S Hq D`` a token and layer (see ``attention_flops``). ``bench.py``
    has ``3 L S H`` there: it counts one of attention's two forward matmuls.
    A multiply-add is two operations, everywhere. ``d`` is what the family's
    reference gives as ``dims(values)``."""
    matmul_params = d["layers"] * d["layer_matmul_params"] \
        + d["vocab"] * d["hidden"]
    attn = attention_flops(1, d["heads"], seq, d["head_dim"]) / seq
    return 6.0 * matmul_params + d["layers"] * attn


def attention_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    """Required FLOPs of causal attention, forward and backward, for one
    layer: forward is QK^T and PV, 2 matmuls of 2 B Hq S^2 D each at full
    density; backward is dV, dP, dQ, dK, 4 of them; causal keeps half."""
    full = 2.0 * batch * heads * seq * seq * head_dim
    return (2 + 4) * full / 2


def attention_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                    head_dim: int, itemsize: int = 2) -> float:
    """Least bytes causal attention has to move for one layer, forward and
    backward, when nothing of size S^2 touches memory: forward reads Q, K, V
    and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    fwd = q + 2 * kv + q
    bwd = (q + 2 * kv + q + q) + (q + 2 * kv)
    return float(fwd + bwd)
