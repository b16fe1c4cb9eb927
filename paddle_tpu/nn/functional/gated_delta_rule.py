"""The gated delta rule, the token mixer of Gated DeltaNet layers (Qwen3-Next's
linear-attention layers): [B, S, H, d] in and out, differentiable on the tape
and under ``jax.grad``. The kernels are ``ops/pallas/gated_delta.py``; the XLA
path beside them serves hosts without a chip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import run_op
from ...ops.pallas import common as _common
from .block_sparse_attention import _kernels_here

__all__ = ["gated_delta_rule"]


def _l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gated_delta_rule(query, key, value, g, beta, rest_decay=None):
    """``query``, ``key`` [B, S, Hk, dk], ``value`` [B, S, Hv, dv] with Hv a
    multiple of Hk (value head h reads key head ``h // (Hv / Hk)``), ``g``
    (<= 0) and ``beta`` [B, S, Hv] float32 -> [B, S, Hv, dv]::

        S_t = exp(g_t) S_(t-1);  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
        o_t = S_t^T q_t

    q and k are L2-normalised (eps 1e-6) here and q scaled by ``1 /
    sqrt(dk)``; the state is float32. Leaves a ``gdn::plan`` trace event;
    ``rest_decay`` = (smallest, largest) per-token decay of the layer's heads
    at a zero input, for that event."""
    from ...ops.pallas import gated_delta as gd
    from ...profiler.tracing import trace_event
    b, s, hk, dk = query.shape
    hv, dv = value.shape[2], value.shape[3]
    kernels = _kernels_here()
    chunk = gd.gated_delta_plan(s)
    n = -(-s // chunk)
    path = "kernel" if kernels else "xla"
    gd.GDN_PLAN_TALLY[(hv, hk, dk, dv, s, chunk, path)] += 1
    lo, hi = rest_decay if rest_decay is not None else (None, None)
    trace_event("gdn::plan", cat="kernel", heads=hv, key_heads=hk,
                key_dim=dk, value_dim=dv, seq=s, path=path, chunk=chunk,
                chunks=n, smallest_decay=lo, largest_decay=hi,
                parked_state_bytes=b * hv * n * dk * dv * 4 if kernels else 0)
    interpret = _common.pallas_interpret()
    scale = np.float32(1.0 / math.sqrt(dk))

    def fn(q, k, v, gg, bb):
        q = (_l2_normalize(q) * scale).astype(v.dtype)
        k = _l2_normalize(k).astype(v.dtype)
        gg, bb = gg.astype(jnp.float32), bb.astype(jnp.float32)
        if kernels:
            return gd.gated_delta(q, k, v, gg, bb, chunk, interpret)
        return gd.gated_delta_xla(q, k, v, gg, bb, chunk)
    return run_op("gdn_recurrence", fn, (query, key, value, g, beta))
