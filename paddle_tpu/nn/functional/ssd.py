"""Mamba-2's state-space scan (SSD), the token mixer of Nemotron-H's Mamba-2
layers: [B, S, H, P] in and out, differentiable on the tape and under
``jax.grad``. The kernels are ``ops/pallas/ssd.py``; the XLA path beside them
serves hosts without a chip.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.dispatch import run_op
from .block_sparse_attention import _kernels_here

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, A, B, C, D, chunk: int = 128, rest_decay=None):
    """``x`` [B, S, H, P], ``dt`` [B, S, H] float32 (> 0), ``A`` [H] float32
    (< 0), ``B``, ``C`` [B, S, G, N] (head h reads group ``h // (H / G)``),
    ``D`` [H] -> y [B, S, H, P] in x's dtype::

        S_t = exp(dt_t A) S_(t-1) + (dt_t x_t) B_t^T;   y_t = S_t C_t + D x_t

    The state [P, N] a head is float32. Leaves an ``ssd::plan`` trace event;
    ``rest_decay`` = (smallest, largest) per-token decay of the layer's heads
    at a zero input, for that event."""
    from ...distributed.fleet.recompute import replayed
    from ...ops.pallas import ssd as sd
    from ...profiler.tracing import trace_event
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    kernels = _kernels_here()
    cs = sd.ssd_plan(s, chunk)
    chunks = -(-s // cs)
    path = "kernel" if kernels else "xla"
    forward = "replayed" if replayed("ssd_states") else "kept"
    sd.SSD_PLAN_TALLY[(s, cs, h, g, n, p, path, forward)] += 1
    lo, hi = rest_decay if rest_decay is not None else (None, None)
    trace_event("ssd::plan", cat="kernel", seq=s, chunk=cs, chunks=chunks,
                heads=h, groups=g, state=n, head_dim=p, path=path,
                forward=forward, smallest_decay=lo, largest_decay=hi,
                parked_state_bytes=b * h * chunks * p * n * 4
                if kernels else 0)

    def fn(xx, dd, aa, bb, cc, skip):
        scan = sd.ssd if kernels else sd.ssd_xla
        y = scan(xx, dd.astype(jnp.float32), aa.astype(jnp.float32), bb, cc,
                 cs)
        return (y.astype(jnp.float32) + skip.astype(jnp.float32)[:, None]
                * xx.astype(jnp.float32)).astype(xx.dtype)
    return run_op("ssd_scan", fn, (x, dt, A, B, C, D))
