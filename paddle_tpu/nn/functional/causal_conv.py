"""The short causal depthwise convolution that state-space and linear-attention
mixers put before their scans (Gated DeltaNet, Mamba-2)."""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["causal_depthwise_conv"]


def causal_depthwise_conv(x, w, bias=None):
    """x [B, S, C], w [C, K], bias [C] or None -> [B, S, C] float32: ``y_t =
    sum_j w[:, j] x_(t - K + 1 + j) (+ bias)``, zeros before the first
    token."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + s] * wf[:, j] for j in range(k))
    return y if bias is None else y + bias.astype(jnp.float32)
