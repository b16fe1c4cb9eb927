"""Mamba2Mixer: the state-space token mixer of Nemotron-H's ``M`` layers
(Mamba-2, Dao & Gu 2024, as Nemotron-H's public modeling code lays it out).

From the layer's input u [B, S, hidden]:

- ``in_proj`` [hidden, I + (I + 2 G N) + H], no bias, ``I = H P``, split
  into the gate z [I], xBC [I + 2 G N] and dt [H];
- a causal depthwise convolution of width ``conv_kernel`` WITH a bias over
  xBC, then SiLU; split into x [H, P], B [G, N] and C [G, N] (head h reads
  group ``h // (H / G)``);
- ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, each per head in
  float32, ``A_log`` and ``dt_bias`` [H] each plus a fixed per-head offset
  (``A_offset``, ``dt_offset``);
- the SSD scan (``F.ssd_scan``: a float32 state [P, N] a head) and the skip
  ``D x``;
- a GATED, GROUPED RMSNorm with the gate BEFORE the norm: ``w rmsnorm(y
  silu(z))`` over G groups of ``I / G`` channels;
- ``out_proj`` [I, hidden], no bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import run_op
from .. import functional as F
from ..functional.causal_conv import causal_depthwise_conv
from ..initializer import Constant, Normal
from .common import Linear
from .layers import Layer

__all__ = ["Mamba2Mixer"]


class Mamba2Mixer(Layer):
    """The mixer (the module docstring has the equations). ``A_offset`` and
    ``dt_offset`` [H] are added to ``A_log`` and ``dt_bias``: a fixed
    reparametrisation, so that a zero-initialised leaf starts from a
    published initialisation's decays."""

    def __init__(self, hidden: int, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int,
                 chunk_size: int, eps: float, A_offset, dt_offset):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads over {n_groups} groups")
        self.h, self.p, self.g, self.n = num_heads, head_dim, n_groups, \
            state_size
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.chunk = int(chunk_size)
        self.eps = float(eps)
        self.A_offset = np.asarray(A_offset, np.float64)
        self.dt_offset = np.asarray(dt_offset, np.float64)
        init = Normal(0.0, 0.02)

        def linear(n_in, n_out):
            from ..parameter import ParamAttr
            return Linear(n_in, n_out, bias_attr=False,
                          weight_attr=ParamAttr(initializer=init))
        self.in_proj = linear(hidden, self.inner + self.conv_dim + num_heads)
        # ones: the scan's inputs B, C and x of the order of the skip's
        # (PERF.md section 4, the configuration's ``assumed``)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, conv_kernel], default_initializer=Constant(1.0))
        self.conv_bias = self.create_parameter(
            [self.conv_dim], default_initializer=init)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [num_heads], default_initializer=Constant(0.0))
        self.D = self.create_parameter(
            [num_heads], default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [self.inner], default_initializer=Constant(1.0))
        self.out_proj = linear(self.inner, hidden)

    def rest_decay(self):
        """(smallest, largest) per-token decay ``exp(dt A)`` of the heads at a
        zero input and zero ``A_log``, ``dt_bias``: from the offsets."""
        rate = np.exp(self.A_offset) * np.log1p(np.exp(self.dt_offset))
        return float(np.exp(-rate.max())), float(np.exp(-rate.min()))

    def forward(self, u):
        b, s, _ = u.shape
        h, p, g, n, inner = self.h, self.p, self.g, self.n, self.inner
        a_off = jnp.asarray(self.A_offset, jnp.float32)
        dt_off = jnp.asarray(self.dt_offset, jnp.float32)

        def inputs(zxbcdt, conv_w, conv_b, a_log, dt_bias):
            z = zxbcdt[..., :inner]
            xbc = jax.nn.silu(causal_depthwise_conv(
                zxbcdt[..., inner:inner + self.conv_dim], conv_w, conv_b)
            ).astype(zxbcdt.dtype)
            x = xbc[..., :inner].reshape(b, s, h, p)
            bm = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
            cm = xbc[..., inner + g * n:].reshape(b, s, g, n)
            dt = jax.nn.softplus(
                zxbcdt[..., inner + self.conv_dim:].astype(jnp.float32)
                + dt_bias.astype(jnp.float32) + dt_off)
            a = -jnp.exp(a_log.astype(jnp.float32) + a_off)
            return x, dt, a, bm, cm, z

        x, dt, a, bm, cm, z = run_op(
            "ssd_inputs", inputs,
            (self.in_proj(u), self.conv_weight, self.conv_bias, self.A_log,
             self.dt_bias))
        y = F.ssd_scan(x, dt, a, bm, cm, self.D, chunk=self.chunk,
                       rest_decay=self.rest_decay())

        def gated_norm(y, z, w):
            v = y.astype(jnp.float32).reshape(b, s, inner) \
                * jax.nn.silu(z.astype(jnp.float32))
            v = v.reshape(b, s, g, -1)
            v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                                  + self.eps)
            return (v.reshape(b, s, inner) * w.astype(jnp.float32)
                    ).astype(y.dtype)
        return self.out_proj(run_op("ssd_gated_norm", gated_norm,
                                    (y, z, self.norm_weight)))
