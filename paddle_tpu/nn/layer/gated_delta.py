"""GatedDeltaNet: the linear-attention token mixer of Qwen3-Next's layers (Gated
DeltaNet, Yang et al. 2024, as Qwen3-Next's public modeling code lays it
out).

From the layer's input u [B, S, hidden]:

- ``in_proj_qkvz`` [hidden, Hk (2 dk + 2 r dv)], ``r = Hv / Hk``, laid out
  per key head as [q dk, k dk, v r dv, z r dv]; ``in_proj_ba`` [hidden, 2
  Hv], per key head [b r, a r];
- a causal depthwise convolution of width ``conv_kernel``, no bias, over the
  channels of (q, k, v) side by side, then SiLU;
- ``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)`` in
  float32, ``A_log`` and ``dt_bias`` [Hv] each plus a fixed per-head offset
  (``A_offset``, ``dt_offset``);
- the gated delta rule (``F.gated_delta_rule``: q and k L2-normalised, q
  scaled by ``1 / sqrt(dk)``, a float32 state [dk, dv] a value head);
- a gated RMSNorm a value head, ``norm(o) w silu(z)`` (w [dv], eps), and
  ``out_proj`` [Hv dv, hidden].
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

__all__ = ["GatedDeltaNet"]


class GatedDeltaNet(Layer):
    """The mixer (the module docstring has the equations). ``A_offset`` and
    ``dt_offset`` [Hv] are added to ``A_log`` and ``dt_bias``: a fixed
    reparametrisation, so that a zero-initialised leaf starts from a
    published initialisation's decays."""

    def __init__(self, hidden: int, num_k_heads: int, num_v_heads: int,
                 head_k_dim: int, head_v_dim: int, conv_kernel: int,
                 eps: float, A_offset, dt_offset):
        super().__init__()
        if num_v_heads % num_k_heads:
            raise ValueError(f"{num_v_heads} value heads over {num_k_heads} "
                             f"key heads")
        self.hk, self.hv = num_k_heads, num_v_heads
        self.dk, self.dv = head_k_dim, head_v_dim
        self.rep = num_v_heads // num_k_heads
        self.eps = float(eps)
        self.conv_dim = 2 * num_k_heads * head_k_dim + num_v_heads * head_v_dim
        self.A_offset = np.asarray(A_offset, np.float64)
        self.dt_offset = np.asarray(dt_offset, np.float64)
        init = Normal(0.0, 0.02)

        def linear(n_in, n_out):
            from ..parameter import ParamAttr
            return Linear(n_in, n_out, bias_attr=False,
                          weight_attr=ParamAttr(initializer=init))
        self.in_proj_qkvz = linear(
            hidden, num_k_heads * (2 * head_k_dim + 2 * self.rep * head_v_dim))
        self.in_proj_ba = linear(hidden, 2 * num_v_heads)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, conv_kernel], default_initializer=init)
        self.A_log = self.create_parameter(
            [num_v_heads], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [num_v_heads], default_initializer=Constant(0.0))
        self.norm_weight = self.create_parameter(
            [head_v_dim], default_initializer=Constant(1.0))
        self.out_proj = linear(num_v_heads * head_v_dim, hidden)

    def rest_decay(self):
        """(smallest, largest) per-token decay ``exp(g)`` of the heads at a
        zero input and zero ``A_log``, ``dt_bias``: from the offsets."""
        rate = np.exp(self.A_offset) * np.log1p(np.exp(self.dt_offset))
        return float(np.exp(-rate.max())), float(np.exp(-rate.min()))

    def _split(self, qkvz, ba):
        """The projections' per-key-head layout -> q, k [B, S, Hk, dk], v, z
        [B, S, Hv, dv], b, a [B, S, Hv]."""
        b, s = qkvz.shape[:2]
        hk, dk, dv, r = self.hk, self.dk, self.dv, self.rep
        x = qkvz.reshape(b, s, hk, 2 * dk + 2 * r * dv)
        q, k = x[..., :dk], x[..., dk:2 * dk]
        v = x[..., 2 * dk:2 * dk + r * dv].reshape(b, s, self.hv, dv)
        z = x[..., 2 * dk + r * dv:].reshape(b, s, self.hv, dv)
        y = ba.reshape(b, s, hk, 2 * r)
        return q, k, v, z, y[..., :r].reshape(b, s, self.hv), \
            y[..., r:].reshape(b, s, self.hv)

    def forward(self, u):
        b, s, _ = u.shape
        hk, hv, dk, dv = self.hk, self.hv, self.dk, self.dv
        a_off = jnp.asarray(self.A_offset, jnp.float32)
        dt_off = jnp.asarray(self.dt_offset, jnp.float32)

        def inputs(qkvz, ba, conv_w, a_log, dt_bias):
            q, k, v, z, bb, aa = self._split(qkvz, ba)
            mixed = jnp.concatenate([q.reshape(b, s, hk * dk),
                                     k.reshape(b, s, hk * dk),
                                     v.reshape(b, s, hv * dv)], axis=-1)
            mixed = jax.nn.silu(causal_depthwise_conv(mixed, conv_w)
                                ).astype(qkvz.dtype)
            q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
            k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
            v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
            beta = jax.nn.sigmoid(bb.astype(jnp.float32))
            g = -jnp.exp(a_log.astype(jnp.float32) + a_off) * jax.nn.softplus(
                aa.astype(jnp.float32) + dt_bias.astype(jnp.float32) + dt_off)
            return q, k, v, z, g, beta

        q, k, v, z, g, beta = run_op(
            "gdn_inputs", inputs,
            (self.in_proj_qkvz(u), self.in_proj_ba(u), self.conv_weight,
             self.A_log, self.dt_bias))
        o = F.gated_delta_rule(q, k, v, g, beta, rest_decay=self.rest_decay())

        def gated_norm(o, z, w):
            of = o.astype(jnp.float32)
            of = of * jax.lax.rsqrt(jnp.mean(jnp.square(of), -1,
                                             keepdims=True) + self.eps)
            y = of * w.astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            return y.reshape(b, s, hv * dv).astype(o.dtype)
        return self.out_proj(run_op("gdn_gated_norm", gated_norm,
                                    (o, z, self.norm_weight)))
