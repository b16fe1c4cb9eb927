"""Qwen3-Next family (Qwen's Qwen3-Next-80B-A3B, ``model_type`` "qwen3_next"): a
decoder whose token mixers are of two kinds, every layer's FFN a mixture of
experts.

One layer body, ``Qwen3NextDecoderLayer``: input RMSNorm -> mixer ->
residual; post RMSNorm -> experts -> residual. Layer i's mixer is full
attention when ``(i + 1) % full_attention_interval == 0``, else a Gated
DeltaNet (``nn.GatedDeltaNet``: the gated delta rule over a short causal
convolution, ``linear_num_value_heads`` heads of ``linear_value_head_dim``
over ``linear_num_key_heads`` key heads).

- Gated full attention (``Qwen3NextAttention``): ``q_proj`` gives [query D,
  gate D] per head, ``num_attention_heads`` over ``num_key_value_heads`` of
  ``head_dim``; q and k pass an RMSNorm over the head; rotary embedding on
  the first ``partial_rotary_factor`` of each head at ``rope_theta``, pairs
  (2i, 2i + 1) as the repo's other families; causal flash attention; the
  output times ``sigmoid(gate)`` elementwise before ``o_proj``.
- Experts (``incubate.moe.DroplessMoE``, ``score="softmax"``): the float32
  softmax over all ``num_experts`` logits, the top ``num_experts_per_tok``
  renormalised (``norm_topk_prob``), the experts this chip holds; its shared
  expert a ``GatedSharedExpert``, a SwiGLU scaled per token by
  ``sigmoid(x W_sg)``.

Norm gains are plain weights initialised to 1 (the public code's
zero-centred ``1 + w`` is the same function at these weights, and no decay
touches 1-D leaves). Each DeltaNet's ``A_log`` and ``dt_bias`` start at zero
with a fixed per-head offset (``linear_decay_init``: ``log A0`` and
``softplus^-1(dt0)``), so that the heads start from the decays of Gated
DeltaNet's published initialisation.

RMSNorm, the SwiGLU expert, the rotary application and the blockwise LM loss
are ``models/llama.py``'s; the rotary tables, ``RoutingStats`` and the
partial rotary helper are ``models/laguna.py``'s, the sigmoid output gate
``models/minicpm_sala.py``'s.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import run_op
from ..nn import functional as F
from .laguna import (RoutingStats, _linear, _rms_norm_f32, _rope_partial,
                     laguna_rope_tables)
from .llama import (LlamaConfig, LlamaMLP, blockwise_lm_loss,
                    causal_lm_loss)
from .minicpm_sala import _gated

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM", "Qwen3NextAttention",
           "GatedSharedExpert", "deltanet_decay_init", "qwen3_next_tiny"]

FULL, LINEAR = "full_attention", "linear_attention"


def deltanet_decay_init(heads: int):
    """(A0, dt0) of each value head, float64: A0 spread over (0, 16], dt0
    log-spaced over [1e-3, 1e-1] (the ranges of Gated DeltaNet's published
    initialisation), both rising with a rank that interleaves the heads
    (even heads take the lower half of the ranks, odd heads the upper), so
    that neighbouring heads remember over very different spans: the decay
    ``exp(-A0 dt0)`` at a zero input runs from 0.9995 to 0.2."""
    h = np.arange(heads)
    rank = (h % 2) * ((heads + 1) // 2) + h // 2
    a0 = 16.0 * (rank + 1) / heads
    dt0 = 1e-3 * 100.0 ** (rank / max(heads - 1, 1))
    return a0, dt0


@dataclass
class Qwen3NextConfig:
    """Defaults are Qwen3-Next-80B-A3B's published values. ``experts_held``
    is the chip's share ``(first, count)`` of ``num_experts`` (None: all);
    ``vocab_size`` may be the chip's slice, ``num_layers`` the layers held.
    ``linear_decay_init`` = (A0, dt0) per value head; None takes
    ``deltanet_decay_init``."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_decay_init: Optional[Tuple[tuple, tuple]] = None
    num_experts: int = 512
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    use_recompute: bool = False
    # the forward kernels are the dearest things in a block to replay: the
    # full layer's flash call and the DeltaNet layers' delta rule keep their
    # outputs (and the flash statistics, the delta rule's chunk states) for
    # the backward pass (fleet.recompute's policies; "full" replays them)
    recompute_policy: str = "flash_saveable"
    lm_ce: str = "blockwise"
    layer_types: Tuple[str, ...] = field(init=False)

    def __post_init__(self):
        n = self.full_attention_interval
        self.layer_types = tuple(FULL if (i + 1) % n == 0 else LINEAR
                                 for i in range(self.num_layers))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of kv heads")
        if self.linear_decay_init is None:
            a0, dt0 = deltanet_decay_init(self.linear_num_value_heads)
            self.linear_decay_init = (tuple(a0), tuple(dt0))

    @classmethod
    def from_published(cls, v: dict, **overrides):
        """From the keys of a published ``config.json`` (and, where a
        configuration's file gives it, ``linear_decay_init`` {A, dt});
        ``overrides`` are fields of this class. Keys that would change an
        equation written here are refused, not ignored."""
        refused = {
            "decoder_sparse_step": v.get("decoder_sparse_step", 1) != 1,
            "mlp_only_layers": bool(v.get("mlp_only_layers")),
            "norm_topk_prob": not v.get("norm_topk_prob", True),
            "rope_scaling": v.get("rope_scaling") is not None,
            "use_sliding_window": bool(v.get("use_sliding_window")),
            "tie_word_embeddings": bool(v.get("tie_word_embeddings")),
            "attention_bias": bool(v.get("attention_bias")),
            "hidden_act": v.get("hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, is_bad in refused.items() if is_bad)
        if bad:
            raise ValueError(f"models/qwen3_next.py does not compute these "
                             f"as the config states them: {bad}")
        kw = {k: v[k] for k in (
            "vocab_size", "hidden_size", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "rms_norm_eps",
            "max_position_embeddings")}
        kw["num_layers"] = v["num_hidden_layers"]
        init = v.get("linear_decay_init")
        if init is not None:
            kw["linear_decay_init"] = (tuple(init["A"]), tuple(init["dt"]))
        kw.update(overrides)
        return cls(**kw)


def qwen3_next_tiny(**overrides):
    """The published structure at toy sizes, for tests: one period (three
    DeltaNet layers and a full one), 4 value heads over 2 key heads, 4 query
    heads over 2 kv heads of 16 with a quarter rotated, 16 experts top-4 and
    a gated shared expert."""
    kw = dict(
        vocab_size=96, hidden_size=48, num_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=8, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=24, shared_expert_intermediate_size=24,
        max_position_embeddings=256, lm_ce="plain")
    kw.update(overrides)
    return Qwen3NextConfig(**kw)


class Qwen3NextAttention(nn.Layer):
    """Gated causal GQA (the module docstring has the equations)."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        h, d, eps = cfg.hidden_size, cfg.head_dim, cfg.rms_norm_eps
        self.heads, self.kv_heads, self.head_dim = \
            cfg.num_attention_heads, cfg.num_key_value_heads, d
        self.q_proj = _linear(h, self.heads * 2 * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.q_norm = nn.RMSNorm(d, eps)
        self.k_norm = nn.RMSNorm(d, eps)
        self.o_proj = _linear(self.heads * d, h)

    def forward(self, u, cos_sin):
        b, s, _ = u.shape
        d = self.head_dim
        qg = self.q_proj(u).reshape([b, s, self.heads, 2 * d])
        q, gate = qg[..., :d], qg[..., d:]
        q = self.q_norm(q)
        k = self.k_norm(self.k_proj(u).reshape([b, s, self.kv_heads, d]))
        v = self.v_proj(u).reshape([b, s, self.kv_heads, d])
        cos, sin = cos_sin
        q, k = run_op("fused_rope",
                      lambda qa, ka: _rope_partial(qa, ka, cos[:s], sin[:s]),
                      (q, k))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(_gated(out, gate).reshape([b, s, self.heads * d]))


class GatedSharedExpert(LlamaMLP):
    """The shared expert: a SwiGLU whose output, in float32, is scaled per
    token by ``sigmoid(x @ expert_gate)``, ``expert_gate`` [hidden, 1]."""

    def __init__(self, hidden: int, intermediate: int):
        super().__init__(LlamaConfig(hidden_size=hidden,
                                     intermediate_size=intermediate))
        from ..nn.initializer import Normal
        self.expert_gate = self.create_parameter(
            [hidden, 1], default_initializer=Normal(0.0, 0.02))

    def forward(self, x):
        return super().forward(x).astype("float32") * run_op(
            "moe_shared_gate",
            lambda a, w: jax.nn.sigmoid(jnp.dot(
                a, w, preferred_element_type=jnp.float32)),
            (x, self.expert_gate))


class Qwen3NextDecoderLayer(nn.Layer):
    """The one layer body (the module docstring has it)."""

    def __init__(self, cfg: Qwen3NextConfig, index: int):
        super().__init__()
        from ..incubate.moe import DroplessMoE
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.eps = eps
        self.kind = cfg.layer_types[index]
        self.input_layernorm = nn.RMSNorm(h, eps)
        if self.kind == FULL:
            self.self_attn = Qwen3NextAttention(cfg)
        else:
            a0, dt0 = (np.asarray(x, np.float64)
                       for x in cfg.linear_decay_init)
            self.linear_attn = nn.GatedDeltaNet(
                h, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                cfg.linear_conv_kernel_dim, eps, A_offset=np.log(a0),
                dt_offset=np.log(np.expm1(dt0)))
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.mlp = DroplessMoE(
            h, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held=cfg.experts_held,
            shared=GatedSharedExpert(h, cfg.shared_expert_intermediate_size),
            score="softmax")

    def forward(self, h, cos_sin):
        u = self.input_layernorm(h)
        if self.kind == FULL:
            h = h + self.self_attn(u, cos_sin)
        else:
            h = h + self.linear_attn(u)
        t = self.post_attention_layernorm(h)
        return h + self.mlp(t, router_input=_rms_norm_f32(
            h, self.post_attention_layernorm.weight, self.eps))


class Qwen3NextModel(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [Qwen3NextDecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def rope_tables(self, seq_len: int):
        cfg = self.cfg
        if seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        return laguna_rope_tables(
            seq_len, cfg.head_dim,
            {"rope_theta": cfg.rope_theta,
             "partial_rotary_factor": cfg.partial_rotary_factor})

    def forward(self, input_ids, recompute_layers=None):
        cfg = self.cfg
        tables = self.rope_tables(input_ids.shape[1])
        if recompute_layers is None:
            recompute_layers = cfg.use_recompute and self.training
        from ..distributed.fleet.recompute import recompute
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            if recompute_layers:
                h = recompute(layer, h, tables, policy=cfg.recompute_policy)
            else:
                h = layer(h, tables)
        return self.norm(h)


class Qwen3NextForCausalLM(RoutingStats, nn.Layer):
    """Trains through ``create_train_step`` / ``run_steps`` as the other
    families do (``loss(ids, labels)``: token-mean cross entropy, no balance
    term). ``routing_stats`` reads the expert layers' loads off the step's
    path."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)
        self._routing_jit = None

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, input_ids, labels):
        if self.cfg.lm_ce == "blockwise":
            return blockwise_lm_loss(self.model(input_ids),
                                     self.lm_head.weight, labels,
                                     transpose_w=True)
        return causal_lm_loss(self(input_ids), labels)

    def _route_pass(self, ids):
        self.model(ids, recompute_layers=False)

    def sparse_layers(self):
        return [(i, layer.mlp) for i, layer in enumerate(self.model.layers)]
