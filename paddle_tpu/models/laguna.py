"""Laguna model family (poolside's Laguna-XS.2, ``model_type`` "laguna"): a
decoder whose layers are of more than one kind.

One layer body, ``LagunaDecoderLayer``, whose mixer and FFN are picked per
layer from three lists of the published config:

- ``layer_types[i]``: ``full_attention`` (causal, rotary embedding on the
  first ``partial_rotary_factor`` of each head with YaRN frequencies, cos and
  sin scaled by ``attention_factor``) or ``sliding_attention`` (causal within
  ``sliding_window`` keys, rotary embedding on the whole head with plain
  frequencies). Both through ``F.scaled_dot_product_attention``: the flash
  kernels with ``window=``.
- ``num_attention_heads_per_layer[i]``: the query heads of the layer, over
  ``num_key_value_heads`` kv heads of ``head_dim``, which is its own key and
  not ``hidden_size / heads``. A per-head output gate, a sigmoid of the
  layer's normed input, scales each head before the output projection.
- ``mlp_layer_types[i]``: ``dense`` (the SwiGLU MLP the dense decoders use,
  ``LlamaMLP``) or ``sparse`` (``incubate.moe.DroplessMoE``: sigmoid scores,
  top-k renormalised and scaled, a shared SwiGLU expert, the experts this
  chip holds).

RMSNorm, the SwiGLU MLP, the rotary application and the blockwise LM loss are
``models/llama.py``'s; the rotary tables are new (``laguna_rope_tables``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import run_op
from ..nn import functional as F
from .llama import (LlamaConfig, LlamaMLP, apply_rotary_pos_emb,
                    blockwise_lm_loss, causal_lm_loss)

__all__ = ["LagunaConfig", "LagunaForCausalLM", "laguna_rope_tables",
           "laguna_tiny"]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

_XS2_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclass
class LagunaConfig:
    """Defaults are Laguna-XS.2's published values. ``experts_held`` is the
    chip's share ``(first, count)`` of ``num_experts`` (None: all of them);
    ``vocab_size`` may likewise be the chip's slice."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    num_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    num_experts: int = 256
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=lambda: dict(_XS2_ROPE))
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    gating: bool = True
    use_recompute: bool = False
    # the published contexts start at 8k, where the flash forward kernel is
    # the dearest thing in a block to replay: keep its output and statistics
    # (fleet.recompute's policies; "full" replays the kernel too)
    recompute_policy: str = "flash_saveable"
    lm_ce: str = "blockwise"

    def __post_init__(self):
        n = len(self.layer_types)
        if not (len(self.mlp_layer_types) == len(self.num_heads_per_layer)
                == n):
            raise ValueError("layer_types, mlp_layer_types and "
                             "num_heads_per_layer differ in length")
        for h in self.num_heads_per_layer:
            if h % self.num_kv_heads:
                raise ValueError(f"{h} query heads over "
                                 f"{self.num_kv_heads} kv heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_published(cls, v: dict, **overrides):
        """From the keys of a published ``config.json``; ``overrides`` are
        fields of this class (``experts_held``, ``vocab_size`` for a chip's
        share, ``use_recompute``)."""
        n = v["num_hidden_layers"]
        kw = dict(
            vocab_size=v["vocab_size"], hidden_size=v["hidden_size"],
            intermediate_size=v["intermediate_size"],
            num_kv_heads=v["num_key_value_heads"], head_dim=v["head_dim"],
            layer_types=tuple(v["layer_types"][:n]),
            mlp_layer_types=tuple(v["mlp_layer_types"][:n]),
            num_heads_per_layer=tuple(
                v["num_attention_heads_per_layer"][:n]),
            num_experts=v["num_experts"],
            num_experts_per_tok=v["num_experts_per_tok"],
            moe_intermediate_size=v["moe_intermediate_size"],
            shared_expert_intermediate_size=v[
                "shared_expert_intermediate_size"],
            moe_routed_scaling_factor=v["moe_routed_scaling_factor"],
            sliding_window=v["sliding_window"],
            rope_parameters=v["rope_parameters"],
            max_position_embeddings=v["max_position_embeddings"],
            rms_norm_eps=v["rms_norm_eps"], gating=v.get("gating", True))
        kw.update(overrides)
        return cls(**kw)


def laguna_tiny(**overrides):
    """The published structure at toy sizes, for tests: 5 layers of the
    pattern, a head size that is not hidden / heads, 6 and 8 query heads
    over 2 kv heads, window 8, 16 experts top-4 at a width unequal to
    hidden."""
    kw = dict(
        vocab_size=96, hidden_size=48, intermediate_size=80, num_kv_heads=2,
        head_dim=16, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE),
        num_heads_per_layer=(6, 8, 8, 8, 6), num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, sliding_window=8,
        max_position_embeddings=256, lm_ce="plain")
    kw.update(overrides)
    return LagunaConfig(**kw)


def yarn_inv_freq(rot_dim: int, p: dict) -> np.ndarray:
    """YaRN's frequencies for ``rot_dim`` rotated dims (Peng et al. 2023, as
    the Hugging Face ``rope_type`` "yarn" computes them): below ``low`` the
    plain frequency, above ``high`` the frequency divided by ``factor``, a
    linear ramp between, where ``low`` and ``high`` are the dims whose
    wavelengths fit ``beta_fast`` and ``beta_slow`` times into the original
    length."""
    base, factor = float(p["rope_theta"]), float(p["factor"])
    orig = p["original_max_position_embeddings"]
    i = np.arange(rot_dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / rot_dim)
    inter = extra / factor

    def corr(turns):
        return rot_dim * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(p["beta_fast"])), 0)
    high = min(math.ceil(corr(p["beta_slow"])), rot_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


@functools.lru_cache(maxsize=16)
def _rope_tables(seq_len: int, head_dim: int, frozen_params: tuple):
    p = dict(frozen_params)
    rot = int(head_dim * p.get("partial_rotary_factor", 1))
    if p.get("rope_type", "default") == "yarn":
        inv, scale = yarn_inv_freq(rot, p), float(p.get("attention_factor")
                                                  or 1.0)
    else:
        inv = float(p["rope_theta"]) ** (
            -np.arange(0, rot, 2, dtype=np.float64) / rot)
        scale = 1.0
    ang = np.outer(np.arange(seq_len, dtype=np.float64), inv)
    # numpy, not jax arrays: the cache outlives the trace that fills it
    return ((np.cos(ang) * scale).astype(np.float32),
            (np.sin(ang) * scale).astype(np.float32))


def laguna_rope_tables(seq_len: int, head_dim: int, params: dict):
    """(cos, sin), each [seq_len, rot / 2] float32, of one kind of layer:
    ``rot = head_dim * partial_rotary_factor`` dims are rotated, in pairs
    (2i, 2i + 1) as ``apply_rotary_pos_emb`` pairs them; ``rope_type``
    "yarn" takes YaRN's frequencies and scales cos and sin by
    ``attention_factor``."""
    return _rope_tables(seq_len, head_dim, tuple(sorted(params.items())))


def _rope_partial(q, k, cos, sin):
    """Rotary embedding on the first ``2 * cos.shape[-1]`` dims of each head
    of q and k [B, S, H, D]; the rest pass."""
    rot = 2 * cos.shape[-1]
    # rotated in float32 (the tables are), stored in q's dtype: the flash
    # kernels then read bfloat16 q and k
    qr, kr = apply_rotary_pos_emb(q[..., :rot].astype(jnp.float32),
                                  k[..., :rot].astype(jnp.float32), cos, sin)
    qr, kr = qr.astype(q.dtype), kr.astype(k.dtype)
    if rot == q.shape[-1]:
        return qr, kr
    return (jnp.concatenate([qr, q[..., rot:]], axis=-1),
            jnp.concatenate([kr, k[..., rot:]], axis=-1))


def _linear(n_in, n_out):
    from ..nn.initializer import Normal
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))


class LagunaAttention(nn.Layer):
    """Causal GQA with the layer's own head count, rotary kind, window and
    per-head output gate."""

    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__()
        self.kind = cfg.layer_types[index]
        self.heads = cfg.num_heads_per_layer[index]
        self.kv_heads, self.head_dim = cfg.num_kv_heads, cfg.head_dim
        self.window = cfg.sliding_window if self.kind == SLIDING else None
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.o_proj = _linear(self.heads * d, h)
        self.g_proj = _linear(h, self.heads) if cfg.gating else None

    def forward(self, u, cos_sin):
        b, s, _ = u.shape
        d = self.head_dim
        q = self.q_proj(u).reshape([b, s, self.heads, d])
        k = self.k_proj(u).reshape([b, s, self.kv_heads, d])
        v = self.v_proj(u).reshape([b, s, self.kv_heads, d])
        cos, sin = cos_sin
        q, k = run_op("fused_rope",
                      lambda qa, ka: _rope_partial(qa, ka, cos[:s], sin[:s]),
                      (q, k))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training,
                                             window=self.window)
        if self.g_proj is not None:
            out = out * F.sigmoid(self.g_proj(u)).reshape(
                [b, s, self.heads, 1])
        return self.o_proj(out.reshape([b, s, self.heads * d]))


def _rms_norm_f32(x, weight, eps):
    """RMSNorm kept in float32: the router's input."""
    def fn(a, w):
        a = a.astype(jnp.float32)
        return a * jax.lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                                 + eps) * w.astype(jnp.float32)
    return run_op("rms_norm", fn, (x, weight))


class LagunaDecoderLayer(nn.Layer):
    """The one layer body: pre-norm mixer and pre-norm FFN around a
    residual stream, each picked from the config's lists by ``index``."""

    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__()
        from ..incubate.moe import DroplessMoE
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.eps = eps
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = LagunaAttention(cfg, index)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.sparse = cfg.mlp_layer_types[index] == SPARSE

        def swiglu(width):
            return LlamaMLP(LlamaConfig(hidden_size=h,
                                        intermediate_size=width))
        if self.sparse:
            self.mlp = DroplessMoE(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                shared=swiglu(cfg.shared_expert_intermediate_size),
                routed_scale=cfg.moe_routed_scaling_factor)
        else:
            self.mlp = swiglu(cfg.intermediate_size)

    def forward(self, h, tables):
        attn = self.self_attn
        h = h + attn(self.input_layernorm(h), tables[attn.kind])
        t = self.post_attention_layernorm(h)
        if self.sparse:
            return h + self.mlp(t, router_input=_rms_norm_f32(
                h, self.post_attention_layernorm.weight, self.eps))
        return h + self.mlp(t)


class LagunaModel(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def rope_tables(self, seq_len: int) -> dict:
        cfg = self.cfg
        if seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        return {kind: laguna_rope_tables(seq_len, cfg.head_dim,
                                         cfg.rope_parameters[kind])
                for kind in set(cfg.layer_types)}

    def forward(self, input_ids, recompute_layers=None):
        tables = self.rope_tables(input_ids.shape[1])
        h = self.embed_tokens(input_ids)
        if recompute_layers is None:
            recompute_layers = self.cfg.use_recompute and self.training
        from ..distributed.fleet.recompute import recompute
        for layer in self.layers:
            if recompute_layers:
                h = recompute(layer, h, tables,
                              policy=self.cfg.recompute_policy)
            else:
                h = layer(h, tables)
        return self.norm(h)


class RoutingStats:
    """``routing_stats`` and ``chosen_experts`` of a model with
    ``DroplessMoE`` layers, from one forward pass in a jitted function of its
    own, off the training step's path. The model says how to run that pass
    (``_route_pass(ids)``: a forward, nothing recomputed) and which layers
    to read (``sparse_layers()``: (index, DroplessMoE) pairs), and holds
    ``_routing_jit = None``."""

    def _routing(self, input_ids, params):
        """(loads, choices) by sparse layer's index."""
        from ..core.autograd import tape_paused
        from ..core.tensor import Tensor
        from ..nn.layer.layers import _swapped_state, functional_state

        if self._routing_jit is None:
            def routing(arrays, ids):
                with _swapped_state(self, arrays), tape_paused():
                    self._route_pass(Tensor(ids))
                    sparse = self.sparse_layers()
                    return ({i: m.expert_load._data for i, m in sparse},
                            {i: m.expert_choice._data.reshape(
                                ids.shape + (-1,)) for i, m in sparse})
            self._routing_jit = jax.jit(routing)
        arrays = dict(functional_state(self))
        arrays.update(params or {})
        ids = getattr(input_ids, "_data", input_ids)
        return jax.device_get(self._routing_jit(arrays, jnp.asarray(ids)))

    def routing_stats(self, input_ids, params=None) -> list:
        """Per sparse layer, for the batch ``input_ids``: the assignments
        that landed on this chip's experts, and the largest and the mean
        load of a held expert. One forward pass, nothing recomputed, off the
        training step's path: the step's signature and outputs do not
        change. ``params``: the trained leaves (name -> array) where the
        model's own buffers were donated."""
        loads, _ = self._routing(input_ids, params)
        return [{"layer": i, "assignments_here": int(v.sum()),
                 "max_load": int(v.max()), "mean_load": float(v.mean())}
                for i, v in sorted(loads.items())]

    def chosen_experts(self, input_ids, params=None) -> dict:
        """layer index -> the experts each token chose, [B, S, k] int32."""
        return self._routing(input_ids, params)[1]


class LagunaForCausalLM(RoutingStats, nn.Layer):
    """Trains through ``create_train_step`` / ``run_steps`` as the other
    families do (``loss(ids, labels)``: token-mean cross entropy, no balance
    term). ``routing_stats`` reads the expert layers' loads off the step's
    path."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LagunaModel(cfg)
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
        return [(i, layer.mlp) for i, layer in enumerate(self.model.layers)
                if layer.sparse]
