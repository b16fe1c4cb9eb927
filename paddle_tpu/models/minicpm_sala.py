"""MiniCPM-SALA family (openbmb's MiniCPM-SALA, ``model_type``
"minicpm_sala"): a dense decoder whose token mixers are of two kinds, neither
of them attention over all visible keys.

One layer body, ``SALADecoderLayer``; ``mixer_types[i]`` picks its mixer:

- ``minicpm4`` (``SparseMixer``): MiniCPM4's / InfLLM-V2's block-sparse
  attention. ``num_attention_heads`` query heads over ``num_key_value_heads``
  kv heads of ``head_dim``; q and k pass an RMSNorm over the head (one gain
  of ``head_dim``, shared by the heads), no rotary embedding. Up to
  ``sparse.dense_len`` tokens it is plain causal attention (the flash
  kernels); beyond, each query attends the ``sparse.topk`` key blocks it
  chose from mean-pooled keys, block 0 and the ``window_size / block_size``
  nearest always among them, one choice a kv group and no gradient through
  it (``F.block_sparse_attention``; the switch adds no parameter). A sigmoid
  gate of the layer's normed input scales the output elementwise before the
  output projection.
- ``lightning-attn`` (``LightningMixer``): linear attention with a per-head
  decay (Lightning Attention-2). ``lightning_nh`` heads of
  ``lightning_head_dim``; q and k pass the RMSNorm and the rotary embedding
  (the whole head, pairs (2i, 2i + 1) as the repo's other families); ``o_t =
  sum_{s <= t} lambda_h^(t - s) (q_t . k_s) v_s / sqrt(D)`` with ``lambda_h =
  exp(-slope_h (1 - l / (L - 1) + 1e-5))``, ``slope_h = 2^(-8 (h + 1) /
  heads)``, ``l`` the layer's published index and ``L`` the published depth
  (``F.lightning_attention``: a float32 state carried over chunks); ONE
  RMSNorm over all the heads' outputs side by side (a gain of heads x D, as
  Lightning Attention-2's public code norms its output: a norm over each
  head alone is singular at the first token, whose output is ``(q_0 . k_0)
  v_0``), the sigmoid gate, the output projection.

The family's three scalings (MiniCPM's ``scale_emb``, ``scale_depth``,
``dim_model_base``): the embedding is multiplied by ``scale_emb``; every
residual branch by ``scale_depth / sqrt(residual_scale_layers)``, where
``residual_scale_layers`` is the PUBLISHED depth however many layers are held
here; the final norm's output is divided by ``hidden_size / dim_model_base``
before the head. ``mup_denominator`` has no term in the forward pass.

What the published config does not state and is set here by the family's
convention (a configuration's file lists each as ``assumed``): the
``sparse_config`` sizes other than top-64, the decay slopes, the q/k norm's
layout, the output norm's layout (not settled: the model's own modeling code
decides between one norm over all heads, as here, and one a head), the
elementwise gate, the rotary pairing.

RMSNorm, the SwiGLU MLP, the rotary application and the blockwise LM loss are
``models/llama.py``'s; the rotary tables are ``models/laguna.py``'s.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import run_op
from ..nn import functional as F
from ..ops.pallas.sparse_attention import SparseConfig
from .laguna import _linear, _rope_partial, laguna_rope_tables
from .llama import (LlamaConfig, LlamaMLP, blockwise_lm_loss,
                    causal_lm_loss)

__all__ = ["MiniCPMSALAConfig", "MiniCPMSALAForCausalLM", "SparseMixer",
           "LightningMixer", "minicpm_sala_tiny", "lightning_decay_rates"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_PUBLISHED_MIXERS = (
    (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 3)


@dataclass
class MiniCPMSALAConfig:
    """Defaults are MiniCPM-SALA's published values; ``sparse`` is
    MiniCPM4's published ``sparse_config`` (the SALA config states top-64
    only). ``mixer_types`` may be the layers held here, ``vocab_size`` the
    chip's slice; ``residual_scale_layers`` stays the published depth."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    sparse: SparseConfig = SparseConfig()
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    residual_scale_layers: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 524288
    use_recompute: bool = False
    # a replayed block keeps the choice of blocks and the sparse sweep's
    # output and statistics (fleet.recompute's policies; "full" replays the
    # selection and the forward kernel too)
    recompute_policy: str = "sala_saveable"
    lm_ce: str = "blockwise"

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        bad = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if bad:
            raise ValueError(f"unknown mixer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of kv heads")
        if self.lightning_head_dim % 2:
            raise ValueError("lightning_head_dim is rotated in pairs")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.residual_scale_layers)

    @classmethod
    def from_published(cls, v: dict, **overrides):
        """From the keys of a published ``config.json`` (and, where a
        configuration's file gives them, ``sparse_config`` and
        ``residual_scale_layers``); ``overrides`` are fields of this class.
        Keys that would change an equation written here are refused, not
        ignored."""
        refused = {
            "attn_use_rope": bool(v.get("attn_use_rope", False)),
            "lightning_use_rope": not v.get("lightning_use_rope", True),
            "qk_norm": not v.get("qk_norm", True),
            "use_output_norm": not v.get("use_output_norm", True),
            "use_output_gate": not v.get("use_output_gate", True),
            "attn_use_output_gate": not v.get("attn_use_output_gate", True),
            "lightning_nkv": v.get("lightning_nkv", v["lightning_nh"])
            != v["lightning_nh"],
            "lightning_scale": v.get("lightning_scale",
                                     "1/sqrt(d)") != "1/sqrt(d)",
            "attention_bias": bool(v.get("attention_bias")),
            "tie_word_embeddings": bool(v.get("tie_word_embeddings")),
            "hidden_act": v.get("hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, is_bad in refused.items() if is_bad)
        if bad:
            raise ValueError(f"models/minicpm_sala.py does not compute "
                             f"these as the config states them: {bad}")
        n = v["num_hidden_layers"]
        kw = {k: v[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_head_dim", "scale_emb", "scale_depth",
            "dim_model_base", "rope_theta", "rms_norm_eps",
            "max_position_embeddings")}
        kw["mixer_types"] = tuple(v["mixer_types"][:n])
        kw["residual_scale_layers"] = v.get("residual_scale_layers", n)
        if "sparse_config" in v:
            kw["sparse"] = SparseConfig(**v["sparse_config"])
        kw.update(overrides)
        return cls(**kw)


def minicpm_sala_tiny(**overrides):
    """The published structure at toy sizes, for tests: one period of the
    pattern, 4 query heads over 2 kv heads, blocks of 8 with top-4, a window
    of 2 blocks and ``dense_len`` 32, so 64 tokens take the sparse path and
    32 the dense one."""
    kw = dict(
        vocab_size=96, hidden_size=48, intermediate_size=80,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        lightning_nh=3, lightning_head_dim=16,
        sparse=SparseConfig(kernel_size=4, kernel_stride=2, block_size=8,
                            topk=4, init_blocks=1, window_size=16,
                            dense_len=32),
        dim_model_base=12, residual_scale_layers=32,
        max_position_embeddings=256, lm_ce="plain")
    kw.update(overrides)
    return MiniCPMSALAConfig(**kw)


def lightning_decay_rates(heads: int, layer: int, depth: int) -> np.ndarray:
    """``-log(lambda_h)`` of every head of published layer ``layer`` of
    ``depth``: ``2^(-8 (h + 1) / heads) x (1 - layer / (depth - 1) + 1e-5)``
    (Lightning Attention-2's slopes, as MiniMax-Text-01's public code
    builds them), float64."""
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return slope * (1.0 - layer / max(depth - 1, 1) + 1e-5)


def _gated(out, gate):
    return run_op("output_gate",
                  lambda o, g: o * jax.nn.sigmoid(g.astype(jnp.float32)
                                                  ).astype(o.dtype),
                  (out, gate))


class SparseMixer(nn.Layer):
    """The ``minicpm4`` mixer (the module docstring has the equations)."""

    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        h, d, eps = cfg.hidden_size, cfg.head_dim, cfg.rms_norm_eps
        self.heads, self.kv_heads, self.head_dim = \
            cfg.num_attention_heads, cfg.num_key_value_heads, d
        self.sparse = cfg.sparse
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.q_norm = nn.RMSNorm(d, eps)
        self.k_norm = nn.RMSNorm(d, eps)
        self.g_proj = _linear(h, self.heads * d)
        self.o_proj = _linear(self.heads * d, h)

    def qkv(self, u):
        b, s, _ = u.shape
        d = self.head_dim
        return (self.q_norm(self.q_proj(u).reshape([b, s, self.heads, d])),
                self.k_norm(self.k_proj(u).reshape([b, s, self.kv_heads, d])),
                self.v_proj(u).reshape([b, s, self.kv_heads, d]))

    def forward(self, u, cos_sin=None):
        b, s, _ = u.shape
        q, k, v = self.qkv(u)
        out = F.block_sparse_attention(q, k, v, self.sparse,
                                       training=self.training)
        out = out.reshape([b, s, self.heads * self.head_dim])
        return self.o_proj(_gated(out, self.g_proj(u)))


class LightningMixer(nn.Layer):
    """The ``lightning-attn`` mixer (the module docstring has the
    equations). ``index`` is the layer's published index."""

    def __init__(self, cfg: MiniCPMSALAConfig, index: int):
        super().__init__()
        h, d, eps = cfg.hidden_size, cfg.lightning_head_dim, cfg.rms_norm_eps
        self.heads, self.head_dim = cfg.lightning_nh, d
        self.rates = lightning_decay_rates(self.heads, index,
                                           cfg.residual_scale_layers)
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.heads * d)
        self.v_proj = _linear(h, self.heads * d)
        self.q_norm = nn.RMSNorm(d, eps)
        self.k_norm = nn.RMSNorm(d, eps)
        self.o_norm = nn.RMSNorm(self.heads * d, eps)
        self.g_proj = _linear(h, self.heads * d)
        self.o_proj = _linear(self.heads * d, h)

    def forward(self, u, cos_sin):
        b, s, _ = u.shape
        shape = [b, s, self.heads, self.head_dim]
        q = self.q_norm(self.q_proj(u).reshape(shape))
        k = self.k_norm(self.k_proj(u).reshape(shape))
        v = self.v_proj(u).reshape(shape)
        cos, sin = cos_sin
        q, k = run_op("fused_rope",
                      lambda qa, ka: _rope_partial(qa, ka, cos[:s], sin[:s]),
                      (q, k))
        out = F.lightning_attention(q, k, v, self.rates)
        out = self.o_norm(out.reshape([b, s, self.heads * self.head_dim]))
        return self.o_proj(_gated(out, self.g_proj(u)))


class SALADecoderLayer(nn.Layer):
    """The one layer body: pre-norm mixer and pre-norm SwiGLU MLP, each
    branch scaled by ``scale_depth / sqrt(published depth)`` into the
    residual stream."""

    def __init__(self, cfg: MiniCPMSALAConfig, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.kind = cfg.mixer_types[index]
        self.scale = cfg.residual_scale
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = SparseMixer(cfg) if self.kind == SPARSE \
            else LightningMixer(cfg, index)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.mlp = LlamaMLP(LlamaConfig(
            hidden_size=h, intermediate_size=cfg.intermediate_size))

    def forward(self, h, cos_sin):
        h = h + self.self_attn(self.input_layernorm(h), cos_sin) * self.scale
        return h + self.mlp(self.post_attention_layernorm(h)) * self.scale


class MiniCPMSALAModel(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [SALADecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def rope_tables(self, seq_len: int):
        cfg = self.cfg
        if seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        return laguna_rope_tables(seq_len, cfg.lightning_head_dim,
                                  {"rope_theta": cfg.rope_theta})

    def forward(self, input_ids, recompute_layers=None):
        cfg = self.cfg
        tables = self.rope_tables(input_ids.shape[1])
        if recompute_layers is None:
            recompute_layers = cfg.use_recompute and self.training
        from ..distributed.fleet.recompute import recompute
        h = self.embed_tokens(input_ids) * cfg.scale_emb
        for layer in self.layers:
            if recompute_layers:
                h = recompute(layer, h, tables, policy=cfg.recompute_policy)
            else:
                h = layer(h, tables)
        return self.norm(h) * (cfg.dim_model_base / cfg.hidden_size)


class MiniCPMSALAForCausalLM(nn.Layer):
    """Trains through ``create_train_step`` / ``run_steps`` as the other
    families do (``loss(ids, labels)``: token-mean cross entropy).
    ``selection_stats`` reads the sparse layers' choices off the step's
    path."""

    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.cfg = cfg
        self.model = MiniCPMSALAModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)
        self._selection_jit = {}

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, input_ids, labels):
        if self.cfg.lm_ce == "blockwise":
            return blockwise_lm_loss(self.model(input_ids),
                                     self.lm_head.weight, labels,
                                     transpose_w=True)
        return causal_lm_loss(self(input_ids), labels)

    # -- the choice, off the step's path --------------------------------------
    def _chosen(self, ids):
        """layer index -> [B, Hkv, S, blocks] bool of every sparse layer past
        ``dense_len``, from one forward pass, nothing recomputed."""
        cfg = self.cfg
        out = {}
        tables = self.model.rope_tables(ids.shape[1])
        h = self.model.embed_tokens(ids) * cfg.scale_emb
        for i, layer in enumerate(self.model.layers):
            if layer.kind == SPARSE and ids.shape[1] > cfg.sparse.dense_len:
                q, k, _ = layer.self_attn.qkv(layer.input_layernorm(h))
                out[i] = F.select_attention_blocks(q, k, cfg.sparse)._data
            h = layer(h, tables)
        return out

    def _choices(self, input_ids, params, exact=False):
        """layer index -> [B, Hkv, S, blocks] bool numpy: the choice as the
        model is stored or, ``exact``, the choice of the same weights and
        activations in float32; one forward pass in a jitted function of its
        own."""
        from ..core.autograd import tape_paused
        from ..core.tensor import Tensor
        from ..nn.layer.layers import _swapped_state, functional_state

        if exact not in self._selection_jit:
            def chosen(arrays, ids):
                if exact:
                    arrays = {k: a.astype(jnp.float32)
                              if jnp.issubdtype(a.dtype, jnp.floating) else a
                              for k, a in arrays.items()}
                with _swapped_state(self, arrays), tape_paused():
                    return self._chosen(Tensor(ids))
            self._selection_jit[exact] = jax.jit(chosen)
        arrays = dict(functional_state(self))
        arrays.update(params or {})
        ids = jnp.asarray(getattr(input_ids, "_data", input_ids))
        return jax.device_get(self._selection_jit[exact](arrays, ids))

    def chosen_blocks(self, input_ids, params=None) -> dict:
        """layer index -> the blocks each query chose, [B, Hkv, S, blocks]
        bool, of every sparse layer; empty up to ``dense_len``."""
        return self._choices(input_ids, params)

    def selection_stats(self, input_ids, params=None) -> list:
        """Per sparse layer, for the batch ``input_ids`` (longer than
        ``dense_len``): the free blocks a query chose on average (those
        beyond the forced first and nearest ones), their mean distance from
        the query's own block in blocks, and the share of (query, block)
        choices that a selection from float32 weights and activations would
        not have made. Nothing recomputed, off the training step's path: the
        step's signature and outputs do not change. ``params``: the trained
        leaves (name -> array) where the model's own buffers were
        donated."""
        got = self._choices(input_ids, params)
        exact = self._choices(input_ids, params, exact=True)
        sc = self.cfg.sparse
        s = np.shape(getattr(input_ids, "_data", input_ids))[1]
        n_blocks = s // sc.block_size
        own = (np.arange(s) // sc.block_size)[None, None, :, None]
        block = np.arange(n_blocks)[None, None, None, :]
        unforced = (block >= sc.init_blocks) & (block <= own - sc.local_blocks)
        rows = []
        for i in sorted(got):
            mine, theirs = got[i], exact[i]
            free = mine & unforced
            rows.append({
                "layer": i,
                "free_blocks_per_query": float(free.sum(-1).mean()),
                "free_block_mean_distance": float(
                    np.broadcast_to(own - block, free.shape)[free].mean())
                if free.any() else 0.0,
                "choices": int(mine.sum()),
                "choices_differing_share": float(
                    (mine & ~theirs).sum() / max(mine.sum(), 1))})
        return rows
