"""Llama model family (BASELINE.md configs #2/#3: Llama-2 7B TP, 13B
semi-auto SPMD + ZeRO-3).

TPU-first: the model is written once with plain layers; parallelism is a
sharding-spec map over parameter names (Megatron placements: vocab-parallel
embedding, column-parallel qkv/gate/up, row-parallel o/down) applied to the
functional train step — GSPMD inserts the TP collectives, the dp axis gives
DP/ZeRO via Shard over params/opt-state (stage 3 = FSDP layout), and
activations carry (dp, sep) constraints for sequence sharding. The same
module also exposes the fleet-style TP construction path via mpu layers.

Reference parity anchors: llama decoder structure mirrors the reference's
end-to-end parallel test model (test/auto_parallel/hybrid_strategy/
semi_auto_llama.py), RoPE matches fused_rotary_position_embedding
(paddle/phi/kernels/fusion/gpu/fused_rope*), attention matches
flash_attn contract (ops.yaml:978).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import run_op
from ..nn import functional as F

__all__ = ["LlamaConfig", "LlamaForCausalLM", "llama_7b", "llama_13b",
           "llama_tiny", "llama_param_spec", "llama_fsdp_spec",
           "llama_pipeline_model", "apply_rotary_pos_emb"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dropout: float = 0.0
    use_recompute: bool = False
    # jax.checkpoint saveable policy for use_recompute: "full" replays the
    # whole layer; "dots_saveable"/"selective" keep matmul outputs and
    # recompute only elementwise (near-zero extra FLOPs, more memory);
    # "flash_saveable" is "full" with each flash call's output and
    # statistics kept, so the forward kernel is not replayed (long contexts)
    recompute_policy: str = "full"
    # "plain": full logits through lm_head + CE; "blockwise": vocab-chunked
    # streaming LM-head+CE (ops/fused_ce.py) — same math, caps the logits
    # residual at vocab/num_blocks columns (HBM headroom at 0.7B+ on v5e)
    lm_ce: str = "plain"


def llama_7b():
    return LlamaConfig()


def llama_13b():
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_layers=40, num_heads=40, num_kv_heads=40)


def llama_tiny():
    return LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       max_position_embeddings=128)


@functools.lru_cache(maxsize=16)
def _rope_tables(seq_len, head_dim, theta, dtype=jnp.float32):
    """Position-only cos/sin tables; cached so every decoder layer (and
    every pipeline stage) shares one pair per (seq, dim, theta)."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(seq_len)
    freqs = np.outer(t, inv)  # [s, d/2]
    return (jnp.asarray(np.cos(freqs), dtype), jnp.asarray(np.sin(freqs), dtype))


def causal_lm_loss(logits, labels):
    """Token-mean cross entropy over flattened [B,S,V] logits — the one
    causal-LM loss body shared by the stateful model and the pipeline
    variant (so a semantics change cannot diverge between them)."""
    b, s, v = logits.shape
    return F.cross_entropy(logits.reshape([b * s, v]),
                           labels.reshape([b * s]))


def _auto_num_blocks(tokens: int, vocab: int,
                     target_elems: int = 64 * 1024 * 1024) -> int:
    """Vocab-chunk count so one streamed (tokens, vocab/nb) f32 block
    stays ~<= 256 MB regardless of batch: a fixed nb=8 scales the chunk
    residual WITH tokens — at b64/s1024 that is ~1.6 GB per chunk and the
    b128 sweep candidate would OOM on exactly the memory this loss exists
    to save. Doubles nb (while vocab stays divisible, up to 128) until
    the chunk fits."""
    nb = 8
    while (tokens * (vocab // nb) > target_elems and nb < 128
           and vocab % (nb * 2) == 0):
        nb *= 2
    return nb


def blockwise_lm_loss(h, w, labels, transpose_w=False):
    """Token-mean CE through the vocab-streamed LM-head
    (ops/fused_ce.blockwise_linear_cross_entropy) — the one blockwise loss
    body shared by the GPT (tied (V,H) embedding) and Llama (untied (H,V)
    lm_head, ``transpose_w=True``) families, with the same
    ignore_index=-100 semantics as ``causal_lm_loss``."""
    from ..core.dispatch import run_op
    from ..ops.fused_ce import blockwise_linear_cross_entropy
    b, s, d = h.shape
    vocab = w.shape[0] if not transpose_w else w.shape[1]
    nb = _auto_num_blocks(b * s, vocab)

    def fn(hh, ww, yy):
        if transpose_w:
            ww = ww.T
        return blockwise_linear_cross_entropy(
            hh.reshape(b * s, d), ww, yy.reshape(b * s), num_blocks=nb,
            ignore_index=-100)
    return run_op("fused_lm_ce", fn, (h, w, labels))


def apply_rotary_pos_emb(q_arr, k_arr, cos, sin):
    """Rotate-half RoPE on [B, S, H, D] arrays (parity:
    fused_rotary_position_embedding semantics). Rotated in the tables'
    dtype (float32), each of q and k returned in the dtype it came in:
    a bfloat16 model's activations stay bfloat16 past the rotation."""
    def rot(x):
        x1, x2 = x[..., ::2], x[..., 1::2]
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)
    return rot(q_arr), rot(k_arr)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_heads
        from ..nn.initializer import Normal
        init = nn.ParamAttr(initializer=Normal(0.0, 0.02))
        self.q_proj = nn.Linear(cfg.hidden_size,
                                cfg.num_heads * self.head_dim,
                                weight_attr=init, bias_attr=False)
        self.k_proj = nn.Linear(cfg.hidden_size,
                                cfg.num_kv_heads * self.head_dim,
                                weight_attr=init, bias_attr=False)
        self.v_proj = nn.Linear(cfg.hidden_size,
                                cfg.num_kv_heads * self.head_dim,
                                weight_attr=init, bias_attr=False)
        self.o_proj = nn.Linear(cfg.num_heads * self.head_dim,
                                cfg.hidden_size,
                                weight_attr=init, bias_attr=False)

    def forward(self, h, cos_sin):
        b, s, _ = h.shape
        cfg = self.cfg
        q = self.q_proj(h).reshape([b, s, cfg.num_heads, self.head_dim])
        k = self.k_proj(h).reshape([b, s, cfg.num_kv_heads, self.head_dim])
        v = self.v_proj(h).reshape([b, s, cfg.num_kv_heads, self.head_dim])
        cos, sin = cos_sin
        qk = run_op("fused_rope",
                    lambda qa, ka: apply_rotary_pos_emb(qa, ka, cos[:s], sin[:s]),
                    (q, k))
        q, k = qk
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             dropout_p=cfg.dropout,
                                             training=self.training)
        return self.o_proj(out.reshape([b, s, cfg.num_heads * self.head_dim]))


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        from ..nn.initializer import Normal
        init = nn.ParamAttr(initializer=Normal(0.0, 0.02))
        self.gate_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                   weight_attr=init, bias_attr=False)
        self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                 weight_attr=init, bias_attr=False)
        self.down_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                   weight_attr=init, bias_attr=False)

    def forward(self, h):
        return self.down_proj(F.silu(self.gate_proj(h)) * self.up_proj(h))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, h, cos_sin):
        h = h + self.self_attn(self.input_layernorm(h), cos_sin)
        h = h + self.mlp(self.post_attention_layernorm(h))
        return h


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn.initializer import Normal
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self._cos_sin = _rope_tables(cfg.max_position_embeddings,
                                     cfg.hidden_size // cfg.num_heads,
                                     cfg.rope_theta)

    def forward(self, input_ids):
        if input_ids.shape[1] > self.cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {input_ids.shape[1]} exceeds "
                f"max_position_embeddings={self.cfg.max_position_embeddings}")
        h = self.embed_tokens(input_ids)
        from ..distributed.fleet.recompute import recompute
        for layer in self.layers:
            if self.cfg.use_recompute and self.training:
                h = recompute(layer, h, self._cos_sin,
                              policy=self.cfg.recompute_policy)
            else:
                h = layer(h, self._cos_sin)
        return self.norm(h)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        from ..nn.initializer import Normal
        self.lm_head = nn.Linear(
            cfg.hidden_size, cfg.vocab_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)),
            bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, input_ids, labels):
        if self.cfg.lm_ce == "blockwise":
            return blockwise_lm_loss(self.model(input_ids),
                                     self.lm_head.weight, labels,
                                     transpose_w=True)
        return causal_lm_loss(self(input_ids), labels)

    # -- autoregressive decode (use_cache path) ---------------------------
    def decode_meta(self) -> dict:
        """Cache geometry for the serving decode engine. Llama caches
        ``num_kv_heads`` heads (GQA: the pool stays small, queries repeat
        heads at attention time)."""
        cfg = self.cfg
        return {"num_layers": cfg.num_layers,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.hidden_size // cfg.num_heads,
                "max_len": cfg.max_position_embeddings,
                "vocab_size": cfg.vocab_size}

    def init_decode_cache(self, batch: int, max_len: int = None):
        """Contiguous per-layer (k, v) caches for ``decode_step``."""
        from .decode import init_contiguous_cache
        m = self.decode_meta()
        return init_contiguous_cache(
            m["num_layers"], batch, max_len or m["max_len"],
            m["num_kv_heads"], m["head_dim"])

    def decode_step(self, tokens, positions, kv_caches, kv_ops=None):
        """One cached decode (or prefill) step — same contract as
        ``GPTForCausalLM.decode_step`` (see models/decode.py for the
        kv_ops protocol). RoPE is applied at each slot's absolute
        positions; only ``num_kv_heads`` K/V heads are cached and the
        GQA head expansion happens inside ``decode_attention``."""
        from ..core.tensor import Tensor
        from .decode import (ContiguousKV, apply_rope_at, decode_attention,
                             unwrap_array)
        kv_ops = kv_ops or ContiguousKV()
        tok = unwrap_array(tokens)
        if tok.ndim == 1:
            tok = tok[:, None]
        pos = unwrap_array(positions).astype(jnp.int32)
        b, s = tok.shape
        cfg, m = self.cfg, self.model
        cos, sin = m._cos_sin
        head_dim = cfg.hidden_size // cfg.num_heads
        h = m.embed_tokens(Tensor(tok))
        new_caches = []
        for i, layer in enumerate(m.layers):
            a = layer.self_attn
            hn = layer.input_layernorm(h)
            q = a.q_proj(hn).reshape([b, s, cfg.num_heads, head_dim])
            k = a.k_proj(hn).reshape([b, s, cfg.num_kv_heads, head_dim])
            v = a.v_proj(hn).reshape([b, s, cfg.num_kv_heads, head_dim])
            q, k = apply_rope_at(q, k, cos, sin, pos)
            k_all, v_all, cache = kv_ops.update(i, kv_caches[i], k, v, pos)
            o = decode_attention(q, k_all, v_all, pos)
            h = h + a.o_proj(o.reshape([b, s, cfg.num_heads * head_dim]))
            h = h + layer.mlp(layer.post_attention_layernorm(h))
            new_caches.append(cache)
        return self.lm_head(m.norm(h)), new_caches


class _LlamaEmbedPipe(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn.initializer import Normal
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))

    def forward(self, input_ids):
        if input_ids.shape[1] > self.cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {input_ids.shape[1]} exceeds "
                f"max_position_embeddings="
                f"{self.cfg.max_position_embeddings}")
        return self.embed_tokens(input_ids)


class LlamaDecoderLayerPipe(LlamaDecoderLayer):
    """Single-tensor-signature decoder layer for PipelineLayer: the RoPE
    tables are position-only, so each stage recomputes them locally instead
    of shipping them across the stage boundary."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__(cfg)
        self.cfg = cfg
        self._cos_sin = _rope_tables(cfg.max_position_embeddings,
                                     cfg.hidden_size // cfg.num_heads,
                                     cfg.rope_theta)

    def forward(self, h):
        if self.cfg.use_recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            return recompute(super().forward, h, self._cos_sin,
                             policy=self.cfg.recompute_policy)
        return super().forward(h, self._cos_sin)


class _LlamaHeadPipe(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = nn.Linear(
            cfg.hidden_size, cfg.vocab_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)),
            bias_attr=False)

    def forward(self, h):
        return self.lm_head(self.norm(h))


def llama_pipeline_model(cfg: LlamaConfig, num_stages: int, loss_fn=None,
                         **pipeline_kwargs):
    """Llama-for-causal-LM as a PipelineLayer (untied head, so a plain
    LayerDesc chain: embed | decoder x N | norm+head). Same parameterization
    as LlamaForCausalLM so trial throughputs are comparable across pp and
    non-pp candidates (reference analog: the gpt PipelineLayer variant in
    the hybrid-parallel tests)."""
    from ..distributed.fleet.meta_parallel.parallel_layers import (
        LayerDesc, PipelineLayer)

    if loss_fn is None:
        loss_fn = causal_lm_loss

    descs = [LayerDesc(_LlamaEmbedPipe, cfg)]
    descs += [LayerDesc(LlamaDecoderLayerPipe, cfg)
              for _ in range(cfg.num_layers)]
    descs.append(LayerDesc(_LlamaHeadPipe, cfg))
    return PipelineLayer(descs, num_stages=num_stages, loss_fn=loss_fn,
                         seg_method="layer:LlamaDecoderLayerPipe",
                         **pipeline_kwargs)


def _llama_param_role(name: str) -> str:
    """Megatron role of a parameter: 'rows' (leading dim over tp),
    'cols' (trailing dim over tp), or 'replicated'."""
    if "embed_tokens.weight" in name:
        return "rows"                 # vocab-parallel embedding
    if "lm_head.weight" in name:
        return "cols"
    if any(k in name for k in ("q_proj.weight", "k_proj.weight",
                               "v_proj.weight", "gate_proj.weight",
                               "up_proj.weight")):
        return "cols"
    if any(k in name for k in ("o_proj.weight", "down_proj.weight")):
        return "rows"
    return "replicated"


def llama_param_spec(name: str, P=None):
    """Megatron TP placement by parameter role over axes ('dp', 'tp')
    (SURVEY.md §2.7; the reference encodes the same mapping in its
    ColumnParallelLinear/RowParallelLinear wiring), routed through the
    canonical SpecLayout vocabulary. ``P`` injects a spec constructor
    for jax-free callers (the completer tests)."""
    role = _llama_param_role(name)
    if P is not None:
        return {"rows": P("tp", None), "cols": P(None, "tp"),
                "replicated": P()}[role]
    from ..distributed.spec_layout import default_layout
    layout = default_layout()
    return {"rows": layout.tp_rows(), "cols": layout.tp_cols(),
            "replicated": layout.replicated()}[role]


def llama_fsdp_spec(name: str, shape, n_dp: int):
    """ZeRO-3/FSDP overlay: additionally shard dim 0 over the FSDP axis
    (= the data axis, see SpecLayout) when even — applied on top of the
    TP spec when that dim is free."""
    from jax.sharding import PartitionSpec

    from ..distributed.spec_layout import default_layout
    layout = default_layout()
    tp = llama_param_spec(name)
    entries = list(tp) + [None] * (len(shape) - len(tp))
    for d in range(len(shape)):
        if entries[d] is None and shape[d] % n_dp == 0:
            entries[d] = layout.fsdp_axis
            break
    return PartitionSpec(*entries)
