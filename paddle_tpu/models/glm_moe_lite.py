"""GLM MoE "lite" family (zai-org's GLM-4.7-Flash, ``model_type``
"glm4_moe_lite"): latent attention, bias-corrected sparse experts and a
multi-token-prediction module in the loss.

Every layer's mixer is **multi-head latent attention as training computes
it**, the expanded form (``MLAttention``): queries through a
``q_lora_rank`` bottleneck with an RMSNorm inside it; keys and values
through a ``kv_lora_rank`` latent with an RMSNorm, beside ONE rotary key of
``qk_rope_head_dim`` a token that every head shares. A head's query and key
are ``[nope; rope]`` (192 + 64 = 256 as published), its value ``v_head_dim``
(256), as many key heads as query heads. The rotary slices are rotated in
float32 over pairs (2i, 2i + 1) and stored back in the weights' dtype; q, k
and v reach ``F.scaled_dot_product_attention`` as [B, S, heads, 256], so the
flash kernels run at a head of 256. No decode path, cache shape or absorbed
form is here.

The first ``first_k_dense_replace`` layers carry the dense decoders' SwiGLU
(``LlamaMLP``), the rest ``incubate.moe.DroplessMoE`` with a shared expert
and ``score_bias=True``: the top k of ``sigmoid + bias`` are chosen and
weighted by their sigmoids alone, renormalised and scaled
(``topk_method`` "noaux_tc", ``n_group = topk_group = 1``). The bias is a
buffer: nothing here moves it.

``num_nextn_predict_layers`` multi-token-prediction modules (one, as
published) live in ``GlmMoeLiteForCausalLM.loss``: position t's
``[RMSNorm(Emb(id_{t+1})); RMSNorm(h_t)]`` through a ``2 hidden -> hidden``
projection, one sparse layer of its own, an RMSNorm, then the main model's
OWN head predicts ``id_{t+2}``; ``Emb`` is the main model's own table and
``h_t`` its output after the final norm. ``loss = CE(main) + mtp_loss_weight
x CE(mtp)``, each a mean over the positions that have a target.

RMSNorm, the SwiGLU MLP, the rotary application and the blockwise LM loss are
``models/llama.py``'s; the rotary tables and the router's float32 norm are
``models/laguna.py``'s.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

from .. import nn
from ..core.dispatch import run_op
from ..nn import functional as F
from .laguna import (RoutingStats, _linear, _rms_norm_f32,
                     laguna_rope_tables)
from .llama import (LlamaConfig, LlamaMLP, apply_rotary_pos_emb,
                    blockwise_lm_loss, causal_lm_loss)

__all__ = ["GlmMoeLiteConfig", "GlmMoeLiteForCausalLM", "MLAttention",
           "MLA_PLAN_TALLY", "glm_moe_lite_tiny"]

# one count per lowered attention layer, by (heads, nope, rope, value dims,
# q rank, kv rank, tokens, route): trace time only, nothing a step
MLA_PLAN_TALLY: collections.Counter = collections.Counter()
IGNORE = -100


@dataclass
class GlmMoeLiteConfig:
    """Defaults are GLM-4.7-Flash's published values. ``experts_held`` is
    the chip's share ``(first, count)`` of ``n_routed_experts`` (None: all of
    them); ``vocab_size`` may likewise be the chip's slice.
    ``mtp_loss_weight`` is not in ``config.json`` (0.3: what the DeepSeek-V3
    and GLM-4.5 reports give for most of pre-training)."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 202752
    use_recompute: bool = False
    # the published contexts start at 8k, where the flash forward kernel is
    # the dearest thing in a block to replay: keep its output and statistics
    # (fleet.recompute's policies; "full" replays the kernel too)
    recompute_policy: str = "flash_saveable"
    lm_ce: str = "blockwise"

    def __post_init__(self):
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.v_head_dim != qk:
            raise ValueError(
                f"v_head_dim {self.v_head_dim} is not qk_nope_head_dim + "
                f"qk_rope_head_dim = {qk}: the attention call takes one head "
                "size for q, k and v, and nothing here pads the values")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim is rotated in pairs")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module or none")

    @classmethod
    def from_published(cls, v: dict, **overrides):
        """From the keys of a published ``config.json``; ``overrides`` are
        fields of this class (``experts_held``, ``vocab_size`` for a chip's
        share, ``use_recompute``, ``mtp_loss_weight``). Keys that would
        change an equation written here are refused, not ignored."""
        refused = {
            "num_key_value_heads": v.get("num_key_value_heads",
                                         v["num_attention_heads"])
            != v["num_attention_heads"],
            "n_group / topk_group": (v.get("n_group", 1),
                                     v.get("topk_group", 1)) != (1, 1),
            "rope_scaling": v.get("rope_scaling") is not None,
            "norm_topk_prob": not v.get("norm_topk_prob", True),
            "topk_method": v.get("topk_method", "noaux_tc") != "noaux_tc",
            "attention_bias": bool(v.get("attention_bias")),
            "tie_word_embeddings": bool(v.get("tie_word_embeddings")),
            "partial_rotary_factor": v.get("partial_rotary_factor", 1) != 1,
        }
        bad = sorted(k for k, is_bad in refused.items() if is_bad)
        if bad:
            raise ValueError(f"models/glm_moe_lite.py does not compute "
                             f"these as the config states them: {bad}")
        kw = {k: v[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts",
            "routed_scaling_factor", "first_k_dense_replace",
            "num_nextn_predict_layers", "rope_theta", "rms_norm_eps",
            "max_position_embeddings")}
        kw.update(overrides)
        return cls(**kw)


def glm_moe_lite_tiny(**overrides):
    """The published structure at toy sizes, for tests: a dense first layer
    and two sparse ones, nope : rope : value dims 3 : 1 : 4, both ranks
    unequal to hidden, top-4 of 16 experts, one MTP module."""
    kw = dict(
        vocab_size=96, hidden_size=48, intermediate_size=80,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=24, max_position_embeddings=256,
        lm_ce="plain")
    kw.update(overrides)
    return GlmMoeLiteConfig(**kw)


class MLAttention(nn.Layer):
    """Causal multi-head latent attention in the expanded form (the module
    docstring has the equations). Leaves carry the published names."""

    def __init__(self, cfg: GlmMoeLiteConfig):
        super().__init__()
        self.cfg = cfg
        h, heads, eps = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.rms_norm_eps
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _linear(h, cfg.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank, eps)
        self.q_b_proj = _linear(cfg.q_lora_rank, heads * qk)
        self.kv_a_proj_with_mqa = _linear(
            h, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank, eps)
        self.kv_b_proj = _linear(
            cfg.kv_lora_rank, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _linear(heads * cfg.v_head_dim, h)

    def _plan(self, q, k):
        """The ``mla::plan`` event and its tally, once for each lowered
        attention layer (trace time): the sizes, the route this layer's
        call gets with the rule that decided, and the tiles of a kernel
        route."""
        from ..ops.pallas.flash_attention import route_here, tile_plan
        from ..profiler.tracing import trace_event
        cfg = self.cfg
        b, s, heads, d = q.shape
        route, _ = route_here(q, k, causal=True)
        tiles = ""
        if route.impl == "kernel":
            size = jnp.dtype(q.dtype).itemsize
            tiles = " ".join(
                f"{name}={t.bq}x{t.bk}/{t.sub_q}x{t.sub_k}" for name, t in
                tile_plan(s, s, d, size, size, size).lowered().items())
        MLA_PLAN_TALLY[(heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank,
                        b * s, route.impl)] += 1
        trace_event(
            "mla::plan", cat="kernel", heads=heads,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, q_lora_rank=cfg.q_lora_rank,
            kv_lora_rank=cfg.kv_lora_rank, tokens=b * s, route=route.impl,
            rule=route.rule, tiles=tiles)

    def forward(self, u, cos_sin):
        cfg = self.cfg
        b, s, _ = u.shape
        heads, dn, dr = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
            cfg.qk_rope_head_dim
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u))).reshape(
            [b, s, heads, dn + dr])
        rank = cfg.kv_lora_rank
        kva = self.kv_a_proj_with_mqa(u)
        kv = self.kv_b_proj(self.kv_a_layernorm(kva[..., :rank])).reshape(
            [b, s, heads, dn + cfg.v_head_dim])
        cos, sin = cos_sin

        def heads_of(qa, kva, kvb):
            """q [B,S,H,nope+rope], [latent; the one rotary key]
            [B,S,rank+rope], and [k_nope; v] [B,S,H,nope+v] -> q, k, v
            [B,S,H,256]: the rotary slices rotated in float32 and stored
            back, the one rotary key given to every head."""
            q_rot, k_rot = apply_rotary_pos_emb(
                qa[..., dn:], kva[:, :, None, rank:], cos[:s], sin[:s])
            return (jnp.concatenate([qa[..., :dn], q_rot], axis=-1),
                    jnp.concatenate(
                        [kvb[..., :dn],
                         jnp.broadcast_to(k_rot, (b, s, heads, dr))],
                        axis=-1),
                    kvb[..., dn:])
        q, k, v = run_op("fused_rope", heads_of, (q, kva, kv))
        self._plan(q, k)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(out.reshape([b, s, heads * cfg.v_head_dim]))


class GlmMoeLiteDecoderLayer(nn.Layer):
    """Pre-norm latent attention and a pre-norm FFN around the residual
    stream: the SwiGLU MLP, or (``sparse``) the experts with their shared
    expert and selection bias."""

    def __init__(self, cfg: GlmMoeLiteConfig, sparse: bool):
        super().__init__()
        from ..incubate.moe import DroplessMoE
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.eps, self.sparse = eps, sparse
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = MLAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)

        def swiglu(width):
            return LlamaMLP(LlamaConfig(hidden_size=h,
                                        intermediate_size=width))
        if sparse:
            self.mlp = DroplessMoE(
                h, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                shared=swiglu(cfg.moe_intermediate_size
                              * cfg.n_shared_experts),
                routed_scale=cfg.routed_scaling_factor, score_bias=True)
        else:
            self.mlp = swiglu(cfg.intermediate_size)

    def forward(self, h, cos_sin):
        h = h + self.self_attn(self.input_layernorm(h), cos_sin)
        t = self.post_attention_layernorm(h)
        if self.sparse:
            return h + self.mlp(t, router_input=_rms_norm_f32(
                h, self.post_attention_layernorm.weight, self.eps))
        return h + self.mlp(t)


def _run_layer(layer, h, cos_sin, cfg, recompute_layers):
    if recompute_layers:
        from ..distributed.fleet.recompute import recompute
        return recompute(layer, h, cos_sin, policy=cfg.recompute_policy)
    return layer(h, cos_sin)


class GlmMoeLiteModel(nn.Layer):
    def __init__(self, cfg: GlmMoeLiteConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [GlmMoeLiteDecoderLayer(cfg, i >= cfg.first_k_dense_replace)
             for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def rope_tables(self, seq_len: int):
        cfg = self.cfg
        if seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        return laguna_rope_tables(seq_len, cfg.qk_rope_head_dim,
                                  {"rope_theta": cfg.rope_theta})

    def recomputes(self, recompute_layers=None) -> bool:
        if recompute_layers is None:
            return self.cfg.use_recompute and self.training
        return recompute_layers

    def forward(self, input_ids, recompute_layers=None):
        tables = self.rope_tables(input_ids.shape[1])
        rec = self.recomputes(recompute_layers)
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = _run_layer(layer, h, tables, self.cfg, rec)
        return self.norm(h)


class GlmMtpModule(nn.Layer):
    """One multi-token-prediction module: its norms, its projection, one
    sparse layer and the norm before the (main model's) head."""

    def __init__(self, cfg: GlmMoeLiteConfig):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.enorm = nn.RMSNorm(h, eps)
        self.hnorm = nn.RMSNorm(h, eps)
        self.eh_proj = _linear(2 * h, h)
        self.block = GlmMoeLiteDecoderLayer(cfg, sparse=True)
        self.norm = nn.RMSNorm(h, eps)

    def forward(self, emb_next, h, cos_sin, cfg, recompute_layers):
        from .. import concat
        z = self.eh_proj(concat([self.enorm(emb_next), self.hnorm(h)],
                                axis=-1))
        return self.norm(_run_layer(self.block, z, cos_sin, cfg,
                                    recompute_layers))


class GlmMoeLiteForCausalLM(RoutingStats, nn.Layer):
    """Trains through ``create_train_step`` / ``run_steps`` as the other
    families do: ``loss(ids, labels)`` (``labels[t] = id_{t+1}``) is the one
    scalar the step differentiates, the main cross entropy plus
    ``mtp_loss_weight`` times the MTP module's. ``routing_stats`` reads the
    expert layers' loads off the step's path, the MTP module's layer last."""

    def __init__(self, cfg: GlmMoeLiteConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GlmMoeLiteModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)
        self.mtp = GlmMtpModule(cfg) if cfg.num_nextn_predict_layers \
            else None
        self._routing_jit = None

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def mtp_hidden(self, h, next_ids, recompute_layers=None):
        """The MTP module's output before the head, [B, S, hidden], from the
        main model's normed output ``h`` and ``next_ids[t] = id_{t+1}``."""
        return self.mtp(self.model.embed_tokens(next_ids), h,
                        self.model.rope_tables(next_ids.shape[1]), self.cfg,
                        self.model.recomputes(recompute_layers))

    def forward_mtp(self, input_ids, next_ids):
        """(main logits, MTP logits): position t of the first predicts
        ``id_{t+1}``, of the second ``id_{t+2}``, through the same head."""
        h = self.model(input_ids)
        return self.lm_head(h), self.lm_head(self.mtp_hidden(h, next_ids))

    def _mtp_targets(self, labels):
        """(ids the module embeds, its targets): ``labels[t]`` and
        ``labels[t + 1]``; the last position, and any whose own label is
        ignored, has no target."""
        def fn(y):
            nxt = jnp.concatenate(
                [y[:, 1:], jnp.full_like(y[:, :1], IGNORE)], axis=1)
            return jnp.maximum(y, 0), jnp.where(y < 0, IGNORE, nxt)
        return run_op("mtp_targets", fn, (labels,), num_nondiff_outputs=2)

    def _mtp_plan(self, labels):
        from ..profiler.tracing import trace_event
        b, s = labels.shape
        trace_event(
            "mtp::plan", cat="model", depth=self.cfg.num_nextn_predict_layers,
            weight=self.cfg.mtp_loss_weight, positions=b * s,
            positions_with_target=b * (s - 1),
            # this class hands the module no table and no head of its own:
            # it embeds through ``model.embed_tokens`` and predicts through
            # ``lm_head``, whose gradients are the sums of both uses
            own_table=True, own_head=True)

    def _ce(self, h, labels):
        if self.cfg.lm_ce == "blockwise":
            return blockwise_lm_loss(h, self.lm_head.weight, labels,
                                     transpose_w=True)
        return causal_lm_loss(self.lm_head(h), labels)

    def loss(self, input_ids, labels):
        h = self.model(input_ids)
        main = self._ce(h, labels)
        if self.mtp is None:
            return main
        self._mtp_plan(labels)
        next_ids, targets = self._mtp_targets(labels)
        return main + self.cfg.mtp_loss_weight * self._ce(
            self.mtp_hidden(h, next_ids), targets)

    # -- RoutingStats ---------------------------------------------------------
    def _route_pass(self, ids):
        """One forward pass over every expert layer, nothing recomputed:
        the MTP module is fed the ids' own successors (the last position a
        zero, which has no target anyway)."""
        h = self.model(ids, recompute_layers=False)
        if self.mtp is not None:
            nxt = run_op("shift", lambda a: jnp.concatenate(
                [a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1), (ids,),
                num_nondiff_outputs=1)
            self.mtp_hidden(h, nxt, recompute_layers=False)

    def sparse_layers(self):
        out = [(i, layer.mlp) for i, layer in enumerate(self.model.layers)
               if layer.sparse]
        if self.mtp is not None:
            out.append((self.cfg.num_hidden_layers, self.mtp.block.mlp))
        return out
