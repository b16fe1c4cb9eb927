"""Nemotron-H family (NVIDIA's Nemotron-3 Nano 30B-A3B, ``model_type``
"nemotron_h"): a decoder whose every layer is ONE pre-norm residual
sublayer, ``h + mixer(RMSNorm(h))``, the mixer picked per layer by the
characters of ``hybrid_override_pattern``:

- ``M``: Mamba-2 (``nn.Mamba2Mixer``: ``mamba_num_heads`` heads of
  ``mamba_head_dim`` over ``n_groups`` groups of B and C, state
  ``ssm_state_size``, a causal convolution of ``conv_kernel`` with a bias,
  the SSD scan chunked at ``chunk_size``, a gated grouped RMSNorm);
- ``E``: experts (``incubate.moe.DroplessMoE``, ``activation="relu2"``:
  NON-gated ``down(relu(up x)^2)`` experts of ``moe_intermediate_size``;
  sigmoid scores, the top ``num_experts_per_tok`` chosen by ``score +
  e_score_correction_bias`` and weighted by their scores over their sum
  times ``routed_scaling_factor``); one shared relu^2 expert of
  ``moe_shared_expert_intermediate_size`` beside them;
- ``*``: causal GQA, ``num_attention_heads`` over ``num_key_value_heads``
  of ``head_dim``, no bias, NO rotary embedding (the public modeling code
  builds none: ``rope_theta`` and ``partial_rotary_factor`` are read by
  nothing), scale ``1 / sqrt(head_dim)``.

A final RMSNorm and an untied head. Each Mamba-2 layer's ``A_log`` and
``dt_bias`` start at zero with a fixed per-head offset (``ssm_decay_init``),
so that the heads start from the decays of Mamba-2's published
initialisation.

RMSNorm, the blockwise LM loss and ``Linear`` are the other families';
``RoutingStats`` and the router's float32 input ``models/laguna.py``'s.
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
from .laguna import RoutingStats, _linear, _rms_norm_f32
from .llama import blockwise_lm_loss, causal_lm_loss

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "NemotronHAttention",
           "Relu2MLP", "ssm_decay_init", "nemotron_h_tiny"]

MAMBA, MOE, ATTENTION = "M", "E", "*"


def ssm_decay_init(heads: int):
    """(A0, dt0) of each head, float64: A0 spread over [1, 16], dt0
    log-spaced over [1e-3, 1e-1] (Mamba-2's published ``time_step_min`` and
    ``time_step_max``) and held at ``time_step_floor`` 1e-4 from below, both
    rising with a rank that interleaves the heads (even heads take the lower
    half of the ranks, odd heads the upper), so that neighbouring heads
    remember over very different spans: the decay ``exp(-A0 dt0)`` at a zero
    input runs from 0.999 to 0.2."""
    h = np.arange(heads)
    rank = (h % 2) * ((heads + 1) // 2) + h // 2
    top = max(heads - 1, 1)
    a0 = 1.0 + 15.0 * rank / top
    dt0 = np.maximum(1e-3 * 100.0 ** (rank / top), 1e-4)
    return a0, dt0


@dataclass
class NemotronHConfig:
    """Defaults are Nemotron-3 Nano 30B-A3B's published values.
    ``experts_held`` is the chip's share ``(first, count)`` of
    ``n_routed_experts`` (None: all); ``vocab_size`` may be the chip's slice
    and ``hybrid_override_pattern`` the layers held. ``ssm_decay_init`` =
    (A0, dt0) per Mamba-2 head; None takes ``ssm_decay_init()``."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    ssm_decay_init: Optional[Tuple[tuple, tuple]] = None
    n_routed_experts: int = 128
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    use_recompute: bool = False
    # the forward kernels are the dearest things in a block to replay: the
    # attention layer's flash call and the Mamba-2 layers' SSD scan keep
    # their outputs (and the flash statistics, the scan's chunk states) for
    # the backward pass (fleet.recompute's policies; "full" replays them)
    recompute_policy: str = "flash_saveable"
    lm_ce: str = "blockwise"
    layer_types: Tuple[str, ...] = field(init=False)

    def __post_init__(self):
        self.layer_types = tuple(self.hybrid_override_pattern)
        if set(self.layer_types) - {MAMBA, MOE, ATTENTION}:
            raise ValueError(f"hybrid_override_pattern "
                             f"{self.hybrid_override_pattern!r}: M, E, * only")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of kv heads")
        if self.ssm_decay_init is None:
            a0, dt0 = ssm_decay_init(self.mamba_num_heads)
            self.ssm_decay_init = (tuple(a0), tuple(dt0))

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_published(cls, v: dict, **overrides):
        """From the keys of a published ``config.json`` (and, where a
        configuration's file gives it, ``ssm_decay_init`` {A, dt});
        ``overrides`` are fields of this class. Keys that would change an
        equation written here are refused, not ignored."""
        refused = {
            "num_hidden_layers": v["num_hidden_layers"]
            != len(v["hybrid_override_pattern"]),
            "mamba_hidden_act": v.get("mamba_hidden_act", "silu") != "silu",
            "mlp_hidden_act": v.get("mlp_hidden_act", "relu2") != "relu2",
            "attention_bias": bool(v.get("attention_bias")),
            "mlp_bias": bool(v.get("mlp_bias")),
            "use_bias": bool(v.get("use_bias")),
            "mamba_proj_bias": bool(v.get("mamba_proj_bias")),
            "use_conv_bias": not v.get("use_conv_bias", True),
            "tie_word_embeddings": bool(v.get("tie_word_embeddings")),
            "n_group": v.get("n_group", 1) != 1,
            "topk_group": v.get("topk_group", 1) != 1,
            "n_shared_experts": v.get("n_shared_experts", 1) != 1,
            "norm_topk_prob": not v.get("norm_topk_prob", True),
            "residual_in_fp32": bool(v.get("residual_in_fp32")),
            "sliding_window": v.get("sliding_window") is not None,
            "norm_eps": v.get("norm_eps", v["layer_norm_epsilon"])
            != v["layer_norm_epsilon"],
        }
        bad = sorted(k for k, is_bad in refused.items() if is_bad)
        if bad:
            raise ValueError(f"models/nemotron_h.py does not compute these "
                             f"as the config states them: {bad}")
        kw = {k: v[k] for k in (
            "vocab_size", "hidden_size", "hybrid_override_pattern",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "routed_scaling_factor",
            "layer_norm_epsilon")}
        init = v.get("ssm_decay_init")
        if init is not None:
            kw["ssm_decay_init"] = (tuple(init["A"]), tuple(init["dt"]))
        kw.update(overrides)
        return cls(**kw)


def nemotron_h_tiny(**overrides):
    """The published structure at toy sizes, for tests: the pattern's first
    attention period ``MEMEM*EME``, 8 Mamba-2 heads of 8 over 2 groups of
    state 16, 4 query heads over 2 kv heads of 16, 16 relu^2 experts top-4
    and a shared one."""
    kw = dict(
        vocab_size=96, hidden_size=48, hybrid_override_pattern="MEMEM*EME",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=32, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
        lm_ce="plain")
    kw.update(overrides)
    return NemotronHConfig(**kw)


class NemotronHAttention(nn.Layer):
    """Causal GQA with no rotary embedding and no bias (the module docstring
    has it)."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        self.heads, self.kv_heads, self.head_dim = \
            cfg.num_attention_heads, cfg.num_key_value_heads, d
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.o_proj = _linear(self.heads * d, h)

    def forward(self, u):
        b, s, _ = u.shape
        d = self.head_dim
        q = self.q_proj(u).reshape([b, s, self.heads, d])
        k = self.k_proj(u).reshape([b, s, self.kv_heads, d])
        v = self.v_proj(u).reshape([b, s, self.kv_heads, d])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(out.reshape([b, s, self.heads * d]))


class Relu2MLP(nn.Layer):
    """``down(relu(up x)^2)``, no gate, no bias: the shared expert."""

    def __init__(self, hidden: int, intermediate: int):
        super().__init__()
        self.up_proj = _linear(hidden, intermediate)
        self.down_proj = _linear(intermediate, hidden)

    def forward(self, x):
        return self.down_proj(run_op(
            "relu2", lambda a: jnp.square(jax.nn.relu(
                a.astype(jnp.float32))).astype(a.dtype), (self.up_proj(x),)))


class NemotronHBlock(nn.Layer):
    """The one layer body: norm, the mixer the pattern picks, residual."""

    def __init__(self, cfg: NemotronHConfig, index: int):
        super().__init__()
        from ..incubate.moe import DroplessMoE
        h, eps = cfg.hidden_size, cfg.layer_norm_epsilon
        self.eps = eps
        self.kind = cfg.layer_types[index]
        self.norm = nn.RMSNorm(h, eps)
        if self.kind == MAMBA:
            a0, dt0 = (np.asarray(x, np.float64) for x in cfg.ssm_decay_init)
            self.mixer = nn.Mamba2Mixer(
                h, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size, eps,
                A_offset=np.log(a0), dt_offset=np.log(np.expm1(dt0)))
        elif self.kind == MOE:
            self.mixer = DroplessMoE(
                h, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                shared=Relu2MLP(h, cfg.moe_shared_expert_intermediate_size),
                routed_scale=cfg.routed_scaling_factor, score_bias=True,
                activation="relu2")
        else:
            self.mixer = NemotronHAttention(cfg)

    def forward(self, h):
        u = self.norm(h)
        if self.kind == MOE:
            return h + self.mixer(u, router_input=_rms_norm_f32(
                h, self.norm.weight, self.eps))
        return h + self.mixer(u)


class NemotronHModel(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        from ..nn.initializer import Normal
        self.cfg = cfg
        self.embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [NemotronHBlock(cfg, i) for i in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)

    def forward(self, input_ids, recompute_layers=None):
        cfg = self.cfg
        if recompute_layers is None:
            recompute_layers = cfg.use_recompute and self.training
        from ..distributed.fleet.recompute import recompute
        h = self.embeddings(input_ids)
        for layer in self.layers:
            h = recompute(layer, h, policy=cfg.recompute_policy) \
                if recompute_layers else layer(h)
        return self.norm_f(h)


class NemotronHForCausalLM(RoutingStats, nn.Layer):
    """Trains through ``create_train_step`` / ``run_steps`` as the other
    families do (``loss(ids, labels)``: token-mean cross entropy, no balance
    term). ``routing_stats`` reads the expert layers' loads off the step's
    path."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.model = NemotronHModel(cfg)
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
        return [(i, layer.mixer) for i, layer in enumerate(self.model.layers)
                if layer.kind == MOE]
