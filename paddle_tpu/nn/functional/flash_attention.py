"""Attention functionals (parity: python/paddle/nn/functional/
flash_attention.py:146 flash_attention, :441 scaled_dot_product_attention).

The reference dynloads the flash-attn CUDA library
(paddle/phi/backends/dynload/flashattn.h, gpu/flash_attn_kernel.cu:91); here
the op name "flash_attention" dispatches through the registry: a Pallas
blockwise kernel (ops/pallas/flash_attention.py) on TPU, and an XLA
reference implementation everywhere (also the CPU-interpret fallback).
Layout follows the reference contract: q/k/v are [batch, seqlen, num_heads,
head_dim]; GQA (kv heads < q heads) is supported.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import run_op, select_impl, register_op_impl

__all__ = ["flash_attention", "scaled_dot_product_attention",
           "flash_attn_unpadded", "sdp_kernel"]

# The name attention carries on the device, whatever implements it (Pallas
# or XLA): entered INSIDE the function handed to ``run_op``, because the tape
# may trace that function later, outside any scope of the caller. A trace's
# reader finds attention by this name (benchmarks/metrics/
# attn_device_ms_per_step.py), so it is part of the yardstick.
ATTENTION_SCOPE = "attention"


def _visible(Sq, Sk, window):
    """(Sq, Sk) bool: at or below the (Sk - Sq)-offset diagonal and, with a
    ``window``, fewer than ``window`` keys behind it."""
    mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq - int(window))
    return mask


@register_op_impl("flash_attention", "xla")
def _attention_xla(q, k, v, bias, causal, scale, dropout_p, dropout_key,
                   window=None):
    """Reference XLA attention: [B, S, H, D] layout, fp32 softmax.
    ``window`` (causal only): key j is visible to query i when ``j <= i``
    and ``i - j < window``."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    if window is not None and not causal:
        raise ValueError("a window goes with causal attention")
    if Hk != Hq:  # GQA: repeat kv heads
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if bias is None and dropout_p == 0.0 \
            and jnp.issubdtype(q.dtype, jnp.floating) \
            and q.dtype == k.dtype == v.dtype:
        # MXU-native mixed precision: storage-dtype operands with f32
        # accumulation; XLA's autodiff of this form keeps the big bwd
        # matmuls at bf16 rate too (measured faster than a custom-vjp
        # that pins bf16 residuals — the saved S^2 probs cost more HBM
        # than the f32 cotangent saves)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                            preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(_visible(Sq, Sk, window), logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        logits = jnp.where(_visible(Sq, Sk, window), logits, -1e30)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        # deterministic (seed, position)-hashed mask shared with the Pallas
        # kernel (reference (seed, offset) contract, ops.yaml:978-989):
        # both impls drop the same positions for a given key
        from ...ops.pallas.flash_attention import (dropout_keep_mask,
                                                   seed_from_key)
        B, H, Sq2, Sk2 = probs.shape
        keep = dropout_keep_mask(seed_from_key(dropout_key), B * H, Sq2,
                                 Sk2, dropout_p).reshape(B, H, Sq2, Sk2)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)




def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None, window=None):
    """q/k/v: [batch, seq, heads, head_dim] (the reference's flash-attn
    contract, ops.yaml:978). Returns (out, softmax_lse_placeholder) like the
    reference returns (out, softmax, softmax_lse, seed_offset) — softmax is
    only returned when return_softmax (debug). ``window`` (None or a
    length, causal only): a query sees itself and the ``window - 1`` keys
    before it."""
    from ...core import random as _random
    scale = 1.0 / math.sqrt(query.shape[-1])
    dk = _random.default_generator.next_key() if (dropout > 0.0 and training) else None
    impl = select_impl("flash_attention")

    def fn(q, k, v):
        with jax.named_scope(ATTENTION_SCOPE):
            return impl(q, k, v, None, causal, scale,
                        dropout if training else 0.0, dk,
                        **_window_arg(window))
    out = run_op("flash_attention", fn, (query, key, value))
    return out, None


def _window_arg(window) -> dict:
    """The keyword a windowed call adds: an implementation registered
    without one keeps working for every call that has no window."""
    return {} if window is None else {"window": int(window)}


def _segments_from_cu(cu_seqlens, total):
    """cu_seqlens [n+1] -> per-position segment id [1, total] (positions
    past cu_seqlens[-1] get the one-past-the-end bucket: they only ever
    match each other, and their outputs are packing don't-cares)."""
    import jax.numpy as jnp

    cu = cu_seqlens
    cu = getattr(cu, "_data", cu)
    cu = jnp.asarray(cu, jnp.int32).reshape(-1)
    pos = jnp.arange(total, dtype=jnp.int32)
    return jnp.searchsorted(cu[1:], pos, side="right") \
        .astype(jnp.int32)[None, :]


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen flash attention over PACKED inputs (reference contract:
    flash_attn_unpadded, call site flash_attn_kernel.cu:199): q/k/v are
    [total_tokens, heads, head_dim] with ``cu_seqlens_*`` delimiting the
    sequences. TPU-native mechanism: per-position segment ids derived from
    cu_seqlens are masked IN-KERNEL (attention never crosses a sequence
    boundary; causal masking applies within each segment because packing
    keeps positions contiguous) — the segment-ids form of the reference's
    ragged batching, with no S^2 mask materialization."""
    import jax

    from ...core import flags as _flags
    from ...core import random as _random
    from ...ops.pallas.flash_attention import (flash_attention_ext,
                                               seed_from_key)

    import jax.numpy as jnp

    from ...core.dispatch import select_impl
    from ...ops.pallas.flash_attention import _attention_pallas

    del max_seqlen_q, max_seqlen_k, return_softmax  # static shapes own this
    rate = float(dropout) if training else 0.0
    dk = _random.default_generator.next_key() if rate > 0.0 else None

    total_q = query.shape[0]
    total_k = key.shape[0]
    seg_q = _segments_from_cu(cu_seqlens_q, total_q)
    seg_k = _segments_from_cu(cu_seqlens_k, total_k)

    on_tpu = jax.default_backend() == "tpu"
    # honor the registry/sdp_kernel selection exactly like the dense path
    use_kernel = (select_impl("flash_attention") is _attention_pallas
                  and (on_tpu or _flags.get_flag("pallas_force_interpret"))
                  and query.shape[-1] <= 256)

    def _visibility():
        """(Tq, Tk) bool mask: same segment, per-segment causal diagonal
        (k_local - Lk <= q_local - Lq when causal)."""
        def local_and_len(seg_row):
            pos = jnp.arange(seg_row.shape[0], dtype=jnp.int32)
            left = jnp.searchsorted(seg_row, seg_row, side="left")
            right = jnp.searchsorted(seg_row, seg_row, side="right")
            return (pos - left) - (right - left)   # local - L
        same = seg_q[0][:, None] == seg_k[0][None, :]
        if causal:
            qv = local_and_len(seg_q[0])
            kv = local_and_len(seg_k[0])
            same = same & (kv[None, :] <= qv[:, None])
        return same

    def fn(q, k, v):
        with jax.named_scope(ATTENTION_SCOPE):
            q4, k4, v4 = q[None], k[None], v[None]
            if use_kernel:
                seed = (seed_from_key(dk) if rate > 0.0
                        else jnp.zeros((1,), jnp.int32))
                out4 = flash_attention_ext(q4, k4, v4, None, seed, seg_q,
                                           seg_k, bool(causal), float(scale),
                                           rate, None, None, not on_tpu)
            else:
                vis = _visibility()
                bias = jnp.where(vis, 0.0, float("-inf"))[None, None]
                out4 = _attention_xla(q4, k4, v4, bias, False, float(scale),
                                      rate, dk)
                # a q row with no visible key softmaxes -inf into NaN: zero it
                # (the kernel path's l==0 handling) so packing don't-cares
                # never poison real gradients
                dead = ~jnp.any(vis, axis=-1)                  # (Tq,)
                out4 = jnp.where(dead[None, :, None, None], 0.0, out4)
            return out4[0]

    out = run_op("flash_attention", fn, (query, key, value))
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, window=None):
    """Parity: F.scaled_dot_product_attention (flash_attention.py:441) —
    [B, S, H, D] layout, optional additive mask. ``window`` (None or a
    length, with ``is_causal``): key j is visible to query i when
    ``j <= i`` and ``i - j < window``."""
    from ...core import random as _random
    scale = 1.0 / math.sqrt(query.shape[-1])
    dk = _random.default_generator.next_key() if (dropout_p > 0.0 and training) else None
    impl = select_impl("flash_attention")
    if attn_mask is not None:
        def fn(q, k, v, m):
            with jax.named_scope(ATTENTION_SCOPE):
                return impl(q, k, v, m, is_causal, scale,
                            dropout_p if training else 0.0, dk,
                            **_window_arg(window))
        return run_op("flash_attention", fn, (query, key, value, attn_mask))

    def fn(q, k, v):
        with jax.named_scope(ATTENTION_SCOPE):
            return impl(q, k, v, None, is_causal, scale,
                        dropout_p if training else 0.0, dk,
                        **_window_arg(window))
    return run_op("flash_attention", fn, (query, key, value))


class sdp_kernel:
    """Context manager parity shim for kernel selection flags."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        from ...core import flags as _flags
        self._want = enable_flash
        self._flags = _flags

    def __enter__(self):
        self._prev = self._flags.get_flag("use_pallas_kernels")
        self._flags.set_flags({"use_pallas_kernels": self._want})
        return self

    def __exit__(self, *exc):
        self._flags.set_flags({"use_pallas_kernels": self._prev})
        return False
