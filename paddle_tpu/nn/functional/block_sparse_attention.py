"""The two token mixers of the sparse + linear hybrids (MiniCPM-SALA):
attention over key blocks each query chose itself, and linear attention with
a per-head decay. Both take and return [B, S, H, D] and are differentiable
on the tape and under ``jax.grad``; the kernels are
``ops/pallas/sparse_attention.py`` and ``ops/pallas/linear_attention.py``,
the XLA paths beside them serve hosts without a chip.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from ...core import flags as _flags
from ...core.dispatch import run_op
from ...ops.pallas import common as _common

__all__ = ["block_sparse_attention", "lightning_attention",
           "select_attention_blocks"]


def _kernels_here() -> bool:
    """Whether a call takes the Pallas kernels: on a TPU, or where the tests
    force interpret mode; ``use_pallas_kernels`` off sends every op to XLA."""
    return bool(_flags.get_flag("use_pallas_kernels")) and (
        _common.on_tpu() or bool(_flags.get_flag("pallas_force_interpret")))


def select_attention_blocks(query, key, config):
    """The blocks each query attends under ``config`` (a ``SparseConfig``):
    [B, Hkv, S, blocks] bool. No gradient."""
    from ...ops.pallas.sparse_attention import select_blocks
    return run_op("sparse_attention_select",
                  lambda q, k: select_blocks(q, k, config), (query, key),
                  num_nondiff_outputs=1)


def block_sparse_attention(query, key, value, config, training=True):
    """Causal attention of ``query`` [B, S, Hq, D] over ``key``/``value`` [B,
    S, Hkv, D] as MiniCPM4's ``sparse_config`` (``config``: a
    ``SparseConfig``) prescribes: up to ``dense_len`` plain causal softmax
    attention (``scaled_dot_product_attention``); beyond it each query
    attends the keys not after itself in the ``topk`` blocks it chose,
    one choice a kv group, the choice made here from the pooled keys and
    carrying no gradient. Scale ``1 / sqrt(D)``. Leaves a
    ``sparse_attn::plan`` trace event."""
    from ...distributed.fleet.recompute import keep
    from ...ops.pallas import sparse_attention as sa
    from ...profiler.tracing import trace_event
    from .flash_attention import scaled_dot_product_attention
    b, s, hq, d = query.shape
    hkv = key.shape[2]
    dense = s <= config.dense_len
    kernels = _kernels_here()
    tiles = sa.sparse_tile_plan(s, config.block_size) \
        if kernels and not dense else None
    path = "dense" if dense else ("kernel" if kernels else "xla")
    n_pool = 0 if dense else (s - config.kernel_size) \
        // config.kernel_stride + 1
    sa.SPARSE_PLAN_TALLY[(hq, hkv, s, path) + tuple(tiles or (0, 0))] += 1
    trace_event(
        "sparse_attn::plan", cat="kernel", heads=hq, kv_heads=hkv, seq=s,
        path=path, block=config.block_size, topk=config.topk,
        forced_blocks=config.init_blocks + config.local_blocks,
        pooled_keys=n_pool,
        mean_keys_per_query=sa.mean_attended_keys(s, config),
        tiles="x".join(str(t) for t in tiles) if tiles else "",
        # what a recomputed block may keep of this call, by name
        replay_keeps="flash_out,flash_lse" if dense
        else "sparse_choice,sparse_out,sparse_lse" if kernels
        else "sparse_choice")
    if dense:
        return scaled_dot_product_attention(query, key, value, is_causal=True,
                                            training=training)
    scale = 1.0 / math.sqrt(d)
    interpret = _common.pallas_interpret()

    def fn(q, k, v):
        chosen = keep(sa.select_blocks(q, k, config), "sparse_choice")
        if kernels:
            return sa.sparse_attention(q, k, v, chosen, scale,
                                       config.block_size, tiles, interpret)
        return sa.sparse_attention_xla(q, k, v, chosen, scale,
                                       config.block_size)
    return run_op("block_sparse_attention", fn, (query, key, value))


def lightning_attention(query, key, value, rates, scale=None):
    """Causal linear attention with a per-head decay: ``o_t = scale x sum_{s
    <= t} exp(-rates[h] (t - s)) (q_t . k_s) v_s`` for [B, S, H, D] inputs
    and ``rates`` [H] (no gradient), no normaliser; ``scale`` defaults to
    ``1 / sqrt(D)``. A float32 state [D, D] a head is carried over chunks of
    the sequence. Leaves a ``linear_attn::plan`` trace event."""
    from ...ops.pallas import linear_attention as la
    from ...profiler.tracing import trace_event
    b, s, h, d = query.shape
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    kernels = _kernels_here()
    chunk = la.linear_chunk_plan(s)
    host_rates = np.asarray(getattr(rates, "_data", rates), np.float64)
    path = "kernel" if kernels else "xla"
    la.LINEAR_PLAN_TALLY[(h, d, s, chunk, path)] += 1
    trace_event("linear_attn::plan", cat="kernel", heads=h, head_dim=d, seq=s,
                path=path, chunk=chunk, chunks=-(-s // chunk),
                smallest_decay=float(np.exp(-host_rates.max())),
                largest_decay=float(np.exp(-host_rates.min())))
    interpret = _common.pallas_interpret()
    r = jnp.asarray(host_rates, jnp.float32)

    def fn(q, k, v):
        if kernels:
            return la.linear_attention(q, k, v, r, scale, chunk, interpret)
        return la.linear_attention_xla(q, k, v, r, scale, chunk)
    return run_op("lightning_attention", fn, (query, key, value))
