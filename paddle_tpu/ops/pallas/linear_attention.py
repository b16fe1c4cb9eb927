"""Causal linear attention with a per-head decay (Lightning Attention-2, Qin
et al. 2024), as Pallas TPU kernels with an XLA ``lax.scan`` path for hosts
without a chip.

``o_t = scale x sum_{s <= t} lambda_h^(t - s) (q_t . k_s) v_s``, no
normaliser, ``lambda_h = exp(-rate_h)``. Nothing of size S x S exists: the
sequence is walked in chunks of ``C`` with a state ``KV`` [D, D] float32
carried along, ``KV_(i+1) = lambda^C KV_i + (Lambda_k K_i)^T V_i`` and
``O_i = [(Q_i K_i^T) * D] V_i + Lambda_q Q_i KV_i`` with ``D_ts =
lambda^(t - s)`` for ``t >= s`` in the chunk, ``Lambda_q[r] = lambda^(r +
1)``, ``Lambda_k[s] = lambda^(C - 1 - s)``: every exponent is non-positive,
so nothing overflows whatever the decay. The backward pass is the same scan
reversed, carrying ``dKV_i = lambda^C dKV_(i+1) + (Lambda_q Q_i)^T dO_i``.

Kernels: ``linear_attn_fwd``, grid (batch x heads, chunks), the chunk axis
sequential, the state in VMEM scratch; it also writes each chunk's incoming
state (``C`` 256 at S 12288, head 128: 48 states of 64 KiB a head), which
``linear_attn_bwd``, the same grid walked from the last chunk to the first,
reads for dq while it carries ``dKV`` for dk and dv. State and accumulation
are float32; the products' operands ride the MXU in the storage dtype.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _Z, pallas_interpret

__all__ = ["linear_attention", "linear_attention_xla", "linear_chunk_plan",
           "LINEAR_PLAN_TALLY"]

_F0 = np.float32(0.0)
_KERNEL_NAMES = {"fwd": "linear_attn_fwd", "bwd": "linear_attn_bwd"}
# one count per lowered lightning mixer, by (heads, head dim, S, chunk, path)
LINEAR_PLAN_TALLY: collections.Counter = collections.Counter()


def linear_chunk_plan(seq: int) -> int:
    """The chunk the kernels walk the sequence in: 256 measured best of
    {128, 256, 512} at S 12288, head 128 (PERF.md, PR 34). A shorter
    sequence is one chunk, rounded up to a multiple of 8 rows."""
    return 256 if seq >= 256 else -(-seq // 8) * 8


def _pad_seq(x, chunk):
    pad = (-x.shape[1]) % chunk
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


# ---------------------------------------------------------------------------
# the XLA path: the same chunked scan under lax.scan, plain autodiff
# ---------------------------------------------------------------------------

def _chunk_terms(rate, c):
    """(D [c, c], Lambda_q [c, 1], Lambda_k [c, 1], lambda^c) of one head."""
    r = jnp.arange(c, dtype=jnp.float32)
    gap = r[:, None] - r[None, :]
    dmat = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, _F0)), _F0)
    return (dmat, jnp.exp(-rate * (r + 1))[:, None],
            jnp.exp(-rate * (c - 1 - r))[:, None], jnp.exp(-rate * c))


def linear_attention_xla(q, k, v, rates, scale: float,
                         chunk: Optional[int] = None):
    """q, k, v [B, S, H, D], ``rates`` [H] float32 (= -log lambda) -> [B, S,
    H, D]: ``lax.scan`` over chunks, state float32."""
    b, s, h, d = q.shape
    c = chunk or linear_chunk_plan(s)
    qp, kp, vp = (_pad_seq(x, c) for x in (q, k, v))
    n = qp.shape[1] // c

    def head(qh, kh, vh, rate):         # [n, c, D] each
        dmat, lq, lk, lc = _chunk_terms(rate, c)

        def step(kv, x):
            qc, kc, vc = (a.astype(jnp.float32) for a in x)
            a = jnp.einsum("td,sd->ts", qc, kc) * dmat
            o = a @ vc + (qc * lq) @ kv
            return lc * kv + (kc * lk).T @ vc, o
        _, out = jax.lax.scan(step, jnp.zeros((d, d), jnp.float32),
                              (qh, kh, vh))
        return out

    def split(x):                        # [B,S,H,D] -> [B,H,n,c,D]
        return x.reshape(b, n, c, h, d).transpose(0, 3, 1, 2, 4)
    out = jax.vmap(jax.vmap(head, in_axes=(0, 0, 0, 0)),
                   in_axes=(0, 0, 0, None))(split(qp), split(kp), split(vp),
                                            rates.astype(jnp.float32))
    out = out.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h, d)[:, :s]
    return (out * np.float32(scale)).astype(q.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _decays(rate, c):
    """In-kernel (D [c, c], Lambda_q [c, 1], Lambda_k [c, 1]) from the head's
    rate, a [1, 1] float32."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    gap = (row - col).astype(jnp.float32)
    dmat = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, _F0)), _F0)
    r = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0).astype(jnp.float32)
    return (dmat, jnp.exp(-rate * (r + np.float32(1.0))),
            jnp.exp(-rate * (np.float32(c - 1) - r)))


def _fwd_kernel(rate_ref, q_ref, k_ref, v_ref, o_ref, st_ref, kv_ref, *, c,
                scale):
    i = pl.program_id(1)
    rate = rate_ref[0][:, :1]                      # [1, 1]

    @pl.when(i == 0)
    def _init():
        kv_ref[...] = jnp.zeros_like(kv_ref)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    dmat, lq, lk = _decays(rate, c)
    kv = kv_ref[...]
    st_ref[0, 0] = kv
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * dmat
    o = jax.lax.dot(a.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o += jax.lax.dot(q.astype(jnp.float32) * lq, kv,
                     preferred_element_type=jnp.float32)
    o_ref[0] = (o * np.float32(scale)).astype(o_ref.dtype)
    kd = (k.astype(jnp.float32) * lk).astype(k.dtype)
    kv_ref[...] = jnp.exp(-rate * np.float32(c)) * kv + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _bwd_kernel(rate_ref, q_ref, k_ref, v_ref, do_ref, st_ref, dq_ref, dk_ref,
                dv_ref, dkv_ref, *, c, scale):
    """Chunk ``n - 1 - i`` at grid step ``i`` (the index maps reverse the
    walk). With A = (Q K^T) * D and dA = (dO V^T) * D:
    dq = dA K + Lambda_q (dO KV^T);  dk = dA^T Q + Lambda_k (V dKV^T);
    dv = A^T dO + Lambda_k (K dKV);  dKV <- lambda^C dKV + (Lambda_q Q)^T dO.
    """
    i = pl.program_id(1)
    rate = rate_ref[0][:, :1]                      # [1, 1]

    @pl.when(i == 0)
    def _init():
        dkv_ref[...] = jnp.zeros_like(dkv_ref)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    dmat, lq, lk = _decays(rate, c)
    kv, dkv = st_ref[0, 0], dkv_ref[...]
    f32 = jnp.float32
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * dmat
    da = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32) * dmat
    dq = jax.lax.dot(da.astype(k.dtype), k, preferred_element_type=f32) \
        + lq * jax.lax.dot_general(do.astype(f32), kv,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)
    dk = jax.lax.dot_general(da.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                             preferred_element_type=f32) \
        + lk * jax.lax.dot_general(v.astype(f32), dkv,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)
    dv = jax.lax.dot_general(a.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                             preferred_element_type=f32) \
        + lk * jax.lax.dot(k.astype(f32), dkv, preferred_element_type=f32)
    s = np.float32(scale)
    dq_ref[0] = (dq * s).astype(dq_ref.dtype)
    dk_ref[0] = (dk * s).astype(dk_ref.dtype)
    dv_ref[0] = (dv * s).astype(dv_ref.dtype)
    qd = (q.astype(f32) * lq).astype(q.dtype)
    dkv_ref[...] = jnp.exp(-rate * np.float32(c)) * dkv + jax.lax.dot_general(
        qd, do, (((0,), (0,)), ((), ())), preferred_element_type=f32)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _rates3(rates, b):
    """[H] -> [B*H, 1, 128] float32: a head's rate as one lane-wide row."""
    r = jnp.tile(rates.astype(jnp.float32), b)
    return jnp.broadcast_to(r[:, None, None], (r.shape[0], 1, 128))


@functools.partial(jax.jit, static_argnames=("c", "scale", "interpret"))
def _fwd(q3, k3, v3, rates3, *, c, scale, interpret):
    bh, s, d = q3.shape
    n = s // c
    row = pl.BlockSpec((1, c, d), lambda b, i: (b, i, _Z))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, scale=scale),
        grid=(bh, n),
        in_specs=[pl.BlockSpec((1, 1, 128), lambda b, i: (b, _Z, _Z)),
                  row, row, row],
        out_specs=[row, pl.BlockSpec((1, 1, d, d), lambda b, i: (b, i, _Z, _Z))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, n, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["fwd"],
    )(rates3, q3, k3, v3)


@functools.partial(jax.jit, static_argnames=("c", "scale", "interpret"))
def _bwd(q3, k3, v3, do3, states, rates3, *, c, scale, interpret):
    bh, s, d = q3.shape
    n = s // c
    last = np.int32(n - 1)
    row = pl.BlockSpec((1, c, d), lambda b, i: (b, last - i, _Z))
    shape = jax.ShapeDtypeStruct((bh, s, d), q3.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, scale=scale),
        grid=(bh, n),
        in_specs=[pl.BlockSpec((1, 1, 128), lambda b, i: (b, _Z, _Z)),
                  row, row, row, row,
                  pl.BlockSpec((1, 1, d, d),
                               lambda b, i: (b, last - i, _Z, _Z))],
        out_specs=[row, row, row],
        out_shape=[shape, shape, shape],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["bwd"],
    )(rates3, q3, k3, v3, do3, states)


def _heads_first(x, c):
    b, s, h, d = x.shape
    return _pad_seq(x.transpose(0, 2, 1, 3).reshape(b * h, s, d), c)


def _heads_last(x3, b, s):
    bh, _, d = x3.shape
    return x3[:, :s].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def linear_attention(q, k, v, rates, scale, chunk, interpret):
    """q, k, v [B, S, H, D], ``rates`` [H] (= -log lambda; no gradient) ->
    [B, S, H, D] through the two kernels. ``chunk``: None for the plan's."""
    out, _ = _la_fwd(q, k, v, rates, scale, chunk, interpret)
    return out


def _la_fwd(q, k, v, rates, scale, chunk, interpret):
    b, s, _, _ = q.shape
    c = chunk or linear_chunk_plan(s)
    interpret = pallas_interpret() if interpret is None else interpret
    out3, states = _fwd(_heads_first(q, c), _heads_first(k, c),
                        _heads_first(v, c), _rates3(rates, b), c=c,
                        scale=float(scale), interpret=interpret)
    return _heads_last(out3, b, s), (q, k, v, rates, states)


def _la_bwd(scale, chunk, interpret, res, dout):
    q, k, v, rates, states = res
    b, s, _, _ = q.shape
    c = chunk or linear_chunk_plan(s)
    interpret = pallas_interpret() if interpret is None else interpret
    dq3, dk3, dv3 = _bwd(_heads_first(q, c), _heads_first(k, c),
                         _heads_first(v, c),
                         _heads_first(dout.astype(q.dtype), c), states,
                         _rates3(rates, b), c=c, scale=float(scale),
                         interpret=interpret)
    return (_heads_last(dq3, b, s), _heads_last(dk3, b, s),
            _heads_last(dv3, b, s), jnp.zeros_like(rates))


linear_attention.defvjp(_la_fwd, _la_bwd)
