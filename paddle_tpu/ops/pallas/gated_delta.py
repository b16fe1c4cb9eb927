"""The gated delta rule (Gated DeltaNet, Yang et al. 2024) as Pallas TPU
kernels, with an XLA ``lax.scan`` path for hosts without a chip.

Per value head, with a state ``S`` [dk, dv] float32, a decay ``exp(g_t)``
(``g_t <= 0``) and a write strength ``beta_t``::

    S_t = exp(g_t) S_(t-1)
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t

q and k arrive normalised and scaled (``nn/functional/gated_delta_rule.py``
does that); value head h reads key head ``h // (Hv / Hk)``, through the index
map: no repeated copy of q or k is made.

The sequence is walked in chunks of ``C`` in the WY / UT form. With ``G`` the
cumulative sum of g inside the chunk (``G_i``, ``G_C`` its last), ``D_ij =
exp(G_i - G_j)`` for ``i >= j`` (masked before the exponent, so that every
exponent taken is <= 0), ``A = strict_tril(beta_i (k_i . k_j) D_ij)`` and
``T = (I + A)^-1``::

    W  = T (beta exp(G) K),  U = T (beta V),  V' = U - W S
    O  = (Q exp(G)) S + (tril(Q K^T) * D) V'
    S' = exp(G_C) S + (K exp(G_C - G))^T V'

``T`` comes by blocks (``_unit_lower_inverse``: diagonal blocks of 8 as
``(I - A)(I + A^2)(I + A^4)``, merged pairwise), float32, as are the state
and every product with it (``Precision.HIGHEST``). The products of the
inputs alone (``Q K^T``, ``K K^T``, ``P V'``) take their operands in the
storage dtype and accumulate in float32.

Kernels: ``gated_delta_fwd``, grid (batch x value heads, chunks), the chunk
axis sequential, the state in VMEM scratch; it also parks each chunk's
incoming state (at S 16384, C 128: 128 states of 64 KiB a head), which
``gated_delta_bwd``, the same grid walked from the last chunk to the first,
reads while it carries dS. The backward kernel recomputes the chunk's ``T``,
``W``, ``U`` and ``V'`` from the parked state. G enters as a column [C, 1]
and as a row [1, C] of the same numbers, so that no kernel transposes a
vector; its gradient leaves the same two ways and is summed outside.
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

__all__ = ["gated_delta", "gated_delta_xla", "gated_delta_plan",
           "GDN_PLAN_TALLY"]

_F0 = np.float32(0.0)
# rows of the diagonal blocks the triangular solve starts from
_BASE = 8
_KERNEL_NAMES = {"fwd": "gated_delta_fwd", "bwd": "gated_delta_bwd"}
# one count per lowered DeltaNet mixer, by (value heads, key heads, dk, dv,
# S, chunk, path)
GDN_PLAN_TALLY: collections.Counter = collections.Counter()


def gated_delta_plan(seq: int) -> int:
    """The chunk the kernels walk the sequence in: 128 measured best of {64,
    128} at S 16384, 32 value heads of 128 (46.0 -> 37.2 ms a layer's
    forward and backward, the solve then a product of powers; PERF.md
    section 6). A shorter sequence is one chunk, rounded up to a multiple
    of 8 rows."""
    return 128 if seq >= 128 else -(-seq // 8) * 8


def _pad_seq(x, chunk):
    pad = (-x.shape[1]) % chunk
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def chunk_cumsum(g, chunk):
    """g [B, S, H] -> G [B, Sp, H] float32: the cumulative sum of g inside
    each chunk of ``chunk`` tokens, the sequence padded with g = 0."""
    b, _, h = g.shape
    gp = _pad_seq(g.astype(jnp.float32), chunk)
    n = gp.shape[1] // chunk
    return jnp.cumsum(gp.reshape(b, n, chunk, h), axis=2).reshape(
        b, n * chunk, h)


# ---------------------------------------------------------------------------
# the XLA path: the same chunked form under lax.scan, plain autodiff
# ---------------------------------------------------------------------------

def gated_delta_xla(q, k, v, g, beta, chunk: Optional[int] = None):
    """q, k [B, S, Hk, dk], v [B, S, Hv, dv], g and beta [B, S, Hv] -> o [B,
    S, Hv, dv] in v's dtype: ``lax.scan`` over chunks, everything float32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep = hv // hk
    c = chunk or gated_delta_plan(s)
    f32 = jnp.float32
    gam = chunk_cumsum(g, c)
    qp, kp, vp, bp = (_pad_seq(x.astype(f32), c) for x in (q, k, v, beta))
    n = qp.shape[1] // c
    qp, kp = (jnp.repeat(x, rep, axis=2) for x in (qp, kp))
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    strict, incl = row > col, row >= col

    def head(qh, kh, vh, gh, bh):           # [n, c, d] / [n, c]
        def step(st, x):
            qc, kc, vc, gc, bc = x
            dmat = jnp.where(incl, jnp.exp(jnp.where(
                incl, gc[:, None] - gc[None, :], _F0)), _F0)
            e, f = jnp.exp(gc), jnp.exp(gc[-1] - gc)
            a = jnp.where(strict, (kc @ kc.T) * dmat * bc[:, None], _F0)
            eye = jnp.eye(c, dtype=f32)
            w = jax.scipy.linalg.solve_triangular(
                eye + a, kc * (bc * e)[:, None], lower=True,
                unit_diagonal=True)
            u = jax.scipy.linalg.solve_triangular(
                eye + a, vc * bc[:, None], lower=True, unit_diagonal=True)
            vnew = u - w @ st
            o = (qc * e[:, None]) @ st + ((qc @ kc.T) * dmat) @ vnew
            return jnp.exp(gc[-1]) * st + (kc * f[:, None]).T @ vnew, o
        with jax.default_matmul_precision("highest"):
            _, out = jax.lax.scan(step, jnp.zeros((dk, dv), f32),
                                  (qh, kh, vh, gh, bh))
        return out

    def split(x):                            # [B,Sp,H,...] -> [B,H,n,c,...]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)
    out = jax.vmap(jax.vmap(head))(split(qp), split(kp), split(vp),
                                   split(gam), split(bp))
    out = jnp.moveaxis(out, 1, 3).reshape(b, n * c, hv, dv)[:, :s]
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _mm(a, b, dims, hi):
    """a . b accumulated in float32. ``hi``: both operands in float32 at
    ``Precision.HIGHEST``; else each operand in one pass as it is typed."""
    if hi:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if hi else None)


def _dot(a, b, hi=False):
    return _mm(a, b, (((1,), (0,)), ((), ())), hi)


def _dot_t0(a, b, hi=False):
    """a^T b: contract the first dims."""
    return _mm(a, b, (((0,), (0,)), ((), ())), hi)


def _dot_t1(a, b, hi=False):
    """a b^T: contract the last dims."""
    return _mm(a, b, (((1,), (1,)), ((), ())), hi)


def _unit_lower_inverse(a, c):
    """(I + a)^-1 of a strictly lower-triangular a [c, c] float32, by blocks:
    each diagonal block of ``_BASE`` as ``(I - a)(I + a^2)(I + a^4)``, then
    blocks of m merged into blocks of 2m, the lower-left block of each as
    ``-T22 a21 T11``. No power of a past the fourth is formed: a product of
    powers over the whole chunk overflows and cancels once keys correlate
    (``tests/test_gated_delta.py`` has such keys)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def block(x, m):                # the index's block of m (lax: Mosaic)
        return jax.lax.div(x, np.int32(m))
    eye = jnp.where(row == col, np.float32(1.0), _F0)
    p = -jnp.where(block(row, _BASE) == block(col, _BASE), a, _F0)
    t = eye + p
    span = 2
    while span < _BASE:
        p = _dot(p, p, hi=True)
        t = _dot(t, eye + p, hi=True)
        span *= 2
    m = _BASE
    while m < c:
        r = block(row, m)
        lower_left = (r == block(col, m) + 1) & \
            (jax.lax.rem(r, np.int32(2)) == 1)
        t = t - _dot(_dot(t, jnp.where(lower_left, a, _F0), hi=True), t,
                     hi=True)
        m *= 2
    return t


def _chunk_terms(k, gc, gr, b, c):
    """What forward and backward share of one chunk: the masks, D, exp(G),
    exp(G_C - G), exp(G_C), K K^T, A and T."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    strict, incl = row > col, row >= col
    dmat = jnp.where(incl, jnp.exp(jnp.where(incl, gc - gr, _F0)), _F0)
    g_last = gr[:, c - 1:c]                         # [1, 1]
    e, f = jnp.exp(gc), jnp.exp(g_last - gc)        # [c, 1] each
    kk = _dot_t1(k, k)
    a = jnp.where(strict, kk * dmat * b, _F0)
    return dict(strict=strict, incl=incl, dmat=dmat, e=e, f=f,
                e_last=jnp.exp(g_last), kk=kk, t=_unit_lower_inverse(a, c))


def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref, o_ref, st_ref,
                s_ref, *, c):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    gc, gr, b = gc_ref[0], gr_ref[0, 0], b_ref[0]
    m = _chunk_terms(k, gc, gr, b, c)
    kf = k.astype(f32)
    s = s_ref[...]
    st_ref[0, 0] = s
    w = _dot(m["t"], kf * (b * m["e"]), hi=True)
    u = _dot(m["t"], v.astype(f32) * b, hi=True)
    vnew = u - _dot(w, s, hi=True)
    p = _dot_t1(q, k) * m["dmat"]
    o = _dot(q.astype(f32) * m["e"], s, hi=True) \
        + _dot(p.astype(v.dtype), vnew.astype(v.dtype))
    o_ref[0] = o.astype(o_ref.dtype)
    s_ref[...] = m["e_last"] * s + _dot_t0(kf * m["f"], vnew, hi=True)


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref, db_ref, ds_ref, *,
                c):
    """Chunk ``n - 1 - i`` at grid step ``i`` (the index maps reverse the
    walk). ``ds_ref`` holds dS', the gradient of the state this chunk leaves;
    it is left holding the gradient of the state the chunk received."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    f32 = jnp.float32
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    gc, gr, b = gc_ref[0], gr_ref[0, 0], b_ref[0]
    m = _chunk_terms(k, gc, gr, b, c)
    e, f, t, dmat = m["e"], m["f"], m["t"], m["dmat"]
    dt_in = v.dtype
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    do_in = do_ref[0]
    do = do_in.astype(f32)
    s, ds_next = st_ref[0, 0], ds_ref[...]
    # the forward again, from the parked state
    xw, xu = kf * (b * e), vf * b
    w, u = _dot(t, xw, hi=True), _dot(t, xu, hi=True)
    vnew = u - _dot(w, s, hi=True)
    qk = _dot_t1(q, k)
    p = qk * dmat
    # O = (Q e) S + P V';  S' = e_C S + (K f)^T V'
    kds = _dot(k, ds_next, hi=True)                        # [c, dv]
    dvnew = _dot_t0(p.astype(dt_in), do_in) + f * kds
    dp = jnp.where(m["incl"], _dot_t1(do_in, vnew.astype(dt_in)), _F0)
    dqk = dp * dmat
    qs = _dot(q, s, hi=True)
    dq = e * _dot_t1(do_in, s, hi=True) + _dot(dqk.astype(dt_in), k)
    dk = _dot_t0(dqk.astype(dt_in), q) \
        + f * _dot_t1(vnew, ds_next, hi=True)
    ds = _dot_t0(qf * e, do, hi=True) + m["e_last"] * ds_next
    de = jnp.sum(do * qs, axis=1, keepdims=True)
    df = jnp.sum(kds * vnew, axis=1, keepdims=True)
    dg_last = jnp.sum(df * f) + jnp.sum(s * ds_next) * m["e_last"]
    # V' = U - W S;  U = T (beta V);  W = T (beta e K)
    dw = -_dot_t1(dvnew, s, hi=True)
    ds = ds - _dot_t0(w, dvnew, hi=True)
    dt = _dot_t1(dvnew, xu, hi=True) + _dot_t1(dw, xw, hi=True)
    dxu, dxw = _dot_t0(t, dvnew, hi=True), _dot_t0(t, dw, hi=True)
    dv = dxu * b
    xw_k = jnp.sum(dxw * kf, axis=1, keepdims=True)
    db = jnp.sum(dxu * vf, axis=1, keepdims=True) + e * xw_k
    de = de + b * xw_k
    dk = dk + dxw * (b * e)
    # T = (I + A)^-1: dA = -T^T dT T^T, on the strict lower triangle
    da = jnp.where(m["strict"],
                   -_dot_t1(_dot_t0(t, dt, hi=True), t, hi=True), _F0)
    kk = m["kk"]
    db = db + jnp.sum(da * kk * dmat, axis=1, keepdims=True)
    dkk = da * b * dmat
    dk = dk + _dot(dkk.astype(dt_in), k) + _dot_t0(dkk.astype(dt_in), k)
    # D_ij = exp(G_i - G_j);  e = exp(G);  f = exp(G_C - G)
    r = (dp * qk + da * b * kk) * dmat
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    dgc = jnp.sum(r, axis=1, keepdims=True) + de * e - df * f \
        + jnp.where(row == c - 1, dg_last, _F0)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dgc_ref[0] = dgc
    dgr_ref[0, 0] = -jnp.sum(r, axis=0, keepdims=True)
    db_ref[0] = db
    ds_ref[...] = ds


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _specs(c, dk, dv, rep, n, reverse):
    """BlockSpecs of q, k, v, G (column), G (row), beta, walked forward or
    from the last chunk."""
    last = np.int32(n - 1)
    rep = np.int32(rep)

    def at(i):
        return last - i if reverse else i
    qk = pl.BlockSpec((1, c, dk), lambda h, i: (h // rep, at(i), _Z))
    val = pl.BlockSpec((1, c, dv), lambda h, i: (h, at(i), _Z))
    column = pl.BlockSpec((1, c, 1), lambda h, i: (h, at(i), _Z))
    row = pl.BlockSpec((1, 1, 1, c), lambda h, i: (h, at(i), _Z, _Z))
    state = pl.BlockSpec((1, 1, dk, dv), lambda h, i: (h, at(i), _Z, _Z))
    return qk, val, column, row, state


@functools.partial(jax.jit, static_argnames=("c", "rep", "interpret"))
def _fwd(q3, k3, v3, gc3, gr4, b3, *, c, rep, interpret):
    bh, s, dv = v3.shape
    dk = q3.shape[-1]
    n = s // c
    qk, val, column, row, state = _specs(c, dk, dv, rep, n, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c),
        grid=(bh, n),
        in_specs=[qk, qk, val, column, row, column],
        out_specs=[val, state],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), v3.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["fwd"],
    )(q3, k3, v3, gc3, gr4, b3)


@functools.partial(jax.jit, static_argnames=("c", "rep", "interpret"))
def _bwd(q3, k3, v3, gc3, gr4, b3, do3, states, *, c, rep, interpret):
    bh, s, dv = v3.shape
    dk = q3.shape[-1]
    n = s // c
    qk, val, column, row, state = _specs(c, dk, dv, rep, n, True)
    per_head = pl.BlockSpec((1, c, dk), val.index_map)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, c=c),
        grid=(bh, n),
        in_specs=[qk, qk, val, column, row, column, val, state],
        out_specs=[per_head, per_head, val, column, row, column],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dk), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, dk), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, dv), v3.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
                   jax.ShapeDtypeStruct((bh, n, 1, c), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["bwd"],
    )(q3, k3, v3, gc3, gr4, b3, do3, states)


def _heads_first(x):
    """[B, Sp, H, ...] -> [B * H, Sp, ...]."""
    b, s, h = x.shape[:3]
    return jnp.moveaxis(x, 2, 1).reshape((b * h, s) + x.shape[3:])


def _heads_last(x3, b, s):
    bh, _, d = x3.shape
    return x3[:, :s].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _operands(q, k, v, gam, beta, c):
    """The kernels' operands: heads first, the sequence padded to whole
    chunks (g = beta = 0 there: the state passes the padding unchanged)."""
    qp, kp, vp = (_pad_seq(x, c) for x in (q, k, v))
    bp = _pad_seq(beta.astype(jnp.float32), c)
    gc3 = _heads_first(gam)[..., None]
    bh, sp, _ = gc3.shape
    return (_heads_first(qp), _heads_first(kp), _heads_first(vp), gc3,
            gc3.reshape(bh, sp // c, 1, c), _heads_first(bp)[..., None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, gam, beta, c, interpret):
    out, _ = _gdn_fwd(q, k, v, gam, beta, c, interpret)
    return out


def _gdn_fwd(q, k, v, gam, beta, c, interpret):
    b, s = v.shape[:2]
    rep = v.shape[2] // q.shape[2]
    ops = _operands(q, k, v, gam, beta, c)
    out3, states = _fwd(*ops, c=c, rep=rep, interpret=interpret)
    # a recomputed block may keep these two (recompute's "flash_saveable")
    # and spare this kernel's second run; elsewhere a name is the identity
    from ...distributed.fleet.recompute import keep
    out = keep(_heads_last(out3, b, s), "gdn_out")
    states = keep(states, "gdn_states")
    return out, (q, k, v, gam, beta, states)


def _gdn_bwd(c, interpret, res, dout):
    q, k, v, gam, beta, states = res
    b, s, hk, dk = q.shape
    hv = v.shape[2]
    rep = hv // hk
    ops = _operands(q, k, v, gam, beta, c)
    do3 = _heads_first(_pad_seq(dout.astype(v.dtype), c))
    dq3, dk3, dv3, dgc, dgr, db3 = _bwd(*ops, do3, states, c=c, rep=rep,
                                        interpret=interpret)

    def to_key_heads(x3, dtype):
        # value head h's part of key head h // rep, summed over the rep
        x = _heads_last(x3, b, s).astype(jnp.float32)
        return x.reshape(b, s, hk, rep, dk).sum(axis=3).astype(dtype)
    sp = gam.shape[1]
    dgam = (dgc[..., 0] + dgr.reshape(b * hv, sp)).reshape(b, hv, sp)
    return (to_key_heads(dq3, q.dtype), to_key_heads(dk3, k.dtype),
            _heads_last(dv3, b, s), jnp.moveaxis(dgam, 1, 2),
            jnp.moveaxis(db3[..., 0].reshape(b, hv, sp), 1, 2)[:, :s]
            .astype(beta.dtype))


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gated_delta(q, k, v, g, beta, chunk: Optional[int] = None,
                interpret: Optional[bool] = None):
    """q, k [B, S, Hk, dk], v [B, S, Hv, dv], g (<= 0) and beta [B, S, Hv]
    -> o [B, S, Hv, dv] through the two kernels; gradients for all five.
    ``chunk``: None for the plan's."""
    c = chunk or gated_delta_plan(q.shape[1])
    interpret = pallas_interpret() if interpret is None else interpret
    return _gdn(q, k, v, chunk_cumsum(g, c), beta, c, interpret)
