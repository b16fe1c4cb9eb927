"""The state-space duality (SSD) scan of Mamba-2 (Dao & Gu 2024) as Pallas TPU
kernels, with an XLA ``lax.scan`` path for hosts without a chip.

Per head h, with a state ``S`` [P, N] float32 (head dim P, state N), a step
``dt_t > 0``, a decay rate ``A_h < 0`` and the group's ``B_t``, ``C_t`` [N]::

    S_t = exp(dt_t A_h) S_(t-1) + (dt_t x_t) B_t^T
    y_t = S_t C_t

Heads of one group (``H / G`` of them, head h in group ``h // (H / G)``)
share B and C. The skip term ``D x`` is the caller's.

The sequence is walked in chunks of ``c``. With ``G`` the cumulative sum of
``dt A`` inside the chunk (``G_c`` its last), ``L_ts = exp(G_t - G_s)`` for
``t >= s`` (masked before the exponent, so that every exponent taken is <=
0 whatever dt is) and ``X = dt x``::

    Y  = ((C B^T) * L) X + exp(G) (C S^T)
    S' = exp(G_c) S + X^T (B exp(G_c - G))

Kernels: ``ssd_fwd``, grid (batch x groups, chunks), the chunk axis
sequential. A grid step takes one group's heads: ``C B^T`` [c, c] is made
once and serves them all, B and C are read once a group, and the heads'
states ride in one VMEM scratch [H / G, P, N] float32. It also parks each
chunk's incoming states (at S 16384, c 128: 128 states of 32 KiB a head),
which ``ssd_bwd``, the same grid walked from the last chunk to the first,
reads while it carries dS. The state and every product with it are float32
at ``Precision.HIGHEST``; the products of the inputs alone (``C B^T``, the
chunk's ``(C B^T * L) X`` and their gradients) take their operands in the
storage dtype and accumulate in float32. ``dt`` and ``G`` enter as rows [1, c]
a head (lane-dense: an operand [., S, 1] would pad its lane to 128, 8 GiB of
temporaries at the cell's size); the kernels make the columns they need by a
masked lane sum, exactly, and hand their gradients back as rows too. The
cumulative sum's own gradient is XLA's.
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
from .gated_delta import _dot, _dot_t0, _dot_t1, _pad_seq, chunk_cumsum

__all__ = ["ssd", "ssd_xla", "ssd_plan", "SSD_PLAN_TALLY"]

_F0 = np.float32(0.0)
_KERNEL_NAMES = {"fwd": "ssd_fwd", "bwd": "ssd_bwd"}
# one count per lowered SSD mixer, by (S, chunk, heads, groups, state, head
# dim, path, kept or replayed)
SSD_PLAN_TALLY: collections.Counter = collections.Counter()


def ssd_plan(seq: int, chunk: int = 128) -> int:
    """The chunk the kernels walk the sequence in: the configuration's
    ``chunk_size`` (Mamba-2 publishes 128); a shorter sequence is one chunk,
    rounded up to a multiple of 8 rows."""
    return chunk if seq >= chunk else -(-seq // 8) * 8


# ---------------------------------------------------------------------------
# the XLA path: the same chunked form under lax.scan, plain autodiff
# ---------------------------------------------------------------------------

def ssd_xla(x, dt, a, b, c, chunk: Optional[int] = None):
    """x [Bt, S, H, P], dt [Bt, S, H] float32, a [H] float32 (<= 0), b and c
    [Bt, S, G, N] -> y [Bt, S, H, P] in x's dtype: ``lax.scan`` over chunks,
    everything float32."""
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    cs = chunk or ssd_plan(s)
    f32 = jnp.float32
    dtf = dt.astype(f32)
    gam = chunk_cumsum(dtf * a.astype(f32), cs)
    xp = _pad_seq(x.astype(f32) * dtf[..., None], cs)
    bp, cp = (jnp.repeat(_pad_seq(z.astype(f32), cs), h // g, axis=2)
              for z in (b, c))
    nc = xp.shape[1] // cs
    incl = jnp.arange(cs)[:, None] >= jnp.arange(cs)[None, :]

    def head(xh, gh, bh, ch):                # [nc, cs, .] / [nc, cs]
        def step(st, z):
            xc, gc, bc, cc = z
            lmat = jnp.where(incl, jnp.exp(jnp.where(
                incl, gc[:, None] - gc[None, :], _F0)), _F0)
            y = ((cc @ bc.T) * lmat) @ xc + jnp.exp(gc)[:, None] * (cc @ st.T)
            st = jnp.exp(gc[-1]) * st \
                + xc.T @ (bc * jnp.exp(gc[-1] - gc)[:, None])
            return st, y
        with jax.default_matmul_precision("highest"):
            _, out = jax.lax.scan(step, jnp.zeros((p, n), f32),
                                  (xh, gh, bh, ch))
        return out

    def split(z):                            # [Bt,Sp,H,...] -> [Bt,H,nc,cs,...]
        z = z.reshape((bt, nc, cs) + z.shape[2:])
        return jnp.moveaxis(z, 3, 1)
    out = jax.vmap(jax.vmap(head))(split(xp), split(gam), split(bp),
                                   split(cp))
    out = jnp.moveaxis(out, 1, 3).reshape(bt, nc * cs, h, p)[:, :s]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _eye(c):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row == col, row >= col


def _column(r, eye):
    """A row [1, c] as a column [c, 1], exactly (a masked lane sum): no
    vector is transposed, and no operand [., S, 1] pads its lane to 128."""
    return jnp.sum(jnp.where(eye, r, _F0), axis=1, keepdims=True)


def _row(v, eye):
    return jnp.sum(jnp.where(eye, v, _F0), axis=0, keepdims=True)


def _decays(gr, eye, incl, c):
    """One head's chunk terms from G as a row [1, c]: L, exp(G), exp(G_c -
    G) (columns), exp(G_c)."""
    gc = _column(gr, eye)
    lmat = jnp.where(incl, jnp.exp(jnp.where(incl, gc - gr, _F0)), _F0)
    g_last = gr[:, c - 1:c]                          # [1, 1]
    return lmat, jnp.exp(gc), jnp.exp(g_last - gc), jnp.exp(g_last)


def _fwd_kernel(x_ref, dt_ref, g_ref, b_ref, c_ref, y_ref, st_ref, s_ref, *,
                c, heads):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    eye, incl = _eye(c)
    bm, cm = b_ref[0], c_ref[0]
    cb = _dot_t1(cm, bm)                              # [c, c], once a group
    bf, cf = bm.astype(f32), cm.astype(f32)
    for j in range(heads):
        lmat, e, f, e_last = _decays(g_ref[j], eye, incl, c)
        x = x_ref[j]
        xt = x.astype(f32) * _column(dt_ref[j], eye)
        s = s_ref[j]
        st_ref[j, 0] = s
        y = _dot((cb * lmat).astype(x.dtype), xt.astype(x.dtype)) \
            + e * _dot_t1(cf, s, hi=True)
        y_ref[j] = y.astype(y_ref.dtype)
        s_ref[j] = e_last * s + _dot_t0(xt, bf * f, hi=True)


def _bwd_kernel(x_ref, dt_ref, g_ref, b_ref, c_ref, dy_ref, st_ref, dx_ref,
                ddt_ref, dg_ref, db_ref, dc_ref, ds_ref, *, c, heads):
    """Chunk ``n - 1 - i`` at grid step ``i`` (the index maps reverse the
    walk). ``ds_ref`` holds each head's dS', the gradient of the state the
    chunk leaves; it is left holding the gradient of the state the chunk
    received. With M = (C B^T) * L::

        dX  = M^T dY + (B f) dS'^T;   dM = dY X^T (t >= s)
        dC  = (dM * L) B + e (dY S);  dB = (dM * L)^T C + f (X dS')
        dS  = e_C dS' + (e dY)^T C
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    f32 = jnp.float32
    eye, incl = _eye(c)
    bm, cm = b_ref[0], c_ref[0]
    dt_in = bm.dtype
    cb = _dot_t1(cm, bm)
    bf, cf = bm.astype(f32), cm.astype(f32)
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dcb = jnp.zeros((c, c), f32)
    dbm = jnp.zeros(bm.shape, f32)
    dcm = jnp.zeros(cm.shape, f32)
    for j in range(heads):
        lmat, e, f, e_last = _decays(g_ref[j], eye, incl, c)
        x = x_ref[j]
        dtj = _column(dt_ref[j], eye)
        xf = x.astype(f32)
        xt = xf * dtj
        s, dsn = st_ref[j, 0], ds_ref[j]
        dy = dy_ref[j]
        dyf = dy.astype(f32)
        m = cb * lmat
        dm = jnp.where(incl, _dot_t1(dy, xt.astype(x.dtype)), _F0)
        dxt = _dot_t0(m.astype(x.dtype), dy) + _dot_t1(bf * f, dsn, hi=True)
        dcb = dcb + dm * lmat
        de = jnp.sum(dyf * _dot_t1(cf, s, hi=True), axis=1, keepdims=True)
        dcm = dcm + e * _dot(dyf, s, hi=True)
        xds = _dot(xt, dsn, hi=True)                  # [c, N]
        dbm = dbm + f * xds
        df = jnp.sum(xds * bf, axis=1, keepdims=True)
        dg_last = jnp.sum(df * f) + jnp.sum(s * dsn) * e_last
        r = dm * m
        dgc = jnp.sum(r, axis=1, keepdims=True) + de * e - df * f \
            + jnp.where(last, dg_last, _F0)
        dg_ref[j] = _row(dgc, eye) - jnp.sum(r, axis=0, keepdims=True)
        dx_ref[j] = (dxt * dtj).astype(dx_ref.dtype)
        ddt_ref[j] = _row(jnp.sum(dxt * xf, axis=1, keepdims=True), eye)
        ds_ref[j] = e_last * dsn + _dot_t0(dyf * e, cf, hi=True)
    dcb_in = dcb.astype(dt_in)
    db_ref[0] = (dbm + _dot_t0(dcb_in, cm)).astype(db_ref.dtype)
    dc_ref[0] = (dcm + _dot(dcb_in, bm)).astype(dc_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _specs(c, p, n_state, heads, n, reverse):
    """BlockSpecs of x (a group's heads), a per-head row (dt, G), B / C (the
    group's) and the parked states, walked forward or from the last
    chunk."""
    last = np.int32(n - 1)

    def at(i):
        return last - i if reverse else i
    x = pl.BlockSpec((heads, c, p), lambda g, i: (g, at(i), _Z))
    row = pl.BlockSpec((heads, 1, c), lambda g, i: (g, _Z, at(i)))
    bc = pl.BlockSpec((1, c, n_state), lambda g, i: (g, at(i), _Z))
    state = pl.BlockSpec((heads, 1, p, n_state),
                         lambda g, i: (g, at(i), _Z, _Z))
    return x, row, bc, state


@functools.partial(jax.jit, static_argnames=("c", "heads", "interpret"))
def _fwd(x3, dt3, g3, b3, c3, *, c, heads, interpret):
    bh, s, p = x3.shape
    n_state = b3.shape[-1]
    n = s // c
    x, row, bc, state = _specs(c, p, n_state, heads, n, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, heads=heads),
        grid=(bh // heads, n),
        in_specs=[x, row, row, bc, bc],
        out_specs=[x, state],
        out_shape=[jax.ShapeDtypeStruct((bh, s, p), x3.dtype),
                   jax.ShapeDtypeStruct((bh, n, p, n_state), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, p, n_state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["fwd"],
    )(x3, dt3, g3, b3, c3)


@functools.partial(jax.jit, static_argnames=("c", "heads", "interpret"))
def _bwd(x3, dt3, g3, b3, c3, dy3, states, *, c, heads, interpret):
    bh, s, p = x3.shape
    n_state = b3.shape[-1]
    n = s // c
    x, row, bc, state = _specs(c, p, n_state, heads, n, True)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, heads=heads),
        grid=(bh // heads, n),
        in_specs=[x, row, row, bc, bc, x, state],
        out_specs=[x, row, row, bc, bc],
        out_shape=[jax.ShapeDtypeStruct((bh, s, p), x3.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s), f32),
                   jax.ShapeDtypeStruct((bh, 1, s), f32),
                   jax.ShapeDtypeStruct(b3.shape, b3.dtype),
                   jax.ShapeDtypeStruct(c3.shape, c3.dtype)],
        scratch_shapes=[pltpu.VMEM((heads, p, n_state), f32)],
        compiler_params=_params(), interpret=interpret,
        name=_KERNEL_NAMES["bwd"],
    )(x3, dt3, g3, b3, c3, dy3, states)


def _heads_first(z):
    """[Bt, Sp, H, ...] -> [Bt * H, Sp, ...]."""
    b, s, h = z.shape[:3]
    return jnp.moveaxis(z, 2, 1).reshape((b * h, s) + z.shape[3:])


def _heads_last(z3, b, s):
    bh, _, d = z3.shape
    return z3[:, :s].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _operands(x, dt, gam, b, c, cs):
    """The kernels' operands: heads (groups for B and C) first, dt and G as
    rows [B x H, 1, Sp], the sequence padded to whole chunks (dt = 0 there:
    the state passes the padding unchanged)."""
    xp, bp, cp = (_pad_seq(z, cs) for z in (x, b, c))
    dt3 = _heads_first(_pad_seq(dt.astype(jnp.float32), cs))[:, None]
    return (_heads_first(xp), dt3, _heads_first(gam)[:, None],
            _heads_first(bp), _heads_first(cp))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, gam, b, c, cs, interpret):
    out, _ = _ssd_fwd(x, dt, gam, b, c, cs, interpret)
    return out


def _ssd_fwd(x, dt, gam, b, c, cs, interpret):
    bt, s, h = x.shape[:3]
    heads = h // b.shape[2]
    y3, states = _fwd(*_operands(x, dt, gam, b, c, cs), c=cs, heads=heads,
                      interpret=interpret)
    # a recomputed block may keep these two (recompute's "flash_saveable")
    # and spare this kernel's second run; elsewhere a name is the identity
    from ...distributed.fleet.recompute import keep
    y = keep(_heads_last(y3, bt, s), "ssd_out")
    states = keep(states, "ssd_states")
    return y, (x, dt, gam, b, c, states)


def _ssd_bwd(cs, interpret, res, dy):
    x, dt, gam, b, c, states = res
    bt, s, h = x.shape[:3]
    g = b.shape[2]
    dy3 = _heads_first(_pad_seq(dy.astype(x.dtype), cs))
    dx3, ddt3, dg3, db3, dc3 = _bwd(
        *_operands(x, dt, gam, b, c, cs), dy3, states, c=cs, heads=h // g,
        interpret=interpret)
    sp = gam.shape[1]

    def per_token(z3):                       # [B x H, 1, Sp] -> [B, Sp, H]
        return jnp.moveaxis(z3[:, 0].reshape(bt, h, sp), 1, 2)
    return (_heads_last(dx3, bt, s), per_token(ddt3)[:, :s].astype(dt.dtype),
            per_token(dg3), _heads_last(db3, bt, s), _heads_last(dc3, bt, s))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, b, c, chunk: Optional[int] = None,
        interpret: Optional[bool] = None):
    """x [Bt, S, H, P], dt [Bt, S, H] float32 (> 0), a [H] float32 (< 0), b
    and c [Bt, S, G, N] -> y [Bt, S, H, P] through the two kernels;
    gradients for all five. ``chunk``: None for the plan's."""
    cs = chunk or ssd_plan(x.shape[1])
    interpret = pallas_interpret() if interpret is None else interpret
    dtf = dt.astype(jnp.float32)
    return _ssd(x, dtf, chunk_cumsum(dtf * a.astype(jnp.float32), cs), b, c,
                cs, interpret)
