"""Grouped matrix products over the experts a chip holds, as Pallas TPU kernels.

``lhs`` holds the rows of every group one after another, each group's rows
starting at a multiple of the row tile ``tm`` (``group_layout`` makes that
layout from the expert each assignment chose), so a row tile belongs to one
group and the kernels need no mask: tile ``i`` multiplies by the weights of
group ``tile_group[i]``, read through a scalar-prefetched index map. The
buffers are sized for the worst routing (every assignment lands here); only
the first ``n_tiles`` tiles hold rows, the steps of the others are skipped
and ask for the blocks the last live step already holds, so they move no
data.

Three kernels, found in a device trace by their names (benchmarks/metrics/
moe_gmm_roofline.py):

- ``moe_gmm_fwd``:   out[M, N]  = lhs[M, K] @ rhs[g, K, N]
- ``moe_gmm_bwd_x``: dlhs[M, K] = dout[M, N] @ rhs[g, K, N]^T
- ``moe_gmm_bwd_w``: drhs[g, K, N] = sum over the rows of g of lhs^T dout

Operands stay in their storage dtype (bf16 on the training path) and
accumulate in float32, as the dense matmuls do. ``grouped_matmul`` ties the
three together with a ``custom_vjp``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import pallas_interpret

__all__ = ["grouped_matmul", "group_layout", "GroupLayout", "gmm_tiles",
           "ROW_TILE"]

# rows of a tile: groups are padded to a multiple of it, so on average half
# a tile a group is computed for nothing (PERF.md, "PR 28": the sweep)
ROW_TILE = 256
_KERNEL_NAMES = {"fwd": "moe_gmm_fwd", "bwd_x": "moe_gmm_bwd_x",
                 "bwd_w": "moe_gmm_bwd_w"}
_VMEM_DEFAULT_LIMIT = 16 << 20
_VMEM_MAX_LIMIT = 64 << 20


class GroupLayout(NamedTuple):
    """Where each assignment's row lies in the padded, group-sorted buffer.

    ``dest`` [A]: row of assignment a, or ``rows`` (one past the end) if its
    expert is not held here; ``row_src`` [rows]: the assignment a row holds,
    or A for a padding row; ``tile_group`` [rows // tm]: the group of each
    row tile (dead tiles repeat the last live tile's); ``n_tiles`` [1]: the
    live tiles; ``sizes`` [held]: assignments of each held expert."""
    dest: jax.Array
    row_src: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    sizes: jax.Array


def padded_rows(assignments: int, held: int, tm: int = ROW_TILE) -> int:
    """Rows of the buffer that takes any routing of ``assignments`` onto
    ``held`` groups: each group padded to a multiple of ``tm``, at least one
    tile each."""
    return -(-assignments // tm) * tm + held * tm


def group_layout(expert, first: int, held: int, tm: int = ROW_TILE
                 ) -> GroupLayout:
    """The layout of ``expert`` [A] (the expert every assignment chose, of
    all the experts) on the chip that holds experts ``first .. first + held
    - 1``. Every held expert gets at least one tile, so that the weight
    gradient's kernel visits, and zeroes, an expert nobody chose."""
    a = expert.shape[0]
    rows = padded_rows(a, held, tm)
    i32 = jnp.int32
    local = expert.astype(i32) - i32(first)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, i32(held))
    counts = jnp.zeros((held + 1,), i32).at[key].add(1)
    sizes = counts[:held]
    padded = jnp.maximum(-(-sizes // i32(tm)) * i32(tm), i32(tm))
    ends = jnp.cumsum(padded)
    starts = ends - padded
    order = jnp.argsort(key, stable=True).astype(i32)
    sorted_key = key[order]
    sorted_start = jnp.cumsum(counts) - counts
    rank = jnp.arange(a, dtype=i32) - sorted_start[sorted_key]
    dest_sorted = jnp.where(
        sorted_key < held,
        jnp.concatenate([starts, jnp.zeros((1,), i32)])[sorted_key] + rank,
        i32(rows))
    dest = jnp.zeros((a,), i32).at[order].set(dest_sorted)
    row_src = jnp.full((rows + 1,), a, i32).at[dest].set(
        jnp.arange(a, dtype=i32))[:rows]
    tile_start = jnp.arange(rows // tm, dtype=i32) * i32(tm)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right").astype(i32),
        i32(held - 1))
    n_tiles = (ends[-1:] // i32(tm)).astype(i32)
    return GroupLayout(dest, row_src, tile_group, n_tiles, sizes)


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

def gmm_tiles(k: int, n: int):
    """(tk, tn) of a kernel on a [.., k] x [g, k, n] product: the whole of
    a side up to 2048 x 1024 elements, where one group's weights are one
    block that stays in VMEM while the group's row tiles pass (PERF.md,
    "PR 28": the sweep at 2048 x 1024 and 512 x 2048)."""
    return _divisor(k, 2048), _divisor(n, 1024)


def _divisor(size, cap):
    """The largest multiple of 128 up to ``cap`` that divides ``size``, or
    the whole size where it is small or 128 does not divide it."""
    if size <= cap or size % 128:
        return size
    return max(t for t in range(128, cap + 1, 128) if size % t == 0)


def _params(semantics, vmem):
    limit = None
    if vmem > _VMEM_DEFAULT_LIMIT * 3 // 4:
        limit = min(int(vmem * 1.5), _VMEM_MAX_LIMIT)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


# ---------------------------------------------------------------------------
# out = lhs @ rhs[g] (rhs transposed for the backward's dlhs)
# ---------------------------------------------------------------------------

def _gmm_kernel(tg_ref, na_ref, x_ref, w_ref, o_ref, *scratch, nk, trans):
    del tg_ref
    mi, ki = pl.program_id(0), pl.program_id(2)
    dims = (((1,), (1,)), ((), ())) if trans else (((1,), (0,)), ((), ()))

    def product():
        return jax.lax.dot_general(x_ref[...], w_ref[0], dims,
                                   preferred_element_type=jnp.float32)

    @pl.when(mi < na_ref[0])
    def _live():
        if nk == 1:
            o_ref[...] = product().astype(o_ref.dtype)
            return
        acc_ref, = scratch

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += product()

        @pl.when(ki == nk - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm(x, w, tile_group, n_tiles, *, trans, tiles, interpret):
    """x [M, K] @ w [G, K, N] -> [M, N]; with ``trans`` x [M, N] @ w[g]^T ->
    [M, K] (the contraction runs over w's last dim)."""
    m, kx = x.shape
    g, wk, wn = w.shape
    kdim, ndim = (wn, wk) if trans else (wk, wn)   # contraction, output
    assert kx == kdim, (x.shape, w.shape, trans)
    m_tiles = tile_group.shape[0]
    tm = m // m_tiles
    kind = "bwd_x" if trans else "fwd"
    tk, tn = tiles or gmm_tiles(kdim, ndim)
    nk, nn = kdim // tk, ndim // tn
    last_n, last_k = np.int32(nn - 1), np.int32(nk - 1)

    def where(mi, ni, ki, na):
        """A dead step asks for the last live step's blocks."""
        live = mi < na[0]
        return (jnp.where(live, mi, na[0] - 1), jnp.where(live, ni, last_n),
                jnp.where(live, ki, last_k))

    def x_map(mi, ni, ki, tg, na):
        mi, _, ki = where(mi, ni, ki, na)
        return mi, ki

    def w_map(mi, ni, ki, tg, na):
        mi, ni, ki = where(mi, ni, ki, na)
        return (tg[mi], ni, ki) if trans else (tg[mi], ki, ni)

    def o_map(mi, ni, ki, tg, na):
        mi, ni, _ = where(mi, ni, ki, na)
        return mi, ni

    w_block = (1, tn, tk) if trans else (1, tk, tn)
    item = x.dtype.itemsize
    vmem = 2 * (tm * tk * item + tk * tn * w.dtype.itemsize
                + tm * tn * item) + (tm * tn * 4 if nk > 1 else 0) \
        + tm * tn * 4
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk, trans=trans),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m_tiles, nn, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if nk > 1 else []),
        out_shape=jax.ShapeDtypeStruct((m, ndim), x.dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary"), vmem),
        interpret=interpret,
        name=_KERNEL_NAMES[kind],
    )(tile_group, n_tiles, x, w)


# ---------------------------------------------------------------------------
# drhs[g] = sum over g's row tiles of lhs^T @ dout
# ---------------------------------------------------------------------------

def _gmm_dw_kernel(tg_ref, na_ref, x_ref, dy_ref, o_ref, acc_ref, *, m_tiles):
    mi = pl.program_id(2)
    na = na_ref[0]
    g = tg_ref[mi]
    first = jnp.logical_or(
        mi == 0, tg_ref[jnp.maximum(mi - 1, np.int32(0))] != g)
    last = jnp.logical_or(
        mi == na - 1, tg_ref[jnp.minimum(mi + 1, np.int32(m_tiles - 1))] != g)

    @pl.when(mi < na)
    def _live():
        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gmm_dw(x, dy, tile_group, n_tiles, groups, dtype, *, tiles, interpret):
    """x [M, K], dy [M, N] -> [G, K, N]: each group's rows contracted."""
    m, k = x.shape
    n = dy.shape[1]
    m_tiles = tile_group.shape[0]
    tm = m // m_tiles
    tk, tn = tiles or gmm_tiles(k, n)
    nk, nn = k // tk, n // tn

    def row(mi, na):
        return jnp.minimum(mi, na[0] - 1)

    def x_map(ki, ni, mi, tg, na):
        return row(mi, na), ki

    def dy_map(ki, ni, mi, tg, na):
        return row(mi, na), ni

    def o_map(ki, ni, mi, tg, na):
        return tg[row(mi, na)], ki, ni

    item = x.dtype.itemsize
    vmem = 2 * (tm * tk * item + tm * tn * item
                + tk * tn * jnp.dtype(dtype).itemsize) + 2 * tk * tn * 4
    return pl.pallas_call(
        functools.partial(_gmm_dw_kernel, m_tiles=m_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nk, nn, m_tiles),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((tm, tn), dy_map)],
            out_specs=pl.BlockSpec((1, tk, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary"), vmem),
        interpret=interpret,
        name=_KERNEL_NAMES["bwd_w"],
    )(tile_group, n_tiles, x, dy)


# ---------------------------------------------------------------------------
# the differentiable product
# ---------------------------------------------------------------------------

def grouped_matmul(lhs, rhs, tile_group, n_tiles, tiles=None, interpret=None):
    """lhs [M, K] (rows in ``group_layout``'s order) times rhs [G, K, N],
    each row tile by its group's matrix -> [M, N] in lhs's dtype. Rows of
    dead tiles are not written, in the product and in lhs's gradient alike.
    ``tiles``: None (``gmm_tiles`` chooses) or {"fwd": (tk, tn), "bwd_x":
    (tk, tn), "bwd_w": (tk, tn)}."""
    return _grouped(lhs, rhs, tile_group, n_tiles,
                    tuple(sorted((tiles or {}).items())),
                    pallas_interpret() if interpret is None else interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(lhs, rhs, tile_group, n_tiles, tiles, interpret):
    return _gmm(lhs, rhs, tile_group, n_tiles, trans=False,
                tiles=dict(tiles).get("fwd"), interpret=interpret)


def _gm_fwd(lhs, rhs, tile_group, n_tiles, tiles, interpret):
    out = _grouped(lhs, rhs, tile_group, n_tiles, tiles, interpret)
    return out, (lhs, rhs, tile_group, n_tiles)


def _gm_bwd(tiles, interpret, res, dout):
    lhs, rhs, tile_group, n_tiles = res
    tiles = dict(tiles)
    dlhs = _gmm(dout, rhs, tile_group, n_tiles, trans=True,
                tiles=tiles.get("bwd_x"), interpret=interpret)
    drhs = _gmm_dw(lhs, dout, tile_group, n_tiles, rhs.shape[0], rhs.dtype,
                   tiles=tiles.get("bwd_w"), interpret=interpret)
    zero = np.zeros(tile_group.shape, jax.dtypes.float0)
    return dlhs, drhs, zero, np.zeros(n_tiles.shape, jax.dtypes.float0)


_grouped.defvjp(_gm_fwd, _gm_bwd)
