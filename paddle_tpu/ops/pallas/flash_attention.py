"""Blockwise flash attention as a Pallas TPU kernel.

TPU-native equivalent of the reference's dynloaded flash-attn CUDA library
(paddle/phi/backends/dynload/flashattn.h; call sites
paddle/phi/kernels/gpu/flash_attn_kernel.cu:91,199). Contract matches the
reference op (paddle/phi/api/yaml/ops.yaml:978-989 flash_attn entry): q/k/v
are [batch, seqlen, num_heads, head_dim]; GQA (kv heads < q heads); causal
masking uses the (Sk - Sq)-offset diagonal; softmax statistics (lse) are
produced by the forward pass and consumed by the backward kernels; dropout
follows the reference's (seed, offset) determinism contract — the mask is a
pure function of (seed, batch*head, query index, key index), replayed
bit-exactly by the backward kernels instead of being stored.

Design (online-softmax, Dao et al. 2022, re-derived for the MXU):
- tiles: ``tile_plan`` chooses each kernel's (block_q, block_k) from what a
  call can see at trace time (lengths, head dim, item sizes, whether bias,
  segment ids or dropout ride along, a VMEM budget): the largest tile that
  fits, up to 1024 x 1024, since a grid step costs about half a microsecond
  with nothing in it and 128 x 128 blocks spent the kernels' time there
  (PERF.md, "PR 26"). A call whose estimate passes Mosaic's 16 MiB of
  scoped VMEM asks for what it needs (``_compiler_params``). Explicit
  block arguments win over the plan. Every call leaves a
  ``flash::tile_plan`` trace event and a count in ``TILE_PLAN_TALLY``; the
  Mosaic calls are jitted on their own, so a model's equal layers share
  one trace and one lowering of each.
- sub-tiles: a grid step does not compute a (block_q, block_k) tile in one
  piece where an edge of what attention may see crosses it. It walks it in
  the plan's (sub_q, sub_k) sub-tiles, static slices of the refs it already
  holds: what lies above the diagonal, below a window's band or in the
  padded keys is never entered, and only a sub-tile that an edge crosses is
  masked. Where the edges lie in a tile is one of a few static facts, so a
  kernel holds one straight-line body for each kind of tile its grid meets
  (PERF.md, "PR 31"). A tile that nothing crosses, and every tile of a call
  that is neither causal nor padded, is computed in one piece, unmasked.
  ``_tile`` decides all of this once a call; its ``Tile`` is what the
  kernel lowers and what the event reports.
- route: ``attention_route`` decides from the same kind of facts whether a
  dense call takes these kernels or XLA's attention.
- forward: grid (batch*heads, q_blocks, k_blocks) with the k dimension
  innermost/sequential ("arbitrary"); VMEM scratch carries the running
  (acc, m, l) across k blocks; causal blocks above the diagonal are skipped
  with pl.when, and their index maps are clamped at the diagonal, so a
  skipped step asks for the block it already holds and moves no data.
- backward: recomputes p from q,k and the saved lse instead of storing the
  S×S probability matrix. One kernel (``flash_bwd``) on the grid
  (batch*kv_heads, k_blocks, group_heads, q_blocks) — the last two dims
  sweep the kv head's q-head group with affine index maps — computes each
  visible piece's scores, p, dp and ds once for dk, dv and dq: dk/dv
  accumulate per key block, dq in float32 for all the group's q rows
  across the sweep, leaving by DMA when a query block's last key block is
  done. Where that accumulator does not fit, or a full bias asks for dbias
  tiles, the call keeps two kernels: one for dq (+ dbias) on the forward's
  grid, one for dk/dv on that grid (``backward_rule``).
- GQA is expressed in the BlockSpec index maps (kv block index derived from
  the q head index), so kv tensors are never materialised per-q-head in
  the forward; the dkv kernel accumulates dk/dv over the group's q-heads
  in-grid (no per-q-head dk/dv in HBM, no post-kernel group sum).
- dropout: the keep-mask is a murmur3-finalizer hash of the global (row,
  col) element index mixed with a per-(batch*head) seed — plain int32
  vector ops, so the identical mask is produced by the compiled Mosaic
  kernel, interpret mode, and the XLA fallback (which shares
  ``dropout_keep_mask`` below); softmax statistics (l, lse) are computed
  from the *undropped* probabilities, dropout scales only the value
  accumulation, matching dropout-after-softmax semantics.
- additive bias (attn_mask) broadcastable over batch/head/query dims rides
  in as an extra block input; its gradient is emitted by the dq kernel and
  sum-reduced onto the broadcast shape.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...core import flags as _flags
from ...core.dispatch import register_op_impl
from .common import _Z, pallas_interpret


__all__ = ["flash_attention_pallas", "flash_attention_ext",
           "flash_chunk_fwd", "flash_chunk_bwd",
           "dropout_keep_mask", "seed_from_key"]

# typed f32 constants: under the package-wide jax_enable_x64 a bare Python
# float handed to jnp.where/jnp.maximum enters the kernel jaxpr as an f64
# scalar, and Mosaic has no f64 -> f32 cast ("Unsupported cast")
_NEG_INF = np.float32("-inf")
_F0 = np.float32(0.0)
_F1 = np.float32(1.0)
_LANES = 128
# the names the device trace finds the kernels by (benchmarks/metrics)
_KERNEL_NAMES = {"fwd": "flash_fwd", "dq": "flash_bwd_dq",
                 "dkv": "flash_bwd_dkv", "bwd": "flash_bwd"}
# a call with a window is another kernel to the trace's readers
# (benchmarks/metrics/window_attn_roofline.py)
_WIN_KERNEL_NAMES = {"fwd": "flash_win_fwd", "dq": "flash_win_bwd_dq",
                     "dkv": "flash_win_bwd_dkv", "bwd": "flash_win_bwd"}
# one count per lowered pallas_call, by (kernel name, bq, bk, sub_q, sub_k):
# trace time only, nothing per step
TILE_PLAN_TALLY: collections.Counter = collections.Counter()


def _kv_index(bh, hq, hk):
    """Flattened (b*Hq) program index -> flattened (b*Hk) kv index (GQA).

    All constants forced to i32: index maps lower through Mosaic, which
    rejects the i64 values the x64-enabled tracer would otherwise produce.
    """
    rep = np.int32(hq // hk)
    return (bh // np.int32(hq)) * np.int32(hk) + (bh % np.int32(hq)) // rep


# ---------------------------------------------------------------------------
# deterministic dropout mask (shared by the kernels, the XLA fallback, and
# the parity tests — the TPU analog of the reference's (seed, offset) pairs)
# ---------------------------------------------------------------------------

def _i32(v: int) -> np.int32:
    """uint32 bit-pattern as the int32 Mosaic vector units operate on."""
    return np.int32(v - (1 << 32) if v >= (1 << 31) else v)


_SIGN = _i32(0x80000000)


def _dropout_thresh(rate: float) -> np.int32:
    """keep iff hash >=u thresh, so P(drop) == rate. Returned pre-biased
    (^0x80000000) so the kernels compare with a plain SIGNED >=: Mosaic's
    vector ISA is int32 — every hash op below is wraparound-identical in
    int32, and unsigned compare is signed compare of sign-flipped values."""
    t = np.uint32(min(int(float(rate) * 2 ** 32), 2 ** 32 - 1))
    return _i32(int(t)) ^ _SIGN


def _srl(h, n):
    return jax.lax.shift_right_logical(h, np.int32(n))


def _mix_seed(seed, bh):
    """Per-(batch*head) 32-bit seed: murmur-style avalanche of seed ^ bh
    (int32 wraparound arithmetic == the uint32 reference bit-for-bit)."""
    h = seed.astype(jnp.int32) ^ (jnp.int32(bh) * _i32(0x9E3779B1))
    h = h * _i32(0x85EBCA6B)
    h = h ^ _srl(h, 7)
    h = h * _i32(0xC2B2AE35)
    h = h ^ _srl(h, 15)
    return h


def _keep_block(seed_bh, q_start, k_start, bq, bk, sk, thresh):
    """(bq, bk) bool keep-mask for the block at (q_start, k_start).

    The hash input is the *global* element index row * Sk + col with the
    real (unpadded) Sk stride — padded key columns hash to colliding
    indices, but those positions are masked out by the sk_real check before
    they ever matter. ``thresh`` comes pre-biased from _dropout_thresh."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    h = (rows * np.int32(sk) + cols) ^ seed_bh
    h = h * _i32(0x85EBCA6B)
    h = h ^ _srl(h, 13)
    h = h * _i32(0xC2B2AE35)
    h = h ^ _srl(h, 16)
    return (h ^ _SIGN) >= thresh


def seed_from_key(key) -> jax.Array:
    """Fold a jax PRNG key (typed or raw uint32 pair) to the (1,)-shaped
    int32 seed the kernels consume."""
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = jnp.asarray(key)
    data = data.astype(jnp.uint32).reshape(-1)
    folded = data[0]
    for i in range(1, int(data.shape[0])):
        folded = folded ^ data[i]
    return folded.astype(jnp.int32).reshape(1)


def dropout_keep_mask(seed, bh_total, sq, sk, rate):
    """Full (BH, Sq, Sk) keep-mask — the exact mask the kernels generate,
    computed with plain XLA ops. Used by the XLA fallback (so both impls
    drop the same positions for a given seed) and by the parity tests."""
    thresh = _dropout_thresh(rate)
    seed = jnp.asarray(seed).reshape(-1)[0]

    def one(bh):
        return _keep_block(_mix_seed(seed, bh), 0, 0, sq, sk, sk, thresh)
    return jax.vmap(one)(jnp.arange(bh_total, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _hide(s, q_start, k_start, *, pad_keys, causal, offset, sk_real,
          qseg=None, kseg=None, seg_causal=False, window=None):
    """The score block whose first score is (q_start, k_start) with what
    attention may not see set to -inf: padded key columns, what lies above
    the (Sk - Sq)-offset causal diagonal, what lies ``window`` or more keys
    below it, other segments (``qseg`` (rows, 1) and ``kseg`` (1, cols)
    encoded words). A term is built only where it can hide something."""
    mask = None
    if pad_keys or causal:
        kidx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if pad_keys:
        mask = kidx < sk_real
    if causal:
        qidx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        diag = kidx <= qidx + offset
        mask = diag if mask is None else mask & diag
        if window is not None:
            mask = mask & (kidx > qidx + np.int32(offset - window))
    if qseg is not None:  # varlen packing: never across sequences
        seg = _seg_mask(qseg, kseg, seg_causal)
        mask = seg if mask is None else mask & seg
    return s if mask is None else jnp.where(mask, s, _NEG_INF)


def _when_visible(body, q_start, k_start, bq, *, causal, offset, bk=None,
                  window=None, inside=None):
    """Run ``body`` unless the (bq, bk) tile at (q_start, k_start) lies wholly
    above the causal diagonal (its first key column beyond the horizon of
    its last query row), wholly below the window's band (its last key
    column ``window`` or more behind its first query row), or, on a band
    grid, past the operand's end (``inside`` false). This is the skip at
    the grain of a grid step: a skipped step moves no data (the index maps
    clamp). Inside a tile that runs, ``body`` skips at the grain of the
    sub-tile (``_key_subs``, ``_query_subs``)."""
    run = True
    if causal:
        run = k_start <= q_start + bq - 1 + offset
    if window is not None:
        run = jnp.logical_and(run, k_start + np.int32(bk - 1)
                              > q_start + np.int32(offset - window))
        run = jnp.logical_and(run, inside)
    pl.when(run)(body)


# ---------------------------------------------------------------------------
# sub-tiles: a grid step does not compute its (bq, bk) tile in one piece
# where an edge of what attention may see crosses it. It walks it in (sub_q,
# sub_k) pieces of the refs it already holds: what lies above the diagonal,
# below the band or in the padding is never entered, and only a piece that
# an edge crosses is masked. Where the edges lie in a tile is one of a few
# static facts (``_tile_kinds``), so a walk is straight-line code over
# static slices, one body a kind: a loop with dynamic bounds was measured
# and lost more to its serial trips than the skip won (PERF.md, "PR 31").
# The same Python arithmetic lays out the walks and gives the
# ``flash::tile_plan`` event its counts.
# ---------------------------------------------------------------------------

def _pieces(vis_lo, vis_hi, plain_lo, plain_hi, sub, n):
    """Of ``n`` pieces of length ``sub`` laid from 0: (lo, a, b, hi) where
    [lo, hi) are the pieces that hold a position of [vis_lo, vis_hi) and
    [a, b), within them, those that lie wholly inside [plain_lo, plain_hi).
    [lo, a) and [b, hi) are then the pieces an edge crosses."""
    span = n * sub

    def whole_below(x):     # pieces that end at or before x
        return min(max(x, 0), span) // sub

    def begun_below(x):     # pieces that start before x
        return (min(max(x, 0), span) + sub - 1) // sub
    lo = whole_below(vis_lo)
    hi = max(begun_below(vis_hi), lo)
    a = min(max(begun_below(plain_lo), lo), hi)
    b = min(max(whole_below(plain_hi), a), hi)
    return lo, a, b, hi


def _key_subs(r0, sub_q, k_start, sub_k, nsk, *, causal, offset, window,
              sk_real):
    """The key sub-tiles, of the ``nsk`` from ``k_start``, that the query
    rows ``r0 .. r0 + sub_q - 1`` enter: ``_pieces`` over key columns. A row
    r sees the real keys in (r + offset - window, r + offset]."""
    real = sk_real - k_start
    vis_lo = plain_lo = 0
    vis_hi = plain_hi = real
    if causal:
        vis_hi = min(r0 + sub_q + offset - k_start, real)
        plain_hi = min(r0 + offset + 1 - k_start, real)
    if window is not None:
        vis_lo = r0 + offset - window + 1 - k_start
        plain_lo = vis_lo + sub_q - 1
    return _pieces(vis_lo, vis_hi, plain_lo, plain_hi, sub_k, nsk)


def _query_subs(c0, sub_k, q_start, sub_q, nsq, *, causal, offset, window,
                sk_real):
    """The query sub-tiles, of the ``nsq`` from ``q_start``, that see the key
    columns ``c0 .. c0 + sub_k - 1``: ``_pieces`` over query rows. A real key
    c is seen by the rows in [c - offset, c - offset + window); a piece that
    holds a padded key is masked whole, one of padded keys alone is left."""
    vis_lo = plain_lo = 0
    vis_hi = plain_hi = nsq * sub_q
    if causal:
        vis_lo = c0 - offset - q_start
        plain_lo = vis_lo + sub_k - 1
    if window is not None:
        plain_hi = c0 + window - offset - q_start
        vis_hi = plain_hi + sub_k - 1
    if c0 >= sk_real:
        vis_hi = 0
    if c0 + sub_k > sk_real:
        plain_hi = 0
    return _pieces(vis_lo, vis_hi, plain_lo, plain_hi, sub_q, nsq)


# a tile's kind is (delta, real). ``delta`` places the diagonal in the tile,
# q_start + offset - k_start: the column of the tile that its first row sees
# last; an int where the diagonal or the band's lower edge crosses the tile,
# None where neither does, _ANY for every tile one of them or the padding
# touches, where the tiles are not walked. ``real`` counts the tile's real
# keys where it holds padded ones too, else None.
_ANY = "any"
# the walked bodies a kernel may hold. This bounds the kernel's text, which
# grows by one unrolled body a kind, and is no tuned number: the plan's own
# tiles meet at most three kinds (square tiles on a diagonal at a multiple of
# the side, one of them padded). A call that meets more (explicit block sides
# that differ and share no large divisor with the offset) is not walked.
_MAX_WALKS = 4


def _tile_kinds(bq, bk, nq, nk, *, causal, offset, window, sk_real):
    """The kinds of the tiles that run, over the whole grid of a call."""
    kinds = set()
    for qi in range(nq):
        lo, _, _, hi = _key_subs(qi * bq, bq, 0, bk, nk, causal=causal,
                                 offset=offset, window=window,
                                 sk_real=sk_real)
        for ki in range(lo, hi):
            delta = qi * bq + offset - ki * bk
            inside = not causal or (delta >= bk - 1 and (
                window is None or delta <= window - bq))
            real = sk_real - ki * bk
            kinds.add((None if inside else delta,
                       real if real < bk else None))
    return kinds


def _tile_is(kind, q_start, k_start, ki, *, bq, bk, causal, offset, window,
             nk_all, pad_keys):
    """Whether the tile at (q_start, k_start), key block ``ki`` of
    ``nk_all``, is of ``kind``: traced, all i32."""
    delta, real = kind
    here = q_start + np.int32(offset) - k_start
    inside = True
    if causal:
        inside = here >= np.int32(bk - 1)
        if window is not None:
            inside = jnp.logical_and(inside, here <= np.int32(window - bq))
    last = ki == np.int32(nk_all - 1)
    whole = jnp.logical_not(last) if pad_keys else True
    if delta == _ANY:
        return jnp.logical_not(jnp.logical_and(inside, whole))
    hit = inside if delta is None else here == np.int32(delta)
    return jnp.logical_and(hit, whole if real is None else last)


def _walks(sweeps, kind, bq, bk, sub_q, sub_k, *, causal, window, segments):
    """The static walk of a tile of ``kind``, in the tile's own rows and
    columns: [(outer, inner, runs, local)] with ``outer`` the slice of the
    side a kernel accumulates over (``sweeps`` "k": fwd and dq, ``sub_q``
    query rows; "q": dkv, ``sub_k`` keys), ``inner`` the contiguous slice
    of the other side that those enter, ``runs`` its cut into [(first,
    last, masked)] from the slice's start, and ``local`` the edges as
    ``_hide`` takes them in the tile's own coordinates."""
    delta, real = kind
    local = dict(causal=causal and delta is not None, offset=delta or 0,
                 window=window if delta is not None else None,
                 sk_real=bk if real is None else real)
    out = []
    for o in range((bq // sub_q) if sweeps == "k" else (bk // sub_k)):
        if sweeps == "k":
            sub_o, sub_i = sub_q, sub_k
            lo, a, b, hi = _key_subs(o * sub_q, sub_q, 0, sub_k, bk // sub_k,
                                     **local)
        else:
            sub_o, sub_i = sub_k, sub_q
            lo, a, b, hi = _query_subs(o * sub_k, sub_k, 0, sub_q,
                                       bq // sub_q, **local)
        cuts = [(lo, hi, True)] if segments else \
            [(lo, a, True), (a, b, False), (b, hi, True)]
        runs = [((x - lo) * sub_i, (y - lo) * sub_i, masked)
                for x, y, masked in cuts if y > x]
        if runs:
            out.append((slice(o * sub_o, (o + 1) * sub_o),
                        slice(lo * sub_i, hi * sub_i), runs,
                        dict(local, pad_keys=real is not None)))
    return out


def _is_walked(kind, bq, bk, sub_q, sub_k):
    """Whether a tile of ``kind`` is walked in its sub-tile: where an edge
    or the padding touches it and the sub-tile is not the tile itself. A
    tile nothing touches has nothing to skip: one piece, the largest the
    MXU is fed with."""
    return kind != (None, None) and (sub_q, sub_k) != (bq, bk)


def _hide_runs(s, axis, runs, hide):
    """``s`` with ``hide(piece, first)`` in place of each masked run of its
    ``axis``: the runs start at whole sublanes or lanes, so cutting and
    joining moves nothing."""
    if len(runs) == 1:
        (first, _, masked), = runs
        return hide(s, first) if masked else s
    parts = []
    for first, last, masked in runs:
        piece = jax.lax.slice_in_dim(s, first, last, axis=axis)
        parts.append(hide(piece, first) if masked else piece)
    return jnp.concatenate(parts, axis=axis)


def _attend_tile(attend, sweeps, kinds, q_start, k_start, ki, qseg_ref,
                 kseg_ref, *, bq, bk, sub_q, sub_k, nk_all, pad_keys, causal,
                 offset, sk_real, window, inside, seg_causal):
    """What a kernel does with the tile at (q_start, k_start), key block
    ``ki`` of ``nk_all``, unless no query row of it sees a key of it
    (``_when_visible``): ``attend(rows, cols, hide)`` on static slices of
    the tile, the scores of each passed through ``hide(s)``. One body for
    each of the ``kinds`` of tile the grid meets, run where the tile is of
    that kind: walked in (sub_q, sub_k) as ``sweeps`` says (``_walks``),
    with the edges in the tile's own rows and columns, or in one piece,
    with the call's edges where one touches the tile (``_ANY``) and the
    segment ids alone, if any, where none does."""
    edges = dict(pad_keys=pad_keys, causal=causal, offset=offset,
                 sk_real=sk_real, window=window)
    whole = slice(None)

    def segs(rows, cols):
        if qseg_ref is None:
            return {}
        return dict(qseg=qseg_ref[0, rows, :], kseg=kseg_ref[0, :, cols],
                    seg_causal=seg_causal)

    def one_piece(kind):
        on = edges if kind[0] == _ANY else dict(
            edges, causal=False, pad_keys=False, window=None)
        attend(whole, whole, lambda s: _hide(s, q_start, k_start, **on,
                                             **segs(whole, whole)))

    def walked(kind):
        axis = 1 if sweeps == "k" else 0      # the side the runs cut
        for outer, inner, runs, local in _walks(
                sweeps, kind, bq, bk, sub_q, sub_k, causal=causal,
                window=window, segments=qseg_ref is not None):
            rows, cols = (outer, inner) if sweeps == "k" else (inner, outer)

            def hide(piece, first, rows=rows, cols=cols, local=local):
                at = [rows.start, cols.start]
                at[axis] += first
                r, c = (slice(o, o + n) for o, n in zip(at, piece.shape))
                return _hide(piece, *at, **local, **segs(r, c))
            attend(rows, cols, functools.partial(
                _hide_runs, axis=axis, runs=runs, hide=hide))

    def bodies():
        for kind in kinds:
            body = functools.partial(
                walked if _is_walked(kind, bq, bk, sub_q, sub_k)
                else one_piece, kind)
            if len(kinds) == 1:
                body()
            else:
                pl.when(_tile_is(kind, q_start, k_start, ki, bq=bq, bk=bk,
                                 causal=causal, offset=offset, window=window,
                                 nk_all=nk_all, pad_keys=pad_keys))(body)
    _when_visible(bodies, q_start, k_start, bq, causal=causal, offset=offset,
                  bk=bk, window=window, inside=inside)


def _band(bq, bk, offset, window, nq, nk, py=False):
    """The band of a windowed causal call, in blocks: (k_first, k_last,
    q_first, q_last). A query block ``qi`` sees the key blocks
    ``k_first(qi) .. k_last(qi)``, a key block ``ki`` is seen by the query
    blocks ``q_first(ki) .. q_last(ki)``. ``py`` gives them over Python
    ints (the grid's extent, at trace time); otherwise all i32, for index
    maps and kernel bodies, which lower through Mosaic."""
    mx, mn = (max, min) if py else (jnp.maximum, jnp.minimum)
    c = int if py else np.int32

    def k_first(qi):
        return mx(qi * c(bq) + c(offset - window + 1), c(0)) // c(bk)

    def k_last(qi):
        return mn(mx(qi * c(bq) + c(bq - 1 + offset), c(0)) // c(bk),
                  c(nk - 1))

    def q_first(ki):
        return mx(ki * c(bk) - c(offset), c(0)) // c(bq)

    def q_last(ki):
        return mn(mx(ki * c(bk) + c(bk + window - 2 - offset), c(0))
                  // c(bq), c(nq - 1))
    return k_first, k_last, q_first, q_last


def _band_extent(first, last, n):
    """The most blocks any of the ``n`` outer blocks has in its band: the
    inner extent of a windowed call's grid."""
    return max(1, max(last(i) - first(i) + 1 for i in range(n)))


def _band_grid(sweeps, window, bq, bk, offset, nq, nk):
    """(inner extent of the grid, the kernels' names, the band's keywords of
    the kernel body) of a call that ``sweeps`` "k" (fwd, dq: key blocks
    inside a query block) or "q" (dkv): every block, or with a ``window`` the
    band's."""
    if window is None:
        return (nk if sweeps == "k" else nq), _KERNEL_NAMES, {}
    k_first, k_last, q_first, q_last = _band(bq, bk, offset, window, nq, nk,
                                             py=True)
    extent = _band_extent(k_first, k_last, nq) if sweeps == "k" \
        else _band_extent(q_first, q_last, nk)
    return extent, _WIN_KERNEL_NAMES, dict(window=window, nq_all=nq,
                                           nk_all=nk)


def _fwd_kernel(*refs, scale, causal, offset, bq, bk, sub_q, sub_k, kinds,
                nk, sk_real, pad_keys, has_bias, has_seg, seg_causal, rate,
                window=None, nq_all=None, nk_all=None):
    """``nk`` is the grid's extent over key blocks: all of them, or with a
    ``window`` the band's (the step ``kj`` then works on key block
    ``k_first(qi) + kj`` of ``nk_all``). ``kinds`` are the kinds of tile the
    grid meets (``_tile``): a body each, walked in (sub_q, sub_k) for
    each ``sub_q`` query rows over the keys those rows see, or, where the
    sub-tile is the tile, computed in one piece."""
    scale = np.float32(scale)  # strong f64 scalars poison Mosaic under x64
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if rate > 0.0 else None
    o_ref, lse_ref = next(it), next(it)
    acc_ref, m_ref, l_ref = next(it), next(it), next(it)

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    ki, inside = kj, None
    if window is not None:
        ki = _band(bq, bk, offset, window, nq_all, nk_all)[0](qi) + kj
        inside = ki < np.int32(nk_all)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(rows, cols, hide):
        """The online softmax of the query rows ``rows`` over the keys
        ``cols`` of the tile, their scores passed through ``hide``."""
        # inputs stay in storage dtype (bf16 on the training path): the MXU
        # multiplies bf16 natively at 2x f32 rate, accumulating f32 via
        # preferred_element_type; scale is applied to the f32 product
        q = q_ref[0, rows, :]                                    # (sq, d)
        k = k_ref[0, cols, :]                                    # (sk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            brows = slice(None) if bias_ref.shape[1] == 1 else rows
            s = s + bias_ref[0, brows, cols].astype(jnp.float32)
        s = hide(s)
        m_prev = m_ref[rows, :]                                  # (sq, LANES)
        s_max = jnp.max(s, axis=1, keepdims=True)                # (sq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(s_max, m_prev.shape))
        # fully-masked-so-far rows keep m = -inf; use a safe exponent base so
        # exp() never sees (-inf) - (-inf)
        m_safe = jnp.where(m_new == _NEG_INF, _F0, m_new)
        alpha = jnp.exp(m_prev - m_safe)                         # (sq, LANES)
        p = jnp.exp(s - m_safe[:, :1])                           # (sq, sk)
        # l and lse come from the UNDROPPED probabilities (dropout applies
        # after softmax); only the value accumulation sees the mask
        l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        if rate > 0.0:
            keep = _keep_block(
                _mix_seed(seed_ref[0], bh), q_start + (rows.start or 0),
                k_start + (cols.start or 0), *s.shape, sk_real,
                _dropout_thresh(rate))
            p = jnp.where(keep, p * np.float32(1.0 / (1.0 - rate)), _F0)
        v = v_ref[0, cols, :]                                    # (sk, d)
        # probabilities ride the MXU in v's storage dtype (bf16-safe: p in
        # [0,1], the f32 accumulator keeps the sum exact enough)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[rows, :] = m_new

    _attend_tile(
        attend, "k", kinds, q_start, k_start, ki, qseg_ref, kseg_ref, bq=bq,
        bk=bk, sub_q=sub_q, sub_k=sub_k, nk_all=nk_all or nk,
        pad_keys=pad_keys, causal=causal, offset=offset, sk_real=sk_real,
        window=window, inside=inside, seg_causal=seg_causal)

    @pl.when(kj == nk - 1)
    def _fin():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, _F1, l)
        o_ref[0] = jnp.where(l > 0.0, acc_ref[...] / safe_l, _F0
                             ).astype(o_ref.dtype)
        # lse rides as a (bq, 1) trailing-unit ref (Mosaic rejects (1, bq)
        # blocks whose sublane dim is neither full nor a multiple of 8)
        m = m_ref[:, :1]
        lse_ref[0] = jnp.where(l > 0.0,
                               m + jnp.log(jnp.maximum(l, np.float32(1e-38))),
                               _NEG_INF)


def _last_key_block(qi, bq, bk, offset, nk):
    """The last of the ``nk`` key blocks that query block ``qi`` sees
    causally (block 0 where it sees none): traced, all i32."""
    return jnp.minimum(
        jnp.maximum(qi * np.int32(bq) + np.int32(bq - 1 + offset),
                    np.int32(0)) // np.int32(bk), np.int32(nk - 1))


def _key_block(causal, bq, bk, offset, nk, window=None, nq=None):
    """``kblk(qi, ki)``: the key block step (qi, ki) of the fwd/dq grid
    asks for. A causally skipped step asks for the last block its query
    block sees, which it already holds, so it moves no data. With a
    ``window`` the grid's steps count from the band's first block. All i32:
    index maps lower through Mosaic."""
    if not causal:
        return lambda qi, ki: ki
    if window is not None:
        k_first, k_last, _, _ = _band(bq, bk, offset, window, nq, nk)
        return lambda qi, kj: jnp.minimum(k_first(qi) + kj, k_last(qi))
    return lambda qi, ki: jnp.minimum(
        ki, _last_key_block(qi, bq, bk, offset, nk))


def _query_block(causal, bq, bk, offset, nq, window=None, nk=None):
    """``qblk(ki, qi)``: the query block step (ki, qi) of the dkv grid asks
    for. The steps before a key block's first visible query block ask for
    that block already, so they move no data and the sweep starts loaded.
    With a ``window`` the grid's steps count from that first block and end
    with the band."""
    if not causal:
        return lambda ki, qi: qi
    if window is not None:
        _, _, q_first, q_last = _band(bq, bk, offset, window, nq, nk)
        return lambda ki, qj: jnp.minimum(q_first(ki) + qj, q_last(ki))

    def qblk(ki, qi):
        first = jnp.maximum(ki * np.int32(bk) - np.int32(offset),
                            np.int32(0)) // np.int32(bq)
        return jnp.maximum(qi, jnp.minimum(first, np.int32(nq - 1)))
    return qblk


def _sub_tile_counts(kernel, bq, bk, sub_q, sub_k, nq, nk, *, causal, offset,
                     window, sk_real, segments):
    """(entered, masked) sub-tiles of one (batch, q head) of a call, by the
    arithmetic that lays out the kernels' walks. A tile computed in one
    piece (the sub-tile is the tile) is entered if it runs and masked if an
    edge or the padding touches it; under segment ids whatever is entered
    keeps its mask."""
    edges = dict(causal=causal, offset=offset, window=window,
                 sk_real=sk_real)
    entered = plain = 0
    for qi in range(nq):
        # the tiles that run are the pieces of a walk at the tile's grain
        t_lo, t_a, t_b, t_hi = _key_subs(qi * bq, bq, 0, bk, nk, **edges)
        if (sub_q, sub_k) == (bq, bk):
            entered, plain = entered + t_hi - t_lo, plain + t_b - t_a
            continue
        for ki in range(t_lo, t_hi):
            if kernel in ("dkv", "bwd"):
                walks = (_query_subs(ki * bk + j * sub_k, sub_k, qi * bq,
                                     sub_q, bq // sub_q, **edges)
                         for j in range(bk // sub_k))
            else:
                walks = (_key_subs(qi * bq + i * sub_q, sub_q, ki * bk,
                                   sub_k, bk // sub_k, **edges)
                         for i in range(bq // sub_q))
            for lo, a, b, hi in walks:
                entered, plain = entered + hi - lo, plain + b - a
    return entered, entered if segments else entered - plain


def _dq_rows(kernel, q3, kx):
    """The q rows whose dq ``kernel`` holds at once: all the kv head's
    (rep x Sq) in the one backward kernel, none elsewhere."""
    return (q3.shape[0] // kx.shape[0]) * q3.shape[1] if kernel == "bwd" \
        else 0


def _call_vmem(kernel, q3, kx, vx, bias3, dbias, has_seg, rate, tile):
    return _vmem_bytes(kernel, tile.bq, tile.bk, q3.shape[2],
                       q3.dtype.itemsize, kx.dtype.itemsize,
                       vx.dtype.itemsize,
                       bias3.dtype.itemsize if bias3 is not None else 0,
                       dbias, has_seg, rate > 0.0,
                       dq_rows=_dq_rows(kernel, q3, kx))


def _compiler_params(semantics, vmem):
    """``CompilerParams`` of a call whose VMEM estimate is ``vmem``. Mosaic's
    scoped VMEM default is 16 MiB; a tile whose estimate does not fit it
    asks for what it needs, with the room the estimate leaves out (spills,
    the compiler's own scratch)."""
    limit = None
    if vmem > _VMEM_DEFAULT_LIMIT * 3 // 4:
        limit = min(int(vmem * 1.5), _VMEM_MAX_LIMIT)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _record_call(kernel, q3, kx, vx, bias3, dbias, has_seg, rate, tile,
                 offset, causal, sk_real, window=None, split_by=None):
    """One call of ``kernel`` ("fwd", "dq", "dkv", "bwd") on its way to be
    lowered: a ``flash::tile_plan`` trace event and a count in
    ``TILE_PLAN_TALLY``. Python at trace time, outside the jitted call, so
    every call counts though equal calls share one lowering. The one
    backward kernel's event carries ``dq_resident_bytes``, its float32 dq
    accumulator; the split kernels' carry ``split_by``, why the call did
    not take it."""
    from ...profiler.tracing import trace_event
    bq, bk, sub_q, sub_k, _ = tile
    bhq, sq, _ = q3.shape
    nq, nk = sq // bq, kx.shape[1] // bk
    # blocks wholly above the diagonal: the steps causality skips
    skipped = sum(1 for qi in range(nq) for ki in range(nk)
                  if ki * bk > qi * bq + bq - 1 + offset) if causal else 0
    entered, masked = _sub_tile_counts(
        kernel, bq, bk, sub_q, sub_k, nq, nk, causal=causal, offset=offset,
        window=window, sk_real=sk_real, segments=has_seg)
    every = (sq // sub_q) * (kx.shape[1] // sub_k)
    attrs = dict(bq=bq, bk=bk, sub_q=sub_q, sub_k=sub_k,
                 sub_tiles=bhq * entered,
                 sub_tiles_skipped=bhq * (every - entered),
                 sub_tiles_masked=bhq * masked,
                 vmem_bytes=_call_vmem(kernel, q3, kx, vx, bias3, dbias,
                                       has_seg, rate, tile))
    if kernel == "bwd":
        attrs["dq_resident_bytes"] = _dq_acc_bytes(_dq_rows(kernel, q3, kx),
                                                   q3.shape[2])
    elif kernel in ("dq", "dkv"):
        attrs["split_by"] = split_by
    if window is None:
        name = _KERNEL_NAMES[kernel]
        attrs.update(grid_steps=bhq * nq * nk, skipped_steps=bhq * skipped)
    else:
        # the grid holds the band's steps only; of those, the ones past a
        # block's own band (the band is narrower at the sequence's ends)
        # are skipped in the kernel
        k_first, k_last, q_first, q_last = _band(bq, bk, offset, window, nq,
                                                 nk, py=True)
        if kernel in ("dkv", "bwd"):
            steps = nk * _band_extent(q_first, q_last, nk)
            run = sum(max(q_last(i) - q_first(i) + 1, 0) for i in range(nk))
        else:
            steps = nq * _band_extent(k_first, k_last, nq)
            run = sum(max(k_last(i) - k_first(i) + 1, 0) for i in range(nq))
        name = _WIN_KERNEL_NAMES[kernel]
        attrs.update(grid_steps=bhq * steps,
                     skipped_steps=bhq * (steps - run), window=window,
                     band_skipped_steps=bhq * (nq * nk - steps))
    TILE_PLAN_TALLY[(name, bq, bk, sub_q, sub_k)] += 1
    trace_event("flash::tile_plan", cat="kernel", kernel=name, **attrs)


class _Static(dict):
    """A dict of plain values that a jitted call takes as a static
    argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# the three calls are jitted on their own: a model's layers make equal calls,
# and a walked body is long (one straight-line piece a row block), so tracing
# and lowering it to Mosaic once a layer cost the GPT-2 cell's twelve layers
# 6 to 9 s of set-up. Equal calls share one trace, and one lowering a module
# (that cell's step: 3.5 s to trace and lower, 6.1 s before tiles were
# walked; PERF.md, "PR 31"); XLA inlines the calls, so the compiled step and
# its instructions' names are what they were.
_CALL_STATICS = ("hq", "hk", "causal", "scale", "offset", "sk_real", "tile",
                 "bias_maps", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_CALL_STATICS)
def _fwd(q3, k3, v3, bias3, seed, hq, hk, causal, scale, offset, sk_real,
         tile, bias_maps, interpret, qseg3=None, kseg3=None, window=None):
    """q3: (B*Hq, Sq, D) padded; k3/v3: (B*Hk, Sk, D) padded; bias3:
    (Bb*Hb, Sqb, Sk_pad) or None; seed: (1,) i32 or None; qseg3/kseg3:
    (B*Hq, Sq, 1) / (B*Hq, 1, Sk) i32 segment ids or None; ``tile`` the
    plan's ``Tile`` of the forward; ``bias_maps`` a ``_Static``. With a
    ``window`` the grid's last dim holds the band's key blocks only."""
    bhq, sq, d = q3.shape
    sk = k3.shape[1]
    bq, bk, sub_q, sub_k, kinds = tile
    nq, nk_all = sq // bq, sk // bk
    nk, names, band = _band_grid("k", window, bq, bk, offset, nq, nk_all)
    grid = (bhq, nq, nk)
    kv_map = functools.partial(_kv_index, hq=hq, hk=hk)
    has_bias = bias3 is not None
    has_seg = qseg3 is not None

    kblk = _key_block(causal, bq, bk, offset, nk_all, window, nq)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _Z)),
        pl.BlockSpec((1, bk, d),
                     lambda bh, qi, ki: (kv_map(bh), kblk(qi, ki), _Z)),
        pl.BlockSpec((1, bk, d),
                     lambda bh, qi, ki: (kv_map(bh), kblk(qi, ki), _Z)),
    ]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias_maps, bq, bk, kblk=kblk))
        args.append(bias3)
    if has_seg:
        in_specs.append(
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, _Z)))
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, qi, ki: (bh, _Z, kblk(qi, ki))))
        args += [qseg3, kseg3]
    if seed is not None:
        in_specs.append(pl.BlockSpec((1,), lambda bh, qi, ki: (_Z,), memory_space=pltpu.SMEM))
        args.append(seed)

    rate = bias_maps["rate"]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, offset=offset, bq=bq, bk=bk,
        sub_q=sub_q, sub_k=sub_k, kinds=kinds, nk=nk, sk_real=sk_real,
        pad_keys=sk != sk_real,
        has_bias=has_bias, has_seg=has_seg,
        seg_causal=bias_maps.get("seg_causal", False), rate=rate, **band)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _Z)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, _Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhq, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bhq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            _call_vmem("fwd", q3, k3, v3, bias3, False, has_seg, rate, tile)),
        interpret=interpret,
        name=names["fwd"],
    )(*args)
    return out, lse[..., 0]


def _fwd_impl(q3, k3, v3, bias3, seed, hq, hk, causal, scale, offset,
              sk_real, tile, bias_maps, interpret, qseg3=None, kseg3=None,
              window=None):
    """The forward kernel over padded operands, as ``_bwd_impl`` is the
    backward's: the call recorded, then made."""
    _record_call("fwd", q3, k3, v3, bias3, False, qseg3 is not None,
                 bias_maps["rate"], tile, offset, causal, sk_real, window)
    return _fwd(q3, k3, v3, bias3, seed, hq, hk, causal, scale, offset,
                sk_real, tile, _Static(bias_maps), interpret, qseg3, kseg3,
                window)


# ---------------------------------------------------------------------------
# bias plumbing: (B?, H?, Sq?, Sk) broadcastable bias -> flattened 3-D block
# input whose index map collapses broadcast dims
# ---------------------------------------------------------------------------

def _bias_shape4(bias):
    return (1,) * (4 - jnp.asarray(bias).ndim) + tuple(
        jnp.asarray(bias).shape)


def bias_supported(bias, B, Hq, Sq, Sk) -> bool:
    """Single source of truth for which bias layouts the kernels take:
    broadcastable to (B, Hq, Sq, Sk) with the Sk dim full."""
    Bb, Hb, Sqb, Skb = _bias_shape4(bias)
    return (Skb == Sk and Sqb in (1, Sq) and Bb in (1, B)
            and Hb in (1, Hq))


def _prep_bias(bias, B, Hq, Sq, Sk, bq, bk):
    """Normalise bias to (Bb*Hb, Sqb_pad, Sk_pad) + static map info.

    Supports any bias broadcastable to (B, Hq, Sq, Sk) where the Sk dim is
    full (singleton batch/head/query dims stay singleton — never
    materialised)."""
    if not bias_supported(bias, B, Hq, Sq, Sk):
        raise ValueError(f"bias shape {bias.shape} not broadcastable to "
                         f"({B},{Hq},{Sq},{Sk}) with full Sk")
    b4 = jnp.asarray(bias)
    while b4.ndim < 4:
        b4 = b4[None]
    Bb, Hb, Sqb, Skb = b4.shape
    b3 = b4.reshape(Bb * Hb, Sqb, Skb)
    pad_k = (-Skb) % bk
    pad_q = 0 if Sqb == 1 else (-Sqb) % bq
    if pad_k or pad_q:
        b3 = jnp.pad(b3, ((0, 0), (0, pad_q), (0, pad_k)))
    # full == dbias can be emitted tile-per-tile by the dq kernel with no
    # memory amplification; anything broadcast goes through the bounded
    # recompute path in _fa_bwd instead
    full = (Bb == B and Hb == Hq and Sqb == Sq)
    return b3, {"Bb": Bb, "Hb": Hb, "Sqb": Sqb, "B": B, "Hq": Hq,
                "full": full}


def _bias_row(maps, bh):
    Bb, Hb, Hq = maps["Bb"], maps["Hb"], maps["Hq"]
    b = bh // np.int32(Hq)
    h = bh % np.int32(Hq)
    return (b if Bb > 1 else np.int32(0)) * np.int32(Hb) + \
        (h if Hb > 1 else np.int32(0))


def _bias_spec(maps, bq, bk, kblk=None, qblk=None):
    """Bias block spec. ``kblk(qi, ki)`` gives the fwd/dq (bh, qi, ki) grid
    with its clamped key-block index; ``qblk(ki, qi)`` gives the dkv
    kernel's 4-D (bh, ki, r, qi) grid (bias + GQA expands kv, so r is
    always 0 and the q-head row is bh itself)."""
    Sqb = maps["Sqb"]
    bq_eff = 1 if Sqb == 1 else bq

    if qblk is not None:
        def idx4(bh, ki, r, qi):
            return (_bias_row(maps, bh),
                    np.int32(0) if Sqb == 1 else qblk(ki, qi), ki)
        return pl.BlockSpec((1, bq_eff, bk), idx4)

    def idx(bh, qi, ki):
        return (_bias_row(maps, bh),
                np.int32(0) if Sqb == 1 else qi, kblk(qi, ki))
    return pl.BlockSpec((1, bq_eff, bk), idx)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, scale, causal, offset, bq, bk, sub_q, sub_k, kinds,
               nk, sk_real, pad_keys, has_bias, has_seg, seg_causal,
               emit_dbias, rate, window=None, nq_all=None, nk_all=None):
    """The forward's grid, kinds of tile and walk: for each ``sub_q`` query
    rows the keys they see, dq summed over them."""
    scale = np.float32(scale)  # strong f64 scalars poison Mosaic under x64
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if rate > 0.0 else None
    dq_ref = next(it)
    dbias_ref = next(it) if emit_dbias else None
    dq_acc = next(it)

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    ki, inside = kj, None
    if window is not None:
        ki = _band(bq, bk, offset, window, nq_all, nk_all)[0](qi) + kj
        inside = ki < np.int32(nk_all)
    q_start, k_start = qi * bq, ki * bk

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if emit_dbias:
        # every (qi, ki) block owns exactly one dbias tile; what is skipped,
        # a whole tile or a part of one, must still be written (zeros), so
        # zero first and let the pieces that run overwrite
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    def attend(rows, cols, hide):
        """dq of the query rows ``rows`` from the keys ``cols`` of the tile,
        their scores passed through ``hide``."""
        # storage-dtype MXU inputs, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0, rows, :]
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, rows, :]                               # (sq, 1)
        lse_safe = jnp.where(lse == _NEG_INF, _F0, lse)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            brows = slice(None) if bias_ref.shape[1] == 1 else rows
            s = s + bias_ref[0, brows, cols].astype(jnp.float32)
        s = hide(s)
        p = jnp.exp(s - lse_safe)                               # (sq, sk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _keep_block(
                _mix_seed(seed_ref[0], bh), q_start + (rows.start or 0),
                k_start + (cols.start or 0), *s.shape, sk_real,
                _dropout_thresh(rate))
            dp = jnp.where(keep, dp * np.float32(1.0 / (1.0 - rate)), _F0)
        ds = p * (dp - delta_ref[0, rows, :])                   # (sq, sk)
        if emit_dbias:
            dbias_ref[0, rows, cols] = ds.astype(dbias_ref.dtype)
        dq_acc[rows, :] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32) * scale

    _attend_tile(
        attend, "k", kinds, q_start, k_start, ki, qseg_ref, kseg_ref, bq=bq,
        bk=bk, sub_q=sub_q, sub_k=sub_k, nk_all=nk_all or nk,
        pad_keys=pad_keys, causal=causal, offset=offset, sk_real=sk_real,
        window=window, inside=inside, seg_causal=seg_causal)

    @pl.when(kj == nk - 1)
    def _fin():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, offset, bq, bk, sub_q, sub_k, kinds, nq,
                nk_grid, rep, sk_real, pad_keys, has_bias, has_seg,
                seg_causal, rate, with_dq=False, window=None, nq_all=None,
                nk_all=None):
    """Grid (B*Hk, nk, rep, nq): one kv-head block accumulates dk/dv over
    ALL rep q-heads of its group (GQA-native — no rep-expanded K/V in HBM
    and no post-kernel sum over q-head groups). rep == 1 is plain MHA.
    The (r, qi) sweep rides two AFFINE grid dims — the earlier folded
    j = r*nq + qi form put div/mod into every q-side index map, which
    blocks Mosaic's cross-iteration DMA pipelining (suspected cause of
    the r3 GQA fwd_bwd 0.837; on-chip recapture verifies). A tile is
    walked the other way round from fwd and dq: for each ``sub_k`` keys,
    the query rows that see them.

    ``with_dq`` makes it the one backward kernel (``flash_bwd``): each
    piece's ``ds`` enters dq too, summed in float32 for all the kv head's
    q rows (``dq_acc``, (rep, Sq, D)) over the whole sweep. A query block's
    dq is final at the last key block it sees, and leaves then, in q's
    dtype, by a DMA to the output in HBM that the next write (or the kv
    head's last step) waits for."""
    scale = np.float32(scale)  # strong f64 scalars poison Mosaic under x64
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if rate > 0.0 else None
    dk_ref, dv_ref = next(it), next(it)
    dq_hbm = next(it) if with_dq else None
    dk_acc, dv_acc = next(it), next(it)
    if with_dq:
        dq_acc, dq_out, dq_sem, dq_pending = (next(it), next(it), next(it),
                                              next(it))

    ki = pl.program_id(1)
    r = pl.program_id(2)                  # q-head within the kv group
    qj = pl.program_id(3)                 # q block (of the band, if any)
    qi, inside = qj, None
    if window is not None:
        qi = _band(bq, bk, offset, window, nq_all, nk_all)[2](ki) + qj
        inside = qi < np.int32(nq_all)
    # global q-head row — the dropout mask replay is per q-head (fwd hashes
    # with the q-head program index)
    bh = pl.program_id(0) * np.int32(rep) + r
    q_start, k_start = qi * bq, ki * bk

    @pl.when(jnp.logical_and(r == 0, qj == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if with_dq:
        @pl.when(jnp.logical_and(ki == 0, jnp.logical_and(r == 0, qj == 0)))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            dq_pending[0] = np.int32(0)

        def dq_rows(r0, n):
            start = q_start + np.int32(r0)
            return pl.ds(pl.multiple_of(start, 8) if bq % 8 == 0 else start,
                         n)

        def dq_copy(start):
            return pltpu.make_async_copy(
                dq_out, dq_hbm.at[bh, pl.ds(start, bq), :], dq_sem)

        def dq_wait():
            @pl.when(dq_pending[0] == 1)
            def _():
                dq_copy(np.int32(0)).wait()
                dq_pending[0] = np.int32(0)

    def attend(rows, cols, hide):
        """dk and dv of the keys ``cols`` from the query rows ``rows`` of
        the tile, their scores passed through ``hide``."""
        # storage-dtype MXU inputs, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0, rows, :]
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, rows, :]                               # (sq, 1)
        lse_safe = jnp.where(lse == _NEG_INF, _F0, lse)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            brows = slice(None) if bias_ref.shape[1] == 1 else rows
            s = s + bias_ref[0, brows, cols].astype(jnp.float32)
        s = hide(s)
        p = jnp.exp(s - lse_safe)                               # (sq, sk)
        if rate > 0.0:
            keep = _keep_block(
                _mix_seed(seed_ref[0], bh), q_start + (rows.start or 0),
                k_start + (cols.start or 0), *s.shape, sk_real,
                _dropout_thresh(rate))
            inv = np.float32(1.0 / (1.0 - rate))
            p_v = jnp.where(keep, p * inv, _F0)
        else:
            p_v = p
        dv_acc[cols, :] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (sk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = jnp.where(keep, dp * inv, _F0)
        ds = p * (dp - delta_ref[0, rows, :])
        # s = scale * (q . k) with q unscaled on load, so dk = scale *
        # ds^T @ q carries the factor explicitly
        ds_q = ds.astype(q.dtype)
        dk_acc[cols, :] += jax.lax.dot_general(
            ds_q, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (sk, d)
        if with_dq:     # the dq kernel's product, of the same ds
            r0, r1 = rows.start or 0, bq if rows.stop is None else rows.stop
            dq_acc[r, dq_rows(r0, r1 - r0), :] += jax.lax.dot(
                ds_q if k.dtype == q.dtype else ds.astype(k.dtype), k,
                preferred_element_type=jnp.float32) * scale      # (sq, d)

    _attend_tile(
        attend, "q", kinds, q_start, k_start, ki, qseg_ref, kseg_ref, bq=bq,
        bk=bk, sub_q=sub_q, sub_k=sub_k, nk_all=nk_grid, pad_keys=pad_keys,
        causal=causal, offset=offset, sk_real=sk_real, window=window,
        inside=inside, seg_causal=seg_causal)

    if with_dq:
        # the query block's last key block: every key block when not causal
        done = ki == np.int32(nk_grid - 1)
        if causal:
            done = ki == _last_key_block(qi, bq, bk, offset, nk_grid)
        if window is not None:
            done = jnp.logical_and(done, inside)

        @pl.when(done)
        def _write_dq():
            dq_wait()
            # a head under 128 lanes leaves in whole lanes, the rest unread
            d = dq_acc.shape[2]
            dq_out[:, :d] = dq_acc[r, dq_rows(0, bq), :].astype(dq_out.dtype)
            dq_copy(q_start).start()
            dq_pending[0] = np.int32(1)

        @pl.when(jnp.logical_and(
            ki == np.int32(nk_grid - 1), jnp.logical_and(
                r == np.int32(rep - 1), qj == np.int32(nq - 1))))
        def _drain():
            dq_wait()

    @pl.when(jnp.logical_and(r == np.int32(rep - 1),
                             qj == np.int32(nq - 1)))
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS)
def _bwd_dq(q3, kx, vx, do3, lse3, delta3, bias3, seed, causal, scale,
            offset, sk_real, tile, bias_maps, interpret, qseg3, kseg3,
            hq, hk, window=None):
    """dq (and, for a full per-(batch, head) bias, its (bq, bk) dbias
    tiles) on the forward's (bh, qi, ki) grid: q3/do3/lse3/delta3 per
    q-head (BHq, ...), kx/vx per KV head (BHk, Sk, D), read through the
    forward's index map so GQA never expands K/V in HBM."""
    bhq, sq, d = q3.shape
    sk = kx.shape[1]
    bq, bk, sub_q, sub_k, kinds = tile
    nq, nk = sq // bq, sk // bk
    kv_map = functools.partial(_kv_index, hq=hq, hk=hk)
    has_bias = bias3 is not None
    has_seg = qseg3 is not None
    # in-kernel dbias tiles only when bias is full per-(batch, head): then
    # the output is exactly bias-sized. Broadcast biases would amplify to
    # (B*Hq, Sq, Sk) — they take the bounded recompute path in _fa_bwd.
    emit_dbias = has_bias and bias_maps["full"]
    rate = bias_maps["rate"]
    nk_all = nk
    nk, names, band = _band_grid("k", window, bq, bk, offset, nq, nk_all)

    kblk = _key_block(causal, bq, bk, offset, nk_all, window, nq)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _Z)),
        pl.BlockSpec((1, bk, d),
                     lambda bh, qi, ki: (kv_map(bh), kblk(qi, ki), _Z)),
        pl.BlockSpec((1, bk, d),
                     lambda bh, qi, ki: (kv_map(bh), kblk(qi, ki), _Z)),
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _Z)),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, _Z)),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, _Z)),
    ]
    args = [q3, kx, vx, do3, lse3, delta3]
    if has_bias:
        in_specs.append(_bias_spec(bias_maps, bq, bk, kblk=kblk))
        args.append(bias3)
    if has_seg:
        in_specs.append(
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, _Z)))
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, qi, ki: (bh, _Z, kblk(qi, ki))))
        args += [qseg3, kseg3]
    if rate > 0.0:
        in_specs.append(pl.BlockSpec((1,), lambda bh, qi, ki: (_Z,), memory_space=pltpu.SMEM))
        args.append(seed)

    dq_out_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _Z))]
    dq_out_shape = [jax.ShapeDtypeStruct((bhq, sq, d), q3.dtype)]
    if emit_dbias:
        dq_out_specs.append(
            pl.BlockSpec((1, bq, bk), lambda bh, qi, ki: (bh, qi, ki)))
        dq_out_shape.append(
            jax.ShapeDtypeStruct((bhq, sq, sk), jnp.float32))

    dq_outs = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          offset=offset, bq=bq, bk=bk, sub_q=sub_q,
                          sub_k=sub_k, kinds=kinds, nk=nk,
                          sk_real=sk_real, pad_keys=sk != sk_real,
                          has_bias=has_bias, has_seg=has_seg,
                          seg_causal=bias_maps.get("seg_causal", False),
                          emit_dbias=emit_dbias, rate=rate, **band),
        grid=(bhq, nq, nk),
        in_specs=in_specs,
        out_specs=dq_out_specs if emit_dbias else dq_out_specs[0],
        out_shape=dq_out_shape if emit_dbias else dq_out_shape[0],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            _call_vmem("dq", q3, kx, vx, bias3, emit_dbias, has_seg, rate,
                       tile)),
        interpret=interpret,
        name=names["dq"],
    )(*args)
    return dq_outs if emit_dbias else (dq_outs, None)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS + ("with_dq",))
def _bwd_dkv(q3, kx, vx, do3, lse3, delta3, bias3, seed, causal, scale,
             offset, sk_real, tile, bias_maps, interpret, qseg3, kseg3,
             hq, hk, window=None, with_dq=False):
    """dk/dv per KV head on the (kv-head, k-block, r, qi) grid: the
    (q-head-of-group, q-block) sweep as two AFFINE dims, all i32 (index
    maps lower through Mosaic), accumulated in-grid over the group's
    q-heads (no per-q-head dk/dv in HBM). ``with_dq``: the one backward
    kernel, which returns dq too (``_dkv_kernel``); its dq accumulator
    lives across the key blocks, so only the kv head's axis is parallel."""
    bhq, sq, d = q3.shape
    bhk, sk = kx.shape[0], kx.shape[1]
    rep = hq // hk
    bq, bk, sub_q, sub_k, kinds = tile
    nq, nk = sq // bq, sk // bk
    has_bias = bias3 is not None
    has_seg = qseg3 is not None
    rate = bias_maps["rate"]
    rep_i = np.int32(rep)
    nq_all = nq
    nq, names, band = _band_grid("q", window, bq, bk, offset, nq_all, nk)

    def qrow(bh, r):
        return bh * rep_i + r

    qblk = _query_block(causal, bq, bk, offset, nq_all, window, nk)

    def qside(last):
        return pl.BlockSpec(
            (1, bq, last),
            lambda bh, ki, r, qi: (qrow(bh, r), qblk(ki, qi), _Z))

    kq_specs = [
        qside(d),
        pl.BlockSpec((1, bk, d), lambda bh, ki, r, qi: (bh, ki, _Z)),
        pl.BlockSpec((1, bk, d), lambda bh, ki, r, qi: (bh, ki, _Z)),
        qside(d), qside(1), qside(1),
    ]
    kq_args = [q3, kx, vx, do3, lse3, delta3]
    if has_bias:
        # bias rows are per q-head: callers expand K/V for bias + GQA, so
        # rep == 1 here and the bias map sees the plain q-head index
        kq_specs.append(_bias_spec(bias_maps, bq, bk, qblk=qblk))
        kq_args.append(bias3)
    if has_seg:
        kq_specs.append(qside(1))
        kq_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, ki, r, qi: (qrow(bh, r), _Z, ki)))
        kq_args += [qseg3, kseg3]
    if rate > 0.0:
        kq_specs.append(pl.BlockSpec(
            (1,), lambda bh, ki, r, qi: (_Z,), memory_space=pltpu.SMEM))
        kq_args.append(seed)

    out_specs = [
        pl.BlockSpec((1, bk, d), lambda bh, ki, r, qi: (bh, ki, _Z)),
        pl.BlockSpec((1, bk, d), lambda bh, ki, r, qi: (bh, ki, _Z)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bhk, sk, d), q3.dtype),
        jax.ShapeDtypeStruct((bhk, sk, d), q3.dtype),
    ]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    kernel, semantics = "dkv", ("parallel", "parallel", "arbitrary",
                                "arbitrary")
    if with_dq:
        # dq stays in HBM (``memory_space=pl.ANY``): the kernel writes each
        # query block once, when it is final, by a DMA of whole lanes (a
        # head of 64 takes 128 and drops the rest below)
        lanes = _round_up(d, _LANES)
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct((bhq, sq, lanes), q3.dtype))
        scratch += [pltpu.VMEM((rep, sq, d), jnp.float32),
                    pltpu.VMEM((bq, lanes), q3.dtype),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SMEM((1,), jnp.int32)]
        kernel, semantics = "bwd", ("parallel", "arbitrary", "arbitrary",
                                    "arbitrary")
    outs = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          offset=offset, bq=bq, bk=bk, sub_q=sub_q,
                          sub_k=sub_k, kinds=kinds, nq=nq, nk_grid=nk,
                          rep=rep,
                          sk_real=sk_real, pad_keys=sk != sk_real,
                          has_bias=has_bias, has_seg=has_seg,
                          seg_causal=bias_maps.get("seg_causal", False),
                          rate=rate, with_dq=with_dq, **band),
        grid=(bhk, nk, rep, nq),
        in_specs=kq_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(
            semantics, _call_vmem(kernel, q3, kx, vx, bias3, False, has_seg,
                                  rate, tile)),
        interpret=interpret,
        name=names[kernel],
    )(*kq_args)
    if with_dq:
        dk, dv, dq = outs
        return dk, dv, dq[..., :d]
    return outs


def _bwd_impl(q3, kx, vx, do3, lse, delta, bias3, seed, causal, scale,
              offset, sk_real, plan, bias_maps, interpret, qseg3=None,
              kseg3=None, hq=None, hk=None, window=None):
    """The backward pass over operands padded to ``_bwd_lengths``: the one
    kernel where ``plan.bwd`` is a tile, else the dq and the dkv kernel.
    hq == hk is plain MHA. Returns (dq, dk (BHk), dv (BHk),
    dbias_blocks)."""
    bhq = q3.shape[0]
    hq = hq if hq is not None else bhq
    hk = hk if hk is not None else bhq
    common = (q3, kx, vx, do3, lse[..., None], delta[..., None], bias3,
              seed, causal, scale, offset, sk_real)
    rest = (_Static(bias_maps), interpret, qseg3, kseg3, hq, hk, window)
    has_seg, rate = qseg3 is not None, bias_maps["rate"]
    if plan.bwd is not None:
        _record_call("bwd", q3, kx, vx, bias3, False, has_seg, rate,
                     plan.bwd, offset, causal, sk_real, window)
        dk, dv, dq = _bwd_dkv(*common, plan.bwd, *rest, with_dq=True)
        return dq, dk, dv, None
    for kernel, tile, dbias in (
            ("dq", plan.dq, bias3 is not None and bias_maps["full"]),
            ("dkv", plan.dkv, False)):
        _record_call(kernel, q3, kx, vx, bias3, dbias, has_seg, rate, tile,
                     offset, causal, sk_real, window, split_by=plan.split_by)
    dq, dbias_blocks = _bwd_dq(*common, plan.dq, *rest)
    dk, dv = _bwd_dkv(*common, plan.dkv, *rest)
    return dq, dk, dv, dbias_blocks


def _bwd_lengths(plan):
    """(q, k) block lengths the backward's operands are padded to a
    multiple of: the one kernel's tile, or one length both split kernels'
    tiles divide."""
    if plan.bwd is not None:
        return plan.bwd.bq, plan.bwd.bk
    return math.lcm(plan.dq.bq, plan.dkv.bq), math.lcm(plan.dq.bk,
                                                       plan.dkv.bk)


# ---------------------------------------------------------------------------
# custom_vjp wrapper in the reference layout [B, S, H, D]
# ---------------------------------------------------------------------------

def _dbias_broadcast(q3, kx, vx, do3, lse_p, delta, bias3, seed, maps,
                     causal, scale, offset, sk_real, Sq, Sk, qseg3=None,
                     kseg3=None):
    """Memory-bounded dbias for broadcast bias shapes: recompute ds one
    (batch*head) row at a time with a sequential fori_loop, accumulating
    straight into the reduced (Bb*Hb, Sqb, Sk) buffer — peak extra memory
    is one (Sq_pad, Sk_pad) matrix, never (B*Hq, Sq, Sk)."""
    bhq, sq_pad, d = q3.shape
    sk_pad = kx.shape[1]
    Hq, Sqb = maps["Hq"], maps["Sqb"]
    rate = maps["rate"]
    acc0 = jnp.zeros((bias3.shape[0], bias3.shape[1], sk_pad), jnp.float32)

    def body(bh, acc):
        qb = jax.lax.dynamic_index_in_dim(q3, bh, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kx, bh, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vx, bh, 0, keepdims=False)
        dob = jax.lax.dynamic_index_in_dim(do3, bh, 0, keepdims=False)
        lse_b = jax.lax.dynamic_index_in_dim(lse_p, bh, 0, keepdims=False)
        delta_b = jax.lax.dynamic_index_in_dim(delta, bh, 0, keepdims=False)
        bias_b = jax.lax.dynamic_index_in_dim(
            bias3, _bias_row(maps, bh), 0, keepdims=False)
        s = jnp.dot(qb.astype(jnp.float32) * np.float32(scale),
                    kb.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)
        s = s + bias_b.astype(jnp.float32)
        kidx = jax.lax.broadcasted_iota(jnp.int32, (sq_pad, sk_pad), 1)
        mask = kidx < sk_real
        if causal:
            qidx = jax.lax.broadcasted_iota(jnp.int32, (sq_pad, sk_pad), 0)
            mask = mask & (kidx <= qidx + offset)
        if qseg3 is not None:
            qs = jax.lax.dynamic_index_in_dim(qseg3, bh, 0, keepdims=False)
            ks = jax.lax.dynamic_index_in_dim(kseg3, bh, 0, keepdims=False)
            mask = mask & _seg_mask(qs, ks,
                                    maps.get("seg_causal", False))
        s = jnp.where(mask, s, _NEG_INF)
        lse_safe = jnp.where(lse_b == _NEG_INF, _F0, lse_b)
        p = jnp.exp(s - lse_safe[:, None])
        dp = jnp.dot(dob.astype(jnp.float32), vb.astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _keep_block(_mix_seed(seed[0], bh), 0, 0, sq_pad, sk_pad,
                               sk_real, _dropout_thresh(rate))
            dp = jnp.where(keep, dp * np.float32(1.0 / (1.0 - rate)), _F0)
        ds = p * (dp - delta_b[:, None])
        red = ds[:bias3.shape[1]] if Sqb != 1 else \
            jnp.sum(ds, axis=0, keepdims=True)
        return acc.at[_bias_row(maps, bh)].add(red)

    acc = jax.lax.fori_loop(0, bhq, body, acc0)
    return acc[:, :, :Sk]


# ---------------------------------------------------------------------------
# the tile plan: how the kernels cut attention into grid steps
# ---------------------------------------------------------------------------

class Tile(NamedTuple):
    """What one kernel of a call lowers: the (block_q, block_k) a grid step
    is handed, the (sub_q, sub_k) it walks that in (each divides its side;
    the tile's own sides where a tile is computed in one piece), and the
    ``kinds`` of tile the call's grid meets, a straight-line body each
    (``_tile_kinds``, ``_attend_tile``)."""
    bq: int
    bk: int
    sub_q: int
    sub_k: int
    kinds: tuple


class TilePlan(NamedTuple):
    """The ``Tile`` of each kernel. They need not agree: fwd and dq hold one
    (bq, d) accumulator and sweep k, dkv holds two (bk, d) accumulators and
    sweeps q. ``bwd`` is the one backward kernel's (dkv's grid and walk,
    with dq besides), or None where the call keeps dq and dkv apart;
    ``split_by`` then says why (``backward_rule``)."""
    fwd: Tile
    dq: Tile
    dkv: Tile
    bwd: Optional[Tile] = None
    split_by: Optional[str] = None

    def lowered(self):
        """{kernel: Tile} of the kernels a call of this plan lowers."""
        if self.bwd is not None:
            return {"fwd": self.fwd, "bwd": self.bwd}
        return {"fwd": self.fwd, "dq": self.dq, "dkv": self.dkv}


# Mosaic's scoped VMEM: 16 MiB unless the call asks for more (a v5e core
# has 128 MiB)
_VMEM_DEFAULT_LIMIT = 16 << 20
_VMEM_MAX_LIMIT = 64 << 20
# what a plan's estimate may take: 1024 x 1024 tiles of a float32 head of
# 128 fit (24.5 MiB in dkv), and 1.5 times it stays under the limit above
_VMEM_BUDGET = 32 << 20
# the (bq, bk) float32 score-sized temporaries a body keeps alive
_SCORE_TEMPS = {"fwd": 3, "dq": 4, "dkv": 4, "bwd": 4}
_MAX_BLOCK = 1024


def _round_up(n, m):
    return -(-n // m) * m


def _dq_acc_bytes(rows, d):
    """The one backward kernel's float32 dq accumulator of ``rows`` q rows
    (rep x the padded Sq), whole 128-lane tiles."""
    return rows * _round_up(d, _LANES) * 4


def _vmem_bytes(kernel, bq, bk, d, q_bytes, k_bytes, v_bytes, bias_bytes,
                dbias, segments, dropout, dq_rows=0):
    """Estimated VMEM of one call of ``kernel`` ("fwd", "dq", "dkv", "bwd")
    at a (bq, bk) tile: in/out blocks double-buffered by the pipeline,
    scratch, and the score-sized temporaries of the body counted at the
    whole tile, which a tile computed in one piece needs (a walked tile's
    pieces are smaller; the estimate does not follow them, so a call asks
    for the limit it asked for before tiles were walked). A (bq, 1) column
    block occupies whole 128-lane tiles. "bwd" holds dkv's blocks and the
    dq of ``dq_rows`` q rows besides, and one q block of it on its way
    out."""
    dl = _round_up(d, _LANES)
    col = _round_up(bq, 8) * _LANES * 4
    q_blk, k_blk, v_blk = bq * dl * q_bytes, bk * dl * k_bytes, \
        bk * dl * v_bytes
    if kernel == "fwd":       # q, k, v in; o (q's dtype), lse out
        blocks = 2 * q_blk + k_blk + v_blk + col
        scratch = bq * dl * 4 + 2 * col
    elif kernel == "dq":      # q, k, v, do, lse, delta in; dq out
        blocks = 3 * q_blk + k_blk + v_blk + 2 * col
        scratch = bq * dl * 4
    else:                     # q, k, v, do, lse, delta in; dk, dv out
        blocks = 2 * q_blk + k_blk + v_blk + 2 * col + 2 * bk * dl * q_bytes
        scratch = 2 * bk * dl * 4
        if kernel == "bwd":
            scratch += _dq_acc_bytes(dq_rows, d) + q_blk
    temps = _SCORE_TEMPS[kernel] + (2 if dropout else 0)
    if bias_bytes:
        blocks += bq * bk * bias_bytes
        temps += 1
    if dbias:
        blocks += bq * bk * 4
    if segments:
        blocks += col + 8 * _round_up(bk, _LANES) * 4
        temps += 1
    return 2 * blocks + scratch + temps * bq * bk * 4


def _side_blocks(s, max_block=_MAX_BLOCK):
    """Block lengths one side of the score tile may take: the whole length
    where it is short (decode has Sq = 1), else the multiples of 128, up to
    ``max_block``, that divide the length padded to a multiple of 128 —
    so no block pads more than 128 does, and any two of them divide one
    padded length (the backward's two kernels share their operands)."""
    if s <= _LANES:
        return (s,)
    n = _round_up(s, _LANES) // _LANES
    return tuple(_LANES * m for m in range(1, max_block // _LANES + 1)
                 if n % m == 0)


def _sub_tile(kernel, bq, bk, d, window=None):
    """(sub_q, sub_k) that ``kernel`` walks a (bq, bk) tile in, square where
    the sides allow: on each side the largest multiple of 128 that divides
    it, up to what the sweep on the chip found fastest (PERF.md, "PR 31").
    dq takes 128 and dkv 256 whatever the head; the forward 128 under a
    head of 64, where the vector unit bounds it and the finest skip wins,
    and 512 over a wider head, where the MXU wants long pieces. Measured at
    heads of 64 and 128 in bf16, at 1024 x 1024 tiles (512 x 512 too under
    the window): a head of 256, or of 32, follows its neighbour's rule
    unmeasured. The one backward kernel walks as dkv does and takes its
    256: fastest, or within 1% of 512, at heads of 64, 128 and 256 and
    under the window 512 (PERF.md, section 6: the one kernel's sweep). No
    piece is longer than the
    window rounded up to 128, or
    the band skips nothing (measured at the window 512 alone). A side that
    no multiple of 128 divides (a short one, taken whole) is one piece; a
    query side under 8 rows (decode) leaves nothing to skip."""
    if bq < 8:
        return bq, bk
    most = {"fwd": 128 if d <= 64 else 512, "dq": 128, "dkv": 256,
            "bwd": 256}[kernel]
    if window is not None:
        most = min(most, _round_up(window, _LANES))

    def side(block):
        return max((m for m in range(_LANES, most + 1, _LANES)
                    if block % m == 0), default=block)
    return side(bq), side(bk)


def _tile(kernel, bq, bk, sq, sk, d, *, causal, window=None, sub=None):
    """The ``Tile`` that ``kernel`` lowers for a call of ``sq`` queries on
    ``sk`` keys cut into (bq, bk) blocks: the one place that decides how a
    call's tiles are walked, for the kernel's body, the ``flash::tile_plan``
    event and the tests alike. The operands are padded to whole blocks and
    the diagonal lies ``sk - sq`` to the right, as every caller has it.

    The sub-tile is ``_sub_tile``'s (``sub`` overrides it: the sweep), and
    the kinds are those of the tiles an edge or the padding touches, where
    a walk has something to skip or a mask to save and the kinds are few.
    Else the tile is computed in one piece: a call that is neither causal
    nor padded, a decode step, a call with more than ``_MAX_WALKS`` kinds.
    The sub-tile is then the tile, masked where an edge or the padding
    touches it (``_ANY``) and not elsewhere."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    sub_q, sub_k = sub or _sub_tile(kernel, bq, bk, d, window)
    kinds = _tile_kinds(bq, bk, nq, nk, causal=causal, offset=sk - sq,
                        window=window, sk_real=sk)
    touched = kinds - {(None, None)}
    if not touched or len(touched) > _MAX_WALKS:
        sub_q, sub_k = bq, bk
    if (sub_q, sub_k) == (bq, bk):
        kinds = {(None, None) if kind == (None, None) else (_ANY, None)
                 for kind in kinds}
    return Tile(bq, bk, sub_q, sub_k, tuple(sorted(kinds, key=str)))


def backward_rule(dkv, sq, sk, d, q_bytes, k_bytes, v_bytes, *, rep,
                  bias_bytes, dbias, segments, dropout, window, causal):
    """(the one backward kernel's ``Tile``, None), or (None, why the call
    keeps the dq and the dkv kernel apart), from the facts ``tile_plan``
    sees and the dkv kernel's ``Tile``, whose grid and walk it takes. The
    one kernel computes each visible piece's scores once for dq, dk and dv
    (five products where the split kernels take seven, one ``exp`` pass
    where they take two), and holds dq for all the kv head's ``rep`` q rows
    in float32 across the sweep. The split kernels stay only where:

    - "dbias": a full per-(batch, head) bias asks for dbias tiles, an
      output only the dq kernel's grid emits;
    - "rows_unseen": a window on more queries than keys leaves query
      blocks that no step of the band's grid visits, whose dq would go
      unwritten;
    - "vmem": the estimate with the accumulator passes the largest scoped
      VMEM a call asks for."""
    if dbias:
        return None, "dbias"
    if window is not None and sq > sk:
        return None, "rows_unseen"
    rows = rep * _round_up(sq, dkv.bq)
    if _vmem_bytes("bwd", dkv.bq, dkv.bk, d, q_bytes, k_bytes, v_bytes,
                   bias_bytes, False, segments, dropout,
                   dq_rows=rows) > _VMEM_MAX_LIMIT:
        return None, "vmem"
    return _tile("bwd", dkv.bq, dkv.bk, sq, sk, d, causal=causal,
                 window=window), None


def tile_plan(sq, sk, d, q_bytes=2, k_bytes=2, v_bytes=2, *, bias_bytes=0,
              dbias=False, segments=False, dropout=False,
              vmem_budget=_VMEM_BUDGET, window=None, causal=True,
              rep=1) -> TilePlan:
    """The ``Tile`` of each kernel, from what a call can see at trace
    time: the lengths, the head dim, the operands' item sizes, whether a
    bias block (and its dbias tile), segment ids or dropout ride along, a
    VMEM budget, whether the kernels hide what lies above the diagonal
    (``causal``; under segment ids they do not, the diagonals ride in the
    segment words) or outside a ``window``, and the q heads a kv head
    serves in the backward pass (``rep``). Each kernel takes the largest
    tile whose sides suit the lengths and whose estimate fits the budget
    (PERF.md, "PR 26": the sweep on the chip; a grid step costs about half
    a microsecond whatever is in it). The one backward kernel takes the
    dkv kernel's tile where ``backward_rule`` lets the call have it.

    What a large tile would waste it does not compute: a step walks its
    tile in ``_sub_tile``'s pieces, enters only those that hold a score
    attention may see, and masks only those an edge crosses (the diagonal,
    the band's lower edge, the padded keys' column). ``_tile`` says which
    tiles of the call are walked so; what it returns is what is lowered.
    Causal or not does not move the tile's sides.

    A ``window`` does: a step is handed whole tiles, so a (bq, bk) tile on
    the band moves ``bq + window`` keys, rounded up to blocks, for ``bq``
    queries that need ``window`` of them. The sides stop at twice the
    window rounded up to 128: walked, 1024 x 1024 beat 512 x 512 under a
    window of 512, which was the other way round while a tile was computed
    whole (PERF.md, "PR 28" and "PR 31": the sweeps; no other window was
    measured, so a window of 8 or 200 follows 512's rule)."""
    side = _MAX_BLOCK if window is None else \
        min(_MAX_BLOCK, 2 * _round_up(window, _LANES))
    qs, ks = _side_blocks(sq, side), _side_blocks(sk, side)

    def pick(kernel):
        fits = [(bq, bk) for bq in qs for bk in ks
                if _vmem_bytes(kernel, bq, bk, d, q_bytes, k_bytes, v_bytes,
                               bias_bytes, dbias and kernel == "dq",
                               segments, dropout) <= vmem_budget]
        # the largest tile, and of two as large the one wider in keys: a
        # grid step's own cost and the (bq, 128) softmax statistics are paid
        # once a step, so they shrink with the tile, the latter with bk
        bq, bk = max(fits, key=lambda t: (t[0] * t[1], t[1]),
                     default=(min(qs), min(ks)))
        return _tile(kernel, bq, bk, sq, sk, d, causal=causal, window=window)
    return _with_backward(
        pick("fwd"), pick("dq"), pick("dkv"), sq, sk, d, q_bytes, k_bytes,
        v_bytes, rep=rep, bias_bytes=bias_bytes, dbias=dbias,
        segments=segments, dropout=dropout, window=window, causal=causal)


def _with_backward(fwd, dq, dkv, *args, **facts) -> TilePlan:
    return TilePlan(fwd, dq, dkv, *backward_rule(dkv, *args, **facts))


def _blocks(block_q, block_k, q, k, v, bias, segments, rate, causal,
            window=None):
    """TilePlan of a call on q [B,Sq,Hq,D], k/v [B,Sk,Hk,D]: explicit
    ``block_q`` and ``block_k`` (cut to the length) go to every kernel,
    each with its own sub-tile; both None, the plan chooses. ``causal`` is
    the call's; under ``segments`` the kernels see none. A bias on grouped
    heads expands K and V for the backward pass (``_fa_bwd``), which then
    sees one q head a kv head."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    causal = causal and not segments
    if (block_q is None) != (block_k is None):
        raise ValueError("block_q and block_k are given together or not at "
                         f"all, got {block_q!r} and {block_k!r}")
    facts = dict(
        bias_bytes=jnp.asarray(bias).dtype.itemsize if bias is not None
        else 0,
        dbias=bias is not None and _bias_shape4(bias) == (B, Hq, Sq, Sk),
        segments=segments, dropout=rate > 0.0, window=window, causal=causal)
    sizes = (q.dtype.itemsize, k.dtype.itemsize, v.dtype.itemsize)
    rep = 1 if bias is not None else Hq // Hk
    if block_q is not None:
        bq, bk = min(block_q, Sq), min(block_k, Sk)
        return _with_backward(
            *(_tile(kernel, bq, bk, Sq, Sk, D, causal=causal, window=window)
              for kernel in ("fwd", "dq", "dkv")), Sq, Sk, D, *sizes,
            rep=rep, **facts)
    return tile_plan(Sq, Sk, D, *sizes, rep=rep, **facts)


# ---------------------------------------------------------------------------
# the route: whether a dense call takes the kernels or XLA's attention
# ---------------------------------------------------------------------------

class Route(NamedTuple):
    """``impl`` is "kernel" or "xla"; ``rule`` names the rule that decided."""
    impl: str
    rule: str


# Two rules no cell of the benchmark stands on either side of (every cell's
# kv is 1024 or longer, and where its heads are grouped its scores are past
# the budget): kept as they were, until a cell or a measurement on the chip
# decides them (ROADMAP S5 b).
# The kv length under which the chip takes XLA's fused attention.
_MIN_KV_ON_CHIP = 1024
# Grouped heads on the chip take XLA's attention while the float32 score
# matrix, B * Hq * Sq * Sk * 4 bytes whatever the operands' dtype, is no
# larger than this: XLA's backward keeps the probabilities, the flash
# backward recomputes them for every q head of a group.
_GQA_XLA_SCORE_BYTES = 4_500_000_000


def attention_route(q, k, bias, *, dropout_rate, has_key, causal, window,
                    meshed, on_tpu, force_interpret) -> Route:
    """The implementation a dense attention call gets, from its static
    facts: q [B,Sq,Hq,D] and k [B,Sk,Hk,D] (anything with a ``shape``), the
    bias or None, the dropout rate and whether a key came with it,
    ``causal``, ``window``, whether GSPMD-owned mesh axes larger than 1
    surround the call (``meshed``), the backend, and the
    ``pallas_force_interpret`` flag. The first rule that holds decides.
    The kernel's tiles are ``tile_plan``'s."""
    B, sq, Hq, D = q.shape
    sk, Hk = k.shape[1], k.shape[2]
    if bias is not None and not bias_supported(bias, B, Hq, sq, sk):
        return Route("xla", "bias_layout")
    if D > 256:
        return Route("xla", "head_dim")
    if dropout_rate > 0.0 and not has_key:
        return Route("xla", "dropout_without_key")
    # see ``_per_shard``: bias-free, dropout-free calls only
    if meshed and (bias is not None or dropout_rate > 0.0):
        return Route("xla", "mesh_with_bias_or_dropout")
    if window is not None and (bias is not None or not causal):
        return Route("xla", "window_with_bias_or_not_causal")
    if on_tpu and sk < _MIN_KV_ON_CHIP:
        return Route("xla", "short_kv")
    if not on_tpu and not force_interpret:
        return Route("xla", "interpret_not_forced")
    if window is not None:
        # never XLA for its size: the score matrix is what a window avoids
        return Route("kernel", "window")
    if (Hq // max(Hk, 1) > 1 and on_tpu
            and B * Hq * sq * sk * 4 <= _GQA_XLA_SCORE_BYTES):
        return Route("xla", "gqa_scores_fit")
    return Route("kernel", "default")


def route_here(q, k, bias=None, *, dropout_rate=0.0, has_key=False,
               causal=True, window=None):
    """``attention_route`` of a call made here and now: the backend, the
    ``pallas_force_interpret`` flag and the mesh axes GSPMD still owns at
    this point of the trace (inside a shard_map the manual axes are
    per-shard already) are read, the rest is the call's. Returns the
    ``Route`` and those axes, {name: size}. What the Pallas implementation
    of a dense call asks, and what a model asks that reports the route its
    attention will get (``mla::plan``)."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a: mesh.shape[a] for a in mesh.auto_axes}
    return attention_route(
        q, k, bias, dropout_rate=dropout_rate, has_key=has_key,
        causal=causal, window=window,
        meshed=any(n > 1 for n in auto.values()),
        on_tpu=not pallas_interpret(),
        force_interpret=bool(_flags.get_flag("pallas_force_interpret"))), auto


def _pad_seq(x3, block):
    s = x3.shape[1]
    pad = (-s) % block
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    return x3


def _encode_seg(seg):
    """Nondecreasing (B, S) segment ids -> int32 words carrying BOTH the
    id (high 15 bits) and the end-relative position v = local - seg_len
    (low 16 bits, biased by 0x8000). Two positions are in the same segment
    iff their high bits match, and the per-segment causal relation
    k_local <= q_local + Lk - Lq is exactly klow <= qlow — so varlen
    causal masking with unequal q/k segment lengths needs no extra kernel
    inputs. Limits: ids < 2^15, segment length <= 2^15."""
    seg = seg.astype(jnp.int32)
    pos = jnp.arange(seg.shape[1], dtype=jnp.int32)

    def one(row):
        left = jnp.searchsorted(row, row, side="left").astype(jnp.int32)
        right = jnp.searchsorted(row, row, side="right").astype(jnp.int32)
        v = (pos - left) - (right - left)         # local - L, in [-L, -1]
        return (row << 16) | (v + np.int32(0x8000))
    return jax.vmap(one)(seg)


def _seg3(q_seg, k_seg, B, Hq, bq, bk):
    """(B, Sq)/(B, Sk) segment ids -> per-q-head kernel layouts
    (BHq, Sq_pad, 1) and (BHq, 1, Sk_pad) of encoded seg words; pads take
    distinct far-negative words so padded rows/cols can never match
    anything real (or each other) even after the >>16 id extraction."""
    pad_q = (-q_seg.shape[1]) % bq
    pad_k = (-k_seg.shape[1]) % bk
    qs = jnp.pad(_encode_seg(q_seg), ((0, 0), (0, pad_q)),
                 constant_values=np.int32(-(1 << 20)))
    ks = jnp.pad(_encode_seg(k_seg), ((0, 0), (0, pad_k)),
                 constant_values=np.int32(-(2 << 20)))
    qs = jnp.repeat(qs, Hq, axis=0)[..., None]       # (BHq, Sq_pad, 1)
    ks = jnp.repeat(ks, Hq, axis=0)[:, None, :]      # (BHq, 1, Sk_pad)
    return qs, ks


def _seg_mask(qenc, kenc, seg_causal):
    """(bq,1) x (1,bk) encoded seg words -> (bq,bk) visibility mask."""
    same = (qenc >> np.int32(16)) == (kenc >> np.int32(16))
    if seg_causal:
        low = np.int32(0xFFFF)
        same = same & ((kenc & low) <= (qenc & low))
    return same


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def flash_attention_ext(q, k, v, bias, seed, q_seg, k_seg, causal, scale,
                        dropout_rate, block_q, block_k, interpret,
                        window=None):
    """Full-contract flash attention: q [B,Sq,Hq,D], k/v [B,Sk,Hk,D],
    optional additive ``bias`` broadcastable to [B,Hq,Sq,Sk] (full Sk dim),
    deterministic dropout driven by ``seed`` ((1,) int32; see
    ``dropout_keep_mask``), optional varlen packing via ``q_seg``/``k_seg``
    ((B, Sq)/(B, Sk) int32 segment ids — attention is masked where the ids
    differ, the TPU-native form of the reference's cu_seqlens contract,
    flash_attn_kernel.cu:199). ``window`` (None or a length; causal calls
    without bias or segment ids) hides the keys that lie ``window`` or more
    behind a query: key j is visible to query i when ``j <= i`` and
    ``i - j < window``; the kernels then sweep the band's blocks only and
    carry the names ``flash_win_*``. Returns out [B,Sq,Hq,D]."""
    out, _ = _fa_fwd(q, k, v, bias, seed, q_seg, k_seg, causal, scale,
                     dropout_rate, block_q, block_k, interpret, window)
    return out


def _check_window(window, causal, bias, q_seg):
    if window is None:
        return
    if not causal or bias is not None or q_seg is not None:
        raise ValueError("flash_attention_ext: a window goes with causal "
                         "attention, without bias and without segment ids")
    if int(window) < 1:
        raise ValueError(f"window must be at least 1, got {window!r}")


def _fa_fwd(q, k, v, bias, seed, q_seg, k_seg, causal, scale, dropout_rate,
            block_q, block_k, interpret, window=None):
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    _check_window(window, causal, bias, q_seg)
    tile = _blocks(block_q, block_k, q, k, v, bias, q_seg is not None,
                   dropout_rate, causal, window).fwd
    bq, bk = tile.bq, tile.bk
    offset = Sk - Sq

    q3 = _pad_seq(q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    k3 = _pad_seq(k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    v3 = _pad_seq(v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    qseg3, kseg3 = (_seg3(q_seg, k_seg, B, Hq, bq, bk)
                    if q_seg is not None else (None, None))
    seg_causal = causal and q_seg is not None
    if seg_causal:
        # per-segment diagonals (k_local - Lk <= q_local - Lq) ride in the
        # seg words; the kernel's single global diagonal (and its block
        # skip) would be wrong whenever q/k segment lengths differ
        causal = False

    if bias is not None:
        bias3, maps = _prep_bias(bias, B, Hq, Sq, Sk, bq, bk)
    else:
        bias3, maps = None, {}
    maps = dict(maps, rate=float(dropout_rate), seg_causal=seg_causal)
    if dropout_rate > 0.0:
        if seed is None:
            raise ValueError("flash_attention_ext: seed is required when "
                             "dropout_rate > 0")
        seed_in = seed
    else:
        seed_in = None

    out3, lse = _fwd_impl(q3, k3, v3, bias3, seed_in, Hq, Hk, causal, scale,
                          offset, Sk, tile, maps, interpret, qseg3, kseg3,
                          window)
    out = out3[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    # a recomputed block may keep these two (recompute's "flash_saveable")
    # and spare this kernel's second run; elsewhere a name is the identity
    from ...distributed.fleet.recompute import keep
    out, lse = keep(out, "flash_out"), keep(lse, "flash_lse")
    return out, (q, k, v, bias, seed, q_seg, k_seg, out, lse)


def _fa_bwd(causal, scale, dropout_rate, block_q, block_k, interpret, window,
            res, dout):
    q, k, v, bias, seed, q_seg, k_seg, out, lse = res
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    rep = Hq // Hk
    plan = _blocks(block_q, block_k, q, k, v, bias, q_seg is not None,
                   dropout_rate, causal, window)
    bq, bk = _bwd_lengths(plan)
    offset = Sk - Sq

    q3 = _pad_seq(q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    do3 = _pad_seq(dout.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    # GQA-native: K/V stay per-kv-head — the dq kernel indexes its group's
    # kv block (the forward's kv_map) and the dkv kernel accumulates over
    # the group's q-heads in-grid. The one exception is bias + GQA (the
    # per-q-head dbias tiling assumes q-head rows): expand there only.
    expand_kv = rep > 1 and bias is not None
    if expand_kv:
        k4 = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
        v4 = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
        kx = _pad_seq(k4.reshape(B * Hq, Sk, D), bk)
        vx = _pad_seq(v4.reshape(B * Hq, Sk, D), bk)
        hq_eff = hk_eff = Hq
    else:
        kx = _pad_seq(k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
        vx = _pad_seq(v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
        hq_eff, hk_eff = Hq, Hk
    qseg3, kseg3 = (_seg3(q_seg, k_seg, B, Hq, bq, bk)
                    if q_seg is not None else (None, None))
    seg_causal = causal and q_seg is not None
    if seg_causal:
        causal = False   # per-segment diagonals ride in the seg words

    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, leave to XLA
    out3 = out.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    delta = jnp.sum(do3[:, :Sq].astype(jnp.float32) *
                    out3.astype(jnp.float32), axis=-1)
    pad_q = (-Sq) % bq
    if pad_q:
        delta = jnp.pad(delta, ((0, 0), (0, pad_q)))
        # padded query rows get lse = +inf => p = exp(s - inf) = 0, so they
        # contribute nothing to dk/dv sums
        lse_p = jnp.pad(lse[:, :Sq], ((0, 0), (0, pad_q)),
                        constant_values=float("inf"))
    else:
        lse_p = lse[:, :Sq]

    if bias is not None:
        bias3, maps = _prep_bias(bias, B, Hq, Sq, Sk, bq, bk)
    else:
        bias3, maps = None, {}
    maps = dict(maps, rate=float(dropout_rate), seg_causal=seg_causal)
    if dropout_rate > 0.0:
        if seed is None:
            raise ValueError("flash_attention_ext: seed is required when "
                             "dropout_rate > 0")
        seed_in = seed
    else:
        seed_in = None

    dq3, dk3, dv3, dbias_blocks = _bwd_impl(
        q3, kx, vx, do3, lse_p, delta, bias3, seed_in, causal, scale,
        offset, Sk, plan, maps, interpret, qseg3, kseg3,
        hq=hq_eff, hk=hk_eff, window=window)
    dq = dq3[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    if expand_kv:  # per-q-head dk/dv: sum q-head groups onto their kv head
        dk4 = dk3[:, :Sk].reshape(B, Hk, rep, Sk, D).sum(axis=2)
        dv4 = dv3[:, :Sk].reshape(B, Hk, rep, Sk, D).sum(axis=2)
    else:          # GQA-native: already per-kv-head
        dk4 = dk3[:, :Sk].reshape(B, Hk, Sk, D)
        dv4 = dv3[:, :Sk].reshape(B, Hk, Sk, D)
    dk = dk4.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv4.transpose(0, 2, 1, 3).astype(v.dtype)

    if bias is None:
        dbias = None
    elif dbias_blocks is not None:
        # full-shape bias: (BHq, Sq_pad, Sk_pad) in-kernel tiles == dbias
        dbias = dbias_blocks[:, :Sq, :Sk].reshape(B, Hq, Sq, Sk) \
            .reshape(jnp.asarray(bias).shape).astype(bias.dtype)
    else:
        # broadcast bias: memory-bounded sequential recompute
        db3 = _dbias_broadcast(q3, kx, vx, do3, lse_p, delta, bias3,
                               seed_in, maps, causal, scale, offset, Sk,
                               Sq, Sk, qseg3, kseg3)
        dbias = db3[:, :maps["Sqb"]].reshape(
            jnp.asarray(bias).shape).astype(bias.dtype)
    dseed = np.zeros(np.shape(seed), jax.dtypes.float0)
    dqseg = (np.zeros(np.shape(q_seg), jax.dtypes.float0)
             if q_seg is not None else None)
    dkseg = (np.zeros(np.shape(k_seg), jax.dtypes.float0)
             if k_seg is not None else None)
    return dq.astype(q.dtype), dk, dv, dbias, dseed, dqseg, dkseg


flash_attention_ext.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_pallas(q, k, v, causal, scale, interpret,
                           block_q=None, block_k=None):
    """Bias-free, dropout-free fast path (back-compat signature)."""
    return flash_attention_ext(q, k, v, None, jnp.zeros((1,), jnp.int32),
                               None, None, causal, scale, 0.0, block_q,
                               block_k, interpret)


# ---------------------------------------------------------------------------
# chunk-level entry points: the building blocks ring attention runs inside
# each ring step (distributed/long_context.py). No custom_vjp here — the
# ring owns the backward (a second ring pass with rotating dk/dv), these
# just expose the Pallas forward with its lse and the Pallas backward fed
# a GLOBAL lse/delta. GQA-native: Hk may divide Hq, K/V never expand.
# ---------------------------------------------------------------------------

def flash_chunk_fwd(q, k, v, causal, scale, block_q=None, block_k=None,
                    interpret=False):
    """Partial attention of q [B,Sq,Hq,D] against one k/v chunk
    [B,Sc,Hk,D]. Returns (out [B,Sq,Hq,D], lse [B,Hq,Sq]) — normalized
    over THIS chunk only; callers merge chunks by log-sum-exp. ``causal``
    masks the q/k diagonal (same global offset, the ring's j == idx
    chunk); fully-visible chunks pass causal=False."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    tile = _blocks(block_q, block_k, q, k, v, None, False, 0.0, causal).fwd
    bq, bk = tile.bq, tile.bk
    q3 = _pad_seq(q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    k3 = _pad_seq(k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    v3 = _pad_seq(v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    out3, lse = _fwd_impl(q3, k3, v3, None, None, Hq, Hk, causal, scale,
                          Sk - Sq, Sk, tile, {"rate": 0.0}, interpret)
    out = out3[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    return out, lse[:, :Sq].reshape(B, Hq, Sq)


def flash_chunk_bwd(q, k, v, do, lse, delta, causal, scale, block_q=None,
                    block_k=None, interpret=False):
    """(dq, dk, dv) of one chunk's contribution, given the GLOBAL (all
    chunks merged) lse and delta = rowsum(do * out), both [B,Hq,Sq].
    With the global lse, p = exp(s - lse) is each chunk's true posterior
    slice, so per-chunk (dq, dk, dv) sum exactly to the full gradients —
    the flash-attention backward identity at ring granularity."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    plan = _blocks(block_q, block_k, q, k, v, None, False, 0.0, causal)
    bq, bk = _bwd_lengths(plan)
    q3 = _pad_seq(q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    kx = _pad_seq(k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    vx = _pad_seq(v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D), bk)
    do3 = _pad_seq(do.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D), bq)
    pad_q = (-Sq) % bq
    lse2 = lse.reshape(B * Hq, Sq)
    delta2 = delta.reshape(B * Hq, Sq)
    if pad_q:
        # padded query rows: lse = +inf => p = 0, no dk/dv contribution
        lse2 = jnp.pad(lse2, ((0, 0), (0, pad_q)),
                       constant_values=float("inf"))
        delta2 = jnp.pad(delta2, ((0, 0), (0, pad_q)))
    dq3, dk3, dv3, _ = _bwd_impl(
        q3, kx, vx, do3, lse2, delta2, None, None, causal, scale,
        Sk - Sq, Sk, plan, {"rate": 0.0}, interpret, hq=Hq, hk=Hk)
    dq = dq3[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    dk = dk3[:, :Sk].reshape(B, Hk, Sk, D).transpose(0, 2, 1, 3)
    dv = dv3[:, :Sk].reshape(B, Hk, Sk, D).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# registry wiring
# ---------------------------------------------------------------------------

@register_op_impl("flash_attention", "pallas")
def _attention_pallas(q, k, v, bias, causal, scale, dropout_p, dropout_key,
                      window=None):
    """Pallas path for the training hot path, including attention dropout
    and additive bias in-kernel (reference contract
    paddle/phi/api/yaml/ops.yaml:978-989). ``attention_route`` says whether
    the call takes the kernels, with the plan's tiles, or the XLA reference
    impl."""
    from ...nn.functional.flash_attention import _attention_xla
    interpret = pallas_interpret()
    rate = float(dropout_p or 0.0)
    route, auto = route_here(q, k, bias, dropout_rate=rate,
                             has_key=dropout_key is not None,
                             causal=bool(causal), window=window)
    meshed = any(n > 1 for n in auto.values())
    if route.impl == "xla":
        return _attention_xla(q, k, v, bias, causal, scale, dropout_p,
                              dropout_key, window)
    seed = seed_from_key(dropout_key) if rate > 0.0 \
        else jnp.zeros((1,), jnp.int32)

    def kernel(q_, k_, v_):
        return flash_attention_ext(q_, k_, v_, bias, seed, None, None,
                                   bool(causal), float(scale), rate, None,
                                   None, interpret, window)
    if meshed:
        kernel = _per_shard(kernel, auto, q, k)
    return kernel(q, k, v)


def _per_shard(kernel, auto, q, k):
    """``kernel(q, k, v)`` as a shard_map over ``auto``, the {name: size}
    of the ambient mesh's GSPMD-owned axes (``jax.set_mesh``, entered by
    models.trainer's sharded step). GSPMD refuses a Mosaic call whose
    operands are sharded ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), and attention is
    independent across batch and heads, so each device runs the kernel on
    its own [B/dp, S, H/tp, D] block: batch over the data axis, heads over
    the tensor-parallel axis (SpecLayout vocabulary). An axis the mesh
    lacks, or that does not divide the dim, is left out of the spec — that
    dim is then computed whole on every device of the axis. Bias-free,
    dropout-free calls only: the in-kernel dropout hash and the bias index
    maps are written against global (batch*head) indices."""
    from jax.sharding import PartitionSpec

    from ...distributed.spec_layout import default_layout
    layout = default_layout()

    def axis(name, *dims):
        n = auto.get(name, 1)
        return name if n > 1 and all(d % n == 0 for d in dims) else None

    spec = PartitionSpec(axis(layout.data_axis, q.shape[0]), None,
                         axis(layout.tp_axis, q.shape[2], k.shape[2]), None)
    # every GSPMD-owned axis goes manual, named in the spec or not: one
    # left automatic would still face the partitioner with a Mosaic call.
    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # metadata (same as distributed/long_context.py)
    return jax.shard_map(kernel, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset(auto),
                         check_vma=False)
