"""Causal attention over key blocks that each query chose itself (InfLLM-V2,
the ``minicpm4`` mixer of MiniCPM4 / MiniCPM-SALA), as Pallas TPU kernels
with an XLA path for hosts without a chip.

The choice (``select_blocks``; XLA on every backend, float32, no gradient):
keys are mean-pooled over ``kernel_size`` every ``kernel_stride``; each query
head's softmax over the pooled keys that END at or before the query is summed
over the kv group's heads; a block's score is the max over the pooled keys
that overlap it (max-pool ``block/stride + 1``, stride ``block/stride``,
padding 1); block 0 (``init_blocks``) and the ``window_size / block_size``
blocks ending at the query's own are always taken, and the best-scoring
others fill up to ``topk`` in all (the lower block wins a tie). One choice
serves the whole kv group, and comes out as a bool [B, Hkv, S, blocks].

The kernels (``sparse_attn_fwd``, ``sparse_attn_bwd_dq``,
``sparse_attn_bwd_dkv``) are a causal sweep, not a gather: the flash
kernels' grids (``flash_attention.py`` is the template; nothing of it is
edited or imported for this) with two additions. A (query tile, key tile)
step that no query of the tile chose a block in is skipped, by a flag the
kernel reads from SMEM (scalar prefetch); inside a step each query's scores
are hidden except in the blocks it chose. The choice reaches the kernels
packed: one int32 word for each (query, key tile), bit ``j`` set when the
query chose the tile's ``j``-th block, so a 512-key tile of 64-key blocks
uses 8 bits. A step picks its tile's word out of the query's row of words
(one compare and lane sum), shifts it by each key's block number within the
tile and tests the low bit. With random weights the free choices scatter and
nearly no step is skipped: the sweep then costs what causal flash attention
costs, and is credited only the keys the rule keeps.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _Z, pallas_interpret

__all__ = ["SparseConfig", "select_blocks", "sparse_attention",
           "sparse_attention_xla", "sparse_tile_plan", "mean_attended_keys",
           "SPARSE_PLAN_TALLY"]

_NEG_INF = np.float32("-inf")
_F0 = np.float32(0.0)
_F1 = np.float32(1.0)
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
# the names the device trace finds the kernels by (benchmarks/metrics)
_KERNEL_NAMES = {"fwd": "sparse_attn_fwd", "dq": "sparse_attn_bwd_dq",
                 "dkv": "sparse_attn_bwd_dkv"}
# one count per lowered sparse mixer, by (heads, kv heads, S, path, bq, bk):
# trace time only, nothing a step
SPARSE_PLAN_TALLY: collections.Counter = collections.Counter()


class SparseConfig(NamedTuple):
    """MiniCPM4's published ``sparse_config``."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    def check(self, seq: int):
        if self.block_size % self.kernel_stride or \
                self.kernel_size != 2 * self.kernel_stride:
            raise ValueError(
                "the block score pools kernel block/stride + 1, stride "
                "block/stride, padding 1: kernel_size must be twice "
                "kernel_stride and block_size a multiple of it")
        if seq % self.block_size:
            raise ValueError(f"sequence {seq} is no multiple of block_size "
                             f"{self.block_size}")


def mean_attended_keys(seq: int, sc: SparseConfig) -> float:
    """The mean over positions of the keys a query attends: every visible
    key up to ``topk`` blocks (or on the dense path), then ``topk - 1`` whole
    blocks and its own so far."""
    if seq <= sc.dense_len:
        return (seq + 1) / 2.0
    t = np.arange(seq, dtype=np.int64)
    seen = t // sc.block_size + 1
    return float(np.where(seen <= sc.topk, t + 1,
                          (sc.topk - 1) * sc.block_size
                          + t % sc.block_size + 1).mean())


# ---------------------------------------------------------------------------
# the choice (XLA)
# ---------------------------------------------------------------------------

def _query_tile(s: int, most: int = 512) -> int:
    t = most
    while s % t:
        t //= 2
    return t


def block_scores(q, kbar, t, sc: SparseConfig, n_blocks: int):
    """q [G, T, D] float32 at positions ``t`` [T], pooled keys ``kbar``
    [n_pool, D] -> the blocks' scores [T, n_blocks]."""
    per = sc.block_size // sc.kernel_stride
    d, n_pool = q.shape[-1], kbar.shape[0]
    z = jnp.einsum("gtd,jd->gtj", q, kbar, precision=_HIGHEST,
                   preferred_element_type=jnp.float32) \
        * np.float32(1.0 / math.sqrt(d))
    last = jnp.arange(n_pool, dtype=jnp.int32) * sc.kernel_stride \
        + (sc.kernel_size - 1)
    vis = last[None, :] <= t[:, None]
    z = jnp.where(vis[None], z, _NEG_INF)
    m = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.where(vis[None], jnp.exp(z - jnp.where(m == _NEG_INF, _F0, m)),
                  _F0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.sum(e / jnp.where(den > 0, den, _F1), axis=0)
    width = per * n_blocks + 1
    padded = jnp.zeros((p.shape[0], width), p.dtype)
    padded = padded.at[:, 1:1 + min(n_pool, width - 1)].set(p[:, :width - 1])
    return jnp.max(jnp.stack(
        [padded[:, o:o + per * (n_blocks - 1) + 1:per]
         for o in range(per + 1)]), axis=0)


def choose(score, t, sc: SparseConfig):
    """[T, n_blocks] scores -> [T, n_blocks] bool, the ``min(topk, visible)``
    blocks each query takes: the forced ones rank first, an invisible one
    never, a tie goes to the lower block. A block's rank is counted (how many
    beat it) rather than sorted: ``jax.lax.top_k`` is a sort on the TPU, 38
    ms a step at 24,576 rows of 192 (PERF.md, PR 34), and the kernels want
    the set, not an order."""
    n_blocks = score.shape[1]
    own = (t // sc.block_size)[:, None]
    b = jnp.arange(n_blocks, dtype=jnp.int32)[None]
    forced = (b < sc.init_blocks) | ((b > own - sc.local_blocks) & (b <= own))
    ranked = jnp.where(b > own, _NEG_INF,
                       jnp.where(forced, np.float32("inf"), score))
    mine, other = ranked[:, :, None], ranked[:, None, :]
    beaten_by = (other > mine) | ((other == mine)
                                  & (b[:, None, :] < b[:, :, None]))
    rank = jnp.sum(beaten_by, axis=-1, dtype=jnp.int32)
    return (rank < min(sc.topk, n_blocks)) & (ranked > _NEG_INF)


def select_blocks(q, k, sc: SparseConfig):
    """q [B, S, Hq, D], k [B, S, Hkv, D] -> the chosen blocks [B, Hkv, S,
    n_blocks] bool, scored in float32 at highest precision whatever q and k
    are stored in. No gradient flows."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    sc.check(s)
    n_blocks = s // sc.block_size
    # the choice is XLA's, no kernel: the scope puts a name on its
    # operations (their ``op_name``) for whoever reads a profile
    with jax.named_scope("sparse_select"):
        q = jax.lax.stop_gradient(q).astype(jnp.float32)
        k = jax.lax.stop_gradient(k).astype(jnp.float32)
        n_pool = (s - sc.kernel_size) // sc.kernel_stride + 1
        win = jnp.arange(n_pool)[:, None] * sc.kernel_stride \
            + jnp.arange(sc.kernel_size)[None]
        kbar = jnp.mean(k[:, win], axis=2)             # [B, n_pool, Hkv, D]
        kbar = kbar.transpose(0, 2, 1, 3)
        tq = _query_tile(s)
        # [nq, B, Hkv, G, tq, D]
        qt = q.reshape(b, s // tq, tq, hkv, hq // hkv, d).transpose(
            1, 0, 3, 4, 2, 5)
        pos = jnp.arange(s, dtype=jnp.int32).reshape(s // tq, tq)

        def tile(args):
            qs, ts = args
            one = lambda qg, kb: choose(  # noqa: E731
                block_scores(qg, kb, ts, sc, n_blocks), ts, sc)
            return jax.vmap(jax.vmap(one))(qs, kbar)    # [B, Hkv, tq, nb]
        chosen = jax.lax.map(tile, (qt, pos))
        return chosen.transpose(1, 2, 0, 3, 4).reshape(b, hkv, s, n_blocks)


# ---------------------------------------------------------------------------
# the XLA path (CPU tests; plain autodiff)
# ---------------------------------------------------------------------------

def sparse_attention_xla(q, k, v, chosen, scale: float, block_size: int):
    """Masked softmax over all keys: q [B,S,Hq,D], k/v [B,S,Hkv,D], chosen
    [B,Hkv,S,n_blocks] bool -> [B,S,Hq,D]. Scores and softmax in float32."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    see = jnp.repeat(chosen, block_size, axis=-1) \
        & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])
    qg = q.reshape(b, s, hkv, g, d)
    z = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    z = jnp.where(see[:, :, None], z, _NEG_INF)
    p = jax.nn.softmax(z, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

class SparseTiles(NamedTuple):
    bq: int
    bk: int


def sparse_tile_plan(seq: int, block_size: int) -> SparseTiles:
    """(query tile, key tile) of the three kernels: 1024 x 1024 measured best
    of 256 x 256 ... 1024 x 1024 at S 12288, head 128 (39.5 ms forward and
    backward against 53.6 at 512 x 512 and 130.6 at 256 x 256; PERF.md, PR
    34); shorter sequences take the largest power of two that divides them.
    A key tile holds at most 32 blocks (one bit each of an int32 word)."""
    bq = _query_tile(seq, 1024)
    bk = max(_query_tile(seq, 1024), block_size)
    while bk // block_size > 32:
        bk //= 2
    return SparseTiles(bq, bk)


def pack_choice(chosen, seq: int, block_size: int, tiles: SparseTiles):
    """chosen [B,Hkv,S,n_blocks] bool -> (words [B*Hkv, S, lanes] int32, flags
    [B*Hkv*nq*nk] int32): bit j of ``words[., t, ki]`` says query ``t`` chose
    block ``ki * per + j``; a flag says some query of the query tile chose
    some block of the key tile. ``lanes`` is ``nk`` rounded up to 128."""
    b, hkv, s, _ = chosen.shape
    bq, bk = tiles
    per = bk // block_size
    nq, nk = seq // bq, seq // bk
    bits = chosen.reshape(b * hkv, s, nk, per)
    words = jnp.sum(bits.astype(jnp.int32)
                    << jnp.arange(per, dtype=jnp.int32), axis=-1,
                    dtype=jnp.int32)
    flags = jnp.any(words.reshape(b * hkv, nq, bq, nk) != 0, axis=2)
    lanes = -(-nk // _LANES) * _LANES
    words = jnp.pad(words, ((0, 0), (0, 0), (0, lanes - nk)))
    return words, flags.astype(jnp.int32).reshape(-1)


def _see(w_ref, ki, q_start, k_start, bq, bk, log2_block, diagonal):
    """[bq, bk] bool: the keys of tile ``ki`` each query of the tile may
    attend: those of its chosen blocks and, where the diagonal crosses the
    tile, not after itself."""
    w = w_ref[0]                                           # [bq, lanes]
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    word = jnp.sum(jnp.where(lane == ki, w, np.int32(0)), axis=1,
                   keepdims=True, dtype=jnp.int32)          # [bq, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    shift = jax.lax.shift_right_logical(col, np.int32(log2_block))
    bit = jax.lax.shift_right_logical(
        jnp.broadcast_to(word, (bq, bk)), shift) & np.int32(1)
    see = bit != 0
    if diagonal:
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        see = jnp.logical_and(see, k_start + col <= q_start + row)
    return see


def _step(body, flag, q_start, k_start, bq, bk):
    """Run ``body(diagonal)`` if the step is live: not wholly above the
    diagonal, and flagged; once with the causal mask where the diagonal
    crosses the tile, once without."""
    live = jnp.logical_and(k_start <= q_start + np.int32(bq - 1), flag != 0)
    crossed = k_start + np.int32(bk - 1) > q_start

    @pl.when(jnp.logical_and(live, crossed))
    def _diag():
        body(True)

    @pl.when(jnp.logical_and(live, jnp.logical_not(crossed)))
    def _below():
        body(False)


def _fwd_kernel(flags_ref, q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, bq, bk, nq, nk, group,
                log2_block):
    scale = np.float32(scale)
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kvh = jax.lax.div(bh, np.int32(group))
    flag = flags_ref[(kvh * np.int32(nq) + qi) * np.int32(nk) + ki]
    q_start, k_start = qi * np.int32(bq), ki * np.int32(bk)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(diagonal):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_see(w_ref, ki, q_start, k_start, bq, bk, log2_block,
                           diagonal), s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(
            jnp.max(s, axis=1, keepdims=True), m_prev.shape))
        m_safe = jnp.where(m_new == _NEG_INF, _F0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _step(attend, flag, q_start, k_start, bq, bk)

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_ref[:, :1]
        o_ref[0] = jnp.where(l > 0.0, acc_ref[...]
                             / jnp.where(l == 0.0, _F1, l), _F0
                             ).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            l > 0.0, m_ref[:, :1] + jnp.log(jnp.maximum(l, np.float32(1e-38))),
            _NEG_INF)


def _probabilities(q, k, lse_ref, w_ref, ki, q_start, k_start, bq, bk,
                   log2_block, diagonal, scale):
    lse = lse_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_see(w_ref, ki, q_start, k_start, bq, bk, log2_block,
                       diagonal), s, _NEG_INF)
    return jnp.exp(s - jnp.where(lse == _NEG_INF, _F0, lse))


def _dq_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               w_ref, dq_ref, acc_ref, *, scale, bq, bk, nq, nk, group,
               log2_block):
    scale = np.float32(scale)
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kvh = jax.lax.div(bh, np.int32(group))
    flag = flags_ref[(kvh * np.int32(nq) + qi) * np.int32(nk) + ki]
    q_start, k_start = qi * np.int32(bq), ki * np.int32(bk)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(diagonal):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p = _probabilities(q, k, lse_ref, w_ref, ki, q_start, k_start, bq,
                           bk, log2_block, diagonal, scale)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _step(attend, flag, q_start, k_start, bq, bk)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                w_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk, nq,
                nk, group, log2_block):
    """Grid (B*Hkv, nk, group, nq): a kv head's key tile gathers dk and dv
    over its group's query heads and the query tiles at or below it."""
    scale = np.float32(scale)
    kvh, ki = pl.program_id(0), pl.program_id(1)
    r, qi = pl.program_id(2), pl.program_id(3)
    flag = flags_ref[(kvh * np.int32(nq) + qi) * np.int32(nk) + ki]
    q_start, k_start = qi * np.int32(bq), ki * np.int32(bk)

    @pl.when(jnp.logical_and(r == 0, qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def attend(diagonal):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p = _probabilities(q, k, lse_ref, w_ref, ki, q_start, k_start, bq,
                           bk, log2_block, diagonal, scale)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _step(attend, flag, q_start, k_start, bq, bk)

    @pl.when(jnp.logical_and(r == np.int32(group - 1),
                             qi == np.int32(nq - 1)))
    def _fin():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _params(semantics, bq, bk, d):
    # q, k, v, do, out tiles double-buffered, the words, three f32 tiles of
    # scores and two accumulators
    vmem = 2 * 2 * (3 * bq + 2 * bk) * d + 2 * bq * _LANES * 4 \
        + 4 * bq * bk * 4 + 2 * max(bq, bk) * d * 4 + 2 * bq * _LANES * 4
    limit = None
    if vmem > 12 * 1024 * 1024:
        limit = min(int(vmem * 1.5), 100 * 1024 * 1024)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _query_major_maps(bq, bk, group):
    """Index maps of the forward and dq grids (batch x query head, query
    tile, key tile; the scalar-prefetched flags ride last): a step above the
    diagonal asks for the key tile it already holds."""
    g = np.int32(group)

    def q_map(b, qi, ki, fl):
        return b, qi, _Z

    def kv_map(b, qi, ki, fl):
        last = jax.lax.div((qi + 1) * np.int32(bq) - 1, np.int32(bk))
        return jax.lax.div(b, g), jnp.minimum(ki, last), _Z

    def w_map(b, qi, ki, fl):
        return jax.lax.div(b, g), qi, _Z
    return q_map, kv_map, w_map


def _statics(q3, block_size, tiles, group, scale):
    _, s, d = q3.shape
    bq, bk = tiles
    return dict(scale=float(scale), bq=bq, bk=bk, nq=s // bq, nk=s // bk,
                group=group, log2_block=int(math.log2(block_size))), d


@functools.partial(jax.jit, static_argnames=("block_size", "tiles", "group",
                                             "scale", "interpret"))
def _fwd(q3, k3, v3, words, flags, *, block_size, tiles, group, scale,
         interpret):
    kw, d = _statics(q3, block_size, tiles, group, scale)
    bq, bk, nq, nk = kw["bq"], kw["bk"], kw["nq"], kw["nk"]
    bh, s, _ = q3.shape
    q_map, kv_map, w_map = _query_major_maps(bq, bk, group)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, nq, nk),
            in_specs=[pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bq, words.shape[2]), w_map)],
            out_specs=[pl.BlockSpec((1, bq, d), q_map),
                       pl.BlockSpec((1, bq, 1), q_map)],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "arbitrary"), bq,
                                bk, d),
        interpret=interpret, name=_KERNEL_NAMES["fwd"],
    )(flags, q3, k3, v3, words)


@functools.partial(jax.jit, static_argnames=("block_size", "tiles", "group",
                                             "scale", "interpret"))
def _bwd_dq(q3, k3, v3, do3, lse, delta, words, flags, *, block_size, tiles,
            group, scale, interpret):
    kw, d = _statics(q3, block_size, tiles, group, scale)
    bq, bk, nq, nk = kw["bq"], kw["bk"], kw["nq"], kw["nk"]
    bh, s, _ = q3.shape
    q_map, kv_map, w_map = _query_major_maps(bq, bk, group)
    return pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, nq, nk),
            in_specs=[pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bq, 1), q_map),
                      pl.BlockSpec((1, bq, 1), q_map),
                      pl.BlockSpec((1, bq, words.shape[2]), w_map)],
            out_specs=pl.BlockSpec((1, bq, d), q_map),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary"), bq,
                                bk, d),
        interpret=interpret, name=_KERNEL_NAMES["dq"],
    )(flags, q3, k3, v3, do3, lse, delta, words)


@functools.partial(jax.jit, static_argnames=("block_size", "tiles", "group",
                                             "scale", "interpret"))
def _bwd_dkv(q3, k3, v3, do3, lse, delta, words, flags, *, block_size, tiles,
             group, scale, interpret):
    kw, d = _statics(q3, block_size, tiles, group, scale)
    bq, bk, nq, nk = kw["bq"], kw["bk"], kw["nq"], kw["nk"]
    bkv, s, _ = k3.shape
    g = np.int32(group)

    def first_query(ki):
        return jax.lax.div(ki * np.int32(bk), np.int32(bq))

    def q_map(b, ki, r, qi, fl):
        return b * g + r, jnp.maximum(qi, first_query(ki)), _Z

    def kv_map(b, ki, r, qi, fl):
        return b, ki, _Z

    def w_map(b, ki, r, qi, fl):
        return b, jnp.maximum(qi, first_query(ki)), _Z

    return pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bkv, nk, group, nq),
            in_specs=[pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bq, 1), q_map),
                      pl.BlockSpec((1, bq, 1), q_map),
                      pl.BlockSpec((1, bq, words.shape[2]), w_map)],
            out_specs=[pl.BlockSpec((1, bk, d), kv_map),
                       pl.BlockSpec((1, bk, d), kv_map)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bkv, s, d), k3.dtype),
                   jax.ShapeDtypeStruct((bkv, s, d), v3.dtype)],
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary", "arbitrary"), bq, bk, d),
        interpret=interpret, name=_KERNEL_NAMES["dkv"],
    )(flags, q3, k3, v3, do3, lse, delta, words)


def _heads_first(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _heads_last(x3, b):
    bh, s, d = x3.shape
    return x3.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sparse_attention(q, k, v, chosen, scale, block_size, tiles, interpret):
    """q [B,S,Hq,D], k/v [B,S,Hkv,D], chosen [B,Hkv,S,n_blocks] bool (the
    blocks each query attends) -> out [B,S,Hq,D] through the three kernels.
    ``tiles``: a ``SparseTiles`` or None for the plan's."""
    out, _ = _sa_fwd(q, k, v, chosen, scale, block_size, tiles, interpret)
    return out


def _sa_fwd(q, k, v, chosen, scale, block_size, tiles, interpret):
    from ...distributed.fleet.recompute import keep
    b, s, hq, d = q.shape
    tiles = tiles or sparse_tile_plan(s, block_size)
    interpret = pallas_interpret() if interpret is None else interpret
    words, flags = pack_choice(chosen, s, block_size, tiles)
    out3, lse = _fwd(_heads_first(q), _heads_first(k), _heads_first(v),
                     words, flags, block_size=block_size, tiles=tiles,
                     group=hq // k.shape[2], scale=scale, interpret=interpret)
    # a recomputed block may keep these (recompute's "sala_saveable") and
    # spare the forward sweep's second run
    out = keep(_heads_last(out3, b), "sparse_out")
    lse = keep(lse, "sparse_lse")
    return out, (q, k, v, chosen, out, lse)


def _sa_bwd(scale, block_size, tiles, interpret, res, dout):
    q, k, v, chosen, out, lse = res
    b, s, hq, d = q.shape
    tiles = tiles or sparse_tile_plan(s, block_size)
    interpret = pallas_interpret() if interpret is None else interpret
    words, flags = pack_choice(chosen, s, block_size, tiles)
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32),
                    axis=-1)                                # [B, S, Hq]
    delta = delta.transpose(0, 2, 1).reshape(b * hq, s, 1)
    kw = dict(block_size=block_size, tiles=tiles, group=hq // k.shape[2],
              scale=scale, interpret=interpret)
    q3, k3, v3 = _heads_first(q), _heads_first(k), _heads_first(v)
    do3 = _heads_first(dout.astype(q.dtype))
    dq3 = _bwd_dq(q3, k3, v3, do3, lse, delta, words, flags, **kw)
    dk3, dv3 = _bwd_dkv(q3, k3, v3, do3, lse, delta, words, flags, **kw)
    return (_heads_last(dq3, b), _heads_last(dk3, b), _heads_last(dv3, b),
            None)


sparse_attention.defvjp(_sa_fwd, _sa_bwd)
