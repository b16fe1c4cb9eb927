"""Long-context attention strategies: ring attention and Ulysses.

The reference's long-context story (SURVEY.md §5.7) stops at sharding the
sequence axis and gathering before a local attention kernel
(fleet/meta_parallel/segment_parallel.py, sequence_parallel_utils.py, and
the sep axis in base/topology.py:64); it has no ring-attention or
all-to-all attention in-tree. Here both are first-class TPU-native
strategies, designed for the ICI torus:

- ``ring_attention``: q/k/v stay sharded on the sequence axis; k/v chunks
  rotate around the ring via ``ppermute`` while each device accumulates its
  queries' attention with the online-softmax (m, l) recurrence — the
  flash-attention math at the inter-chip level. Communication is
  neighbor-to-neighbor, exactly what ICI is best at, and overlaps with the
  per-chunk compute.
- ``ulysses_attention``: one ``all_to_all`` re-shards activations from
  sequence-sharded to head-sharded, runs the full-sequence local kernel
  (the Pallas flash kernel on TPU), and swaps back. Cheaper for moderate
  sequence lengths; requires num_heads % axis_size == 0.
- ``ring_attention(..., layout="zigzag")``: the causal ring's load
  balance fix — device d holds sub-chunks (c_d, c_{2N-1-d}), making every
  step near-equal work instead of the last device gating the ring.

Both are pure-jnp + lax collectives, so jax.vjp differentiates through
them (the scan body is rematerialized instead of storing per-step score
matrices).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.dispatch import run_op

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_local",
           "ulysses_attention_local"]


def shard_map(f, mesh, in_specs, out_specs):
    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # metadata, so the vma checker rejects any kernel launched inside
    # the shard (both the ring chunk kernels and Ulysses' local flash)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

_NEG_INF = float("-inf")


def _online_update(qf, kc, vc, acc, m, l, q_off, k_off, causal):
    """One blockwise softmax-accumulation step.

    qf: (B, Sq, H, D) f32 (pre-scaled by the caller); kc/vc: (B, Sc, Hk, D)
    with Hk == H or a GQA divisor of it (expanded here, after the ring
    transfer, so only Hk heads ride the ICI);
    acc: (B, H, Sq, D); m, l: (B, H, Sq, 1). Offsets are global sequence
    positions of the q and k chunks (traced scalars are fine).
    """
    kc = _repeat_kv(kc, qf.shape[2])
    vc = _repeat_kv(vc, qf.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32))
    if causal:
        sq, sk = qf.shape[1], kc.shape[1]
        qidx = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kidx = k_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((kidx <= qidx)[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
    alpha = jnp.exp(m - m_safe)
    p = jnp.exp(s - m_safe)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd", p,
                                       vc.astype(jnp.float32))
    return acc_new, m_new, l_new


def _repeat_kv(k, hq):
    hk = k.shape[2]
    if hk != hq:
        k = jnp.repeat(k, hq // hk, axis=2)
    return k


def _vary(xs, axis_name):
    """Mark replicated-constant scan carries device-varying over the mesh
    axis (required before they meet ppermute'd values in the carry)."""
    return tuple(jax.lax.pcast(x, (axis_name,), to="varying") for x in xs)


# ---------------------------------------------------------------------------
# Pallas-backed ring attention (VERDICT r4 #5): each ring step runs the
# flash block kernel (ops/pallas/flash_attention.py) on the resident k/v
# chunk, and per-chunk (out, lse) pairs merge by log-sum-exp — the online-
# softmax carry at inter-chip granularity, with the intra-chip tiling done
# by the same kernel the single-chip path ships. The backward is a second
# ring pass: dk/dv accumulators rotate WITH their chunk while each device
# adds its queries' contribution via the Pallas backward fed the global
# lse/delta (with the global lse, per-chunk gradients sum exactly).
# ---------------------------------------------------------------------------

def _bwd_delta(do, out):
    """delta_i = rowsum(dO_i * O_i) — shared by every chunk's backward."""
    return jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                      out.astype(jnp.float32))


def _merge_lse(out_acc, lse_acc, o, lse):
    """Merge a new chunk's normalized (o, lse) into the running pair."""
    lse_new = jnp.logaddexp(lse_acc, lse)
    safe = jnp.where(lse_new == _NEG_INF, 0.0, lse_new)
    wa = jnp.where(lse_acc == _NEG_INF, 0.0, jnp.exp(lse_acc - safe))
    wb = jnp.where(lse == _NEG_INF, 0.0, jnp.exp(lse - safe))

    def tr(w):  # (B, H, S) weights onto (B, S, H, 1) activations
        return w.transpose(0, 2, 1)[..., None]

    return out_acc * tr(wa) + o.astype(jnp.float32) * tr(wb), lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, axis_size, causal, scale, interpret):
    """Ring attention with Pallas per-chunk compute; call inside shard_map
    with q/k/v sequence-sharded [B, S/N, H(k), D]. GQA-native: kv chunks
    rotate un-expanded (Hk heads of ICI traffic)."""
    out, _ = _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, scale,
                             interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, scale,
                    interpret):
    from ..ops.pallas.flash_attention import flash_chunk_fwd
    B, sc, H, D = q.shape
    # only the causal schedule consults the device index; a dead
    # axis_index in the non-causal graph survives DCE and lowers to a
    # PartitionId instruction the SPMD partitioner rejects
    idx = jax.lax.axis_index(axis_name) if causal else None
    perm = [((r + 1) % axis_size, r) for r in range(axis_size)]
    out0 = jnp.zeros((B, sc, H, D), jnp.float32)
    lse0 = jnp.full((B, H, sc), _NEG_INF, jnp.float32)
    out0, lse0 = _vary((out0, lse0), axis_name)

    def full(kc, vc):
        return flash_chunk_fwd(q, kc, vc, False, scale, interpret=interpret)

    def diag(kc, vc):
        return flash_chunk_fwd(q, kc, vc, True, scale, interpret=interpret)

    def skip(kc, vc):
        return (jnp.zeros((B, sc, H, D), q.dtype),
                jnp.full((B, H, sc), _NEG_INF, jnp.float32))

    def body(carry, t):
        kc, vc, out_acc, lse_acc = carry
        if causal:
            # j < idx: chunk fully visible; j == idx: the diagonal chunk
            # (in-kernel causal mask); j > idx: fully masked — skip the
            # compute entirely (lax.switch runs one branch at runtime)
            j = (idx + t) % axis_size
            br = jnp.where(j == idx, 1, jnp.where(j < idx, 0, 2))
            o, lse = jax.lax.switch(br, (full, diag, skip), kc, vc)
        else:
            o, lse = full(kc, vc)
        out_acc, lse_acc = _merge_lse(out_acc, lse_acc, o, lse)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, out_acc, lse_acc), None

    (_, _, out_acc, lse), _ = jax.lax.scan(
        body, (k, v, out0, lse0), jnp.arange(axis_size))
    out = out_acc.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, axis_size, causal, scale, interpret, res,
                    do):
    from ..ops.pallas.flash_attention import flash_chunk_bwd
    q, k, v, out, lse = res
    B, sc, H, D = q.shape
    idx = jax.lax.axis_index(axis_name) if causal else None
    perm = [((r + 1) % axis_size, r) for r in range(axis_size)]
    delta = _bwd_delta(do, out)
    dq0 = jnp.zeros((B, sc, H, D), jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0, dk0, dv0 = _vary((dq0, dk0, dv0), axis_name)

    def full(kc, vc):
        return flash_chunk_bwd(q, kc, vc, do, lse, delta, False, scale,
                               interpret=interpret)

    def diag(kc, vc):
        return flash_chunk_bwd(q, kc, vc, do, lse, delta, True, scale,
                               interpret=interpret)

    def skip(kc, vc):
        return (jnp.zeros((B, sc, H, D), q.dtype),
                jnp.zeros(k.shape, q.dtype), jnp.zeros(v.shape, q.dtype))

    def body(carry, t):
        kc, vc, dkc, dvc, dq_acc = carry
        if causal:
            j = (idx + t) % axis_size
            br = jnp.where(j == idx, 1, jnp.where(j < idx, 0, 2))
            dq_c, dk_c, dv_c = jax.lax.switch(br, (full, diag, skip),
                                              kc, vc)
        else:
            dq_c, dk_c, dv_c = full(kc, vc)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        # dk/dv accumulators rotate WITH their chunk: after axis_size
        # steps every chunk is home carrying all devices' contributions
        dkc = dkc + dk_c.astype(jnp.float32)
        dvc = dvc + dv_c.astype(jnp.float32)
        kc, vc, dkc, dvc = (jax.lax.ppermute(x, axis_name, perm)
                            for x in (kc, vc, dkc, dvc))
        return (kc, vc, dkc, dvc, dq_acc), None

    (_, _, dkc, dvc, dq_acc), _ = jax.lax.scan(
        body, (k, v, dk0, dv0, dq0), jnp.arange(axis_size))
    return (dq_acc.astype(q.dtype), dkc.astype(k.dtype),
            dvc.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_chunked_single(q, k, v, n_chunks, causal, scale, interpret):
    """Single-chip model of the per-device ring compute: q/k/v [B,S,H(k),D]
    split into ``n_chunks`` sequence chunks, flash block kernel per (qi,
    kj) chunk pair, log-sum-exp merge — exactly what each ring device
    executes, minus the ppermute: its time against the monolithic kernel
    is the ring's single-chip compute overhead."""
    out, _ = _ring_chunked_fwd(q, k, v, n_chunks, causal, scale, interpret)
    return out


def _ring_chunked_fwd(q, k, v, n_chunks, causal, scale, interpret):
    from ..ops.pallas.flash_attention import flash_chunk_fwd
    B, S, H, D = q.shape
    if S % n_chunks:
        raise ValueError(
            f"ring_chunked_single: sequence {S} not divisible by "
            f"n_chunks {n_chunks}")
    sc = S // n_chunks
    outs, lses = [], []
    for i in range(n_chunks):
        qi = q[:, i * sc:(i + 1) * sc]
        out_acc = jnp.zeros((B, sc, H, D), jnp.float32)
        lse_acc = jnp.full((B, H, sc), _NEG_INF, jnp.float32)
        for j in range(i + 1 if causal else n_chunks):
            kc = k[:, j * sc:(j + 1) * sc]
            vc = v[:, j * sc:(j + 1) * sc]
            o, lse = flash_chunk_fwd(qi, kc, vc, causal and j == i, scale,
                                     interpret=interpret)
            out_acc, lse_acc = _merge_lse(out_acc, lse_acc, o, lse)
        outs.append(out_acc.astype(q.dtype))
        lses.append(lse_acc)
    out = jnp.concatenate(outs, axis=1)
    lse = jnp.concatenate(lses, axis=2)
    return out, (q, k, v, out, lse)


def _ring_chunked_bwd(n_chunks, causal, scale, interpret, res, do):
    from ..ops.pallas.flash_attention import flash_chunk_bwd
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    sc = S // n_chunks
    delta = _bwd_delta(do, out)
    dqs = []
    dks = [jnp.zeros((B, sc) + k.shape[2:], jnp.float32)
           for _ in range(n_chunks)]
    dvs = [jnp.zeros((B, sc) + v.shape[2:], jnp.float32)
           for _ in range(n_chunks)]
    for i in range(n_chunks):
        qi = q[:, i * sc:(i + 1) * sc]
        doi = do[:, i * sc:(i + 1) * sc]
        lsei = lse[:, :, i * sc:(i + 1) * sc]
        deltai = delta[:, :, i * sc:(i + 1) * sc]
        dq_acc = jnp.zeros((B, sc, H, D), jnp.float32)
        for j in range(i + 1 if causal else n_chunks):
            kc = k[:, j * sc:(j + 1) * sc]
            vc = v[:, j * sc:(j + 1) * sc]
            dq_c, dk_c, dv_c = flash_chunk_bwd(
                qi, kc, vc, doi, lsei, deltai, causal and j == i, scale,
                interpret=interpret)
            dq_acc = dq_acc + dq_c.astype(jnp.float32)
            dks[j] = dks[j] + dk_c.astype(jnp.float32)
            dvs[j] = dvs[j] + dv_c.astype(jnp.float32)
        dqs.append(dq_acc.astype(q.dtype))
    return (jnp.concatenate(dqs, axis=1),
            jnp.concatenate(dks, axis=1).astype(k.dtype),
            jnp.concatenate(dvs, axis=1).astype(v.dtype))


ring_chunked_single.defvjp(_ring_chunked_fwd, _ring_chunked_bwd)


# ---------------------------------------------------------------------------
# Zigzag ring attention: causal load balancing. With contiguous chunks,
# device 0's queries see only their own chunk (idle N-1 of N steps) while
# device N-1 computes against every chunk — the causal ring's wall time is
# the LAST device's. The zigzag layout gives device d sub-chunks
# (c_d, c_{2N-1-d}) of the 2N-way split; at every step each device runs
# exactly one always-visible pair (q_hi x k_lo) plus one pair that is
# full/diag/skip complementarily across devices — near-perfect balance,
# ~2x causal ring throughput at scale. (Same trick as the public zigzag /
# striped ring-attention formulations; built here from the identical
# flash_chunk primitives + lse merges the contiguous ring uses.)
# ---------------------------------------------------------------------------

def _zigzag_perm(S: int, N: int):
    """new-position -> old-position index map: device d's shard is
    (c_d, c_{2N-1-d}) of the 2N-way chunk split. (Reference layout for
    tests; the runtime exchange is the structured ppermute pair in
    ``_zz_shard_exchange`` — never a global gather.)"""
    import numpy as _np
    if S % (2 * N):
        raise ValueError(
            f"zigzag ring needs seq {S} divisible by 2*axis_size {2 * N}")
    scc = S // (2 * N)
    idx = []
    for d in range(N):
        idx.extend(range(d * scc, (d + 1) * scc))
        j = 2 * N - 1 - d
        idx.extend(range(j * scc, (j + 1) * scc))
    return _np.asarray(idx, dtype=_np.int32)


def _zz_shard_exchange(lo, hi, axis_name, axis_size, inverse=False):
    """Contiguous <-> zigzag shard layout in TWO ppermutes (each sub-chunk
    travels once over ICI; a global take across the sharded axis would
    all-gather the sequence and forfeit the O(S/N) memory property).

    Forward: device d holds contiguous (c_{2d}, c_{2d+1}) and ends with
    zigzag (c_d, c_{2N-1-d}). Each stream's source->target map is a
    device permutation; receivers select by their own parity (device t's
    zig-lo c_t arrives on the even-chunk stream iff t is even)."""
    n = axis_size
    idx = jax.lax.axis_index(axis_name)
    even = (idx % 2 == 0)
    if not inverse:
        # stream 0 carries c_{2d} (even chunks), stream 1 carries
        # c_{2d+1} (odd chunks); chunk c_j lands on device j if j < n
        # else 2n-1-j
        perm0 = [(d, 2 * d if 2 * d < n else 2 * n - 1 - 2 * d)
                 for d in range(n)]
        perm1 = [(d, 2 * d + 1 if 2 * d + 1 < n else 2 * n - 2 - 2 * d)
                 for d in range(n)]
        r0 = jax.lax.ppermute(lo, axis_name, perm0)
        r1 = jax.lax.ppermute(hi, axis_name, perm1)
        return jnp.where(even, r0, r1), jnp.where(even, r1, r0)
    # inverse: device d holds (c_d, c_{2n-1-d}); exactly one of the two is
    # an even chunk (parity of d decides which) — send it on the even
    # stream toward device j//2, likewise the odd chunk
    send_even = jnp.where(even, lo, hi)
    send_odd = jnp.where(even, hi, lo)
    perm_e = [(d, (d if d % 2 == 0 else 2 * n - 1 - d) // 2)
              for d in range(n)]
    perm_o = [(d, (d if d % 2 == 1 else 2 * n - 1 - d) // 2)
              for d in range(n)]
    return (jax.lax.ppermute(send_even, axis_name, perm_e),
            jax.lax.ppermute(send_odd, axis_name, perm_o))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _zigzag_ring_flash(q, k, v, axis_name, axis_size, scale, interpret):
    """Causal-only, zigzag-sharded per-device body: q/k/v
    [B, 2*scc, H(k), D] holding (c_d, c_{2N-1-d}). Call inside shard_map
    over the zigzag-permuted sequence."""
    out, _ = _zz_fwd(q, k, v, axis_name, axis_size, scale, interpret)
    return out


def _zz_split(x):
    scc = x.shape[1] // 2
    return x[:, :scc], x[:, scc:]


def _zz_fwd(q, k, v, axis_name, axis_size, scale, interpret):
    from ..ops.pallas.flash_attention import flash_chunk_fwd
    B, sc2, H, D = q.shape
    scc = sc2 // 2
    idx = jax.lax.axis_index(axis_name)
    perm = [((r + 1) % axis_size, r) for r in range(axis_size)]
    q_lo, q_hi = _zz_split(q)

    def acc0():
        return (jnp.zeros((B, scc, H, D), jnp.float32),
                jnp.full((B, H, scc), _NEG_INF, jnp.float32))

    o_lo, l_lo = _vary(acc0(), axis_name)
    o_hi, l_hi = _vary(acc0(), axis_name)

    def pair(qc, causal):
        def run(kc, vc):
            return flash_chunk_fwd(qc, kc, vc, causal, scale,
                                   interpret=interpret)
        return run

    def skip(kc, vc):
        return (jnp.zeros((B, scc, H, D), q.dtype),
                jnp.full((B, H, scc), _NEG_INF, jnp.float32))

    def body(carry, t):
        kc2, vc2, o_lo, l_lo, o_hi, l_hi = carry
        j = (idx + t) % axis_size
        br = jnp.where(j == idx, 1, jnp.where(j < idx, 0, 2))
        k_lo, k_hi = _zz_split(kc2)
        v_lo, v_hi = _zz_split(vc2)
        # pair3 (q_hi x k_lo): c_{2N-1-idx} always AFTER c_j — every
        # branch computes it, so it stays outside the switch
        o3, l3 = flash_chunk_fwd(q_hi, k_lo, v_lo, False, scale,
                                 interpret=interpret)
        o_hi, l_hi = _merge_lse(o_hi, l_hi, o3, l3)
        # pair1 (q_lo x k_lo): full when j < idx, diag at j == idx,
        # fully-masked after
        o1, l1 = jax.lax.switch(
            br, (pair(q_lo, False), pair(q_lo, True), skip), k_lo, v_lo)
        o_lo, l_lo = _merge_lse(o_lo, l_lo, o1, l1)
        # pair4 (q_hi x k_hi): the complement — masked when j < idx,
        # diag at j == idx, full after (c_{2N-1-j} < c_{2N-1-idx})
        o4, l4 = jax.lax.switch(
            br, (skip, pair(q_hi, True), pair(q_hi, False)), k_hi, v_hi)
        o_hi, l_hi = _merge_lse(o_hi, l_hi, o4, l4)
        kc2 = jax.lax.ppermute(kc2, axis_name, perm)
        vc2 = jax.lax.ppermute(vc2, axis_name, perm)
        return (kc2, vc2, o_lo, l_lo, o_hi, l_hi), None

    (_, _, o_lo, l_lo, o_hi, l_hi), _ = jax.lax.scan(
        body, (k, v, o_lo, l_lo, o_hi, l_hi), jnp.arange(axis_size))
    out = jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)
    lse = jnp.concatenate([l_lo, l_hi], axis=2)
    return out, (q, k, v, out, lse)


def _zz_bwd(axis_name, axis_size, scale, interpret, res, do):
    from ..ops.pallas.flash_attention import flash_chunk_bwd
    q, k, v, out, lse = res
    B, sc2, H, D = q.shape
    scc = sc2 // 2
    idx = jax.lax.axis_index(axis_name)
    perm = [((r + 1) % axis_size, r) for r in range(axis_size)]
    delta = _bwd_delta(do, out)
    q_lo, q_hi = _zz_split(q)
    do_lo, do_hi = _zz_split(do)
    l_lo, l_hi = lse[:, :, :scc], lse[:, :, scc:]
    d_lo, d_hi = delta[:, :, :scc], delta[:, :, scc:]

    kv_shape = (B, scc) + k.shape[2:]

    def bwd_pair(qc, doc, lc, dc, causal):
        def run(kc, vc):
            return flash_chunk_bwd(qc, kc, vc, doc, lc, dc, causal,
                                   scale, interpret=interpret)
        return run

    def skip(kc, vc):
        return (jnp.zeros((B, scc, H, D), q.dtype),
                jnp.zeros(kv_shape, q.dtype),
                jnp.zeros(kv_shape, q.dtype))

    dq0 = jnp.zeros((B, sc2, H, D), jnp.float32)
    dkv0 = jnp.zeros((B, sc2) + k.shape[2:], jnp.float32)
    dq0, dk0, dv0 = _vary((dq0, dkv0, dkv0), axis_name)

    def body(carry, t):
        kc2, vc2, dkc2, dvc2, dq = carry
        j = (idx + t) % axis_size
        br = jnp.where(j == idx, 1, jnp.where(j < idx, 0, 2))
        k_lo, k_hi = _zz_split(kc2)
        v_lo, v_hi = _zz_split(vc2)
        # pair3: q_hi x k_lo, always visible
        dq3, dk3, dv3 = flash_chunk_bwd(q_hi, k_lo, v_lo, do_hi, l_hi,
                                        d_hi, False, scale,
                                        interpret=interpret)
        # pair1: q_lo x k_lo (full / diag / masked)
        dq1, dk1, dv1 = jax.lax.switch(
            br, (bwd_pair(q_lo, do_lo, l_lo, d_lo, False),
                 bwd_pair(q_lo, do_lo, l_lo, d_lo, True), skip),
            k_lo, v_lo)
        # pair4: q_hi x k_hi (masked / diag / full)
        dq4, dk4, dv4 = jax.lax.switch(
            br, (skip, bwd_pair(q_hi, do_hi, l_hi, d_hi, True),
                 bwd_pair(q_hi, do_hi, l_hi, d_hi, False)),
            k_hi, v_hi)
        f32 = jnp.float32
        dq = dq.at[:, :scc].add(dq1.astype(f32))
        dq = dq.at[:, scc:].add(dq3.astype(f32) + dq4.astype(f32))
        dkc2 = dkc2.at[:, :scc].add(dk1.astype(f32) + dk3.astype(f32))
        dkc2 = dkc2.at[:, scc:].add(dk4.astype(f32))
        dvc2 = dvc2.at[:, :scc].add(dv1.astype(f32) + dv3.astype(f32))
        dvc2 = dvc2.at[:, scc:].add(dv4.astype(f32))
        kc2, vc2, dkc2, dvc2 = (jax.lax.ppermute(x, axis_name, perm)
                                for x in (kc2, vc2, dkc2, dvc2))
        return (kc2, vc2, dkc2, dvc2, dq), None

    (_, _, dkc2, dvc2, dq), _ = jax.lax.scan(
        body, (k, v, dk0, dv0, dq0), jnp.arange(axis_size))
    return dq.astype(q.dtype), dkc2.astype(k.dtype), dvc2.astype(v.dtype)


_zigzag_ring_flash.defvjp(_zz_fwd, _zz_bwd)


def ring_attention_local(q, k, v, axis_name, axis_size, causal=True,
                         scale=None, impl=None):
    """Per-shard body: call inside shard_map with q/k/v sequence-sharded
    [B, S/N, H, D]. Returns the local output chunk [B, S/N, H, D].

    ``impl``: "pallas" runs the flash block kernel inside each ring step
    (the TPU path — interpret-mode on CPU when forced); "xla" is the
    pure-jnp online-softmax reference; None picks by backend."""
    B, sc, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    from ..ops.pallas.common import pallas_interpret
    if impl is None:
        impl = "xla" if pallas_interpret() else "pallas"
    if impl == "pallas":
        interpret = pallas_interpret()
        return _ring_flash(q, k, v, axis_name, axis_size, causal,
                           float(scale), interpret)
    # GQA kv chunks rotate un-expanded (Hk heads of ICI traffic, not H)
    idx = jax.lax.axis_index(axis_name)
    qf = q.astype(jnp.float32) * scale
    acc = jnp.zeros((B, H, sc, D), jnp.float32)
    m = jnp.full((B, H, sc, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, sc, 1), jnp.float32)
    # the scan carry must be device-varying over the mesh axis from step 0
    acc, m, l = _vary((acc, m, l), axis_name)
    # neighbor ring: each step every device hands its current k/v chunk to
    # the previous rank, so device i sees chunk (i + t) mod N at step t
    perm = [((r + 1) % axis_size, r) for r in range(axis_size)]

    def body(carry, t):
        kc, vc, acc, m, l = carry
        j = (idx + t) % axis_size
        # remat: recompute the per-step score matrix in backward instead of
        # storing N of them (the flash-attention memory property, at the
        # inter-chip granularity)
        acc, m, l = jax.checkpoint(
            lambda kc_, vc_, a, mm, ll: _online_update(
                qf, kc_, vc_, a, mm, ll, q_off=idx * sc, k_off=j * sc,
                causal=causal))(kc, vc, acc, m, l)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, acc, m, l), None

    (kc, vc, acc, m, l), _ = jax.lax.scan(
        body, (k, v, acc, m, l), jnp.arange(axis_size))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(l > 0.0, acc / safe_l, 0.0)                # (B,H,Sq,D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name, axis_size, causal=True,
                            scale=None):
    """Per-shard body: all_to_all seq-shard -> head-shard, local full-seq
    attention, swap back. q/k/v [B, S/N, H, D]; needs H % N == 0. GQA kv
    heads swap UN-expanded when Hk % N == 0 (Hk/H of the all_to_all
    bytes — the local flash kernel is GQA-native); only Hk < N forces
    the expansion."""
    B, sc, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if k.shape[2] % axis_size:
        k = _repeat_kv(k, H)
        v = _repeat_kv(v, H)

    def swap_in(x):   # [B, S/N, H, D] -> [B, S, H/N, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def swap_out(x):  # [B, S, H/N, D] -> [B, S/N, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = swap_in(q), swap_in(k), swap_in(v)
    from ..core.dispatch import select_impl
    impl = select_impl("flash_attention")
    out = impl(qg, kg, vg, None, causal, scale, 0.0, None)
    return swap_out(out)


def _as_mesh(mesh):
    if isinstance(mesh, Mesh):
        return mesh
    if mesh is None:
        from .process_mesh import get_mesh
        mesh = get_mesh()
        if mesh is None:
            raise RuntimeError("ring/ulysses attention needs a mesh: pass "
                               "one or call dist.set_mesh/init_mesh first")
    return mesh.to_jax()  # ProcessMesh


def ring_attention(q, k, v, mesh=None, seq_axis="sep", causal=True,
                   scale=None, impl=None, layout="contiguous"):
    """User API: q/k/v Tensors/arrays [B, S, H, D]; runs ring attention with
    the sequence dim sharded over ``seq_axis`` of ``mesh``. Differentiable
    through the tape (run_op -> jax.vjp through shard_map). ``impl``:
    "pallas" (flash block kernel per ring step), "xla" (pure-jnp), or None
    to pick by backend. ``layout="zigzag"`` (causal only) load-balances
    the ring: device d holds sub-chunks (c_d, c_{2N-1-d}) so every step
    does near-equal work instead of the last device gating the ring."""
    jmesh = _as_mesh(mesh)
    n = int(jmesh.shape[seq_axis])
    spec = P(None, seq_axis, None, None)
    if layout == "zigzag":
        if not causal:
            raise ValueError("zigzag layout only balances the CAUSAL "
                             "ring; use layout='contiguous'")
        if impl == "xla":
            raise ValueError("zigzag ring is built from the Pallas chunk "
                             "kernels; impl='xla' is only available with "
                             "layout='contiguous'")
        if scale is None:
            scale = 1.0 / math.sqrt(int(q.shape[-1]))
        from ..ops.pallas.common import pallas_interpret
        interpret = pallas_interpret()
        _zigzag_perm(int(q.shape[1]), n)  # validate divisibility early

        def shard_body(a, b, c):
            # contiguous -> zigzag in-shard (two ppermutes), ring, back
            def to_zz(x):
                l, h = _zz_split(x)
                l, h = _zz_shard_exchange(l, h, seq_axis, n)
                return jnp.concatenate([l, h], axis=1)

            o = _zigzag_ring_flash(to_zz(a), to_zz(b), to_zz(c),
                                   seq_axis, n, float(scale), interpret)
            ol, oh = _zz_split(o)
            rl, rh = _zz_shard_exchange(ol, oh, seq_axis, n, inverse=True)
            return jnp.concatenate([rl, rh], axis=1)

        fn = shard_map(shard_body, jmesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
        return run_op("ring_attention_zigzag", fn, (q, k, v))
    if layout != "contiguous":
        raise ValueError(f"unknown ring layout {layout!r}: expected "
                         "'contiguous' | 'zigzag'")
    body = functools.partial(ring_attention_local, axis_name=seq_axis,
                             axis_size=n, causal=causal, scale=scale,
                             impl=impl)
    fn = shard_map(lambda a, b, c: body(a, b, c), jmesh,
                   in_specs=(spec, spec, spec), out_specs=spec)
    return run_op("ring_attention", fn, (q, k, v))


def ulysses_attention(q, k, v, mesh=None, seq_axis="sep", causal=True,
                      scale=None):
    """User API: Ulysses all-to-all attention over ``seq_axis``."""
    jmesh = _as_mesh(mesh)
    n = int(jmesh.shape[seq_axis])
    spec = P(None, seq_axis, None, None)
    body = functools.partial(ulysses_attention_local, axis_name=seq_axis,
                             axis_size=n, causal=causal, scale=scale)
    fn = shard_map(lambda a, b, c: body(a, b, c), jmesh,
                   in_specs=(spec, spec, spec), out_specs=spec)
    return run_op("ulysses_attention", fn, (q, k, v))
