"""sala_kernel_sweep.py -- the chosen-block attention and the linear-attention
kernels on the chip: agreement with their XLA paths, and device time over
tiles and chunks.

    chiprun -- python3 tools/sala_kernel_sweep.py            # needs a TPU

1. Agreement (``--check``, on by default): ``sparse_attention`` and
   ``linear_attention`` against ``sparse_attention_xla`` /
   ``linear_attention_xla`` at S 2048 (dense_len 1024, top-12 of 32 blocks),
   forward and gradients, bfloat16 inputs, as max|a - b| / max|b|.
2. Times at the cell's shapes (minicpm-sala-train-s12288: B 1, S 12288, 32 / 2
   heads of 128, top-64 blocks of 64; 32 lightning heads of 128): each
   (kernel family, tile or chunk) is one jitted program of a forward and a
   backward pass; the time is the summed device duration of the
   ``sparse_attn_*`` / ``linear_attn_*`` "XLA Ops" events inside each run of
   the program, the median of ``--reps`` runs under ``jax.profiler``. The
   choice is the selection's own on random normalised q and k (scattered, as
   in the cell) or, with ``--clustered``, the forced blocks and the 31 before
   them (what a trained model tends to). The table goes to stdout and to
   ``chiprun_out/sala_kernel_sweep.json``; ``sparse_tile_plan`` and
   ``linear_chunk_plan`` were read off it (PERF.md, PR 34).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rel(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _normed(rng, shape):
    import jax.numpy as jnp
    import numpy as np
    x = rng.standard_normal(shape).astype(np.float32)
    x /= np.sqrt((x ** 2).mean(-1, keepdims=True))
    return jnp.asarray(x, jnp.bfloat16)


def check():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import linear_attention as la
    from paddle_tpu.ops.pallas import sparse_attention as sa
    rng = np.random.RandomState(0)
    s, d = 2048, 128
    sc = sa.SparseConfig(topk=12, window_size=256, dense_len=1024)
    q, k, v = _normed(rng, (1, s, 8, d)), _normed(rng, (1, s, 2, d)), \
        jnp.asarray(rng.standard_normal((1, s, 2, d)) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((1, s, 8, d)), jnp.float32)
    chosen = jax.jit(lambda a, b: sa.select_blocks(a, b, sc))(q, k)
    scale = d ** -0.5

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda a, b, c: jnp.sum(fn(a, b, c).astype(jnp.float32) * w),
            (0, 1, 2)))
    out = {}
    kern = loss(lambda a, b, c: sa.sparse_attention(
        a, b, c, chosen, scale, sc.block_size, None, False))(q, k, v)
    xla = loss(lambda a, b, c: sa.sparse_attention_xla(
        a, b, c, chosen, scale, sc.block_size))(q, k, v)
    out["sparse"] = {"loss": abs(float(kern[0] - xla[0])) / abs(float(xla[0])),
                     **{n: _rel(a, b) for n, a, b in zip(
                         ("dq", "dk", "dv"), kern[1], xla[1])}}
    fwd = jax.jit(lambda a, b, c: sa.sparse_attention(
        a, b, c, chosen, scale, sc.block_size, None, False))(q, k, v)
    out["sparse"]["out"] = _rel(fwd, sa.sparse_attention_xla(
        q, k, v, chosen, scale, sc.block_size))
    h = 8
    rates = jnp.asarray(2.0 ** (-8.0 * (np.arange(h) + 1) / h), jnp.float32)
    ql, kl = _normed(rng, (1, s, h, d)), _normed(rng, (1, s, h, d))
    vl = jnp.asarray(rng.standard_normal((1, s, h, d)) * 0.5, jnp.bfloat16)
    kern = loss(lambda a, b, c: la.linear_attention(
        a, b, c, rates, scale, None, False))(ql, kl, vl)
    xla = loss(lambda a, b, c: la.linear_attention_xla(
        a, b, c, rates, scale, None))(ql, kl, vl)
    out["linear"] = {"loss": abs(float(kern[0] - xla[0])) / abs(float(xla[0])),
                     **{n: _rel(a, b) for n, a, b in zip(
                         ("dq", "dk", "dv"), kern[1], xla[1])}}
    return out


def measure(tiles, chunks, reps, clustered):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import trace as _trace
    from paddle_tpu.ops.pallas import linear_attention as la
    from paddle_tpu.ops.pallas import sparse_attention as sa
    rng = np.random.RandomState(1)
    s, d, hq, hkv = 12288, 128, 32, 2
    sc = sa.SparseConfig()
    scale = d ** -0.5
    q, k = _normed(rng, (1, s, hq, d)), _normed(rng, (1, s, hkv, d))
    v = jnp.asarray(rng.standard_normal((1, s, hkv, d)) * 0.5, jnp.bfloat16)
    if clustered:
        own = np.arange(s)[:, None] // sc.block_size
        block = np.arange(s // sc.block_size)[None]
        near = (block <= own) & (block > own - (sc.topk - 1))
        chosen = jnp.asarray(np.broadcast_to(
            near | (block == 0), (1, hkv, s, s // sc.block_size)))
    else:
        chosen = jax.jit(lambda a, b: sa.select_blocks(a, b, sc))(q, k)
    kl = _normed(rng, (1, s, hq, d))
    vl = jnp.asarray(rng.standard_normal((1, s, hq, d)) * 0.5, jnp.bfloat16)
    rates = jnp.asarray(2.0 ** (-8.0 * (np.arange(hq) + 1) / hq), jnp.float32)
    programs = {}
    for bq, bk in tiles:
        def fn(a, b, c, t=sa.SparseTiles(bq, bk)):
            return jax.grad(lambda x, y, z: jnp.sum(sa.sparse_attention(
                x, y, z, chosen, scale, sc.block_size, t, False
            ).astype(jnp.float32)), (0, 1, 2))(a, b, c)
        fn.__name__ = f"sweep_sparse_{bq}x{bk}"
        programs[fn.__name__] = (jax.jit(fn), (q, k, v), "sparse_attn_",
                                 {"family": "sparse", "bq": bq, "bk": bk})
    for c in chunks:
        def fn(a, b, cc, c=c):
            return jax.grad(lambda x, y, z: jnp.sum(la.linear_attention(
                x, y, z, rates, scale, c, False).astype(jnp.float32)),
                (0, 1, 2))(a, b, cc)
        fn.__name__ = f"sweep_linear_{c}"
        programs[fn.__name__] = (jax.jit(fn), (q, kl, vl), "linear_attn_",
                                 {"family": "linear", "chunk": c})
    rows, live = [], {}
    for name, (j, args, prefix, row) in programs.items():
        rows.append(row)
        try:
            jax.block_until_ready(j(*args))
            live[name] = (j, args, prefix, row)
        except Exception as e:  # noqa: BLE001 -- a refused tile is a row
            row["error"] = str(e).strip().splitlines()[-1][:200]
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for j, args, _, _ in live.values():
            for _ in range(reps):
                r = j(*args)
            jax.block_until_ready(r)
        jax.profiler.stop_trace()
        events = _trace.load_events(_trace.find_xplane(tmp))
    for key, evs in events.items():
        if not key.endswith("|" + _trace.MODULE_LINE):
            continue
        ops = events.get(key[:-len(_trace.MODULE_LINE)] + _trace.OP_LINE, [])
        for mod, st, dur in evs:
            mod = mod.split("(")[0]
            if not mod.startswith("jit_") or mod[4:] not in live:
                continue
            _, _, prefix, row = live[mod[4:]]
            per = {}
            for name, s0, d0 in ops:
                head = name.partition(" = ")[0]
                at = head.find(prefix)
                if at >= 0 and st <= s0 and s0 + d0 <= st + dur:
                    kern = head[at:].split(".")[0]
                    per[kern] = per.get(kern, 0.0) + d0 / 1e6
            row.setdefault("runs", []).append(per)
    for row in rows:
        runs = row.pop("runs", [])
        if runs:
            for kern in sorted({k for r in runs for k in r}):
                row[kern + "_ms"] = statistics.median(
                    r.get(kern, 0.0) for r in runs)
            row["ms"] = statistics.median(sum(r.values()) for r in runs)
            row["n"] = len(runs)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiles", default="256x256,512x512,512x1024,1024x512,"
                    "1024x1024")
    ap.add_argument("--chunks", default="128,256,512")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--no-check", action="store_true")
    ap.add_argument("--clustered", action="store_true")
    ap.add_argument("--out", default="chiprun_out/sala_kernel_sweep.json")
    a = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    out = {"device": jax.devices()[0].device_kind}
    if not a.no_check:
        out["check"] = check()
        print(json.dumps(out["check"]), flush=True)
    tiles = [tuple(int(x) for x in t.split("x"))
             for t in a.tiles.split(",") if t]
    chunks = [int(c) for c in a.chunks.split(",") if c]
    out["clustered"] = a.clustered
    out["rows"] = measure(tiles, chunks, a.reps, a.clustered)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
