"""flash_tile_sweep.py -- device time of the flash kernels over tiles.

    chiprun -- python3 tools/flash_tile_sweep.py            # needs a TPU
    python3 tools/flash_tile_sweep.py --compile-only        # here: does Mosaic
                                                            # take each tile?

Times ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv`` and the one backward
kernel ``flash_bwd`` (``--kernels bwd``: dq, dk and dv from one sweep) of
``paddle_tpu/ops/pallas/flash_attention.py`` at the benchmark cells' own
attention shapes, one layer's call each, over a grid of (block_q, block_k)
tiles and of the (sub_q, sub_k) sub-tiles a grid step walks its tile in
(``--tiles 1024x1024/256x256,...``; a tile alone is computed in one piece).
Each (shape, kernel, tile) is one jitted program holding the one Mosaic call,
named so that the profile's "XLA Modules" line tells the runs apart; the time
is the device duration of the ``flash_*`` "XLA Ops" event inside each run (what
the benchmark's readers sum), the median of ``--reps`` runs under
``jax.profiler``. The
table goes to stdout and, whole, to ``chiprun_out/flash_tile_sweep.json``.
``tile_plan``'s preference order (PERF.md, "PR 26": ``--tiles pr26``, the
(block_q, block_k) grid, each tile in one piece) and its sub-tiles
(``flash_attention._sub_tile``; PERF.md, "PR 31": the default ``--tiles``)
were read off this table; run both again on a new chip generation before
trusting either there.

``--compile-only`` compiles every tile for a described v5e (no chip, no
times): what the chip's compiler refuses, it refuses here.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (B*Hq, B*Hk, heads q, heads kv, S, D, q/k dtype, v dtype[, window])
SHAPES = {
    # gpt2s-train-s1024: b32, 12 heads of 64, bf16
    "gpt2": (384, 384, 12, 12, 1024, 64, "bfloat16", "bfloat16"),
    # mistral7b-l2-train-s4096: b4, 32/8 heads of 128, bf16 throughout since
    # the rotation keeps q's and k's dtype (PR 29)
    "mistral": (128, 32, 32, 8, 4096, 128, "bfloat16", "bfloat16"),
    # laguna-xs2-train-s8192: b2, head 128, bf16 throughout; a window layer
    # (64 heads over 8, window 512: the kernels are then flash_win_*) and a
    # full layer (48 heads over 8)
    "laguna_win": (128, 16, 64, 8, 8192, 128, "bfloat16", "bfloat16", 512),
    "laguna_full": (96, 16, 48, 8, 8192, 128, "bfloat16", "bfloat16"),
    # glm47-flash-train-s8192: b2, latent attention expanded to 20 heads
    # over 20 of 192 + 64 = 256 (values 256 too), bf16
    "glm": (40, 40, 20, 20, 8192, 256, "bfloat16", "bfloat16"),
}
# (block_q, block_k, sub_q, sub_k): the plan's tile (512 x 512 under the
# window, which 1024-long sides do not divide: give its own list) in one
# piece and walked in sub-tiles
TILES = tuple((1024, 1024) + sub for sub in (
    (1024, 1024), (128, 128), (256, 256), (128, 256), (256, 128), (256, 512),
    (512, 256), (128, 512), (512, 512), (256, 1024)))
# PR 26's sweep: the tile's own sides, each tile computed in one piece
PR26_TILES = tuple(t + t for t in (
    (128, 128), (256, 256), (256, 512), (512, 256), (512, 512), (256, 1024),
    (1024, 256), (512, 1024), (1024, 512), (1024, 1024)))
KERNELS = ("fwd", "dq", "dkv", "bwd")


def build(shape, kernel, tile):
    """(fn, abstract args) of one kernel call at one (bq, bk, sub_q, sub_k)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    bhq, bhk, hq, hk, s, d, qk_dt, v_dt = SHAPES[shape][:8]
    window = (SHAPES[shape] + (None,))[8]
    scale = float(d) ** -0.5
    qk_dt, v_dt = jnp.dtype(qk_dt), jnp.dtype(v_dt)
    q = jax.ShapeDtypeStruct((bhq, s, d), qk_dt)
    k = jax.ShapeDtypeStruct((bhk, s, d), qk_dt)
    v = jax.ShapeDtypeStruct((bhk, s, d), v_dt)
    col = jax.ShapeDtypeStruct((bhq, s, 1), jnp.float32)
    maps = fa._Static(rate=0.0)
    tile = fa._tile(kernel, *tile[:2], s, s, d, causal=True, window=window,
                    sub=tile[2:])
    if kernel == "fwd":
        def fn(q, k, v):
            return fa._fwd(q, k, v, None, None, hq, hk, True, scale, 0, s,
                           tile, maps, False, window=window)
        return fn, (q, k, v)
    impl = fa._bwd_dq if kernel == "dq" else functools.partial(
        fa._bwd_dkv, with_dq=kernel == "bwd")

    def fn(q, k, v, do, lse, delta):
        return impl(q, k, v, do, lse, delta, None, None, True, scale, 0, s,
                    tile, maps, False, None, None, hq, hk, window)
    return fn, (q, k, v, q, col, col)   # do has the output's dtype: q's


def _label(tile, sep="/"):
    bq, bk, sub_q, sub_k = tile
    return f"{bq}x{bk}{sep}{sub_q}x{sub_k}"


def _row(shape, kernel, tile):
    return {"shape": shape, "kernel": kernel,
            **dict(zip(("bq", "bk", "sub_q", "sub_k"), tile))}


def compile_only(shapes, tiles, kernels=KERNELS):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    rows = []
    for shape in shapes:
        for kernel in kernels:
            for tile in tiles:
                fn, args = build(shape, kernel, tile)
                args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
                        for a in args]
                row = _row(shape, kernel, tile)
                try:
                    jax.jit(fn).lower(*args).compile()
                    row["compiles"] = True
                except Exception as e:  # noqa: BLE001 -- the refusal is the result
                    row["compiles"] = False
                    row["error"] = str(e).strip().splitlines()[-1][:200]
                rows.append(row)
                print(json.dumps(row), flush=True)
    return rows


def measure(shapes, tiles, reps, kernels=KERNELS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import trace as _trace

    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r} "
                         "(use --compile-only here)")
    rows = []
    for shape in shapes:
        bhq, bhk, hq, hk, s, d, qk_dt, v_dt = SHAPES[shape][:8]
        rng = np.random.RandomState(0)

        def rand(n, dt):
            return jnp.asarray(rng.standard_normal((n, s, d)) * 0.5,
                               jnp.dtype(dt))
        q, k, v, do = rand(bhq, qk_dt), rand(bhk, qk_dt), rand(bhk, v_dt), \
            rand(bhq, qk_dt)
        fwd0, _ = build(shape, "fwd", (128, 128, 128, 128))
        out, lse = jax.jit(fwd0)(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        lse3 = lse[..., None]
        del out
        args = {"fwd": (q, k, v), "dq": (q, k, v, do, lse3, delta),
                "dkv": (q, k, v, do, lse3, delta),
                "bwd": (q, k, v, do, lse3, delta)}
        jitted = {}
        for kernel in kernels:
            for tile in tiles:
                fn, _ = build(shape, kernel, tile)
                fn.__name__ = f"sweep_{shape}_{kernel}_" + _label(tile, "_")
                row = _row(shape, kernel, tile)
                rows.append(row)
                try:
                    j = jax.jit(fn)
                    jax.block_until_ready(j(*args[kernel]))   # compile
                    jitted[fn.__name__] = (j, kernel, row)
                except Exception as e:  # noqa: BLE001 -- a refused tile is a row
                    row["error"] = str(e).strip().splitlines()[-1][:200]
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for j, kernel, _ in jitted.values():
                for _ in range(reps):
                    r = j(*args[kernel])
                jax.block_until_ready(r)
            jax.profiler.stop_trace()
            events = _trace.load_events(_trace.find_xplane(tmp))
        # a run of a program is one "XLA Modules" event; the Mosaic call in
        # it is the "XLA Ops" event named flash_* inside that interval
        # (what the benchmark's readers sum). Both are kept: the module's
        # time also holds what XLA adds around the call.
        module_ms, kernel_ms = {}, {}
        for key, evs in events.items():
            if not key.endswith("|" + _trace.MODULE_LINE):
                continue
            ops = sorted((st, dur) for name, st, dur in events.get(
                key[:-len(_trace.MODULE_LINE)] + _trace.OP_LINE, [])
                if "flash_" in name.partition(" = ")[0])
            for name, st, dur in evs:
                name = name.split("(")[0]
                module_ms.setdefault(name, []).append(dur / 1e6)
                kernel_ms.setdefault(name, []).append(sum(
                    d for s0, d in ops if st <= s0 and s0 + d <= st + dur)
                    / 1e6)
        for name, (_, _, row) in jitted.items():
            got = kernel_ms.get("jit_" + name, [])
            if got and min(got) > 0:
                row["ms"] = statistics.median(got)
                row["module_ms"] = statistics.median(module_ms["jit_" + name])
                row["runs"] = len(got)
        for row in rows:
            if row["shape"] == shape:
                print(json.dumps(row), flush=True)
    return rows


def table(rows):
    out = []
    for shape in sorted({r["shape"] for r in rows}):
        out.append(f"\n{shape}: ms a call (device, median)")
        out.append(f"{'tile/sub-tile':<20}" + "".join(
            f"{k:>10}" for k in KERNELS))

        def tile_of(r):
            return r["bq"], r["bk"], r["sub_q"], r["sub_k"]
        tiles = []
        for r in rows:
            if r["shape"] == shape and tile_of(r) not in tiles:
                tiles.append(tile_of(r))
        for tile in tiles:
            cells = []
            for kernel in KERNELS:
                ms = [r.get("ms") for r in rows if r["shape"] == shape
                      and r["kernel"] == kernel and tile_of(r) == tile]
                if ms and ms[0] is not None:
                    cells.append(f"{ms[0]:10.3f}")
                else:
                    cells.append(f"{'-':>10}")
            out.append(f"{_label(tile):<20}" + "".join(cells))
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default="gpt2,mistral")
    ap.add_argument("--tiles", default=",".join(_label(t) for t in TILES),
                    help="BQxBK[/SQxSK],... or pr26: PR 26's tile grid")
    ap.add_argument("--kernels", default="fwd,bwd",
                    help="of fwd,dq,dkv,bwd: a dozen programs a process is "
                    "what one trace holds, so a long list of tiles wants one "
                    "kernel a call")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default="chiprun_out/flash_tile_sweep.json")
    a = ap.parse_args(argv)
    shapes = a.shapes.split(",")
    if a.tiles == "pr26":
        a.tiles = ",".join(_label(t) for t in PR26_TILES)
    tiles = []
    for t in a.tiles.split(","):      # BQxBK or BQxBK/SQxSK
        sides = [int(x) for part in t.split("/") for x in part.split("x")]
        tiles.append(tuple(sides + sides[:4 - len(sides)]))
    kernels = tuple(a.kernels.split(","))
    if a.compile_only:
        rows = compile_only(shapes, tiles, kernels)
    else:
        rows = measure(shapes, tiles, a.reps, kernels)
        print(table(rows))
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
