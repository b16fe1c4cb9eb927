"""moe_gmm_sweep.py -- device time of the grouped matmuls over tiles.

    chiprun -- python3 tools/moe_gmm_sweep.py              # needs a TPU

Times ``moe_gmm_fwd``, ``moe_gmm_bwd_x`` and ``moe_gmm_bwd_w`` of
``paddle_tpu/ops/pallas/grouped_matmul.py`` at the shapes of the cell
``laguna-xs2-train-s8192``: 16,384 tokens x top-8 over 256 experts of which
32 are held (about 512 rows a held expert, uniformly random routing from a
seed), gate and up in one product (2048 x 1024) and down (512 x 2048), over
row tiles and (tk, tn) tiles, and ``jax.lax.ragged_dot`` (forward, and its
two gradients in one program) on the same rows without padding. Each variant
is one jitted program; the time is the device duration of its "XLA Modules"
event under ``jax.profiler``, the median of ``--reps`` runs (a program holds
the one product, so the module's time is the product's plus what XLA puts
around it). The table goes to stdout and to
``chiprun_out/moe_gmm_sweep.json``. ``ROW_TILE`` and ``gmm_tiles`` (PERF.md,
"PR 28") were read off this table.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOKENS, TOP_K, OF, HELD = 16384, 8, 256, 32
# product: (K, N) of rhs [HELD, K, N]
PRODUCTS = {"gate_up": (2048, 1024), "down": (512, 2048)}
ROW_TILES = (128, 256, 512)
TILES = {"gate_up": ((2048, 1024), (1024, 1024), (2048, 512), (512, 512)),
         "down": ((512, 2048), (512, 1024), (512, 512))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/moe_gmm_sweep.json")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import trace as _trace
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    rng = np.random.default_rng(0)
    expert = jnp.asarray(rng.integers(0, OF, TOKENS * TOP_K), jnp.int32)
    programs, rows = {}, []

    def add(name, fn, args, **row):
        fn.__name__ = name
        row = dict(row, program=name)
        rows.append(row)
        try:
            j = jax.jit(fn)
            jax.block_until_ready(j(*args))
            programs[name] = (j, args, row)
        except Exception as e:  # noqa: BLE001 -- a refused tile is a row
            row["error"] = str(e).strip().splitlines()[-1][:200]

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.bfloat16)

    for tm in ROW_TILES:
        lay = gm.group_layout(expert, 0, HELD, tm)
        n_rows = gm.padded_rows(TOKENS * TOP_K, HELD, tm)
        for prod, (k, n) in PRODUCTS.items():
            x, w, dy = rand(n_rows, k), rand(HELD, k, n), rand(n_rows, n)
            for tk, tn in TILES[prod]:
                tag = f"{prod}_tm{tm}_{tk}x{tn}"
                add(f"fwd_{tag}", lambda x, w, tg, nt, t=(tk, tn): gm._gmm(
                    x, w, tg, nt, trans=False, tiles=t, interpret=False),
                    (x, w, lay.tile_group, lay.n_tiles), kernel="fwd",
                    product=prod, tm=tm, tk=tk, tn=tn)
                # dlhs contracts over n and writes k wide
                add(f"bwd_x_{tag}", lambda dy, w, tg, nt, t=(tn, tk):
                    gm._gmm(dy, w, tg, nt, trans=True, tiles=t,
                            interpret=False),
                    (dy, w, lay.tile_group, lay.n_tiles), kernel="bwd_x",
                    product=prod, tm=tm, tk=tn, tn=tk)
                add(f"bwd_w_{tag}", lambda x, dy, tg, nt, t=(tk, tn):
                    gm._gmm_dw(x, dy, tg, nt, HELD, jnp.bfloat16, tiles=t,
                               interpret=False),
                    (x, dy, lay.tile_group, lay.n_tiles), kernel="bwd_w",
                    product=prod, tm=tm, tk=tk, tn=tn)
    # ragged_dot on the same assignments, rows sorted by expert, no padding
    sizes = jnp.bincount(jnp.where(expert < HELD, expert, HELD),
                         length=HELD + 1)[:HELD].astype(jnp.int32)
    here = int(sizes.sum())
    for prod, (k, n) in PRODUCTS.items():
        x, w, dy = rand(here, k), rand(HELD, k, n), rand(here, n)
        add(f"ragged_fwd_{prod}", lambda x, w, gs: jax.lax.ragged_dot(
            x, w, gs, preferred_element_type=jnp.float32).astype(x.dtype),
            (x, w, sizes), kernel="ragged_fwd", product=prod)
        add(f"ragged_bwd_{prod}", lambda x, w, gs, dy: jax.vjp(
            lambda x, w: jax.lax.ragged_dot(x, w, gs), x, w)[1](dy),
            (x, w, sizes, dy), kernel="ragged_bwd", product=prod)

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for j, args, _ in programs.values():
            for _ in range(a.reps):
                r = j(*args)
            jax.block_until_ready(r)
        jax.profiler.stop_trace()
        events = _trace.load_events(_trace.find_xplane(tmp))
    module_ms = {}
    for key, evs in events.items():
        if key.endswith("|" + _trace.MODULE_LINE):
            for name, _, dur in evs:
                module_ms.setdefault(name.split("(")[0], []).append(dur / 1e6)
    for name, (_, _, row) in programs.items():
        got = module_ms.get("jit_" + name, [])
        if got:
            row["ms"], row["runs"] = statistics.median(got), len(got)
    for row in rows:
        print(json.dumps(row), flush=True)
    print(f"rows here {here}, sizes min {int(sizes.min())} max "
          f"{int(sizes.max())}")
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
