"""gdn_kernel_sweep.py -- the gated delta rule's kernels on the chip: agreement
with their XLA path, and device time over chunks.

    python3 tools/gdn_kernel_sweep.py            # needs a TPU

1. Agreement (``--check``, on by default): ``gated_delta`` against
   ``gated_delta_xla`` at S 2048, 32 value heads over 16 key heads of 128,
   forward and the gradients of q, k, v, g and beta, bfloat16 q, k, v, as
   max|a - b| / max|b|, with decays from 0.9995 to 0.2 a token (the
   configuration's range).
2. Times at the cell's shapes (qwen3-next-train-s16384: B 1, S 16384, the
   same heads): each chunk is one jitted program of a forward and a backward
   pass; the time is the summed device duration of the ``gated_delta_*``
   "XLA Ops" events inside each run of the program, the median of ``--reps``
   runs under ``jax.profiler``. The table goes to stdout and to
   ``chiprun_out/gdn_kernel_sweep.json``; ``gated_delta_plan`` was read off
   it (PERF.md section 6).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HV, HK, D = 32, 16, 128


def _rel(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def inputs(s, seed):
    """q (normalised, scaled), k (normalised), v in bfloat16; g and beta in
    float32, the decay spread over the configuration's range."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.qwen3_next import deltanet_decay_init
    rng = np.random.RandomState(seed)

    def unit(shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit((1, s, HK, D)) / np.sqrt(D), jnp.bfloat16)
    k = jnp.asarray(unit((1, s, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, s, HV, D)) * 0.5, jnp.bfloat16)
    a0, dt0 = deltanet_decay_init(HV)
    a = rng.standard_normal((1, s, HV)).astype(np.float32)
    g = -a0 * np.log1p(np.exp(a + np.log(np.expm1(dt0))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((1, s, HV))))
    return q, k, v, jnp.asarray(g, jnp.float32), \
        jnp.asarray(beta, jnp.float32)


def check():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import gated_delta as gd
    args = inputs(2048, 0)
    w = jnp.asarray(np.random.RandomState(1).standard_normal(
        (1, 2048, HV, D)), jnp.float32)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            (0, 1, 2, 3, 4)))
    kern = loss(lambda *a: gd.gated_delta(*a, interpret=False))(*args)
    xla = loss(gd.gated_delta_xla)(*args)
    out = {"loss": abs(float(kern[0] - xla[0])) / abs(float(xla[0]))}
    out.update({n: _rel(a, b) for n, a, b in zip(
        ("dq", "dk", "dv", "dg", "dbeta"), kern[1], xla[1])})
    out["out"] = _rel(jax.jit(lambda *a: gd.gated_delta(
        *a, interpret=False))(*args), gd.gated_delta_xla(*args))
    return out


def measure(chunks, reps):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace as _trace
    from paddle_tpu.ops.pallas import gated_delta as gd
    args = inputs(16384, 2)
    rows, live = [], {}
    for c in chunks:
        def fn(*a, c=c):
            return jax.grad(lambda *x: jnp.sum(gd.gated_delta(
                *x, chunk=c, interpret=False).astype(jnp.float32)),
                (0, 1, 2, 3, 4))(*a)
        fn.__name__ = f"sweep_gdn_{c}"
        row = {"chunk": c}
        rows.append(row)
        try:
            jax.block_until_ready(jax.jit(fn)(*args))
            live[fn.__name__] = (jax.jit(fn), row)
        except Exception as e:  # noqa: BLE001 -- a refused chunk is a row
            row["error"] = str(e).strip().splitlines()[-1][:200]
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for j, _ in live.values():
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
            _, row = live[mod[4:]]
            per = {}
            for name, s0, d0 in ops:
                head = name.partition(" = ")[0]
                at = head.find("gated_delta_")
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
    ap.add_argument("--chunks", default="64,128")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--no-check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/gdn_kernel_sweep.json")
    a = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    out = {"device": jax.devices()[0].device_kind}
    if not a.no_check:
        out["check"] = check()
        print(json.dumps(out["check"]), flush=True)
    out["rows"] = measure([int(c) for c in a.chunks.split(",") if c],
                          a.reps)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
