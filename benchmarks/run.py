"""The benchmark's entry: one run of one cell, in one process that holds the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, measure, check, print one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``compared`` (each
number compared beside its limit, also the last lines of standard error).
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

Everything that belongs to one cell, configuration, runner, family or metric
is a file of its own that ``harness/spec.py`` finds by name; this file knows
none of them. It exits non-zero, and prints no result, without a TPU, with
fewer chips than the cell asks for, or for a ``device_kind`` that
``peaks.json`` does not have.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, benchmark_json=None, rehearsal=False,
         wrap_step=None) -> int:
    """``benchmark_json``, ``rehearsal`` and ``wrap_step`` are for the
    benchmark's own tests: a tiny preset on the CPU, which skips the look for
    a chip and drives the rest of a run, and a fault planted under the timed
    path. The command line reaches none of them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import spec
    bench = spec.load_benchmark(
        benchmark_json or os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    cell = bench.cell(args.workload)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not rehearsal:
        if dev.platform != "tpu":
            raise SystemExit(f"this benchmark needs a TPU; jax found "
                             f"platform {dev.platform!r} ({dev.device_kind})")
        if len(devices) < cell.chips:
            raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s); "
                             f"jax found {len(devices)}")
        peaks = bench.peaks(dev.device_kind)
    else:
        peaks = None
    # <checkout>/.jax_cache, or where JAX_COMPILATION_CACHE_DIR says
    from paddle_tpu.core.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    runner = bench.module("runners", cell.params["runner"])
    result = runner.run({
        "bench": bench, "cell": cell, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "t_start": T_START, "log": log,
        "wrap_step": wrap_step})

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["counters"]["peak_bytes_in_use"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if args.trace:
        reduced = result["trace"]
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        ctx = {"counters": result["counters"], "trace": reduced,
               "cell": cell, "peaks": peaks, "bench": bench, "log": log}
        for m in bench.metrics_for(cell.name, "per_layer"):
            value = bench.module("metrics", m["name"]).read(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
    else:
        for m in bench.metrics_for(cell.name, "end_to_end"):
            if m["name"] in result["end_to_end"]:
                line["metrics"][m["name"]] = {
                    "value": float(result["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    line["compared"] = result["compared"]
    for c in result["compared"]:
        log((f"compared {c['name']}: {c['value']!r} limit {c['limit']!r}"
             if c["limit"] is not None else
             f"read, not compared {c['name']}: {c['value']!r}")
            + (f" (leaf {c['leaf']})" if c.get("leaf") else ""))
    log(f"correct: {result['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
